"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The harness wraps every call into the program in a host span named
``CALL_SPAN`` and each wait for a call to finish in ``WAIT_SPAN``
(``jax.profiler.TraceAnnotation``); the traced calls are dispatched
ahead of the waits and all of them are waited for. The traced window
runs from the start of the first call span to the end of the last wait. On each device
plane (``/device:TPU:<n>``) the operations are the events of the
``XLA Ops`` line; a device is busy where at least one of them runs,
and idle elsewhere in the window.
"""
from __future__ import annotations

import dataclasses
import gzip
from pathlib import Path

CALL_SPAN = "bench.call"
WAIT_SPAN = "bench.wait"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
TOP = 10


@dataclasses.dataclass
class TraceSummary:
    window_s: float                 # first call span start to last wait end
    busy_s: list[float]             # per device: union of op intervals
    calls: int                      # calls finished in the window (waits)
    device_ops: list[list]          # [[op name, seconds per device], ...]
    idle_gaps: list[list]           # [[host activity, seconds], ...]

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    @property
    def idle_share_max(self) -> float:
        return max(1.0 - b / self.window_s for b in self.busy_s)


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def load(path: Path):
    """ProfileData of an ``.xplane.pb`` (optionally gzipped) file, or of
    the newest one under a profiler output directory."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        found = sorted(path.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    if path.suffix == ".gz":
        return ProfileData.from_serialized_xspace(gzip.decompress(path.read_bytes()))
    return ProfileData.from_file(str(path))


def _events(plane, line_name=None):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for ev in line.events:
                yield ev


def reduce(profile) -> "TraceSummary | None":
    """The device numbers of a traced window; None where the trace holds
    no call or wait span, or a device ran no operation inside the window."""
    host_spans: list[tuple[float, float, str]] = []
    devices: list[list[tuple[float, float, str]]] = []
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            host_spans += [
                (ev.start_ns, ev.end_ns, ev.name)
                for ev in _events(plane)
                if ev.name in (CALL_SPAN, WAIT_SPAN)
            ]
        elif plane.name.startswith(DEVICE_PREFIX):
            ops = [(ev.start_ns, ev.end_ns, ev.name)
                   for ev in _events(plane, OPS_LINE)]
            if ops:
                devices.append(ops)
    calls = [s for s, _, n in host_spans if n == CALL_SPAN]
    waits = [e for _, e, n in host_spans if n == WAIT_SPAN]
    if not calls or not waits or not devices:
        return None
    lo = min(calls)
    hi = max(waits)
    window_ns = hi - lo

    busy, gaps, per_op = [], [], {}
    for ops in devices:
        merged = union([(s, e) for s, e, _ in ops], lo, hi)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps.append((ge - gs, (gs + ge) / 2))
        for s, e, name in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per_op[name] = per_op.get(name, 0.0) + d / 1e9
    if not all(busy):
        return None  # no operation inside the window: nothing to read
    n_dev = len(devices)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps, key=lambda g: -g[0])[:TOP]
    return TraceSummary(
        window_s=window_ns / 1e9,
        busy_s=busy,
        calls=len(waits),
        device_ops=[[name, s / n_dev] for name, s in top_ops],
        idle_gaps=[[_activity(host_spans, mid), g / 1e9]
                   for g, mid in top_gaps],
    )


def _activity(host_spans, t: float) -> str:
    """The innermost benchmark span running on the host at time t."""
    inner = None
    for s, e, name in host_spans:
        if s <= t < e and (inner is None or e - s < inner[1] - inner[0]):
            inner = (s, e, name)
    return inner[2] if inner else "host_between_calls"
