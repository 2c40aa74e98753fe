"""One run of one benchmark cell (see ``bench/run.py`` for the command).

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a
configuration file under ``bench/configs/`` and a traffic file under
``bench/traffic/``, both found by name. A run

1. refuses to start without a TPU, or with fewer chips than the cell
   asks for;
2. keeps JAX's compile cache at its fixed place in the checkout;
3. builds the cell's engine state on the device in one program from
   ``--seed``: the rings prefilled by the closed loop under the drive's
   salt, and the seeded flash image (``bench/data.py``);
4. compiles and warms the cell's own runner with two calls; everything
   up to here is ``setup_s``;
5. calls the runner back to back for ``--seconds``, with about
   ``AHEAD_S`` seconds of calls queued on the device ahead of the one
   the host waits for, then waits for every call sent: the window ends
   there. It counts compilations in the window;
6. with ``--trace 1``, profiles the first calls of the window (about
   ``TRACE_S`` seconds of them, waited for before more are sent) and
   reduces the trace (``bench/trace.py``);
7. reads the device's peak memory, copies the final state to the host,
   frees the device, and compares it with the plain reference run for
   the same number of rounds (``bench/compare.py``);
8. prints each number compared beside its limit on standard error, and
   the result as the last line of standard output.

End-to-end metrics come from ``--trace 0`` runs and per-layer metrics
from ``--trace 1`` runs; each is read by ``bench/metrics/<name>.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import time
from collections import deque
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = BENCH / ".trace"
TRACE_S = 3.0       # length of the profiled part of a --trace 1 window
AHEAD_S = 4.0       # device time queued ahead of the call the host waits for
WARM_CALLS = 2


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = cells[name]
    return make_cell(name, entry["config"], entry["traffic"], entry["chips"])


def make_cell(name: str, config: str, traffic: str, chips: int) -> Cell:
    """A cell of ``bench/configs/<config>.json`` under
    ``bench/traffic/<traffic>.json``."""
    return Cell(
        name, chips,
        json.loads((BENCH / "configs" / f"{config}.json").read_text()),
        json.loads((BENCH / "traffic" / f"{traffic}.json").read_text()),
    )


def load_limits(config_name: str) -> dict:
    return json.loads((BENCH / "limits" / f"{config_name}.json").read_text())


def metric_reader(name: str):
    """``read(run) -> float | None`` from ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    setup_s: float
    window_s: float
    retired: float           # emulated requests completed in the window
    rounds_per_call: int
    peak_bytes: int
    trace: object = None     # bench.trace.TraceSummary of the traced calls


def program(cell: Cell):
    """The program's (cfg, ssd, workload, platform) for a cell."""
    from repro.core.types import EngineConfig, PlatformModel, SSDConfig
    from repro.workloads import ClosedLoop

    c, t = cell.config, cell.traffic
    if t["kind"] != "closed_loop":
        raise ValueError(f"unknown traffic kind {t['kind']!r}")
    cfg = EngineConfig(**c["engine"])
    ssd = SSDConfig(**c["ssd"], num_blocks=c["num_blocks"],
                    mapping_hit_rate=t["mapping_hit_rate"])
    wl = ClosedLoop(io_depth=t["io_depth"], read_frac=t["read_frac"],
                    resubmit_delay_us=t["resubmit_delay_us"])
    return cfg, ssd, wl, PlatformModel(**c["platform"])


def devices_for(cell: Cell):
    """The chips the cell runs on; ``NoChip`` without a TPU or enough."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, found {devs[0].platform!r}")
    if len(devs) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} chips, found {len(devs)}")
    return devs[:cell.chips]


def build_state(cell: Cell, prog, seed: int, devices):
    """The cell's engine state, built on the device(s) in one program.

    One drive: an ``EngineState``; several: the same with a leading drive
    axis, sharded over ``devices`` when the configuration spans chips."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bench.data import drive_keys, image_jnp
    from repro.core import engine

    cfg, ssd, wl, _ = prog
    drives, words = cell.config["drives"], cell.config["block_words"]
    salts, keys = drive_keys(seed, drives)

    def one(salt, key):
        st = engine.init_state(cfg, ssd, wl, words, salt=salt)
        return dataclasses.replace(st, flash=image_jnp(ssd.num_blocks, words,
                                                       key))

    salts = salts.view(np.int32)
    if drives == 1:
        build = jax.jit(lambda s, k: one(s[0], k[0]))
        args = jax.device_put((salts, keys), devices[0])
    elif cell.config["chips"] > 1:
        sharding = NamedSharding(Mesh(np.asarray(devices), ("dev",)), P("dev"))
        build = jax.jit(jax.vmap(one), in_shardings=sharding,
                        out_shardings=sharding)
        args = jax.device_put((salts, keys), sharding)
    else:
        build = jax.jit(jax.vmap(one))
        args = jax.device_put((salts, keys), devices[0])
    return jax.block_until_ready(build(*args))


def make_call(cell: Cell, prog, devices):
    """The runner the window drives: the donated single-drive or array
    runner on one chip, the sharded array runner across chips."""
    from jax.sharding import Mesh

    from repro.core import engine

    cfg, ssd, wl, plat = prog
    rounds = cell.traffic["rounds_per_call"]
    if cell.config["chips"] > 1:
        mesh = Mesh(np.asarray(devices), ("dev",))
        return engine.make_sharded_array_runner(cfg, ssd, wl, plat, rounds,
                                                mesh=mesh)
    if cell.config["drives"] > 1:
        return engine.make_array_runner(cfg, ssd, wl, plat, rounds,
                                        donate=True)
    return engine.make_runner(cfg, ssd, wl, plat, rounds, donate=True)


class CompileCounter:
    """Counts JAX traces and backend compiles inside a ``with`` block."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        self.count = 0

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.count += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)
        return False


def completed(state) -> float:
    import jax

    return float(np.sum(jax.device_get(state.metrics.completed)))


def drive(call, mark, state, seconds: float, ahead: int,
          trace_calls: int = 0):
    """Calls for ``seconds``, each dispatched while up to ``ahead``
    earlier ones are still queued on the device, so that a host that
    stands still does not leave the chip idle. The host waits for each
    call in turn through ``mark``, a copy of one small leaf of its
    output, taken before the next call donates that output. Once the
    time is up nothing more is sent: the window ends when every call
    sent has finished. Returns the state, the number of calls and the
    window's length.

    With ``trace_calls``, the profiler records the first that many
    calls, or fewer where the time is up first: they are dispatched, all
    of them are waited for, and the profiler stops before the next is
    sent."""
    import jax

    from bench.trace import CALL_SPAN, WAIT_SPAN

    pending = deque()

    def wait_oldest():
        with jax.profiler.TraceAnnotation(WAIT_SPAN):
            pending.popleft().block_until_ready()

    t_start = time.perf_counter()
    tracing = trace_calls > 0
    if tracing:
        jax.profiler.start_trace(str(TRACE_DIR))
    sent = 0
    while True:
        with jax.profiler.TraceAnnotation(CALL_SPAN):
            state = call(state)
            pending.append(mark(state.clock))
        sent += 1
        up = time.perf_counter() - t_start >= seconds
        if tracing and (sent == trace_calls or up):
            while pending:
                wait_oldest()
            jax.profiler.stop_trace()
            tracing = False
        elif len(pending) > ahead:
            wait_oldest()
        if up:
            break
    while pending:
        wait_oldest()
    jax.block_until_ready(state)
    return state, sent, time.perf_counter() - t_start


def host_copy(state, drives: int):
    """The final state on the host, flash image left out, with a leading
    drive axis on every leaf."""
    import jax

    host = jax.device_get(dataclasses.replace(state, flash=None))
    if drives == 1:
        host = jax.tree.map(lambda x: np.asarray(x)[None], host)
    return host


def check(cell: Cell, seed: int, host, rounds: int) -> dict:
    from bench.compare import gaps
    from bench.data import drive_keys
    from bench.reference import Reference, model_from

    salts, keys = drive_keys(seed, cell.config["drives"])
    ref = Reference(model_from(cell.config, cell.traffic), salts)
    ref.run(rounds)
    return gaps(host, ref, keys, cell.config["block_words"])


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, devices=None):
    """One run; returns the result line as a dict. ``devices`` defaults
    to the chips the cell asks for (tests pass a CPU device)."""
    import jax

    from bench import trace as trace_mod
    from bench.compare import verdict

    if devices is None:
        devices = devices_for(cell)
    prog = program(cell)
    state = build_state(cell, prog, seed, devices)
    call = make_call(cell, prog, devices)
    mark = jax.jit(lambda clock: clock + 1)
    for _ in range(WARM_CALLS):
        t0 = time.perf_counter()
        state = call(state)
        jax.block_until_ready(mark(state.clock))
        warm_s = time.perf_counter() - t0
    done_before = completed(state)
    setup_s = time.perf_counter() - t_process

    trace_calls = 0
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_calls = max(2, math.ceil(TRACE_S / warm_s))
    ahead = max(1, math.ceil(AHEAD_S / warm_s))
    with CompileCounter() as counter:
        state, sent, window_s = drive(call, mark, state, seconds, ahead,
                                      trace_calls)
    if counter.count:
        raise RuntimeError(
            f"{counter.count} compilations inside the measured window"
        )
    retired = completed(state) - done_before
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    calls = WARM_CALLS + sent
    host = host_copy(state, cell.config["drives"])
    del state, call
    summary = None
    if trace:
        summary = trace_mod.reduce(trace_mod.load(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    rounds = calls * cell.traffic["rounds_per_call"]
    numbers = check(cell, seed, host, rounds)
    limits = load_limits(cell.config["name"])
    run = Run(setup_s=setup_s, window_s=window_s,
              retired=retired, rounds_per_call=cell.traffic["rounds_per_call"],
              peak_bytes=int(peak), trace=summary)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    result = {
        "correct": verdict(numbers, limits),
        "attempted": sent,
        "failed": 0,
        "metrics": read_metrics(spec, cell.name, run, trace),
        "device": device_record(peak, summary),
    }
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in numbers}
    return result


def read_metrics(spec: dict, cell_name: str, run: Run, trace: bool) -> dict:
    """The cell's end-to-end metrics (``trace`` off) or per-layer metrics
    (``trace`` on); a reader that finds nothing leaves its metric out."""
    out = {}
    for m in spec["per_layer" if trace else "end_to_end"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        value = metric_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_record(peak: int, summary) -> dict:
    import jax

    dev = jax.devices()[0]
    rec = {"platform": dev.platform, "kind": dev.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    if summary is not None:
        rec["busy_s"] = summary.busy_mean_s
        rec["window_s"] = summary.window_s
    return rec


def main(argv=None, t_process: float | None = None) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_process)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
