#!/usr/bin/env python3
"""Device self time of each stage of a cell's engine round, on the chip:

    python3 bench/stage_trace.py --workload <cell> --seed <n> --calls <k> \
        [--rounds <r>] [--fixture <dir>]

Builds and warms the cell as ``bench/harness.py`` does, maps the compiled
runner's instructions to their ``stage.*`` scopes (``bench/stages.py``),
profiles ``--calls`` calls through the harness's own loop, and prints one
JSON line: busy time and self time per stage, in ms per round, and the
longest operations of each stage. ``--rounds`` overrides the rounds per
call. ``--fixture`` writes the trace, cut to the harness's host spans and
the device's ``XLA Modules`` and ``XLA Ops`` lines, as a gzipped XSpace
beside the op map and this result: a recorded trace for ``bench/tests``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import Counter  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

TOP = 8


def cut_trace(profile) -> str:
    """The XSpace text of the harness's host spans and of each device's
    module and op lines, every other plane, line and event left out."""
    from bench import stages, trace

    keep = {trace.OPS_LINE, stages.MODULES_LINE}
    out = []
    for pid, plane in enumerate(profile.planes, 1):
        if plane.name == trace.HOST_PLANE:
            lines = [(ln.name, [e for e in ln.events
                                if e.name in (trace.CALL_SPAN,
                                              trace.WAIT_SPAN)])
                     for ln in plane.lines]
        elif plane.name.startswith(trace.DEVICE_PREFIX):
            lines = [(ln.name, list(ln.events)) for ln in plane.lines
                     if ln.name in keep]
        else:
            continue
        lines = [(n, evs) for n, evs in lines if evs]
        names = sorted({e.name for _, evs in lines for e in evs})
        ids = {n: i for i, n in enumerate(names, 1)}
        body = ""
        for lid, (lname, evs) in enumerate(lines, 1):
            body += f"lines {{ id: {lid} name: {json.dumps(lname)} " \
                    "timestamp_ns: 0\n"
            body += "".join(
                f"events {{ metadata_id: {ids[e.name]} "
                f"offset_ps: {int(e.start_ns * 1000)} "
                f"duration_ps: {int(e.duration_ns * 1000)} }}\n"
                for e in evs)
            body += "}\n"
        meta = "".join(
            f"event_metadata {{ key: {i} value {{ id: {i} "
            f"name: {json.dumps(n)} }} }}\n" for n, i in ids.items())
        out.append(f"planes {{ id: {pid} name: {json.dumps(plane.name)}\n"
                   f"{body}{meta}}}\n")
    return "".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=9)
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--fixture", type=Path, default=None)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from bench import harness, stages, trace
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = harness.load_cell(args.workload)
    if args.rounds:
        cell = dataclasses.replace(
            cell, traffic={**cell.traffic, "rounds_per_call": args.rounds})
    devices = harness.devices_for(cell)
    prog = harness.program(cell)
    state = harness.build_state(cell, prog, args.seed, devices)
    call = harness.make_call(cell, prog, devices)
    mark = jax.jit(lambda clock: clock + 1)
    for _ in range(harness.WARM_CALLS):
        t0 = time.perf_counter()
        state = call(state)
        jax.block_until_ready(mark(state.clock))
        warm_s = time.perf_counter() - t0
    setup_s = time.perf_counter() - T_PROCESS

    t0 = time.perf_counter()
    hlo = call.lower(state).compile().as_text()
    scopes = stages.op_scopes(hlo)
    module = stages.module_name(hlo)
    map_s = time.perf_counter() - t0

    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    fetched = float(np.sum(jax.device_get(state.metrics.fetched)))
    t0 = time.perf_counter()
    # Time enough for every traced call after the profiler's start-up.
    state, sent, _ = harness.drive(
        call, mark, state, (args.calls + 0.5) * warm_s + 2.0,
        max(1, math.ceil(harness.AHEAD_S / warm_s)), args.calls)
    drive_s = time.perf_counter() - t0
    fetched = float(np.sum(jax.device_get(state.metrics.fetched))) - fetched
    valid_share = stages.epoch_valid_share(
        cell, fetched, sent * cell.traffic["rounds_per_call"])
    profile = trace.load(harness.TRACE_DIR)
    summary = trace.reduce(profile)
    ops = stages.op_self_times(profile, module)
    per_stage = stages.self_times(profile, scopes, module)

    rounds = summary.calls * cell.traffic["rounds_per_call"]
    ms = 1e3 / rounds
    top: dict[str, Counter] = {}
    for (name, inside), sec in ops.items():
        stage = scopes.get(name, stages.UNSCOPED) if inside \
            else stages.UNSCOPED
        top.setdefault(stage, Counter())[name] += sec * ms
    result = {
        "device": jax.devices()[0].device_kind,
        "setup_s": setup_s, "hlo_map_s": map_s, "traced_drive_s": drive_s,
        "calls": summary.calls, "rounds": rounds, "sent": sent,
        "round_device_ms": summary.busy_mean_s * ms,
        "device_idle_share": summary.idle_share_max * 100,
        "stage_ms": {k: v * ms for k, v in sorted(per_stage.items())},
        "stage_sum_ms": sum(per_stage.values()) * ms,
        "epoch_valid_share": valid_share,
        "top_ops_ms": {k: v.most_common(TOP) for k, v in sorted(top.items())},
        "instructions_mapped": len(scopes), "module": module,
    }
    if args.fixture:
        args.fixture.mkdir(parents=True, exist_ok=True)
        text = cut_trace(profile)
        (args.fixture / "trace.xplane.pb.gz").write_bytes(gzip.compress(
            ProfileData.text_proto_to_serialized_xspace(text)))
        (args.fixture / "op_scopes.json.gz").write_bytes(gzip.compress(
            json.dumps({"module": module, "rounds_per_call":
                        cell.traffic["rounds_per_call"], "scopes": scopes,
                        "result": result}, sort_keys=True).encode()))
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
