"""Profile one steady-state engine round: trace + per-stage cost table.

Two views of where the emulator's wall-clock goes:

  * a ``jax.profiler`` trace of one post-warmup steady-state runner
    invocation, written to ``--outdir`` (open with TensorBoard or
    Perfetto via ``xprof``);
  * a per-stage cost table: each pipeline stage (frontend fetch, timing
    model, data path, flash backend, CQ post/reap) jitted in isolation
    over a representative fetched batch and timed post-warmup, alongside
    the full ``engine_round`` — so stage costs and their sum can be
    compared against the fused round.

Stage closures honor the config's compaction/Pallas flags exactly as
``DevicePipeline.process`` threads them, so the table reflects the
pipeline actually being benchmarked. ``--assert-shares`` turns the table
into a CI smoke gate: exit 1 if any of the historical hot stages
(timing, flash, qp) exceeds ``--max-share`` of a full engine round —
the regression signature PR 8 optimized away. ``--no-trace`` skips the
profiler trace for fast smoke runs.

    PYTHONPATH=src python scripts/profile_engine.py \
        [--config local_1drive|array_4drive|remote_qos] \
        [--rounds N] [--reps N] [--outdir DIR] \
        [--no-trace] [--assert-shares] [--max-share F]
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402

from benchmarks import common as C  # noqa: E402
from benchmarks.emulator_speed import _configs  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import engine, frontend, qp, timing  # noqa: E402
from repro.core import datapath, flash  # noqa: E402
from repro.core.device import DevicePipeline  # noqa: E402
from repro.core.epoch import (  # noqa: E402
    Epoch,
    admission_row_order,
    unit_ready_order,
)
from repro.core.types import PlatformModel  # noqa: E402


def _timeit(fn, *args, reps: int) -> float:
    """Mean post-warmup seconds per call of a jitted closure."""
    jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def stage_table(spec, reps: int):
    """Time each pipeline stage in isolation over one fetched batch."""
    cfg, ssd, wl = spec["cfg"], spec["ssd"], spec["wl"]
    plat = PlatformModel()
    pipe = DevicePipeline(cfg, ssd, plat)
    st = engine.init_state(cfg, ssd, wl)
    unit = frontend.fetch_row_units(cfg)
    # Resolve the flags the same way DevicePipeline.process does, so the
    # isolated-stage closures time the code path the engine actually runs
    # (use_pallas_segscan may be None = auto).
    pallas = cfg.resolve_pallas_segscan(ssd, plat)
    compact = cfg.use_compaction

    fetch_fn = jax.jit(lambda s: frontend.fetch(
        s.rings, s.clock, s.device.disp_time, cfg, plat
    ))
    _, disp, batch, fetch_done = jax.block_until_ready(fetch_fn(st))
    dev = dataclasses.replace(st.device, disp_time=disp)
    tbatch = dataclasses.replace(batch, arrival=fetch_done)

    # The timing closure honors cfg.lock_order the way process does:
    # under the ready-time lock the batch dispatches through the epoch's
    # admission-order row permutation (a representative one, derived
    # from this batch's post-fetch ready times).
    dispatch_order = None
    if cfg.lock_order == "ready_time" and cfg.timing_scope != "local":
        ep = Epoch.from_batch(batch, fetch_done, unit, "ring")
        dispatch_order = admission_row_order(
            unit_ready_order(ep.unit_ready(cfg.num_units)),
            ep, cfg.num_units,
        )

    rows = [("frontend.fetch", _timeit(fetch_fn, st, reps=reps))]
    rows.append(("timing.update", _timeit(
        jax.jit(lambda ts, b: timing.update(
            ts, b, ssd, cfg.mode, use_compaction=compact,
            dispatch_order=dispatch_order,
        )),
        dev.tstate, tbatch, reps=reps,
    )))
    if cfg.batched_datapath:
        rows.append(("datapath.dsa_worker_times", _timeit(
            jax.jit(lambda d, fd, b: datapath.dsa_worker_times(
                d, fd, b, cfg, plat, ssd, unit=unit
            )),
            dev.dsa_time, fetch_done, batch, reps=reps,
        )))
    else:
        rows.append(("datapath.baseline_worker_times", _timeit(
            jax.jit(lambda w, m, fd, b: datapath.baseline_worker_times(
                w, m, fd, b, cfg, plat, ssd, unit=unit,
                use_counting_sort=compact,
            )),
            dev.work_time, dev.map_time, fetch_done, batch, reps=reps,
        )))
    if ssd.flash_backend:
        rows.append(("flash.flash_stage", _timeit(
            jax.jit(lambda f, b, a: flash.flash_stage(
                f, b, a, a, ssd, use_pallas=pallas,
                use_counting_sort=compact,
                use_pallas_flash=cfg.use_pallas_flash,
            )),
            dev.flash, batch, fetch_done, reps=reps,
        )))
    rows.append(("qp.post_and_reap", _timeit(
        jax.jit(lambda c, b, d: qp.post_and_reap(
            c, b.sq_id, d, b.req_id, b.valid, cfg.qp,
            fused_sort=cfg.use_sort_plan,
            use_pallas=pallas,
            fused_scatter=compact,
            use_pallas_reap=cfg.use_pallas_reap,
        )),
        st.cq, batch, fetch_done, reps=reps,
    )))
    rows.append(("pipeline.process (stages 2-5)", _timeit(
        jax.jit(lambda d, b, fd, c: pipe.process(
            d, b, fd, unit, c, ring_layout=True
        )),
        dev, batch, fetch_done, st.cq, reps=reps,
    )))
    rows.append(("engine_round (full)", _timeit(
        jax.jit(lambda s: engine.engine_round(s, cfg, ssd, wl, plat)),
        st, reps=reps,
    )))
    return rows


# Stages whose share of a full round ``--assert-shares`` gates on: the
# three that dominated the seed profile (and that PR 8's compaction /
# fused-kernel work targeted).
HOT_STAGES = ("timing.update", "flash.flash_stage", "qp.post_and_reap")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="local_1drive",
                    choices=[s["name"] for s in _configs(quick=True)])
    ap.add_argument("--rounds", type=int, default=24,
                    help="engine rounds per traced runner invocation")
    ap.add_argument("--reps", type=int, default=20,
                    help="timed repetitions per stage closure")
    ap.add_argument("--outdir", default="experiments/profile",
                    help="jax.profiler trace output directory")
    ap.add_argument("--no-trace", action="store_true",
                    help="skip the jax.profiler trace (fast smoke)")
    ap.add_argument("--assert-shares", action="store_true",
                    help="exit 1 if any hot stage (timing/flash/qp) "
                         "exceeds --max-share of a full engine round")
    ap.add_argument("--max-share", type=float, default=0.5,
                    help="per-stage share ceiling for --assert-shares "
                         "(fraction of engine_round; generous by design "
                         "— CI machines are noisy)")
    ap.add_argument("--lock-order", default=None,
                    choices=["program", "ready_time"],
                    help="override EngineConfig.lock_order — profile the "
                         "ready-time admission permutation's overhead "
                         "against the program-order path")
    ap.add_argument("--sanitize", action="store_true",
                    help="run one checkify-instrumented invocation "
                         "(EngineConfig.sanitize=True) before profiling "
                         "— certifies the profiled config's pipeline "
                         "invariants; the timed stage closures stay "
                         "unsanitized (checkify rewrites the program)")
    args = ap.parse_args()

    spec = dict(next(s for s in _configs(quick=False)
                     if s["name"] == args.config))
    if args.lock_order is not None:
        spec["cfg"] = spec["cfg"].replace(lock_order=args.lock_order)
    cfg, ssd, wl = spec["cfg"], spec["ssd"], spec["wl"]
    plat = PlatformModel()
    enable_compile_cache()
    C.jit_warmup()

    # -- optional sanitized certification pass -----------------------------
    if args.sanitize:
        m = spec["num_devices"]
        if m == 1:
            s_st = engine.init_state(cfg, ssd, wl)
            s_runner = engine.make_runner(
                cfg, ssd, wl, plat, args.rounds, sanitize=True
            )
        else:
            s_st = engine.init_array_state(cfg, ssd, wl, m)
            s_runner = engine.make_array_runner(
                cfg, ssd, wl, plat, args.rounds, sanitize=True
            )
        jax.block_until_ready(s_runner(s_st))
        print(f"sanitize: {args.config} checkify-clean "
              f"({args.rounds} rounds)")

    # -- trace one post-warmup steady-state runner invocation --------------
    if not args.no_trace:
        m = spec["num_devices"]
        if m == 1:
            st = engine.init_state(cfg, ssd, wl)
            runner = engine.make_runner(cfg, ssd, wl, plat, args.rounds)
        else:
            st = engine.init_array_state(cfg, ssd, wl, m)
            runner = engine.make_array_runner(
                cfg, ssd, wl, plat, args.rounds
            )
        st = jax.block_until_ready(runner(st))  # warmup/compile round
        Path(args.outdir).mkdir(parents=True, exist_ok=True)
        with jax.profiler.trace(args.outdir):
            st = jax.block_until_ready(runner(st))
        print(f"trace: 1 x {args.rounds}-round invocation -> "
              f"{args.outdir}")

    # -- per-stage cost table ----------------------------------------------
    print(f"\nper-stage cost, config={args.config} "
          f"(mean of {args.reps} post-warmup reps, one epoch batch):")
    rows = stage_table(spec, args.reps)
    width = max(len(n) for n, _ in rows)
    total = next(dt for n, dt in rows if n.startswith("engine_round"))
    for name, dt in rows:
        print(f"  {name:<{width}}  {dt * 1e6:>10.1f} us/call "
              f"({dt / total * 100:5.1f}% of a round)")

    if args.assert_shares:
        bad = [
            (name, dt / total)
            for name, dt in rows
            if name in HOT_STAGES and dt / total > args.max_share
        ]
        if bad:
            for name, share in bad:
                print(f"FAIL: {name} is {share * 100:.1f}% of a round "
                      f"(ceiling {args.max_share * 100:.0f}%)")
            return 1
        print(f"OK: all hot stages <= {args.max_share * 100:.0f}% "
              f"of a round ({', '.join(HOT_STAGES)})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
