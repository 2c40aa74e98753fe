"""Emulator wall-clock speed benchmark (the paper's headline axis).

Times jit-compiled steady-state engine rounds — ``make_runner`` /
``make_array_runner`` — with ``time.perf_counter`` after an explicit
warmup/compile invocation, and reports **emulated requests per
wall-second** across three configs:

  * ``local_1drive``  — one SwarmIO-config drive at the future-40M target;
  * ``array_4drive``  — the same drive vmapped into a 4-drive array;
  * ``remote_qos``    — one remote drive behind a switched fabric with
                        two WFQ tenant classes (the heaviest pipeline).

Each config runs three variants:

  * ``seed``             — the pre-optimization path (no buffer donation,
                           per-stage sorts and segmented reductions:
                           ``use_sort_plan=False, use_compaction=False``);
  * ``optimized``        — donated state buffers + the epoch sort plan +
                           the PR-8 compaction paths (sort-free timing
                           layout, counting-sorted flash/lanes, block CQ
                           ranks, fused ring scatters);
  * ``optimized_pallas`` — optimized plus the Pallas segmented-scan
                           queueing core (``use_pallas_segscan=True``).

Every variant is timed for ``--reps`` repetitions *post-warmup*, chaining
the state through (``st = runner(st)``) so donation is observable; each
rep records its own wall seconds and requests retired. Each (config,
variant) gets its own two-invocation warmup — compile plus one dispatch
on already-device-resident state — and rep 0 is sanity-checked at
``<= 3x`` the rep median (a violation means compile or retrace leaked
into the timed region; it is recorded in the JSON and warned about, not
fatal). Results persist to ``BENCH_emulator_speed.json`` at the repo
root (schema documented in the README's "Emulator speed" section) and a
CSV summary row per config/variant flows through ``benchmarks/run.py``.

    PYTHONPATH=src python -m benchmarks.emulator_speed [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import time

import jax
import jax.numpy as jnp

from benchmarks import common as C
from repro.compile_cache import enable_compile_cache
from repro.core import engine
from repro.core.types import FabricConfig, PlatformModel, WorkloadConfig
from repro.workloads import MultiTenant

SCHEMA = "emulator_speed/v1"
JSON_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_emulator_speed.json",
)

# variant name -> (EngineConfig field overrides, donate buffers?)
VARIANTS = [
    (
        "seed",
        dict(
            use_sort_plan=False, use_compaction=False,
            use_pallas_segscan=False,
        ),
        False,
    ),
    (
        "optimized",
        dict(
            use_sort_plan=True, use_compaction=True,
            use_pallas_segscan=False,
        ),
        True,
    ),
    (
        "optimized_pallas",
        dict(
            use_sort_plan=True, use_compaction=True,
            use_pallas_segscan=True,
        ),
        True,
    ),
]


def _configs(quick: bool):
    rounds = 6 if quick else 24
    # The remote fabric adds whole-RTT + MTU-timeout latency, so the
    # first completions land several rounds after submission; keep the
    # per-invocation round count above that bubble even in --quick so
    # every timed rep retires work.
    remote_rounds = 24
    wl = WorkloadConfig(io_depth=256)
    fab = FabricConfig(
        remote=True,
        tx_bytes_per_us=30_000.0, rx_bytes_per_us=30_000.0,
        rtt_us=2.0, wire_txn_us=0.2, mtu_batch=8, mtu_timeout_us=5.0,
        switch_bytes_per_us=60_000.0, switch_fanin=4,
        qos_weights=(2.0, 1.0),
    )
    mt = MultiTenant(io_depth=256, tenant_read_frac=(1.0, 0.0))
    return [
        dict(name="local_1drive", cfg=C.swarmio_cfg(), ssd=C.FUTURE_40M,
             wl=wl, num_devices=1, rounds=rounds),
        dict(name="array_4drive", cfg=C.swarmio_cfg(), ssd=C.FUTURE_40M,
             wl=wl, num_devices=4, rounds=rounds),
        dict(name="remote_qos", cfg=C.swarmio_cfg(fabric=fab),
             ssd=C.FUTURE_40M, wl=mt, num_devices=1,
             rounds=remote_rounds),
    ]


def _completed(st) -> float:
    """Array-aggregate completed count (device axis summed away)."""
    return float(jnp.sum(st.metrics.completed))


def sanitize_pass(quick: bool = True) -> None:
    """Run every config family once under ``EngineConfig.sanitize``.

    One checkify-instrumented invocation per family (same specs the
    timed benchmark uses) — raises ``checkify.JaxRuntimeError`` on the
    first violated pipeline invariant, so a clean pass certifies the
    benchmarked configs before any timing is trusted. Never timed: the
    functionalized program is a different (slower) XLA program than the
    benchmarked one.
    """
    plat = PlatformModel()
    for spec in _configs(quick):
        cfg, ssd, wl = spec["cfg"], spec["ssd"], spec["wl"]
        m, rounds = spec["num_devices"], spec["rounds"]
        if m == 1:
            st = engine.init_state(cfg, ssd, wl)
            runner = engine.make_runner(
                cfg, ssd, wl, plat, rounds, sanitize=True
            )
        else:
            st = engine.init_array_state(cfg, ssd, wl, m)
            runner = engine.make_array_runner(
                cfg, ssd, wl, plat, rounds, sanitize=True
            )
        jax.block_until_ready(runner(st))
        print(f"  sanitize: {spec['name']} checkify-clean")


def time_variant(cfg, ssd, wl, rounds, num_devices, donate, reps):
    """Warm up one runner, then time ``reps`` chained invocations.

    Returns the per-rep records plus the final state (for virtual-time
    metrics). Two warmup calls pay compile + first dispatch and are never
    timed — the second catches any retrace triggered by the first call's
    *output* avals differing from ``init_state``'s (the historical rep-0
    contamination: a weak-typed leaf in ``Metrics.zero`` silently forced
    a second compile inside the first timed rep). Reps feed each call's
    output back in, which is exactly the regime buffer donation
    optimizes.
    """
    plat = PlatformModel()
    if num_devices == 1:
        st = engine.init_state(cfg, ssd, wl)
        runner = engine.make_runner(cfg, ssd, wl, plat, rounds,
                                    donate=donate)
    else:
        st = engine.init_array_state(cfg, ssd, wl, num_devices)
        runner = engine.make_array_runner(cfg, ssd, wl, plat, rounds,
                                          donate=donate)
    if donate:
        st = engine.unalias(st)
    st = jax.block_until_ready(runner(st))  # warmup 1: compile + run
    st = jax.block_until_ready(runner(st))  # warmup 2: steady-state avals
    rep_records = []
    for _ in range(reps):
        before = _completed(st)
        t0 = time.perf_counter()
        st = runner(st)
        jax.block_until_ready(st)
        dt = time.perf_counter() - t0
        n = _completed(st) - before
        rep_records.append({
            "wall_s": dt,
            "requests": n,
            "req_per_wall_s": n / dt,
        })
    return rep_records, st


def bench(quick: bool = False, reps: int | None = None):
    """Run all configs x variants; write the JSON; return CSV rows."""
    reps = reps if reps is not None else (3 if quick else 5)
    results = []
    rows = []
    for spec in _configs(quick):
        name = spec["name"]
        variants = {}
        for vname, overrides, donate in VARIANTS:
            cfg = spec["cfg"].replace(**overrides)
            recs, st = time_variant(
                cfg, spec["ssd"], spec["wl"], spec["rounds"],
                spec["num_devices"], donate, reps,
            )
            best = max(r["req_per_wall_s"] for r in recs)
            walls = sorted(r["wall_s"] for r in recs)
            median_wall = walls[len(walls) // 2]
            rep0_clean = recs[0]["wall_s"] <= 3.0 * median_wall
            if not rep0_clean:
                print(
                    f"  WARN: {name}/{vname} rep 0 took "
                    f"{recs[0]['wall_s']:.3f}s vs median "
                    f"{median_wall:.3f}s — compile/retrace leaked into "
                    f"the timed region"
                )
            variants[vname] = {
                "donate": donate,
                "use_sort_plan": overrides["use_sort_plan"],
                "use_compaction": overrides["use_compaction"],
                "use_pallas_segscan": overrides["use_pallas_segscan"],
                "reps": recs,
                "req_per_wall_s": best,  # best-of-reps (noise floor)
                "rep0_clean": rep0_clean,  # rep 0 <= 3x median wall_s
                "virtual_miops": float(engine.aggregate_iops(st)) / 1e6,
            }
            rows.append([
                name, vname, spec["rounds"], spec["num_devices"], reps,
                best, variants[vname]["virtual_miops"],
            ])
        seed_rate = variants["seed"]["req_per_wall_s"]

        def _speedup(v):
            # None (JSON null) if the seed retired nothing — a config
            # misconfigured to complete zero requests must not crash
            # the whole matrix.
            return v["req_per_wall_s"] / seed_rate if seed_rate else None

        entry = {
            "name": name,
            "rounds": spec["rounds"],
            "num_devices": spec["num_devices"],
            "variants": variants,
            "speedup_optimized_vs_seed": _speedup(variants["optimized"]),
            "speedup_optimized_pallas_vs_seed":
                _speedup(variants["optimized_pallas"]),
        }
        results.append(entry)
        opt = entry["speedup_optimized_vs_seed"]
        pal = entry["speedup_optimized_pallas_vs_seed"]
        print(
            f"  {name}: seed {seed_rate:,.0f} req/wall-s, optimized "
            f"{f'{opt:.2f}x' if opt else '—'}, "
            f"+pallas {f'{pal:.2f}x' if pal else '—'}"
        )

    payload = {
        "schema": SCHEMA,
        "quick": quick,
        "host": {
            "machine": platform.machine(),
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
        },
        "configs": results,
    }
    with open(JSON_PATH, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"  -> {JSON_PATH}")
    header = ["config", "variant", "rounds", "num_devices", "reps",
              "req_per_wall_s", "virtual_miops"]
    return header, rows


def bench_figure(quick: bool = False):
    """`benchmarks/run.py` entry point (figure-function signature)."""
    return bench(quick=quick)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="reduced rounds/reps for CI smoke")
    ap.add_argument("--reps", type=int, default=None,
                    help="timed repetitions per variant (post-warmup)")
    args = ap.parse_args()
    enable_compile_cache()
    C.jit_warmup()
    header, rows = bench(quick=args.quick, reps=args.reps)
    C.write_csv("emulator_speed", header, rows)


if __name__ == "__main__":
    main()
