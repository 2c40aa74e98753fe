"""Benchmark driver: one benchmark per paper figure/table.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig12]

Writes CSVs to experiments/bench/ and prints one summary line per figure.
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweeps for CI")
    ap.add_argument("--only", default=None)
    ap.add_argument("--sanitize", action="store_true",
                    help="first run each config family once with "
                         "EngineConfig.sanitize=True (checkify pipeline "
                         "invariants); fails fast on the first violation")
    args = ap.parse_args()

    from benchmarks import common as C
    from repro.compile_cache import enable_compile_cache
    from benchmarks.emulator_speed import bench_figure, sanitize_pass
    from benchmarks.figures import ALL

    # One warmup invocation before anything is timed: the first jit call
    # of the process pays backend init + dispatch warm-up on top of its
    # own compile, which would otherwise land in the first figure's time.
    enable_compile_cache()
    C.jit_warmup()

    if args.sanitize:
        t = time.perf_counter()
        sanitize_pass(quick=args.quick)
        print(f"  sanitize pass clean ({time.perf_counter()-t:.1f}s)")

    # perf_counter everywhere: the same monotonic clock benchmarks/common.py
    # times the engine with (time.time() can step under NTP adjustment).
    t0 = time.perf_counter()
    for name, fn in ALL + [("emulator_speed", bench_figure)]:
        if args.only and args.only not in name:
            continue
        t = time.perf_counter()
        try:
            header, rows = fn(quick=args.quick)
            path = C.write_csv(name, header, rows)
            print(f"  -> {path} ({time.perf_counter()-t:.1f}s)")
        except Exception as e:  # noqa: BLE001
            print(f"  !! {name} FAILED: {type(e).__name__}: {e}")
            raise
    print(f"all benchmarks done in {time.perf_counter()-t0:.1f}s")


if __name__ == "__main__":
    main()
