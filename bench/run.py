#!/usr/bin/env python3
"""Run one benchmark cell once, from the root of a checkout:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, their metrics and their files are listed in BENCHMARK.json;
``bench/harness.py`` says what a run does. The last line of standard
output is the result as one JSON object. Without a TPU, or with fewer
chips than the cell asks for, the run exits nonzero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

if __name__ == "__main__":
    from bench.harness import main

    sys.exit(main(t_process=T_PROCESS))
