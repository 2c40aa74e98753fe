"""Toy-size runs of each cell through the harness's internal functions on
the CPU, the result line's schema, and the refusal to run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.compare import NAMES
from bench.tests.toy import CASES, run_toy

CELLS = list(CASES)


@pytest.fixture(scope="module", params=CELLS)
def toy_result(request):
    return request.param, run_toy(request.param)


def test_toy_run_is_correct(toy_result):
    cell, res = toy_result
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["checks"]["discrete_mismatches"]["value"] == 0
    assert res["checks"]["bad_buffer_rows"]["value"] == 0


def test_result_schema(toy_result):
    cell, res = toy_result
    keys = list(res)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert keys[-1] == "checks" and set(res["checks"]) == set(NAMES)
    assert all(set(c) == {"value", "limit"} for c in res["checks"].values())
    assert {"emul_req_per_s", "setup_s"} <= set(res["metrics"])
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    json.dumps(res)


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300,
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr
