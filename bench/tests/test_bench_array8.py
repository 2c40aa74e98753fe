"""The ``array8.randread_qd256`` cell at toy size on the CPU.

The cell's registered files, shrunk as ``toy.toy_cell`` shrinks every
case, run through ``harness.run_cell`` on a 1-device mesh: its four
drives go through the sharded runner, which consumes its state at each
call. The run must be ``correct``, and each fault of
``test_bench_faults`` must make it not so.
"""
import json

import jax
import pytest

from bench import harness
from bench.tests import toy
from bench.tests.test_bench_faults import FAULTS

CELL = "array8.randread_qd256"
SEED = 2**33 + 17


def toy_cell() -> harness.Cell:
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    entry = {w["name"]: w for w in spec["workloads"]}[CELL]
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(toy.CASES, CELL,
                   (entry["config"], entry["traffic"], entry["chips"]))
        return toy.toy_cell(CELL)


def run_toy():
    return harness.run_cell(toy_cell(), SEED, 0.3, trace=False,
                            t_process=0.0, devices=jax.devices()[:1])


def test_toy_cell_is_the_registered_array():
    cell = toy_cell()
    assert cell.config["name"] == "array8_d40m"
    assert cell.config["chips"] == 4 and cell.config["drives"] == 4


def test_toy_array_run_is_correct():
    res = run_toy()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["checks"]["discrete_mismatches"]["value"] == 0
    assert res["checks"]["bad_buffer_rows"]["value"] == 0


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_fault_makes_the_array_run_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = run_toy()
    assert res["correct"] is False, res["checks"]
