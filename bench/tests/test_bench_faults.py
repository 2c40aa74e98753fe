"""The comparison that decides ``correct`` catches a broken timed path.

Each case breaks the program underneath a toy run of a cell (the harness
skips its look for a chip and drives everything else) and expects
``correct`` to come out false. The cells have no exchange between chips
(each chip emulates its own drives; the sharded program holds no
collective), so that fault has no case here. The control, the plain
reference in bfloat16 put in the program's place, must fail too.
"""
import dataclasses

import jax.numpy as jnp
import pytest

from bench import harness
from bench.control import control_numbers
from bench.compare import verdict
from bench.tests.toy import CASES, run_toy, toy_cell
from repro.core import datapath, engine, frontend, timing

CELLS = list(CASES)


def state_unchanged(mp):
    mp.setattr(engine, "run", lambda state, *a, **k: state)


def half_the_batch_dropped(mp):
    fetch = frontend.fetch

    def half(rings, clock, disp, cfg, plat):
        rings, disp, batch, done = fetch(rings, clock, disp, cfg, plat)
        keep = batch.valid & (batch.sq_id < cfg.num_sqs // 2)
        return rings, disp, dataclasses.replace(batch, valid=keep), done

    mp.setattr(frontend, "fetch", half)


def data_altered(mp):
    apply_reads = datapath.apply_reads

    def altered(flash, bufs, batch, use_pallas=False):
        out = apply_reads(flash, bufs, batch, use_pallas)
        return out.at[batch.buf_id[0], 1].add(1.0)

    mp.setattr(datapath, "apply_reads", altered)


def completion_altered(mp):
    update = timing.update

    def late(*a, **k):
        state, completion = update(*a, **k)
        return state, completion + jnp.float32(0.5)

    mp.setattr(timing, "update", late)


FAULTS = [state_unchanged, half_the_batch_dropped, data_altered,
          completion_altered]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    res = run_toy(cell)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    c = toy_cell(cell)
    nums = control_numbers(c, seed=77, rounds=40)
    assert not verdict(nums, harness.load_limits(c.config["name"])), nums
