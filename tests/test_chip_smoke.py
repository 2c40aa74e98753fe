"""chip_smoke.py's phases at toy size on the CPU (kernels interpreted).

A rehearsal of control flow and checks, not of the chip: the phase
functions run with tiny rings and flash images, and ``main`` must refuse
to run without a TPU.
"""
import dataclasses

import jax
import pytest

import chip_smoke as cs
from repro.core.types import WorkloadConfig

IO_DEPTH = 16
WL = WorkloadConfig(io_depth=IO_DEPTH)


@pytest.fixture(scope="module")
def tiny():
    cfg, ssd = cs.phase_a_config(num_bufs=8 * IO_DEPTH)
    cfg = cfg.replace(num_sqs=8, sq_depth=64, fetch_width=16, num_units=4)
    return cfg, ssd.replace(num_blocks=1 << 12)


@pytest.fixture(scope="module")
def engine_run(tiny):
    cfg, ssd = tiny
    return cs.phase_engine(cfg, ssd, WL, rounds=4, invocations=2,
                           block_words=16, min_iops=1e6)


def test_phase_engine(engine_run):
    report, state = engine_run
    assert report["completed"] > 0
    assert report["buffer_rows_checked"] > 0
    assert report["next_reads_checked"] > 0
    assert report["flash_image_bytes"] == (1 << 12) * 16 * 4


def test_phase_client(tiny, engine_run):
    cfg, ssd = tiny
    _, state = engine_run
    report = cs.phase_client(cfg, ssd, state.flash, reads=256)
    assert report["reads"] == 256
    assert report["mean_latency_us"] > 0


def test_phase_array(tiny):
    cfg, ssd = tiny
    report = cs.phase_array(cfg, ssd, WL, drives=2, rounds=4,
                            invocations=2, block_words=128, min_iops=2e6)
    assert report["drives"] == 2


def test_phase_exactness(tiny):
    cfg, ssd = tiny
    report = cs.phase_exactness(
        cfg, ssd, rounds=3, requests=128, block_words=128,
        reference_device=jax.devices("cpu")[0],
    )
    assert report["completed"] > 0


def test_phase_sanitize(tiny):
    cfg, ssd = tiny
    report = cs.phase_sanitize(cfg, ssd, WL, rounds=2, block_words=16)
    assert report["completed"] > 0


def test_phase_sharded_on_one_device(tiny):
    cfg, ssd = tiny
    report = cs.phase_sharded(cfg, ssd, WL, drives=2, rounds=2,
                              block_words=16, devices=jax.devices()[:1])
    assert report["devices"] == 1


def test_check_catches_a_wrong_buffer_row(engine_run):
    """The data check is not vacuous: one flipped word fails it."""
    _, state = engine_run
    row = int((abs(state.bufs).sum(axis=1) > 0).argmax())
    bad = dataclasses.replace(state, bufs=state.bufs.at[row, 3].add(1.0))
    with pytest.raises(cs.SmokeFailure):
        cs.check_buffers(bad, 1 << 12)


def test_main_refuses_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert cs.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
