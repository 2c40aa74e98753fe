"""Compile-only checks against a described TPU v5e chip.

The TPU compiler compiles for a chip that is described, not attached:
these tests lower the Pallas stage kernels and the phase-a engine runner
of ``chip_smoke.py`` at their real shapes and let Mosaic and XLA refuse
what the chip would refuse (scalar stores to vector memory, unaligned
blocks, programs that do not fit HBM). Nothing runs, so they say nothing
about results or times.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke as cs
from repro.core import engine
from repro.core.types import PlatformModel, WorkloadConfig
from repro.kernels import block_gather, die_contention, fused_reap, seg_scan
from repro.kernels import ops as kops

ROWS = 8192                 # one epoch: 32 SQs x fetch_width 256
NUM_CQS, DEPTH = 32, 1024
DIES = 32
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shp, dtype):
        return jax.ShapeDtypeStruct(shp, dtype, sharding=one_chip)

    return make


def _compiled_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_seg_scan_compiles(shape):
    _compiled_kernel(
        lambda v, h: seg_scan.seg_scan(v, h, interpret=False),
        shape((ROWS,), jnp.float32), shape((ROWS,), jnp.bool_),
    )


def test_fused_reap_compiles(shape):
    ring = (NUM_CQS, DEPTH)
    _compiled_kernel(
        lambda *a: fused_reap.fused_reap(*a, interpret=False),
        shape(ring, jnp.float32), shape(ring, jnp.float32),
        shape(ring, jnp.int32), shape((NUM_CQS,), jnp.int32),
        shape((ROWS,), jnp.int32), shape((ROWS,), jnp.float32),
        shape((ROWS,), jnp.int32), shape((ROWS,), jnp.bool_),
    )


def test_die_contention_compiles(shape):
    _compiled_kernel(
        lambda *a: die_contention.die_contention(*a, interpret=False),
        shape((ROWS,), jnp.float32), shape((ROWS,), jnp.float32),
        shape((ROWS,), jnp.int32), shape((ROWS,), jnp.bool_),
        shape((DIES,), jnp.float32),
    )


def test_block_gather_compiles_on_sector_rows(shape):
    """A 2^24-block image of 512-B (128-word) rows: one row DMA each."""
    _compiled_kernel(
        lambda f, i: block_gather.block_gather(f, i, interpret=False),
        shape((cs.NUM_BLOCKS, 128), jnp.float32),
        shape((ROWS,), jnp.int32),
    )


def test_block_gather_refuses_16_word_rows(shape):
    """The engine's default 16-word image cannot be row-DMA'd from its
    (8, 128) tiles: the kernel says so instead of relaying it out."""
    with pytest.raises(ValueError, match="block_gather"):
        jax.jit(
            lambda f, i: block_gather.block_gather(f, i, interpret=False)
        ).lower(
            shape((cs.NUM_BLOCKS, cs.BLOCK_WORDS), jnp.float32),
            shape((ROWS,), jnp.int32),
        )


def _runner_memory(shape, cfg, ssd, wl, plat, block_words):
    state = jax.eval_shape(
        lambda: engine.init_state(cfg, ssd, wl, block_words)
    )
    state = jax.tree.map(lambda s: shape(s.shape, s.dtype), state)
    runner = engine.make_runner(cfg, ssd, wl, plat, cs.ROUNDS, donate=True)
    compiled = runner.lower(state).compile()
    mem = compiled.memory_analysis()
    live = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    )
    return compiled, mem, live


def test_phase_a_runner_fits_one_chip(shape):
    """The 40-MIOPS drive over 2^24 blocks compiles for v5e, donates its
    state in place, and fits 16 GB of HBM."""
    cfg, ssd = cs.phase_a_config()
    _, mem, live = _runner_memory(
        shape, cfg, ssd, WorkloadConfig(io_depth=cs.IO_DEPTH),
        PlatformModel(), cs.BLOCK_WORDS,
    )
    assert mem.alias_size_in_bytes >= cs.NUM_BLOCKS * cs.BLOCK_WORDS * 4
    assert live < HBM_BYTES


def test_exactness_runner_compiles_with_every_kernel(shape, monkeypatch):
    """Phase d's program with all four kernels compiled in (the wrappers
    would interpret on this CPU backend, so they are told otherwise)."""
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    cfg, ssd = cs.phase_a_config()
    cfg, ssd = cs.exactness_config(
        cfg, ssd.replace(num_blocks=cs.EXACT_BLOCKS), kernels=True
    )
    wl = cs.integer_trace(cfg, ssd, requests=cs.EXACT_REQUESTS,
                          span_us=240)
    compiled, _, live = _runner_memory(
        shape, cfg, ssd, wl, cs.integer_platform(), cs.EXACT_WORDS,
    )
    assert compiled.as_text().count("tpu_custom_call") >= 4
    assert live < HBM_BYTES
