"""DSA-analogue batched block copy as a Pallas TPU kernel.

The paper offloads emulated storage copies to Intel DSA via *batch
descriptors*: an array of (src, dst) copy descriptors issued at once, with
the engine pipelining the copies while the CPU does other work. The TPU
analogue: the descriptor array (block indices) is *scalar-prefetched* into
SMEM, the flash image stays in HBM (``pl.ANY``), and each grid step issues
one batch of ``batch`` row DMAs HBM -> VMEM on a shared semaphore before
waiting for them all — the copies overlap in the DMA engines the way DSA's
pipelined engines overlap a batch descriptor's copies. Pallas writes each
gathered (batch, width) tile back to HBM while the next batch is issued.

A block is one row of the (num_blocks, width) flash image. The TPU keeps
such an array in (8, 128) tiles, so a row DMA must span whole 128-lane
rows: compiled, the kernel needs ``width % 128 == 0`` — a 512-byte
emulated sector is one 128-word f32 row (``engine.init_state(...,
block_words=128)``). A narrower image is refused with an error rather
than copied per call; the interpreter takes any width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8
LANES = 128


def _gather_kernel(idx_ref, flash_ref, out_ref, sem, *, batch: int):
    base = pl.program_id(0) * batch

    def copy(j, src):
        return pltpu.make_async_copy(
            flash_ref.at[pl.ds(src, 1)], out_ref.at[pl.ds(j, 1)], sem
        )

    def issue(j, carry):
        copy(j, idx_ref[base + j]).start()
        return carry

    def wait(j, carry):
        copy(j, 0).wait()
        return carry

    jax.lax.fori_loop(0, batch, issue, 0)
    jax.lax.fori_loop(0, batch, wait, 0)


@functools.partial(jax.jit, static_argnames=("batch", "interpret"))
def block_gather(
    flash: jax.Array,   # (num_blocks, width)
    idx: jax.Array,     # (n,) i32 block indices ("copy descriptors")
    *,
    batch: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """out[i] = flash[idx[i]] — one DMA'd block per descriptor."""
    n = idx.shape[0]
    width = flash.shape[1]
    if not interpret and width % LANES:
        raise ValueError(
            f"block_gather: a compiled row DMA moves whole 128-lane rows, "
            f"but the flash image rows are {width} words wide; build the "
            f"image with a multiple of 128 words per block"
        )
    batch = min(batch, -(-n // SUBLANES) * SUBLANES)
    steps = -(-n // batch)
    idx = jnp.pad(idx.astype(jnp.int32), (0, steps * batch - n))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(steps,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((batch, width), lambda i, idx_ref: (i, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, batch=batch),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((steps * batch, width), flash.dtype),
        interpret=interpret,
    )(idx, flash)
    return out[:n]
