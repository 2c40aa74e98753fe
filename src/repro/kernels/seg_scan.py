"""Segmented prefix-max scan as a Pallas kernel (timing-model hot loop).

The aggregated timing update reduces to a segmented inclusive prefix max
(core/segops.py). The kernel lays the padded input out lane-dense as
(rows, 128) tiles in flattened order and scans in two levels:

1. within each row, a Hillis-Steele doubling scan over the 128 lanes
   (``pltpu.roll`` by 1, 2, ..., 64 with an iota mask in place of a
   vector gather);
2. across rows, the same doubling scan over each row's summary (its
   last lane: "saw a head" flag and running max), then one more roll by
   a row gives every row the carry of all rows before it. Lanes that
   have not seen a head yet in their row (the row-level flag *is* that
   test, so no in-kernel cumsum) continue the carried segment.

Grid steps walk blocks of rows in order; the carry between them is a
lane-broadcast (8, 128) VMEM tile, never a scalar.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -3e38  # python float: jnp constants would be captured as kernel consts
LANES = 128
SUBLANES = 8


def _shifted(x, ids, shift: int, axis: int, fill):
    """``x`` moved ``shift`` places toward higher indices along ``axis``
    (``pltpu.roll`` turns like ``jnp.roll``); the first ``shift``
    positions read ``fill``."""
    return jnp.where(ids >= shift, pltpu.roll(x, shift, axis), fill)


def _scan(v, f, axis: int, n: int):
    """Inclusive segmented (flag, max) doubling scan along ``axis``."""
    ids = jax.lax.broadcasted_iota(jnp.int32, v.shape, axis)
    stride = 1
    while stride < n:
        v_prev = _shifted(v, ids, stride, axis, NEG)
        f_prev = _shifted(f, ids, stride, axis, 0)
        v = jnp.where(f != 0, v, jnp.maximum(v, v_prev))
        f = jnp.maximum(f, f_prev)
        stride *= 2
    return v, f


def _seg_scan_kernel(vals_ref, heads_ref, out_ref, carry_ref, *, rows: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[...] = jnp.full(carry_ref.shape, NEG, jnp.float32)

    v, f = _scan(vals_ref[...], heads_ref[...], axis=1, n=LANES)

    # Row summaries, broadcast across lanes: (saw a head, running max).
    shape = (rows, LANES)
    rv = jnp.broadcast_to(v[:, LANES - 1:], shape)
    rf = jnp.broadcast_to(f[:, LANES - 1:], shape)
    rv, rf = _scan(rv, rf, axis=0, n=rows)
    # Block-level segmented max through the end of each row; the row
    # before the first is the carry from the previous block.
    carry_in = jnp.broadcast_to(carry_ref[0:1, :], shape)
    rv = jnp.where(rf != 0, rv, jnp.maximum(rv, carry_in))
    ids = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    before = _shifted(rv, ids, 1, 0, carry_in)

    out_ref[...] = jnp.where(f != 0, v, jnp.maximum(v, before))
    carry_ref[...] = jnp.broadcast_to(rv[rows - 1:, :], carry_ref.shape)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def seg_scan(
    values: jax.Array,  # (n,) f32
    heads: jax.Array,   # (n,) bool — segment starts
    *,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    n = values.shape[0]
    tile = SUBLANES * LANES
    rows = -(-n // tile) * SUBLANES
    block = min(block_rows, rows)
    rows = -(-rows // block) * block
    pad = rows * LANES - n
    v = jnp.pad(values.astype(jnp.float32), (0, pad), constant_values=NEG)
    h = jnp.pad(heads.astype(jnp.int32), (0, pad), constant_values=1)
    spec = pl.BlockSpec((block, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_seg_scan_kernel, rows=block),
        grid=(rows // block,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(v.reshape(rows, LANES), h.reshape(rows, LANES))
    return out.reshape(-1)[:n]
