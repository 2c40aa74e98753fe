"""Fused CQ post-and-reap as a Pallas kernel (neutral QP hot path).

The neutral completion path (core/qp.py) spends its time on bookkeeping,
not modeling: a per-CQ posting rank (``segment_rank`` — a stable sort),
three ring scatters, and a per-CQ ``segment_sum`` of valid entries. This
kernel fuses all of it into one sequential pass over the epoch's rows:
a (Q,) counter vector *is* the rank, the ring slot, and the count at
once — row i of CQ c posts at ``(tail[c] + cnt[c]) % D`` and bumps
``cnt[c]``. Everything is integer bookkeeping and data movement, so the
result is bit-exact against the reference for *any* inputs.

Layout: the per-row operands and the counters are scalars in SMEM. The
rings are flattened and viewed lane-dense as (rows, 128) VMEM tiles, so
posting entry ``c * D + pos`` is a masked select on one dynamically
indexed 128-lane row — the vector unit never stores a scalar.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8


def _post(ring_ref, row, lane_ids, lane, value):
    cur = ring_ref[pl.ds(row, 1), :]
    ring_ref[pl.ds(row, 1), :] = jnp.where(lane_ids == lane, value, cur)


def _fused_reap_kernel(
    tail_ref, key_ref, done_ref, req_ref, valid_ref,
    dt_in, vt_in, rid_in,
    dt_out, vt_out, rid_out, counts_ref, *, depth: int,
):
    del dt_in, vt_in, rid_in  # aliased to the outputs
    num_cqs = counts_ref.shape[0]
    lane_ids = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def zero(c, carry):
        counts_ref[c] = 0
        return carry

    jax.lax.fori_loop(0, num_cqs, zero, 0)

    def body(i, carry):
        @pl.when(valid_ref[i] != 0)
        def _post_row():
            c = key_ref[i]
            r = counts_ref[c]
            flat = c * depth + (tail_ref[c] + r) % depth
            row, lane = flat // LANES, flat % LANES
            # Neutral path: visible time == device completion time.
            t = jnp.full((1, LANES), done_ref[i], jnp.float32)
            _post(dt_out, row, lane_ids, lane, t)
            _post(vt_out, row, lane_ids, lane, t)
            _post(rid_out, row, lane_ids, lane,
                  jnp.full((1, LANES), req_ref[i], jnp.int32))
            counts_ref[c] = r + 1

        return carry

    jax.lax.fori_loop(0, key_ref.shape[0], body, 0)


def _to_rows(ring: jax.Array, rows: int) -> jax.Array:
    flat = ring.reshape(-1)
    return jnp.pad(flat, (0, rows * LANES - flat.shape[0])).reshape(
        rows, LANES
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_reap(
    done_time: jax.Array,     # (Q, D) f32 ring
    visible_time: jax.Array,  # (Q, D) f32 ring
    req_id_ring: jax.Array,   # (Q, D) i32 ring
    tail: jax.Array,          # (Q,) i32 free-running producer index
    key: jax.Array,           # (N,) i32 target CQ, == Q for invalid rows
    done: jax.Array,          # (N,) f32 completion times
    req_id: jax.Array,        # (N,) i32
    valid: jax.Array,         # (N,) bool
    *,
    interpret: bool = False,
):
    """One-pass neutral post: returns (done_time', visible_time',
    req_id', counts) with ``counts`` the (Q,) per-CQ valid entries."""
    q, d = done_time.shape
    tile = SUBLANES * LANES
    rows = -(-(q * d) // tile) * SUBLANES
    # Invalid rows carry key == Q; clip for safe counter indexing (the
    # valid gate already keeps them from posting).
    safe_key = jnp.clip(key, 0, q - 1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    ring_shape = jax.ShapeDtypeStruct((rows, LANES), jnp.float32)
    dt, vt, rid, counts = pl.pallas_call(
        functools.partial(_fused_reap_kernel, depth=d),
        in_specs=[smem] * 5 + [vmem] * 3,
        out_specs=[vmem, vmem, vmem, smem],
        out_shape=[
            ring_shape,
            ring_shape,
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((q,), jnp.int32),
        ],
        input_output_aliases={5: 0, 6: 1, 7: 2},
        interpret=interpret,
    )(
        tail, safe_key, done, req_id, valid.astype(jnp.int32),
        _to_rows(done_time, rows), _to_rows(visible_time, rows),
        _to_rows(req_id_ring, rows),
    )

    def back(x):
        return x.reshape(-1)[: q * d].reshape(q, d)

    return back(dt), back(vt), back(rid), counts
