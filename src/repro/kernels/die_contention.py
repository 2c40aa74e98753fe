"""Per-die flash contention as a Pallas kernel (flash-stage hot path).

The flash stage (core/flash.py) queues each epoch's event rows (writes
and mapping misses) on their die: sort rows by die, run a segmented
queueing scan seeded by the epoch-start die cursors, scatter back, then
``segment_max`` the results into the new cursors. This kernel replaces
sort + scan + unsort + max with one sequential left fold over the rows
in dispatch order, carrying a (K,) busy-cursor vector in the output
ref: row i on die c observes ``b = max(cur[c], ready_i) + cost_i`` and
advances ``cur[c] = b``.

All operands are scalars in SMEM (the fold is scalar work by nature).

The fold evaluates the queueing recurrence literally, while the lax
reference re-associates it (a segmented max-plus scan) — the two agree
bit-exactly only when timestamps are integer-valued (the same contract
as ``use_pallas_segscan``; see ``types.integer_timestamps``). Events
only move cursors forward (cost > 0), so ``new_cursors >= chip_busy``
holds like the reference's outer ``maximum``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _die_contention_kernel(
    ready_ref, cost_ref, chip_ref, event_ref, cur_in, busy_ref, cur_out
):
    def copy(c, carry):
        cur_out[c] = cur_in[c]
        return carry

    jax.lax.fori_loop(0, cur_in.shape[0], copy, 0)

    def body(i, carry):
        busy_ref[i] = jnp.float32(0)

        @pl.when(event_ref[i] != 0)
        def _ev():
            c = chip_ref[i]
            b = jnp.maximum(cur_out[c], ready_ref[i]) + cost_ref[i]
            busy_ref[i] = b
            cur_out[c] = b

        return carry

    jax.lax.fori_loop(0, ready_ref.shape[0], body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def die_contention(
    ready: jax.Array,      # (N,) f32 post-lock dispatch times
    cost: jax.Array,       # (N,) f32 die occupancy per event row
    chip: jax.Array,       # (N,) i32 die per row, pre-clipped to [0, K)
    event: jax.Array,      # (N,) bool rows that occupy their die
    chip_busy: jax.Array,  # (K,) f32 epoch-start die cursors
    *,
    interpret: bool = False,
):
    """Returns (busy, new_cursors): per-row die-service completion (0 for
    non-event rows — the flash stage never reads those) and the advanced
    (K,) cursors."""
    n = ready.shape[0]
    k = chip_busy.shape[0]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _die_contention_kernel,
        in_specs=[smem] * 5,
        out_specs=[smem, smem],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((k,), jnp.float32),
        ],
        interpret=interpret,
    )(ready, cost, chip, event.astype(jnp.int32), chip_busy)
