"""Symmetric queue-pair layer: completion-queue rings (the CQ half).

``SQRings`` (frontend.py) models the submission half of an NVMe queue
pair; this module adds the symmetric completion half. Completions are no
longer read straight out of ``PipelineResult`` — the device *posts* a
completion entry to the CQ paired with the request's SQ, rings a CQ
doorbell, and the GPU consumer *polls* the ring and *reaps* the entry.
Three virtual-time effects the implicit completion path could not
express live here:

  * **completion coalescing** — the device batches ``cq_coalesce_n``
    CQEs per doorbell (with a ``cq_coalesce_us`` timer bound on how long
    the oldest pending entry may wait), trading doorbell rate for
    completion latency (paper Fig. 13's fetch-coalescing knob, mirrored
    onto the completion path — fig21);
  * **doorbell serialization** — each doorbell occupies the CQ's
    completion poster for ``cq_doorbell_us`` (a per-CQ single server),
    so an uncoalesced completion stream can throttle delivered IOPS;
  * **GPU poll cost** — the consumer pays ``cq_poll_us`` per reaped
    doorbell batch plus ``cqe_reap_us`` per entry read from the ring.

All accounting is epoch-batched like the rest of the pipeline: one
``post_and_reap`` call prices a whole completed batch, groups form
within the epoch (the engine's poll quantum acts as an implicit flush
timer), and entries whose completion outruns their group's timer are
posted at their own completion time. With the neutral default config
(``QPConfig().neutral``) the layer stores entries but adds zero virtual
time, so pre-QP completion times reproduce bit-exactly.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.segops import (
    NEG,
    lex_sort_by_segment,
    queueing_scan,
    segment_rank,
    segmented_prefix_max,
    sort_by_segment,
    stable_argsort,
)
from repro.core.types import QPConfig


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CQRings:
    """Struct-of-arrays NVMe completion queues (one ring per CQ).

    Mirrors ``SQRings``: CQ q is paired with SQ q. ``head`` is the
    consumer (GPU reap) index, ``tail`` the producer (device post)
    index; both are free-running. ``bell_time`` is the per-CQ
    completion-poster busy-until cursor (doorbell serialization).
    """

    done_time: jax.Array  # (Q, D) f32 — device-side completion time
    visible_time: jax.Array  # (Q, D) f32 — doorbell-visible time
    req_id: jax.Array  # (Q, D) i32
    head: jax.Array  # (Q,) i32 free-running consumer index
    tail: jax.Array  # (Q,) i32 free-running producer index
    bell_time: jax.Array  # (Q,) f32 doorbell-poster busy-until

    @property
    def num_cqs(self) -> int:
        return self.done_time.shape[0]

    @property
    def depth(self) -> int:
        return self.done_time.shape[1]

    @staticmethod
    def empty(num_cqs: int, depth: int) -> "CQRings":
        return CQRings(
            done_time=jnp.full((num_cqs, depth), 3e38, jnp.float32),
            visible_time=jnp.full((num_cqs, depth), 3e38, jnp.float32),
            req_id=jnp.zeros((num_cqs, depth), jnp.int32),
            head=jnp.zeros((num_cqs,), jnp.int32),
            tail=jnp.zeros((num_cqs,), jnp.int32),
            bell_time=jnp.zeros((num_cqs,), jnp.float32),
        )


def _scatter_entries(
    cq: CQRings,
    key: jax.Array,  # (N,) i32 CQ per row, num_cqs for invalid rows
    rank: jax.Array,  # (N,) i32 posting order within the row's CQ
    done: jax.Array,
    visible: jax.Array,
    req_id: jax.Array,
    valid: jax.Array,
    counts: jax.Array | None = None,  # (Q,) i32 valid entries per CQ
    fused: bool = False,
) -> CQRings:
    """Write posted entries into the rings and advance the tails.

    ``counts`` lets the caller hand in per-CQ valid counts it already
    knows (the compacted epoch's block counts) instead of paying a
    segment_sum. ``fused`` replaces the three ring scatters with one
    stacked (N, 3) i32 scatter — the f32 time channels ride as raw bits
    via ``bitcast_convert_type`` (scatters move bits, never arithmetic,
    so the round-trip is exact). The stack is i32, not f32: an i32
    below 2^23 read as f32 bits is a subnormal, which the TPU flushes
    to zero.
    """
    q, d = cq.num_cqs, cq.depth
    row = jnp.clip(key, 0, q - 1)
    pos = (cq.tail[row] + rank) % d
    pos = jnp.where(valid, pos, d)  # invalid rows drop out of bounds
    if counts is None:
        counts = jax.ops.segment_sum(
            valid.astype(jnp.int32), key, num_segments=q + 1
        )[:q]
    # The consumer polls continuously: every entry posted this epoch
    # is reaped within it, so the head tracks the tail.
    if fused:
        bits = jax.lax.bitcast_convert_type

        def i32(x):
            return bits(x, jnp.int32)

        page = jnp.stack([i32(done), i32(visible), req_id], axis=-1)
        rings = jnp.stack(
            [i32(cq.done_time), i32(cq.visible_time), cq.req_id], axis=-1
        ).at[row, pos].set(page, mode="drop")
        return dataclasses.replace(
            cq,
            done_time=bits(rings[..., 0], jnp.float32),
            visible_time=bits(rings[..., 1], jnp.float32),
            req_id=rings[..., 2],
            tail=cq.tail + counts,
            head=cq.head + counts,
        )
    return dataclasses.replace(
        cq,
        done_time=cq.done_time.at[row, pos].set(done, mode="drop"),
        visible_time=cq.visible_time.at[row, pos].set(visible, mode="drop"),
        req_id=cq.req_id.at[row, pos].set(req_id, mode="drop"),
        tail=cq.tail + counts,
        head=cq.head + counts,
    )


def post_and_reap(
    cq: CQRings,
    cq_id: jax.Array,  # (N,) i32 target CQ (= source SQ) per completion
    done: jax.Array,  # (N,) f32 device-side completion times
    req_id: jax.Array,  # (N,) i32
    valid: jax.Array,  # (N,) bool
    qp: QPConfig,
    posted_rank: jax.Array | None = None,  # (N,) epoch-plan CQ ranks
    fused_sort: bool = False,
    use_pallas: bool = False,
    posted_counts: jax.Array | None = None,  # (Q,) per-CQ valid counts
    fused_scatter: bool = False,
    use_pallas_reap: bool = False,
) -> Tuple[CQRings, jax.Array]:
    """Post one epoch's completions and reap them. Returns (cq', reaped).

    ``reaped[i]`` is when the GPU consumer observes request i's
    completion: device completion -> coalescing group doorbell ->
    doorbell service on the per-CQ poster -> consumer poll + CQE read.
    Invalid rows return 0 and touch nothing.

    ``posted_rank`` lets ``DevicePipeline.process`` hand in the neutral
    path's per-CQ ranks from its epoch sort plan (fetched batches are
    SQ-major, so the ranks come sort-free); ``fused_sort`` replaces the
    non-neutral path's two-sort layout with the fused lexicographic
    sort; ``posted_counts``/``fused_scatter`` (PR 8) skip the per-CQ
    segment_sum and collapse the three ring scatters into one stacked
    pass. All are bit-exact layout changes, not model changes.
    ``use_pallas_reap`` routes the whole neutral posting path (rank +
    ring scatter + counts) through the ``kernels/ops`` fused one-pass
    kernel — pure integer bookkeeping and data movement, exact for any
    inputs (parity-tested in tests/test_segops.py).
    """
    q = cq.num_cqs
    key = jnp.where(valid, cq_id, q)

    if qp.neutral:
        # Transparent completion path: entries are recorded for ring
        # observability, but nothing is ever delayed (bit-exact parity
        # with the pre-QP pipeline by construction).
        if use_pallas_reap:
            from repro.kernels import ops as kops  # lazy: pulls in pallas

            dt, vt, rid, counts = kops.fused_reap(
                cq.done_time, cq.visible_time, cq.req_id, cq.tail,
                key, done, req_id, valid,
            )
            cq = dataclasses.replace(
                cq, done_time=dt, visible_time=vt, req_id=rid,
                tail=cq.tail + counts, head=cq.head + counts,
            )
            return cq, jnp.where(valid, done, 0.0)
        rank = posted_rank if posted_rank is not None else segment_rank(key)
        cq = _scatter_entries(
            cq, key, rank, done, done, req_id, valid,
            counts=posted_counts, fused=fused_scatter,
        )
        return cq, jnp.where(valid, done, 0.0)

    n_coal = qp.cq_coalesce_n

    # CQEs post in completion-time order within each CQ: sort rows by
    # done time, then stable segment sort by CQ (composition keeps the
    # time order inside each segment).
    if fused_sort:
        order, heads, rank = lex_sort_by_segment(key, done)
    else:
        ord1 = stable_argsort(done)
        ord2, heads, rank = sort_by_segment(key[ord1])
        order = ord1[ord2]
    s_done = done[order]
    s_valid = valid[order]
    s_key = key[order]
    safe = jnp.clip(s_key, 0, q - 1)

    # Coalescing groups: contiguous runs of n_coal entries per CQ.
    gheads = heads | (rank % n_coal == 0)
    n = done.shape[0]
    tails = jnp.concatenate([gheads[1:], jnp.ones((1,), bool)])

    # Doorbell fires when the group fills (time of its last member) or
    # its timer expires (first member + cq_coalesce_us), whichever is
    # earlier; an entry completing after that flush posts at its own
    # completion time (it would have been in the next group).
    first = segmented_prefix_max(jnp.where(gheads, s_done, NEG), gheads)
    rev = slice(None, None, -1)
    full = segmented_prefix_max(
        jnp.where(tails, s_done, NEG)[rev], tails[rev]
    )[rev]
    bell_raw = jnp.minimum(full, first + jnp.float32(qp.cq_coalesce_us))
    ready = jnp.maximum(s_done, bell_raw)

    # Doorbell serialization: one cq_doorbell_us of poster time per
    # group, charged at the group head, serialized per CQ.
    cost = jnp.where(gheads & s_valid, jnp.float32(qp.cq_doorbell_us), 0.0)
    posted = queueing_scan(
        ready, cost, heads, cq.bell_time[safe], use_pallas=use_pallas
    )
    bell_time = jnp.maximum(
        cq.bell_time,
        jax.ops.segment_max(
            jnp.where(s_valid, posted, NEG), safe, num_segments=q
        ),
    )

    # Consumer reap: one poll pass per doorbell batch plus a per-CQE
    # ring read, in posting order within the batch.
    reap_rank = (rank % n_coal).astype(jnp.float32)
    reaped_s = (
        posted
        + jnp.float32(qp.cq_poll_us)
        + (reap_rank + 1.0) * jnp.float32(qp.cqe_reap_us)
    )

    cq = dataclasses.replace(
        _scatter_entries(
            cq, s_key, rank, s_done, posted, req_id[order], s_valid,
            # Per-CQ valid counts are layout-independent, so the
            # dispatch-order epoch counts apply to the sorted layout too.
            counts=posted_counts, fused=fused_scatter,
        ),
        bell_time=bell_time,
    )
    reaped = jnp.zeros_like(done).at[order].set(reaped_s, mode="drop")
    return cq, jnp.where(valid, reaped, 0.0)
