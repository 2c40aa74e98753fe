"""Frontend: submission-queue rings, doorbells, and request fetching.

The submission half of the queue-pair layer — ``qp.CQRings`` is the
symmetric completion half (SQ q pairs with CQ q). SQ entries live in
contiguous ring buffers (the CQR-bit analogue — paper §IV-B), so a
coalesced fetch of n entries is a single bulk transfer whose
virtual-time cost is ``txn_base + n*sqe_bytes/bw`` instead of n separate
transactions. The *distributed* frontend partitions SQs across service units
and fetches all units' SQs in parallel; the *centralized* baseline models
NVMeVirt's single dispatcher that serializes over every SQ and fetches one
entry per transaction.

Fetching is op-agnostic: each ring entry carries its NVMe ``opcode``
(OP_READ/OP_WRITE) end to end, so the downstream pipeline stages — and in
particular the flash backend, which prices programs and GC — see the
read/write mix exactly as submitted.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.types import (
    EngineConfig,
    PlatformModel,
    RequestBatch,
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SQRings:
    """Struct-of-arrays NVMe submission queues (one ring per SQ)."""

    submit_time: jax.Array  # (Q, D) f32 — virtual time the entry was posted
    opcode: jax.Array       # (Q, D) i32
    lba: jax.Array          # (Q, D) i32
    nblocks: jax.Array      # (Q, D) i32
    buf_id: jax.Array       # (Q, D) i32
    req_id: jax.Array       # (Q, D) i32
    tenant: jax.Array       # (Q, D) i32 — QoS/tenant class of the entry
    head: jax.Array         # (Q,) i32 free-running consumer index
    tail: jax.Array         # (Q,) i32 free-running producer index (doorbell)

    @property
    def num_sqs(self) -> int:
        return self.submit_time.shape[0]

    @property
    def depth(self) -> int:
        return self.submit_time.shape[1]

    @staticmethod
    def empty(num_sqs: int, depth: int) -> "SQRings":
        z = jnp.zeros((num_sqs, depth), jnp.int32)
        return SQRings(
            submit_time=jnp.full((num_sqs, depth), 3e38, jnp.float32),
            opcode=z, lba=z, nblocks=jnp.ones_like(z), buf_id=z, req_id=z,
            tenant=z,
            head=jnp.zeros((num_sqs,), jnp.int32),
            tail=jnp.zeros((num_sqs,), jnp.int32),
        )


def submit(
    rings: SQRings,
    sq_id: jax.Array,       # (M,) i32 target SQ per new entry
    submit_time: jax.Array,  # (M,) f32
    opcode: jax.Array,
    lba: jax.Array,
    nblocks: jax.Array,
    buf_id: jax.Array,
    req_id: jax.Array,
    valid: jax.Array,        # (M,) bool
    tenant: jax.Array | None = None,  # (M,) i32 QoS class (None = 0)
) -> SQRings:
    """Append entries to their SQs (ring the doorbells).

    Entries targeting the same SQ are appended in array order; callers must
    pre-sort per-SQ batches by submit time to model in-order posting.
    """
    # Per-entry offset within its SQ = number of earlier valid entries
    # targeting the same SQ (within-segment rank, O(M log M)).
    from repro.core.segops import segment_rank

    q = rings.num_sqs
    if tenant is None:
        tenant = jnp.zeros_like(sq_id)
    sq_key = jnp.where(valid, sq_id, q)
    offset = segment_rank(sq_key)
    pos = (rings.tail[jnp.clip(sq_key, 0, q - 1)] + offset) % rings.depth
    # Invalid rows scatter out of bounds and are dropped (never collide with
    # valid writes).
    pos = jnp.where(valid, pos, rings.depth)
    row = jnp.clip(sq_key, 0, q - 1)

    def scat(field, val):
        return field.at[row, pos].set(val, mode="drop")

    counts = jax.ops.segment_sum(
        valid.astype(jnp.int32), sq_key, num_segments=q + 1
    )[:q]
    return dataclasses.replace(
        rings,
        submit_time=scat(rings.submit_time, submit_time),
        opcode=scat(rings.opcode, opcode),
        lba=scat(rings.lba, lba),
        nblocks=scat(rings.nblocks, nblocks),
        buf_id=scat(rings.buf_id, buf_id),
        req_id=scat(rings.req_id, req_id),
        tenant=scat(rings.tenant, tenant),
        tail=rings.tail + counts,
    )


def submit_grouped(
    rings: SQRings,
    submit_time: jax.Array,  # (Q, F) — row q targets SQ q
    opcode: jax.Array,
    lba: jax.Array,
    nblocks: jax.Array,
    buf_id: jax.Array,
    req_id: jax.Array,
    valid: jax.Array,        # (Q, F) bool
    tenant: jax.Array | None = None,  # (Q, F) i32 QoS class (None = 0)
    fused: bool = False,
) -> SQRings:
    """Fast-path append: row q's valid entries go to SQ q in array order.

    Used by the closed-loop engine where resubmissions are naturally SQ-major.
    Rows must be pre-sorted by submit time.

    ``fused`` collapses the seven per-field ring scatters into one
    stacked (Q, F, 7) i32 pass: the f32 submit time rides as raw bits
    via ``bitcast_convert_type`` (scatters move bits, never arithmetic,
    so the round-trip is exact and the rings land bit-identical). The
    stack is i32, not f32: an i32 below 2^23 read as f32 bits is a
    subnormal, which the TPU flushes to zero.
    """
    q, f = submit_time.shape
    if tenant is None:
        tenant = jnp.zeros_like(opcode)
    offset = jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1
    pos = (rings.tail[:, None] + offset) % rings.depth
    pos = jnp.where(valid, pos, rings.depth)  # drop invalid
    rows = jnp.broadcast_to(jnp.arange(q, dtype=jnp.int32)[:, None], (q, f))
    tail = rings.tail + jnp.sum(valid, axis=1, dtype=jnp.int32)

    if fused:
        bits = jax.lax.bitcast_convert_type
        fields = (opcode, lba, nblocks, buf_id, req_id, tenant)
        page = jnp.stack(
            [bits(submit_time, jnp.int32), *fields], axis=-1
        )
        stacked = jnp.stack(
            [
                bits(rings.submit_time, jnp.int32), rings.opcode,
                rings.lba, rings.nblocks, rings.buf_id, rings.req_id,
                rings.tenant,
            ],
            axis=-1,
        ).at[rows, pos].set(page, mode="drop")
        return dataclasses.replace(
            rings,
            submit_time=bits(stacked[..., 0], jnp.float32),
            opcode=stacked[..., 1],
            lba=stacked[..., 2],
            nblocks=stacked[..., 3],
            buf_id=stacked[..., 4],
            req_id=stacked[..., 5],
            tenant=stacked[..., 6],
            tail=tail,
        )

    def scat(field, val):
        return field.at[rows, pos].set(val, mode="drop")

    return dataclasses.replace(
        rings,
        submit_time=scat(rings.submit_time, submit_time),
        opcode=scat(rings.opcode, opcode),
        lba=scat(rings.lba, lba),
        nblocks=scat(rings.nblocks, nblocks),
        buf_id=scat(rings.buf_id, buf_id),
        req_id=scat(rings.req_id, req_id),
        tenant=scat(rings.tenant, tenant),
        tail=tail,
    )


def _gather_entries(
    rings: SQRings, nfetch: jax.Array, fetch_width: int
) -> Tuple[RequestBatch, jax.Array]:
    """Gather up to ``nfetch[q]`` entries from each SQ head (SQ-major order).

    Returns a RequestBatch of capacity Q*fetch_width plus the per-row source
    SQ for cost accounting. Arrival times are filled by the caller (they
    depend on the dispatcher schedule).
    """
    q, d = rings.num_sqs, rings.depth
    j = jnp.arange(fetch_width, dtype=jnp.int32)[None, :]        # (1, F)
    pos = (rings.head[:, None] + j) % d                          # (Q, F)
    valid = j < nfetch[:, None]                                  # (Q, F)
    rows = jnp.arange(q, dtype=jnp.int32)[:, None]

    def take(f):
        return f[rows, pos].reshape(-1)

    batch = RequestBatch(
        arrival=take(rings.submit_time),   # provisional: submit time
        sq_id=jnp.broadcast_to(rows, (q, fetch_width)).reshape(-1),
        slot=pos.reshape(-1),
        opcode=take(rings.opcode),
        lba=take(rings.lba),
        nblocks=take(rings.nblocks),
        buf_id=take(rings.buf_id),
        req_id=take(rings.req_id),
        valid=valid.reshape(-1),
        tenant=take(rings.tenant),
    )
    return batch, valid


def fetch_distributed(
    rings: SQRings,
    clock: jax.Array,            # f32 — entries visible iff submit <= clock
    disp_time: jax.Array,        # (U,) f32 dispatcher busy-until cursors
    cfg: EngineConfig,
    plat: PlatformModel,
) -> Tuple[SQRings, jax.Array, RequestBatch, jax.Array]:
    """SwarmIO frontend: all units fetch their SQs in parallel, coalesced.

    Returns (rings', disp_time', batch, fetch_done_per_row). Within a unit,
    SQs are drained round-robin in one pass; the unit's dispatcher cursor
    advances by the summed transaction costs. Fetches are coalesced (one
    transaction per SQ) when cfg.coalesced, else one transaction per entry.
    """
    qs, f = cfg.num_sqs, cfg.fetch_width
    u = cfg.num_units
    per_unit = qs // u

    avail = rings.tail - rings.head
    visible = _visible_count(rings, clock, f)
    nfetch = jnp.minimum(jnp.minimum(avail, visible), f)
    # Self-pacing: a dispatcher still busy with its previous pass skips this
    # round; pending entries accumulate and are coalesced into one larger
    # fetch when it next polls (how the real polling loop batches under
    # load — without this, per-pass setup cost is paid per round and the
    # frontend artificially saturates).
    active_u = disp_time <= clock                                # (U,)
    active = jnp.repeat(active_u, per_unit)                      # (Q,)
    nfetch = jnp.where(active, nfetch, 0)
    cost = fetch_cost(nfetch, cfg, plat)
    cost = jnp.where(active, cost, 0.0)

    # Per-unit sequential pass over its SQs: cumulative cost gives each SQ's
    # fetch-completion time.
    cost_u = cost.reshape(u, per_unit)
    cum = jnp.cumsum(cost_u, axis=1)
    start = jnp.maximum(disp_time, clock)                        # (U,)
    fetch_done_sq = (start[:, None] + cum).reshape(qs)           # (Q,)
    disp_time = start + cum[:, -1]

    batch, valid2d = _gather_entries(rings, nfetch, f)
    fetch_done = jnp.repeat(fetch_done_sq, f)
    rings = dataclasses.replace(rings, head=rings.head + nfetch)
    return rings, disp_time, batch, fetch_done


def fetch_centralized(
    rings: SQRings,
    clock: jax.Array,
    disp_time: jax.Array,        # (1,) f32
    cfg: EngineConfig,
    plat: PlatformModel,
) -> Tuple[SQRings, jax.Array, RequestBatch, jax.Array]:
    """NVMeVirt baseline: ONE dispatcher serializes over all SQs, one entry
    per transaction (no coalescing), draining each SQ before the next."""
    qs, f = cfg.num_sqs, cfg.fetch_width

    avail = rings.tail - rings.head
    visible = _visible_count(rings, clock, f)
    nfetch = jnp.minimum(jnp.minimum(avail, visible), f)
    nfetch = jnp.where(disp_time[0] <= clock, nfetch, 0)  # self-pacing

    per_entry = _per_entry_cost(cfg, plat)
    cost = nfetch.astype(jnp.float32) * per_entry + plat.doorbell_poll_us
    cum = jnp.cumsum(cost)
    start = jnp.maximum(disp_time[0], clock)
    sq_base = start + cum - cost                                  # (Q,)
    disp_time = (start + cum[-1])[None]

    batch, _ = _gather_entries(rings, nfetch, f)
    # Entry j of SQ q completes fetching at base_q + (j+1)*per_entry.
    j = jnp.arange(f, dtype=jnp.float32)[None, :]
    done = sq_base[:, None] + (j + 1.0) * per_entry
    fetch_done = done.reshape(-1)
    rings = dataclasses.replace(rings, head=rings.head + nfetch)
    return rings, disp_time, batch, fetch_done


def fetch(
    rings: SQRings,
    clock: jax.Array,
    disp_time: jax.Array,
    cfg: EngineConfig,
    plat: PlatformModel,
) -> Tuple[SQRings, jax.Array, RequestBatch, jax.Array]:
    """Dispatch to the configured ring frontend — the single fetch entry
    point shared by ``engine_round`` and ``StorageClient`` (divergence
    here would silently break their bit-exact parity contract)."""
    if cfg.frontend == "distributed":
        return fetch_distributed(rings, clock, disp_time, cfg, plat)
    return fetch_centralized(rings, clock, disp_time, cfg, plat)


def fetch_row_units(cfg: EngineConfig) -> jax.Array:
    """(Q*F,) i32 service-unit id per fetch-batch row (SQ-major layout),
    non-decreasing as the pipeline's datapath stage requires."""
    u = cfg.num_units if cfg.frontend == "distributed" else 1
    rows = cfg.num_sqs * cfg.fetch_width
    return jnp.arange(rows, dtype=jnp.int32) // (rows // u)


def _per_entry_cost(cfg: EngineConfig, plat: PlatformModel):
    """Non-coalesced per-SQE fetch cost by transport/engine."""
    if cfg.transport == "host":
        return jnp.float32(
            plat.host_txn_base_us + plat.sqe_bytes / plat.host_bytes_per_us
        )
    if cfg.dsa_fetch:
        return jnp.float32(plat.dsa_sqe_fetch_us)
    return jnp.float32(plat.cpu_sqe_fetch_us)


def fetch_cost(
    nfetch: jax.Array, cfg: EngineConfig, plat: PlatformModel
) -> jax.Array:
    """Virtual-time cost to fetch ``nfetch[q]`` entries from each SQ.

    Coalescing turns per-SQE transactions into one bulk transfer per SQ
    (enabled by CQR-contiguous rings); DSA fetch replaces uncached CPU p2p
    reads with a bulk engine transfer (paper Fig. 13's A and C knobs).
    """
    nf = nfetch.astype(jnp.float32)
    bytes_per_sq = nf * plat.sqe_bytes
    per_entry = nf * _per_entry_cost(cfg, plat)
    if not cfg.coalesced:
        return per_entry + plat.doorbell_poll_us
    if cfg.transport == "host":
        cost = (
            plat.host_txn_base_us + bytes_per_sq / plat.host_bytes_per_us
        )
    elif cfg.dsa_fetch:
        cost = plat.dsa_coal_base_us + bytes_per_sq / plat.dsa_bytes_per_us
    else:
        cost = plat.cpu_coal_base_us + bytes_per_sq * plat.cpu_coal_byte_us
    # An adaptive dispatcher falls back to per-entry fetches when only a
    # few entries are pending (bulk-txn setup would dominate).
    cost = jnp.minimum(cost, per_entry)
    return jnp.where(nfetch > 0, cost, plat.doorbell_poll_us)


def deal_sqs(n: int, cfg: EngineConfig) -> jax.Array:
    """SQ assignment for a flat application batch: request i's SQ, (N,).

    Requests interleave across service units first and then round-robin
    over each unit's SQs, so a small batch spreads over all dispatchers
    instead of serializing behind one unit's SQ-drain pass. Within each
    SQ, assigned requests keep ascending batch order (in-order rings).
    """
    u = cfg.num_units if cfg.frontend == "distributed" else 1
    per_unit = cfg.num_sqs // u
    i = jnp.arange(n, dtype=jnp.int32)
    return (i % u) * per_unit + (i // u) % per_unit


def direct_fetch_times(
    disp_time: jax.Array,        # (U,) f32 dispatcher busy-until cursors
    t_submit: jax.Array,         # (N,) f32 virtual submission times
    valid: jax.Array,            # (N,) bool
    cfg: EngineConfig,
    plat: PlatformModel,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """TEST-ONLY ring-less frontend for directly submitted batches.

    Production consumers submit through the SQ rings; this shortcut
    backs ``DevicePipeline._fetch_direct`` for stage-2-4 unit tests.

    Applications issue a flat batch with no SQ machinery: requests are dealt
    round-robin to the ``U`` service units in contiguous runs, and each
    unit's dispatcher streams them in — one coalesced transaction per
    ``fetch_width`` entries (CQR-contiguous bulk transfer) or one
    transaction per entry when coalescing is off. Cost parameters are the
    same per-entry/coalesced fetch model as the ring frontends.

    Returns (fetch_done (N,), disp_time' (U,), unit (N,)); ``unit`` is
    non-decreasing, as the datapath stage requires.
    """
    n = t_submit.shape[0]
    u = disp_time.shape[0]
    per_unit = -(-n // u)  # ceil
    idx = jnp.arange(n, dtype=jnp.int32)
    unit = idx // per_unit
    rank = idx % per_unit
    if cfg.transport == "host":
        txn = jnp.float32(plat.host_txn_base_us)
        bw = jnp.float32(plat.host_bytes_per_us)
    else:
        txn = jnp.float32(plat.txn_base_us)
        bw = jnp.float32(plat.link_bytes_per_us)
    start = jnp.maximum(t_submit, disp_time[unit])
    if cfg.coalesced:
        # One transaction per fetch_width entries per unit; entries become
        # visible progressively as the bulk transfer streams.
        n_txn = rank // cfg.fetch_width + 1
        fetch_done = (
            start
            + n_txn.astype(jnp.float32) * txn
            + (rank + 1).astype(jnp.float32) * plat.sqe_bytes / bw
        )
    else:
        fetch_done = (
            start + (rank + 1).astype(jnp.float32) * _per_entry_cost(cfg, plat)
        )
    fetch_done = jnp.where(valid, fetch_done, 0.0)
    disp_time = jnp.maximum(
        jax.ops.segment_max(fetch_done, unit, num_segments=u), disp_time
    )
    return fetch_done, disp_time, unit


def _visible_count(rings: SQRings, clock: jax.Array, f: int) -> jax.Array:
    """How many contiguous head entries of each SQ were posted by ``clock``.

    Entries are posted in ring order; an entry is fetchable only when its
    submit_time <= clock, and fetching stops at the first non-visible entry
    (in-order consumption).
    """
    d = rings.depth
    j = jnp.arange(f, dtype=jnp.int32)[None, :]
    pos = (rings.head[:, None] + j) % d
    rows = jnp.arange(rings.num_sqs, dtype=jnp.int32)[:, None]
    t = rings.submit_time[rows, pos]
    in_ring = j < (rings.tail - rings.head)[:, None]
    vis = (t <= clock) & in_ring
    # Count of leading True per row.
    return jnp.sum(jnp.cumprod(vis.astype(jnp.int32), axis=1), axis=1)
