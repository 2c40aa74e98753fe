"""Backend data path: functional block copies + worker/DSA cost model.

Functional emulation: the flash address space is an HBM-resident array of
blocks; a read gathers ``flash[lba] -> bufs[buf_id]``, a write scatters the
reverse. With ``EngineConfig.use_pallas`` the gather runs as the
``block_gather`` Pallas kernel (the DSA analogue: a batch of row DMAs per
grid step; compiled, it needs 128-word flash rows); otherwise the jnp
gather is used.

Virtual-time model: the *baseline* backend charges each request the
map/unmap software overhead plus a small sequential CPU copy (paper Fig. 4),
serialized per worker lane. The *DSA* backend charges batched descriptor
issue plus pipelined engine bandwidth, and shares the engine with
dispatcher-side fetching (paper Fig. 9 interference).
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.segops import (
    block_counts,
    counting_sort_plan,
    queueing_scan,
    segment_rank,
    stable_argsort,
)
from repro.core.types import (
    EngineConfig,
    PlatformModel,
    RequestBatch,
    SSDConfig,
)


# ---------------------------------------------------------------------------
# Functional data movement.
# ---------------------------------------------------------------------------

class Window(NamedTuple):
    """The epoch's valid rows as one dense list, moved ``width`` at a time.

    A fetched batch is SQ-major with ``fetch_width`` rows per SQ, and the
    valid rows of each SQ's block are a prefix (``frontend._gather_entries``).
    So the k-th valid row lies in the last SQ whose first valid index is
    at most k, found from the per-SQ counts alone: no sort, no scatter.
    """

    width: int                # W rows per chunk (static)
    fetch_width: int          # F rows per SQ block (static)
    starts: jax.Array         # (Q,) i32 index of each SQ's first valid row
    total: jax.Array          # () i32 valid rows in the epoch
    read_chunks: jax.Array    # () i32 trips of the read loop
    write_chunks: jax.Array   # () i32 trips of the write loop

    def chunk(self, batch: RequestBatch, c: jax.Array) -> RequestBatch:
        """Rows ``[c*W, (c+1)*W)`` of the valid list, in batch order; the
        padding past the last valid row is marked invalid."""
        k = c * self.width + jnp.arange(self.width, dtype=jnp.int32)
        before = self.starts[None, :] <= k[:, None]
        q = jnp.sum(before, axis=1, dtype=jnp.int32) - 1
        live = k < self.total
        rows = jnp.where(live, q * self.fetch_width + k - self.starts[q], 0)
        sub = jax.tree.map(lambda x: x[rows], batch)
        return dataclasses.replace(sub, valid=live)


def window_rows(cfg: EngineConfig, ssd: SSDConfig) -> int | None:
    """Chunk width W of the windowed data path, or None where W would
    cover the whole epoch (the full-width form is then used).

    The drive retires about ``t_max_iops * poll_quantum_us`` requests a
    round; W doubles that and rounds up to a power of two of at least 128.
    """
    n = cfg.num_sqs * cfg.fetch_width
    expected = ssd.t_max_iops * cfg.poll_quantum_us * 1e-6
    w = max(128, 1 << math.ceil(math.log2(max(2.0 * expected, 1.0))))
    return w if w < n else None


def data_window(batch: RequestBatch, fetch_width: int, width: int) -> Window:
    """The ``Window`` over ``batch``: each loop runs ``ceil(valid / W)``
    chunks, and none where the epoch holds no valid row of its kind."""
    counts = block_counts(batch.valid, fetch_width)
    ends = jnp.cumsum(counts)
    total = ends[-1]
    chunks = (total + width - 1) // width
    live = batch.valid.reshape(-1, fetch_width)
    op = batch.opcode.reshape(-1, fetch_width)
    return Window(
        width, fetch_width, ends - counts, total,
        jnp.where(jnp.any(live & (op == 0)), chunks, 0),
        jnp.where(jnp.any(live & (op == 1)), chunks, 0),
    )


def apply_reads(
    flash: jax.Array, bufs: jax.Array, batch: RequestBatch,
    use_pallas: bool = False, window: Window | None = None,
) -> jax.Array:
    """Copy flash[lba] into bufs[buf_id] for valid read requests.

    With a ``window``, only the epoch's valid rows are gathered, in
    ``window.read_chunks`` chunks of ``window.width`` rows."""
    def copy(bufs, b):
        is_read = b.valid & (b.opcode == 0)
        src = jnp.where(is_read, b.lba, 0)
        if use_pallas:
            from repro.kernels import ops as kops

            data = kops.block_gather(flash, src)
        else:
            data = flash[src]
        dst = jnp.where(is_read, b.buf_id, bufs.shape[0])
        return bufs.at[dst].set(data, mode="drop")

    if window is None:
        return copy(bufs, batch)
    return jax.lax.fori_loop(
        0, window.read_chunks,
        lambda c, bufs: copy(bufs, window.chunk(batch, c)), bufs,
    )


def apply_writes(
    flash: jax.Array, bufs: jax.Array, batch: RequestBatch,
    window: Window | None = None,
) -> jax.Array:
    """Copy bufs[buf_id] into flash[lba] for valid write requests.

    With a ``window``, only the epoch's valid rows are scattered, in
    ``window.write_chunks`` chunks of ``window.width`` rows that keep
    the batch's order; the loop carries the image in place."""
    def copy(flash, b):
        is_write = b.valid & (b.opcode == 1)
        src = jnp.where(is_write, b.buf_id, 0)
        data = bufs[src]
        dst = jnp.where(is_write, b.lba, flash.shape[0])
        return flash.at[dst].set(data, mode="drop")

    if window is None:
        return copy(flash, batch)
    return jax.lax.fori_loop(
        0, window.write_chunks,
        lambda c, flash: copy(flash, window.chunk(batch, c)), flash,
    )


# ---------------------------------------------------------------------------
# Virtual-time backend cost model.
# ---------------------------------------------------------------------------

def _bytes(batch: RequestBatch, ssd: SSDConfig) -> jax.Array:
    return (batch.nblocks * ssd.block_bytes).astype(jnp.float32)


def baseline_worker_times(
    work_time: jax.Array,       # (U, W) worker busy-until cursors
    map_time: jax.Array,        # ()  global map/unmap lock busy-until
    fetch_done: jax.Array,      # (N,) per request
    batch: RequestBatch,
    cfg: EngineConfig,
    plat: PlatformModel,
    ssd: SSDConfig,
    unit: jax.Array | None = None,   # (N,) non-decreasing service-unit ids
    unit_rank: jax.Array | None = None,  # (N,) within-unit rank (epoch plan)
    use_counting_sort: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """NVMeVirt backend: per-request map/unmap + CPU copy, W lanes per unit.

    memremap()/memunmap() mutate page tables under *global* kernel locks
    (paper §III-B: 94us per transfer at 32 threads ⇒ the 2.9us map cost is
    serialized across every worker, capping aggregate throughput at
    1/map_us ≈ 0.34 MIOPS). We model it as a single global queueing server
    feeding per-lane copy servers. Returns (work_time', map_time', ready).

    ``unit_rank`` (``DevicePipeline.process``'s epoch sort plan) supplies
    the within-unit ranks precomputed without a sort; omitted, they are
    recovered from ``unit`` via ``segment_rank`` (a full stable sort).
    ``use_counting_sort`` swaps the stable lane sort for the
    bit-identical counting-sort plan (the lane alphabet is u*w, small).
    """
    u, w = work_time.shape
    n = fetch_done.shape[0]
    pallas = cfg.resolve_pallas_segscan(ssd, plat)
    txn, bw = _p2p(cfg, plat)
    idx = jnp.arange(n, dtype=jnp.int32)
    if unit is None:
        unit = idx // (n // u)
        rank_in_unit = idx % (n // u)
    elif unit_rank is not None:
        rank_in_unit = unit_rank
    else:
        rank_in_unit = segment_rank(unit)

    # --- global map/unmap serialization (requests in dispatch order).
    map_cost = jnp.where(batch.valid, jnp.float32(plat.per_req_map_us), 0.0)
    heads0 = jnp.zeros((n,), bool).at[0].set(True, mode="drop")
    seed0 = jnp.broadcast_to(map_time, (n,))
    mapped = queueing_scan(
        fetch_done, map_cost, heads0, seed0, use_pallas=pallas
    )
    new_map = jnp.maximum(jnp.max(mapped), map_time)

    # --- per-lane p2p copy after mapping.
    cost = txn + _bytes(batch, ssd) / bw
    cost = jnp.where(batch.valid, cost, 0.0)
    lane = unit * w + (rank_in_unit % w)            # global lane id
    if use_counting_sort:
        plan = counting_sort_plan(lane, u * w)
        order, heads = plan.order, plan.heads
    else:
        order = stable_argsort(lane)
        heads = jnp.concatenate(
            [jnp.ones((1,), bool), lane[order][1:] != lane[order][:-1]]
        )
    seed = work_time.reshape(-1)[lane[order]]
    busy = queueing_scan(
        mapped[order], cost[order], heads, seed, use_pallas=pallas
    )
    ready = jnp.zeros_like(busy).at[order].set(busy, mode="drop")

    new_work = jax.ops.segment_max(
        busy, lane[order], num_segments=u * w
    )
    new_work = jnp.maximum(new_work, work_time.reshape(-1)).reshape(u, w)
    return new_work, new_map, jnp.where(batch.valid, ready, 0.0)


def dsa_worker_times(
    dsa_time: jax.Array,        # (U,) DSA-engine busy-until cursors
    fetch_done: jax.Array,      # (N,)
    batch: RequestBatch,
    cfg: EngineConfig,
    plat: PlatformModel,
    ssd: SSDConfig,
    dsa_batch_size: int = 16,
    unit: jax.Array | None = None,   # (N,) non-decreasing service-unit ids
) -> Tuple[jax.Array, jax.Array]:
    """SwarmIO backend: batched async DSA offload (paper §IV-C).

    CPU-side issue cost is amortized per batch descriptor; the DSA engine is
    a pipelined single server per unit at ``dsa_bytes_per_us``. No map/unmap
    (DSA operates on PAs). Returns (dsa_time', ready).
    """
    u = dsa_time.shape[0]
    n = fetch_done.shape[0]
    # Issue: one batch descriptor per `dsa_batch_size` requests.
    issue = plat.dsa_desc_issue_us + plat.dsa_batch_setup_us / dsa_batch_size
    ready_in = fetch_done + issue
    # Engine: pipelined copies, service time = bytes/bw (+ tiny per-desc).
    cost = _bytes(batch, ssd) / plat.dsa_bytes_per_us + 0.01
    cost = jnp.where(batch.valid, cost, 0.0)

    if unit is None:
        unit = jnp.arange(n, dtype=jnp.int32) // (n // u)
    heads = jnp.concatenate([jnp.ones((1,), bool), unit[1:] != unit[:-1]])
    seed = dsa_time[unit]
    busy = queueing_scan(ready_in, cost, heads, seed)

    new_dsa = jax.ops.segment_max(busy, unit, num_segments=u)
    new_dsa = jnp.maximum(new_dsa, dsa_time)
    return new_dsa, jnp.where(batch.valid, busy, 0.0)


def _p2p(cfg: EngineConfig, plat: PlatformModel):
    if cfg.transport == "p2p":
        return plat.txn_base_us, plat.link_bytes_per_us
    return plat.host_txn_base_us, plat.host_bytes_per_us
