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

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.segops import (
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

def apply_reads(
    flash: jax.Array, bufs: jax.Array, batch: RequestBatch,
    use_pallas: bool = False,
) -> jax.Array:
    """Copy flash[lba] into bufs[buf_id] for valid read requests."""
    is_read = batch.valid & (batch.opcode == 0)
    src = jnp.where(is_read, batch.lba, 0)
    if use_pallas:
        from repro.kernels import ops as kops

        data = kops.block_gather(flash, src)
    else:
        data = flash[src]
    dst = jnp.where(is_read, batch.buf_id, bufs.shape[0])
    return bufs.at[dst].set(data, mode="drop")


def apply_writes(
    flash: jax.Array, bufs: jax.Array, batch: RequestBatch
) -> jax.Array:
    """Copy bufs[buf_id] into flash[lba] for valid write requests."""
    is_write = batch.valid & (batch.opcode == 1)
    src = jnp.where(is_write, batch.buf_id, 0)
    data = bufs[src]
    dst = jnp.where(is_write, batch.lba, flash.shape[0])
    return flash.at[dst].set(data, mode="drop")


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
