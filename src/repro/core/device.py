"""The unified, layered device pipeline (single source of truth for cost).

Every consumer of the emulated SSD — the closed-loop engine and the
application-facing ``StorageClient`` — prices I/O through the same
stages over one ``DeviceState`` pytree:

    stage 0  page cache          GPU-side set-associative cache filters
                                 hits *before* SQ submission (cache.py;
                                 applied by the consumers, not here)
    stage 1  frontend fetch      how/when posted SQ entries become visible
                                 to a service unit (ring fetch, distributed
                                 or centralized — frontend.py)
    stage 2  timing model        target completion times under the global
                                 lock (aggregated / per-request, global /
                                 local scope — timing.py)
    stage 3  data path           when the emulated transfer lands (batched
                                 DSA offload or baseline worker threads —
                                 datapath.py)
    stage 4  flash backend       channel/chip occupancy for writes, greedy
                                 GC, and cached-mapping-table misses —
                                 surcharges the simple timing model omits
                                 (flash.py; exact no-op for all-hit
                                 read-only traffic)
    stage 5  CQ completion path  every completion is *posted* to the CQ
                                 paired with its SQ and *reaped* by the
                                 GPU consumer — coalescing, doorbell
                                 serialization, poll cost (qp.py; exact
                                 no-op under the neutral QPConfig)

For *remote* drives (``EngineConfig.fabric.remote``) two fabric hops
wrap the target-side stages (fabric.py): fetched SQEs plus write
payloads cross the TX link before stage 2, and completions plus read
payloads cross the RX link back before stage 5 — MTU-batched wire
transactions on per-link serialization cursors, plus half-RTT
propagation each way. With a finite ``switch_bytes_per_us`` the frames
additionally serialize through the shared switch/initiator-NIC port
(fan-out before the TX link, incast after the RX link) at the lane's
fair share of the aggregate roof, and with ``qos_weights`` configured
every shared hop serves tenants in weighted-fair order
(``RequestBatch.tenant``). Local drives (the default) skip all hops,
so the pipeline reproduces the fabric-less code path bit-exactly.

``DevicePipeline.process`` composes stages 2-5 for a fetched
``RequestBatch``: it threads the ``CQRings`` through and returns per-
request (arrival, target, ready, flash_done, done, reaped), where
``reaped`` — not ``done`` — is what consumers observe. Both the engine
and the client run ``frontend.fetch_{distributed,centralized}`` over the
same SQ rings and then call the identical ``process``; the queue-pair
layer is symmetric end to end. A multi-drive array is the same program
``vmap``-ed over a leading device axis (see
``engine.simulate(num_devices=...)`` and ``StorageClient.read_striped``).

Stage 2 consumes the batch as an admission ``Epoch`` (epoch.py): the
post-fabric-TX ready times, tenant ids, validity, unit ids, and the
row-layout promise travel as one struct, and ``EngineConfig.lock_order``
decides how service units acquire the global timing lock over it —
``"program"`` (default, bit-exact with every earlier PR) serializes
units in loop index order; ``"ready_time"`` grants the lock in order of
each unit's batch ready time and dispatches the timing model in the
same acquisition order (whole unit blocks permute; within a unit
program order always holds, and stages 3-5 keep the program row layout
— their resources are per-unit/per-die, so only the lock and the shared
timing state are admission-ordered).

The ring-less direct path (``_fetch_direct``/``_submit_direct``) is a
test-only shortcut for unit tests that probe stages 2-4 in isolation —
no production consumer uses it. The deprecated public aliases
``fetch_direct``/``submit_direct`` were removed in PR 9; go through
``StorageClient.submit`` (or the underscore names in tests).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import checkify

from repro.core import datapath, fabric as fabric_mod, frontend, qp, segops
from repro.core import timing
from repro.core.epoch import Epoch, admission_row_order, unit_ready_order
from repro.core.fabric import FabricState
from repro.core.flash import FlashState, flash_stage
from repro.core.qp import CQRings
from repro.core.types import (
    EngineConfig,
    PlatformModel,
    RequestBatch,
    SSDConfig,
    TimingState,
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DeviceState:
    """All virtual-time emulator-side state for one emulated device."""

    tstate: TimingState    # shared timing model (busy_until + rr cursor)
    disp_time: jax.Array   # (U,) dispatcher busy-until cursors
    work_time: jax.Array   # (U, W) baseline worker lanes busy-until
    dsa_time: jax.Array    # (U,) DSA engine busy-until cursors
    lock_time: jax.Array   # ()  global timing-lock busy-until
    map_time: jax.Array    # ()  global map/unmap-lock busy-until
    flash: FlashState      # stage-4 flash-array state (chips, pages, GC)
    fabric: FabricState    # NIC/link cursors for remote drives (fabric.py)

    @staticmethod
    def init(ssd: SSDConfig, num_units: int, workers_per_unit: int = 1,
             num_tenants: int = 1) -> "DeviceState":
        return DeviceState(
            tstate=TimingState.init(ssd.n_instances),
            disp_time=jnp.zeros((num_units,), jnp.float32),
            work_time=jnp.zeros((num_units, workers_per_unit), jnp.float32),
            dsa_time=jnp.zeros((num_units,), jnp.float32),
            lock_time=jnp.float32(0),
            map_time=jnp.float32(0),
            flash=FlashState.init(ssd),
            fabric=FabricState.init(num_tenants),
        )

    @property
    def num_units(self) -> int:
        return self.disp_time.shape[0]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PipelineResult:
    """Per-request virtual-time outcome of one pipeline pass (all (N,))."""

    arrival: jax.Array     # post-lock dispatch time seen by the timing model
    target: jax.Array      # timing-model completion (device fidelity)
    ready: jax.Array       # data-path completion (copy landed)
    flash_done: jax.Array  # flash-backend completion (programs/GC/misses)
    done: jax.Array        # max(target, ready, flash_done), 0 if invalid
    reaped: jax.Array      # when the consumer observed the completion via
                           # the fabric RX hop + CQ (== done for a local
                           # drive with no CQ threaded or a neutral QP)


def acquire_lock(
    lock_time: jax.Array,
    epoch: Epoch,
    num_units: int,
    cfg: EngineConfig,
    plat: PlatformModel,
) -> Tuple[jax.Array, jax.Array, jax.Array | None]:
    """Serialize service units on the global timing-model lock.

    Returns ``(lock_time', lock_done (U,), unit_order)``. Cost =
    per-request (baseline) or per-batch (aggregated). Local timing scope
    has no shared lock at all: the "grant" is each unit's own batch
    ready time and ``unit_order`` is ``None``.

    ``cfg.lock_order`` picks the acquisition order:

      * ``"program"`` — units acquire in index order once their batch is
        ready (``unit_order=None``; the scan below runs on the unordered
        arrays, so the code path is byte-identical to every pre-PR-9
        release — the bit-exactness contract);
      * ``"ready_time"`` — units acquire in order of their batch ready
        time (ties by unit index, a stable sort): the ``(ready, unit)``
        keys permute the scan inputs, the grants unsort back to unit
        index order, and ``unit_order`` (the (U,) acquisition
        permutation) is returned so the caller can dispatch the timing
        model in the same order. When ready times are monotone in
        program order the permutation is the identity and both orders
        produce bit-identical grants.
    """
    if cfg.timing_scope == "local":
        return lock_time, epoch.unit_ready(num_units), None
    n_valid_u = epoch.unit_counts(num_units)
    batch_ready = epoch.unit_ready(num_units)
    if cfg.mode == "per_request":
        cost = n_valid_u.astype(jnp.float32) * plat.lock_per_req_us
    else:
        cost = jnp.where(n_valid_u > 0, plat.lock_per_batch_us, 0.0)

    # repro-lint: pinned-expr lock-scan
    def step(t, x):
        ready, c = x
        done = jnp.maximum(t, ready) + c
        return done, done

    if cfg.lock_order == "ready_time":
        unit_order = unit_ready_order(batch_ready)
        lock_end, granted = jax.lax.scan(
            step, lock_time, (batch_ready[unit_order], cost[unit_order])
        )
        lock_done = jnp.zeros_like(granted).at[unit_order].set(
            granted, mode="drop"
        )
        return lock_end, lock_done, unit_order
    lock_end, lock_done = jax.lax.scan(step, lock_time, (batch_ready, cost))
    return lock_end, lock_done, None
    # repro-lint: end-pinned-expr


def _sanitize_checks(
    cfg: EngineConfig,
    prev: DeviceState,
    new: DeviceState,
    batch: RequestBatch,
    res: PipelineResult,
    dispatch_order: jax.Array | None,
    cq_counts: jax.Array | None,
) -> None:
    """The ``EngineConfig.sanitize`` checkify assertions (PR 10).

    Pure observation — no data-path op changes — so a sanitized run's
    state stays bit-exact with the default run. These guard the failure
    modes JAX makes *silent*: an OOB ring index clamps/drops instead of
    erroring (corrupting CQ permutations), a broken admission or
    compaction permutation double-prices some rows and drops others,
    and flash/fabric accounting underflow shows up only as impossible
    virtual times rounds later. Callers must functionalize with
    ``checkify.checkify`` before jit (``engine.make_runner(...,
    sanitize=True)`` does); a plain jit trace with sanitize on raises
    at trace time by design — the flag must never be silently inert.
    """
    valid = batch.valid

    def rows_ok(pred: jax.Array) -> jax.Array:
        return jnp.all(jnp.where(valid, pred, True))

    # -- ring scatter/gather indices in bounds ---------------------------
    checkify.check(
        rows_ok((batch.sq_id >= 0) & (batch.sq_id < cfg.num_sqs)),
        "sanitize: valid row carries an SQ id outside [0, num_sqs) — "
        "the CQ scatter would silently drop its completion",
    )
    checkify.check(
        rows_ok((batch.slot >= 0) & (batch.slot < cfg.sq_depth)),
        "sanitize: valid row carries a ring slot outside [0, sq_depth)",
    )

    # -- completion times monotone non-negative --------------------------
    checkify.check(
        rows_ok(res.arrival >= 0.0),
        "sanitize: negative post-lock arrival time on a valid row",
    )
    checkify.check(
        rows_ok(res.target >= res.arrival),
        "sanitize: timing-model completion precedes its arrival",
    )
    checkify.check(
        rows_ok(res.ready >= res.arrival),
        "sanitize: data-path completion precedes its arrival",
    )
    checkify.check(
        rows_ok(res.flash_done >= 0.0),
        "sanitize: negative flash-backend completion time",
    )
    checkify.check(
        rows_ok(res.reaped >= res.done),
        "sanitize: CQ reap time precedes the wire completion it reaps",
    )
    checkify.check(
        jnp.all(new.disp_time >= prev.disp_time)
        & (new.lock_time >= prev.lock_time),
        "sanitize: a dispatcher/lock busy-until cursor moved backwards",
    )

    # -- valid-mask conservation across permutations ---------------------
    n = valid.shape[0]
    nv = jnp.sum(valid.astype(jnp.int32))
    if dispatch_order is not None:
        hits = jnp.zeros((n,), jnp.int32).at[dispatch_order].add(
            1, mode="drop"
        )
        checkify.check(
            jnp.all(hits == 1),
            "sanitize: admission dispatch_order is not a permutation — "
            "some rows would be double-priced and others dropped",
        )
        checkify.check(
            jnp.sum(valid[dispatch_order].astype(jnp.int32)) == nv,
            "sanitize: valid-mask not conserved through the admission "
            "permutation",
        )
    if cfg.use_compaction:
        plan = segops.compact_epoch(valid)
        hits = jnp.zeros((n,), jnp.int32).at[plan.pos].add(1, mode="drop")
        checkify.check(
            jnp.all(hits == 1) & (plan.n_valid == nv),
            "sanitize: epoch compaction does not conserve the valid "
            "mask (pos is not a permutation or n_valid drifted)",
        )
    if cq_counts is not None:
        checkify.check(
            jnp.sum(cq_counts.astype(jnp.int32)) == nv,
            "sanitize: per-CQ valid counts do not sum to the epoch's "
            "valid count",
        )

    # -- flash page accounting and fabric cursors ------------------------
    checkify.check(
        (new.flash.free_pages >= 0.0) & (new.flash.valid_pages >= 0.0),
        "sanitize: flash page accounting went negative (free or live "
        "page underflow — GC cannot keep up or double-counted)",
    )
    checkify.check(
        jnp.all(new.flash.chip_busy >= prev.flash.chip_busy),
        "sanitize: a flash die busy-until cursor moved backwards",
    )
    checkify.check(
        jnp.all(new.fabric.tx_busy >= prev.fabric.tx_busy)
        & jnp.all(new.fabric.rx_busy >= prev.fabric.rx_busy)
        & jnp.all(new.fabric.switch_tx >= prev.fabric.switch_tx)
        & jnp.all(new.fabric.switch_rx >= prev.fabric.switch_rx),
        "sanitize: a fabric serialization cursor moved backwards",
    )


@dataclasses.dataclass(frozen=True)
class DevicePipeline:
    """Static composition of the three stages for one device model."""

    cfg: EngineConfig
    ssd: SSDConfig
    plat: PlatformModel

    @property
    def num_units(self) -> int:
        return self.cfg.num_units if self.cfg.frontend == "distributed" else 1

    def init_state(self) -> DeviceState:
        return DeviceState.init(
            self.ssd, self.num_units, self.cfg.workers_per_unit,
            self.cfg.fabric.num_tenants,
        )

    # -- stage 1 (ring variants live in frontend.py) -------------------------
    def _fetch_direct(
        self,
        state: DeviceState,
        t_submit: jax.Array,   # (N,) f32
        valid: jax.Array,      # (N,) bool
    ) -> Tuple[DeviceState, jax.Array, jax.Array]:
        """TEST-ONLY: fetch a directly submitted flat batch (no SQ rings).

        Production consumers (engine *and* client) submit through the SQ
        rings and fetch via ``frontend.fetch_{distributed,centralized}``;
        this ring-less shortcut exists so unit tests can probe stages
        2-4 without ring machinery. Returns (state', fetch_done (N,),
        unit (N,)).
        """
        fetch_done, disp_time, unit = frontend.direct_fetch_times(
            state.disp_time, t_submit, valid, self.cfg, self.plat
        )
        return (
            dataclasses.replace(state, disp_time=disp_time), fetch_done, unit
        )

    def init_cq(self) -> CQRings:
        """Fresh CQ rings shaped to mirror the configured SQ rings."""
        return CQRings.empty(self.cfg.num_sqs, self.cfg.sq_depth)

    # -- stages 2-5 ----------------------------------------------------------
    def process(
        self,
        state: DeviceState,
        batch: RequestBatch,
        fetch_done: jax.Array,  # (N,) per-row fetch completion times
        unit: jax.Array,        # (N,) i32 non-decreasing service-unit ids
        cq: CQRings | None = None,
        ring_layout: bool = False,
    ) -> Tuple[DeviceState, CQRings | None, PipelineResult]:
        """Timing model under the global lock, then the backend data path,
        then the flash-level backend (writes/GC/mapping misses), then the
        CQ completion path: every completion is posted to the CQ paired
        with its SQ (``batch.sq_id``) and reaped by the consumer —
        ``result.reaped`` is the consumer-observed completion time.

        ``cq=None`` (test-only) skips stage 5: ``reaped`` is the wire-
        returned completion with no CQ machinery on top.

        ``ring_layout=True`` promises the batch came from the SQ-ring
        gather (``frontend._gather_entries``): rows are SQ-major with
        exactly ``cfg.fetch_width`` rows per SQ and ``N // num_units``
        rows per unit, so the compaction path may replace segmented
        reductions with fixed-width block reductions. The engine and
        client set it; the test-only direct path (whose ``sq_id`` is all
        zero) must not."""
        cfg, ssd, plat = self.cfg, self.ssd, self.plat
        fab = cfg.fabric
        u = state.num_units
        valid = batch.valid
        tenant = batch.tenants if fab.num_tenants > 1 else None

        # -- epoch sort plan (wall-clock optimization, bit-exact). The
        # fetched batch is SQ-major, so the service-unit and CQ keys are
        # non-decreasing: their segment layouts need no sort at all, and
        # the time-major fabric/CQ sorts fuse into one lexicographic
        # pass. Virtual time is identical either way (parity-tested).
        # ``use_compaction`` (PR 8) layers the epoch-compacted forms on
        # top: block-wise CQ ranks/counts and unit reductions (ring
        # layout only), the dense round-robin timing matrix, the
        # counting-sorted flash layout, and fused ring scatters — all
        # bit-exact, pinned by full-run parity tests.
        use_plan = cfg.use_sort_plan
        compact = cfg.use_compaction
        blocky = compact and ring_layout
        pallas = cfg.resolve_pallas_segscan(ssd, plat)
        with jax.named_scope("stage.lock"):
            unit_rank = (
                segops.presorted_plan(unit).rank if use_plan else None
            )
            if blocky:
                cq_rank = segops.block_masked_rank(valid, cfg.fetch_width)
                cq_counts = segops.block_counts(valid, cfg.fetch_width)
            else:
                cq_rank = (
                    segops.masked_presorted_rank(batch.sq_id, valid)
                    if use_plan else None
                )
                cq_counts = None

        # -- stage 1.5: fabric TX hop (remote drives only). Fetched SQEs
        # (plus write payloads) cross the wire before the target-side
        # pipeline sees them — through the shared switch port first
        # (fan-out direction), then this drive's own link; local drives
        # skip the stage entirely.
        fab_tx, fab_rx = state.fabric.tx_busy, state.fabric.rx_busy
        sw_tx, sw_rx = state.fabric.switch_tx, state.fabric.switch_rx
        if fab.remote:
            with jax.named_scope("stage.fabric_tx"):
                tx_bytes = fabric_mod.tx_wire_bytes(
                    batch, plat.sqe_bytes, ssd
                )
                if fab.switched:
                    sw_tx, fetch_done = fabric_mod.switch_hop(
                        sw_tx, fetch_done, tx_bytes, valid, fab, tenant,
                        fused_sort=use_plan, use_pallas=pallas,
                    )
                fab_tx, fetch_done = fabric_mod.fabric_hop(
                    fab_tx, fetch_done, tx_bytes,
                    valid, fab, fab.tx_bytes_per_us, tenant,
                    fused_sort=use_plan, use_pallas=pallas,
                )

        # -- stage 2a: global timing-model lock over the admission epoch.
        # The post-TX ``fetch_done`` *defines* the epoch's ready times (a
        # remote unit's batch is not at the device until its last frame
        # lands); the epoch's per-unit reductions are reshapes under the
        # ring layout (fixed-width unit slabs — integer sums and f32
        # maxes, exact under any association) and segmented forms on the
        # direct path. ``cfg.lock_order`` decides acquisition order; see
        # ``acquire_lock``.
        with jax.named_scope("stage.lock"):
            epoch = Epoch.from_batch(
                batch, fetch_done, unit, "ring" if ring_layout else "direct"
            )
            n_valid_u = epoch.unit_counts(u)
            lock_time, lock_done, unit_order = acquire_lock(
                state.lock_time, epoch, u, cfg, plat
            )
            disp_time = jnp.maximum(state.disp_time, lock_done)
            epoch = epoch.admit(lock_done)
            arrival = epoch.arrival

        # -- stage 2b: target completion times. Under the ready-time lock
        # the shared timing state is updated in lock-acquisition order:
        # unit blocks dispatch as their units acquired the lock (within a
        # unit program order holds), via a pure gather/scatter row
        # permutation — the float expression tree inside timing.update is
        # the verbatim reference one either way.
        with jax.named_scope("stage.timing"):
            tbatch = dataclasses.replace(batch, arrival=arrival)
            dispatch_order = (
                admission_row_order(unit_order, epoch, u)
                if unit_order is not None else None
            )
            if cfg.timing_scope == "local":
                tstate, target = timing.local_scope_update(
                    state.tstate, arrival, valid, ssd, u,
                    use_compaction=compact,
                )
            else:
                tstate, target = timing.update(
                    state.tstate, tbatch, ssd, cfg.mode,
                    use_compaction=compact, dispatch_order=dispatch_order,
                )

        # -- stage 3: backend data transfer.
        with jax.named_scope("stage.datapath"):
            if cfg.batched_datapath:
                # DSA engine also carried the fetch transfer (engine
                # sharing / interference, paper Fig. 9b): bump cursors by
                # fetch bytes. count * sqe_bytes == the segment_sum of the
                # constant bit-for-bit: every partial sum of equal
                # integer-valued f32 terms below 2^24 is exact under any
                # association.
                if blocky:
                    fetch_bytes_u = n_valid_u.astype(
                        jnp.float32
                    ) * jnp.float32(plat.sqe_bytes)
                else:
                    fetch_bytes_u = jax.ops.segment_sum(
                        jnp.where(valid, jnp.float32(plat.sqe_bytes), 0.0),
                        unit, num_segments=u,
                    )
                dsa_time0 = (
                    state.dsa_time + fetch_bytes_u / plat.dsa_bytes_per_us
                )
                dsa_time, ready = datapath.dsa_worker_times(
                    dsa_time0, arrival, batch, cfg, plat, ssd, unit=unit
                )
                work_time, map_time = state.work_time, state.map_time
            else:
                work_time, map_time, ready = datapath.baseline_worker_times(
                    state.work_time, state.map_time, arrival, batch, cfg,
                    plat, ssd, unit=unit, unit_rank=unit_rank,
                    use_counting_sort=compact,
                )
                dsa_time = state.dsa_time

        # -- stage 4: flash-level backend (writes, GC, mapping misses).
        with jax.named_scope("stage.flash"):
            if ssd.flash_backend:
                fstate, flash_done = flash_stage(
                    state.flash, batch, arrival, target, ssd,
                    use_pallas=pallas, use_counting_sort=compact,
                    use_pallas_flash=cfg.use_pallas_flash,
                )
            else:
                fstate = state.flash
                flash_done = jnp.where(valid, arrival, 0.0)

            done = jnp.where(
                valid, jnp.maximum(jnp.maximum(target, ready), flash_done),
                0.0,
            )

        # -- stage 4.5: fabric RX hop. Completions (plus read payloads)
        # cross back to the initiator — over this drive's link first,
        # then the shared switch port all M return streams converge on
        # (incast) — before they reach its CQ.
        if fab.remote:
            with jax.named_scope("stage.fabric_rx"):
                rx_bytes = fabric_mod.rx_wire_bytes(batch, fab, ssd)
                fab_rx, wire_done = fabric_mod.fabric_hop(
                    fab_rx, done, rx_bytes,
                    valid, fab, fab.rx_bytes_per_us, tenant,
                    fused_sort=use_plan, use_pallas=pallas,
                )
                if fab.switched:
                    sw_rx, wire_done = fabric_mod.switch_hop(
                        sw_rx, wire_done, rx_bytes, valid, fab, tenant,
                        fused_sort=use_plan, use_pallas=pallas,
                    )
                wire_done = jnp.where(valid, wire_done, 0.0)
        else:
            wire_done = done

        new_state = DeviceState(
            tstate=tstate, disp_time=disp_time, work_time=work_time,
            dsa_time=dsa_time, lock_time=lock_time, map_time=map_time,
            flash=fstate,
            fabric=FabricState(
                tx_busy=fab_tx, rx_busy=fab_rx,
                switch_tx=sw_tx, switch_rx=sw_rx,
            ),
        )

        # -- stage 5: post to the CQ and reap (queue-pair layer).
        if cq is None:
            reaped = wire_done
        else:
            with jax.named_scope("stage.cq"):
                cq, reaped = qp.post_and_reap(
                    cq, batch.sq_id, wire_done, batch.req_id, valid, cfg.qp,
                    posted_rank=cq_rank, fused_sort=use_plan,
                    use_pallas=pallas, posted_counts=cq_counts,
                    fused_scatter=compact,
                    use_pallas_reap=cfg.use_pallas_reap,
                )
        res = PipelineResult(
            arrival=arrival, target=target, ready=ready,
            flash_done=flash_done, done=done, reaped=reaped,
        )
        if cfg.sanitize:
            with jax.named_scope("stage.sanitize"):
                _sanitize_checks(
                    cfg, state, new_state, batch, res,
                    dispatch_order, cq_counts,
                )
        return new_state, cq, res

    def _submit_direct(
        self,
        state: DeviceState,
        batch: RequestBatch,
    ) -> Tuple[DeviceState, PipelineResult]:
        """TEST-ONLY: _fetch_direct + process with no rings on either side.

        Op-agnostic — the batch's ``opcode`` decides read vs write pricing
        (stage 2/3 cost both identically; stage 4 charges programs, GC,
        and mapping misses where they apply). Production consumers go
        through the SQ/CQ rings instead (see ``StorageClient.submit``).
        """
        state, fetch_done, unit = self._fetch_direct(
            state, batch.arrival, batch.valid
        )
        state, _, res = self.process(state, batch, fetch_done, unit)
        return state, res


def init_array_state(init_fn, num_devices: int):
    """Stacked per-device state with a leading (M,) axis for vmap.

    ``init_fn(salt)`` builds one device's state pytree from its i32
    device index (salt-aware initializers — e.g. the engine's workload
    prefill — produce distinct per-drive streams; salt-oblivious ones —
    e.g. ``DevicePipeline.init_state`` — broadcast identically). This is
    the single device-layer stacking helper; ``engine.init_array_state``
    and ``StorageClient.init_array_state`` are thin adapters over it.
    """
    return jax.vmap(init_fn)(jnp.arange(num_devices, dtype=jnp.int32))


def make_direct_batch(
    lba: jax.Array,
    t_submit: jax.Array,
    valid: jax.Array | None = None,
    opcode: jax.Array | None = None,
    nblocks: jax.Array | None = None,
    tenant: jax.Array | None = None,
) -> RequestBatch:
    """RequestBatch for ring-less direct submission (test-only path)."""
    n = lba.shape[0]
    z = jnp.zeros((n,), jnp.int32)
    if valid is None:
        valid = jnp.ones((n,), bool)
    t_submit = jnp.broadcast_to(jnp.asarray(t_submit, jnp.float32), (n,))
    return RequestBatch(
        arrival=t_submit,
        sq_id=z, slot=z,
        opcode=z if opcode is None else opcode,
        lba=lba.astype(jnp.int32),
        nblocks=jnp.ones((n,), jnp.int32) if nblocks is None else nblocks,
        buf_id=z,
        req_id=jnp.arange(n, dtype=jnp.int32),
        valid=valid,
        tenant=z if tenant is None else tenant,
    )
