"""Closed-loop SwarmIO-JAX emulation engine.

One engine "round" mirrors a service-unit iteration in the paper (Fig. 6):

  1. dispatchers fetch newly visible SQ entries     (frontend.py)
  2. the timing model derives target completions    (timing.py) — guarded by
     the global lock, entered per-request (baseline) or per-batch (SwarmIO)
  3. the backend emulates the storage data transfer (datapath.py) — CPU
     worker threads with map/unmap (baseline) or batched async DSA offload
  4. the flash backend prices flash-level events    (flash.py) — write
     programs serializing per chip, greedy GC stealing die time, and
     cached-mapping-table misses (epoch-batched per round)
  5. completions are *posted* to the CQ paired with each request's SQ and
     *reaped* by the GPU consumer (qp.py) — coalesced doorbells, per-CQ
     doorbell serialization, and poll cost; the workload generator decides
     what each reaped slot submits next (closed-loop resubmit, open-loop
     arrival, or nothing for replays), and an optional stage-0 GPU page
     cache (cache.py) filters proposed reads that hit before they ever
     post an SQE

Stages 2-5 are the shared ``DevicePipeline`` (device.py) — the identical
code path ``StorageClient`` prices application I/O with. Two time domains
are tracked: *virtual time* (the emulated device's event time — fidelity
metrics: IOPS, latency vs. the modeled SSD) and the engine's own
*wall-clock throughput* (measured by benchmarks around ``run``).

A multi-drive array is the same jit program ``vmap``-ed over a leading
device axis: ``simulate(..., num_devices=M)`` emulates M independent drives
(per-device salted workload streams; fixed traces are striped row
``i % M -> drive i``) in one XLA computation —
``make_sharded_array_runner`` spreads the same stacked state over a real
JAX device mesh via ``shard_map``. With ``EngineConfig.fabric.remote``
each drive additionally sits behind its own NIC/link (fabric.py): SQEs
cross the wire before the target-side stages and completions cross back
before the CQ, so the array emulates a *disaggregated remote* all-flash
array.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.experimental import checkify

from repro.core import cache as cache_mod
from repro.core import datapath, frontend, segops
from repro.core.cache import CacheState
from repro.core.device import DevicePipeline, DeviceState
from repro.core.device import init_array_state as _stack_states
from repro.core.frontend import SQRings
from repro.core.qp import CQRings
from repro.core.types import (
    OP_READ,
    EngineConfig,
    PlatformModel,
    SSDConfig,
    WorkloadConfig,
)
from repro.workloads import Workload, as_workload

FAR = 3e38  # python float: jnp module constants leak into jaxprs

# Fixed log-spaced latency histogram: HIST_BUCKETS buckets spanning
# [HIST_LO_US, HIST_LO_US * 10**HIST_DECADES) microseconds; under- and
# overflow clamp to the edge buckets.
HIST_BUCKETS = 64
HIST_LO_US = 1.0
HIST_DECADES = 5.0


def latency_bucket(lat_us: jax.Array) -> jax.Array:
    """Histogram bucket index for an E2E latency (elementwise)."""
    lg = jnp.log10(jnp.maximum(lat_us, 1e-6)) - jnp.log10(
        jnp.float32(HIST_LO_US)
    )
    idx = jnp.clip(lg * (HIST_BUCKETS / HIST_DECADES), 0, HIST_BUCKETS - 1)
    return idx.astype(jnp.int32)


def hist_percentile(hist: jax.Array, q: float) -> jax.Array:
    """Approximate latency percentile from (possibly device-stacked) hist.

    Leading axes (e.g. a vmap device axis) are summed away, so array runs
    report the aggregate distribution. Returns the geometric midpoint of the
    first bucket where the CDF reaches ``q``.
    """
    h = hist.reshape(-1, HIST_BUCKETS).sum(axis=0)
    c = jnp.cumsum(h)
    idx = jnp.argmax(c >= q * c[-1])
    return jnp.float32(HIST_LO_US) * 10 ** (
        (idx.astype(jnp.float32) + 0.5) * HIST_DECADES / HIST_BUCKETS
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Metrics:
    completed: jax.Array      # f32 count (device completions + cache hits)
    fetched: jax.Array        # f32 count
    sum_e2e: jax.Array        # f32 us   (reap - submit, consumer-observed)
    sum_target: jax.Array     # f32 us   (timing-model latency)
    sum_proc: jax.Array       # f32 us   (copy-ready - dispatch)
    last_completion: jax.Array  # f32 us  max completion time seen
    first_submit: jax.Array   # f32 us   min submit time seen
    lat_hist: jax.Array       # (HIST_BUCKETS,) f32 E2E latency histogram
    cache_hits: jax.Array     # f32 count of stage-0 page-cache hits
    # Per-tenant (QoS class) device completions and E2E sums, shape (T,)
    # with T = max(fabric arbiter classes, workload classes) at init —
    # a single bucket by default. Stage-0 cache hits never reach the
    # device and are excluded.
    tenant_completed: jax.Array  # (T,) f32
    tenant_sum_e2e: jax.Array    # (T,) f32 us
    # Per-tenant E2E latency histograms, same log-spaced buckets as
    # lat_hist — the tail-latency view the ready-time lock study (fig29)
    # reads its per-class p99 and SLO-attainment numbers from.
    tenant_lat_hist: jax.Array   # (T, HIST_BUCKETS) f32
    # Rows the functional data path gathered or scattered, reads plus
    # writes: 2 x the epoch per round in the full-width form, chunks x W
    # in the windowed one (``datapath.Window``).
    data_rows: jax.Array         # f32 count

    @staticmethod
    def zero(num_tenants: int = 1) -> "Metrics":
        z = jnp.float32(0)
        # first_submit must be a *strong* f32: a python-float FAR would
        # make the fresh state weakly typed where a runner's output state
        # is strong — an aval mismatch that silently recompiled the jit
        # runner on the first benchmark rep (the rep-0 "compile" outlier
        # was mostly this second trace, not the warmup's).
        return Metrics(
            z, z, z, z, z, jnp.float32(0), jnp.float32(FAR),
            jnp.zeros((HIST_BUCKETS,), jnp.float32), z,
            jnp.zeros((num_tenants,), jnp.float32),
            jnp.zeros((num_tenants,), jnp.float32),
            jnp.zeros((num_tenants, HIST_BUCKETS), jnp.float32), z,
        )

    def iops(self) -> jax.Array:
        """Virtual-time sustained IOPS (requests per emulated second)."""
        span = jnp.maximum(self.last_completion - self.first_submit, 1e-6)
        return self.completed / span * 1e6

    def avg_e2e_us(self) -> jax.Array:
        return self.sum_e2e / jnp.maximum(self.completed, 1.0)

    def avg_target_us(self) -> jax.Array:
        return self.sum_target / jnp.maximum(self.completed, 1.0)

    def avg_proc_us(self) -> jax.Array:
        return self.sum_proc / jnp.maximum(self.completed, 1.0)

    def hit_rate(self) -> jax.Array:
        """Fraction of completed requests served by the stage-0 cache."""
        return self.cache_hits / jnp.maximum(self.completed, 1.0)

    def tenant_share(self) -> jax.Array:
        """(T,) fraction of device completions per tenant (sums to 1
        whenever anything completed). Leading device axes of an array
        run are summed away, so the shares are array-aggregate."""
        c = self.tenant_completed.reshape(
            -1, self.tenant_completed.shape[-1]
        ).sum(axis=0)
        return c / jnp.maximum(jnp.sum(c), 1.0)

    def tenant_avg_e2e_us(self) -> jax.Array:
        """(T,) mean consumer-observed latency per tenant."""
        c = self.tenant_completed.reshape(
            -1, self.tenant_completed.shape[-1]
        ).sum(axis=0)
        s = self.tenant_sum_e2e.reshape(
            -1, self.tenant_sum_e2e.shape[-1]
        ).sum(axis=0)
        return s / jnp.maximum(c, 1.0)

    def _pooled_tenant_hist(self) -> jax.Array:
        """(T, HIST_BUCKETS) with any leading device axes summed away."""
        t = self.tenant_completed.shape[-1]
        return self.tenant_lat_hist.reshape(-1, t, HIST_BUCKETS).sum(axis=0)

    def tenant_p99_us(self) -> jax.Array:
        """(T,) per-tenant p99 E2E latency (device completions; stage-0
        cache hits never reach the device and are excluded, matching
        ``tenant_completed``)."""
        return jax.vmap(lambda h: hist_percentile(h, 0.99))(
            self._pooled_tenant_hist()
        )

    def tenant_p50_us(self) -> jax.Array:
        """(T,) per-tenant median E2E latency (device completions)."""
        return jax.vmap(lambda h: hist_percentile(h, 0.50))(
            self._pooled_tenant_hist()
        )

    def slo_attainment(self, slo_us: float) -> jax.Array:
        """(T,) fraction of each tenant's device completions whose E2E
        latency landed at or below ``slo_us`` (histogram-resolution: a
        request counts as attained when its bucket's *lower* edge is
        under the SLO, so the estimate errs optimistic by at most one
        log-bucket). Tenants with no completions report 1.0 — an empty
        class has missed nothing."""
        h = self._pooled_tenant_hist()
        n = jnp.arange(HIST_BUCKETS, dtype=jnp.int32)
        ok = (n <= latency_bucket(jnp.float32(slo_us))).astype(jnp.float32)
        met = jnp.sum(h * ok[None, :], axis=1)
        tot = jnp.sum(h, axis=1)
        return jnp.where(tot > 0, met / jnp.maximum(tot, 1.0), 1.0)

    def p50_us(self) -> jax.Array:
        return hist_percentile(self.lat_hist, 0.50)

    def p95_us(self) -> jax.Array:
        return hist_percentile(self.lat_hist, 0.95)

    def p99_us(self) -> jax.Array:
        return hist_percentile(self.lat_hist, 0.99)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EngineState:
    rings: SQRings         # submission half of the queue pairs
    cq: CQRings            # completion half (SQ q pairs with CQ q)
    device: DeviceState    # the unified pipeline's virtual-time state
    cache: "CacheState | None"  # stage-0 GPU page cache (None = disabled)
    clock: jax.Array       # ()  virtual now
    flash: jax.Array       # (num_blocks, block_words) emulated flash
    bufs: jax.Array        # (num_bufs, block_words) I/O buffers
    req_counter: jax.Array  # i32 next request id
    salt: jax.Array        # i32 per-device workload salt (array emulation)
    last_submit: jax.Array  # (Q,) f32 newest submit time posted per SQ —
                            # the anchor open-loop arrival chains extend
    metrics: Metrics


# ---------------------------------------------------------------------------
# Workload initialization.
# ---------------------------------------------------------------------------

def init_state(
    cfg: EngineConfig,
    ssd: SSDConfig,
    wl: "Workload | WorkloadConfig",
    block_words: int = 16,
    salt: "jax.Array | int" = 0,
) -> EngineState:
    """Build rings pre-filled from the workload generator at t~0.

    ``salt`` differentiates the request streams of the devices in a vmapped
    multi-SSD array (pass the device index).
    """
    wl = as_workload(wl)
    if getattr(wl, "precondition_drive", False):
        # Steady-state generators start the flash array fully written.
        ssd = ssd.replace(preconditioned=True)
    q, dep = cfg.num_sqs, cfg.sq_depth
    rings = SQRings.empty(q, dep)

    pre = wl.prefill(cfg, ssd, salt)
    n_pre = pre.req_id.shape[0] * pre.req_id.shape[1]
    buf_id = (pre.req_id % cfg.num_bufs).astype(jnp.int32)
    rings = frontend.submit_grouped(
        rings, pre.submit, pre.opcode, pre.lba, pre.nblocks, buf_id,
        pre.req_id, pre.valid, tenant=pre.tenant,
        fused=cfg.use_compaction,
    )

    nb = ssd.num_blocks if cfg.emulate_data else 1
    nbuf = cfg.num_bufs if cfg.emulate_data else 1
    flash = (
        jnp.arange(nb, dtype=jnp.float32)[:, None]
        + jnp.arange(block_words, dtype=jnp.float32)[None, :] / block_words
    )
    bufs = jnp.zeros((nbuf, block_words), jnp.float32)
    pipe = DevicePipeline(cfg, ssd, PlatformModel())
    last_submit = jnp.max(
        jnp.where(pre.valid, pre.submit, 0.0), axis=1
    )
    return EngineState(
        rings=rings,
        cq=pipe.init_cq(),
        device=pipe.init_state(),
        cache=(
            CacheState.init(cfg.cache) if cfg.cache.enabled else None
        ),
        clock=jnp.float32(0),
        flash=flash,
        bufs=bufs,
        req_counter=jnp.int32(n_pre),
        salt=jnp.asarray(salt, jnp.int32),
        last_submit=last_submit,
        # Tenant metric buckets: enough for whichever layer defines more
        # classes — the fabric arbiter (qos_weights) or the workload
        # generator — so an unweighted (FIFO) baseline still reports
        # per-tenant shares/latency for a multi-tenant request stream.
        metrics=Metrics.zero(
            max(cfg.fabric.num_tenants, getattr(wl, "num_tenants", 1))
        ),
    )


# ---------------------------------------------------------------------------
# The engine round.
# ---------------------------------------------------------------------------

def engine_round(
    state: EngineState,
    cfg: EngineConfig,
    ssd: SSDConfig,
    wl: "Workload | WorkloadConfig",
    plat: PlatformModel,
    data_window: int | None = None,
) -> EngineState:
    """One round. ``data_window`` (static) is the chunk width W of the
    windowed data path (``datapath.window_rows``); None moves the whole
    epoch's rows, the form a vmapped runner needs (a per-drive trip count
    would turn the loop into a select over the whole image)."""
    wl = as_workload(wl)
    pipe = DevicePipeline(cfg, ssd, plat)
    q, f = cfg.num_sqs, cfg.fetch_width

    # Every stage runs under a ``stage.<name>`` named scope: XLA keeps the
    # scope in each instruction's ``op_name``, so a device trace of the
    # fused round can be read by stage. The scopes change no computation.

    # -- 1. frontend fetch ---------------------------------------------------
    with jax.named_scope("stage.fetch"):
        rings, disp_time, batch, fetch_done = frontend.fetch(
            state.rings, state.clock, state.device.disp_time, cfg, plat
        )
        unit = frontend.fetch_row_units(cfg)
    submit_t = batch.arrival                       # provisional = submit time
    n = batch.valid.shape[0]

    # -- 2-5. the unified device pipeline (timing + data path + QP) ----------
    dev = dataclasses.replace(state.device, disp_time=disp_time)
    # Fetched batches are SQ-major with fetch_width rows per SQ — the
    # ring-layout promise that lets compaction use block reductions.
    # process() wraps the batch in one admission epoch: the service
    # units of this round contend for the stage-2a lock in unit-loop
    # order, or by post-TX batch arrival under lock_order="ready_time".
    dev, cqr, res = pipe.process(
        dev, batch, fetch_done, unit, state.cq, ring_layout=True
    )

    # -- completion metrics: the consumer observes ``reaped`` (post-CQ) ------
    valid = batch.valid
    done = res.reaped
    with jax.named_scope("stage.account"):
        e2e = jnp.where(valid, done - submit_t, 0.0)
        tgt_lat = jnp.where(valid, res.target - res.arrival, 0.0)
        proc = jnp.where(valid, res.ready - res.arrival, 0.0)
        nvalid = jnp.sum(valid.astype(jnp.float32))
        lat_hist = jax.ops.segment_sum(
            valid.astype(jnp.float32), latency_bucket(e2e),
            num_segments=HIST_BUCKETS,
        )
        # Per-tenant (QoS class) completion accounting: T is static (the
        # metrics' bucket count, fixed at init).
        n_ten = state.metrics.tenant_completed.shape[0]
        t_bucket = jnp.clip(batch.tenants, 0, n_ten - 1)
        tenant_completed = jax.ops.segment_sum(
            valid.astype(jnp.float32), t_bucket, num_segments=n_ten
        )
        tenant_sum_e2e = jax.ops.segment_sum(
            e2e, t_bucket, num_segments=n_ten
        )
        tenant_lat_hist = jnp.zeros((n_ten, HIST_BUCKETS), jnp.float32).at[
            t_bucket, latency_bucket(e2e)
        ].add(valid.astype(jnp.float32), mode="drop")

    # -- functional data movement --------------------------------------------
    flash, bufs = state.flash, state.bufs
    data_rows = jnp.float32(0)
    if cfg.emulate_data:
        with jax.named_scope("stage.data_read"):
            # The window is passed only where there is one, so the
            # full-width form calls apply_* exactly as before.
            kw, data_rows = {}, jnp.float32(2 * n)
            if data_window is not None:
                win = datapath.data_window(batch, f, data_window)
                kw = {"window": win}
                data_rows = (
                    (win.read_chunks + win.write_chunks) * data_window
                ).astype(jnp.float32)
            bufs = datapath.apply_reads(
                flash, bufs, batch, cfg.use_pallas, **kw
            )
        with jax.named_scope("stage.data_write"):
            flash = datapath.apply_writes(flash, bufs, batch, **kw)

    # -- workload-driven resubmission (stage-0 cache filters first) ----------
    # Rows are SQ-major (q, f); a row's tenant is its SQ's static class.
    with jax.named_scope("stage.resubmit"):
        tenant_rows = jnp.repeat(
            wl.tenant_of_sq(jnp.arange(q, dtype=jnp.int32), cfg, state.salt),
            f,
        )
        new_req = state.req_counter + jnp.arange(n, dtype=jnp.int32)
        new_lba = wl.address(new_req, ssd, state.salt)
        new_op = wl.opcode(new_req, state.salt, tenant=tenant_rows)
        anchor = jnp.repeat(state.last_submit, f)
        resub_t, resub_valid = wl.next_submit(
            new_req, done, valid, anchor, cfg, ssd, state.salt
        )

    cstate = state.cache
    ccfg = cfg.cache
    hits_count = jnp.float32(0)
    hit_e2e = jnp.float32(0)
    hit_last = jnp.float32(0)
    hit_first = jnp.float32(FAR)
    hit_bucket = jnp.zeros((HIST_BUCKETS,), jnp.float32)
    ids_per_round = n
    if ccfg.enabled:
        with jax.named_scope("stage.cache"):
            # Fills: this round's completed device reads are now GPU-resident.
            cstate = cache_mod.insert(
                cstate, batch.lba, valid & (batch.opcode == OP_READ), ccfg
            )
            # Hit chase: a proposed read that hits completes at GPU-local
            # latency without ever posting an SQE, and the slot immediately
            # proposes its next request — up to ``chase`` hits per slot per
            # round; the survivor (first miss or chase-truncated request)
            # is what actually enters the rings.
            for k in range(ccfg.chase):
                hit, done_h = cache_mod.serve(
                    cstate, new_lba,
                    resub_valid & (new_op == OP_READ), resub_t, ccfg,
                )
                nh = jnp.sum(hit.astype(jnp.float32))
                hits_count = hits_count + nh
                hit_e2e = hit_e2e + nh * jnp.float32(ccfg.hit_us)
                hit_last = jnp.maximum(
                    hit_last, jnp.max(jnp.where(hit, done_h, 0.0))
                )
                hit_first = jnp.minimum(
                    hit_first, jnp.min(jnp.where(hit, resub_t, FAR))
                )
                hit_bucket = hit_bucket.at[
                    latency_bucket(jnp.float32(ccfg.hit_us))
                ].add(nh, mode="drop")
                ids = (
                    state.req_counter
                    + n * (k + 1)
                    + jnp.arange(n, dtype=jnp.int32)
                )
                s_lba = wl.address(ids, ssd, state.salt)
                s_op = wl.opcode(ids, state.salt, tenant=tenant_rows)
                s_t, s_valid = wl.next_submit(
                    ids, done_h, hit, anchor, cfg, ssd, state.salt
                )
                new_lba = jnp.where(hit, s_lba, new_lba)
                new_op = jnp.where(hit, s_op, new_op)
                new_req = jnp.where(hit, ids, new_req)
                resub_t = jnp.where(hit, s_t, resub_t)
                resub_valid = jnp.where(hit, s_valid, resub_valid)
            ids_per_round = n * (ccfg.chase + 1)

    with jax.named_scope("stage.account"):
        m = state.metrics
        metrics = Metrics(
            completed=m.completed + nvalid + hits_count,
            fetched=m.fetched + nvalid,
            sum_e2e=m.sum_e2e + jnp.sum(e2e) + hit_e2e,
            sum_target=m.sum_target + jnp.sum(tgt_lat),
            sum_proc=m.sum_proc + jnp.sum(proc),
            last_completion=jnp.maximum(
                jnp.maximum(
                    m.last_completion, jnp.max(jnp.where(valid, done, 0.0))
                ),
                hit_last,
            ),
            first_submit=jnp.minimum(
                jnp.minimum(
                    m.first_submit, jnp.min(jnp.where(valid, submit_t, FAR))
                ),
                hit_first,
            ),
            lat_hist=m.lat_hist + lat_hist + hit_bucket,
            cache_hits=m.cache_hits + hits_count,
            tenant_completed=m.tenant_completed + tenant_completed,
            tenant_sum_e2e=m.tenant_sum_e2e + tenant_sum_e2e,
            tenant_lat_hist=m.tenant_lat_hist + tenant_lat_hist,
            data_rows=m.data_rows + data_rows,
        )

    with jax.named_scope("stage.resubmit"):
        resub_t = jnp.where(resub_valid, resub_t, FAR)
        last_submit = jnp.maximum(
            state.last_submit,
            jnp.max(
                jnp.where(resub_valid, resub_t, 0.0).reshape(q, f), axis=1
            ),
        )
        # Rows are SQ-major (q, f); sort each SQ's resubmissions by time.
        rt = resub_t.reshape(q, f)
        order = segops.stable_argsort(rt, axis=1)
        rows = jnp.arange(q, dtype=jnp.int32)[:, None]

        def pick(x):
            return x.reshape(q, f)[rows, order]

        rings = frontend.submit_grouped(
            rings,
            rt[rows, order],
            pick(new_op),
            pick(new_lba),
            pick(jnp.ones((n,), jnp.int32)),
            pick(batch.buf_id),
            pick(new_req),
            pick(resub_valid),
            tenant=pick(tenant_rows),
            fused=cfg.use_compaction,
        )

        # -- clock advance ----------------------------------------------------
        # Discrete-event step with a poll quantum: each round ingests the
        # submissions of a bounded virtual-time window (dispatchers poll
        # continuously in the real emulator; the quantum is our emulation
        # granularity — it bounds arrival-time rounding at <= quantum, far
        # below the >=50us device latencies modeled). Idle gaps are skipped
        # by jumping to the earliest pending submission.
        dpos = rings.head % rings.depth
        head_t = rings.submit_time[jnp.arange(q), dpos]
        head_t = jnp.where(rings.tail > rings.head, head_t, FAR)
        nxt = jnp.min(head_t)
        stepped = state.clock + jnp.float32(cfg.poll_quantum_us)
        clock = jnp.where(nxt < FAR, jnp.maximum(stepped, nxt), stepped)

    return EngineState(
        rings=rings, cq=cqr, device=dev, cache=cstate, clock=clock,
        flash=flash, bufs=bufs,
        req_counter=state.req_counter + jnp.int32(ids_per_round),
        salt=state.salt, last_submit=last_submit, metrics=metrics,
    )


def run(
    state: EngineState,
    cfg: EngineConfig,
    ssd: SSDConfig,
    wl: "Workload | WorkloadConfig",
    plat: PlatformModel,
    rounds: int,
    data_window: int | None = None,
) -> EngineState:
    """Run ``rounds`` engine rounds under jit (lax.scan over rounds);
    ``data_window`` as in ``engine_round``."""
    wl = as_workload(wl)

    def body(s, _):
        return engine_round(s, cfg, ssd, wl, plat, data_window), None

    out, _ = jax.lax.scan(body, state, None, length=rounds)
    return out


def unalias(state):
    """Deep-copy a pytree's leaves so no two share a device buffer.

    Freshly initialized states alias constants (JAX caches identical
    zero arrays), and XLA rejects donating the same buffer twice —
    run a donated runner's input through this once before the first
    call. Outputs of a jit call never alias, so reps can chain freely.
    """
    return jax.tree.map(lambda x: jnp.array(x, copy=True), state)


def _jit_runner(run_fn, donate: bool, sanitized: bool):
    """jit (and, when sanitized, checkify-functionalize) a runner body.

    ``checkify.check`` calls cannot trace under plain jit — they must be
    functionalized first, so the sanitized path wraps ``run_fn`` with
    ``checkify.checkify`` *inside* the jit boundary and the returned
    runner ``err.throw()``s on the host. The error pytree rides along as
    a regular output; the engine state itself is bit-exact with the
    unsanitized run (the checks only observe). Either runner has the
    jit's ``lower``, so its compiled program can be read.
    """
    donate_argnums = (0,) if donate else ()
    if not sanitized:
        return jax.jit(run_fn, donate_argnums=donate_argnums)
    jitted = jax.jit(
        checkify.checkify(run_fn, errors=checkify.user_checks),
        donate_argnums=donate_argnums,
    )

    def runner(state):
        err, out = jitted(state)
        err.throw()
        return out

    runner.lower = jitted.lower
    return runner


def make_runner(
    cfg: EngineConfig, ssd: SSDConfig, wl, plat: PlatformModel,
    rounds: int, donate: bool = False, sanitize: bool = False,
):
    """jit-compiled engine runner with static configs baked in.

    ``donate=True`` donates the input ``EngineState``'s buffers to the
    call (``donate_argnums``), letting XLA reuse the ring/flash/buffer
    storage in place instead of copying it — the steady-state benchmark
    mode, where each rep feeds the previous rep's output back in. The
    caller must not reuse a donated input afterwards, hence default off.

    ``sanitize=True`` (or ``cfg.sanitize``) threads the checkify
    invariant assertions through every pipeline pass (see
    ``device._sanitize_checks``) and raises
    ``checkify.JaxRuntimeError`` from the returned runner on the first
    violated invariant. Virtual time is unchanged — the sanitized
    runner's output state is bit-exact with the default runner's
    (pinned by tests/test_sanitize.py).

    The data path moves only the epoch's valid rows, in chunks of
    ``datapath.window_rows(cfg, ssd)``; the array runners move them all.
    """
    wl = as_workload(wl)
    sanitized = sanitize or cfg.sanitize
    if sanitized:
        cfg = cfg.replace(sanitize=True)
    window = datapath.window_rows(cfg, ssd)

    def _run(state: EngineState) -> EngineState:
        return run(state, cfg, ssd, wl, plat, rounds, window)

    return _jit_runner(_run, donate, sanitized)


def make_array_runner(
    cfg: EngineConfig, ssd: SSDConfig, wl, plat: PlatformModel,
    rounds: int, donate: bool = False, sanitize: bool = False,
):
    """jit-compiled M-drive array runner: ``run`` vmapped over the leading
    device axis of a stacked EngineState — one XLA program per array.
    ``donate``/``sanitize`` as in ``make_runner`` (checkify composes
    with the vmap: any drive's violated invariant throws)."""
    wl = as_workload(wl)
    sanitized = sanitize or cfg.sanitize
    if sanitized:
        cfg = cfg.replace(sanitize=True)

    def _run(states: EngineState) -> EngineState:
        return jax.vmap(
            lambda s: run(s, cfg, ssd, wl, plat, rounds)
        )(states)

    return _jit_runner(_run, donate, sanitized)


def make_sharded_array_runner(
    cfg: EngineConfig, ssd: SSDConfig, wl, plat: PlatformModel,
    rounds: int, mesh=None, axis_name: str = "dev",
):
    """M-drive array runner sharded across a JAX device mesh.

    Where ``make_array_runner`` vmaps the whole array onto one
    accelerator, this shards the stacked ``EngineState``'s leading
    device axis over a 1-D mesh via ``jax.shard_map`` and vmaps each
    shard locally — so an M-drive array spreads over however many real
    devices the process holds, one XLA program per shard. M must be
    divisible by the mesh size. With a 1-device mesh this is semantically identical
    to ``make_array_runner`` (asserted bit-exactly in
    ``tests/test_fabric.py``).

    The call consumes its input: the stacked state is donated
    (``donate_argnums``), so each chip holds one copy of its drives'
    images, and the steady-state loop ``states = call(states)`` runs in
    place. A caller that needs its input afterwards passes a copy
    (``unalias(states)``).

    ``mesh`` defaults to all local devices on a ``(axis_name,)`` mesh.
    The runner has the jit's ``lower``, as ``make_runner``'s does.
    """
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    wl = as_workload(wl)
    if mesh is None:
        mesh = Mesh(np.asarray(jax.devices()), (axis_name,))

    def _shard(states: EngineState) -> EngineState:
        return jax.vmap(
            lambda s: run(s, cfg, ssd, wl, plat, rounds)
        )(states)

    sharded = jax.jit(jax.shard_map(
        _shard, mesh=mesh, in_specs=P(axis_name), out_specs=P(axis_name),
        check_vma=False,
    ), donate_argnums=(0,))
    mesh_size = int(np.prod(mesh.devices.shape))

    def _run(states: EngineState) -> EngineState:
        m = jax.tree.leaves(states)[0].shape[0]
        if m % mesh_size != 0:
            raise ValueError(
                f"array of M={m} drives cannot shard over a mesh of "
                f"{mesh_size} devices — M must be divisible by the mesh "
                "size (pass a smaller mesh or resize the array)"
            )
        return sharded(states)

    _run.lower = sharded.lower
    return _run


def init_array_state(
    cfg: EngineConfig,
    ssd: SSDConfig,
    wl: "Workload | WorkloadConfig",
    num_devices: int,
    block_words: int = 16,
) -> EngineState:
    """Stacked EngineState for an M-drive array (device axis leading).

    Each drive gets a distinct workload salt, so salt-aware generators
    (closed loop, Poisson, Zipf) serve M independent request streams.
    Fixed-trace replays are striped via ``Workload.sharded``: drive d
    replays the rows whose time-sorted trace index i satisfies
    ``i % M == d`` (arrival times preserved), so array aggregates
    measure the one trace split M ways.
    """
    wl = as_workload(wl).sharded(num_devices)
    return _stack_states(
        lambda salt: init_state(cfg, ssd, wl, block_words, salt=salt),
        num_devices,
    )


def aggregate_iops(state: EngineState) -> jax.Array:
    """Array-aggregate virtual IOPS: sum of per-device sustained rates."""
    return jnp.sum(state.metrics.iops())


def simulate(
    cfg: EngineConfig,
    ssd: SSDConfig,
    wl: "Workload | WorkloadConfig",
    plat: PlatformModel | None = None,
    rounds: int = 64,
    block_words: int = 16,
    num_devices: int = 1,
) -> EngineState:
    """Convenience: init + run. Returns the final state.

    With ``num_devices=M > 1`` the returned EngineState has a leading (M,)
    device axis on every leaf (an emulated M-drive array, one jit program);
    aggregate throughput is ``aggregate_iops(state)`` and the histogram
    percentiles already pool across drives.
    """
    plat = plat or PlatformModel()
    if num_devices == 1:
        state = init_state(cfg, ssd, wl, block_words)
        return make_runner(cfg, ssd, wl, plat, rounds)(state)
    states = init_array_state(cfg, ssd, wl, num_devices, block_words)
    return make_array_runner(cfg, ssd, wl, plat, rounds)(states)
