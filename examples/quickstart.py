"""Quickstart: emulate a future 40-MIOPS SSD and measure what a
GPU-initiated workload sees.

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax

from repro.core import engine
from repro.core.types import EngineConfig, SSDConfig, WorkloadConfig

# 1. Describe the device you want to emulate (NVMeVirt simple timing model).
ssd = SSDConfig(
    name="future-iops-optimized",
    t_max_iops=40e6,       # sustained random-read ceiling
    l_min_us=30.0,         # latency floor
    n_instances=512,       # abstract flash channels/controllers
    num_blocks=1 << 14,
)

# 2. Configure the SwarmIO engine: 16 service units, coalesced fetching,
#    DSA-offloaded data path, aggregated timing updates.
cfg = EngineConfig(
    num_sqs=32, sq_depth=1024, fetch_width=256,  # coalesce deeply
    num_units=16, frontend="distributed", mode="aggregated",
    coalesced=True, dsa_fetch=True, batched_datapath=True,
)

# 3. A BaM-like closed-loop workload: 32 SQs x 1024 outstanding 512B reads.
wl = WorkloadConfig(io_depth=1024)

final = engine.simulate(cfg, ssd, wl, rounds=64)
m = final.metrics
print(f"device target : {ssd.t_max_iops/1e6:.1f} MIOPS, "
      f"floor {ssd.l_min_us:.0f} us")
print(f"sustained     : {float(m.iops())/1e6:.1f} MIOPS "
      f"({float(m.iops())/ssd.t_max_iops*100:.1f}% of target)")
print(f"avg E2E       : {float(m.avg_e2e_us()):.1f} us "
      f"(includes queueing at this load)")
print(f"latency dist  : p50={float(m.p50_us()):.0f} "
      f"p95={float(m.p95_us()):.0f} p99={float(m.p99_us()):.0f} us")
print(f"requests done : {int(float(m.completed))}")

# 4. Compare with the NVMeVirt baseline under the same load.
base_cfg = EngineConfig(
    num_sqs=32, sq_depth=1024, fetch_width=64,
    num_units=1, frontend="centralized", mode="per_request",
    coalesced=False, dsa_fetch=False, batched_datapath=False,
)
base = engine.simulate(base_cfg, ssd, wl, rounds=64)
print(f"NVMeVirt base : {float(base.metrics.iops())/1e6:.2f} MIOPS "
      f"-> SwarmIO speedup "
      f"{float(m.iops())/float(base.metrics.iops()):.0f}x")

# 5. Scale out: vmap the unified pipeline over a 4-drive array — one jit
#    program emulating 4x40 MIOPS, the paper-title 100-MIOPS regime.
arr = engine.simulate(cfg, ssd, wl, rounds=64, num_devices=4)
print(f"4-drive array : {float(engine.aggregate_iops(arr))/1e6:.0f} MIOPS "
      f"aggregate (p99 {float(arr.metrics.p99_us()):.0f} us)")

# 6. Swap the arrival process: open-loop Poisson at 60% of the device
#    ceiling (closed loops can't show overload latency; open loops can).
from repro import workloads

open_wl = workloads.PoissonOpenLoop(io_depth=1024, rate_iops=24e6)
po = engine.simulate(cfg, ssd, open_wl, rounds=64)
pm = po.metrics
print(f"open-loop 24M : sustained {float(pm.iops())/1e6:.1f} MIOPS, "
      f"p99 {float(pm.p99_us()):.0f} us")

# 7. Turn on the flash-level backend's hard cases: a 70/30 read/write mix
#    on a steady-state (fully written) drive. Write programs serialize per
#    chip and greedy GC steals die time once the free-page pool drains —
#    watch the tail inflate relative to the read-only runs above.
mixed = workloads.SteadyStateMixed(io_depth=1024, read_frac=0.7, theta=0.9)
mx = engine.simulate(cfg, ssd, mixed, rounds=64)
mm = mx.metrics
print(f"70/30 steady  : {float(mm.iops())/1e6:.2f} MIOPS, "
      f"p99 {float(mm.p99_us()):.0f} us, "
      f"{float(mx.device.flash.gc_count):.0f} GC invocations")

# 8. Cold mapping state: a 50% cached-mapping-table hit rate charges a
#    translation-page read on every miss (the KV-SSD random-read story).
cold = engine.simulate(
    cfg, ssd.replace(mapping_hit_rate=0.5), wl, rounds=64
)
print(f"CMT 50% hits  : avg E2E {float(cold.metrics.avg_e2e_us()):.0f} us "
      f"vs {float(m.avg_e2e_us()):.0f} us all-hit")

# 9. The queue-pair completion path and the GPU page cache. By default
#    both are neutral: completions post to CQ rings and reap with zero
#    added time. Turning the knobs on shows the two tradeoffs:
#    (a) completion coalescing — with a per-doorbell cost, batching 16
#    completions per CQ doorbell recovers IOPS an uncoalesced stream
#    loses to doorbell serialization (fig21);
#    (b) a Zipf-hot workload in front of a GPU-side page cache — hits
#    complete at GPU-local latency and never post an SQE, so delivered
#    IOPS amplify with the hit rate (fig22).
from repro.core.types import CacheConfig, QPConfig

bell = QPConfig(cq_coalesce_n=1, cq_coalesce_us=50.0, cq_doorbell_us=1.0)
coal = bell.replace(cq_coalesce_n=16)
slow_cq = engine.simulate(cfg.replace(qp=bell), ssd, wl, rounds=64)
fast_cq = engine.simulate(cfg.replace(qp=coal), ssd, wl, rounds=64)
print(f"CQ coalescing : 1/doorbell {float(slow_cq.metrics.iops())/1e6:.1f} "
      f"MIOPS -> 16/doorbell {float(fast_cq.metrics.iops())/1e6:.1f} MIOPS")

cached_cfg = cfg.replace(
    cache=CacheConfig(enabled=True, num_sets=1024, ways=4, hit_us=0.5)
)
zipf = workloads.ZipfClosedLoop(io_depth=1024, theta=0.9)
uncached = engine.simulate(cfg, ssd, zipf, rounds=64)
cached = engine.simulate(cached_cfg, ssd, zipf, rounds=64)
cm = cached.metrics
print(f"page cache    : Zipf {float(uncached.metrics.iops())/1e6:.1f} MIOPS "
      f"-> {float(cm.iops())/1e6:.1f} MIOPS at "
      f"{float(cm.hit_rate())*100:.0f}% hit rate")

# 10. Disaggregate: put every drive of the 4-drive array behind its own
#     NIC/link (remote all-flash array). Reads return ~528 B per request
#     over the RX direction, so at 40M IOPS/drive the *wire* becomes the
#     roof long before the flash does: a 2 GB/s-class link clamps each
#     drive near rx_bytes_per_us/528 IOPS, while an unconstrained link
#     (the `remote=True` default) reproduces the local array bit-exactly.
#     Sweeps: benchmarks fig23 (bandwidth/RTT roofline) and fig24
#     (stripe-width x replication via StorageClient.read_striped /
#     read_replicated over the per-link load cursors).
from repro.core.types import FabricConfig

link = FabricConfig(
    remote=True, rtt_us=10.0,           # network round trip
    tx_bytes_per_us=8000.0,             # SQEs + write payloads ->
    rx_bytes_per_us=2000.0,             # <- CQEs + read payloads (binding)
    wire_txn_us=0.2, mtu_batch=8, mtu_timeout_us=20.0,  # NIC doorbells
)
remote = engine.simulate(cfg.replace(fabric=link), ssd, wl, rounds=64,
                         num_devices=4)
print(f"remote array  : {float(engine.aggregate_iops(remote))/1e6:.0f} MIOPS "
      f"aggregate behind 4x2 GB/s links "
      f"(local array above: {float(engine.aggregate_iops(arr))/1e6:.0f}; "
      f"p99 {float(remote.metrics.p99_us()):.0f} us)")

# 11. Share the fabric: (a) all four drives' return streams converge on
#     one switch/initiator NIC (incast) — even with unconstrained
#     per-drive links the array clamps at switch_bytes_per_us / ~528 B
#     (fig25); (b) two tenants on one remote drive — a latency
#     read tenant and a bulk-write tenant whose 576 B frames starve the
#     64 B read SQEs on the TX wire under FIFO — get weighted-fair
#     arbitration from qos_weights: backlogged classes split every
#     shared cursor in weight proportion (fig26). MultiTenant
#     partitions the SQs into contiguous per-tenant blocks.
incast = FabricConfig(remote=True, switch_bytes_per_us=8000.0,
                      switch_fanin=4)
sw = engine.simulate(cfg.replace(fabric=incast), ssd, wl, rounds=64,
                     num_devices=4)
print(f"shared switch : {float(engine.aggregate_iops(sw))/1e6:.1f} MIOPS "
      f"aggregate at an 8 GB/s switch "
      f"(roof {8000.0 / (16 + 512):.1f} MIOPS, links unconstrained)")

two_tenants = workloads.MultiTenant(io_depth=64,
                                    tenant_read_frac=(1.0, 0.0))
qos_cfg = cfg.replace(num_sqs=16, fetch_width=64, num_units=8)
d7 = SSDConfig()  # the D7-class drive: the wire binds, not the flash
for label, weights in [("fifo", ()), ("wfq 4:1", (4.0, 1.0))]:
    fab = FabricConfig(remote=True, tx_bytes_per_us=400.0,
                       rx_bytes_per_us=16000.0, qos_weights=weights)
    out = engine.simulate(qos_cfg.replace(fabric=fab), d7, two_tenants,
                          rounds=96)
    lat = out.metrics.tenant_avg_e2e_us()
    shares = [round(s, 2) for s in out.metrics.tenant_share().tolist()]
    print(f"2-tenant {label:7s}: reads {float(lat[0]):5.0f} us, bulk "
          f"writes {float(lat[1]):5.0f} us (shares {shares})")

# 12. Wall-clock speed is its own axis: the numbers above are *virtual*
#     throughput (emulated time), while how fast the engine retires
#     emulated requests per *real* second is what
#     `benchmarks/emulator_speed.py` measures (full matrix ->
#     BENCH_emulator_speed.json). EngineConfig gates the fast path:
#     use_sort_plan (default on) computes each epoch's segment
#     order/heads/rank once and reuses it across the unit, CQ, and
#     fabric sorts; use_compaction (default on) adds the sort-free
#     epoch-compacted forms (dense round-robin timing layout,
#     counting-sorted flash/lanes, block CQ ranks, fused ring
#     scatters); use_pallas_segscan (default None = auto) routes the
#     queueing recurrence through the Pallas segmented-scan kernel
#     whenever types.integer_timestamps proves it bit-exact for this
#     platform. All are bit-exact in virtual time
#     (tests/test_emulator_speed.py). donate=True lets XLA reuse the
#     state buffers in place — donated inputs must not alias, so
#     deep-copy fresh states with engine.unalias before the first call.
from repro.core.types import PlatformModel

fast_cfg = cfg.replace(use_compaction=True)  # the default, shown explicit
runner = engine.make_runner(fast_cfg, ssd, wl, PlatformModel(), rounds=8,
                            donate=True)
st = engine.unalias(engine.init_state(fast_cfg, ssd, wl))
st = jax.block_until_ready(runner(st))      # untimed: compile + warmup
t0 = time.perf_counter()
st = jax.block_until_ready(runner(st))      # steady-state round, timed
dt = time.perf_counter() - t0
done = float(st.metrics.completed)
print(f"wall-clock    : {done / dt:,.0f} emulated req/wall-sec "
      f"({done:.0f} reqs in {dt*1e3:.0f} ms; virtual "
      f"{float(st.metrics.iops())/1e6:.1f} MIOPS)")

# 13. LLM serving on the emulated array: the SSD-backed paged-KV tier
#     (src/repro/serving/) keeps each sequence's hot attention window in
#     the GPU pool and pages everything colder to the drive. Every
#     decode step faults the cold pages back in as page-table-driven
#     LBA-run reads through the same SQ -> timing -> flash -> CQ path
#     as above, demoted hot-window pages are written back through it,
#     and the bytes each fault gathers are checked bit-exactly against
#     the live pool (data_check_max_abs must be 0.0). Tokens/s is
#     min(GPU roof, storage-bound rate); striping over num_devices
#     drives lifts the storage bound (fig27/fig28,
#     benchmarks/kv_serving.py -> BENCH_kv_tier.json).
import dataclasses

from repro import configs
from repro.serving import kv_tier

model = configs.get_config("yi-34b", smoke=True)
tier = kv_tier.KVTierConfig(page_tokens=16, hot_window=64,
                            gpu_step_us=100.0)
serve_ecfg = EngineConfig(num_units=8, fetch_width=64)
for label, t, dev in [
    ("1x 2.5M drive", tier, SSDConfig(t_max_iops=2.5e6, l_min_us=30.0,
                                      n_instances=64)),
    ("4x 40M striped", dataclasses.replace(tier, num_devices=4),
     SSDConfig(t_max_iops=40e6, l_min_us=30.0, n_instances=512)),
]:
    r = kv_tier.decode_tokens_per_s(model, t, dev, serve_ecfg, batch=4,
                                    start_len=256, n_steps=4)
    print(f"kv tier {label:14s}: {r['tokens_per_s']:8,.0f} tok/s "
          f"(step {r['avg_step_us']:.0f} us, "
          f"{r['blocks_per_step']:.0f} blk/step, "
          f"data check {r['data_check_max_abs']:.1f})")

# 14. Misaligned multi-tenant isolation: the ready-time timing lock.
#     A latency read tenant and a bulk write tenant on *interleaved*
#     SQs (tenant = sq % 2, one unit per SQ) — the placement where the
#     default program-order lock chains every latency unit behind the
#     bulk unit one loop position earlier, even with weighted-fair wire
#     QoS. lock_order="ready_time" admits units by post-fabric-TX batch
#     arrival instead and restores isolation (fig29,
#     BENCH_lock_order.json).
from repro.core.types import FabricConfig
from repro.workloads import MultiTenant

mt_wl = MultiTenant(io_depth=64, tenant_read_frac=(1.0, 0.0),
                    interleave=True)
mt_ssd = SSDConfig(t_max_iops=2.47e6, l_min_us=50.0, n_instances=64)
for order in ("program", "ready_time"):
    mt_cfg = EngineConfig(
        num_sqs=16, num_units=16, sq_depth=128, fetch_width=64,
        fabric=FabricConfig(remote=True, tx_bytes_per_us=400.0,
                            rx_bytes_per_us=16000.0,
                            qos_weights=(2.0, 1.0)),
        lock_order=order,
    )
    mm = engine.simulate(mt_cfg, mt_ssd, mt_wl, rounds=32).metrics
    p99 = mm.tenant_p99_us()
    slo = mm.slo_attainment(500.0)
    print(f"lock {order:10s}: latency-tenant p99 {float(p99[0]):7.0f} us "
          f"(SLO<=500us attained {float(slo[0])*100:5.1f}%), "
          f"bulk p99 {float(p99[1]):7.0f} us")

# 15. Trust but checkify: sanitize=True threads jax.experimental.checkify
#     assertions through the whole pipeline (ring indices in bounds,
#     completion times monotone and non-negative, valid-mask
#     conservation across the compaction/admission permutations, flash
#     free-page and fabric cursor invariants). The checks only observe —
#     the sanitized run's final state is bitwise identical to the
#     default run's (tests/test_sanitize.py) — but the program is
#     slower, so it's off by default; benchmarks/run.py --sanitize runs
#     it as a certification pass before timing anything. A violated invariant raises
#     checkify.JaxRuntimeError with the failed check's message.
san_runner = engine.make_runner(fast_cfg, ssd, wl, PlatformModel(),
                                rounds=8, sanitize=True)
san = jax.block_until_ready(
    san_runner(engine.init_state(fast_cfg, ssd, wl))
)
print(f"sanitized run : checkify-clean, "
      f"{float(san.metrics.completed):.0f} reqs retired "
      f"(bit-exact with the unsanitized pipeline)")
