"""LLM-serving case study: decode tokens/s on the SSD-backed KV tier.

The end-to-end story the emulator exists to tell (paper §I): with the
cold KV history in an IOPS-optimized storage tier, decode throughput is
a function of device IOPS. Both figures run the *real* tier — paged KV
cache, page-table LBA runs, write-backs, and faults through the full
``StorageClient.submit`` rings -> timing -> flash -> CQ path — in
virtual time (deterministic; no wall-clock noise).

``fig27``  decode tokens/s vs device MIOPS (2.5 -> 40 MIOPS single
           drive, then a 4 x 40M striped array): tokens/s must be
           monotone non-decreasing in device capability and saturate at
           the GPU-compute roof (``1e6 * batch / gpu_step_us``).

``fig28``  two sweeps of the serving memory hierarchy:
           * ``hot_cache`` — HBM hot window x stage-0 GPU page-cache
             size (cache off / small / large, with readahead): larger
             stage-0 caches absorb re-faulted cold pages at GPU-local
             latency;
           * ``tenant_mix`` — a background bulk-ingest write stream
             (prefill tenant) sharing a switched remote fabric with the
             latency (decode) tenant, FIFO vs weighted-fair QoS.

Results persist to ``BENCH_kv_tier.json`` at the repo root;
``scripts/check_bench_floor.py`` runs an advisory monotonicity check
over the fig27 points.

    PYTHONPATH=src python -m benchmarks.kv_serving [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import platform

import jax

from benchmarks import common as C
from repro import configs
from repro.compile_cache import enable_compile_cache
from repro.core.types import CacheConfig, EngineConfig, FabricConfig, SSDConfig
from repro.serving import kv_tier

SCHEMA = "kv_tier/v1"
JSON_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_kv_tier.json",
)

ARCH = "yi-34b"          # smoke dims; the tier scales I/O by n_layers
GPU_STEP_US = 100.0      # modeled per-token GPU compute


def _serve_shape(quick: bool):
    # start_len stays at 512 in --quick: the cold working set is what
    # makes the low-MIOPS points storage-bound (the >= 3x fig27 gain).
    return dict(batch=4, start_len=512, n_steps=4 if quick else 16)


def _tier(**kw) -> kv_tier.KVTierConfig:
    base = dict(page_tokens=16, hot_window=64, gpu_step_us=GPU_STEP_US)
    base.update(kw)
    return kv_tier.KVTierConfig(**base)


def _ssd(miops: float) -> SSDConfig:
    """A drive at ``miops`` MIOPS (instances scaled with capability,
    latency floor held fixed so the sweep isolates the IOPS axis)."""
    return SSDConfig(
        t_max_iops=miops * 1e6, l_min_us=30.0,
        n_instances=max(64, int(miops * 12.8)), num_blocks=1 << 14,
    )


def _run(tier, ssd, ecfg, quick):
    cfg = configs.get_config(ARCH, smoke=True)
    return kv_tier.decode_tokens_per_s(
        cfg, tier, ssd, ecfg, **_serve_shape(quick)
    )


def fig27_kv_serving_iops(quick: bool = False):
    """Decode tokens/s vs device MIOPS (single drive -> 4 x 40M array)."""
    ecfg = EngineConfig(num_units=8, fetch_width=64)
    sweep = [2.5, 10.0, 40.0] if quick else [2.5, 5.0, 10.0, 20.0, 40.0]
    rows = []
    points = []
    for miops in sweep:
        r = _run(_tier(), _ssd(miops), ecfg, quick)
        rows.append(["1drive", miops, r["tokens_per_s"], r["avg_step_us"],
                     r["avg_storage_us"], r["blocks_per_step"],
                     r["iops_demand"], r["data_check_max_abs"]])
        points.append({"config": "1drive", "miops": miops, **r})
    # The paper-title regime: 4 x 40-MIOPS drives, faults striped
    # round-robin over the array (160 MIOPS aggregate).
    r = _run(_tier(num_devices=4), _ssd(40.0), ecfg, quick)
    rows.append(["4x40m_striped", 160.0, r["tokens_per_s"],
                 r["avg_step_us"], r["avg_storage_us"],
                 r["blocks_per_step"], r["iops_demand"],
                 r["data_check_max_abs"]])
    points.append({"config": "4x40m_striped", "miops": 160.0, **r})

    shape = _serve_shape(quick)
    roof = 1e6 * shape["batch"] / GPU_STEP_US
    first, last = points[0]["tokens_per_s"], points[-1]["tokens_per_s"]
    print(f"fig27: {first:,.0f} -> {last:,.0f} tok/s over "
          f"{sweep[0]}->160 MIOPS ({last / first:.1f}x, GPU roof "
          f"{roof:,.0f}); data check "
          f"{max(p['data_check_max_abs'] for p in points):.1f}")
    header = ["config", "miops", "tokens_per_s", "avg_step_us",
              "avg_storage_us", "blocks_per_step", "iops_demand",
              "data_check_max_abs"]
    return header, rows, points


def fig28_kv_tier_hierarchy(quick: bool = False):
    """Hot-window x stage-0 cache size, and tenant-mix QoS sweeps."""
    rows = []
    points = []

    # Sweep 1: HBM hot window x GPU page-cache capacity. Re-faulted
    # cold pages hit the stage-0 cache at GPU-local latency, so cache
    # capacity trades directly against device IOPS demand.
    caches = [
        ("off", CacheConfig(enabled=False)),
        ("small", CacheConfig(enabled=True, num_sets=64, ways=4,
                              readahead=2)),
        ("large", CacheConfig(enabled=True, num_sets=512, ways=8,
                              readahead=2)),
    ]
    if quick:
        caches = [caches[0], caches[2]]
    hot_windows = [32, 128] if quick else [32, 64, 128]
    for hw in hot_windows:
        for cname, ccfg in caches:
            ecfg = EngineConfig(num_units=8, fetch_width=64, cache=ccfg)
            r = _run(_tier(hot_window=hw), _ssd(2.5), ecfg, quick)
            rows.append(["hot_cache", f"hw{hw}_cache_{cname}",
                         r["tokens_per_s"], r["avg_storage_us"],
                         r["blocks_per_step"], r["data_check_max_abs"]])
            points.append({"sweep": "hot_cache", "hot_window": hw,
                           "cache": cname, **r})

    # Sweep 2: tenant mix on a remote fabric — a bulk context-ingest
    # read stream (prefill tenant) congests the shared wire against
    # the decode tenant's faults; WFQ weights protect the decode
    # tenant's latency, FIFO does not. The drive itself is fast (40M)
    # so the contention is squarely on the fabric.
    fab = dict(
        remote=True, tx_bytes_per_us=1_500.0, rx_bytes_per_us=1_500.0,
        rtt_us=2.0, wire_txn_us=0.2, mtu_batch=8, mtu_timeout_us=5.0,
        switch_bytes_per_us=1_500.0, switch_fanin=1,
    )
    mixes = [
        ("idle_fifo", 0, ()),
        ("bulk_fifo", 2048, ()),
        ("bulk_wfq_4_1", 2048, (4.0, 1.0)),
    ]
    if quick:
        mixes = mixes[1:]
    for name, bulk, weights in mixes:
        ecfg = EngineConfig(
            num_units=8, fetch_width=64,
            fabric=FabricConfig(qos_weights=weights, **fab),
        )
        r = _run(_tier(bulk_blocks_per_step=bulk), _ssd(40.0), ecfg,
                 quick)
        rows.append(["tenant_mix", name, r["tokens_per_s"],
                     r["avg_storage_us"], r["blocks_per_step"],
                     r["data_check_max_abs"]])
        points.append({"sweep": "tenant_mix", "mix": name,
                       "bulk_blocks_per_step": bulk, **r})

    hc = [p for p in points if p["sweep"] == "hot_cache"]
    tm = [p for p in points if p["sweep"] == "tenant_mix"]
    mix_txt = ", ".join(
        "{}={:,.0f}".format(p["mix"], p["tokens_per_s"]) for p in tm
    )
    print(f"fig28: hot/cache sweep {min(p['tokens_per_s'] for p in hc):,.0f}"
          f" -> {max(p['tokens_per_s'] for p in hc):,.0f} tok/s; "
          f"tenant mix {mix_txt}")
    header = ["sweep", "point", "tokens_per_s", "avg_storage_us",
              "blocks_per_step", "data_check_max_abs"]
    return header, rows, points


def _persist(key: str, points: list, quick: bool) -> None:
    """Read-modify-write ``BENCH_kv_tier.json`` with one figure's points
    (each figure can run standalone via ``benchmarks/run.py``)."""
    payload = {}
    if os.path.exists(JSON_PATH):
        try:
            with open(JSON_PATH) as f:
                payload = json.load(f)
        except (OSError, ValueError):
            payload = {}
    shape = _serve_shape(quick)
    payload.update({
        "schema": SCHEMA,
        "quick": quick,
        "host": {
            "machine": platform.machine(),
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
        },
        "arch": ARCH,
        "serve_shape": shape,
        "gpu_step_us": GPU_STEP_US,
        "gpu_roof_tokens_per_s": 1e6 * shape["batch"] / GPU_STEP_US,
        key: points,
    })
    with open(JSON_PATH, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"  -> {JSON_PATH} [{key}]")


def bench(quick: bool = False):
    """Run both figures, persist the JSON, return per-figure CSV data."""
    h27, r27 = fig27(quick)
    h28, r28 = fig28(quick)
    return (h27, r27), (h28, r28)


def fig27(quick: bool = False):
    """figures.ALL entry point (also refreshes the JSON's fig27 key)."""
    h, r, p = fig27_kv_serving_iops(quick)
    _persist("fig27", p, quick)
    return h, r


def fig28(quick: bool = False):
    """figures.ALL entry point (also refreshes the JSON's fig28 key)."""
    h, r, p = fig28_kv_tier_hierarchy(quick)
    _persist("fig28", p, quick)
    return h, r


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweep/steps for CI smoke")
    args = ap.parse_args()
    enable_compile_cache()
    C.jit_warmup()
    (h27, r27), (h28, r28) = bench(quick=args.quick)
    C.write_csv("fig27_kv_serving_iops", h27, r27)
    C.write_csv("fig28_kv_tier_hierarchy", h28, r28)


if __name__ == "__main__":
    main()
