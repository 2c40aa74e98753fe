#!/usr/bin/env python3
"""Smoke run of the emulator's main path on one TPU chip.

Drives the engine, the vmapped array, the storage client, the Pallas
stage kernels and the checkify sanitizer once each, through the entry
points a user calls, at deployment size: a 40-MIOPS drive (32 SQs x
1024 entries, 16 service units) over a 2^24-block (8 GiB) LBA space
whose flash image lives on the device. Each phase checks its results
and prints one line of numbers; wall-clock figures are smoke readings,
not benchmark results. The last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

and any failed phase exits nonzero before it. Without a TPU the run
fails at start-up.

    python chip_smoke.py             # phases a-e on one chip
    python chip_smoke.py --chips 4   # 16-drive array sharded over 4 chips,
                                     # against the same array on one chip
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    # A missing TPU must be an error, never a silent CPU run.
    os.environ["JAX_PLATFORMS"] = "tpu,cpu"

ROOT = Path(__file__).resolve().parent
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import common as C  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import datapath, engine, frontend  # noqa: E402
from repro.core.client import StorageClient  # noqa: E402
from repro.core.types import (  # noqa: E402
    OP_READ,
    EngineConfig,
    PlatformModel,
    SSDConfig,
    WorkloadConfig,
    integer_timestamps,
)
from repro.workloads import TraceReplay  # noqa: E402

# Phase a: one 40-MIOPS drive, closed loop, flash image on the device.
NUM_BLOCKS = 1 << 24        # 8 GiB of 512-B blocks
BLOCK_WORDS = 16            # engine default: a 64-B fingerprint per block
IO_DEPTH = 256              # outstanding requests per SQ
ROUNDS = 24                 # engine rounds per runner invocation
INVOCATIONS = 4             # chained donated invocations (first is warm-up)
TARGET_IOPS = 40e6
# Phase b: 4 drives vmapped on one chip. 128-word rows keep the image
# row-major under vmap (a vmapped 16-word image is relaid out inside
# every round and compiles for minutes at 2^24 blocks), and four 8-GiB
# full-sector images do not fit 16 GB of HBM: each drive is cut to 2^22.
ARRAY_DRIVES = 4
ARRAY_BLOCKS = 1 << 22
ARRAY_WORDS = 128
MIN_ARRAY_IOPS = 150e6
# Phase c: one client batch of random reads against phase a's image.
CLIENT_READS = 8192
# Phase d: every Pallas kernel on (block_gather needs 128-word rows),
# driven by a seeded read trace with integer-us arrivals: with the
# integer platform every timestamp and latency is an integer-valued f32,
# and the trace is small enough that every sum stays below 2^24, so any
# reduction order (kernel, XLA:TPU, XLA:CPU) gives the same bits.
# Mapping-table misses put events on the dies for the contention
# kernel. Writes are left out: they move the fractional page-pool
# expectation (``FlashState.valid_pages``), whose f32 multiply-add
# XLA:CPU contracts into an FMA and XLA:TPU does not.
EXACT_BLOCKS = 1 << 20
EXACT_WORDS = 128
EXACT_ROUNDS = 24
EXACT_REQUESTS = 2048
EXACT_HIT_RATE = 0.5
# --chips 4: 16 drives, 4 per chip.
SHARDED_DRIVES = 16
SHARDED_BLOCKS = 1 << 20


class SmokeFailure(AssertionError):
    """A phase produced a wrong or implausible result."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def phase_a_config(num_bufs: int = 32 * IO_DEPTH):
    """The 40-MIOPS drive: ``benchmarks.common.swarmio_cfg`` with the
    data path on and one I/O buffer per outstanding request (32 SQs x
    ``IO_DEPTH``), so a buffer row names the one read that filled it."""
    cfg = C.swarmio_cfg(emulate_data=True, num_bufs=num_bufs)
    return cfg, C.FUTURE_40M.replace(num_blocks=NUM_BLOCKS)


def integer_platform() -> PlatformModel:
    """Platform costs ``types.integer_timestamps`` proves integer-valued:
    every virtual timestamp is an integer-valued f32, so kernels that
    re-associate the queueing sums stay bit-exact
    (tests/test_emulator_speed.py, auto-resolution case)."""
    return PlatformModel(
        cpu_sqe_fetch_us=10.0, cpu_coal_byte_us=0.0, cpu_coal_base_us=1.0,
        dsa_sqe_fetch_us=4.0, dsa_coal_base_us=18.0,
        dsa_desc_issue_us=1.0, dsa_batch_setup_us=1.0,
        dsa_bytes_per_us=64.0, doorbell_poll_us=1.0,
        host_txn_base_us=1.0, host_bytes_per_us=64.0,
        txn_base_us=1.0, link_bytes_per_us=64.0,
        per_req_map_us=3.0, lock_per_req_us=1.0, lock_per_batch_us=1.0,
    )


def integer_trace(cfg: EngineConfig, ssd: SSDConfig, *, requests: int,
                  span_us: int, seed: int = 0):
    """Seeded read ``TraceReplay`` with integer-us arrivals in
    [0, span_us)."""
    rng = np.random.default_rng(seed)
    return TraceReplay.from_trace(
        rng.integers(0, span_us, requests),
        rng.integers(0, ssd.num_blocks, requests),
        np.full(requests, OP_READ, np.int32),
        cfg,
    )


# ---------------------------------------------------------------------------
# Helpers.
# ---------------------------------------------------------------------------

def flash_rows(flash, lba: np.ndarray) -> np.ndarray:
    """``flash[lba]`` as host rows. Word 0 of an initial image row is
    its LBA, exactly (``engine.init_state``), which pins the addressing
    independently of the gather."""
    rows = np.asarray(flash[jnp.asarray(lba)])
    check(np.array_equal(rows[:, 0], lba.astype(np.float32)),
          "a flash row does not start with its LBA")
    return rows


def bits(x) -> np.ndarray:
    x = np.asarray(x)
    return x.view(f"u{x.dtype.itemsize}") if x.dtype.itemsize > 1 else x


def diverged_leaves(a, b) -> list:
    """Paths of the leaves of two pytrees that differ in any bit."""
    out = []
    for (path, x), (_, y) in zip(
        jax.tree_util.tree_flatten_with_path(a)[0],
        jax.tree_util.tree_flatten_with_path(b)[0],
    ):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or not np.array_equal(bits(x), bits(y)):
            out.append(jax.tree_util.keystr(path))
    return out


def built_on_device(init):
    """Run a state initializer as one device program. Its outputs are
    distinct buffers, so the state can be donated without the extra
    copy ``engine.unalias`` makes (which an 8-GiB array cannot afford)."""
    return jax.block_until_ready(jax.jit(init)())


def compile_timed(jitted, *args):
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def run_timed(compiled, state, invocations: int):
    """Warm-up call, then ``invocations - 1`` chained timed calls."""
    state = jax.block_until_ready(compiled(state))
    t0 = time.perf_counter()
    for _ in range(invocations - 1):
        state = compiled(state)
    state = jax.block_until_ready(state)
    return state, (time.perf_counter() - t0) / max(invocations - 1, 1)


def latency_report(metrics) -> dict:
    p50, p95, p99 = (
        float(metrics.p50_us()), float(metrics.p95_us()),
        float(metrics.p99_us()),
    )
    check(p50 <= p95 <= p99, f"percentiles out of order: {p50} {p95} {p99}")
    return {"p50_us": p50, "p95_us": p95, "p99_us": p99}


def check_buffers(state, num_blocks: int) -> int:
    """Every written I/O buffer row holds its block's flash row, bit for
    bit (word 0 of a row is its LBA). Returns the rows checked."""
    bufs = np.asarray(state.bufs)
    written = np.any(bufs != 0, axis=1)
    check(written.any(), "no I/O buffer row was ever written")
    lba = bufs[written, 0].astype(np.int64)
    check(
        np.all((lba >= 0) & (lba < num_blocks)),
        "a buffer row names an LBA outside the drive",
    )
    check(
        np.array_equal(bits(bufs[written]), bits(flash_rows(state.flash,
                                                            lba))),
        "a buffer row differs from its flash block",
    )
    return int(written.sum())


def check_next_reads(state, cfg) -> int:
    """Fetch the pending requests from the final rings (a clock past
    every submission makes them all visible) and copy them with the
    engine's own data path: every read's buffer row must equal
    ``flash[lba]`` of that read. Returns the reads checked."""
    plat = PlatformModel()

    @jax.jit
    def next_reads(st):
        _, _, batch, _ = frontend.fetch(
            st.rings, jnp.float32(engine.FAR), st.device.disp_time, cfg,
            plat,
        )
        bufs = datapath.apply_reads(st.flash, st.bufs, batch, cfg.use_pallas)
        is_read = batch.valid & (batch.opcode == 0)
        return bufs[batch.buf_id], batch.lba, is_read

    rows, lba, is_read = map(np.asarray, next_reads(state))
    check(is_read.any(), "the next fetch holds no reads")
    check(
        np.array_equal(
            bits(rows[is_read]), bits(flash_rows(state.flash, lba[is_read]))
        ),
        "a read's buffer row differs from flash[lba]",
    )
    return int(is_read.sum())


# ---------------------------------------------------------------------------
# Phases. Each returns (report, ...) and raises SmokeFailure on a wrong
# result; sizes are arguments so the test suite can rehearse at toy size.
# ---------------------------------------------------------------------------

def phase_engine(cfg: EngineConfig, ssd: SSDConfig, wl, *, rounds: int,
                 invocations: int, block_words: int, min_iops: float):
    """a. One drive through ``engine.make_runner(..., donate=True)``."""
    plat = PlatformModel()
    runner = engine.make_runner(cfg, ssd, wl, plat, rounds, donate=True)
    state = built_on_device(
        lambda: engine.init_state(cfg, ssd, wl, block_words)
    )
    compiled, compile_s = compile_timed(runner, state)
    mem = compiled.memory_analysis()
    state, per_call = run_timed(compiled, state, invocations)
    m = state.metrics
    iops, completed = float(m.iops()), float(m.completed)
    check(completed > 0, "no request completed")
    check(iops >= min_iops, f"virtual IOPS {iops:.6g} < {min_iops:.6g}")
    report = {
        "compile_s": compile_s,
        "s_per_invocation": per_call,
        "rounds_per_invocation": rounds,
        "virtual_miops": iops / 1e6,
        "completed": completed,
        **latency_report(m),
        "flash_image_bytes": int(state.flash.nbytes),
        "program_argument_bytes": int(mem.argument_size_in_bytes),
        "program_temp_bytes": int(mem.temp_size_in_bytes),
        "buffer_rows_checked": check_buffers(state, ssd.num_blocks),
        "next_reads_checked": check_next_reads(state, cfg),
    }
    return report, state


def phase_array(cfg: EngineConfig, ssd: SSDConfig, wl, *, drives: int,
                rounds: int, invocations: int, block_words: int,
                min_iops: float):
    """b. ``drives`` drives vmapped on one chip (``make_array_runner``)."""
    plat = PlatformModel()
    runner = engine.make_array_runner(cfg, ssd, wl, plat, rounds,
                                      donate=True)
    states = built_on_device(
        lambda: engine.init_array_state(cfg, ssd, wl, drives, block_words)
    )
    compiled, compile_s = compile_timed(runner, states)
    states, per_call = run_timed(compiled, states, invocations)
    agg = float(engine.aggregate_iops(states))
    check(float(jnp.sum(states.metrics.completed)) > 0,
          "no request completed")
    check(agg >= min_iops, f"aggregate IOPS {agg:.6g} < {min_iops:.6g}")
    return {
        "compile_s": compile_s,
        "s_per_invocation": per_call,
        "drives": drives,
        "aggregate_virtual_miops": agg / 1e6,
        **latency_report(states.metrics),
    }


def phase_client(cfg: EngineConfig, ssd: SSDConfig, flash, *, reads: int,
                 seed: int = 0):
    """c. One ``StorageClient.read`` batch of random LBAs."""
    client = StorageClient(ssd, cfg)
    rng = np.random.default_rng(seed)
    lba = rng.integers(0, ssd.num_blocks, reads, dtype=np.int32)
    t_submit = rng.uniform(0.0, 100.0, reads).astype(np.float32)
    read = jax.jit(client.read)
    t0 = time.perf_counter()
    _, data, done = jax.block_until_ready(
        read(client.init_state(), flash, lba, t_submit)
    )
    first_s = time.perf_counter() - t0
    data, done = np.asarray(data), np.asarray(done)
    check(
        np.array_equal(bits(data), bits(flash_rows(flash, lba))),
        "client data differs from flash[lba]",
    )
    check(np.all(np.isfinite(done)), "a completion time is not finite")
    check(np.all(done >= t_submit), "a read completed before its submit")
    return {
        "compile_and_run_s": first_s,
        "reads": reads,
        "mean_latency_us": float(np.mean(done - t_submit)),
        "max_completion_us": float(np.max(done)),
    }


def exactness_config(cfg: EngineConfig, ssd: SSDConfig, kernels: bool):
    """Phase a's geometry on the integer platform, with the per-request
    (NVMeVirt-style) data path: the DSA model carries fractional
    constants that ``integer_timestamps`` rejects."""
    return cfg.replace(
        batched_datapath=False,
        use_pallas=kernels,
        use_pallas_segscan=kernels,
        use_pallas_reap=kernels,
        use_pallas_flash=kernels,
    ), ssd.replace(
        l_min_us=50.0, t_max_iops=64e6, n_instances=64,
        mapping_hit_rate=EXACT_HIT_RATE,
    )


def phase_exactness(cfg: EngineConfig, ssd: SSDConfig, *, rounds: int,
                    requests: int, block_words: int, reference_device=None):
    """d. Every Pallas kernel on, compiled, against every kernel off on
    the same device and on ``reference_device`` (the host CPU)."""
    plat = integer_platform()
    off_cfg, _ = exactness_config(cfg, ssd, kernels=False)
    on_cfg, ssd = exactness_config(cfg, ssd, kernels=True)
    wl = integer_trace(
        on_cfg, ssd, requests=requests,
        span_us=int(rounds * on_cfg.poll_quantum_us),
    )
    check(integer_timestamps(on_cfg, ssd, plat),
          "exactness platform is not integer-valued")

    def run(c):
        st = engine.init_state(c, ssd, wl, block_words)
        runner = engine.make_runner(c, ssd, wl, plat, rounds)
        t0 = time.perf_counter()
        out = jax.block_until_ready(runner(st))
        return jax.device_get(out), time.perf_counter() - t0

    on, on_s = run(on_cfg)
    off, off_s = run(off_cfg)
    bad = diverged_leaves(on, off)
    check(not bad, f"kernels on vs off diverged in {bad}")
    ref_s = None
    if reference_device is not None:
        with jax.default_device(reference_device):
            ref, ref_s = run(off_cfg)
        bad = diverged_leaves(off, ref)
        check(not bad, f"device vs {reference_device.platform} diverged "
                       f"in {bad}")
    check(float(on.metrics.completed) > 0, "no request completed")
    dies_busy = int(np.sum(on.device.flash.chip_busy > 0))
    check(dies_busy > 0, "no flash die saw an event")
    return {
        "rounds": rounds,
        "dies_busy": dies_busy,
        "kernels_on_s": on_s,
        "kernels_off_s": off_s,
        "reference_s": ref_s,
        "completed": float(on.metrics.completed),
        "leaves_compared": len(jax.tree.leaves(on)),
    }


def phase_sanitize(cfg: EngineConfig, ssd: SSDConfig, wl, *, rounds: int,
                   block_words: int):
    """e. One checkify-sanitized invocation; any violated invariant
    raises out of the runner."""
    runner = engine.make_runner(cfg, ssd, wl, PlatformModel(), rounds,
                                sanitize=True)
    st = engine.init_state(cfg, ssd, wl, block_words)
    t0 = time.perf_counter()
    out = jax.block_until_ready(runner(st))
    check(float(out.metrics.completed) > 0, "no request completed")
    return {"compile_and_run_s": time.perf_counter() - t0,
            "completed": float(out.metrics.completed)}


def phase_sharded(cfg: EngineConfig, ssd: SSDConfig, wl, *, drives: int,
                  rounds: int, block_words: int, devices):
    """--chips 4: the array sharded over ``devices`` with
    ``make_sharded_array_runner`` against ``make_array_runner`` on the
    first device; final states must be equal.

    The one-device runs take each shard's drives in turn, so both sides
    run the same vmap width: XLA:TPU orders the f32 metric sums
    (``jnp.sum`` over an epoch) by the width of the vmapped batch, and a
    16-wide vmap differs from a 4-wide one in their last bits."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    plat = PlatformModel()
    mesh = Mesh(np.asarray(devices), ("dev",))
    states = engine.init_array_state(cfg, ssd, wl, drives, block_words)
    sharded = engine.make_sharded_array_runner(cfg, ssd, wl, plat, rounds,
                                               mesh=mesh)
    t0 = time.perf_counter()
    # The sharded runner consumes its input: give it a copy, since the
    # one-device runs below take their drives from ``states``.
    out_s = jax.block_until_ready(sharded(jax.device_put(
        engine.unalias(states), NamedSharding(mesh, P("dev"))
    )))
    sharded_s = time.perf_counter() - t0
    spans = {len(x.sharding.device_set) for x in jax.tree.leaves(out_s)}
    check(spans == {len(devices)},
          f"sharded leaves span {sorted(spans)} devices, "
          f"not {len(devices)}")
    single = engine.make_array_runner(cfg, ssd, wl, plat, rounds)
    per = drives // len(devices)
    t0 = time.perf_counter()
    parts = [
        jax.device_get(single(jax.device_put(
            jax.tree.map(lambda x: x[i:i + per], states), devices[0]
        )))
        for i in range(0, drives, per)
    ]
    single_s = time.perf_counter() - t0
    out_1 = jax.tree.map(lambda *xs: np.concatenate(xs), *parts)
    bad = diverged_leaves(out_s, out_1)
    check(not bad, f"sharded vs single-chip array diverged in {bad}")
    return {
        "drives": drives,
        "devices": len(devices),
        "sharded_compile_and_run_s": sharded_s,
        "single_chip_compile_and_run_s": single_s,
        "aggregate_virtual_miops": float(engine.aggregate_iops(out_s)) / 1e6,
        "leaves_compared": len(jax.tree.leaves(out_s)),
    }


# ---------------------------------------------------------------------------
# Driver.
# ---------------------------------------------------------------------------

def emit(name: str, report: dict) -> None:
    print(f"phase {name}: {json.dumps(report)}", flush=True)


def peak_bytes(device) -> "int | None":
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_one_chip(dev) -> None:
    cfg, ssd = phase_a_config()
    wl = WorkloadConfig(io_depth=IO_DEPTH)

    report, state = phase_engine(
        cfg, ssd, wl, rounds=ROUNDS, invocations=INVOCATIONS,
        block_words=BLOCK_WORDS, min_iops=0.95 * TARGET_IOPS,
    )
    emit("a engine", {**report, "peak_bytes_in_use": peak_bytes(dev)})

    flash = state.flash
    del state
    emit("c client", {
        **phase_client(cfg, ssd, flash, reads=CLIENT_READS),
        "peak_bytes_in_use": peak_bytes(dev),
    })
    del flash

    emit("b array", {
        **phase_array(
            cfg, ssd.replace(num_blocks=ARRAY_BLOCKS), wl,
            drives=ARRAY_DRIVES, rounds=ROUNDS, invocations=INVOCATIONS,
            block_words=ARRAY_WORDS, min_iops=MIN_ARRAY_IOPS,
        ),
        "cut": f"num_blocks {NUM_BLOCKS} -> {ARRAY_BLOCKS} per drive, "
               f"{ARRAY_WORDS}-word rows",
        "peak_bytes_in_use": peak_bytes(dev),
    })

    emit("d exactness", {
        **phase_exactness(
            cfg, ssd.replace(num_blocks=EXACT_BLOCKS),
            rounds=EXACT_ROUNDS, requests=EXACT_REQUESTS,
            block_words=EXACT_WORDS,
            reference_device=jax.devices("cpu")[0],
        ),
        "peak_bytes_in_use": peak_bytes(dev),
    })

    emit("e sanitize", {
        **phase_sanitize(cfg, ssd, wl, rounds=ROUNDS,
                         block_words=BLOCK_WORDS),
        "peak_bytes_in_use": peak_bytes(dev),
    })


def run_four_chips(devices) -> None:
    cfg, ssd = phase_a_config()
    emit("sharded", phase_sharded(
        cfg, ssd.replace(num_blocks=SHARDED_BLOCKS),
        WorkloadConfig(io_depth=IO_DEPTH), drives=SHARDED_DRIVES,
        rounds=ROUNDS, block_words=BLOCK_WORDS, devices=devices,
    ))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    print(f"device_kind: {dev.device_kind}; compile cache: "
          f"{enable_compile_cache()}", flush=True)
    print("wall-clock numbers below are smoke readings, not benchmark "
          "results", flush=True)
    if args.chips == 4:
        run_four_chips(devices[:4])
    else:
        run_one_chip(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
