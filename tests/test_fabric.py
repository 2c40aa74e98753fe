"""Fabric/interconnect layer tests (remote all-flash arrays).

Contracts under test:
  * the neutral default (``remote=False``) and a zero-cost remote wire
    are *exact* no-ops — engine and client completion times reproduce
    the fabric-less pipeline bit-exactly (the acceptance parity bar);
  * fabric serialization is monotone: lower link bandwidth (or added
    RTT) never decreases any completion time;
  * MTU batching holds early frames for the flush and the timeout
    bounds the wait;
  * replica reads route around a placement-skewed batch via the
    least-loaded link;
  * ``make_sharded_array_runner`` (shard_map) matches the vmap array
    runner bit-exactly on a 1-device mesh, and consumes (donates) its
    input.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import engine
from repro.core.client import StorageClient
from repro.core.fabric import fabric_hop
from repro.core.types import (
    EngineConfig,
    FabricConfig,
    PlatformModel,
    SSDConfig,
    WorkloadConfig,
)

SSD = SSDConfig(t_max_iops=2.47e6, l_min_us=50.0, n_instances=64,
                num_blocks=1 << 12)
CFG = EngineConfig(num_sqs=8, sq_depth=256, fetch_width=32, num_units=4,
                   emulate_data=False, num_bufs=512)

ZERO_COST = FabricConfig(remote=True)  # remote, but a free wire


def _flash_store(words=8):
    return jnp.arange(SSD.num_blocks, dtype=jnp.float32)[:, None] * jnp.ones(
        (1, words)
    )


# ---------------------------------------------------------------------------
# Unit-level hop behavior.
# ---------------------------------------------------------------------------

def test_zero_cost_hop_is_identity():
    """Unconstrained bandwidth, zero RTT/txn, no batching: frames land
    at their ready times and the link cursor never moves — across
    multiple epochs (later epochs may carry earlier-timed frames)."""
    busy = jnp.float32(0)
    for t0 in (100.0, 10.0):  # second epoch is *earlier* than the first
        t = t0 + jnp.arange(16, dtype=jnp.float32)
        nbytes = jnp.full((16,), 576.0)
        busy, out = fabric_hop(
            busy, t, nbytes, jnp.ones((16,), bool), ZERO_COST, float("inf")
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(t))
        assert float(jnp.max(busy)) == 0.0


def test_finite_bandwidth_serializes():
    """N frames of B bytes on one link: the last lands no earlier than
    first_ready + N*B/bw, and the cursor advances accordingly."""
    n, b, bw = 32, 528.0, 1000.0
    fab = FabricConfig(remote=True, tx_bytes_per_us=bw, rx_bytes_per_us=bw)
    t = jnp.zeros((n,), jnp.float32)
    busy, out = fabric_hop(
        jnp.float32(0), t, jnp.full((n,), b), jnp.ones((n,), bool), fab, bw
    )
    assert float(jnp.max(out)) == pytest.approx(n * b / bw, rel=1e-5)
    assert float(jnp.max(busy)) == pytest.approx(n * b / bw, rel=1e-5)
    # Streaming: frame k lands after (k+1) frames' bytes, not all at once.
    np.testing.assert_allclose(
        np.sort(np.asarray(out)),
        (np.arange(n) + 1) * b / bw,
        rtol=1e-5,
    )


def test_mtu_batching_waits_for_flush_and_timeout_bounds_it():
    n = 16
    t = jnp.arange(n, dtype=jnp.float32)  # 1 us apart
    ones = jnp.ones((n,), bool)
    nbytes = jnp.full((n,), 64.0)
    fab = FabricConfig(remote=True, mtu_batch=4, mtu_timeout_us=1e6)
    _, out = fabric_hop(jnp.float32(0), t, nbytes, ones, fab, float("inf"))
    r = np.asarray(out).reshape(4, 4)
    # Every member of an MTU batch waits for the batch's last frame.
    np.testing.assert_allclose(r, r[:, -1:].repeat(4, axis=1), rtol=1e-6)
    # A tight timeout caps the wait.
    fab_t = FabricConfig(remote=True, mtu_batch=4, mtu_timeout_us=1.5)
    _, out_t = fabric_hop(jnp.float32(0), t, nbytes, ones, fab_t,
                          float("inf"))
    assert (np.asarray(out_t) <= np.asarray(t) + 1.5 + 1e-5).all()


def test_invalid_rows_pass_through_untouched():
    n = 12
    t = jnp.arange(n, dtype=jnp.float32)
    valid = (jnp.arange(n) % 2 == 0)
    fab = FabricConfig(remote=True, rtt_us=8.0, tx_bytes_per_us=100.0,
                       rx_bytes_per_us=100.0)
    _, out = fabric_hop(
        jnp.float32(0), t, jnp.full((n,), 64.0), valid, fab, 100.0
    )
    np.testing.assert_array_equal(
        np.asarray(out)[1::2], np.asarray(t)[1::2]
    )
    assert (np.asarray(out)[::2] > np.asarray(t)[::2]).all()


def test_fabric_config_validation_and_neutrality():
    with pytest.raises(ValueError, match="mtu_batch"):
        FabricConfig(mtu_batch=0)
    with pytest.raises(ValueError, match="bytes_per_us"):
        FabricConfig(rx_bytes_per_us=0.0)
    with pytest.raises(ValueError, match="rtt_us"):
        FabricConfig(rtt_us=-1.0)
    assert FabricConfig().neutral
    assert ZERO_COST.neutral
    assert FabricConfig(remote=True, mtu_batch=8).neutral  # timeout 0
    assert not FabricConfig(remote=True, rtt_us=1.0).neutral
    assert not FabricConfig(remote=True, rx_bytes_per_us=1e4).neutral


# ---------------------------------------------------------------------------
# Parity: local drive == remote drive behind a zero-cost wire, bit-exact.
# ---------------------------------------------------------------------------

def test_engine_parity_zero_cost_wire_bit_exact():
    """The fabric stage on a free wire reproduces the local pipeline
    bit-exactly over many engine rounds — metrics and device state."""
    wl = WorkloadConfig(io_depth=32)
    local = engine.simulate(CFG, SSD, wl, rounds=24)
    remote = engine.simulate(
        CFG.replace(fabric=ZERO_COST), SSD, wl, rounds=24
    )
    for got, want in [
        (remote.metrics.sum_e2e, local.metrics.sum_e2e),
        (remote.metrics.lat_hist, local.metrics.lat_hist),
        (remote.metrics.last_completion, local.metrics.last_completion),
        (remote.device.tstate.busy_until, local.device.tstate.busy_until),
        (remote.device.dsa_time, local.device.dsa_time),
    ]:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # The free wire really occupied no link time.
    assert float(jnp.max(remote.device.fabric.tx_busy)) == 0.0
    assert float(jnp.max(remote.device.fabric.rx_busy)) == 0.0


def test_client_parity_zero_cost_wire_bit_exact():
    flash = _flash_store()
    lba = (jnp.arange(512, dtype=jnp.int32) * 37) % SSD.num_blocks
    cfg = EngineConfig(num_units=4, fetch_width=64)
    local = StorageClient(SSD, cfg)
    remote = StorageClient(SSD, cfg.replace(fabric=ZERO_COST))
    _, _, dl = local.read(local.init_state(), flash, lba, jnp.float32(3.0))
    _, _, dr = remote.read(remote.init_state(), flash, lba, jnp.float32(3.0))
    np.testing.assert_array_equal(np.asarray(dl), np.asarray(dr))


def test_engine_parity_mixed_writes_zero_cost_wire():
    """Parity holds through the flash backend too (writes change the TX
    payload bytes, but a free wire still prices them at zero)."""
    from repro import workloads

    wl = workloads.MixedReadWrite(io_depth=16, read_frac=0.7)
    local = engine.simulate(CFG, SSD, wl, rounds=16)
    remote = engine.simulate(
        CFG.replace(fabric=ZERO_COST), SSD, wl, rounds=16
    )
    np.testing.assert_array_equal(
        np.asarray(local.metrics.lat_hist),
        np.asarray(remote.metrics.lat_hist),
    )


# ---------------------------------------------------------------------------
# Monotonicity: a worse wire never helps.
# ---------------------------------------------------------------------------

def test_lower_bandwidth_never_decreases_any_completion():
    flash = _flash_store()
    lba = (jnp.arange(384, dtype=jnp.int32) * 29) % SSD.num_blocks
    cfg = EngineConfig(num_units=4, fetch_width=64)
    prev = None
    for bw in [float("inf"), 8000.0, 2000.0, 500.0]:
        fab = FabricConfig(remote=True, rtt_us=4.0, tx_bytes_per_us=bw,
                           rx_bytes_per_us=bw, wire_txn_us=0.2,
                           mtu_batch=8, mtu_timeout_us=20.0)
        client = StorageClient(SSD, cfg.replace(fabric=fab))
        _, _, done = client.read(
            client.init_state(), flash, lba, jnp.float32(0)
        )
        done = np.asarray(done)
        if prev is not None:
            assert (done >= prev - 1e-5).all(), bw
        prev = done


def test_rtt_adds_full_round_trip_to_an_idle_read():
    flash = _flash_store()
    lba = jnp.arange(8, dtype=jnp.int32)
    cfg = EngineConfig(num_units=4, fetch_width=64)
    base = StorageClient(SSD, cfg.replace(fabric=ZERO_COST))
    lag = StorageClient(
        SSD, cfg.replace(fabric=FabricConfig(remote=True, rtt_us=30.0))
    )
    _, _, d0 = base.read(base.init_state(), flash, lba, jnp.float32(0))
    _, _, d1 = lag.read(lag.init_state(), flash, lba, jnp.float32(0))
    np.testing.assert_allclose(
        np.asarray(d1 - d0), 30.0, rtol=1e-5
    )


def test_engine_fabric_limited_regime_is_monotone():
    """Engine closed loop: sustained IOPS never increases as the link
    narrows, and a hard-clamped link lands near its frame roof."""
    wl = WorkloadConfig(io_depth=256)
    ssd = SSDConfig(t_max_iops=1e7, l_min_us=30.0, n_instances=256,
                    num_blocks=1 << 12)
    iops = []
    for bw in [float("inf"), 4000.0, 1000.0]:
        fab = FabricConfig(remote=True, tx_bytes_per_us=bw,
                           rx_bytes_per_us=bw)
        out = engine.simulate(
            CFG.replace(fabric=fab), ssd, wl, rounds=24
        )
        iops.append(float(out.metrics.iops()))
    assert iops[0] >= iops[1] >= iops[2]
    frame = FabricConfig().cqe_bytes + ssd.block_bytes
    roof = 1000.0 / frame * 1e6
    assert iops[2] == pytest.approx(roof, rel=0.25)


# ---------------------------------------------------------------------------
# Replicated reads over remote links.
# ---------------------------------------------------------------------------

def test_replica_read_spreads_skewed_batch_over_links():
    """All blocks homed on drive 0: replicas=1 serializes on one link,
    replicas=M re-engages the others and cuts the makespan."""
    m, n = 4, 256
    fab = FabricConfig(remote=True, rtt_us=5.0, tx_bytes_per_us=8000.0,
                       rx_bytes_per_us=2000.0)
    client = StorageClient(
        SSD, EngineConfig(num_units=4, fetch_width=64,
                          fabric=fab)
    )
    flash = _flash_store()
    skew = ((jnp.arange(n, dtype=jnp.int32) * 13) % SSD.num_blocks) \
        // m * m  # every lba % m == 0
    state = client.init_array_state(m)
    _, _, d1 = client.read_replicated(
        state, flash, skew, jnp.float32(0), replicas=1
    )
    _, _, dm = client.read_replicated(
        state, flash, skew, jnp.float32(0), replicas=m
    )
    assert float(jnp.max(dm)) < 0.6 * float(jnp.max(d1))


def test_replica_read_matches_striped_for_uniform_single_replica():
    """replicas=1 routes every block to its home drive (lba % M) — the
    same placement as an lba-keyed stripe; completions stay a
    permutation-free match on a round-robin-homed batch."""
    m, n = 4, 512
    cfg = EngineConfig(num_units=4, fetch_width=64)
    client = StorageClient(SSD, cfg)
    flash = _flash_store()
    # lba ≡ i (mod m): home drive of request i == i % m, so replicas=1
    # placement coincides with read_striped's fixed interleave.
    lba = (jnp.arange(n, dtype=jnp.int32) * (m + 1)) % SSD.num_blocks
    state = client.init_array_state(m)
    _, _, ds = client.read_striped(state, flash, lba, jnp.float32(0))
    _, _, dr = client.read_replicated(
        state, flash, lba, jnp.float32(0), replicas=1
    )
    np.testing.assert_array_equal(np.asarray(ds), np.asarray(dr))


def test_replicas_validation():
    client = StorageClient(SSD, EngineConfig(num_units=4, fetch_width=64))
    state = client.init_array_state(2)
    with pytest.raises(ValueError, match="replicas"):
        client.read_replicated(
            state, _flash_store(), jnp.arange(8, dtype=jnp.int32),
            jnp.float32(0), replicas=3,
        )


# ---------------------------------------------------------------------------
# shard_map array runner.
# ---------------------------------------------------------------------------

def test_sharded_array_runner_matches_vmap_on_single_device_mesh():
    wl = WorkloadConfig(io_depth=16)
    plat = PlatformModel()
    states = engine.init_array_state(CFG, SSD, wl, 4)
    vm = engine.make_array_runner(CFG, SSD, wl, plat, 12)(states)
    sh = engine.make_sharded_array_runner(CFG, SSD, wl, plat, 12)(states)
    for a, b in zip(jax.tree.leaves(vm), jax.tree.leaves(sh)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_array_runner_donates_its_state():
    """The sharded runner consumes its input: every leaf of the stacked
    state aliases an output in the lowered program, the input is deleted
    by the call, and the result still equals the vmap runner's bit for
    bit on a 1-device mesh."""
    wl = WorkloadConfig(io_depth=16)
    plat = PlatformModel()
    states = engine.init_array_state(CFG, SSD, wl, 4)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dev",))
    sharded = engine.make_sharded_array_runner(CFG, SSD, wl, plat, 12,
                                               mesh=mesh)
    leaves = len(jax.tree.leaves(states))
    assert sharded.lower(states).as_text().count(
        "tf.aliasing_output") == leaves
    vm = engine.make_array_runner(CFG, SSD, wl, plat, 12)(states)
    given = engine.unalias(states)
    sh = sharded(given)
    assert all(x.is_deleted() for x in jax.tree.leaves(given))
    assert not any(x.is_deleted() for x in jax.tree.leaves(states))
    for a, b in zip(jax.tree.leaves(vm), jax.tree.leaves(sh)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >= 2 JAX devices (e.g. XLA_FLAGS="
           "--xla_force_host_platform_device_count=2)",
)
def test_sharded_array_runner_multi_device():
    wl = WorkloadConfig(io_depth=16)
    plat = PlatformModel()
    states = engine.init_array_state(CFG, SSD, wl, 4)
    vm = engine.make_array_runner(CFG, SSD, wl, plat, 8)(states)
    sh = engine.make_sharded_array_runner(CFG, SSD, wl, plat, 8)(states)
    np.testing.assert_allclose(
        np.asarray(vm.metrics.completed), np.asarray(sh.metrics.completed)
    )


# ---------------------------------------------------------------------------
# Remote arrays end to end.
# ---------------------------------------------------------------------------

def test_remote_array_vmaps_per_drive_links():
    """An M-drive remote array carries one pair of link cursors per
    drive, and a constrained link shows up in every drive's cursor."""
    fab = FabricConfig(remote=True, rx_bytes_per_us=1000.0,
                       tx_bytes_per_us=8000.0)
    arr = engine.simulate(
        CFG.replace(fabric=fab), SSD, WorkloadConfig(io_depth=32),
        rounds=12, num_devices=3,
    )
    # (M, T) stacked cursors: one per-tenant vector per drive (T=1 here).
    rx = np.asarray(arr.device.fabric.rx_busy)
    assert rx.shape == (3, 1)
    assert (rx > 0.0).all()


def test_fabric_composes_with_non_neutral_qp():
    """RX hop then CQ coalescing: reaped >= wire-delayed done and the
    run still completes (the two layers stack without conflict)."""
    from repro.core.types import QPConfig

    fab = FabricConfig(remote=True, rtt_us=5.0, rx_bytes_per_us=2000.0,
                       tx_bytes_per_us=8000.0)
    qp = QPConfig(cq_coalesce_n=4, cq_coalesce_us=40.0, cq_doorbell_us=0.5)
    out = engine.simulate(
        CFG.replace(fabric=fab, qp=qp), SSD,
        WorkloadConfig(io_depth=32), rounds=16,
    )
    assert float(out.metrics.completed) > 0
    assert np.isfinite(float(out.metrics.avg_e2e_us()))
