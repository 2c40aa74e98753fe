"""The windowed data path moves the same bits as the full-width one.

``make_runner`` gathers and scatters only the epoch's valid rows, in
chunks of ``datapath.window_rows`` rows walked by a loop whose trip count
is set on the device (``datapath.Window``). The window rests on one
promise of the frontend: within each SQ's block of ``fetch_width`` rows,
the valid rows are a prefix. These tests hold the window to the
full-width form row for row, the single-drive runner to the array runner
(which keeps the full-width form) over whole runs, and the frontend to
its promise.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import datapath, engine, frontend
from repro.core.device import DevicePipeline
from repro.core.frontend import SQRings
from repro.core.types import (
    EngineConfig,
    PlatformModel,
    RequestBatch,
    SSDConfig,
    WorkloadConfig,
)

Q, F, W = 8, 64, 128          # N = 512 rows, four chunks at most
N = Q * F
BLOCKS, WORDS = 4096, 8
PLAT = PlatformModel()
# 4 MIOPS x 10 us: 40 rows a round, so W = 128 < N. With io_depth 48 the
# rounds fetch from 0 rows to 383 (four chunks).
SSD = SSDConfig(num_blocks=BLOCKS, t_max_iops=4e6, n_instances=16,
                l_min_us=10.0)
ROUNDS = 64


def engine_cfg(**kw) -> EngineConfig:
    return EngineConfig(num_sqs=Q, sq_depth=128, fetch_width=F,
                        num_units=4, num_bufs=N, emulate_data=True, **kw)


def prefix_batch(n_valid: int, seed: int) -> RequestBatch:
    """N rows, SQ-major, ``n_valid`` of them valid as per-SQ prefixes;
    mixed reads and writes, distinct LBAs and distinct buffers."""
    rng = np.random.default_rng(seed)
    counts = np.zeros(Q, int)
    for _ in range(n_valid):     # one row at a time to a random open SQ
        counts[rng.choice(np.flatnonzero(counts < F))] += 1
    valid = np.arange(F)[None, :] < counts[:, None]
    return RequestBatch(
        arrival=jnp.zeros((N,), jnp.float32),
        sq_id=jnp.repeat(jnp.arange(Q, dtype=jnp.int32), F),
        slot=jnp.tile(jnp.arange(F, dtype=jnp.int32), Q),
        opcode=jnp.asarray(rng.integers(0, 2, N), jnp.int32),
        lba=jnp.asarray(rng.permutation(BLOCKS)[:N], jnp.int32),
        nblocks=jnp.ones((N,), jnp.int32),
        buf_id=jnp.asarray(rng.permutation(N), jnp.int32),
        req_id=jnp.arange(N, dtype=jnp.int32),
        valid=jnp.asarray(valid.reshape(-1)),
    )


def image(seed: int):
    rng = np.random.default_rng(seed)
    flash = jnp.asarray(rng.standard_normal((BLOCKS, WORDS)), jnp.float32)
    bufs = jnp.asarray(rng.standard_normal((N, WORDS)), jnp.float32)
    return flash, bufs


COUNTS = [0, 1, W - 1, W, W + 1, N]


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("n_valid", COUNTS)
def test_window_moves_what_the_full_width_form_moves(n_valid, use_pallas):
    batch = prefix_batch(n_valid, seed=n_valid)
    flash, bufs = image(seed=n_valid + 1)
    win = datapath.data_window(batch, F, W)

    bufs_full = datapath.apply_reads(flash, bufs, batch, use_pallas)
    bufs_win = datapath.apply_reads(flash, bufs, batch, use_pallas,
                                    window=win)
    np.testing.assert_array_equal(np.asarray(bufs_win), np.asarray(bufs_full))

    flash_full = datapath.apply_writes(flash, bufs_full, batch)
    flash_win = datapath.apply_writes(flash, bufs_full, batch, window=win)
    np.testing.assert_array_equal(np.asarray(flash_win),
                                  np.asarray(flash_full))

    chunks = -(-n_valid // W)
    ops = np.asarray(batch.opcode)[np.asarray(batch.valid)]
    assert int(win.read_chunks) == (chunks if (ops == 0).any() else 0)
    assert int(win.write_chunks) == (chunks if (ops == 1).any() else 0)


@pytest.mark.parametrize("n_valid", COUNTS)
def test_window_chunks_list_the_valid_rows_in_batch_order(n_valid):
    batch = prefix_batch(n_valid, seed=n_valid)
    win = datapath.data_window(batch, F, W)
    rows = []
    for c in range(-(-N // W)):
        sub = win.chunk(batch, jnp.int32(c))
        rows += np.asarray(sub.req_id)[np.asarray(sub.valid)].tolist()
    assert rows == np.flatnonzero(np.asarray(batch.valid)).tolist()


def leaves_by_path(state):
    return {jax.tree_util.keystr(p): np.asarray(x)
            for p, x in jax.tree_util.tree_flatten_with_path(state)[0]}


@pytest.mark.parametrize("front", ["distributed", "centralized"])
def test_single_drive_runner_matches_the_array_runner(front):
    """``make_runner`` (windowed) and ``make_array_runner`` (full width)
    end in the same state, bit for bit, on a read/write mix; only the
    count of rows moved differs."""
    cfg = engine_cfg(frontend=front)
    wl = WorkloadConfig(io_depth=48, read_frac=0.7)
    assert datapath.window_rows(cfg, SSD) == W
    one = engine.make_runner(cfg, SSD, wl, PLAT, ROUNDS)(
        engine.init_state(cfg, SSD, wl)
    )
    arr = engine.make_array_runner(cfg, SSD, wl, PLAT, ROUNDS)(
        engine.init_array_state(cfg, SSD, wl, 2)
    )
    a = leaves_by_path(one)
    b = leaves_by_path(jax.tree.map(lambda x: x[0], arr))
    rows = ".metrics.data_rows"
    assert a.keys() == b.keys()
    for k in a.keys() - {rows}:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    assert float(b[rows]) == 2 * N * ROUNDS
    assert 0 < float(a[rows]) < float(b[rows])


@pytest.mark.parametrize("read_frac", [1.0, 0.0], ids=["reads", "writes"])
def test_data_rows_counts_whole_chunks(read_frac):
    """With one kind of request, one loop runs ``ceil(fetched / W)``
    chunks a round and the other none."""
    cfg = engine_cfg()
    wl = WorkloadConfig(io_depth=48, read_frac=read_frac)
    step = engine.make_runner(cfg, SSD, wl, PLAT, 1)
    state = engine.init_state(cfg, SSD, wl)
    fetched = []
    for _ in range(32):
        before = float(state.metrics.fetched)
        state = step(state)
        fetched.append(int(float(state.metrics.fetched) - before))
    assert max(fetched) > W
    expected = sum(-(-k // W) * W for k in fetched)
    assert float(state.metrics.data_rows) == expected


def random_rings(rng) -> SQRings:
    """Rings holding a random number of entries per SQ, posted at
    random times (non-decreasing within each SQ)."""
    depth = 128
    t = np.sort(rng.uniform(0.0, 20.0, (Q, depth)), axis=1)
    n = rng.integers(0, depth, Q)
    valid = np.arange(depth)[None, :] < n[:, None]
    z = jnp.zeros((Q, depth), jnp.int32)
    return frontend.submit_grouped(
        SQRings.empty(Q, depth), jnp.asarray(t, jnp.float32), z, z,
        z + 1, z, z, jnp.asarray(valid),
    )


@pytest.mark.parametrize("fetch", [frontend.fetch_distributed,
                                   frontend.fetch_centralized],
                         ids=lambda f: f.__name__)
def test_fetched_validity_is_a_per_sq_prefix(fetch):
    cfg = engine_cfg(frontend=fetch.__name__.removeprefix("fetch_"))
    disp = DevicePipeline(cfg, SSD, PLAT).init_state().disp_time
    rng = np.random.default_rng(5)
    seen = 0
    for _ in range(6):
        rings = random_rings(rng)
        for clock in (2.0, 6.0, 11.0, 19.0):
            rings, _, batch, _ = fetch(rings, jnp.float32(clock),
                                       disp, cfg, PLAT)
            valid = np.asarray(batch.valid).reshape(Q, F)
            assert (valid[:, 1:] <= valid[:, :-1]).all()
            seen += int(valid.sum())
    assert seen > 0


def compiled_whiles(make, cfg, wl, state) -> list[str]:
    """The ``op_name`` of every ``while`` of the compiled runner."""
    hlo = make(cfg, SSD, wl, PLAT, 2).lower(state).compile().as_text()
    return re.findall(r'^\s+%while[.\d]* = .* while\(.*op_name="([^"]*)"',
                      hlo, re.M)


def test_only_the_single_drive_runner_loops_over_the_window():
    """``make_runner``'s round adds exactly two loops to the array
    runner's, the read loop and the write loop; the array runner has no
    data-path loop."""
    cfg = engine_cfg()
    wl = WorkloadConfig(io_depth=48, read_frac=0.7)
    one = compiled_whiles(engine.make_runner, cfg, wl,
                          engine.init_state(cfg, SSD, wl))
    arr = compiled_whiles(engine.make_array_runner, cfg, wl,
                          engine.init_array_state(cfg, SSD, wl, 2))
    data = sorted(n.split("/")[-2] for n in one if "stage.data_" in n)
    assert data == ["stage.data_read", "stage.data_write"]
    assert not [n for n in arr if "stage.data_" in n]
    assert len(one) == len(arr) + 2
