"""Per-kernel allclose vs the pure-jnp oracle, sweeping shapes/dtypes.

All kernels run in interpret mode on CPU (the kernel body itself executes,
BlockSpec pipeline included); on TPU the same entry points compile natively.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.block_gather import block_gather
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.seg_scan import seg_scan


# ---------------------------------------------------------------------------
# block_gather
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nblocks,width,n", [
    (64, 128, 32), (256, 128, 256), (128, 256, 64), (32, 512, 8),
    (64, 16, 1000), (4096, 128, 1500),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_gather(nblocks, width, n, dtype):
    key = jax.random.PRNGKey(0)
    flash = jax.random.normal(key, (nblocks, width), dtype=dtype)
    idx = jax.random.randint(jax.random.PRNGKey(1), (n,), 0, nblocks)
    out = block_gather(flash, idx, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.block_gather_ref(flash, idx))
    )


def test_block_gather_refuses_narrow_rows_when_compiled():
    """A compiled row DMA needs whole 128-lane rows: a 16-word image is
    refused with an error naming the kernel, not copied per call."""
    flash = jnp.zeros((64, 16), jnp.float32)
    with pytest.raises(ValueError, match="block_gather"):
        block_gather(flash, jnp.zeros((8,), jnp.int32), interpret=False)


@pytest.mark.parametrize("batch", [8, 64])
def test_block_gather_batches(batch):
    """Descriptor batches per grid step: ragged tails pad and slice off."""
    flash = jax.random.normal(jax.random.PRNGKey(2), (64, 128))
    idx = jax.random.randint(jax.random.PRNGKey(3), (100,), 0, 64)
    out = block_gather(flash, idx, batch=batch, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.block_gather_ref(flash, idx))
    )


# ---------------------------------------------------------------------------
# seg_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,block_rows", [
    (16, 8), (3000, 8), (100, 256), (1, 8), (20000, 64),
])
def test_seg_scan(n, block_rows):
    rng = np.random.default_rng(n)
    vals = rng.uniform(-100, 100, n).astype(np.float32)
    heads = rng.random(n) < 0.2
    heads[0] = True
    out = seg_scan(jnp.asarray(vals), jnp.asarray(heads),
                   block_rows=block_rows, interpret=True)
    expect = ref.seg_scan_ref(jnp.asarray(vals), jnp.asarray(heads))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-6)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # (B, Hq, Hkv, S, D, causal, window, softcap)
    (1, 4, 4, 128, 64, True, None, None),        # MHA causal
    (2, 8, 2, 128, 64, True, None, None),        # GQA 4:1
    (1, 4, 1, 256, 32, True, None, None),        # MQA
    (1, 4, 4, 256, 64, True, 64, None),          # local window
    (1, 4, 2, 128, 64, True, None, 50.0),        # logit softcap (gemma2)
    (1, 8, 2, 256, 64, True, 128, 30.0),         # local + softcap
    (1, 2, 2, 128, 128, False, None, None),      # bidirectional
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(case, dtype):
    b, hq, hkv, s, d, causal, window, cap = case
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype=dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype=dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype=dtype)
    out = flash_attention(
        q, k, v, causal=causal, window=window, logit_softcap=cap,
        block_q=64, block_k=64, interpret=True,
    )
    expect = ref.attention_ref(
        q, k, v, causal=causal, window=window, logit_softcap=cap
    )
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_attention_blocksize_invariance():
    """Same result across block shapes (pipeline correctness)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    outs = [
        np.asarray(flash_attention(q, k, v, block_q=bq, block_k=bk,
                                   interpret=True))
        for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------

DECODE_CASES = [
    # (B, Hq, Hkv, S, D, window, softcap)
    (2, 4, 4, 256, 64, None, None),
    (2, 8, 2, 256, 64, None, None),
    (1, 4, 1, 512, 32, None, None),
    (2, 4, 2, 256, 64, 64, None),
    (1, 4, 4, 256, 64, None, 50.0),
]


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(case, dtype):
    b, hq, hkv, s, d, window, cap = case
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (b, hq, d), dtype=dtype)
    kc = jax.random.normal(ks[1], (b, hkv, s, d), dtype=dtype)
    vc = jax.random.normal(ks[2], (b, hkv, s, d), dtype=dtype)
    lengths = jnp.asarray([s // 2, s][:b] if b <= 2 else [s] * b, jnp.int32)
    out = decode_attention(
        q, kc, vc, lengths, window=window, logit_softcap=cap,
        block_k=64, interpret=True,
    )
    expect = ref.decode_attention_ref(
        q, kc, vc, lengths, window=window, logit_softcap=cap
    )
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=tol, atol=tol,
    )


def test_decode_attention_short_lengths():
    """Blocks past `length` must be skipped, not just masked."""
    b, hq, hkv, s, d = 3, 4, 2, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    kc = jax.random.normal(ks[1], (b, hkv, s, d))
    vc = jax.random.normal(ks[2], (b, hkv, s, d))
    lengths = jnp.asarray([1, 65, 512], jnp.int32)
    out = decode_attention(q, kc, vc, lengths, block_k=64, interpret=True)
    expect = ref.decode_attention_ref(q, kc, vc, lengths)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5
    )
