"""Everything a run makes from ``--seed``: per-drive salts and the flash image.

The program generates its own closed-loop request stream from the
workload salt it is handed (``EngineState.salt``); the benchmark derives
that salt, and the key of each drive's flash image, from the seed here.
Both are 32-bit values passed to the device as data, so every seed runs
the same compiled programs.

Image row ``lba`` of a drive holds ``lba`` in word 0 (exact in f32 below
2^24 blocks), so a gathered row names its block, and seeded integer
values below 2^23 in the other words, so two blocks never share a row.
``image_rows`` (NumPy) is the plain reference's copy of what
``image_jnp`` builds on the device; both use the same integer hash.
"""
from __future__ import annotations

import numpy as np

MASK32 = 0xFFFFFFFF


def mix32(x: int) -> int:
    """The xorshift-multiply finalizer on a Python int (32 bits)."""
    x &= MASK32
    x = ((x ^ (x >> 16)) * 0x7FEB352D) & MASK32
    x = ((x ^ (x >> 15)) * 0x846CA68B) & MASK32
    return x ^ (x >> 16)


def drive_keys(seed: int, drives: int) -> tuple[np.ndarray, np.ndarray]:
    """(workload salts, image keys), each (drives,) uint32, from ``seed``.

    Any whole number is accepted; bits above 32 are folded in.
    """
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    base = mix32(seed & MASK32) ^ mix32((seed >> 32) + 0x51ED270B)
    salts = [mix32(base + d * 0x85EBCA6B) for d in range(drives)]
    keys = [mix32(s ^ 0x5BD1E995) for s in salts]
    return np.array(salts, np.uint32), np.array(keys, np.uint32)


def hash_np(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32)
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> np.uint32(15))) * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def image_rows(lba: np.ndarray, block_words: int, key: int) -> np.ndarray:
    """Rows ``lba`` of the image with ``key``, as (len(lba), W) f32."""
    lba = np.asarray(lba, np.int64)
    j = np.arange(block_words, dtype=np.uint32)[None, :]
    with np.errstate(over="ignore"):
        idx = lba.astype(np.uint32)[:, None] * np.uint32(block_words) + j
        h = hash_np(idx ^ np.uint32(key))
    rows = (h >> np.uint32(9)).astype(np.float32)
    rows[:, 0] = lba.astype(np.float32)
    return rows


def image_jnp(num_blocks: int, block_words: int, key):
    """The whole image with ``key`` (a traced uint32 scalar), on device."""
    import jax
    import jax.numpy as jnp

    lba = jax.lax.broadcasted_iota(jnp.uint32, (num_blocks, block_words), 0)
    j = jax.lax.broadcasted_iota(jnp.uint32, (num_blocks, block_words), 1)
    x = (lba * jnp.uint32(block_words) + j) ^ key
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return jnp.where(j == 0, lba, x >> 9).astype(jnp.float32)
