"""Public jit'd wrappers for the Pallas kernels.

On a TPU backend the kernels compile natively. On the CPU backend (the
test suite) they run in ``interpret=True`` mode, which executes the
kernel body against the same BlockSpec pipeline — the correctness
contract tested against ref.py holds in both modes. Any other backend
is an error: a kernel never falls back to the interpreter or to a
reference path in silence.
"""
from __future__ import annotations

import jax

from repro.kernels import block_gather as _bg
from repro.kernels import decode_attention as _da
from repro.kernels import die_contention as _dc
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_reap as _fr
from repro.kernels import seg_scan as _ss


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels compile for TPU and are interpreted on CPU; "
            f"the {backend!r} backend has neither path"
        )
    return backend == "cpu"


def block_gather(flash, idx):
    return _bg.block_gather(flash, idx, interpret=_interpret())


def seg_scan(values, heads):
    return _ss.seg_scan(values, heads, interpret=_interpret())


def fused_reap(done_time, visible_time, req_id_ring, tail, key, done,
               req_id, valid):
    return _fr.fused_reap(
        done_time, visible_time, req_id_ring, tail, key, done, req_id,
        valid, interpret=_interpret(),
    )


def die_contention(ready, cost, chip, event, chip_busy):
    return _dc.die_contention(
        ready, cost, chip, event, chip_busy, interpret=_interpret()
    )


def flash_attention(q, k, v, **kw):
    kw.setdefault("interpret", _interpret())
    return _fa.flash_attention(q, k, v, **kw)


def decode_attention(q, k_cache, v_cache, lengths, **kw):
    kw.setdefault("interpret", _interpret())
    return _da.decode_attention(q, k_cache, v_cache, lengths, **kw)
