"""Attention: GQA projections + flash-style chunked jnp path (dry-run/CPU)
or the Pallas kernels (TPU), with RoPE / M-RoPE, local windows, softcap,
and a KV-cache decode path.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models import layers
from repro.models.config import ATTN_LOCAL, ModelConfig

NEG = -3e38  # python float: jnp module constants leak into jaxprs


def attn_init(key: jax.Array, cfg: ModelConfig, dtype) -> tuple[dict, dict]:
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (d, hq * dh), dtype) * s,
        "wk": jax.random.normal(ks[1], (d, hkv * dh), dtype) * s,
        "wv": jax.random.normal(ks[2], (d, hkv * dh), dtype) * s,
        "wo": jax.random.normal(ks[3], (hq * dh, d), dtype) * (hq * dh) ** -0.5,
    }
    a = {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv_heads"),
        "wv": ("embed", "kv_heads"),
        "wo": ("heads", "embed"),
    }
    if cfg.use_bias or cfg.qkv_bias:
        p.update(
            bq=jnp.zeros((hq * dh,), dtype),
            bk=jnp.zeros((hkv * dh,), dtype),
            bv=jnp.zeros((hkv * dh,), dtype),
        )
        a.update(bq=("heads",), bk=("kv_heads",), bv=("kv_heads",))
    if cfg.use_bias:
        p["bo"] = jnp.zeros((d,), dtype)
        a["bo"] = ("embed",)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((dh,), dtype)
        p["k_norm"] = jnp.zeros((dh,), dtype)
        a["q_norm"] = ("head_dim",)
        a["k_norm"] = ("head_dim",)
    return p, a


def _project_qkv(params, x, cfg: ModelConfig, positions, mrope_positions):
    b, s, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.use_bias or cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(b, s, hq, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, hkv, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, hkv, dh).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = layers.rms_norm(q, params["q_norm"])
        k = layers.rms_norm(k, params["k_norm"])
    if cfg.rope == "rope":
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope == "mrope":
        q = layers.apply_mrope(q, mrope_positions, cfg.mrope_sections,
                               cfg.rope_theta)
        k = layers.apply_mrope(k, mrope_positions, cfg.mrope_sections,
                               cfg.rope_theta)
    q = constrain(q, ("batch", "heads", "seq", "head_dim"))
    k = constrain(k, ("batch", "kv_heads", "seq", "head_dim"))
    v = constrain(v, ("batch", "kv_heads", "seq", "head_dim"))
    return q, k, v


def _flash_jnp(
    q, k, v, *, causal, window, cap, scale, q_chunk, kv_chunk
):
    """Memory-bounded flash-style attention in pure jnp.

    lax.map over query chunks; inside, lax.scan over kv chunks with an
    online-softmax carry. Peak live memory is O(B·H·q_chunk·kv_chunk),
    independent of S² — which is what lets 32k-token prefill lower within
    HBM in the dry-run.
    """
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    q_chunk = min(q_chunk, s)
    kv_chunk = min(kv_chunk, s)
    assert s % q_chunk == 0 and s % kv_chunk == 0
    nq, nk = s // q_chunk, s // kv_chunk
    kg = k.reshape(b, hkv, nk, kv_chunk, d)
    vg = v.reshape(b, hkv, nk, kv_chunk, d)

    def one_q_chunk(iq):
        qc = jax.lax.dynamic_slice_in_dim(q, iq * q_chunk, q_chunk, axis=2)
        qc = qc.reshape(b, hkv, group, q_chunk, d).astype(jnp.float32) * scale
        rows = iq * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, ik):
            m, l, acc = carry
            kc = jax.lax.dynamic_index_in_dim(kg, ik, axis=2, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(vg, ik, axis=2, keepdims=False)
            sc = jnp.einsum(
                "bhgqd,bhkd->bhgqk", qc, kc.astype(jnp.float32)
            )
            if cap is not None:
                sc = layers.softcap(sc, cap)
            cols = ik * kv_chunk + jnp.arange(kv_chunk)
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= cols[None, :] <= rows[:, None]
            if window is not None:
                mask &= cols[None, :] > rows[:, None] - window
            sc = jnp.where(mask, sc, NEG)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, vc.astype(jnp.float32)
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, group, q_chunk, 1), NEG)
        l0 = jnp.zeros((b, hkv, group, q_chunk, 1))
        a0 = jnp.zeros((b, hkv, group, q_chunk, d))
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), jnp.arange(nk)
        )
        out = acc / jnp.where(l > 0, l, 1.0)
        return out.reshape(b, hq, q_chunk, d)

    if nq == 1:
        out = one_q_chunk(0)
    else:
        out = jax.lax.map(one_q_chunk, jnp.arange(nq))      # (nq, b, hq, qc, d)
        out = jnp.moveaxis(out, 0, 2).reshape(b, hq, s, d)
    return out.astype(q.dtype)


def _flash_core(q, k, v, cfg: ModelConfig, window, scale):
    """Flash attention on *local* tensors (no sharded dims inside)."""
    if cfg.use_pallas:
        from repro.kernels import ops as kops

        return kops.flash_attention(
            q, k, v, causal=True, window=window,
            logit_softcap=cfg.attn_softcap, scale=scale,
        )
    from repro.models.flash_vjp import flash_attention_jnp

    s_len = q.shape[2]
    return flash_attention_jnp(
        q, k, v, True, window, cfg.attn_softcap, scale,
        min(cfg.attn_chunk, s_len), min(cfg.attn_chunk, s_len),
    )


def _sharded_flash(q, k, v, cfg: ModelConfig, window, scale):
    """Tensor-parallel flash attention via explicit shard_map.

    GSPMD cannot partition the chunked flash loops (reshapes + dynamic
    slices over sharded seq/head dims trigger involuntary full
    rematerialization — measured 6.4 GB/device replicated score tensors on
    command-r train_4k). Instead: q heads are sharded over "model", K/V are
    replicated per shard (the GQA KV block is small), each shard expands
    its local q-heads' KV via the global head map and runs the flash core
    on fully local tensors.
    """
    from repro.distributed import sharding as shd

    ctx = shd.current_context()
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    group = hq // hkv
    if ctx is None:
        return _flash_core(q, k, v, cfg, window, scale)
    mesh, rules = ctx
    from jax.sharding import PartitionSpec as P

    dp = shd.spec_for(("batch",), rules, mesh, (q.shape[0],))[0]
    msize = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    if msize == 1 or hq % msize != 0:
        return _flash_core(q, k, v, cfg, window, scale)
    hq_loc = hq // msize

    def body(q_l, k_l, v_l):
        # q_l: (B_loc, hq_loc, S, D); k_l/v_l: (B_loc, hkv, S, D) replicated.
        base = jax.lax.axis_index("model") * hq_loc
        kv_idx = (base + jnp.arange(hq_loc)) // group
        k_sel = jnp.take(k_l, kv_idx, axis=1)
        v_sel = jnp.take(v_l, kv_idx, axis=1)
        return _flash_core(q_l, k_sel, v_sel, cfg, window, scale)

    qspec = P(dp, "model", None, None)
    kvspec = P(dp, None, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(qspec, kvspec, kvspec),
        out_specs=qspec,
        check_vma=False,
    )(q, k, v)


def _megatron_attention(
    params, x, cfg: ModelConfig, window, scale, positions, mrope_positions,
    mesh, rules,
):
    """Sequence-parallel attention block fully inside shard_map.

    Megatron-SP schedule: all-gather the seq-sharded residual (bf16), run
    column-parallel QKV (local q heads, replicated GQA KV), the local flash
    core, then row-parallel output projection finished with a
    reduce-scatter back onto the seq dim. Doing this explicitly removes
    GSPMD's involuntary full rematerializations (f32 full-seq tensors)
    around the projections — measured 12.9 GB/device on command-r train_4k.
    """
    from repro.distributed import sharding as shd
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    msize = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    group = hq // hkv
    hq_loc = hq // msize
    dp = shd.spec_for(("batch",), rules, mesh, (b,))[0]

    wspec = {"wq": P(None, "model"), "wk": P(None, None),
             "wv": P(None, None), "wo": P("model", None)}
    for name in ("bq",):
        if name in params:
            wspec["bq"] = P("model")
    for name in ("bk", "bv", "bo", "q_norm", "k_norm"):
        if name in params:
            wspec[name] = P()
    wspec = {k_: v_ for k_, v_ in wspec.items() if k_ in params}
    p_in = {k_: params[k_] for k_ in wspec}

    pos_spec = P(dp, None)
    mpos_spec = P(None, dp, None)

    def body(pp, x_loc, pos, mpos):
        # x_loc: (B_loc, S/msize, D) -> gather full seq in bf16.
        x_full = jax.lax.all_gather(x_loc, "model", axis=1, tiled=True)
        q = x_full @ pp["wq"]                    # (B, S, hq_loc*dh)
        k = x_full @ pp["wk"]
        v = x_full @ pp["wv"]
        if "bq" in pp:
            q = q + pp["bq"]
            k = k + pp["bk"]
            v = v + pp["bv"]
        bl, sl = x_full.shape[:2]
        q = q.reshape(bl, sl, hq_loc, dh).transpose(0, 2, 1, 3)
        k = k.reshape(bl, sl, hkv, dh).transpose(0, 2, 1, 3)
        v = v.reshape(bl, sl, hkv, dh).transpose(0, 2, 1, 3)
        if cfg.qk_norm:
            q = layers.rms_norm(q, pp["q_norm"])
            k = layers.rms_norm(k, pp["k_norm"])
        if cfg.rope == "rope":
            q = layers.apply_rope(q, pos, cfg.rope_theta)
            k = layers.apply_rope(k, pos, cfg.rope_theta)
        elif cfg.rope == "mrope":
            q = layers.apply_mrope(q, mpos, cfg.mrope_sections,
                                   cfg.rope_theta)
            k = layers.apply_mrope(k, mpos, cfg.mrope_sections,
                                   cfg.rope_theta)
        # Local flash: map each local q head to its GQA kv head.
        base = jax.lax.axis_index("model") * hq_loc
        kv_idx = (base + jnp.arange(hq_loc)) // group
        k_sel = jnp.take(k, kv_idx, axis=1)
        v_sel = jnp.take(v, kv_idx, axis=1)
        o = _flash_core(q, k_sel, v_sel, cfg, window, scale)
        o = o.transpose(0, 2, 1, 3).reshape(bl, sl, hq_loc * dh)
        part = o @ pp["wo"]                      # (B, S, D) partial sum
        y = jax.lax.psum_scatter(part, "model", scatter_dimension=1,
                                 tiled=True)
        if "bo" in pp:
            y = y + pp["bo"]
        return y

    x_spec = P(dp, "model", None)
    y = jax.shard_map(
        body, mesh=mesh,
        in_specs=(wspec, x_spec, pos_spec, mpos_spec),
        out_specs=x_spec,
        check_vma=False,
    )(p_in, x, positions,
      mrope_positions if mrope_positions is not None
      else jnp.zeros((3, b, s), jnp.int32))
    return y


def attention_apply(
    params: dict,
    x: jax.Array,                 # (B, S, D)
    cfg: ModelConfig,
    kind: str,
    positions: jax.Array,         # (B, S)
    mrope_positions: jax.Array | None = None,
) -> jax.Array:
    """Training / prefill self-attention. Returns (B, S, D)."""
    from repro.distributed import sharding as shd

    window = cfg.window if kind == ATTN_LOCAL else None
    scale = cfg.attn_scale if cfg.attn_scale is not None else cfg.d_head ** -0.5
    b, s = x.shape[:2]

    ctx = shd.current_context()
    if ctx is not None:
        mesh, rules = ctx
        msize = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
        if (msize > 1 and cfg.n_heads % msize == 0 and s % msize == 0
                and not cfg.use_pallas):
            y = _megatron_attention(
                params, x, cfg, window, scale, positions, mrope_positions,
                mesh, rules,
            )
            return constrain(y, ("batch", "seq", "embed"))

    q, k, v = _project_qkv(params, x, cfg, positions, mrope_positions)
    o = _sharded_flash(q, k, v, cfg, window, scale)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.d_head)
    y = o @ params["wo"]
    if cfg.use_bias:
        y = y + params["bo"]
    return constrain(y, ("batch", "seq", "embed"))


def attention_prefill(
    params, x, cfg: ModelConfig, kind, positions, mrope_positions=None,
    cache_len: int | None = None,
):
    """Prefill: same as apply but also returns the KV cache (padded to
    ``cache_len``)."""
    window = cfg.window if kind == ATTN_LOCAL else None
    scale = cfg.attn_scale if cfg.attn_scale is not None else cfg.d_head ** -0.5
    q, k, v = _project_qkv(params, x, cfg, positions, mrope_positions)
    o = _sharded_flash(q, k, v, cfg, window, scale)
    b, s = x.shape[:2]
    out = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.d_head)
    y = out @ params["wo"]
    if cfg.use_bias:
        y = y + params["bo"]
    if cache_len is not None and cache_len > s:
        pad = [(0, 0), (0, 0), (0, cache_len - s), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    return constrain(y, ("batch", "seq", "embed")), (k, v)


def attention_decode(
    params: dict,
    x: jax.Array,                  # (B, 1, D)
    cache: Tuple[jax.Array, jax.Array],  # k,v: (B, Hkv, S_max, Dh)
    pos: jax.Array,                # () i32 current position
    cfg: ModelConfig,
    kind: str,
    mrope_positions: jax.Array | None = None,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One-token decode with KV-cache update."""
    window = cfg.window if kind == ATTN_LOCAL else None
    scale = cfg.attn_scale if cfg.attn_scale is not None else cfg.d_head ** -0.5
    b = x.shape[0]
    positions = jnp.broadcast_to(pos, (b, 1))
    q, k_new, v_new = _project_qkv(
        params, x, cfg, positions,
        mrope_positions if cfg.rope == "mrope" else None,
    )
    k_cache, v_cache = cache
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new, pos, axis=2)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new, pos, axis=2)
    s_max = k_cache.shape[2]
    length = pos + 1

    if cfg.use_pallas:
        from repro.kernels import ops as kops

        lengths = jnp.broadcast_to(length, (b,)).astype(jnp.int32)
        o = kops.decode_attention(
            q[:, :, 0], k_cache, v_cache, lengths, window=window,
            logit_softcap=cfg.attn_softcap, scale=scale,
        )[:, :, None, :]
    else:
        hq, hkv = cfg.n_heads, cfg.n_kv_heads
        group = hq // hkv
        # Keep cache operands in their storage dtype and accumulate in f32
        # via preferred_element_type — an explicit .astype(f32) on the
        # cache materializes a full-cache f32 copy (3 GB/device per stack
        # on the 32k decode cells).
        qg = (q.astype(jnp.float32) * scale).astype(q.dtype)
        qg = qg.reshape(b, hkv, group, cfg.d_head)
        if window is not None and window < s_max:
            # Local layers touch only the last `window` entries — slicing
            # the cache cuts per-step read traffic by s_max/window (8x on
            # the gemma2 decode_32k cell).
            start = jnp.clip(length - window, 0, s_max - window)
            k_att = jax.lax.dynamic_slice_in_dim(k_cache, start, window, 2)
            v_att = jax.lax.dynamic_slice_in_dim(v_cache, start, window, 2)
            cols = start + jnp.arange(window)
        else:
            k_att, v_att = k_cache, v_cache
            cols = jnp.arange(s_max)
        logits = jnp.einsum(
            "bhgd,bhkd->bhgk", qg, k_att,
            preferred_element_type=jnp.float32,
        )
        if cfg.attn_softcap is not None:
            logits = layers.softcap(logits, cfg.attn_softcap)
        mask = cols < length
        if window is not None:
            mask &= cols > length - 1 - window
        logits = jnp.where(mask[None, None, None], logits, NEG)
        p = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum(
            "bhgk,bhkd->bhgd", p.astype(v_att.dtype), v_att,
            preferred_element_type=jnp.float32,
        )
        o = o.reshape(b, hq, 1, cfg.d_head).astype(x.dtype)

    out = o.transpose(0, 2, 1, 3).reshape(b, 1, cfg.n_heads * cfg.d_head)
    y = out @ params["wo"]
    if cfg.use_bias:
        y = y + params["bo"]
    return constrain(y, ("batch", "seq", "embed")), (k_cache, v_cache)
