"""Mixture-of-Experts: top-k routing, capacity + sort-based LOCAL dispatch
under an explicit ``shard_map``.

Why shard_map: expressing MoE dispatch as global scatter/gather under
GSPMD triggers involuntary full rematerialization (the partitioner cannot
shard data-dependent scatters — we measured 43 GB/device index planes on
the assigned qwen2-moe train_4k cell). Instead each device dispatches its
OWN tokens (batch x seq fully local), with expert weights all-gathered from
their FSDP shards — token compute stays sharded, weight traffic equals the
dense-FSDP all-gather the rest of the model already pays. The roofline's
collective term shows this weight gather; a true all-to-all EP layout is a
further optimization tracked in EXPERIMENTS §Perf.

Dispatch per device is Megablocks-style: sort the (token, expert) pairs by
expert, scatter into an (E, C_local, D) buffer with capacity dropping,
batched per-expert matmuls, weighted scatter-add back. Includes qwen2-moe's
always-on shared experts (sigmoid gate) and the load-balancing aux loss.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.segops import segment_rank
from repro.distributed import sharding as shd
from repro.models import layers
from repro.models.config import ModelConfig


def moe_init(key: jax.Array, cfg: ModelConfig, dtype) -> tuple[dict, dict]:
    d, e, de = cfg.d_model, cfg.n_experts, cfg.d_expert
    ks = jax.random.split(key, 6)
    s_in, s_out = d ** -0.5, de ** -0.5
    p = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * s_in,
        "w_gate": jax.random.normal(ks[1], (e, d, de), dtype) * s_in,
        "w_up": jax.random.normal(ks[2], (e, d, de), dtype) * s_in,
        "w_down": jax.random.normal(ks[3], (e, de, d), dtype) * s_out,
    }
    a = {
        "router": ("embed", "experts"),
        "w_gate": ("experts", "embed", "expert_mlp"),
        "w_up": ("experts", "embed", "expert_mlp"),
        "w_down": ("experts", "expert_mlp", "embed"),
    }
    if cfg.n_shared_experts:
        ds = cfg.d_shared_expert
        sp, sa = layers.mlp_init(ks[4], d, ds, cfg.mlp_gated, False, dtype)
        p["shared"] = sp
        a["shared"] = sa
        p["shared_gate"] = jax.random.normal(ks[5], (d, 1), jnp.float32) * s_in
        a["shared_gate"] = ("embed", None)
    return p, a


def _local_moe(params: dict, xt: jax.Array, cfg: ModelConfig, t_for_cap: int):
    """Per-device dispatch + expert compute. xt: (T_local, D), weights full.

    Returns (out (T_local, D), local aux-loss numerator terms)."""
    t, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k

    logits = jnp.einsum(
        "td,de->te", xt, params["router"].astype(xt.dtype),
        preferred_element_type=jnp.float32,
    )                                                            # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # Load-balance stats (Switch): fraction routed + mean prob per expert.
    f_e = jnp.mean(jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32), axis=0)
    p_e = jnp.mean(probs, axis=0)

    cap = int(t_for_cap * k / e * cfg.capacity_factor + 0.999)
    cap = max(4, -(-cap // 4) * 4)
    e_flat = top_e.reshape(t * k)
    p_flat = top_p.reshape(t * k)
    tok_flat = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    rank = segment_rank(e_flat)
    keep = rank < cap
    slot = jnp.where(keep, e_flat * cap + rank, e * cap)

    buf = jnp.zeros((e * cap, d), xt.dtype).at[slot].set(
        xt[tok_flat], mode="drop"
    ).reshape(e, cap, d)

    if cfg.mlp_gated:
        g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
        u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
        h = jax.nn.silu(g) * u if cfg.mlp_act == "silu" else jax.nn.gelu(g) * u
    else:
        h = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
        h = jax.nn.silu(h) if cfg.mlp_act == "silu" else jax.nn.gelu(h)
    y_e = jnp.einsum("ecf,efd->ecd", h, params["w_down"]).reshape(e * cap, d)

    y_rows = y_e[jnp.minimum(slot, e * cap - 1)]
    y_rows = jnp.where(keep[:, None], y_rows, 0.0)
    w = jnp.where(keep, p_flat, 0.0).astype(xt.dtype)
    out = jnp.zeros((t, d), xt.dtype).at[tok_flat].add(y_rows * w[:, None])

    if cfg.n_shared_experts:
        sh = layers.mlp_apply(params["shared"], xt, cfg.mlp_act, cfg.mlp_gated)
        gate = jax.nn.sigmoid(
            xt.astype(jnp.float32) @ params["shared_gate"]
        ).astype(xt.dtype)
        out = out + sh * gate
    return out, f_e, p_e


def _ep_moe(params: dict, x: jax.Array, cfg: ModelConfig, mesh, rules):
    """Expert-parallel MoE: experts sharded over "model" inside shard_map.

    Each model shard: all-gathers the seq-sharded tokens (bf16), routes
    (replicated routing math), dispatches only the (token, expert) pairs
    owned locally, runs its E/msize experts, and contributes its partial
    combine through one reduce-scatter back onto the seq dim. Versus the
    replicated-expert path this cuts BOTH the expert weight gather and the
    expert gradient reduction by the model-axis extent (measured 119
    GB/device/step of expert-grad all-reduce on qwen3-moe train_4k).
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    axes = tuple(mesh.axis_names)
    dp_axes = tuple(a for a in axes if a != "model")
    msize = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    e_loc = e // msize
    x_spec = shd.spec_for(("batch", "seq", None), rules, mesh, x.shape)

    wspec = {
        "router": P(),
        "w_gate": P("model", None, None),
        "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }
    p_in = {k_: params[k_] for k_ in wspec}

    def body(pp, x_loc):
        bl = x_loc.shape[0]
        x_full = jax.lax.all_gather(x_loc, "model", axis=1, tiled=True)
        t = bl * s
        xt = x_full.reshape(t, d)

        # Router in the token dtype with f32 accumulation: an f32 xt copy
        # would make the whole residual cotangent f32 (measured +52% memory
        # term via f32 reduce-scatters).
        logits = jnp.einsum(
            "td,de->te", xt, pp["router"].astype(xt.dtype),
            preferred_element_type=jnp.float32,
        )                                                      # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        f_e = jnp.mean(
            jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32), axis=0
        )
        p_e = jnp.mean(probs, axis=0)
        f_e = jax.lax.pmean(f_e, dp_axes)
        p_e = jax.lax.pmean(p_e, dp_axes)
        aux = cfg.router_aux_coef * e * jnp.sum(f_e * p_e)

        my = jax.lax.axis_index("model")
        cap = int(t * k / e * cfg.capacity_factor + 0.999)
        cap = max(4, -(-cap // 4) * 4)
        e_flat = top_e.reshape(t * k)
        p_flat = top_p.reshape(t * k)
        tok_flat = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
        mine = (e_flat // e_loc) == my
        e_local = jnp.where(mine, e_flat % e_loc, e_loc)
        rank = segment_rank(e_local)
        keep = mine & (rank < cap)
        slot = jnp.where(keep, e_local * cap + rank, e_loc * cap)

        buf = jnp.zeros((e_loc * cap, d), xt.dtype).at[slot].set(
            xt[tok_flat], mode="drop"
        ).reshape(e_loc, cap, d)
        if cfg.mlp_gated:
            g = jnp.einsum("ecd,edf->ecf", buf, pp["w_gate"])
            u = jnp.einsum("ecd,edf->ecf", buf, pp["w_up"])
            h = (jax.nn.silu(g) if cfg.mlp_act == "silu"
                 else jax.nn.gelu(g)) * u
        else:
            h = jnp.einsum("ecd,edf->ecf", buf, pp["w_up"])
            h = jax.nn.silu(h) if cfg.mlp_act == "silu" else jax.nn.gelu(h)
        y_e = jnp.einsum("ecf,efd->ecd", h, pp["w_down"]).reshape(
            e_loc * cap, d
        )
        y_rows = y_e[jnp.minimum(slot, e_loc * cap - 1)]
        y_rows = jnp.where(keep[:, None], y_rows, 0.0)
        w = jnp.where(keep, p_flat, 0.0).astype(xt.dtype)
        part = jnp.zeros((t, d), xt.dtype).at[tok_flat].add(
            y_rows * w[:, None]
        )
        # Sum partial expert outputs across shards + scatter back to seq.
        out = jax.lax.psum_scatter(
            part.reshape(bl, s, d), "model", scatter_dimension=1, tiled=True
        )
        return out, aux

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(wspec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(p_in, x)


def moe_apply(
    params: dict, x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), aux load-balancing loss scalar)."""
    b, s, d = x.shape
    e = cfg.n_experts
    ctx = shd.current_context()
    if ctx is None:
        # Single-device path (smoke tests / CPU examples).
        out, f_e, p_e = _local_moe(params, x.reshape(b * s, d), cfg, b * s)
        aux = cfg.router_aux_coef * e * jnp.sum(f_e * p_e)
        return out.reshape(b, s, d), aux

    mesh, rules = ctx
    axes = tuple(mesh.axis_names)          # ("pod","data","model") or 2D
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    msize = sizes.get("model", 1)
    if (cfg.moe_ep and msize > 1 and e % msize == 0 and s % msize == 0
            and not cfg.n_shared_experts):
        return _ep_moe(params, x, cfg, mesh, rules)
    x_spec = shd.spec_for(("batch", "seq", None), rules, mesh, x.shape)

    # Weight in_specs: replicated E, FSDP-sharded middle dim (the gather
    # back to full D happens inside, over the FSDP axes).
    wspec = {
        "router": P(),
        "w_gate": P(None, None, None),
        "w_up": P(None, None, None),
        "w_down": P(None, None, None),
    }
    if cfg.n_shared_experts:
        wspec["shared"] = jax.tree.map(lambda _: P(), params["shared"])
        wspec["shared_gate"] = P()

    def body(pp, x_loc):
        bl, sl, _ = x_loc.shape
        out, f_e, p_e = _local_moe(pp, x_loc.reshape(bl * sl, d), cfg,
                                   bl * sl)
        # Global stats: mean across every mesh axis (tokens are sharded
        # over batch+seq axes; replicated elsewhere — pmean is exact for
        # equal local token counts).
        f_e = jax.lax.pmean(f_e, axes)
        p_e = jax.lax.pmean(p_e, axes)
        aux = cfg.router_aux_coef * e * jnp.sum(f_e * p_e)
        return out.reshape(bl, sl, d), aux

    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(wspec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(params, x)
    return out, aux
