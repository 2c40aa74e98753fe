"""Logical-axis sharding: MaxText-style rules mapping model-space axis names
to mesh axes, applied through ``with_sharding_constraint`` hooks that are
no-ops outside a mesh context (so the same model code runs on one CPU device
and on a (pod, data, model) production mesh).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Default production rules. None ⇒ replicated. An axis only binds when the
# dimension is divisible by the mesh extent (spec_for checks shapes), so
# e.g. MQA kv_heads=1 falls through and the kv_seq dim picks up "model".
DEFAULT_RULES: tuple[tuple[str, Any], ...] = (
    ("batch", ("pod", "data")),   # falls back to ("data",) on single-pod
    ("seq", "model"),             # sequence parallelism on the residual
    ("embed", "data"),            # FSDP dim of weight matrices
    ("heads", "model"),
    ("kv_heads", "model"),
    ("kv_seq", "model"),          # long KV caches when kv_heads can't shard
    ("head_dim", None),
    ("mlp", "model"),
    ("vocab", "model"),
    ("experts", "model"),
    ("expert_mlp", None),
    ("expert_cap", "data"),       # MoE dispatch buffer rows follow tokens
    ("tokens", ("pod", "data", "model")),  # flattened (B*S) token dim
    ("lru", "model"),
    ("conv", None),
    ("layers", None),
)

_ctx = threading.local()


def _rules_dict(rules) -> dict:
    return dict(rules)


def _mesh_axes(mesh: Mesh) -> set:
    return set(mesh.axis_names)


def _resolve(logical: str, rules: dict, mesh: Mesh):
    """Logical axis -> mesh axis (or tuple), dropping absent mesh axes."""
    target = rules.get(logical)
    if target is None:
        return None
    axes = _mesh_axes(mesh)
    if isinstance(target, (tuple, list)):
        kept = tuple(t for t in target if t in axes)
        return kept if kept else None
    return target if target in axes else None


def spec_for(
    logical_axes: Sequence[str | None],
    rules,
    mesh: Mesh,
    shape: Sequence[int] | None = None,
) -> P:
    if tuple(logical_axes) == REPLICATED:
        return P()
    rd = _rules_dict(rules)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    parts = []
    used: set = set()

    def extent(r) -> int:
        if isinstance(r, tuple):
            out = 1
            for x in r:
                out *= sizes[x]
            return out
        return sizes[r]

    def fit(r, dim: int | None):
        """Drop already-used axes; drop bindings the dim can't divide."""
        if r is None:
            return None
        if isinstance(r, tuple):
            kept = tuple(x for x in r if x not in used)
            if not kept:
                return None
            if dim is not None and dim % extent(kept) != 0:
                # Try each member axis alone (largest first).
                for x in sorted(kept, key=lambda x: -sizes[x]):
                    if dim % sizes[x] == 0:
                        used.add(x)
                        return x
                return None
            used.update(kept)
            return kept
        if r in used:
            return None
        if dim is not None and dim % extent(r) != 0:
            return None
        used.add(r)
        return r

    for i, ax in enumerate(logical_axes):
        r = None if ax is None else _resolve(ax, rd, mesh)
        dim = None if shape is None else shape[i]
        parts.append(fit(r, dim))
    return P(*parts)


# Sentinel axes for scalar/replicated leaves (a bare () would be
# indistinguishable from an empty *structural* tuple in a pytree).
REPLICATED = ("__replicated__",)


def _is_axes(x) -> bool:
    return (
        isinstance(x, tuple)
        and len(x) > 0
        and all(isinstance(e, (str, type(None))) for e in x)
    )


def sharding_tree(axes_tree, rules, mesh: Mesh, shapes_tree=None):
    """Map a tree of logical-axis tuples to NamedShardings.

    ``shapes_tree`` (same structure; leaves with .shape, e.g. arrays or
    ShapeDtypeStructs) enables divisibility-aware binding.
    """
    if shapes_tree is None:
        return jax.tree.map(
            lambda ax: NamedSharding(mesh, spec_for(ax, rules, mesh)),
            axes_tree, is_leaf=_is_axes,
        )
    flat_ax, treedef = jax.tree_util.tree_flatten(axes_tree, is_leaf=_is_axes)
    flat_shape = treedef.flatten_up_to(shapes_tree)
    out = [
        NamedSharding(mesh, spec_for(ax, rules, mesh, leaf.shape))
        for ax, leaf in zip(flat_ax, flat_shape)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


def current_context():
    """(mesh, rules) if inside ``use_rules``, else None."""
    return getattr(_ctx, "state", None)


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules=DEFAULT_RULES):
    """Activate logical constraints inside model code."""
    prev = getattr(_ctx, "state", None)
    _ctx.state = (mesh, rules)
    try:
        yield
    finally:
        _ctx.state = prev


def constrain(x: jax.Array, logical: Sequence[str | None]) -> jax.Array:
    """Apply a logical sharding constraint if a rules context is active."""
    state = getattr(_ctx, "state", None)
    if state is None:
        return x
    mesh, rules = state
    spec = spec_for(logical, rules, mesh, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
