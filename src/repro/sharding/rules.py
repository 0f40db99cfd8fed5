"""Logical-axis -> mesh-axis rules and sharding trees.

Parallelism map (production mesh (pod, data, model) / (data, model)):

  batch        -> ("pod", "data")   data parallelism (+pod DP across pods)
  embed        -> "data"            FSDP: params + optimizer state sharded
  heads/kv_heads/mlp/inner/experts/vocab -> "model"   tensor/expert parallel
  cache seq    -> "data" for long_500k (batch=1 -> sequence parallelism)
  everything else replicated

A contextvar carries (mesh, rules) so model code can place activation
constraints via :func:`constrain` without threading the mesh through
every call (no-op outside a sharding context — e.g. single-device tests).
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, ShapeConfig

_CTX: contextvars.ContextVar[Optional[Tuple[Mesh, Dict[str, Any]]]] = (
    contextvars.ContextVar("sharding_ctx", default=None)
)


def make_rules(
    mesh: Mesh, shape: Optional[ShapeConfig] = None
) -> Dict[str, Any]:
    axes = mesh.axis_names
    has_pod = "pod" in axes
    batch = ("pod", "data") if has_pod else ("data",)
    # KV/state caches shard their *sequence* dim over "model" (flash-decode
    # style partial-softmax; GSPMD inserts the combine) because kv_heads
    # (4-36 across the archs) rarely divide the model axis.
    seq_kv = ("model",)
    act_seq = "model"              # Megatron-style sequence parallelism
    if shape is not None and shape.is_decode:
        act_seq = None             # decode steps have T=1
        if shape.global_batch < mesh.shape["data"]:
            # long-context decode (batch=1): batch can't cover the data
            # axis; fold it into the cache sequence sharding instead
            batch = None
            seq_kv = ("pod", "data", "model") if has_pod else ("data", "model")
    return {
        "batch": batch,
        "seq_kv": seq_kv,
        "act_seq": act_seq,
        "embed": "data",
        "heads": "model",
        "kv_heads": None,          # see seq_kv note
        "mlp": "model",
        "inner": "model",
        "experts": "model",
        "expert_mlp": None,
        "vocab": "model",
        "state": None,
        "layers": None,
    }


@contextlib.contextmanager
def sharding_context(mesh: Mesh, rules: Dict[str, Any]):
    token = _CTX.set((mesh, rules))
    try:
        with mesh:
            yield
    finally:
        _CTX.reset(token)


def _flatten_entry(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def _fit_entry(dim: int, entry, mesh: Optional[Mesh]):
    """Drop mesh axes (from the right) until the dim divides evenly —
    pjit arguments require exact divisibility."""
    if mesh is None:
        return entry
    names = _flatten_entry(entry)
    while names:
        prod = 1
        for n in names:
            prod *= mesh.shape[n]
        if dim % prod == 0:
            return names if len(names) > 1 else names[0]
        names = names[:-1]
    return None


def pspec(
    axes: Tuple[Optional[str], ...],
    rules: Dict[str, Any],
    shape: Optional[Tuple[int, ...]] = None,
    mesh: Optional[Mesh] = None,
) -> P:
    parts = []
    for i, a in enumerate(axes):
        entry = None if a is None else rules.get(a)
        if shape is not None:
            entry = _fit_entry(shape[i], entry, mesh)
        parts.append(entry)
    # trailing Nones are implicit
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def constrain(x: jax.Array, axes: Tuple[Optional[str], ...]) -> jax.Array:
    ctx = _CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    return jax.lax.with_sharding_constraint(
        x, pspec(axes, rules, shape=x.shape, mesh=mesh))


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _grad_constrained(x, axes):
    return x


def _gc_fwd(x, axes):
    return x, None


def _gc_bwd(axes, _res, g):
    return (constrain(g, axes),)


_grad_constrained.defvjp(_gc_fwd, _gc_bwd)


def grad_constrained(x: jax.Array, axes: Tuple[Optional[str], ...]):
    """Identity whose *cotangent* is sharding-constrained.

    Applied to layer parameters at scan-group entry so each group's
    parameter gradient is reduce-scattered to the parameter sharding
    inside the backward loop, instead of GSPMD materializing (and
    all-reducing) the full replicated gradient per group (measured:
    512 x 1.7 GB all-reduces on qwen3 train_4k)."""
    return _grad_constrained(x, axes)


def tree_shardings(axes_tree, mesh: Mesh, rules: Dict[str, Any],
                   shapes_tree=None):
    """Map a pytree of logical-axis tuples to NamedShardings. When
    ``shapes_tree`` is given, non-divisible mesh axes are dropped per dim."""
    if shapes_tree is None:
        return jax.tree.map(
            lambda axes: NamedSharding(mesh, pspec(axes, rules)),
            axes_tree,
            is_leaf=lambda t: isinstance(t, tuple),
        )
    return jax.tree.map(
        lambda axes, s: NamedSharding(
            mesh, pspec(axes, rules, shape=s.shape, mesh=mesh)),
        axes_tree, shapes_tree,
        is_leaf=lambda t: isinstance(t, tuple),
    )


# --- cache sharding (leaf-name based; see models.blocks.block_cache_specs) --

_CACHE_AXES = {
    # attention kv cache (stacked): (layers, batch, seq, kv_heads, head_dim)
    "k": ("layers", "batch", "seq_kv", "kv_heads", None),
    "v": ("layers", "batch", "seq_kv", "kv_heads", None),
    "pos": ("layers", "seq_kv"),
    # mamba: h (layers, batch, inner, state); conv (layers, batch, k, inner)
    "h": ("layers", "batch", "inner", "state"),
    "conv": ("layers", "batch", None, "inner"),
    # mlstm state
    "C": ("layers", "batch", None, None, None),
    "n": ("layers", "batch", None, None),
    "m": ("layers", "batch", None),
    # slstm state (same leaf names h/c/n/m at rank 4)
    "c": ("layers", "batch", None, None),
}


def cache_axes(cache_shapes) -> Any:
    def rec(path, leaf):
        name = str(path[-1].key)
        axes = _CACHE_AXES.get(name)
        if axes is None or len(axes) != len(leaf.shape):
            # fall back by rank: slstm h/n/m are rank-4/3 f32 states
            if name in ("h", "n", "m", "c"):
                axes = ("layers", "batch") + (None,) * (len(leaf.shape) - 2)
            else:
                axes = (None,) * len(leaf.shape)
        return tuple(axes)

    return jax.tree_util.tree_map_with_path(rec, cache_shapes)


def cache_shardings(cache_shapes, mesh: Mesh, rules: Dict[str, Any]):
    return tree_shardings(cache_axes(cache_shapes), mesh, rules, cache_shapes)


# --- batch sharding ---------------------------------------------------------

def batch_axes_for(batch_tree) -> Any:
    def rec(path, leaf):
        name = str(path[-1].key)
        if name in ("tokens", "labels"):
            return ("batch",) + (None,) * (len(leaf.shape) - 1)
        if name in ("frames", "encoder_embeddings"):
            return ("batch",) + (None,) * (len(leaf.shape) - 1)
        if name == "pos":
            return ()
        return (None,) * len(leaf.shape)

    return jax.tree_util.tree_map_with_path(rec, batch_tree)


def batch_shardings(batch_tree, mesh: Mesh, rules: Dict[str, Any]):
    return tree_shardings(batch_axes_for(batch_tree), mesh, rules, batch_tree)
