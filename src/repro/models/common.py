"""Parameter spec tables + shared layer math.

Every module declares its parameters once as a dict of :class:`ParamSpec`
(shape, logical axes, initializer). Initialization, abstract shapes
(dry-run), and sharding rules all derive from that single table, so they
cannot drift.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Axes = Tuple[Optional[str], ...]


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Axes                      # logical axis names, len == len(shape)
    init: str = "normal"            # normal | zeros | ones | scaled | mamba_a | const
    scale: float = 0.02
    dtype: Any = None               # defaults to cfg dtype at init time

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


SpecTree = Dict[str, Any]           # nested dicts of ParamSpec


def init_param(key: jax.Array, spec: ParamSpec, dtype) -> jax.Array:
    dt = spec.dtype or dtype
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dt)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dt)
    if spec.init == "const":
        return jnp.full(spec.shape, spec.scale, dt)
    if spec.init == "mamba_a":
        # S4D-real initialization: A = -(1..d_state) broadcast over d_inner
        # (and over any leading stack dims)
        d_state = spec.shape[-1]
        a = jnp.broadcast_to(
            jnp.arange(1, d_state + 1, dtype=jnp.float32), spec.shape)
        return jnp.log(a).astype(dt)   # stored as log(-A)
    if spec.init == "scaled":
        # fan-in is the contraction dim, second from last: a leading dim
        # is the layer stack, experts or heads, never the fan-in
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else 1
        return (jax.random.normal(key, spec.shape, jnp.float32)
                * (spec.scale / math.sqrt(max(1, fan_in)))).astype(dt)
    return (jax.random.normal(key, spec.shape, jnp.float32) * spec.scale).astype(dt)


def _tree_map_with_path(fn, tree: SpecTree, path=()):
    if isinstance(tree, ParamSpec):
        return fn(path, tree)
    return {k: _tree_map_with_path(fn, v, path + (k,)) for k, v in tree.items()}


def init_from_specs(key: jax.Array, specs: SpecTree, dtype) -> Dict[str, Any]:
    def mk(path, spec: ParamSpec):
        sub = jax.random.fold_in(key, hash("/".join(path)) % (2**31))
        return init_param(sub, spec, dtype)

    return _tree_map_with_path(mk, specs)


def shapes_from_specs(specs: SpecTree, dtype) -> Dict[str, Any]:
    return _tree_map_with_path(
        lambda _p, s: jax.ShapeDtypeStruct(s.shape, s.dtype or dtype), specs
    )


def axes_from_specs(specs: SpecTree) -> Dict[str, Any]:
    return _tree_map_with_path(lambda _p, s: s.axes, specs)


# ---------------------------------------------------------------------------
# shared layer math
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def activation(name: str) -> Callable[[jax.Array], jax.Array]:
    return {
        "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "relu": jax.nn.relu,
        "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
    }[name]


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., T, H, D); positions: (..., T)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                            # (D/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., T, D/2)
    cos = jnp.cos(angles)[..., :, None, :]                  # (..., T, 1, D/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(logits: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)
