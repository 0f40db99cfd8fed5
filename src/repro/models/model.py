"""Model assembly: embedding -> scan over layer groups -> final norm.

Parameters for each pattern position are stacked over the ``n_groups``
scan dimension (leading "layers" axis), so HLO size is independent of
depth — 64-layer qwen3 compiles as fast as a 4-layer toy. Decode carries
a per-position cache pytree stacked the same way and scanned jointly.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import LayerSpec, ModelConfig
from .blocks import block_apply, block_cache_specs, block_specs
from .common import (
    ParamSpec,
    SpecTree,
    axes_from_specs,
    init_from_specs,
    rms_norm,
    shapes_from_specs,
)

N_AUX = 4  # fixed-size aux vector: [moe_aux_loss, load_balance, router_z, dropped]


def _stack_specs(specs: SpecTree, n: int) -> SpecTree:
    def rec(t):
        if isinstance(t, ParamSpec):
            return ParamSpec((n,) + t.shape, ("layers",) + t.axes,
                             init=t.init, scale=t.scale, dtype=t.dtype)
        return {k: rec(v) for k, v in t.items()}

    return rec(specs)


def model_specs(cfg: ModelConfig) -> SpecTree:
    specs: SpecTree = {}
    Vp = cfg.padded_vocab_size
    if cfg.input_mode != "frames":
        specs["embed"] = ParamSpec((Vp, cfg.d_model), ("vocab", None))
    for i, lspec in enumerate(cfg.pattern):
        specs[f"pos{i}"] = _stack_specs(block_specs(cfg, lspec), cfg.n_groups)
    specs["final_norm"] = ParamSpec((cfg.d_model,), (None,), init="zeros")
    specs["lm_head"] = ParamSpec(
        (cfg.d_model, cfg.n_codebooks * Vp), (None, "vocab"))
    return specs


def init_params(key: jax.Array, cfg: ModelConfig,
                dtype=jnp.float32) -> Dict[str, Any]:
    """Master parameters are f32 (FSDP-sharded); forward casts to the
    compute dtype per step. Pass cfg.dtype for inference-only weights."""
    return init_from_specs(key, model_specs(cfg), jnp.dtype(dtype))


def param_shapes(cfg: ModelConfig, dtype=jnp.float32) -> Dict[str, Any]:
    return shapes_from_specs(model_specs(cfg), jnp.dtype(dtype))


def param_axes(cfg: ModelConfig) -> Dict[str, Any]:
    return axes_from_specs(model_specs(cfg))


def param_count(cfg: ModelConfig) -> int:
    import math

    leaves = jax.tree.leaves(param_shapes(cfg))
    return sum(math.prod(l.shape) for l in leaves)


def active_param_count(cfg: ModelConfig) -> int:
    """Parameters touched per token (MoE: top_k + shared experts only;
    padded dead experts never receive tokens)."""
    total = param_count(cfg)
    if cfg.moe is None:
        return total
    m = cfg.moe
    n_moe_layers = sum(1 for s in cfg.pattern if s.ffn == "moe") * cfg.n_groups
    per_expert = 3 * cfg.d_model * m.d_expert
    inactive = n_moe_layers * (cfg.padded_n_experts - m.top_k) * per_expert
    return total - inactive


# parameters that stay f32 in compute (routing / SSM dynamics / gate logits)
_KEEP_F32 = ("router", "A_log", "D", "w_if", "b_if", "dt_w", "dt_b")


def _cast(params, dtype):
    def c(path, x):
        name = str(path[-1].key) if path else ""
        if name in _KEEP_F32:
            return x
        if x.dtype in (jnp.float32, jnp.float64) and x.ndim > 1:
            return x.astype(dtype)
        return x

    return jax.tree_util.tree_map_with_path(c, params)


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def embed_tokens(params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Vocab-sharded embedding lookup.

    Under a sharding context this is a shard_map masked *local* lookup +
    psum_scatter: each model shard gathers the ids it owns and the partial
    rows are reduce-scattered straight into the sequence-parallel layout.
    GSPMD's own lowering of a gather from a vocab-sharded table can
    degenerate into a one-hot dot (measured: ~14x the model's useful
    flops on deepseek prefill_32k), which this path avoids entirely —
    and the backward pass becomes a shard-local scatter-add.
    """
    from ..sharding.rules import _CTX, pspec

    table = params["embed"]
    scale = jnp.sqrt(float(cfg.d_model)).astype(jnp.dtype(cfg.dtype))
    ctx = _CTX.get()
    model_size = ctx[0].shape.get("model", 1) if ctx is not None else 1
    Vp = cfg.padded_vocab_size
    T = tokens.shape[-1]
    if (ctx is None or model_size == 1 or Vp % model_size
            or table.ndim != 2):
        return jnp.take(table, tokens, axis=0).astype(
            jnp.dtype(cfg.dtype)) * scale
    mesh, rules = ctx
    from jax.sharding import PartitionSpec as P


    v_shard = Vp // model_size
    scatter_seq = rules.get("act_seq") == "model" and T % model_size == 0

    def local(tab, tok):
        i = jax.lax.axis_index("model")
        lo = i * v_shard
        ids = jnp.clip(tok - lo, 0, v_shard - 1)
        x = jnp.take(tab, ids, axis=0)
        ok = (tok >= lo) & (tok < lo + v_shard)
        x = jnp.where(ok[..., None], x, 0).astype(jnp.dtype(cfg.dtype))
        if scatter_seq:
            return jax.lax.psum_scatter(x, "model", scatter_dimension=1,
                                        tiled=True)
        return jax.lax.psum(x, "model")

    batch_ax = rules.get("batch")
    tok_spec = P(batch_ax, None)
    out_spec = P(batch_ax, "model" if scatter_seq else None, None)
    x = jax.shard_map(
        local, mesh=mesh,
        in_specs=(pspec(("vocab", None), rules), tok_spec),
        out_specs=out_spec,
    )(table, tokens)
    return x * scale


def _aux_vector(aux: Dict[str, jax.Array]) -> jax.Array:
    keys = ("moe_aux_loss", "moe_load_balance", "moe_router_z",
            "moe_dropped_frac")
    return jnp.stack([jnp.float32(aux.get(k, 0.0)) for k in keys])


def forward(
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
    cfg: ModelConfig,
    mode: str = "train",                  # train | prefill
) -> Tuple[jax.Array, jax.Array, Optional[Dict[str, Any]]]:
    """Returns (hidden (B,T,E), aux_vec (N_AUX,), caches_or_None)."""
    compute_params = _cast(params, jnp.dtype(cfg.dtype))
    if cfg.input_mode == "frames":
        x = batch["frames"].astype(jnp.dtype(cfg.dtype))
    else:
        x = embed_tokens(compute_params, batch["tokens"], cfg)
    enc = batch.get("encoder_embeddings")
    if enc is not None:
        enc = enc.astype(jnp.dtype(cfg.dtype))
    B, T = x.shape[0], x.shape[1]
    positions = jnp.arange(T, dtype=jnp.int32)
    stacked = {f"pos{i}": compute_params[f"pos{i}"]
               for i in range(len(cfg.pattern))}
    # sequence-parallel residual stream: the scan carry (and thus the
    # per-group saved activation) lives sharded over the model axis
    from ..sharding.rules import constrain, grad_constrained

    x = constrain(x, ("batch", "act_seq", None))
    # per-group parameter cotangents reduce-scatter straight to the
    # parameter sharding (axes minus the leading scan/"layers" dim)
    sliced_axes = {
        k: jax.tree.map(lambda ax: tuple(ax[1:]), param_axes(cfg)[k],
                        is_leaf=lambda t: isinstance(t, tuple))
        for k in stacked
    }

    def _constrain_grads(tree, axes_tree):
        leaves, treedef = jax.tree.flatten(tree)
        axes = jax.tree.flatten(
            axes_tree, is_leaf=lambda t: isinstance(t, tuple))[0]
        return jax.tree.unflatten(
            treedef,
            [grad_constrained(a, ax) for a, ax in zip(leaves, axes)])

    def group_body(x, gparams):
        if mode == "train":
            gparams = {k: _constrain_grads(gparams[k], sliced_axes[k])
                       for k in gparams}
        aux_acc = jnp.zeros((N_AUX,), jnp.float32)
        caches = {}
        for i, lspec in enumerate(cfg.pattern):
            x, nc, aux = block_apply(
                gparams[f"pos{i}"], x, cfg, lspec, positions,
                enc=enc, mode=mode)
            caches[f"pos{i}"] = nc or {}
            if aux:
                aux_acc = aux_acc + _aux_vector(aux)
        x = constrain(x, ("batch", "act_seq", None))
        if mode == "prefill":
            return x, (aux_acc, caches)
        return x, aux_acc

    body = _remat(group_body, cfg.remat if mode == "train" else "none")
    if mode == "prefill":
        x, (aux_all, caches) = jax.lax.scan(body, x, stacked)
        aux = aux_all.sum(0)
    else:
        x, aux_all = jax.lax.scan(body, x, stacked)
        aux = aux_all.sum(0)
        caches = None
    x = rms_norm(x, compute_params["final_norm"], cfg.norm_eps)
    return x, aux, caches


def decode_step(
    params: Dict[str, Any],
    caches: Dict[str, Any],
    batch: Dict[str, jax.Array],          # tokens (B,1) or frames (B,1,E)
    pos: jax.Array,                       # scalar int32 current position
    cfg: ModelConfig,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step. Returns (logits (B, ncb, V), new caches)."""
    compute_params = _cast(params, jnp.dtype(cfg.dtype))
    if cfg.input_mode == "frames":
        x = batch["frames"].astype(jnp.dtype(cfg.dtype))
    else:
        x = embed_tokens(compute_params, batch["tokens"], cfg)
    stacked = {f"pos{i}": compute_params[f"pos{i}"]
               for i in range(len(cfg.pattern))}

    def group_body(x, xs):
        gparams, gcache = xs
        new_caches = {}
        for i, lspec in enumerate(cfg.pattern):
            x, nc, _ = block_apply(
                gparams[f"pos{i}"], x, cfg, lspec, pos,
                cache=gcache[f"pos{i}"], mode="decode")
            new_caches[f"pos{i}"] = nc or {}
        return x, new_caches

    x, new_caches = jax.lax.scan(group_body, x, (stacked, caches))
    x = rms_norm(x, compute_params["final_norm"], cfg.norm_eps)
    logits = (x[:, 0] @ compute_params["lm_head"]).astype(jnp.float32)
    B = logits.shape[0]
    logits = logits.reshape(B, cfg.n_codebooks, cfg.padded_vocab_size)
    return mask_pad_logits(logits, cfg), new_caches


def mask_pad_logits(logits: jax.Array, cfg: ModelConfig) -> jax.Array:
    """-inf the padded vocab tail so sampling/argmax never picks it."""
    Vp = cfg.padded_vocab_size
    if Vp == cfg.vocab_size:
        return logits
    valid = jnp.arange(Vp) < cfg.vocab_size
    return jnp.where(valid, logits, -1e30)


def init_cache_shapes(
    cfg: ModelConfig, batch: int, seq_len: int
) -> Dict[str, Any]:
    """Abstract stacked cache pytree for decode dry-runs/serving."""
    out: Dict[str, Any] = {}
    for i, lspec in enumerate(cfg.pattern):
        sub = block_cache_specs(cfg, lspec, batch, seq_len)

        def stack(t):
            if isinstance(t, dict):
                return {k: stack(v) for k, v in t.items()}
            return jax.ShapeDtypeStruct((cfg.n_groups,) + t.shape, t.dtype)

        out[f"pos{i}"] = stack(sub)
    return out


def init_cache_zeros(cfg: ModelConfig, batch: int, seq_len: int):
    """Concrete zero caches; attention position slots start at -1 so the
    decode mask treats them as empty."""
    shapes = init_cache_shapes(cfg, batch, seq_len)

    def mk(path, t):
        if path and getattr(path[-1], "key", None) == "pos":
            return jnp.full(t.shape, -1, jnp.int32)
        return jnp.zeros(t.shape, t.dtype)

    return jax.tree_util.tree_map_with_path(mk, shapes)
