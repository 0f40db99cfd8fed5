"""Plain float32 reference of the xLSTM language model and its AdamW step.

Written from arXiv:2405.04517 (xLSTM: Extended Long Short-Term Memory)
in straightforward ``jax.numpy``: the mLSTM in the paper's parallel
(quadratic) form, the sLSTM as a step-by-step recurrence, every matrix
product at ``precision=HIGHEST``. It imports nothing of the program. It
reads parameters by their names in the program's parameter tree (the
layout is the interface) and follows the program's conventions where
they depart from the paper:

1. Pre-norm blocks use RMSNorm with a ``(1 + scale)`` gain; the paper's
   LayerNorm has no such offset.
2. The mLSTM output is normalised by one RMSNorm over all heads, and the
   sLSTM output likewise; the paper uses a per-head GroupNorm.
3. The sLSTM normaliser is held at or above ``exp(-m)`` and starts at 1
   (``n_0 = 1``, ``m_0 = 0``); the paper starts it at 0.
4. The sLSTM block adds its gated MLP to the normalised cell output
   (``y + MLP(y)``) and the model then adds the block's output to the
   residual stream; the paper's post-up-projection block adds the MLP
   output only.
5. The mLSTM forget gate is ``sigmoid`` in log space and the input gate
   ``exp``, as the paper allows; the skip path is a learned per-channel
   scale of the convolved input.
6. The embedding is scaled by ``sqrt(d_model)`` and the loss adds a
   ``1e-4`` z-loss (mean squared log-partition) to the cross entropy.
7. AdamW decays every parameter whose name is not a norm, ``b_if``,
   ``b_gates`` or ``skip`` (so the convolution bias decays too), after
   clipping the global gradient norm to ``clip_norm``.

``mode="fp8"`` computes every matrix product from float8 (e4m3) inputs,
each scaled per tensor to the format's range, with float32 gradients
passed straight through: the precision below the configuration's
bfloat16, used as the control.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
Z_WEIGHT = 1e-4


@jax.custom_vjp
def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


def einsum(spec: str, a, b, mode: str):
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def rms_norm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def mlstm_block(p, x, cfg: dict, mode: str):
    """Pre-up-projection mLSTM block (without the residual add)."""
    B, T, E = x.shape
    H = cfg["n_heads"]
    dI = int(E * cfg["proj_factor_mlstm"])
    Dh = dI // H
    up = einsum("bte,ef->btf", x, p["up_proj"], mode)
    xm, z = up[..., :dI], up[..., dI:]
    K = p["conv_w"].shape[0]
    xp = jnp.pad(xm, ((0, 0), (K - 1, 0), (0, 0)))
    xc = sum(xp[:, i:i + T] * p["conv_w"][i] for i in range(K)) + p["conv_b"]
    xc = jax.nn.silu(xc)
    # heads lead the sequence axes, so that the (B, H, T, S) tensors keep
    # their two long axes minor, where the chip tiles an array; H (4) as
    # the minor axis would be padded to a full tile
    heads = lambda a: a.reshape(B, T, H, Dh).transpose(0, 2, 1, 3)
    q = heads(einsum("btf,fg->btg", xc, p["wq"], mode))
    k = heads(einsum("btf,fg->btg", xc, p["wk"], mode))
    v = heads(einsum("btf,fg->btg", xm, p["wv"], mode))
    gates = (einsum("btf,fg->btg", xc, p["w_if"], mode) + p["b_if"])
    gates = gates.reshape(B, T, 2, H).transpose(0, 2, 3, 1)   # (B, 2, H, T)
    i_pre, f_log = gates[:, 0], jax.nn.log_sigmoid(gates[:, 1])
    # log D[t, s] = sum_{r=s+1..t} log f_r + i_s  for s <= t
    F = jnp.cumsum(f_log, axis=-1)                         # (B, H, T)
    logD = F[..., :, None] - F[..., None, :] + i_pre[..., None, :]
    causal = jnp.tril(jnp.ones((T, T), bool))
    logD = jnp.where(causal, logD, -jnp.inf)               # (B, H, T, S)
    m = jnp.max(logD, axis=-1)                             # (B, H, T)
    D = jnp.exp(logD - m[..., None])
    S = einsum("bhtd,bhsd->bhts", q, k, mode) / math.sqrt(Dh) * D
    num = einsum("bhts,bhsd->bhtd", S, v, mode)
    den = jnp.maximum(jnp.abs(S.sum(axis=-1)), jnp.exp(-m))
    h = (num / den[..., None]).transpose(0, 2, 1, 3).reshape(B, T, dI)
    h = rms_norm(h, p["out_norm"], cfg["norm_eps"])
    y = h + p["skip"] * xc
    return einsum("btf,fe->bte", y * jax.nn.silu(z), p["down_proj"], mode)


def slstm_block(p, x, cfg: dict, mode: str):
    """sLSTM block with its gated MLP (without the residual add)."""
    B, T, E = x.shape
    H = cfg["n_heads"]
    Dh = E // H
    wx = einsum("bte,ef->btf", x, p["w_gates"], mode) + p["b_gates"]
    wx = jnp.moveaxis(wx.reshape(B, T, H, 4 * Dh), 1, 0)   # (T, B, H, 4Dh)
    r = p["r_gates"]

    def cell(state, wxt):
        h, c, n, m = state
        pre = wxt + einsum("bhd,hde->bhe", h, r, mode)
        zi, ii, fi, oi = jnp.split(pre, 4, axis=-1)
        f_log = jax.nn.log_sigmoid(fi)
        m_new = jnp.maximum(f_log + m, ii)
        i_w = jnp.exp(ii - m_new)
        f_w = jnp.exp(f_log + m - m_new)
        c = f_w * c + i_w * jnp.tanh(zi)
        n = jnp.maximum(f_w * n + i_w, jnp.exp(-m_new))
        h = jax.nn.sigmoid(oi) * c / n
        return (h, c, n, m_new), h

    zero = jnp.zeros((B, H, Dh), jnp.float32)
    _, hs = jax.lax.scan(cell, (zero, zero, jnp.ones_like(zero), zero), wx)
    y = jnp.moveaxis(hs, 0, 1).reshape(B, T, E)
    y = rms_norm(y, p["group_norm"], cfg["norm_eps"])
    hid = (jax.nn.silu(einsum("bte,ef->btf", y, p["mlp_wg"], mode))
           * einsum("bte,ef->btf", y, p["mlp_wi"], mode))
    return y + einsum("btf,fe->bte", hid, p["mlp_wo"], mode)


BLOCKS = {"mlstm": mlstm_block, "slstm": slstm_block}


def loss_sum(params, tokens, labels, cfg: dict, mode: str = "f32"):
    """Summed token cross entropy plus z-loss terms over a block of rows
    (divide by the number of tokens for the mean). The layer groups are a
    scan over the stacked parameters, each group recomputed in the
    backward pass."""
    E, V = cfg["d_model"], cfg["vocab_size"]
    x = params["embed"][tokens] * math.sqrt(E)
    pattern = cfg["pattern"]

    def group(x, ps):
        for kind, p in zip(pattern, ps):
            h = rms_norm(x, p["norm_mixer"], cfg["norm_eps"])
            x = x + BLOCKS[kind](p["mixer"], h, cfg, mode)
        return x, None

    stacked = [params[f"pos{i}"] for i in range(len(pattern))]
    x, _ = jax.lax.scan(jax.checkpoint(group), x, stacked)
    x = rms_norm(x, params["final_norm"], cfg["norm_eps"])
    logits = einsum("bte,ev->btv", x, params["lm_head"], mode)
    logits = jnp.where(jnp.arange(logits.shape[-1]) < V, logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked) + Z_WEIGHT * jnp.sum(lse * lse)


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode", "rows"))
def _loss_and_grads(params, tokens, labels, cfg_items, mode, rows):
    cfg = dict(cfg_items)
    cfg["pattern"] = list(cfg["pattern"])
    n = tokens.shape[0] * tokens.shape[1]
    blocks = lambda a: a.reshape((-1, rows) + a.shape[1:])

    def block(acc, rows_tl):
        f = lambda p: loss_sum(p, *rows_tl, cfg, mode) / n
        l, g = jax.value_and_grad(f)(params)
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, params))
    out, _ = jax.lax.scan(block, zero, (blocks(tokens), blocks(labels)))
    return out


def _freeze(cfg: dict) -> tuple:
    keys = ("d_model", "n_heads", "n_layers", "vocab_size", "norm_eps",
            "proj_factor_mlstm", "pattern")
    return tuple((k, tuple(cfg[k]) if k == "pattern" else cfg[k])
                 for k in keys)


def loss_and_grads(params, tokens, labels, cfg: dict, mode: str = "f32",
                   rows_per_block: int = 4):
    """Mean loss and its gradients over all rows, in one call that takes
    a block of rows at a time, so that the activations of one block
    fit."""
    rows = min(rows_per_block, tokens.shape[0])
    if tokens.shape[0] % rows:
        raise ValueError(f"{tokens.shape[0]} rows do not divide into "
                         f"blocks of {rows}")
    return _loss_and_grads(params, tokens, labels, _freeze(cfg), mode, rows)


def _decays(path) -> bool:
    name = str(path[-1].key)
    return not ("norm" in name or name in ("b_if", "b_gates", "skip"))


def learning_rate(opt: dict, step: int) -> float:
    """Linear warm-up to ``lr``, then cosine decay to ``min_lr_ratio``."""
    warm = min(1.0, step / max(1, opt["warmup_steps"]))
    if opt["schedule"] == "constant":
        return opt["lr"] * warm
    t = min(max((step - opt["warmup_steps"])
                / max(1, opt["total_steps"] - opt["warmup_steps"]), 0.0), 1.0)
    ratio = opt["min_lr_ratio"]
    return opt["lr"] * warm * (ratio + (1 - ratio) * 0.5
                               * (1 + math.cos(math.pi * t)))


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd",
                                             "clip"),
                   donate_argnums=(0, 1, 2, 3))
def _adamw(params, grads, m, v, lr, b1c, b2c, b1, b2, eps, wd, clip):
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.where(gnorm > clip, clip / jnp.maximum(gnorm, 1e-12), 1.0)

    def upd(path, p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        delta = (m / b1c) / (jnp.sqrt(v / b2c) + eps)
        if _decays(path):
            delta = delta + wd * p
        return p - lr * delta, m, v, g

    out = jax.tree_util.tree_map_with_path(upd, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), pick(3)


def adamw_step(params, grads, m, v, step: int, opt: dict):
    """One AdamW update (``step`` counts from 1). Returns the new
    parameters and moments, and the clipped gradient it applied."""
    return _adamw(params, grads, m, v, learning_rate(opt, step),
                  1 - opt["b1"] ** step, 1 - opt["b2"] ** step,
                  b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                  wd=opt["weight_decay"], clip=opt["clip_norm"])


def train(params, batches, cfg: dict, opt: dict, mode: str = "f32",
          rows_per_block: int = 4) -> Dict[str, object]:
    """Run the reference over ``batches`` [(tokens, labels), ...] from
    ``params``. Returns the loss of every step, the first step's clipped
    gradient and the final parameters."""
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
    m, v = zeros(params), zeros(params)
    losses, first_grad = [], None
    for s, (tokens, labels) in enumerate(batches, start=1):
        loss, grads = loss_and_grads(params, tokens, labels, cfg, mode,
                                     rows_per_block)
        losses.append(loss)
        params, m, v, clipped = adamw_step(params, grads, m, v, s, opt)
        if first_grad is None:
            first_grad = clipped
        del grads, clipped
    return {"losses": [float(l) for l in losses], "first_grad": first_grad,
            "params": params}
