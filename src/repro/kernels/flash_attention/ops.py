"""jit'd public wrapper: custom-VJP flash attention with GQA handling.

``flash_attention(q, k, v)`` takes model-layout tensors (B, T, H, D) /
(B, S, K, D) (K kv heads), expands GQA groups, transposes to the kernel
layout, and differentiates through the Pallas bwd kernels. The caller
says whether to run the kernel body in the Pallas interpreter
(``interpret=True``, the CPU tests) or compile it for the TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import flash_attention_bwd, flash_attention_fwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, block_q, block_k, interpret):
    out, _ = flash_attention_fwd(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return out


def _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    out, lse = flash_attention_fwd(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = flash_attention_bwd(
        q, k, v, out, lse, do, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,                  # (B, T, H, D)
    k: jax.Array,                  # (B, S, K, D), K | H
    v: jax.Array,                  # (B, S, K, D)
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, T, H, D = q.shape
    K = k.shape[2]
    assert H % K == 0, (H, K)
    if K != H:
        rep = H // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq = min(block_q, T)
    bk = min(block_k, k.shape[1])
    out = _flash(qt, kt, vt, causal, window, bq, bk, interpret)
    return out.transpose(0, 2, 1, 3)
