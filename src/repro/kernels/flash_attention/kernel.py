"""Flash attention TPU kernel (Pallas): fwd + bwd, VMEM-resident blocks.

Layout: (B, H, T, D). The grid iterates kv blocks minor-most, so the
(acc, m, l) scratch carries across kv steps for one (b, h, q-block) and
the output is written on the last kv step — scores never touch HBM.
Causal/windowed blocks that are fully masked are skipped with pl.when
(real compute savings on TPU, unlike a masked dense path).

Backward is the standard two-kernel split (dq; then dk/dv) using the
saved row logsumexp and delta = rowsum(do * o). Default blocks (128, 512)
keep MXU dims 128-aligned; per-step VMEM working set is
  q(bq*D) + k,v(bk*D) + p(bq*bk) + acc(bq*D) ~ 1.6 MB  << 16 MB.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e38


def _mask(i, j, bq, bk, causal: bool, window: Optional[int]):
    """(bq, bk) bool mask for q block i vs kv block j."""
    pos_q = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    pos_k = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    m = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        m &= pos_k <= pos_q
    if window is not None:
        m &= pos_k > pos_q - window
    return m


def _block_needed(i, j, bq, bk, causal, window):
    needed = jnp.bool_(True)
    if causal:
        needed &= j * bk <= i * bq + bq - 1
    if window is not None:
        needed &= (j + 1) * bk - 1 > i * bq - window
    return needed


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc,
                *, causal, window, scale, bq, bk, nk):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    @pl.when(_block_needed(i, j, bq, bk, causal, window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        s = jnp.where(_mask(i, j, bq, bk, causal, window), s, NEG_INF)
        m_prev = m_sc[...]                                # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_sc[...] + jnp.log(l)


def flash_attention_fwd(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = True, window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128, block_k: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """q,k,v: (B, H, T, D) -> (out (B,H,T,D), lse (B,H,T,1)).

    Row statistics (lse, and delta in the backward) keep a trailing unit
    dim: Mosaic tiles the last two block dims by (8, 128) unless a dim is
    whole, so a (bq, 1) column block lowers where a (1, bq) row does not."""
    B, H, T, D = q.shape
    S = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    bq = min(block_q, T)
    bk = min(block_k, S)
    assert T % bq == 0 and S % bk == 0, (T, bq, S, bk)
    nq, nk = T // bq, S // bk
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, window=window,
                          scale=scale, bq=bq, bk=bk, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_sc, *, causal, window, scale, bq, bk, nk):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    @pl.when(_block_needed(i, j, bq, bk, causal, window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(_mask(i, j, bq, bk, causal, window), s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_sc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_sc, dv_sc,
                *, causal, window, scale, bq, bk, nq):
    j = pl.program_id(2)          # kv block (major)
    i = pl.program_id(3)          # q block (minor, accumulated)

    @pl.when(i == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    @pl.when(_block_needed(i, j, bq, bk, causal, window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(_mask(i, j, bq, bk, causal, window), s, NEG_INF)
        p = jnp.exp(s - lse)                              # (bq, bk)
        dv_sc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, D)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                     # (bq, bk)
        dk_sc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, D)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def flash_attention_bwd(
    q, k, v, o, lse, do,
    causal: bool = True, window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128, block_k: int = 512,
    interpret: bool = False,
):
    B, H, T, D = q.shape
    S = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    bq = min(block_q, T)
    bk = min(block_k, S)
    nq, nk = T // bq, S // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)                        # (B, H, T, 1)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, window=window,
                          scale=scale, bq=bq, bk=bk, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, window=window,
                          scale=scale, bq=bq, bk=bk, nq=nq),
        grid=(B, H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, S, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
