"""Selective-scan (Mamba) TPU kernel: VMEM-resident state.

The jnp path materializes dA/dBx = (B, T, dI, N) intermediates chunk by
chunk in HBM; this kernel never leaves VMEM with them. Grid is
(B, dI/bd, T/bt) with time minor-most: the state scratch carries across
time blocks, and each block steps through its bt time steps with
(N, bd) vector ops on the VPU.

The state is held as (N, bd), channels on lanes: at N=16 a (bd, N)
layout would fill one lane in eight. So A, Bc and Cc enter transposed,
(N, dI) and (B, N, T), and each time step reads a row of x/dt and a
column of Bc/Cc at a static offset; Mosaic refuses the dynamic
sub-tile row loads a fori_loop over time would need.

HBM traffic per step: x, dt (bd*bt), Bc, Cc (bt*N), y (bd*bt) — i.e. the
theoretical minimum (inputs+outputs once), vs the jnp path's
O(T * dI * N) intermediate traffic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, h_sc,
                 y_sc, *, bt: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_sc[...] = jnp.zeros_like(h_sc)

    a = a_ref[...].astype(jnp.float32)                 # (N, bd)
    d = d_ref[...].astype(jnp.float32)                 # (1, bd)
    x = x_ref[0].astype(jnp.float32)                   # (bt, bd)
    dt = dt_ref[0].astype(jnp.float32)                 # (bt, bd)
    b = b_ref[0].astype(jnp.float32)                   # (N, bt)
    c = c_ref[0].astype(jnp.float32)                   # (N, bt)
    h = h_sc[...]                                      # (N, bd)
    for t in range(bt):
        xt, dtt = x[t:t + 1], dt[t:t + 1]              # (1, bd)
        h = jnp.exp(dtt * a) * h + (dtt * xt) * b[:, t:t + 1]
        y_sc[t:t + 1, :] = ((h * c[:, t:t + 1]).sum(axis=0, keepdims=True)
                            + d * xt)
    h_sc[...] = h
    y_ref[0] = y_sc[...].astype(y_ref.dtype)


def selective_scan(
    x: jax.Array,        # (B, T, dI)
    dt: jax.Array,       # (B, T, dI)
    A: jax.Array,        # (dI, N)
    Bc: jax.Array,       # (B, T, N)
    Cc: jax.Array,       # (B, T, N)
    D: jax.Array,        # (dI,)
    block_d: int = 512,
    block_t: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, T, dI = x.shape
    N = A.shape[1]
    bd = min(block_d, dI)
    bt = min(block_t, T)
    assert dI % bd == 0 and T % bt == 0, (dI, bd, T, bt)
    grid = (B, dI // bd, T // bt)
    return pl.pallas_call(
        functools.partial(_scan_kernel, bt=bt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, bd), lambda b, di, t: (b, t, di)),
            pl.BlockSpec((1, bt, bd), lambda b, di, t: (b, t, di)),
            pl.BlockSpec((N, bd), lambda b, di, t: (0, di)),
            pl.BlockSpec((1, N, bt), lambda b, di, t: (b, 0, t)),
            pl.BlockSpec((1, N, bt), lambda b, di, t: (b, 0, t)),
            pl.BlockSpec((1, bd), lambda b, di, t: (0, di)),
        ],
        out_specs=pl.BlockSpec((1, bt, bd), lambda b, di, t: (b, t, di)),
        out_shape=jax.ShapeDtypeStruct((B, T, dI), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, bd), jnp.float32),
                        pltpu.VMEM((bt, bd), jnp.float32)],
        interpret=interpret,
    )(x, dt, A.T, Bc.transpose(0, 2, 1), Cc.transpose(0, 2, 1),
      D.reshape(1, dI))
