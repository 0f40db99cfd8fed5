"""7-point periodic stencil TPU kernel: one HBM pass over the block.

The jnp form, six ``jnp.roll``s and ``-6u``, makes XLA write the rolled
copies to HBM and read them back: about 16 passes over the field per
step. Here each grid step holds a slab of ``bx`` x-planes in VMEM, plus
the plane before and the plane after it, fetched by two (1, Y, Z)
blocks whose index maps wrap modulo X. Each slab so reads ``1 + 2/bx``
of the field and writes its slab once; the y and z neighbours of a
plane are ``pltpu.roll``s on its sublane and lane axes, in VMEM.

The answer is the periodic stencil of the block on every axis, summed
in the order the jnp form sums it, in float32: ``-6u`` then the x, y
and z neighbour pairs, lower side first.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SLAB_DEPTHS = (8, 4, 2, 1)
# the kernel keeps its buffers under this much VMEM: v5e has 128 MiB per
# core, of which a kernel may use 16 MiB unless it asks for more
VMEM_BUDGET = 64 * 2**20
# Mosaic's scratch for one plane's temporaries (the plane and its rolled
# copies): 4.2 planes at 512^2 when compiled for v5e; 6 leaves room
_TEMP_PLANES = 6


def vmem_bytes(shape, itemsize: int, bx: int) -> int:
    """VMEM the kernel needs for slabs of ``bx`` planes: the slab in and
    out and both neighbour planes, each double-buffered, and one plane's
    temporaries."""
    _, Y, Z = shape
    return (4 * bx + 4 + _TEMP_PLANES) * Y * Z * itemsize


def slab_depth(shape, itemsize: int):
    """The deepest slab of :data:`SLAB_DEPTHS` that divides X and fits
    :data:`VMEM_BUDGET`, or None where none does."""
    for bx in SLAB_DEPTHS:
        if (shape[0] % bx == 0
                and vmem_bytes(shape, itemsize, bx) <= VMEM_BUDGET):
            return bx
    return None


def _plane(c, lo, hi):
    """The stencil of plane ``c`` between its x neighbours ``lo`` and
    ``hi``; a roll by n - 1 is the roll by -1."""
    Y, Z = c.shape
    return (-6.0 * c + lo + hi
            + pltpu.roll(c, 1, 0) + pltpu.roll(c, Y - 1, 0)
            + pltpu.roll(c, 1, 1) + pltpu.roll(c, Z - 1, 1))


def _stencil_kernel(lo_ref, u_ref, hi_ref, o_ref, *, bx: int):
    last = bx - 1
    if bx == 1:
        o_ref[0] = _plane(u_ref[0], lo_ref[0], hi_ref[0])
        return
    o_ref[0] = _plane(u_ref[0], lo_ref[0], u_ref[1])

    def body(i, carry):
        o_ref[i] = _plane(u_ref[i], u_ref[i - 1], u_ref[i + 1])
        return carry

    jax.lax.fori_loop(1, last, body, 0)
    o_ref[last] = _plane(u_ref[last], u_ref[last - 1], hi_ref[0])


def stencil7(u: jax.Array, interpret: bool = False) -> jax.Array:
    """The periodic 7-point stencil of the (X, Y, Z) block ``u``, in
    slabs of the deepest :func:`slab_depth` allows."""
    X, Y, Z = u.shape
    itemsize = u.dtype.itemsize
    bx = slab_depth(u.shape, itemsize)
    assert bx, u.shape
    plane = (1, Y, Z)
    field_bytes = u.size * itemsize
    return pl.pallas_call(
        functools.partial(_stencil_kernel, bx=bx),
        grid=(X // bx,),
        in_specs=[
            pl.BlockSpec(plane, lambda i: ((i * bx + X - 1) % X, 0, 0)),
            pl.BlockSpec((bx, Y, Z), lambda i: (i, 0, 0)),
            pl.BlockSpec(plane, lambda i: ((i * bx + bx) % X, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bx, Y, Z), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype,
                                       vma=jax.typeof(u).vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(vmem_bytes(u.shape, itemsize, bx),
                                 16 * 2**20)),
        cost_estimate=pl.CostEstimate(
            flops=7 * u.size, transcendentals=0,
            bytes_accessed=field_bytes * (2 * bx + 2) // bx),
        name="halo_stencil7",
        interpret=interpret,
    )(u, u, u)
