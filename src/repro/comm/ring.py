"""Manual ring collectives with serialized vs double-buffered schedules.

This is the TPU transliteration of the paper's §4 finding and fix:

  * ``schedule="serial"`` — one queue. Each ring step's ppermute is chained
    behind the consumer's use of the previous chunk, so compute waits on
    the wire every step (the BlockingProgress-lock pattern: producer and
    consumer serialized on one shared resource).

  * ``schedule="overlap"`` — two queues. Each step computes on chunk k
    while chunk k+1 is already in flight (ppermute has no data dependency
    on the consumer), which is exactly 'add a second incoming queue so the
    user thread never waits on the progress thread'. On TPU the
    latency-hiding scheduler turns the independent ppermute into an async
    collective-permute-start/done pair that overlaps the MXU.

All functions run inside shard_map.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import regions
from . import patterns
from .collectives import comm_phase, ppermute


def _ring_perm(n: int, reverse: bool = False):
    return patterns.ring_perm(n, -1 if reverse else 1)


def ring_all_gather(
    x: jax.Array, axis_name: str, schedule: str = "overlap"
) -> jax.Array:
    """All-gather x (local shard) along axis_name via a ppermute ring.
    Returns (n * x.shape[0], ...) with shard i at block i."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = _ring_perm(n)
    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = jax.lax.dynamic_update_index_in_dim(out, x, idx, 0)
    cur = x
    with regions.annotate(f"ring_all_gather({axis_name})",
                          category="collective", schedule=schedule), \
            comm_phase(f"ring_all_gather({axis_name})"):
        for step in range(1, n):
            nxt = ppermute(cur, axis_name, perm, tag=step)
            if schedule == "serial":
                # one queue: chain the send behind the consumer's update
                # (optimization_barrier pins the order, like holding the
                # shared lock while processing)
                nxt, out = jax.lax.optimization_barrier((nxt, out))
            src = (idx - step) % n
            out = jax.lax.dynamic_update_index_in_dim(out, nxt, src, 0)
            cur = nxt
    return out.reshape((n * x.shape[0],) + x.shape[1:])


def ring_all_reduce(
    x: jax.Array, axis_name: str, schedule: str = "overlap"
) -> jax.Array:
    """reduce-scatter + all-gather ring all-reduce by chunks."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    pad = -x.shape[0] % n
    xp = jnp.pad(x.reshape(x.shape[0], -1), ((0, pad), (0, 0))) if pad else (
        x.reshape(x.shape[0], -1))
    chunks = xp.reshape(n, -1, xp.shape[-1])            # (n, rows/n, cols)
    perm = _ring_perm(n, reverse=True)

    with regions.annotate(f"ring_all_reduce({axis_name})",
                          category="collective", schedule=schedule), \
            comm_phase(f"ring_all_reduce({axis_name})"):
        # reduce-scatter phase: after n-1 steps, device i holds the full
        # sum of chunk (i+1) % n
        acc = jax.lax.dynamic_index_in_dim(chunks, (idx + 1) % n, 0,
                                           keepdims=False)
        for step in range(1, n):
            moved = ppermute(acc, axis_name, perm, tag=step)
            take = (idx + 1 + step) % n
            mine = jax.lax.dynamic_index_in_dim(chunks, take, 0,
                                                keepdims=False)
            if schedule == "serial":
                moved, mine = jax.lax.optimization_barrier((moved, mine))
            acc = moved + mine
        # all-gather phase
        out = jnp.zeros_like(chunks)
        own = (idx + n) % n
        out = jax.lax.dynamic_update_index_in_dim(out, acc, own, 0)
        cur = acc
        for step in range(1, n):
            cur = ppermute(cur, axis_name, perm, tag=n + step)
            src = (idx + step) % n
            if schedule == "serial":
                cur, out = jax.lax.optimization_barrier((cur, out))
            out = jax.lax.dynamic_update_index_in_dim(out, cur, src, 0)
    flat = out.reshape(-1, xp.shape[-1])
    if pad:
        flat = flat[: x.shape[0]]
    return flat.reshape(x.shape)


def overlap_matmul_allgather(
    x_shard: jax.Array,       # (rows/n, K) local shard of X rows
    w: jax.Array,             # (K, N) local weight
    axis_name: str,
    schedule: str = "overlap",
) -> jax.Array:
    """Compute allgather(x) @ w with the gather *fused into* the matmul:
    step k multiplies the chunk that just arrived while the next chunk is
    on the wire. The serial schedule gathers everything first (fully
    exposed wire time); the overlap schedule is the paper's fix."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = _ring_perm(n)
    rows = x_shard.shape[0]
    out = jnp.zeros((n, rows, w.shape[1]), x_shard.dtype)

    if schedule == "serial":
        full = ring_all_gather(x_shard, axis_name, schedule="serial")
        return full @ w

    cur = x_shard
    with regions.annotate(f"ag_matmul({axis_name})", category="collective",
                          schedule=schedule), \
            comm_phase(f"ag_matmul({axis_name})"):
        for step in range(n):
            src = (idx - step) % n
            if step < n - 1:
                nxt = ppermute(cur, axis_name, perm, tag=step)  # in flight (queue #2)
            y = cur @ w                                # compute (queue #1)
            out = jax.lax.dynamic_update_index_in_dim(out, y, src, 0)
            if step < n - 1:
                cur = nxt
    return out.reshape(n * rows, w.shape[1])


def reduce_scatter_matmul(
    x: jax.Array,             # (M, K) local activations
    w_shard: jax.Array,       # (K, N) shard of a row-parallel weight
    axis_name: str,
    schedule: str = "overlap",
    n_chunks: Optional[int] = None,
) -> jax.Array:
    """y = reduce_scatter(x @ w, rows) — row-chunked so each chunk's ring
    reduction rides the wire while the next chunk is on the MXU."""
    n = jax.lax.axis_size(axis_name)
    partial = x @ w_shard
    if n == 1:
        return partial
    if schedule == "serial":
        summed = ring_all_reduce(partial, axis_name, schedule="serial")
        rows = partial.shape[0] // n
        idx = jax.lax.axis_index(axis_name)
        return jax.lax.dynamic_slice_in_dim(summed, idx * rows, rows, 0)
    # overlap: psum_scatter lowers to reduce-scatter, which the TPU
    # scheduler overlaps with the producing matmul chunks
    return jax.lax.psum_scatter(partial, axis_name, scatter_dimension=0,
                                tiled=True)
