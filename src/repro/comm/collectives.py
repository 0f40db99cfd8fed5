"""Instrumented collective primitives (the 'MPI procedure calls').

Each wrapper is usable inside shard_map and annotates the *dispatch site*
with a profiling region (category="collective") carrying logical byte
counts — the host-side analog of Caliper-instrumented MPI entry points.
jax.named_scope mirrors the region into HLO metadata so host regions can
be correlated with compiled collectives.

When a matching fabric is configured (:func:`configure_matching`), every
wrapper additionally routes its *point-to-point decomposition* through
the message-matching engine (:mod:`repro.match`) — the paper's second
profiling method: collectives become the send/recv streams an
implementation like ExaMPI issues, and the engine's counters record
queue depths, match latency and unexpected-message counts for them.

If the fabric carries a trace sink (:mod:`repro.trace`), each dispatch is
additionally phase-labeled after its call site (``psum(x)``,
``ring_all_gather(r)``, ...) via :func:`comm_phase`, so recorded traces
diff per collective phase offline.
"""
from __future__ import annotations

import contextlib
from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from ..core import regions

AxisName = Union[str, Tuple[str, ...]]

_FABRIC = None                       # Optional[repro.match.Fabric]


def configure_matching(fabric) -> None:
    """Install (or, with None, remove) the matching fabric every comm-layer
    dispatch is decomposed into. Runtime-toggleable like region categories."""
    global _FABRIC
    _FABRIC = fabric


def matching_fabric():
    return _FABRIC


@contextlib.contextmanager
def comm_phase(label: str):
    """Label the fabric phase markers emitted while the body runs, so a
    recorded trace names phases after the dispatch site (ring schedules
    and halo faces use this). No-op when no fabric is configured."""
    fab = _FABRIC
    if fab is None:
        yield
        return
    prev = fab.set_label(label)
    try:
        yield
    finally:
        fab.set_label(prev)


def _nbytes(x) -> int:
    return int(x.size * x.dtype.itemsize)


def psum(x: jax.Array, axis_name: AxisName) -> jax.Array:
    with regions.annotate(f"psum({axis_name})", category="collective",
                          bytes=_nbytes(x)):
        if _FABRIC is not None:
            with comm_phase(f"psum({axis_name})"):
                _FABRIC.all_reduce(jax.lax.axis_size(axis_name),
                                   nbytes=_nbytes(x))
        with jax.named_scope(f"comm_psum_{axis_name}"):
            return jax.lax.psum(x, axis_name)


def all_gather(x: jax.Array, axis_name: AxisName, axis: int = 0,
               tiled: bool = True) -> jax.Array:
    with regions.annotate(f"all_gather({axis_name})", category="collective",
                          bytes=_nbytes(x)):
        if _FABRIC is not None:
            with comm_phase(f"all_gather({axis_name})"):
                _FABRIC.all_gather(jax.lax.axis_size(axis_name),
                                   nbytes=_nbytes(x))
        with jax.named_scope(f"comm_all_gather_{axis_name}"):
            return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x: jax.Array, axis_name: AxisName,
                   scatter_dimension: int = 0) -> jax.Array:
    with regions.annotate(f"reduce_scatter({axis_name})",
                          category="collective", bytes=_nbytes(x)):
        if _FABRIC is not None:
            with comm_phase(f"reduce_scatter({axis_name})"):
                _FABRIC.reduce_scatter(jax.lax.axis_size(axis_name),
                                       nbytes=_nbytes(x))
        with jax.named_scope(f"comm_reduce_scatter_{axis_name}"):
            return jax.lax.psum_scatter(
                x, axis_name, scatter_dimension=scatter_dimension, tiled=True)


def all_to_all(x: jax.Array, axis_name: AxisName, split_axis: int,
               concat_axis: int) -> jax.Array:
    with regions.annotate(f"all_to_all({axis_name})", category="collective",
                          bytes=_nbytes(x)):
        if _FABRIC is not None:
            with comm_phase(f"all_to_all({axis_name})"):
                _FABRIC.all_to_all(jax.lax.axis_size(axis_name),
                                   nbytes=_nbytes(x))
        with jax.named_scope(f"comm_all_to_all_{axis_name}"):
            return jax.lax.all_to_all(
                x, axis_name, split_axis=split_axis, concat_axis=concat_axis,
                tiled=True)


def ppermute(x: jax.Array, axis_name: AxisName,
             perm: Sequence[Tuple[int, int]],
             tag: int = 0) -> jax.Array:
    """``tag`` distinguishes envelopes of back-to-back permutes with the
    same pattern (ring steps, halo faces) in the matching engine."""
    with regions.annotate(f"ppermute({axis_name})", category="collective",
                          bytes=_nbytes(x)):
        if _FABRIC is not None:
            _FABRIC.ppermute(perm, nbytes=_nbytes(x), tag=tag)
        with jax.named_scope(f"comm_ppermute_{axis_name}"):
            return jax.lax.ppermute(x, axis_name, perm)


def axis_index(axis_name: AxisName) -> jax.Array:
    return jax.lax.axis_index(axis_name)


def axis_size(axis_name: AxisName) -> int:
    return jax.lax.axis_size(axis_name)
