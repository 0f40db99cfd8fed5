"""COMB analog: 3-D halo exchange + stencil under shard_map.

COMB (paper §2.3) explores communication-pattern tradeoffs for structured
mesh halo exchanges: blocking vs non-blocking, staging buffers, message
sizes. The TPU-meaningful axes of that design space:

  * variant="blocking"  — exchange all faces, *then* compute the stencil
    (the wire time is fully exposed; COMB's waitall-before-compute).
  * variant="overlap"   — compute the interior stencil while faces are in
    flight; apply boundary columns afterwards (comm hidden behind compute).
  * width, box          — message size sweep (COMB's size sweeps).

Two kinds of names mark the program's phases, one per side of the host /
device boundary:

  * Host regions (:mod:`repro.core.regions`) time what the host does.
    They are named after COMB's own Caliper annotations (bench_comm,
    pre-comm, post-send, post-comm, wait-recv, post-recv, wait-send), so
    the comparison trees in benchmarks/ read like the paper's Figures
    1-3. Only :class:`HaloProgram` opens them: it dispatches one jitted
    segment per phase, so a region there times real work. Inside one
    ``jit`` a region would fire once, while the function is traced.
  * Device scopes (``jax.named_scope``) name the ops XLA emits: every
    instruction's ``op_name`` metadata, and so every op of a profiler
    trace, carries one of ``halo.extract`` (face slicing),
    ``halo.exchange`` (the ppermutes), ``halo.interior``
    (:func:`stencil_interior`) and ``halo.boundary``
    (:func:`_apply_halos`). The fused program (:func:`make_halo_fn`, both
    variants), :func:`make_xla_auto_fn` and the segments of
    :class:`HaloProgram` carry the same four.

Two stencils. Where the program holds one block per device (the fused
program and ``HaloProgram(explicit=True)``, every chip benchmark cell),
:func:`stencil_interior` computes the block's periodic stencil, on a TPU
with the ``stencil7`` Pallas kernel (:mod:`repro.kernels.stencil7`): one
pass over HBM where the rolled form takes about sixteen, and the step's
largest cost. Where the program rolls a sharded global array
(:func:`make_xla_auto_fn`, ``HaloProgram(explicit=False)``), the roll
crosses shard edges and GSPMD picks the collectives; that is the vendor
analog's meaning, so :func:`stencil_global` keeps the jnp roll there.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import regions
from ..kernels.stencil7.ops import stencil7, tiles as stencil7_tiles
from . import patterns
from .collectives import comm_phase, ppermute


def _shift(x: jax.Array, axis_name: str, direction: int,
           ax: int = 0) -> jax.Array:
    n = jax.lax.axis_size(axis_name)
    # perm + envelope tag per (mesh axis position, direction) come from
    # comm.patterns so the matching engine and the offline workload
    # scenarios see the exact message streams the stencil issues
    return ppermute(x, axis_name, patterns.ring_perm(n, direction),
                    tag=patterns.halo_tag(ax, direction))


_AXIS = {"x": 0, "y": 1, "z": 2}


def _face_index(ax: int, width: int, front: bool) -> tuple:
    idx = [slice(None)] * 3
    idx[ax] = slice(0, width) if front else slice(-width, None)
    return tuple(idx)


def extract_faces(u: jax.Array, axis_names, width: int
                  ) -> Dict[str, Tuple[jax.Array, jax.Array]]:
    """The (lo, hi) face of the local block along each mesh axis."""
    with jax.named_scope("halo.extract"):
        return {name: (u[_face_index(_AXIS[name], width, True)],
                       u[_face_index(_AXIS[name], width, False)])
                for name in axis_names}


def exchange_faces(faces, axis_names) -> Dict[str, Tuple[jax.Array,
                                                         jax.Array]]:
    """Receive the neighbor's hi face as my lo halo and vice versa."""
    with jax.named_scope("halo.exchange"), comm_phase("halo_exchange"):
        return {name: (_shift(faces[name][1], name, +1, ax=i),
                       _shift(faces[name][0], name, -1, ax=i))
                for i, name in enumerate(axis_names)}


def rolled_stencil(u: jax.Array) -> jax.Array:
    """7-point Laplacian of ``u``, periodic on every axis, as six
    ``jnp.roll``s: the jnp form, which XLA compiles to several passes over
    the field."""
    return (
        -6.0 * u
        + jnp.roll(u, 1, 0) + jnp.roll(u, -1, 0)
        + jnp.roll(u, 1, 1) + jnp.roll(u, -1, 1)
        + jnp.roll(u, 1, 2) + jnp.roll(u, -1, 2)
    )


def stencil_interior(u: jax.Array) -> jax.Array:
    """7-point Laplacian on the local block (interior only; edges wrong
    until halos are applied).

    Compiled for a TPU, a block whose y-z planes tile runs the
    ``stencil7`` Pallas kernel, one pass over HBM; elsewhere (the CPU, a
    block such as 16^3) it is :func:`rolled_stencil`. The choice is made
    as the program is lowered, by the platform it is lowered for. Both
    compute the same float32 sum, periodic within the block."""
    with jax.named_scope("halo.interior"):
        if not stencil7_tiles(u.shape, u.dtype.itemsize):
            return rolled_stencil(u)
        return jax.lax.platform_dependent(u, tpu=stencil7,
                                          default=rolled_stencil)


def stencil_global(u: jax.Array) -> jax.Array:
    """One complete periodic stencil step of a sharded *global* array:
    :func:`rolled_stencil` rolls across shard edges, so GSPMD chooses the
    collectives. The kernel cannot serve here: it sees one block."""
    with jax.named_scope("halo.interior"):
        return rolled_stencil(u)


def _apply_halos(out, u, halos, width: int):
    """Fix the wrap-around faces of the rolled stencil with true halos."""
    with jax.named_scope("halo.boundary"):
        for axis, (lo, hi) in halos.items():
            ax = _AXIS[axis]
            # replace the wrong wrap contribution with the neighbor's face
            for front, halo in ((True, lo), (False, hi)):
                idx = _face_index(ax, width, front)
                wrong = jnp.roll(u, 1 if front else -1, ax)[idx]
                out = out.at[idx].set(out[idx] - wrong + halo)
        return out


def halo_step(u: jax.Array, axis_names=("x", "y", "z"), width: int = 1,
              variant: str = "overlap") -> jax.Array:
    """One stencil step with halo exchange on the local block (in shard_map)."""
    halos = exchange_faces(extract_faces(u, axis_names, width), axis_names)
    if variant == "blocking":
        with jax.named_scope("halo.exchange"):
            # one queue: pin compute behind the completed exchange
            flat, tree = jax.tree.flatten(halos)
            flat = list(jax.lax.optimization_barrier(tuple(flat)))
            u = jax.lax.optimization_barrier(u)
            halos = jax.tree.unflatten(tree, flat)
    # overlap: the interior stencil runs while faces fly
    return _apply_halos(stencil_interior(u), u, halos, width)


def make_halo_fn(mesh: Mesh, width: int = 1, variant: str = "overlap",
                 steps: int = 1):
    """shard_map'd multi-step halo/stencil program over a 3-D mesh."""
    axes = mesh.axis_names
    spec = P(*axes)

    def local(u):
        for _ in range(steps):
            u = halo_step(u, axis_names=axes, width=width, variant=variant)
        return u

    return jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec)


class HaloProgram:
    """Segmented (multi-jit) halo program for *measured* host profiling.

    Regions inside one jit fire only at trace time, so per-run timing
    needs the program split at communication boundaries — which is also
    how real MPI codes are structured (compute kernels between comm
    calls). All backends share the exact same region structure (as COMB's
    regions are identical whichever MPI library is linked); only the
    implementation behind each segment differs:

      explicit=True   shard_map + ppermute faces (ExaMPI analog)
      explicit=False  sharded-global jnp ops, GSPMD picks collectives
                      (vendor/Spectrum analog)

    The communication segment can be dispatched through a
    :class:`repro.comm.progress.ProgressEngine` — mode "shared"
    reproduces the paper's one-queue lock contention; mode "incoming" is
    the second-queue fix. ``fence_every_op`` reproduces §3's
    host-scheduling defect (even compute-only regions slow down).
    """

    def __init__(self, mesh: Mesh, width: int = 1, explicit: bool = True):
        self.mesh = mesh
        self.width = width
        axes = mesh.axis_names
        spec = P(*axes)
        w = width

        def extract(u):
            return extract_faces(u, axes, w)

        def exchange(faces):
            return exchange_faces(faces, axes)

        def interior(u):
            # the block's stencil under shard_map; the global array's
            # under GSPMD
            return stencil_interior(u) if explicit else stencil_global(u)

        def boundary(out, u, halos):
            return _apply_halos(out, u, halos, w)

        fspec = {n: (spec, spec) for n in axes}
        if explicit:
            sm = functools.partial(jax.shard_map, mesh=mesh)
            self.extract = jax.jit(sm(extract, in_specs=spec,
                                      out_specs=fspec))
            self.exchange = jax.jit(sm(exchange, in_specs=(fspec,),
                                       out_specs=fspec))
            self.interior = jax.jit(sm(interior, in_specs=spec,
                                       out_specs=spec))
            self.boundary = jax.jit(
                sm(boundary, in_specs=(spec, spec, fspec), out_specs=spec))
        else:
            # GSPMD variant: the global-roll stencil IS the complete
            # periodic answer — XLA hides the cross-shard communication
            # inside the compute segment (the vendor-black-box property:
            # you cannot see its comm separately, exactly like timing a
            # closed MPI library from outside). The comm-specific
            # segments are structurally present but trivially cheap.
            def exchange_noop(u):
                return {}

            def boundary_noop(out, u, halos):
                return out

            from jax.sharding import NamedSharding
            shd = NamedSharding(mesh, spec)
            self.extract = jax.jit(extract, in_shardings=shd)
            self.exchange = jax.jit(exchange_noop, in_shardings=shd)
            self.interior = jax.jit(interior, in_shardings=shd,
                                    out_shardings=shd)
            self.boundary = boundary_noop
        self._exchange_takes_u = not explicit

    def step(self, u, engine=None, fence_every_op: bool = False):
        fence = jax.block_until_ready if fence_every_op else (lambda x: x)
        ex_arg = u if self._exchange_takes_u else None
        with regions.annotate("bench_comm", category="app"):
            with regions.annotate("pre-comm", category="api"):
                faces = fence(self.extract(u))
            with regions.annotate("post-send", category="api"):
                arg = ex_arg if self._exchange_takes_u else faces
                if engine is not None:
                    req = self.exchange_request = engine.submit(
                        self.exchange, arg)
                    halos = None
                else:
                    halos = fence(self.exchange(arg))
            with regions.annotate("post-comm", category="api"):
                # compute-only region: always fenced so every backend's
                # tree charges its stencil cost here (the engine's
                # exchange still progresses concurrently on its thread)
                out = self.interior(u)
                jax.block_until_ready(out)
            with regions.annotate("wait-recv", category="collective"):
                if engine is not None:
                    halos = req.wait()
                else:
                    jax.block_until_ready(halos)
            with regions.annotate("post-recv", category="api"):
                out = fence(self.boundary(out, u, halos))
        return out

    def run(self, u, steps: int, engine=None, fence_every_op: bool = False):
        for s in range(steps):
            with regions.annotate(f"cycle_{s}", category="app"):
                u = self.step(u, engine=engine,
                              fence_every_op=fence_every_op)
        with regions.annotate("wait-send", category="collective"):
            jax.block_until_ready(u)
        return u


def make_xla_auto_fn(mesh: Mesh, width: int = 1, steps: int = 1):
    """The 'vendor' implementation: plain jnp.roll on a sharded global
    array — GSPMD chooses the collectives (Spectrum-MPI analog)."""

    def run(u):
        for _ in range(steps):
            u = stencil_global(u)
        return u

    return run
