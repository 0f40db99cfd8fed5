"""COMB analog: 3-D halo exchange + stencil under shard_map.

COMB (paper §2.3) explores communication-pattern tradeoffs for structured
mesh halo exchanges: blocking vs non-blocking, staging buffers, message
sizes. The TPU-meaningful axes of that design space:

  * variant="blocking"  — exchange all faces, *then* compute the stencil
    (the wire time is fully exposed; COMB's waitall-before-compute).
  * variant="overlap"   — compute the interior stencil while faces are in
    flight; apply boundary columns afterwards (comm hidden behind compute).
  * width, box          — message size sweep (COMB's size sweeps).

Regions are named after COMB's own Caliper annotations (pre-comm,
post-send, wait-recv, post-comm, ...) so the comparison trees in
benchmarks/ read like the paper's Figures 1-3.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import regions
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


def stencil_interior(u: jax.Array) -> jax.Array:
    """7-point Laplacian on the local block (interior only; edges wrong
    until halos are applied)."""
    return (
        -6.0 * u
        + jnp.roll(u, 1, 0) + jnp.roll(u, -1, 0)
        + jnp.roll(u, 1, 1) + jnp.roll(u, -1, 1)
        + jnp.roll(u, 1, 2) + jnp.roll(u, -1, 2)
    )


def _apply_halos(out, u, halos, width: int):
    """Fix the wrap-around faces of the rolled stencil with true halos."""
    w = width
    for axis, (lo, hi) in halos.items():
        ax = {"x": 0, "y": 1, "z": 2}[axis]

        def face(arr, front: bool):
            idx = [slice(None)] * 3
            idx[ax] = slice(0, w) if front else slice(-w, None)
            return arr[tuple(idx)]

        # replace the wrong wrap contribution with the neighbor's face
        def fix(front, halo):
            nonlocal out
            idx = [slice(None)] * 3
            idx[ax] = slice(0, w) if front else slice(-w, None)
            wrong = face(jnp.roll(u, 1 if front else -1, ax), front)
            corr = face(out, front) - wrong + halo
            out = out.at[tuple(idx)].set(corr)

        fix(True, lo)
        fix(False, hi)
    return out


def halo_step(u: jax.Array, axis_names=("x", "y", "z"), width: int = 1,
              variant: str = "overlap") -> jax.Array:
    """One stencil step with halo exchange on the local block (in shard_map)."""
    w = width
    halos: Dict[str, Tuple[jax.Array, jax.Array]] = {}

    with regions.annotate("bench_comm", category="app"):
        with regions.annotate("pre-comm", category="api"):
            faces = {}
            for name in axis_names:
                ax = {"x": 0, "y": 1, "z": 2}[name]
                idx_lo = [slice(None)] * 3
                idx_lo[ax] = slice(0, w)
                idx_hi = [slice(None)] * 3
                idx_hi[ax] = slice(-w, None)
                faces[name] = (u[tuple(idx_lo)], u[tuple(idx_hi)])

        with regions.annotate("post-send", category="api"), \
                comm_phase("halo_exchange"):
            for i, name in enumerate(axis_names):
                lo_face, hi_face = faces[name]
                # receive the neighbor's hi face as my lo halo and vice versa
                halos[name] = (
                    _shift(hi_face, name, +1, ax=i),
                    _shift(lo_face, name, -1, ax=i),
                )

        if variant == "blocking":
            with regions.annotate("wait-recv", category="api"):
                # one queue: pin compute behind the completed exchange
                flat, tree = jax.tree.flatten(halos)
                flat = list(jax.lax.optimization_barrier(tuple(flat)))
                u_b = jax.lax.optimization_barrier(u)
                halos = jax.tree.unflatten(tree, flat)
            with regions.annotate("post-comm", category="api"):
                out = stencil_interior(u_b)
                out = _apply_halos(out, u_b, halos, w)
        else:
            with regions.annotate("post-comm", category="api"):
                # second queue: interior stencil runs while faces fly
                out = stencil_interior(u)
            with regions.annotate("wait-recv", category="api"):
                out = _apply_halos(out, u, halos, w)
    return out


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
            faces = {}
            for name in axes:
                ax = {"x": 0, "y": 1, "z": 2}[name]
                idx_lo = [slice(None)] * 3
                idx_lo[ax] = slice(0, w)
                idx_hi = [slice(None)] * 3
                idx_hi[ax] = slice(-w, None)
                faces[name] = (u[tuple(idx_lo)], u[tuple(idx_hi)])
            return faces

        def exchange(faces):
            halos = {}
            with comm_phase("halo_exchange"):
                for i, name in enumerate(axes):
                    lo_face, hi_face = faces[name]
                    halos[name] = (
                        _shift(hi_face, name, +1, ax=i),
                        _shift(lo_face, name, -1, ax=i),
                    )
            return halos

        def interior(u):
            return stencil_interior(u)

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
        from ..core import regions
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
        from ..core import regions
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

    def step(u):
        with regions.annotate("bench_comm", category="app"):
            with regions.annotate("post-comm", category="api"):
                return (
                    -6.0 * u
                    + jnp.roll(u, 1, 0) + jnp.roll(u, -1, 0)
                    + jnp.roll(u, 1, 1) + jnp.roll(u, -1, 1)
                    + jnp.roll(u, 1, 2) + jnp.roll(u, -1, 2)
                )

    def run(u):
        for _ in range(steps):
            u = step(u)
        return u

    return run
