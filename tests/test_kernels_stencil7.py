"""7-point stencil Pallas kernel (interpret mode) against the jnp rolled
stencil, and the choice between them in the halo program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm.halo import make_halo_fn, rolled_stencil, stencil_interior
from repro.core.compat import make_mesh
from repro.kernels.stencil7.kernel import VMEM_BUDGET, slab_depth, vmem_bytes
from repro.kernels.stencil7.ops import stencil7, tiles


def field(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


@pytest.mark.parametrize("shape,bx", [
    ((8, 8, 128), 8),      # X == bx: both neighbour planes wrap onto the slab
    ((2, 8, 128), 2),      # the smallest slab that holds two planes
    ((24, 8, 128), 8),     # several slabs
    ((12, 16, 256), 4),    # Y != Z, several slabs
    ((6, 8, 128), 2),
    ((5, 24, 128), 1),     # slabs of one plane
    ((1, 8, 128), 1),      # one plane, its own neighbour on both sides
])
def test_kernel_matches_rolled_stencil(shape, bx):
    assert slab_depth(shape, 4) == bx
    u = field(shape)
    out = stencil7(u, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(rolled_stencil(u)),
                               rtol=0, atol=1e-5)


def test_slab_depth():
    assert slab_depth((512, 512, 512), 4) == 8
    assert slab_depth((12, 8, 128), 4) == 4
    assert slab_depth((6, 8, 128), 4) == 2
    assert slab_depth((7, 8, 128), 4) == 1
    # a plane too wide for even a one-plane slab
    wide = (8, 4096, 4096)
    assert vmem_bytes(wide, 4, 1) > VMEM_BUDGET
    assert slab_depth(wide, 4) is None


@pytest.mark.parametrize("shape,want", [
    ((512, 512, 512), True),
    ((8, 8, 128), True),
    ((16, 16, 16), False),     # the tests' box: z is not a lane tile
    ((8, 12, 128), False),     # y is not a sublane tile
    ((8, 4096, 4096), False),  # no slab fits VMEM
])
def test_tiles(shape, want):
    assert tiles(shape, 4) is want


@pytest.mark.parametrize("shape", [(8, 8, 128), (16, 16, 16)])
def test_cpu_takes_the_rolled_stencil(shape):
    """On the CPU, a block that tiles and one that does not both compile
    to XLA's rolls, with no kernel, and give the rolled answer."""
    u = field(shape)
    fn = jax.jit(stencil_interior)
    assert "custom-call" not in fn.lower(u).compile().as_text()
    np.testing.assert_array_equal(np.asarray(fn(u)),
                                  np.asarray(jax.jit(rolled_stencil)(u)))


def test_fused_program_on_a_tiling_block():
    """The fused program traces the kernel's branch under shard_map (its
    output carries the block's varying axes) and, on the CPU, runs the
    rolled branch: one step on one device is the periodic stencil."""
    mesh = make_mesh((1, 1, 1), ("x", "y", "z"), devices=jax.devices()[:1])
    u = jax.device_put(field((8, 8, 128), seed=1),
                       NamedSharding(mesh, P("x", "y", "z")))
    out = jax.jit(make_halo_fn(mesh, steps=1))(u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(rolled_stencil(u)),
                               rtol=0, atol=1e-5)
