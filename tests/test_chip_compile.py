"""Compile the main-path kernels and the halo step at real widths for one
described TPU v5e chip. Nothing runs: the TPU compiler is installed here
and refuses what the chip would refuse (block shapes off the (8, 128)
tiling, dynamic sub-tile loads, programs over the 16 GB of HBM) at no
chip time. Interpret-mode tests cannot see any of that.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.comm.halo import HaloProgram, make_halo_fn
from repro.core import hlo
from repro.core.compat import make_mesh
from repro.kernels.flash_attention.kernel import (flash_attention_bwd,
                                                  flash_attention_fwd)
from repro.kernels.mamba_scan.kernel import selective_scan
from repro.kernels.stencil7.kernel import slab_depth, vmem_bytes

HBM_BYTES = 16 * 10**9
VMEM_BYTES = 128 * 2**20  # per v5e core


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    compilation_cache.reset_cache()
    if prev_log is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled) -> bool:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes) <= HBM_BYTES


@pytest.mark.parametrize("d_head", [64, 128])
def test_flash_attention_compiles(one_chip, d_head):
    B, H, T = 1, 8, 4096
    q = _spec((B, H, T, d_head), jnp.bfloat16, one_chip)
    lse = _spec((B, H, T, 1), jnp.float32, one_chip)
    fwd = jax.jit(lambda q, k, v: flash_attention_fwd(
        q, k, v, block_q=128, block_k=512, interpret=False)
    ).lower(q, q, q).compile()
    bwd = jax.jit(lambda q, k, v, o, l, do: flash_attention_bwd(
        q, k, v, o, l, do, block_q=128, block_k=512, interpret=False)
    ).lower(q, q, q, q, lse, q).compile()
    for compiled in (fwd, bwd):
        assert "tpu_custom_call" in compiled.as_text()
        assert _fits(compiled)


def test_selective_scan_compiles(one_chip):
    # Jamba's mixer widths: d_inner 8192, d_state 16
    B, T, dI, N = 1, 4096, 8192, 16
    act = _spec((B, T, dI), jnp.bfloat16, one_chip)
    bc = _spec((B, T, N), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda x, dt, a, b, c, d: selective_scan(
        x, dt, a, b, c, d, interpret=False)).lower(
        act, act, _spec((dI, N), jnp.float32, one_chip), bc, bc,
        _spec((dI,), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits(compiled)


BOX = 512


@pytest.fixture(scope="module")
def halo_mesh(topo):
    return make_mesh((1, 1, 1), ("x", "y", "z"), devices=topo.devices[:1])


@pytest.fixture(scope="module")
def halo_field(halo_mesh):
    """A 512^3 float32 box (512 MiB field, 1 MiB faces) on one chip."""
    return _spec((BOX, BOX, BOX), jnp.float32,
                 NamedSharding(halo_mesh, P("x", "y", "z")))


@pytest.fixture(scope="module")
def halo_512(halo_mesh, halo_field):
    """The fused 4-step halo program on the 512^3 box, compiled once per
    variant."""
    compiled = {}

    def get(variant):
        if variant not in compiled:
            compiled[variant] = jax.jit(make_halo_fn(
                halo_mesh, variant=variant, steps=4)).lower(
                halo_field).compile()
        return compiled[variant]
    return get


@pytest.mark.parametrize("variant", ["overlap", "blocking"])
def test_halo_step_compiles(halo_512, variant):
    compiled = halo_512(variant)
    assert "collective-permute" in compiled.as_text()
    assert _fits(compiled)


_OPCODE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?\s"
                     r"(fusion|custom-call|collective-permute"
                     r"(?:-start|-done)?)\(")


_ANY_OP = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?\s([\w\-]+)\(")
# ops that move no data
_VIEWS = {"bitcast", "get-tuple-element", "tuple", "parameter"}


def _kernel_calls(text):
    """(instruction, op_name) of each Mosaic kernel call in ``text``."""
    scopes = hlo.op_scopes(text)
    return [(m.group(1), scopes.get(m.group(1), ""))
            for line in hlo.logical_lines(text)
            for m in [_ANY_OP.match(line)]
            if m and m.group(2) == "custom-call" and "tpu_custom_call" in line]


def _kernel_fits_vmem():
    # Mosaic refuses a kernel whose scoped VMEM exceeds the limit it
    # asks for; that limit must lie within the core's VMEM
    bx = slab_depth((BOX, BOX, BOX), 4)
    assert bx == 8
    assert vmem_bytes((BOX, BOX, BOX), 4, bx) <= VMEM_BYTES


@pytest.mark.parametrize("variant", ["overlap", "blocking"])
def test_halo_interior_is_the_kernel(halo_512, variant):
    """Each of the 4 steps computes its interior in one stencil7 kernel
    call, in the halo.interior scope, and no XLA op of that scope is
    left to pass over the field."""
    text = halo_512(variant).as_text()
    calls = _kernel_calls(text)
    assert len(calls) == 4, calls
    for name, op_name in calls:
        assert "halo.interior" in op_name.split("/"), (name, op_name)
        assert "halo_stencil7" in op_name, (name, op_name)
    scopes = hlo.op_scopes(text)
    others = [(m.group(1), m.group(2)) for line in hlo.logical_lines(text)
              for m in [_ANY_OP.match(line)]
              if m and m.group(2) not in _VIEWS | {"custom-call"}
              and "halo.interior" in scopes.get(m.group(1), "").split("/")]
    assert not others, others
    _kernel_fits_vmem()
    assert _fits(halo_512(variant))


def test_profiled_interior_segment_is_the_kernel(halo_mesh, halo_field):
    """HaloProgram(explicit=True)'s interior segment, the profiled
    cell's post-comm region, is one stencil7 kernel call."""
    compiled = HaloProgram(halo_mesh, explicit=True).interior.lower(
        halo_field).compile()
    calls = _kernel_calls(compiled.as_text())
    assert len(calls) == 1 and "halo.interior" in calls[0][1], calls
    _kernel_fits_vmem()
    assert _fits(compiled)


@pytest.mark.parametrize("variant", ["overlap", "blocking"])
def test_halo_ops_carry_their_scope(halo_512, variant):
    """Every fusion, kernel call and collective-permute the chip's
    compiler emits for the halo step names one of the program's halo.*
    scopes in its op_name, which the profiler trace carries to each
    device op."""
    text = halo_512(variant).as_text()
    scopes = hlo.op_scopes(text)
    ops = [m.groups() for m in map(_OPCODE.match, hlo.logical_lines(text))
           if m]
    assert ({"fusion", "custom-call", "collective-permute-start"}
            <= {op for _, op in ops})
    for name, op in ops:
        parts = scopes.get(name, "").split("/")
        scope = [p for p in parts if p.startswith("halo.")]
        assert scope, (name, op, scopes.get(name))
        if op.startswith("collective-permute"):
            assert scope == ["halo.exchange"], (name, scopes[name])
