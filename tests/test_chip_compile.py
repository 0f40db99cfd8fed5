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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.comm.halo import make_halo_fn
from repro.core.compat import make_mesh
from repro.kernels.flash_attention.kernel import (flash_attention_bwd,
                                                  flash_attention_fwd)
from repro.kernels.mamba_scan.kernel import selective_scan

HBM_BYTES = 16 * 10**9


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


@pytest.mark.parametrize("variant", ["overlap", "blocking"])
def test_halo_step_compiles(topo, variant):
    # a 512^3 float32 box (512 MiB field, 1 MiB faces) on one chip
    mesh = make_mesh((1, 1, 1), ("x", "y", "z"), devices=topo.devices[:1])
    u = _spec((512, 512, 512), jnp.float32,
              NamedSharding(mesh, P("x", "y", "z")))
    compiled = jax.jit(make_halo_fn(mesh, variant=variant, steps=4)
                       ).lower(u).compile()
    assert "collective-permute" in compiled.as_text()
    assert _fits(compiled)
