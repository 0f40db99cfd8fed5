"""Mamba selective-scan Pallas kernel vs the naive-scan oracle."""
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
import hypothesis.strategies as st

from repro.kernels.mamba_scan.ops import mamba_scan
from repro.kernels.mamba_scan.ref import selective_scan_reference

KEY = jax.random.PRNGKey(7)


def inputs(B, T, dI, N, dtype=jnp.float32):
    ks = [jax.random.fold_in(KEY, i) for i in range(6)]
    x = jax.random.normal(ks[0], (B, T, dI), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(
        jax.random.normal(ks[1], (B, T, dI), jnp.float32) - 2).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (dI, N), jnp.float32) * 0.5)
    Bc = jax.random.normal(ks[3], (B, T, N), jnp.float32).astype(dtype)
    Cc = jax.random.normal(ks[4], (B, T, N), jnp.float32).astype(dtype)
    D = jax.random.normal(ks[5], (dI,), jnp.float32)
    return x, dt, A, Bc, Cc, D


@pytest.mark.parametrize("B,T,dI,N", [
    (1, 32, 64, 4), (2, 64, 128, 8), (1, 128, 64, 16),
])
def test_shapes(B, T, dI, N):
    x, dt, A, Bc, Cc, D = inputs(B, T, dI, N)
    out = mamba_scan(x, dt, A, Bc, Cc, D, block_d=32, block_t=32,
                     interpret=True)
    ref = selective_scan_reference(x, dt, A, Bc, Cc, D)
    assert float(jnp.abs(out - ref).max()) < 1e-4


def test_bf16_inputs():
    x, dt, A, Bc, Cc, D = inputs(1, 64, 64, 8, dtype=jnp.bfloat16)
    out = mamba_scan(x, dt, A, Bc, Cc, D, block_d=32, block_t=32,
                     interpret=True)
    ref = selective_scan_reference(x, dt, A, Bc, Cc, D)
    assert float(jnp.abs(out.astype(jnp.float32) - ref).max()) < 5e-2


def test_state_carries_across_time_blocks():
    # output at t > block_t must depend on inputs before the block boundary
    x, dt, A, Bc, Cc, D = inputs(1, 64, 32, 4)
    out1 = mamba_scan(x, dt, A, Bc, Cc, D, block_d=32, block_t=16,
                      interpret=True)
    x2 = x.at[:, 0].set(x[:, 0] + 10.0)
    out2 = mamba_scan(x2, dt, A, Bc, Cc, D, block_d=32, block_t=16,
                      interpret=True)
    assert float(jnp.abs(out1[:, 32:] - out2[:, 32:]).max()) > 0


@settings(max_examples=6, deadline=None)
@given(st.sampled_from([16, 32, 48]), st.sampled_from([32, 64]),
       st.sampled_from([4, 8]))
def test_property_sweep(T, dI, N):
    x, dt, A, Bc, Cc, D = inputs(1, T, dI, N)
    out = mamba_scan(x, dt, A, Bc, Cc, D, block_d=16, block_t=16,
                     interpret=True)
    ref = selective_scan_reference(x, dt, A, Bc, Cc, D)
    assert float(jnp.abs(out - ref).max()) < 1e-4
