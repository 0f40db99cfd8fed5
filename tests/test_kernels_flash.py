"""Flash-attention Pallas kernel vs the jnp oracle: shape/dtype sweeps,
GQA, sliding windows, gradients — all in interpret mode on CPU."""
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
import hypothesis.strategies as st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import mha_reference

KEY = jax.random.PRNGKey(0)


def rand(shape, dtype, i):
    return jax.random.normal(jax.random.fold_in(KEY, i), shape, jnp.float32
                             ).astype(dtype)


@pytest.mark.parametrize("B,T,H,D", [
    (1, 128, 1, 64), (2, 256, 4, 64), (1, 128, 2, 128), (1, 64, 8, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_shapes_dtypes(B, T, H, D, dtype):
    q = rand((B, T, H, D), dtype, 1)
    k = rand((B, T, H, D), dtype, 2)
    v = rand((B, T, H, D), dtype, 3)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    ref = mha_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert float(jnp.abs(out.astype(jnp.float32) - ref).max()) < tol
    assert out.dtype == dtype and out.shape == q.shape


def test_gqa_expansion():
    B, T, H, K, D = 2, 128, 8, 2, 64
    q = rand((B, T, H, D), jnp.float32, 1)
    k = rand((B, T, K, D), jnp.float32, 2)
    v = rand((B, T, K, D), jnp.float32, 3)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    kx = jnp.repeat(k, H // K, axis=2)
    vx = jnp.repeat(v, H // K, axis=2)
    ref = mha_reference(q, kx, vx)
    assert float(jnp.abs(out - ref).max()) < 2e-5


@pytest.mark.parametrize("window", [32, 64, 100])
def test_sliding_window(window):
    B, T, H, D = 1, 256, 2, 64
    q = rand((B, T, H, D), jnp.float32, 1)
    k = rand((B, T, H, D), jnp.float32, 2)
    v = rand((B, T, H, D), jnp.float32, 3)
    out = flash_attention(q, k, v, window=window, block_q=64, block_k=64,
                          interpret=True)
    ref = mha_reference(q, k, v, window=window)
    assert float(jnp.abs(out - ref).max()) < 2e-5


def test_non_causal():
    B, T, H, D = 1, 128, 2, 64
    q = rand((B, T, H, D), jnp.float32, 1)
    k = rand((B, T, H, D), jnp.float32, 2)
    v = rand((B, T, H, D), jnp.float32, 3)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=False)
    assert float(jnp.abs(out - ref).max()) < 2e-5


def test_gradients_match_reference():
    B, T, H, D = 1, 128, 2, 64
    q = rand((B, T, H, D), jnp.float32, 1)
    k = rand((B, T, H, D), jnp.float32, 2)
    v = rand((B, T, H, D), jnp.float32, 3)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=64, block_k=64,
                                interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (mha_reference(q, k, v) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        rel = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
        assert rel < 1e-4, rel


def test_windowed_gradients():
    B, T, H, D = 1, 128, 2, 64
    q = rand((B, T, H, D), jnp.float32, 1)
    k = rand((B, T, H, D), jnp.float32, 2)
    v = rand((B, T, H, D), jnp.float32, 3)

    def lf(q, k, v):
        return (flash_attention(q, k, v, window=48, block_q=64,
                                block_k=64, interpret=True) ** 2).sum()

    def lr(q, k, v):
        return (mha_reference(q, k, v, window=48) ** 2).sum()

    g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        rel = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
        assert rel < 1e-4, rel


@settings(max_examples=8, deadline=None)
@given(
    st.sampled_from([32, 64, 96, 128]),      # T
    st.sampled_from([32, 64]),               # D
    st.sampled_from([1, 2]),                 # H
    st.booleans(),                           # causal
)
def test_property_sweep(T, D, H, causal):
    q = rand((1, T, H, D), jnp.float32, T + D)
    k = rand((1, T, H, D), jnp.float32, T + D + 1)
    v = rand((1, T, H, D), jnp.float32, T + D + 2)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=causal)
    assert float(jnp.abs(out - ref).max()) < 2e-5
