"""Optimizer math, schedules, compression error feedback, data pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import hypothesis.strategies as st
from hypothesis import given, settings

from repro.configs.archs import get_config
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.optim import adamw, compress


def test_adamw_matches_reference_step():
    cfg = adamw.AdamWConfig(lr=1e-2, b1=0.9, b2=0.999, eps=1e-8,
                            weight_decay=0.0, clip_norm=None,
                            schedule="constant", warmup_steps=1)
    params = {"w": jnp.array([1.0, 2.0])}
    grads = {"w": jnp.array([0.1, -0.2])}
    state = adamw.init_state(params)
    new_params, new_state, _ = adamw.apply_updates(params, grads, state, cfg)
    # hand-computed adam step 1: mhat=g, vhat=g^2 -> delta = g/(|g|+eps)
    expect = params["w"] - 1e-2 * np.sign([0.1, -0.2])
    assert np.allclose(np.asarray(new_params["w"]), expect, atol=1e-5)
    assert int(new_state["step"]) == 1


def test_weight_decay_mask():
    cfg = adamw.AdamWConfig(lr=1e-2, weight_decay=0.5, clip_norm=None,
                            schedule="constant", warmup_steps=1)
    params = {"w": jnp.array([[1.0]]), "norm_scale": jnp.array([1.0])}
    grads = jax.tree.map(jnp.zeros_like, params)
    new_params, _, _ = adamw.apply_updates(
        params, grads, adamw.init_state(params), cfg)
    assert float(new_params["w"][0, 0]) < 1.0        # decayed
    assert float(new_params["norm_scale"][0]) == 1.0  # masked


def test_grad_clipping():
    cfg = adamw.AdamWConfig(lr=0.0, clip_norm=1.0, schedule="constant")
    params = {"w": jnp.zeros((3,))}
    grads = {"w": jnp.array([10.0, 0.0, 0.0])}
    _, _, metrics = adamw.apply_updates(params, grads,
                                        adamw.init_state(params), cfg)
    assert float(metrics["grad_norm"]) > 9.0


def test_wsd_schedule_shape():
    cfg = adamw.AdamWConfig(lr=1.0, schedule="wsd", warmup_steps=10,
                            total_steps=100, decay_frac=0.2,
                            min_lr_ratio=0.1)
    fn = adamw.schedule_fn(cfg)
    assert float(fn(jnp.int32(5))) == 0.5          # warmup
    assert abs(float(fn(jnp.int32(50))) - 1.0) < 1e-6   # stable plateau
    assert abs(float(fn(jnp.int32(79))) - 1.0) < 1e-6   # still stable
    assert float(fn(jnp.int32(100))) <= 0.11       # decayed to min ratio
    # decay is monotone
    vals = [float(fn(jnp.int32(s))) for s in range(80, 101, 5)]
    assert all(a >= b - 1e-9 for a, b in zip(vals, vals[1:]))


@settings(max_examples=40, deadline=None)
@given(st.lists(st.floats(min_value=-100, max_value=100,
                          allow_nan=False), min_size=1, max_size=32))
def test_compression_error_feedback_contracts(values):
    """Quantize-with-error-feedback property: the carried error is bounded
    by one quantization bucket, so accumulated updates stay unbiased."""
    x = jnp.asarray(values, jnp.float32)
    err = jnp.zeros_like(x)
    q, scale, err2 = compress.compress(x, err)
    deq = compress.decompress(q, scale)
    assert np.allclose(np.asarray(deq + err2), np.asarray(x), atol=1e-4)
    assert float(jnp.abs(err2).max()) <= float(scale) / 2 + 1e-6


def test_compressed_psum_single_device():
    # axis size 1: compressed psum == identity up to quantization
    from jax.sharding import PartitionSpec as P
    from repro.core.compat import make_mesh
    mesh = make_mesh((1,), ("d",))
    g = {"w": jnp.array([1.0, -2.0, 3.0])}
    e = compress.init_error(g)
    out, _ = jax.jit(jax.shard_map(
        lambda g, e: compress.compressed_psum(g, e, "d"),
        mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P())))(g, e)
    assert np.allclose(np.asarray(out["w"]), np.asarray(g["w"]), atol=0.05)


def test_data_determinism_and_resume():
    cfg = get_config("yi-6b", "smoke")
    d1 = SyntheticTokens(cfg, DataConfig(seed=7, batch=4, seq_len=32))
    d2 = SyntheticTokens(cfg, DataConfig(seed=7, batch=4, seq_len=32))
    b1 = d1.batch_at(123)
    b2 = d2.batch_at(123)            # fresh object, same (seed, step)
    assert np.array_equal(b1["tokens"], b2["tokens"])
    it = d1.iterate(start_step=123)
    assert np.array_equal(next(it)["tokens"], b1["tokens"])
    # different steps differ
    assert not np.array_equal(d1.batch_at(124)["tokens"], b1["tokens"])


def test_data_has_learnable_structure():
    cfg = get_config("yi-6b", "smoke")
    d = SyntheticTokens(cfg, DataConfig(seed=7, batch=8, seq_len=64))
    b = d.batch_at(0)
    # each label token must be one of the 64 allowed successors
    succ = d._succ
    tok, lab = b["tokens"], b["labels"]
    ok = np.zeros(tok.shape, bool)
    for j in range(succ.shape[1]):
        ok |= succ[tok][:, :, j] == lab
    assert ok.mean() == 1.0
