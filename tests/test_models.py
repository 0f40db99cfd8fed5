"""Per-arch smoke tests (reduced configs): one train step on CPU, shape
and finiteness assertions; decode==forward consistency per family."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import ARCHS, get_config
from repro.models import model as M
from repro.optim import adamw
from repro.train.step import (make_decode_step, make_prefill_step,
                              make_train_step)

KEY = jax.random.PRNGKey(0)
B, T = 2, 24


def batch_for(cfg, B, T, with_labels=True):
    b = {}
    if cfg.input_mode == "frames":
        b["frames"] = jax.random.normal(KEY, (B, T, cfg.d_model), jnp.float32)
        if with_labels:
            b["labels"] = jax.random.randint(
                KEY, (B, T, cfg.n_codebooks), 0, cfg.vocab_size)
    else:
        b["tokens"] = jax.random.randint(KEY, (B, T), 0, cfg.vocab_size)
        if with_labels:
            b["labels"] = jax.random.randint(KEY, (B, T), 0, cfg.vocab_size)
    if cfg.input_mode == "tokens+image":
        b["encoder_embeddings"] = jax.random.normal(
            KEY, (B, cfg.encoder_len, cfg.d_model), jnp.float32) * 0.1
    return b


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_train_step_smoke(arch):
    cfg = get_config(arch, "smoke")
    params = M.init_params(KEY, cfg)
    batch = batch_for(cfg, B, T)
    step = make_train_step(cfg, adamw.AdamWConfig(total_steps=4))
    opt = adamw.init_state(params)
    p2, o2, metrics = jax.jit(step)(params, opt, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss)
    # loss near ln(vocab) at random init
    assert abs(loss - np.log(cfg.vocab_size)) < 1.0
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, x: a + float(jnp.abs(x).sum()),
        jax.tree.map(lambda a, b: a - b, p2, params), 0.0)
    assert delta > 0
    assert int(o2["step"]) == 1


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch, "smoke")
    params = M.init_params(KEY, cfg)
    hidden, aux, _ = M.forward(params, batch_for(cfg, B, T, False), cfg,
                               mode="train")
    assert hidden.shape == (B, T, cfg.d_model)
    assert bool(jnp.isfinite(hidden.astype(jnp.float32)).all())


# MoE archs use capacity_factor=8 here: capacity dropping (not a bug)
# otherwise makes parallel and token-by-token paths diverge.
@pytest.mark.parametrize("arch", [
    "qwen3-32b", "gemma3-12b", "jamba-v0.1-52b", "xlstm-125m",
    "musicgen-large", "llama-3.2-vision-11b", "deepseek-moe-16b", "yi-6b",
])
def test_decode_matches_forward(arch):
    cfg = get_config(arch, "smoke")
    changes = {"dtype": "float32"}
    if cfg.moe is not None:
        changes["moe"] = dataclasses.replace(cfg.moe, capacity_factor=8.0)
    cfg = dataclasses.replace(cfg, **changes)
    params = M.init_params(KEY, cfg)
    batch = batch_for(cfg, B, T, with_labels=False)
    hidden, _, _ = M.forward(params, batch, cfg, mode="train")
    full_logits = (hidden @ params["lm_head"]).astype(jnp.float32).reshape(
        B, T, cfg.n_codebooks, cfg.padded_vocab_size)

    Tp = T - 4
    pb = {k: (v[:, :Tp] if k in ("tokens", "frames") else v)
          for k, v in batch.items()}
    logits_p, caches = make_prefill_step(cfg)(params, pb)
    assert float(jnp.abs(
        logits_p - M.mask_pad_logits(full_logits[:, Tp - 1], cfg)).max()) < 1e-4

    # grow full-attention caches from Tp to T capacity
    def grow(path, arr):
        nm = path[-1].key
        if nm in ("k", "v") and arr.ndim == 5 and arr.shape[2] == Tp:
            pad = jnp.zeros((arr.shape[0], arr.shape[1], T - Tp)
                            + arr.shape[3:], arr.dtype)
            return jnp.concatenate([arr, pad], axis=2)
        if nm == "pos" and arr.ndim == 2 and arr.shape[1] == Tp:
            return jnp.concatenate(
                [arr, jnp.full((arr.shape[0], T - Tp), -1, jnp.int32)], 1)
        return arr

    caches = jax.tree_util.tree_map_with_path(grow, caches)
    ds = make_decode_step(cfg)
    for t in range(Tp, T):
        db = {k: v[:, t:t + 1] for k, v in batch.items()
              if k in ("tokens", "frames")}
        logits_d, _, caches = ds(params, caches, db, jnp.int32(t))
        err = float(jnp.abs(
            logits_d - M.mask_pad_logits(full_logits[:, t], cfg)).max())
        assert err < 1e-3, (t, err)


def test_param_counts_full_configs():
    # full-config param counts should be in the right ballpark
    expect = {
        "qwen3-32b": (30e9, 36e9),
        "yi-6b": (5e9, 7e9),
        "jamba-v0.1-52b": (45e9, 60e9),
        "deepseek-moe-16b": (14e9, 20e9),
        "minicpm-2b": (2e9, 3.5e9),
        "xlstm-125m": (0.1e9, 0.2e9),
        "gemma3-12b": (10e9, 14e9),
    }
    for arch, (lo, hi) in expect.items():
        n = M.param_count(get_config(arch))
        assert lo < n < hi, (arch, n)


def test_moe_capacity_drops_are_bounded():
    cfg = get_config("deepseek-moe-16b", "smoke")
    params = M.init_params(KEY, cfg)
    batch = batch_for(cfg, 4, 64, with_labels=False)
    _, aux, _ = M.forward(params, batch, cfg, mode="train")
    # aux = [aux_loss, load_balance, router_z, dropped]; drop rate sane
    n_moe_layers = cfg.n_layers
    dropped = float(aux[3]) / n_moe_layers
    assert 0.0 <= dropped < 0.5


def test_windowed_cache_smaller_than_full():
    cfg = get_config("gemma3-12b", "smoke")
    shapes = M.init_cache_shapes(cfg, batch=2, seq_len=4096)
    # local layers (window=1024 in full cfg; smoke keeps window value)
    win = cfg.pattern[0].window
    k0 = shapes["pos0"]["mixer"]["k"].shape
    k5 = shapes["pos5"]["mixer"]["k"].shape
    assert k0[2] == min(4096, win)
    assert k5[2] == 4096


def test_scaled_init_uses_contraction_fan_in():
    # a stacked (layers, fan_in, out) projection: the leading layer dim
    # must not stand in for the fan-in
    from repro.models.common import ParamSpec, init_param
    spec = ParamSpec((6, 1536, 768), ("layers", None, "embed"),
                     init="scaled", scale=1.0)
    w = init_param(KEY, spec, jnp.float32)
    assert abs(float(w.std()) * np.sqrt(1536) - 1.0) < 0.01


def test_slstm_gradient_bounded_over_long_sequence():
    # the sLSTM recurrence is backpropagated through 1024 steps; with the
    # recurrent weights initialized too large the gradient norm explodes
    cfg = get_config("xlstm-125m", "smoke")
    params = M.init_params(KEY, cfg)
    opt_cfg = adamw.AdamWConfig(total_steps=2, warmup_steps=1)
    step = jax.jit(make_train_step(cfg, opt_cfg))
    _, _, m = step(params, adamw.init_state(params), batch_for(cfg, 1, 1024))
    assert float(m["grad_norm"]) < 10.0
