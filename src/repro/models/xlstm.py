"""xLSTM blocks: mLSTM (parallel, matrix-memory) and sLSTM (recurrent).

mLSTM runs in a chunked linear-attention form with exponential input gates
and sigmoid-in-log-space forget gates, carrying (C, n, m) state across
chunks (C: (B, H, D, D) matrix memory; n: normalizer; m: log-stabilizer).
sLSTM is a true recurrence (scan over time) with exponential gating,
per-head block-diagonal recurrent weights and the (c, n, m) stabilized
state of the paper.

Per xLSTM-125M, blocks are pre-up-projection: the config's d_ff=0 means
the feed-forward lives inside the blocks (mLSTM pf=2, sLSTM MLP pf=4/3).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .common import ParamSpec, activation, rms_norm


def _mlstm_dims(cfg: ModelConfig) -> Tuple[int, int, int]:
    pf = cfg.xlstm.proj_factor_mlstm
    d_inner = int(cfg.d_model * pf)
    H = cfg.n_heads
    return d_inner, H, d_inner // H


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

def mlstm_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    E = cfg.d_model
    dI, H, Dh = _mlstm_dims(cfg)
    dC = cfg.xlstm.conv_kernel
    return {
        "up_proj": ParamSpec((E, 2 * dI), ("embed", "inner")),
        "conv_w": ParamSpec((dC, dI), (None, "inner"), init="normal", scale=0.1),
        "conv_b": ParamSpec((dI,), ("inner",), init="zeros"),
        # row-parallel: contract the model-sharded inner dim -> psum; the
        # matrix-memory cell then runs on replicated heads (xlstm-125m is
        # far below the TP=16 sweet spot anyway — see DESIGN.md)
        "wq": ParamSpec((dI, dI), ("inner", None)),
        "wk": ParamSpec((dI, dI), ("inner", None)),
        "wv": ParamSpec((dI, dI), ("inner", None)),
        "w_if": ParamSpec((dI, 2 * H), ("inner", None), dtype=jnp.float32),
        "b_if": ParamSpec((2 * H,), (None,), init="zeros", dtype=jnp.float32),
        "skip": ParamSpec((dI,), (None,), init="ones"),
        "out_norm": ParamSpec((dI,), (None,), init="zeros"),
        "down_proj": ParamSpec((dI, E), (None, "embed"), init="scaled", scale=1.0),
    }


def _mlstm_chunk(q, k, v, ilog, flog, state):
    """One chunk of the stabilized chunked mLSTM.

    q,k,v: (B, Q, H, D); ilog, flog: (B, Q, H) log-space gates.
    state: (C (B,H,D,D), n (B,H,D), m (B,H))."""
    B, Q, H, D = q.shape
    C, n, m = state
    F = jnp.cumsum(flog, axis=1)                     # (B, Q, H) inclusive
    Ftot = F[:, -1]                                  # (B, H)
    # log weight of history seen from position t: F_t + m_prev
    # log weight of source s -> target t (s<=t): F_t - F_s + i_s
    logD = (
        F[:, :, None, :] - F[:, None, :, :] + ilog[:, None, :, :]
    )                                                # (B, T=Q, S=Q, H)
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    logD = jnp.where(tri[None, :, :, None], logD, -jnp.inf)
    m_intra = jnp.max(logD, axis=2)                  # (B, Q, H)
    m_inter = F + m[:, None, :]                      # (B, Q, H)
    m_new = jnp.maximum(jnp.maximum(m_intra, m_inter), -1e30)
    Dmat = jnp.exp(logD - m_new[:, :, None, :])      # (B, Q, Q, H)
    scale = 1.0 / jnp.sqrt(D)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bthd,bshd->btsh", qf * scale, kf) * Dmat
    intra = jnp.einsum("btsh,bshd->bthd", scores, vf)
    inter_w = jnp.exp(m_inter - m_new)               # (B, Q, H)
    inter = jnp.einsum("bthd,bhde->bthe", qf * scale, C) * inter_w[..., None]
    num = intra + inter
    qn = jnp.einsum("bthd,bhd->bth", qf * scale, n) * inter_w
    denom = scores.sum(axis=2) + qn                  # (B, Q, H)
    denom = jnp.maximum(jnp.abs(denom), jnp.exp(-m_new))
    h = num / denom[..., None]                       # (B, Q, H, D)
    # ---- state update to end of chunk ----
    m_next = jnp.maximum(Ftot + m, jnp.max(Ftot[:, None, :] - F + ilog, axis=1))
    w_old = jnp.exp(Ftot + m - m_next)               # (B, H)
    w_src = jnp.exp(Ftot[:, None, :] - F + ilog - m_next[:, None, :])  # (B,Q,H)
    C_next = C * w_old[..., None, None] + jnp.einsum(
        "bshd,bshe->bhde", kf * w_src[..., None], vf
    )
    n_next = n * w_old[..., None] + jnp.einsum("bshd,bsh->bhd", kf, w_src)
    return h, (C_next, n_next, m_next)


def mlstm_apply(
    params: Dict[str, jax.Array],
    x: jax.Array,                                    # (B, T, E)
    cfg: ModelConfig,
    cache: Optional[Dict[str, jax.Array]] = None,
    mode: str = "train",
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    from .mamba import _causal_conv

    B, T, E = x.shape
    dI, H, Dh = _mlstm_dims(cfg)
    up = x @ params["up_proj"]
    xm, z = jnp.split(up, 2, axis=-1)
    if mode == "decode":
        conv_tail = cache["conv"]
        xc = _causal_conv(xm, params["conv_w"], params["conv_b"], tail=conv_tail)
        new_tail = jnp.concatenate([conv_tail[:, 1:], xm], axis=1)
    else:
        xc = _causal_conv(xm, params["conv_w"], params["conv_b"])
        new_tail = None
    xc = jax.nn.silu(xc)
    q = (xc @ params["wq"]).reshape(B, T, H, Dh)
    k = (xc @ params["wk"]).reshape(B, T, H, Dh)
    v = (xm @ params["wv"]).reshape(B, T, H, Dh)
    gates = xc.astype(jnp.float32) @ params["w_if"] + params["b_if"]
    ilog, fpre = jnp.split(gates.reshape(B, T, 2, H), 2, axis=2)
    ilog = ilog[:, :, 0]                             # (B, T, H)
    flog = jax.nn.log_sigmoid(fpre[:, :, 0])

    if mode == "decode":
        assert T == 1
        C, n, m = cache["C"], cache["n"], cache["m"]
        m_next = jnp.maximum(flog[:, 0] + m, ilog[:, 0])
        w_old = jnp.exp(flog[:, 0] + m - m_next)
        w_new = jnp.exp(ilog[:, 0] - m_next)
        kf = k[:, 0].astype(jnp.float32)
        vf = v[:, 0].astype(jnp.float32)
        C = C * w_old[..., None, None] + jnp.einsum(
            "bhd,bhe->bhde", kf * w_new[..., None], vf)
        n = n * w_old[..., None] + kf * w_new[..., None]
        qf = q[:, 0].astype(jnp.float32) / jnp.sqrt(Dh)
        num = jnp.einsum("bhd,bhde->bhe", qf, C)
        denom = jnp.maximum(
            jnp.abs(jnp.einsum("bhd,bhd->bh", qf, n)), jnp.exp(-m_next))
        h = (num / denom[..., None])[:, None]        # (B,1,H,D)
        new_cache = {"C": C, "n": n, "m": m_next, "conv": new_tail}
    else:
        # SP boundary: the chunk scan slices time; gather it here
        from ..sharding.rules import constrain

        q = constrain(q, ("batch", None, None, None))
        k = constrain(k, ("batch", None, None, None))
        v = constrain(v, ("batch", None, None, None))
        ilog = constrain(ilog, ("batch", None, None))
        flog = constrain(flog, ("batch", None, None))
        chunk = min(cfg.xlstm.chunk, T)
        pad = -T % chunk
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        ip = jnp.pad(ilog, ((0, 0), (0, pad), (0, 0)), constant_values=-1e30)
        fp = jnp.pad(flog, ((0, 0), (0, pad), (0, 0)))
        nC = qp.shape[1] // chunk

        def step(state, xs):
            qc, kc, vc, ic, fc = xs
            h, state = _mlstm_chunk(qc, kc, vc, ic, fc, state)
            return state, h

        resh = lambda a: jnp.moveaxis(
            a.reshape(B, nC, chunk, *a.shape[2:]), 1, 0)
        state0 = (
            jnp.zeros((B, H, Dh, Dh), jnp.float32),
            jnp.zeros((B, H, Dh), jnp.float32),
            jnp.full((B, H), -1e30, jnp.float32),
        )
        state, hs = jax.lax.scan(
            step, state0, (resh(qp), resh(kp), resh(vp), resh(ip), resh(fp)))
        h = jnp.moveaxis(hs, 0, 1).reshape(B, nC * chunk, H, Dh)[:, :T]
        new_cache = None
        if mode == "prefill":
            dC = cfg.xlstm.conv_kernel
            tail = jnp.pad(xm, ((0, 0), (dC - 1, 0), (0, 0)))[:, -(dC - 1):]
            new_cache = {"C": state[0], "n": state[1], "m": state[2],
                         "conv": tail}

    hflat = h.astype(x.dtype).reshape(B, T, dI)
    hflat = rms_norm(hflat, params["out_norm"], cfg.norm_eps)
    y = hflat + params["skip"] * xc
    out = (y * jax.nn.silu(z)) @ params["down_proj"]
    return out, new_cache


def mlstm_cache_specs(cfg: ModelConfig, batch: int):
    dI, H, Dh = _mlstm_dims(cfg)
    dt = jnp.dtype(cfg.dtype)
    return {
        "C": jax.ShapeDtypeStruct((batch, H, Dh, Dh), jnp.float32),
        "n": jax.ShapeDtypeStruct((batch, H, Dh), jnp.float32),
        "m": jax.ShapeDtypeStruct((batch, H), jnp.float32),
        "conv": jax.ShapeDtypeStruct((batch, cfg.xlstm.conv_kernel - 1, dI), dt),
    }


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

def slstm_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    E = cfg.d_model
    H = cfg.n_heads
    Dh = E // H
    pf = cfg.xlstm.proj_factor_slstm
    F = int(E * pf)
    return {
        "w_gates": ParamSpec((E, 4 * E), ("embed", None)),
        "r_gates": ParamSpec((H, Dh, 4 * Dh), (None, None, None),
                             init="scaled", scale=1.0),
        "b_gates": ParamSpec((4 * E,), (None,), init="zeros"),
        "group_norm": ParamSpec((E,), (None,), init="zeros"),
        "mlp_wi": ParamSpec((E, F), ("embed", "mlp")),
        "mlp_wg": ParamSpec((E, F), ("embed", "mlp")),
        "mlp_wo": ParamSpec((F, E), ("mlp", "embed"), init="scaled", scale=1.0),
    }


def _slstm_cell(state, wx, r_gates, H, Dh):
    """state: (h, c, n, m) each (B, H, Dh); wx: (B, 4*E) preactivations."""
    h, c, n, m = state
    B = h.shape[0]
    rx = jnp.einsum("bhd,hde->bhe", h, r_gates)      # (B, H, 4*Dh)
    pre = wx.reshape(B, H, 4 * Dh) + rx
    zi, ii, fi, oi = jnp.split(pre, 4, axis=-1)      # (B, H, Dh)
    zt = jnp.tanh(zi)
    ot = jax.nn.sigmoid(oi)
    m_new = jnp.maximum(jax.nn.log_sigmoid(fi) + m, ii)
    i_w = jnp.exp(ii - m_new)
    f_w = jnp.exp(jax.nn.log_sigmoid(fi) + m - m_new)
    c_new = f_w * c + i_w * zt
    n_new = jnp.maximum(f_w * n + i_w, jnp.exp(-m_new))
    h_new = ot * c_new / n_new
    return (h_new, c_new, n_new, m_new)


def slstm_apply(
    params: Dict[str, jax.Array],
    x: jax.Array,
    cfg: ModelConfig,
    cache: Optional[Dict[str, jax.Array]] = None,
    mode: str = "train",
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    B, T, E = x.shape
    H = cfg.n_heads
    Dh = E // H
    act = activation(cfg.act)
    wx = (x @ params["w_gates"] + params["b_gates"]).astype(jnp.float32)
    if mode != "decode":
        # SP boundary: the per-timestep recurrence indexes the time dim —
        # on an act_seq-sharded wx that was one collective per time step
        # (measured: 885k collectives in xlstm train_4k before this fix)
        from ..sharding.rules import constrain

        wx = constrain(wx, ("batch", None, None))

    if cache is not None and mode == "decode":
        state = (cache["h"], cache["c"], cache["n"], cache["m"])
    else:
        zero = jnp.zeros((B, H, Dh), jnp.float32)
        state = (zero, zero, jnp.ones_like(zero),
                 jnp.full((B, H, Dh), 0.0, jnp.float32))

    if mode == "decode":
        state = _slstm_cell(state, wx[:, 0], params["r_gates"], H, Dh)
        hs = state[0][:, None]                       # (B, 1, H, Dh)
        new_cache = {"h": state[0], "c": state[1], "n": state[2], "m": state[3]}
    else:
        def run_scan(wx_in, r_gates, st0):
            def step(st, wxt):
                st = _slstm_cell(st, wxt, r_gates, H, Dh)
                return st, st[0]

            st, hs_out = jax.lax.scan(step, st0, jnp.moveaxis(wx_in, 0, 1))
            return st, jnp.moveaxis(hs_out, 0, 1)    # (B, T, H, Dh)

        state, hs = _shardmapped_scan(run_scan, wx, params["r_gates"], state)
        new_cache = None
        if mode == "prefill":
            new_cache = {"h": state[0], "c": state[1], "n": state[2],
                         "m": state[3]}

    y = hs.reshape(B, T, E).astype(x.dtype)
    y = rms_norm(y, params["group_norm"], cfg.norm_eps)
    # post MLP (pf = 4/3)
    hmlp = act(y @ params["mlp_wg"]) * (y @ params["mlp_wi"])
    out = y + hmlp @ params["mlp_wo"]
    return out, new_cache


def _shardmapped_scan(run_scan, wx, r_gates, state):
    """Run the recurrent scan inside shard_map over the data axes.

    Under plain GSPMD, the reverse-mode accumulation of the grad of
    ``r_gates`` (closed over by every scan step) inserts an all-reduce
    over "data" *per time step* — measured 24.7k collectives/step on
    xlstm train_4k. Inside shard_map the per-shard cotangents accumulate
    locally and a single psum fires at the boundary."""
    from ..sharding.rules import _CTX
    from jax.sharding import PartitionSpec as P

    ctx = _CTX.get()
    if ctx is None:
        return run_scan(wx, r_gates, state)
    mesh, rules = ctx
    batch_ax = rules.get("batch")
    if batch_ax is None:
        return run_scan(wx, r_gates, state)
    axes_flat = (batch_ax,) if isinstance(batch_ax, str) else tuple(batch_ax)
    bspec3 = P(batch_ax, None, None)
    sspec = P(batch_ax, None, None)

    def wrapped(wx_in, r_in, st0):
        # mark the weight *varying* before the scan: its cotangent then
        # accumulates shard-locally across all T steps and the psum fires
        # once at the pcast boundary (outside the loop) instead of
        # per-step (jax emits psum_invariant inside the while body for
        # invariant inputs — measured 24.6k in-loop all-reduces).
        r_in = jax.lax.pcast(r_in, axes_flat, to="varying")
        return run_scan(wx_in, r_in, st0)

    return jax.shard_map(
        wrapped, mesh=mesh,
        in_specs=(bspec3, P(), (sspec, sspec, sspec, sspec)),
        out_specs=((sspec, sspec, sspec, sspec),
                   P(batch_ax, None, None, None)),
    )(wx, r_gates, state)


def slstm_cache_specs(cfg: ModelConfig, batch: int):
    H = cfg.n_heads
    Dh = cfg.d_model // H
    f32 = jnp.float32
    sd = lambda: jax.ShapeDtypeStruct((batch, H, Dh), f32)
    return {"h": sd(), "c": sd(), "n": sd(), "m": sd()}
