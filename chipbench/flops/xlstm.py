"""Model FLOPs of an xLSTM language model, from its configuration file.

The arithmetic is that of the program's ``launch/flops.py`` (6 N D for
training, with N the parameters engaged in per-token matrix products,
plus the mLSTM chunk terms), rewritten here over the configuration's
own numbers so that the yardstick does not move when the program does.
"""
from __future__ import annotations


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def _mlstm_dims(cfg: dict) -> tuple:
    d_inner = int(cfg["d_model"] * cfg["proj_factor_mlstm"])
    return d_inner, cfg["n_heads"], d_inner // cfg["n_heads"]


def block_params(cfg: dict, mixer: str) -> int:
    """Parameters of one block, its pre-norm included."""
    E, H = cfg["d_model"], cfg["n_heads"]
    if mixer == "mlstm":
        dI, _, _ = _mlstm_dims(cfg)
        dC = cfg["conv_kernel"]
        return (E * 2 * dI + dC * dI + dI + 3 * dI * dI + dI * 2 * H
                + 2 * H + dI + dI + dI * E + E)
    if mixer == "slstm":
        Dh = E // H
        F = int(E * cfg["proj_factor_slstm"])
        return (E * 4 * E + H * Dh * 4 * Dh + 4 * E + E + 3 * E * F + E)
    raise ValueError(f"no parameter count for mixer {mixer!r}")


def param_count(cfg: dict) -> int:
    E, Vp = cfg["d_model"], padded_vocab(cfg)
    groups = cfg["n_layers"] // len(cfg["pattern"])
    blocks = sum(block_params(cfg, m) for m in cfg["pattern"]) * groups
    return Vp * E + blocks + E + E * Vp


def matmul_param_count(cfg: dict) -> int:
    """Parameters engaged in per-token matrix products: no embedding
    gather, no padded vocabulary tail of the output head."""
    Vp = padded_vocab(cfg)
    return (param_count(cfg) - Vp * cfg["d_model"]
            - (Vp - cfg["vocab_size"]) * cfg["d_model"])


def mixer_flops_token(cfg: dict, ctx: int) -> float:
    """Sequence-mixing operations of one token over ``ctx`` of history
    that are not parameter products (the mLSTM chunk terms)."""
    dI, H, Dh = _mlstm_dims(cfg)
    q = cfg["chunk"]
    per_mlstm = 4.0 * H * Dh * min(q, max(ctx, 1)) + 4.0 * dI * Dh
    groups = cfg["n_layers"] // len(cfg["pattern"])
    return per_mlstm * sum(1 for m in cfg["pattern"] if m == "mlstm") * groups


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward operations per trained token; recomputed
    operations do not count."""
    return (6.0 * matmul_param_count(cfg)
            + 3.0 * mixer_flops_token(cfg, ctx=seq_len // 2))
