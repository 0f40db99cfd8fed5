"""Synthetic token batches for training cells.

The benchmark's own copy of the bigram stream of the program's
``data/pipeline.SyntheticTokens``: token ``t+1`` follows token ``t``
through a random successor table, so the loss can fall. Every batch is a
function of (seed, step) alone, every row differs, and the host does the
same per-batch work as the program's pipeline (one Python pass over the
sequence), so the window pays what training pays.
"""
from __future__ import annotations

import numpy as np


class TokenStream:
    def __init__(self, seed: int, batch: int, seq_len: int, vocab: int,
                 n_successors: int):
        self.seed, self.batch, self.seq_len = seed, batch, seq_len
        self.vocab = vocab
        rng = np.random.default_rng(seed)
        k = min(n_successors, vocab)
        self.succ = rng.integers(0, vocab, size=(vocab, k), dtype=np.int32)

    def batch_at(self, step: int) -> tuple:
        """(tokens, labels), each (batch, seq_len) int32."""
        rng = np.random.default_rng((self.seed, step))
        B, T = self.batch, self.seq_len
        toks = np.empty((B, T + 1), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, size=B)
        choices = rng.integers(0, self.succ.shape[1], size=(B, T))
        for t in range(T):
            toks[:, t + 1] = self.succ[toks[:, t], choices[:, t]]
        return toks[:, :-1], toks[:, 1:].copy()
