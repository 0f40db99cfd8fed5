"""Wrapper for the selective-scan kernel; the caller says whether to run
it in the Pallas interpreter (``interpret=True``) or compile it for the
TPU."""
from __future__ import annotations

from .kernel import selective_scan


def mamba_scan(x, dt, A, Bc, Cc, D, block_d: int = 512, block_t: int = 128,
               interpret: bool = False):
    return selective_scan(
        x, dt, A, Bc, Cc, D, block_d=block_d, block_t=block_t,
        interpret=interpret)
