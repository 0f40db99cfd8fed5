"""Work fixed by the problem, computed from shapes alone.

These numbers are the numerators of roofline shares: they say what the
problem needs, not what an implementation does, so the same step reads
the same work whatever computes it.
"""
from __future__ import annotations

STENCIL_FLOPS_PER_POINT = 8      # six neighbour adds, one scale, one add


def stencil_step_bytes(box: int, width: int, itemsize: int) -> int:
    """Least HBM bytes of one 7-point step on one ``box``^3 block: read
    the field once, write it once, and read the six received faces."""
    field = box ** 3 * itemsize
    faces = 6 * box * box * width * itemsize
    return 2 * field + faces


def stencil_step_flops(box: int) -> int:
    """Floating-point operations of one 7-point step on one block."""
    return STENCIL_FLOPS_PER_POINT * box ** 3


def least_time_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of operations over peak FLOP/s and
    bytes over peak bandwidth, and which of the two it is."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    if t_bytes >= t_flops:
        return t_bytes, "hbm"
    return t_flops, "flops"
