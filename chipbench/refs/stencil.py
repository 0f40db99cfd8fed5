"""Plain reference of COMB's periodic 7-point stencil.

``plain_stencil`` is the whole periodic field stepped ``steps`` times
(numpy or jax.numpy). ``block_reference`` gives one block of that answer
from the block's slab widened by ``steps`` cells on every side, so each
chip can check its own block without holding the whole field: a point
after ``steps`` steps depends only on points at most ``steps`` away.
"""
from __future__ import annotations

import numpy as np


def _step_valid(p, xp):
    """One step on the points of ``p`` whose six neighbours are in ``p``."""
    return (-6.0 * p[1:-1, 1:-1, 1:-1]
            + p[:-2, 1:-1, 1:-1] + p[2:, 1:-1, 1:-1]
            + p[1:-1, :-2, 1:-1] + p[1:-1, 2:, 1:-1]
            + p[1:-1, 1:-1, :-2] + p[1:-1, 1:-1, 2:])


def plain_stencil(u, steps: int, xp=np):
    """Periodic 7-point Laplacian applied ``steps`` times to the whole
    field."""
    for _ in range(steps):
        u = _step_valid(xp.pad(u, 1, mode="wrap"), xp)
    return u


def block_reference(slab, steps: int, xp=np):
    """The block at the centre of ``slab`` (widened by ``steps`` on every
    side) after ``steps`` periodic steps of the whole field."""
    for _ in range(steps):
        slab = _step_valid(slab, xp)
    return slab


def widened_slab(field: np.ndarray, index: tuple, margin: int) -> np.ndarray:
    """``field[index]`` widened by ``margin`` cells on every side, wrapping
    round the periodic boundary. ``index`` is a tuple of slices."""
    idx = []
    for sl, n in zip(index, field.shape):
        start, stop, _ = sl.indices(n)
        idx.append(np.arange(start - margin, stop + margin) % n)
    return field[np.ix_(*idx)]
