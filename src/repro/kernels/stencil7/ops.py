"""The 7-point stencil kernel, and whether a block suits it. The caller
of :func:`stencil7` says whether to run it in the Pallas interpreter
(``interpret=True``) or compile it for the TPU."""
from __future__ import annotations

from .kernel import slab_depth, stencil7  # noqa: F401


def tiles(shape, itemsize: int) -> bool:
    """Whether an (X, Y, Z) block fills whole (8, 128) tiles of its y-z
    planes and a slab of one plane fits the kernel's VMEM."""
    return (len(shape) == 3 and shape[1] % 8 == 0 and shape[2] % 128 == 0
            and slab_depth(shape, itemsize) is not None)
