"""Mesh construction in one place.

Meshes are built with ``AxisType.Auto`` axes, so GSPMD propagates
shardings through un-annotated ops the way every caller in this repo
expects. Every mesh in the repo is built through these helpers.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax

__all__ = ["make_mesh", "mesh_from_devices"]


def _auto_axes(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    shape = tuple(axis_shapes)
    return jax.make_mesh(shape, tuple(axis_names),
                         axis_types=_auto_axes(len(shape)), devices=devices)


def mesh_from_devices(device_array, axis_names: Sequence[str]) -> jax.sharding.Mesh:
    """``jax.sharding.Mesh`` from an explicit device ndarray, with Auto
    axis types (the elastic-restart construction path)."""
    return jax.sharding.Mesh(device_array, tuple(axis_names),
                             axis_types=_auto_axes(device_array.ndim))
