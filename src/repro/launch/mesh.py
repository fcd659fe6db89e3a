"""Mesh factories.

Functions, not module constants: importing this module never touches jax
device state. Every mesh is built with ``Auto`` axis types, so sharding
follows the logical-axis rules (``repro.distributed.sharding``) rather than
explicit-sharding types.
"""
from __future__ import annotations

import jax


def mesh_of(axis_shapes, axis_names, devices=None):
    """``jax.make_mesh`` with Auto axis types (over ``devices`` if given)."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         (jax.sharding.AxisType.Auto,) * len(axis_names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return mesh_of(shape, axes)


def make_mesh(data: int, model: int, pod: int = 1):
    """Arbitrary mesh for tests / small-scale runs."""
    if pod > 1:
        return mesh_of((pod, data, model), ("pod", "data", "model"))
    return mesh_of((data, model), ("data", "model"))
