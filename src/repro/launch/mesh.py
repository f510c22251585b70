"""Mesh construction (DESIGN.md §5).

Kept as functions (not module constants) so importing never touches jax
device state — the dry-run must set XLA_FLAGS before any jax init.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, *, devices=None):
    """The one mesh constructor: every axis is ``Auto``, so GSPMD
    propagates shardings and ``with_sharding_constraint`` may name any
    axis (``jax.make_mesh`` defaults to ``Explicit`` axes)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips as (data, model).
    Multi-pod: 2 pods x 16 x 16 = 512 chips as (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small host-device mesh for CI-scale sharding tests."""
    return make_mesh(shape, axes)


def dp_axes(mesh) -> tuple:
    """Axes that carry the batch (all but 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")
