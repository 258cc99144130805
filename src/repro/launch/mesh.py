"""Production mesh construction.

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialization; everything else sees the real device count).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.

    ``jax.make_mesh`` defaults to explicit axes, under which an indexing
    gather such as the model's embedding lookup must name its output
    sharding.  The model code shards through ``with_sharding_constraint``
    hints (the auto-partitioned style), so its meshes are built here."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
