"""Mesh helpers shared by the distributed engines and their tests.

``shard_map`` is re-exported from jax so call sites keep one import
point; :func:`make_mesh` pins the Auto axis types the shard_map engines
are written for (sharding propagates through ``jit`` around them).
"""
from __future__ import annotations

import jax
from jax import shard_map  # noqa: F401 — re-exported


def make_mesh(shape, axes):
    """``jax.make_mesh`` with explicit Auto axis types."""
    axes = tuple(axes)
    return jax.make_mesh(
        tuple(shape), axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )
