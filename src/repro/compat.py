"""Thin wrappers over the installed JAX (0.9) where call sites gain from one.

``jax.make_mesh`` defaults every axis to ``AxisType.Explicit``; the engines
here shard through ``jax.shard_map`` and jit and want Auto axes, so every
mesh in the repo is built by :func:`make_mesh`.
"""

from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(
        axis_shapes, axis_names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
