"""Production mesh construction.

A function (not a module constant) so importing never touches jax device
state.  Single pod: (16, 16) = 256 chips, axes (data, model).  Multi-pod:
(2, 16, 16) = 512 chips with the leading ``pod`` axis as outer data
parallelism (the slow inter-pod DCI links only ever carry gradient
all-reduces, never layer-wise TP traffic).

All meshes go through ``repro.compat.make_mesh``, which builds them with
Auto axes (``jax.make_mesh`` defaults to Explicit).
"""

from __future__ import annotations

import jax

from repro import compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_flat_mesh(*, multi_pod: bool = False, axis: str = "data"):
    """Same devices as one ring — the CF engines' 1-axis partition view."""
    n = 512 if multi_pod else 256
    return compat.make_mesh((n,), (axis,))


def make_local_mesh(shape=None, axes=None):
    """Mesh over whatever local devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        shape, axes = (n,), ("data",)
    return compat.make_mesh(shape, axes)
