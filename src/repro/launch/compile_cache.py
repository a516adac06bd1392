"""Persistent XLA compilation cache for the entry points.

Called from ``main`` of every entry point (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks/run.py`` and the examples), never at
import: tests and worker processes import these modules.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# the checkout root (src/repro/launch/ → three levels up); the cache path
# is fixed because a later run only finds entries under the same path
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads the
    variable itself, so nothing is set here.  Otherwise the cache lives
    at ``<checkout>/.jax_cache`` (ignored by git).
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
