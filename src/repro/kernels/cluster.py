"""Fused centroid-distance Pallas TPU kernel for the clustered ANN index.

The candidate-generation stage of :mod:`repro.index` assigns every user row
to its nearest k-means centroid and shortlists the ``n_probe`` nearest
clusters per query.  Both need the (m, C) squared-Euclidean distance matrix

    dist[i, j] = ||x_i - c_j||^2 = ||x_i||^2 - 2 x_i.c_j + ||c_j||^2

between mean-centered rating rows ``x`` and centroids ``c``.  The fused
kernel accumulates the cross term and both squared norms in one K-blocked
VMEM pass — one read of each operand tile instead of three XLA ops that each
re-stream the rows from HBM — and applies the epilogue in-register.

Grid: (M/bm, C/bn, D/bk) with the K axis innermost ("arbitrary" — it carries
the accumulators); M/C are "parallel".  Interpret mode runs the same kernel
on CPU and is what the tests validate against the jnp oracle in
``repro.kernels.ref``; production CPU paths use the oracle directly (see
``centroid_distances`` below), Mosaic compiles it on real TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# default MXU-aligned tile sizes (v5e: 128×128 MXU, 8×128 VREG lanes)
BM, BN, BK = 256, 256, 512


def _dot_t(a, b):
    """a (m,k) · b (n,k)ᵀ with f32 accumulation on the MXU."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _dist_kernel(x_ref, c_ref, out_ref, acc_dot, acc_xx, acc_cc, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        for r in (acc_dot, acc_xx, acc_cc):
            r[...] = jnp.zeros_like(r)

    x = x_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    acc_dot[...] += _dot_t(x, c)
    acc_xx[...] += jnp.sum(x * x, axis=1, keepdims=True)        # (bm, 1)
    acc_cc[...] += jnp.sum(c * c, axis=1, keepdims=True).T      # (1, bn)

    @pl.when(k == n_k - 1)
    def _epilogue():
        d = acc_xx[...] - 2.0 * acc_dot[...] + acc_cc[...]
        out_ref[...] = jnp.maximum(d, 0.0)   # clamp float-cancellation noise


def _pad_to(x, mult, axis):
    rem = x.shape[axis] % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def fused_centroid_distances(x: jnp.ndarray, c: jnp.ndarray, *,
                             bm: int = BM, bn: int = BN, bk: int = BK,
                             interpret: bool = False) -> jnp.ndarray:
    """(m, D) rows × (n, D) centroids → (m, n) squared Euclidean distances."""
    m, d = x.shape
    n = c.shape[0]
    bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, d)
    x_p = _pad_to(_pad_to(x, bm_, 0), bk_, 1)
    c_p = _pad_to(_pad_to(c, bn_, 0), bk_, 1)
    mp, dp = x_p.shape
    np_ = c_p.shape[0]
    grid = (mp // bm_, np_ // bn_, dp // bk_)

    out = pl.pallas_call(
        functools.partial(_dist_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn_, bk_), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32),
                        pltpu.VMEM((bm_, 1), jnp.float32),
                        pltpu.VMEM((1, bn_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_p, c_p)
    return out[:m, :n]


def centroid_distances(x: jnp.ndarray, c: jnp.ndarray, *,
                       use_kernel: bool = False,
                       interpret: bool = False) -> jnp.ndarray:
    """Backend-dispatching wrapper: fused kernel on TPU, jnp oracle elsewhere.

    The interpret-mode kernel is a correctness vehicle, not a fast path —
    the index only routes through it when ``use_kernel`` is set (auto-on
    for real TPU; tests force it with ``interpret=True`` at toy sizes).
    """
    if use_kernel:
        return fused_centroid_distances(x, c, interpret=interpret)
    from repro.kernels import ref
    return ref.centroid_distances_ref(x, c)
