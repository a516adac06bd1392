"""Fused tile-predict Pallas TPU kernel for the blocked recommend path.

One tile of the mean-centered weighted-deviation predictor
(``repro.core.predict``) needs, per (query block, item tile):

    num[m, t] = Σ_k w[m,k] · (nbr[m,k,t] − nb_mean[m,k]) · 1[nbr > 0]
    den[m, t] = Σ_k w[m,k] · 1[nbr[m,k,t] > 0]
    pred      = clip(q_mean[m] + num/den, lo, hi)  (q_mean when den == 0)

XLA materialises the mask and deviation tensors as separate (m, k, T)
HBM intermediates; the fused kernel keeps one VMEM-resident pass over the
gathered neighbor tile — mask, deviation, both k-reductions, and the
division/fallback/clip epilogue in-register.  The gather that produces the
tile stays outside (it is the memory-bound stage the *blocked* driver in
``repro.core.predict`` bounds at O(m·k·item_block)).

Grid: (M/bm, T/bt); the small k axis lives whole inside each block (k ≤
~64 in every engine configuration, padded to the f32 sublane multiple).
Interpret mode runs on CPU and is validated against the jnp oracle in
``repro.kernels.ref``; production CPU paths use the jnp tile directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.predict import clip_to_scale
from repro.kernels.similarity import _pad_to

# default tile sizes: bm·k·bt f32 must sit comfortably in VMEM
# (128·64·512·4 B = 16 MB/ tile upper bound; real k≈40 ⇒ ~10 MB)
BM, BT = 128, 512
_DEN_EPS = 1e-8


def _predict_kernel(nbr_ref, w_ref, nbm_ref, qm_ref, out_ref, *, scale):
    nbr = nbr_ref[...].astype(jnp.float32)        # (bm, k, bt)
    w = w_ref[...].astype(jnp.float32)            # (bm, k)
    nbm = nbm_ref[...].astype(jnp.float32)        # (bm, k)
    qm = qm_ref[...].astype(jnp.float32)          # (bm, 1)
    mask = (nbr > 0).astype(jnp.float32)
    dev = (nbr - nbm[:, :, None]) * mask
    num = jnp.sum(w[:, :, None] * dev, axis=1)    # (bm, bt)
    den = jnp.sum(w[:, :, None] * mask, axis=1)
    pred = qm + num / jnp.maximum(den, _DEN_EPS)
    pred = jnp.where(den > _DEN_EPS, pred, qm)
    out_ref[...] = clip_to_scale(pred, scale)


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bt",
                                             "interpret"))
def fused_tile_predict(nbr: jnp.ndarray, w: jnp.ndarray,
                       nb_means: jnp.ndarray, q_means: jnp.ndarray, *,
                       scale: tuple, bm: int = BM, bt: int = BT,
                       interpret: bool = False) -> jnp.ndarray:
    """(m, k, T) gathered neighbor tile → (m, T) predictions.

    ``w`` must already be the masked weights (invalid/negative-score
    neighbors at 0 — a zero weight cancels in both reductions, which is
    also why the k padding below is harmless); ``scale``: the ``(lo, hi)``
    clip (``repro.core.predict.rating_scale``).
    """
    m, k, t = nbr.shape
    bm_, bt_ = min(bm, m), min(bt, t)
    # k → f32 sublane multiple with zero weights; m/t → tile multiples
    nbr_p = _pad_to(_pad_to(_pad_to(nbr, bm_, 0), 8, 1), bt_, 2)
    w_p = _pad_to(_pad_to(w, bm_, 0), 8, 1)
    nbm_p = _pad_to(_pad_to(nb_means, bm_, 0), 8, 1)
    qm_p = _pad_to(q_means[:, None], bm_, 0)
    mp, kp, tp = nbr_p.shape
    grid = (mp // bm_, tp // bt_)

    out = pl.pallas_call(
        functools.partial(_predict_kernel, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, kp, bt_), lambda i, j: (i, 0, j)),
            pl.BlockSpec((bm_, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bm_, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bm_, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm_, bt_), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, tp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(nbr_p, w_p, nbm_p, qm_p)
    return out[:m, :t]
