"""Rating prediction from selected neighbors (memory-based user CF).

Implements the standard mean-centered weighted-deviation predictor the paper
uses:

    p(u, i) = r̄_u + Σ_{v ∈ N(u), v rated i} s_uv · (r_vi − r̄_v)
              ───────────────────────────────────────────────────
                        Σ_{v ∈ N(u), v rated i} |s_uv|

falling back to r̄_u when no selected neighbor rated item i, clipped to the
rating scale of the corpus (:func:`rating_scale`: its smallest and largest
rated value).  Three forms are provided:

* ``predict_from_neighbors`` — one-shot gather form; materialises the
  ``(m, k, I)`` neighbor-rating intermediate, fine up to ~10⁴ users;
* ``predict_from_neighbors_blocked`` — streams item tiles of width
  ``item_block`` so peak memory is O(m·k·T), never O(m·k·I); bit-identical
  to the one-shot form (the k-reduction per output element is unchanged,
  tiling only splits the independent item axis).  Optionally routes each
  tile through the fused Pallas kernel (``repro.kernels.predict``).  It is
  also the exact rerank of the two-stage recommend path, which masks its
  rows to each user's shortlist;
* ``predict_dense`` — dense matmul oracle for tests.

This module owns the rating matrix's gather format (:class:`GatherSource`):
an int8 code ``r / step`` with ``step`` a power of two when every rating is
such a multiple (integer stars: step 1; half stars: step 0.5), the f32
matrix otherwise.  Consumers decode ``code · step`` in-register; a power-of-
two step makes the decode exact, so every result is bit-identical to the
f32 matrix at a quarter of the gather traffic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.similarity import user_means

_DEN_EPS = 1e-8
# candidate code steps, coarsest first: 1, 1/2, …, 1/128
_STEPS = tuple(2.0 ** -e for e in range(8))
_CODE_MAX = 127


def rating_scale(ratings) -> tuple:
    """``(lo, hi)``: the smallest and largest rated (> 0) value of
    ``ratings`` as host floats — the scale every prediction is clipped
    to; ``(inf, -inf)`` when nothing is rated (no clip)."""
    lo, hi = _scale_of(jnp.asarray(ratings, jnp.float32))
    return float(lo), float(hi)


@jax.jit
def _scale_of(ratings):
    rated = ratings > 0
    return (jnp.min(jnp.where(rated, ratings, jnp.inf)),
            jnp.max(jnp.where(rated, ratings, -jnp.inf)))


def widen(a: tuple, b: tuple) -> tuple:
    """The smallest scale holding both ``a`` and ``b``."""
    return min(a[0], b[0]), max(a[1], b[1])


def clip_to_scale(pred, scale: tuple):
    """``pred`` clipped to ``scale`` (no clip for an empty scale)."""
    lo, hi = scale
    return jnp.clip(pred, lo, hi) if lo <= hi else pred


@jax.tree_util.register_pytree_node_class
class GatherSource:
    """The rating matrix as the gather operand of every neighbor-row read.

    ``code``: ``(U, I)`` int8 ``r / step`` (``step`` a power of two ≤ 1), or
    the f32 matrix itself with ``step == 0``.  ``scale``: ``(lo, hi)`` of
    the rated values, derived with the code and only ever widened by
    updates.  ``step`` and ``scale`` are static (pytree aux data), so a
    jitted consumer compiles per format and scale, and decodes with a
    constant multiply."""

    __slots__ = ("code", "step", "scale")

    def __init__(self, code, step: float, scale: tuple):
        self.code, self.step, self.scale = code, float(step), tuple(scale)

    def tree_flatten(self):
        return (self.code,), (self.step, self.scale)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(leaves[0], *aux)

    @property
    def shape(self):
        return self.code.shape

    @property
    def factor(self) -> float:
        """What a code value is multiplied by to decode it (1 for f32)."""
        return self.step or 1.0

    def decode(self, x):
        """Code values (any gather or slice of ``code``) → f32 ratings;
        exact, since ``step`` is a power of two."""
        x = x.astype(jnp.float32)
        return x if self.factor == 1.0 else x * self.factor

    def rows(self, ids):
        """Decoded rating rows ``ids``."""
        return self.decode(self.code[ids])


@jax.jit
def _code_steps(ratings):
    """Per candidate step: True iff every rating is a non-negative integer
    multiple of it with a code ≤ 127 (an int8 code round-trips exactly);
    plus the rated scale."""
    ok = jnp.stack([jnp.all((ratings >= 0) & (ratings <= _CODE_MAX * s)
                            & (ratings == jnp.round(ratings / s) * s))
                    for s in _STEPS])
    return (ok,) + _scale_of(ratings)


@functools.partial(jax.jit, static_argnames=("step",))
def _encode(ratings, *, step):
    return (ratings / step).astype(jnp.int8)


def _publish(src: GatherSource) -> GatherSource:
    obs.registry().gauge("engine.gather_step").set(src.step)
    return src


def _inspect(ratings):
    ok, lo, hi = _code_steps(ratings)
    ok = np.asarray(ok)
    step = _STEPS[int(np.argmax(ok))] if ok.any() else 0.0
    return step, (float(lo), float(hi))


def make_gather_source(ratings: jnp.ndarray) -> GatherSource:
    """The gather operand of ``ratings``: the int8 code at the coarsest
    power-of-two step that every rating is a multiple of, with a code ≤
    127; the f32 matrix (``step`` 0) when no such step exists.  Carries the
    rating scale.  Callers cache the result per ratings array."""
    step, scale = _inspect(ratings)
    code = _encode(ratings, step=step) if step else ratings
    return _publish(GatherSource(code, step, scale))


@functools.partial(jax.jit, static_argnames=("step",))
def _scatter_rows(code, rows, vals, *, step):
    # no buffer donation: a concurrent reader (the serving batcher) may
    # still hold the pre-delta operand mid-call — the patch must be
    # copy-on-write like every other published model array
    return code.at[rows].set((vals / step).astype(jnp.int8), mode="drop")


def patch_gather_source(src: GatherSource, ratings: jnp.ndarray,
                        touched: jnp.ndarray) -> GatherSource:
    """Refresh a cached :func:`make_gather_source` result for a row delta.

    ``src`` must be the cached operand of the *pre-delta* matrix and
    ``ratings`` the post-delta matrix whose only changed rows are
    ``touched`` (ids may be padded with out-of-range values — the scatter
    drops them).  Touched rows are checked against the code's step and
    scattered into a fresh copy (copy-on-write — the pre-delta operand
    stays valid for concurrent readers), so a small delta skips the
    full-matrix encode and scan a cold rebuild pays.  A delta that the
    step cannot hold falls back to a full rebuild.  The scale only widens:
    an update never narrows it.
    """
    rows = ratings[jnp.clip(touched, 0, ratings.shape[0] - 1)]
    row_step, row_scale = _inspect(rows)
    scale = widen(src.scale, row_scale)
    if not src.step:
        # the f32 source is the rating matrix itself: the fresh matrix *is*
        # the patched operand (a delta could newly qualify for a code, but
        # staying f32 is always correct — the next cold build decides)
        return _publish(GatherSource(ratings, 0.0, scale))
    if not row_step or row_step < src.step:
        fresh = make_gather_source(ratings)
        return _publish(GatherSource(fresh.code, fresh.step,
                                     widen(fresh.scale, scale)))
    return _publish(GatherSource(
        _scatter_rows(src.code, touched, rows, step=src.step), src.step,
        scale))


def _tile_predict(w, nbr, nb_means, query_means, scale):
    """Shared per-tile epilogue — the exact arithmetic of the one-shot
    form restricted to one item tile (the item axis is embarrassingly
    independent, so per-tile results concatenate bit-identically)."""
    nb_mask = (nbr > 0).astype(jnp.float32)
    dev = (nbr - nb_means[..., None]) * nb_mask
    # explicit multiply+reduce (not einsum): the per-element k-reduction is
    # then independent of the item-tile width, so any tiling of the item
    # axis reproduces the one-shot result bit for bit (an einsum may pick a
    # different contraction strategy per shape and round differently)
    num = jnp.sum(w[..., None] * dev, axis=-2)
    den = jnp.sum(w[..., None] * nb_mask, axis=-2)
    pred = query_means[:, None] + num / jnp.maximum(den, _DEN_EPS)
    pred = jnp.where(den > _DEN_EPS, pred, query_means[:, None])
    return clip_to_scale(pred, scale)


def _scale_or_derived(scale, ratings) -> tuple:
    """``scale`` as given, else derived from ``ratings`` (which must then
    be concrete: under a trace the caller passes the scale)."""
    return rating_scale(ratings) if scale is None else tuple(scale)


def predict_from_neighbors(ratings: jnp.ndarray, scores: jnp.ndarray,
                           idx: jnp.ndarray, *,
                           means: jnp.ndarray | None = None,
                           query_means: jnp.ndarray | None = None,
                           scale: tuple | None = None) -> jnp.ndarray:
    """Predict the full item row for every query user.

    ``ratings``: (U, I) full training matrix (candidate users);
    ``scores``/``idx``: (m, k) top-k neighbor weights and global user ids for
    the m query users; ``query_means``: (m,) rated-item means of the query
    users (defaults to ``means[idx_of_query]`` being unavailable here, so pass
    it explicitly when m ≠ U); ``scale``: the ``(lo, hi)`` clip, by default
    :func:`rating_scale` of ``ratings``.

    Returns (m, I) predicted ratings.
    """
    scale = _scale_or_derived(scale, ratings)
    safe_idx, w, nb_means, query_means = _neighbor_inputs(
        ratings, scores, idx, means, query_means)
    nb_ratings = ratings[safe_idx]                            # (m, k, I)
    return _tile_predict(w, nb_ratings, nb_means, query_means, scale)


def _neighbor_inputs(ratings, scores, idx, means, query_means):
    """Common setup: masked weights, safe gather ids, neighbor means."""
    if means is None:
        means = user_means(ratings)
    if query_means is None:
        if scores.shape[0] != ratings.shape[0]:
            raise ValueError("query_means is required when predicting for a "
                             "subset of users")
        query_means = means
    safe_idx = jnp.where(idx >= 0, idx, 0)
    w = jnp.where((scores > 0.0) & (idx >= 0), scores, 0.0)
    return safe_idx, w, means[safe_idx], query_means


def predict_from_neighbors_blocked(ratings: jnp.ndarray, scores: jnp.ndarray,
                                   idx: jnp.ndarray, *,
                                   means: jnp.ndarray | None = None,
                                   query_means: jnp.ndarray | None = None,
                                   item_block: int = 512,
                                   gather_src: GatherSource | None = None,
                                   scale: tuple | None = None,
                                   use_kernel: bool = False,
                                   interpret: bool = False) -> jnp.ndarray:
    """Blocked form of :func:`predict_from_neighbors`: stream over item
    tiles of width ``item_block`` so the ``(m, k, I)`` neighbor-rating
    intermediate is never materialised — peak memory O(m·k·item_block).

    Bit-identical to the one-shot form.  Neighbor rows are read from
    ``gather_src`` (its code decoded exactly) when given, and clipped to
    ``scale``, else to the gather source's scale, else to
    :func:`rating_scale` of ``ratings``.  With ``use_kernel`` each tile's
    mask/deviation/reduction epilogue runs as one fused Pallas VMEM pass
    (float-rounding-identical, validated against ``repro.kernels.ref``).
    """
    if scale is None and gather_src is not None:
        scale = gather_src.scale
    scale = _scale_or_derived(scale, ratings)
    safe_idx, w, nb_means, query_means = _neighbor_inputs(
        ratings, scores, idx, means, query_means)
    src = (GatherSource(ratings, 0.0, scale) if gather_src is None
           else gather_src)
    n_items = ratings.shape[1]
    tiles = []
    for lo in range(0, n_items, item_block):
        tile = jax.lax.slice_in_dim(src.code, lo,
                                    min(lo + item_block, n_items), axis=1)
        nbr = src.decode(tile[safe_idx])                # (m, k, T)
        if use_kernel:
            from repro.kernels.predict import fused_tile_predict
            tiles.append(fused_tile_predict(nbr, w, nb_means, query_means,
                                            scale=scale,
                                            interpret=interpret))
        else:
            tiles.append(_tile_predict(w, nbr, nb_means, query_means,
                                       scale))
    return jnp.concatenate(tiles, axis=1)


def predict_dense(ratings: jnp.ndarray, weight_matrix: jnp.ndarray, *,
                  means: jnp.ndarray | None = None,
                  scale: tuple | None = None) -> jnp.ndarray:
    """Oracle: same predictor via a dense (U, U) weight matrix matmul."""
    scale = _scale_or_derived(scale, ratings)
    if means is None:
        means = user_means(ratings)
    mask = (ratings > 0).astype(jnp.float32)
    dev = (ratings - means[:, None]) * mask
    num = weight_matrix @ dev
    den = weight_matrix @ mask
    pred = means[:, None] + num / jnp.maximum(den, 1e-8)
    pred = jnp.where(den > 1e-8, pred, means[:, None])
    return clip_to_scale(pred, scale)


@functools.partial(jax.jit, static_argnames=("n",))
def recommend_topn(pred: jnp.ndarray, seen_mask: jnp.ndarray, n: int):
    """Top-n unseen items per user from a predicted rating matrix."""
    masked = jnp.where(seen_mask, -jnp.inf, pred)
    # reprolint: disable=canonical-selection -- XLA top_k ties break toward the lower item id (the recommend contract); topn_unseen sanitises -inf slots
    scores, items = jax.lax.top_k(masked, n)
    return scores, items


def topn_unseen(pred: jnp.ndarray, seen_mask: jnp.ndarray, n: int):
    """``recommend_topn`` with sanitised ids: when a user has fewer than
    ``n`` unseen items, the -inf filler slots surface as item id -1
    (``lax.top_k`` would otherwise hand back arbitrary *seen* items for
    them).  Both recommend paths share this so the recommendation contract
    — never return an already-rated item — holds unconditionally."""
    scores, items = recommend_topn(pred, seen_mask, n)
    return scores, jnp.where(scores == -jnp.inf, -1, items)
