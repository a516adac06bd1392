"""Rating prediction from selected neighbors (memory-based user CF).

Implements the standard mean-centered weighted-deviation predictor the paper
uses:

    p(u, i) = r̄_u + Σ_{v ∈ N(u), v rated i} s_uv · (r_vi − r̄_v)
              ───────────────────────────────────────────────────
                        Σ_{v ∈ N(u), v rated i} |s_uv|

falling back to r̄_u when no selected neighbor rated item i.  Three forms are
provided:

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

``gather_src`` on the streaming forms accepts a cheaper gather operand for
the same ratings (e.g. an int8 copy when every rating is a small integer —
the gather is element-count bound and int8 moves ~4× less traffic); the
cast back to f32 is exact, so results are unchanged bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.similarity import user_means

_DEN_EPS = 1e-8


@jax.jit
def _int8_exact(ratings):
    """True iff every rating is an integer in [0, 127] — i.e. an int8 copy
    round-trips exactly (MovieLens-style 0..5 matrices qualify)."""
    return jnp.all((ratings >= 0) & (ratings <= 127)
                   & (ratings == jnp.round(ratings)))


def make_gather_source(ratings: jnp.ndarray) -> jnp.ndarray:
    """Rating matrix as a gather operand: an int8 copy when that
    round-trips exactly (the cast back to f32 is then exact, so results
    are unchanged bit for bit at ~4× less gather traffic), the matrix
    itself otherwise.  Callers cache the result per ratings array."""
    return (ratings.astype(jnp.int8) if bool(_int8_exact(ratings))
            else ratings)


@jax.jit
def _scatter_rows_int8(src, rows, vals):
    # no buffer donation: a concurrent reader (the serving batcher) may
    # still hold the pre-delta operand mid-call — the patch must be
    # copy-on-write like every other published model array
    return src.at[rows].set(vals.astype(jnp.int8), mode="drop")


def patch_gather_source(src: jnp.ndarray, ratings: jnp.ndarray,
                        touched: jnp.ndarray) -> jnp.ndarray:
    """Refresh a cached :func:`make_gather_source` result for a row delta.

    ``src`` must be the cached operand of the *pre-delta* matrix and
    ``ratings`` the post-delta matrix whose only changed rows are
    ``touched`` (ids may be padded with out-of-range values — the scatter
    drops them).  Touched rows are re-checked for int8 exactness and
    scattered into a fresh copy (copy-on-write — the pre-delta operand
    stays valid for concurrent readers), so a small delta skips the
    full-matrix cast + exactness scan a cold rebuild pays.  A delta that
    breaks int8 exactness falls back to a full rebuild.
    """
    if src.dtype != jnp.int8:
        # non-int8 source is the rating matrix itself: the fresh matrix
        # *is* the patched operand (a delta could newly qualify for int8,
        # but staying f32 is always correct — the next cold build decides)
        return ratings
    rows = ratings[jnp.clip(touched, 0, ratings.shape[0] - 1)]
    if not bool(_int8_exact(rows)):
        return make_gather_source(ratings)
    return _scatter_rows_int8(src, touched, rows)


def _tile_predict(w, nbr, nb_means, query_means):
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
    return jnp.clip(pred, 1.0, 5.0)


def predict_from_neighbors(ratings: jnp.ndarray, scores: jnp.ndarray,
                           idx: jnp.ndarray, *,
                           means: jnp.ndarray | None = None,
                           query_means: jnp.ndarray | None = None,
                           ) -> jnp.ndarray:
    """Predict the full item row for every query user.

    ``ratings``: (U, I) full training matrix (candidate users);
    ``scores``/``idx``: (m, k) top-k neighbor weights and global user ids for
    the m query users; ``query_means``: (m,) rated-item means of the query
    users (defaults to ``means[idx_of_query]`` being unavailable here, so pass
    it explicitly when m ≠ U).

    Returns (m, I) predicted ratings.
    """
    safe_idx, w, nb_means, query_means = _neighbor_inputs(
        ratings, scores, idx, means, query_means)
    nb_ratings = ratings[safe_idx]                            # (m, k, I)
    return _tile_predict(w, nb_ratings, nb_means, query_means)


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
                                   gather_src: jnp.ndarray | None = None,
                                   use_kernel: bool = False,
                                   interpret: bool = False) -> jnp.ndarray:
    """Blocked form of :func:`predict_from_neighbors`: stream over item
    tiles of width ``item_block`` so the ``(m, k, I)`` neighbor-rating
    intermediate is never materialised — peak memory O(m·k·item_block).

    Bit-identical to the one-shot form.  With ``use_kernel`` each tile's
    mask/deviation/reduction epilogue runs as one fused Pallas VMEM pass
    (float-rounding-identical, validated against ``repro.kernels.ref``).
    """
    safe_idx, w, nb_means, query_means = _neighbor_inputs(
        ratings, scores, idx, means, query_means)
    src = ratings if gather_src is None else gather_src
    n_items = ratings.shape[1]
    tiles = []
    for lo in range(0, n_items, item_block):
        tile = jax.lax.slice_in_dim(src, lo, min(lo + item_block, n_items),
                                    axis=1)
        nbr = tile[safe_idx].astype(jnp.float32)        # (m, k, T)
        if use_kernel:
            from repro.kernels.predict import fused_tile_predict
            tiles.append(fused_tile_predict(nbr, w, nb_means, query_means,
                                            interpret=interpret))
        else:
            tiles.append(_tile_predict(w, nbr, nb_means, query_means))
    return jnp.concatenate(tiles, axis=1)


def predict_dense(ratings: jnp.ndarray, weight_matrix: jnp.ndarray, *,
                  means: jnp.ndarray | None = None) -> jnp.ndarray:
    """Oracle: same predictor via a dense (U, U) weight matrix matmul."""
    if means is None:
        means = user_means(ratings)
    mask = (ratings > 0).astype(jnp.float32)
    dev = (ratings - means[:, None]) * mask
    num = weight_matrix @ dev
    den = weight_matrix @ mask
    pred = means[:, None] + num / jnp.maximum(den, 1e-8)
    pred = jnp.where(den > 1e-8, pred, means[:, None])
    return jnp.clip(pred, 1.0, 5.0)


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
