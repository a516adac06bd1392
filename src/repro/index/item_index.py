"""The recommend stage: support scores → device top-``m`` → exact rerank.

:class:`ItemClusteredIndex` answers ``recommend`` for a block of query
users in three steps:

1. **Score** — every item with the exact predictor's num/den form,

       num[u, i] = Σ_k w[u,k] · dev[nb[u,k], i]
       den[u, i] = Σ_k w[u,k] · msk[nb[u,k], i]
       score     = clip(r̄_u + num/den, lo, hi)   (r̄_u where den = 0)

   over the ``(U, 1, W)`` deviation/mask tables of
   :mod:`repro.kernels.support`.  On a TPU the Pallas kernel
   ``fused_support_scores`` evaluates them; elsewhere its oracle
   ``repro.kernels.ref.support_scores_ref`` evaluates the same tables
   under ``jit``.  The scorer *is* the predictor, so the shortlist holds
   the exact top-n up to float summation order.
2. **Select** — seen items are knocked out and the canonical
   (-score, item id) top-``m`` unseen items form a candidate mask, by
   ``lax.top_k`` at a static width with ``m`` traced: every smaller
   budget of the serving ladder runs one program.
3. **Rerank** — the rows are predicted over every item by the exact
   blocked form (``predict_from_neighbors_blocked`` over the engine's
   gather code: whole neighbor rows per item tile), masked to the
   candidates, and canonically top-n selected.  Returned scores are
   exact predictions; only the candidate set is approximate.

Steps 2 and 3 are one device program per block of rows
(:func:`_select_rerank_items`).  ``shortlist = 0``, or a shortlist of at
least the catalog, selects every unseen item: the answers are then
bit-identical to the exact recommend path.

The stage holds no model state.  Ratings, means, the neighbor cache and
the gather code come with every call; what it keeps is its support
tables, derived data keyed by ratings identity and patched by a row
scatter when the engine absorbs a rating delta.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import predict as pred_mod
from repro.index.clustered import _bucket
from repro.kernels import ref
from repro.kernels.support import (BB, fused_support_scores, support_rows,
                                   support_width)


@dataclasses.dataclass(frozen=True)
class ItemIndexConfig:
    """Knobs of :class:`ItemClusteredIndex`.

    ``shortlist`` caps the exactly reranked candidate items per user (the
    accuracy/latency dial; ``0`` reranks every unseen item, the bit-exact
    degenerate mode).  ``use_kernel``: ``None`` runs the Pallas scorer on
    a TPU and its reference elsewhere; ``interpret`` runs the kernel in
    Pallas interpret mode off the TPU.
    """
    shortlist: int = 512
    item_block: int = 512                 # rerank/predict tile width
    rerank_block: int = 1024              # rows of one score and one
                                          # select-rerank program
    use_kernel: Optional[bool] = None
    interpret: bool = False


@dataclasses.dataclass
class RecommendStats:
    """Work accounting for one ``recommend`` call."""
    n_queries: int
    n_items: int           # candidate population the fraction refers to
    n_reranked: int        # candidates exactly reranked (a device scalar,
                           # read when the fraction is asked for)
    scorer: str = ""       # "kernel" (Pallas) or "ref" (its reference)

    @property
    def rerank_fraction(self) -> float:
        return int(self.n_reranked) / max(
            self.n_queries * max(self.n_items, 1), 1)


def _rerank_attrs(gather_src, rows: int, k: int, n_items: int,
                  item_block: int) -> dict:
    """Attributes of a ``recommend.rerank`` span: its query rows, and
    the neighbor-row bytes the call reads from the gather source — each
    row's ``k`` neighbors over the whole item tiles at the code's width."""
    width = -(-n_items // item_block) * item_block
    return {"rows": rows, "gather_bytes": rows * k * width
            * gather_src.code.dtype.itemsize}


def _seen_rows(gather_src, q, n_items: int, item_block: int):
    """(b, I) rated mask of the query rows ``q`` (clipped ids), read from
    the item tiles of the gather code that the blocked prediction slices:
    no row gather of a (U, I) operand, which a column-major device layout
    (the TPU's choice at ML-10M's shape) would copy whole."""
    return jnp.concatenate(
        [jax.lax.slice_in_dim(gather_src.code, lo,
                              min(lo + item_block, n_items), axis=1)[q] > 0
         for lo in range(0, n_items, item_block)], axis=1)


def _rerank_masked(ratings, gather_src, nb_scores, nb_idx, means, q_means,
                   cand_mask, seen, *, n, item_block):
    """The rerank body: the rows predicted over every item by the exact
    blocked recommend path's own form, the unseen candidates kept, the
    canonical (-score, item id) top-n.  Excluded items get -inf and
    surface as item id -1, the recommendation contract."""
    n_items = ratings.shape[1]
    pred = pred_mod.predict_from_neighbors_blocked(
        ratings, nb_scores, nb_idx, means=means, query_means=q_means,
        item_block=item_block, gather_src=gather_src)
    s = jnp.where(cand_mask & ~seen, pred, -jnp.inf)
    if n_items < n:
        s = jnp.pad(s, ((0, 0), (0, n - n_items)), constant_values=-jnp.inf)
    # reprolint: disable=canonical-selection -- column j is item j and XLA top_k ties break toward the lower index: the canonical (-score, item id) order
    top_s, top_i = jax.lax.top_k(s, n)
    return top_s, jnp.where(top_s == -jnp.inf, -1, top_i)


@jax.jit
def _support_operands(nb_scores, nb_idx, means, q_ids):
    """The support scorer's operands for a block of padded query ids:
    the neighbor ids clipped into ``[0, U)``, the prediction weights
    (invalid and non-positive neighbors at 0) and the query means."""
    n_users = means.shape[0]
    q = jnp.clip(q_ids, 0, n_users - 1)
    sc, idx = nb_scores[q], nb_idx[q]
    w = jnp.where((sc > 0.0) & (idx >= 0), sc, 0.0)
    return jnp.clip(idx, 0, n_users - 1), w, means[q]


@functools.partial(jax.jit, static_argnames=("scale",))
def _support_scores_ref(dev, msk, nb_idx, nb_w, q_means, *, scale):
    """The kernel's reference over the kernel's own ``(U, 1, W)`` tables,
    ``BB`` query rows at a time as the kernel takes them: (b, W) scores."""
    return jnp.concatenate([
        ref.support_scores_ref(dev[:, 0], msk[:, 0], nb_idx[lo:lo + BB],
                               nb_w[lo:lo + BB], q_means[lo:lo + BB],
                               scale=scale)
        for lo in range(0, nb_idx.shape[0], BB)])


@jax.jit
def _patch_tables(tables, ratings, means, ids):
    """The support tables with rows ``ids`` (padded with ``U``, dropped)
    re-derived from the post-delta ratings and means; copy-on-write, so a
    concurrent reader keeps the pre-delta tables."""
    q = jnp.clip(ids, 0, ratings.shape[0] - 1)
    rows = support_rows(ratings[q], means[q], tables[0].shape[2])
    return tuple(t.at[ids].set(r, mode="drop")
                 for t, r in zip(tables, rows))


def _canonical_top_mask(s, m, *, width: int):
    """(b, I) scores, -inf where excluded → (b, I) mask of each row's
    canonical (-score, item id) top-``m`` finite items, ``m <= width``
    traced.  The top-``m`` are the items at or before the ``m``-th pick
    of ``top_k(s, width)`` in that order, read off as a threshold on
    (score, id); a row with fewer than ``m`` finite items takes them
    all."""
    # reprolint: disable=canonical-selection -- column j is item j and XLA top_k ties break toward the lower index: the canonical (-score, item id) order
    top_v, top_c = jax.lax.top_k(s, width)
    v = jax.lax.dynamic_index_in_dim(top_v, m - 1, axis=1)
    c = jax.lax.dynamic_index_in_dim(top_c, m - 1, axis=1)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return (s > -jnp.inf) & ((s > v) | ((s == v) & (col <= c)))


@functools.partial(jax.jit,
                   static_argnames=("n", "shortlist", "item_block"))
def _select_rerank_items(ratings, gather_src, nb_scores, nb_idx, means,
                         q_ids, scores, m, *, n, shortlist, item_block):
    """Shortlist selection and exact rerank of one block, in one program.

    ``scores``: the block's (b, >= I) support scores (columns past ``I``
    are the tables' pad tiles and are dropped).  Seen items are knocked
    out and the canonical (-score, item id) top-``m`` unseen items form
    the candidate mask (:func:`_canonical_top_mask`).  ``shortlist`` is
    the static selection width; ``m <= shortlist`` is traced, so every
    smaller budget of the serving ladder runs this one program.  Padding
    rows carry the id ``U``.  Returns the rerank's ``(scores, ids)`` and
    the real rows' candidate count."""
    n_users, n_items = ratings.shape
    q = jnp.clip(q_ids, 0, n_users - 1)
    seen = _seen_rows(gather_src, q, n_items, item_block)
    cand = _canonical_top_mask(
        jnp.where(seen, -jnp.inf, scores[:, :n_items]), m, width=shortlist)
    n_cand = jnp.sum(cand & (q_ids < n_users)[:, None])
    top_s, top_i = _rerank_masked(ratings, gather_src, nb_scores[q],
                                  nb_idx[q], means, means[q], cand, seen,
                                  n=n, item_block=item_block)
    return top_s, top_i, n_cand


class ItemClusteredIndex:
    """The recommend stage (see the module docstring).  Never owns the
    rating matrix, the neighbor cache or the gather code: the caller
    (``CFEngine``) passes them into every call."""

    def __init__(self, cfg: ItemIndexConfig = ItemIndexConfig()):
        self.cfg = cfg
        self._tables: Optional[tuple] = None   # (ratings, (dev, msk))
        self._budgets: dict = {}               # m → device scalar
        self.last_recommend: Optional[RecommendStats] = None

    def _scorer(self) -> str:
        """``"kernel"`` where the Pallas scorer runs (a TPU, or interpret
        mode asked for), ``"ref"`` elsewhere: Mosaic lowers only on TPU."""
        on_tpu = jax.default_backend() == "tpu"
        use = on_tpu if self.cfg.use_kernel is None else self.cfg.use_kernel
        return "kernel" if use and (on_tpu or self.cfg.interpret) else "ref"

    def _support_tables(self, ratings, means):
        """The ``(U, 1, W)`` deviation/mask tables of ``ratings``, built
        once per ratings array at the kernel's tile width.  The cache
        reference is read once: an update may swap it on the writer
        thread, and a stale entry is a rebuild, never a wrong table."""
        cache = self._tables
        if cache is not None and cache[0] is ratings:
            return cache[1]
        tables = support_rows(ratings, means, support_width(ratings.shape[1]))
        self._tables = (ratings, tables)
        return tables

    def patch(self, prev, ratings, means, ids) -> bool:
        """Carry the support tables from ``prev`` to ``ratings``, whose
        only changed rows are ``ids`` (touched users, padded with ``U``):
        those rows are scattered, copy-on-write.  Tables of any other
        array are dropped and rebuilt on next use.  Returns whether the
        tables were patched."""
        cache = self._tables
        if cache is None or cache[0] is not prev:
            self._tables = None
            return False
        self._tables = (ratings, _patch_tables(cache[1], ratings, means,
                                               ids))
        return True

    def _budget(self, m: int):
        """The traced selection budget ``m`` as a device scalar, made once
        per value so a served batch uploads only its ids."""
        if m not in self._budgets:
            self._budgets[m] = jnp.asarray(m, jnp.int32)
        return self._budgets[m]

    def recommend(self, ratings: jnp.ndarray, means: jnp.ndarray,
                  nb_scores: jnp.ndarray, nb_idx: jnp.ndarray,
                  user_ids=None, *, n: int = 10,
                  shortlist: Optional[int] = None, gather_src=None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Top-n unseen items ``(scores, item ids)`` of shape
        ``(len(user_ids), n)``: exact predicted ratings, -1 for slots a
        user cannot fill.  Sets ``self.last_recommend``.

        ``nb_scores``/``nb_idx``: the engine's full (U, k) neighbor cache
        (the scores are the prediction weights).  ``gather_src``: the
        engine's gather code of ``ratings``; a standalone call without
        one derives it for this call only.  ``shortlist`` overrides the
        config budget for this call: the serving ladder trades candidate
        set size for latency per request class through it.
        """
        n_users, n_items = ratings.shape
        uids = (np.arange(n_users, dtype=np.int32) if user_ids is None
                else np.atleast_1d(np.asarray(user_ids, np.int32)))
        if uids.size == 0:
            self.last_recommend = RecommendStats(0, n_items, 0)
            return (jnp.zeros((0, n), jnp.float32),
                    jnp.full((0, n), -1, jnp.int32))
        if gather_src is None:
            gather_src = pred_mod.make_gather_source(ratings)

        def cap(s):                           # 0 = every item
            return n_items if not s else min(max(n, s), n_items)

        m = cap(self.cfg.shortlist if shortlist is None
                else max(int(shortlist), n))
        width = max(m, cap(self.cfg.shortlist))
        scorer = self._scorer()
        with obs.span("item_index.recommend", n_queries=len(uids), n=n,
                      scorer=scorer) as sp:
            out = self._recommend_blocks(ratings, means, nb_scores, nb_idx,
                                         uids, gather_src, n=n, m=m,
                                         width=width, scorer=scorer)
        obs.registry().histogram("item_index.recommend.seconds").observe(
            sp.duration)
        return out

    def _recommend_blocks(self, ratings, means, nb_scores, nb_idx,
                          uids: np.ndarray, gather_src, *, n: int, m: int,
                          width: int, scorer: str):
        """Score, select and rerank ``rerank_block`` rows at a time: a
        block uploads only its ids, its scores never leave the device,
        and the one fence is the last block's, in its
        ``recommend.rerank`` span.  Returns device arrays."""
        n_users, n_items = ratings.shape
        dev, msk = self._support_tables(ratings, means)
        budget = self._budget(m)
        bq = min(self.cfg.rerank_block, _bucket(len(uids)))
        outs = []
        n_reranked = None
        for b0 in range(0, len(uids), bq):
            sub = uids[b0:b0 + bq]
            nv = len(sub)
            ids_pad = np.full((bq,), n_users, np.int32)
            ids_pad[:nv] = sub
            ids_j = jnp.asarray(ids_pad)
            with obs.span("recommend.score", rows=nv):
                safe, w, q_means = _support_operands(nb_scores, nb_idx,
                                                     means, ids_j)
                if scorer == "kernel":
                    scores = fused_support_scores(
                        dev, msk, safe, w, q_means, scale=gather_src.scale,
                        interpret=self.cfg.interpret)
                else:
                    scores = _support_scores_ref(dev, msk, safe, w, q_means,
                                                 scale=gather_src.scale)
            with obs.span("recommend.rerank", block=b0 // bq,
                          **_rerank_attrs(gather_src, nv, nb_idx.shape[1],
                                          n_items, self.cfg.item_block)):
                s, i, cnt = _select_rerank_items(
                    ratings, gather_src, nb_scores, nb_idx, means, ids_j,
                    scores, budget, n=n, shortlist=width,
                    item_block=self.cfg.item_block)
                if b0 + bq >= len(uids):
                    jax.block_until_ready((s, i))
            outs.append((s, i) if nv == bq else (s[:nv], i[:nv]))
            n_reranked = cnt if n_reranked is None else n_reranked + cnt
        obs.registry().counter("item_index.device_select_rows").inc(
            len(uids))
        self.last_recommend = RecommendStats(
            n_queries=len(uids), n_items=n_items, n_reranked=n_reranked,
            scorer=scorer)
        if len(outs) == 1:
            return outs[0]
        return (jnp.concatenate([o[0] for o in outs]),
                jnp.concatenate([o[1] for o in outs]))

