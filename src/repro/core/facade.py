"""Unified CF engine facade: one entry point over every exact engine.

``CFEngine`` owns the rating matrix and the fitted neighbor state — cached
``(U, k)`` scores/ids, per-user rating statistics, and means — and dispatches
``fit`` to any of the four backends:

* ``sequential`` — single-device ``topk_neighbors`` (the paper's baseline),
* ``sharded``    — query users sharded over a mesh axis,
* ``ring``       — systolic candidate rotation (O(U/P) memory per device),
* ``pallas``     — the fused Gram-term TPU kernel (interpret mode on CPU).

All four are exact: the three XLA engines are bit-identical by construction
(the paper's "parallelisation does not change results" claim) and the fused
kernel matches to float-rounding.

``neighbor_mode="approx"`` swaps the all-pairs fit for the clustered
candidate-generation index (:mod:`repro.index`): sublinear two-stage
search — probe the nearest user clusters, shortlist by projected proxy
scores, exactly rerank the shortlist — with true similarity scores in the
cache.  The exact backends remain the oracle (``recall_vs_exact``).

Incremental maintenance
-----------------------
``update_ratings(user_ids, item_ids, values)`` absorbs a rating delta
without recomputing every Gram term.  Let S be the set of touched users:

1. the per-user sufficient statistics (rated count, rating sum → means) are
   refolded for the rows of S only — the rank-1 correction to the Gram
   aggregates, since no other row of the rating matrix moved;
2. similarities of *all* users against S are recomputed as one (U, |S|)
   Gram pass — the only pairwise terms that changed;
3. rows whose cached top-k contains no member of S are exact after merging
   the cached top-k with the fresh (row, S) scores: their other candidates'
   similarities did not move, and the cached top-k already holds the k best
   of them (``merge_topk``'s canonical tie-break keeps this order-invariant);
4. rows in S, and rows whose cached top-k intersects S (a stale neighbor
   whose score may have *dropped*), are recomputed against all candidates
   via ``block_topk`` with explicit ``q_ids``.

The result is bit-identical to a cold ``fit`` — pass ``oracle_check=True``
to assert that on every update.  Work scales with |S| + |affected| rather
than U², which is what makes neighborhood CF deployable under heavy update
traffic (cf. incremental similarity maintenance in arXiv:2106.10679).

Touched-row gathers are padded to power-of-two buckets so repeated updates
reuse a handful of compiled executables instead of recompiling per delta.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import obs
from repro.core import engine as dist_engine
from repro.core import neighbors as nb
from repro.core import predict as pred_mod
from repro.core import similarity as sim
from repro.kernels.similarity import fused_similarity

BACKENDS = ("sequential", "sharded", "ring", "pallas")
NEIGHBOR_MODES = ("exact", "approx")
RECOMMEND_MODES = ("exact", "approx")

# exact-recommend streaming: users per block and items per predict tile —
# peak intermediate is O(user_block · k · item_block), never O(m·k·I)
USER_BLOCK = 1024
ITEM_BLOCK = 512

# share of the users each query of the auto user index exactly reranks:
# at ML-10M's 69,878 users a 0.15 shortlist holds recall@40 at 0.58-0.76
# on the chip, under the 0.70 that deployment states; 0.4 holds it near
# 0.9 (PERF.md).  Once a query block's shortlists can cover every user
# the fit scores the whole matrix anyway, so only the top-M selection
# grows with it.
AUTO_RERANK_FRAC = 0.4


def _bucket(n: int, cap: int) -> int:
    """Next power of two ≥ n (≥ 8), capped — bounds distinct compile shapes."""
    b = 8
    while b < n:
        b *= 2
    return min(b, cap)


@dataclasses.dataclass
class UpdateStats:
    """What one ``update_ratings`` call did (sizes drive the speedup)."""
    n_deltas: int           # rating cells written
    n_touched: int          # distinct users whose rows changed
    n_affected: int         # rows fully recomputed (touched ∪ stale top-k)
    n_merged: int           # rows fixed by the cheap cached-merge path
    seconds: float
    oracle_ok: Optional[bool] = None    # set when oracle_check=True


@functools.partial(jax.jit, static_argnames=("measure", "beta"))
def _cross_scores(ratings, cand_ids, *, measure, beta=None):
    """Similarity of every user against the (padded) touched set.

    ``cand_ids``: (S,) global user ids, padded with out-of-range ids (≥ U).
    Self-pairs and padding columns get NEG_INF so they can never win a
    merge; the padding id must be *high* so it also loses every NEG_INF
    tie against the cache's -1 padding under merge_topk's lower-id-wins
    rule (a low sentinel would displace -1 slots and corrupt rows whose
    cached top-k is partly padding, i.e. k > n valid candidates).
    """
    n_users = ratings.shape[0]
    cand = ratings[jnp.clip(cand_ids, 0, n_users - 1)]
    s = sim.pairwise_similarity(ratings, cand, measure=measure, beta=beta)
    invalid = (cand_ids[None, :] < 0) | (cand_ids[None, :] >= n_users) | \
              (cand_ids[None, :] == jnp.arange(n_users)[:, None])
    s = jnp.where(invalid, nb.NEG_INF, s)
    ids = jnp.broadcast_to(cand_ids[None, :], s.shape)
    return s, ids


@functools.partial(jax.jit, static_argnames=("k",))
def _repair_rows(scores, idx, cross_s, cross_i, touch_ids, *, k):
    """Drop stale entries, merge fresh (row, S) scores, and certify rows.

    A repaired row is *certified exact* when every merged top-k entry scores
    strictly above the row's old k-th score (``cut``), or ties it with a
    neighbor id ≤ the old k-th entry's id ``L``.  The cache was the exact
    *canonical* top-k, so every unseen candidate scores ≤ cut, and any
    unseen candidate tied at the cut ranks canonically after the old k-th
    entry — i.e. has id > L.  Certified entries therefore cannot be
    displaced by anything outside the merge; the certificate also
    re-establishes itself for the row's next update (the repaired row is
    again an exact canonical top-k).  Rows failing the check get a full
    recompute.

    ``touch_ids``: (S,) touched user ids padded with ids ≥ U (never match
    a cached id, including empty -1 slots, and lose every NEG_INF tie).
    """
    stale = (idx[..., None] == touch_ids[None, None, :]).any(-1)
    cut = scores[:, k - 1]
    last_id = idx[:, k - 1]
    s_m = jnp.where(stale, nb.NEG_INF, scores)
    i_m = jnp.where(stale, -1, idx)
    ms, mi = nb.merge_topk(s_m, i_m, cross_s, cross_i, k)
    ok = (ms > cut[:, None]) | \
         ((ms == cut[:, None]) & (mi <= last_id[:, None]))
    return ms, mi, ok.all(axis=1)


@functools.partial(jax.jit, static_argnames=("k", "measure", "block_size",
                                             "beta"))
def _rows_topk(ratings, q_ids, *, k, measure, block_size, beta=None):
    """Full recompute for a gathered (padded) set of query rows."""
    n_users = ratings.shape[0]
    q = ratings[jnp.clip(q_ids, 0, n_users - 1)]
    return nb.block_topk(q, ratings, k, measure=measure, q_ids=q_ids,
                         block_size=min(block_size, n_users), beta=beta)


_user_stats = jax.jit(sim.user_stats)


@functools.partial(jax.jit, static_argnames=("n", "item_block"))
def _recommend_block(ratings, gather_src, scores, idx, means, q_means,
                     q_ids, *, n, item_block):
    """Exact recommend for one (padded) user block: blocked prediction
    over item tiles (the (m, k, I) intermediate is never materialised),
    seen-mask, canonical top-n with -1 for unfillable slots."""
    n_users = ratings.shape[0]
    safe = jnp.clip(q_ids, 0, n_users - 1)
    pred = pred_mod.predict_from_neighbors_blocked(
        ratings, scores, idx, means=means, query_means=q_means,
        item_block=item_block, gather_src=gather_src)
    seen = ratings[safe] > 0
    return pred_mod.topn_unseen(pred, seen, n)


@jax.jit
def _refold_stats(ratings, cnt, tot, ids):
    """Rank-1 refold: recompute count/total for the touched rows only.

    ``ids`` padded with an out-of-range id (= U) so scatters drop them.
    """
    n_users = ratings.shape[0]
    rows = ratings[jnp.clip(ids, 0, n_users - 1)]
    mask = rows > 0
    cnt = cnt.at[ids].set(jnp.sum(mask, axis=-1), mode="drop")
    tot = tot.at[ids].set(jnp.sum(rows, axis=-1), mode="drop")
    return cnt, tot, sim.means_from_stats(cnt, tot)


@jax.jit
def _scatter_rows(scores, idx, rows, new_s, new_i):
    scores = scores.at[rows].set(new_s, mode="drop")
    idx = idx.at[rows].set(new_i, mode="drop")
    return scores, idx


class CFEngine:
    """Facade over the exact CF engines with incremental rating updates.

    Parameters
    ----------
    ratings : (U, I) dense rating matrix, 0 = unrated.
    backend : one of ``BACKENDS``; ``sharded``/``ring`` need ``mesh`` (or use
        ``local_mesh()`` over all local devices when none is given).
    neighbor_mode : ``"exact"`` (default) computes true all-pairs top-k with
        the selected backend; ``"approx"`` fits a
        :class:`repro.index.ClusteredIndex` and fills the neighbor cache
        through its sublinear two-stage query — candidates from the probed
        clusters, scores still the true similarity measure.  With
        ``index_cfg`` at ``n_probe = n_clusters`` and ``rerank_frac = 0``
        the approx cache is bit-identical to the exact one.
    index_cfg : optional :class:`repro.index.IndexConfig`; default auto
        (feature geometry follows ``measure``: mean-centered rows for pcc,
        raw rows for cosine/jaccard; shortlists of ``AUTO_RERANK_FRAC`` of
        the users).
    interpret : force Pallas interpret mode; default auto (on unless TPU).
    """

    # Deliberately lock-free single-writer design, audited by the runtime
    # race harness (repro.analysis.races): one writer thread mutates the
    # model, concurrent readers (the serving batcher) take the whole model
    # through snapshot() — a single reference read of an immutable tuple
    # published atomically under the GIL.  Each entry below is a reasoned
    # annotation, not a silencer: remove one and the harness flags the
    # attribute again.
    _reprolint_race_ok = {
        "_snapshot": "atomic reference publish of an immutable tuple; "
                     "readers dereference once and never see a mix",
        "ratings": "written by the single update thread; readers use the "
                   "snapshot tuple, never this attribute mid-update",
        "scores": "same single-writer/snapshot contract as ratings",
        "idx": "same single-writer/snapshot contract as ratings",
        "means": "same single-writer/snapshot contract as ratings",
        "_cnt": "internal sufficient statistic, only the update thread "
                "reads or writes it",
        "_tot": "internal sufficient statistic, only the update thread "
                "reads or writes it",
        "_gather_cache": "immutable (ratings, operand) tuple swapped "
                         "atomically; consumers read the reference once "
                         "and validate by ratings identity, so the worst "
                         "interleaving is one redundant rebuild",
        "ratings_version": "monotone int bumped by the single writer; "
                           "readers only compare for staleness",
        "last_update": "diagnostic record, atomically rebound",
        "fit_seconds": "diagnostic scalar, atomically rebound",
    }

    def __init__(self, ratings, *, measure: str = "pcc", k: int = 40,
                 backend: str = "sequential", mesh: Optional[Mesh] = None,
                 axis: str = "data", block_size: int = 1024,
                 neighbor_mode: str = "exact", index_cfg=None,
                 recommend_mode: str = "exact", item_index_cfg=None,
                 interpret: Optional[bool] = None,
                 pcc_sig_beta: Optional[float] = None):
        if measure not in sim.SIMILARITY_MEASURES:
            raise ValueError(f"unknown measure {measure!r}; want one of "
                             f"{sim.SIMILARITY_MEASURES}")
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; want one of "
                             f"{BACKENDS}")
        if neighbor_mode not in NEIGHBOR_MODES:
            raise ValueError(f"unknown neighbor_mode {neighbor_mode!r}; "
                             f"want one of {NEIGHBOR_MODES}")
        if recommend_mode not in RECOMMEND_MODES:
            raise ValueError(f"unknown recommend_mode {recommend_mode!r}; "
                             f"want one of {RECOMMEND_MODES}")
        self.ratings = jnp.asarray(ratings, jnp.float32)
        self.measure = measure
        self.k = int(k)
        self.backend = backend
        self.axis = axis
        self.block_size = int(block_size)
        if backend in ("sharded", "ring") and mesh is None:
            mesh = dist_engine.local_mesh()
        self.mesh = mesh
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        self.interpret = bool(interpret)
        # pcc_sig shrink horizon: one engine-level setting reaching every
        # scoring path (exact backends, fused kernel, index rerank)
        self.pcc_sig_beta = sim.resolve_beta(pcc_sig_beta)

        self.neighbor_mode = neighbor_mode
        self.index = None
        if neighbor_mode == "approx":
            from repro.index import ClusteredIndex, IndexConfig
            if index_cfg is None:
                index_cfg = IndexConfig(
                    features="centered" if measure in ("pcc", "pcc_sig")
                    else "raw", rerank_frac=AUTO_RERANK_FRAC)
            self.index = ClusteredIndex(index_cfg, mesh=self.mesh,
                                        mesh_axis=self.axis)

        self.recommend_mode = recommend_mode
        self.item_index = None
        if recommend_mode == "approx":
            from repro.index import ItemClusteredIndex, ItemIndexConfig
            if item_index_cfg is None:
                item_index_cfg = ItemIndexConfig()
            self.item_index = ItemClusteredIndex(item_index_cfg)

        self.scores: Optional[jnp.ndarray] = None    # (U, k)
        self.idx: Optional[jnp.ndarray] = None       # (U, k)
        self.means: Optional[jnp.ndarray] = None     # (U,)
        self._cnt = None                             # (U,) rated-item counts
        self._tot = None                             # (U,) rating sums
        self._snapshot: Optional[tuple] = None       # atomically-published
        self._gather_cache: Optional[tuple] = None   # gather code + scale
        # ratings version counter: every update_ratings bumps it, and the
        # derived per-ratings caches (the gather code here, the user
        # index's CSR / pair tables, the item stage's support tables) are
        # delta-patched along the version chain instead of rebuilt
        # wholesale — a 1-rating delta no longer pays an O(U·I) rebuild
        self.ratings_version = 0
        self.fit_seconds = 0.0
        self.last_update: Optional[UpdateStats] = None
        # chaos hook: a FaultInjector armed here fires inside
        # update_ratings after the ratings mutation but before any derived
        # state is repaired — the torn-engine drill (see bench_chaos);
        # None in production
        self.fault_injector = None
        self._update_seq = 0

    # -- properties --------------------------------------------------------
    @property
    def n_users(self) -> int:
        return self.ratings.shape[0]

    @property
    def n_items(self) -> int:
        return self.ratings.shape[1]

    @property
    def fitted(self) -> bool:
        return self.scores is not None

    # -- fit ---------------------------------------------------------------
    def fit(self) -> "CFEngine":
        """Compute and cache top-k neighbors with the selected backend
        (exact mode) or through the clustered index (approx mode)."""
        with obs.span("engine.fit", backend=self.backend,
                      neighbor_mode=self.neighbor_mode,
                      n_users=self.n_users, n_items=self.n_items) as sp:
            self._cnt, self._tot, self.means = _user_stats(self.ratings)
            if self.neighbor_mode == "approx":
                self.index.fit(self.ratings, self.means)
                self.scores, self.idx = self.index.query(
                    self.ratings, self.means, k=self.k,
                    measure=self.measure, beta=self.pcc_sig_beta,
                    gather_src=self._gather_source(self.ratings))
            else:
                with obs.span("fit.topk", backend=self.backend):
                    self.scores, self.idx = self._topk(self.ratings)
            self.scores = jax.block_until_ready(self.scores)
            self._snapshot = (self.ratings, self.scores, self.idx,
                              self.means)
        self.fit_seconds = sp.duration
        reg = obs.registry()
        reg.histogram("engine.fit.seconds").observe(self.fit_seconds)
        reg.gauge("engine.ratings_version").set(self.ratings_version)
        return self

    def _topk(self, ratings) -> Tuple[jnp.ndarray, jnp.ndarray]:
        bs = min(self.block_size, ratings.shape[0])
        if self.backend == "sequential":
            return nb.topk_neighbors(ratings, self.k, measure=self.measure,
                                     block_size=bs, beta=self.pcc_sig_beta)
        if self.backend == "sharded":
            return dist_engine.sharded_topk(
                ratings, self.k, self.mesh, measure=self.measure,
                axis=self.axis, block_size=bs, beta=self.pcc_sig_beta)
        if self.backend == "ring":
            return dist_engine.ring_sharded_topk(
                ratings, self.k, self.mesh, measure=self.measure,
                axis=self.axis, block_size=bs, beta=self.pcc_sig_beta)
        return self._pallas_topk(ratings)

    def _pallas_topk(self, ratings) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Streaming top-k over candidate blocks scored by the fused kernel."""
        n_users, n_items = ratings.shape
        bs = min(self.block_size, n_users)
        best_s = jnp.full((n_users, self.k), nb.NEG_INF, jnp.float32)
        best_i = jnp.full((n_users, self.k), -1, jnp.int32)
        q_ids = jnp.arange(n_users)
        for b0 in range(0, n_users, bs):
            block = ratings[b0:b0 + bs]
            s = fused_similarity(
                ratings, block, measure=self.measure,
                bm=min(256, n_users), bn=min(256, block.shape[0]),
                bk=min(512, n_items), interpret=self.interpret,
                beta=self.pcc_sig_beta)
            cand_ids = b0 + jnp.arange(block.shape[0])
            s = jnp.where(cand_ids[None, :] == q_ids[:, None], nb.NEG_INF, s)
            ids = jnp.broadcast_to(cand_ids[None, :], s.shape)
            best_s, best_i = nb.merge_topk(best_s, best_i, s, ids, self.k)
        return best_s, best_i

    def _obs_update(self, stats: UpdateStats) -> UpdateStats:
        """Publish one ``update_ratings`` outcome to the registry (and to
        the enclosing ``engine.update`` span)."""
        sp = obs.current_span()
        if sp is not None:
            sp.set_attr("n_deltas", stats.n_deltas)
            sp.set_attr("n_affected", stats.n_affected)
        reg = obs.registry()
        reg.counter("engine.update.count").inc()
        reg.counter("engine.update.deltas").inc(stats.n_deltas)
        reg.histogram("engine.update.seconds").observe(stats.seconds)
        reg.gauge("engine.ratings_version").set(self.ratings_version)
        self.last_update = stats
        return stats

    # -- incremental update ------------------------------------------------
    @obs.traced("engine.update")
    def update_ratings(self, user_ids, item_ids, values, *,
                       oracle_check: bool = False) -> UpdateStats:
        """Absorb a rating delta; cached neighbors stay exact (see module doc).

        ``values`` of 0 delete ratings.  Duplicate (user, item) cells in one
        batch resolve last-wins.  Returns per-call :class:`UpdateStats`;
        with ``oracle_check`` the refreshed cache is verified bit-for-bit
        against a cold recompute (raises ``RuntimeError`` on any mismatch).

        The gather code, and the item stage's support tables, are patched
        first, once, for every reader.  In approx mode the clustered index
        is refolded next (touched proxies, centroid mass, and spill
        assignments repaired exactly — see ``repro.index``), then the same
        certificate machinery repairs the neighbor cache: certified rows
        merge the fresh touched-pair scores (true similarities),
        uncertified and touched rows re-query the index.  ``oracle_check``
        then asserts the index consistency invariant instead of bitwise
        cache equality, which is an exact-mode concept.

        The ``pallas`` backend refits in full instead of repairing: its
        cached scores carry the fused kernel's rounding, which the XLA
        repair path cannot reproduce bit-for-bit (and the kernel makes the
        refit cheap on TPU).
        """
        if not self.fitted:
            raise RuntimeError("call fit() before update_ratings()")
        t0 = time.perf_counter()
        user_ids = np.atleast_1d(np.asarray(user_ids, np.int32))
        item_ids = np.atleast_1d(np.asarray(item_ids, np.int32))
        values = np.atleast_1d(np.asarray(values, np.float32))
        if not (user_ids.shape == item_ids.shape == values.shape):
            raise ValueError("user_ids, item_ids, values must align")
        if user_ids.size == 0:
            return UpdateStats(0, 0, 0, 0, 0.0)
        if (user_ids < 0).any() or (user_ids >= self.n_users).any():
            raise ValueError("user id out of range")
        if (item_ids < 0).any() or (item_ids >= self.n_items).any():
            raise ValueError("item id out of range")

        # stream semantics: the last write to a (user, item) cell wins —
        # JAX scatter order for duplicate indices is undefined, so dedupe
        # on the host before applying
        cell = user_ids.astype(np.int64) * self.n_items + item_ids
        _, last_rev = np.unique(cell[::-1], return_index=True)
        keep = np.sort(cell.size - 1 - last_rev)
        user_ids, item_ids, values = (user_ids[keep], item_ids[keep],
                                      values[keep])

        touched = np.unique(user_ids)
        prev_ratings = self.ratings
        self.ratings = self.ratings.at[jnp.asarray(user_ids),
                                       jnp.asarray(item_ids)].set(
                                           jnp.asarray(values))
        self.ratings_version += 1
        self._update_seq += 1
        if self.fault_injector is not None:
            # chaos hook: the ratings array has been swapped and the
            # version bumped, but stats/caches/snapshot are all stale —
            # exactly the torn state a recovery must repair.  The failure
            # is recorded before the raise (concurrent readers keep the
            # previous snapshot: it is only republished at the end of a
            # successful update).
            try:
                self.fault_injector.check(self._update_seq)
            except Exception:
                obs.registry().counter("engine.update.failures").inc()
                raise

        # 1. refold the touched rows' sufficient statistics
        s_pad = _bucket(len(touched), self.n_users)
        pad_touch = np.full((s_pad,), self.n_users, np.int32)  # drop-scatter
        pad_touch[:len(touched)] = touched
        pad_touch_j = jnp.asarray(pad_touch)
        self._cnt, self._tot, self.means = _refold_stats(
            self.ratings, self._cnt, self._tot, pad_touch_j)
        # delta-patch the gather code and the item stage's support tables
        # along the version chain, before either index reads them
        # (copy-on-write: concurrent snapshot readers keep the old operand;
        # single local read of the cache reference — see _gather_source)
        gather_cache = self._gather_cache
        if gather_cache is not None and gather_cache[0] is prev_ratings:
            self._gather_cache = (self.ratings, pred_mod.patch_gather_source(
                gather_cache[1], self.ratings, pad_touch_j))
        else:
            self._gather_cache = None
        if self.item_index is not None:
            self.item_index.patch(prev_ratings, self.ratings, self.means,
                                  pad_touch_j)
        if self.neighbor_mode == "approx":
            self.index.refold(self.ratings, self.means, touched,
                              version=self.ratings_version)

        # the pallas backend's scores carry the fused kernel's rounding; the
        # XLA-scored repair path would mix incomparable floats into the
        # cache, so exactness there means a full refit — which is the cheap
        # operation that backend exists to provide (approx mode never uses
        # the backend's fit, so the repair path below applies instead)
        if self.backend == "pallas" and self.neighbor_mode == "exact":
            self.scores, self.idx = self._topk(self.ratings)
            self.scores = jax.block_until_ready(self.scores)
            self._snapshot = (self.ratings, self.scores, self.idx,
                              self.means)
            stats = UpdateStats(
                n_deltas=int(user_ids.size), n_touched=int(len(touched)),
                n_affected=self.n_users, n_merged=0,
                seconds=time.perf_counter() - t0)
            if oracle_check:
                stats.oracle_ok = self._check_oracle()
            return self._obs_update(stats)

        # 2. one (U, |S|) Gram pass for the changed pairwise terms
        cross_s, cross_i = _cross_scores(self.ratings, pad_touch_j,
                                         measure=self.measure,
                                         beta=self.pcc_sig_beta)

        # 3. cheap path: drop stale entries, merge fresh (row, S) scores,
        #    and certify which rows that provably repaired
        merged_s, merged_i, safe = _repair_rows(
            self.scores, self.idx, cross_s, cross_i, pad_touch_j, k=self.k)

        # 4. recompute path for touched and uncertified rows: exact top-k
        #    in exact mode, a fresh index query (same candidate policy as
        #    fit) in approx mode
        need = ~np.asarray(safe)
        need[touched] = True
        affected = np.nonzero(need)[0].astype(np.int32)
        n_merged = self.n_users - len(affected)
        if len(affected):
            a_pad = _bucket(len(affected), self.n_users)
            rows = np.full((a_pad,), self.n_users, np.int32)
            rows[:len(affected)] = affected
            rows_j = jnp.asarray(rows)
            if self.neighbor_mode == "approx":
                q_s, q_i = self.index.query(
                    self.ratings, self.means, affected, k=self.k,
                    measure=self.measure, beta=self.pcc_sig_beta,
                    gather_src=self._gather_source(self.ratings))
                new_s = np.full((a_pad, self.k), nb.NEG_INF, np.float32)
                new_i = np.full((a_pad, self.k), -1, np.int32)
                new_s[:len(affected)] = np.asarray(q_s)
                new_i[:len(affected)] = np.asarray(q_i)
                new_s, new_i = jnp.asarray(new_s), jnp.asarray(new_i)
            else:
                new_s, new_i = _rows_topk(self.ratings, rows_j, k=self.k,
                                          measure=self.measure,
                                          block_size=self.block_size,
                                          beta=self.pcc_sig_beta)
            merged_s, merged_i = _scatter_rows(merged_s, merged_i, rows_j,
                                               new_s, new_i)
        self.scores = jax.block_until_ready(merged_s)
        self.idx = merged_i
        # single atomic publish: a concurrent reader (the serving batcher)
        # sees either the whole old model or the whole new one, never a mix
        self._snapshot = (self.ratings, self.scores, self.idx, self.means)

        stats = UpdateStats(
            n_deltas=int(user_ids.size), n_touched=int(len(touched)),
            n_affected=int(len(affected)), n_merged=int(n_merged),
            seconds=time.perf_counter() - t0)
        if oracle_check:
            stats.oracle_ok = self._check_oracle()
        return self._obs_update(stats)

    def _check_oracle(self) -> bool:
        """Exact mode: assert cache == cold full recompute, bit for bit.
        Approx mode: the cache is defined by the index's candidate policy,
        so the oracle instead asserts the *index* invariant — assignments
        and proxies equal a cold reassignment — plus exact means."""
        if self.neighbor_mode == "approx":
            ok = self.index.check_consistent(self.ratings, self.means)
            _, _, ref_m = _user_stats(self.ratings)
            if not np.array_equal(np.asarray(ref_m), np.asarray(self.means)):
                raise RuntimeError("incremental means diverged from a "
                                   "full recompute")
            return ok
        ref_s, ref_i = self._topk(self.ratings)
        _, _, ref_m = _user_stats(self.ratings)
        errs = []
        if not np.array_equal(np.asarray(ref_s), np.asarray(self.scores)):
            errs.append("scores")
        if not np.array_equal(np.asarray(ref_i), np.asarray(self.idx)):
            errs.append("neighbor ids")
        if not np.array_equal(np.asarray(ref_m), np.asarray(self.means)):
            errs.append("means")
        if errs:
            raise RuntimeError(
                f"incremental update diverged from full recompute: "
                f"{', '.join(errs)}")
        return True

    # -- diagnostics -------------------------------------------------------
    def recall_vs_exact(self, sample: int = 1024, seed: int = 0) -> float:
        """Mean recall@k of the cached neighbors against the exact engine.

        Samples ``sample`` users (seeded, without replacement), recomputes
        their exact top-k rows, and returns the mean fraction of exact
        neighbor ids present in the cache.  1.0 in exact mode by
        construction; the approx-mode quality diagnostic.
        """
        if not self.fitted:
            raise RuntimeError("call fit() first")
        rng = np.random.default_rng(seed)
        n = min(sample, self.n_users)
        users = np.sort(rng.choice(self.n_users, n, replace=False)
                        ).astype(np.int32)
        u_pad = _bucket(len(users), self.n_users)
        rows = np.full((u_pad,), -1, np.int32)
        rows[:len(users)] = users
        ref_s, ref_i = _rows_topk(self.ratings, jnp.asarray(rows),
                                  k=self.k, measure=self.measure,
                                  block_size=self.block_size,
                                  beta=self.pcc_sig_beta)
        ref_i = np.asarray(ref_i)[:len(users)]
        got_i = np.asarray(self.idx)[users]
        hits = 0
        total = 0
        for row in range(len(users)):
            exact = set(int(j) for j in ref_i[row] if j >= 0)
            if not exact:
                continue
            hits += len(exact & set(int(j) for j in got_i[row]))
            total += len(exact)
        return hits / max(total, 1)

    # -- inference ---------------------------------------------------------
    def snapshot(self) -> tuple:
        """Consistent (ratings, scores, idx, means) view for concurrent readers."""
        if self._snapshot is None:
            raise RuntimeError("call fit() first")
        return self._snapshot

    def neighbors(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        if not self.fitted:
            raise RuntimeError("call fit() first")
        return self.scores, self.idx

    # -- persistence -------------------------------------------------------
    def state(self) -> dict:
        """Checkpointable engine state as a pytree of host arrays, shaped
        for ``repro.distributed.checkpoint.save`` — the recovery path the
        chaos drills exercise: save after each committed update, and a
        fault that tears the model mid-update restores the last committed
        tree with :meth:`load_state`.

        Every leaf is a fresh host copy (the index core hands out live
        ledger references), so a captured tree can never alias state a
        later update mutates in place.  Derived caches (gather code,
        CSR/pair/support tables) are deliberately absent: they are keyed
        by ratings-array identity and rebuild lazily after a restore.  The
        item stage holds no other state, so it has no entry.
        """
        if not self.fitted:
            raise RuntimeError("call fit() first")
        copy = functools.partial(jax.tree_util.tree_map,
                                 lambda x: np.array(x))
        return {
            "ratings": np.array(self.ratings),
            "scores": np.array(self.scores),
            "idx": np.array(self.idx),
            "means": np.array(self.means),
            "cnt": np.array(self._cnt),
            "tot": np.array(self._tot),
            "meta": np.asarray([self.ratings_version], np.int64),
            # a fitted engine implies a fitted index, so presence alone
            # decides the tree structure — state_template() must mirror it
            # exactly for checkpoint.restore(like=...)
            "index": copy(self.index.state())
            if self.index is not None else {},
        }

    def state_template(self) -> dict:
        """Structure-only tree for ``checkpoint.restore(..., like=...)``,
        mirroring this engine's configuration (leaf values are ignored —
        shapes come from the checkpoint shards)."""
        out = {k: 0 for k in ("ratings", "scores", "idx", "means",
                              "cnt", "tot", "meta")}
        out["index"] = (type(self.index).state_template()
                        if self.index is not None else {})
        return out

    def load_state(self, tree: dict) -> "CFEngine":
        """Restore a :meth:`state` tree (typically from
        ``checkpoint.restore``): model arrays, sufficient statistics, and
        index state return to the committed point, derived caches drop
        (identity-keyed, so they rebuild lazily and can never serve the
        torn model), and the snapshot is republished atomically — a
        concurrent reader flips to the restored model in one reference
        swap, exactly like a successful update.

        A tree written before the item stage became stateless also holds
        an ``item_index`` entry of cluster state; it is ignored (restore
        such a file with its own structure as ``like``)."""
        self.ratings = jnp.asarray(np.asarray(tree["ratings"], np.float32))
        scores = jnp.asarray(np.asarray(tree["scores"], np.float32))
        self.idx = jnp.asarray(np.asarray(tree["idx"], np.int32))
        self.means = jnp.asarray(np.asarray(tree["means"], np.float32))
        self._cnt = jnp.asarray(np.asarray(tree["cnt"]))
        self._tot = jnp.asarray(np.asarray(tree["tot"]))
        self.ratings_version = int(np.asarray(tree["meta"]).reshape(-1)[0])
        self._gather_cache = None
        if self.index is not None and tree.get("index"):
            self.index.load_state(tree["index"])
        self.scores = jax.block_until_ready(scores)
        self._snapshot = (self.ratings, self.scores, self.idx, self.means)
        obs.registry().gauge("engine.ratings_version").set(
            self.ratings_version)
        return self

    def _gather_source(self, ratings):
        """The engine's one gather operand (``predict.GatherSource``: an
        int8 code when the matrix round-trips exactly) and its rating
        scale, read by predict, both recommend paths and the user index's
        rerank: derived once per ratings array, patched by updates, the
        scale only widened (cached per ratings array — a rating update
        replaces the array, which invalidates by identity).

        Read the cache reference ONCE: the serving batcher calls this
        while ``update_ratings`` may swap ``_gather_cache`` on the writer
        thread, and a second dereference after the swap could see ``None``
        (the race harness in ``repro.analysis.races`` flags exactly this
        check-then-use shape).  Each published tuple is immutable and
        keyed by ratings identity, so a stale local is merely a rebuild,
        never a wrong answer."""
        cache = self._gather_cache
        if cache is not None and cache[0] is ratings:
            return cache[1]
        src = pred_mod.make_gather_source(ratings)
        self._gather_cache = (ratings, src)
        return src

    def predict(self, user_ids=None) -> jnp.ndarray:
        """Predicted full item rows for ``user_ids`` (default: all users).

        Streams over item tiles (``predict_from_neighbors_blocked``), so
        the ``(m, k, I)`` neighbor-rating intermediate is never
        materialised; the returned ``(m, I)`` matrix is the only large
        allocation.  Bit-identical to the one-shot gather form.  Reads
        the atomically-published snapshot, like every inference path.
        """
        if not self.fitted:
            raise RuntimeError("call fit() first")
        ratings, scores, idx, means = self.snapshot()
        if user_ids is not None:
            u = jnp.asarray(user_ids)
            scores, idx, q_means = scores[u], idx[u], means[u]
        else:
            q_means = means
        return pred_mod.predict_from_neighbors_blocked(
            ratings, scores, idx, means=means,
            query_means=q_means, item_block=ITEM_BLOCK,
            gather_src=self._gather_source(ratings))

    def recommend(self, user_ids=None, n: int = 10, *,
                  mode: Optional[str] = None,
                  shortlist: Optional[int] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Top-n unseen items ``(scores, item ids)`` for ``user_ids``.

        ``mode`` overrides the engine's ``recommend_mode`` per call
        (``"approx"`` requires the item stage).  ``shortlist`` is a
        per-call candidate budget forwarded to the item stage (approx
        mode only — the exact path has no candidate stage, so passing it
        there raises instead of silently ignoring a quality knob).  The
        serving degradation ladder uses it to trade recall for latency
        per request class.  The exact path streams user blocks × item
        tiles — peak memory O(UB·k·IB); the approx path scores every item,
        shortlists on the device and reranks exactly, returning exact
        predicted ratings for an approximate candidate set.  Slots a user
        cannot fill (fewer unseen items than ``n``) come back as item -1
        with score -inf in both modes; already-rated items are never
        returned.

        Model arrays and the gather code come from the atomically
        published snapshot and its ratings array, so a concurrent
        ``update_ratings`` can never produce a torn read: the item stage's
        support tables are keyed by that same ratings array.
        """
        if not self.fitted:
            raise RuntimeError("call fit() first")
        mode = mode or self.recommend_mode
        if mode not in RECOMMEND_MODES:
            raise ValueError(f"unknown recommend mode {mode!r}")
        ratings, scores, idx, means = self.snapshot()
        uids = (np.arange(self.n_users, dtype=np.int32) if user_ids is None
                else np.atleast_1d(np.asarray(user_ids, np.int32)))
        src = self._gather_source(ratings)
        if mode == "approx":
            if self.item_index is None:
                raise RuntimeError(
                    "recommend(mode='approx') needs the item stage — "
                    "construct with recommend_mode='approx'")
            kw = dict(n=n, shortlist=shortlist, gather_src=src)
            # taste-cluster query order: users of one cluster share
            # neighbors, so the support scorer re-reads the same table
            # rows while they are still cache-resident; results are
            # scattered back to the caller's order
            if self.index is not None and self.index.fitted \
                    and len(uids) > 4096:
                perm = np.argsort(self.index.assign[uids], kind="stable")
                s, i = self.item_index.recommend(
                    ratings, means, scores, idx, uids[perm], **kw)
                inv = np.empty_like(perm)
                inv[perm] = np.arange(len(perm))
                return s[jnp.asarray(inv)], i[jnp.asarray(inv)]
            return self.item_index.recommend(ratings, means, scores, idx,
                                             uids, **kw)

        if shortlist is not None:
            raise ValueError(
                "shortlist is an approx-mode candidate budget; the exact "
                "path scores every item and cannot honor it")
        out_s = np.empty((len(uids), n), np.float32)
        out_i = np.empty((len(uids), n), np.int32)
        ub = min(USER_BLOCK, _bucket(len(uids), self.n_users))
        for lo in range(0, len(uids), ub):
            ids = uids[lo:lo + ub]
            ids_pad = np.full((ub,), self.n_users, np.int32)
            ids_pad[:len(ids)] = ids
            ids_j = jnp.asarray(ids_pad)
            safe = jnp.clip(ids_j, 0, self.n_users - 1)
            s, i = _recommend_block(
                ratings, src, scores[safe], idx[safe],
                means, means[safe], ids_j, n=n,
                item_block=ITEM_BLOCK)
            out_s[lo:lo + len(ids)] = np.asarray(s)[:len(ids)]
            out_i[lo:lo + len(ids)] = np.asarray(i)[:len(ids)]
        return jnp.asarray(out_s), jnp.asarray(out_i)

    def recommend_recall_vs_exact(self, sample: int = 256, n: int = 10,
                                  seed: int = 0) -> float:
        """Mean recall@n of approx recommendations against the exact
        blocked path on a seeded user sample — the recommend analogue of
        ``recall_vs_exact``.  1.0 when the item stage's shortlist is
        uncapped."""
        if not self.fitted:
            raise RuntimeError("call fit() first")
        rng = np.random.default_rng(seed)
        n_s = min(sample, self.n_users)
        users = np.sort(rng.choice(self.n_users, n_s, replace=False)
                        ).astype(np.int32)
        _, ref_i = self.recommend(users, n, mode="exact")
        _, got_i = self.recommend(users, n, mode="approx")
        ref_i, got_i = np.asarray(ref_i), np.asarray(got_i)
        hits = 0
        total = 0
        for row in range(n_s):
            ref = set(int(j) for j in ref_i[row] if j >= 0)
            if not ref:
                continue
            hits += len(ref & set(int(j) for j in got_i[row]))
            total += len(ref)
        return hits / max(total, 1)
