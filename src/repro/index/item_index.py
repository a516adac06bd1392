"""Item-side clustered index: the two-stage *recommend* path.

PR 2's :class:`repro.index.ClusteredIndex` made neighbor search sublinear,
but ``recommend`` still scored **every** item for every query user — the
remaining O(U·I) wall.  :class:`ItemClusteredIndex` applies the same
two-stage idea on the item axis:

1. **Project** — item *columns* of the rating matrix (optionally centered
   by user means, so a column reads "which users liked this item more than
   usual") become unit proxy vectors via the same seeded randomized-SVD
   range finder.
2. **Cluster** — the shared blocked spill k-means partitions items by
   audience; each item spill-assigns to its nearest clusters exactly as
   users do (all bookkeeping inherited from ``_SpillClusterCore``).
3. **Shortlist** — a cheap full-width scorer ranks candidate items per
   query user; the canonical (-score, item id) best ``shortlist`` unseen
   items go forward, selected on the device by ``lax.top_k`` in the same
   program as the rerank.  The scorers (``shortlist_mode``):

   * ``"kernel"`` (TPU default) — the support pass below as a fused
     Pallas segmented SpMM (``repro.kernels.support``); its scores stay
     on the device.
   * ``"support"`` (CPU default) — the *item-major sparse pass*: the
     predictor ``r̄_u + Σ w·dev / Σ w·mask`` is one sparse×dense product
     ``W @ [DEV | MASK]`` between the k-sparse neighbor-weight matrix and
     a precomputed stacked deviation/mask table, walked row-major (CSR)
     instead of as per-user random gathers.  Empirically the exact top-n
     is dominated by items a *single* neighbor rated far above their mean
     — spiky, profile-blind — so the shortlist must evaluate the true
     num/den form; this pass does, in f32 with the same clip-and-tie
     epilogue, so shortlist containment of the exact top-n is ≈1 even at
     tiny shortlists.  Uses ``scipy.sparse`` when importable (gated; the
     container ships it) and a jnp gather fallback otherwise.
   * ``"proxy"`` — MXU-friendly two-stage candidate
     generation: each user carries a *taste profile* in item-proxy space
     (``Σ max(r−r̄,0)·proxy_i`` over their rated items; neighbors'
     profiles aggregated with the prediction weights), the profile probes
     its ``n_probe`` nearest item clusters, and probed members are scored
     with one proxy GEMM.  Smooth — it cannot see single-neighbor spikes,
     so its recall is bounded by how far taste geometry predicts the
     spiky exact top-n; it exists for accelerators where the host sparse
     pass is unavailable and as the candidate-pruning stage the cluster
     structure was built for.

4. **Rerank** — the batch's rows are predicted over every item with the
   *true* neighbor-weighted prediction of the dense blocked path
   (``repro.core.predict.predict_from_neighbors_blocked``: whole neighbor
   rows gathered per item tile, O(m·k·I) streamed rather than
   O(m·k·shortlist) gathered one element at a time), masked to the
   shortlist's unseen items, and canonically top-n selected.  Returned
   scores are exact predictions — the dense blocked path's own
   arithmetic — so only the candidate set is approximate.

With ``n_probe == n_clusters`` and ``shortlist = 0`` (uncapped) the
shortlist stage is bypassed, the candidate set is every item, and the
result is bit-identical to the exact blocked recommend path — the
degenerate mode the oracle tests pin down.

Maintenance mirrors the user index: ``refold`` refreshes the touched item
columns' proxies, repairs spill assignments exactly through the shared
certificate, and maintains the user profiles by a rank-deficient
correction (untouched users get ``Σ w_col · Δproxy`` over the touched
columns — exact because their weight columns did not move; touched users
are recomputed in full).  ``check_consistent`` asserts all of it against
a cold rebuild, and the shared auto-refit guard bounds centroid drift.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

try:                       # optional host fast path (see shortlist_mode)
    import scipy.sparse as _scipy_sparse
except ImportError:        # pragma: no cover - container ships scipy
    _scipy_sparse = None

from repro import obs
from repro.core import predict as pred_mod
from repro.core import similarity as sim
from repro.index.clustered import (_SpillClusterCore, _bucket, _project,
                                   _svd_basis)
from repro.index.kmeans import normalize_rows


@dataclasses.dataclass(frozen=True)
class ItemIndexConfig:
    """Tuning knobs for :class:`ItemClusteredIndex`.

    Auto values: ``n_clusters = 0`` → ``⌈√I⌉``; ``n_probe = 0`` → half the
    clusters.  ``shortlist`` caps the exactly-reranked candidate items per
    user (the accuracy/latency dial; ``0`` reranks every probed item — the
    bit-exact degenerate mode when ``n_probe = n_clusters``).
    ``project_dim`` is clamped to the user count; ``0`` disables the
    projection.  ``features="centered"`` clusters columns of the user-mean
    deviation matrix (prediction geometry); ``"raw"`` clusters raw rating
    columns and makes ``refold`` cheaper (a rating write only touches its
    own column, no user-mean coupling).
    """
    n_clusters: int = 0
    n_probe: int = 0
    seed: int = 0
    iters: int = 8
    features: str = "raw"                 # "raw" | "centered"
    project_dim: int = 128
    spill: int = 2
    shortlist: int = 512
    shortlist_mode: str = "auto"          # "support" | "kernel" | "proxy" |
                                          # "auto" (kernel — the fused Pallas
                                          # segmented SpMM over the same
                                          # exact num/den form — where
                                          # use_kernel resolves true, TPU
                                          # by default; support elsewhere)
    item_block: int = 512                 # rerank/predict tile width
    kmeans_block: int = 2048
    query_block: int = 256
    rerank_block: int = 1024              # support-path block rows: one
                                          # score and one select-rerank
                                          # program each
    use_kernel: Optional[bool] = None     # None → auto: fused kernel on TPU
    interpret: bool = False
    refit_reassign_frac: float = 0.5      # shared auto-refit drift guard
    # periodic profile re-fold: the Σ w·Δproxy profile correction is exact
    # in exact arithmetic but accumulates float error over many refolds;
    # when the cumulative touched-column fraction since the last fold
    # crosses this, profiles are re-folded from scratch (one (U,I)·(I,p)
    # matmul), zeroing the drift (0 disables).  Piggybacks the same
    # refold bookkeeping as the auto-refit guard, at a lower threshold.
    profile_refold_frac: float = 0.25


@dataclasses.dataclass
class RecommendStats:
    """Work accounting for one ``recommend`` call."""
    n_queries: int
    n_items: int           # candidate population the fractions refer to
    n_probed: int          # probed-member items summed over queries
    n_reranked: int        # items exactly predicted (true rerank); the
                           # support path leaves a device scalar, read
                           # when a fraction is asked for
    scorer: str = ""       # shortlist scorer that ran: "support" (host
                           # CSR pass), "kernel" (Pallas support kernel)
                           # or "proxy" (proxy GEMM + top-M selection)

    def _frac(self, total) -> float:
        return int(total) / max(self.n_queries * max(self.n_items, 1), 1)

    @property
    def probed_fraction(self) -> float:
        return self._frac(self.n_probed)

    @property
    def rerank_fraction(self) -> float:
        return self._frac(self.n_reranked)


@functools.partial(jax.jit, static_argnames=("features",))
def _item_feats(cols: jnp.ndarray, means: jnp.ndarray, *,
                features: str) -> jnp.ndarray:
    """(U, T) column slice of the rating matrix → (T, U) unit feature rows.

    ``centered`` subtracts each rating user's mean on rated cells (a zero
    stays "no information"), matching the deviations the predictor sums.
    """
    z = (jnp.where(cols > 0, cols - means[:, None], 0.0)
         if features == "centered" else cols)
    return normalize_rows(z.T)


@jax.jit
def _affinity_weights(ratings: jnp.ndarray, means: jnp.ndarray):
    """Per-user item-affinity weights for the taste profile: positive
    above-mean deviation, falling back to the plain rated mask for users
    with no above-mean rating (so every rated user has a live profile)."""
    mask = ratings > 0
    pos = jnp.where(mask, jnp.maximum(ratings - means[:, None], 0.0), 0.0)
    has_pos = jnp.any(pos > 0, axis=1)
    w = jnp.where(has_pos[:, None], pos, mask.astype(jnp.float32))
    return w, has_pos


@jax.jit
def _fold_profiles(w: jnp.ndarray, proxies: jnp.ndarray) -> jnp.ndarray:
    """(U, I) affinity weights × (I, p) item proxies → (U, p) profiles."""
    return jnp.matmul(w, proxies)


@jax.jit
def _query_profiles(profiles, nb_scores, nb_idx, q_ids):
    """Unit recommendation profile per (padded) query row: the cached
    neighbors' profiles combined with the prediction weights; a user with
    no positive-score neighbor falls back to their own profile."""
    n_users = profiles.shape[0]
    w = jnp.where((nb_scores > 0.0) & (nb_idx >= 0), nb_scores, 0.0)
    nbp = profiles[jnp.clip(nb_idx, 0, n_users - 1)]          # (b, k, p)
    agg = jnp.sum(w[..., None] * nbp, axis=1)
    own = profiles[jnp.clip(q_ids, 0, n_users - 1)]
    has_nb = jnp.any(w > 0, axis=1, keepdims=True)
    return normalize_rows(jnp.where(has_nb, agg, own))


@jax.jit
def _shortlist_scores(prof, proxies, cand_ids, seen_rows):
    """Proxy affinity of each query profile against the shared candidate
    item set — one GEMM; seen items and padding are knocked out."""
    n_items = proxies.shape[0]
    safe = jnp.clip(cand_ids, 0, n_items - 1)
    sp = prof @ proxies[safe].T                               # (b, L)
    seen = jnp.take_along_axis(seen_rows, safe[None, :].repeat(
        prof.shape[0], axis=0), axis=1)
    invalid = (cand_ids[None, :] >= n_items) | seen
    return jnp.where(invalid, -jnp.inf, sp)


@jax.jit
def _shortlist_scores_all(prof, proxies, seen_rows):
    """Full-pool variant (column j is item j): no candidate gather."""
    sp = prof @ proxies.T
    return jnp.where(seen_rows, -jnp.inf, sp)


def _support_rows(rows: np.ndarray, row_means: np.ndarray) -> np.ndarray:
    """(b, I) rating rows → (b, 2I) stacked [deviation | rated-mask] —
    the support scorer's table (dense form, for the jnp fallback)."""
    mask = rows > 0
    dev = np.where(mask, rows - row_means[:, None], 0.0).astype(np.float32)
    return np.concatenate([dev, mask.astype(np.float32)], axis=1)


def _support_csr(rnp: np.ndarray, means_np: np.ndarray):
    """Sparse (U, 2I) stacked [deviation | rated-mask] in CSR.

    The rating matrix is ~96% zeros, so the item-major scorer multiplies
    sparse × sparse — ~50× fewer multiply-adds than walking dense table
    rows.  Both channels share the rating matrix's sparsity pattern, so
    the structure is built from one ``np.nonzero`` scan.
    """
    n_users, n_items = rnp.shape
    rows, cols = np.nonzero(rnp)
    counts = np.bincount(rows, minlength=n_users)
    indptr = np.zeros(n_users + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    dev_vals = (rnp[rows, cols] - means_np[rows]).astype(np.float32)
    dev = _scipy_sparse.csr_matrix(
        (dev_vals, cols.astype(np.int32), indptr),
        shape=(n_users, n_items))
    mask = _scipy_sparse.csr_matrix(
        (np.ones(len(cols), np.float32), cols.astype(np.int32), indptr),
        shape=(n_users, n_items))
    return _scipy_sparse.hstack([dev, mask], format="csr")


@functools.partial(jax.jit, static_argnames=("scale",))
def _support_scores_jnp(stacked, nb_scores, nb_idx, q_means, *, scale):
    """jnp fallback for the support scorer (no scipy): gather the (b, k,
    2I) stacked rows and reduce — exact same num/den epilogue, element-
    bound instead of row-major."""
    n_users = stacked.shape[0]
    n_items = stacked.shape[1] // 2
    w = jnp.where((nb_scores > 0.0) & (nb_idx >= 0), nb_scores, 0.0)
    rows = stacked[jnp.clip(nb_idx, 0, n_users - 1)]          # (b, k, 2I)
    nd = jnp.sum(w[:, :, None] * rows, axis=1)                # (b, 2I)
    num, den = nd[:, :n_items], nd[:, n_items:]
    pred = q_means[:, None] + num / jnp.maximum(den, 1e-8)
    pred = jnp.where(den > 1e-8, pred, q_means[:, None])
    return pred_mod.clip_to_scale(pred, scale)


def _rerank_attrs(gather_src, rows: int, k: int, n_items: int,
                  item_block: int) -> dict:
    """Attributes of a ``recommend.rerank`` span: its query rows, and
    the neighbor-row bytes the call reads from the gather source — each
    row's ``k`` neighbors over the whole item tiles at the code's width."""
    width = -(-n_items // item_block) * item_block
    return {"rows": rows, "gather_bytes": rows * k * width
            * gather_src.code.dtype.itemsize}


def _shortlist_mask(short: np.ndarray, n_items: int) -> np.ndarray:
    """(b, L) int32 shortlist rows → (b, I) boolean candidate mask.

    Built on the host: the ids land in a (b, I + 1) array, so the
    ``n_items`` padding sentinel falls in the dropped last column."""
    mask = np.zeros((short.shape[0], n_items + 1), bool)
    mask[np.arange(short.shape[0])[:, None], short] = True
    return mask[:, :n_items]


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
    """The rerank body: predict the rows over every item, keep the unseen
    candidates, canonical top-n (see :func:`_rerank_items`)."""
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


@functools.partial(jax.jit, static_argnames=("n", "item_block"))
def _rerank_items(ratings, gather_src, nb_scores, nb_idx, means, q_means,
                  q_ids, cand_mask, *, n, item_block):
    """Exact top-n over per-query candidate item masks.

    The batch's rows are predicted over every item by the exact blocked
    recommend path's own form (``predict_from_neighbors_blocked``: whole
    neighbor rows gathered per item tile, never one element per
    (neighbor, candidate)), then masked to the candidates.  Selection is
    the canonical (-score, item id) top-n, so the full-candidate case is
    bit-identical to the dense path.  Seen and non-candidate items get
    -inf and surface as item id -1, the recommendation contract.
    """
    n_users, n_items = ratings.shape
    seen = _seen_rows(gather_src, jnp.clip(q_ids, 0, n_users - 1), n_items,
                      item_block)
    return _rerank_masked(ratings, gather_src, nb_scores, nb_idx, means,
                          q_means, cand_mask, seen, n=n,
                          item_block=item_block)


@jax.jit
def _support_operands(nb_scores, nb_idx, means, q_ids):
    """The support kernel's operands for a block of padded query ids:
    the neighbor ids clipped into ``[0, U)``, the prediction weights
    (invalid and non-positive neighbors at 0) and the query means."""
    n_users = means.shape[0]
    q = jnp.clip(q_ids, 0, n_users - 1)
    sc, idx = nb_scores[q], nb_idx[q]
    w = jnp.where((sc > 0.0) & (idx >= 0), sc, 0.0)
    return jnp.clip(idx, 0, n_users - 1), w, means[q]


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
    are the kernel's pad tiles and are dropped).  Seen items are knocked
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


class ItemClusteredIndex(_SpillClusterCore):
    """Item-clustering index powering the two-stage recommend path (see
    module docstring).  Never owns the rating matrix or the neighbor
    cache — the caller (``CFEngine``) passes both into every call."""

    def __init__(self, cfg: ItemIndexConfig = ItemIndexConfig(),
                 mesh=None, mesh_axis: str = "data"):
        if cfg.shortlist_mode not in ("support", "kernel", "proxy", "auto"):
            raise ValueError(
                f"unknown shortlist_mode {cfg.shortlist_mode!r}; "
                "want 'support', 'kernel', 'proxy', or 'auto'")
        super().__init__(cfg, mesh=mesh, mesh_axis=mesh_axis)
        self.n_users = 0
        self.profiles: Optional[jnp.ndarray] = None   # (U, p) taste mass
        self._has_pos: Optional[jnp.ndarray] = None   # (U,) bool
        self._support_cache: Optional[tuple] = None   # per-ratings [dev|mask]
        self._support_dense_cache: Optional[tuple] = None  # kernel operands
        self._budgets: dict = {}                      # m → device scalar
        self._touched_since_profile = 0               # profile-refold drift
        self.last_recommend: Optional[RecommendStats] = None

    def _shortlist_mode(self) -> str:
        if self.cfg.shortlist_mode != "auto":
            return self.cfg.shortlist_mode
        return "kernel" if self._use_kernel() else "support"

    def _support_dense(self, ratings, means):
        """Device-resident ``(U, 1, W)`` deviation/mask tables for the
        fused support-scorer kernel (``repro.kernels.support``), built
        once at the kernel's tile width so the jitted call never re-pads
        or re-lays them out.  Cached per ratings array, like every
        derived operand."""
        if self._support_dense_cache is not None and \
                self._support_dense_cache[0] is ratings:
            return self._support_dense_cache[1]
        from repro.kernels.support import support_rows, support_width
        pair = support_rows(ratings, means, support_width(ratings.shape[1]))
        self._support_dense_cache = (ratings, pair)
        return pair

    def _support_table(self, ratings, means):
        """The stacked [deviation | mask] scorer operand — sparse CSR
        with scipy, dense rows otherwise.  Derived data, cached per
        ratings array (a rating update replaces the array, which
        invalidates by identity), so it is always exact and needs no
        refold bookkeeping or checkpointing."""
        if self._support_cache is not None and \
                self._support_cache[0] is ratings:
            return self._support_cache[1]
        if _scipy_sparse is not None:
            tbl = _support_csr(np.asarray(ratings), np.asarray(means))
        else:
            tbl = _support_rows(np.asarray(ratings), np.asarray(means))
        self._support_cache = (ratings, tbl)
        return tbl

    @property
    def n_items(self) -> int:
        return self.n_rows

    def _proxy_rows(self, cols, means):
        """(U, T) column slice → (T, p) unit proxies."""
        z = _item_feats(cols, means, features=self.cfg.features)
        return _project(z, self.basis) if self.basis is not None else z

    # -- fit ---------------------------------------------------------------
    def fit(self, ratings: jnp.ndarray,
            means: Optional[jnp.ndarray] = None) -> "ItemClusteredIndex":
        """Project, cluster, and spill-assign the item columns, then fold
        every user's taste profile into item-proxy space."""
        ratings = jnp.asarray(ratings, jnp.float32)
        self._ratings_key = ratings          # (re)anchor the version chain
        self.n_users, self.n_rows = ratings.shape
        if means is None:
            means = sim.user_stats(ratings)[2]
        self._resolve_sizes()

        with obs.span("item_index.fit", device_sync=True,
                      n_users=self.n_users, n_items=self.n_rows,
                      n_clusters=self.cfg.n_clusters) as sp:
            z = _item_feats(ratings, means, features=self.cfg.features)
            p = min(self.cfg.project_dim, self.n_users)
            if self.cfg.project_dim and p < self.n_users:
                with obs.span("fit.svd_basis", dim=p):
                    self.basis = jnp.asarray(
                        _svd_basis(np.asarray(z), p, self.cfg.seed))
            else:
                self.basis = None
            self.proxies = (_project(z, self.basis)
                            if self.basis is not None else z)
            self._fit_clusters()

            w, has_pos = _affinity_weights(ratings, means)
            self.profiles = _fold_profiles(w, self.proxies)
            self._has_pos = has_pos
            self._support_cache = None
            self._support_dense_cache = None
            self._touched_since_profile = 0
            if self._shortlist_mode() != "kernel":
                # pre-warm scorer operand
                self._support_table(ratings, means)
            sp.track(self.profiles)
        obs.registry().histogram("item_index.fit.seconds").observe(
            sp.duration)
        return self

    # -- recommend ---------------------------------------------------------
    def recommend(self, ratings: jnp.ndarray, means: jnp.ndarray,
                  nb_scores: jnp.ndarray, nb_idx: jnp.ndarray,
                  user_ids=None, *, n: int = 10,
                  n_probe: Optional[int] = None,
                  shortlist: Optional[int] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Top-n unseen items through the two-stage pipeline.

        ``nb_scores``/``nb_idx``: the engine's full (U, k) neighbor cache
        (scores must be the prediction weights, i.e. the cached true
        similarities).  Returns ``(scores, item_ids)`` of shape
        ``(len(user_ids), n)`` with exact predicted ratings as scores and
        -1 for slots a user cannot fill; sets ``self.last_recommend``.

        ``n_probe``/``shortlist`` override the config budgets for this
        call only — the serving degradation ladder trades candidate-set
        size for latency per request class without touching the frozen
        config other callers resolve.
        """
        if not self.fitted:
            raise RuntimeError("call fit() first")
        uids = (np.arange(self.n_users, dtype=np.int32) if user_ids is None
                else np.atleast_1d(np.asarray(user_ids, np.int32)))
        if uids.size == 0:
            self.last_recommend = RecommendStats(0, self.n_items, 0, 0)
            return (jnp.zeros((0, n), jnp.float32),
                    jnp.full((0, n), -1, jnp.int32))
        n_probe = min(n_probe or self.n_probe, self.n_clusters)
        shortlist = self.cfg.shortlist if shortlist is None \
            else max(int(shortlist), n)
        s_mode = self._shortlist_mode()
        if s_mode == "kernel" and jax.default_backend() != "tpu" \
                and not self.cfg.interpret:
            # Mosaic does not lower on CPU and interpret mode was not
            # requested: score the same exact num/den form through the
            # host support pass instead (the kernel's CPU twin)
            s_mode = "support"
        if shortlist and s_mode in ("support", "kernel") \
                and max(n, shortlist) < self.n_items:
            with obs.span("item_index.recommend", n_queries=len(uids),
                          n=n, scorer=s_mode) as sp:
                out = self._recommend_support(ratings, means, nb_scores,
                                              nb_idx, uids, n=n,
                                              scorer=s_mode,
                                              shortlist=shortlist)
            self._obs_recommend(sp)
            return out
        with obs.span("item_index.recommend", n_queries=len(uids), n=n,
                      scorer="proxy") as sp:
            out = self._recommend_proxy(ratings, means, nb_scores, nb_idx,
                                        uids, n=n, n_probe=n_probe,
                                        shortlist=shortlist)
        self._obs_recommend(sp)
        return out

    @staticmethod
    def _obs_recommend(sp) -> None:
        """Publish one recommend call's time to the registry (root span
        closed); its counts are in ``last_recommend``."""
        obs.registry().histogram("item_index.recommend.seconds").observe(
            sp.duration)

    def _recommend_proxy(self, ratings, means, nb_scores, nb_idx,
                         uids: np.ndarray, *, n: int, n_probe: int,
                         shortlist: Optional[int] = None):
        """The dense proxy-scorer path: probe item clusters near each
        query block's taste profile, proxy-shortlist, exact rerank (the
        non-support fallback of :meth:`recommend`)."""
        if shortlist is None:
            shortlist = self.cfg.shortlist
        gather_src = self._gather_source(ratings)
        bq = min(self.cfg.query_block, _bucket(len(uids)))
        out_s = np.empty((len(uids), n), np.float32)
        out_i = np.empty((len(uids), n), np.int32)
        n_probed = 0
        n_reranked = 0
        # full probing covers every item (each item's primary cluster is
        # always among its spill clusters), so skip the per-block union
        pool_all = n_probe >= self.n_clusters
        cand_all = np.arange(self.n_items, dtype=np.int32)

        for lo in range(0, len(uids), bq):
            ids = uids[lo:lo + bq]
            nv = len(ids)
            ids_pad = np.full((bq,), self.n_users, np.int32)
            ids_pad[:nv] = ids
            ids_j = jnp.asarray(ids_pad)
            safe_j = jnp.clip(ids_j, 0, self.n_users - 1)
            nbs, nbi = nb_scores[safe_j], nb_idx[safe_j]
            q_means = means[safe_j]
            prof = _query_profiles(self.profiles, nbs, nbi, ids_j)
            seen_rows = ratings[safe_j] > 0                   # (bq, I)

            if pool_all:
                cand, cand_pad = cand_all, cand_all
            else:
                d = self._distances(prof, self.centroids)
                # reprolint: disable=canonical-selection -- probe-cluster ties break toward the lowest cluster id: canonical by construction
                probe = np.asarray(jax.lax.top_k(-d, n_probe)[1])
                clusters = np.unique(probe[:nv])
                cand = np.unique(np.concatenate(
                    [self._members[c] for c in clusters]))
                L = _bucket(len(cand))
                cand_pad = np.full((L,), self.n_items, np.int32)
                cand_pad[:len(cand)] = cand
            n_probed += nv * len(cand)

            m_short = max(n, shortlist) if shortlist else 0
            if m_short and m_short < len(cand):
                with obs.span("recommend.shortlist", block=lo // bq,
                              candidates=len(cand)):
                    sp_dev = (_shortlist_scores_all(prof, self.proxies,
                                                    seen_rows)
                              if pool_all else
                              _shortlist_scores(prof, self.proxies,
                                                jnp.asarray(cand_pad),
                                                seen_rows))
                    # device top-M on every backend — proxy scores never
                    # round-trip to the host: the exact lax.top_k twin
                    # (Mosaic cannot lower the Pallas select kernel).
                    # cand_pad is ascending, so lower-index ties are
                    # lower-id ties; the scores already carry the
                    # seen-item knockout, so no self-knockout is needed
                    # reprolint: disable=canonical-selection -- exact lax.top_k twin of kernels/select.py: XLA ties break toward the lower index, same canonical (-score, id) order
                    v, sel = jax.lax.top_k(
                        sp_dev, min(m_short, sp_dev.shape[1]))
                    selv = np.asarray(v)[:nv]
                    sel = np.asarray(sel)[:nv]
                    # sel uses the sentinel id len(cand_pad) for -inf
                    # slots; clamp before the gather, then mask — never
                    # index a member table through a dead slot
                    sel = np.minimum(sel, len(cand_pad) - 1)
                    short = np.where(np.isneginf(selv), self.n_items,
                                     cand_pad[sel]).astype(np.int32)
                    short = np.sort(short, axis=1)  # ascending → monotone
                    short_pad = np.full((bq, m_short), self.n_items,
                                        np.int32)
                    short_pad[:nv] = short
            else:
                short_pad = np.broadcast_to(cand_pad[None, :],
                                            (bq, len(cand_pad)))
            blk_rows = int((short_pad[:nv] < self.n_items).sum())
            n_reranked += blk_rows

            with obs.span("recommend.rerank", block=lo // bq,
                          candidates=blk_rows,
                          **_rerank_attrs(gather_src, nv, nbi.shape[1],
                                          self.n_items,
                                          self.cfg.item_block)):
                s, i = _rerank_items(
                    ratings, gather_src, nbs, nbi, means, q_means, ids_j,
                    jnp.asarray(_shortlist_mask(short_pad, self.n_items)),
                    n=n, item_block=self.cfg.item_block)
                out_s[lo:lo + nv] = np.asarray(s)[:nv]
                out_i[lo:lo + nv] = np.asarray(i)[:nv]

        self.last_recommend = RecommendStats(
            n_queries=len(uids), n_items=self.n_items,
            n_probed=n_probed, n_reranked=n_reranked, scorer="proxy")
        return jnp.asarray(out_s), jnp.asarray(out_i)

    def _host_support_scores(self, stacked, safe_idx, w, q_means,
                             scale: tuple):
        """The support scores of one block on the host: the exact f32
        num/den form with the clip-to-``scale`` epilogue, as (b, I)."""
        n_items = self.n_items
        if _scipy_sparse is None:
            return _support_scores_jnp(jnp.asarray(stacked), w, safe_idx,
                                       q_means, scale=scale)
        w, safe_idx = np.asarray(w), np.asarray(safe_idx)
        rows = np.repeat(np.arange(w.shape[0]), w.shape[1])
        W = _scipy_sparse.csr_matrix(
            (w.reshape(-1), (rows, safe_idx.reshape(-1))),
            shape=(w.shape[0], self.n_users))
        nd = (W @ stacked).toarray()              # (b, 2I)
        num, den = nd[:, :n_items], nd[:, n_items:]
        qm = np.asarray(q_means)[:, None]
        fallback = den <= 1e-8
        np.maximum(den, 1e-8, out=den)
        np.divide(num, den, out=num)
        num += qm
        if scale[0] <= scale[1]:
            np.clip(num, *scale, out=num)
        np.copyto(num, np.broadcast_to(qm, num.shape), where=fallback)
        return num

    def _budget(self, m: int):
        """The traced selection budget ``m`` as a device scalar, made once
        per value so a served batch uploads only its ids."""
        if m not in self._budgets:
            self._budgets[m] = jnp.asarray(m, jnp.int32)
        return self._budgets[m]

    def _recommend_support(self, ratings, means, nb_scores, nb_idx,
                           uids: np.ndarray, *, n: int, scorer: str,
                           shortlist: int):
        """Support-scorer path: every item scored with the exact num/den
        predictor form, the canonical top ``shortlist`` unseen items per
        user go to the exact rerank.

        ``scorer="kernel"`` computes the num/den form with the fused
        Pallas segmented SpMM (``repro.kernels.support``), gathering each
        neighbor row tile once through VMEM; ``scorer="support"`` is its
        host twin, one ``W @ [DEV|MASK]`` product between the k-sparse
        neighbor-weight matrix and the stacked deviation/mask CSR.  Either
        way the scorer *is* the predictor, so shortlist containment of the
        exact top-n is limited only by float summation order.  Selection
        and rerank run in one device program per block of
        ``rerank_block`` rows (:func:`_select_rerank_items`): on the
        kernel path the scores never leave the device, and a block uploads
        only its ids.  Returns device arrays; the one fence is the last
        block's, in its ``recommend.rerank`` span.
        """
        from repro.kernels.support import fused_support_scores
        stacked = (self._support_table(ratings, means)
                   if scorer == "support" else None)
        n_items = self.n_items
        width = min(max(n, self.cfg.shortlist, shortlist), n_items)
        m = self._budget(min(max(n, shortlist), n_items))
        gather_src = self._gather_source(ratings)
        scale = gather_src.scale
        bq = min(self.cfg.rerank_block, _bucket(len(uids)))
        outs = []
        n_reranked = None
        for b0 in range(0, len(uids), bq):
            sub = uids[b0:b0 + bq]
            nv = len(sub)
            ids_pad = np.full((bq,), self.n_users, np.int32)
            ids_pad[:nv] = sub
            ids_j = jnp.asarray(ids_pad)
            with obs.span("recommend.score", rows=nv):
                safe, w, q_means = _support_operands(nb_scores, nb_idx,
                                                     means, ids_j)
                if scorer == "kernel":
                    dev, msk = self._support_dense(ratings, means)
                    scores = fused_support_scores(
                        dev, msk, safe, w, q_means, scale=scale,
                        interpret=self.cfg.interpret)
                else:
                    scores = self._host_support_scores(stacked, safe, w,
                                                       q_means, scale)
            with obs.span("recommend.rerank", block=b0 // bq,
                          **_rerank_attrs(gather_src, nv, nb_idx.shape[1],
                                          n_items, self.cfg.item_block)):
                s, i, cnt = _select_rerank_items(
                    ratings, gather_src, nb_scores, nb_idx, means, ids_j,
                    scores, m, n=n, shortlist=width,
                    item_block=self.cfg.item_block)
                if b0 + bq >= len(uids):
                    jax.block_until_ready((s, i))
            outs.append((s, i) if nv == bq else (s[:nv], i[:nv]))
            n_reranked = cnt if n_reranked is None else n_reranked + cnt
        obs.registry().counter("item_index.device_select_rows").inc(
            len(uids))

        self.last_recommend = RecommendStats(
            n_queries=len(uids), n_items=n_items,
            n_probed=len(uids) * n_items, n_reranked=n_reranked,
            scorer=scorer)
        if len(outs) == 1:
            return outs[0]
        return (jnp.concatenate([o[0] for o in outs]),
                jnp.concatenate([o[1] for o in outs]))

    # -- delta-aware cache maintenance -------------------------------------
    def _patch_extra_row_caches(self, ratings, means, touched, old) -> int:
        """Delta-patch the support-scorer operands for a user-row delta:
        the stacked [dev|mask] CSR gets a row splice (touched users'
        deviations re-derive from their moved means; untouched rows
        bulk-copy), the dense kernel operands a row scatter."""
        patched = 0
        if self._support_cache is not None and \
                self._support_cache[0] is old and means is not None:
            tbl = self._support_cache[1]
            rows_new = np.asarray(ratings[jnp.asarray(touched)])
            means_t = np.asarray(means[jnp.asarray(touched)])
            if _scipy_sparse is not None and _scipy_sparse.issparse(tbl):
                n_items = self.n_items
                stacked_rows = _support_rows(rows_new, means_t)
                from repro.index.clustered import _patch_csr
                indptr, idx, data = _patch_csr(
                    (tbl.indptr.astype(np.int64), tbl.indices, tbl.data),
                    touched, stacked_rows)
                tbl = _scipy_sparse.csr_matrix(
                    (data, idx, indptr), shape=(self.n_users,
                                                2 * n_items))
            else:
                tbl = tbl.copy()
                tbl[touched] = _support_rows(rows_new, means_t)
            self._support_cache = (ratings, tbl)
            patched += 1
        else:
            self._support_cache = None
        if self._support_dense_cache is not None and \
                self._support_dense_cache[0] is old and means is not None:
            from repro.kernels.support import support_rows
            dev, msk = self._support_dense_cache[1]
            t_j = jnp.asarray(touched)
            d_rows, m_rows = support_rows(ratings[t_j], means[t_j],
                                          dev.shape[2])
            self._support_dense_cache = (
                ratings, (dev.at[t_j].set(d_rows),
                          msk.at[t_j].set(m_rows)))
            patched += 1
        else:
            self._support_dense_cache = None
        return patched

    def _drop_extra_row_caches(self) -> None:
        self._support_cache = None
        self._support_dense_cache = None

    # -- incremental maintenance ------------------------------------------
    def refold(self, ratings: jnp.ndarray, means: jnp.ndarray,
               touched_users: np.ndarray,
               touched_items: np.ndarray, *,
               version: Optional[int] = None):
        """Fold a rating delta into the item index.

        ``touched_users``/``touched_items``: the delta's distinct user and
        item ids; ``ratings``/``means`` the post-update arrays.  In
        ``centered`` mode the touched-column set expands to every item the
        touched users rate (their mean moved, which re-centers all their
        columns).  Assignment repair is exact (shared certificate);
        profiles are maintained exactly: untouched users take the
        ``Σ w·Δproxy`` correction over the touched columns (their weight
        columns did not move), touched users are recomputed in full.
        ``version``: the caller's ratings version counter — derived
        per-ratings caches (gather source, support-scorer operands) are
        delta-patched along an unbroken chain instead of rebuilt.
        """
        if not self.fitted:
            raise RuntimeError("call fit() first")
        t_users = np.unique(np.atleast_1d(
            np.asarray(touched_users, np.int32)))
        t_items = np.unique(np.atleast_1d(
            np.asarray(touched_items, np.int32)))
        n_patched = self._patch_row_caches(ratings, t_users, version,
                                           means=means)
        if self.cfg.features == "centered" and t_users.size:
            rated = np.asarray(ratings[jnp.asarray(t_users)] > 0)
            t_items = np.unique(np.concatenate(
                [t_items, np.nonzero(rated.any(axis=0))[0]])
            ).astype(np.int32)
        from repro.index.clustered import RefoldStats
        if t_items.size == 0:
            self.last_refold = RefoldStats(0, 0, 0, 0, self.n_items)
            return self.last_refold

        with obs.span("item_index.refold",
                      n_touched=int(t_items.size)) as sp:
            ti_j = jnp.asarray(t_items)
            p_old = np.asarray(self.proxies[ti_j])
            p_new_j = self._proxy_rows(ratings[:, ti_j], means)
            changed, full_rows, reassigned = self._refold_rows(t_items,
                                                               p_new_j)

            # profile maintenance against the moved proxies
            d_p = jnp.asarray(np.asarray(p_new_j) - p_old)    # (T, p)
            cols = ratings[:, ti_j]                           # (U, T)
            mask = cols > 0
            pos = jnp.where(mask,
                            jnp.maximum(cols - means[:, None], 0.0), 0.0)
            w_cols = jnp.where(self._has_pos[:, None], pos,
                               mask.astype(jnp.float32))
            if t_users.size:
                w_cols = w_cols.at[jnp.asarray(t_users)].set(0.0)
            self.profiles = self.profiles + w_cols @ d_p
            if t_users.size:
                tu_j = jnp.asarray(t_users)
                w_t, hp_t = _affinity_weights(ratings[tu_j], means[tu_j])
                self.profiles = self.profiles.at[tu_j].set(
                    _fold_profiles(w_t, self.proxies))
                self._has_pos = self._has_pos.at[tu_j].set(hp_t)

            stats = RefoldStats(
                n_touched=int(t_items.size),
                n_changed_clusters=len(changed),
                n_reassigned=reassigned, n_full_rows=len(full_rows),
                n_certified=self.n_items - len(full_rows),
                caches_patched=n_patched)

            # periodic profile re-fold (ROADMAP "profile drift"): once the
            # cumulative touched-column fraction crosses the threshold,
            # zero the accumulated Σ w·Δproxy float error with one cold
            # fold — piggybacking the same drift bookkeeping as the refit
            # guard
            self._touched_since_profile += int(t_items.size)
            thr = getattr(self.cfg, "profile_refold_frac", 0.0)
            if thr and self._touched_since_profile >= thr * self.n_items:
                w_all, hp_all = _affinity_weights(ratings, means)
                self.profiles = _fold_profiles(w_all, self.proxies)
                self._has_pos = hp_all
                self._touched_since_profile = 0
                stats.profile_refold = True

            self._maybe_refit(ratings, means, stats)
            if stats.refit:
                self._touched_since_profile = 0  # fit re-folded profiles
        self.last_refold = stats
        reg = obs.registry()
        reg.counter("item_index.refold.count").inc()
        reg.histogram("item_index.refold.seconds").observe(sp.duration)
        reg.gauge("item_index.refold.reassign_frac").set(
            stats.reassigned_frac)
        reg.gauge("item_index.refold.caches_patched").set(
            stats.caches_patched)
        if stats.refit:
            reg.counter("item_index.refit.count").inc()
        if version is not None:
            reg.gauge("item_index.ratings_version").set(version)
        return stats

    # -- diagnostics -------------------------------------------------------
    def check_consistent(self, ratings: jnp.ndarray,
                         means: jnp.ndarray) -> bool:
        """Assert proxies/spill/mass equal a cold rebuild (shared refold
        invariants) and the user profiles equal a cold fold of the current
        affinity weights; raises on mismatch."""
        p_cold = np.asarray(self._proxy_rows(ratings, means))
        errs = self._check_spill_state(p_cold)
        w, has_pos = _affinity_weights(ratings, means)
        if not np.array_equal(np.asarray(has_pos),
                              np.asarray(self._has_pos)):
            errs.append("affinity flags")
        cold_prof = np.asarray(_fold_profiles(w, self.proxies))
        # profiles are maintained by Δproxy corrections; only float
        # accumulation of the corrections themselves can drift
        if not np.allclose(cold_prof, np.asarray(self.profiles),
                           rtol=1e-4, atol=1e-3):
            errs.append("profiles")
        if errs:
            raise RuntimeError(
                "item index diverged from a cold rebuild: "
                f"{', '.join(errs)}")
        return True

    # -- persistence -------------------------------------------------------
    _STATE_KEYS = _SpillClusterCore._STATE_KEYS + ("has_pos", "item_meta",
                                                   "profiles")

    def _extra_state(self) -> dict:
        return {
            "has_pos": np.asarray(self._has_pos),
            "item_meta": np.asarray([self.n_users,
                                     self._touched_since_profile], np.int64),
            "profiles": np.asarray(self.profiles),
        }

    def _load_extra_state(self, tree: dict) -> None:
        meta = np.asarray(tree["item_meta"]).reshape(-1)
        self.n_users = int(meta[0])
        self.profiles = jnp.asarray(
            np.asarray(tree["profiles"], np.float32))
        self._has_pos = jnp.asarray(np.asarray(tree["has_pos"]).astype(bool))
        # the scorer operands are derived data: rebuilt lazily per ratings
        self._support_cache = None
        self._support_dense_cache = None
        # profile-refold drift restored exactly (older checkpoints carry
        # only n_users; they predate the counter and start it at 0)
        self._touched_since_profile = int(meta[1]) if meta.size > 1 else 0
