"""Clustered candidate-generation index: sublinear two-stage neighbor search.

Exact all-pairs neighbor search costs O(U²·D) — fine for the paper's 6040
MovieLens users, hopeless at the ROADMAP's millions.  :class:`ClusteredIndex`
makes candidate generation cheap while keeping the scoring stage exact:

1. **Project** — a seeded randomized-SVD basis maps each user's (optionally
   mean-centered) unit rating row to a ``project_dim``-dim *proxy* vector.
   The rating matrix is low-rank-plus-noise, so the proxy preserves the
   neighbor geometry at a fraction of the item dimension.
2. **Cluster** — blocked k-means (``repro.index.kmeans``) partitions the
   proxies; each user is *spill-assigned* to its ``spill`` nearest clusters
   so near-boundary neighbors are never lost to a hard partition.  This is
   the paper's thread partition extended from "split users across threads"
   to "split users across taste clusters".
3. **Probe** — a query shortlists its ``n_probe`` nearest clusters by
   centroid distance (the fused Pallas kernel on TPU).
4. **Shortlist** — the probed-cluster members of each query block are
   scored with one cheap proxy GEMM; the best ``rerank_frac · U`` per
   query go forward.  (The shortlist pool is the block's probed union —
   per-query probe restriction is exact in the unfiltered mode below.)
5. **Rerank** — only the shortlist is scored with the *true* similarity
   measure (the same Gram-term formulas the exact engines use), so returned
   neighbors carry exact similarity scores.

With ``n_probe == n_clusters`` and ``rerank_frac == 0`` (no shortlist cap)
every probed member is reranked through the same shared-candidate
``pairwise_similarity`` + canonical-sort path as the exact engines, and the
result is bit-identical to their top-k — the degenerate case the oracle
tests pin down.

Consistency under rating updates
--------------------------------
``refold`` mirrors the facade's touched-set repair design: proxies and
centroid mass are refolded for the touched rows only, and spill assignments
are repaired *exactly* against the moved centroids via a certificate — a
row provably keeps its cluster list when it owns no moved cluster and no
moved centroid beats its cached spill distances (canonical tie: lower
cluster id wins); every other row gets a full distance row.  After
``refold`` the spill lists equal what a cold reassignment against the
current centroids would produce (``check_consistent`` asserts it).
Centroid *positions* refold the touched mass exactly; mass moved by repair
reassignment is deliberately not cascaded (that would re-run k-means), so
positions drift from a cold refit the way any online k-means does — an
index-quality concern, never a correctness one, because reranking is exact
for whatever candidates the probes produce.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import neighbors as nb
from repro.core import predict as pred_mod
from repro.core import similarity as sim
from repro.index.kmeans import (KMeansStats, center_rows, kmeans,
                                normalize_rows)
from repro.kernels import select as sel_mod
from repro.kernels.cluster import centroid_distances
from repro.kernels.rerank import (BK, BN, fused_rerank_scores,
                                  rerank_scores_host, rerank_scores_xla)

try:                # optional host fast path for the proxy scan: torch's
                    # CPU mm/topk are multithreaded and topk selects k
                    # directly instead of materialising a full argsort
                    # permutation (numpy's argpartition writes U int64s
                    # per row — ~0.5 GB per query block at U=32768)
    import torch as _torch
except ImportError:  # pragma: no cover - container ships torch
    _torch = None

try:                # survivor grouping in the symmetric scan: scipy's
                    # COO→CSR is the O(n) counting sort (np.lexsort
                    # fallback below when absent)
    import scipy.sparse as _scipy_sparse
except ImportError:  # pragma: no cover - container ships scipy
    _scipy_sparse = None

RERANK_MODES = ("auto", "gather", "grouped")
SCAN_MODES = ("auto", "pool", "cluster", "kernel")
QUERY_MODES = ("auto", "staged", "fused")

# symmetric-pair scan: each unordered query-block pair's P·Pᵀ GEMM runs
# once and is consumed for both sides while cache-resident (half the
# proxy-GEMM FLOPs, no O(U²) score buffer).  The per-row thresholds are
# oversampled so the expected survivor count is _SYM_OVERSAMPLE·M; the
# survivor arrays are ~that many (id, val, row) entries per query, and
# the path gates on this byte budget.
_SYM_OVERSAMPLE = 1.5
_SYM_MAX_BYTES = 8 << 30
# symmetric scan pays off where the threshold filter is selective: at
# rerank budgets past this fraction of the pool the survivor mass stops
# filtering (≥ ~10% of every score block survives) and the plain
# streaming top-M measures faster, so *auto* prefers it there — the
# resolved reason lands in QueryStats.scan_gate.  A forced
# cfg.scan_symmetric=True is never silently ignored: it runs the leveled
# scan below (or raises when the config cannot run it at all).
_SYM_FRAC_MAX = 0.06
# fat-budget degrade levels: the threshold oversample steps down until
# the projected survivor mass fits _SYM_MAX_BYTES (the selected level is
# recorded in QueryStats.scan_gate); whatever the level, the per-block
# survivor compaction in _scan_symmetric bounds peak memory by folding
# accumulated survivors into running per-row top-M panels once they
# exceed _SYM_COMPACT_FACTOR times the expected mass
_SYM_LEVELS = (1.5, 1.25, 1.1)
_SYM_COMPACT_FACTOR = 2
_SYM_COMPACT_MIN = 256         # per-row floor: never fold tiny panels

# gather-mode rerank: queries per device call (block) — large blocks
# amortise per-call dispatch/sort overhead; the byte budget bounds the
# (b, M, nnz) gather intermediate for wide-support buckets
_RERANK_BMAX = 1024
_RERANK_BUDGET = 512 << 20
# support-split threshold: queries rating more than this many items score
# their pairs through the pair-major min-side pass (see _rerank_gather) —
# each pair then walks min(nnz_q, nnz_c) items instead of nnz_q
_REHOME_NNZ = 128
_PAIR_BLOCK = 32768            # pair-major pass: pairs per device call


def _bucket(n: int, cap: int = 1 << 30) -> int:
    """Next power of two ≥ n (≥ 8), capped — bounds distinct compile shapes."""
    b = 8
    while b < n:
        b *= 2
    return min(b, cap)


@dataclasses.dataclass(frozen=True)
class IndexConfig:
    """Tuning knobs for :class:`ClusteredIndex`.

    Auto values: ``n_clusters = 0`` → ``⌈√U⌉``; ``n_probe = 0`` → half the
    clusters (the probe stage is the cheap stage — it bounds which rows the
    proxy pass may scan; recall is then set by ``rerank_frac``).
    ``project_dim`` is clamped to the item count; ``0`` disables the
    projection (proxies = feature rows).  ``rerank_frac = 0`` disables the
    proxy shortlist: every probed member is exactly reranked (the bit-exact
    degenerate mode).
    """
    n_clusters: int = 0
    n_probe: int = 0
    seed: int = 0
    iters: int = 8
    features: str = "centered"            # "centered" (pcc geometry) |
                                          # "raw" (cosine/jaccard geometry)
    project_dim: int = 256
    spill: int = 2
    rerank_frac: float = 0.15
    kmeans_block: int = 2048
    query_block: int = 256
    use_kernel: Optional[bool] = None     # None → auto: fused kernel on TPU
    interpret: bool = False               # force kernel interpret mode
    # exact-rerank execution strategy:
    #   "gather"  — the CPU fast path: queries batched by rated-item
    #               support (CSR row lengths) into tight nnz buckets, the
    #               (M, nnz) int8 gather walk + fused stats, with host
    #               block prep pipelined against the async device call;
    #   "grouped" — the accelerator path: queries grouped by taste
    #               cluster, each group's candidate-union rows gathered
    #               once and scored by the fused Pallas co-rated Gram
    #               kernel (kernels/rerank.py; its OpenBLAS twin off-TPU);
    #   "auto"    — grouped on TPU, gather elsewhere (measured: at CPU
    #               memory bandwidth the candidate unions of a 3%-budget
    #               shortlist barely overlap, so the union gather loses
    #               to the bucketed walk — see BENCH_index.json).
    rerank_mode: str = "auto"
    rerank_batch: int = 256               # grouped-mode queries per union
    # shortlist-selection scan strategy (see README's scan-mode matrix):
    #   "pool"    — dense proxy scan over the whole candidate pool (host
    #               GEMM + canonical top-M; the symmetric-pair variant
    #               when the query set is the full population), with the
    #               block-union gather scan as the fallback when probing
    #               does not saturate the pool;
    #   "cluster" — cluster-restricted scan: each query block scores only
    #               its probed clusters' member proxies through padded
    #               per-cluster tables (no per-block set algebra over
    #               member lists, no full-pool score matrix);
    #   "kernel"  — accelerator path: one device proxy GEMM over the
    #               full pool and an on-device top-M — scores never
    #               round-trip to the host.  The top-M is the exact
    #               lax.top_k twin on every backend: Mosaic cannot lower
    #               the Pallas select kernel's in-kernel sort
    #               (kernels/select.py; QueryStats.select_mode);
    #   "auto"    — kernel where the fused kernels run (TPU), else by
    #               probe fraction: pool when n_probe·spill ≥ n_clusters
    #               (the probed union provably saturates), cluster below.
    # All modes implement the same canonical (-score, id) selection, so
    # shortlists are bit-identical wherever the candidate pools coincide.
    shortlist_scan_mode: str = "auto"
    # symmetric-pair scan override: None → auto (on for full-population
    # host pool scans at selective rerank budgets), False → always the
    # plain streaming scan, True → force it — fat budgets degrade through
    # the _SYM_LEVELS oversample ladder instead of being silently gated,
    # and a config that cannot run it at all (subset queries, a non-pool
    # scan mode, the fused query mode) raises instead of ignoring the
    # override.  The resolved gate lands in QueryStats.scan_gate.
    scan_symmetric: Optional[bool] = None
    # query-pipeline orchestration:
    #   "staged" — every stage returns to the host between device calls:
    #              shortlists come back as numpy tables and pass 2
    #              re-dispatches them through the gather walk / grouped
    #              rerank (the CPU-measured fast path, and the bit-exact
    #              oracle the fused path is pinned against);
    #   "fused"  — per query block the proxy scan, shortlist selection,
    #              candidate-union gather and exact co-rated Gram rerank
    #              chain through device-resident arrays: proxy scores and
    #              candidate id lists never round-trip to the host (the
    #              Pallas kernels where they run, their XLA twins
    #              elsewhere — the staged-dispatch twin that makes the
    #              same orchestration testable off-TPU).  Cluster probe
    #              ids and their member-table unions (pre-score data) may
    #              surface to the host; scores and shortlists do not;
    #   "auto"   — fused where the accelerator kernels run (TPU), staged
    #              elsewhere (measured: at CPU memory bandwidth the
    #              bucketed gather walk beats the device union-Gram).
    query_mode: str = "auto"
    # auto-refit drift guard: when the cumulative fraction of rows whose
    # spill list changed since the last cold fit crosses this, refold
    # performs a fresh k-means fit (0 disables).  refold keeps assignments
    # exactly argmin-consistent, but centroid *positions* drift from a
    # cold refit under heavy update traffic (the no-cascade rule); this
    # bounds how far.
    refit_reassign_frac: float = 0.5


@dataclasses.dataclass
class QueryStats:
    """Work accounting for one ``query`` call."""
    n_queries: int
    n_users: int           # candidate population the fractions refer to
    n_probed: int          # probed-member rows summed over queries
    n_reranked: int        # rows exactly reranked (true similarity)
    seconds_shortlist: float = 0.0   # probe + proxy scan + selection, and
                                     # every other non-rerank cost of the
                                     # call (setup, assembly, the
                                     # symmetric scan's certificate
                                     # rescue rows): total − rerank
    seconds_rerank: float = 0.0      # exact rerank stage (including the
                                     # unfiltered blocks' shared-matmul
                                     # rerank, which is rerank work even
                                     # though it runs during pass 1)
    seconds_total: float = 0.0       # shortlist + rerank, by construction
                                     # (the two stages partition the wall
                                     # clock *exactly* on every scan and
                                     # query mode — rerank is measured,
                                     # shortlist absorbs the remainder;
                                     # pinned by the benchmark's
                                     # stage-sum check)
    rerank_mode: str = ""            # resolved mode ("gather" | "grouped"
                                     # | "fused")
    scan_mode: str = ""              # resolved shortlist scan mode
    query_mode: str = ""             # resolved orchestration
                                     # ("staged" | "fused")
    select_mode: str = ""            # shortlist top-M selection: "top_k"
                                     # (device lax.top_k twin: the kernel
                                     # scan and the fused chain) or
                                     # "host" (staged host scans); ""
                                     # when no scan stage exists
    scan_gate: str = ""              # resolved symmetric-scan gate:
                                     # "sym:on:level=…" when it ran,
                                     # "sym:off:<reason>" when another
                                     # scan ran instead ("" only when no
                                     # scan stage exists at all)

    def _frac(self, total: int) -> float:
        pairs = self.n_queries * max(self.n_users - 1, 1)
        return total / max(pairs, 1)

    @property
    def probed_fraction(self) -> float:
        """Proxy-scanned candidates per query over all possible pairs."""
        return self._frac(self.n_probed)

    @property
    def rerank_fraction(self) -> float:
        """Exactly-reranked rows per query over all possible pairs."""
        return self._frac(self.n_reranked)


@dataclasses.dataclass
class RefoldStats:
    """What one ``refold`` call did (sizes drive the sublinear claim)."""
    n_touched: int
    n_changed_clusters: int
    n_reassigned: int      # rows whose spill list actually changed
    n_full_rows: int       # rows needing a full distance row
    n_certified: int       # rows kept/merged by the cheap certificate
    reassigned_frac: float = 0.0   # cumulative reassigned/rows since fit
    caches_patched: int = 0        # derived per-ratings caches refreshed
                                   # in place by the delta (vs rebuilt
                                   # from scratch on next use)
    refit: bool = False            # this call crossed the drift threshold
                                   # and performed a cold refit


@functools.partial(jax.jit, static_argnames=("features", "spherical"))
def _featurize(ratings, means, *, features, spherical=True):
    """The index's feature map: (centered|raw), unit rows."""
    z = center_rows(ratings, means) if features == "centered" else ratings
    return normalize_rows(z) if spherical else z


@jax.jit
def _project(z, basis):
    """Unit proxy vectors: project then re-normalize (angles, not lengths)."""
    return normalize_rows(z @ basis)


def _svd_basis(z: np.ndarray, dim: int, seed: int) -> np.ndarray:
    """Seeded randomized range-finder SVD basis, (D, dim), deterministic.

    Two matmul passes + a small QR/SVD on the host — O(U·D·dim), a rounding
    error next to one exact similarity pass.
    """
    rng = np.random.default_rng(seed)
    g = rng.normal(size=(z.shape[1], min(dim + 16, z.shape[1]))
                   ).astype(np.float32)
    q, _ = np.linalg.qr(z @ g)
    _, _, vt = np.linalg.svd(q.T @ z, full_matrices=False)
    return np.ascontiguousarray(vt[:dim].T)


@functools.partial(jax.jit, static_argnames=("spill", "block_size",
                                             "use_kernel", "interpret"))
def _spill_assign(proxies, centroids, *, spill, block_size, use_kernel,
                  interpret):
    """Canonical top-``spill`` clusters (ids + distances) per proxy row."""
    n = proxies.shape[0]
    pad = (-n) % block_size
    p = jnp.pad(proxies, ((0, pad), (0, 0)))
    blocks = p.reshape(-1, block_size, p.shape[1])

    def body(_, blk):
        d = centroid_distances(blk, centroids, use_kernel=use_kernel,
                               interpret=interpret)
        # reprolint: disable=canonical-selection -- negated-distance ties break toward the lowest cluster id: canonical by construction
        neg_d, ids = jax.lax.top_k(-d, spill)   # ties → lowest cluster id
        return (), (-neg_d, ids.astype(jnp.int32))

    _, (dist, ids) = jax.lax.scan(body, (), blocks)
    return ids.reshape(-1, spill)[:n], dist.reshape(-1, spill)[:n]


@functools.partial(jax.jit, static_argnames=("n_probe", "use_kernel",
                                             "interpret"))
def _probe_clusters(proxies, centroids, q_ids, *, n_probe, use_kernel,
                    interpret):
    """Nearest ``n_probe`` cluster ids for each (padded) query row."""
    zq = proxies[jnp.clip(q_ids, 0, proxies.shape[0] - 1)]
    d = centroid_distances(zq, centroids, use_kernel=use_kernel,
                           interpret=interpret)
    # reprolint: disable=canonical-selection -- probe-cluster ties break toward the lowest cluster id: canonical by construction
    _, probe = jax.lax.top_k(-d, n_probe)
    return probe


def _argpartition_rows(sp: np.ndarray, m: int) -> np.ndarray:
    """Row-wise top-m argpartition, split over two host threads (numpy's
    partition releases the GIL, and the selection is per-row independent).

    Partitions the *upper* side in place of negating the matrix first —
    at shortlist scale the score matrix is hundreds of MB, and the
    negation pass alone used to cost seconds at CPU memory bandwidth.
    Returns the selected column ids (tie order at the cut is whatever
    introselect leaves — callers needing the canonical tie set go through
    :func:`_topm_rows`).  ``m >= width`` selects every column; empty and
    single-row inputs skip the thread split.
    """
    n, w = sp.shape
    if m >= w:
        return np.broadcast_to(np.arange(w), (n, w)).copy()
    kth = w - m
    if n < 64:
        return np.argpartition(sp, kth, axis=1)[:, kth:]
    from concurrent.futures import ThreadPoolExecutor
    half = n // 2
    with ThreadPoolExecutor(max_workers=2) as pool:
        top = pool.submit(np.argpartition, sp[:half], kth, 1)
        bot = np.argpartition(sp[half:], kth, axis=1)
        return np.concatenate([top.result()[:, kth:], bot[:, kth:]], axis=0)


def _topm_rows(sp: np.ndarray, m: int,
               col_ids: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Canonical row-wise top-``m``: ``(values, column ids)``, selection
    set under the exact engines' ``(-score, id)`` order.

    The fast paths (torch ``topk``, the threaded numpy argpartition) pick
    an *arbitrary* subset of a tie group straddling the selection cut, so
    both are followed by a boundary repair: rows whose cut value also
    appears just below the cut are re-selected canonically — everything
    strictly above the cut stays, and the tie group contributes its
    lowest candidate ids (``col_ids`` maps columns to candidate ids when
    the column order is not already ascending-by-id, e.g. the
    cluster-restricted scan's cluster-major candidate layout).  This is
    what makes every shortlist scan mode (host torch/numpy, the Pallas
    select kernel, the lax.top_k twin) produce bit-identical shortlists.
    ``-inf`` (knockout) columns may be selected when a row has fewer than
    ``m`` finite scores; callers map them to their padding id.
    ``m >= width`` returns every column.  Output order within the
    selection is unspecified (callers sort the shortlists ascending
    downstream).
    """
    n, w = sp.shape
    if m >= w:
        ids = np.broadcast_to(np.arange(w), (n, w)).copy()
        return sp.copy(), ids
    if m == 0:
        return (np.empty((n, 0), np.float32), np.empty((n, 0), np.int64))
    if _torch is not None and n:
        sp_t = sp if isinstance(sp, _torch.Tensor) else _torch.from_numpy(sp)
        v1, i1 = _torch.topk(sp_t, m + 1, dim=1, sorted=True)
        v1, i1 = v1.numpy(), i1.numpy()
        selv, sel = v1[:, :m].copy(), i1[:, :m].astype(np.int64)
        cut, below = v1[:, m - 1], v1[:, m]
    else:
        sel1 = _argpartition_rows(sp, m + 1)                  # (n, m+1)
        v1 = np.take_along_axis(sp, sel1, 1)
        drop = v1.argmin(axis=1)                              # (m+1)-th best
        below = v1[np.arange(n), drop]
        keep = np.arange(m + 1)[None, :] != drop[:, None]
        sel = sel1[keep].reshape(n, m)
        selv = v1[keep].reshape(n, m)
        cut = selv.min(axis=1) if m else below
    # canonical boundary repair: only rows where the cut value is tied
    # across the selection boundary need the full-row pass (rare — exact
    # score ties, e.g. duplicate users or zero-overlap knockouts)
    need = np.nonzero((below == cut) & np.isfinite(cut))[0]
    for row in need:
        above = np.nonzero(sp[row] > cut[row])[0]
        tied = np.nonzero(sp[row] == cut[row])[0]
        if col_ids is not None:       # canonical order is by candidate id
            tied = tied[np.argsort(col_ids[tied], kind="stable")]
        tied = tied[:m - len(above)]
        sel[row, :len(above)] = above
        sel[row, len(above):len(above) + len(tied)] = tied
        selv[row] = sp[row, sel[row]]
    return selv, sel


def _patch_csr(csr, touched: np.ndarray, rows_new: np.ndarray):
    """Row-splice a host CSR for a rating delta: ``touched`` (sorted
    unique row ids) get fresh rows from the dense ``rows_new`` (T, I)
    slab; every untouched row's span is bulk-copied.  O(nnz) memcpy per
    delta instead of the full ``np.nonzero`` matrix scan a cold rebuild
    pays — the delta-aware replacement for wholesale identity
    invalidation."""
    indptr, indices, data = csr
    n_rows = len(indptr) - 1
    rr, cc = np.nonzero(rows_new)
    t_lens = np.bincount(rr, minlength=len(touched)).astype(np.int64)
    t_off = np.cumsum(t_lens) - t_lens
    t_vals = rows_new[rr, cc].astype(data.dtype)
    counts = np.diff(indptr)
    counts[touched] = t_lens
    indptr_new = np.zeros(n_rows + 1, np.int64)
    np.cumsum(counts, out=indptr_new[1:])
    idx_new = np.empty(indptr_new[-1], indices.dtype)
    data_new = np.empty(indptr_new[-1], data.dtype)
    prev = 0
    for t_pos, t in enumerate(touched):
        if t > prev:        # bulk-copy the untouched run [prev, t)
            idx_new[indptr_new[prev]:indptr_new[t]] = \
                indices[indptr[prev]:indptr[t]]
            data_new[indptr_new[prev]:indptr_new[t]] = \
                data[indptr[prev]:indptr[t]]
        lo, n = indptr_new[t], t_lens[t_pos]
        src = slice(t_off[t_pos], t_off[t_pos] + n)
        idx_new[lo:lo + n] = cc[src].astype(indices.dtype)
        data_new[lo:lo + n] = t_vals[src]
        prev = t + 1
    if prev < n_rows:
        idx_new[indptr_new[prev]:] = indices[indptr[prev]:]
        data_new[indptr_new[prev]:] = data[indptr[prev]:]
    return indptr_new, idx_new, data_new


def _sym_group(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
               nv: int, n: int):
    """COO survivor triplets → CSR groups per row with ascending candidate
    ids — an O(n) counting sort whose column order makes the padded table
    canonical for tie repair.  ``(rows, cols)`` pairs are unique by
    construction (each unordered pair's GEMM block runs once, and the
    symmetric scan's compaction only ever keeps subsets)."""
    if _scipy_sparse is not None:
        a = _scipy_sparse.coo_matrix((vals, (rows, cols)),
                                     shape=(nv, n)).tocsr()
        return a.indptr, a.indices, a.data
    order = np.lexsort((cols, rows))
    indptr = np.zeros(nv + 1, np.int64)
    np.cumsum(np.bincount(rows[order], minlength=nv), out=indptr[1:])
    return indptr, cols[order], vals[order]


def _sym_pad(indptr, grp_i, grp_v, nv: int, n: int):
    """CSR survivor groups → padded ``(nv, w)`` value/id tables
    (``-inf`` / sentinel-``n`` padding) ready for ``_topm_rows``."""
    cnt = np.diff(indptr)
    w = max(int(cnt.max()), 1)
    padv = np.full((nv, w), -np.inf, np.float32)
    padi = np.full((nv, w), n, np.int32)
    rr = np.repeat(np.arange(nv), cnt)
    within = np.arange(len(grp_v)) - np.repeat(
        indptr[:-1].astype(np.int64), cnt)
    padv[rr, within] = grp_v
    padi[rr, within] = grp_i
    return padv, padi


@jax.jit
def _user_norms_counts(ratings):
    """Per-user full-row L2 norms and rated-item counts (one cheap pass)."""
    return (jnp.sqrt(jnp.sum(ratings * ratings, axis=-1)),
            jnp.sum(ratings > 0, axis=-1).astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("k", "measure", "beta"))
def _rerank_sparse(r_gather, norms, counts, q_ids, q_items, q_vals,
                   cand_ids, *, k, measure, beta=sim.PCC_SIG_BETA):
    """Exact top-k over per-query candidate lists via the co-rated gather.

    The paper's insight, batched: every similarity term between a query and
    a candidate lives on the query's *rated* items, so instead of gathering
    full (M, D) candidate rows we gather the (M, nnz) sub-block
    ``ratings[cand, items_q]`` — O(M·nnz) traffic instead of O(M·D).
    ``r_gather`` is the rating matrix as the gather source
    (``predict.GatherSource``): an int8 code when every rating is a small
    multiple of a power-of-two step (MovieLens stars and half stars — the
    gather is element-count bound and int8 moves ~4× faster on CPU; the
    decode back to f32 is exact), f32 otherwise.

    ``q_items``/``q_vals``: (b, nnz) the query's rated item ids and values,
    zero-padded (a zero value knocks the slot out of every term, since each
    Gram term carries a query-side factor).  ``cand_ids``: (b, M) global
    ids, padding = n_users.  Scores follow the exact formulas of
    ``repro.core.similarity`` (reduction association differs by float
    rounding only); selection is the canonical (-score, id) sort.
    """
    n_users = r_gather.shape[0]
    safe_c = jnp.clip(cand_ids, 0, n_users - 1)
    rc = r_gather.rows((safe_c[:, :, None],
                        q_items[:, None, :]))                # (b, M, nnz)
    vq = q_vals                                              # (b, nnz)
    vq_pos = (vq > 0).astype(jnp.float32)
    mc = (rc > 0).astype(jnp.float32)
    pe = functools.partial(jnp.einsum,
                           precision=jax.lax.Precision.HIGHEST)
    eps = 1e-8
    if measure == "cosine":
        dot = pe("bmn,bn->bm", rc, vq)
        nq = jnp.sqrt(jnp.sum(vq * vq, -1))[:, None]
        s = dot / jnp.maximum(nq * norms[safe_c], eps)
    elif measure == "jaccard":
        n = pe("bmn,bn->bm", mc, vq_pos)
        union = jnp.sum(vq_pos, -1)[:, None] + counts[safe_c] - n
        s = n / jnp.maximum(union, eps)
    else:   # pcc / pcc_sig over co-rated items, normalised to [0, 1]
        n = pe("bmn,bn->bm", mc, vq_pos)
        dot = pe("bmn,bn->bm", rc, vq)
        sum_a = pe("bmn,bn->bm", mc, vq)
        sum_b = pe("bmn,bn->bm", rc, vq_pos)
        sq_a = pe("bmn,bn->bm", mc, vq * vq)
        sq_b = pe("bmn,bn->bm", rc * rc, vq_pos)
        cov = n * dot - sum_a * sum_b
        var_a = n * sq_a - sum_a * sum_a
        var_b = n * sq_b - sum_b * sum_b
        denom = jnp.sqrt(jnp.maximum(var_a, 0.0)
                         * jnp.maximum(var_b, 0.0))
        valid = (n >= 2) & (denom > eps)
        pcc = jnp.clip(cov / jnp.maximum(denom, eps), -1.0, 1.0)
        s = jnp.where(valid, (pcc + 1.0) * 0.5, 0.0)
        if measure == "pcc_sig":
            s = s * (jnp.minimum(n, beta) / beta)

    invalid = (cand_ids >= n_users) | (cand_ids == q_ids[:, None])
    s = jnp.where(invalid, nb.NEG_INF, s)
    ci = cand_ids
    if s.shape[1] < k:
        s = jnp.pad(s, ((0, 0), (0, k - s.shape[1])),
                    constant_values=nb.NEG_INF)
        ci = jnp.pad(ci, ((0, 0), (0, k - ci.shape[1])),
                     constant_values=n_users)
    neg_sorted, idx_sorted = jax.lax.sort((-s, ci), num_keys=2)
    top_s, top_i = -neg_sorted[:, :k], idx_sorted[:, :k]
    return top_s, jnp.where(top_s <= nb.NEG_INF, -1, top_i)


@functools.partial(jax.jit, static_argnames=("measure", "beta"))
def _pair_scores_sparse(r_gather, norms, counts, tbl_items, tbl_vals,
                        w_local, w_ids, v_ids, *, measure,
                        beta=sim.PCC_SIG_BETA):
    """Exact similarity of independent (walk, other) user pairs.

    The pair-major leg of the support-split rerank: each pair walks the
    *thinner* side's rated items.  ``tbl_items``/``tbl_vals``: the walk
    bucket's padded per-user item/value tables (rows indexed by
    ``w_local``); ``w_ids``/``v_ids``: global ids of the walk/other side.
    Same formulas as ``_rerank_sparse`` — the similarity statistics are
    symmetric in the pair, and for integer rating matrices every Gram sum
    is an exact f32 integer, so which side walks cannot change the score.
    Returns (P,) scores; caller discards padding slots.
    """
    n_users = r_gather.shape[0]
    it = tbl_items[w_local]                                  # (P, nnz)
    vq = tbl_vals[w_local]
    safe_v = jnp.clip(v_ids, 0, n_users - 1)
    rc = r_gather.rows((safe_v[:, None], it))                # (P, nnz)
    vq_pos = (vq > 0).astype(jnp.float32)
    mc = (rc > 0).astype(jnp.float32)
    eps = 1e-8
    if measure == "cosine":
        dot = jnp.sum(rc * vq, axis=-1)
        s = dot / jnp.maximum(norms[w_ids] * norms[safe_v], eps)
    elif measure == "jaccard":
        n = jnp.sum(mc * vq_pos, axis=-1)
        union = counts[w_ids] + counts[safe_v] - n
        s = n / jnp.maximum(union, eps)
    else:   # pcc / pcc_sig over co-rated items, normalised to [0, 1]
        n = jnp.sum(mc * vq_pos, axis=-1)
        dot = jnp.sum(rc * vq, axis=-1)
        sum_a = jnp.sum(mc * vq, axis=-1)
        sum_b = jnp.sum(rc * vq_pos, axis=-1)
        sq_a = jnp.sum(mc * vq * vq, axis=-1)
        sq_b = jnp.sum(rc * rc * vq_pos, axis=-1)
        cov = n * dot - sum_a * sum_b
        var_a = n * sq_a - sum_a * sum_a
        var_b = n * sq_b - sum_b * sum_b
        denom = jnp.sqrt(jnp.maximum(var_a, 0.0)
                         * jnp.maximum(var_b, 0.0))
        valid = (n >= 2) & (denom > eps)
        pcc = jnp.clip(cov / jnp.maximum(denom, eps), -1.0, 1.0)
        s = jnp.where(valid, (pcc + 1.0) * 0.5, 0.0)
        if measure == "pcc_sig":
            s = s * (jnp.minimum(n, beta) / beta)
    return s


@functools.partial(jax.jit, static_argnames=("k", "measure", "beta"))
def _rerank_shared(ratings, q_ids, cand_ids, allowed, *, k, measure,
                   beta=sim.PCC_SIG_BETA):
    """Exact top-k over a block-shared candidate set (the unfiltered path).

    Scores come from the same ``pairwise_similarity`` Gram pass the exact
    engines use; selection is the same canonical sort (descending score,
    lower id on ties) as ``merge_topk`` — which is what makes the
    ``n_probe == n_clusters`` case bit-identical to ``block_topk``.
    Padding/self/unprobed pairs get NEG_INF; NEG_INF slots surface as id -1,
    matching the exact engines' padding convention.
    """
    n_users = ratings.shape[0]
    q = ratings[jnp.clip(q_ids, 0, n_users - 1)]
    cand = ratings[jnp.clip(cand_ids, 0, n_users - 1)]
    s = sim.pairwise_similarity(q, cand, measure=measure, beta=beta)
    invalid = (~allowed) | (cand_ids[None, :] >= n_users) | \
              (cand_ids[None, :] == q_ids[:, None])
    s = jnp.where(invalid, nb.NEG_INF, s)
    ids = jnp.broadcast_to(cand_ids[None, :], s.shape)
    if s.shape[1] < k:
        s = jnp.pad(s, ((0, 0), (0, k - s.shape[1])),
                    constant_values=nb.NEG_INF)
        ids = jnp.pad(ids, ((0, 0), (0, k - ids.shape[1])),
                      constant_values=n_users)
    neg_sorted, idx_sorted = jax.lax.sort((-s, ids), num_keys=2)
    top_s, top_i = -neg_sorted[:, :k], idx_sorted[:, :k]
    return top_s, jnp.where(top_s <= nb.NEG_INF, -1, top_i)


# -- fused query pipeline (device-resident stage chain) -----------------------

@functools.partial(jax.jit, static_argnames=("m",))
def _fused_scan_pool(proxies, q_ids, *, m):
    """Device full-pool proxy scan of one query block.

    (Q,) padded global query ids → canonical top-``m`` ``(values,
    global shortlist ids)`` with the sentinel id ``U`` on every ``-inf``
    slot.  Selection is the exact ``lax.top_k`` twin on every backend
    (Mosaic cannot lower the Pallas select kernel's in-kernel sort),
    pinned bit-identical to ``ref.scan_topm_ref``.  The staged kernel
    scan dispatches this same
    function, so the fused path's shortlists are bit-identical to the
    staged ones.  Padded query rows (id ``U``) score garbage and are
    sliced off by the caller; proxy scores never leave the device.
    """
    n = proxies.shape[0]
    q = proxies[jnp.minimum(q_ids, n - 1)]
    return sel_mod.scan_topm_xla(q, proxies, q_ids, m=m)


@functools.partial(jax.jit, static_argnames=("m",))
def _fused_scan_restricted(proxies, cand_pad, q_ids, *, m):
    """Device cluster-restricted proxy scan of one query block.

    ``cand_pad``: (L,) *ascending* dup-free candidate ids out of the
    block's probed member-table union (padding ``U``) — ascending so the
    block-local tie-break of ``lax.top_k`` is the canonical global-id
    order.  Scores the block against the gathered candidate proxies, maps
    the block-local selection back to global ids on device, and returns
    ``(values, global shortlist ids)`` under the same sentinel contract
    as :func:`_fused_scan_pool`.
    """
    n = proxies.shape[0]
    L = cand_pad.shape[0]
    q = proxies[jnp.minimum(q_ids, n - 1)]
    cp = proxies[jnp.minimum(cand_pad, n - 1)]
    sp = jnp.matmul(q, cp.T, precision=jax.lax.Precision.HIGHEST)
    invalid = (cand_pad[None, :] >= n) | (cand_pad[None, :] == q_ids[:, None])
    sp = jnp.where(invalid, -jnp.inf, sp)
    # reprolint: disable=canonical-selection -- exact lax.top_k twin of kernels/select.py: XLA ties break toward the lower index, same canonical (-score, id) order
    v, sel = jax.lax.top_k(sp, m)
    # block-local → global, masking sentinels *before* the gather (the
    # select contract: -inf slots carry the local sentinel id L)
    shorts = jnp.where(jnp.isneginf(v), n,
                       cand_pad[jnp.minimum(sel, L - 1)])
    return v, shorts.astype(jnp.int32)


def _gram_scores(q_rows, r_gather, cand, norms, counts, *, measure, beta,
                 use_pallas, interpret):
    """(b, J) query rows against the candidate code rows ``cand`` of
    ``r_gather``: the fused co-rated Gram kernel, or its XLA twin."""
    score = fused_rerank_scores if use_pallas else rerank_scores_xla
    kw = dict(interpret=interpret) if use_pallas else {}
    return score(q_rows, cand, norms, counts, measure=measure, beta=beta,
                 cand_step=r_gather.factor, **kw)


def _restrict_topk(sc, q_ids, shorts, n, k):
    """Scores of each query's own shortlist ``sc`` (b, M) → canonical
    ``(-score, id)`` top-k; sentinel and self slots are masked and NEG_INF
    slots surface as id -1 like every exact path."""
    invalid = (shorts >= n) | (shorts == q_ids[:, None])
    sc = jnp.where(invalid, nb.NEG_INF, sc)
    ci = jnp.where(invalid, n, shorts)
    if sc.shape[1] < k:
        sc = jnp.pad(sc, ((0, 0), (0, k - sc.shape[1])),
                     constant_values=nb.NEG_INF)
        ci = jnp.pad(ci, ((0, 0), (0, k - ci.shape[1])),
                     constant_values=n)
    neg_sorted, idx_sorted = jax.lax.sort((-sc, ci), num_keys=2)
    top_s, top_i = -neg_sorted[:, :k], idx_sorted[:, :k]
    return top_s, jnp.where(top_s <= nb.NEG_INF, -1, top_i)


@functools.partial(jax.jit, static_argnames=(
    "ku", "k", "measure", "beta", "use_pallas", "interpret"))
def _fused_rerank_block(r_gather, ratings, norms, counts, q_ids, shorts, *,
                        ku, k, measure, beta, use_pallas, interpret):
    """Device union-Gram rerank of one query block's shortlists.

    ``shorts``: (b, M) global shortlist ids with sentinel ``U`` padding,
    straight from the device scan — never materialised on the host.  The
    block's candidate union comes out of a sized ``jnp.unique`` (``ku``
    bounds the distinct count, so nothing is silently truncated), the
    union rows are gathered once, and the whole (block, union) slab is
    scored by the fused co-rated Gram kernel (its XLA twin off-TPU).
    Scoring the union — a superset of each query's shortlist — changes
    nothing: the result is defined by the ``searchsorted`` restriction
    back to each query's own shortlist, and every Gram statistic is
    exact (bit-identical to the sparse gather walk for integer and
    half-star rating matrices).  Where the union can reach every row,
    :func:`_fused_rerank_whole` scores the matrix without the union.
    """
    n = r_gather.shape[0]
    u = jnp.unique(shorts, size=ku, fill_value=n)
    safe_u = jnp.minimum(u, n - 1)
    q_rows = ratings[jnp.minimum(q_ids, n - 1)]
    s = _gram_scores(q_rows, r_gather, r_gather.code[safe_u], norms[safe_u],
                     counts[safe_u], measure=measure, beta=beta,
                     use_pallas=use_pallas, interpret=interpret)
    # restriction: every real shortlist id is present in the union, so
    # searchsorted lands exactly on its column; sentinel slots are masked
    # (never gathered as row 0 — the clamp below is for the pad columns)
    col = jnp.clip(jnp.searchsorted(u, shorts), 0, ku - 1)
    return _restrict_topk(jnp.take_along_axis(s, col, axis=1), q_ids,
                          shorts, n, k)


@functools.partial(jax.jit, static_argnames=(
    "k", "measure", "beta", "use_pallas", "interpret"))
def _fused_rerank_whole(r_gather, norms, counts, q_ids, shorts, *, k,
                        measure, beta, use_pallas, interpret):
    """:func:`_fused_rerank_block` where the block's union can hold every
    row (``bq·M ≥ U``): the block is scored against all ``U`` rows of the
    gather code — no ``unique``, no row gather, no padding rows — and
    column ``j`` of the scores is user ``j``, so each shortlist id is its
    own column.  Bit-identical to the union path: the kernel computes each
    (query, candidate) statistic the same way whatever else is in the
    block.  The query rows are decoded from the code, so the f32 matrix is
    no operand.  ``r_gather``'s code may be padded past ``U`` rows and
    ``I`` columns (:func:`_whole_operand`); ``norms`` and ``counts`` give
    ``U``.
    """
    n = norms.shape[0]
    q_rows = r_gather.rows(jnp.minimum(q_ids, n - 1))
    s = _gram_scores(q_rows, r_gather, r_gather.code, norms, counts,
                     measure=measure, beta=beta, use_pallas=use_pallas,
                     interpret=interpret)
    sc = jnp.take_along_axis(s, jnp.minimum(shorts, n - 1), axis=1)
    return _restrict_topk(sc, q_ids, shorts, n, k)


@jax.jit
def _whole_operand(r_gather):
    """The gather code padded to the Gram kernel's whole tiles (rows of
    ``min(BN, U)``, columns of ``min(BK, I)``, as the kernel pads), so the
    kernel uses its candidate operand as it is: padded once per query
    call, not copied in every block."""
    code = r_gather.code
    n, j = code.shape
    pad = ((0, -n % min(BN, n)), (0, -j % min(BK, j)))
    return pred_mod.GatherSource(jnp.pad(code, pad), r_gather.step,
                                 r_gather.scale)


def _call_source(ratings, gather_src):
    """The caller's gather code of ``ratings``, or one derived for this
    call only (a standalone index call; nothing is cached)."""
    return (gather_src if gather_src is not None
            else pred_mod.make_gather_source(ratings))


class _SpillClusterCore:
    """The spill-cluster core of :class:`ClusteredIndex`.

    Owns the spill-cluster bookkeeping over generic *rows*: k-means fit +
    spill assignment, the exact certificate-based refold of assignments
    and the centroid-mass ledger, the auto-refit drift guard, the host CSR
    and pair-table caches, and checkpointable state.  The subclass
    provides the feature map (``_proxy_rows``) and the query semantics.
    The gather code is the caller's (``CFEngine``), passed into each
    query.
    """

    def __init__(self, cfg, mesh=None, mesh_axis: str = "data"):
        if cfg.features not in ("centered", "raw"):
            raise ValueError(f"unknown features {cfg.features!r}; "
                             "want 'centered' or 'raw'")
        if cfg.spill < 1:
            raise ValueError("spill must be ≥ 1")
        if getattr(cfg, "rerank_mode", "auto") not in RERANK_MODES:
            raise ValueError(f"unknown rerank_mode {cfg.rerank_mode!r}; "
                             f"want one of {RERANK_MODES}")
        if getattr(cfg, "shortlist_scan_mode", "auto") not in SCAN_MODES:
            raise ValueError(
                f"unknown shortlist_scan_mode {cfg.shortlist_scan_mode!r}; "
                f"want one of {SCAN_MODES}")
        if getattr(cfg, "query_mode", "auto") not in QUERY_MODES:
            raise ValueError(f"unknown query_mode {cfg.query_mode!r}; "
                             f"want one of {QUERY_MODES}")
        self.cfg = cfg
        self.mesh = mesh              # k-means fit shards over this mesh
        self.mesh_axis = mesh_axis
        self.n_rows = 0
        self.n_clusters = 0
        self.n_probe = 0
        self.basis: Optional[jnp.ndarray] = None       # (D, p) or None
        self.proxies: Optional[jnp.ndarray] = None     # (R, p) unit rows
        self.centroids: Optional[jnp.ndarray] = None   # (C, p)
        self.spill_ids: Optional[np.ndarray] = None    # (R, spill) int32
        self.spill_dist: Optional[np.ndarray] = None   # (R, spill) float32
        self._sums: Optional[np.ndarray] = None        # (C, p) cluster mass
        self._counts: Optional[np.ndarray] = None      # (C,)
        self._members: List[np.ndarray] = []           # per-cluster row ids
        self.kmeans_stats: Optional[KMeansStats] = None
        self.last_refold: Optional[RefoldStats] = None
        self._reassigned_since_fit = 0
        self._csr_cache: Optional[tuple] = None        # per-ratings CSR
        self._proxies_np_cache: Optional[tuple] = None # per-proxies host copy
        self._short_buf = None                         # torch GEMM output
        # ratings version chain for delta-aware cache maintenance: caches
        # above are keyed by array identity; ``refold`` advances the chain
        # and *patches* caches keyed to the previous array in place of the
        # wholesale invalidation an identity miss implies (see
        # ``_patch_row_caches``)
        self._ratings_key = None          # the array the caches track
        self._ratings_version = 0         # bumped by every refold
        self._member_table_cache = None   # padded per-cluster scan tables
        # chaos hooks: a FaultInjector armed here fires mid-refold (after
        # ledger mass is removed, before it is re-added) — the torn-index
        # case the checkpoint-restore drill recovers from
        self.fault_injector = None
        self._refold_seq = 0

    def _ratings_csr(self, ratings):
        """Host CSR view of the rating matrix (indptr, indices, data) —
        the rerank's query-side item lists come straight from these arrays
        instead of a per-block argsort over dense rows.  Cached per
        ratings array (updates replace the array → identity invalidation).
        """
        if self._csr_cache is not None and self._csr_cache[0] is ratings:
            return self._csr_cache[1]
        rnp = np.asarray(ratings)
        rows, cols = np.nonzero(rnp)
        counts = np.bincount(rows, minlength=rnp.shape[0])
        indptr = np.zeros(rnp.shape[0] + 1, np.int64)
        np.cumsum(counts, out=indptr[1:])
        csr = (indptr, cols.astype(np.int32),
               rnp[rows, cols].astype(np.float32))
        self._csr_cache = (ratings, csr)
        return csr

    @staticmethod
    def _rerank_bucket(nnz: int, n_items: int) -> int:
        """Rated-item support bucket: multiples of 64 to 256, of 128 to
        512, then powers of two — tight enough that a (M, nnz) gather
        pads ~15% instead of ~45%, coarse enough to bound compiled
        shapes."""
        if nnz <= 256:
            b = 64 * -(-nnz // 64)
        elif nnz <= 512:
            b = 128 * -(-nnz // 128)
        else:
            b = _bucket(nnz)
        return min(b, n_items)

    @staticmethod
    def _bucket_table(indptr, indices, data, rows, b):
        """One padded (len(rows), b) item/value table sliced out of the
        CSR arrays (vectorized variable-length row copy)."""
        items = np.zeros((len(rows), b), np.int32)
        vals = np.zeros((len(rows), b), np.float32)
        lens = (indptr[rows + 1] - indptr[rows]).astype(np.int64)
        total = int(lens.sum())
        if total:
            dst_row = np.repeat(np.arange(len(rows)), lens)
            off = np.cumsum(lens) - lens
            dst_col = np.arange(total) - np.repeat(off, lens)
            src = np.arange(total) + np.repeat(indptr[rows] - off, lens)
            items[dst_row, dst_col] = indices[src]
            vals[dst_row, dst_col] = data[src]
        return jnp.asarray(items), jnp.asarray(vals)

    def _item_tables(self, ratings):
        """Device-resident padded per-user item/value tables, bucketed by
        rated-item support — the walk-side operands of the pair-major
        rerank (rows gather sequentially on device, no host copies).
        Returns ``(bucket_of (U,), local_of (U,), {bucket: (items, vals)})``
        with items/vals jnp (U_b, bucket).  Cached per ratings array."""
        if self._csr_cache is not None and len(self._csr_cache) > 2 and \
                self._csr_cache[0] is ratings:
            return self._csr_cache[2]
        indptr, indices, data = self._ratings_csr(ratings)
        n_users = len(indptr) - 1
        n_items = ratings.shape[1]
        nnz = (indptr[1:] - indptr[:-1]).astype(np.int64)
        bucket_of = np.array([self._rerank_bucket(max(int(v), 1), n_items)
                              for v in nnz], np.int32)
        local_of = np.empty(n_users, np.int32)
        tables = {}
        for b in np.unique(bucket_of):
            rows = np.nonzero(bucket_of == b)[0]
            local_of[rows] = np.arange(len(rows))
            tables[int(b)] = self._bucket_table(indptr, indices, data,
                                                rows, int(b))
        out = (bucket_of, local_of, tables)
        self._csr_cache = (ratings, self._csr_cache[1], out)
        return out

    def _proxies_np(self) -> np.ndarray:
        """Host copy of the proxy table for the OpenBLAS shortlist scan
        (cached per proxies array — refolds replace the array)."""
        if self._proxies_np_cache is not None and \
                self._proxies_np_cache[0] is self.proxies:
            return self._proxies_np_cache[1]
        # np.array: jax hands back a read-only view; torch.from_numpy
        # wants a writable buffer
        p_np = np.array(np.asarray(self.proxies), np.float32, order="C")
        self._proxies_np_cache = (self.proxies, p_np)
        return p_np

    # -- delta-aware cache maintenance -------------------------------------
    def _patch_row_caches(self, ratings, touched: np.ndarray,
                          version: Optional[int]) -> int:
        """Advance the ratings version chain and delta-patch the derived
        per-ratings caches (CSR, pair tables) for a row delta, in place of
        the wholesale rebuild an identity miss forces.

        ``touched``: sorted unique changed user ids.  ``version``: the
        caller's ratings version counter; when provided it must be exactly
        one past the version this core last saw, else the chain is broken
        (an unknown number of deltas passed by) and every cache is
        dropped.  Returns
        the number of caches patched.
        """
        old = self._ratings_key
        chain_ok = (old is not None and ratings is not old
                    and (version is None
                         or version == self._ratings_version + 1))
        self._ratings_key = ratings
        self._ratings_version = (version if version is not None
                                 else self._ratings_version + 1)
        if not chain_ok:
            if ratings is not old:
                self._csr_cache = None
            return 0
        patched = 0
        if self._csr_cache is not None and self._csr_cache[0] is old:
            rows_new = np.asarray(ratings[jnp.asarray(touched)])
            csr = _patch_csr(self._csr_cache[1], touched, rows_new)
            entry = (ratings, csr)
            patched += 1
            if len(self._csr_cache) > 2:
                entry = entry + (self._patch_item_tables(
                    self._csr_cache[2], csr, touched, ratings.shape[1]),)
                patched += 1
            self._csr_cache = entry
        else:
            self._csr_cache = None
        return patched

    def _patch_item_tables(self, old_tables, csr, touched: np.ndarray,
                           n_items: int):
        """Refresh the bucketed pair tables for a row delta: only buckets
        holding a touched row (before or after its support moved) are
        rebuilt from the patched CSR; every other bucket's device tables
        are reused untouched."""
        bucket_of, local_of, tables = old_tables
        indptr, indices, data = csr
        nnz_t = (indptr[touched + 1] - indptr[touched]).astype(np.int64)
        new_b = np.array([self._rerank_bucket(max(int(v), 1), n_items)
                          for v in nnz_t], np.int32)
        affected = np.unique(np.concatenate([bucket_of[touched], new_b]))
        bucket_of = bucket_of.copy()
        bucket_of[touched] = new_b
        local_of = local_of.copy()
        tables = dict(tables)
        for b in affected:
            rows = np.nonzero(bucket_of == b)[0]
            if not len(rows):
                tables.pop(int(b), None)
                continue
            local_of[rows] = np.arange(len(rows))
            tables[int(b)] = self._bucket_table(indptr, indices, data,
                                                rows, int(b))
        return bucket_of, local_of, tables

    # -- resolution --------------------------------------------------------
    @property
    def fitted(self) -> bool:
        return self.centroids is not None

    @property
    def assign(self) -> np.ndarray:
        """Primary (nearest-centroid) cluster per row."""
        return self.spill_ids[:, 0]

    def _use_kernel(self) -> bool:
        if self.cfg.use_kernel is None:
            return jax.default_backend() == "tpu"
        return bool(self.cfg.use_kernel)

    def _distances(self, x, c):
        return centroid_distances(x, c, use_kernel=self._use_kernel(),
                                  interpret=self.cfg.interpret)

    def _proxy_rows(self, ratings, means):
        raise NotImplementedError

    # -- shared fit tail ---------------------------------------------------
    def _resolve_sizes(self) -> None:
        """``n_clusters``/``n_probe`` auto values against ``n_rows``."""
        c = self.cfg.n_clusters or int(np.ceil(np.sqrt(self.n_rows)))
        self.n_clusters = max(1, min(c, self.n_rows))
        # half the clusters, rounded *up*: with the default spill of 2
        # this keeps n_probe·spill ≥ C at odd C too, so the auto config
        # rides the provable pool-saturation shortcut instead of falling
        # just short of it (C//2 at C=91 probed 45 — one shy)
        self.n_probe = self.cfg.n_probe or max(1, -(-self.n_clusters // 2))
        self.n_probe = min(self.n_probe, self.n_clusters)

    def _fit_clusters(self) -> None:
        """k-means over ``self.proxies`` + spill assignment + mass ledger;
        resets the auto-refit drift counter."""
        spill = min(self.cfg.spill, self.n_clusters)
        self.centroids, _, _, self.kmeans_stats = kmeans(
            self.proxies, self.n_clusters, seed=self.cfg.seed,
            iters=self.cfg.iters, block_size=self.cfg.kmeans_block,
            use_kernel=self._use_kernel(), interpret=self.cfg.interpret,
            mesh=self.mesh, axis=self.mesh_axis)
        ids, dist = _spill_assign(
            self.proxies, self.centroids, spill=spill,
            block_size=min(self.cfg.kmeans_block, self.n_rows),
            use_kernel=self._use_kernel(), interpret=self.cfg.interpret)
        self.spill_ids = np.array(ids)
        self.spill_dist = np.array(dist)
        self._fold_mass()
        self._rebuild_members()
        self._reassigned_since_fit = 0

    def _fold_mass(self) -> None:
        p_np = np.asarray(self.proxies)
        self._sums = np.zeros((self.n_clusters, p_np.shape[1]), np.float32)
        np.add.at(self._sums, self.assign, p_np)
        self._counts = np.bincount(self.assign,
                                   minlength=self.n_clusters).astype(np.int64)

    def _rebuild_members(self) -> None:
        """Per-cluster member lists from the spill assignment (ascending)."""
        flat = self.spill_ids.reshape(-1)
        rows = np.repeat(np.arange(self.n_rows, dtype=np.int32),
                         self.spill_ids.shape[1])
        order = np.lexsort((rows, flat))
        flat, rows = flat[order], rows[order]
        splits = np.searchsorted(flat, np.arange(1, self.n_clusters))
        self._members = list(np.split(rows, splits))
        self._member_table_cache = None      # padded scan tables are stale

    # -- incremental maintenance (shared core) -----------------------------
    def _refold_rows(self, touched: np.ndarray, p_new_j: jnp.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray, int]:
        """Fold refreshed proxy rows into the ledger and repair spill
        assignments exactly (see the module docstring).  ``touched``:
        sorted unique row ids; ``p_new_j``: their fresh proxy rows.
        Returns ``(changed_clusters, full_rows, n_reassigned)``.

        The mass ledger invariant — every row's proxy mass sits at its
        *current primary cluster* — is what keeps repeated refolds exact:
        removal always subtracts the very value that was added (the stored
        proxy row), never a recomputation of it.
        """
        spill = self.spill_ids.shape[1]

        # 1. refold proxies and centroid mass for the touched rows: remove
        #    the *stored* proxy at the ledger location (current primary),
        #    add the fresh proxy at the nearest current centroid; the
        #    repair below establishes the final canonical spill lists and
        #    step 4 re-homes any mass whose primary moved
        p_old = np.asarray(self.proxies[jnp.asarray(touched)])
        p_new = np.asarray(p_new_j)
        if self._proxies_np_cache is not None and \
                self._proxies_np_cache[0] is self.proxies:
            # delta-patch the host proxy copy alongside the device update
            # (the array identity changes below, which would otherwise
            # force a full device→host round-trip on the next scan).
            # Copy-on-write like every published operand: a concurrent
            # reader mid-scan keeps the pre-delta table
            p_host = self._proxies_np_cache[1].copy()
            p_host[touched] = p_new
        else:
            p_host = None
        self.proxies = self.proxies.at[jnp.asarray(touched)].set(p_new_j)
        if p_host is not None:
            self._proxies_np_cache = (self.proxies, p_host)
        a_old = self.assign[touched].copy()
        np.add.at(self._sums, a_old, -p_old)
        np.add.at(self._counts, a_old, -1)
        self._refold_seq += 1
        if self.fault_injector is not None:
            # chaos hook: fire with the ledger genuinely torn — touched
            # rows' mass removed but not yet re-added, so check_consistent
            # fails until the caller restores a committed checkpoint
            self.fault_injector.check(self._refold_seq)
        d_new = np.asarray(self._distances(p_new_j, self.centroids))
        a_prov = d_new.argmin(axis=1).astype(np.int32)
        np.add.at(self._sums, a_prov, p_new)
        np.add.at(self._counts, a_prov, 1)

        # 2. recompute the moved centroids (empty → keep position: nothing
        #    is assigned there, so it merely stops attracting probes)
        changed = np.unique(np.concatenate([a_old, a_prov]))
        cent = np.array(self.centroids)
        upd = changed[self._counts[changed] > 0]
        cent[upd] = self._sums[upd] / self._counts[upd, None]
        self.centroids = jnp.asarray(cent)

        # 3. exact spill repair against the moved centroids.  full rows:
        #    touched rows (their proxy moved) and rows owning a moved
        #    cluster (their cached spill distances are stale)
        old_ids = self.spill_ids.copy()
        need_full = np.isin(self.spill_ids, changed).any(axis=1)
        need_full[touched] = True

        # cheap certificate for the rest: merge the moved centroids'
        # fresh distances into the still-valid cached spill list; clusters
        # outside (spill ∪ changed) kept their distances and already lost
        # to the cached spill-th entry, so the merge is exact
        cb = _bucket(len(changed))
        cent_ch = cent[np.pad(changed, (0, cb - len(changed)),
                              constant_values=changed[0])]
        d_ch = np.asarray(self._distances(self.proxies,
                                          jnp.asarray(cent_ch))
                          )[:, :len(changed)]
        merge_d = np.concatenate([self.spill_dist, d_ch], axis=1)
        merge_i = np.concatenate(
            [self.spill_ids,
             np.broadcast_to(changed[None, :],
                             (self.n_rows, len(changed)))], axis=1)
        order = np.lexsort((merge_i, merge_d), axis=1)[:, :spill]
        keep = ~need_full
        rows = np.nonzero(keep)[0]
        self.spill_ids[rows] = np.take_along_axis(
            merge_i, order, axis=1)[rows]
        self.spill_dist[rows] = np.take_along_axis(
            merge_d, order, axis=1)[rows]

        full_rows = np.nonzero(need_full)[0].astype(np.int32)
        if len(full_rows):
            fb = _bucket(len(full_rows))
            rows_pad = np.pad(full_rows, (0, fb - len(full_rows)),
                              constant_values=full_rows[0])
            ids, dist = _spill_assign(
                self.proxies[jnp.asarray(rows_pad)], self.centroids,
                spill=spill, block_size=fb,
                use_kernel=self._use_kernel(),
                interpret=self.cfg.interpret)
            self.spill_ids[full_rows] = np.asarray(ids)[:len(full_rows)]
            self.spill_dist[full_rows] = np.asarray(dist)[:len(full_rows)]

        # 4. re-home the mass ledger: any row whose primary cluster moved
        #    (touched rows relative to their provisional fold, repaired
        #    rows relative to their old primary) carries its stored proxy
        #    to the new primary.  The receiving clusters' centroids are
        #    deliberately not recomputed this round (the no-cascade rule);
        #    they will be recomputed from this exact ledger the next time
        #    a refold touches them.
        ledger = old_ids[:, 0].copy()
        ledger[touched] = a_prov
        new_prim = self.spill_ids[:, 0]
        moved = np.nonzero(ledger != new_prim)[0]
        if len(moved):
            pm = np.asarray(self.proxies[jnp.asarray(moved)])
            np.add.at(self._sums, ledger[moved], -pm)
            np.add.at(self._counts, ledger[moved], -1)
            np.add.at(self._sums, new_prim[moved], pm)
            np.add.at(self._counts, new_prim[moved], 1)

        reassigned = int((self.spill_ids != old_ids).any(axis=1).sum())
        if reassigned:
            self._rebuild_members()
        self._reassigned_since_fit += reassigned
        return changed, full_rows, reassigned

    def _maybe_refit(self, ratings, means, stats: RefoldStats) -> None:
        """The drift guard: cold-refit when cumulative reassignment since
        the last fit crosses ``cfg.refit_reassign_frac`` (0 disables)."""
        stats.reassigned_frac = self._reassigned_since_fit / max(
            self.n_rows, 1)
        thr = self.cfg.refit_reassign_frac
        if thr and stats.reassigned_frac >= thr:
            self.fit(ratings, means)
            stats.refit = True

    # -- diagnostics (shared core) -----------------------------------------
    def _check_spill_state(self, p_cold: np.ndarray) -> List[str]:
        """Refold invariants common to both axes: proxies, mass ledger,
        and spill assignments all equal a cold recomputation."""
        errs = []
        if not np.array_equal(p_cold, np.asarray(self.proxies)):
            errs.append("proxies")
        cold_counts = np.bincount(self.assign, minlength=self.n_clusters)
        if not np.array_equal(cold_counts, self._counts):
            errs.append("mass counts")
        cold_sums = np.zeros_like(self._sums)
        np.add.at(cold_sums, self.assign, p_cold)
        # the ledger is maintained by exact-value add/remove pairs; only
        # the rounding of the running sums themselves can drift
        if not np.allclose(cold_sums, self._sums, atol=1e-3):
            errs.append("mass sums")
        ids, dist = _spill_assign(
            jnp.asarray(p_cold), self.centroids,
            spill=self.spill_ids.shape[1],
            block_size=min(self.cfg.kmeans_block, self.n_rows),
            use_kernel=self._use_kernel(), interpret=self.cfg.interpret)
        if not np.array_equal(np.asarray(ids), self.spill_ids):
            errs.append("spill assignments")
        if not np.array_equal(np.asarray(dist), self.spill_dist):
            errs.append("spill distances")
        return errs

    def member_counts(self) -> np.ndarray:
        return np.array([len(m) for m in self._members])

    # -- persistence -------------------------------------------------------
    _STATE_KEYS = ("basis", "centroids", "counts", "meta", "proxies",
                   "spill_dist", "spill_ids", "sums")

    def state(self) -> dict:
        """Checkpointable state: a flat dict of arrays, shaped for
        ``repro.distributed.checkpoint.save``.  ``basis=None`` is encoded
        as an empty array so the tree structure is fixed."""
        if not self.fitted:
            raise RuntimeError("call fit() first")
        return {
            "basis": (np.zeros((0, 0), np.float32) if self.basis is None
                      else np.asarray(self.basis)),
            "centroids": np.asarray(self.centroids),
            "counts": np.asarray(self._counts),
            "meta": np.asarray([self.n_rows, self.n_clusters, self.n_probe,
                                self._reassigned_since_fit], np.int64),
            "proxies": np.asarray(self.proxies),
            "spill_dist": self.spill_dist,
            "spill_ids": self.spill_ids,
            "sums": self._sums,
        }

    @classmethod
    def state_template(cls) -> dict:
        """Structure-only tree for ``checkpoint.restore(..., like=...)``
        (leaf values are ignored by restore; shapes come from the
        checkpoint shards)."""
        return {k: 0 for k in cls._STATE_KEYS}

    def load_state(self, tree: dict) -> "_SpillClusterCore":
        """Restore ``state()`` output (e.g. from ``checkpoint.restore``);
        the k-means fit is skipped entirely.  Writable copies are taken —
        restore hands back read-only buffer views."""
        meta = np.asarray(tree["meta"]).reshape(-1)
        self.n_rows = int(meta[0])
        self.n_clusters = int(meta[1])
        self.n_probe = int(meta[2])
        self._reassigned_since_fit = int(meta[3])
        basis = np.asarray(tree["basis"], np.float32)
        self.basis = jnp.asarray(basis) if basis.size else None
        self.proxies = jnp.asarray(np.asarray(tree["proxies"], np.float32))
        self.centroids = jnp.asarray(
            np.asarray(tree["centroids"], np.float32))
        self.spill_ids = np.array(tree["spill_ids"], np.int32)
        self.spill_dist = np.array(tree["spill_dist"], np.float32)
        self._sums = np.array(tree["sums"], np.float32)
        self._counts = np.array(tree["counts"], np.int64)
        self.kmeans_stats = None
        self._rebuild_members()
        return self


class ClusteredIndex(_SpillClusterCore):
    """User-clustering ANN index with exact rerank (see module docstring).

    The index never owns the rating matrix — the caller (typically
    :class:`repro.core.facade.CFEngine`) passes ``ratings``/``means`` into
    every call, so one index serves whatever snapshot the caller holds.
    """

    def __init__(self, cfg: IndexConfig = IndexConfig(), mesh=None,
                 mesh_axis: str = "data"):
        super().__init__(cfg, mesh=mesh, mesh_axis=mesh_axis)
        self.last_query: Optional[QueryStats] = None
        # per-index runtime override of the frozen cfg.query_mode: the
        # serving degradation ladder steps fused→staged under pressure
        # (and back) without rebuilding the index around a new config;
        # None defers to cfg resolution
        self.query_mode_override: Optional[str] = None

    @property
    def n_users(self) -> int:
        return self.n_rows

    def _featurize(self, ratings, means):
        return _featurize(ratings, means, features=self.cfg.features)

    def _proxy_rows(self, ratings, means):
        z = self._featurize(ratings, means)
        return _project(z, self.basis) if self.basis is not None else z

    def _max_rerank(self, k: int) -> int:
        if not self.cfg.rerank_frac:
            return 0
        return max(k, int(np.ceil(self.cfg.rerank_frac * self.n_users)))

    # -- fit ---------------------------------------------------------------
    def fit(self, ratings: jnp.ndarray,
            means: Optional[jnp.ndarray] = None) -> "ClusteredIndex":
        """Project, cluster, and spill-assign the users of ``ratings``."""
        ratings = jnp.asarray(ratings, jnp.float32)
        self._ratings_key = ratings          # (re)anchor the version chain
        self.n_rows, n_items = ratings.shape
        if means is None:
            means = sim.user_stats(ratings)[2]
        self._resolve_sizes()

        with obs.span("index.fit", device_sync=True, n_users=self.n_rows,
                      n_items=n_items, n_clusters=self.n_clusters) as sp:
            z = self._featurize(ratings, means)
            p = min(self.cfg.project_dim, n_items)
            if self.cfg.project_dim and p < n_items:
                with obs.span("fit.svd_basis", dim=p):
                    self.basis = jnp.asarray(
                        _svd_basis(np.asarray(z), p, self.cfg.seed))
            else:
                self.basis = None
            self.proxies = (_project(z, self.basis)
                            if self.basis is not None else z)
            self._fit_clusters()
            sp.track(self.proxies)
        obs.histogram("index.fit.seconds").observe(sp.duration)
        return self

    # auto rerank-mode split point: at rerank budgets ≥ ~8% of the pool
    # the grouped candidate unions saturate and the union-GEMM beats the
    # gather walk even on CPU (measured in BENCH_index.json: 2.3× at
    # U=8192/15%); at thin budgets (2-3%) the unions barely overlap and
    # the bucketed gather walk wins at CPU memory bandwidth
    _GROUPED_FRAC = 0.08

    def _rerank_mode(self, max_rerank: int = 0) -> str:
        """Resolve ``cfg.rerank_mode``: grouped where the fused kernel
        runs (TPU) and at dense rerank budgets on CPU, the bucketed
        gather walk elsewhere (see IndexConfig)."""
        if self.cfg.rerank_mode != "auto":
            return self.cfg.rerank_mode
        if self._use_kernel():
            return "grouped"
        return ("grouped" if max_rerank >= self._GROUPED_FRAC * self.n_rows
                else "gather")

    def _query_mode(self) -> str:
        """Resolve ``cfg.query_mode`` (see IndexConfig): the fused
        device-resident stage chain where the accelerator kernels run,
        the staged host pipeline elsewhere.  The fused chain is correct
        everywhere (its stages fall back to jitted XLA twins off-TPU),
        but the staged host BLAS + bucketed gather walk is faster at CPU
        memory bandwidth — only the device backend flips the default.

        ``query_mode_override`` (set by the serving degradation ladder)
        wins over everything: a degraded server must be able to force the
        cheaper staged pipeline per transition, not per rebuild."""
        override = self.query_mode_override
        if override is not None:
            if override not in ("fused", "staged"):
                raise ValueError(
                    f"query_mode_override must be 'fused' or 'staged', "
                    f"got {override!r}")
            return override
        if self.cfg.query_mode != "auto":
            return self.cfg.query_mode
        return "fused" if self._use_kernel() else "staged"

    # -- shortlist scan ----------------------------------------------------
    def _scan_mode(self, n_probe: int) -> str:
        """Resolve ``cfg.shortlist_scan_mode`` (see IndexConfig): the
        fused select kernel where the accelerator kernels run, else by
        probe fraction — the dense pool scan when probing saturates the
        candidate pool (``n_probe·spill ≥ C``: every user's spill list
        intersects the probes), the cluster-restricted scan below."""
        mode = self.cfg.shortlist_scan_mode
        if mode != "auto":
            return mode
        if self._use_kernel():
            return "kernel"
        # the cluster-restricted scan touches ~(n_probe/C)·spill·U table
        # slots per query block where the pool scan touches U, so it only
        # wins at genuinely thin probe fractions — at or near saturation
        # it would do up to spill× the pool's work
        if 2 * n_probe * self.spill_ids.shape[1] <= self.n_clusters:
            return "cluster"
        return "pool"

    def _member_table(self) -> np.ndarray:
        """Padded per-cluster member-id table, (C, Lmax) int32 with
        ``n_rows`` padding — the cluster-restricted scan's candidate
        source (rebuilt lazily after any spill reassignment)."""
        if self._member_table_cache is None:
            lmax = max(int(self.member_counts().max()), 1)
            tbl = np.full((self.n_clusters, lmax), self.n_rows, np.int32)
            for c, mem in enumerate(self._members):
                tbl[c, :len(mem)] = mem
            self._member_table_cache = tbl
        return self._member_table_cache

    def _proxy_gemm(self, q_c: np.ndarray, b_c: np.ndarray,
                    reuse_buf: bool = False):
        """Host proxy-score GEMM ``q_c @ b_cᵀ`` — torch ``mm`` when
        available (multithreaded), numpy otherwise."""
        if _torch is None:
            return q_c @ b_c.T
        nv = len(q_c)
        if reuse_buf:
            if self._short_buf is None or \
                    self._short_buf.shape[1] != len(b_c) or \
                    self._short_buf.shape[0] < nv:
                self._short_buf = _torch.empty(nv, len(b_c),
                                               dtype=_torch.float32)
            out = self._short_buf[:nv]
        else:
            out = _torch.empty(nv, len(b_c), dtype=_torch.float32)
        _torch.mm(_torch.from_numpy(np.ascontiguousarray(q_c)),
                  _torch.from_numpy(b_c).T, out=out)
        return out.numpy()          # shared-memory view

    def _scan_dense_block(self, p_np: np.ndarray, ids: np.ndarray,
                          cand: Optional[np.ndarray],
                          max_rerank: int) -> np.ndarray:
        """Dense proxy scan of one query block: one host GEMM against the
        full pool (``cand is None`` — the pool shortcut) or a gathered
        candidate union (the legacy fallback when probing does not
        saturate), then the canonical top-M (``_topm_rows``: the torch
        ``topk`` / threaded-introselect fast path with the tie-boundary
        repair, so the selection set matches the exact engines'
        ``(-score, id)`` policy bit for bit)."""
        nv = len(ids)
        pool_all = cand is None
        q_c = np.ascontiguousarray(p_np[ids])
        b_c = p_np if pool_all else np.ascontiguousarray(p_np[cand])
        sp = self._proxy_gemm(q_c, b_c, reuse_buf=True)
        if pool_all:                # self-pair knockout
            sp[np.arange(nv), ids] = -np.inf
        else:
            at = np.searchsorted(cand, ids)
            hit = np.nonzero((at < len(cand))
                             & (cand[np.minimum(at, len(cand) - 1)]
                                == ids))[0]
            sp[hit, at[hit]] = -np.inf
        selv, sel = _topm_rows(sp, max_rerank)
        picked = sel if pool_all else cand[sel]
        return np.where(selv == -np.inf, self.n_users,
                        picked).astype(np.int32)

    def _cluster_candidates(self, clusters: np.ndarray) -> np.ndarray:
        """Dup-free member union of the probed ``clusters`` through the
        padded member table — no per-block set algebra over member
        lists.  Spill duplicates are knocked out by the canonical
        ownership rule (a member is contributed by the *first probed*
        cluster of its spill list), so the result equals the probed
        clusters' member union exactly, in member-table (cluster-major)
        order — callers needing ascending-id order sort it."""
        n = self.n_users
        tbl = self._member_table()[clusters]              # (ncl, Lmax)
        flat = tbl.reshape(-1)
        sp_l = self.spill_ids[np.minimum(flat, n - 1)]    # (F, spill)
        probed = np.zeros(self.n_clusters, bool)
        probed[clusters] = True
        first = sp_l[np.arange(len(flat)), probed[sp_l].argmax(axis=1)]
        own = np.repeat(clusters.astype(np.int32), tbl.shape[1])
        return flat[(flat < n) & (first == own)]

    def _scan_cluster_block(self, p_np: np.ndarray, ids: np.ndarray,
                            clusters: np.ndarray, max_rerank: int
                            ) -> Tuple[np.ndarray, int]:
        """Cluster-restricted scan of one query block: score only the
        probed clusters' member proxies through the padded member table —
        no per-block set algebra over member lists and no full-pool score
        matrix.  Spill duplicates are knocked out by the canonical
        ownership rule (a member scores from the *first probed* cluster
        of its spill list), so the candidate set equals the block's
        probed-cluster union exactly and the canonical top-M matches the
        dense scan's wherever the pools coincide.  Returns the (nv, M)
        shortlist and the scanned-slot count."""
        n = self.n_users
        cand = self._cluster_candidates(clusters)         # dup-free union
        sp = self._proxy_gemm(np.ascontiguousarray(p_np[ids]),
                              np.ascontiguousarray(p_np[cand]))
        inv = np.full(n, -1, np.int64)                    # self knockout
        inv[cand] = np.arange(len(cand))
        at = inv[ids]
        hit = np.nonzero(at >= 0)[0]
        sp[hit, at[hit]] = -np.inf
        selv, sel = _topm_rows(sp, min(max_rerank, len(cand)),
                               col_ids=cand)
        short = np.where(selv == -np.inf, n, cand[sel]).astype(np.int32)
        if short.shape[1] < max_rerank:
            short = np.pad(short,
                           ((0, 0), (0, max_rerank - short.shape[1])),
                           constant_values=n)
        return short, len(cand)

    def _scan_kernel_block(self, ids_pad: np.ndarray, nv: int,
                           max_rerank: int) -> np.ndarray:
        """Device shortlist scan of one query block: proxy GEMM plus the
        canonical ``lax.top_k`` selection — scores never
        round-trip to the host.  Dispatches the *same* jitted scan as
        the fused pipeline (``_fused_scan_pool``), so staged and fused
        shortlists are identical by construction — only this staged
        wrapper pulls them to the host."""
        m = min(max_rerank, self.n_users)
        v, i = _fused_scan_pool(
            self.proxies, jnp.asarray(ids_pad), m=m)
        v = np.asarray(v)[:nv]
        short = np.where(np.isneginf(v), self.n_users,
                         np.asarray(i)[:nv]).astype(np.int32)
        if short.shape[1] < max_rerank:
            short = np.pad(short,
                           ((0, 0), (0, max_rerank - short.shape[1])),
                           constant_values=self.n_users)
        return short

    def _sym_level(self, max_rerank: int) -> float:
        """Largest ``_SYM_LEVELS`` threshold oversample whose projected
        survivor mass fits ``_SYM_MAX_BYTES``; the survivor compaction
        inside ``_scan_symmetric`` bounds peak memory at any level, so
        the ladder floor is always runnable."""
        for os_ in _SYM_LEVELS:
            if os_ * max_rerank * self.n_users * 12 <= _SYM_MAX_BYTES:
                return os_
        return _SYM_LEVELS[-1]

    def _sym_eligibility(self, max_rerank: int, scan: str, pool_all: bool,
                         full_pop: bool, qmode: str) -> Tuple[bool, str]:
        """Resolve the symmetric-pair scan gate to ``(use, reason)``.

        The reason string lands in ``QueryStats.scan_gate``, so a caller
        always sees *which* scan ran and why — no silent fallbacks.  A
        forced ``cfg.scan_symmetric=True`` raises on the hard gates
        (the fused query mode keeps the scan on device, a subset query
        set has no full pair population, a non-saturated or non-pool
        scan has no symmetric GEMM to halve) instead of being ignored.
        Fat budgets are no longer a hard gate: auto still prefers the
        plain streaming scan there (the survivor filter stops being
        selective and measures slower), but a forced config degrades
        through the ``_SYM_LEVELS`` oversample ladder and runs.
        """
        forced = self.cfg.scan_symmetric is True
        if self.cfg.scan_symmetric is False:
            return False, "sym:off:config"

        def gate(reason: str, detail: str) -> Tuple[bool, str]:
            if forced:
                raise ValueError(
                    f"scan_symmetric=True cannot run: {detail}")
            return False, reason

        if qmode == "fused":
            return gate(
                "sym:off:fused",
                "query_mode='fused' keeps the scan on device; the "
                "symmetric-pair scan is the host pool path (set "
                "query_mode='staged' to use it)")
        if scan != "pool" or not pool_all:
            return gate(
                "sym:off:scan-mode",
                f"the resolved scan mode ({scan!r}, "
                f"pool_all={pool_all}) is not the saturated host pool "
                "scan the symmetric pair schedule halves")
        if not full_pop:
            return gate(
                "sym:off:subset-queries",
                "the pair buffer covers unordered pairs of the full "
                "population only; this query set is a subset")
        if not forced and max_rerank > _SYM_FRAC_MAX * self.n_users:
            return False, "sym:off:fat-budget"
        return True, f"sym:on:level={self._sym_level(max_rerank):.2f}"

    def _scan_symmetric(self, p_np: np.ndarray, max_rerank: int,
                        bq: int,
                        oversample: float = _SYM_OVERSAMPLE) -> np.ndarray:
        """Symmetric-pair full-population proxy scan with fused
        threshold selection.

        Proxy affinity is symmetric (``P·Pᵀ``), yet the plain pool scan
        computes every unordered pair twice — once per side.  Here each
        unordered query-block pair's GEMM runs once and the block is
        consumed for *both* sides while cache-resident, cutting
        proxy-GEMM FLOPs in half and replacing the full-width top-M
        passes with cheap vectorized threshold filters:

        1. **Thresholds** — each diagonal block doubles as a uniform
           population sample (user ids carry no taste order): a row's
           ``tau`` is its block-local rank-``ks`` score, with ``ks``
           oversampled so the expected full-row survivor count is
           ``~1.5·M``.
        2. **Survivor extraction** — every pair block contributes its
           entries ``> tau`` to both row sides via row-major compare +
           ``flatnonzero`` + gather (no transposes, no strided passes,
           no O(U²) score buffer).
        3. **Assembly + exact select** — per row block, one COO→CSR
           counting sort groups the survivors by row in ascending
           candidate-id order, and the canonical top-M (``_topm_rows``)
           runs over the narrow padded survivor table.

        Exactness certificate: a row with ≥ M survivors has its M-th
        best score strictly above ``tau``, so the canonical top-M over
        its survivors *is* the canonical top-M over the full row — bit
        against the plain scan's selection (ties at the cut included:
        they are all > tau).  Rows with < M *observed* survivors
        (sampling-noise tail, ~0.1 %) are recomputed exactly through
        the dense scan.  Returns the (U, M) shortlist table.

        Fat budgets: ``oversample`` is the threshold ladder level
        (``_sym_level``) — lower levels trade survivor mass for a
        slightly longer fallback tail.  Peak survivor memory is bounded
        at *any* level by panelized spilling: when a row block's pending
        entries exceed ``_SYM_COMPACT_FACTOR`` times its expected mass,
        they are folded down to the per-row canonical top-M.  The fold
        is exact — every entry it drops is canonically after ≥ M kept
        survivors of its row, so it can never re-enter the final top-M —
        and the ``seen`` tally (observed counts, accumulated before the
        fold) keeps the < M certificate honest.
        """
        n = self.n_users
        m = max_rerank
        bq = min(bq, n)
        nb = -(-n // bq)
        use_t = _torch is not None
        pt = _torch.from_numpy(p_np) if use_t else None
        scr_t = _torch.empty(bq, bq) if use_t else None
        scr = scr_t.numpy() if use_t else np.empty((bq, bq), np.float32)
        taus = np.empty(n, np.float32)
        tri: List[list] = [[] for _ in range(nb)]   # (rows, cols, vals)
        nvs = [min((b + 1) * bq, n) - b * bq for b in range(nb)]
        seen = np.zeros(n, np.int64)     # observed survivors per row
        pend = np.zeros(nb, np.int64)    # pending (uncompacted) entries
        cap = max(int(_SYM_COMPACT_FACTOR * oversample * m),
                  _SYM_COMPACT_MIN)

        def mm_block(i0, i1, j0, j1):
            if use_t:
                view = scr_t[:i1 - i0, :j1 - j0]
                _torch.mm(pt[i0:i1], pt[j0:j1].t(), out=view)
                return view.numpy()
            view = scr[:i1 - i0, :j1 - j0]
            np.matmul(p_np[i0:i1], p_np[j0:j1].T, out=view)
            return view

        def compact(dst):
            """Panelized survivor spilling: fold ``dst``'s pending
            triplets to the per-row canonical top-M (exact — see the
            docstring; ``seen`` already holds the observed tally)."""
            rows = np.concatenate([t[0] for t in tri[dst]])
            cols = np.concatenate([t[1] for t in tri[dst]])
            vals = np.concatenate([t[2] for t in tri[dst]])
            indptr, grp_i, grp_v = _sym_group(rows, cols, vals,
                                              nvs[dst], n)
            padv, padi = _sym_pad(indptr, grp_i, grp_v, nvs[dst], n)
            selv, sel = _topm_rows(padv, min(m, padv.shape[1]))
            picked = np.take_along_axis(padi, sel, axis=1)
            rr, cc = np.nonzero(~np.isneginf(selv))
            tri[dst] = [(rr.astype(np.int32), picked[rr, cc],
                         selv[rr, cc].astype(np.float32))]
            pend[dst] = len(rr)

        def collect(dst, s, mask, col0, transpose):
            """Append ``mask`` survivors of block ``s`` to row side
            ``dst`` (``transpose``: the entries' columns are the dst
            block's rows — pair block consumed for its second side)."""
            flat = np.flatnonzero(mask)
            if not len(flat):
                return
            vals = s.reshape(-1)[flat]
            r, c = np.divmod(flat, s.shape[1])
            if transpose:
                r, c = c, r
            tri[dst].append((r.astype(np.int32),
                             (col0 + c).astype(np.int32), vals))
            d0 = dst * bq
            seen[d0:d0 + nvs[dst]] += np.bincount(r, minlength=nvs[dst])
            pend[dst] += len(flat)
            if pend[dst] > cap * nvs[dst]:
                compact(dst)

        # phase 1 — diagonal blocks: thresholds + own survivors
        ks = max(1, int(oversample * m * bq / n))
        for bi in range(nb):
            i0, i1 = bi * bq, min((bi + 1) * bq, n)
            s = mm_block(i0, i1, i0, i1)
            ar = np.arange(i1 - i0)
            s[ar, ar] = -np.inf                      # self knockout
            kk = min(ks, s.shape[1] - 1)
            if kk < 1:
                # degenerate trailing block (width 1: the knockout ate
                # the only sample) — no threshold to take; +inf yields
                # zero survivors, routing the rows to the exact fallback
                taus[i0:i1] = np.inf
                continue
            if use_t:
                # reprolint: disable=canonical-selection -- threshold sampling only: the kk-th VALUE feeds the survivor cut, ids are never consumed, so tie order cannot leak
                v = _torch.topk(scr_t[:i1 - i0, :i1 - i0], kk, dim=1,
                                sorted=True)[0]
                taus[i0:i1] = v[:, -1].numpy()
            else:
                taus[i0:i1] = np.partition(
                    s, s.shape[1] - kk, axis=1)[:, s.shape[1] - kk]
            collect(bi, s, s > taus[i0:i1, None], i0, False)

        # phase 2 — off-diagonal pairs, both sides from one GEMM
        for bi in range(nb):
            i0, i1 = bi * bq, min((bi + 1) * bq, n)
            for bj in range(bi + 1, nb):
                j0, j1 = bj * bq, min((bj + 1) * bq, n)
                s = mm_block(i0, i1, j0, j1)
                collect(bi, s, s > taus[i0:i1, None], j0, False)
                collect(bj, s, s > taus[j0:j1][None, :], i0, True)

        # phase 3 — per-row-block survivor assembly + canonical top-M
        # (the certificate reads the *observed* tally: a compaction fold
        # may keep exactly M entries for a row that saw more)
        shorts = np.full((n, m), n, np.int32)
        fallback: list = []
        for bi in range(nb):
            i0, i1 = bi * bq, min((bi + 1) * bq, n)
            nv = i1 - i0
            fb = np.nonzero(seen[i0:i1] < m)[0]
            fallback.extend((i0 + fb).tolist())
            if not tri[bi]:
                continue
            rows = np.concatenate([t[0] for t in tri[bi]])
            cols = np.concatenate([t[1] for t in tri[bi]])
            vals = np.concatenate([t[2] for t in tri[bi]])
            indptr, grp_i, grp_v = _sym_group(rows, cols, vals, nv, n)
            padv, padi = _sym_pad(indptr, grp_i, grp_v, nv, n)
            selv, sel = _topm_rows(padv, min(m, padv.shape[1]))
            picked = np.take_along_axis(padi, sel, axis=1)
            shorts[i0:i1, :picked.shape[1]] = np.where(
                np.isneginf(selv), n, picked)
        if fallback:
            fb_ids = np.asarray(fallback, np.int32)
            shorts[fb_ids] = self._scan_dense_block(p_np, fb_ids, None, m)
        return shorts

    # -- query -------------------------------------------------------------
    def query(self, ratings: jnp.ndarray, means: jnp.ndarray,
              user_ids=None, *, k: int, measure: str = "pcc",
              n_probe: Optional[int] = None,
              beta: Optional[float] = None, gather_src=None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Top-k true-similarity neighbors through the two-stage pipeline.

        Returns ``(scores, neighbor_ids)`` of shape ``(len(user_ids), k)``;
        sets ``self.last_query`` with work accounting and per-stage wall
        times.  ``beta`` is the ``pcc_sig`` shrink horizon (None → module
        default).  ``gather_src``: the caller's gather code of
        ``ratings`` (``CFEngine`` passes its own); a standalone call
        without one derives it for this call only.  With
        ``n_probe == n_clusters`` and ``rerank_frac == 0`` the result is
        bit-identical to the exact engines.

        Pass 1 builds per-query shortlists through the resolved scan mode
        (``_scan_mode``); blocks whose candidate union already fits the
        rerank budget go straight through the shared-matmul exact path
        (also the bit-exact degenerate mode).  All scan modes share the
        canonical ``(-score, id)`` selection policy, so they agree bit
        for bit wherever their candidate pools coincide.  Under
        ``query_mode="fused"`` both passes run as one device-resident
        chain per block (``_query_fused``) — same candidate semantics,
        bit-identical results for integer rating matrices.

        Stage timers: the rerank stage is *measured* (every exact-scoring
        interval, whichever pass it runs in) and the shortlist stage
        absorbs the remainder of the wall clock, so
        ``seconds_shortlist + seconds_rerank == seconds_total`` exactly
        on every scan and query mode.
        """
        if not self.fitted:
            raise RuntimeError("call fit() first")
        beta = sim.resolve_beta(beta)
        uids = (np.arange(self.n_users, dtype=np.int32) if user_ids is None
                else np.atleast_1d(np.asarray(user_ids, np.int32)))
        n_probe = min(n_probe or self.n_probe, self.n_clusters)
        max_rerank = self._max_rerank(k)
        bq = min(self.cfg.query_block, _bucket(len(uids)))
        out_s = np.empty((len(uids), k), np.float32)
        out_i = np.empty((len(uids), k), np.int32)
        n_probed = 0
        n_reranked = 0
        t_rerank = 0.0
        # the query root span *is* the total-time clock: rerank-stage
        # child spans are measured, the shortlist stage absorbs the
        # remainder, so the QueryStats partition invariant
        # (shortlist + rerank == total, exactly) is derived from spans
        qspan = obs.span("index.query", n_queries=len(uids), k=k,
                         measure=measure)
        qspan.__enter__()
        try:
            scan = self._scan_mode(n_probe) if max_rerank else "pool"
            qmode = self._query_mode() if max_rerank else "staged"
            # pool shortcut: candidates = the whole population, no per-block
            # probing — always for the device scan (it never materialises
            # the score matrix; the fused chain's pool branch is the same
            # scan), on the host when probing saturates the pool
            # (n_probe·spill ≥ C: every user's spill list meets the probes)
            pool_all = (bool(max_rerank) and max_rerank < self.n_users
                        and (scan == "kernel"
                             or (qmode == "fused" and scan == "pool")
                             or (scan == "pool"
                                 and n_probe * self.spill_ids.shape[1]
                                 >= self.n_clusters)))
            full_pop = np.array_equal(uids, np.arange(self.n_users))
            sym_use, scan_gate = ((False, "") if not max_rerank else
                                  self._sym_eligibility(max_rerank, scan,
                                                        pool_all, full_pop,
                                                        qmode))
            # host proxy table only exists where a host scan runs; the
            # fused chain, the device scan, and the unfiltered/degenerate
            # mode never pay the copy
            p_np = (self._proxies_np()
                    if max_rerank and scan != "kernel" and qmode != "fused"
                    else None)
            if pool_all:
                # no per-block probe work here, so score in tall blocks —
                # the (bq, p)·(p, U) GEMM runs ~2.5× faster at bq=2048
                bq = min(2048, _bucket(len(uids)))
            mode = ("fused" if qmode == "fused" and max_rerank
                    else self._rerank_mode(max_rerank))
            qspan.set_attr("scan_mode", scan if max_rerank else "")
            qspan.set_attr("query_mode", qmode)
            qspan.set_attr("scan_gate", scan_gate)
            qspan.set_attr("rerank_mode", mode)
            select = ("" if not max_rerank else
                      "top_k" if scan == "kernel" or qmode == "fused"
                      else "host")
            qspan.set_attr("select_mode", select)

            if qmode == "fused" and max_rerank:
                n_probed, n_reranked, t_rerank = self._query_fused(
                    ratings, uids, out_s, out_i, k=k, measure=measure,
                    beta=beta, n_probe=n_probe, max_rerank=max_rerank,
                    pool_all=pool_all, bq=bq, r_gather=gather_src)
            else:
                n_probed, n_reranked, t_rerank = self._query_staged(
                    ratings, uids, out_s, out_i, k=k, measure=measure,
                    beta=beta, n_probe=n_probe, max_rerank=max_rerank,
                    scan=scan, pool_all=pool_all, bq=bq, p_np=p_np,
                    sym_use=sym_use, mode=mode, r_gather=gather_src)
            qspan.set_attr("n_probed", n_probed)
            qspan.set_attr("n_reranked", n_reranked)
        finally:
            qspan.__exit__(None, None, None)

        # rerank is measured (the sum of the rerank-stage child spans),
        # shortlist absorbs the remainder of the root span — so the two
        # stages partition seconds_total exactly by construction
        t_short = max(qspan.duration - t_rerank, 0.0)
        self.last_query = QueryStats(n_queries=len(uids),
                                     n_users=self.n_users,
                                     n_probed=n_probed,
                                     n_reranked=n_reranked,
                                     seconds_shortlist=t_short,
                                     seconds_rerank=t_rerank,
                                     seconds_total=t_short + t_rerank,
                                     rerank_mode=mode,
                                     scan_mode=scan if max_rerank else "",
                                     query_mode=qmode,
                                     select_mode=select,
                                     scan_gate=scan_gate)
        reg = obs.registry()
        reg.counter("index.query.count").inc()
        reg.counter("index.query.queries").inc(len(uids))
        reg.counter("index.query.probed_rows").inc(n_probed)
        reg.counter("index.query.reranked_rows").inc(n_reranked)
        reg.histogram("index.query.seconds").observe(t_short + t_rerank)
        reg.histogram("index.query.shortlist_seconds").observe(t_short)
        reg.histogram("index.query.rerank_seconds").observe(t_rerank)
        return jnp.asarray(out_s), jnp.asarray(out_i)

    def _query_staged(self, ratings, uids, out_s, out_i, *, k, measure,
                      beta, n_probe, max_rerank, scan, pool_all, bq,
                      p_np, sym_use, mode, r_gather=None):
        """The two-pass host-orchestrated pipeline (shortlists round-trip
        through host memory between the scan and the exact rerank) —
        also the bit-exact oracle the fused chain is pinned against.
        Returns ``(n_probed, n_reranked, seconds_rerank)``."""
        n_probed = 0
        n_reranked = 0
        t_rerank = 0.0
        mc = self.member_counts() if scan == "cluster" else None
        spill = self.spill_ids.shape[1]
        pend_pos: list = []        # output row ranges awaiting pass 2
        pend_short: list = []      # their (nv, max_rerank) shortlists

        # pass 1 — shortlist scan (see the class docstring's stage map)
        if sym_use:
            with obs.span("query.scan", scan="symmetric",
                          oversample=self._sym_level(max_rerank)):
                shorts_all = self._scan_symmetric(
                    p_np, max_rerank, bq,
                    oversample=self._sym_level(max_rerank))
            n_probed += len(uids) * self.n_users
            n_reranked += int((shorts_all < self.n_users).sum())
            pend_pos.append(np.arange(len(uids)))
            pend_short.append(shorts_all)
        else:
            for lo in range(0, len(uids), bq):
                ids = uids[lo:lo + bq]
                nv = len(ids)
                ids_pad = np.full((bq,), self.n_users, np.int32)
                ids_pad[:nv] = ids
                if pool_all:
                    with obs.span("query.scan", scan=scan, block=lo // bq,
                                  candidates=self.n_users):
                        short_np = (
                            self._scan_kernel_block(ids_pad, nv, max_rerank)
                            if scan == "kernel" else
                            self._scan_dense_block(p_np, ids, None,
                                                   max_rerank))
                    n_probed += nv * self.n_users
                    n_reranked += int((short_np < self.n_users).sum())
                    pend_pos.append(np.arange(lo, lo + nv))
                    pend_short.append(short_np)
                    continue
                ids_j = jnp.asarray(ids_pad)
                with obs.span("query.probe", block=lo // bq,
                              n_probe=n_probe):
                    probe = np.asarray(_probe_clusters(
                        self.proxies, self.centroids, ids_j,
                        n_probe=n_probe, use_kernel=self._use_kernel(),
                        interpret=self.cfg.interpret))
                clusters = np.unique(probe[:nv])
                if max_rerank and scan == "cluster" and \
                        int(mc[clusters].sum()) > max_rerank * spill:
                    # cluster-restricted scan (the slot count provably
                    # exceeds the budget even after spill dedup)
                    with obs.span("query.scan", scan="cluster",
                                  block=lo // bq) as scsp:
                        short_np, n_slots = self._scan_cluster_block(
                            p_np, ids, clusters, max_rerank)
                        scsp.set_attr("candidates", n_slots)
                    n_probed += nv * n_slots
                    n_reranked += int((short_np < self.n_users).sum())
                    pend_pos.append(np.arange(lo, lo + nv))
                    pend_short.append(short_np)
                    continue
                with obs.span("query.union", block=lo // bq):
                    cand = np.unique(np.concatenate(
                        [self._members[c] for c in clusters]))
                L = _bucket(len(cand))
                cand_pad = np.full((L,), self.n_users, np.int32)
                cand_pad[:len(cand)] = cand
                if max_rerank and max_rerank < len(cand):
                    # dense fallback: block-union gather scan
                    with obs.span("query.scan", scan="dense",
                                  block=lo // bq, candidates=len(cand)):
                        short_np = self._scan_dense_block(p_np, ids, cand,
                                                          max_rerank)
                    n_probed += nv * len(cand)
                    n_reranked += int((short_np < self.n_users).sum())
                    pend_pos.append(np.arange(lo, lo + nv))
                    pend_short.append(short_np)
                    continue
                # unfiltered path: exact per-query probe semantics — a
                # candidate counts iff one of its spill clusters was probed
                # by that query (the bit-exact degenerate mode lives here)
                allowed = np.zeros((bq, L), bool)
                probed_tbl = np.zeros((nv, self.n_clusters), bool)
                probed_tbl[np.arange(nv)[:, None], probe[:nv]] = True
                sp_c = self.spill_ids[cand]                  # (Lc, spill)
                allowed[:nv, :len(cand)] = probed_tbl[:, sp_c].any(-1)
                n_pairs = int((allowed[:nv]
                               & (cand_pad[None, :] != ids[:, None])).sum())
                n_probed += n_pairs
                n_reranked += n_pairs
                # candidate generation above is shortlist-stage work; the
                # shared-matmul exact scoring below is rerank work even
                # though it runs inside pass 1 (the stage timers must
                # partition the wall total — see QueryStats)
                with obs.span("query.rerank", kind="shared",
                              block=lo // bq, rows=n_pairs) as rsp:
                    s, i = _rerank_shared(ratings, ids_j,
                                          jnp.asarray(cand_pad),
                                          jnp.asarray(allowed), k=k,
                                          measure=measure, beta=beta)
                    out_s[lo:lo + bq] = np.asarray(s)[:nv]
                    out_i[lo:lo + bq] = np.asarray(i)[:nv]
                t_rerank += rsp.duration

        # pass 2 — exact rerank of the shortlists
        if pend_pos:
            with obs.span("query.rerank", kind=mode) as rsp:
                pos = np.concatenate(pend_pos)
                # ascending shortlists give the gather a monotone row walk
                # and make stable score sorts canonical (lower id wins ties)
                shorts = np.sort(np.concatenate(pend_short, axis=0), axis=1)
                rsp.set_attr("queries", len(pos))
                q_all = uids[pos]
                norms, counts = _user_norms_counts(ratings)
                if mode == "grouped":
                    self._rerank_grouped(ratings, norms, counts, q_all,
                                         shorts, pos, out_s, out_i, k=k,
                                         measure=measure, beta=beta,
                                         r_gather=r_gather)
                else:
                    self._rerank_gather(ratings, norms, counts, q_all,
                                        shorts, pos, out_s, out_i, k=k,
                                        measure=measure, beta=beta,
                                        max_rerank=max_rerank,
                                        r_gather=r_gather)
            t_rerank += rsp.duration
        return n_probed, n_reranked, t_rerank

    def _query_fused(self, ratings, uids, out_s, out_i, *, k, measure,
                     beta, n_probe, max_rerank, pool_all, bq, r_gather=None):
        """The fused query pipeline: per query block, proxy scan →
        canonical top-M shortlist → candidate-union gather → exact
        co-rated Gram rerank stream through device memory, with scores
        and shortlist id lists never returning to the host (the cluster
        branch's probe ids and member-table unions — pre-score data —
        are the only host round-trips).  Two jitted calls per block keep
        the stage timers separable; the ``shorts`` array handed between
        them stays a device array.

        The scan is the *same* jitted computation the staged kernel path
        dispatches, and every Gram statistic is an exactly-representable
        f32 integer for integer rating matrices — so the fused output is
        bit-identical to the staged gather-walk oracle (pinned across
        all four measures in ``tests/test_fused_query.py``).  Returns
        ``(n_probed, n_reranked, seconds_rerank)``."""
        n = self.n_users
        use_pallas = self._use_kernel() or self.cfg.interpret
        interpret = self.cfg.interpret
        m = min(max_rerank, n)
        r_gather = _call_source(ratings, r_gather)
        norms, counts = _user_norms_counts(ratings)
        # a block's candidate union can reach every row: score the whole
        # matrix (the union ``bq·m`` ids can hold is at least ``n``)
        whole = bq * m >= n
        r_whole = (_whole_operand(r_gather) if whole and use_pallas
                   else r_gather)
        union_rows = obs.registry().counter("index.rerank_union_rows")
        n_probed = 0
        n_reranked = 0
        t_rerank = 0.0

        for lo in range(0, len(uids), bq):
            ids = uids[lo:lo + bq]
            nv = len(ids)
            ids_pad = np.full((bq,), n, np.int32)
            ids_pad[:nv] = ids
            ids_j = jnp.asarray(ids_pad)
            if pool_all:
                with obs.span("query.scan", scan="pool", fused=True,
                              block=lo // bq, candidates=n):
                    _, shorts = _fused_scan_pool(self.proxies, ids_j, m=m)
                n_probed += nv * n
            else:
                with obs.span("query.probe", block=lo // bq,
                              n_probe=n_probe):
                    probe = np.asarray(_probe_clusters(
                        self.proxies, self.centroids, ids_j,
                        n_probe=n_probe, use_kernel=self._use_kernel(),
                        interpret=interpret))
                clusters = np.unique(probe[:nv])
                # ascending candidate ids make the restricted select's
                # block-local tie-break the canonical global-id order
                cand = np.sort(self._cluster_candidates(clusters))
                L = _bucket(len(cand))
                cand_pad = np.full((L,), n, np.int32)
                cand_pad[:len(cand)] = cand
                if max_rerank >= len(cand):
                    # unfiltered block: the candidate union already fits
                    # the budget — straight to the shared-matmul exact
                    # path (identical to the staged degenerate mode)
                    allowed = np.zeros((bq, L), bool)
                    probed_tbl = np.zeros((nv, self.n_clusters), bool)
                    probed_tbl[np.arange(nv)[:, None], probe[:nv]] = True
                    sp_c = self.spill_ids[cand]
                    allowed[:nv, :len(cand)] = probed_tbl[:, sp_c].any(-1)
                    n_pairs = int((allowed[:nv] & (cand_pad[None, :]
                                                   != ids[:, None])).sum())
                    n_probed += n_pairs
                    n_reranked += n_pairs
                    with obs.span("query.rerank", kind="shared",
                                  block=lo // bq, rows=n_pairs) as rsp:
                        s, i = _rerank_shared(ratings, ids_j,
                                              jnp.asarray(cand_pad),
                                              jnp.asarray(allowed), k=k,
                                              measure=measure, beta=beta)
                        out_s[lo:lo + bq] = np.asarray(s)[:nv]
                        out_i[lo:lo + bq] = np.asarray(i)[:nv]
                    t_rerank += rsp.duration
                    continue
                with obs.span("query.scan", scan="restricted", fused=True,
                              block=lo // bq, candidates=len(cand)):
                    _, shorts = _fused_scan_restricted(
                        self.proxies, jnp.asarray(cand_pad), ids_j, m=m)
                n_probed += nv * len(cand)
            # the count sync below also fences the scan, so its cost
            # lands in the shortlist stage (rerank timing starts after)
            n_reranked += int(jnp.sum(shorts[:nv] < n))
            # union gather (or the whole matrix) + Gram rerank run inside
            # one jitted call; the host copy of the outputs is the fence
            # that keeps the span honest about device time
            if whole:
                rsp = obs.span("query.rerank", kind="whole",
                               block=lo // bq, rows=n)
                call = functools.partial(_fused_rerank_whole, r_whole)
            else:
                ku = _bucket(min(bq * shorts.shape[1], n) + 1)
                rsp = obs.span("query.rerank", kind="fused",
                               block=lo // bq, ku=ku)
                call = functools.partial(_fused_rerank_block, r_gather,
                                         ratings, ku=ku)
                union_rows.inc(ku)
            with rsp:
                s, i = call(norms, counts, ids_j, shorts, k=k,
                            measure=measure, beta=beta,
                            use_pallas=use_pallas, interpret=interpret)
                out_s[lo:lo + bq] = np.asarray(s)[:nv]
                out_i[lo:lo + bq] = np.asarray(i)[:nv]
            t_rerank += rsp.duration
        return n_probed, n_reranked, t_rerank

    def _rerank_gather(self, ratings, norms, counts, q_all, shorts, pos,
                       out_s, out_i, *, k, measure, beta, max_rerank,
                       r_gather=None):
        """The CSR-batched gather walk (CPU fast path).

        Queries are ordered by rated-item support (their CSR row length)
        and batched into support buckets, so each block compiles one tight
        ``(b, M, nnz)`` executable; rated-item lists slice straight out of
        the cached CSR arrays (no dense-row argsort), and the next block's
        host prep overlaps the in-flight async device call.

        Queries rating more than ``_REHOME_NNZ`` items take the
        *support-split* path instead: their (query, candidate) pairs are
        re-homed to the pair-major pass, which walks each pair over the
        **thinner** side's rated items (``_pair_scores_sparse``) — the
        similarity statistics live on the co-rated set, so either side's
        support carries them, and min(nnz_q, nnz_c) is typically several
        times smaller than a wide query's nnz.  Scores are identical
        (bit-identical for integer ratings: every Gram sum is an exact
        integer either way), only the walk order changes.
        """
        # an update-path repair of a few rows must not walk the whole
        # matrix: below this pending-query count (with no CSR cached for
        # this ratings array) the item lists come from just the pending
        # rows, and the support-split stays off (its per-user item
        # tables are a full-matrix artifact)
        cached = self._csr_cache is not None and \
            self._csr_cache[0] is ratings
        if cached or len(q_all) > 256:
            indptr, indices, data = self._ratings_csr(ratings)
            nnz_user = (indptr[1:] - indptr[:-1]).astype(np.int64)
            nnz = nnz_user[q_all]
            row_key = q_all
            heavy = np.nonzero(nnz > _REHOME_NNZ)[0]
        else:
            q_rows = np.asarray(ratings[jnp.asarray(q_all)])
            rr, cc = np.nonzero(q_rows)
            nnz = np.bincount(rr, minlength=len(q_all)).astype(np.int64)
            indptr = np.zeros(len(q_all) + 1, np.int64)
            np.cumsum(nnz, out=indptr[1:])
            indices = cc.astype(np.int32)
            data = q_rows[rr, cc].astype(np.float32)
            row_key = np.arange(len(q_all))
            heavy = np.empty(0, np.int64)
        r_gather = _call_source(ratings, r_gather)
        n_items = ratings.shape[1]
        bmax = max(_RERANK_BMAX, self.cfg.query_block)

        if len(heavy):
            self._rerank_pairs(ratings, norms, counts, q_all, shorts, pos,
                               out_s, out_i, heavy, nnz_user, k=k,
                               measure=measure, beta=beta, r_gather=r_gather)
            light = np.nonzero(nnz <= _REHOME_NNZ)[0]
            order = light[np.argsort(nnz[light], kind="stable")]
        else:
            order = np.argsort(nnz, kind="stable")

        def prep(lo2):
            """Host-side block prep: padded item/value/shortlist arrays."""
            tail = order[lo2:lo2 + bmax]
            nnz_b = self._rerank_bucket(max(int(nnz[tail].max()), 1),
                                        n_items)
            b = int(max(8, 1 << int(np.log2(
                max(_RERANK_BUDGET // (max_rerank * nnz_b * 4), 8)))))
            b = min(b, bmax, _bucket(len(order)))
            sel = order[lo2:lo2 + b]
            nnz_b = self._rerank_bucket(max(int(nnz[sel].max()), 1),
                                        n_items)
            items = np.zeros((b, nnz_b), np.int32)
            vals = np.zeros((b, nnz_b), np.float32)
            starts = indptr[row_key[sel]]
            lens = nnz[sel]
            # vectorized variable-length row copy out of the CSR arrays
            total = int(lens.sum())
            if total:
                dst_row = np.repeat(np.arange(len(sel)), lens)
                dst_col = np.arange(total) - np.repeat(
                    np.cumsum(lens) - lens, lens)
                src = np.arange(total) + np.repeat(
                    starts - (np.cumsum(lens) - lens), lens)
                items[dst_row, dst_col] = indices[src]
                vals[dst_row, dst_col] = data[src]
            qi_pad = np.full((b,), self.n_users, np.int32)
            qi_pad[:len(sel)] = q_all[sel]
            sh_pad = np.full((b, max_rerank), self.n_users, np.int32)
            sh_pad[:len(sel)] = shorts[sel]
            return lo2 + b, sel, items, vals, qi_pad, sh_pad

        lo2 = 0
        pending = None          # (sel, async device result)
        while lo2 < len(order) or pending is not None:
            nxt = None
            if lo2 < len(order):
                lo2, sel, items, vals, qi_pad, sh_pad = prep(lo2)
                s, i = _rerank_sparse(
                    r_gather, norms, counts, jnp.asarray(qi_pad),
                    jnp.asarray(items), jnp.asarray(vals),
                    jnp.asarray(sh_pad), k=k, measure=measure, beta=beta)
                nxt = (sel, s, i)
            if pending is not None:
                sel_p, s_p, i_p = pending
                out_s[pos[sel_p]] = np.asarray(s_p)[:len(sel_p)]
                out_i[pos[sel_p]] = np.asarray(i_p)[:len(sel_p)]
            pending = nxt

    def _rerank_pairs(self, ratings, norms, counts, q_all, shorts, pos,
                      out_s, out_i, heavy, nnz_user, *, k, measure, beta,
                      r_gather):
        """Pair-major min-side scoring for wide-support queries.

        Flattens the heavy queries' (query, candidate) pairs, picks the
        thinner side of each as the walk side, groups pairs by the walk
        side's support bucket (so every block compiles one tight
        ``(P, nnz)`` executable over the padded item tables), scores them
        with ``_pair_scores_sparse``, scatters scores back to each query's
        shortlist slots, and selects the canonical top-k on the host.
        """
        bucket_of, local_of, tables = self._item_tables(ratings)
        nh, m = len(heavy), shorts.shape[1]
        sh_h = shorts[heavy]
        q_h = q_all[heavy]
        valid = (sh_h < self.n_users).ravel()
        rows_rep = np.repeat(np.arange(nh, dtype=np.int64), m)[valid]
        slot = np.tile(np.arange(m, dtype=np.int64), nh)[valid]
        pq = np.repeat(q_h.astype(np.int64), m)[valid]
        pc = sh_h.ravel().astype(np.int64)[valid]
        keep = pq != pc                       # self pairs stay NEG_INF
        rows_rep, slot, pq, pc = (rows_rep[keep], slot[keep], pq[keep],
                                  pc[keep])
        # similarity is symmetric: mutual pairs — (q, c) and (c, q) both
        # re-homed — are scored once and scattered to both slots
        pkey = np.minimum(pq, pc) * np.int64(self.n_users) \
            + np.maximum(pq, pc)
        ukey, inv = np.unique(pkey, return_inverse=True)
        first = np.full(len(ukey), -1, np.int64)
        first_src = np.arange(len(pkey))[::-1]
        first[inv[::-1]] = first_src            # first occurrence wins
        pq_u, pc_u = pq[first], pc[first]
        walk_c = nnz_user[pc_u] < nnz_user[pq_u]   # ties walk the query side
        w_ids = np.where(walk_c, pc_u, pq_u).astype(np.int32)
        v_ids = np.where(walk_c, pq_u, pc_u).astype(np.int32)
        pair_scores = np.empty(len(ukey), np.float32)

        scores_h = np.full((nh, m), np.float32(nb.NEG_INF), np.float32)
        w_bkt = bucket_of[w_ids]
        order_p = np.lexsort((w_ids, w_bkt))  # bucket-major, row-coherent
        bounds = np.searchsorted(w_bkt[order_p],
                                 np.unique(w_bkt).astype(np.int64))
        bounds = np.append(bounds, len(order_p))
        pending = None
        chunks = []
        for gi in range(len(bounds) - 1):
            for lo in range(bounds[gi], bounds[gi + 1], _PAIR_BLOCK):
                chunks.append((lo, min(lo + _PAIR_BLOCK, bounds[gi + 1])))
        ci = 0
        while ci < len(chunks) or pending is not None:
            nxt = None
            if ci < len(chunks):
                lo, hi = chunks[ci]
                ci += 1
                sel = order_p[lo:hi]
                bkt = int(w_bkt[sel[0]])
                pb = _bucket(len(sel), _PAIR_BLOCK)
                wl = np.zeros((pb,), np.int32)
                wi = np.zeros((pb,), np.int32)
                vi = np.zeros((pb,), np.int32)
                wl[:len(sel)] = local_of[w_ids[sel]]
                wi[:len(sel)] = w_ids[sel]
                vi[:len(sel)] = v_ids[sel]
                it, vl = tables[bkt]
                s = _pair_scores_sparse(
                    r_gather, norms, counts, it, vl, jnp.asarray(wl),
                    jnp.asarray(wi), jnp.asarray(vi), measure=measure,
                    beta=beta)
                nxt = (sel, s)
            if pending is not None:
                sel_p, s_p = pending
                pair_scores[sel_p] = np.asarray(s_p)[:len(sel_p)]
            pending = nxt
        scores_h[rows_rep, slot] = pair_scores[inv]

        # canonical host selection: stable sort on descending score over
        # the ascending shortlist reproduces the exact (-score, id) order
        # reprolint: disable=canonical-selection -- stable argsort over ascending-id columns IS the canonical (-score, id) order
        o = np.argsort(-scores_h, axis=1, kind="stable")[:, :k]
        top_s = np.take_along_axis(scores_h, o, axis=1)
        top_i = np.take_along_axis(sh_h, o, axis=1).astype(np.int32)
        if top_s.shape[1] < k:
            padw = k - top_s.shape[1]
            top_s = np.pad(top_s, ((0, 0), (0, padw)),
                           constant_values=np.float32(nb.NEG_INF))
            top_i = np.pad(top_i, ((0, 0), (0, padw)),
                           constant_values=self.n_users)
        top_i = np.where(top_s <= np.float32(nb.NEG_INF), -1, top_i)
        out_s[pos[heavy]] = top_s
        out_i[pos[heavy]] = top_i

    def _rerank_grouped(self, ratings, norms, counts, q_all, shorts, pos,
                        out_s, out_i, *, k, measure, beta, r_gather=None):
        """The grouped union-Gram rerank (accelerator path).

        Queries are grouped by taste cluster, each group's candidate-union
        rows are gathered once, and the whole (group, union) score block
        comes out of one fused pass — the Pallas kernel on TPU, its
        OpenBLAS twin elsewhere.  Results are identical to the gather walk
        (bit-identical for integer rating matrices).
        """
        use_kernel = self._use_kernel() or self.cfg.interpret
        groups = np.argsort(self.assign[q_all], kind="stable")
        rnp = None if use_kernel else np.asarray(ratings)
        norms_np = np.asarray(norms)
        counts_np = np.asarray(counts)
        r_gather = _call_source(ratings, r_gather)
        neg = np.float32(nb.NEG_INF)
        for glo in range(0, len(groups), self.cfg.rerank_batch):
            gs = groups[glo:glo + self.cfg.rerank_batch]
            q = q_all[gs]
            sh = shorts[gs]                                   # (g, M)
            cu = np.unique(sh)
            cu = cu[cu < self.n_users]
            if not len(cu):
                out_s[pos[gs]] = neg
                out_i[pos[gs]] = -1
                continue
            if use_kernel:
                # pad the group and union to buckets so repeated groups
                # reuse a handful of compiled kernels; padded union rows
                # duplicate cu[0] (never referenced by the column map)
                gb = min(self.cfg.rerank_batch, _bucket(len(groups)))
                kb = _bucket(len(cu))
                q_pad = np.pad(q, (0, gb - len(q)), constant_values=q[0])
                cu_j = jnp.asarray(np.pad(cu, (0, kb - len(cu)),
                                          constant_values=cu[0]))
                s = np.asarray(fused_rerank_scores(
                    ratings[jnp.asarray(q_pad)], r_gather.code[cu_j],
                    norms[cu_j], counts[cu_j], measure=measure,
                    beta=beta, cand_step=r_gather.factor,
                    interpret=self.cfg.interpret))[:len(gs), :len(cu)]
            else:
                s = rerank_scores_host(
                    rnp[q], np.take(rnp, cu, axis=0),
                    norms_np[cu], counts_np[cu],
                    measure=measure, beta=beta)
            # per-query selection: map shortlists to union columns (an
            # appended NEG_INF column absorbs padding ids), knock out
            # self pairs, and take the canonical top-k — a stable sort on
            # descending score over the ascending shortlist reproduces
            # the (-score, id) tie-break of the exact engines
            s_ext = np.concatenate(
                [s, np.full((len(gs), 1), neg, np.float32)], axis=1)
            colmap = np.full(self.n_users + 1, len(cu), np.int32)
            colmap[cu] = np.arange(len(cu))
            sc = np.take_along_axis(s_ext, colmap[sh], axis=1)  # (g, M)
            sc[sh == q[:, None]] = neg
            # reprolint: disable=canonical-selection -- stable argsort over ascending-id shortlist columns IS the canonical (-score, id) order
            o = np.argsort(-sc, axis=1, kind="stable")[:, :k]
            top_s = np.take_along_axis(sc, o, axis=1)
            top_i = np.take_along_axis(sh, o, axis=1).astype(np.int32)
            if top_s.shape[1] < k:
                padw = k - top_s.shape[1]
                top_s = np.pad(top_s, ((0, 0), (0, padw)),
                               constant_values=neg)
                top_i = np.pad(top_i, ((0, 0), (0, padw)),
                               constant_values=self.n_users)
            top_i = np.where(top_s <= neg, -1, top_i)
            out_s[pos[gs]] = top_s
            out_i[pos[gs]] = top_i

    # -- incremental maintenance ------------------------------------------
    def refold(self, ratings: jnp.ndarray, means: jnp.ndarray,
               touched: np.ndarray, *,
               version: Optional[int] = None) -> RefoldStats:
        """Fold a rating delta into the index (see module docstring).

        ``touched``: sorted unique user ids whose rows changed;
        ``ratings``/``means`` are the post-update arrays.  Assignment
        repair is exact (``_refold_rows``); when cumulative reassignment
        crosses ``cfg.refit_reassign_frac`` a cold refit re-anchors the
        drifted centroid positions.  ``version`` is the caller's ratings
        version counter (``CFEngine`` passes its own): the derived
        per-ratings caches are delta-patched along an unbroken version
        chain instead of being rebuilt wholesale on the next query.
        """
        if not self.fitted:
            raise RuntimeError("call fit() first")
        touched = np.atleast_1d(np.asarray(touched, np.int32))
        if touched.size == 0:
            self.last_refold = RefoldStats(0, 0, 0, 0, self.n_users)
            return self.last_refold
        with obs.span("index.refold", n_touched=int(touched.size)) as sp:
            patched = self._patch_row_caches(ratings, np.unique(touched),
                                             version)
            p_new_j = self._proxy_rows(ratings[jnp.asarray(touched)],
                                       means[jnp.asarray(touched)])
            changed, full_rows, reassigned = self._refold_rows(touched,
                                                               p_new_j)
            stats = RefoldStats(
                n_touched=int(touched.size),
                n_changed_clusters=len(changed),
                n_reassigned=reassigned, n_full_rows=len(full_rows),
                n_certified=self.n_users - len(full_rows),
                caches_patched=patched)
            self._maybe_refit(ratings, means, stats)
        self.last_refold = stats
        # index-health gauges: the drift/mass ledgers become scrapeable
        # (the serving autotuner's staleness inputs — ROADMAP item 3)
        reg = obs.registry()
        reg.counter("index.refold.count").inc()
        reg.histogram("index.refold.seconds").observe(sp.duration)
        reg.gauge("index.refold.reassign_frac").set(stats.reassigned_frac)
        reg.gauge("index.refold.caches_patched").set(stats.caches_patched)
        if stats.refit:
            reg.counter("index.refit.count").inc()
        if version is not None:
            reg.gauge("index.ratings_version").set(version)
        return stats

    # -- diagnostics -------------------------------------------------------
    def check_consistent(self, ratings: jnp.ndarray,
                         means: jnp.ndarray) -> bool:
        """Assert spill lists/distances and proxies equal a cold
        reassignment against the current centroids and basis, and the mass
        ledger equals a cold fold by primary cluster (the refold
        invariants); raises on mismatch."""
        p_cold = np.asarray(self._proxy_rows(ratings, means))
        errs = self._check_spill_state(p_cold)
        if errs:
            raise RuntimeError(
                "index diverged from a cold reassignment: "
                f"{', '.join(errs)}")
        return True
