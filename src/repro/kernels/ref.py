"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against (interpret mode
on CPU, Mosaic on real TPU).  They are intentionally simple and allocate
freely; production code calls ``repro.kernels.ops`` instead.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import similarity as core_sim
from repro.core.predict import clip_to_scale


# -- fused pairwise similarity ------------------------------------------------

def similarity_ref(ra: jnp.ndarray, rb: jnp.ndarray, measure: str = "all"):
    """(m, D) × (n, D) → similarity under ``measure`` (or all three)."""
    g = core_sim.gram_terms(ra, rb)
    out = {
        "jaccard": core_sim.jaccard_from_gram(g),
        "cosine": core_sim.cosine_from_gram(g),
        "pcc": core_sim.pcc_from_gram(g),
    }
    if measure == "all":
        return out["jaccard"], out["cosine"], out["pcc"]
    return out[measure]


# -- fused co-rated Gram rerank ----------------------------------------------

def rerank_scores_ref(q_vals: jnp.ndarray, cand_rows: jnp.ndarray,
                      cand_norms: jnp.ndarray, cand_counts: jnp.ndarray,
                      measure: str = "cosine",
                      beta: float | None = None,
                      cand_step: float = 1.0) -> jnp.ndarray:
    """(G, J) query rows × (Kc, J) candidate-union rows → (G, Kc) exact
    similarity under ``measure``, with full-row candidate norms/counts
    passed in (the union block may be item-compressed, so they cannot be
    derived from it); ``cand_rows`` may be a gather code, decoded by the
    power-of-two ``cand_step``.  Oracle for
    ``repro.kernels.rerank.fused_rerank_scores`` and its host BLAS twin;
    the same sparse num/den formulas as the index's ``_rerank_sparse``.
    """
    eps = 1e-8
    beta = core_sim.resolve_beta(beta)
    vq = q_vals.astype(jnp.float32)
    rc = cand_rows.astype(jnp.float32)
    if cand_step != 1.0:
        rc = rc * cand_step
    mq = (vq > 0).astype(jnp.float32)
    mc = (rc > 0).astype(jnp.float32)
    dot_kw = dict(precision=jax.lax.Precision.HIGHEST)
    if measure == "cosine":
        dot = jnp.matmul(vq, rc.T, **dot_kw)
        nq = jnp.sqrt(jnp.sum(vq * vq, axis=-1))[:, None]
        return dot / jnp.maximum(nq * cand_norms[None, :], eps)
    if measure == "jaccard":
        n = jnp.matmul(mq, mc.T, **dot_kw)
        union = jnp.sum(mq, -1)[:, None] + cand_counts[None, :] - n
        return n / jnp.maximum(union, eps)
    n = jnp.matmul(mq, mc.T, **dot_kw)
    dot = jnp.matmul(vq, rc.T, **dot_kw)
    sum_a = jnp.matmul(vq, mc.T, **dot_kw)
    sum_b = jnp.matmul(mq, rc.T, **dot_kw)
    sq_a = jnp.matmul(vq * vq, mc.T, **dot_kw)
    sq_b = jnp.matmul(mq, (rc * rc).T, **dot_kw)
    cov = n * dot - sum_a * sum_b
    var_a = n * sq_a - sum_a * sum_a
    var_b = n * sq_b - sum_b * sum_b
    denom = jnp.sqrt(jnp.maximum(var_a, 0.0) * jnp.maximum(var_b, 0.0))
    valid = (n >= 2) & (denom > eps)
    pcc = jnp.clip(cov / jnp.maximum(denom, eps), -1.0, 1.0)
    s = jnp.where(valid, (pcc + 1.0) * 0.5, 0.0)
    if measure == "pcc_sig":
        s = s * (jnp.minimum(n, beta) / beta)
    return s


# -- fused support-scorer (shortlist SpMM) ------------------------------------

def support_scores_ref(dev: jnp.ndarray, msk: jnp.ndarray,
                       nb_idx: jnp.ndarray, nb_w: jnp.ndarray,
                       q_means: jnp.ndarray, *, scale: tuple
                       ) -> jnp.ndarray:
    """(U, I) deviation/mask tables, (b, k) masked neighbor weights/ids →
    (b, I) exact clipped predictions.  Oracle for
    ``repro.kernels.support.fused_support_scores``; the same num/den
    epilogue as the item index's support scorer and the tile predictor
    (``nb_w`` must already be the masked weights — invalid neighbors at 0,
    ids clipped into range)."""
    rows_d = dev[nb_idx]                                   # (b, k, I)
    rows_m = msk[nb_idx]
    num = jnp.einsum("bk,bki->bi", nb_w, rows_d)
    den = jnp.einsum("bk,bki->bi", nb_w, rows_m)
    pred = q_means[:, None] + num / jnp.maximum(den, 1e-8)
    pred = jnp.where(den > 1e-8, pred, q_means[:, None])
    return clip_to_scale(pred, scale)


# -- blockwise top-M select ---------------------------------------------------

def select_topm_ref(scores: jnp.ndarray, m: int):
    """(Q, N) scores → canonical top-``m``: ``(values, ids)`` under the
    exact engines' ``(-score, id)`` order (descending score, ties to the
    lower id).  Every ``-inf`` slot (knockout or starved-row padding)
    carries the sentinel id ``N`` so no dead slot can alias a real row.
    Oracle for ``repro.kernels.select`` — the selection policy every
    shortlist scan mode must reproduce bit for bit."""
    n = scores.shape[1]
    ids = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :],
                           scores.shape)
    ids = jnp.where(jnp.isneginf(scores), n, ids)
    neg_sorted, idx_sorted = jax.lax.sort((-scores, ids), num_keys=2)
    m = min(m, n)
    return -neg_sorted[:, :m], idx_sorted[:, :m]


def scan_topm_ref(q: jnp.ndarray, proxies: jnp.ndarray,
                  q_ids: jnp.ndarray, m: int):
    """(Q, P) query proxies × (N, P) pool → canonical top-``m`` of the
    proxy scores with the self-pair knockout.  Oracle for
    ``repro.kernels.select.fused_scan_topm``."""
    s = jnp.matmul(q, proxies.T, precision=jax.lax.Precision.HIGHEST)
    col = jnp.arange(proxies.shape[0], dtype=jnp.int32)[None, :]
    s = jnp.where(col == q_ids.astype(jnp.int32)[:, None], -jnp.inf, s)
    return select_topm_ref(s, m)


# -- fused centroid distances -------------------------------------------------

def centroid_distances_ref(x: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """(m, D) rows × (n, D) centroids → (m, n) squared Euclidean distances.

    Oracle for ``repro.kernels.cluster.fused_centroid_distances``; clamped at
    zero like the kernel so float cancellation never yields tiny negatives.
    """
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    xx = jnp.sum(x * x, axis=-1, keepdims=True)
    cc = jnp.sum(c * c, axis=-1, keepdims=True).T
    d = xx - 2.0 * jnp.matmul(x, c.T,
                              precision=jax.lax.Precision.HIGHEST) + cc
    return jnp.maximum(d, 0.0)


# -- fused tile predict -------------------------------------------------------

def tile_predict_ref(nbr: jnp.ndarray, w: jnp.ndarray, nb_means: jnp.ndarray,
                     q_means: jnp.ndarray, *, scale: tuple) -> jnp.ndarray:
    """(m, k, T) gathered neighbor ratings, (m, k) weights/means, (m,) query
    means → (m, T) clipped predictions.  Oracle for
    ``repro.kernels.predict.fused_tile_predict`` (and the same arithmetic as
    one item tile of ``repro.core.predict``)."""
    nbr = nbr.astype(jnp.float32)
    mask = (nbr > 0).astype(jnp.float32)
    dev = (nbr - nb_means[:, :, None]) * mask
    num = jnp.einsum("mk,mkt->mt", w, dev)
    den = jnp.einsum("mk,mkt->mt", w, mask)
    pred = q_means[:, None] + num / jnp.maximum(den, 1e-8)
    pred = jnp.where(den > 1e-8, pred, q_means[:, None])
    return clip_to_scale(pred, scale)


# -- attention ----------------------------------------------------------------

def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, scale: float | None = None,
                  ) -> jnp.ndarray:
    """Naive attention oracle.  q: (B, Hq, Sq, D); k,v: (B, Hkv, Skv, D).

    GQA: Hq must be a multiple of Hkv; kv heads are repeated.
    """
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    group = hq // hkv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        qpos = jnp.arange(sq)[:, None] + (skv - sq)
        kpos = jnp.arange(skv)[None, :]
        logits = jnp.where(qpos >= kpos, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32)
                      ).astype(q.dtype)


# -- embedding bag --------------------------------------------------------------

def embedding_bag_ref(table: jnp.ndarray, indices: jnp.ndarray, *,
                      combiner: str = "sum") -> jnp.ndarray:
    """(V, D) table, (B, L) indices with -1 padding → (B, D) bags."""
    valid = indices >= 0
    safe = jnp.where(valid, indices, 0)
    rows = table[safe] * valid[..., None].astype(table.dtype)   # (B, L, D)
    bags = jnp.sum(rows, axis=1)
    if combiner == "mean":
        cnt = jnp.maximum(jnp.sum(valid, axis=1, keepdims=True), 1)
        bags = bags / cnt.astype(bags.dtype)
    elif combiner != "sum":
        raise ValueError(f"unknown combiner {combiner!r}")
    return bags
