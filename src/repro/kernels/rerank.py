"""Fused co-rated Gram rerank Pallas TPU kernel + host BLAS twin.

The exact rerank of the clustered index scores each query against its
shortlisted candidates with the *true* similarity measure.  The sparse
gather form (``repro.index.clustered._rerank_sparse``) walks an
``(M, nnz)`` sub-block per query — optimal when a fast random-access
gather exists (CPU caches).  On TPU there is no such gather: XLA lowers
it to per-element dynamic slices, and the six Gram statistics each
re-stream the gathered block from HBM.

This kernel is the MXU formulation.  Queries are grouped (by taste
cluster — neighbors of one cluster shortlist largely the same
candidates), the group's candidate-union rows are gathered **once**, and
all num/den statistics for the whole ``(group, union)`` block come out of
one K-blocked VMEM pass:

    n     = Σ_i 1[vq>0]·1[rc>0]      dot  = Σ_i vq·rc
    sum_a = Σ_i vq·1[rc>0]           sum_b = Σ_i 1[vq>0]·rc
    sq_a  = Σ_i vq²·1[rc>0]          sq_b  = Σ_i 1[vq>0]·rc²

Every statistic carries a query-side factor, so terms vanish off the
query's rated items — full-width candidate rows give exactly the sparse
co-rated sums (the paper's per-pair loop, lifted onto the MXU).  Cosine's
full-vector candidate norms and jaccard's rated counts cannot be derived
from a column-compressed union block, so they stream in precomputed
(one cheap global pass, shapes ``(1, Kc)``).

Candidate rows may be the int8 gather code of ``repro.core.predict``
(``r / cand_step``); tiles are decoded in-register by the power-of-two
step, exactly.  For integer-valued rating matrices (MovieLens 1..5), and
for half-star ones, every Gram sum is an exactly-representable f32
number regardless of accumulation order, so
the kernel, the jnp oracle (``repro.kernels.ref.rerank_scores_ref``), the
host BLAS twin below, and ``_rerank_sparse`` all agree **bit for bit** —
the equivalence the oracle tests pin.

Grid: (G/bm, Kc/bn, J/bk), K innermost ("arbitrary" — it carries the
accumulators); group/union axes are "parallel".  Interpret mode runs on
CPU for tests; production CPU reranking uses :func:`rerank_scores_host`
(OpenBLAS) because at CPU memory bandwidth the bucketed int8 gather walk
or the BLAS twin win over interpret-mode Pallas by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import similarity as sim

_EPS = 1e-8
MEASURES = ("jaccard", "cosine", "pcc", "pcc_sig")

# default MXU-aligned tile sizes (v5e: 128×128 MXU, 8×128 VREG lanes)
BM, BN, BK = 128, 256, 512


def _dot_t(a, b):
    """a (m,k) · b (n,k)ᵀ with f32 accumulation on the MXU."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _decode(rows, step):
    """Candidate code → f32 ratings (exact: ``step`` is a power of two;
    1.0 for f32 rows and integer codes)."""
    rows = rows.astype(jnp.float32)
    return rows if step == 1.0 else rows * step


def _rerank_kernel(q_ref, c_ref, cn_ref, cc_ref, out_ref, *accs,
                   n_k: int, measure: str, beta: float, cand_step: float):
    (acc_n, acc_dot, acc_sa, acc_sb, acc_qa, acc_qb,
     acc_qn, acc_qc) = accs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        for r in accs:
            r[...] = jnp.zeros_like(r)

    vq = q_ref[...].astype(jnp.float32)            # (bm, bk) query values
    rc = _decode(c_ref[...], cand_step)            # (bn, bk) candidate rows
    mq = (vq > 0).astype(jnp.float32)
    mc = (rc > 0).astype(jnp.float32)

    if measure == "cosine":
        acc_dot[...] += _dot_t(vq, rc)
        acc_qn[...] += jnp.sum(vq * vq, axis=1, keepdims=True)   # (bm, 1)
    elif measure == "jaccard":
        acc_n[...] += _dot_t(mq, mc)
        acc_qc[...] += jnp.sum(mq, axis=1, keepdims=True)
    else:                                          # pcc / pcc_sig
        acc_n[...] += _dot_t(mq, mc)
        acc_dot[...] += _dot_t(vq, rc)
        acc_sa[...] += _dot_t(vq, mc)
        acc_sb[...] += _dot_t(mq, rc)
        acc_qa[...] += _dot_t(vq * vq, mc)
        acc_qb[...] += _dot_t(mq, rc * rc)

    @pl.when(k == n_k - 1)
    def _epilogue():
        if measure == "cosine":
            nq = jnp.sqrt(acc_qn[...])
            denom = nq * cn_ref[...]
            out_ref[...] = acc_dot[...] / jnp.maximum(denom, _EPS)
        elif measure == "jaccard":
            n = acc_n[...]
            union = acc_qc[...] + cc_ref[...] - n
            out_ref[...] = n / jnp.maximum(union, _EPS)
        else:
            n = acc_n[...]
            cov = n * acc_dot[...] - acc_sa[...] * acc_sb[...]
            var_a = n * acc_qa[...] - acc_sa[...] * acc_sa[...]
            var_b = n * acc_qb[...] - acc_sb[...] * acc_sb[...]
            denom = jnp.sqrt(jnp.maximum(var_a, 0.0)
                             * jnp.maximum(var_b, 0.0))
            valid = (n >= 2) & (denom > _EPS)
            pcc = jnp.clip(cov / jnp.maximum(denom, _EPS), -1.0, 1.0)
            s = jnp.where(valid, (pcc + 1.0) * 0.5, 0.0)
            if measure == "pcc_sig":
                s = s * (jnp.minimum(n, beta) / beta)
            out_ref[...] = s


def _pad_to(x, mult, axis):
    rem = x.shape[axis] % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=(
    "measure", "beta", "cand_step", "bm", "bn", "bk", "interpret"))
def fused_rerank_scores(q_vals: jnp.ndarray, cand_rows: jnp.ndarray,
                        cand_norms: jnp.ndarray, cand_counts: jnp.ndarray,
                        *, measure: str = "cosine", beta: float = 50.0,
                        cand_step: float = 1.0,
                        bm: int = BM, bn: int = BN, bk: int = BK,
                        interpret: bool = False) -> jnp.ndarray:
    """Exact similarity of a query group against a candidate union.

    ``q_vals``: (G, J) query rating rows (0 = unrated); ``cand_rows``:
    (Kc, J) candidate rows over the same item axis (int8 code or f32 — the
    kernel decodes tiles in-register by ``cand_step``, so the int8 gather
    source streams 4× less HBM); ``cand_norms``/``cand_counts``: (Kc,)
    full-row L2 norms and rated counts.  Operands already whole tiles
    (rows of ``bn``, columns of ``bk``) are used as they are, uncopied.
    Returns (G, Kc) scores under ``measure`` — the same formulas as
    ``_rerank_sparse``; self/padding masking is the caller's.
    """
    if measure not in MEASURES:
        raise ValueError(f"unknown measure {measure!r}; want one of "
                         f"{MEASURES}")
    g, j = q_vals.shape
    kc = cand_rows.shape[0]
    bm_, bn_, bk_ = min(bm, g), min(bn, kc), min(bk, j)
    q_p = _pad_to(_pad_to(q_vals, bm_, 0), bk_, 1)
    c_p = _pad_to(_pad_to(cand_rows, bn_, 0), bk_, 1)
    cn_p = _pad_to(cand_norms[None, :].astype(jnp.float32), bn_, 1)
    cc_p = _pad_to(cand_counts[None, :].astype(jnp.float32), bn_, 1)
    gp, jp = q_p.shape
    kp = c_p.shape[0]
    grid = (gp // bm_, kp // bn_, jp // bk_)

    out = pl.pallas_call(
        functools.partial(_rerank_kernel, n_k=grid[2], measure=measure,
                          # reprolint: disable=host-transfer -- beta is a static Python scalar baked into the kernel closure, never traced
                          beta=float(beta), cand_step=cand_step),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j_, k: (i, k)),
            pl.BlockSpec((bn_, bk_), lambda i, j_, k: (j_, k)),
            pl.BlockSpec((1, bn_), lambda i, j_, k: (0, j_)),
            pl.BlockSpec((1, bn_), lambda i, j_, k: (0, j_)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j_, k: (i, j_)),
        out_shape=jax.ShapeDtypeStruct((gp, kp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)] * 6
        + [pltpu.VMEM((bm_, 1), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_p, c_p, cn_p, cc_p)
    return out[:g, :kc]


@functools.partial(jax.jit, static_argnames=("measure", "beta",
                                             "cand_step"))
def rerank_scores_xla(q_vals: jnp.ndarray, cand_rows: jnp.ndarray,
                      cand_norms: jnp.ndarray, cand_counts: jnp.ndarray,
                      *, measure: str = "cosine",
                      beta: float = 50.0,
                      cand_step: float = 1.0) -> jnp.ndarray:
    """XLA twin of :func:`fused_rerank_scores`: the same union-Gram
    statistics as one jitted jnp pass — the fused query pipeline's rerank
    stage wherever the Pallas kernel does not run.  Delegates to the jnp
    oracle (``ref.rerank_scores_ref``), so the twin is the oracle by
    construction; for integer rating matrices it is bit-identical to the
    kernel, the host BLAS twin, and the sparse gather walk.
    """
    if measure not in MEASURES:
        raise ValueError(f"unknown measure {measure!r}; want one of "
                         f"{MEASURES}")
    from repro.kernels import ref
    return ref.rerank_scores_ref(q_vals, cand_rows, cand_norms,
                                 cand_counts, measure=measure, beta=beta,
                                 cand_step=cand_step)


def rerank_scores_host(q_vals: np.ndarray, cand_rows: np.ndarray,
                       cand_norms: np.ndarray, cand_counts: np.ndarray,
                       *, measure: str = "cosine",
                       beta: float = 50.0) -> np.ndarray:
    """Host twin of :func:`fused_rerank_scores` on OpenBLAS.

    Same inputs/outputs, numpy f32 throughout.  One sgemm for cosine and
    jaccard, six (stacked) for pcc — for integer rating matrices every
    Gram sum is an exact f32 integer, so the result is bit-identical to
    the kernel, the jnp oracle, and ``_rerank_sparse``.
    """
    if measure not in MEASURES:
        raise ValueError(f"unknown measure {measure!r}; want one of "
                         f"{MEASURES}")
    vq = np.ascontiguousarray(q_vals, np.float32)
    rc = np.ascontiguousarray(cand_rows, np.float32)
    if measure == "cosine":
        dot = vq @ rc.T
        nq = np.sqrt(np.einsum("ij,ij->i", vq, vq))[:, None]
        return dot / np.maximum(nq * cand_norms[None, :], _EPS)
    mq = (vq > 0).astype(np.float32)
    mc = (rc > 0).astype(np.float32)
    if measure == "jaccard":
        n = mq @ mc.T
        union = mq.sum(1)[:, None] + cand_counts[None, :] - n
        return n / np.maximum(union, _EPS)
    n = mq @ mc.T
    dot = vq @ rc.T
    sum_a = vq @ mc.T
    sum_b = mq @ rc.T
    sq_a = (vq * vq) @ mc.T
    sq_b = mq @ (rc * rc).T
    cov = n * dot - sum_a * sum_b
    var_a = n * sq_a - sum_a * sum_a
    var_b = n * sq_b - sum_b * sum_b
    denom = np.sqrt(np.maximum(var_a, 0.0) * np.maximum(var_b, 0.0))
    valid = (n >= 2) & (denom > _EPS)
    pcc = np.clip(cov / np.maximum(denom, _EPS), -1.0, 1.0)
    s = np.where(valid, (pcc + 1.0) * np.float32(0.5), np.float32(0.0))
    if measure == "pcc_sig":
        s = s * (np.minimum(n, np.float32(beta)) / np.float32(beta))
    return s.astype(np.float32, copy=False)
