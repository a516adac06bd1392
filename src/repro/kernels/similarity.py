"""Fused pairwise-similarity Pallas TPU kernel — the paper's compute hot spot.

One K-blocked pass over a (query-block, candidate-block) pair of rating
tiles accumulates all six Gram terms in VMEM (see DESIGN.md §2) and computes
the Jaccard / Cosine / PCC epilogues in-register, instead of six separate XLA
matmuls that each re-stream the rating matrix from HBM.

Arithmetic intensity: the fused kernel reads (bm+bn)·bk·4 bytes per
6·2·bm·bn·bk flops step ⇒ at bm=bn=256, bk=512 that is ~196 flops/byte,
comfortably past the v5e ridge (197e12/819e9 ≈ 240 flops/byte when counting
a single product; the six share the same operand reads, so the *effective*
intensity versus unfused is 6×).

Grid: (M/bm, N/bn, D/bk) with the K axis innermost ("arbitrary" semantics —
it carries the accumulators); M/N axes are "parallel", which is exactly the
paper's thread partition mapped onto the MXU grid.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.similarity import PCC_SIG_BETA

_EPS = 1e-8
MEASURES = ("jaccard", "cosine", "pcc")
ALL_MEASURES = MEASURES + ("pcc_sig",)    # "all" keeps the original 3-tuple

# default MXU-aligned tile sizes (v5e: 128×128 MXU, 8×128 VREG lanes)
BM, BN, BK = 256, 256, 512


def _dot_t(a, b):
    """a (m,k) · b (n,k)ᵀ with f32 accumulation on the MXU."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _sim_kernel(ra_ref, rb_ref, *refs, n_k: int, measures: Sequence[str],
                beta: float = PCC_SIG_BETA):
    out_refs = refs[:len(measures)]
    (acc_n, acc_dot, acc_sa, acc_sb, acc_qa, acc_qb,
     acc_ca, acc_cb, acc_na, acc_nb) = refs[len(measures):]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        for r in (acc_n, acc_dot, acc_sa, acc_sb, acc_qa, acc_qb,
                  acc_ca, acc_cb, acc_na, acc_nb):
            r[...] = jnp.zeros_like(r)

    a = ra_ref[...].astype(jnp.float32)
    b = rb_ref[...].astype(jnp.float32)
    ma = (a > 0).astype(jnp.float32)
    mb = (b > 0).astype(jnp.float32)

    acc_n[...] += _dot_t(ma, mb)
    acc_dot[...] += _dot_t(a, b)
    acc_sa[...] += _dot_t(a, mb)
    acc_sb[...] += _dot_t(ma, b)
    acc_qa[...] += _dot_t(a * a, mb)
    acc_qb[...] += _dot_t(ma, b * b)
    acc_ca[...] += jnp.sum(ma, axis=1, keepdims=True)          # (bm, 1)
    acc_cb[...] += jnp.sum(mb, axis=1, keepdims=True).T        # (1, bn)
    acc_na[...] += jnp.sum(a * a, axis=1, keepdims=True)       # (bm, 1)
    acc_nb[...] += jnp.sum(b * b, axis=1, keepdims=True).T     # (1, bn)

    @pl.when(k == n_k - 1)
    def _epilogue():
        n = acc_n[...]
        for ref, measure in zip(out_refs, measures):
            if measure == "jaccard":
                union = acc_ca[...] + acc_cb[...] - n
                ref[...] = n / jnp.maximum(union, _EPS)
            elif measure == "cosine":
                denom = jnp.sqrt(acc_na[...] * acc_nb[...])
                ref[...] = acc_dot[...] / jnp.maximum(denom, _EPS)
            else:  # pcc / pcc_sig, normalised to [0, 1] (paper convention)
                cov = n * acc_dot[...] - acc_sa[...] * acc_sb[...]
                var_a = jnp.maximum(n * acc_qa[...] - acc_sa[...] ** 2, 0.0)
                var_b = jnp.maximum(n * acc_qb[...] - acc_sb[...] ** 2, 0.0)
                denom = jnp.sqrt(var_a * var_b)
                valid = (n >= 2) & (denom > _EPS)
                pcc = jnp.clip(cov / jnp.maximum(denom, _EPS), -1.0, 1.0)
                pcc01 = jnp.where(valid, (pcc + 1.0) * 0.5, 0.0)
                if measure == "pcc_sig":
                    pcc01 = pcc01 * (jnp.minimum(n, beta) / beta)
                ref[...] = pcc01


def _pad_to(x, mult, axis):
    rem = x.shape[axis] % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=(
    "measure", "bm", "bn", "bk", "interpret", "beta"))
def fused_similarity(ra: jnp.ndarray, rb: jnp.ndarray, *,
                     measure: str = "all", bm: int = BM, bn: int = BN,
                     bk: int = BK, interpret: bool = False,
                     beta: float = PCC_SIG_BETA):
    """All-pairs similarity between rating blocks via the fused kernel.

    ``ra``: (m, D), ``rb``: (n, D); returns (m, n) for a single measure or a
    3-tuple (jaccard, cosine, pcc) for ``measure='all'``.  ``beta`` is the
    ``pcc_sig`` significance horizon.
    """
    if measure != "all" and measure not in ALL_MEASURES:
        raise ValueError(f"unknown measure {measure!r}; want one of "
                         f"{ALL_MEASURES} or 'all'")
    measures = MEASURES if measure == "all" else (measure,)
    m, d = ra.shape
    n = rb.shape[0]
    bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, d)
    ra_p = _pad_to(_pad_to(ra, bm_, 0), bk_, 1)
    rb_p = _pad_to(_pad_to(rb, bn_, 0), bk_, 1)
    mp, dp = ra_p.shape
    np_ = rb_p.shape[0]
    grid = (mp // bm_, np_ // bn_, dp // bk_)

    out_shape = [jax.ShapeDtypeStruct((mp, np_), jnp.float32)
                 for _ in measures]
    out_specs = [pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j))
                 for _ in measures]
    scratch = ([pltpu.VMEM((bm_, bn_), jnp.float32)] * 6
               + [pltpu.VMEM((bm_, 1), jnp.float32),
                  pltpu.VMEM((1, bn_), jnp.float32),
                  pltpu.VMEM((bm_, 1), jnp.float32),
                  pltpu.VMEM((1, bn_), jnp.float32)])

    kernel = pl.pallas_call(
        functools.partial(_sim_kernel, n_k=grid[2], measures=measures,
                          # reprolint: disable=host-transfer -- beta is a static Python scalar baked into the kernel closure, never traced
                          beta=float(beta)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn_, bk_), lambda i, j, k: (j, k)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    outs = kernel(ra_p, rb_p)
    outs = tuple(o[:m, :n] for o in outs)
    return outs if measure == "all" else outs[0]
