"""Blockwise top-M selection for the shortlist scan: device-side select.

The shortlist stage of :class:`repro.index.ClusteredIndex` scores a query
block against a candidate pool (one proxy GEMM) and keeps the best
``max_rerank`` per query.  On the host that is a torch ``mm``/``topk``
pair; on an accelerator the score matrix used to round-trip to the host
for selection — ~0.27 GB per 2048-query block at U=32768.  This module
keeps selection on the device:

* :func:`fused_scan_topm` — the Pallas blockwise-select kernel.  Grid
  ``(Q/bq, N/bn)`` with the candidate axis innermost: each step computes
  one ``q_tile @ proxies_tileᵀ`` score block on the MXU, knocks out
  self-pairs and padding, and folds the block into a VMEM-resident
  running top-``m`` buffer via one canonical ``(-score, id)`` sort over
  ``m_pad + bn`` lanes.  The (Q, N) score matrix is never materialised —
  not even in HBM.  The merge uses ``jax.lax.sort`` inside the kernel
  body; that is exact and runs under interpret mode, but Mosaic has no
  TPU lowering for ``sort`` (compiling for a v5e is refused).  So the
  served paths select with :func:`scan_topm_xla` / ``lax.top_k`` on
  every backend, TPU included (``QueryStats.select_mode`` reads
  ``top_k``); this kernel is kept, oracle-tested in interpret mode, for
  a lowered merge to replace the twin.
* :func:`select_topm` — the same running merge over a precomputed score
  matrix.
* :func:`scan_topm_xla` — the XLA twin: one jnp GEMM plus
  ``jax.lax.top_k`` (exact; XLA's top_k breaks ties toward the lower
  index, which *is* the canonical ``(-score, id)`` policy), or
  ``jax.lax.approx_max_k`` when ``approx=True`` — TPU's O(N) partial
  reduce, recall < 1 by construction, for latency-bound serving only.

Selection policy — identical across every path and pinned by the oracle
(``ref.select_topm_ref``): descending score, ties broken toward the lower
candidate id, knocked-out slots at ``-inf`` (callers map them to their
padding id).  This is the same canonical order as the exact engines'
``(-score, id)`` sort, so shortlists are bit-identical whether selected
here, by the host torch/numpy scan, or by the degenerate exact path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# MXU-aligned defaults (v5e: 128×128 MXU, 8×128 VREG lanes); bn bounds the
# per-step sort width (m_pad + bn lanes resident in VMEM)
BQ, BN = 256, 1024

_NEG_INF = float("-inf")


def _pad_axis(x, mult, axis, value=0.0):
    rem = x.shape[axis] % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(x, pad, constant_values=value)


def _merge_topm(acc_v, acc_i, s, col, m_pad):
    """Fold one score block into the running buffer: one canonical
    ``(-score, id)`` sort over the concatenation, keep the best m_pad."""
    cat_v = jnp.concatenate([acc_v, s], axis=1)
    cat_i = jnp.concatenate([acc_i, col], axis=1)
    neg_sorted, idx_sorted = jax.lax.sort((-cat_v, cat_i), num_keys=2)
    return -neg_sorted[:, :m_pad], idx_sorted[:, :m_pad]


def _topm_step(s, qid_ref, val_ref, idx_ref, acc_v, acc_i, *, n_j: int,
               n_valid: int, bn: int, m_pad: int):
    """Shared kernel step: init the running buffer on the first column
    block, knock out self/padding slots of this block's scores ``s``,
    fold them into the running canonical top-m, and emit on the last."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_v[...] = jnp.full(acc_v.shape, _NEG_INF, jnp.float32)
        acc_i[...] = jnp.full(acc_i.shape, n_valid, jnp.int32)

    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    invalid = (col >= n_valid) | (col == qid_ref[...])
    s = jnp.where(invalid, _NEG_INF, s)
    # sentinel policy: every -inf slot (knockout here, or a precomputed
    # knockout in the caller's scores) carries id n_valid, so downstream
    # gathers can never silently index a real row through a dead slot
    col = jnp.where(jnp.isneginf(s), n_valid, col)
    acc_v[...], acc_i[...] = _merge_topm(acc_v[...], acc_i[...], s, col,
                                         m_pad)

    @pl.when(j == n_j - 1)
    def _out():
        val_ref[...] = acc_v[...]
        idx_ref[...] = acc_i[...]


def _scan_kernel(q_ref, p_ref, qid_ref, val_ref, idx_ref, acc_v, acc_i,
                 **kw):
    s = jax.lax.dot_general(
        q_ref[...].astype(jnp.float32), p_ref[...].astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    _topm_step(s, qid_ref, val_ref, idx_ref, acc_v, acc_i, **kw)


def _select_kernel(s_ref, qid_ref, val_ref, idx_ref, acc_v, acc_i, **kw):
    _topm_step(s_ref[...].astype(jnp.float32), qid_ref, val_ref, idx_ref,
               acc_v, acc_i, **kw)


def _m_pad(m: int) -> int:
    return max(128, -(-m // 128) * 128)


@functools.partial(jax.jit, static_argnames=("m", "bq", "bn", "interpret"))
def fused_scan_topm(q: jnp.ndarray, proxies: jnp.ndarray,
                    q_ids: jnp.ndarray, *, m: int, bq: int = BQ,
                    bn: int = BN, interpret: bool = False):
    """(Q, P) query proxies × (N, P) pool proxies → canonical top-``m``
    per query: ``(values (Q, m), ids (Q, m) int32)``.

    ``q_ids``: (Q,) global ids for the self-pair knockout (out-of-range,
    e.g. -1 or N, for padding queries — they never match a column).
    Knocked-out and padding slots come back as ``-inf`` with id ``N``.
    """
    n_q, p = q.shape
    n = proxies.shape[0]
    m = min(m, n)
    mp = _m_pad(m)
    bq_, bn_ = min(bq, _m_pad(n_q)), min(bn, _m_pad(n))
    q_p = _pad_axis(q, bq_, 0)
    prox_p = _pad_axis(proxies, bn_, 0)
    qid_p = _pad_axis(q_ids.astype(jnp.int32).reshape(-1, 1), bq_, 0,
                      value=-1)
    grid = (q_p.shape[0] // bq_, prox_p.shape[0] // bn_)

    vals, ids = pl.pallas_call(
        functools.partial(_scan_kernel, n_j=grid[1], n_valid=n, bn=bn_,
                          m_pad=mp),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq_, p), lambda i, j: (i, 0)),
            pl.BlockSpec((bn_, p), lambda i, j: (j, 0)),
            pl.BlockSpec((bq_, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((bq_, mp), lambda i, j: (i, 0)),
                   pl.BlockSpec((bq_, mp), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((q_p.shape[0], mp), jnp.float32),
                   jax.ShapeDtypeStruct((q_p.shape[0], mp), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((bq_, mp), jnp.float32),
                        pltpu.VMEM((bq_, mp), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q_p, prox_p, qid_p)
    return vals[:n_q, :m], ids[:n_q, :m]


@functools.partial(jax.jit, static_argnames=("m", "bq", "bn", "interpret"))
def select_topm(scores: jnp.ndarray, q_ids: jnp.ndarray, *, m: int,
                bq: int = BQ, bn: int = BN, interpret: bool = False):
    """Canonical top-``m`` over precomputed (Q, N) scores (running
    blockwise merge, no full-width sort).  Same contract as
    :func:`fused_scan_topm`; pass out-of-range ``q_ids`` when the scores
    already carry their self/seen knockout."""
    n_q, n = scores.shape
    m = min(m, n)
    mp = _m_pad(m)
    bq_, bn_ = min(bq, _m_pad(n_q)), min(bn, _m_pad(n))
    s_p = _pad_axis(_pad_axis(scores, bq_, 0), bn_, 1)
    qid_p = _pad_axis(q_ids.astype(jnp.int32).reshape(-1, 1), bq_, 0,
                      value=-1)
    grid = (s_p.shape[0] // bq_, s_p.shape[1] // bn_)

    vals, ids = pl.pallas_call(
        functools.partial(_select_kernel, n_j=grid[1], n_valid=n, bn=bn_,
                          m_pad=mp),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq_, bn_), lambda i, j: (i, j)),
            pl.BlockSpec((bq_, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((bq_, mp), lambda i, j: (i, 0)),
                   pl.BlockSpec((bq_, mp), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((s_p.shape[0], mp), jnp.float32),
                   jax.ShapeDtypeStruct((s_p.shape[0], mp), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((bq_, mp), jnp.float32),
                        pltpu.VMEM((bq_, mp), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(s_p, qid_p)
    return vals[:n_q, :m], ids[:n_q, :m]


@functools.partial(jax.jit, static_argnames=("m", "approx",
                                             "recall_target"))
def scan_topm_xla(q: jnp.ndarray, proxies: jnp.ndarray,
                  q_ids: jnp.ndarray, *, m: int, approx: bool = False,
                  recall_target: float = 0.95):
    """The XLA twin of :func:`fused_scan_topm`: one device GEMM feeding
    ``jax.lax.top_k`` (exact — XLA breaks ties toward the lower index,
    the canonical policy) or ``jax.lax.approx_max_k`` (``approx=True``:
    TPU's blockwise partial reduce, recall < 1, never used where the
    bit-parity contract applies)."""
    n = proxies.shape[0]
    m = min(m, n)
    s = jnp.matmul(q, proxies.T, precision=jax.lax.Precision.HIGHEST)
    col = jnp.arange(n, dtype=jnp.int32)[None, :]
    s = jnp.where(col == q_ids.astype(jnp.int32)[:, None], _NEG_INF, s)
    if approx:
        vals, ids = jax.lax.approx_max_k(s, m,
                                         recall_target=recall_target)
    else:
        vals, ids = jax.lax.top_k(s, m)
    ids = jnp.where(jnp.isneginf(vals), n, ids)   # sentinel policy
    return vals, ids.astype(jnp.int32)
