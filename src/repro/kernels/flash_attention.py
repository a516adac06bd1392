"""Causal GQA flash-attention Pallas TPU kernel (forward).

IO-aware attention for the LM-family architectures: online-softmax over KV
blocks so the (Sq × Skv) score matrix never leaves VMEM.  Supports GQA
(q-heads grouped over kv-heads via the K/V BlockSpec index maps) and decode
shapes (Sq=1 block with a long KV).  Training on CPU/dry-run uses the
XLA chunked reference in ``repro.models.layers``; this kernel is the TPU
target and is validated in interpret mode against ``ref.attention_ref``.

Grid: (B, Hq, Sq/bq, Skv/bk), KV innermost (carries the running max / sum /
accumulator scratch).  Fully-masked KV blocks (beyond the causal frontier)
are skipped with ``pl.when`` — on TPU the grid is executed sequentially per
core, so the skip saves real time, the analogue of a CUDA early-exit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BQ, DEFAULT_BK = 256, 512
NEG_INF = float(jnp.finfo(jnp.float32).min)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, n_kv: int, bq: int, bk: int,
                  q_offset: int):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # global positions: queries sit at the END of the kv sequence (decode)
    q_pos = q_offset + iq * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)      # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows (all NEG_INF): keep exp at 0
        p = jnp.exp(jnp.where(s == NEG_INF, NEG_INF, s - m_new))
        alpha = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_new))
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    if causal:
        # first kv position of this block must not exceed last q position
        pl.when(ik * bk <= q_offset + iq * bq + bq - 1)(compute)
    else:
        compute()

    @pl.when(ik == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "bq", "bk", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, scale: float | None = None,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D) → (B, Hq, Sq, D).

    GQA via Hq = g·Hkv.  For decode, Sq < Skv and queries are aligned to the
    end of the KV sequence (q_offset = Skv − Sq).
    """
    b, hq, sq, d = q.shape
    dv = v.shape[-1]
    hkv, skv = k.shape[1], k.shape[2]
    if hq % hkv:
        raise ValueError(f"Hq={hq} must be a multiple of Hkv={hkv}")
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    bq_ = min(bq, sq)
    bk_ = min(bk, skv)
    if sq % bq_ or skv % bk_:
        raise ValueError(f"Sq={sq} (Skv={skv}) must divide bq={bq_} (bk={bk_})")
    n_kv = skv // bk_
    grid = (b, hq, sq // bq_, n_kv)

    kernel = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, n_kv=n_kv,
            bq=bq_, bk=bk_, q_offset=skv - sq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq_, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk_, d),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk_, dv),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq_, dv),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )
    return kernel(q, k, v)
