"""Fused support-scorer (segmented SpMM) Pallas TPU kernel.

The recommend stage's shortlist scorer evaluates the *true* predictor
num/den form for every item:

    num[u, i] = Σ_k w[u,k] · dev[nb[u,k], i]
    den[u, i] = Σ_k w[u,k] · msk[nb[u,k], i]
    pred      = clip(r̄_u + num/den, lo, hi)   (r̄_u when den == 0)

— a segmented SpMM between the k-sparse neighbor-weight matrix and the
stacked deviation/mask table (the exact top-n is dominated by items with
a *median of one* supporting neighbor, which no smooth taste geometry
can see, so the shortlist scores the predictor itself).

TPU formulation via *scalar prefetch* (the embedding-bag pattern): the
(b, k) neighbor ids and weights and the (b,) query means are prefetched
to SMEM, so each grid step's BlockSpec index map selects which table row
tile to DMA — the deviation/mask tables never leave HBM except for the
touched rows, and each gathered tile is consumed by one VMEM
multiply-accumulate with the division/fallback/clip epilogue in-register.

Layout: the tables are ``(U, 1, I)`` (:func:`support_rows` builds them).
Mosaic requires the last two block dims to divide by (8, 128) or equal
the array dims; a one-row tile of a ``(U, I)`` table satisfies neither,
while a ``(1, bt)`` tile of a ``(U, 1, I)`` table does, with the row dim
squeezed.  The tables are stored in that layout once per ratings array
(a per-call reshape would copy both, ~198 MB at the ML-1M shape).  SMEM
holds 1 MiB and pads a 2-D operand's last dim to 128 lanes, so one call
takes at most ``BB`` query rows; larger batches loop over row blocks.

Grid per row block: (b, I/bt, k) with the neighbor axis innermost (it
carries the num/den accumulators).  Interpret mode runs on CPU and is
validated against ``repro.kernels.ref.support_scores_ref``, which also
evaluates the same tables wherever the kernel does not run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.predict import clip_to_scale

_DEN_EPS = 1e-8

BT = 512            # item-tile width: 2 tables · (1, bt) f32 per step
ROWS = 2048         # rating rows laid out per step of the table build
# query rows per pallas_call: two (BB, k) SMEM operands padded to 128
# lanes stay at 256 KiB of the 1 MiB SMEM
BB = 256


def support_width(n_items: int, bt: int = BT) -> int:
    """Stored table width: ``n_items`` padded to a whole number of tiles."""
    bt_ = min(bt, n_items)
    return -(-n_items // bt_) * bt_


@functools.partial(jax.jit, static_argnames=("width",))
def support_rows(rows: jnp.ndarray, row_means: jnp.ndarray,
                 width: int) -> jnp.ndarray:
    """(n, I) rating rows → ``(dev, msk)`` kernel table rows, each
    ``(n, 1, width)`` f32: mean-centred ratings and the rated mask, zero
    in the pad columns (den 0 there → mean fallback, sliced off).

    Written ``ROWS`` rows at a time into the two tables: the device may
    lay a ``(n, I)`` matrix out column-major (the TPU does at ML-10M's
    shape), and a whole-matrix pass would then hold a relaid-out copy of
    each table, ~3 GB apiece, beside the tables themselves.  The last
    block overlaps the one before it; it writes the same values."""
    n = rows.shape[0]
    blk = min(n, ROWS)
    pad = ((0, 0), (0, width - rows.shape[1]))

    def body(b, tables):
        lo = jnp.minimum(b * blk, n - blk)
        r = jax.lax.dynamic_slice_in_dim(rows, lo, blk)
        m = jax.lax.dynamic_slice_in_dim(row_means, lo, blk)
        mask = r > 0
        dev = jnp.where(mask, r - m[:, None], 0.0).astype(jnp.float32)
        new = (jnp.pad(dev, pad), jnp.pad(mask.astype(jnp.float32), pad))
        return tuple(jax.lax.dynamic_update_slice(t, x[:, None, :],
                                                  (lo, 0, 0))
                     for t, x in zip(tables, new))

    empty = jnp.zeros((n, 1, width), jnp.float32)
    return jax.lax.fori_loop(0, -(-n // blk), body, (empty, empty))


def _support_kernel(idx_ref, w_ref, qm_ref, dev_ref, msk_ref, out_ref,
                    acc_num, acc_den, *, k_len: int, scale: tuple):
    b, kk = pl.program_id(0), pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_num[...] = jnp.zeros_like(acc_num)
        acc_den[...] = jnp.zeros_like(acc_den)

    del idx_ref
    w = w_ref[b, kk]
    acc_num[...] += w * dev_ref[...].astype(jnp.float32)
    acc_den[...] += w * msk_ref[...].astype(jnp.float32)

    @pl.when(kk == k_len - 1)
    def _epilogue():
        qm = qm_ref[b]
        num, den = acc_num[...], acc_den[...]
        pred = qm + num / jnp.maximum(den, _DEN_EPS)
        pred = jnp.where(den > _DEN_EPS, pred, qm)
        out_ref[...] = clip_to_scale(pred, scale)


def _support_call(dev, msk, nb_idx, nb_w, q_means, *, scale, bt, interpret):
    b, k_len = nb_idx.shape
    width = dev.shape[2]
    row = lambda bb, j, kk, idx_ref, w_ref, qm_ref: (idx_ref[bb, kk], 0, j)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, width // bt, k_len),
        in_specs=[pl.BlockSpec((None, 1, bt), row),
                  pl.BlockSpec((None, 1, bt), row)],
        out_specs=pl.BlockSpec(
            (None, 1, bt), lambda bb, j, kk, *_: (bb, 0, j)),
        scratch_shapes=[pltpu.VMEM((1, bt), jnp.float32),
                        pltpu.VMEM((1, bt), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_support_kernel, k_len=k_len, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, width), jnp.float32),
        interpret=interpret,
    )(nb_idx, nb_w, q_means, dev, msk)
    return out[:, 0, :]


@functools.partial(jax.jit, static_argnames=("scale", "bt", "interpret"))
def fused_support_scores(dev: jnp.ndarray, msk: jnp.ndarray,
                         nb_idx: jnp.ndarray, nb_w: jnp.ndarray,
                         q_means: jnp.ndarray, *, scale: tuple,
                         bt: int = BT,
                         interpret: bool = False) -> jnp.ndarray:
    """(U, 1, W) deviation/mask tables × (b, k) neighbors → (b, W) scores.

    ``nb_w`` must be the masked weights (invalid/negative-score neighbors
    at 0 — a zero weight cancels both accumulators) and ``nb_idx`` must be
    clipped into ``[0, U)``; both are what the item index's scorer already
    prepares.  ``scale``: the ``(lo, hi)`` clip of the predictions
    (``repro.core.predict.rating_scale``).  Seen-item knockout is the
    caller's (it owns the ratings).
    The tables' width must be whole tiles: build them with
    :func:`support_rows` at :func:`support_width` for the same ``bt``.
    """
    width = dev.shape[2]
    bt_ = min(bt, width)
    assert width % bt_ == 0, (
        f"table width {width} is not whole {bt_}-wide tiles; build the "
        "tables with support_rows(..., support_width(n_items, bt))")
    nb_idx = nb_idx.astype(jnp.int32)
    nb_w = nb_w.astype(jnp.float32)
    q_means = q_means.astype(jnp.float32)
    outs = [_support_call(dev, msk, nb_idx[lo:lo + BB], nb_w[lo:lo + BB],
                          q_means[lo:lo + BB], scale=scale, bt=bt_,
                          interpret=interpret)
            for lo in range(0, nb_idx.shape[0], BB)]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)
