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

TPU formulation: a gather inside the grid step.  The (b, k) neighbor
ids are prefetched to SMEM (scalar prefetch), and the deviation/mask
tables stay in HBM (``memory_space=pl.ANY``).  A grid step is a group of
``G`` query rows × one item tile: it copies each row's k neighbor slices
of both tables into a ``(k, G, 1, tile)`` VMEM buffer with one async copy
per slice, double-buffered across steps (step s+1's copies start before
step s waits for its own), then loops over k in the body, accumulating
``acc += w[kk] · row[kk]`` in f32 on ``(G, ·)`` registers, with the
division/fallback/clip epilogue in-register.  The tables never leave HBM
except for the touched rows.

Layout: the tables are ``(U, 1, I)`` (:func:`support_rows` builds them).
Mosaic slices VMEM only in whole (8, 128) tiles along the last two dims,
so a one-row copy into a ``(k, G, tile)`` buffer is refused, while a
``(1, tile)`` slice of a ``(U, 1, I)`` table into a ``(…, 1, tile)``
buffer is whole in both.  The tables are stored in that layout once per
ratings array (a per-call reshape would copy both, ~198 MB at the ML-1M
shape).  SMEM holds 1 MiB and pads a 2-D operand's last dim to 128 lanes,
so one call takes at most ``BB`` query rows; larger batches loop over
row blocks.

Grid per row block: (⌈b/G⌉, I/tile), both sequential (the copies cross
steps).  The tile is the widest whole number of ``bt``-tiles whose
double-buffered rows fit ``GATHER_VMEM``.  Interpret mode runs on CPU and
is validated against ``repro.kernels.ref.support_scores_ref``, which also
evaluates the same tables wherever the kernel does not run.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.predict import clip_to_scale

_DEN_EPS = 1e-8

BT = 512            # the tables' width is whole bt-tiles
G = 8               # query rows a grid step: (8, ·) accumulators fill the sublanes
CW = 512            # lanes of accumulators held in registers at once
# the double-buffered (k, G, tile) neighbor rows of both tables
GATHER_VMEM = 12 << 20
VMEM_LIMIT = 32 << 20
ROWS = 2048         # rating rows laid out per step of the table build
# query rows per pallas_call: the (BB, k) neighbor ids, padded to 128
# lanes in SMEM, stay at 128 KiB of the 1 MiB SMEM
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


def _tile_width(width: int, k_len: int, bt: int) -> int:
    """The widest whole number of ``bt``-tiles that divides ``width`` and
    whose double-buffered neighbor rows fit ``GATHER_VMEM``."""
    per_col = 2 * 2 * G * k_len * 4          # slots · tables · rows · f32
    n = width // bt
    fits = [t for t in range(1, n + 1)
            if n % t == 0 and t * bt * per_col <= GATHER_VMEM]
    return (fits[-1] if fits else 1) * bt


def _support_kernel(idx_ref, w_ref, qm_ref, dev_hbm, msk_hbm, out_ref,
                    dev_buf, msk_buf, sems, *, k_len: int, bt: int,
                    scale: tuple):
    n_tiles = pl.num_programs(1)
    step = pl.program_id(0) * n_tiles + pl.program_id(1)
    slot = step % 2

    def copies(s, slot, act):
        """Start (or wait for) the copies of grid step ``s``: for each of
        its ``G`` query rows, its k neighbors' ``(1, bt)`` slices of both
        tables into ``slot`` of the row buffers."""
        g, col = s // n_tiles, (s % n_tiles) * bt

        def one(n, carry):
            r, kk = n // k_len, n % k_len
            nb = idx_ref[g * G + r, kk]
            for t, (hbm, buf) in enumerate(((dev_hbm, dev_buf),
                                            (msk_hbm, msk_buf))):
                act(pltpu.make_async_copy(
                    hbm.at[nb, :, pl.ds(col, bt)],
                    buf.at[slot, kk, r], sems.at[slot, t]))
            return carry

        jax.lax.fori_loop(0, G * k_len, one, 0)

    @pl.when(step == 0)
    def _first():
        copies(step, slot, lambda c: c.start())

    @pl.when(step + 1 < pl.num_programs(0) * n_tiles)
    def _prefetch():
        copies(step + 1, 1 - slot, lambda c: c.start())

    copies(step, slot, lambda c: c.wait())

    qm = qm_ref[...]
    cw = math.gcd(bt, CW)
    for c in range(0, bt, cw):
        def acc(kk, nd, c=c):
            w = w_ref[kk]
            return (nd[0] + w * dev_buf[slot, kk, :, 0, pl.ds(c, cw)],
                    nd[1] + w * msk_buf[slot, kk, :, 0, pl.ds(c, cw)])

        zero = jnp.zeros((G, cw), jnp.float32)
        num, den = jax.lax.fori_loop(0, k_len, acc, (zero, zero))
        pred = qm + num / jnp.maximum(den, _DEN_EPS)
        pred = jnp.where(den > _DEN_EPS, pred, qm)
        out_ref[:, pl.ds(c, cw)] = clip_to_scale(pred, scale)


def _support_call(dev, msk, nb_idx, nb_w, q_means, *, scale, bt, interpret):
    b, k_len = nb_idx.shape
    width = dev.shape[2]
    tile = _tile_width(width, k_len, bt)
    pad = -b % G
    nb_idx = jnp.pad(nb_idx, ((0, pad), (0, 0)))
    w_cols = jnp.pad(nb_w, ((0, pad), (0, 0))).T[:, :, None]
    qm_col = jnp.pad(q_means, (0, pad))[:, None]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=((b + pad) // G, width // tile),
        in_specs=[pl.BlockSpec((k_len, G, 1), lambda g, j, _: (0, g, 0)),
                  pl.BlockSpec((G, 1), lambda g, j, _: (g, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((G, tile), lambda g, j, _: (g, j)),
        scratch_shapes=[pltpu.VMEM((2, k_len, G, 1, tile), jnp.float32),
                        pltpu.VMEM((2, k_len, G, 1, tile), jnp.float32),
                        pltpu.SemaphoreType.DMA((2, 2))],
    )
    out = pl.pallas_call(
        functools.partial(_support_kernel, k_len=k_len, bt=tile,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b + pad, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(nb_idx, w_cols, qm_col, dev, msk)
    return out[:b]


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
