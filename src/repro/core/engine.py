"""The paper's multi-threaded engine, recast as mesh-sharded SPMD.

The paper partitions query users across OS threads.  Here the partition is
across mesh devices via ``jax.shard_map``; two engines are provided:

* ``sharded_topk``      — query users shard over an axis, every device holds
                          the full candidate rating matrix (the direct
                          analogue of the paper's shared-memory threads).
* ``ring_sharded_topk`` — query users AND candidate users are sharded; the
                          candidate shard rotates around the axis with
                          ``jax.lax.ppermute`` (systolic ring), so no device
                          ever holds the full matrix.  This is the production
                          form for user counts that exceed one device's HBM,
                          and it overlaps each tile's matmuls with the
                          neighbor-to-neighbor transfer of the next shard.

Both are exact: results are bit-identical to the sequential engine
(`topk_neighbors` on one device), which is the paper's correctness claim.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.core import neighbors as nb
from repro.core import predict as pred_mod
from repro.core.similarity import user_means


def _block_topk_local(q_block, cand_block, k, measure, q_offset, cand_offset,
                      n_valid_cand, block_size, beta=None):
    """block_topk against one candidate shard with global-id bookkeeping."""
    return nb.block_topk(
        q_block, cand_block, k, measure=measure, q_offset=q_offset,
        cand_offset=cand_offset,
        block_size=min(block_size, cand_block.shape[0]), beta=beta)


def sharded_topk(ratings: jnp.ndarray, k: int, mesh: Mesh, *,
                 measure: str = "pcc", axis: str = "data",
                 block_size: int = 1024, beta: float | None = None,
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Paper-faithful engine: shard queries over ``axis``, replicate candidates.

    ``ratings`` (U, I) with U divisible by the axis size.  Returns (U, k)
    scores and neighbor ids, identical to the single-device result.
    """
    n_users = ratings.shape[0]
    axis_size = mesh.shape[axis]
    if n_users % axis_size != 0:
        raise ValueError(f"U={n_users} must divide over axis {axis}={axis_size}")
    shard = n_users // axis_size

    def per_shard(q_block, all_ratings):
        i = jax.lax.axis_index(axis)
        return _block_topk_local(q_block, all_ratings, k, measure,
                                 i * shard, 0, n_users, block_size, beta)

    f = jax.shard_map(per_shard, mesh=mesh,
                      in_specs=(P(axis, None), P(None, None)),
                      out_specs=(P(axis, None), P(axis, None)),
                      check_vma=False)
    return f(ratings, ratings)


def ring_sharded_topk(ratings: jnp.ndarray, k: int, mesh: Mesh, *,
                      measure: str = "pcc", axis: str = "data",
                      block_size: int = 1024, beta: float | None = None,
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Systolic engine: candidates rotate around the ring; O(U/P) memory/device.

    Each of the P devices starts with its own candidate shard and, for P
    steps, computes its query-block × current-shard tile then passes the
    shard to the next device.  The running top-k merge is associative, so the
    result equals the sequential engine exactly.
    """
    n_users = ratings.shape[0]
    axis_size = mesh.shape[axis]
    if n_users % axis_size != 0:
        raise ValueError(f"U={n_users} must divide over axis {axis}={axis_size}")
    shard = n_users // axis_size

    def per_shard(q_block):
        i = jax.lax.axis_index(axis)
        q_offset = i * shard
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

        def body(carry, step):
            best_s, best_i, cand = carry
            # candidate shard currently held started at device (i - step) % P
            src = jnp.mod(i - step, axis_size)
            s, ids = _block_topk_local(q_block, cand, k, measure, q_offset,
                                       src * shard, shard, block_size, beta)
            best_s, best_i = nb.merge_topk(best_s, best_i, s, ids, k)
            cand = jax.lax.ppermute(cand, axis, perm)
            return (best_s, best_i, cand), ()

        init = (jnp.full((shard, k), nb.NEG_INF, jnp.float32),
                jnp.full((shard, k), -1, jnp.int32), q_block)
        (best_s, best_i, _), _ = jax.lax.scan(
            body, init, jnp.arange(axis_size))
        return best_s, best_i

    f = jax.shard_map(per_shard, mesh=mesh,
                      in_specs=(P(axis, None),),
                      out_specs=(P(axis, None), P(axis, None)),
                      check_vma=False)
    return f(ratings)


def sharded_predict(ratings: jnp.ndarray, scores: jnp.ndarray,
                    idx: jnp.ndarray, mesh: Mesh, *, axis: str = "data"
                    ) -> jnp.ndarray:
    """Mean-centered neighbor prediction with query users sharded over ``axis``."""
    means = user_means(ratings)
    scale = pred_mod.rating_scale(ratings)

    def per_shard(scores_blk, idx_blk, all_ratings, all_means):
        i = jax.lax.axis_index(axis)
        m = scores_blk.shape[0]
        qm = jax.lax.dynamic_slice_in_dim(all_means, i * m, m)
        return pred_mod.predict_from_neighbors(
            all_ratings, scores_blk, idx_blk, means=all_means, query_means=qm,
            scale=scale)

    f = jax.shard_map(per_shard, mesh=mesh,
                      in_specs=(P(axis, None), P(axis, None),
                                P(None, None), P(None)),
                      out_specs=P(axis, None), check_vma=False)
    return f(scores, idx, ratings, means)


def ring_sharded_predict(ratings: jnp.ndarray, scores: jnp.ndarray,
                         idx: jnp.ndarray, mesh: Mesh, *, axis: str = "data",
                         ) -> jnp.ndarray:
    """Production-scale prediction: ratings stay sharded; shards rotate.

    The mean-centred weighted predictor is recast as two masked matmuls per
    arriving candidate shard (DESIGN.md §2): a (m, shard) neighbor-weight
    matrix (scatter of the top-k weights whose ids fall in the shard's user
    range) times the shard's deviation/mask matrices, accumulated over the
    full ring rotation.  Exactly equals ``predict_from_neighbors``.
    """
    n_users, n_items = ratings.shape
    axis_size = mesh.shape[axis]
    if n_users % axis_size != 0:
        raise ValueError(f"U={n_users} must divide over axis {axis}={axis_size}")
    shard = n_users // axis_size

    def per_shard(q_ratings, w, nb_idx):
        i = jax.lax.axis_index(axis)
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        m = q_ratings.shape[0]

        # global mean for zero-raters (psum over the ring)
        loc_cnt = jnp.sum(q_ratings > 0)
        loc_tot = jnp.sum(q_ratings)
        g_cnt = jax.lax.psum(loc_cnt, axis)
        g_tot = jax.lax.psum(loc_tot, axis)
        global_mean = g_tot / jnp.maximum(g_cnt, 1)
        # the rating scale: smallest and largest rated value of any shard
        rated = q_ratings > 0
        lo = jax.lax.pmin(jnp.min(jnp.where(rated, q_ratings, jnp.inf)), axis)
        hi = jax.lax.pmax(jnp.max(jnp.where(rated, q_ratings, -jnp.inf)),
                          axis)

        def means_of(block):
            mask = block > 0
            cnt = jnp.sum(mask, axis=-1)
            tot = jnp.sum(block, axis=-1)
            return jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1), global_mean)

        my_means = means_of(q_ratings)
        w_pos = jnp.where((w > 0) & (nb_idx >= 0), w, 0.0)    # (m, k)

        def body(carry, step):
            num, den, cand = carry
            src = jnp.mod(i - step, axis_size)
            rel = nb_idx - src * shard                         # (m, k)
            valid = (rel >= 0) & (rel < shard)
            wv = jnp.where(valid, w_pos, 0.0)
            rows = jnp.broadcast_to(jnp.arange(m)[:, None], rel.shape)
            wmat = jnp.zeros((m, shard), jnp.float32).at[
                rows, rel.clip(0, shard - 1)].add(wv)
            mask = (cand > 0).astype(jnp.float32)
            dev = (cand - means_of(cand)[:, None]) * mask
            num = num + wmat @ dev
            den = den + wmat @ mask
            cand = jax.lax.ppermute(cand, axis, perm)
            return (num, den, cand), ()

        init = (jnp.zeros((m, n_items), jnp.float32),
                jnp.zeros((m, n_items), jnp.float32), q_ratings)
        (num, den, _), _ = jax.lax.scan(body, init, jnp.arange(axis_size))
        pred = my_means[:, None] + num / jnp.maximum(den, 1e-8)
        pred = jnp.where(den > 1e-8, pred, my_means[:, None])
        return jnp.where(lo <= hi, jnp.clip(pred, lo, hi), pred)

    f = jax.shard_map(per_shard, mesh=mesh,
                      in_specs=(P(axis, None), P(axis, None), P(axis, None)),
                      out_specs=P(axis, None), check_vma=False)
    return f(ratings, scores, idx)


@functools.lru_cache(maxsize=None)
def local_mesh(n_devices: int | None = None, axis: str = "data") -> Mesh:
    """1-axis mesh over ``n_devices`` local devices (default: all) — the
    chips of a TPU host, or fake CPU devices in tests."""
    n = n_devices or len(jax.devices())
    return compat.make_mesh((n,), (axis,))
