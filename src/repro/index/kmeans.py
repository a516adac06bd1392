"""Blocked mini-batch k-means over mean-centered rating rows.

The clustered candidate-generation index partitions users by taste: each
user's dense rating row is mean-centered over its *rated* entries
(``z = (r - mean_u) · 1[r > 0]``, so a zero stays "no information" rather
than "strong dislike") and Lloyd iterations run over fixed-order user
blocks — the mini-batches — folding per-cluster sums/counts on device and
updating centroids once per sweep.  Because every block is folded every
iteration in a fixed order, the result is deterministic per
``(seed, shape)``: same centroids, same assignments, bit for bit.

Empty clusters are re-seeded deterministically to the rows *farthest* from
their current centroid (ties broken by lowest row id), the standard
farthest-point repair that keeps all ``n_clusters`` partitions live.

Distances go through :func:`repro.kernels.cluster.centroid_distances` —
the fused Pallas kernel on TPU, the jnp oracle elsewhere.

Sharded fit
-----------
The blocked sweep is a per-row fold — exactly the shape ``shard_map``
wants.  With ``mesh=`` the rows shard over a mesh axis, every device runs
the same blocked scan over its shard, and the per-cluster sums/counts
``psum`` across the axis; assignments/distances stay row-sharded and
gather on the host.  The centroid update and the deterministic
farthest-point reseed are global reductions over gathered per-row state,
so they are unchanged.  On a 1-device mesh the shard is the whole array
and the scan order is identical, so the fit is **bit-identical** to the
unsharded path; on P devices the per-shard partial sums reduce in a
different order, so centroids agree to float rounding (deterministic per
``(seed, shape, P)``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.kernels.cluster import centroid_distances


def center_rows(ratings: jnp.ndarray, means: jnp.ndarray) -> jnp.ndarray:
    """Mean-centered rating rows: rated cells become (r - mean), rest 0."""
    return jnp.where(ratings > 0, ratings - means[:, None], 0.0)


def normalize_rows(z: jnp.ndarray, eps: float = 1e-8) -> jnp.ndarray:
    """L2-normalize rows (spherical k-means feature map).

    A raw centered row's norm grows with the user's *activity* (√#rated),
    so Euclidean k-means on raw rows clusters by rating count — one giant
    near-origin cluster of typical users.  Similarity search cares about
    taste *direction*, so the index clusters unit rows by default.
    """
    n = jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True))
    return z / jnp.maximum(n, eps)


@dataclasses.dataclass
class KMeansStats:
    """What one ``kmeans`` run did (the re-seed count drives a test)."""
    iters: int
    n_reseeds: int
    inertia: float          # sum of squared distances to assigned centroids


@functools.partial(jax.jit, static_argnames=("block_size", "n_clusters",
                                             "use_kernel", "interpret"))
def _sweep(z, valid, centroids, *, block_size, n_clusters, use_kernel,
           interpret):
    """One blocked Lloyd sweep: assign every row, fold cluster sums/counts.

    ``z`` is padded to a multiple of ``block_size``; ``valid`` masks the
    padding rows out of the fold (their assignment is scattered with
    ``mode='drop'`` via an out-of-range cluster id).
    """
    d_feat = z.shape[1]
    blocks = z.reshape(-1, block_size, d_feat)
    vblocks = valid.reshape(-1, block_size)

    def body(carry, inp):
        sums, counts = carry
        blk, vb = inp
        d = centroid_distances(blk, centroids, use_kernel=use_kernel,
                               interpret=interpret)
        a = jnp.argmin(d, axis=1).astype(jnp.int32)   # ties → lowest id
        bd = jnp.min(d, axis=1)
        a_fold = jnp.where(vb, a, n_clusters)          # padding → dropped
        sums = sums.at[a_fold].add(blk, mode="drop")
        counts = counts.at[a_fold].add(1, mode="drop")
        return (sums, counts), (a, bd)

    init = (jnp.zeros((n_clusters, d_feat), jnp.float32),
            jnp.zeros((n_clusters,), jnp.int32))
    (sums, counts), (assign, best_d) = jax.lax.scan(body, init,
                                                    (blocks, vblocks))
    return (sums, counts, assign.reshape(-1), best_d.reshape(-1))


def _pad_rows(z: jnp.ndarray, block_size: int, mult: int = 1):
    n = z.shape[0]
    unit = block_size * mult
    rem = n % unit
    valid = np.zeros((n + (unit - rem if rem else 0),), bool)
    valid[:n] = True
    if rem:
        z = jnp.pad(z, ((0, unit - rem), (0, 0)))
    return z, jnp.asarray(valid)


@functools.lru_cache(maxsize=16)
def _sharded_sweep(mesh, axis: str, *, block_size: int, n_clusters: int,
                   use_kernel: bool, interpret: bool):
    """Build (and cache) the shard_mapped blocked sweep for a mesh axis:
    rows sharded, centroids replicated, sums/counts psum-reduced across
    the axis, assignments/distances returned row-sharded."""

    def local(z_s, valid_s, centroids):
        sums, counts, assign, best_d = _sweep(
            z_s, valid_s, centroids, block_size=block_size,
            n_clusters=n_clusters, use_kernel=use_kernel,
            interpret=interpret)
        return (jax.lax.psum(sums, axis), jax.lax.psum(counts, axis),
                assign, best_d)

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(axis), P()),
        out_specs=(P(), P(), P(axis), P(axis)), check_vma=False))


def kmeans(z: jnp.ndarray, n_clusters: int, *, seed: int = 0, iters: int = 8,
           block_size: int = 2048, use_kernel: bool = False,
           interpret: bool = False, mesh=None, axis: str = "data"
           ) -> Tuple[jnp.ndarray, np.ndarray, np.ndarray, KMeansStats]:
    """Deterministic blocked k-means, optionally sharded over a mesh.

    Returns ``(centroids (C, D), assign (U,), best_dist (U,), stats)`` where
    ``assign[u]`` is the canonical nearest centroid of row ``u`` (ties →
    lowest cluster id) and ``best_dist[u]`` its squared distance — the
    invariant the index's refold certificate maintains under updates.

    With ``mesh`` the blocked sweep runs under ``shard_map`` with rows
    partitioned over ``axis`` (see module docstring): bit-identical on a
    1-device mesh, float-rounding-identical (and deterministic) beyond.
    """
    n_rows, d_feat = z.shape
    if not 1 <= n_clusters <= n_rows:
        raise ValueError(f"need 1 <= n_clusters <= {n_rows}, "
                         f"got {n_clusters}")
    n_shards = 1
    if mesh is not None:
        n_shards = int(np.prod([mesh.shape[a] for a in mesh.axis_names
                                if a == axis]))
    block_size = min(block_size, max(n_rows // max(n_shards, 1), 1))
    rng = np.random.default_rng(seed)
    init_rows = np.sort(rng.choice(n_rows, size=n_clusters, replace=False))
    centroids = z[jnp.asarray(init_rows)]

    z_p, valid = _pad_rows(z, block_size, mult=n_shards)
    if mesh is not None:
        sweep = _sharded_sweep(mesh, axis, block_size=block_size,
                               n_clusters=n_clusters, use_kernel=use_kernel,
                               interpret=interpret)
    else:
        sweep = functools.partial(
            _sweep, block_size=block_size, n_clusters=n_clusters,
            use_kernel=use_kernel, interpret=interpret)
    n_reseeds = 0
    with obs.span("kmeans.fit", n_rows=n_rows, n_clusters=n_clusters,
                  iters=iters, n_shards=n_shards) as sp:
        for _ in range(iters):
            sums, counts, assign, best_d = sweep(z_p, valid, centroids)
            counts_np = np.asarray(counts)
            new_c = np.asarray(sums) / np.maximum(counts_np, 1)[:, None]
            empty = np.nonzero(counts_np == 0)[0]
            if len(empty):
                # farthest-point re-seed: rows worst-served by their
                # centroid, lowest row id on ties — deterministic
                bd = np.asarray(best_d)[:n_rows]
                donors = np.lexsort((np.arange(n_rows), -bd))[:len(empty)]
                new_c[empty] = np.asarray(z)[donors]
                n_reseeds += len(empty)
            centroids = jnp.asarray(new_c, jnp.float32)

        # final canonical assignment against the converged centroids
        _, _, assign, best_d = sweep(z_p, valid, centroids)
        assign = np.array(assign[:n_rows])     # writable host copies: the
        best_d = np.array(best_d[:n_rows])     # index repairs them in place
        sp.set_attr("n_reseeds", n_reseeds)
    stats = KMeansStats(iters=iters, n_reseeds=n_reseeds,
                        inertia=float(best_d.sum()))
    reg = obs.registry()
    reg.histogram("kmeans.fit.seconds").observe(sp.duration)
    reg.gauge("kmeans.inertia").set(stats.inertia)
    reg.gauge("kmeans.reseeds").set(n_reseeds)
    return centroids, assign, best_d, stats
