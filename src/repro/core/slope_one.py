"""(Weighted) Slope One — the paper's cited prior art (ref [12]).

Karydi & Margaritis's multithreaded Slope One is the comparison point the
paper builds on (5–9× at 16 threads).  Implementing it makes the baseline
family complete: Slope One is *item*-based (a deviation matrix between item
pairs), so its parallel axis is items where UserCF's is users — the same
partition-over-independent-outputs structure, rotated 90°.

    dev(i, j) = Σ_{u rated both} (r_ui − r_uj) / |co-raters(i, j)|
    pred(u, i) = Σ_{j∈rated(u)} c_ij · (dev(i, j) + r_uj) / Σ_j c_ij

Both phases are masked matmuls over the item axis (MXU-friendly, same
DESIGN.md §2 move): the deviation/count matrices come from three Gram-style
products, prediction from two more.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



@functools.partial(jax.jit, static_argnames=())
def deviation_matrix(ratings: jnp.ndarray) -> Tuple[jnp.ndarray,
                                                     jnp.ndarray]:
    """ratings (U, I) with 0 = unrated → (dev (I, I), counts (I, I)).

    dev[i, j] = mean over co-raters of (r_ui − r_uj); counts[i, j] = number
    of co-raters.  Three matmuls: Mᵀ·M, Rᵀ·M, Mᵀ·R.
    """
    r = ratings.astype(jnp.float32)
    m = (r > 0).astype(jnp.float32)
    counts = m.T @ m                                   # (I, I)
    sum_i = r.T @ m                                    # Σ r_ui over co-raters
    sum_j = m.T @ r                                    # Σ r_uj over co-raters
    dev = (sum_i - sum_j) / jnp.maximum(counts, 1.0)
    return dev, counts


@jax.jit
def predict(ratings: jnp.ndarray, dev: jnp.ndarray, counts: jnp.ndarray
            ) -> jnp.ndarray:
    """Weighted Slope One prediction for every (user, item) cell."""
    r = ratings.astype(jnp.float32)
    m = (r > 0).astype(jnp.float32)
    # num[u, i] = Σ_j m[u, j]·c_ij·(dev_ij + r_uj)
    #           = Σ_j c_ij·dev_ij·m[u, j] + Σ_j c_ij·r_uj
    num = m @ (counts * dev).T + r @ counts.T
    den = m @ counts.T
    pred = num / jnp.maximum(den, 1e-8)
    fallback = jnp.sum(r, axis=1, keepdims=True) / \
        jnp.maximum(jnp.sum(m, axis=1, keepdims=True), 1.0)
    pred = jnp.where(den > 1e-8, pred, fallback)
    return jnp.clip(pred, 1.0, 5.0)


def sharded_deviation(ratings: jnp.ndarray, mesh: Mesh, *,
                      axis: str = "data") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Item-sharded deviation build: each shard owns a block of item ROWS.

    The multithreaded Slope One of the paper's ref [12]: threads partition
    the item axis; each computes dev[i_block, :].  Exact, like the UserCF
    engines.
    """
    n_items = ratings.shape[1]
    axis_size = mesh.shape[axis]
    if n_items % axis_size != 0:
        raise ValueError(f"I={n_items} must divide axis {axis}={axis_size}")

    def per_shard(r_block_t, full_r):
        # r_block_t: (I/P, U) — this shard's item rows (transposed view)
        m_block = (r_block_t > 0).astype(jnp.float32)
        full_m = (full_r > 0).astype(jnp.float32)
        counts = m_block @ full_m                       # (I/P, I)
        sum_i = r_block_t @ full_m
        sum_j = m_block @ full_r
        dev = (sum_i - sum_j) / jnp.maximum(counts, 1.0)
        return dev, counts

    f = jax.shard_map(per_shard, mesh=mesh,
                      in_specs=(P(axis, None), P(None, None)),
                      out_specs=(P(axis, None), P(axis, None)),
                      check_vma=False)
    rt = ratings.T.astype(jnp.float32)
    return f(rt, ratings.astype(jnp.float32))


class SlopeOne:
    """fit/predict/evaluate API mirroring UserCF."""

    def __init__(self, mesh: Mesh | None = None):
        self.mesh = mesh
        self.dev = None
        self.counts = None

    def fit(self, ratings: jnp.ndarray):
        if self.mesh is None:
            self.dev, self.counts = deviation_matrix(ratings)
        else:
            self.dev, self.counts = sharded_deviation(ratings, self.mesh)
        return self

    def predict(self, ratings: jnp.ndarray) -> jnp.ndarray:
        if self.dev is None:
            raise RuntimeError("call fit() first")
        return predict(ratings, self.dev, self.counts)

    def evaluate(self, train: jnp.ndarray, test: jnp.ndarray) -> dict:
        from repro.core import metrics
        pred = self.predict(train)
        mask = test > 0
        out = {"mae": metrics.mae(pred, test, mask),
               "rmse": metrics.rmse(pred, test, mask)}
        out.update(metrics.precision_recall_f1(pred, test, mask=mask))
        return {k: float(v) for k, v in out.items()}
