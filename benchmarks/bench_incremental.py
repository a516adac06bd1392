"""Incremental neighbor maintenance vs cold recompute (the facade's claim).

A 1% rating delta folded with ``CFEngine.update_ratings`` must be ≥5× faster
than refitting from scratch, while staying bit-identical (checked once via
the oracle).  Timing follows bench_kernels.py conventions: one warm-up call
to compile each executable, then the mean of ``reps`` timed calls.

``run_cache`` additionally times the delta-aware cache maintenance on the
approx engine: a small delta used to invalidate every derived per-ratings
cache (int8 gather operand, host CSR, bucketed pair tables) wholesale —
the patched version chain keeps them warm, which is what makes tiny-delta
update streams cheap (the ROADMAP "incremental-update batching" item).

    PYTHONPATH=src python benchmarks/bench_incremental.py
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import predict as pred_mod
from repro.core.facade import CFEngine
from repro.data import load_ml1m_synthetic
from repro.index import IndexConfig


def _deltas(rng, n_users, n_items, frac, per_user, count):
    """Pre-generate ``count`` delta batches touching ``frac`` of users."""
    out = []
    for _ in range(count):
        us = rng.choice(n_users, max(int(n_users * frac), 1), replace=False)
        uids = np.repeat(us, per_user).astype(np.int32)
        iids = rng.integers(0, n_items, uids.size).astype(np.int32)
        vals = rng.integers(1, 6, uids.size).astype(np.float32)
        out.append((uids, iids, vals))
    return out


def run(n_users=2048, n_items=512, k=10, frac=0.01, reps=5):
    rng = np.random.default_rng(0)
    train, _, _ = load_ml1m_synthetic(n_users=n_users, n_items=n_items,
                                      seed=0)
    eng = CFEngine(jnp.asarray(train), measure="pcc", k=k,
                   block_size=256).fit()

    # correctness once: the timed path must be the exact path
    uids, iids, vals = _deltas(rng, n_users, n_items, frac, 4, 1)[0]
    assert eng.update_ratings(uids, iids, vals, oracle_check=True).oracle_ok

    # warm-up compiled both the full fit and all update executables above;
    # time the cold recompute
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(eng._topk(eng.ratings)[0])
    full_s = (time.perf_counter() - t0) / reps

    # time incremental updates (fresh deltas each rep — realistic stream)
    batches = _deltas(rng, n_users, n_items, frac, 4, reps)
    stats = []
    t0 = time.perf_counter()
    for uids, iids, vals in batches:
        stats.append(eng.update_ratings(uids, iids, vals))
    inc_s = (time.perf_counter() - t0) / reps

    affected = np.mean([s.n_affected for s in stats])
    return [
        (f"full_refit_U{n_users}_k{k}", full_s * 1e3, "ms"),
        (f"incremental_{frac:.0%}_delta", inc_s * 1e3,
         f"ms (mean {affected:.0f}/{n_users} rows recomputed)"),
        ("speedup", full_s / inc_s, "x (target ≥5)"),
    ]


def run_cache(n_users=8192, n_items=None, k=10, per_user=4, reps=3):
    """Delta-aware cache patching vs wholesale invalidation.

    Two views of the same change: the *end-to-end* rows fold identical
    tiny deltas through the approx engine, with the "wholesale" arm
    dropping the derived caches before every update (the
    pre-patch identity-invalidation behavior) and re-warming them after
    (the cost wholesale invalidation pushes onto the next serving query);
    the *refresh* rows isolate the cache maintenance itself — a full
    cold rebuild of the index's CSR / pair tables and the engine's gather
    code vs the version-chain row patch of each for an 8-user delta.
    """
    rng = np.random.default_rng(0)
    train, _, _ = load_ml1m_synthetic(n_users=n_users, n_items=n_items,
                                      seed=0)
    n_items = train.shape[1]
    eng = CFEngine(jnp.asarray(train), measure="cosine", k=k,
                   neighbor_mode="approx",
                   index_cfg=IndexConfig(seed=0, features="raw")).fit()
    ix = eng.index

    def warm():
        ix._ratings_csr(eng.ratings)
        ix._item_tables(eng.ratings)
        eng._gather_source(eng.ratings)

    warm()
    frac = 8 / n_users                         # ~8 touched users per delta
    eng.update_ratings(*_deltas(rng, n_users, n_items, frac, per_user,
                                1)[0])         # compile the update path

    batches = _deltas(rng, n_users, n_items, frac, per_user, 2 * reps)
    t0 = time.perf_counter()
    for uids, iids, vals in batches[:reps]:
        eng.update_ratings(uids, iids, vals)
        assert ix.last_refold.caches_patched >= 2
    patched_s = (time.perf_counter() - t0) / reps

    t0 = time.perf_counter()
    for uids, iids, vals in batches[reps:]:
        ix._csr_cache = None                   # the pre-patch behavior:
        eng._gather_cache = None               # identity invalidation
        eng.update_ratings(uids, iids, vals)
        warm()                                 # re-warm for serving
    wholesale_s = (time.perf_counter() - t0) / reps

    # isolated cache refresh: cold rebuild vs version-chain row patch
    t0 = time.perf_counter()
    for _ in range(reps):
        ix._csr_cache = None
        eng._gather_cache = None
        warm()
    cold_s = (time.perf_counter() - t0) / reps
    ratings = eng.ratings
    src = eng._gather_source(ratings)
    t0 = time.perf_counter()
    for uids, iids, vals in batches[:reps]:
        ratings = ratings.at[jnp.asarray(uids),
                             jnp.asarray(iids)].set(jnp.asarray(vals))
        n = ix._patch_row_caches(ratings, np.unique(uids),
                                 ix._ratings_version + 1)
        assert n >= 2
        src = pred_mod.patch_gather_source(src, ratings,
                                           jnp.asarray(np.unique(uids)))
    patch_s = (time.perf_counter() - t0) / reps

    return [
        (f"cache_patched_update_U{n_users}", patched_s * 1e3, "ms"),
        (f"cache_wholesale_update_U{n_users}", wholesale_s * 1e3, "ms"),
        ("cache_patch_update_speedup", wholesale_s / patched_s, "x"),
        (f"cache_refresh_cold_U{n_users}", cold_s * 1e3, "ms"),
        (f"cache_refresh_patched_U{n_users}", patch_s * 1e3, "ms"),
        ("cache_refresh_speedup", cold_s / patch_s, "x"),
    ]


def main():
    print("name,value,unit")
    for name, val, unit in run():
        print(f"{name},{val:.2f},{unit}")
    for name, val, unit in run_cache():
        print(f"{name},{val:.2f},{unit}")


if __name__ == "__main__":
    main()
