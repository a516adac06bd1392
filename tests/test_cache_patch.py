"""Delta-aware cache maintenance: patched caches equal cold rebuilds.

``update_ratings`` used to invalidate every derived per-ratings cache
(the engine's int8 gather code, the user index's host CSR and bucketed
pair tables, the item stage's support tables) wholesale — even for a
1-rating delta.  These tests pin the
version-chain patching: after a stream of updates each cache must equal
what a cold rebuild against the current ratings produces, and a broken
chain (a ratings array the index never saw) must fall back to rebuilds.
"""

import jax.numpy as jnp
import numpy as np

from repro.core import predict as pred_mod
from repro.core import similarity as sim
from repro.core.facade import CFEngine
from repro.index import (ClusteredIndex, IndexConfig, ItemClusteredIndex,
                         ItemIndexConfig)
from repro.kernels.support import support_rows, support_width


def _ratings(rng, u, d, density=0.4):
    return jnp.asarray((rng.integers(1, 6, (u, d))
                        * (rng.random((u, d)) < density)).astype(np.float32))


def _delta(rng, n_users, n_items, n):
    us = rng.choice(n_users, n, replace=False).astype(np.int32)
    return (us, rng.integers(0, n_items, n).astype(np.int32),
            rng.integers(0, 6, n).astype(np.float32))


def _assert_src_equal(got, want):
    assert (got.step, got.scale) == (want.step, want.scale)
    np.testing.assert_array_equal(np.asarray(got.code),
                                  np.asarray(want.code))


def _assert_csr_equal(got, want):
    for g, w, name in zip(got, want, ("indptr", "indices", "data")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


def test_engine_caches_patched_across_updates(rng):
    """Approx engine: the index's CSR and pair tables and the engine's
    gather code survive a stream of deltas by patching and stay
    bit-equal to cold rebuilds."""
    r = _ratings(rng, 128, 64)
    eng = CFEngine(r, measure="cosine", k=6, neighbor_mode="approx",
                   index_cfg=IndexConfig(n_clusters=8, seed=0,
                                         features="raw",
                                         refit_reassign_frac=0.0)).fit()
    ix = eng.index
    # warm every cache on the fitted ratings
    ix._ratings_csr(eng.ratings)
    ix._item_tables(eng.ratings)
    for _ in range(4):
        st = eng.update_ratings(*_delta(rng, 128, 64, 5))
        rf = ix.last_refold
        assert rf.caches_patched >= 2, rf
        # patched caches are keyed to the *current* ratings array...
        assert ix._csr_cache[0] is eng.ratings
        assert eng._gather_cache[0] is eng.ratings
        # ...and bit-equal to cold rebuilds
        cold = ClusteredIndex(IndexConfig(n_clusters=8, seed=0,
                                          features="raw"))
        _assert_csr_equal(ix._csr_cache[1],
                          cold._ratings_csr(eng.ratings))
        _assert_src_equal(eng._gather_cache[1],
                          pred_mod.make_gather_source(eng.ratings))
        b_got, l_got, t_got = ix._csr_cache[2]
        b_want, l_want, t_want = cold._item_tables(eng.ratings)
        np.testing.assert_array_equal(b_got, b_want)
        np.testing.assert_array_equal(l_got, l_want)
        assert set(t_got) == set(t_want)
        for b in t_want:
            np.testing.assert_array_equal(np.asarray(t_got[b][0]),
                                          np.asarray(t_want[b][0]))
            np.testing.assert_array_equal(np.asarray(t_got[b][1]),
                                          np.asarray(t_want[b][1]))


def test_engine_gather_cache_patched(rng):
    """The facade's recommend gather operand follows the version chain."""
    r = _ratings(rng, 64, 48)
    eng = CFEngine(r, measure="cosine", k=5).fit()
    eng.recommend(n=4)                      # warms the gather cache
    assert eng._gather_cache is not None
    eng.update_ratings(*_delta(rng, 64, 48, 3))
    assert eng._gather_cache[0] is eng.ratings
    _assert_src_equal(eng._gather_cache[1],
                      pred_mod.make_gather_source(eng.ratings))


def test_gather_patch_int8_fallout(rng):
    """A delta that breaks the code's exactness must rebuild, not
    mis-patch: a half star moves an integer code to step 0.5, a value on
    no power-of-two grid to the f32 matrix."""
    r = _ratings(rng, 32, 16)
    src = pred_mod.make_gather_source(r)
    assert src.code.dtype == jnp.int8 and src.step == 1.0
    r2 = r.at[3, 2].set(2.5)                # a half star
    patched = pred_mod.patch_gather_source(
        src, r2, jnp.asarray([3], jnp.int32))
    assert patched.code.dtype == jnp.int8 and patched.step == 0.5
    np.testing.assert_array_equal(np.asarray(patched.code),
                                  np.asarray(r2 * 2).astype(np.int8))
    r3 = r2.at[5, 1].set(2.3)               # on no power-of-two grid
    patched = pred_mod.patch_gather_source(
        patched, r3, jnp.asarray([5], jnp.int32))
    assert patched.code.dtype == jnp.float32 and patched.step == 0.0
    np.testing.assert_array_equal(np.asarray(patched.code), np.asarray(r3))


def test_item_index_support_caches_patched(rng):
    """The item stage's (U, 1, W) support tables patch under updates by a
    row scatter, equal a cold ``support_rows``, and answer as a stage
    that builds its own tables and gather code."""
    r = _ratings(rng, 96, 48)
    eng = CFEngine(r, measure="pcc", k=6, recommend_mode="approx",
                   item_index_cfg=ItemIndexConfig(shortlist=16)).fit()
    it = eng.item_index
    users = np.arange(16)
    eng.recommend(users, n=5)               # builds the tables
    for _ in range(3):
        eng.update_ratings(*_delta(rng, 96, 48, 4))
        key, (dev, msk) = it._tables
        assert key is eng.ratings
        want_d, want_m = support_rows(eng.ratings, eng.means,
                                      support_width(48))
        np.testing.assert_array_equal(np.asarray(dev), np.asarray(want_d))
        np.testing.assert_array_equal(np.asarray(msk), np.asarray(want_m))
        # behaviour check: recommendations from the patched tables and
        # code match a stage that derives both cold
        s1, i1 = eng.recommend(users, n=5, mode="approx")
        s2, i2 = ItemClusteredIndex(it.cfg).recommend(
            eng.ratings, eng.means, eng.scores, eng.idx, users, n=5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_item_stage_tables_dropped_off_the_chain(rng):
    """A patch from an array the tables were not built on drops them
    (never patches a stranger's tables); the next recommend rebuilds
    them cold against the current ratings."""
    r = _ratings(rng, 64, 32)
    eng = CFEngine(r, measure="cosine", k=5, recommend_mode="approx",
                   item_index_cfg=ItemIndexConfig(shortlist=8)).fit()
    it = eng.item_index
    eng.recommend(np.arange(8), n=4)
    foreign = jnp.asarray(np.asarray(r).copy())
    ids = jnp.asarray([2, 64], jnp.int32)          # padded with U
    assert not it.patch(foreign, eng.ratings, eng.means, ids)
    assert it._tables is None
    eng.recommend(np.arange(8), n=4)
    key, tables = it._tables
    assert key is eng.ratings
    want = support_rows(eng.ratings, eng.means, support_width(32))
    for got, w in zip(tables, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(w))


def test_broken_chain_drops_caches(rng):
    """A refold outside the version chain (foreign ratings array) must
    not patch — the caches drop and rebuild cold on next use."""
    r = _ratings(rng, 64, 32)
    means = sim.user_stats(r)[2]
    ix = ClusteredIndex(IndexConfig(n_clusters=8, seed=0,
                                    features="raw")).fit(r, means)
    ix._ratings_csr(r)
    r2 = jnp.asarray(np.asarray(r).copy())
    r2 = r2.at[1, 1].set(4.0)
    means2 = sim.user_stats(r2)[2]
    # version jump: engine says this is delta #5, index only saw #0
    st = ix.refold(r2, means2, np.array([1], np.int32), version=5)
    assert st.caches_patched == 0
    assert ix._csr_cache is None
    # next use rebuilds against the new array
    _assert_csr_equal(
        ix._ratings_csr(r2),
        ClusteredIndex(IndexConfig(n_clusters=8))._ratings_csr(r2))


def test_update_stream_oracle_with_patching(rng):
    """End-to-end: oracle-checked update stream through both indexes with
    patching active (query results come from patched operands)."""
    r = _ratings(rng, 96, 48)
    eng = CFEngine(r, measure="cosine", k=6, neighbor_mode="approx",
                   recommend_mode="approx",
                   index_cfg=IndexConfig(n_clusters=8, seed=0,
                                         features="raw")).fit()
    eng.index._ratings_csr(eng.ratings)
    for _ in range(5):
        st = eng.update_ratings(*_delta(rng, 96, 48, 3),
                                oracle_check=True)
        assert st.oracle_ok
