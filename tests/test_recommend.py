"""Two-stage recommend path: blocked-predict bit-identity, the fused
tile-predict kernel oracle, the recommendation contract (never return a
rated item), degenerate exactness, item-index recall, checkpointing, and
the auto-refit drift guard."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import CFEngine
from repro.core import neighbors as nb
from repro.core import predict as pr
from repro.core import similarity as sim
from repro.distributed import checkpoint as ckpt
from repro.index import (ClusteredIndex, IndexConfig, ItemClusteredIndex,
                         ItemIndexConfig)
from repro.kernels.predict import fused_tile_predict
from repro.kernels.ref import tile_predict_ref


def _ratings(rng, u, d, density=0.4):
    return jnp.asarray((rng.integers(1, 6, (u, d))
                        * (rng.random((u, d)) < density)).astype(np.float32))


# -- blocked prediction -------------------------------------------------------

@pytest.mark.parametrize("item_block", [16, 33, 64, 512])
def test_blocked_predict_bit_identical_to_dense(item_block, rng):
    """The tiled fallback must reproduce the one-shot (m, k, I) gather
    form bit for bit, for any tile width (including non-dividing)."""
    r = _ratings(rng, 100, 130)
    scores, idx = nb.topk_neighbors(r, 7, measure="pcc", block_size=32)
    dense = np.asarray(pr.predict_from_neighbors(r, scores, idx))
    blocked = np.asarray(pr.predict_from_neighbors_blocked(
        r, scores, idx, item_block=item_block))
    np.testing.assert_array_equal(dense, blocked)


def test_blocked_predict_int8_gather_src_is_exact(rng):
    """The int8 gather operand must not change a single bit (integer
    ratings round-trip the cast exactly)."""
    r = _ratings(rng, 64, 96)
    scores, idx = nb.topk_neighbors(r, 5, measure="cosine", block_size=16)
    dense = np.asarray(pr.predict_from_neighbors(r, scores, idx))
    src = pr.make_gather_source(r)
    assert src.code.dtype == jnp.int8 and src.step == 1.0
    np.testing.assert_array_equal(np.asarray(src.code),
                                  np.asarray(r.astype(jnp.int8)))
    blocked = np.asarray(pr.predict_from_neighbors_blocked(
        r, scores, idx, item_block=32, gather_src=src))
    np.testing.assert_array_equal(dense, blocked)


def test_fused_tile_predict_matches_oracle(rng):
    """Interpret-mode kernel vs the jnp oracle (and the core tile)."""
    r = _ratings(rng, 37, 100)
    scores, idx = nb.topk_neighbors(r, 7, measure="pcc", block_size=16)
    means = sim.user_means(r)
    safe = jnp.where(idx >= 0, idx, 0)
    w = jnp.where((scores > 0) & (idx >= 0), scores, 0.0)
    nbr = r[safe]
    scale = pr.rating_scale(r)
    got = fused_tile_predict(nbr, w, means[safe], means, scale=scale,
                             bm=16, bt=64, interpret=True)
    ref = tile_predict_ref(nbr, w, means[safe], means, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)
    dense = np.asarray(pr.predict_from_neighbors(r, scores, idx))
    np.testing.assert_allclose(np.asarray(got), dense, atol=2e-5)


def test_blocked_predict_kernel_path(rng):
    r = _ratings(rng, 24, 90)
    scores, idx = nb.topk_neighbors(r, 4, measure="cosine", block_size=8)
    dense = np.asarray(pr.predict_from_neighbors(r, scores, idx))
    kblk = np.asarray(pr.predict_from_neighbors_blocked(
        r, scores, idx, item_block=48, use_kernel=True, interpret=True))
    np.testing.assert_allclose(kblk, dense, atol=2e-5)


# -- the recommendation contract ----------------------------------------------

def _assert_unseen(items, ratings):
    seen = np.asarray(ratings) > 0
    items = np.asarray(items)
    for u in range(items.shape[0]):
        row = items[u]
        assert not seen[u, row[row >= 0]].any()


@pytest.mark.parametrize("mode_kwargs", [
    dict(),                                               # exact
    dict(recommend_mode="approx",                         # support scorer
         item_index_cfg=ItemIndexConfig(n_clusters=8, shortlist=16)),
    dict(recommend_mode="approx",                         # proxy scorer
         item_index_cfg=ItemIndexConfig(n_clusters=8, shortlist=16,
                                        shortlist_mode="proxy")),
])
def test_recommend_never_returns_rated(mode_kwargs, rng):
    """No path may recommend an already-rated item — including right
    after update_ratings adds ratings, and for users with fewer unseen
    items than n (those slots must surface as -1)."""
    r = np.asarray(_ratings(rng, 64, 48, density=0.5)).copy()
    r[3, :46] = 4.0                      # user 3: only 2 unseen items
    eng = CFEngine(jnp.asarray(r), measure="cosine", k=6, block_size=16,
                   **mode_kwargs).fit()
    s, items = eng.recommend(n=8)
    _assert_unseen(items, eng.ratings)
    assert (np.asarray(items)[3] == -1).sum() >= 6     # -1 fills, not seen
    # absorb new ratings (including into previously-unseen cells), re-check
    us = rng.choice(64, 6, replace=False).astype(np.int32)
    iids = rng.integers(0, 48, 6).astype(np.int32)
    vals = rng.integers(1, 6, 6).astype(np.float32)
    eng.update_ratings(us, iids, vals)
    _, items = eng.recommend(n=8)
    _assert_unseen(items, eng.ratings)
    for u, i in zip(us, iids):          # the fresh cells are now seen
        assert i not in np.asarray(items)[u]


def test_degenerate_approx_recommend_bit_identical(rng):
    """Full probing + uncapped shortlist must reproduce the exact blocked
    recommend path bit for bit (scores and canonically tie-broken ids)."""
    r = _ratings(rng, 96, 64)
    ex = CFEngine(r, measure="cosine", k=6, block_size=32).fit()
    s_ex, i_ex = ex.recommend(n=8)
    cfg = ItemIndexConfig(n_clusters=8, n_probe=8, shortlist=0)
    ap = CFEngine(r, measure="cosine", k=6, block_size=32,
                  recommend_mode="approx", item_index_cfg=cfg).fit()
    s_ap, i_ap = ap.recommend(n=8)
    np.testing.assert_array_equal(np.asarray(s_ex), np.asarray(s_ap))
    np.testing.assert_array_equal(np.asarray(i_ex), np.asarray(i_ap))
    assert ap.recommend_recall_vs_exact(sample=48, n=8) == 1.0


def test_recommend_empty_user_list(rng):
    """Both modes must return empty (0, n) results for an empty query."""
    r = _ratings(rng, 32, 24)
    eng = CFEngine(r, measure="cosine", k=4, block_size=8,
                   recommend_mode="approx",
                   item_index_cfg=ItemIndexConfig(n_clusters=4,
                                                  shortlist=8)).fit()
    for mode in ("exact", "approx"):
        s, i = eng.recommend(user_ids=[], n=5, mode=mode)
        assert s.shape == (0, 5) and i.shape == (0, 5), mode


def test_recommend_mode_validation(rng):
    r = _ratings(rng, 16, 12)
    with pytest.raises(ValueError):
        CFEngine(r, recommend_mode="sparse")
    with pytest.raises(ValueError):
        ItemClusteredIndex(ItemIndexConfig(shortlist_mode="magic"))
    eng = CFEngine(r, k=3, block_size=8).fit()
    with pytest.raises(RuntimeError):
        eng.recommend(n=4, mode="approx")   # no item index fitted


# -- item-index recall --------------------------------------------------------

def test_item_index_recall_floor_small():
    """ML-1M surrogate: the support-scorer two-stage path must recover
    ≥95% of the exact top-10 while exactly reranking a small fraction of
    the catalog."""
    from repro.data import load_ml1m_synthetic
    train, _, _ = load_ml1m_synthetic(n_users=512, n_items=256, seed=0)
    r = jnp.asarray(train)
    eng = CFEngine(r, measure="cosine", k=20, block_size=128,
                   recommend_mode="approx",
                   item_index_cfg=ItemIndexConfig(seed=0, shortlist=48)
                   ).fit()
    rec = eng.recommend_recall_vs_exact(sample=256, n=10)
    frac = eng.item_index.last_recommend.rerank_fraction
    assert rec >= 0.95, (rec, frac)
    assert frac < 0.30, frac


# -- maintenance under updates ------------------------------------------------

def test_item_index_update_stream_consistent(rng):
    """A stream of updates must keep every item-index invariant (proxies,
    spill lists, mass ledger, profiles, support table) cold-equal."""
    r = _ratings(rng, 80, 48)
    for feats in ("raw", "centered"):
        eng = CFEngine(r, measure="cosine", k=5, block_size=16,
                       recommend_mode="approx",
                       item_index_cfg=ItemIndexConfig(
                           n_clusters=6, features=feats, shortlist=16)
                       ).fit()
        for _ in range(3):
            m = int(rng.integers(1, 8))
            st = eng.update_ratings(
                rng.choice(80, m, replace=False).astype(np.int32),
                rng.integers(0, 48, m).astype(np.int32),
                rng.integers(0, 6, m).astype(np.float32),
                oracle_check=True)
            assert st.oracle_ok
        assert eng.item_index.check_consistent(eng.ratings, eng.means)


def test_refold_auto_refit_trigger(rng):
    """Crossing the cumulative-reassignment threshold must trigger a cold
    refit (reported in RefoldStats) and leave a consistent index; a zero
    threshold must never refit."""
    r = _ratings(rng, 80, 48)
    cfg = IndexConfig(n_clusters=8, seed=0, features="raw",
                      refit_reassign_frac=0.01)
    eng = CFEngine(r, measure="cosine", k=5, neighbor_mode="approx",
                   index_cfg=cfg).fit()
    fired = False
    for _ in range(5):
        us = rng.choice(80, 6, replace=False).astype(np.int32)
        st = eng.update_ratings(us, rng.integers(0, 48, 6).astype(np.int32),
                                rng.integers(1, 6, 6).astype(np.float32),
                                oracle_check=True)
        assert st.oracle_ok
        fired |= eng.index.last_refold.refit
    assert fired
    assert eng.index._reassigned_since_fit == 0 or \
        eng.index.last_refold.reassigned_frac < 0.01

    cfg_off = IndexConfig(n_clusters=8, seed=0, features="raw",
                          refit_reassign_frac=0.0)
    eng2 = CFEngine(r, measure="cosine", k=5, neighbor_mode="approx",
                    index_cfg=cfg_off).fit()
    for _ in range(3):
        us = rng.choice(80, 6, replace=False).astype(np.int32)
        eng2.update_ratings(us, rng.integers(0, 48, 6).astype(np.int32),
                            rng.integers(1, 6, 6).astype(np.float32))
        assert not eng2.index.last_refold.refit


# -- checkpointing ------------------------------------------------------------

def test_user_index_checkpoint_roundtrip(rng, tmp_path):
    """save → restore must skip the k-means fit yet pass the cold-rebuild
    consistency oracle and answer queries identically."""
    r = _ratings(rng, 80, 48)
    means = sim.user_stats(r)[2]
    cfg = IndexConfig(n_clusters=8, seed=0, features="raw")
    ix = ClusteredIndex(cfg).fit(r, means)
    ckpt.save(tmp_path, 0, ix.state())
    ix2 = ClusteredIndex(cfg)
    ix2.load_state(ckpt.restore(tmp_path, 0,
                                like=ClusteredIndex.state_template()))
    assert ix2.check_consistent(r, means)
    s1, i1 = ix.query(r, means, k=5, measure="cosine")
    s2, i2 = ix2.query(r, means, k=5, measure="cosine")
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    # the restored index keeps absorbing updates exactly
    ix2.refold(r, means, np.array([3, 7], np.int32))
    assert ix2.check_consistent(r, means)


def test_item_index_checkpoint_roundtrip(rng, tmp_path):
    r = _ratings(rng, 64, 40)
    eng = CFEngine(r, measure="cosine", k=5, block_size=16,
                   recommend_mode="approx",
                   item_index_cfg=ItemIndexConfig(n_clusters=6,
                                                  shortlist=12)).fit()
    ckpt.save(tmp_path, 0, eng.item_index.state())
    it2 = ItemClusteredIndex(ItemIndexConfig(n_clusters=6, shortlist=12))
    it2.load_state(ckpt.restore(tmp_path, 0,
                                like=ItemClusteredIndex.state_template()))
    assert it2.check_consistent(eng.ratings, eng.means)
    sa, ia = eng.item_index.recommend(eng.ratings, eng.means, eng.scores,
                                      eng.idx, n=6)
    sb, ib = it2.recommend(eng.ratings, eng.means, eng.scores, eng.idx, n=6)
    np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))


# -- serving ------------------------------------------------------------------

def test_batching_server_approx_recommend(rng):
    """The serving tier routes an approx-recommend engine through the
    two-stage path and honours the recommendation contract."""
    from repro.serving.engine import BatchingServer
    r = _ratings(rng, 48, 32)
    eng = CFEngine(r, measure="cosine", k=4, block_size=16,
                   recommend_mode="approx",
                   item_index_cfg=ItemIndexConfig(n_clusters=6,
                                                  shortlist=8)).fit()
    server = BatchingServer(eng, max_batch=4, max_wait_ms=5.0, topn=5)
    server.start()
    try:
        futs = [server.submit(u) for u in (0, 7, 31, 47)]
        seen = np.asarray(r) > 0
        for f in futs:
            rec = f.result(timeout=30)
            items = rec.items[rec.items >= 0]
            assert not seen[rec.user, items].any()
    finally:
        server.stop()
