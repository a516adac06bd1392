"""Two-stage recommend path: blocked-predict bit-identity, the fused
tile-predict kernel oracle, the recommendation contract (never return a
rated item), degenerate exactness, item-stage recall, one gather code
under updates, checkpointing, and the auto-refit drift guard."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import CFEngine
from repro.core import neighbors as nb
from repro.core import predict as pr
from repro.core import similarity as sim
from repro.distributed import checkpoint as ckpt
from repro.index import ClusteredIndex, IndexConfig, ItemIndexConfig
from repro.kernels.predict import fused_tile_predict
from repro.kernels.ref import tile_predict_ref
from repro.kernels.support import support_rows, support_width


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
         item_index_cfg=ItemIndexConfig(shortlist=16)),
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


@pytest.mark.parametrize("shortlist", [0, 64, 71], ids=["0", "I", "I+7"])
def test_degenerate_approx_recommend_bit_identical(shortlist, rng):
    """An uncapped shortlist (0), or one of at least the catalog's 64
    items, selects every unseen item: the approx path must reproduce the
    exact blocked recommend path bit for bit (scores and canonically
    tie-broken ids)."""
    r = _ratings(rng, 96, 64)
    ex = CFEngine(r, measure="cosine", k=6, block_size=32).fit()
    s_ex, i_ex = ex.recommend(n=8)
    cfg = ItemIndexConfig(shortlist=shortlist)
    ap = CFEngine(r, measure="cosine", k=6, block_size=32,
                  recommend_mode="approx", item_index_cfg=cfg).fit()
    s_ap, i_ap = ap.recommend(n=8)
    np.testing.assert_array_equal(np.asarray(s_ex), np.asarray(s_ap))
    np.testing.assert_array_equal(np.asarray(i_ex), np.asarray(i_ap))
    seen = int((np.asarray(r) > 0).sum())
    assert int(ap.item_index.last_recommend.n_reranked) == 96 * 64 - seen
    assert ap.recommend_recall_vs_exact(sample=48, n=8) == 1.0


def test_recommend_empty_user_list(rng):
    """Both modes must return empty (0, n) results for an empty query."""
    r = _ratings(rng, 32, 24)
    eng = CFEngine(r, measure="cosine", k=4, block_size=8,
                   recommend_mode="approx",
                   item_index_cfg=ItemIndexConfig(shortlist=8)).fit()
    for mode in ("exact", "approx"):
        s, i = eng.recommend(user_ids=[], n=5, mode=mode)
        assert s.shape == (0, 5) and i.shape == (0, 5), mode


def test_recommend_mode_validation(rng):
    r = _ratings(rng, 16, 12)
    with pytest.raises(ValueError):
        CFEngine(r, recommend_mode="sparse")
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
                   item_index_cfg=ItemIndexConfig(shortlist=48)).fit()
    rec = eng.recommend_recall_vs_exact(sample=256, n=10)
    frac = eng.item_index.last_recommend.rerank_fraction
    assert rec >= 0.95, (rec, frac)
    assert frac < 0.30, frac


# -- maintenance under updates ------------------------------------------------

def test_item_index_update_stream_consistent(rng):
    """A stream of updates must keep the item stage's patched (U, 1, W)
    support tables equal to a cold ``support_rows`` of the current
    ratings and means (the means of pcc move with every touched row)."""
    r = _ratings(rng, 80, 48)
    for measure in ("cosine", "pcc"):
        eng = CFEngine(r, measure=measure, k=5, block_size=16,
                       recommend_mode="approx",
                       item_index_cfg=ItemIndexConfig(shortlist=16)).fit()
        eng.recommend(np.arange(4), n=5)        # builds the tables
        for _ in range(3):
            m = int(rng.integers(1, 8))
            st = eng.update_ratings(
                rng.choice(80, m, replace=False).astype(np.int32),
                rng.integers(0, 48, m).astype(np.int32),
                rng.integers(0, 6, m).astype(np.float32),
                oracle_check=True)
            assert st.oracle_ok
            key, tables = eng.item_index._tables
            assert key is eng.ratings              # patched, not dropped
            cold = support_rows(eng.ratings, eng.means, support_width(48))
            for got, want in zip(tables, cold):
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(want))


def _stars(rng, u, d, step):
    """Ratings on a ``step`` grid up to 5.0 (whole or half stars)."""
    top = int(5 / step)
    return jnp.asarray((rng.integers(1, top + 1, (u, d)) * step
                        * (rng.random((u, d)) < 0.4)).astype(np.float32))


@pytest.mark.parametrize("step", [1.0, 0.5], ids=["whole", "half"])
def test_update_stream_keeps_one_gather_code(step, rng):
    """After a stream of updates the approx engine holds one gather code,
    the engine's, patched along the version chain (neither index keeps
    one), and answers as an engine freshly fitted on the same ratings."""
    r = _stars(rng, 80, 48, step)
    kw = dict(measure="pcc", k=6, block_size=16, neighbor_mode="approx",
              recommend_mode="approx",
              index_cfg=IndexConfig(n_clusters=6, n_probe=6, seed=0,
                                    rerank_frac=0.0),
              item_index_cfg=ItemIndexConfig(shortlist=16))
    eng = CFEngine(r, **kw).fit()
    users = np.arange(0, 80, 3, dtype=np.int32)
    eng.recommend(users, n=5)
    for _ in range(4):
        m = int(rng.integers(1, 6))
        top = int(5 / step)
        eng.update_ratings(rng.choice(80, m, replace=False).astype(np.int32),
                           rng.integers(0, 48, m).astype(np.int32),
                           (rng.integers(0, top + 1, m) * step
                            ).astype(np.float32))
        key, src = eng._gather_cache           # patched by the update
        assert key is eng.ratings
        assert src.code.dtype == jnp.int8 and src.step == step
        got = eng.recommend(users, n=5)
    for owner in (eng.index, eng.item_index):
        assert not any("gather" in name for name in vars(owner)), owner
    cold = pr.make_gather_source(eng.ratings)
    np.testing.assert_array_equal(np.asarray(src.code),
                                  np.asarray(cold.code))
    want = CFEngine(eng.ratings, **kw).fit().recommend(users, n=5)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


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


def test_legacy_engine_checkpoint_loads_and_serves(rng, tmp_path):
    """An engine checkpoint written when the item index kept cluster
    state (an ``item_index`` entry: basis, centroids, profiles, spill
    lists, ...) restores with its own structure into a new engine, which
    ignores that entry and serves the answers the saved engine served."""
    r = _ratings(rng, 64, 40)
    kw = dict(measure="cosine", k=5, block_size=16, neighbor_mode="approx",
              recommend_mode="approx",
              index_cfg=IndexConfig(n_clusters=6, seed=0, features="raw"),
              item_index_cfg=ItemIndexConfig(shortlist=12))
    eng = CFEngine(r, **kw).fit()
    eng.update_ratings([3, 9], [5, 7], [4.0, 2.0])
    want = eng.recommend(n=6)
    tree = eng.state()
    c, p = 7, 16                          # ⌈√40⌉ clusters, proxy width
    tree["item_index"] = {
        "basis": np.zeros((64, p), np.float32),
        "centroids": np.zeros((c, p), np.float32),
        "counts": np.zeros((c,), np.int64),
        "has_pos": np.ones((64,), bool),
        "item_meta": np.asarray([64, 3], np.int64),
        "meta": np.asarray([40, c, 4, 0], np.int64),
        "profiles": np.zeros((64, p), np.float32),
        "proxies": np.zeros((40, p), np.float32),
        "spill_dist": np.zeros((40, 2), np.float32),
        "spill_ids": np.zeros((40, 2), np.int32),
        "sums": np.zeros((c, p), np.float32)}
    ckpt.save(tmp_path, 0, tree)
    restored = CFEngine(r, **kw)
    restored.load_state(ckpt.restore(tmp_path, 0, like=tree))
    assert restored.ratings_version == 1
    got = restored.recommend(n=6)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -- serving ------------------------------------------------------------------

def test_batching_server_approx_recommend(rng):
    """The serving tier routes an approx-recommend engine through the
    two-stage path and honours the recommendation contract."""
    from repro.serving.engine import BatchingServer
    r = _ratings(rng, 48, 32)
    eng = CFEngine(r, measure="cosine", k=4, block_size=16,
                   recommend_mode="approx",
                   item_index_cfg=ItemIndexConfig(shortlist=8)).fit()
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
