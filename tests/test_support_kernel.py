"""Fused support-scorer kernel (shortlist SpMM), the item index's kernel
shortlist mode, the periodic profile re-fold, and the engine-level
``pcc_sig`` shrink-horizon (β) plumbing."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CFEngine
from repro.core import neighbors as nb
from repro.core import similarity as sim
from repro.core.predict import rating_scale
from repro.index import (ClusteredIndex, IndexConfig, ItemClusteredIndex,
                         ItemIndexConfig)
from repro.index.item_index import (_affinity_weights,
                                    _canonical_top_mask, _fold_profiles)
from repro.kernels import ref
from repro.kernels.support import (fused_support_scores, support_rows,
                                   support_width)


def _ratings(rng, u, d, density=0.3):
    return jnp.asarray((rng.integers(1, 6, (u, d))
                        * (rng.random((u, d)) < density)).astype(np.float32))


# -- kernel vs oracle ---------------------------------------------------------

@pytest.mark.parametrize("shape", [(5, 7, 40, 130), (9, 3, 25, 64),
                                   (2, 12, 50, 33), (260, 2, 20, 33)])
def test_support_kernel_matches_ref(shape, rng):
    """Tables built by ``support_rows`` at ``support_width`` (pad columns
    whenever ``i`` is not whole tiles) score as the oracle on the
    unpadded deviation/mask."""
    b, k, u, i = shape
    r = np.asarray(_ratings(rng, u, i))
    means = rng.uniform(2, 4, u).astype(np.float32)
    dev = np.where(r > 0, r - means[:, None], 0.0).astype(np.float32)
    msk = (r > 0).astype(np.float32)
    idx = rng.integers(0, u, (b, k)).astype(np.int32)
    w = (rng.random((b, k)) * (rng.random((b, k)) < 0.8)).astype(np.float32)
    qm = rng.uniform(2, 4, b).astype(np.float32)
    scale = rating_scale(r)
    want = ref.support_scores_ref(jnp.asarray(dev), jnp.asarray(msk),
                                  jnp.asarray(idx), jnp.asarray(w),
                                  jnp.asarray(qm), scale=scale)
    dev_t, msk_t = support_rows(jnp.asarray(r), jnp.asarray(means),
                                support_width(i, 32))
    got = fused_support_scores(dev_t, msk_t, jnp.asarray(idx),
                               jnp.asarray(w), jnp.asarray(qm), scale=scale,
                               bt=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[:, :i], np.asarray(want),
                               atol=1e-5)


def test_support_kernel_all_masked_neighbors(rng):
    """All-zero weights must fall back to the query mean, clipped."""
    dev = rng.normal(size=(20, 48)).astype(np.float32)
    msk = np.ones((20, 48), np.float32)
    idx = rng.integers(0, 20, (3, 4)).astype(np.int32)
    w = np.zeros((3, 4), np.float32)
    qm = np.array([1.5, 3.0, 4.5], np.float32)
    got = np.asarray(fused_support_scores(
        jnp.asarray(dev[:, None]), jnp.asarray(msk[:, None]),
        jnp.asarray(idx),
        jnp.asarray(w), jnp.asarray(qm), scale=(1.0, 5.0), bt=16,
        interpret=True))
    np.testing.assert_allclose(got, np.broadcast_to(qm[:, None], got.shape),
                               atol=1e-6)


# -- item index: kernel shortlist mode ---------------------------------------

def test_kernel_shortlist_mode_matches_support(rng):
    """The Pallas segmented-SpMM scorer evaluates the same exact num/den
    form as the scipy CSR pass, so the two-stage recommendations are
    identical."""
    r = _ratings(rng, 180, 140)
    outs = {}
    for mode in ("support", "kernel"):
        eng = CFEngine(r, measure="cosine", k=8, recommend_mode="approx",
                       item_index_cfg=ItemIndexConfig(
                           n_clusters=8, seed=0, shortlist=32,
                           shortlist_mode=mode, interpret=True)).fit()
        s, i = eng.recommend(n=5)
        outs[mode] = (np.asarray(s), np.asarray(i))
    np.testing.assert_array_equal(outs["support"][0], outs["kernel"][0])
    np.testing.assert_array_equal(outs["support"][1], outs["kernel"][1])


def test_kernel_path_stage_spans_and_repair_counts(rng):
    """On the kernel path each block of rows is a ``recommend.score`` and
    a ``recommend.rerank`` span on the calling thread under
    ``item_index.recommend``; selection runs on the device, so no host
    selection span appears, and ``item_index.device_select_rows`` counts
    every row recommended."""
    from repro import obs
    r = _ratings(rng, 180, 140)
    eng = CFEngine(r, measure="cosine", k=8, recommend_mode="approx",
                   item_index_cfg=ItemIndexConfig(
                       n_clusters=8, seed=0, shortlist=32, rerank_block=64,
                       shortlist_mode="kernel", interpret=True)).fit()
    counter = obs.registry().counter("item_index.device_select_rows")
    before = counter.value
    obs.clear()
    eng.recommend(n=5)
    spans = obs.get_spans()
    (root,) = [s for s in spans if s.name == "item_index.recommend"]
    for name in ("recommend.score", "recommend.rerank"):
        stage = [s for s in spans if s.name == name]
        assert len(stage) == 3
        assert all(s.parent_id == root.span_id for s in stage)
        assert sum(s.attrs["rows"] for s in stage) == 180
    assert sorted(s.attrs["block"] for s in spans
                  if s.name == "recommend.rerank") == [0, 1, 2]
    assert not [s for s in spans
                if s.name in ("recommend.select", "recommend.select_wait")]
    assert counter.value - before == 180

    # every score tied: each row keeps the lowest ids
    mask = np.asarray(_canonical_top_mask(
        jnp.full((5, 140), 3.0, jnp.float32), jnp.int32(32), width=32))
    assert mask.sum(axis=1).tolist() == [32] * 5
    np.testing.assert_array_equal(np.nonzero(mask)[1].reshape(5, 32),
                                  np.tile(np.arange(32), (5, 1)))


def test_shortlist_mode_validation():
    with pytest.raises(ValueError):
        ItemClusteredIndex(ItemIndexConfig(shortlist_mode="psychic"))


# -- periodic profile re-fold -------------------------------------------------

def test_profile_refold_zeroes_drift(rng):
    """ROADMAP "profile drift": with the re-fold threshold armed, a long
    update stream keeps the user taste profiles *exactly* equal to a cold
    fold — the Σ w·Δproxy float error is periodically zeroed."""
    r = _ratings(rng, 150, 120)
    eng = CFEngine(r, measure="cosine", k=6, recommend_mode="approx",
                   item_index_cfg=ItemIndexConfig(
                       n_clusters=8, seed=0, shortlist=32,
                       profile_refold_frac=0.01,
                       refit_reassign_frac=0.0)).fit()
    saw = 0
    for _ in range(8):
        us = rng.choice(150, 4, replace=False).astype(np.int32)
        eng.update_ratings(us, rng.integers(0, 120, 4).astype(np.int32),
                           rng.integers(1, 6, 4).astype(np.float32),
                           oracle_check=True)
        saw += int(eng.item_index.last_refold.profile_refold)
    assert saw >= 6          # the tiny threshold re-folds ~every update
    w, _ = _affinity_weights(eng.ratings, eng.means)
    cold = np.asarray(_fold_profiles(w, eng.item_index.proxies))
    np.testing.assert_array_equal(cold,
                                  np.asarray(eng.item_index.profiles))


def test_profile_refold_disabled_keeps_tolerance_contract(rng):
    """With the re-fold disabled the correction-only path still passes
    the (tolerance-based) consistency check — the pre-existing
    contract."""
    r = _ratings(rng, 100, 80)
    eng = CFEngine(r, measure="cosine", k=5, recommend_mode="approx",
                   item_index_cfg=ItemIndexConfig(
                       n_clusters=6, seed=0, shortlist=16,
                       profile_refold_frac=0.0)).fit()
    for _ in range(4):
        us = rng.choice(100, 3, replace=False).astype(np.int32)
        eng.update_ratings(us, rng.integers(0, 80, 3).astype(np.int32),
                           rng.integers(1, 6, 3).astype(np.float32))
        assert not eng.item_index.last_refold.profile_refold
    assert eng.item_index.check_consistent(eng.ratings, eng.means)


# -- pcc_sig shrink horizon (β) ----------------------------------------------

def test_resolve_beta_validation():
    assert sim.resolve_beta(None) == sim.PCC_SIG_BETA
    assert sim.resolve_beta(7) == 7.0
    with pytest.raises(ValueError):
        sim.resolve_beta(0.0)


def test_beta_reaches_every_scoring_path(rng):
    """One engine-level β must flow through the exact backend, the fused
    kernel, and the index rerank: the degenerate-index engine stays
    bit-identical to the exact engine under a custom β, and a small β
    measurably changes the scores."""
    r = _ratings(rng, 96, 64, density=0.4)
    ex = CFEngine(r, measure="pcc_sig", k=6, block_size=32,
                  pcc_sig_beta=8.0).fit()
    ap = CFEngine(r, measure="pcc_sig", k=6, neighbor_mode="approx",
                  pcc_sig_beta=8.0,
                  index_cfg=IndexConfig(n_clusters=8, n_probe=8,
                                        rerank_frac=0.0)).fit()
    np.testing.assert_array_equal(np.asarray(ex.scores),
                                  np.asarray(ap.scores))
    np.testing.assert_array_equal(np.asarray(ex.idx), np.asarray(ap.idx))
    default = CFEngine(r, measure="pcc_sig", k=6, block_size=32).fit()
    assert not np.array_equal(np.asarray(ex.scores),
                              np.asarray(default.scores))
    # filtered index path honours the per-query beta too
    ix = ClusteredIndex(IndexConfig(n_clusters=8, seed=0,
                                    features="centered",
                                    rerank_frac=0.3)).fit(
                                        r, sim.user_stats(r)[2])
    means = sim.user_stats(r)[2]
    s8, i8 = ix.query(r, means, k=6, measure="pcc_sig", beta=8.0)
    s50, _ = ix.query(r, means, k=6, measure="pcc_sig")
    assert not np.array_equal(np.asarray(s8), np.asarray(s50))
    full = np.asarray(sim.pairwise_similarity(r, r, measure="pcc_sig",
                                              beta=8.0))
    s8, i8 = np.asarray(s8), np.asarray(i8)
    for row in range(0, 96, 7):
        for col in range(6):
            if i8[row, col] >= 0:
                np.testing.assert_allclose(s8[row, col],
                                           full[row, i8[row, col]],
                                           atol=2e-5)


def test_fused_similarity_beta(rng):
    from repro.kernels.similarity import fused_similarity
    ra = _ratings(rng, 33, 65, density=0.4)
    got = fused_similarity(ra, ra, measure="pcc_sig", bm=16, bn=16,
                           bk=32, interpret=True, beta=5.0)
    g = sim.gram_terms(ra, ra)
    want = sim.pcc_sig_from_gram(g, beta=5.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)
