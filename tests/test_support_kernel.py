"""Fused support-scorer kernel (shortlist SpMM), the item stage's two
evaluators of its tables, and the engine-level ``pcc_sig`` shrink-horizon
(β) plumbing."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CFEngine
from repro.core import neighbors as nb
from repro.core import similarity as sim
from repro.core.predict import rating_scale
from repro.index import ClusteredIndex, IndexConfig, ItemIndexConfig
from repro.index.item_index import _canonical_top_mask
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


@pytest.mark.parametrize("b", [5, 300])
def test_reference_evaluator_on_kernel_tables(b, rng):
    """The item stage's off-TPU scorer evaluates the kernel's own
    ``(U, 1, W)`` tables through ``support_scores_ref``, ``BB`` query rows
    at a time: the same scores as the oracle on the unpadded
    deviation/mask, across a block boundary too (300 > BB)."""
    from repro.index.item_index import _support_scores_ref
    u, i, k = 40, 70, 6
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
    got = _support_scores_ref(dev_t, msk_t, jnp.asarray(idx),
                              jnp.asarray(w), jnp.asarray(qm), scale=scale)
    assert got.shape == (b, support_width(i, 32))
    np.testing.assert_allclose(np.asarray(got)[:, :i], np.asarray(want),
                               atol=1e-6)


# -- item stage: the kernel and its reference on the same tables ------------

def test_kernel_shortlist_mode_matches_support(rng):
    """The Pallas segmented-SpMM scorer (interpret mode) and its
    reference ``support_scores_ref`` evaluate the same (U, 1, W) tables
    in the same exact num/den form, so the recommendations are
    identical."""
    r = _ratings(rng, 180, 140)
    outs = {}
    for use_kernel in (False, True):
        eng = CFEngine(r, measure="cosine", k=8, recommend_mode="approx",
                       item_index_cfg=ItemIndexConfig(
                           shortlist=32, use_kernel=use_kernel,
                           interpret=True)).fit()
        s, i = eng.recommend(n=5)
        assert eng.item_index.last_recommend.scorer == \
            ("kernel" if use_kernel else "ref")
        outs[use_kernel] = (np.asarray(s), np.asarray(i))
    np.testing.assert_array_equal(outs[False][0], outs[True][0])
    np.testing.assert_array_equal(outs[False][1], outs[True][1])


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
                       shortlist=32, rerank_block=64, use_kernel=True,
                       interpret=True)).fit()
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
