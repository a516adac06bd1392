"""Fused query pipeline vs the staged oracle.

The acceptance contract of ``IndexConfig.query_mode="fused"``: per query
block the proxy scan, shortlist selection, candidate-union gather, and
exact co-rated Gram rerank stream through device memory — and the result
is **bit-identical** to the staged two-pass pipeline (device scan +
CSR-batched gather-walk rerank) on every measure, because the fused chain
dispatches the *same* jitted scan and every Gram statistic is an exactly
representable f32 integer for integer rating matrices.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import repro.index.clustered as cl
from repro.core import similarity as sim
from repro.index import ClusteredIndex, IndexConfig

MEASURES = ("cosine", "jaccard", "pcc", "pcc_sig")


def _ratings(rng, u, d, density=0.35):
    return jnp.asarray((rng.integers(1, 6, (u, d))
                        * (rng.random((u, d)) < density)).astype(np.float32))


def _pair(rng, u=220, d=72, **kw):
    """(staged, fused) index twins over the same fit (same seed)."""
    r = _ratings(rng, u, d)
    means = sim.user_stats(r)[2]
    cfg = dict(n_clusters=12, n_probe=12, seed=0, features="raw",
               rerank_frac=0.3, project_dim=24, rerank_mode="gather",
               shortlist_scan_mode="kernel", interpret=True)
    cfg.update(kw)
    ix_s = ClusteredIndex(IndexConfig(query_mode="staged", **cfg)
                          ).fit(r, means)
    ix_f = ClusteredIndex(IndexConfig(query_mode="fused", **cfg)
                          ).fit(r, means)
    return r, means, ix_s, ix_f


@pytest.mark.parametrize("measure", MEASURES)
def test_fused_bit_matches_staged_pool(measure, rng):
    """Pool branch: fused output == staged kernel-scan + gather-walk
    output bit for bit, on all four measures."""
    r, means, ix_s, ix_f = _pair(rng)
    s1, i1 = ix_s.query(r, means, k=8, measure=measure)
    s2, i2 = ix_f.query(r, means, k=8, measure=measure)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    st = ix_f.last_query
    assert st.query_mode == "fused" and st.rerank_mode == "fused"
    assert ix_s.last_query.query_mode == "staged"
    assert st.n_probed == ix_s.last_query.n_probed
    assert st.n_reranked == ix_s.last_query.n_reranked


def test_fused_shortlists_pin_to_gather_oracle(rng, monkeypatch):
    """The oracle pin: capture the *device* shortlists the fused rerank
    consumes, replay them through the CSR-batched gather walk (the
    bit-exact oracle), and require the fused chain's output bit for bit.
    This is the guarantee that fusing moved the pipeline, not the math."""
    r, means, _, ix_f = _pair(rng, u=260)
    k = 8
    captured = []

    def grabbing(name):
        orig = getattr(cl, name)

        def grab(*args, **kw):
            q_ids, shorts = args[-2:]
            captured.append((np.asarray(q_ids), np.asarray(shorts)))
            return orig(*args, **kw)
        monkeypatch.setattr(cl, name, grab)

    # the union path, and the whole-matrix path a block takes where its
    # union can reach every row
    grabbing("_fused_rerank_block")
    grabbing("_fused_rerank_whole")
    s_f, i_f = ix_f.query(r, means, k=k, measure="pcc")
    assert captured, "fused rerank never ran"
    qs, shorts = [], []
    for q_ids, sh in captured:
        live = q_ids < ix_f.n_users
        qs.append(q_ids[live])
        shorts.append(sh[:live.sum()])
    q_all = np.concatenate(qs)
    shorts_np = np.sort(np.concatenate(shorts, axis=0), axis=1)
    out_s = np.empty((len(q_all), k), np.float32)
    out_i = np.empty((len(q_all), k), np.int32)
    norms, counts = cl._user_norms_counts(r)
    ix_f._rerank_gather(r, norms, counts, q_all, shorts_np,
                        np.arange(len(q_all)), out_s, out_i, k=k,
                        measure="pcc", beta=sim.PCC_SIG_BETA,
                        max_rerank=ix_f._max_rerank(k))
    np.testing.assert_array_equal(out_i, np.asarray(i_f))
    np.testing.assert_array_equal(out_s, np.asarray(s_f))


@pytest.mark.parametrize("measure", ("cosine", "pcc_sig"))
def test_fused_cluster_branch_matches_staged(measure, rng):
    """Cluster branch (thin probes): the fused restricted scan's
    ascending-candidate tie-break keeps the canonical policy, so results
    match the staged cluster scan bit for bit."""
    r, means, ix_s, ix_f = _pair(
        rng, u=420, d=56, n_clusters=24, n_probe=2, spill=1,
        rerank_frac=0.05, project_dim=16, query_block=64,
        shortlist_scan_mode="cluster")
    s1, i1 = ix_s.query(r, means, k=5, measure=measure)
    s2, i2 = ix_f.query(r, means, k=5, measure=measure)
    assert ix_f.last_query.scan_mode == "cluster"
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_fused_unfiltered_blocks_match_staged(rng):
    """Blocks whose candidate union fits the rerank budget route through
    the shared-matmul exact path inside the fused chain too — identical
    to the staged degenerate mode."""
    r, means, ix_s, ix_f = _pair(
        rng, u=300, d=56, n_clusters=20, n_probe=2, spill=1,
        rerank_frac=0.9, query_block=64,
        shortlist_scan_mode="cluster")
    s1, i1 = ix_s.query(r, means, k=6, measure="pcc")
    s2, i2 = ix_f.query(r, means, k=6, measure="pcc")
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_fused_subset_and_partial_blocks(rng):
    """Subset queries pad the trailing block with sentinel query ids;
    their garbage shortlists must never leak into real rows (the
    union-gather masks sentinels before indexing)."""
    r, means, ix_s, ix_f = _pair(rng, u=200)
    sub = np.asarray([0, 7, 63, 64, 199], np.int32)
    s1, i1 = ix_s.query(r, means, sub, k=8, measure="cosine")
    s2, i2 = ix_f.query(r, means, sub, k=8, measure="cosine")
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert (np.asarray(i2) < 200).all()      # sentinels never surface


def test_fused_k_exceeds_population(rng):
    """k beyond the candidate population: starved slots surface as the
    exact engines' (-inf, -1) padding through the fused chain as well."""
    r, means, ix_s, ix_f = _pair(rng, u=10, d=40, n_clusters=2, n_probe=2,
                                 project_dim=8, rerank_frac=0.9)
    s1, i1 = ix_s.query(r, means, k=12, measure="cosine")
    s2, i2 = ix_f.query(r, means, k=12, measure="cosine")
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert (np.asarray(i2)[:, -1] == -1).all()    # only 9 real neighbors


def test_fused_xla_twin_path_matches_interpret(rng):
    """interpret=False on CPU routes the fused stages through their XLA
    twins; the twins implement the same canonical selection and the same
    integer-exact Gram statistics, so outputs are unchanged."""
    r = _ratings(rng, 180, 72)
    means = sim.user_stats(r)[2]
    outs = []
    for interpret in (True, False):
        ix = ClusteredIndex(IndexConfig(
            n_clusters=12, n_probe=12, seed=0, features="raw",
            rerank_frac=0.3, project_dim=24, query_mode="fused",
            shortlist_scan_mode="kernel", interpret=interpret)).fit(r, means)
        outs.append(tuple(np.asarray(x) for x in
                          ix.query(r, means, k=6, measure="jaccard")))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])


def test_fused_stage_timers_partition_exactly(rng):
    """The fused chain's two jitted calls per block keep the stages
    separately timeable: the partition must be exact."""
    r, means, _, ix_f = _pair(rng)
    ix_f.query(r, means, k=8, measure="cosine")
    st = ix_f.last_query
    assert st.seconds_total == st.seconds_shortlist + st.seconds_rerank
    assert st.seconds_rerank > 0.0


def test_query_mode_resolution_and_validation(rng, monkeypatch):
    """auto resolves by backend (staged off-TPU, fused where the kernels
    run); unknown modes fail fast at construction."""
    r, means, _, ix_f = _pair(rng, u=60, d=32)
    ix = ClusteredIndex(IndexConfig(n_clusters=4, seed=0, features="raw",
                                    rerank_frac=0.3))
    assert ix._query_mode() == ("fused" if ix._use_kernel() else "staged")
    monkeypatch.setattr(ClusteredIndex, "_use_kernel", lambda self: True)
    assert ix._query_mode() == "fused"
    with pytest.raises(ValueError, match="query_mode"):
        ClusteredIndex(IndexConfig(query_mode="magic"))
    ix_auto = ClusteredIndex(dataclasses.replace(ix_f.cfg,
                                                 query_mode="auto"))
    ix_auto.fit(r, means)
    ix_auto.query(r, means, k=4, measure="cosine")
    assert ix_auto.last_query.query_mode in ("staged", "fused")
