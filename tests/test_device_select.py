"""The support path's shortlist selection on the device.

``_canonical_top_mask`` picks each row's canonical (-score, item id)
top-``m`` finite scores with ``lax.top_k`` at a static width and a
traced ``m``; ``_select_rerank_items`` knocks out seen items and the
kernel's pad columns, selects, and reranks in one program.  Both are
pinned here against a plain numpy selection: a lexsort on (-score, id).
End to end, the kernel and its reference give the same answers, which
equal the exact path's wherever the shortlist holds every unseen item,
and a smaller per-call budget compiles nothing new.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.retrace import RetraceSentinel
from repro.core import CFEngine
from repro.core import neighbors as nb
from repro.core import predict as pr
from repro.core import similarity as sim
from repro.index import ItemIndexConfig
from repro.index.item_index import (_canonical_top_mask, _rerank_masked,
                                    _select_rerank_items)

U, I, K, B, WIDTH, PAD, ITEM_BLOCK = 40, 60, 5, 8, 24, 36, 32
_mask = jax.jit(_canonical_top_mask, static_argnames=("width",))
_rerank = jax.jit(_rerank_masked, static_argnames=("n", "item_block"))


def _np_top_mask(s, m):
    """Reference: each row's first ``m`` finite items in (-score, id)
    order."""
    out = np.zeros(s.shape, bool)
    for row, v in enumerate(s):
        ok = np.nonzero(np.isfinite(v))[0]
        out[row, ok[np.lexsort((ok, -v[ok]))][:m]] = True
    return out


def _scores(case, rng, q_means):
    """(B, I) support scores with the ties of one case; -inf marks
    excluded items."""
    s = rng.choice([1.5, 2.0, 3.25, 4.0, 4.5], (B, I)).astype(np.float32)
    s += rng.normal(0, 1e-3, (B, I)).astype(np.float32)
    if case == "all_tied":
        s[:] = 3.0
    elif case == "clipped_top":            # a group clipped at the top
        s[rng.random((B, I)) < 0.6] = 5.0
    elif case == "qmean_fallback":         # unsupported items at r̄_u
        fb = rng.random((B, I)) < 0.7
        s[fb] = np.broadcast_to(q_means[:, None], (B, I))[fb]
    elif case == "few_finite":             # fewer than the width left
        s[rng.random((B, I)) < 0.8] = -np.inf
        s[0, :] = -np.inf
    return s


CASES = ["all_tied", "clipped_top", "qmean_fallback", "few_finite",
         "distinct"]


@pytest.mark.parametrize("m", [WIDTH, 7])
@pytest.mark.parametrize("case", CASES)
def test_canonical_top_mask_matches_lexsort(case, m, rng):
    """The mask is the canonical top-``m`` set, also for ``m`` below the
    static width, and drops -inf picks."""
    q_means = rng.uniform(2.5, 4.0, B).astype(np.float32)
    s = _scores(case, rng, q_means)
    got = np.asarray(_mask(jnp.asarray(s), jnp.int32(m), width=WIDTH))
    want = _np_top_mask(s, m)
    np.testing.assert_array_equal(got, want)
    finite = np.isfinite(s).sum(axis=1)
    assert (got.sum(axis=1) == np.minimum(finite, m)).all()
    if case == "few_finite":
        assert (finite < m).any() and not got[0].any()


def _block(rng):
    """Ratings, gather code, neighbor tables, means and padded query ids
    of one block; the first query user has rated all but 5 items, and
    the last query row is padding (id ``U``)."""
    q_ids = rng.choice(U, B, replace=False).astype(np.int32)
    q_ids[-1] = U
    r = rng.integers(1, 6, (U, I)) * (rng.random((U, I)) < 0.35)
    r[q_ids[0]] = rng.integers(1, 6, I)
    r[q_ids[0], rng.choice(I, 5, replace=False)] = 0
    ratings = jnp.asarray(r.astype(np.float32))
    means = sim.user_means(ratings)
    scores, idx = nb.topk_neighbors(ratings, K, measure="pcc",
                                    block_size=16)
    idx = idx.at[0, -1].set(-1)            # a dead neighbor slot
    return r, ratings, pr.make_gather_source(ratings), scores, idx, means, \
        q_ids


@pytest.mark.parametrize("m", [WIDTH, 9])
@pytest.mark.parametrize("case", CASES)
def test_select_rerank_matches_host_selection(case, m, rng):
    """Device selection + rerank == the rerank of a host-built canonical
    top-``m`` mask (seen items and pad columns excluded), bit for bit;
    a row with fewer than ``m`` unseen items takes just those, and the
    candidate count covers the real rows only."""
    r, ratings, src, scores, idx, means, q_ids = _block(rng)
    q = np.clip(q_ids, 0, U - 1)
    qm = np.asarray(means)[q]
    s = _scores(case, rng, qm)
    s = np.where(np.isneginf(s), 2.0, s)   # the scorer's are finite
    # the kernel's pad tiles read high: they must never be picked
    padded = np.concatenate([s, np.full((B, PAD), 99.0, np.float32)], 1)
    got_s, got_i, n_cand = _select_rerank_items(
        ratings, src, scores, idx, means, jnp.asarray(q_ids),
        jnp.asarray(padded), jnp.int32(m), n=6, shortlist=WIDTH,
        item_block=ITEM_BLOCK)
    want_mask = _np_top_mask(np.where(r[q] > 0, -np.inf, s), m)
    assert want_mask[0].sum() == 5
    want_s, want_i = _rerank(
        ratings, src, scores[jnp.asarray(q)], idx[jnp.asarray(q)], means,
        jnp.asarray(qm), jnp.asarray(want_mask), jnp.asarray(r[q] > 0),
        n=6, item_block=ITEM_BLOCK)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    assert int(n_cand) == int(want_mask[:-1].sum())


def _engine(r, shortlist, scorer):
    return CFEngine(r, measure="cosine", k=6, recommend_mode="approx",
                    item_index_cfg=ItemIndexConfig(
                        shortlist=shortlist, use_kernel=scorer == "kernel",
                        interpret=True)).fit()


def test_scorers_agree_and_full_shortlist_is_exact(rng):
    """The kernel (interpret mode) and its reference give identical
    answers on the same tables, and with a shortlist that holds every
    unseen item both equal the exact path."""
    r = rng.integers(1, 6, (64, I)) * (rng.random((64, I)) < 0.4)
    r[:, :12] = rng.integers(1, 6, (64, 12))   # ≥ 12 rated per user
    r = jnp.asarray(r.astype(np.float32))
    shortlist = I - 12                          # every unseen item
    outs = {}
    for scorer in ("kernel", "ref"):
        eng = _engine(r, shortlist, scorer)
        s, i = eng.recommend(n=8)
        assert eng.item_index.last_recommend.scorer == scorer
        outs[scorer] = (np.asarray(s), np.asarray(i))
    np.testing.assert_array_equal(outs["kernel"][1], outs["ref"][1])
    np.testing.assert_array_equal(outs["kernel"][0], outs["ref"][0])
    ex_s, ex_i = eng.recommend(n=8, mode="exact")
    np.testing.assert_array_equal(outs["kernel"][1], np.asarray(ex_i))
    np.testing.assert_array_equal(outs["kernel"][0], np.asarray(ex_s))


def test_ladder_budgets_share_one_program(rng):
    """Two smaller per-call shortlist budgets compile nothing after
    warm-up, and each answers as an engine configured at that budget."""
    r = jnp.asarray((rng.integers(1, 6, (64, I))
                     * (rng.random((64, I)) < 0.3)).astype(np.float32))
    eng = _engine(r, 32, "kernel")
    users = np.arange(16, dtype=np.int32)
    eng.recommend(users, n=5)
    with RetraceSentinel("ladder", publish=False) as sentinel:
        got = {b: eng.recommend(users, n=5, shortlist=b) for b in (16, 8)}
        got = {b: (np.asarray(s), np.asarray(i))
               for b, (s, i) in got.items()}
    assert sentinel.count == 0
    for b in (16, 8):
        want_s, want_i = _engine(r, b, "kernel").recommend(users, n=5)
        np.testing.assert_array_equal(got[b][1], np.asarray(want_i))
        np.testing.assert_array_equal(got[b][0], np.asarray(want_s))
