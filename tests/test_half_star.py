"""Half-star ratings (MovieLens-10M's 0.5-5.0 scale) through the engine.

The corpus is the benchmark generator's (``bench/corpus.py``) at the
ML-10M configuration's scale and value step, cut to 300 × 200.  Pinned
here: the int8 code at step 0.5 gives predictions, recommend lists and
neighbor-cache scores bit-identical to the f32 matrix for every measure;
served scores match the plain float64 reference
(``bench/reference/plain.py``) and clip to the corpus's own scale, down
to 0.5; the fit's whole-matrix rerank is bit-identical to the union
path; and a rating update outside the scale widens it.
"""

import importlib.util
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CFEngine
from repro.core import predict as pr
from repro.index import IndexConfig, ItemIndexConfig
from repro.index import clustered as cl

ROOT = Path(__file__).resolve().parents[1]
MEASURES = ("pcc", "cosine", "jaccard", "pcc_sig")
U, I, K = 300, 200, 8


def _module(rel: str):
    spec = importlib.util.spec_from_file_location(
        "half_star_" + Path(rel).stem, ROOT / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


corpus = _module("bench/corpus.py")
plain = _module("bench/reference/plain.py")


@pytest.fixture(scope="module")
def half_stars():
    """A seeded 300 × 200 half-star corpus, 40 ratings a user on average,
    with user 0 made a low rater (0.5 and 1.0 alternately on 60 items):
    some of its predictions fall below 1.0."""
    cfg = json.loads((ROOT / "bench/configs/ml10m-served.json").read_text())
    cfg.update(n_users=U, n_items=I, n_ratings=U * 40)
    cfg["assumed"]["max_user_ratings"] = 150
    r = np.array(corpus.generate(corpus.seed_key(2**31 + 15),
                                 corpus.corpus_shape(cfg)))
    r[0] = 0.0
    r[0, :60] = np.where(np.arange(60) % 2, 1.0, 0.5)
    return jnp.asarray(r)


def _engine(r, measure, query_mode):
    index_cfg = IndexConfig(
        n_clusters=8, seed=0, query_mode=query_mode,
        features="centered" if measure.startswith("pcc") else "raw",
        **(dict(use_kernel=True, interpret=True)
           if query_mode == "fused" else {}))
    return CFEngine(r, measure=measure, k=K, neighbor_mode="approx",
                    recommend_mode="approx", index_cfg=index_cfg,
                    item_index_cfg=ItemIndexConfig(shortlist=48)).fit()


def _f32_source(ratings):
    return pr.GatherSource(ratings, 0.0, pr.rating_scale(ratings))


def test_corpus_is_half_stars(half_stars):
    src = pr.make_gather_source(half_stars)
    assert src.code.dtype == jnp.int8 and src.step == 0.5
    assert src.scale == (0.5, 5.0)
    assert np.any(np.asarray(half_stars) % 1 == 0.5)
    np.testing.assert_array_equal(np.asarray(src.code) * 0.5,
                                  np.asarray(half_stars))


@pytest.mark.parametrize("query_mode", ["staged", "fused"])
@pytest.mark.parametrize("measure", MEASURES)
def test_int8_code_bit_identical_to_f32(measure, query_mode, half_stars,
                                        monkeypatch):
    """Neighbor cache, predictions and approx recommend lists from the
    step-0.5 int8 code equal those from the f32 matrix, bit for bit."""
    users = np.arange(0, U, 7, dtype=np.int32)
    code = _engine(half_stars, measure, query_mode)
    assert code._gather_source(half_stars).step == 0.5
    got = (code.scores, code.idx, code.predict(users),
           *code.recommend(users, n=10))
    monkeypatch.setattr(pr, "make_gather_source", _f32_source)
    f32 = _engine(half_stars, measure, query_mode)
    assert f32._gather_source(half_stars).step == 0.0
    want = (f32.scores, f32.idx, f32.predict(users),
            *f32.recommend(users, n=10))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_served_scores_match_plain_reference(half_stars):
    """Approx engine against the float64 reference on its own neighbor
    cache: predicted rows and served lists within 1e-5, clipped to the
    corpus's scale [0.5, 5.0] — user 0's lowest predictions clip to 0.5,
    which a [1, 5] clip would have raised to 1.0."""
    eng = _engine(half_stars, "pcc", "fused")
    r = np.asarray(half_stars)
    means = plain.user_means(r)
    idx, sc = np.asarray(eng.idx), np.asarray(eng.scores)
    users = np.array([0, 17, 123, 299], np.int32)
    pred = np.asarray(eng.predict(users))
    s_served, i_served = map(np.asarray, eng.recommend(users, n=10))
    for row, u in enumerate(users):
        ok = idx[u] >= 0
        w = np.zeros(K)
        w[ok] = plain.similarity(r, np.array([u]), idx[u][ok])[0]
        ref = plain.predict(r, means, u, idx[u].astype(np.int64), w,
                            0.5, 5.0)
        np.testing.assert_allclose(pred[row], ref, rtol=0, atol=1e-5)
        live = i_served[row] >= 0
        np.testing.assert_allclose(s_served[row][live],
                                   ref[i_served[row][live]], atol=1e-5)
        if u == 0:
            raw = plain.predict(r, means, u, idx[u].astype(np.int64), w,
                                -np.inf, np.inf)
            assert (raw < 0.5).any() and (raw < 1.0).any()
            assert pred[row].min() == 0.5
            np.testing.assert_array_equal(pred[row][raw < 0.5], 0.5)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["xla", "interpret"])
@pytest.mark.parametrize("measure", MEASURES)
def test_whole_matrix_rerank_bit_identical_to_union(measure, use_pallas,
                                                    half_stars, rng):
    """At bq·m ≥ U the fit scores every row; the result equals the
    union path's on the same shortlists, bit for bit."""
    bq, m = 32, 60                               # bq·m = 1920 ≥ 300
    assert bq * m >= U
    src = pr.make_gather_source(half_stars)
    norms, counts = cl._user_norms_counts(half_stars)
    q_ids = np.full(bq, U, np.int32)
    q_ids[:29] = rng.choice(U, 29, replace=False)
    shorts = np.sort(np.stack([rng.choice(U, m, replace=False)
                               for _ in range(bq)]), axis=1).astype(np.int32)
    shorts[3, -9:] = U                           # sentinel padding
    shorts = np.sort(shorts, axis=1)
    kw = dict(k=K, measure=measure, beta=50.0, use_pallas=use_pallas,
              interpret=use_pallas)
    union = cl._fused_rerank_block(
        src, half_stars, norms, counts, jnp.asarray(q_ids),
        jnp.asarray(shorts), ku=cl._bucket(min(bq * m, U) + 1), **kw)
    operand = cl._whole_operand(src) if use_pallas else src
    whole = cl._fused_rerank_whole(operand, norms, counts,
                                   jnp.asarray(q_ids), jnp.asarray(shorts),
                                   **kw)
    for g, w in zip(whole, union):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_update_outside_the_scale_widens_it(half_stars):
    """The scale comes from the ratings held: an update below or above it
    widens it in the engine's gather code, the one every recommend path
    reads; deleting the extreme rating never narrows it back."""
    r = np.asarray(half_stars).copy()
    r = np.clip(r, 0.0, 4.5)                 # scale [0.5, 4.5]
    eng = CFEngine(jnp.asarray(r), measure="pcc", k=K,
                   neighbor_mode="approx", recommend_mode="approx",
                   index_cfg=IndexConfig(n_clusters=8, seed=0)).fit()
    eng.recommend(np.arange(4), n=5)

    def scale():
        return eng._gather_source(eng.ratings).scale

    assert scale() == (0.5, 4.5)
    eng.update_ratings([5], [7], [5.0])
    assert scale() == (0.5, 5.0)
    assert eng._gather_source(eng.ratings).step == 0.5
    eng.update_ratings([5], [7], [0.0])      # the only 5.0 deleted
    assert scale() == (0.5, 5.0)
    s, _ = eng.recommend(np.arange(U), n=5)
    assert float(jnp.max(s)) <= 5.0
    assert float(jnp.max(eng.predict(np.arange(U)))) <= 5.0
