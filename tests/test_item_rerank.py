"""The recommend stage's exact rerank (``_rerank_masked``).

The rerank predicts the batch's rows over every item with the blocked
form and masks them to each row's shortlist.  Its answers are pinned
here, bit for bit, against a plain numpy point-gather rerank: one
rating per (neighbor, candidate), the tile arithmetic of
``repro.core.predict._tile_predict``, and a (-score, item id) sort.  A
structural test keeps the element-granular gather out of the program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import neighbors as nb
from repro.core import predict as pr
from repro.core import similarity as sim
from repro.index.item_index import _rerank_masked, _select_rerank_items

U, I, K, M, N, ITEM_BLOCK = 48, 70, 6, 8, 10, 32
_rerank = jax.jit(_rerank_masked, static_argnames=("n", "item_block"))


def _mask(short):
    """(M, L) item lists, padded with the ``I`` sentinel → (M, I)
    candidate mask."""
    mask = np.zeros((len(short), I + 1), bool)
    mask[np.arange(len(short))[:, None], short] = True
    return mask[:, :I]


def _fma(a, b, acc):
    """float32 ``acc + a * b`` rounded once, as XLA's CPU backend
    computes a multiply fused into a running sum (a product of two
    float32 numbers is exact in float64)."""
    return (acc.astype(np.float64)
            + np.float64(a) * b.astype(np.float64)).astype(np.float32)


def _point_gather_rerank(src, ratings, nb_scores, nb_idx, means, q_means,
                         q_ids, short, n):
    """Reference: score each row's candidate list by gathering
    ``src[neighbor, candidate]`` one element at a time (decoded by the
    code's step), clip to the gather source's scale, then sort by
    (-score, item id); -inf slots come back as id -1."""
    scale, factor = src.scale, np.float32(src.factor)
    src, ratings = np.asarray(src.code), np.asarray(ratings)
    means, q_means = np.asarray(means), np.asarray(q_means)
    n_users, n_items = ratings.shape
    out_s = np.full((len(q_ids), n), -np.inf, np.float32)
    out_i = np.full((len(q_ids), n), -1, np.int32)
    for row, cand in enumerate(np.asarray(short)):
        idx, sc = np.asarray(nb_idx[row]), np.asarray(nb_scores[row])
        safe_nb = np.where(idx >= 0, idx, 0)
        w = np.where((sc > 0) & (idx >= 0), sc, 0).astype(np.float32)
        safe_c = np.clip(cand, 0, n_items - 1)
        num = np.zeros(len(cand), np.float32)
        den = np.zeros(len(cand), np.float32)
        for j in range(len(idx)):          # the k-reduction, in order
            nbr = src[safe_nb[j], safe_c].astype(np.float32) * factor
            mask = (nbr > 0).astype(np.float32)
            dev = (nbr - means[safe_nb[j]]) * mask
            num = _fma(w[j], dev, num)
            den = _fma(w[j], mask, den)
        qm = q_means[row]
        pred = qm + num / np.maximum(den, np.float32(1e-8))
        pred = np.clip(np.where(den > 1e-8, pred, qm), *scale)
        seen = ratings[min(max(q_ids[row], 0), n_users - 1), safe_c] > 0
        valid = (cand >= 0) & (cand < n_items) & ~seen
        s = np.where(valid, pred, -np.inf).astype(np.float32)
        order = np.lexsort((cand, -s))[:n]
        out_s[row, :len(order)] = s[order]
        out_i[row, :len(order)] = np.where(np.isneginf(s[order]), -1,
                                           cand[order])
    return out_s, out_i


def _lists(rng, width, valid):
    """(M, width) ascending per-row item lists: ``valid`` distinct ids,
    then the ``I`` padding sentinel."""
    out = np.full((M, width), I, np.int32)
    for row in range(M):
        out[row, :valid] = np.sort(rng.choice(I, valid, replace=False))
    return out


def _case(name, rng):
    """(ratings, means, q_means, q_ids, shortlist rows, n) for one case."""
    r = (rng.integers(1, 6, (U, I)) * (rng.random((U, I)) < 0.35))
    ratings = jnp.asarray(r.astype(np.float32))
    means = sim.user_means(ratings)
    q_ids = rng.choice(U, M, replace=False).astype(np.int32)
    q_ids[-1] = U                          # a padded query row
    q_means = means[jnp.clip(jnp.asarray(q_ids), 0, U - 1)]
    n = N
    if name == "per_row":                  # a different list in every row
        short = _lists(rng, 24, 24)
    elif name == "sentinel":               # padded with the n_items id
        short = _lists(rng, 24, 15)
    elif name == "seen":                   # the users' rated items listed
        short = np.full((M, I), I, np.int32)
        for row, u in enumerate(np.clip(q_ids, 0, U - 1)):
            rated = np.nonzero(r[u])[0]
            extra = rng.choice(I, 6, replace=False)
            ids = np.union1d(rated, extra)
            short[row, :len(ids)] = ids
    elif name == "few":                    # fewer than n valid candidates
        short = _lists(rng, 16, 4)
    elif name == "tied":                   # every score clips to 5.0
        means = jnp.ones((U,), jnp.float32)
        q_means = jnp.full((M,), 5.0, jnp.float32)
        short = _lists(rng, 32, 30)
    elif name == "broadcast":              # every item, sentinel-padded
        short = np.broadcast_to(
            np.concatenate([np.arange(I), np.full(128 - I, I)])
            .astype(np.int32)[None, :], (M, 128))
    elif name == "n_over_items":           # more slots than items
        short = np.broadcast_to(np.arange(I, dtype=np.int32)[None, :],
                                (M, I))
        n = I + 5
    return ratings, means, q_means, q_ids, short, n


CASES = ["per_row", "sentinel", "seen", "few", "tied", "broadcast",
         "n_over_items"]


@pytest.mark.parametrize("src_dtype", ["float32", "int8"])
@pytest.mark.parametrize("case", CASES)
def test_rerank_matches_point_gather_reference(case, src_dtype, rng):
    """Masked dense rerank == point-gather rerank: the same ids and
    bit-identical scores, whatever the shortlist and gather operand."""
    ratings, means, q_means, q_ids, short, n = _case(case, rng)
    scores, idx = nb.topk_neighbors(ratings, K, measure="pcc",
                                    block_size=16)
    nbs = scores[jnp.clip(jnp.asarray(q_ids), 0, U - 1)]
    nbi = idx[jnp.clip(jnp.asarray(q_ids), 0, U - 1)]
    # a dead neighbor slot and a negative weight, as a cache may hold
    nbi = nbi.at[0, -1].set(-1)
    nbs = nbs.at[1, -1].set(-0.5)
    src = (pr.make_gather_source(ratings) if src_dtype == "int8" else
           pr.GatherSource(ratings, 0.0, pr.rating_scale(ratings)))
    assert src.code.dtype == jnp.dtype(src_dtype)
    seen = np.asarray(ratings)[np.clip(q_ids, 0, U - 1)] > 0
    got_s, got_i = _rerank(
        ratings, src, nbs, nbi, means, q_means, jnp.asarray(_mask(short)),
        jnp.asarray(seen), n=n, item_block=ITEM_BLOCK)
    ref_s, ref_i = _point_gather_rerank(src, ratings, nbs, nbi, means,
                                        q_means, q_ids, short, n)
    np.testing.assert_array_equal(np.asarray(got_i), ref_i)
    np.testing.assert_array_equal(np.asarray(got_s), ref_s)
    if case == "tied":
        assert (ref_s[ref_i >= 0] == 5.0).all()
    if case in ("few", "n_over_items"):
        assert (ref_i == -1).any()


def _gathers(jaxpr):
    """Every gather equation of ``jaxpr`` and of the programs it calls."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _gathers(sub)


@pytest.mark.parametrize("src_dtype", ["float32", "int8"])
def test_rerank_has_no_element_gather_on_the_rating_matrix(src_dtype):
    """No gather whose slice is a single element of a (users, items)
    operand: the served select-and-rerank program reads whole neighbor
    rows of an item tile."""
    ratings = jnp.zeros((U, I), jnp.float32)
    closed = jax.make_jaxpr(
        lambda r, s, nbs, nbi, mu, q, sc, m: _select_rerank_items(
            r, s, nbs, nbi, mu, q, sc, m, n=N, shortlist=24,
            item_block=ITEM_BLOCK))(
        ratings, pr.GatherSource(ratings.astype(jnp.dtype(src_dtype)),
                                 float(src_dtype == "int8"), (1.0, 5.0)),
        jnp.zeros((U, K), jnp.float32), jnp.zeros((U, K), jnp.int32),
        jnp.zeros((U,), jnp.float32), jnp.zeros((M,), jnp.int32),
        jnp.zeros((M, I), jnp.float32), jnp.int32(24))
    gathers = list(_gathers(closed.jaxpr))
    assert gathers, "the rerank gathers neighbor rows"
    for eqn in gathers:
        shape = eqn.invars[0].aval.shape
        if len(shape) >= 2 and shape[0] == U:
            assert any(s > 1 for s in eqn.params["slice_sizes"]), (
                f"element gather on the {shape} rating operand")
