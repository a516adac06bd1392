"""The comparison that decides ``correct``.

What the timed path produced is compared with the plain float64
reference (``plain.py``) on a sample drawn from the seed, over three
layers of the engine:

* the user index's neighbor cache: every cached neighbor carries its
  true similarity (``nbr_err``), and recall@k of the cached ids against
  the reference's exact top-k, ties at the k-th score counted as hits,
  meets the deployment's stated floor (``nbr_recall``);
* the item index's candidate stage: at each rank ``j`` of a served list,
  the reference's ``j``-th best prediction over all unseen items lies
  at most ``rank_gap`` above the reference prediction of the served item;
* the rerank: each served score is the reference prediction of its item
  for the served neighbor cache (``score_err``), no served item is one
  the user rated (``rated_served``), lists are in descending order
  (``order_breaks``), and every request due in the window was answered
  (``missing``).

Each number has a limit (``limits`` in the configuration file, the
recall floor under ``guarantees``); ``correct`` is true when every
number is within its limit.
"""

from __future__ import annotations

import numpy as np

from . import plain

SAMPLE_REQUESTS = 256      # served answers compared per run
SAMPLE_CACHE = 32          # users whose neighbor cache is compared

# numbers whose limit is an upper one; the recall floor is a lower one
UPPER = ("missing", "rated_served", "order_breaks", "score_err",
         "rank_gap", "nbr_err")


def sample(n: int, size: int, seed: int) -> np.ndarray:
    """Sorted sample of ``size`` indices of ``n`` drawn from ``seed``."""
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    return np.sort(rng.choice(n, min(size, n), replace=False))


def readings(ratings: np.ndarray, cfg: dict, answers, cache: dict,
             missing: int) -> dict:
    """The compared numbers of one run.

    ``answers``: ``[(user, items, scores)]`` of served lists;
    ``cache``: ``{user: (neighbor ids, neighbor scores)}`` of the served
    neighbor cache for every user in ``answers``; ``missing``: requests
    due in the window that never resolved or resolved with an error.
    """
    lo, hi = float(cfg["value_min"]), float(cfg["value_max"])
    k = int(cfg["engine"]["k"])
    means = plain.user_means(ratings)
    users = np.array(sorted(cache), np.int64)
    # neighbor cache against the reference similarity of each cached pair
    nbr_err = 0.0
    weights = {}
    for u in users:
        ids, sc = cache[u]
        ok = ids >= 0
        ref = np.zeros(len(ids))
        if ok.any():
            ref[ok] = plain.similarity(ratings, np.array([u]), ids[ok])[0]
            nbr_err = max(nbr_err, float(np.abs(ref[ok] - sc[ok]).max()))
        weights[u] = (ids, ref)
    # recall@k against the exact top-k, on the first users of the sample
    rec_users = users[:SAMPLE_CACHE]
    sims = plain.similarity(ratings, rec_users)
    hits = total = 0
    for row, u in enumerate(rec_users):
        s = sims[row].copy()
        s[u] = -np.inf
        kth = np.sort(s)[-k]
        ids = cache[u][0]
        ids = ids[ids >= 0]
        hits += int((s[ids] >= kth - 1e-12).sum())
        total += k
    # served lists against the reference predictor over the served cache
    score_err = rank_gap = 0.0
    rated = breaks = 0
    worst = hi - lo + 1.0          # a gap no two scores on the scale reach
    for u, items, scores in answers:
        ids, w = weights[u]
        pred = plain.predict(ratings, means, u, ids, w, lo, hi)
        seen = ratings[u] > 0
        ref_s, _ = plain.top_n(pred, seen, len(items))
        ok = items >= 0
        rated += int(seen[items[ok]].sum())
        breaks += int(np.any(np.diff(scores[ok]) > 0))
        if ok.any():
            score_err = max(score_err, float(
                np.abs(pred[items[ok]] - scores[ok]).max()))
        for j in range(len(items)):
            if j >= len(ref_s):
                continue
            got = pred[items[j]] if items[j] >= 0 else None
            gap = worst if got is None or seen[items[j]] else ref_s[j] - got
            rank_gap = max(rank_gap, float(gap))
    return {"missing": int(missing), "rated_served": rated,
            "order_breaks": breaks, "score_err": score_err,
            "rank_gap": rank_gap, "nbr_err": nbr_err,
            "nbr_recall": hits / max(total, 1)}


def limits(cfg: dict) -> dict:
    """Each compared number's limit, from the configuration file."""
    out = {"missing": 0, "rated_served": 0, "order_breaks": 0}
    out.update({name: float(v) for name, v in cfg["limits"].items()
                if name in UPPER})
    out["nbr_recall"] = float(cfg["guarantees"]["neighbor_recall_floor"])
    return out


def judge(read: dict, lim: dict) -> tuple:
    """``(correct, {name: {"value", "limit"}})``."""
    table = {}
    ok = True
    for name, limit in lim.items():
        v = read[name]
        table[name] = {"value": v, "limit": limit}
        ok &= (v >= limit) if name == "nbr_recall" else (v <= limit)
    return bool(ok), table
