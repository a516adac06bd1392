"""Plain reference of user-based CF with Pearson similarity, float64 on
the host, computed in row blocks so that an ML-10M-sized corpus fits in
host memory.

Semantics (the engine's, as ``chip_smoke.py``'s reference states them):

* a user's mean is over the items they rated; a user with none gets the
  global mean;
* similarity is Pearson over co-rated items with co-rated means, mapped
  to ``[0, 1]`` as ``(pcc + 1) / 2``, and 0 for a pair with fewer than two
  co-rated items or no variance;
* a prediction is ``mean_u + Σ w·(r_v - mean_v) / Σ w`` over the
  neighbors ``v`` with weight ``w > 0`` that rated the item, the user's
  mean where none did, clipped to the rating scale.

The ``dtype`` hooks let the control (``control.py``) run the same code in
a lower precision.
"""

from __future__ import annotations

import numpy as np

ROWS = 2048          # users per block of the all-users passes


def f64(x):
    return np.asarray(x, np.float64)


def user_means(ratings: np.ndarray, cast=f64) -> np.ndarray:
    """Per-user mean over rated items (global mean for users with none)."""
    n = ratings.shape[0]
    tot = np.zeros(n)
    cnt = np.zeros(n)
    for lo in range(0, n, ROWS):
        r = cast(ratings[lo:lo + ROWS])
        tot[lo:lo + ROWS] = r.sum(1)
        cnt[lo:lo + ROWS] = (r > 0).sum(1)
    glob = tot.sum() / max(cnt.sum(), 1)
    return cast(np.where(cnt > 0, tot / np.maximum(cnt, 1), glob))


def _pcc(ru, mu, rb, mb, cast):
    """[0, 1]-mapped co-rated Pearson of rows ``ru`` against rows ``rb``."""
    n = cast(mu @ mb.T)
    dot = cast(ru @ rb.T)
    sa, sb = cast(ru @ mb.T), cast(mu @ rb.T)
    qa, qb = cast((ru * ru) @ mb.T), cast(mu @ (rb * rb).T)
    cov = cast(cast(n * dot) - cast(sa * sb))
    var_a = np.maximum(cast(cast(n * qa) - cast(sa * sa)), 0.0)
    var_b = np.maximum(cast(cast(n * qb) - cast(sb * sb)), 0.0)
    denom = cast(np.sqrt(cast(var_a * var_b)))
    valid = (n >= 2) & (denom > 1e-8)
    pcc = np.clip(np.where(valid, cov / np.where(valid, denom, 1.0), 0.0),
                  -1.0, 1.0)
    return cast(np.where(valid, cast((pcc + 1.0) * 0.5), 0.0))


def similarity(ratings: np.ndarray, users: np.ndarray,
               others: np.ndarray | None = None, cast=f64) -> np.ndarray:
    """Similarity of each of ``users`` to each of ``others`` (all users
    when None), ``(len(users), len(others))``."""
    ru = cast(ratings[users])
    mu = cast(ru > 0)
    if others is not None:
        rb = cast(ratings[others])
        return _pcc(ru, mu, rb, cast(rb > 0), cast)
    n = ratings.shape[0]
    out = np.empty((len(users), n), np.float64)
    for lo in range(0, n, ROWS):
        rb = cast(ratings[lo:lo + ROWS])
        out[:, lo:lo + ROWS] = _pcc(ru, mu, rb, cast(rb > 0), cast)
    return out


def predict(ratings: np.ndarray, means: np.ndarray, user: int,
            nb_ids: np.ndarray, nb_w: np.ndarray, lo: float, hi: float,
            cast=f64) -> np.ndarray:
    """Predicted rating of every item for ``user`` from the neighbors
    ``nb_ids`` with weights ``nb_w`` (non-positive weights and ids < 0
    take no part)."""
    ok = (nb_ids >= 0) & (nb_w > 0)
    ids, w = nb_ids[ok], cast(nb_w[ok])
    rows = cast(ratings[ids])
    mask = cast(rows > 0)
    dev = cast(cast(rows - cast(means[ids])[:, None]) * mask)
    num = cast(w @ dev)
    den = cast(w @ mask)
    mu = cast(means[user])
    pred = cast(mu + cast(num / np.maximum(den, 1e-8)))
    return np.clip(np.where(den > 1e-8, pred, mu), lo, hi)


def top_n(pred: np.ndarray, seen: np.ndarray, n: int):
    """Best ``n`` unseen items by ``(-score, item id)``: ``(scores, ids)``,
    shorter where fewer than ``n`` items are unseen."""
    s = np.where(seen, -np.inf, pred)
    order = np.lexsort((np.arange(len(s)), -s))[:n]
    order = order[np.isfinite(s[order])]
    return s[order], order
