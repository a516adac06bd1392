"""The control: the plain reference put in the program's place, computed
in bfloat16 (the precision below the float32 the configurations state).

Every operand and every intermediate result is rounded to bfloat16;
products accumulate in float32, as a TPU's bfloat16 matmul does.  It
answers the same sampled requests the program answered: its own exact
top-k neighbors, and the top-n of its own predictions.  ``check.py``
must judge it not correct; ``bench/calibrate.py`` reads its numbers on
the chip, and ``bench/tests`` keeps it failing at a small size.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

from . import plain


def bf16(x):
    """Round to bfloat16, carried in float32."""
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def answers(ratings: np.ndarray, cfg: dict, users, n: int):
    """The control's ``(answers, cache)`` for the requested ``users``, in
    the shapes ``check.readings`` takes."""
    lo, hi = float(cfg["value_min"]), float(cfg["value_max"])
    k = int(cfg["engine"]["k"])
    means = plain.user_means(ratings, cast=bf16)
    uniq = np.array(sorted(set(int(u) for u in users)), np.int64)
    sims = plain.similarity(ratings, uniq, cast=bf16)
    cache = {}
    for row, u in enumerate(uniq):
        s = sims[row].copy()
        s[u] = -np.inf
        ids = np.lexsort((np.arange(len(s)), -s))[:k]
        cache[int(u)] = (ids.astype(np.int64), s[ids])
    out = []
    for u in users:
        ids, w = cache[int(u)]
        pred = plain.predict(ratings, means, int(u), ids, w, lo, hi,
                             cast=bf16)
        s, items = plain.top_n(pred, ratings[int(u)] > 0, n)
        out.append((int(u), items.astype(np.int64), s))
    return out, cache
