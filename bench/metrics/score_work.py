"""The work of the recommend path's score stage, counted from the
problem's shapes and not from any kernel's implementation.

For each user whose list is computed, the stage scores every item from
that user's ``k`` neighbors: it must read the ``k · n_items`` neighbor
ratings (one byte each: both deployments' value sets fit an 8-bit code)
and make two multiply-adds per neighbor rating (the weighted deviation
sum and the weight sum).  Outputs and the per-user epilogue are left
out, so the count is a floor of the work.
"""

from __future__ import annotations

# the score stage's program as named in the device trace today
SCORE_MODULE = "jit_fused_support_scores"


def score_bytes(users: int, k: int, n_items: int) -> float:
    return float(users) * k * n_items


def score_ops(users: int, k: int, n_items: int) -> float:
    return 4.0 * users * k * n_items


def roofline_share(ctx) -> float | None:
    """Least time the chip needs for the window's score-stage work over
    the device time of the stage's programs in the trace, in %; None when
    the trace holds no score stage."""
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    if not trace or not peaks:
        return None
    busy = sum(t for name, t in trace["modules"].items()
               if name == SCORE_MODULE)
    users = int(ctx.get("users_scored") or 0)
    if busy <= 0 or users <= 0:
        return None
    cfg = ctx["config"]
    k, items = int(cfg["engine"]["k"]), int(cfg["n_items"])
    least = max(score_bytes(users, k, items) / peaks["hbm_bytes_per_s"],
                score_ops(users, k, items) / peaks["bf16_flops_per_s"])
    return 100.0 * least / busy
