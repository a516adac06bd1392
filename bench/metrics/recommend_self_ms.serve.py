"""Self time of the recommend call per batch (ms): each
``item_index.recommend`` span less its children (score, selection wait,
rerank): operand staging, allocation and dispatch that no stage names.
None for a program whose score and selection wait are not spans, where
this would count them as self time."""

from bench.metrics.span_time import RECOMMEND, per_batch_ms


def read(ctx):
    if not any(s.name == "recommend.select_wait"
               for s in ctx.get("spans") or ()):
        return None
    return per_batch_ms(ctx, RECOMMEND, self_time=True)
