"""Self time of the item index's score stage per batch (ms): the
``recommend.score`` spans' self time over the ``item_index.recommend``
spans.  On the kernel path the span holds the operands' staging, the
support kernel and the host copy that fences it."""

from bench.metrics.span_time import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, "recommend.score", self_time=True)
