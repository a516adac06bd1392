"""Time per batch that the recommend call waits for host selection of
its shortlists (ms): ``recommend.select_wait`` over the
``item_index.recommend`` spans."""

from bench.metrics.span_time import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, "recommend.select_wait", self_time=False)
