"""Share of the window that bulk steps spent waiting for host selection
of their shortlists (%): ``recommend.select_wait`` over ``window_s``."""

from bench.metrics.span_time import seconds

WAIT = "recommend.select_wait"


def read(ctx):
    spans = ctx.get("spans") or ()
    if not any(s.name == WAIT for s in spans) or ctx.get("window_s", 0) <= 0:
        return None
    return 100.0 * seconds(spans, WAIT) / ctx["window_s"]
