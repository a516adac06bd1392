"""The 99th percentile of the window's request latencies, each from
when the request was due until its answer resolved (ms): the end-to-end
tail, reported per layer because the host's stalls, of a tenth of a
second to seconds, decide it from run to run."""

import numpy as np


def read(ctx):
    lat = ctx.get("latency_s")
    if lat is None or len(lat) == 0:
        return None
    return float(np.percentile(np.asarray(lat, np.float64), 99)) * 1e3
