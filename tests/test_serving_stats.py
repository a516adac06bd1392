"""BatchingServer telemetry: latency percentiles, batch fill, queue depth."""

import numpy as np
import jax.numpy as jnp

from repro.core import CFEngine
from repro.serving.engine import BatchingServer


def _engine(rng, u=64, d=32, **kw):
    r = jnp.asarray((rng.integers(1, 6, (u, d))
                     * (rng.random((u, d)) < 0.5)).astype(np.float32))
    return CFEngine(r, measure="cosine", k=5, block_size=16, **kw).fit()


def test_stats_empty_before_traffic(rng):
    server = BatchingServer(_engine(rng), max_batch=4, topn=3)
    s = server.stats()
    assert s["n_requests"] == 0 and s["n_batches"] == 0
    assert s["latency_p50_ms"] == 0.0 and s["latency_p99_ms"] == 0.0


def test_stats_accumulate_over_requests(rng):
    server = BatchingServer(_engine(rng), max_batch=4, max_wait_ms=5.0,
                            topn=3)
    server.start()
    futures = [server.submit(int(u))
               for u in rng.integers(0, 64, 24)]
    results = [f.result(timeout=30) for f in futures]
    server.stop()
    s = server.stats()
    assert s["n_requests"] == 24
    assert s["n_batches"] >= 24 // 4
    assert 0.0 < s["latency_p50_ms"] <= s["latency_p99_ms"]
    assert 0.0 < s["mean_batch_fill"] <= 1.0
    assert s["mean_queue_depth"] >= 1.0
    # per-request latencies surfaced on the results agree with the stats:
    # histogram percentiles are bucket *upper bounds*, at most one bucket
    # width (10^0.1 ≈ 1.26×) above the truest sample
    assert max(r.latency_ms for r in results) * 10 ** 0.1 \
        >= s["latency_p50_ms"]
    assert s["queue_wait_mean_ms"] >= 0.0
    assert s["compute_mean_ms"] > 0.0


def test_stats_consistent_under_concurrent_submits(rng):
    """stats() reads one registry snapshot while the batcher is mutating
    histograms — hammer it from a second thread and check every snapshot
    is internally consistent (no torn reads, percentiles ordered)."""
    import threading

    server = BatchingServer(_engine(rng), max_batch=4, max_wait_ms=2.0,
                            topn=3)
    server.start()
    stop = threading.Event()
    bad = []

    def reader():
        while not stop.is_set():
            s = server.stats()
            if not (0.0 <= s["latency_p50_ms"] <= s["latency_p99_ms"]):
                bad.append(s)
            if s["n_requests"] < 0 or s["mean_batch_fill"] > 1.0:
                bad.append(s)

    th = threading.Thread(target=reader)
    th.start()
    futures = [server.submit(int(u)) for u in rng.integers(0, 64, 64)]
    for f in futures:
        f.result(timeout=30)
    stop.set()
    th.join(timeout=10)
    server.stop()
    assert not bad
    s = server.stats()
    assert s["n_requests"] == 64
    # a second server keeps its own registry: no cross-talk
    other = BatchingServer(_engine(rng), max_batch=4, topn=3)
    assert other.stats()["n_requests"] == 0


def test_stats_with_approx_engine(rng):
    """The serving tier fronts the clustered-index engine unchanged."""
    from repro.index import IndexConfig
    eng = _engine(rng, neighbor_mode="approx",
                  index_cfg=IndexConfig(n_clusters=8, seed=0,
                                        features="raw"))
    server = BatchingServer(eng, max_batch=4, max_wait_ms=5.0, topn=3)
    server.start()
    futures = [server.submit(int(u)) for u in rng.integers(0, 64, 8)]
    for f in futures:
        items = f.result(timeout=30).items
        assert len(items) == 3
    # a live update lands between batches; the next batch serves from it
    eng.update_ratings([1], [2], [5.0])
    fut = server.submit(1)
    assert fut.result(timeout=30).items.shape == (3,)
    server.stop()
    assert server.stats()["n_requests"] == 9


def test_one_gather_span_per_batch_after_idling(rng):
    """An idle batcher polls its empty queue every ``max_wait_ms`` inside
    one open ``serve.gather`` span, recorded only once a batch forms: one
    gather per batch, linked to the batch and its answers by
    ``batch_seq``."""
    import time

    from repro import obs

    server = BatchingServer(_engine(rng), max_batch=4, max_wait_ms=2.0,
                            topn=3)
    obs.clear()
    server.start()
    time.sleep(0.05)                      # 25 empty polls
    results = [f.result(timeout=30) for f in
               [server.submit(int(u)) for u in rng.integers(0, 64, 6)]]
    server.stop()
    spans = [s for s in obs.get_spans()
             if s.thread_id == server._thread.ident]
    gathers = [s for s in spans if s.name == "serve.gather"]
    batches = [s for s in spans if s.name == "serve.batch"]
    assert batches and len(gathers) == len(batches)
    assert [g.attrs["batch_seq"] for g in gathers] == \
        [b.attrs["batch_seq"] for b in batches]
    assert [g.attrs["batch_size"] for g in gathers] == \
        [b.attrs["batch_size"] for b in batches]
    assert gathers[0].duration >= 0.04    # the idle wait, in one span
    assert all(g.parent_id == 0 for g in gathers)
    # a request's batch_seq finds its batch's spans
    by_seq = {b.attrs["batch_seq"]: b for b in batches}
    predicts = {s.attrs["batch_seq"]: s for s in spans
                if s.name == "serve.predict"}
    for r in results:
        assert r.batch_seq in by_seq and r.batch_seq in predicts
        assert predicts[r.batch_seq].parent_id == by_seq[r.batch_seq].span_id
    assert sum(by_seq[q].attrs["batch_size"]
               for q in {r.batch_seq for r in results}) == len(results)
