"""The observability layer: spans, metrics, exporters, and their wiring.

Covers the tentpole contracts: span nesting and thread-aware parenting,
``device_sync`` fencing (a jitted stage's span must cover the device
work, not the dispatch), the chrome-trace / metrics-dump schema
round-trip, the histogram quantile conventions (bucket upper bounds; the
small-n estimator fix), registry snapshot consistency under concurrent
observers, and the regression pin that the span-derived stage timers
partition ``QueryStats.seconds_total`` exactly in every query mode.
"""

import json
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Each test starts with an empty trace buffer and registry and may
    not leak state into the process-wide singletons."""
    obs.clear()
    obs.reset_metrics()
    obs.enable()
    yield
    obs.clear()
    obs.reset_metrics()
    obs.enable()
    obs.set_capacity(200_000)


# -- spans ------------------------------------------------------------------
def test_span_nesting_and_parent_ids():
    with obs.span("outer", mode="x") as so:
        with obs.span("inner") as si:
            pass
        with obs.span("inner2") as sj:
            pass
    recs = {r.name: r for r in obs.get_spans()}
    assert set(recs) == {"outer", "inner", "inner2"}
    assert recs["outer"].parent_id == 0
    assert recs["inner"].parent_id == recs["outer"].span_id
    assert recs["inner2"].parent_id == recs["outer"].span_id
    assert recs["inner"].span_id != recs["inner2"].span_id
    assert recs["outer"].attrs == {"mode": "x"}
    # children close before the parent, and fit inside its window
    assert recs["outer"].duration >= si.duration + sj.duration


def test_spans_time_even_while_disabled():
    obs.disable()
    with obs.span("quiet") as sp:
        time.sleep(0.01)
    assert sp.duration >= 0.01
    assert obs.get_spans() == []   # nothing recorded
    obs.enable()


def test_worker_threads_get_their_own_roots():
    def worker():
        with obs.span("w.root"):
            with obs.span("w.child"):
                pass

    with obs.span("main.root"):
        ts = [threading.Thread(target=worker, name=f"wk-{i}")
              for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    recs = obs.get_spans()
    roots = [r for r in recs if r.name == "w.root"]
    childs = [r for r in recs if r.name == "w.child"]
    assert len(roots) == len(childs) == 2
    # worker roots do NOT parent under main.root (different thread)
    assert all(r.parent_id == 0 for r in roots)
    by_id = {r.span_id: r for r in recs}
    for c in childs:   # ...but worker children parent on their own thread
        assert by_id[c.parent_id].name == "w.root"
        assert by_id[c.parent_id].thread_id == c.thread_id
    assert {r.thread_name for r in roots} == {"wk-0", "wk-1"}


def test_traced_decorator_names_and_attrs():
    @obs.traced("custom.name", flavor="vanilla")
    def f(x):
        return x + 1

    assert f(1) == 2
    (rec,) = obs.get_spans()
    assert rec.name == "custom.name"
    assert rec.attrs == {"flavor": "vanilla"}


class _FakeDevice:
    """Duck-types jax.block_until_ready's protocol: sleeping in the fence
    makes the device-sync contract deterministic to test."""

    def __init__(self, delay):
        self.delay = delay
        self.fenced = 0

    def block_until_ready(self):
        self.fenced += 1
        time.sleep(self.delay)
        return self


def test_device_sync_fences_return_value():
    fake = _FakeDevice(0.03)

    @obs.traced("jitted", device_sync=True)
    def dispatch():
        return fake    # returns immediately; work "completes" in fence

    dispatch()
    (rec,) = obs.get_spans()
    assert fake.fenced == 1
    assert rec.duration >= 0.03   # span covers the fence, not dispatch


def test_device_sync_without_flag_skips_fence():
    fake = _FakeDevice(0.05)

    @obs.traced("dispatch-only")
    def dispatch():
        return fake

    dispatch()
    (rec,) = obs.get_spans()
    assert fake.fenced == 0
    assert rec.duration < 0.05


def test_span_track_fences_immediately():
    fake = _FakeDevice(0.03)
    with obs.span("staged", device_sync=True) as sp:
        sp.track(fake)
        assert fake.fenced == 1   # fenced at track(), inside the span
    assert sp.duration >= 0.03


def test_capacity_bound_drops_newest():
    obs.set_capacity(3)
    for i in range(5):
        with obs.span(f"s{i}"):
            pass
    assert [r.name for r in obs.get_spans()] == ["s0", "s1", "s2"]
    assert obs.dropped_spans() == 2
    obs.clear()
    assert obs.dropped_spans() == 0


def test_discarded_span_times_but_records_nothing():
    with obs.span("given.up") as sp:
        time.sleep(0.005)
        sp.discard()
    assert sp.duration >= 0.005
    assert obs.get_spans() == []


# -- the profiler bridge ------------------------------------------------------
def _profiled(log_dir, body):
    """Run ``body`` inside a JAX profiler session; the session's host
    events as ``{name: [(start_ns, end_ns)]}``."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(log_dir))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return events


def test_span_is_a_host_event_on_the_profilers_clock(tmp_path):
    def body():
        with obs.span("t.clock"):
            time.sleep(0.02)

    events = _profiled(tmp_path, body)
    (rec,) = obs.get_spans()
    ((start, end),) = events["t.clock"]
    assert abs((end - start) / 1e9 - rec.duration) < 2e-3


def test_misnested_exit_closes_profiler_annotations_inner_first(tmp_path):
    def body():
        outer, inner = obs.span("t.outer"), obs.span("t.inner")
        outer.__enter__()
        inner.__enter__()
        time.sleep(0.005)
        outer.__exit__(None, None, None)    # closes inner's annotation too
        inner.__exit__(None, None, None)

    events = _profiled(tmp_path, body)
    ((o_start, o_end),) = events["t.outer"]
    ((i_start, i_end),) = events["t.inner"]
    assert o_start <= i_start < i_end <= o_end
    assert obs.current_span() is None


def test_span_without_a_profiler_session_times_and_records():
    from jax.profiler import TraceAnnotation
    assert not TraceAnnotation.is_enabled()
    with obs.span("t.plain", k=1) as sp:
        time.sleep(0.005)
    (rec,) = obs.get_spans()
    assert (rec.name, rec.attrs) == ("t.plain", {"k": 1})
    assert rec.duration == sp.duration >= 0.005


# -- histograms -------------------------------------------------------------
def test_histogram_single_observation_p50_equals_p99():
    """The small-n estimator fix: one sample must give p50 == p99 == the
    sample's bucket upper bound (the old sorted-sample ``int(n*0.99)``
    indexing collapsed p99 onto the *lowest* sample)."""
    h = obs.MetricsRegistry().histogram("h")
    h.observe(0.1)
    assert h.quantile(0.5) == h.quantile(0.99)
    assert 0.1 <= h.quantile(0.5) <= 0.1 * 10 ** 0.1


def test_histogram_quantiles_are_bucket_upper_bounds():
    h = obs.MetricsRegistry().histogram("h")
    vals = [0.001, 0.01, 0.1, 1.0, 10.0]
    for v in vals:
        h.observe(v)
    # rank = ceil(q·5): q=0.2 → 1st (0.001), 0.5 → 3rd (0.1), 0.8 → 4th
    for q, true_v in ((0.2, 0.001), (0.5, 0.1), (0.8, 1.0), (1.0, 10.0)):
        got = h.quantile(q)
        assert true_v <= got <= true_v * 10 ** 0.1 + 1e-12, (q, got)
    assert h.count == 5 and h.min == 0.001 and h.max == 10.0
    assert h.sum == pytest.approx(sum(vals))


def test_histogram_overflow_reports_observed_max():
    h = obs.MetricsRegistry().histogram("h", buckets=(1.0, 2.0))
    h.observe(50.0)
    h.observe(99.0)
    assert h.quantile(0.5) == 99.0   # overflow bucket → exact max
    assert h.quantile(0.99) == 99.0


def test_histogram_quantile_validation_and_empty():
    h = obs.MetricsRegistry().histogram("h")
    assert h.quantile(0.5) == 0.0
    with pytest.raises(ValueError):
        h.quantile(0.0)
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_default_buckets_monotone_and_span_latency_range():
    b = obs.DEFAULT_BUCKETS
    assert all(x < y for x, y in zip(b, b[1:]))
    assert b[0] <= 1e-7 and b[-1] >= 1000.0


# -- registry ---------------------------------------------------------------
def test_registry_get_or_create_and_snapshot():
    reg = obs.MetricsRegistry()
    assert reg.counter("c") is reg.counter("c")
    reg.counter("c").inc(3)
    reg.gauge("g").set(0.5)
    reg.histogram("h").observe(2.0)
    snap = reg.snapshot()
    assert snap["schema"] == obs.metrics.SCHEMA
    assert snap["counters"] == {"c": 3}
    assert snap["gauges"] == {"g": 0.5}
    h = snap["histograms"]["h"]
    assert h["count"] == 1 and h["sum"] == 2.0
    assert sum(h["counts"]) == h["count"]
    # trimmed ladder segment is still aligned: bounds[i] covers counts[i]
    assert len(h["bounds"]) == len(h["counts"])
    assert h["p50"] == h["p99"]


def test_registry_snapshot_consistent_under_concurrent_observe():
    """count must equal the bucket-count sum in *every* snapshot taken
    while another thread hammers observe() — a torn read would break
    the equality."""
    reg = obs.MetricsRegistry()
    h = reg.histogram("h")
    c = reg.counter("c")
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            h.observe(float(i % 7) + 0.1)
            c.inc()
            i += 1

    th = threading.Thread(target=writer)
    th.start()
    try:
        for _ in range(300):
            snap = reg.snapshot()
            hs = snap["histograms"]["h"]
            assert sum(hs["counts"]) == hs["count"]
    finally:
        stop.set()
        th.join(timeout=10)


# -- exporters --------------------------------------------------------------
def test_chrome_trace_round_trip(tmp_path):
    with obs.span("root", scan_mode="pool"):
        with obs.span("child", rows=np.int64(7)):
            pass
    path = tmp_path / "trace.json"
    n = obs.export_chrome_trace(str(path))
    assert n == 2
    doc = json.loads(path.read_text())
    assert doc["otherData"]["schema"] == obs.export.TRACE_SCHEMA
    assert doc["otherData"]["dropped_spans"] == 0
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert meta and meta[0]["name"] == "thread_name"
    assert set(xs) == {"root", "child"}
    assert xs["child"]["args"]["parent_id"] == xs["root"]["args"]["span_id"]
    assert xs["child"]["args"]["rows"] == 7          # numpy scalar → int
    assert xs["root"]["args"]["scan_mode"] == "pool"
    assert xs["root"]["dur"] >= xs["child"]["dur"]   # µs, nested
    # child window inside the root window (complete events, same clock)
    assert xs["root"]["ts"] <= xs["child"]["ts"]
    assert (xs["child"]["ts"] + xs["child"]["dur"]
            <= xs["root"]["ts"] + xs["root"]["dur"] + 1.0)


def test_metrics_dump_round_trip(tmp_path):
    obs.counter("a.count").inc(2)
    obs.histogram("a.seconds").observe(0.5)
    path = tmp_path / "metrics.json"
    snap = obs.export_metrics(str(path))
    doc = json.loads(path.read_text())
    assert doc == json.loads(json.dumps(snap))   # file == snapshot
    assert doc["schema"] == obs.metrics.SCHEMA
    assert doc["counters"]["a.count"] == 2
    assert doc["histograms"]["a.seconds"]["count"] == 1


# -- wiring: span-derived stage timers --------------------------------------
def _small_index(rng, query_mode, u=192, d=64):
    from repro.core import similarity as sim
    from repro.index import ClusteredIndex, IndexConfig
    r = jnp.asarray((rng.integers(1, 6, (u, d))
                     * (rng.random((u, d)) < 0.35)).astype(np.float32))
    means = sim.user_stats(r)[2]
    ix = ClusteredIndex(IndexConfig(n_clusters=8, n_probe=8, seed=0,
                                    features="raw", rerank_frac=0.3,
                                    project_dim=16,
                                    query_mode=query_mode)).fit(r, means)
    return r, means, ix


@pytest.mark.parametrize("query_mode", ("staged", "fused", "auto"))
def test_stage_timers_partition_query_total(query_mode, rng):
    """Regression pin: the span-derived stage timers partition
    ``seconds_total`` exactly (stage_gap == 0.0) in every query mode, and
    the trace holds the query root with stage children under it."""
    r, means, ix = _small_index(rng, query_mode)
    obs.clear()
    ix.query(r, means, k=5, measure="cosine")
    st = ix.last_query
    assert st.seconds_total == st.seconds_shortlist + st.seconds_rerank
    assert st.seconds_rerank > 0.0
    recs = obs.get_spans()
    roots = [x for x in recs if x.name == "index.query"]
    assert len(roots) == 1
    (root,) = roots
    assert root.attrs["query_mode"] == st.query_mode
    assert root.attrs["scan_mode"] == st.scan_mode
    assert root.attrs["n_reranked"] == st.n_reranked
    # total == the root span's wall (up to one rounding ulp from the
    # (duration − rerank) + rerank reassociation); rerank == the rerank
    # children's sum, exactly, in accumulation order
    assert st.seconds_total == pytest.approx(root.duration, rel=1e-12)
    rer = [x for x in recs if x.name == "query.rerank"
           and x.parent_id == root.span_id]
    assert rer and sum(x.duration for x in rer) == st.seconds_rerank
    scans = [x for x in recs if x.name == "query.scan"
             and x.parent_id == root.span_id]
    assert scans   # shortlist stage visible as children too


def test_query_metrics_land_in_registry(rng):
    r, means, ix = _small_index(rng, "staged")
    obs.reset_metrics()
    ix.query(r, means, k=5, measure="cosine")
    snap = obs.registry().snapshot()
    st = ix.last_query
    assert snap["counters"]["index.query.count"] == 1
    assert snap["counters"]["index.query.queries"] == st.n_queries
    assert snap["counters"]["index.query.reranked_rows"] == st.n_reranked
    h = snap["histograms"]["index.query.seconds"]
    assert h["count"] == 1
    # histogram percentile within one bucket ratio of the measured wall
    assert st.seconds_total <= h["p50"] <= st.seconds_total * 10 ** 0.1


# -- windowed deltas (serving health windows) -------------------------------

def _hist_snaps(obs_values_1, obs_values_2, buckets=(1.0, 10.0, 100.0)):
    """Two cumulative snapshots of one histogram: after the first batch
    of observations, then after the second."""
    reg = obs.MetricsRegistry()
    h = reg.histogram("w.seconds", buckets)
    for v in obs_values_1:
        h.observe(v)
    s1 = reg.snapshot()["histograms"].get("w.seconds")
    for v in obs_values_2:
        h.observe(v)
    s2 = reg.snapshot()["histograms"]["w.seconds"]
    return s1, s2


def test_delta_counts_isolates_the_window():
    s1, s2 = _hist_snaps([0.5, 5.0], [5.0, 50.0, 50.0])
    # absolute ladder indices: 0 → ub 1.0, 1 → ub 10.0, 2 → ub 100.0
    assert obs.delta_counts(s1, s2) == {1: 1, 2: 2}
    # prev=None means since birth: the cumulative counts
    assert obs.delta_counts(None, s2) == {0: 1, 1: 2, 2: 2}


def test_delta_quantile_ignores_lifetime_history():
    """The motivating case: one slow warmup pins the *lifetime* p99
    forever, but the windowed p99 tracks only the current window."""
    # two warmup outliers land in overflow; the window is all fast
    s1, s2 = _hist_snaps([500.0, 600.0], [0.5] * 10)
    assert s2["p99"] == 600.0                   # lifetime: pinned high
    assert obs.delta_quantile(s1, s2, 0.99) == 1.0   # window: first bucket
    assert obs.delta_quantile(s1, s2, 1.0) == 1.0


def test_delta_quantile_rank_convention():
    s1, s2 = _hist_snaps([], [0.5] + [5.0] * 99)
    # rank = max(ceil(q*n), 1): q=0.01 of 100 obs is the single rank-1
    # sample, q=0.02 crosses into the second bucket
    assert obs.delta_quantile(s1, s2, 0.01) == 1.0
    assert obs.delta_quantile(s1, s2, 0.02) == 10.0
    assert obs.delta_quantile(None, s2, 0.5) == 10.0


def test_delta_quantile_overflow_reports_cumulative_max():
    s1, s2 = _hist_snaps([0.5], [0.5, 777.0])
    assert obs.delta_quantile(s1, s2, 1.0) == 777.0


def test_delta_quantile_empty_window_and_validation():
    import pytest as _pytest
    s1, s2 = _hist_snaps([1.0, 2.0], [])
    assert obs.delta_quantile(s1, s2, 0.99) == 0.0
    assert obs.delta_quantile(s1, s1, 0.5) == 0.0
    with _pytest.raises(ValueError):
        obs.delta_quantile(s1, s2, 0.0)
    with _pytest.raises(ValueError):
        obs.delta_quantile(s1, s2, 1.1)


def test_delta_mean():
    s1, s2 = _hist_snaps([100.0], [1.0, 2.0, 3.0])
    assert obs.delta_mean(s1, s2) == 2.0
    assert obs.delta_mean(None, s1) == 100.0
    assert obs.delta_mean(s2, s2) == 0.0
