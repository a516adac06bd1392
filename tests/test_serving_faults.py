"""Supervised serving: batch-failure isolation, transient retry with
backoff, deadlines, admission control, stop semantics (no future is ever
stranded), the degradation ladder, and a RaceTracer-audited chaos stress
run over the whole stack."""

import threading
import time
from concurrent.futures import Future

import numpy as np
import jax.numpy as jnp
import pytest

from repro.analysis.races import RaceTracer
from repro.core import CFEngine
from repro.distributed.fault_tolerance import (FaultInjector, InjectedFault,
                                               RecoveryPolicy,
                                               TransientServeError)
from repro.serving.engine import (DEGRADED, HEALTHY, SHEDDING,
                                  BatchingServer, DeadlineExceeded,
                                  DegradationLadder, Overloaded,
                                  ServerStopped)


def _engine(rng, u=64, d=32, **kw):
    r = jnp.asarray((rng.integers(1, 6, (u, d))
                     * (rng.random((u, d)) < 0.5)).astype(np.float32))
    return CFEngine(r, measure="cosine", k=5, block_size=16, **kw).fit()


def _drain_all(futures, timeout=30):
    """Resolve every future, collecting (result | exception) — the
    universal 'nothing hangs' assertion."""
    out = []
    for f in futures:
        try:
            out.append(f.result(timeout=timeout))
        except Exception as e:            # noqa: BLE001 - collecting
            out.append(e)
    return out


# -- transient faults: retry → recovery --------------------------------------

def test_injected_fault_recovers_and_counts(rng):
    """A transient fault at batch N is retried (the injector is one-shot,
    so the retry lands) — every future still resolves with a result and
    the failure/retry/recovery trail is in the metrics."""
    server = BatchingServer(_engine(rng), max_batch=4, max_wait_ms=5.0,
                            topn=3, fault_injector=FaultInjector(
                                fail_at_steps=(1,)))
    server.start()
    futures = [server.submit(int(u)) for u in rng.integers(0, 64, 8)]
    for r in _drain_all(futures):
        assert not isinstance(r, Exception)
        assert r.items.shape == (3,)
    server.stop()
    s = server.stats()
    assert s["n_failures"] >= 1
    assert s["n_retries"] >= 1
    assert s["n_recoveries"] >= 1
    assert s["n_requests"] == 8


def test_retry_budget_exhaustion_resolves_with_error(rng):
    """When every retry also fails, the batch's futures resolve with the
    transient error after exactly max_restarts retries — bounded, loud,
    and the batcher survives to serve the next batch."""
    server = BatchingServer(_engine(rng), max_batch=4, max_wait_ms=5.0,
                            topn=3,
                            recovery=RecoveryPolicy(max_restarts=2,
                                                    backoff_base_s=1e-4))
    calls = {"n": 0}
    real = server._run_padded

    def always_transient(users, budget=None):
        calls["n"] += 1
        raise TransientServeError("persistent device loss")

    server._run_padded = always_transient
    server.start()
    futures = [server.submit(int(u)) for u in rng.integers(0, 64, 4)]
    results = _drain_all(futures)
    assert all(isinstance(r, TransientServeError) for r in results)
    assert calls["n"] == 3           # initial attempt + 2 retries
    # the batcher survived: restore the predictor and serve again
    server._run_padded = real
    assert server.submit(1).result(timeout=30).items.shape == (3,)
    server.stop()
    s = server.stats()
    assert s["n_retries"] == 2 and s["n_recoveries"] == 0
    assert s["n_failures"] == 3


def test_nontransient_fault_fails_batch_without_retry(rng):
    """Non-transient exceptions are not retried: the batch's futures get
    the exception immediately, and later batches are unaffected."""
    server = BatchingServer(_engine(rng), max_batch=4, max_wait_ms=5.0,
                            topn=3)
    real = server._run_padded
    armed = {"on": True}

    def fail_once(users, budget=None):
        if armed["on"]:
            armed["on"] = False
            raise ValueError("malformed batch")
        return real(users, budget)

    server._run_padded = fail_once
    server.start()
    first = [server.submit(int(u)) for u in rng.integers(0, 64, 4)]
    bad = _drain_all(first)
    assert all(isinstance(r, ValueError) for r in bad)
    ok = [server.submit(int(u)) for u in rng.integers(0, 64, 4)]
    for r in _drain_all(ok):
        assert not isinstance(r, Exception)
    server.stop()
    s = server.stats()
    assert s["n_failures"] == 1 and s["n_retries"] == 0


# -- request lifecycle: deadlines, admission, stop ---------------------------

def test_expired_deadline_resolves_before_compute(rng):
    server = BatchingServer(_engine(rng), max_batch=4, max_wait_ms=5.0,
                            topn=3)
    server.start()
    dead = server.submit(1, deadline_ms=0.0)     # expired on arrival
    live = server.submit(2, deadline_ms=60_000.0)
    with pytest.raises(DeadlineExceeded):
        dead.result(timeout=30)
    assert live.result(timeout=30).items.shape == (3,)
    server.stop()
    s = server.stats()
    assert s["n_deadline_exceeded"] == 1
    assert s["n_requests"] == 2      # both were admitted


def test_bounded_queue_sheds_at_high_water_mark(rng):
    server = BatchingServer(_engine(rng), max_batch=4, topn=3, max_queue=2)
    fut_a = server.submit(1)
    fut_b = server.submit(2)
    with pytest.raises(Overloaded):
        server.submit(3)
    assert server.stats()["n_shed"] == 1
    # shed before a future existed: admitted work is still intact and the
    # batcher (started late) serves it
    server.start()
    for r in _drain_all([fut_a, fut_b]):
        assert not isinstance(r, Exception)
    server.stop()


def test_stop_drains_queued_requests(rng):
    server = BatchingServer(_engine(rng), max_batch=4, max_wait_ms=50.0,
                            topn=3)
    server.start()
    futures = [server.submit(int(u)) for u in rng.integers(0, 64, 10)]
    server.stop()                    # drain=True default
    for r in _drain_all(futures, timeout=5):
        assert not isinstance(r, Exception)
    assert server.stats()["n_requests"] == 10


def test_stop_without_drain_resolves_with_server_stopped(rng):
    # never started: everything stays queued, so drain=False must resolve
    # each future with ServerStopped rather than stranding it
    server = BatchingServer(_engine(rng), max_batch=4, topn=3)
    futures = [server.submit(int(u)) for u in rng.integers(0, 64, 6)]
    server.stop(drain=False)
    for r in _drain_all(futures, timeout=5):
        assert isinstance(r, ServerStopped)


def test_submit_after_stop_raises_and_stop_is_idempotent(rng):
    server = BatchingServer(_engine(rng), max_batch=4, topn=3)
    server.start()
    server.stop()
    server.stop()                    # idempotent
    with pytest.raises(ServerStopped):
        server.submit(1)
    with pytest.raises(ServerStopped):
        server.start()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_batcher_crash_strands_no_future(rng):
    """Regression: even if the batcher thread dies outright, queued
    futures resolve (ServerStopped) and later submits raise instead of
    feeding a dead queue."""
    server = BatchingServer(_engine(rng), max_batch=4, topn=3)
    futures = [server.submit(int(u)) for u in rng.integers(0, 64, 5)]

    def crash(drain=False):
        raise RuntimeError("batcher killed")

    server._gather = crash
    server.start()
    for r in _drain_all(futures, timeout=10):
        assert isinstance(r, ServerStopped)
    with pytest.raises(ServerStopped):
        server.submit(1)
    server.stop()                    # still safe to call


# -- degradation ladder ------------------------------------------------------

def test_ladder_state_machine_steps_and_hysteresis():
    lad = DegradationLadder(degrade_p99_ms=50.0, shed_p99_ms=200.0,
                            recover_p99_ms=25.0, max_queue_depth=64.0,
                            hold_windows=2)
    step = lambda lvl, **kw: lad.next_level(lvl, straggler=False, **kw)
    # escalation is immediate
    assert step(HEALTHY, p99_ms=10.0, queue_depth=1.0)[0] == HEALTHY
    assert step(HEALTHY, p99_ms=60.0, queue_depth=1.0)[0] == DEGRADED
    assert step(HEALTHY, p99_ms=500.0, queue_depth=1.0)[0] == SHEDDING
    assert step(DEGRADED, p99_ms=10.0, queue_depth=100.0)[0] == SHEDDING
    # straggler escalation alone degrades
    lvl, why = lad.next_level(HEALTHY, p99_ms=1.0, queue_depth=0.0,
                              straggler=True)
    assert lvl == DEGRADED and "straggler" in why
    # recovery needs hold_windows consecutive calm windows, one level at
    # a time
    lad.calm_windows = 0
    assert step(SHEDDING, p99_ms=10.0, queue_depth=1.0)[0] == SHEDDING
    assert step(SHEDDING, p99_ms=10.0, queue_depth=1.0)[0] == DEGRADED
    # a loud window resets the calm streak
    assert step(DEGRADED, p99_ms=10.0, queue_depth=1.0)[0] == DEGRADED
    assert step(DEGRADED, p99_ms=40.0, queue_depth=1.0)[0] == DEGRADED
    assert lad.calm_windows == 0
    assert step(DEGRADED, p99_ms=10.0, queue_depth=1.0)[0] == DEGRADED
    assert step(DEGRADED, p99_ms=10.0, queue_depth=1.0)[0] == HEALTHY


def test_ladder_budget_scales_per_level():
    lad = DegradationLadder(shortlist_frac=0.5)
    assert lad.budget(HEALTHY, 64, 10) is None
    assert lad.budget(DEGRADED, 64, 10) == {"shortlist": 32}
    assert lad.budget(SHEDDING, 64, 10) == {"shortlist": 16}
    # floor: shortlist ≥ top-n
    assert lad.budget(SHEDDING, 16, 10) == {"shortlist": 10}


def test_ladder_degrades_live_server_and_recovers(rng):
    """Integration: thresholds set so the first evaluation window trips
    DEGRADED — the gauge, the transition counter, and the engine's
    query_mode override all flip; recovery flips them back."""
    from repro.index import IndexConfig
    eng = _engine(rng, recommend_mode="approx", neighbor_mode="approx",
                  index_cfg=IndexConfig(n_clusters=8, seed=0,
                                        features="raw"))
    lad = DegradationLadder(degrade_p99_ms=0.0, shed_p99_ms=1e9,
                            recover_p99_ms=1e9, max_queue_depth=1e9,
                            window=2, hold_windows=1)
    server = BatchingServer(eng, max_batch=4, max_wait_ms=2.0, topn=3,
                            ladder=lad)
    server.start()
    futures = [server.submit(int(u)) for u in rng.integers(0, 64, 16)]
    for r in _drain_all(futures):
        assert not isinstance(r, Exception)
    deadline = time.perf_counter() + 10
    while server.health != "DEGRADED" and time.perf_counter() < deadline:
        server.submit(1).result(timeout=30)
    assert server.health == "DEGRADED"
    assert eng.index.query_mode_override == "staged"
    # calm the ladder: now nothing exceeds degrade and everything clears
    # recover, so one calm window steps back down
    lad.degrade_p99_ms = 1e9
    deadline = time.perf_counter() + 10
    while server.health != "HEALTHY" and time.perf_counter() < deadline:
        server.submit(1).result(timeout=30)
    assert server.health == "HEALTHY"
    assert eng.index.query_mode_override is None
    server.stop()
    s = server.stats()
    assert s["health"] == "HEALTHY"
    assert int(server.registry.snapshot()["counters"]
               ["serve.health.transitions"]) >= 2


def test_shedding_rejects_bulk_but_serves_interactive(rng):
    eng = _engine(rng, recommend_mode="approx")
    server = BatchingServer(eng, max_batch=4, max_wait_ms=2.0, topn=3,
                            ladder=DegradationLadder())
    with server._state_lock:
        server._health = SHEDDING
    with pytest.raises(Overloaded):
        server.submit(1, request_class="bulk")
    server.start()
    assert server.submit(1).result(timeout=30).items.shape == (3,)
    server.stop()
    assert server.stats()["n_shed"] == 1


def test_degraded_results_stay_well_formed(rng):
    """Under a pinned DEGRADED level the reduced candidate budgets still
    yield full top-n recommendations for both request classes."""
    eng = _engine(rng, recommend_mode="approx")
    server = BatchingServer(eng, max_batch=4, max_wait_ms=2.0, topn=3,
                            ladder=DegradationLadder())
    with server._state_lock:
        server._health = DEGRADED
    server.start()
    futs = [server.submit(int(u), request_class=cls)
            for u in rng.integers(0, 64, 6)
            for cls in ("interactive", "bulk")]
    for r in _drain_all(futs):
        assert not isinstance(r, Exception)
        assert r.items.shape == (3,)
    server.stop()


def test_unknown_request_class_rejected(rng):
    server = BatchingServer(_engine(rng), max_batch=4, topn=3)
    with pytest.raises(ValueError, match="request_class"):
        server.submit(1, request_class="batchy")
    server.stop(drain=False)


# -- chaos stress under the race harness -------------------------------------

def test_chaos_stress_is_race_clean_and_strands_nothing(rng):
    """The satellite: concurrent submits + injected transient faults +
    live update_ratings, the whole stack under the Eraser tracer, ending
    in assert_clean() — and every single future resolves."""
    eng = _engine(rng, recommend_mode="approx")
    server = BatchingServer(
        eng, max_batch=4, max_wait_ms=2.0, topn=3,
        recovery=RecoveryPolicy(max_restarts=3, backoff_base_s=1e-4),
        fault_injector=FaultInjector(fail_at_steps=(2, 4, 7)),
        ladder=DegradationLadder(degrade_p99_ms=0.5, shed_p99_ms=1e9,
                                 recover_p99_ms=1e9, max_queue_depth=1e9,
                                 window=4))
    tracer = RaceTracer()
    futures = []
    fut_lock = threading.Lock()
    with tracer.trace(eng, "engine"), tracer.trace(server, "server"):
        server.start()
        gate = threading.Barrier(3)

        def submitter(seed):
            r = np.random.default_rng(seed)
            gate.wait(timeout=10)
            for u in r.integers(0, 64, 24):
                f = server.submit(int(u), deadline_ms=30_000.0)
                with fut_lock:
                    futures.append(f)

        threads = [threading.Thread(target=submitter, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        gate.wait(timeout=10)
        for i in range(6):
            eng.update_ratings([int(rng.integers(0, 64))],
                               [int(rng.integers(0, 32))], [4.0])
            server.stats()
        for t in threads:
            t.join()
        for r in _drain_all(futures):
            assert not isinstance(r, Exception)
        server.stop()
    tracer.assert_clean()
    s = server.stats()
    assert s["n_requests"] == 48
    assert s["n_recoveries"] >= 1     # at least one injected fault retried
