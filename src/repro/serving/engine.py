"""Batched serving tier for recommendation requests, supervised.

Requests enqueue individually; a background batcher drains up to
``max_batch`` (or waits ``max_wait_ms``), pads user indices into a fixed
batch, runs the predictor once, and resolves per-request futures with
top-n items.  This is the serve_p99 pattern: the fixed padded batch keeps
one compiled executable hot regardless of arrival pattern.

The server fronts a :class:`repro.core.facade.CFEngine` (preferred — the
facade owns the rating matrix and neighbor cache, so ``update_ratings``
between batches is picked up by the very next batch because the model
arrays are passed per call, not baked into the executable) or the legacy
``UserCF`` + ratings pair.  An engine built with
``recommend_mode="approx"`` is served through its two-stage item-index
path — candidate generation + exact rerank, the end-to-end sublinear
configuration.

**Failure model.**  The batcher is a supervisor, not a bare loop: every
batch runs isolated, so an exception resolves that batch's futures with
the error (``serve.failures``) and the batcher survives for the next
batch — a future handed out by ``submit()`` ALWAYS resolves (result or
typed error), across faults, stop, and crash paths alike.  Transient
failures (:class:`repro.distributed.fault_tolerance.TransientServeError`,
which ``InjectedFault`` subclasses) are retried with the bounded
exponential backoff of a ``RecoveryPolicy`` (``serve.retries``, a
``serve.recover`` span per wait, ``serve.recoveries`` on success).

**Request lifecycle.**  ``submit(user, deadline_ms=...)`` attaches a
deadline: a request still queued when its deadline passes resolves with
:class:`DeadlineExceeded` before any compute is spent on it.  With
``max_queue > 0`` the queue is bounded and ``submit`` raises
:class:`Overloaded` at the high-water mark (``serve.shed``).  ``stop()``
drains (default) or cancels the queue — either way nothing is stranded —
and later ``submit()`` calls raise :class:`ServerStopped`.

**Degradation ladder.**  With a :class:`DegradationLadder` the server
runs a health state machine HEALTHY → DEGRADED → SHEDDING fed by the
*windowed* p99 / mean queue depth of its own histograms
(``obs.delta_quantile`` over registry snapshots) and by
``StragglerWatchdog`` escalation on per-batch compute walls.  Pressure
steps the approx engine down — ``query_mode`` fused→staged, smaller
``n_probe``/``shortlist`` per request class (``bulk`` degrades one level
before ``interactive``) — and calm windows step it back up.  Every
transition is the ``serve.health`` gauge plus a
``serve.health.transition`` span carrying the reason, so the chrome
trace shows exactly when and why quality was traded for latency.  In
SHEDDING, bulk traffic is refused at admission.

Telemetry goes through a :class:`repro.obs.MetricsRegistry` (per-server
by default, shareable via ``registry=``): per-request latency splits into
queue wait (enqueue → batch launch) and compute wait (launch → futures
resolved), each a fixed-bucket histogram, so ``stats()`` reads one
lock-consistent snapshot instead of sorting a deque the batcher thread is
mutating.  Percentiles are histogram bucket *upper bounds* (exact bounds,
~26 % worst-case relative error at 10 buckets/decade) — never below the
true quantile.  Each served batch also records a ``serve.batch`` span with
a ``serve.predict`` child, so batches appear on the batcher thread's track
in the exported chrome trace, preceded by one ``serve.gather`` span: the
batcher's wait from being free until the batch formed, empty polls
included.  The three carry the batch's ``batch_seq``, which each
:class:`Recommendation` of the batch carries too.
"""

from __future__ import annotations

import dataclasses
import functools
import queue
import threading
import time
from concurrent.futures import Future
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.predict import predict_from_neighbors_blocked, topn_unseen
from repro.distributed.fault_tolerance import (RecoveryPolicy,
                                               StragglerWatchdog,
                                               TransientServeError)

_ITEM_BLOCK = 512      # predict tile width: batch·k·tile intermediates

# health levels, in escalation order (gauge value = list index)
HEALTHY, DEGRADED, SHEDDING = 0, 1, 2
HEALTH_STATES = ("HEALTHY", "DEGRADED", "SHEDDING")

REQUEST_CLASSES = ("interactive", "bulk")


class DeadlineExceeded(TimeoutError):
    """The request's deadline passed while it was still queued; resolved
    before compute was spent on it."""


class Overloaded(RuntimeError):
    """Admission refused: bounded queue at its high-water mark, or bulk
    traffic while the server is SHEDDING.  Retry with client backoff."""


class ServerStopped(RuntimeError):
    """The server was stopped: a post-stop ``submit()``, or a queued
    request the shutdown resolved instead of serving."""


@dataclasses.dataclass
class Recommendation:
    user: int
    items: np.ndarray
    scores: np.ndarray
    latency_ms: float
    batch_seq: int = 0      # the serving batch's spans carry the same id


@dataclasses.dataclass
class DegradationLadder:
    """Config + transition logic for the serving health state machine.

    Thresholds read *windowed* metrics (between two registry snapshots,
    never lifetime aggregates): escalation is immediate — one bad window
    (or watchdog escalation) steps up, a window past ``shed_p99_ms`` or
    ``max_queue_depth`` jumps straight to SHEDDING — while recovery is
    hysteretic: ``hold_windows`` consecutive windows under
    ``recover_p99_ms`` step down a single level.

    Quality budgets are multiplicative per level: at level ``L`` the
    approx engine runs ``n_probe ≈ base·n_probe_frac**L`` and
    ``shortlist ≈ base·shortlist_frac**L`` (floored at 1 / top-n), and
    ``bulk`` requests are served one level worse than ``interactive``.
    The instance is owned by one server and mutated only on its batcher
    thread.
    """
    degrade_p99_ms: float = 50.0
    shed_p99_ms: float = 200.0
    recover_p99_ms: float = 25.0
    max_queue_depth: float = 64.0
    window: int = 8                 # batches per health evaluation
    hold_windows: int = 2           # calm windows per step *down*
    n_probe_frac: float = 0.5
    shortlist_frac: float = 0.5
    staged_when_degraded: bool = True
    calm_windows: int = 0

    def budget(self, level: int, base_n_probe: int, base_shortlist: int,
               n_min: int) -> Optional[dict]:
        """Per-call candidate budgets for a request served at ``level``
        (None = config defaults, i.e. HEALTHY)."""
        if level <= HEALTHY:
            return None
        return {
            "n_probe": max(1, int(base_n_probe * self.n_probe_frac ** level)),
            "shortlist": max(n_min, int(base_shortlist
                                        * self.shortlist_frac ** level)),
        }

    def next_level(self, level: int, *, p99_ms: float, queue_depth: float,
                   straggler: bool) -> Tuple[int, str]:
        """One evaluation step: ``(new_level, reason)`` (reason empty when
        the level holds)."""
        if p99_ms >= self.shed_p99_ms or queue_depth >= self.max_queue_depth:
            self.calm_windows = 0
            return SHEDDING, (f"window p99 {p99_ms:.1f} ms / depth "
                              f"{queue_depth:.0f} over shed thresholds")
        if p99_ms >= self.degrade_p99_ms or straggler:
            self.calm_windows = 0
            reason = (f"window p99 {p99_ms:.1f} ms ≥ "
                      f"{self.degrade_p99_ms:.1f} ms"
                      if p99_ms >= self.degrade_p99_ms
                      else "straggler watchdog escalation")
            return max(level, DEGRADED), reason
        if level == HEALTHY:
            return HEALTHY, ""
        if p99_ms <= self.recover_p99_ms:
            self.calm_windows += 1
            if self.calm_windows >= self.hold_windows:
                self.calm_windows = 0
                return level - 1, (f"recovered: p99 {p99_ms:.1f} ms ≤ "
                                   f"{self.recover_p99_ms:.1f} ms for "
                                   f"{self.hold_windows} windows")
        else:
            self.calm_windows = 0
        return level, ""


@functools.partial(jax.jit, static_argnames=("topn",))
def _predict_users(users, ratings, scores, idx, means, *, topn):
    pred = predict_from_neighbors_blocked(
        ratings, scores[users], idx[users], means=means,
        query_means=means[users], item_block=_ITEM_BLOCK)
    seen = ratings[users] > 0
    return topn_unseen(pred, seen, topn)


class BatchingServer:
    def __init__(self, cf_model, ratings=None, *, max_batch: int = 16,
                 max_wait_ms: float = 20.0, topn: int = 10,
                 registry: Optional[obs.MetricsRegistry] = None,
                 max_queue: int = 0,
                 recovery: Optional[RecoveryPolicy] = None,
                 fault_injector=None,
                 ladder: Optional[DegradationLadder] = None,
                 watchdog: Optional[StragglerWatchdog] = None):
        self._approx_engine = None
        if ratings is None:
            # CFEngine facade: snapshot() hands a consistent model view even
            # while update_ratings runs on another thread
            if getattr(cf_model, "scores", None) is None:
                raise ValueError("fit the engine first")
            self._snapshot = cf_model.snapshot
            if getattr(cf_model, "recommend_mode", "exact") == "approx":
                # two-stage serving: candidate items from the item index,
                # exact rerank — updates land between batches (the batcher
                # is the only recommend caller, so it always sees a fully
                # refolded index)
                self._approx_engine = cf_model
        else:
            # legacy UserCF + external ratings (static model)
            if cf_model.state is None:
                raise ValueError("fit the model first")
            st = cf_model.state
            snap = (ratings, st.scores, st.idx, st.means)
            self._snapshot = lambda: snap
        self.max_batch = max_batch
        self.max_wait = max_wait_ms / 1e3
        self.topn = topn
        self.max_queue = int(max_queue)
        # maxsize 0 = unbounded, matching queue.Queue — admission control
        # activates with the bound
        self._q: "queue.Queue" = queue.Queue(maxsize=self.max_queue)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # supervision: retry budget + backoff for transient batch failures,
        # optional deterministic fault injection (drills), optional
        # degradation ladder + straggler watchdog
        self._recovery = recovery if recovery is not None else \
            RecoveryPolicy(max_restarts=3)
        self._injector = fault_injector
        self._ladder = ladder
        self._watchdog = watchdog if watchdog is not None else \
            (StragglerWatchdog() if ladder is not None else None)
        # cross-thread control state: submit()/stats() read while stop()
        # and the batcher write — every access goes through _state_lock
        # (both the static lock-discipline check and the runtime race
        # harness hold this pair to account)
        self._state_lock = threading.Lock()
        self._stopped = False
        self._drain = True
        self._health = HEALTHY
        # batcher-thread-only bookkeeping (never touched by callers)
        self._batch_seq = 0
        self._window_n = 0
        self._prev_lat = None
        self._prev_depth = None
        if self._approx_engine is not None:
            ii = self._approx_engine.item_index
            self._base_n_probe = int(ii.n_probe)
            self._base_shortlist = int(ii.cfg.shortlist)
        else:
            self._base_n_probe = 0
            self._base_shortlist = 0
        # per-batch / per-request telemetry: histograms in a registry
        # (per-server by default so tests stay isolated; pass the process
        # registry to fold serving metrics into one dump).  The batcher
        # thread observes, stats() snapshots — both under the registry
        # lock, so there is no torn read of a mid-mutation deque.
        self.registry = registry if registry is not None \
            else obs.MetricsRegistry()
        self._h_latency = self.registry.histogram("serve.latency_seconds")
        self._h_queue = self.registry.histogram("serve.queue_seconds")
        self._h_compute = self.registry.histogram("serve.compute_seconds")
        self._h_fill = self.registry.histogram("serve.batch_fill")
        self._h_depth = self.registry.histogram("serve.queue_depth")
        self._c_requests = self.registry.counter("serve.requests")
        self._c_batches = self.registry.counter("serve.batches")
        self._c_failures = self.registry.counter("serve.failures")
        self._c_retries = self.registry.counter("serve.retries")
        self._c_recoveries = self.registry.counter("serve.recoveries")
        self._c_shed = self.registry.counter("serve.shed")
        self._c_deadline = self.registry.counter("serve.deadline_exceeded")
        self._c_transitions = self.registry.counter(
            "serve.health.transitions")
        self._g_health = self.registry.gauge("serve.health")
        self._g_health.set(HEALTHY)
        # warm the executable with the padded batch shape
        self._run_padded(jnp.zeros((self.max_batch,), jnp.int32))

    def _run_padded(self, users, budget: Optional[dict] = None):
        if self._approx_engine is not None:
            if budget:
                return self._approx_engine.recommend(
                    np.asarray(users), n=self.topn,
                    n_probe=budget["n_probe"],
                    shortlist=budget["shortlist"])
            return self._approx_engine.recommend(np.asarray(users),
                                                 n=self.topn)
        ratings, scores, idx, means = self._snapshot()
        return _predict_users(users, ratings, scores, idx, means,
                              topn=self.topn)

    # -- public API --------------------------------------------------------
    @property
    def n_batches(self) -> int:
        """Batches served so far (lock-consistent registry read)."""
        return int(self.registry.snapshot()["counters"]
                   .get("serve.batches", 0))

    @property
    def health(self) -> str:
        with self._state_lock:
            return HEALTH_STATES[self._health]

    def submit(self, user: int, *, deadline_ms: Optional[float] = None,
               request_class: str = "interactive") -> Future:
        """Enqueue one request; the returned future ALWAYS resolves.

        ``deadline_ms``: budget from now — still queued past it, the
        future resolves with :class:`DeadlineExceeded` before compute.
        ``request_class``: ``"interactive"`` (default) or ``"bulk"``
        (served at one degradation level worse, shed first).  Raises
        :class:`Overloaded` at the admission bound and
        :class:`ServerStopped` once the server stopped — both *before* a
        future exists, so a raised submit never strands anything.
        """
        if request_class not in REQUEST_CLASSES:
            raise ValueError(f"unknown request_class {request_class!r}; "
                             f"want one of {REQUEST_CLASSES}")
        fut: Future = Future()
        t0 = time.perf_counter()
        dl = None if deadline_ms is None else t0 + deadline_ms / 1e3
        # enqueue under the state lock: stop() flips _stopped under the
        # same lock *before* its final flush, so a request admitted here
        # is either served, drained, or flushed — never stranded.  The
        # shed counter is recorded *after* the lock is released: every
        # registry instrument shares the registry's single lock, and
        # nesting it under _state_lock is exactly the ordering edge the
        # lock-order detector exists to keep one-directional
        shed: Optional[Overloaded] = None
        with self._state_lock:
            if self._stopped:
                raise ServerStopped(
                    "submit() after stop(): the queue is no longer drained")
            if request_class == "bulk" and self._health >= SHEDDING:
                shed = Overloaded("shedding bulk traffic (health=SHEDDING)")
            else:
                try:
                    self._q.put_nowait((user, t0, dl, request_class, fut))
                except queue.Full:
                    shed = Overloaded(
                        f"admission queue at high-water mark "
                        f"({self.max_queue}); retry with backoff")
        if shed is not None:
            self._c_shed.inc()
            raise shed
        self._c_requests.inc()
        return fut

    def start(self):
        with self._state_lock:
            if self._stopped:
                raise ServerStopped("server already stopped")
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self, *, drain: bool = True, timeout: float = 30.0):
        """Stop the batcher; idempotent.  ``drain=True`` (default) serves
        everything already queued first; ``drain=False`` resolves queued
        futures with :class:`ServerStopped`.  Either way, when this
        returns no submitted future is unresolved."""
        with self._state_lock:
            self._stopped = True
            self._drain = drain
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
        # whatever is still queued (drain=False, a submit that raced the
        # flag, or a batcher that died) resolves here — never strands
        self._flush_queue(ServerStopped(
            "server stopped before serving this request"))

    # -- batcher -----------------------------------------------------------
    def _flush_queue(self, exc: BaseException) -> None:
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                return
            if not item[4].done():
                item[4].set_exception(exc)

    def _loop(self):
        try:
            while not self._stop.is_set():
                self._serve_next()
            with self._state_lock:
                drain = self._drain
            if drain:
                while self._serve_next(drain=True):
                    pass
        finally:
            # belt and braces: if the batcher exits for ANY reason with
            # requests still queued, mark the server stopped (so submit
            # raises instead of feeding a dead queue) and resolve the
            # leftovers — the no-stranded-future invariant must not
            # depend on which exit path ran
            with self._state_lock:
                self._stopped = True
            self._flush_queue(ServerStopped(
                "batcher exited before serving this request"))

    def _serve_next(self, drain: bool = False) -> bool:
        """Gather one batch of live requests and run it; False when none
        formed before the server stopped (draining: before the queue ran
        dry).  One ``serve.gather`` span covers the wait, however many
        polls time out on an empty queue, and is recorded only for a
        batch that runs."""
        with obs.span("serve.gather") as sp:
            live = self._gather_live(drain)
            if not live:
                sp.discard()
                return False
            self._batch_seq += 1
            sp.set_attr("batch_seq", self._batch_seq)
            sp.set_attr("batch_size", len(live))
        self._run_batch(live, self._batch_seq)
        return True

    def _gather_live(self, drain: bool) -> list:
        """Poll until a batch with a request whose deadline has not passed
        forms; [] once stopped (draining: once the queue is empty)."""
        while True:
            batch = self._gather(drain)
            if batch:
                live = self._triage(batch)
                if live:
                    return live
            elif drain or self._stop.is_set():
                return []

    def _triage(self, batch: list) -> list:
        """Resolve requests whose deadline passed in the queue with
        :class:`DeadlineExceeded`, before compute is spent on them; the
        rest are live."""
        now = time.perf_counter()
        live = []
        for req in batch:
            dl = req[2]
            if dl is not None and now >= dl:
                self._c_deadline.inc()
                req[4].set_exception(DeadlineExceeded(
                    f"deadline passed {(now - dl) * 1e3:.1f} ms ago while "
                    f"queued"))
            else:
                live.append(req)
        return live

    def _gather(self, drain: bool = False) -> list:
        batch: list = []
        if drain:
            while len(batch) < self.max_batch:
                try:
                    batch.append(self._q.get_nowait())
                except queue.Empty:
                    break
            return batch
        deadline = None
        while len(batch) < self.max_batch:
            timeout = self.max_wait if deadline is None else \
                max(deadline - time.perf_counter(), 0)
            try:
                batch.append(self._q.get(timeout=max(timeout, 1e-3)))
            except queue.Empty:
                break
            if deadline is None:
                deadline = time.perf_counter() + self.max_wait
            if time.perf_counter() >= deadline or self._stop.is_set():
                break
        return batch

    def _run_batch(self, live: list, seq: int) -> None:
        """Supervised batch execution: bounded retry on transient
        failures, resolve-with-error on everything else.  The batcher
        thread survives every path."""
        attempt = 0
        while True:
            try:
                if self._injector is not None:
                    self._injector.check(seq)
                self._execute(live, seq)
                if attempt:
                    self._c_recoveries.inc()
                return
            except TransientServeError as e:
                # recorded BEFORE the retry decision: a recovery can never
                # look like healthy batches in the metrics
                self._c_failures.inc()
                self._recovery.record_failure()
                live = [r for r in live if not r[4].done()]
                if attempt >= self._recovery.max_restarts or not live:
                    for r in live:
                        r[4].set_exception(e)
                    return
                attempt += 1
                self._c_retries.inc()
                self._recovery.record_restart()
                with obs.span("serve.recover", batch_seq=seq,
                              attempt=attempt, error=type(e).__name__):
                    time.sleep(self._recovery.backoff_s(attempt - 1))
            except Exception as e:
                # non-transient: fail the batch loudly — every pending
                # future gets the exception — and keep the batcher alive
                self._c_failures.inc()
                for r in live:
                    if not r[4].done():
                        r[4].set_exception(e)
                return

    def _execute(self, live: list, seq: int) -> None:
        # the batch count lives in the registry counter (`serve.batches`),
        # not a bare attribute: the batcher thread increments while
        # stats() reads, and the registry lock is what makes that pair
        # safe (the PR 2 stats() race, now enforced by reprolint's
        # lock-discipline check)
        self._c_batches.inc()
        # depth at launch: what this batch drained plus what is still queued
        self._h_depth.observe(len(live) + self._q.qsize())
        self._h_fill.observe(len(live) / self.max_batch)
        with obs.span("serve.batch", batch_size=len(live), batch_seq=seq):
            t_launch = time.perf_counter()
            for budget, cls, sub in self._plan(live):
                users = np.zeros((self.max_batch,), np.int32)
                for j, r in enumerate(sub):
                    users[j] = r[0]
                with obs.span("serve.predict", batch_size=len(sub),
                              batch_seq=seq, request_class=cls,
                              degraded=bool(budget)):
                    scores, items = self._run_padded(jnp.asarray(users),
                                                     budget)
                    scores = np.asarray(scores)   # host copy = device fence
                    items = np.asarray(items)
                now = time.perf_counter()
                for j, (u, t0, _dl, _cls, fut) in enumerate(sub):
                    # per-request latency split: queue wait (enqueue →
                    # batch launch) + compute wait (launch → resolved)
                    self._h_queue.observe(max(t_launch - t0, 0.0))
                    self._h_compute.observe(now - t_launch)
                    lat = (now - t0) * 1e3
                    self._h_latency.observe(lat / 1e3)
                    fut.set_result(Recommendation(
                        user=u, items=items[j], scores=scores[j],
                        latency_ms=lat, batch_seq=seq))
            compute_s = time.perf_counter() - t_launch
        self._after_batch(seq, compute_s)

    def _plan(self, live: list) -> List[tuple]:
        """Split the batch into (budget, class, requests) groups.  One
        full-batch group while HEALTHY (or without a ladder/approx
        engine); under degradation each request class runs at its own
        candidate budget — bulk one level worse than interactive."""
        if self._ladder is None or self._approx_engine is None:
            return [(None, "interactive", live)]
        with self._state_lock:
            level = self._health
        if level == HEALTHY:
            return [(None, "interactive", live)]
        groups: dict = {}
        for r in live:
            groups.setdefault(r[3], []).append(r)
        out = []
        for cls in sorted(groups):
            eff = level if cls == "interactive" else min(level + 1, SHEDDING)
            out.append((self._ladder.budget(eff, self._base_n_probe,
                                            self._base_shortlist, self.topn),
                        cls, groups[cls]))
        return out

    def _after_batch(self, seq: int, compute_s: float) -> None:
        """Feed the watchdog and, every ``ladder.window`` batches (or
        immediately on straggler escalation), evaluate the health level
        from windowed metrics."""
        straggler = False
        if self._watchdog is not None:
            self._watchdog.observe(seq, compute_s)
            straggler = self._watchdog.needs_escalation
        if self._ladder is None:
            return
        self._window_n += 1
        if self._window_n < self._ladder.window and not straggler:
            return
        self._window_n = 0
        snap = self.registry.snapshot()
        hl = snap["histograms"].get("serve.latency_seconds")
        hd = snap["histograms"].get("serve.queue_depth")
        p99_ms = (obs.delta_quantile(self._prev_lat, hl, 0.99) * 1e3
                  if hl else 0.0)
        depth = obs.delta_mean(self._prev_depth, hd) if hd else 0.0
        self._prev_lat, self._prev_depth = hl, hd
        with self._state_lock:
            level = self._health
        new, reason = self._ladder.next_level(level, p99_ms=p99_ms,
                                              queue_depth=depth,
                                              straggler=straggler)
        if new != level:
            self._transition(level, new, reason, p99_ms, depth)

    def _transition(self, old: int, new: int, reason: str, p99_ms: float,
                    depth: float) -> None:
        with self._state_lock:
            self._health = new
        self._g_health.set(new)
        self._c_transitions.inc()
        with obs.span("serve.health.transition",
                      from_state=HEALTH_STATES[old],
                      to_state=HEALTH_STATES[new], reason=reason,
                      p99_ms=round(p99_ms, 3),
                      queue_depth=round(depth, 2)):
            # engine-side knob: force the cheaper staged user-index
            # pipeline while degraded, restore config resolution on
            # recovery (per-call n_probe/shortlist budgets ride on each
            # recommend call instead — see _plan)
            eng = self._approx_engine
            if eng is not None and getattr(eng, "index", None) is not None \
                    and self._ladder.staged_when_degraded:
                eng.index.query_mode_override = \
                    "staged" if new > HEALTHY else None

    # -- telemetry ---------------------------------------------------------
    def stats(self) -> dict:
        """Serving-tier health from one lock-consistent registry snapshot:
        latency percentiles (histogram bucket upper bounds — see the
        module docstring), the queue-wait vs compute-wait split, batching
        efficiency, queue pressure, and the fault-tolerance counters.
        Counts cover the server's full lifetime."""
        snap = self.registry.snapshot()
        hists = snap["histograms"]

        def mean(name):
            h = hists.get(name)
            return h["sum"] / h["count"] if h and h["count"] else 0.0

        def count(name):
            return int(snap["counters"].get(name, 0))

        lat = hists.get("serve.latency_seconds")
        n = lat["count"] if lat else 0
        return {
            "n_requests": count("serve.requests"),
            "n_batches": count("serve.batches"),
            "latency_p50_ms": (lat["p50"] * 1e3 if n else 0.0),
            "latency_p99_ms": (lat["p99"] * 1e3 if n else 0.0),
            "queue_wait_mean_ms": mean("serve.queue_seconds") * 1e3,
            "compute_mean_ms": mean("serve.compute_seconds") * 1e3,
            "mean_batch_fill": mean("serve.batch_fill"),
            "mean_queue_depth": mean("serve.queue_depth"),
            "n_failures": count("serve.failures"),
            "n_retries": count("serve.retries"),
            "n_recoveries": count("serve.recoveries"),
            "n_shed": count("serve.shed"),
            "n_deadline_exceeded": count("serve.deadline_exceeded"),
            "health": HEALTH_STATES[int(snap["gauges"]
                                        .get("serve.health", 0))],
        }
