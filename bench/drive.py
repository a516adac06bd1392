"""Phases of one benchmark run: the engine fit, the traffic schedule,
warm-up, and the measured window of each kind of loop.

The traffic generator is general: a mix file (``bench/traffic/*.json``)
gives the loop (``open`` or ``closed``), and for an open loop a period of
phases, each at a rate that is a multiple of the configuration's knee
(see ``schedule``).
"""

from __future__ import annotations

import dataclasses
import gc
import threading
import time

import numpy as np

GRACE_S = 60.0        # how long past the window's close a request may take
WARM_REQUESTS = 64    # requests served before the window opens


def fit_engine(ratings, cfg: dict, *, index_cfg=None, item_index_cfg=None):
    """The deployment's engine, fitted: ``CFEngine`` with the file's
    settings and the program's auto index configs (a test may pass
    configs that force interpret mode on a CPU host)."""
    from repro.core import CFEngine
    e = cfg["engine"]
    eng = CFEngine(ratings, measure=e["measure"], k=int(e["k"]),
                   neighbor_mode=e["neighbor_mode"],
                   recommend_mode=e["recommend_mode"],
                   index_cfg=index_cfg, item_index_cfg=item_index_cfg)
    return eng.fit()


def resolved_modes(engine) -> dict:
    """The paths the last user-index query and item-index recommend took."""
    q = engine.index.last_query
    return {"query_mode": q.query_mode, "scan_mode": q.scan_mode,
            "select_mode": q.select_mode, "rerank_mode": q.rerank_mode,
            "item_scorer": engine.item_index.last_recommend.scorer,
            "index_interpret": engine.index.cfg.interpret,
            "item_index_interpret": engine.item_index.cfg.interpret}


# what auto configs resolve to on a TPU (chip_smoke.py, PR 11): the fused
# device query, top_k selection, fused rerank, Pallas support scorer, no
# interpret mode anywhere
DEVICE_MODES = {"query_mode": "fused", "scan_mode": "kernel",
                "select_mode": "top_k", "rerank_mode": "fused",
                "item_scorer": "kernel", "index_interpret": False,
                "item_index_interpret": False}


def schedule(traffic: dict, knee_rps: float, seconds: float, seed: int,
             n_users: int):
    """``(due offsets in s, users)`` of an open-loop mix over ``seconds``.

    Every seed gets the same arrivals in another order: each phase of
    each period draws its gaps from a stream fixed by the period and the
    phase (gaps of sorted uniform times, so a Poisson process conditioned
    on its count), and the seed shuffles them and draws the users.
    """
    if traffic.get("users", "uniform") != "uniform":
        raise ValueError(f"unknown user draw {traffic['users']!r}")
    rng = np.random.default_rng([int(seed), 1])
    period = float(traffic["period_s"])
    due = []
    for p in range(int(np.ceil(seconds / period - 1e-9))):
        start = p * period
        for j, ph in enumerate(traffic["phases"]):
            full = float(ph["seconds"])
            length = min(full, seconds - start)
            if length <= 0:
                break
            count = int(round(float(ph["rate_of_knee"]) * knee_rps * full
                              * length / full))
            fixed = np.random.default_rng([p, j, 0x5EED])
            times = np.sort(fixed.uniform(0.0, length, count + 1))
            gaps = rng.permutation(np.diff(times))
            due.append(start + times[0] + np.cumsum(gaps))
            start += full
    due = np.concatenate(due) if due else np.zeros(0)
    users = rng.integers(0, n_users, len(due))
    return due, users


class OpenLoop:
    """Submit each request at its due time from one thread; record when
    it was due, sent and resolved.  Like a client, it lets go of each
    answer once resolved, but for the requests in ``keep`` (those
    compared for ``correct``): answers held to the end of the window
    would grow the heap that the process's garbage collector walks."""

    def __init__(self, server, due: np.ndarray, users: np.ndarray,
                 keep=()):
        self.server, self.due, self.users = server, due, users
        n = len(due)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.ok = np.zeros(n, bool)
        self.answers = {}            # index in ``keep`` -> Recommendation
        self._keep = frozenset(int(i) for i in keep)
        self._left = n
        self._all_done = threading.Event()
        self._lock = threading.Lock()
        if n == 0:
            self._all_done.set()

    def _resolved(self, i):
        def cb(fut):
            self.done[i] = time.perf_counter()
            with self._lock:
                if fut.exception() is None:
                    self.ok[i] = True
                    if i in self._keep:
                        self.answers[i] = fut.result()
                self._left -= 1
                if self._left == 0:
                    self._all_done.set()
        return cb

    def run(self) -> float:
        """Send every request on schedule; returns the window's open time
        (perf_counter)."""
        t_open = time.perf_counter()
        self.t_open = t_open
        for i, (d, u) in enumerate(zip(self.due, self.users)):
            wait = t_open + d - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.sent[i] = time.perf_counter()
            self.server.submit(int(u)).add_done_callback(self._resolved(i))
        return t_open

    def wait(self, close: float, grace_s: float = GRACE_S) -> None:
        """Wait until every request resolved or ``grace_s`` past
        ``close``."""
        self._all_done.wait(max(close + grace_s - time.perf_counter(), 0.0))

    def results(self):
        """``(latency_s, ok)`` per request: a request that never resolved
        or failed is not ok, and its latency runs to now."""
        now = time.perf_counter()
        with self._lock:
            ok = self.ok.copy()
        lat = np.where(ok, self.done - (self.t_open + self.due),
                       now - (self.t_open + self.due))
        return lat, ok

    def kept(self, ok: np.ndarray) -> list:
        """The kept answers of the requests ``ok`` marks, in order."""
        with self._lock:
            return [r for i, r in sorted(self.answers.items()) if ok[i]]


def warm_serve(server, n_users: int, seed: int,
               count: int = WARM_REQUESTS) -> None:
    """Serve ``count`` requests so every shape of the served path is
    compiled and every lazy operand built before the window."""
    rng = np.random.default_rng([int(seed), 2])
    futs = [server.submit(int(u))
            for u in rng.integers(0, n_users, count)]
    for f in futs:
        f.result(timeout=600)


class GcPauses:
    """Python garbage-collector pauses while open (``gc.callbacks``): a
    pause stops every thread of the process, the server's among them."""

    def __init__(self):
        self.pauses = []
        self._t0 = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))
            self._t0 = None

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)
        return False

    def summary(self) -> str:
        full = [d for g, d in self.pauses if g == 2]
        return (f"gc pauses {len(self.pauses)} ({len(full)} full), "
                f"{sum(d for _, d in self.pauses) * 1e3:.1f} ms in all, "
                f"longest {max((d for _, d in self.pauses), default=0) * 1e3:.1f} ms")


def bulk_step(engine, n: int):
    """One closed-loop step: top-``n`` for every user, on the host."""
    s, i = engine.recommend(None, n)
    return np.asarray(s), np.asarray(i)


@dataclasses.dataclass
class Window:
    """What one measured window produced."""
    t_open: float          # perf_counter when the window opened
    seconds: float         # until the last request resolved / step ended
    attempted: int         # requests sent, or users given a list
    failed: int            # requests that failed or never resolved
    e2e: dict              # end-to-end metrics, setup_s aside
    answers: list          # seeded sample of [(user, items, scores)]
    note: str              # one line for standard error
    latency_s: np.ndarray | None = None   # every request's, from its due time


def _percentile(x, q) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q))


def serve_window(server, due, users, seconds: float, sample) -> Window:
    """Send the schedule, wait for every answer (``GRACE_S`` at most past
    the close), and time each request from when it was due.  ``sample(n)``
    gives the indices of the requests whose answers are compared."""
    loop = OpenLoop(server, due, users, keep=sample(len(due)))
    t_open = loop.run()
    loop.wait(t_open + seconds)
    length = time.perf_counter() - t_open
    lat, ok = loop.results()
    late = loop.sent - (t_open + due)
    answers = [(int(r.user), np.asarray(r.items, np.int64),
                np.asarray(r.scores, np.float64)) for r in loop.kept(ok)]
    note = (f"requests {len(due)} ({len(due) / seconds:.1f}/s offered), "
            f"failed {int((~ok).sum())}; latency p99 "
            f"{_percentile(lat, 99) * 1e3:.3f} ms; generator late p50 "
            f"{_percentile(late, 50) * 1e3:.3f} ms, p99 "
            f"{_percentile(late, 99) * 1e3:.3f} ms, max "
            f"{float(np.max(late)) * 1e3:.3f} ms")
    return Window(t_open, length, len(due), int((~ok).sum()),
                  {"recommend_p50_ms": _percentile(lat, 50) * 1e3},
                  answers, note, lat)


def bulk_window(engine, n: int, seconds: float, sample, seed: int) -> Window:
    """Steps back to back until ``seconds`` have passed; the window runs
    to the end of the last step.  The compared answers are the sampled
    users' lists, each from a step drawn from the seed."""
    outputs = []
    t_open = time.perf_counter()
    while time.perf_counter() - t_open < seconds:
        outputs.append(bulk_step(engine, n))
    length = time.perf_counter() - t_open
    done = len(outputs) * engine.n_users
    rng = np.random.default_rng([int(seed), 3])
    answers = []
    for u in sample(engine.n_users):
        s, i = outputs[int(rng.integers(0, len(outputs)))]
        answers.append((int(u), i[u].astype(np.int64),
                        s[u].astype(np.float64)))
    return Window(t_open, length, done, 0,
                  {"bulk_recs_per_s": done / length}, answers,
                  f"bulk steps {len(outputs)} of {engine.n_users} users "
                  f"in {length:.3f}s")
