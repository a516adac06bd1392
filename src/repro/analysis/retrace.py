"""Retrace sentinel: count jit cache misses per measurement window.

A jitted hot path that retraces at steady state is a silent performance
bug: every new (shape, dtype, static) signature pays tracing + XLA
compilation — hundreds of milliseconds — inside what the benchmarks
believe is a warm measurement.  The shape-bucketing in the fused query
pipeline (the ``ku``/support-bucket padding from PR 4/6) exists exactly
to prevent this, so a regression there shows up as wall-clock noise long
before anyone thinks to check compile counts.  This sentinel makes the
invariant explicit and cheap to assert:

    # warm up first — first-call compiles are expected
    run_queries()
    with RetraceSentinel("bench_index.steady") as s:
        run_queries()          # same shapes: must be all cache hits
    assert s.count == 0

Two complementary probes:

* **Global compile events** — a ``jax.monitoring`` duration listener on
  the backend-compile event, which fires once per real compilation (cache
  hits are silent).  This catches *any* compile in the window, including
  ones inside functions the caller cannot name.  jax.monitoring has no
  unregister API, so one module-level listener is installed once and
  dispatches to whichever sentinels are active.
* **Per-site cache sizes** — ``watch(name, jit_fn)`` snapshots a jitted
  function's ``_cache_size()`` so the exit report attributes misses to
  call sites (``per_site``).

On exit the sentinel publishes ``analysis.retrace.count`` on the default
metrics registry, which the bench JSON artifacts carry and CI asserts
== 0 at steady state.  The linter's trace-level ``retrace`` check uses
:func:`steady_state_findings` to run the same assertion over the
registered hot paths in :mod:`repro.analysis.jaxpr`.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence

from repro.analysis.findings import Finding

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
GAUGE = "analysis.retrace.count"

_mu = threading.Lock()
_active: List["RetraceSentinel"] = []
_listener_installed = False


def _on_duration(event, duration, **kw):
    if not str(event).endswith("backend_compile_duration"):
        return
    with _mu:
        for s in _active:
            s._compiles += 1


def _install_listener() -> bool:
    """Install the module-level jax.monitoring listener exactly once
    (``jax.monitoring`` has no public unregister).  Returns availability."""
    global _listener_installed
    if _listener_installed:
        return True
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
    except Exception:  # reprolint: disable=silent-fallback -- availability is the return value: callers surface it as events_available and fall back to cache-size probes
        return False
    _listener_installed = True
    return True


class RetraceSentinel:
    """Context manager counting jit compilations in its window."""

    def __init__(self, name: str = "retrace", *, publish: bool = True):
        self.name = name
        self.publish = publish
        self._compiles = 0
        self._watched: Dict[str, tuple] = {}     # name -> (fn, size_at_watch)
        self.per_site: Dict[str, int] = {}
        self.count: Optional[int] = None
        self.events_available = False

    def watch(self, name: str, jit_fn) -> None:
        """Attribute cache misses of ``jit_fn`` to ``name`` in the exit
        report.  Works on jax.jit/functools.partial-wrapped callables
        that expose ``_cache_size`` (plain jitted functions do)."""
        probe = getattr(jit_fn, "_cache_size", None)
        if probe is None:
            probe = getattr(getattr(jit_fn, "func", None),
                            "_cache_size", None)
        if probe is not None:
            self._watched[name] = (probe, int(probe()))

    def __enter__(self) -> "RetraceSentinel":
        self.events_available = _install_listener()
        self._compiles = 0
        with _mu:
            _active.append(self)
        return self

    def __exit__(self, *exc) -> bool:
        with _mu:
            if self in _active:
                _active.remove(self)
            compiles = self._compiles
        self.per_site = {name: int(probe()) - start
                         for name, (probe, start) in self._watched.items()}
        site_total = sum(d for d in self.per_site.values() if d > 0)
        self.count = compiles if self.events_available else site_total
        if self.publish:
            try:
                from repro import obs
                obs.registry().gauge(GAUGE).set(float(self.count))
            except Exception:  # reprolint: disable=silent-fallback -- gauge publication must never mask the measurement; self.count is still returned to the caller that asserts on it
                pass
        return False


def steady_state_findings(hot_paths=None) -> List[Finding]:
    """The linter's trace-level ``retrace`` check: warm every registered
    hot path, then call it again with *fresh arrays of the same shapes* —
    any cache growth on the second call is a finding (the function's
    cache key depends on something it shouldn't, e.g. array identity or
    an unhashable static)."""
    from repro.analysis import jaxpr as jx
    hps = jx.HOT_PATHS if hot_paths is None else hot_paths
    out: List[Finding] = []
    for hp in hps:
        fn, call, make_args, _names = hp.build()
        call(*make_args())                         # warmup: compiles expected
        before = int(fn._cache_size())
        call(*make_args())                         # same shapes, fresh arrays
        delta = int(fn._cache_size()) - before
        if delta > 0:
            out.append(Finding(
                check="retrace", path=hp.path, line=0, col=0,
                symbol=f"{hp.name}:steady-state",
                message=f"{hp.name} recompiled {delta}× on a same-shape "
                        f"second call — its jit cache key varies when it "
                        f"should not (check statics/weak types); steady-"
                        f"state retraces burn wall clock silently"))
    return out
