"""Nested, thread-aware span tracing for the two-stage index and serving
tier.

A :class:`Span` times one stage of work on the thread that runs it.  Spans
nest through a thread-local stack — a span opened while another span is
active on the *same* thread records that span as its parent, so one
``index.query`` call yields a tree: probe / scan / select / rerank children
under the query root, and worker-thread spans (the shortlist scorer pool,
the serving batcher) start their own roots tagged with their thread id.

Spans **always time** (the stage timers in ``QueryStats`` are derived from
span durations, so the clock must run whether or not anyone is watching);
the *enabled* flag only controls whether finished records are appended to
the bounded in-process buffer that the exporters read.  That makes the
enabled-vs-disabled delta of the hot paths a few dict writes and one
lock-guarded list append per span — the ≤2 % overhead budget the benchmark
row asserts.

Device stages lie to wall clocks: a jitted call returns after *dispatch*,
not completion.  ``device_sync=True`` (on :func:`traced`) or
:meth:`Span.track` (on a context-manager span) inserts a
``jax.block_until_ready`` fence on the tracked values before the span
closes, so the recorded duration covers the device work — measured
honestly instead of timing dispatch.

While a JAX profiler session is recording, each span also opens a
``jax.profiler.TraceAnnotation`` of the same name, so the program's spans
appear in the profiler's ``.xplane.pb`` on its own clock, on the host
thread that ran them.  With no session recording that costs one
``is_enabled()`` check per span.

No dependencies beyond the standard library; ``jax`` is imported lazily,
only when a fence is actually requested, and the profiler is looked up
only once something else has imported ``jax``.
"""

from __future__ import annotations

import dataclasses
import itertools
import sys
import threading
import time
from typing import Any, Dict, List, Optional

_DEFAULT_CAPACITY = 200_000

_lock = threading.Lock()
_enabled = True
_capacity = _DEFAULT_CAPACITY
_records: List["SpanRecord"] = []
_dropped = 0
_ids = itertools.count(1)
_tls = threading.local()

# perf_counter epoch → unix time, so exported timestamps are wall-clock
# anchored while durations keep perf_counter's monotonic resolution
_EPOCH_UNIX = time.time() - time.perf_counter()

# jax.profiler.TraceAnnotation, once looked up (see _annotate)
_annotation = None


@dataclasses.dataclass
class SpanRecord:
    """One finished span, as the exporters see it."""
    name: str
    span_id: int
    parent_id: int            # 0 → root (no enclosing span on this thread)
    thread_id: int
    thread_name: str
    t_start: float            # perf_counter timebase (see _EPOCH_UNIX)
    duration: float           # seconds
    attrs: Dict[str, Any]


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class Span:
    """Context-manager span; see the module docstring.

    Attributes land in the record via constructor kwargs,
    :meth:`set_attr`, or :meth:`track` (which also registers a value for
    the ``device_sync`` fence).  ``duration`` is valid after ``__exit__``
    whether or not tracing is enabled.
    """

    __slots__ = ("name", "attrs", "device_sync", "span_id", "parent_id",
                 "t_start", "duration", "_tracked", "_ann", "_keep")

    def __init__(self, name: str, *, device_sync: bool = False, **attrs):
        self.name = name
        self.attrs = attrs
        self.device_sync = device_sync
        self.span_id = 0
        self.parent_id = 0
        self.t_start = 0.0
        self.duration = 0.0
        self._tracked: list = []
        self._ann = None
        self._keep = True

    # -- attribute / fence plumbing ---------------------------------------
    def set_attr(self, key: str, value) -> None:
        self.attrs[key] = value

    def track(self, value):
        """Register ``value`` for the exit fence (returns it unchanged),
        and fence it immediately when ``device_sync`` is set so the time
        is attributed to *this* span even if more host work follows."""
        if self.device_sync:
            _fence(value)
        else:
            self._tracked.append(value)
        return value

    def discard(self) -> None:
        """Record nothing when this span closes (it still times): for a
        wait that ended without the work it was waiting for."""
        self._keep = False

    # -- context manager ---------------------------------------------------
    def __enter__(self) -> "Span":
        st = _stack()
        self.parent_id = st[-1].span_id if st else 0
        self.span_id = next(_ids)
        st.append(self)
        self._ann = _annotate(self.name)
        self.t_start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self.device_sync and self._tracked:
            _fence(self._tracked)
        self.duration = time.perf_counter() - self.t_start
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        elif self in st:            # mis-nested exit: drop up to self
            i = st.index(self)
            for inner in reversed(st[i + 1:]):   # annotations close LIFO
                inner._close_annotation()
            del st[i:]
        self._close_annotation()
        if _enabled and self._keep:
            th = threading.current_thread()
            rec = SpanRecord(name=self.name, span_id=self.span_id,
                             parent_id=self.parent_id,
                             thread_id=th.ident or 0, thread_name=th.name,
                             t_start=self.t_start, duration=self.duration,
                             attrs=dict(self.attrs))
            global _dropped
            with _lock:
                if len(_records) < _capacity:
                    _records.append(rec)
                else:
                    _dropped += 1
        return None

    def _close_annotation(self) -> None:
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)


def _annotate(name: str):
    """An entered ``jax.profiler.TraceAnnotation`` named ``name`` while a
    profiler session records, else None.  Before anything has imported
    ``jax`` no session can be recording, so nothing is looked up."""
    global _annotation
    cls = _annotation
    if cls is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation
        cls = _annotation = TraceAnnotation
    if not cls.is_enabled():
        return None
    ann = cls(name)
    ann.__enter__()
    return ann


def span(name: str, *, device_sync: bool = False, **attrs) -> Span:
    """Open a span: ``with obs.span("query.rerank", kind="fused") as sp:``."""
    return Span(name, device_sync=device_sync, **attrs)


def traced(name: Optional[str] = None, *, device_sync: bool = False,
           **attrs):
    """Decorator form: time every call of ``fn`` as a span named after it.

    ``device_sync=True`` fences the return value (``block_until_ready``
    over the pytree) before the span closes — the honest way to time a
    function that dispatches device work.
    """
    def deco(fn):
        import functools
        label = name or f"{fn.__module__}.{fn.__qualname__}"

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with Span(label, device_sync=device_sync, **attrs):
                out = fn(*args, **kwargs)
                if device_sync:
                    _fence(out)
                return out
        return wrapper
    return deco


def _fence(value) -> None:
    """Block until ``value`` (a pytree of device arrays, or anything with
    a ``block_until_ready`` method) is actually computed."""
    try:
        import jax
        jax.block_until_ready(value)
        return
    except ImportError:  # pragma: no cover - jax ships in the container
        pass
    if hasattr(value, "block_until_ready"):  # pragma: no cover
        value.block_until_ready()


def current_span() -> Optional[Span]:
    """The innermost open span on this thread (None outside any span)."""
    st = _stack()
    return st[-1] if st else None


# -- buffer management -----------------------------------------------------
def enable() -> None:
    """Record finished spans into the trace buffer (the default)."""
    global _enabled
    _enabled = True


def disable() -> None:
    """Stop recording (spans still time; see module docstring)."""
    global _enabled
    _enabled = False


def is_enabled() -> bool:
    return _enabled


def set_capacity(n: int) -> None:
    """Bound the trace buffer at ``n`` finished spans (drop-newest)."""
    global _capacity
    with _lock:
        _capacity = max(int(n), 0)
        del _records[_capacity:]


def get_spans() -> List[SpanRecord]:
    """Snapshot of the finished-span buffer (oldest first)."""
    with _lock:
        return list(_records)


def dropped_spans() -> int:
    """Finished spans discarded because the buffer was at capacity."""
    with _lock:
        return _dropped


def clear() -> None:
    """Empty the trace buffer (open spans are unaffected)."""
    global _dropped
    with _lock:
        _records.clear()
        _dropped = 0
