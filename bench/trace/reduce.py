"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time,
per-module and per-operation device time, and idle gaps labelled by what
the host was doing.

On a TPU the device plane ``/device:TPU:<n>`` has an ``XLA Ops`` line (one
event per HLO operation as it ran on the core) and an ``XLA Modules`` line
(one event per executed program, named ``jit_<function>(<hash>)``).  Busy
time is the union of the ``XLA Ops`` intervals; asynchronous copies
(``Async XLA Ops``) are not counted as busy.  Host threads are the lines of
``/host:CPU``; their events (``PjitFunction(<name>)`` dispatches, runtime
work, ``TraceAnnotation`` spans) label the gaps.
"""

from __future__ import annotations

import glob
import heapq
import os
import re
from bisect import bisect_right
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_GAP_NS = 1_000            # gaps shorter than a microsecond are noise
TOP = 10


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` a ``jax.profiler`` trace wrote under
    ``log_dir``."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {log_dir}, "
                                f"found {len(found)}")
    return found[0]


def _union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _module(name: str) -> str:
    """``jit_f(123)`` → ``jit_f``."""
    return name.split("(", 1)[0]


def _op(name: str) -> str:
    """``%fusion.1 = s8[...] fusion(...)`` → ``fusion``: the HLO opcode
    name without its instance number, or the name itself."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _profile_start_ns(planes) -> int | None:
    """Unix time (ns) at which the trace's relative timestamps start."""
    for pl in planes:
        if pl.name == "Task Environment":
            st = dict(pl.stats)
            if "profile_start_time" in st:
                return int(st["profile_start_time"])
    return None


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce_trace(path: str, clip=None, host_spans=()) -> dict:
    """Reduce one trace over ``clip = (start, end)`` in unix ns (the whole
    trace when None).

    ``host_spans``: extra host events ``(start, end, name)`` in unix ns,
    such as the program's own spans, that label idle gaps beside the
    profiler's host events.  Returns ``window_s``; ``devices`` (count);
    ``busy_s`` (mean over the device planes of the union of op
    intervals); ``modules`` and ``ops`` (``{name: device seconds}``,
    summed over devices; op names are ``<module>/<opcode>``);
    ``module_events`` (``{module: count}``); ``idle_gaps``
    (``{host label: seconds}``, device 0).  Raises when the trace has no
    device plane with operations.
    """
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    t0 = _profile_start_ns(planes)
    if clip is not None:
        if t0 is None:
            raise ValueError(f"{path} has no profile start time to clip by")
        lo, hi = clip[0] - t0, clip[1] - t0
    else:
        lo, hi = 0, None
    devices = [pl for pl in planes if pl.name.startswith("/device:TPU:")
               or pl.name.startswith("/device:GPU:")]
    modules = defaultdict(float)
    module_events = defaultdict(int)
    ops = defaultdict(float)
    busy = []
    first_busy = None
    for pl in devices:
        lines = {ln.name: list(ln.events) for ln in pl.lines}
        if hi is None:
            hi = max((e.start_ns + e.duration_ns
                      for ev in lines.values() for e in ev), default=0)
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns, _module(e.name))
                      for e in lines.get(MODULES_LINE, []))
        starts = [m[0] for m in mods]
        for s, e, name in mods:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                modules[name] += (e - s) / 1e9
                module_events[name] += 1
        intervals = []
        for ev in lines.get(OPS_LINE, []):
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if e <= s:
                continue
            intervals.append((s, e))
            j = bisect_right(starts, ev.start_ns) - 1
            mod = mods[j][2] if j >= 0 and ev.start_ns < mods[j][1] else "?"
            ops[f"{mod}/{_op(ev.name)}"] += (e - s) / 1e9
        if not intervals:
            continue
        merged = _union(intervals)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        if first_busy is None:
            first_busy = merged
    if not busy:
        raise ValueError(f"no device operations in {path}")
    extra = [(s - t0, e - t0, name) for s, e, name in host_spans] \
        if t0 is not None else []
    return {"window_s": (hi - lo) / 1e9, "devices": len(busy),
            "busy_s": sum(busy) / len(busy), "modules": dict(modules),
            "module_events": dict(module_events), "ops": dict(ops),
            "idle_gaps": _label_gaps(planes, first_busy, lo, hi, extra)}


def _host_events(planes, extra):
    """``(start, end, name)`` of every host event with a duration."""
    out = list(extra)
    for pl in planes:
        if not pl.name.startswith("/host:CPU"):
            continue
        for ln in pl.lines:
            for ev in ln.events:
                if ev.duration_ns > 0:
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    out.sort()
    return out


def _label_gaps(planes, merged, lo, hi, extra) -> dict:
    """Idle time on device 0 by the innermost host event that covers the
    middle of each gap (``untraced host`` where none does)."""
    gaps = []
    edge = lo
    for s, e in merged:
        if s - edge >= MIN_GAP_NS:
            gaps.append((edge, s))
        edge = max(edge, e)
    if hi - edge >= MIN_GAP_NS:
        gaps.append((edge, hi))
    host = _host_events(planes, extra)
    out = defaultdict(float)
    active = []           # heap of (duration, end, name) started so far
    i = 0
    for s, e in gaps:     # gaps arrive in time order, so do their middles
        mid = (s + e) // 2
        while i < len(host) and host[i][0] <= mid:
            hs, he, name = host[i]
            heapq.heappush(active, (he - hs, he, name))
            i += 1
        # an event ended before this middle ends before every later one
        while active and active[0][1] < mid:
            heapq.heappop(active)
        out[active[0][2] if active else "untraced host"] += (e - s) / 1e9
    return dict(out)


def top(d: dict, n: int = TOP) -> list:
    """The ``n`` largest ``[name, value]`` pairs, largest first."""
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
