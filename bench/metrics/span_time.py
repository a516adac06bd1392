"""Times and counts of the item index's stages, read from the program's
spans of the window (``ctx["spans"]``, ``repro.obs.SpanRecord``).

A batch is one ``item_index.recommend`` span: one recommend call of the
engine and item index.  A span's self time is its duration less the
union of its children's intervals; a child has the span as its parent and
so ran on the same thread.
"""

from __future__ import annotations

RECOMMEND = "item_index.recommend"


def self_seconds(spans, name: str) -> float:
    """Summed self time of the spans called ``name``."""
    spans = list(spans)
    children = {}
    for s in spans:
        children.setdefault(s.parent_id, []).append(s)
    total = 0.0
    for s in spans:
        if s.name != name:
            continue
        lo, hi = s.t_start, s.t_start + s.duration
        covered, edge = 0.0, lo
        for c in sorted(children.get(s.span_id, ()), key=lambda c: c.t_start):
            c_lo, c_hi = max(c.t_start, edge), min(c.t_start + c.duration, hi)
            if c_hi > c_lo:
                covered += c_hi - c_lo
                edge = c_hi
        total += s.duration - covered
    return total


def seconds(spans, name: str) -> float:
    """Summed duration of the spans called ``name``."""
    return sum(s.duration for s in spans if s.name == name)


def per_batch_ms(ctx, name: str, *, self_time: bool) -> float | None:
    """Time in ``name`` spans per batch (ms); None without a batch or
    without such a span."""
    spans = ctx.get("spans") or ()
    batches = sum(s.name == RECOMMEND for s in spans)
    if not batches or not any(s.name == name for s in spans):
        return None
    t = self_seconds(spans, name) if self_time else seconds(spans, name)
    return 1e3 * t / batches


def repair_share(ctx) -> float | None:
    """Rows that took the selection's tie-boundary repair per row that
    ``recommend.select`` selected (%), over the spans that count them."""
    rows = repaired = 0
    for s in ctx.get("spans") or ():
        if s.name == "recommend.select" and "repaired" in s.attrs:
            rows += int(s.attrs["rows"])
            repaired += int(s.attrs["repaired"])
    if rows <= 0:
        return None
    return 100.0 * repaired / rows
