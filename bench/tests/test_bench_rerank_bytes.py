"""The reader of the rerank's gather bytes per query row, on hand-made
span records: what it reads, and that a program whose spans lack the
count reads nothing."""

import pytest

from bench import run
from repro.obs import SpanRecord

_ids = iter(range(1, 10_000))


def _span(name, **attrs):
    return SpanRecord(name=name, span_id=next(_ids), parent_id=0,
                      thread_id=1, thread_name="t", t_start=0.0,
                      duration=0.001, attrs=attrs)


def _read(**ctx):
    return run.metric_reader("rerank_kb_per_user.serve")(ctx)


def test_bytes_per_row_over_the_window():
    # two batches of 16 and 5 rows: k = 40 over 21 tiles of 512 items,
    # one byte a rating, and one batch of an f32 operand (4 bytes)
    row = 40 * 21 * 512
    spans = [_span("recommend.rerank", rows=16, gather_bytes=16 * row),
             _span("recommend.rerank", rows=5, gather_bytes=5 * row),
             _span("recommend.score", rows=16)]
    assert _read(spans=spans) == pytest.approx(row / 1e3)
    spans.append(_span("recommend.rerank", rows=21, gather_bytes=21 * 4 * row))
    assert _read(spans=spans) == pytest.approx(2.5 * row / 1e3)


@pytest.mark.parametrize("spans", [
    [],
    # a program whose rerank spans count shortlist entries and no bytes
    [_span("recommend.rerank", rows=160, chunk=0)],
    [_span("recommend.rerank", rows=0, gather_bytes=0)]])
def test_nothing_to_read(spans):
    assert _read(spans=spans) is None
