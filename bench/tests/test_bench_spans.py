"""The readers of the item index's stage spans, on hand-made span
records, and the pin on the support kernel's name in the device trace."""

import pytest

from bench import run
from bench.metrics import score_work
from repro.obs import SpanRecord

_ids = iter(range(1, 10_000))


def _span(name, t, dur, parent=None, **attrs):
    return SpanRecord(name=name, span_id=next(_ids),
                      parent_id=parent.span_id if parent else 0,
                      thread_id=1, thread_name="t", t_start=t,
                      duration=dur, attrs=attrs)


def _batches():
    """Two recommend calls of 10 ms.  The first has a score stage that
    overlaps its selection wait (1-4 ms and 3-6 ms) and a rerank at
    7-9 ms; the score span has a 0.5 ms child.  The second has only a
    2 ms selection wait."""
    a = _span("item_index.recommend", 0.0, 0.010)
    score = _span("recommend.score", 0.001, 0.003, a)
    b = _span("item_index.recommend", 0.020, 0.010)
    return [a, score,
            _span("recommend.select", 0.0015, 0.0005, score,
                  rows=16, repaired=4),
            _span("recommend.select_wait", 0.003, 0.003, a, chunk=0,
                  rows=16),
            _span("recommend.rerank", 0.007, 0.002, a, chunk=0, rows=160),
            b,
            _span("recommend.select_wait", 0.022, 0.002, b, chunk=0,
                  rows=16),
            _span("recommend.select", 0.022, 0.001, rows=16, repaired=0)]


def _read(name, **ctx):
    return run.metric_reader(name)(ctx)


def test_per_batch_times():
    spans = _batches()
    # score self time: 3 ms less its 0.5 ms child, over two batches
    assert _read("score_ms.serve", spans=spans) == pytest.approx(1.25)
    assert _read("select_wait_ms.serve", spans=spans) == pytest.approx(2.5)
    # self time: 10 ms less the union 1-6 and 7-9 ms (7 ms), and 10 ms
    # less 2 ms
    assert _read("recommend_self_ms.serve", spans=spans) == \
        pytest.approx((3.0 + 8.0) / 2)


@pytest.mark.parametrize("cell", ["serve", "bulk"])
def test_repair_share(cell):
    assert _read(f"select_repair_share.{cell}", spans=_batches()) == \
        pytest.approx(100 * 4 / 32)


def test_select_wait_share_of_the_window():
    assert _read("select_wait_share.bulk", spans=_batches(),
                 window_s=0.05) == pytest.approx(100 * 0.005 / 0.05)


@pytest.mark.parametrize("name", [
    "score_ms.serve", "select_wait_ms.serve", "recommend_self_ms.serve",
    "select_repair_share.serve", "select_repair_share.bulk",
    "select_wait_share.bulk"])
def test_nothing_to_read(name):
    assert _read(name, spans=[], window_s=51.0) is None
    # a program whose spans lack the stages, or the count, reads nothing
    old = [_span("item_index.recommend", 0.0, 0.01),
           _span("recommend.rerank", 0.004, 0.005),
           _span("recommend.select", 0.0, 0.001, rows=16)]
    assert _read(name, spans=old, window_s=51.0) is None


def test_support_kernel_name_is_the_traced_module():
    from repro.kernels.support import fused_support_scores
    assert "jit_" + fused_support_scores.__name__ == score_work.SCORE_MODULE
