"""The trace reduction on a trace recorded on a TPU v5e (a served wave
of 48 requests and a 512-user recommend, compiles included), and the
readers that take device metrics from it."""

from pathlib import Path

import pytest

from bench import run
from bench.metrics import score_work
from bench.trace import peaks, reduce

TRACE = Path(__file__).parent / "data" / "v5e_serve_bulk.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return reduce.reduce_trace(str(TRACE))


# unix ns at which the recorded trace starts (its Task Environment plane)
START_NS = 1792237978047472652


def test_busy_and_window(red):
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    # busy is a union, so at most the sum of the op times
    assert red["busy_s"] <= sum(red["ops"].values()) + 1e-9


def test_clip_to_a_window():
    lo, hi = START_NS + 1_000_000_000, START_NS + 2_000_000_000
    part = reduce.reduce_trace(str(TRACE), clip=(lo, hi))
    assert part["window_s"] == pytest.approx(1.0)
    assert 0 < part["busy_s"] < 1.0
    assert sum(part["idle_gaps"].values()) == pytest.approx(
        1.0 - part["busy_s"], rel=1e-3)


def test_program_spans_label_gaps():
    lo, hi = START_NS + 1_000_000_000, START_NS + 2_000_000_000
    part = reduce.reduce_trace(str(TRACE), clip=(lo, hi),
                               host_spans=[(lo, hi, "bench.outer")])
    # an event spanning the whole window labels the gaps nothing inner does
    assert "bench.outer" in part["idle_gaps"]
    assert "untraced host" not in part["idle_gaps"]


def test_modules_and_ops(red):
    assert red["module_events"][score_work.SCORE_MODULE] == 4
    assert red["modules"][score_work.SCORE_MODULE] > 0
    assert red["modules"]["jit__rerank_items"] > \
        red["modules"][score_work.SCORE_MODULE]
    top = reduce.top(red["ops"])
    assert len(top) == reduce.TOP
    assert top[0][0] == "jit__rerank_items/fusion"
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)


def test_idle_gaps_cover_the_idle_time(red):
    idle = red["window_s"] - red["busy_s"]
    assert sum(red["idle_gaps"].values()) == pytest.approx(idle, rel=1e-3)


def _ctx(red, users):
    cell = run.load_cell("ml1m-steady")
    return {"trace": red, "peaks": peaks.peaks("TPU v5 lite"),
            "config": cell["config"], "users_scored": users,
            "window_s": red["window_s"]}


def test_readers(red):
    ctx = _ctx(red, users=48 + 512)
    share = run.metric_reader("item_scorer_roofline.serve")(ctx)
    bytes_ = score_work.score_bytes(560, 40, 3952)
    least = bytes_ / 819e9
    assert share == pytest.approx(
        100 * least / red["modules"][score_work.SCORE_MODULE])
    assert 0 < share < 100
    idle = run.metric_reader("device_idle_share.bulk")(ctx)
    assert idle == pytest.approx(100 * (1 - red["busy_s"] / red["window_s"]))
    # nothing to read: the reader returns None, never 0
    assert run.metric_reader("item_scorer_roofline.bulk")(
        _ctx(red, users=0)) is None
    assert run.metric_reader("queue_wait_ms.serve")({"serve": None}) is None


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_no_device_plane_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        reduce.find_xplane(str(tmp_path))
