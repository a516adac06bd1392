"""The comparison that decides ``correct`` fails the control (the
reference in bfloat16) and a run whose timed path is broken underneath."""

import numpy as np
import pytest

from bench.tests.conftest import rehearse, tiny_cell


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_not_correct(seed):
    from bench.reference import check
    cell = tiny_cell("ml1m-steady")
    res = rehearse(cell, seed, control=True)
    assert res["correct"]
    lim = check.limits(cell["config"])
    ok, _ = check.judge(res["control_checks"], lim)
    assert not ok


def _alter_top_score(s, i):
    return s.at[:, 0].add(0.01), i


def _promote_a_worse_item(s, i):
    # the served top item replaced by the one ranked just below the list
    return s, i.at[:, 0].set(i[:, -1])


def _half_batch_left_out(s, i):
    # every second row of the batch gets its neighbor row's answer
    return s.at[1::2].set(s[0:-1:2]), i.at[1::2].set(i[0:-1:2])


FAULTS = {"answer_altered": _alter_top_score,
          "worse_item_served": _promote_a_worse_item,
          "half_batch_left_out": _half_batch_left_out}


@pytest.mark.parametrize("cell_name", ["ml1m-steady", "ml1m-bulk"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_path_is_not_correct(cell_name, fault, monkeypatch):
    from repro.index.item_index import ItemClusteredIndex
    real = ItemClusteredIndex.recommend
    broken = FAULTS[fault]

    def recommend(self, *a, **kw):
        s, i = real(self, *a, **kw)
        return broken(s, i)

    monkeypatch.setattr(ItemClusteredIndex, "recommend", recommend)
    res = rehearse(tiny_cell(cell_name), seed=7)
    assert not res["correct"], res["checks"]
