"""Engine-level checkpoint/recovery drills: faults injected during
``update_ratings`` and mid-refold leave no torn state behind once the
engine restores from the last committed checkpoint, and post-recovery
results are bit-identical to a fault-free run."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import CFEngine
from repro.distributed import checkpoint
from repro.distributed.fault_tolerance import FaultInjector, InjectedFault
from repro.index import IndexConfig


def _engine(rng, u=64, d=32, **kw):
    r = jnp.asarray((rng.integers(1, 6, (u, d))
                     * (rng.random((u, d)) < 0.5)).astype(np.float32))
    return CFEngine(r, measure="cosine", k=5, block_size=16, **kw).fit()


def _approx_engine(rng, **kw):
    return _engine(rng, neighbor_mode="approx", recommend_mode="approx",
                   index_cfg=IndexConfig(n_clusters=8, seed=0,
                                         features="raw"), **kw)


def _updates(rng, n, u=64, d=32):
    return [([int(rng.integers(0, u))], [int(rng.integers(0, d))],
             [float(rng.integers(1, 6))]) for _ in range(n)]


def _recs(eng, users=(0, 3, 7, 11)):
    scores, items = eng.recommend(np.asarray(users, np.int32), n=5)
    return np.asarray(scores), np.asarray(items)


def test_state_checkpoint_round_trip_is_bit_identical(rng, tmp_path):
    eng = _approx_engine(rng)
    for uu, ii, vv in _updates(rng, 4):
        eng.update_ratings(uu, ii, vv)
    ref_s, ref_i = _recs(eng)
    checkpoint.save(tmp_path, 1, eng.state())
    # trample the model, then restore: recommendations must match bitwise
    eng.update_ratings([0, 1], [0, 1], [1.0, 1.0])
    eng.load_state(checkpoint.restore(tmp_path, 1, eng.state_template()))
    got_s, got_i = _recs(eng)
    np.testing.assert_array_equal(got_i, ref_i)
    np.testing.assert_array_equal(got_s, ref_s)


def test_exact_engine_state_round_trip(rng, tmp_path):
    eng = _engine(rng)
    ref_s, ref_i = _recs(eng)
    checkpoint.save(tmp_path, 3, eng.state())
    eng.update_ratings([2], [2], [5.0])
    eng.load_state(checkpoint.restore(tmp_path, 3, eng.state_template()))
    got_s, got_i = _recs(eng)
    np.testing.assert_array_equal(got_i, ref_i)
    np.testing.assert_array_equal(got_s, ref_s)
    assert eng.ratings_version == int(np.asarray(
        eng.state()["meta"]).reshape(-1)[0])


def test_fault_during_update_recovers_bit_identical(rng, tmp_path):
    """The drill: checkpoint, inject a fault inside update_ratings,
    restore, re-apply — results must match a fault-free run that took the
    same restore path."""
    eng = _approx_engine(rng)
    u1, u2 = _updates(rng, 2)
    eng.update_ratings(*u1)
    checkpoint.save(tmp_path, 1, eng.state())
    # fault-free reference: restore → apply u2
    eng.load_state(checkpoint.restore(tmp_path, 1, eng.state_template()))
    eng.update_ratings(*u2)
    ref_s, ref_i = _recs(eng)
    # faulted run: restore → fault mid-update → recover → re-apply
    eng.load_state(checkpoint.restore(tmp_path, 1, eng.state_template()))
    eng.fault_injector = FaultInjector(fail_at_steps=(eng._update_seq + 1,))
    with pytest.raises(InjectedFault):
        eng.update_ratings(*u2)
    eng.load_state(checkpoint.restore(tmp_path, 1, eng.state_template()))
    eng.update_ratings(*u2)        # injector is one-shot: this lands
    eng.fault_injector = None
    got_s, got_i = _recs(eng)
    np.testing.assert_array_equal(got_i, ref_i)
    np.testing.assert_array_equal(got_s, ref_s)


def test_fault_mid_refold_restores_consistent_index(rng, tmp_path):
    """A fault between the index ledger subtraction and re-add leaves the
    cluster sums genuinely torn; restore must hand back a consistent
    index (check_consistent) and bit-identical recommendations."""
    eng = _approx_engine(rng)
    u1, u2 = _updates(rng, 2)
    eng.update_ratings(*u1)
    checkpoint.save(tmp_path, 1, eng.state())
    eng.load_state(checkpoint.restore(tmp_path, 1, eng.state_template()))
    eng.update_ratings(*u2)
    ref_s, ref_i = _recs(eng)
    eng.load_state(checkpoint.restore(tmp_path, 1, eng.state_template()))
    eng.index.fault_injector = FaultInjector(
        fail_at_steps=(eng.index._refold_seq + 1,))
    with pytest.raises(InjectedFault):
        eng.update_ratings(*u2)
    eng.index.fault_injector = None
    eng.load_state(checkpoint.restore(tmp_path, 1, eng.state_template()))
    r, means = eng.ratings, eng.means
    assert eng.index.check_consistent(np.asarray(r), np.asarray(means))
    eng.update_ratings(*u2)
    got_s, got_i = _recs(eng)
    np.testing.assert_array_equal(got_i, ref_i)
    np.testing.assert_array_equal(got_s, ref_s)


def test_engine_update_failure_counter_increments(rng):
    from repro import obs
    eng = _engine(rng)
    eng.fault_injector = FaultInjector(fail_at_steps=(1,))
    before = int(obs.registry().snapshot()["counters"]
                 .get("engine.update.failures", 0))
    with pytest.raises(InjectedFault):
        eng.update_ratings([0], [0], [5.0])
    after = int(obs.registry().snapshot()["counters"]
                ["engine.update.failures"])
    assert after == before + 1
    eng.update_ratings([0], [0], [5.0])      # one-shot: retry succeeds


def test_per_call_quality_knobs(rng):
    eng = _approx_engine(rng)
    users = np.arange(8, dtype=np.int32)
    s_full, i_full = eng.recommend(users, n=5)
    s_cheap, i_cheap = eng.recommend(users, n=5, shortlist=8)
    assert np.asarray(i_cheap).shape == np.asarray(i_full).shape
    # exact mode can't honor candidate budgets — loud, not silent
    exact = _engine(rng)
    with pytest.raises(ValueError, match="approx"):
        exact.recommend(users, n=5, shortlist=8)


def test_query_mode_override_survives_updates(rng):
    eng = _approx_engine(rng)
    eng.index.query_mode_override = "staged"
    eng.update_ratings([1], [2], [4.0])
    assert eng.index.query_mode_override == "staged"
    assert eng.index._query_mode() == "staged"
    eng.index.query_mode_override = "bogus"
    with pytest.raises(ValueError, match="bogus"):
        eng.index._query_mode()
