"""jaxpr-level precision audit: seeded int8→fp32 widenings are traced
with provenance (through jit boundaries), clean twins stay quiet, and
the committed PRECISION_audit.json is exactly what a fresh trace of the
registered hot paths produces — ROADMAP item 1's measured starting line
cannot silently rot."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import jaxpr as J

REPO = Path(__file__).resolve().parent.parent
AUDIT = REPO / "PRECISION_audit.json"


def _i8(shape=(4, 3), seed=0):
    return jnp.asarray(np.random.default_rng(seed)
                       .integers(1, 6, shape).astype(np.int8))


# -- seeded widenings --------------------------------------------------------

def test_seeded_int8_upcast_fires_exactly_once():
    def f(x):
        return x.astype(jnp.float32).sum()

    ws = J.trace_widenings(f, [_i8()], ["ratings"],
                           hot_path="fixture.upcast", path="fixture.py")
    assert len(ws) == 1
    w = ws[0]
    assert w.origin == "ratings"
    assert w.from_dtype == "int8" and w.to_dtype == "float32"
    assert w.prim == "convert_element_type"
    assert w.symbol == ("fixture.upcast:ratings:"
                        "convert_element_type:int8->float32")


def test_widening_traced_through_jit_boundary():
    """The real hot paths widen inside nested jit calls; provenance must
    cross the sub-jaxpr boundary with the chain intact."""
    @jax.jit
    def inner(x):
        return x.astype(jnp.float32)

    def f(x):
        g = x[jnp.asarray([0, 1])]          # gather keeps it narrow
        return inner(g).sum()

    ws = J.trace_widenings(f, [_i8()], ["ratings"],
                           hot_path="fixture.nested", path="fixture.py")
    assert len(ws) == 1
    assert ws[0].origin == "ratings"
    assert "gather" in ws[0].provenance


def test_clean_twin_is_quiet():
    def f(x):
        return x * x                        # int8 arithmetic, no widening

    def g(x):
        return x.sum(dtype=jnp.int8)        # explicit dtype: no promotion

    for fn in (f, g):
        assert J.trace_widenings(fn, [_i8()], ["x"],
                                 hot_path="fixture.clean",
                                 path="fixture.py") == []


def test_float32_inputs_never_flag():
    def f(x):
        return x.astype(jnp.float64) if False else x.sum()

    x = jnp.ones((4, 3), jnp.float32)
    assert J.trace_widenings(f, [x], ["x"],
                             hot_path="fixture.f32", path="fixture.py") == []


def test_bool_comparisons_are_not_widenings():
    """int8 > 0 produces bool; bool is a mask, not a precision event."""
    def f(x):
        return (x > 0).sum()

    ws = J.trace_widenings(f, [_i8()], ["x"],
                           hot_path="fixture.mask", path="fixture.py")
    # the mask itself is fine; the sum of bools widens from bool which is
    # excluded too
    assert all(w.from_dtype != "bool" and w.to_dtype != "bool" for w in ws)
    assert ws == []


def test_narrowing_is_not_a_widening():
    def f(x):
        return x.astype(jnp.int8)

    x = jnp.ones((4,), jnp.float32)
    assert J.trace_widenings(f, [x], ["x"],
                             hot_path="fixture.narrow",
                             path="fixture.py") == []


# -- findings + audit file machinery -----------------------------------------

def test_widening_findings_carry_symbol_and_check():
    def f(x):
        return x.astype(jnp.float32)

    ws = J.trace_widenings(f, [_i8()], ["x"],
                           hot_path="fixture.f", path="fixture.py")
    fs = J.widening_findings(ws)
    assert len(fs) == 1
    assert fs[0].check == "precision-widening"
    assert fs[0].symbol == ws[0].symbol
    assert "PRECISION_audit.json" in fs[0].message


def test_load_audit_rejects_reasonless_entry(tmp_path):
    p = tmp_path / "audit.json"
    p.write_text(json.dumps({"schema": J.AUDIT_SCHEMA, "entries": [
        {"path": "x.py", "symbol": "s", "reason": "  "}]}))
    with pytest.raises(ValueError, match="reason"):
        J.load_audit(p)


def test_load_audit_rejects_wrong_schema(tmp_path):
    p = tmp_path / "audit.json"
    p.write_text(json.dumps({"schema": "nope/v0", "entries": []}))
    with pytest.raises(ValueError, match="schema"):
        J.load_audit(p)


def test_write_audit_preserves_reasons_and_stamps_todo(tmp_path):
    def f(x):
        return x.astype(jnp.float32)

    ws = J.trace_widenings(f, [_i8()], ["x"],
                           hot_path="fixture.f", path="fixture.py")
    p = tmp_path / "audit.json"
    n = J.write_audit(p, ws, reasons={ws[0].symbol: "known exact"})
    assert n == 1
    entries = json.loads(p.read_text())["entries"]
    assert entries[0]["reason"] == "known exact"
    n = J.write_audit(p, ws)                 # no reasons: TODO stamp
    assert json.loads(p.read_text())["entries"][0]["reason"].startswith(
        "TODO")


# -- the committed audit against a live trace --------------------------------

def test_committed_audit_matches_live_trace():
    """Every entry in PRECISION_audit.json fires in a fresh trace of the
    registered hot paths, and every live widening is in the audit — the
    file is the measured fp32-compute starting line, not a wish list."""
    live = {w.symbol for w in J.run_precision_audit()}
    audit = J.load_audit(AUDIT)              # raises on missing reasons
    committed = {sym for (_c, _p, sym) in audit}
    assert committed == live, (
        f"audit drift: only-committed={sorted(committed - live)} "
        f"only-live={sorted(live - committed)} — regenerate with "
        f"--write-precision-audit and justify or eliminate the delta")


def test_committed_audit_is_all_int8_gather_casts():
    """The current starting line: every accepted widening is the blessed
    gather-then-cast (int8 rows → f32 in-register before the Gram/score
    math).  A new kind of widening must not hide behind this test."""
    data = json.loads(AUDIT.read_text())
    assert data["schema"] == J.AUDIT_SCHEMA
    for e in data["entries"]:
        assert e["from_dtype"] == "int8" and e["to_dtype"] == "float32", e
        assert e["prim"] == "convert_element_type", e
