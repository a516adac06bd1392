"""CPU rehearsal of ``chip_smoke.py``: its phase functions at a tiny size,
and the script's refusal to run without a TPU or without the repo."""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.index import IndexConfig, ItemIndexConfig

ROOT = Path(__file__).resolve().parents[1]
K = 8


def _quiet(_msg):
    pass


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ratings(cs):
    return cs.surrogate_ratings(240, 160, seed=0)


# auto configs on a CPU host resolve to the staged host pipeline; the
# forced configs run the chip's device orchestration with every Pallas
# kernel in interpret mode and the same top_k selection.  The item
# shortlist is cut below the tiny catalog so the support scorer runs, as it
# does at ML-1M's 3952 items.
CASES = {
    "auto": (None, ItemIndexConfig(shortlist=64), {
        "query_mode": "staged", "scan_mode": "pool", "select_mode": "host",
        "rerank_mode": "grouped", "item_scorer": "ref",
        "index_interpret": False, "item_index_interpret": False}),
    "device_interpret": (
        IndexConfig(features="centered", use_kernel=True, interpret=True),
        ItemIndexConfig(use_kernel=True, interpret=True, shortlist=64), {
            "query_mode": "fused", "scan_mode": "kernel",
            "select_mode": "top_k", "rerank_mode": "fused",
            "item_scorer": "kernel", "index_interpret": True,
            "item_index_interpret": True}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_phases_rehearse_on_cpu(case, cs, ratings):
    index_cfg, item_cfg, modes = CASES[case]
    ex = cs.phase_exact(ratings, k=K, block_size=64, log=_quiet)
    assert ex["id_mismatches"] == 0 and ex["pallas_interpret"]
    ap = cs.phase_approx(ratings, k=K, expect_modes=modes,
                         neighbor_floor=0.5, recommend_floor=0.9,
                         index_cfg=index_cfg, item_index_cfg=item_cfg,
                         log=_quiet)
    sv = cs.phase_serve(ap["engine"], n_requests=12, burst=8, max_batch=4,
                        log=_quiet)
    assert len(sv["served"]) == len(sv["burst_s"]) + 1 == 3
    ref = cs.phase_reference(ex["engine"], ap["engine"], sv["served"],
                             sample=8, log=_quiet)
    assert ref["served_max_score_err"] <= cs.SCORE_ATOL


def test_reference_catches_a_wrong_score(cs, ratings):
    """The host reference is a real check: one corrupted cached score of
    a sampled user fails phase (d)."""
    ex = cs.phase_exact(ratings, k=K, block_size=64, log=_quiet)
    ap = cs.phase_approx(ratings, k=K, expect_modes=None, neighbor_floor=0.0,
                         recommend_floor=0.0, log=_quiet)
    sv = cs.phase_serve(ap["engine"], n_requests=4, burst=2, max_batch=4,
                        log=_quiet)
    eng = ex["engine"]
    eng.scores = eng.scores.at[:, 0].add(0.01)
    with pytest.raises(cs.SmokeFailure, match="exact engine"):
        cs.phase_reference(eng, ap["engine"], sv["served"], sample=8,
                           log=_quiet)


def _run(args, cwd, **env):
    full = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


def test_script_refuses_a_host_without_tpu():
    r = _run(["chip_smoke.py"], ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_script_refuses_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    r = _run(["chip_smoke.py"], tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_four_chip_phase_on_virtual_devices():
    """``--four-chips``' phase on four fake CPU devices: ring and sharded
    top-k bit-identical to sequential, results spread over all four."""
    code = ("import chip_smoke as cs\n"
            "r = cs.surrogate_ratings(240, 160, seed=0)\n"
            "out = cs.phase_four_chips(r, k=8, block_size=64)\n"
            "assert out['ring']['devices'] == out['sharded']['devices'] == 4\n"
            "print('FOUR_CHIPS_OK')\n")
    r = _run(["-c", code], ROOT, PYTHONPATH=str(ROOT / "src"),
             XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "FOUR_CHIPS_OK" in r.stdout
