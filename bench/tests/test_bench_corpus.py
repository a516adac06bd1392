"""The benchmark's corpus generator: published marginals at a tiny
size, determinism from the seed."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import corpus
from bench.tests.conftest import TINY, tiny_cell

SEED = 2**31 + 11          # wider than a signed 32-bit integer
CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def tiny_config(name: str) -> dict:
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    cfg.update(TINY)
    cfg["assumed"]["max_user_ratings"] = 120
    return cfg


# every deployment's file, the ones no cell runs yet among them
@pytest.mark.parametrize("name", sorted(p.stem for p in CONFIGS.glob("*.json")))
def test_marginals(name):
    cfg = tiny_config(name)
    r = np.asarray(corpus.make_corpus(cfg, SEED))
    assert r.shape == (cfg["n_users"], cfg["n_items"])
    assert r.dtype == np.float32
    per_user = (r > 0).sum(1)
    assert int(per_user.sum()) == cfg["n_ratings"]
    assert per_user.min() >= cfg["min_user_ratings"]
    assert per_user.max() <= cfg["assumed"]["max_user_ratings"]
    step = cfg["value_step"]
    scale = np.arange(cfg["value_min"], cfg["value_max"] + step / 2, step)
    values = np.unique(r[r > 0])
    assert set(values.tolist()) <= set(scale.tolist())
    assert len(values) == len(scale)       # the whole scale is used


def test_seed_decides_the_corpus():
    cfg = tiny_cell("ml1m-steady")["config"]
    a = np.asarray(corpus.make_corpus(cfg, SEED))
    b = np.asarray(corpus.make_corpus(cfg, SEED))
    c = np.asarray(corpus.make_corpus(cfg, SEED + 1))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)


def test_refuses_counts_it_cannot_meet():
    cfg = tiny_cell("ml1m-steady")["config"]
    cfg["n_ratings"] = cfg["n_users"] * cfg["min_user_ratings"] - 1
    with pytest.raises(ValueError):
        corpus.corpus_shape(cfg)
