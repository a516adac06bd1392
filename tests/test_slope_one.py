"""Slope One baseline (the paper's ref [12] comparison family)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import slope_one as so


def brute_force_dev(r):
    u, i = r.shape
    dev = np.zeros((i, i))
    cnt = np.zeros((i, i))
    for a in range(i):
        for b in range(i):
            both = (r[:, a] > 0) & (r[:, b] > 0)
            c = both.sum()
            cnt[a, b] = c
            if c:
                dev[a, b] = np.mean(r[both, a] - r[both, b])
    return dev, cnt


def test_deviation_matches_brute_force(rng):
    r = (rng.integers(1, 6, (30, 12))
         * (rng.random((30, 12)) < 0.5)).astype(np.float32)
    dev, cnt = so.deviation_matrix(jnp.asarray(r))
    bd, bc = brute_force_dev(r)
    np.testing.assert_allclose(np.asarray(cnt), bc, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dev), bd, atol=1e-4)


def test_deviation_antisymmetric(rng):
    r = (rng.integers(1, 6, (40, 16))
         * (rng.random((40, 16)) < 0.4)).astype(np.float32)
    dev, cnt = so.deviation_matrix(jnp.asarray(r))
    np.testing.assert_allclose(np.asarray(dev), -np.asarray(dev).T,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(cnt), np.asarray(cnt).T)


def test_slope_one_end_to_end(ml_small):
    train, test, _ = ml_small
    tr, te = jnp.asarray(train), jnp.asarray(test)
    model = so.SlopeOne().fit(tr)
    ev = model.evaluate(tr, te)
    assert 0.5 < ev["mae"] < 1.2
    pred = model.predict(tr)
    assert np.all(np.isfinite(np.asarray(pred)))
    assert np.asarray(pred).min() >= 1.0 and np.asarray(pred).max() <= 5.0


def test_sharded_deviation_subprocess():
    """Item-sharded build == single device (paper ref [12]'s threads)."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path
    code = """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import slope_one as so
        from repro.core.engine import local_mesh
        rng = np.random.default_rng(0)
        r = (rng.integers(1, 6, (60, 32))
             * (rng.random((60, 32)) < 0.5)).astype(np.float32)
        d0, c0 = so.deviation_matrix(jnp.asarray(r))
        mesh = local_mesh(8)
        d1, c1 = so.sharded_deviation(jnp.asarray(r), mesh)
        assert np.allclose(d0, d1, atol=1e-5)
        assert np.allclose(c0, c1)
        print("SLOPE_OK")
    """
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": str(Path(__file__).resolve().parents[1] / "src")}
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "SLOPE_OK" in res.stdout
