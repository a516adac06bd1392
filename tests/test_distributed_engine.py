"""Multi-device behaviour via subprocesses (8 fake CPU devices).

conftest sets no XLA flags, so these tests spawn fresh interpreters with
``--xla_force_host_platform_device_count=8`` — the paper's multi-threaded
engine mapped onto an 8-way mesh, validated bit-exactly against sequential.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def run_with_devices(code: str, n: int = 8) -> str:
    env = {**os.environ,
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={n}",
           "PYTHONPATH": str(REPO / "src")}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-4000:]}"
    return r.stdout


def test_engines_bit_identical_across_devices():
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.data import load_ml1m_synthetic
        from repro.core.engine import (local_mesh, ring_sharded_predict,
                                       ring_sharded_topk, sharded_topk,
                                       sharded_predict)
        from repro.core.neighbors import topk_neighbors
        from repro.core.predict import predict_from_neighbors
        train, _, _ = load_ml1m_synthetic(n_users=256, n_items=200, seed=0)
        r = jnp.asarray(train)
        mesh = local_mesh(8)
        for meas in ("jaccard", "cosine", "pcc"):
            s0, i0 = topk_neighbors(r, 12, measure=meas, block_size=64)
            s1, i1 = sharded_topk(r, 12, mesh, measure=meas, block_size=64)
            s2, i2 = ring_sharded_topk(r, 12, mesh, measure=meas,
                                       block_size=64)
            assert (np.asarray(s0) == np.asarray(s1)).all(), meas
            assert (np.asarray(i0) == np.asarray(i1)).all(), meas
            assert (np.asarray(s0) == np.asarray(s2)).all(), meas
            assert (np.asarray(i0) == np.asarray(i2)).all(), meas
        p0 = predict_from_neighbors(r, s0, i0)
        p1 = sharded_predict(r, s0, i0, mesh)
        p2 = ring_sharded_predict(r, s0, i0, mesh)
        assert np.allclose(p0, p1, atol=1e-5)
        assert np.allclose(p0, p2, atol=1e-5)
        print("ENGINES_OK")
    """)
    assert "ENGINES_OK" in out


def test_sharded_embedding_and_grads():
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.models.embedding import (TableLayout, init_tables,
                                            sharded_lookup)
        from repro.compat import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        layout = TableLayout(field_sizes=(100000, 50, 20000, 3),
                             embed_dim=16, n_shards=8, bucket_slack=4.0)
        tables = init_tables(layout, jax.random.PRNGKey(0))
        ks = [jax.random.PRNGKey(i) for i in range(4)]
        idx = jnp.stack([jax.random.randint(ks[0], (64,), 0, 100000),
                         jax.random.randint(ks[1], (64,), 0, 50),
                         jax.random.randint(ks[2], (64,), 0, 20000),
                         jax.random.randint(ks[3], (64,), 0, 3)], axis=1)
        ref = sharded_lookup(layout, tables, idx, None)
        got = sharded_lookup(layout, tables, idx, mesh)
        assert np.allclose(ref, got), float(jnp.abs(ref - got).max())
        g1 = jax.grad(lambda t: jnp.sum(
            sharded_lookup(layout, t, idx, None) ** 2))(tables)
        g2 = jax.grad(lambda t: jnp.sum(
            sharded_lookup(layout, t, idx, mesh) ** 2))(tables)
        for k in g1:
            assert np.allclose(g1[k], g2[k], atol=1e-5), k
        print("EMBED_OK")
    """)
    assert "EMBED_OK" in out


def test_moe_sharded_matches_single_device():
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp, dataclasses as dc
        from repro.models import transformer as tx
        from repro.models.common import NO_SHARDING, ShardingCtx
        from repro.compat import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = tx.TransformerConfig(
            name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
            head_dim=8, d_ff=64, vocab=128, remat=False,
            moe=tx.MoEConfig(n_experts=8, top_k=2, d_ff=16,
                             capacity_factor=100.0),   # no drops → exact
            attn_chunk_q=16, attn_chunk_kv=16, xent_chunk=16,
            dtype=jnp.float32)
        params = tx.init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 128)
        batch = {"tokens": toks, "labels": toks}
        l0 = tx.loss_fn(cfg, params, batch)
        sc = ShardingCtx(batch=("pod", "data"), model="model", fsdp="data",
                         enabled=True, mesh=mesh)
        with mesh:
            l1 = jax.jit(lambda p, b: tx.loss_fn(cfg, p, b, sc))(params,
                                                                 batch)
        assert np.allclose(float(l0), float(l1), rtol=1e-4), (l0, l1)
        print("MOE_OK", float(l0), float(l1))
    """)
    assert "MOE_OK" in out


def test_dlrm_sharded_train_step_runs():
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs.registry import get_arch
        from repro.models import dlrm
        from repro.data import recsys_batch
        from repro.training.optimizer import get_optimizer
        from repro.compat import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_arch("dlrm_mlperf").smoke_config()
        params = dlrm.init_params(cfg, jax.random.PRNGKey(0))
        opt = get_optimizer("adagrad")
        state = opt.init(params)
        batch = {k: jnp.asarray(v) for k, v in
                 recsys_batch(64, cfg.field_sizes, n_dense=13).items()}
        def step(p, s, b):
            loss, g = jax.value_and_grad(
                lambda pp: dlrm.loss_fn(cfg, pp, b, mesh))(p)
            p, s = opt.update(p, g, s)
            return p, s, loss
        with mesh:
            p, s, loss = jax.jit(step)(params, state, batch)
        assert np.isfinite(float(loss))
        # parity vs unsharded loss
        l0 = dlrm.loss_fn(cfg, params, batch, None)
        l1 = dlrm.loss_fn(cfg, params, batch, mesh)
        assert np.allclose(float(l0), float(l1), rtol=1e-5)
        print("DLRM_OK")
    """)
    assert "DLRM_OK" in out


def test_shard_scaling_timing():
    """The paper's headline: more 'threads' (shards) → less wall time.

    On a single physical core the fake devices timeshare, so wall-clock
    speedup is not observable; instead verify the per-shard work shrinks
    (each device's query block is 1/8th) and the engine still matches.
    """
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.engine import local_mesh, sharded_topk
        from repro.data import load_ml1m_synthetic
        train, _, _ = load_ml1m_synthetic(n_users=512, n_items=256, seed=1)
        r = jnp.asarray(train)
        mesh = local_mesh(8)
        s, i = sharded_topk(r, 8, mesh, measure="cosine", block_size=64)
        # per-device shard of the output is 512/8 = 64 query users
        shards = s.addressable_shards
        assert len(shards) == 8
        assert shards[0].data.shape == (64, 8)
        print("SCALING_OK")
    """)
    assert "SCALING_OK" in out
