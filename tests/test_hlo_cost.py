"""The loop-aware HLO cost parser that backs the roofline analysis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import HloCostModel, analyze


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_matmul_flops_exact():
    co = _compile(lambda a, b: a @ b,
                  jax.ShapeDtypeStruct((512, 1024), jnp.float32),
                  jax.ShapeDtypeStruct((1024, 256), jnp.float32))
    r = analyze(co.as_text())
    assert r["flops"] == pytest.approx(2 * 512 * 1024 * 256, rel=0.01)


def test_scan_multiplies_trip_count():
    def g(w, x):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=16)
        return h.sum()
    co = _compile(g, jax.ShapeDtypeStruct((256, 256), jnp.float32),
                  jax.ShapeDtypeStruct((64, 256), jnp.float32))
    r = analyze(co.as_text())
    want = 16 * 2 * 64 * 256 * 256
    assert r["flops"] == pytest.approx(want, rel=0.05)
    assert r["unknown_trip_count_loops"] == 0


def test_nested_scan():
    def g(w, x):
        def outer(h, _):
            def inner(hh, _):
                return hh @ w, None
            h2, _ = jax.lax.scan(inner, h, None, length=4)
            return h2, None
        h, _ = jax.lax.scan(outer, x, None, length=8)
        return h.sum()
    co = _compile(g, jax.ShapeDtypeStruct((64, 64), jnp.float32),
                  jax.ShapeDtypeStruct((16, 64), jnp.float32))
    r = analyze(co.as_text())
    want = 8 * 4 * 2 * 16 * 64 * 64
    assert r["flops"] == pytest.approx(want, rel=0.1)


def test_bytes_scale_with_tensor_size():
    co1 = _compile(lambda a: a * 2.0,
                   jax.ShapeDtypeStruct((1024, 1024), jnp.float32))
    co2 = _compile(lambda a: a * 2.0,
                   jax.ShapeDtypeStruct((2048, 1024), jnp.float32))
    r1, r2 = analyze(co1.as_text()), analyze(co2.as_text())
    assert r2["bytes"] == pytest.approx(2 * r1["bytes"], rel=0.05)


def test_collectives_counted_inside_loops():
    """A psum inside a scan must count trip_count times."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path
    code = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch.hlo_cost import analyze
        from repro.compat import make_mesh
        mesh = make_mesh((8,), ("d",))
        def f(x):
            def body(h, _):
                h = jax.lax.psum(h, "d")
                return h * 0.125, None
            h, _ = jax.lax.scan(body, x, None, length=10)
            return h
        g = jax.shard_map(f, mesh=mesh, in_specs=P(None, None),
                          out_specs=P(None, None), check_vma=False)
        co = jax.jit(g).lower(
            jax.ShapeDtypeStruct((32, 64), jnp.float32)).compile()
        r = analyze(co.as_text())
        per = 32 * 64 * 4
        assert r["collective_bytes"].get("all-reduce", 0) >= 10 * per, r
        print("COLL_OK", r["collective_bytes"])
    """
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": str(Path(__file__).resolve().parents[1] / "src")}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "COLL_OK" in r.stdout
