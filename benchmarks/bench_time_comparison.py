"""Paper Figs. 1–2: sequential vs multi-threaded similarity wall time.

The paper sweeps OS threads on one box; the analogue here sweeps mesh
shards.  On this single-core container extra fake devices timeshare one
CPU, so wall-clock *speedup* cannot manifest locally; what the sweep
demonstrates is (a) per-shard work shrinking 1/P (the quantity that turns
into speedup on real parallel hardware) and (b) zero accuracy change —
the paper's central claims.  Each shard count runs in a fresh subprocess
with that many host devices.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

_CODE = """
    import time, numpy as np, jax, jax.numpy as jnp
    from repro.core.engine import local_mesh, sharded_topk
    from repro.core.neighbors import topk_neighbors
    from repro.data import load_ml1m_synthetic
    n = {n_shards}
    train, _, _ = load_ml1m_synthetic(n_users=1024, n_items=512, seed=3)
    r = jnp.asarray(train)
    if n == 1:
        fit = lambda: topk_neighbors(r, 20, measure="pcc", block_size=256)
    else:
        mesh = local_mesh(n)
        fit = lambda: sharded_topk(r, 20, mesh, measure="pcc",
                                   block_size=256)
    s, i = fit()                                   # compile + warm
    jax.block_until_ready(s)
    t0 = time.perf_counter()
    for _ in range(3):
        s, i = fit()
        jax.block_until_ready(s)
    dt = (time.perf_counter() - t0) / 3
    # checksum on HOST in f64 so the reduction order is shard-independent
    sh = np.asarray(s, dtype=np.float64)
    csum = float(np.where(np.isfinite(sh), sh, 0.0).sum())
    print(f"RESULT,{{n}},{{dt:.4f}},{{csum:.6f}}".format(
        n=n, dt=dt, csum=csum))
"""


def run_shard(n_shards: int) -> tuple:
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "XLA_FLAGS":
           f"--xla_force_host_platform_device_count={n_shards}"}
    r = subprocess.run(
        [sys.executable, "-c",
         textwrap.dedent(_CODE.format(n_shards=n_shards))],
        capture_output=True, text=True, env=env, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT")][0]
    _, n, dt, csum = line.split(",")
    return int(n), float(dt), float(csum)


def main():
    print("n_shards,seconds,per_shard_users,checksum")
    checks = set()
    for n in (1, 2, 4, 8):
        n, dt, csum = run_shard(n)
        checks.add(round(csum, 3))
        print(f"{n},{dt:.4f},{1024 // n},{csum:.3f}")
    assert len(checks) == 1, f"accuracy changed across shard counts: {checks}"
    print("# checksum identical across shard counts — exactness holds")


if __name__ == "__main__":
    main()
