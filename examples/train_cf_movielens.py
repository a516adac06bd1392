"""End-to-end driver: the paper's full experiment with the sharded engine.

Reproduces §VI of the paper: fit user-based CF under all three similarity
measures on (synthetic) MovieLens-1M, sweep top-N, report MAE / Precision /
Recall / F-Score, and compare sequential vs sharded engines.  Run with
fake devices to exercise the multi-threaded path:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python examples/train_cf_movielens.py --engine ring
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.core import CFConfig, UserCF
from repro.core.engine import local_mesh
from repro.data import load_ml1m_synthetic
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", default="sequential",
                    choices=["sequential", "sharded", "ring"])
    ap.add_argument("--users", type=int, default=2048)
    ap.add_argument("--items", type=int, default=1024)
    ap.add_argument("--topn", type=int, nargs="+", default=[10, 20, 40])
    args = ap.parse_args()
    enable_compile_cache()

    n_dev = len(jax.devices())
    mesh = local_mesh(n_dev) if args.engine != "sequential" else None
    print(f"devices={n_dev} engine={args.engine}")

    train, test, _ = load_ml1m_synthetic(n_users=args.users,
                                         n_items=args.items)
    tr, te = jnp.asarray(train), jnp.asarray(test)

    print("measure,top_n,fit_s,mae,precision,recall,f1")
    for measure in ("jaccard", "cosine", "pcc"):
        for k in args.topn:
            cf = UserCF(CFConfig(measure=measure, top_k=k,
                                 engine=args.engine, block_size=256),
                        mesh=mesh)
            cf.fit(tr)
            ev = cf.evaluate(tr, te)
            print(f"{measure},{k},{cf.state.fit_seconds:.2f},"
                  f"{ev['mae']:.4f},{ev['precision']:.4f},"
                  f"{ev['recall']:.4f},{ev['f1']:.4f}")


if __name__ == "__main__":
    main()
