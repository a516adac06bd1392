"""Quickstart: fit the paper's CF model and get recommendations.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp

from repro.core import CFConfig, UserCF
from repro.data import load_ml1m_synthetic
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    # synthetic MovieLens-1M surrogate (offline container), 90/10 split
    train, test, spec = load_ml1m_synthetic(n_users=1024, n_items=768)
    tr, te = jnp.asarray(train), jnp.asarray(test)
    print(f"dataset: {spec.n_users} users × {spec.n_items} items, "
          f"{int((train > 0).sum())} train ratings")

    for measure in ("jaccard", "cosine", "pcc"):
        cf = UserCF(CFConfig(measure=measure, top_k=40, block_size=256))
        cf.fit(tr)
        ev = cf.evaluate(tr, te)
        print(f"{measure:8s} fit={cf.state.fit_seconds:5.2f}s "
              f"MAE={ev['mae']:.4f} P={ev['precision']:.3f} "
              f"R={ev['recall']:.3f} F1={ev['f1']:.3f}")

    # top-5 recommendations for the first few users (PCC model)
    cf = UserCF(CFConfig(measure="pcc", top_k=40, block_size=256))
    cf.fit(tr)
    scores, items = cf.recommend(tr, n=5)
    for u in range(3):
        pairs = ", ".join(f"item{int(i)}({float(s):.2f})"
                          for s, i in zip(scores[u], items[u]))
        print(f"user {u}: {pairs}")


if __name__ == "__main__":
    main()
