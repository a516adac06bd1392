"""Two-stage recommend path vs dense blocked prediction, at scale.

For each user count the benchmark fits one engine (approx neighbor cache +
item stage on the same synthetic ML-1M surrogate, full item axis), then
produces top-n recommendations for *every* user twice:

* **dense** — the exact path: blocked neighbor-weighted prediction over
  all I items per user (item-tiled), canonical top-n;
* **approx** — the item stage: support scores over every item → device
  top-``shortlist`` → exact rerank of the shortlist.

Reported: end-to-end recommend throughput for both paths, their ratio
(``recommend_speedup``), and recommendation recall@n of approx against
dense.  All timings are single-shot from a cold process (compile time
included on both sides).

Writes ``BENCH_recommend.json`` so the perf trajectory is
machine-readable across PRs.

    PYTHONPATH=src python benchmarks/bench_recommend.py            # sweep
    PYTHONPATH=src python benchmarks/bench_recommend.py --quick    # CI smoke
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_SIZES = (2048, 8192, 32768)

# per-size user-index overrides: the item catalog stays ML-1M-sized, so one
# shortlist budget works across user counts; the neighbor-side knobs follow
# bench_index's tuning (thinner rerank, wider proxies past 10⁴ users)
NEIGHBOR_RERANK = {32768: 0.03}
NEIGHBOR_PROJECT = {32768: 384}


def write_json(path: str, rows: list) -> None:
    with open(path, "w") as f:
        json.dump(rows, f, indent=2, sort_keys=True)
    print(f"wrote {path} ({len(rows)} rows)")


def _recall(ref_i: np.ndarray, got_i: np.ndarray) -> float:
    hits = total = 0
    for row in range(ref_i.shape[0]):
        ref = set(int(j) for j in ref_i[row] if j >= 0)
        if ref:
            hits += len(ref & set(int(j) for j in got_i[row]))
            total += len(ref)
    return hits / max(total, 1)


def run(sizes=DEFAULT_SIZES, n: int = 10, k: int = 40,
        measure: str = "cosine", n_items=None, seed: int = 0,
        shortlist: int = 64) -> list:
    from repro import obs
    from repro.core import CFEngine
    from repro.data import load_ml1m_synthetic
    from repro.index import IndexConfig, ItemIndexConfig

    rows = []
    for n_users in sizes:
        obs.reset_metrics()
        train, _, _ = load_ml1m_synthetic(n_users=n_users, n_items=n_items,
                                          seed=seed)
        ratings = jnp.asarray(train)

        engine = CFEngine(
            ratings, measure=measure, k=k,
            neighbor_mode="approx",
            index_cfg=IndexConfig(
                seed=seed,
                features="centered" if measure.startswith("pcc") else "raw",
                rerank_frac=NEIGHBOR_RERANK.get(n_users, 0.15),
                project_dim=NEIGHBOR_PROJECT.get(n_users, 256)),
            recommend_mode="approx",
            item_index_cfg=ItemIndexConfig(shortlist=shortlist))

        t0 = time.perf_counter()
        engine.fit()
        fit_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        _, dense_i = engine.recommend(n=n, mode="exact")
        dense_i = np.asarray(jax.block_until_ready(dense_i))
        dense_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        _, approx_i = engine.recommend(n=n, mode="approx")
        approx_i = np.asarray(jax.block_until_ready(approx_i))
        approx_s = time.perf_counter() - t0

        recall = _recall(dense_i, approx_i)
        frac = engine.item_index.last_recommend.rerank_fraction
        speedup = dense_s / approx_s
        rows.append({
            "name": f"recommend_{measure}_U{n_users}",
            "us_per_call": approx_s / n_users * 1e6,  # per-user approx cost
            "n_users": n_users,
            "n_items": int(ratings.shape[1]),
            "k": k,
            "topn": n,
            "shortlist": shortlist,
            "fit_s": round(fit_s, 3),
            "dense_recommend_s": round(dense_s, 3),
            "approx_recommend_s": round(approx_s, 3),
            "dense_users_per_s": round(n_users / dense_s, 1),
            "approx_users_per_s": round(n_users / approx_s, 1),
            "recommend_speedup": round(speedup, 3),
            "recall_at_n": round(recall, 4),
            "rerank_fraction": round(frac, 4),
            # registry-derived recommend wall (histogram bucket upper
            # bound of the span duration — within 10^0.1 of approx_s)
            "recommend_p50_s": round(obs.registry().histogram(
                "item_index.recommend.seconds").quantile(0.5), 3),
        })
        print(f"U={n_users}: dense={dense_s:.1f}s approx={approx_s:.1f}s "
              f"speedup={speedup:.2f}x recall@{n}={recall:.4f} "
              f"rerank={frac:.3f}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default=None,
                    help="comma-separated user counts")
    ap.add_argument("--n", type=int, default=10)
    ap.add_argument("--k", type=int, default=40)
    ap.add_argument("--measure", default="cosine",
                    choices=("jaccard", "cosine", "pcc", "pcc_sig"))
    ap.add_argument("--shortlist", type=int, default=64)
    ap.add_argument("--quick", action="store_true",
                    help="toy size for CI smoke (seconds, not minutes)")
    ap.add_argument("--json-path", default="BENCH_recommend.json")
    args = ap.parse_args()

    if args.quick:
        rows = run(sizes=(256,), n=min(args.n, 10), k=min(args.k, 10),
                   measure=args.measure, n_items=128, shortlist=48)
    else:
        sizes = (tuple(int(s) for s in args.sizes.split(","))
                 if args.sizes else DEFAULT_SIZES)
        rows = run(sizes=sizes, n=args.n, k=args.k, measure=args.measure,
                   shortlist=args.shortlist)

    print("name,us_per_call,derived")
    for r in rows:
        derived = (f"speedup={r['recommend_speedup']} "
                   f"recall={r['recall_at_n']} "
                   f"rerank={r['rerank_fraction']}")
        print(f"{r['name']},{r['us_per_call']:.0f},{derived}")
    write_json(args.json_path, rows)


if __name__ == "__main__":
    main()
