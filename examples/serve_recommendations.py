"""Batched recommendation serving: request queue → padded batch → predict.

A minimal but real serving tier over the unified CF engine facade: requests
arrive one by one, a batcher groups them up to ``--max-batch`` or
``--max-wait``, and the predictor scores each user's full item row before
top-n extraction — the pattern the recsys serve_p99 / serve_bulk shape cells
lower at production scale.

``--neighbor-mode approx`` fits the clustered candidate-generation index
(``repro.index``) instead of the exact all-pairs engines: sublinear
two-stage neighbor search with exact rerank, the configuration that keeps
fit/update cost sane past ~10⁴ users.  The recall diagnostic prints how
close the approx cache is to the exact engine.

Halfway through the request stream a batch of fresh ratings is absorbed
with ``CFEngine.update_ratings`` — the incremental path refreshes only the
affected neighbor rows (and, in approx mode, refolds the index's touched
centroids) and the very next batch serves from the updated cache.

    PYTHONPATH=src python examples/serve_recommendations.py
    PYTHONPATH=src python examples/serve_recommendations.py \
        --neighbor-mode approx --n-clusters 32 --n-probe 16
"""

import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro.core import CFEngine
from repro.data import load_ml1m_synthetic
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import BatchingServer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=20.0)
    ap.add_argument("--backend", default="sequential",
                    choices=("sequential", "sharded", "ring", "pallas"))
    ap.add_argument("--neighbor-mode", default="exact",
                    choices=("exact", "approx"))
    ap.add_argument("--measure", default="cosine",
                    choices=("jaccard", "cosine", "pcc"))
    ap.add_argument("--n-clusters", type=int, default=0,
                    help="approx mode: clusters (0 = auto ~sqrt(U))")
    ap.add_argument("--n-probe", type=int, default=0,
                    help="approx mode: probed clusters (0 = auto)")
    ap.add_argument("--query-mode", default="auto",
                    choices=("auto", "staged", "fused"),
                    help="approx mode: index query pipeline (auto picks "
                         "fused where the Pallas kernels run)")
    args = ap.parse_args()
    enable_compile_cache()

    train, _, _ = load_ml1m_synthetic(n_users=1024, n_items=512)
    index_cfg = None
    if args.neighbor_mode == "approx":
        from repro.index import IndexConfig
        index_cfg = IndexConfig(
            n_clusters=args.n_clusters, n_probe=args.n_probe,
            query_mode=args.query_mode,
            features="centered" if args.measure == "pcc" else "raw")
    engine = CFEngine(jnp.asarray(train), measure=args.measure, k=40,
                      backend=args.backend, block_size=256,
                      neighbor_mode=args.neighbor_mode,
                      index_cfg=index_cfg).fit()
    print(f"engine fitted ({args.backend}/{args.neighbor_mode}) "
          f"in {engine.fit_seconds:.2f}s")
    if args.neighbor_mode == "approx":
        qs = engine.index.last_query
        print(f"index: {engine.index.n_clusters} clusters, "
              f"probe {engine.index.n_probe}, "
              f"query={qs.query_mode or 'staged'}, "
              f"{qs.rerank_fraction:.1%} of rows exactly reranked, "
              f"recall@{engine.k} vs exact = "
              f"{engine.recall_vs_exact(sample=256):.3f}")

    server = BatchingServer(engine, max_batch=args.max_batch,
                            max_wait_ms=args.max_wait_ms, topn=5)
    server.start()
    rng = np.random.default_rng(0)
    users = rng.integers(0, engine.n_users, args.requests)

    t0 = time.perf_counter()
    futures = [server.submit(int(u)) for u in users[:args.requests // 2]]

    # live traffic: a burst of new ratings lands mid-stream
    n_delta = 32
    uids = rng.integers(0, engine.n_users, n_delta)
    iids = rng.integers(0, engine.n_items, n_delta)
    vals = rng.integers(1, 6, n_delta).astype(np.float32)
    st = engine.update_ratings(uids, iids, vals)
    print(f"absorbed {st.n_deltas} ratings in {st.seconds * 1e3:.0f}ms "
          f"({st.n_affected} rows recomputed, {st.n_merged} merged)")

    futures += [server.submit(int(u)) for u in users[args.requests // 2:]]
    results = [f.result(timeout=60) for f in futures]
    dt = time.perf_counter() - t0
    server.stop()

    s = server.stats()
    print(f"{s['n_requests']} requests in {dt:.2f}s "
          f"({s['n_requests'] / dt:.1f} req/s)")
    print(f"latency p50={s['latency_p50_ms']:.1f}ms "
          f"p99={s['latency_p99_ms']:.1f}ms "
          f"(queue {s['queue_wait_mean_ms']:.1f}ms, "
          f"compute {s['compute_mean_ms']:.1f}ms)")
    print(f"batches: {s['n_batches']} "
          f"(mean fill {s['mean_batch_fill']:.2f}, "
          f"mean queue depth {s['mean_queue_depth']:.1f})")
    r0 = results[0]
    print(f"sample: user {r0.user} → items {list(map(int, r0.items))}")


if __name__ == "__main__":
    main()
