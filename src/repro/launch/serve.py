"""Serving launcher: fit the CF model and serve batched recommendations.

    PYTHONPATH=src python -m repro.launch.serve --requests 128
    PYTHONPATH=src python -m repro.launch.serve --engine facade \\
        --recommend-mode approx          # two-stage item-index serving

Telemetry: the server publishes into the process-wide ``repro.obs``
registry here (so index/engine metrics and serving metrics land in one
dump); ``--stats-interval`` logs a periodic ``stats()`` line while the
run is in flight and ``--metrics-dump PATH`` writes the final registry
snapshot as the flat JSON metrics artifact.

Fault tolerance (README § Fault tolerance & graceful degradation):
``--deadline-ms`` / ``--max-queue`` exercise the request lifecycle,
``--ladder`` enables the degradation state machine, and
``--chaos-at-batch N`` injects a transient fault at batch N so the
supervised retry shows up in the stats line::

    PYTHONPATH=src python -m repro.launch.serve --engine facade \\
        --max-queue 64 --deadline-ms 200 --ladder --chaos-at-batch 2
"""

from __future__ import annotations

import argparse
import threading
import time

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import CFConfig, UserCF
from repro.data import load_ml1m_synthetic
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import BatchingServer


def _stats_line(server: BatchingServer) -> str:
    s = server.stats()
    line = (f"requests={s['n_requests']} batches={s['n_batches']} "
            f"p50={s['latency_p50_ms']:.1f}ms p99={s['latency_p99_ms']:.1f}ms "
            f"queue={s['queue_wait_mean_ms']:.1f}ms "
            f"compute={s['compute_mean_ms']:.1f}ms "
            f"fill={s['mean_batch_fill']:.2f} "
            f"depth={s['mean_queue_depth']:.1f} "
            f"health={s['health']}")
    if s["n_failures"] or s["n_shed"] or s["n_deadline_exceeded"]:
        line += (f" failures={s['n_failures']} retries={s['n_retries']} "
                 f"recoveries={s['n_recoveries']} shed={s['n_shed']} "
                 f"deadline={s['n_deadline_exceeded']}")
    return line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--users", type=int, default=1024)
    ap.add_argument("--items", type=int, default=512)
    ap.add_argument("--requests", type=int, default=128)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--topn", type=int, default=10)
    ap.add_argument("--engine", choices=("legacy", "facade"),
                    default="legacy")
    ap.add_argument("--measure", default="pcc",
                    choices=("jaccard", "cosine", "pcc", "pcc_sig"))
    ap.add_argument("--recommend-mode", choices=("exact", "approx"),
                    default="exact",
                    help="facade engine only: approx serves through the "
                         "two-stage item index")
    ap.add_argument("--stats-interval", type=float, default=0.0,
                    help="seconds between periodic stats() log lines "
                         "(0 disables)")
    ap.add_argument("--metrics-dump", default=None,
                    help="write the final metrics-registry snapshot "
                         "(fit + serving) to this JSON path")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline; expired-in-queue requests "
                         "resolve with DeadlineExceeded (0 disables)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="admission bound: submits past it shed with "
                         "Overloaded (0 = unbounded)")
    ap.add_argument("--ladder", action="store_true",
                    help="enable the HEALTHY/DEGRADED/SHEDDING "
                         "degradation ladder")
    ap.add_argument("--degrade-p99-ms", type=float, default=50.0)
    ap.add_argument("--shed-p99-ms", type=float, default=200.0)
    ap.add_argument("--chaos-at-batch", type=int, default=0,
                    help="inject a transient fault at this batch number "
                         "(0 disables) — exercises the supervised retry")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="retry budget per faulted batch")
    args = ap.parse_args()
    enable_compile_cache()

    from repro.distributed.fault_tolerance import (FaultInjector,
                                                   RecoveryPolicy)
    from repro.serving.engine import DegradationLadder
    ft_kw = dict(
        max_queue=args.max_queue,
        recovery=RecoveryPolicy(max_restarts=args.max_restarts),
        fault_injector=(FaultInjector(fail_at_steps=(args.chaos_at_batch,))
                        if args.chaos_at_batch > 0 else None),
        ladder=(DegradationLadder(degrade_p99_ms=args.degrade_p99_ms,
                                  shed_p99_ms=args.shed_p99_ms)
                if args.ladder else None))

    train, _, _ = load_ml1m_synthetic(n_users=args.users,
                                      n_items=args.items)
    tr = jnp.asarray(train)
    if args.engine == "facade":
        from repro.core import CFEngine
        engine = CFEngine(tr, measure=args.measure, k=40, block_size=256,
                          recommend_mode=args.recommend_mode).fit()
        server = BatchingServer(engine, max_batch=args.max_batch,
                                topn=args.topn, registry=obs.registry(),
                                **ft_kw)
    else:
        cf = UserCF(CFConfig(measure=args.measure, top_k=40,
                             block_size=256))
        cf.fit(tr)
        server = BatchingServer(cf, tr, max_batch=args.max_batch,
                                topn=args.topn, registry=obs.registry(),
                                **ft_kw)
    server.start()

    stop_log = threading.Event()
    if args.stats_interval > 0:
        def logger():
            while not stop_log.wait(args.stats_interval):
                print(f"[stats] {_stats_line(server)}", flush=True)
        threading.Thread(target=logger, daemon=True).start()

    from repro.serving.engine import DeadlineExceeded, Overloaded
    t0 = time.perf_counter()
    deadline = args.deadline_ms if args.deadline_ms > 0 else None
    futs, shed = [], 0
    for u in np.random.default_rng(0).integers(0, args.users,
                                               args.requests):
        try:
            futs.append(server.submit(int(u), deadline_ms=deadline))
        except Overloaded:
            shed += 1
    res, expired = [], 0
    for f in futs:
        try:
            res.append(f.result(timeout=120))
        except DeadlineExceeded:
            expired += 1
    dt = time.perf_counter() - t0
    stop_log.set()
    server.stop()
    extra = (f", {shed} shed, {expired} expired"
             if shed or expired else "")
    print(f"{len(res)} requests{extra}, {len(res) / dt:.0f} req/s, "
          f"{_stats_line(server)}")
    if args.metrics_dump:
        obs.export_metrics(args.metrics_dump)
        print(f"wrote {args.metrics_dump}")


if __name__ == "__main__":
    main()
