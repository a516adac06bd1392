"""Chaos drills for the fault-tolerant serving tier, machine-checkable.

Four deterministic drills (``FaultInjector`` fires each configured step
exactly once, so recovery is reproducible, not probabilistic):

* **serving** — a live ``BatchingServer`` takes transient faults at
  configured batches; every request must resolve (``stranded_futures``
  counts result() timeouts — the hard invariant is 0), each fired fault
  must be recovered by the bounded-backoff retry, and the recovery
  latency is the extra wall the faulted waves paid over the clean waves.
* **admission** — a bounded queue takes a burst past its high-water
  mark: the overflow is shed with ``Overloaded`` *before* a future
  exists, the admitted remainder is served after a late start.
* **engine recovery** — faults injected inside ``update_ratings`` and
  mid-refold (the cluster ledger genuinely torn at the fault point);
  restoring the last committed checkpoint and re-applying the update
  must produce **bit-identical** recommendations to a fault-free run and
  a consistent index ledger.
* **degraded recall** — the DEGRADED rung of the ladder (staged query
  mode + a halved ``shortlist`` budget, exactly what
  ``DegradationLadder.budget`` hands the batcher) against the
  full-budget path at U=8192: recall@20 must hold the 0.90 floor.

Writes ``BENCH_chaos.json``; CI hard-asserts ``stranded_futures == 0``,
``recoveries >= injected_transient_faults``, both bit-parity flags, and
the recall floor.

    PYTHONPATH=src python benchmarks/bench_chaos.py            # full
    PYTHONPATH=src python benchmarks/bench_chaos.py --quick    # CI smoke
"""

from __future__ import annotations

import argparse
import json
import time

import jax.numpy as jnp
import numpy as np

from repro.core import CFEngine
from repro.data import load_ml1m_synthetic
from repro.distributed import checkpoint
from repro.distributed.fault_tolerance import (FaultInjector, InjectedFault,
                                               RecoveryPolicy)
from repro.index import IndexConfig
from repro.serving.engine import (DEGRADED, BatchingServer,
                                  DegradationLadder, Overloaded)

RECALL_USERS = 8192          # acceptance size for the DEGRADED recall floor


def _engine(u, d):
    from repro.index import ItemIndexConfig
    train, _, _ = load_ml1m_synthetic(n_users=u, n_items=d)
    return CFEngine(jnp.asarray(train), measure="cosine", k=40,
                    block_size=256, neighbor_mode="approx",
                    recommend_mode="approx",
                    index_cfg=IndexConfig(n_clusters=32, n_probe=8, seed=0,
                                          features="raw"),
                    item_index_cfg=ItemIndexConfig(shortlist=256)).fit()


def _drain(futures, timeout=60.0):
    """(results, stranded): a future that neither resolves nor errors
    within the timeout is stranded — the invariant the batcher must never
    violate."""
    out, stranded = [], 0
    for f in futures:
        try:
            out.append(f.result(timeout=timeout))
        except TimeoutError:
            out.append(None)
            stranded += 1
        except Exception as e:          # noqa: BLE001 - drill bookkeeping
            out.append(e)
    return out, stranded


def drill_serving(u, d, *, waves, fail_batches):
    """Transient faults at configured batches under live traffic."""
    eng = _engine(u, d)
    inj = FaultInjector(fail_at_steps=fail_batches)
    server = BatchingServer(
        eng, max_batch=8, max_wait_ms=5.0, topn=10,
        recovery=RecoveryPolicy(max_restarts=3, backoff_base_s=1e-3),
        fault_injector=inj)
    server.start()
    rng = np.random.default_rng(0)
    wave_walls = []
    stranded = 0
    for _ in range(waves):
        t0 = time.perf_counter()
        futs = [server.submit(int(x)) for x in rng.integers(0, u, 8)]
        res, s = _drain(futs)
        stranded += s
        wave_walls.append((time.perf_counter() - t0) * 1e3)
        stranded += sum(1 for r in res if isinstance(r, Exception))
    # steady-state retrace assertion: after the fault waves the batcher is
    # fully warm, so one more same-shape wave must compile nothing — a
    # retrace here means a serving-path cache key varies per request
    from repro.analysis.retrace import RetraceSentinel
    with RetraceSentinel("bench_chaos.serving_steady_state") as sentinel:
        futs = [server.submit(int(x)) for x in rng.integers(0, u, 8)]
        res, s_extra = _drain(futs)
    stranded += s_extra + sum(1 for r in res if isinstance(r, Exception))
    assert sentinel.count == 0, (
        f"{sentinel.count} jit compile(s) during a warm same-shape serving "
        f"wave — steady-state retrace regression (per_site="
        f"{sentinel.per_site})")
    server.stop()
    s = server.stats()
    n_faults = len(inj.fired)
    # recovery cost: the extra wall the faulted waves paid; waves map 1:1
    # to batches here (each wave is one full batch, drained before the
    # next), so the first len(fail_batches) waves with faults are known
    faulted = [wave_walls[b - 1] for b in fail_batches
               if b - 1 < len(wave_walls)]
    clean = [w for i, w in enumerate(wave_walls)
             if (i + 1) not in fail_batches]
    rec_ms = (float(np.mean(faulted) - np.mean(clean))
              if faulted and clean else 0.0)
    return {
        "requests": s["n_requests"],
        "injected_transient_faults": n_faults,
        "failures": s["n_failures"],
        "retries": s["n_retries"],
        "recoveries": s["n_recoveries"],
        "stranded_futures": stranded,
        "recovery_latency_ms": round(max(rec_ms, 0.0), 3),
        "p99_ms": round(s["latency_p99_ms"], 3),
        "retrace_steady_state": int(sentinel.count),
    }


def drill_admission(u, d, *, max_queue, burst):
    """Burst past the high-water mark before the batcher starts: the
    overflow sheds deterministically, the admitted remainder serves."""
    eng = _engine(u, d)
    server = BatchingServer(eng, max_batch=8, max_wait_ms=5.0, topn=10,
                            max_queue=max_queue)
    rng = np.random.default_rng(1)
    futs, shed = [], 0
    for x in rng.integers(0, u, burst):
        try:
            futs.append(server.submit(int(x)))
        except Overloaded:
            shed += 1
    server.start()
    res, stranded = _drain(futs)
    server.stop()
    stranded += sum(1 for r in res if isinstance(r, Exception))
    return {
        "burst": burst,
        "admitted": len(futs),
        "shed": shed,
        "shed_fraction": round(shed / burst, 4),
        "stranded_futures": stranded,
    }


def drill_engine_recovery(u, d, tmp):
    """Faults inside update_ratings and mid-refold; checkpoint restore
    must yield bit-identical results to the fault-free run."""
    rng = np.random.default_rng(2)
    users = np.arange(0, min(u, 64), dtype=np.int32)

    def updates(n):
        return [([int(rng.integers(0, u))], [int(rng.integers(0, d))],
                 [float(rng.integers(1, 6))]) for _ in range(n)]

    out = {}
    for name, hook in (("update", "engine"), ("refold", "index")):
        eng = _engine(u, d)
        u2 = updates(1)[0]
        # checkpoint the fitted state: cold-consistent by construction,
        # so the post-restore ledger check is exact (an *incremental*
        # update's patched proxies can differ from a cold recompute by a
        # reduction-order ulp at scale — that is cache drift, not tearing)
        checkpoint.save(tmp, 1, eng.state())
        tpl = eng.state_template()
        # fault-free reference through the same restore path
        eng.load_state(checkpoint.restore(tmp, 1, tpl))
        eng.update_ratings(*u2)
        ref_s, ref_i = map(np.asarray, eng.recommend(users, n=10))
        # faulted run: restore → fault → restore → re-apply
        eng.load_state(checkpoint.restore(tmp, 1, tpl))
        target = eng if hook == "engine" else eng.index
        seq = eng._update_seq if hook == "engine" else eng.index._refold_seq
        target.fault_injector = FaultInjector(fail_at_steps=(seq + 1,))
        t0 = time.perf_counter()
        try:
            eng.update_ratings(*u2)
            raise AssertionError("injected fault did not fire")
        except InjectedFault:
            pass
        target.fault_injector = None
        eng.load_state(checkpoint.restore(tmp, 1, tpl))
        if hook == "index":
            # the fault left the cluster ledger torn; the restored index
            # must equal a cold reassignment before the update re-applies
            out["index_consistent_after_recovery"] = bool(
                eng.index.check_consistent(np.asarray(eng.ratings),
                                           np.asarray(eng.means)))
        eng.update_ratings(*u2)
        rec_ms = (time.perf_counter() - t0) * 1e3
        got_s, got_i = map(np.asarray, eng.recommend(users, n=10))
        out[f"bit_parity_{name}"] = bool(
            np.array_equal(got_i, ref_i) and np.array_equal(got_s, ref_s))
        out[f"recovery_latency_{name}_ms"] = round(rec_ms, 3)
    return out


def drill_degraded_recall(u, d, *, topn=20):
    """Recall@n of the DEGRADED rung (staged mode + the exact budgets the
    ladder hands the batcher) against the full-budget path."""
    eng = _engine(u, d)
    users = np.arange(u, dtype=np.int32)
    _, ref_i = map(np.asarray, eng.recommend(users, n=topn))
    lad = DegradationLadder()
    budget = lad.budget(DEGRADED, eng.item_index.cfg.shortlist, topn)
    eng.index.query_mode_override = "staged"
    _, got_i = map(np.asarray, eng.recommend(users, n=topn, **budget))
    eng.index.query_mode_override = None
    hits = total = 0
    for row in range(ref_i.shape[0]):
        ref = set(int(j) for j in ref_i[row] if j >= 0)
        hits += len(ref & set(int(j) for j in got_i[row]))
        total += len(ref)
    return {
        "users": u,
        "budget": budget,
        "recall_at20": round(hits / max(total, 1), 4),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: small serving drills; the recall "
                         "drill keeps the acceptance size")
    ap.add_argument("--out", default="BENCH_chaos.json")
    ap.add_argument("--ckpt-dir", default="/tmp/bench_chaos_ckpt")
    args = ap.parse_args()

    u, d = (512, 256) if args.quick else (2048, 512)
    waves = 8 if args.quick else 24
    doc = {"schema": "repro.bench.chaos/v1", "quick": args.quick}

    t0 = time.perf_counter()
    doc["serving"] = drill_serving(u, d, waves=waves,
                                   fail_batches=(2, 4, 6))
    print(f"serving drill: {doc['serving']}", flush=True)
    doc["admission"] = drill_admission(u, d, max_queue=16, burst=48)
    print(f"admission drill: {doc['admission']}", flush=True)
    doc["engine"] = drill_engine_recovery(u, d, args.ckpt_dir)
    print(f"engine drill: {doc['engine']}", flush=True)
    doc["degraded"] = drill_degraded_recall(RECALL_USERS,
                                            512 if args.quick else 1024)
    print(f"degraded-recall drill: {doc['degraded']}", flush=True)

    # roll-up: the fields CI hard-asserts on
    doc["injected_transient_faults"] = \
        doc["serving"]["injected_transient_faults"]
    doc["recoveries"] = doc["serving"]["recoveries"]
    doc["stranded_futures"] = (doc["serving"]["stranded_futures"]
                               + doc["admission"]["stranded_futures"])
    doc["shed_fraction"] = doc["admission"]["shed_fraction"]
    doc["recovery_latency_ms"] = doc["serving"]["recovery_latency_ms"]
    doc["bit_parity"] = (doc["engine"]["bit_parity_update"]
                         and doc["engine"]["bit_parity_refold"])
    doc["retrace_steady_state"] = doc["serving"]["retrace_steady_state"]
    doc["degraded_recall_at20"] = doc["degraded"]["recall_at20"]
    doc["wall_s"] = round(time.perf_counter() - t0, 2)

    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    print(f"wrote {args.out} (wall {doc['wall_s']}s)")


if __name__ == "__main__":
    main()
