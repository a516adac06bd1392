"""Kernel microbenchmarks: fused kernels vs their jnp / XLA references.

On CPU these numbers are indicative only (no MXU); the structural claim —
each fused kernel performs its Gram products for ~1 pass of operand reads
— is checked via the arithmetic-intensity ratio, and wall time is
reported for the XLA paths (the Pallas kernels run interpret-mode on CPU
and are timed at reduced shapes).

The rerank-kernel smoke additionally *verifies* the kernels: the fused
co-rated Gram rerank (``kernels/rerank.py``) and its OpenBLAS host twin
are scored against the jnp oracle on an integer rating block, and the
resulting top-k neighbor sets must match the oracle's exactly — a recall
floor of 1.0, pinned so CI fails loudly on any regression.  Results are
written as a JSON artifact (``--json-path``) alongside the other
``BENCH_*`` files.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.rerank import fused_rerank_scores, rerank_scores_host
from repro.kernels.similarity import fused_similarity

# the smoke's pinned floor: kernel/host top-k sets vs the jnp oracle
RERANK_RECALL_FLOOR = 1.0


def _time(f, *args, reps=5, name=None):
    """Mean wall µs over ``reps`` fenced calls; per-rep walls also land in
    the obs registry (histogram ``kernels.<name>.seconds``) when named."""
    from repro import obs
    f(*args)  # compile
    hist = obs.histogram(f"kernels.{name}.seconds") if name else None
    t0 = time.perf_counter()
    for _ in range(reps):
        t1 = time.perf_counter()
        jax.block_until_ready(f(*args))
        if hist is not None:
            hist.observe(time.perf_counter() - t1)
    return (time.perf_counter() - t0) / reps * 1e6    # µs


def _topk_sets(scores: np.ndarray, k: int) -> list:
    # reprolint: disable=canonical-selection -- stable argsort of negated scores IS the canonical (-score, id) order; set-recall comparison is tie-insensitive anyway
    return [set(np.argsort(-row, kind="stable")[:k].tolist())
            for row in scores]


def _rerank_recall(got: np.ndarray, want: np.ndarray, k: int) -> float:
    hits = total = 0
    for g, w in zip(_topk_sets(got, k), _topk_sets(want, k)):
        hits += len(g & w)
        total += len(w)
    return hits / max(total, 1)


def run():
    rng = np.random.default_rng(0)
    rows = []
    for m, d in ((512, 1024), (1024, 2048)):
        ra = jnp.asarray((rng.integers(1, 6, (m, d))
                          * (rng.random((m, d)) < 0.1)).astype(np.float32))
        xla_all = jax.jit(lambda a, b: ref.similarity_ref(a, b, "all"))
        us_ref = _time(xla_all, ra, ra, name=f"xla_all3_{m}x{d}")
        rows.append({"name": f"xla_unfused_all3_{m}x{d}",
                     "us_per_call": us_ref,
                     "derived": f"flops={12 * m * m * d:.0f}"})
    # pallas interpret at reduced shape (python-loop execution)
    ra = jnp.asarray((rng.integers(1, 6, (128, 256))
                      * (rng.random((128, 256)) < 0.2)).astype(np.float32))
    us_pal = _time(lambda a: fused_similarity(
        a, a, measure="all", bm=64, bn=64, bk=128, interpret=True), ra,
        reps=2)
    rows.append({"name": "pallas_interpret_all3_128x256",
                 "us_per_call": us_pal,
                 "derived": "correctness-mode timing (no Mosaic on CPU)"})
    rows += run_rerank_smoke(rng)
    rows += run_select_smoke(rng)
    rows += run_compiled(rng)
    return rows


def run_compiled(rng, q_n: int = 512, n: int = 8192, p: int = 256,
                 m: int = 256, g: int = 256, kc: int = 1024, j: int = 512):
    """Time the *compiled* fused query-pipeline stages at realistic shapes.

    On a TPU backend the rerank kernel lowers through Mosaic and is
    timed as such (``path: mosaic``); elsewhere the timed program is the
    jitted XLA twin that the fused pipeline actually dispatches off-TPU
    (``path: xla``).  The scan is the XLA twin on every backend — Mosaic
    cannot lower the select kernel's in-kernel sort.  Either way the rows
    record what ``query_mode="fused"`` runs on this host, not an
    interpret-mode proxy.
    """
    from repro.kernels.rerank import rerank_scores_xla
    from repro.kernels.select import scan_topm_xla

    on_tpu = jax.default_backend() == "tpu"
    path = "mosaic" if on_tpu else "xla"
    rows = []

    q = jnp.asarray(rng.normal(size=(q_n, p)).astype(np.float32))
    prox = jnp.asarray(rng.normal(size=(n, p)).astype(np.float32))
    q_ids = jnp.asarray(np.arange(q_n, dtype=np.int32))
    rows.append({"name": f"compiled_scan_{q_n}x{n}_m{m}",
                 "us_per_call": _time(
                     lambda: scan_topm_xla(q, prox, q_ids, m=m)),
                 "path": "xla",
                 "derived": f"flops={2 * q_n * n * p:.0f}"})

    vq = (rng.integers(1, 6, (g, j))
          * (rng.random((g, j)) < 0.3)).astype(np.float32)
    rc = (rng.integers(1, 6, (kc, j))
          * (rng.random((kc, j)) < 0.3)).astype(np.float32)
    norms = jnp.asarray(np.sqrt((rc * rc).sum(1)).astype(np.float32))
    counts = jnp.asarray((rc > 0).sum(1).astype(np.float32))
    vq_j = jnp.asarray(vq)
    rc_j = jnp.asarray(rc.astype(np.int8) if on_tpu else rc)
    for measure in ("cosine", "pcc_sig"):
        fn = ((lambda: fused_rerank_scores(vq_j, rc_j, norms, counts,
                                           measure=measure,
                                           interpret=False))
              if on_tpu else
              (lambda: rerank_scores_xla(vq_j, rc_j, norms, counts,
                                         measure=measure)))
        rows.append({"name": f"compiled_rerank_{measure}_{g}x{kc}x{j}",
                     "us_per_call": _time(fn),
                     "path": path,
                     "derived": f"flops={6 * g * kc * j:.0f}"})
    return rows


def run_select_smoke(rng, q_n: int = 48, n: int = 768, p: int = 32,
                     m: int = 40):
    """Verify + time the blockwise-select kernel and its XLA twin.

    The kernel and the exact ``lax.top_k`` twin implement the canonical
    ``(-score, id)`` selection, so their top-M id sets must equal the
    jnp oracle's exactly — recall 1.0, pinned (CI fails loudly on any
    regression).  The ``approx_max_k`` twin is reported for reference
    under a separate key (it trades recall for the O(N) partial reduce
    and is never used where the bit-parity contract applies).
    """
    from repro.kernels.ref import scan_topm_ref
    from repro.kernels.select import fused_scan_topm, scan_topm_xla
    q = jnp.asarray(rng.normal(size=(q_n, p)).astype(np.float32))
    prox = jnp.asarray(rng.normal(size=(n, p)).astype(np.float32))
    q_ids = jnp.asarray(np.arange(q_n, dtype=np.int32))
    want = np.asarray(scan_topm_ref(q, prox, q_ids, m)[1])

    def recall(got):
        return float(np.mean([len(set(got[r]) & set(want[r])) / m
                              for r in range(q_n)]))

    rows = []
    us_k = _time(lambda: fused_scan_topm(q, prox, q_ids, m=m, bq=16,
                                         bn=128, interpret=True), reps=2)
    got_k = np.asarray(fused_scan_topm(q, prox, q_ids, m=m, bq=16,
                                       bn=128, interpret=True)[1])
    rows.append({"name": f"select_kernel_{q_n}x{n}_m{m}",
                 "us_per_call": us_k,
                 "recall_vs_oracle": recall(got_k),
                 "derived": "interpret-mode (no Mosaic on CPU)"})
    us_x = _time(lambda: scan_topm_xla(q, prox, q_ids, m=m), reps=5)
    got_x = np.asarray(scan_topm_xla(q, prox, q_ids, m=m)[1])
    rows.append({"name": f"select_xla_twin_{q_n}x{n}_m{m}",
                 "us_per_call": us_x,
                 "recall_vs_oracle": recall(got_x),
                 "derived": "lax.top_k twin (exact)"})
    got_a = np.asarray(scan_topm_xla(q, prox, q_ids, m=m,
                                     approx=True)[1])
    rows.append({"name": f"select_approx_twin_{q_n}x{n}_m{m}",
                 "us_per_call": _time(lambda: scan_topm_xla(
                     q, prox, q_ids, m=m, approx=True), reps=5),
                 "approx_recall": recall(got_a),
                 "derived": "approx_max_k twin (recall < 1 by design)"})
    for tag, rec in (("kernel", recall(got_k)), ("xla", recall(got_x))):
        assert rec >= 1.0, (f"select {tag} smoke: recall {rec} below "
                            f"pinned floor 1.0")
    return rows


def run_rerank_smoke(rng, g: int = 48, kc: int = 160, j: int = 256,
                     k: int = 10):
    """Verify + time the co-rated Gram rerank kernel and its host twin.

    Integer ratings make every Gram sum an exact f32 integer, so the
    kernel (interpret mode), the OpenBLAS twin, and the jnp oracle must
    produce *identical* top-k neighbor sets — recall 1.0, pinned.
    """
    vq = (rng.integers(1, 6, (g, j))
          * (rng.random((g, j)) < 0.3)).astype(np.float32)
    rc = (rng.integers(1, 6, (kc, j))
          * (rng.random((kc, j)) < 0.3)).astype(np.float32)
    norms = np.sqrt((rc * rc).sum(1)).astype(np.float32)
    counts = (rc > 0).sum(1).astype(np.float32)
    args_j = (jnp.asarray(vq), jnp.asarray(rc.astype(np.int8)),
              jnp.asarray(norms), jnp.asarray(counts))
    oracle = jax.jit(ref.rerank_scores_ref, static_argnames=("measure",))
    rows = []
    for measure in ("cosine", "jaccard", "pcc_sig"):
        want = np.asarray(oracle(jnp.asarray(vq), jnp.asarray(rc),
                                 jnp.asarray(norms), jnp.asarray(counts),
                                 measure=measure))
        us_k = _time(lambda: fused_rerank_scores(
            *args_j, measure=measure, bm=16, bn=64, bk=128,
            interpret=True), reps=2, name=f"rerank_{measure}")
        got_k = np.asarray(fused_rerank_scores(
            *args_j, measure=measure, bm=16, bn=64, bk=128,
            interpret=True))
        us_h = _time(lambda: rerank_scores_host(
            vq, rc, norms, counts, measure=measure), reps=5)
        got_h = rerank_scores_host(vq, rc, norms, counts, measure=measure)
        rec_k = _rerank_recall(got_k, want, k)
        rec_h = _rerank_recall(got_h, want, k)
        rows.append({"name": f"rerank_kernel_{measure}_{g}x{kc}x{j}",
                     "us_per_call": us_k,
                     "recall_vs_oracle": rec_k,
                     "derived": "interpret-mode (no Mosaic on CPU)"})
        rows.append({"name": f"rerank_host_{measure}_{g}x{kc}x{j}",
                     "us_per_call": us_h,
                     "recall_vs_oracle": rec_h,
                     "derived": "OpenBLAS host twin"})
        for tag, rec in (("kernel", rec_k), ("host", rec_h)):
            assert rec >= RERANK_RECALL_FLOOR, \
                (f"rerank {tag} smoke ({measure}): recall {rec} below "
                 f"pinned floor {RERANK_RECALL_FLOOR}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json-path", default="BENCH_kernels.json")
    ap.add_argument("--metrics-path", default=None,
                    help="dump the per-rep kernel-wall histograms")
    args = ap.parse_args()
    rows = run()
    if args.metrics_path:
        from repro import obs
        obs.export_metrics(args.metrics_path)
        print(f"wrote {args.metrics_path}")
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r.get('derived', '')}")
    with open(args.json_path, "w") as f:
        json.dump(rows, f, indent=2, sort_keys=True)
    print(f"wrote {args.json_path} ({len(rows)} rows)")


if __name__ == "__main__":
    main()
