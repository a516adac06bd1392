"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per the harness contract, then
the roofline table derived from the dry-run artifacts (if present).
Machine-readable artifacts ``BENCH_topk.json`` and ``BENCH_index.json``
are written alongside so the perf trajectory is tracked across PRs.

  paper Figs. 3–6 → MAE/Precision/Recall/F1 vs top-N × {jaccard,cosine,pcc}
  index           → clustered two-stage search vs the exact engine
  methodology     → kernel microbenches + roofline terms

Every section runs in this one process, so it may hold the accelerator.
A failed section prints its traceback, the remaining sections still run,
and the driver exits 1.  The paper's Figs. 1–2 shard sweep starts child
processes on fake CPU devices; it is a standalone CPU demonstration
(``benchmarks/bench_time_comparison.py``), not a section here.
"""

from __future__ import annotations

import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache


def _topn_metrics() -> None:
    """Paper Figs. 3-6: metric curves."""
    from benchmarks import bench_topn_metrics
    from benchmarks.bench_index import write_json
    topk_rows = []
    for r in bench_topn_metrics.run(n_users=1024, n_items=768):
        name = f"topn_{r['measure']}_k{r['top_n']}"
        derived = (f"mae={r['mae']:.4f} p={r['precision']:.4f} "
                   f"r={r['recall']:.4f} f1={r['f1']:.4f}")
        print(f"{name},{r['seconds'] * 1e6:.0f},{derived}")
        topk_rows.append(dict(r, name=name, us_per_call=r["seconds"] * 1e6))
    write_json("BENCH_topk.json", topk_rows)


def _index() -> None:
    """Clustered index vs exact engine."""
    from benchmarks import bench_index
    rows = bench_index.run(sizes=(1024,), k=20, measure="cosine")
    for r in rows:
        derived = (f"speedup={r['fit_query_speedup']} "
                   f"recall={r['recall_at_k']} "
                   f"rerank={r['rerank_fraction']}")
        print(f"{r['name']},{r['us_per_call']:.0f},{derived}")
    bench_index.write_json("BENCH_index.json", rows)


def _kernels() -> None:
    from benchmarks import bench_kernels
    for name, us, derived in bench_kernels.run():
        print(f"kernel_{name},{us:.1f},{derived}")


def _roofline() -> None:
    """Roofline rows from the dry-run artifacts."""
    from benchmarks import roofline
    rows = [roofline.roofline_row(r) for r in roofline.load_cells()]
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        name = f"roofline_{r['arch']}_{r['shape']}"
        derived = (f"compute_s={r['compute_s']:.3e} "
                   f"mem_floor_s={r['memory_s']:.3e} "
                   f"coll_s={r['collective_s']:.3e} "
                   f"bottleneck={r['dominant']} "
                   f"frac={r['roofline_fraction']:.3f}")
        print(f"{name},0,{derived}")


SECTIONS = (("topn_metrics", _topn_metrics), ("index", _index),
            ("kernels", _kernels), ("roofline", _roofline))


def main() -> int:
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for name, section in SECTIONS:
        try:
            section()
        except Exception:   # section boundary: reported, exit code 1
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"failed sections: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
