#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a deployment
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``).  A run refuses anything but a TPU with
the cell's chips; builds the corpus on the device from ``--seed``; fits
the engine; warms the cell's own shapes (all of that is ``setup_s``);
measures for ``--seconds``; compares a seeded sample of what the window
produced with the float64 reference (``bench/reference``); and prints one
JSON object as its last line of standard output.  With ``--trace 0`` its
metrics are the cell's end-to-end ones; with ``--trace 1`` the window is
profiled and its metrics are the cell's per-layer ones, each read by
``bench/metrics/<name>.py``.  The numbers compared for ``correct`` are the
last lines of standard error and the last key of the result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
# fixed, inside the checkout: the path is part of the cache's key
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    traffic mix and the metrics it reports, all found by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def reports(m):
        return "workloads" not in m or name in m["workloads"]

    return {"workload": w,
            "config": json.loads((root / conf["file"]).read_text()),
            "traffic": json.loads(
                (root / "bench" / "traffic" / f"{w['traffic']}.json")
                .read_text()),
            "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
            "per_layer": [m for m in bench["per_layer"] if reports(m)]}


def metric_reader(name: str, root: Path = ROOT):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _device(jax, chips: int, require_tpu: bool):
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"need {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


# persistent-cache misses and hits seen by this process (jax.monitoring
# has no unregister, so one listener serves every run in the process)
_CACHE_EVENTS = {"/jax/compilation_cache/cache_misses": 0,
                 "/jax/compilation_cache/cache_hits": 0}
_listening = []


def _cache_counts() -> tuple:
    """``(misses, hits)`` of the persistent compile cache so far."""
    if not _listening:
        import jax

        def on_event(event, **_kw):
            if event in _CACHE_EVENTS:
                _CACHE_EVENTS[event] += 1

        jax.monitoring.register_event_listener(on_event)
        _listening.append(on_event)
    return tuple(_CACHE_EVENTS.values())


def _hist_delta(before: dict, after: dict) -> dict:
    """``{histogram: {"sum", "count"}}`` observed between two snapshots."""
    out = {}
    for name, h in after["histograms"].items():
        b = before["histograms"].get(name, {"sum": 0.0, "count": 0})
        out[name] = {"sum": h["sum"] - b["sum"],
                     "count": h["count"] - b["count"]}
    return out


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float = T_START, require_tpu: bool = True,
             expect_modes: dict | None = None, index_cfg=None,
             item_index_cfg=None, control: bool = False) -> dict:
    """One run of ``cell``; returns the result object.  Tests pass
    ``require_tpu=False`` with configs that force interpret mode;
    ``control=True`` (``bench/calibrate.py``) adds the control's numbers
    on the same sampled requests as ``control_checks``."""
    import jax

    from repro import obs
    from repro.analysis.retrace import RetraceSentinel

    from bench import drive
    from bench.corpus import make_corpus
    from bench.reference import check
    from bench.trace import peaks as peak_table
    from bench.trace.reduce import top

    w, cfg, traffic = cell["workload"], cell["config"], cell["traffic"]
    devices = _device(jax, int(w["chips"]), require_tpu)
    dev = devices[0]
    peaks = peak_table.peaks(dev.device_kind) if require_tpu else None
    n = int(cfg["engine"]["topn"])
    open_loop = traffic["loop"] == "open"
    if traffic["loop"] not in ("open", "closed"):
        raise ValueError(f"unknown loop {traffic['loop']!r}")

    # set-up; its cache misses are 0 once every program is in the cache
    misses0, hits0 = _cache_counts()
    with RetraceSentinel("bench.setup", publish=False) as setup_compiles:
        corpus = jax.block_until_ready(make_corpus(cfg, seed))
        log(f"corpus {corpus.shape} on {dev.platform} {dev.device_kind}: "
            f"{time.perf_counter() - t_start:.2f}s since start")
        eng = drive.fit_engine(corpus, cfg, index_cfg=index_cfg,
                               item_index_cfg=item_index_cfg)
        log(f"engine fitted: {time.perf_counter() - t_start:.2f}s since "
            "start")
        server = None
        if open_loop:
            from repro.serving.engine import BatchingServer
            server = BatchingServer(eng, topn=n)
            server.start()
            drive.warm_serve(server, eng.n_users, seed)
            due, users = drive.schedule(traffic, float(cfg["knee_rps"]),
                                        seconds, seed, eng.n_users)
        else:
            drive.bulk_step(eng, n)
    modes = drive.resolved_modes(eng)
    want = drive.DEVICE_MODES if expect_modes is None else expect_modes
    mode_mismatch = sum(modes[k] != v for k, v in want.items())
    log(f"modes {modes}")
    obs.clear()
    reg_before = server.registry.snapshot() if server else None
    # every window opens on one collector state: set-up's garbage freed
    # and its survivors in the oldest generation
    gc.collect()
    setup_s = time.perf_counter() - t_start
    misses, hits = _cache_counts()
    log(f"setup_s {setup_s:.3f}: {setup_compiles.count} programs compiled "
        f"or loaded, {misses - misses0} compile-cache misses, "
        f"{hits - hits0} hits; window {seconds}s, trace {int(trace)}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        if open_loop:
            # the profiler's first device activity stalls the host: let it
            # fall on a batch before the window, which the trace leaves out
            drive.warm_serve(server, eng.n_users, seed, server.max_batch)
    # perf_counter → unix ns, to put host times on the trace's clock
    unix_off = time.time_ns() - int(time.perf_counter() * 1e9)

    def sample(count):
        return check.sample(count, check.SAMPLE_REQUESTS, seed)

    with RetraceSentinel("bench.window", publish=False) as window_compiles, \
            drive.GcPauses() as gc_pauses:
        if open_loop:
            win = drive.serve_window(server, due, users, seconds, sample)
        else:
            win = drive.bulk_window(eng, n, seconds, sample, seed)
    if trace:
        jax.profiler.stop_trace()
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
    spans = obs.get_spans()
    reg_delta = (_hist_delta(reg_before, server.registry.snapshot())
                 if server else None)
    if server:
        server.stop()
    log(f"window closed after {win.seconds:.3f}s; programs compiled or "
        f"loaded in the window: {window_compiles.count}")
    log(win.note)
    log(gc_pauses.summary())

    result = {"correct": False, "attempted": win.attempted,
              "failed": win.failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    if trace:
        def unix(t):
            return unix_off + int(t * 1e9)

        t_red = time.perf_counter()
        red = _read_trace(trace_dir,
                          (unix(win.t_open), unix(win.t_open + win.seconds)),
                          [(unix(sp.t_start), unix(sp.t_start + sp.duration),
                            sp.name) for sp in spans])
        log(f"trace reduced in {time.perf_counter() - t_red:.2f}s")
        ctx = {"trace": red, "peaks": peaks, "config": cfg,
               "serve": reg_delta, "spans": spans,
               "users_scored": win.attempted - win.failed,
               "latency_s": win.latency_s, "window_s": red["window_s"]}
        result["metrics"] = _per_layer(cell["per_layer"], ctx)
        result["breakdown"] = {"device_ops": top(red["ops"]),
                               "idle_gaps": top(red["idle_gaps"])}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    else:
        e2e = dict(win.e2e, setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell["end_to_end"]}
    result["device"] = device

    # the reference runs on the host once the program's state is freed
    t_ref = time.perf_counter()
    cache_users = sorted({a[0] for a in win.answers})
    idx_np, sc_np = np.asarray(eng.idx), np.asarray(eng.scores)
    cache = {u: (idx_np[u].astype(np.int64), sc_np[u].astype(np.float64))
             for u in cache_users}
    ratings = np.asarray(corpus)
    del eng, server, corpus
    gc.collect()
    read = check.readings(ratings, cfg, win.answers, cache, win.failed)
    read["mode_mismatch"] = int(mode_mismatch)
    lim = dict(check.limits(cfg), mode_mismatch=0)
    result["correct"], table = check.judge(read, lim)
    log(f"reference compared {len(win.answers)} answers and "
        f"{len(cache_users)} cache rows in {time.perf_counter() - t_ref:.2f}s")
    if control:
        from bench.reference import control as ctl
        c_answers, c_cache = ctl.answers(ratings, cfg,
                                         [a[0] for a in win.answers], n)
        result["control_checks"] = check.readings(ratings, cfg, c_answers,
                                                  c_cache, 0)
    result["checks"] = table
    for name, row in table.items():
        log(f"check {name}: {row['value']!r} limit {row['limit']!r}")
    return result


def _read_trace(trace_dir: str, clip, host_spans) -> dict:
    from bench.trace.reduce import find_xplane, reduce_trace
    try:
        return reduce_trace(find_xplane(trace_dir), clip=clip,
                            host_spans=host_spans)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def _per_layer(metrics: list, ctx: dict) -> dict:
    """Each per-layer metric its reader finds something for."""
    out = {}
    for m in metrics:
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def bootstrap() -> None:
    """Put the program and the benchmark on the path and keep JAX's
    compile cache at the checkout's fixed ``.jax_cache``; call before
    JAX is imported."""
    # the program keeps its compile cache where this variable says
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    for path in (str(SRC), str(ROOT)):
        if path not in sys.path:
            sys.path.insert(0, path)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program, however quick to compile, comes from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        log(f"bench: no program under {SRC}; nothing was run")
        return 2
    cell = load_cell(args.workload)
    bootstrap()
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}; nothing was run")
        return 3
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
