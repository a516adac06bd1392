#!/usr/bin/env python3
"""Chip smoke: the CF serving path end to end on one TPU at ML-1M scale.

    python3 chip_smoke.py                # one chip: phases (a)-(d)
    python3 chip_smoke.py --four-chips   # four chips: ring/sharded exact
                                         # top-k vs sequential, nothing else

Data is the ML-1M surrogate (``repro.data.movielens``: 6040 users × 3952
items, ~1.0 M integer ratings) generated from ``--seed``; the engine is
``configs/cf_movielens.py``'s deployment (pcc, k=40).  Phases:

(a) exact fit, ``sequential`` vs ``pallas`` backend: same neighbor ids;
(b) approx engine (user + item index, auto configs): the modes must
    resolve to the device paths, recall against the exact engine must
    clear floors taken from this phase run at the same size on a CPU host;
(c) a ``BatchingServer`` in front of the approx engine serves three
    waves of requests with an ``update_ratings`` burst of the same shape
    before the second and the third (the first burst pays compilation,
    the second shows the served update path's steady cost): every future
    resolves and every served list equals ``engine.recommend``;
(d) neighbor scores and served recommendations for a seeded user sample
    agree with a plain float64 numpy reference on the host.

Every check that fails makes the script exit non-zero.  The last line of
standard output, printed only when everything passed, is one JSON object
naming the device.  The script refuses to run anywhere but a TPU; the
phase functions take their sizes as arguments, so
``tests/test_chip_smoke.py`` rehearses them at a tiny size on the CPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

K = 40
MEASURE = "pcc"
TOPN = 10
# recall floors for phase (b): this phase at the full ML-1M surrogate size
# (seed 0) on a CPU host gave recall@40 = 0.7160 and recommend
# recall@10 = 1.0000; the floors sit 0.01 below to absorb the device's
# different proxy rounding
NEIGHBOR_RECALL_FLOOR = 0.70
RECOMMEND_RECALL_FLOOR = 0.99
# float64 host reference vs the device's float32 epilogues
SCORE_ATOL = 1e-4


class SmokeFailure(AssertionError):
    """A smoke check disagreed with its reference."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def surrogate_ratings(n_users: int, n_items: int, seed: int) -> np.ndarray:
    """The ML-1M surrogate at its published shape, or scaled down with
    the co-rated overlap preserved (``MovieLensSpec.scaled``)."""
    from repro.data.movielens import (ML1M_ITEMS, ML1M_USERS, MovieLensSpec,
                                      generate_ratings)
    spec = MovieLensSpec(seed=seed)
    if (n_users, n_items) != (ML1M_USERS, ML1M_ITEMS):
        spec = spec.scaled(n_users, n_items)
    return generate_ratings(spec)


def _timed_fit(engine) -> tuple:
    """Fit twice: the first fit pays compilation, the second does not."""
    t0 = time.perf_counter()
    engine.fit()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    engine.fit()
    return first, time.perf_counter() - t0


def phase_exact(ratings: np.ndarray, *, k: int = K, measure: str = MEASURE,
                block_size: int = 1024, log=print) -> dict:
    """(a) Exact top-k through the ``sequential`` and ``pallas`` backends;
    their neighbor ids must agree exactly."""
    from repro.core import CFEngine
    out = {}
    engines = {}
    for backend in ("sequential", "pallas"):
        eng = CFEngine(ratings, measure=measure, k=k, backend=backend,
                       block_size=block_size)
        first, second = _timed_fit(eng)
        engines[backend] = eng
        out[f"{backend}_fit_first_s"] = first
        out[f"{backend}_fit_second_s"] = second
        log(f"(a) {backend}: fit {first:.3f}s first (with compile), "
            f"{second:.3f}s second; interpret={eng.interpret}")
    seq, pal = engines["sequential"], engines["pallas"]
    seq_i, pal_i = np.asarray(seq.idx), np.asarray(pal.idx)
    diff = np.abs(np.asarray(seq.scores) - np.asarray(pal.scores))
    out["id_mismatches"] = int((seq_i != pal_i).sum())
    out["max_score_diff"] = float(diff[np.isfinite(diff)].max(initial=0.0))
    out["pallas_interpret"] = pal.interpret
    log(f"(a) sequential vs pallas: {out['id_mismatches']} neighbor-id "
        f"mismatches of {seq_i.size}, max |score diff| "
        f"{out['max_score_diff']:.3g}")
    _check(out["id_mismatches"] == 0,
           "pallas neighbor ids differ from the sequential backend")
    out["engine"] = seq
    return out


def resolved_modes(engine) -> dict:
    """The paths the approx engine's last index query and last item-index
    recommend resolved to."""
    q = engine.index.last_query
    return {"query_mode": q.query_mode, "scan_mode": q.scan_mode,
            "select_mode": q.select_mode, "rerank_mode": q.rerank_mode,
            "item_scorer": engine.item_index.last_recommend.scorer,
            "index_interpret": engine.index.cfg.interpret,
            "item_index_interpret": engine.item_index.cfg.interpret}


# what auto configs must resolve to on the chip: the fused device query
# with the top_k selection twin and the fused rerank kernel, and the Pallas
# support kernel as the item scorer — no interpret mode anywhere
DEVICE_MODES = {"query_mode": "fused", "scan_mode": "kernel",
                "select_mode": "top_k", "rerank_mode": "fused",
                "item_scorer": "kernel", "index_interpret": False,
                "item_index_interpret": False}


def phase_approx(ratings: np.ndarray, *, k: int = K, measure: str = MEASURE,
                 n: int = TOPN, expect_modes: dict | None = DEVICE_MODES,
                 neighbor_floor: float = NEIGHBOR_RECALL_FLOOR,
                 recommend_floor: float = RECOMMEND_RECALL_FLOOR,
                 index_cfg=None, item_index_cfg=None, log=print) -> dict:
    """(b) Fit the approx engine (user index + item index), check the
    resolved paths and recall against the exact engine."""
    from repro.core import CFEngine
    eng = CFEngine(ratings, measure=measure, k=k, neighbor_mode="approx",
                   recommend_mode="approx", index_cfg=index_cfg,
                   item_index_cfg=item_index_cfg)
    t0 = time.perf_counter()
    eng.fit()
    fit_s = time.perf_counter() - t0
    recall = eng.recall_vs_exact(sample=1024)
    rec_recall = eng.recommend_recall_vs_exact(sample=256, n=n)
    modes = resolved_modes(eng)
    log(f"(b) approx fit {fit_s:.3f}s (index fit + query, with compile); "
        f"modes {modes}")
    log(f"(b) recall@{k} vs exact {recall:.4f} (floor {neighbor_floor}), "
        f"recommend recall@{n} vs exact {rec_recall:.4f} "
        f"(floor {recommend_floor})")
    if expect_modes is not None:
        _check(modes == expect_modes,
               f"approx engine resolved to {modes}, want {expect_modes}")
    _check(recall >= neighbor_floor,
           f"recall@{k} {recall:.4f} under the floor {neighbor_floor}")
    _check(rec_recall >= recommend_floor,
           f"recommend recall@{n} {rec_recall:.4f} under the floor "
           f"{recommend_floor}")
    return {"engine": eng, "fit_s": fit_s, "modes": modes,
            "recall": recall, "recommend_recall": rec_recall}


def _serve_wave(server, users) -> list:
    futs = [server.submit(int(u)) for u in users]
    out = []
    for f in futs:
        out.append(f.result(timeout=600))   # raises if the future failed
    return out


def phase_serve(engine, *, n_requests: int = 48, burst: int = 64,
                seed: int = 0, n: int = TOPN, max_batch: int = 16,
                log=print) -> dict:
    """(c) Serve three waves through a ``BatchingServer`` with one
    ``update_ratings`` burst of ``burst`` ratings before each later
    wave; served lists must equal ``engine.recommend`` for the same
    users on the same model.  Returns each burst's wall seconds."""
    from repro.serving.engine import BatchingServer
    rng = np.random.default_rng(seed)
    n_users, n_items = engine.n_users, engine.n_items
    waves = [rng.integers(0, n_users, len(part))
             for part in np.array_split(np.arange(n_requests), 3)]
    server = BatchingServer(engine, max_batch=max_batch, topn=n)
    server.start()
    served = []
    burst_s = []
    try:
        for w, users in enumerate(waves):
            if w:
                t0 = time.perf_counter()
                st = engine.update_ratings(
                    rng.integers(0, n_users, burst),
                    rng.integers(0, n_items, burst),
                    rng.integers(1, 6, burst).astype(np.float32))
                burst_s.append(time.perf_counter() - t0)
                log(f"(c) update burst {w}: {st.n_deltas} ratings, "
                    f"{st.n_touched} users, {st.n_affected} rows recomputed "
                    f"in {st.seconds:.3f}s ({burst_s[-1]:.3f}s wall)")
            t0 = time.perf_counter()
            recs = _serve_wave(server, users)
            wave_s = time.perf_counter() - t0
            want_s, want_i = engine.recommend(users, n)
            want_s, want_i = np.asarray(want_s), np.asarray(want_i)
            got_i = np.stack([r.items for r in recs])
            got_s = np.stack([r.scores for r in recs])
            _check(np.array_equal(got_i, want_i),
                   f"wave {w}: served items differ from engine.recommend")
            _check(np.array_equal(got_s, want_s),
                   f"wave {w}: served scores differ from engine.recommend")
            log(f"(c) wave {w}: {len(recs)} requests resolved in "
                f"{wave_s:.3f}s, equal to engine.recommend")
            served.append((users, got_s, got_i))
    finally:
        server.stop()
    stats = server.stats()
    _check(stats["n_failures"] == 0, f"server failures: {stats}")
    return {"served": served, "batches": stats["n_batches"],
            "burst_s": burst_s}


def host_similarity(ratings: np.ndarray, users: np.ndarray) -> np.ndarray:
    """Plain float64 pcc (co-rated means, [0, 1]-normalised, 0 for pairs
    with < 2 co-rated items or zero variance) of ``users`` vs everyone."""
    r = ratings.astype(np.float64)
    m = (r > 0).astype(np.float64)
    ru, mu = r[users], m[users]
    n = mu @ m.T
    dot = ru @ r.T
    sa, sb = ru @ m.T, mu @ r.T
    qa, qb = (ru * ru) @ m.T, mu @ (r * r).T
    cov = n * dot - sa * sb
    var_a = np.maximum(n * qa - sa * sa, 0.0)
    var_b = np.maximum(n * qb - sb * sb, 0.0)
    denom = np.sqrt(var_a * var_b)
    valid = (n >= 2) & (denom > 1e-8)
    pcc = np.clip(np.where(valid, cov / np.where(valid, denom, 1.0), 0.0),
                  -1.0, 1.0)
    return np.where(valid, (pcc + 1.0) * 0.5, 0.0)


def host_means(ratings: np.ndarray) -> np.ndarray:
    """Per-user mean over rated items; 0-raters get the global mean."""
    r = ratings.astype(np.float64)
    cnt = (r > 0).sum(1)
    return np.where(cnt > 0, r.sum(1) / np.maximum(cnt, 1),
                    r.sum() / max(cnt.sum(), 1))


def host_predict(ratings: np.ndarray, means: np.ndarray, scores: np.ndarray,
                 idx: np.ndarray, user: int, items: np.ndarray) -> np.ndarray:
    """The mean-centred weighted-deviation predictor, float64."""
    nb, w = idx[user], scores[user].astype(np.float64)
    w = np.where((w > 0) & (nb >= 0), w, 0.0)
    nb = np.where(nb >= 0, nb, 0)
    rows = ratings[nb][:, items].astype(np.float64)
    mask = (rows > 0).astype(np.float64)
    num = (w[:, None] * (rows - means[nb][:, None]) * mask).sum(0)
    den = (w[:, None] * mask).sum(0)
    pred = means[user] + num / np.maximum(den, 1e-8)
    return np.clip(np.where(den > 1e-8, pred, means[user]), 1.0, 5.0)


def phase_reference(exact_engine, approx_engine, served, *,
                    sample: int = 32, seed: int = 0, log=print) -> dict:
    """(d) Against a float64 numpy reference on the host: the exact
    engine's cached rows are a top-k of the reference similarities, the
    approx engine's cached neighbors carry their true similarity, and
    every served score is the reference prediction of its item."""
    rng = np.random.default_rng(seed)
    out = {}
    # exact engine: scores equal the reference and nothing outside the
    # cached set beats the k-th score
    r_ex = np.asarray(exact_engine.ratings)
    users = np.sort(rng.choice(r_ex.shape[0], sample, replace=False))
    ref = host_similarity(r_ex, users)
    ref[np.arange(len(users)), users] = -np.inf          # self-pairs
    s_ex, i_ex = np.asarray(exact_engine.scores), np.asarray(exact_engine.idx)
    worst = 0.0
    for row, u in enumerate(users):
        got = ref[row, i_ex[u]]
        worst = max(worst, float(np.abs(got - s_ex[u]).max()))
        rest = np.delete(ref[row], i_ex[u])
        _check(rest.max(initial=-np.inf) <= s_ex[u, -1] + SCORE_ATOL,
               f"exact engine: user {u} misses a better reference neighbor")
    _check(worst <= SCORE_ATOL,
           f"exact engine scores off the host reference by {worst:.3g}")
    out["exact_max_score_err"] = worst
    # approx engine: cached neighbors carry their true similarity
    r_ap = np.asarray(approx_engine.ratings)
    s_ap, i_ap = np.asarray(approx_engine.scores), np.asarray(approx_engine.idx)
    ref = host_similarity(r_ap, users)
    worst = 0.0
    for row, u in enumerate(users):
        ok = i_ap[u] >= 0
        worst = max(worst, float(np.abs(ref[row, i_ap[u][ok]]
                                        - s_ap[u][ok]).max(initial=0.0)))
    _check(worst <= SCORE_ATOL,
           f"approx engine scores off the host reference by {worst:.3g}")
    out["approx_max_score_err"] = worst
    # served recommendations of the last wave (the engine's current model):
    # unseen items, descending, each score the reference prediction
    w_users, w_s, w_i = served[-1]
    means = host_means(r_ap)
    worst = 0.0
    for u, s, items in zip(w_users, w_s, w_i):
        ok = items >= 0
        _check(not (r_ap[u, items[ok]] > 0).any(),
               f"served an already-rated item to user {u}")
        _check(bool(np.all(np.diff(s[ok]) <= 0)),
               f"served list of user {u} is not in descending order")
        pred = host_predict(r_ap, means, s_ap, i_ap, int(u), items[ok])
        worst = max(worst, float(np.abs(pred - s[ok]).max(initial=0.0)))
    _check(worst <= SCORE_ATOL,
           f"served scores off the host predictor by {worst:.3g}")
    out["served_max_score_err"] = worst
    log(f"(d) host reference over {sample} users: exact scores "
        f"±{out['exact_max_score_err']:.3g}, approx scores "
        f"±{out['approx_max_score_err']:.3g}, served scores "
        f"±{out['served_max_score_err']:.3g} (limit {SCORE_ATOL})")
    return out


def phase_four_chips(ratings: np.ndarray, *, k: int = K,
                     measure: str = MEASURE, block_size: int = 1024,
                     log=print) -> dict:
    """Exact top-k with ``ring`` and ``sharded`` over a 4-device
    ``("data",)`` mesh vs ``sequential`` on device 0, in this process:
    ids and scores must be bit-identical, and the sharded results must
    live on all four devices."""
    import jax
    from repro.core import CFEngine
    from repro.core.engine import local_mesh
    devices = jax.devices()
    _check(len(devices) == 4, f"want 4 devices, found {len(devices)}")
    seq = CFEngine(jax.device_put(ratings, devices[0]), measure=measure,
                   k=k, block_size=block_size)
    first, second = _timed_fit(seq)
    log(f"(4) sequential on {devices[0]}: fit {first:.3f}s first, "
        f"{second:.3f}s second")
    want_s, want_i = np.asarray(seq.scores), np.asarray(seq.idx)
    mesh = local_mesh(4)
    out = {}
    for backend in ("ring", "sharded"):
        eng = CFEngine(ratings, measure=measure, k=k, backend=backend,
                       mesh=mesh, block_size=block_size)
        first, second = _timed_fit(eng)
        n_dev = len(eng.scores.sharding.device_set)
        ids_equal = np.array_equal(np.asarray(eng.idx), want_i)
        scores_equal = np.array_equal(np.asarray(eng.scores), want_s)
        log(f"(4) {backend}: fit {first:.3f}s first, {second:.3f}s second; "
            f"result on {n_dev} devices; ids bit-identical {ids_equal}, "
            f"scores bit-identical {scores_equal}")
        _check(n_dev == 4, f"{backend} result sits on {n_dev} device(s)")
        _check(ids_equal and scores_equal,
               f"{backend} top-k differs from sequential")
        out[backend] = {"fit_first_s": first, "fit_second_s": second,
                        "devices": n_dev}
    return out


def _dir_mib(path: str) -> float:
    """Size of the files under ``path`` in MiB (0 when it is absent)."""
    root = Path(path)
    if not root.is_dir():
        return 0.0
    return sum(f.stat().st_size for f in root.rglob("*")
               if f.is_file()) / 2**20


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip ring/sharded phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device.platform}; "
              "nothing was run", file=sys.stderr)
        return 1
    from repro.data.movielens import ML1M_ITEMS, ML1M_USERS
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    n_dev = len(jax.devices())
    print(f"device: {device.platform} {device.device_kind} x{n_dev}; "
          f"jax {jax.__version__}; compile cache {cache} "
          f"({_dir_mib(cache):.1f} MiB at start)", flush=True)

    def log(msg):
        print(msg, flush=True)

    t0 = time.perf_counter()
    ratings = surrogate_ratings(ML1M_USERS, ML1M_ITEMS, args.seed)
    log(f"data: {ratings.shape[0]} users x {ratings.shape[1]} items, "
        f"{int((ratings > 0).sum())} ratings, seed {args.seed} "
        f"({time.perf_counter() - t0:.2f}s)")
    try:
        if args.four_chips:
            phase_four_chips(ratings, log=log)
        else:
            ex = phase_exact(ratings, log=log)
            ap_ = phase_approx(ratings, log=log)
            sv = phase_serve(ap_["engine"], seed=args.seed, log=log)
            phase_reference(ex["engine"], ap_["engine"], sv["served"],
                            seed=args.seed, log=log)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s; compile "
        f"cache {_dir_mib(cache):.1f} MiB at end")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
