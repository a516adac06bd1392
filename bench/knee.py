#!/usr/bin/env python3
"""Find a serve configuration's knee by one sweep on the chip.

    python3 bench/knee.py --config ml1m --rates 100,150,200 --seconds 8

One process builds the corpus and the engine as a run does, then offers
open-loop Poisson load (``bench/traffic/steady.json``'s generator at a
fixed rate) at each rate in turn.  The knee is the highest rate at which
the server completes, within the window, at least 98% of what was
offered and the backlog at the window's close is at most 0.1 s of
arrivals.
Prints one JSON line per rate and a last line with the knee; the knee
goes into the configuration file's ``knee_rps`` by hand.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
# a server that keeps up holds about rate × latency requests in flight
# (~30 at 575 req/s and 50 ms); a growing backlog holds far more
BACKLOG_S = 0.1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.run import bootstrap
    bootstrap()
    import jax
    from repro.serving.engine import BatchingServer

    from bench import drive
    from bench.corpus import make_corpus
    if jax.devices()[0].platform != "tpu":
        print("knee: needs a TPU", file=sys.stderr)
        return 3
    cfg = json.loads((ROOT / "bench" / "configs" /
                      f"{args.config}.json").read_text())
    t0 = time.perf_counter()
    eng = drive.fit_engine(make_corpus(cfg, args.seed), cfg)
    server = BatchingServer(eng, topn=int(cfg["engine"]["topn"]))
    server.start()
    drive.warm_serve(server, eng.n_users, args.seed)
    print(f"set-up {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    mix = {"period_s": 1.0, "phases": [{"seconds": 1.0, "rate_of_knee": 1.0}]}
    knee = 0.0
    for rate in (float(r) for r in args.rates.split(",")):
        due, users = drive.schedule(mix, rate, args.seconds, args.seed,
                                    eng.n_users)
        loop = drive.OpenLoop(server, due, users)
        t_open = loop.run()
        close = t_open + args.seconds
        time.sleep(max(close - time.perf_counter(), 0.0))
        done_in = int(np.sum(loop.done <= close))
        backlog = len(due) - done_in
        loop.wait(close)
        lat, ok = loop.results()
        row = {"rate": rate, "offered": len(due), "done_in_window": done_in,
               "backlog_at_close": backlog,
               "p50_ms": float(np.percentile(lat, 50) * 1e3),
               "p99_ms": float(np.percentile(lat, 99) * 1e3),
               "failed": int((~ok).sum())}
        keeps_up = (done_in >= 0.98 * len(due)
                    and backlog <= BACKLOG_S * rate)
        row["keeps_up"] = keeps_up
        print(json.dumps(row), flush=True)
        if keeps_up:
            knee = rate
    server.stop()
    print(json.dumps({"config": args.config, "knee_rps": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
