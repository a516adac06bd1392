#!/usr/bin/env python3
"""Read the compared numbers of the program and of the control on many
seeds, at a cell's own size and load, in one process.

    python3 bench/calibrate.py --workload ml1m-steady --seeds 1,2,3 --seconds 5

For each seed it makes one run of the cell (``run.run_cell``, a short
window) and puts the control (``bench/reference/control.py``: the
reference in bfloat16) in the program's place on the same sampled
requests.  Prints one JSON line per seed with both sets of numbers; the
limits in the configuration files are set from these lines
(``PERF.md`` gives the readings and the limits).  The benchmark's own
runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import run
    run.bootstrap()
    cell = run.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, seed, args.seconds, False,
                           t_start=time.perf_counter(), control=True)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": res["correct"],
            "program": {k: v["value"] for k, v in res["checks"].items()},
            "control": res["control_checks"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
