"""Tiny CPU versions of the benchmark's cells: the run's phases in
Pallas interpret mode, with the entry's look for a chip skipped."""

import copy
import time

from bench import drive, run

# the device orchestration the chip runs, with every kernel interpreted
INTERPRET_MODES = dict(drive.DEVICE_MODES, index_interpret=True,
                       item_index_interpret=True)
TINY = {"n_users": 240, "n_items": 160, "n_ratings": 6000, "knee_rps": 60.0}


def tiny_cell(name: str) -> dict:
    """The committed cell ``name`` with its corpus cut to 240 × 160 and
    k = 8, so a run takes seconds on a CPU host."""
    cell = copy.deepcopy(run.load_cell(name))
    cfg = cell["config"]
    cfg.update(TINY)
    cfg["assumed"]["max_user_ratings"] = 120
    cfg["engine"]["k"] = 8
    return cell


def rehearse(cell: dict, seed: int, seconds: float = 2.0, **kw) -> dict:
    """One run of a tiny cell on the CPU host (no chip, interpret mode)."""
    from repro.index import IndexConfig, ItemIndexConfig
    return run.run_cell(
        cell, seed, seconds, False, t_start=time.perf_counter(),
        require_tpu=False, expect_modes=INTERPRET_MODES,
        index_cfg=IndexConfig(features="centered", use_kernel=True,
                              interpret=True),
        item_index_cfg=ItemIndexConfig(use_kernel=True, interpret=True,
                                       shortlist=64), **kw)
