"""Chip benchmark of the CF serving system: one cell (deployment × traffic
mix) per run of ``bench/run.py``; see ``BENCHMARK.json`` at the root."""
