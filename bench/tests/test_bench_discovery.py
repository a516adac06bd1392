"""A later change adds a deployment, a traffic mix or a per-layer metric
by adding files and entries: the harness finds each by its name."""

import json
import shutil
from pathlib import Path

import numpy as np

from bench import drive, run

ROOT = Path(__file__).resolve().parents[2]


def _copy_bench(tmp_path: Path) -> dict:
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return bench


def test_new_config_traffic_and_metric_are_found(tmp_path):
    bench = _copy_bench(tmp_path)
    cfg = json.loads((ROOT / "bench/configs/ml1m.json").read_text())
    cfg.update(name="mini", n_users=300)
    (tmp_path / "bench/configs/mini.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/pulse.json").write_text(json.dumps(
        {"loop": "open", "users": "uniform", "period_s": 1.0,
         "phases": [{"seconds": 0.25, "rate_of_knee": 2.0},
                    {"seconds": 0.75, "rate_of_knee": 0.0}]}))
    (tmp_path / "bench/metrics/fill_twice.serve.py").write_text(
        "def read(ctx):\n    return 2 * ctx['x']\n")
    bench["configs"].append({"name": "mini", "source": "x",
                             "file": "bench/configs/mini.json",
                             "reduced": ["n_users"], "why": "x"})
    bench["workloads"].append({"name": "mini-pulse", "config": "mini",
                               "traffic": "pulse", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "fill_twice.serve", "unit": "%",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "serving",
                               "moves": "recommend_p50_ms",
                               "workloads": ["mini-pulse"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = run.load_cell("mini-pulse", root=tmp_path)
    assert cell["config"]["n_users"] == 300
    assert cell["traffic"]["phases"][0]["rate_of_knee"] == 2.0
    assert [m["name"] for m in cell["per_layer"]] == ["fill_twice.serve"]
    assert {m["name"] for m in cell["end_to_end"]} == {"setup_s"}
    assert run.metric_reader("fill_twice.serve", root=tmp_path)(
        {"x": 21}) == 42
    due, users = drive.schedule(cell["traffic"], 100.0, 3.0, 5, 300)
    assert len(due) == 3 * 50 and (users < 300).all()


def test_schedule_count_is_fixed_by_the_mix():
    for name in ("steady", "burst"):
        mix = json.loads((ROOT / f"bench/traffic/{name}.json").read_text())
        counts = {len(drive.schedule(mix, 200.0, 30.0, s, 6040)[0])
                  for s in (1, 2, 2**31 + 9)}
        assert len(counts) == 1
        due, _ = drive.schedule(mix, 200.0, 30.0, 1, 6040)
        assert (due >= 0).all() and (due < 30.0).all()
    steady = json.loads((ROOT / "bench/traffic/steady.json").read_text())
    assert len(drive.schedule(steady, 200.0, 30.0, 1, 6040)[0]) == 4800


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        for name in m.get("workloads", sorted(cells)):
            cell = run.load_cell(name)
            assert m["moves"] in {e["name"] for e in cell["end_to_end"]}, \
                (m["name"], name)
    for name in cells:
        cell = run.load_cell(name)
        assert "setup_s" in {e["name"] for e in cell["end_to_end"]}
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]


def test_tail_reader_reads_the_request_latencies():
    read = run.metric_reader("recommend_p99_ms.serve")
    lat = np.arange(1, 1001) / 1e3          # 1 … 1000 ms
    assert abs(read({"latency_s": lat}) - np.percentile(lat, 99) * 1e3) \
        < 1e-9
    assert read({"latency_s": None}) is None
    assert read({}) is None
