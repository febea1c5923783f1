"""BENCHMARK.json names only what the harness can find, within the
benchmark's own limits on names, units and sizes."""
import json
import re
from pathlib import Path

import pytest

from bench import serve

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_is_well_formed_and_has_a_reader(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert callable(serve.find_reader(metric["name"]))
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells


def test_every_cell_reports_what_its_layers_move():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for cell in BENCH["workloads"]:
        name = cell["name"]
        reported = {m["name"] for m in serve.metrics_for(BENCH, name,
                                                         "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        layers = serve.metrics_for(BENCH, name, "per_layer")
        assert layers
        for m in layers:
            assert m["moves"] in reported, (name, m["name"])


def test_cells_name_existing_configurations_and_mixes():
    configs = {c["name"]: c for c in BENCH["configs"]}
    pairs = set()
    for cell in BENCH["workloads"]:
        assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
        assert cell["config"] in configs
        assert (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").exists()
        assert (cell["config"], cell["traffic"]) not in pairs
        pairs.add((cell["config"], cell["traffic"]))
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).exists()
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert set(c["reduced"]) <= set(json.loads((ROOT / c["file"]).read_text()))


def test_layer_names_are_consistent():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in l and 0 < len(l) <= 200 for l in layers)
