"""BENCHMARK.json against the benchmark's contract, and each name in it
against the file that it names."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert (ROOT / BENCH["command"][1]).is_file()
    assert all((ROOT / p).is_dir() for p in BENCH["paths"])


@pytest.mark.parametrize("group", sorted(KEYS))
def test_entries_have_just_their_keys(group):
    for entry in BENCH[group]:
        extra = set(entry) - KEYS[group] - ({"workloads"} if group in ("end_to_end", "per_layer") else set())
        assert set(entry) >= KEYS[group] and not extra, entry
        assert NAME.match(entry["name"]), entry["name"]


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert (ROOT / "benchmark" / "metrics" / f"{metric['name']}.py").is_file()
    if "bound" in metric:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["moves"] in [m["name"] for m in BENCH["end_to_end"]]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files(cell):
    bench = ROOT / "benchmark"
    assert cell["chips"] in (1, 4)
    assert cell["config"] in [c["name"] for c in BENCH["configs"]]
    assert (bench / "traffic" / f"{cell['traffic']}.json").is_file()
    limits = json.loads((bench / "workloads" / f"{cell['name']}.json").read_text())["limits"]
    assert limits and set(limits) <= {"loss_gap", "grad_gap", "delta_gap"}
    assert all(0 < v < 1 for v in limits.values())


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_matches_entry(config):
    data = json.loads((ROOT / config["file"]).read_text())
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    assert config["file"] == f"benchmark/configs/{config['name']}.json"
