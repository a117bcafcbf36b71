"""A configuration, a traffic mix, a cell and a metric added as new
files are found by name; nothing that is there is edited."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import discovery

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture()
def checkout(tmp_path):
    """A copy of the benchmark with one more of each, added as files."""
    here = tmp_path / "benchmark"
    shutil.copytree(ROOT / "benchmark", here)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    (here / "configs" / "new-model.json").write_text(json.dumps({"hidden_size": 7}))
    (here / "traffic" / "new-load.json").write_text(json.dumps({"batch": 3, "seq": 9}))
    (here / "workloads" / "new-model.new-load.json").write_text(
        json.dumps({"mesh": None, "limits": {"loss_gap": 0.5}})
    )
    (here / "metrics" / "new.metric_ms.py").write_text(
        "def read(rec):\n    return rec.get('new') and 2 * rec['new']\n"
    )
    bench["workloads"].append({"name": "new-model.new-load", "config": "new-model",
                               "traffic": "new-load", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "new.metric_ms", "unit": "ms", "better": "lower",
                               "source": "host_clock", "layer": "new", "moves": "setup_s",
                               "workloads": ["new-model.new-load"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    yield tmp_path, here
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_new_cell_found_by_name(checkout):
    root, here = checkout
    spec = discovery.load_cell("new-model.new-load", root=root, here=here)
    assert spec["model"] == {"hidden_size": 7}
    assert spec["load"] == {"batch": 3, "seq": 9}
    assert spec["cell"]["limits"] == {"loss_gap": 0.5}


def test_new_metric_read_in_its_cells_only(checkout):
    root, here = checkout
    entries = discovery.metrics_for("new-model.new-load", trace=True, root=root)
    assert "new.metric_ms" in [m["name"] for m in entries]
    got = discovery.read_metrics(
        [m for m in entries if m["name"] == "new.metric_ms"], {"new": 21}, here=here
    )
    assert got == {"new.metric_ms": {"value": 42, "unit": "ms"}}
    others = discovery.metrics_for("ouro-2.6b.short-step", trace=True, root=root)
    assert "new.metric_ms" not in [m["name"] for m in others]


def test_reader_that_finds_nothing_is_left_out(checkout):
    root, here = checkout
    entries = [{"name": "new.metric_ms", "unit": "ms"}]
    assert discovery.read_metrics(entries, {}, here=here) == {}


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        discovery.load_cell("no-such-cell")
