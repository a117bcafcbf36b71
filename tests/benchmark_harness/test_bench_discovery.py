"""A configuration, a family, a traffic mix, a cell and a metric added as
new files are found by name; nothing that is there is edited."""

import json
import shutil
import time
from pathlib import Path

import pytest

from benchmark import discovery, harness, reference

ROOT = Path(__file__).resolve().parents[2]
TOY = Path(__file__).resolve().parent / "toy_family"


@pytest.fixture()
def checkout(tmp_path):
    """A copy of the benchmark with one more of each, added as files: a
    second family (``toy_decoder``, tied embeddings) with its
    configuration, traffic and cell among them."""
    here = tmp_path / "benchmark"
    shutil.copytree(ROOT / "benchmark", here, ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    (here / "configs" / "new-model.json").write_text(
        json.dumps({"hidden_size": 7, "family": "decoder_lm"})
    )
    (here / "traffic" / "new-load.json").write_text(json.dumps({"batch": 3, "seq": 9}))
    (here / "workloads" / "new-model.new-load.json").write_text(
        json.dumps({"mesh": None, "limits": {"loss_gap": 0.5}})
    )
    (here / "metrics" / "new.metric_ms.py").write_text(
        "def read(rec):\n    return rec.get('new') and 2 * rec['new']\n"
    )
    for src in TOY.rglob("*"):
        if src.is_file() and "__pycache__" not in src.parts:
            dst = here / src.relative_to(TOY)
            assert not dst.exists(), dst
            dst.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(src, dst)
    bench["workloads"].append({"name": "new-model.new-load", "config": "new-model",
                               "traffic": "new-load", "chips": 1, "why": "test"})
    bench["configs"].append({"name": "toy", "source": "test", "file": "benchmark/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.tied", "config": "toy", "traffic": "toy",
                               "chips": 1, "why": "test"})
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
    assert spec["model"] == {"hidden_size": 7, "family": "decoder_lm"}
    assert spec["family"].name == "decoder_lm"
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


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "state_unchanged"])
def test_new_family_runs_from_new_files(checkout, monkeypatch, broken):
    """A cell of the toy family, all of it new files, runs on the CPU
    through the generic harness and reference: correct when sound, not
    when its step returns the state unchanged."""
    root, here = checkout
    spec = discovery.load_cell("toy.tied", root=root, here=here)
    fam = spec["family"]
    assert fam.name == "toy_decoder"
    assert reference.leaf_paths(fam.reference.param_shapes(spec["model"])) == [
        "embed/embedding", "mix/kernel", "norm/scale",
    ]
    if broken:
        real = fam.program.build

        def build(spec, key, mesh):
            step, state = real(spec, key, mesh)
            return (lambda state, tokens: (state, step(state, tokens)[1])), state

        monkeypatch.setattr(fam.program, "build", build)
    result, lines = harness.run(
        "toy.tied", 2**33 + 17, 1.0, False, time.monotonic(), require_tpu=False, spec=spec
    )
    assert result["correct"] is (not broken), lines
    assert result["attempted"] > 0


@pytest.mark.parametrize("name", ["no_such_family", "families", "", None])
def test_unknown_family_is_an_error(name):
    with pytest.raises(KeyError, match="known: .*decoder_lm"):
        discovery.load_family(name)


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        discovery.load_cell("no-such-cell")
