"""Finds what belongs to a cell, a configuration, a family, a traffic
mix or a metric by its name, so that adding one means adding files only.

* ``BENCHMARK.json`` (the checkout's root): the cell's configuration,
  traffic and chips, and each metric's unit and cells;
* ``configs/<config>.json``: the model's published sizes as run, and the
  ``family`` of architectures it belongs to;
* ``families/<family>/program.py``: ``build(spec, key, mesh) ->
  (train_step, state)``, the program under test through its public
  entry points;
* ``families/<family>/reference.py``: the family's plain float32 model
  (``param_shapes``, ``pack``, ``unpack``, ``forward``, ``fsdp_dim``,
  ``train_step_flops``), importing nothing of the program;
* ``traffic/<traffic>.json``: batch, sequence, loop and cadences;
* ``workloads/<cell>.json``: the mesh and the limits of ``correct``;
* ``metrics/<metric>.py``: ``read(record) -> float | None``.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType, SimpleNamespace
from typing import Callable, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def _module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_bench(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def load_family(name: str, here: Path = HERE) -> SimpleNamespace:
    """The family's ``program`` and ``reference`` modules, by name; a
    name with no directory under ``families/`` is an error."""
    families = here / "families"
    known = sorted(p.name for p in families.iterdir() if (p / "program.py").is_file())
    if name not in known:
        raise KeyError(f"no family {name!r} under {families}; known: {known}")
    return SimpleNamespace(name=name, **{
        part: _module(families / name / f"{part}.py", f"bench_family_{name}_{part}")
        for part in ("program", "reference")
    })


def load_cell(name: str, root: Path = ROOT, here: Path = HERE) -> dict:
    """Everything a run of cell ``name`` needs, merged into one dict."""
    bench = load_bench(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {known}")
    model = _json(here / "configs" / f"{entry['config']}.json")
    return {
        **entry,
        "family": load_family(model.get("family"), here),
        "model": model,
        "load": _json(here / "traffic" / f"{entry['traffic']}.json"),
        "cell": _json(here / "workloads" / f"{name}.json"),
    }


def metrics_for(cell: str, trace: bool, root: Path = ROOT) -> List[dict]:
    """The metric entries a run of ``cell`` reports: per-layer ones when
    traced, end-to-end ones otherwise, each filtered by its ``workloads``."""
    bench = load_bench(root)
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str, here: Path = HERE) -> Callable[[dict], object]:
    return _module(here / "metrics" / f"{name}.py", f"bench_metric_{name}").read


def read_metrics(entries: List[dict], record: dict, here: Path = HERE) -> Dict:
    """``{name: {"value", "unit"}}`` for each metric whose reader found
    something; a reader that returns None is left out."""
    out = {}
    for m in entries:
        value = reader(m["name"], here)(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
