"""Finds what belongs to a cell, a configuration, a traffic mix or a
metric by its name, so that adding one means adding files only.

* ``BENCHMARK.json`` (the checkout's root): the cell's configuration,
  traffic and chips, and each metric's unit and cells;
* ``configs/<config>.json``: the model's published sizes as run;
* ``traffic/<traffic>.json``: batch, sequence, loop and cadences;
* ``workloads/<cell>.json``: the mesh and the limits of ``correct``;
* ``metrics/<metric>.py``: ``read(record) -> float | None``.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_bench(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def load_cell(name: str, root: Path = ROOT, here: Path = HERE) -> dict:
    """Everything a run of cell ``name`` needs, merged into one dict."""
    bench = load_bench(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {known}")
    return {
        **entry,
        "model": _json(here / "configs" / f"{entry['config']}.json"),
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
    path = here / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_metrics(entries: List[dict], record: dict, here: Path = HERE) -> Dict:
    """``{name: {"value", "unit"}}`` for each metric whose reader found
    something; a reader that returns None is left out."""
    out = {}
    for m in entries:
        value = reader(m["name"], here)(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
