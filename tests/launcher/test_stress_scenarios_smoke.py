"""Smoke the dev stress scenarios (ViT/BERT-style) — real subprocess,
few steps; these scripts are the reference-parity stress harness and
were previously never executed in CI."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]


def _run(module, *args, timeout=240):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO)
    return subprocess.run(
        [sys.executable, "-m", module, *args],
        capture_output=True, text=True, timeout=timeout, env=env,
    )


@pytest.mark.parametrize("module", [
    "traceml_tpu.dev.scenarios.vit_stress",
    "traceml_tpu.dev.scenarios.bert_stress",
])
def test_stress_scenario_runs(module):
    proc = _run(module, "6", "none")
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_vit_stress_with_fault():
    proc = _run("traceml_tpu.dev.scenarios.vit_stress", "6", "input_bound")
    assert proc.returncode == 0, proc.stderr[-2000:]
