"""End-to-end smoke: `traceml-tpu run` on a tiny flax script
(reference: tests/runtime/test_final_summary_smoke.py:26-60 —
subprocess launch through executor + aggregator, asserting the
final_summary.json artifact and the injected INPUT_BOUND verdict).
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

TRAIN_SCRIPT = """
import time
import numpy as np
import jax, jax.numpy as jnp
import traceml_tpu

def step_fn(w, x):
    return w - 0.01 * jax.grad(lambda w, x: jnp.sum((x @ w) ** 2))(w, x)

step = traceml_tpu.wrap_step_fn(step_fn)

def batches():
    rng = np.random.default_rng(0)
    for i in range(60):
        time.sleep(0.02)   # injected slow input
        yield rng.normal(size=(16, 32)).astype(np.float32)

w = jnp.ones((32, 32)) * 0.01
for x in traceml_tpu.wrap_dataloader(batches()):
    with traceml_tpu.trace_step():
        x = jax.device_put(x)
        w = step(w, x)
print("done", float(w.sum()))
"""


def test_run_summary_mode_input_bound(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(TRAIN_SCRIPT)
    logs = tmp_path / "logs"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO)
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "traceml_tpu",
            "run",
            "--mode",
            "summary",
            "--logs-dir",
            str(logs),
            "--run-name",
            "smoke",
            "--sampler-interval",
            "0.25",
            "--finalize-timeout",
            "30",
            str(script),
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=180,
        cwd=str(tmp_path),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    # only directories are sessions — the baseline store file
    # (traceml_baselines.sqlite) also lives at the logs-dir top level
    sessions = [p for p in logs.iterdir() if p.is_dir()]
    assert len(sessions) == 1
    session = sessions[0]
    summary_path = session / "final_summary.json"
    assert summary_path.exists(), proc.stdout[-3000:]
    payload = json.loads(summary_path.read_text())
    assert payload["primary_diagnosis"]["kind"] == "INPUT_BOUND"
    assert payload["sections"]["step_time"]["status"] == "OK"
    assert payload["sections"]["step_time"]["global"]["n_steps"] >= 50
    # manifest lifecycle completed
    manifest = json.loads((session / "manifest.json").read_text())
    assert manifest["status"] == "completed"
    assert manifest["telemetry_status"] == "ok"
    # code manifest detected jax + device_put
    code = json.loads((session / "code_manifest.json").read_text())
    assert code["framework"] == "jax"
    # text + html artifacts exist, verdict printed to launcher stdout
    assert (session / "final_summary.txt").exists()
    assert (session / "final_summary.html").exists()
    assert "INPUT_BOUND" in proc.stdout


def test_run_disabled_passthrough(tmp_path):
    script = tmp_path / "noop.py"
    script.write_text("print('hello untraced')\n")
    logs = tmp_path / "logs"
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO)
    proc = subprocess.run(
        [
            sys.executable, "-m", "traceml_tpu", "run",
            "--disable-traceml", "--logs-dir", str(logs), str(script),
        ],
        env=env, capture_output=True, text=True, timeout=90, cwd=str(tmp_path),
    )
    assert proc.returncode == 0
    assert "hello untraced" in proc.stdout


def test_view_command(tmp_path):
    # create a summary via the pipeline-level generator, then `view` it
    from traceml_tpu.reporting.final import generate_summary
    from traceml_tpu.runtime.settings import TraceMLSettings

    settings = TraceMLSettings(session_id="v", logs_dir=tmp_path)
    generate_summary(tmp_path / "missing.sqlite", tmp_path, settings)
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "traceml_tpu", "view", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0
    assert "VERDICT" in proc.stdout
