"""Failure-detection E2E: the aggregator dying mid-run must DEGRADE the
run (training completes, manifest says so), never fail it
(reference contract: commands.py:549-564 + fail-open TCPClient)."""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

SCRIPT = """
import time
import numpy as np
import jax, jax.numpy as jnp
import traceml_tpu

def step_fn(w, x):
    return w - 0.01 * jax.grad(lambda w, x: jnp.sum((x @ w) ** 2))(w, x)

step = traceml_tpu.wrap_step_fn(step_fn)
w = jnp.ones((16, 16))
rng = np.random.default_rng(0)
for i in range(40):
    with traceml_tpu.trace_step():
        x = jax.device_put(rng.normal(size=(4, 16)).astype(np.float32))
        w = step(w, x)
    time.sleep(0.05)
print("training finished fine")
"""


def test_aggregator_death_degrades_not_fails(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(SCRIPT)
    logs = tmp_path / "logs"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO)
    # this test pins the restart-budget-exhausted contract (degrade, not
    # fail); the restart path itself is covered by test_chaos_e2e.py
    env["TRACEML_AGG_MAX_RESTARTS"] = "0"
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "traceml_tpu", "run",
            "--mode", "summary", "--logs-dir", str(logs),
            "--run-name", "degrade", "--sampler-interval", "0.25",
            "--finalize-timeout", "20", str(script),
        ],
        env=env, cwd=str(tmp_path),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    # wait for the aggregator ready file, then murder the aggregator
    session = None
    deadline = time.monotonic() + 60
    agg_pid = None
    while time.monotonic() < deadline and agg_pid is None:
        sessions = list(logs.glob("degrade*/aggregator_ready.json"))
        if sessions:
            session = sessions[0].parent
            agg_pid = json.loads(sessions[0].read_text())["pid"]
        time.sleep(0.2)
    assert agg_pid, "aggregator never became ready"
    time.sleep(1.5)  # let some telemetry flow first
    os.kill(agg_pid, signal.SIGKILL)

    out, _ = proc.communicate(timeout=240)
    assert proc.returncode == 0, out[-3000:]
    assert "training finished fine" in out
    manifest = json.loads((session / "manifest.json").read_text())
    assert manifest["status"] == "completed"
    assert manifest["telemetry_status"] == "degraded"
