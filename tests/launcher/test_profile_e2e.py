"""`traceml-tpu profile` against a LIVE run: the operator-side CLI
writes the control-file request; the in-job service brackets real steps
with the XLA profiler and answers with a trace directory.
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

SCRIPT = """
from traceml_tpu.dev.demo.scenarios import run_scenario
run_scenario('input_bound', steps=300)
"""


def test_profile_cli_against_live_run(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(SCRIPT)
    logs = tmp_path / "logs"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO)
    job = subprocess.Popen(
        [
            sys.executable, "-m", "traceml_tpu", "run",
            "--mode", "summary", "--logs-dir", str(logs),
            "--run-name", "proftest", "--finalize-timeout", "45",
            str(script),
        ],
        env=env, cwd=str(tmp_path),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        # wait for the session dir to exist (launcher writes manifests
        # before the job steps)
        deadline = time.monotonic() + 60
        session = None
        while time.monotonic() < deadline and session is None:
            if logs.is_dir():
                dirs = [d for d in logs.iterdir() if d.is_dir()]
                if dirs:
                    session = dirs[0]
                    break
            time.sleep(0.25)
        assert session is not None, "session dir never appeared"

        proc = subprocess.run(
            [
                sys.executable, "-m", "traceml_tpu", "profile",
                str(session), "--steps", "3", "--timeout", "120",
            ],
            env=env, capture_output=True, text=True, timeout=150,
        )
        assert proc.returncode == 0, (proc.stdout, proc.stderr)
        assert "trace captured" in proc.stdout
        resp = json.loads(
            (session / "control" / "profile_response.json").read_text()
        )
        assert resp["ok"]
        trace_root = Path(resp["trace_dir"])
        files = [p for p in trace_root.rglob("*") if p.is_file()]
        assert files, "no trace artifacts on disk"
    finally:
        job.terminate()
        try:
            job.wait(timeout=60)
        except subprocess.TimeoutExpired:
            job.kill()
            job.wait(timeout=15)
