"""Crash-path parity (VERDICT r2 item 5; reference
src/traceml_ai/launcher/process.py:30-300): a child that dies before —
or in a way that bypasses — the in-process crash hooks must still leave
a diagnosable artifact.  The launcher keeps a 64 KiB stderr ring per
supervised child and flushes it to ``rank_<r>/crash_stderr.log`` on
abnormal exit; SIGTERM to the launcher tears down the tree like Ctrl-C.
"""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

ABORT_SCRIPT = """
import os, sys
sys.stderr.write("EARLY-NOISE\\n" * 4000)      # ~48 KiB of prelude
sys.stderr.write("BOOM-MARKER before abort\\n")
sys.stderr.flush()
os.abort()  # SIGABRT: bypasses every Python-level crash hook
"""

HANG_SCRIPT = """
import sys, time
sys.stderr.write("rank started\\n"); sys.stderr.flush()
time.sleep(120)
"""


def _launch(tmp_path, script_text, name, wait=True, extra=()):
    script = tmp_path / f"{name}.py"
    script.write_text(script_text)
    logs = tmp_path / "logs"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO)
    argv = [
        sys.executable, "-m", "traceml_tpu", "run",
        "--mode", "summary", "--logs-dir", str(logs),
        "--run-name", name, "--sampler-interval", "0.25",
        "--finalize-timeout", "20", *extra, str(script),
    ]
    if wait:
        proc = subprocess.run(
            argv, env=env, capture_output=True, text=True,
            timeout=180, cwd=str(tmp_path),
        )
        session = next(p for p in logs.iterdir() if p.is_dir())
        return proc, session
    return subprocess.Popen(
        argv, env=env, cwd=str(tmp_path),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    ), logs


def test_sigabrt_child_leaves_crash_stderr(tmp_path):
    proc, session = _launch(tmp_path, ABORT_SCRIPT, "crash")
    assert proc.returncode not in (0, None)
    log = session / "rank_0" / "crash_stderr.log"
    assert log.exists(), sorted(p.name for p in session.rglob("*"))[:20]
    text = log.read_text(errors="replace")
    assert "SIGABRT" in text, text[:300]
    # the ring keeps the NEWEST bytes: the marker written right before
    # death survives even after ~48 KiB of earlier noise
    assert "BOOM-MARKER before abort" in text
    assert log.stat().st_size <= 64 * 1024 + 512  # ring + header
    # the manifest points at the artifact
    manifest = json.loads((session / "manifest.json").read_text())
    assert any("crash_stderr.log" in p for p in manifest.get("crash_logs", []))


def test_healthy_run_leaves_no_crash_log(tmp_path):
    proc, session = _launch(
        tmp_path,
        "import traceml_tpu\n"
        "with traceml_tpu.trace_step():\n"
        "    pass\n"
        "print('ok')\n",
        "healthy",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert not list(session.rglob("crash_stderr.log"))


def test_sigterm_to_launcher_tears_down_tree(tmp_path):
    proc, logs = _launch(tmp_path, HANG_SCRIPT, "hang", wait=False)
    # wait until the rank process is actually up (session dir + manifest)
    deadline = time.monotonic() + 60
    session = None
    while time.monotonic() < deadline:
        sessions = (
            [p for p in logs.iterdir() if p.is_dir()] if logs.exists() else []
        )
        if sessions:
            session = sessions[0]
            manifest = json.loads((session / "manifest.json").read_text())
            if manifest.get("status") == "running":
                break
        time.sleep(0.2)
    assert session is not None, "launcher never reached running state"
    time.sleep(1.0)
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=90)
    assert proc.returncode == 130, (proc.returncode, out[-2000:])
    manifest = json.loads((session / "manifest.json").read_text())
    assert manifest.get("status") == "failed"


def test_systemexit_message_reaches_crash_log(tmp_path):
    """SystemExit("message") must die loudly: the interpreter prints the
    message to stderr before exiting 1, and the executor must too — a
    swallowed message left an empty crash_stderr.log (found in r4
    verification when a demo scenario name was misspelled)."""
    proc, session = _launch(
        tmp_path,
        'raise SystemExit("unknown scenario \'slow_input\'")\n',
        "sysexit",
    )
    crash = session / "rank_0" / "crash_stderr.log"
    assert crash.exists(), "abnormal exit must leave a crash artifact"
    text = crash.read_text()
    assert "unknown scenario" in text, (
        f"SystemExit message swallowed; crash log was:\n{text}"
    )
