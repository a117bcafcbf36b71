"""CLI attach/read commands over a finished session: watch (exits when
the manifest says completed), view (text + json), inspect (decode
msgpack backups) — previously untested surfaces."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def _finished_session(tmp_path):
    """Build a real finished session: DB + summary + completed manifest."""
    from traceml_tpu.aggregator.sqlite_writer import SQLiteWriter
    from traceml_tpu.launcher import manifest as mf
    from traceml_tpu.reporting.final import generate_summary
    from traceml_tpu.runtime.settings import TraceMLSettings
    from traceml_tpu.telemetry.envelope import (
        SenderIdentity,
        build_telemetry_envelope,
    )
    from traceml_tpu.utils import timing as T

    session = tmp_path / "sess"
    session.mkdir()
    w = SQLiteWriter(session / "telemetry.sqlite")
    w.start()
    ident = SenderIdentity(session_id="sess", global_rank=0)
    rows = [
        {"step": s, "timestamp": float(s), "clock": "device",
         "events": {
             T.STEP_TIME: {"cpu_ms": 50.0, "device_ms": 50.0, "count": 1},
             T.COMPUTE_TIME: {"cpu_ms": 1.0, "device_ms": 45.0, "count": 1},
         }}
        for s in range(1, 40)
    ]
    w.ingest(build_telemetry_envelope("step_time", {"step_time": rows}, ident))
    w.force_flush()
    w.finalize()
    settings = TraceMLSettings(session_id="sess", logs_dir=tmp_path)
    generate_summary(session / "telemetry.sqlite", session, settings)
    mf.write_run_manifest(
        session, session_id="sess", script="x.py", mode="summary",
        world_size=1, status=mf.STATUS_COMPLETED,
    )
    return session


def _cli(args, timeout=60):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO)
    return subprocess.run(
        [sys.executable, "-m", "traceml_tpu", *args],
        capture_output=True, text=True, timeout=timeout, env=env,
    )


def test_watch_exits_on_completed_session(tmp_path):
    session = _finished_session(tmp_path)
    proc = _cli(["watch", str(session), "--interval", "0.2"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "VERDICT" in proc.stdout  # the final summary is printed


def test_watch_missing_session(tmp_path):
    proc = _cli(["watch", str(tmp_path / "nope")])
    assert proc.returncode == 1


def test_view_text_and_json(tmp_path):
    session = _finished_session(tmp_path)
    text = _cli(["view", str(session)])
    assert text.returncode == 0
    assert "VERDICT" in text.stdout
    as_json = _cli(["view", str(session), "--format", "json"])
    assert as_json.returncode == 0
    payload = json.loads(as_json.stdout)
    assert payload["schema"].startswith("traceml-tpu/")
    assert payload["sections"]["step_time"]["status"] == "OK"


def test_inspect_decodes_backups(tmp_path):
    from traceml_tpu.database import Database, DatabaseWriter

    db = Database()
    w = DatabaseWriter("step_time", db, tmp_path / "data", flush_every=1)
    db.add_records("steps", [{"step": i, "ms": 10.0 * i} for i in range(5)])
    assert w.flush(force=True) == 5
    proc = _cli(["inspect", str(tmp_path / "data"), "--limit", "3"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "steps" in proc.stdout
    assert "step" in proc.stdout


def test_inspect_domain_filter_and_overlap_column(tmp_path):
    """--domain keeps only the named table and collectives rows gain a
    derived overlap_efficiency column (zero-duration rows read 1.0)."""
    from traceml_tpu.database import Database, DatabaseWriter

    db = Database()
    w = DatabaseWriter("mixed", db, tmp_path / "data", flush_every=1)
    db.add_records("steps", [{"step": i, "ms": 10.0 * i} for i in range(3)])
    db.add_records(
        "collectives",
        [
            {"step": 1, "op": "all_reduce", "dtype": "float32",
             "count": 2, "bytes": 4096, "group_size": 8,
             "duration_ms": 4.0, "exposed_ms": 1.0},
            {"step": 2, "op": "all_gather", "dtype": "bfloat16",
             "count": 1, "bytes": 0, "group_size": 8,
             "duration_ms": 0.0, "exposed_ms": 0.0},
        ],
    )
    assert w.flush(force=True) == 5
    proc = _cli(
        ["inspect", str(tmp_path / "data"), "--domain", "collectives"]
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [
        json.loads(line)
        for line in proc.stdout.splitlines()
        if line.startswith("{")
    ]
    # only the collectives table's rows survive the filter (legacy
    # per-table backups are matched by file stem)
    assert rows and all("op" in r for r in rows)
    assert "steps.msgpack" not in proc.stdout
    by_step = {r["step"]: r for r in rows}
    assert by_step[1]["overlap_efficiency"] == 0.75  # 1 − 1/4
    assert by_step[2]["overlap_efficiency"] == 1.0   # zero comm ≠ NaN
    # unknown domain → helpful non-zero exit
    miss = _cli(["inspect", str(tmp_path / "data"), "--domain", "nope"])
    assert miss.returncode == 1
    assert "no rows for domain" in miss.stdout
