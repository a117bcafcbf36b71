"""Chaos E2E: deterministic fault injection (``TRACEML_FAULT_PLAN``,
dev/chaos.py) through the REAL pipeline — launcher, rank executors,
aggregator over TCP.

The two pillars of the fault-tolerance contract
(docs/developer_guide/fault-tolerance.md):

* aggregator SIGKILL mid-run → supervised restart on the pinned port,
  rank-side spool replay, writer-side seq dedup: the final DB holds the
  SAME per-rank step coverage as a fault-free run — no silent loss, no
  duplicates.
* rank SIGKILL mid-run → the world notices: RANK_LOST verdict in the
  final report's liveness section, a data-gap annotation, and the
  settle-end warning naming the never-finished rank.
"""

import json
import os
import sqlite3
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

# paced training loop: slow enough that an early-run kill leaves a long
# post-restart tail (the replay + live-resume window the test is about)
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
for i in range({steps}):
    with traceml_tpu.trace_step():
        x = jax.device_put(rng.normal(size=(4, 16)).astype(np.float32))
        w = step(w, x)
    time.sleep(0.04)
print("training finished fine")
"""


def _run(tmp_path, name, steps, nprocs=2, extra_env=None, check=True,
         finalize_timeout=45):
    script = tmp_path / f"{name}.py"
    script.write_text(SCRIPT.format(steps=steps))
    logs = tmp_path / f"logs_{name}"
    env = dict(os.environ)
    env.pop("TRACEML_FAULT_PLAN", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO)
    env.update(extra_env or {})
    proc = subprocess.run(
        [
            sys.executable, "-m", "traceml_tpu", "run",
            "--mode", "summary", "--logs-dir", str(logs),
            "--run-name", name, "--sampler-interval", "0.25",
            "--finalize-timeout", str(finalize_timeout),
            "--nprocs", str(nprocs), str(script),
        ],
        env=env, capture_output=True, text=True, timeout=300, cwd=str(tmp_path),
    )
    if check:
        assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-2000:]
    session = next(p for p in logs.iterdir() if p.is_dir())
    return session, proc


def _step_coverage(session):
    """{(rank, step), ...} plus the raw row count (rows > |set| means
    a replayed envelope double-inserted — the dedup failed)."""
    conn = sqlite3.connect(session / "telemetry.sqlite")
    try:
        rows = conn.execute(
            "SELECT global_rank, step FROM step_time_samples"
        ).fetchall()
    finally:
        conn.close()
    return {(r, s) for r, s in rows}, len(rows)


def _kill9_restart_case(tmp_path, transport):
    """Shared body: fault-free baseline vs aggregator-kill9 run over the
    given transport must land IDENTICAL (rank, step) coverage with zero
    duplicate rows."""
    env = {"TRACEML_TRANSPORT": transport}
    baseline_session, _ = _run(
        tmp_path, f"baseline_{transport}", steps=60, extra_env=env
    )
    base_cov, base_rows = _step_coverage(baseline_session)
    assert base_rows == len(base_cov)  # sanity: fault-free has no dupes

    plan = json.dumps(
        [{"point": "aggregator.ingest", "action": "kill9", "after": 40}]
    )
    chaos_session, proc = _run(
        tmp_path, f"aggkill_{transport}", steps=60,
        extra_env=dict(env, TRACEML_FAULT_PLAN=plan),
    )
    manifest = json.loads((chaos_session / "manifest.json").read_text())
    assert manifest["status"] == "completed"
    assert manifest["telemetry_status"] == "restarted", manifest
    assert manifest["aggregator_restarts"] == 1
    assert "restarting" in proc.stdout, proc.stdout[-2000:]

    cov, rows = _step_coverage(chaos_session)
    assert rows == len(cov), f"{rows - len(cov)} duplicate (rank, step) rows"
    # same workload, same coverage: everything in flight at the kill was
    # spooled rank-side and replayed into the restarted incarnation
    assert cov == base_cov, (
        f"missing={sorted(base_cov - cov)[:10]} extra={sorted(cov - base_cov)[:10]}"
    )
    # the report survived the crash too
    summary = json.loads((chaos_session / "final_summary.json").read_text())
    assert sorted(summary["meta"]["topology"]["ranks_seen"]) == [0, 1]
    return chaos_session


def test_aggregator_kill9_restart_no_loss_no_duplicates(tmp_path):
    # pinned to tcp: the pre-transport-tier golden arm
    _kill9_restart_case(tmp_path, "tcp")


def test_aggregator_kill9_restart_over_shm_ring(tmp_path):
    """The r12 contract over the shm fast path: the restarted aggregator
    re-attaches the rings (consumer-generation flip → one failed send →
    spooled replay), and coverage stays exactly-once."""
    session = _kill9_restart_case(tmp_path, "shm")
    # prove the run actually rode the ring, not a silent tcp fallback
    stats = json.loads((session / "ingest_stats.json").read_text())
    transports = stats["transports"]
    assert transports["frames_by_kind"].get("shm", 0) > 0, transports
    assert all(
        h["transport"] == "shm" for h in transports["ranks"].values()
    ), transports["ranks"]


def test_rank_sigkill_reported_lost_with_data_gap(tmp_path):
    plan = json.dumps(
        [{"point": "rank.tick", "action": "kill9", "after": 8, "rank": 1}]
    )
    session, proc = _run(
        tmp_path, "rankkill", steps=400, check=False, finalize_timeout=8,
        extra_env={
            "TRACEML_FAULT_PLAN": plan,
            # tightened so the 8s settle window crosses the LOST line
            "TRACEML_HEARTBEAT_INTERVAL_SEC": "0.5",
            "TRACEML_LIVENESS_STALE_SEC": "1",
            "TRACEML_LIVENESS_LOST_SEC": "3",
        },
    )
    assert proc.returncode != 0  # a SIGKILLed rank is a failed run
    manifest = json.loads((session / "manifest.json").read_text())
    assert manifest["status"] == "failed"

    # the final report still exists and names the dead rank
    summary = json.loads((session / "final_summary.json").read_text())
    sec = summary["sections"]["liveness"]
    assert sec["diagnosis"]["kind"] == "RANK_LOST", sec["diagnosis"]
    assert sec["diagnosis"]["severity"] == "critical"
    assert 1 in sec["diagnosis"]["ranks"], sec["diagnosis"]
    # telemetry from rank 1 is trustworthy only up to the kill
    assert "1" in sec.get("data_gaps", {}), sec.get("data_gaps")
    # settle-end bookkeeping: rank 1 never sent its finish marker
    assert 1 in sec["unfinished_ranks"]
    assert sec["unfinished_rank_states"]["1"] == "lost"
    # a dead world member outranks every perf finding
    assert summary["primary_diagnosis"]["kind"] in (
        "RANK_LOST", "LIKELY_PREEMPTED",
    ), summary["primary_diagnosis"]
