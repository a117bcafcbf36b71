"""Chip smoke: the traced flagship train step, end to end, on a TPU.

Runs ``python -m traceml_tpu run --mode summary`` (launcher, aggregator
process, one rank executor) on ``traceml_tpu/dev/chip_smoke_rank.py``,
then checks the run's artifacts, not the training script's exit code:
tracing is fail-open by design, so only the artifacts show whether it
worked.  It fails unless

* ``final_summary.json`` exists with a verdict and the run manifest's
  ``telemetry_status`` is not degraded;
* the rank's system manifest says platform ``tpu`` and names the kind;
* the step-time section is on the device clock for the traced steps;
* step memory came from the ``jax_memory_stats`` backend;
* a compile phase was recorded, in step 1 only (the same shapes and
  layouts every step: a later compile is a layout the step changed);
* every phase of the rank script ran and passed (train, Pallas kernel
  vs reference, async marker resolution; or, with ``--four-chips``, the
  sharded step vs a 1-device mesh and the ICI stat all-gather).

This process never imports JAX: the rank child owns the chip, and the
device line comes from the system manifest the rank recorded.  The
last line of stdout is ``{"ok": true, "device": {...}}``, printed only
on success.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded path, 4 chips
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import sqlite3
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent
RANK_SCRIPT = REPO / "traceml_tpu" / "dev" / "chip_smoke_rank.py"
MIN_TRACED_STEPS = 8
TIMEOUT_S = 1000  # the driver allows 1200 s for the whole smoke


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


def child_env() -> dict:
    """The launcher's env: the repo importable, and JAX's persistent
    compile cache where ``JAX_COMPILATION_CACHE_DIR`` says, else at one
    fixed path inside the checkout (the path is part of the key)."""
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(REPO / ".jax_cache"))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(REPO), env.get("PYTHONPATH")) if p
    )
    return env


def run_launcher(work: Path, rank_args: list, timeout: float) -> int:
    """One traced run through the real launcher; its output goes to our
    stderr so our stdout carries only the result line.  On timeout the
    whole process group is stopped."""
    cmd = [
        sys.executable, "-m", "traceml_tpu", "run", "--mode", "summary",
        "--logs-dir", str(work / "logs"), "--run-name", "chip_smoke",
        "--sampler-interval", "0.5", "--finalize-timeout", "120",
        str(RANK_SCRIPT), "--out", str(work / "rank_result.json"),
        *rank_args,
    ]
    proc = subprocess.Popen(
        cmd, env=child_env(), cwd=str(REPO), stdout=sys.stderr,
        start_new_session=True,
    )
    try:
        return proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        _log(f"launcher still running after {timeout:.0f} s; stopping it")
        for sig, grace in ((signal.SIGTERM, 30), (signal.SIGKILL, 10)):
            try:
                os.killpg(proc.pid, sig)
                return proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                continue
            except ProcessLookupError:
                break
        return proc.wait()


def _json(path: Path):
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError):
        return None


def _rows(db: Path, sql: str) -> list:
    try:
        conn = sqlite3.connect(f"file:{db}?mode=ro", uri=True)
        try:
            return conn.execute(sql).fetchall()
        finally:
            conn.close()
    except sqlite3.Error:
        return []


def read_session(work: Path) -> dict:
    """Everything the checks read, from the run's artifacts."""
    sessions = [p for p in (work / "logs").glob("*") if p.is_dir()]
    session = sessions[0] if len(sessions) == 1 else None
    art: dict = {"session": session, "rank": _json(work / "rank_result.json")}
    if session is None:
        return art
    art["manifest"] = _json(session / "manifest.json")
    art["summary"] = _json(session / "final_summary.json")
    art["system"] = _json(session / "system_manifest.json")
    db = session / "telemetry.sqlite"
    art["memory_backends"] = sorted(
        r[0] for r in _rows(db, "SELECT DISTINCT backend FROM step_memory_samples")
    )
    art["steps"] = []
    for step, clock, events in _rows(
        db, "SELECT step, clock, events_json FROM step_time_samples"
    ):
        try:
            art["steps"].append((step, clock, json.loads(events)))
        except (TypeError, ValueError):
            continue
    art["mesh_axes"] = [
        json.loads(r[0]) for r in _rows(db, "SELECT axes_json FROM mesh_topology")
    ]
    return art


def check(art: dict, platform: str, four_chips: bool) -> list:
    """Problems found in the artifacts; an empty list means pass.
    ``platform`` is what the devices must be: ``tpu`` for the smoke
    itself (the tests drive the same checks on ``cpu``)."""
    problems = []
    if art.get("session") is None:
        return ["no single session directory under logs/"]
    manifest = art.get("manifest") or {}
    if manifest.get("telemetry_status", "missing") in ("degraded", "missing"):
        problems.append(
            f"telemetry_status={manifest.get('telemetry_status', 'missing')}"
        )
    summary = art.get("summary") or {}
    if not (summary.get("primary_diagnosis") or {}).get("kind"):
        problems.append("final_summary.json missing or without a verdict")

    system = art.get("system") or {}
    devices = system.get("devices") or []
    n_devices = 4 if four_chips else 1
    if system.get("platform") != platform:
        problems.append(f"system manifest platform={system.get('platform')!r}")
    if not devices or not devices[0].get("kind"):
        problems.append("system manifest names no device kind")
    if four_chips:
        if len(devices) != n_devices:
            problems.append(f"system manifest lists {len(devices)} devices")
        coords = {tuple(d.get("coords") or ()) for d in devices}
        if platform == "tpu" and len(coords) != n_devices:
            problems.append(f"device coords not distinct: {sorted(coords)}")
        if not any(
            math.prod(a["size"] for a in axes) == n_devices
            for axes in art.get("mesh_axes", [])
        ):
            problems.append(f"no {n_devices}-device mesh topology recorded")

    st = (summary.get("sections") or {}).get("step_time") or {}
    if (st.get("global") or {}).get("clock") != "device":
        problems.append("step-time section is not on the device clock")
    timed = [
        s for s, clock, ev in art.get("steps", [])
        if clock == "device"
        and (ev.get("_traceml_internal:step_time") or {}).get("device_ms")
    ]
    if len(timed) < MIN_TRACED_STEPS:
        problems.append(f"{len(timed)} steps carry device-clock timing")
    compiled = sorted(
        s for s, _, ev in art.get("steps", [])
        if "_traceml_internal:compile_time" in ev
    )
    if not compiled:
        problems.append("no compile phase recorded")
    if any(s != 1 for s in compiled):
        problems.append(f"compiles outside step 1, at steps {compiled}")
    backends = art.get("memory_backends") or []
    if not backends or (platform == "tpu" and backends != ["jax_memory_stats"]):
        problems.append(f"step memory backends {backends}")

    rank = art.get("rank")
    expected = ("sharded",) if four_chips else ("train", "kernel", "marker")
    if rank is None:
        problems.append("rank script wrote no result")
    else:
        if rank.get("device_count") != n_devices and platform == "tpu":
            problems.append(f"rank saw {rank.get('device_count')} devices")
        for name in expected:
            phase = rank["phases"].get(name)
            if not phase:
                problems.append(f"phase {name} yielded nothing")
            elif phase.get("ok") is not True:
                problems.append(f"phase {name} failed: {json.dumps(phase)}")
    return problems


def report(art: dict, four_chips: bool) -> dict:
    """The numbers worth printing, and the device line."""
    compile_ms = sum(
        (ev.get("_traceml_internal:compile_time") or {}).get("cpu_ms") or 0.0
        for _, _, ev in art["steps"]
    )
    device_ms = sorted(
        ev["_traceml_internal:step_time"]["device_ms"]
        for _, clock, ev in art["steps"]
        if clock == "device" and ev.get("_traceml_internal:step_time")
    )
    phases = art["rank"]["phases"]
    main = phases["sharded" if four_chips else "train"]
    numbers = {
        "compile_s": compile_ms / 1e3,
        "first_step_s": main["first_step_s"],
        "step_ms_median_host": main["step_ms_median"],
        "step_ms_median_device_clock": device_ms[len(device_ms) // 2],
    }
    if four_chips:
        numbers.update(
            loss_4dev=main["losses"][:2], loss_1dev=main["ref_losses_1dev"],
            ici_shape=main["ici_shape"],
            bytes_in_use_per_device=main["bytes_in_use_per_device"],
        )
    else:
        numbers.update(
            n_params=main["n_params"], peak_bytes=main["peak_bytes"],
            kernel_max_abs_err=phases["kernel"]["max_abs_err"],
            marker_ready_after_s=phases["marker"]["ready_after_s"],
        )
    system = art["system"]
    device = {
        "platform": system["platform"],
        "kind": system["devices"][0]["kind"],
        "count": system["global_device_count"],
    }
    return {"numbers": numbers, "device": device}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--four-chips", action="store_true",
        help="run only the sharded 4-chip path",
    )
    args = parser.parse_args(argv)
    if not RANK_SCRIPT.is_file():
        _log(f"{RANK_SCRIPT} not found: run from a checkout of the repo")
        return 2
    work = REPO / "chiprun_out" / "chip_smoke" / (
        "four_chips" if args.four_chips else "one_chip"
    )
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    rc = run_launcher(
        work, ["--four-chips"] if args.four_chips else [], TIMEOUT_S
    )
    art = read_session(work)
    problems = check(art, "tpu", args.four_chips)
    if rc != 0:
        problems.insert(0, f"launcher exited {rc}")
    if problems:
        for p in problems:
            _log(f"FAIL: {p}")
        return 1
    out = report(art, args.four_chips)
    for key, value in out["numbers"].items():
        print(f"[chip_smoke] {key}={value}")
    print(json.dumps({"ok": True, "device": out["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
