"""Launch orchestration
(reference: src/traceml_ai/launcher/commands.py:210-567).

``run``: resolve config (CLI > env > traceml.yaml > defaults), write run +
code manifests, start the aggregator process on the owner node and wait
for its ready file, start N training processes (executor entry, one per
rank with the RANK/WORLD_SIZE contract — the JAX one-process-per-host
model and the torch CPU multi-rank model both fit), supervise, and on
exit enforce ``final_summary.json`` in summary mode.  If the aggregator
dies early the run degrades (training continues untraced) rather than
failing (reference: commands.py:549-564).
"""

from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

from traceml_tpu.config import flags
from traceml_tpu.config.yaml_loader import load_yaml_config
from traceml_tpu.launcher import manifest as mf
from traceml_tpu.launcher.process import (
    SupervisedChild,
    python_argv,
    spawn,
    spawn_supervised,
    terminate,
    wait_for_ready_file,
)
from traceml_tpu.runtime.session import generate_session_id
from traceml_tpu.runtime.settings import (
    ENV_AGG_PORT,
    ENV_SCRIPT,
    ENV_SCRIPT_ARGS,
    AggregatorEndpoint,
    TraceMLSettings,
    settings_to_env,
)
from traceml_tpu.sdk import protocol

# bounded aggregator crash-resume: how many times the launcher respawns
# a dead aggregator (pinned to its original port so the ranks' backoff
# reconnects land) before degrading to untraced
ENV_AGG_MAX_RESTARTS = flags.AGG_MAX_RESTARTS.name
DEFAULT_AGG_MAX_RESTARTS = 3


def _aggregator_env(base_env: Dict[str, str]) -> Dict[str, str]:
    """The aggregator never needs an accelerator: pin any JAX it might
    import to the CPU, so it cannot take a chip from a rank."""
    env = dict(base_env)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _restart_aggregator(
    session_dir: Path, base_env: Dict[str, str], port: int
) -> Optional[SupervisedChild]:
    """Respawn the aggregator after a crash, pinned to the port the dead
    incarnation had bound (ranks keep dialing it; SO_REUSEADDR makes the
    rebind race-free).  The stale ready file must go first — it still
    advertises the dead pid, and waiting on it would succeed instantly.

    The new process reopens the session DB (re-seeding watermark counts
    and the seq-dedup table) and re-seeds liveness/finished ranks from
    rank_status.json — see docs/developer_guide/fault-tolerance.md."""
    ready_path = session_dir / "aggregator_ready.json"
    try:
        ready_path.unlink()
    except OSError:
        pass
    env = _aggregator_env(base_env)
    env[ENV_AGG_PORT] = str(port)
    # A fault plan's counters are per-process: the restarted aggregator
    # would re-parse the inherited plan with fresh counters and a kill9
    # rule would fire again on the replayed backlog — "kill the
    # aggregator once" would mean "kill every incarnation".  The plan
    # describes the incarnation it already killed; restarts run clean.
    # Cleared via empty string, not pop: spawn merges over os.environ,
    # where the launcher's own copy of the plan would resurface.
    env["TRACEML_FAULT_PLAN"] = ""
    child = spawn_supervised(
        python_argv("traceml_tpu.aggregator.aggregator_main"),
        label="aggregator",
        env=env,
    )
    ready = wait_for_ready_file(ready_path, timeout=20.0)
    if ready is None or child.poll() is not None:
        terminate(child.proc, grace_sec=2)
        return None
    return child


def resolve_settings(cli: Dict[str, Any]) -> TraceMLSettings:
    """CLI > env > yaml > defaults (reference: commands.py:264).

    env-level resolution happens implicitly in child processes via
    settings_from_env; here we fold yaml + CLI into the canonical
    settings object that the launcher serializes into the env contract.
    """
    yaml_cfg = load_yaml_config()

    def pick(key: str, default: Any = None) -> Any:
        if cli.get(key) is not None:
            return cli[key]
        env_key = f"TRACEML_{key.upper()}"
        if os.environ.get(env_key) is not None:
            return os.environ[env_key]
        if yaml_cfg.get(key) is not None:
            return yaml_cfg[key]
        return default

    def pick_bool(key: str, default: bool) -> bool:
        v = pick(key, None)
        if v is None:
            return default
        if isinstance(v, bool):
            return v
        return str(v).strip().lower() in ("1", "true", "yes", "on")

    nnodes = int(cli.get("nnodes") or 1)
    # multi-node defaults to summary mode (reference: commands.py:59-71)
    default_mode = "summary" if nnodes > 1 else "cli"
    run_name = pick("run_name")
    # session id is env-overridable (TRACEML_SESSION_ID): multi-node runs
    # launch one launcher per node, and every node must agree on the
    # session identity the telemetry is keyed by
    session_id = (
        cli.get("session_id") or pick("session_id") or generate_session_id(run_name)
    )
    mode = str(pick("mode", default_mode))
    max_steps = pick("trace_max_steps")
    port = int(pick("aggregator_port", 0) or 0)
    if nnodes > 1 and port == 0:
        # the owner's ephemeral port is unknowable to other nodes
        raise ValueError(
            "multi-node runs require an explicit --aggregator-port "
            "(every node must agree on the owner's port)"
        )
    return TraceMLSettings(
        session_id=session_id,
        logs_dir=Path(pick("logs_dir", "./traceml_logs")),
        mode=mode,
        aggregator=AggregatorEndpoint(
            connect_host=str(pick("aggregator_host", "127.0.0.1")),
            bind_host=str(
                pick("aggregator_bind_host", "0.0.0.0" if nnodes > 1 else "127.0.0.1")
            ),
            port=port,
        ),
        sampler_interval_sec=float(pick("sampler_interval_sec", 1.0)),
        trace_max_steps=int(max_steps) if max_steps else None,
        disabled=bool(cli.get("disable", False)),
        disk_backup=pick_bool("disk_backup", False),
        capture_stderr=pick_bool("capture_stderr", True),
        run_name=run_name,
        expected_world_size=int(cli.get("nprocs") or 1) * nnodes,
        finalize_timeout_sec=float(pick("finalize_timeout_sec", 300.0)),
        summary_window_rows=int(pick("summary_window_rows", 10000)),
        # transport tier: yaml/env-configurable, defaults resolve in
        # transport/select.py (same-host → shm ring, else TCP)
        transport=str(pick("transport", "auto")),
        transport_compress=str(pick("transport_compress", "auto")),
        shm_ring_bytes=int(pick("shm_ring_bytes", 4194304)),
        shm_dir=pick("shm_dir") or None,
        uds_path=pick("uds_path") or None,
    )


def _cleanup_ring_segments(session_dir: Path) -> None:
    """End-of-run hygiene: remove the shm ring segment files the ranks
    created (they live outside the session dir, typically /dev/shm, so
    nothing else would ever reap them)."""
    try:
        from traceml_tpu.transport.shm_ring import scan_ring_descriptors

        for desc in scan_ring_descriptors(session_dir):
            for name in (desc.get("path"), desc.get("_descriptor")):
                if not name:
                    continue
                try:
                    Path(name).unlink()
                except OSError:
                    pass
    except Exception:
        pass


def launch_process(
    script: str,
    script_args: Optional[List[str]] = None,
    **cli: Any,
) -> int:
    """The `traceml run` implementation; returns the exit code."""
    script_path = Path(script).resolve()
    if not script_path.is_file():
        print(f"[TraceML] script not found: {script_path}")
        return 2
    try:
        settings = resolve_settings(cli)
    except ValueError as exc:
        print(f"[TraceML] {exc}")
        return 2
    nprocs = int(cli.get("nprocs") or 1)
    nnodes = int(cli.get("nnodes") or 1)
    node_rank = int(cli.get("node_rank") or 0)
    owner = node_rank == 0
    session_dir = settings.session_dir
    session_dir.mkdir(parents=True, exist_ok=True)

    mf.write_run_manifest(
        session_dir,
        session_id=settings.session_id,
        script=str(script_path),
        mode=settings.mode,
        world_size=nprocs * nnodes,
        extra={"nnodes": nnodes, "node_rank": node_rank},
    )
    try:
        mf.write_code_manifest(session_dir, script_path)
    except Exception:
        pass

    if settings.disabled:
        # tracing disabled → just run the script untouched
        proc = spawn(
            [os.sys.executable, str(script_path)] + list(script_args or [])
        )
        code = proc.wait()
        mf.update_run_manifest(
            session_dir,
            status=mf.STATUS_COMPLETED if code == 0 else mf.STATUS_FAILED,
        )
        return code

    base_env = settings_to_env(settings)

    # 1. aggregator on the owner node
    agg_child: Optional[SupervisedChild] = None
    agg_port = settings.aggregator.port
    telemetry_ok = True
    crash_logs: List[str] = []
    if owner:
        agg_child = spawn_supervised(
            python_argv("traceml_tpu.aggregator.aggregator_main"),
            label="aggregator",
            env=_aggregator_env(base_env),
        )
        ready = wait_for_ready_file(
            session_dir / "aggregator_ready.json", timeout=30.0
        )
        if ready is None or agg_child.poll() is not None:
            telemetry_ok = False
            print("[TraceML] aggregator failed to start; running untraced")
            if agg_child.poll() is not None:
                log = agg_child.write_crash_log(session_dir)
                if log is not None:
                    crash_logs.append(str(log))
            mf.update_run_manifest(
                session_dir,
                telemetry_status="degraded",
                **({"crash_logs": crash_logs} if crash_logs else {}),
            )
            if agg_child is not None:
                terminate(agg_child.proc, grace_sec=2)
                agg_child = None
        else:
            agg_port = int(ready["port"])

    # 2. training rank processes
    rank_env_base = dict(base_env)
    rank_env_base["TRACEML_AGGREGATOR_PORT"] = str(agg_port if telemetry_ok else 0)
    rank_env_base[ENV_SCRIPT] = str(script_path)
    if script_args:
        import shlex

        rank_env_base[ENV_SCRIPT_ARGS] = " ".join(shlex.quote(a) for a in script_args)
    if not telemetry_ok:
        rank_env_base["TRACEML_DISABLE"] = "1"

    procs: List[SupervisedChild] = []
    world = nprocs * nnodes
    for local_rank in range(nprocs):
        rank = node_rank * nprocs + local_rank
        env = dict(rank_env_base)
        env.update(
            {
                "RANK": str(rank),
                "WORLD_SIZE": str(world),
                "LOCAL_RANK": str(local_rank),
                "LOCAL_WORLD_SIZE": str(nprocs),
                "NODE_RANK": str(node_rank),
            }
        )
        procs.append(
            spawn_supervised(
                python_argv("traceml_tpu.runtime.executor"),
                label=f"rank_{rank}",
                env=env,
            )
        )
    mf.update_run_manifest(session_dir, status=mf.STATUS_RUNNING)

    # signal propagation: SIGTERM to the launcher tears the tree down
    # exactly like Ctrl-C (children terminated, aggregator finalized,
    # manifest stamped) instead of orphaning the process groups
    import signal as _signal

    def _sigterm(_signum, _frame):
        raise KeyboardInterrupt

    old_sigterm = None
    try:
        old_sigterm = _signal.signal(_signal.SIGTERM, _sigterm)
    except ValueError:
        pass  # non-main thread (embedding): skip, the embedder owns signals

    # 3. supervise
    exit_code = 0
    launcher_stopped: set = set()  # pids WE terminated (victims, not crashes)
    agg_restarts = 0
    agg_max_restarts = flags.AGG_MAX_RESTARTS.get_int(DEFAULT_AGG_MAX_RESTARTS)
    try:
        while True:
            alive = [p for p in procs if p.poll() is None]
            for p in procs:
                if (
                    p.poll() is not None
                    and p.returncode not in (0, None)
                    and p.proc.pid not in launcher_stopped
                ):
                    if exit_code in (0, None):
                        exit_code = p.returncode
                    log = p.write_crash_log(session_dir)
                    if log is not None and str(log) not in crash_logs:
                        print(
                            f"[TraceML] {p.label} {p.describe_exit()}; "
                            f"stderr tail: {log}"
                        )
                        crash_logs.append(str(log))
            if owner and agg_child is not None and agg_child.poll() is not None:
                # aggregator died mid-run: bounded restarts on the same
                # port (ranks spool + reconnect), then degrade
                log = agg_child.write_crash_log(session_dir)
                if log is not None:
                    crash_logs.append(str(log))
                agg_child = None
                if agg_restarts < agg_max_restarts:
                    agg_restarts += 1
                    print(
                        f"[TraceML] aggregator exited mid-run; restarting "
                        f"({agg_restarts}/{agg_max_restarts}) on port {agg_port}"
                    )
                    agg_child = _restart_aggregator(
                        session_dir, base_env, agg_port
                    )
                if agg_child is not None:
                    mf.update_run_manifest(
                        session_dir,
                        telemetry_status="restarted",
                        aggregator_restarts=agg_restarts,
                    )
                else:
                    print(
                        "[TraceML] aggregator exited early; telemetry degraded"
                    )
                    mf.update_run_manifest(
                        session_dir, telemetry_status="degraded"
                    )
                    telemetry_ok = False
            if not alive:
                break
            if exit_code not in (0, None):
                # a rank failed → stop the rest
                for p in alive:
                    launcher_stopped.add(p.proc.pid)
                    terminate(p.proc)
                break
            time.sleep(0.2)
    except KeyboardInterrupt:
        exit_code = 130
        for p in procs:
            launcher_stopped.add(p.proc.pid)
            terminate(p.proc)
    finally:
        # our SIGTERM handler stays installed until the manifest is
        # stamped: finalization can block for finalize_timeout_sec, and
        # a SECOND signal there must cut it short (KeyboardInterrupt
        # caught below), not kill the launcher with status="running"
        try:
            if owner and agg_child is not None:
                # graceful stop: SIGTERM → aggregator finalizes + summary
                try:
                    terminate(
                        agg_child.proc,
                        grace_sec=max(10.0, settings.finalize_timeout_sec),
                    )
                except KeyboardInterrupt:
                    exit_code = exit_code or 130
                    terminate(agg_child.proc, grace_sec=2.0)
            if crash_logs:
                mf.update_run_manifest(session_dir, crash_logs=crash_logs)
            if owner:
                _cleanup_ring_segments(session_dir)
        finally:
            if old_sigterm is not None:
                try:
                    _signal.signal(_signal.SIGTERM, old_sigterm)
                except ValueError:
                    pass

    status = mf.STATUS_COMPLETED if exit_code in (0, None) else mf.STATUS_FAILED
    mf.update_run_manifest(session_dir, status=status, exit_code=exit_code or 0)

    # 4. summary-mode enforcement (reference: commands.py:530-543)
    if owner and telemetry_ok and settings.mode == "summary":
        summary_path = protocol.get_final_summary_json_path(session_dir)
        if not summary_path.exists():
            print(f"[TraceML] WARNING: expected summary missing: {summary_path}")
            mf.update_run_manifest(session_dir, telemetry_status="degraded")
        else:
            txt = protocol.get_final_summary_txt_path(session_dir)
            if txt.exists():
                print(txt.read_text())
            print(f"[TraceML] final summary: {summary_path}")
    return exit_code or 0
