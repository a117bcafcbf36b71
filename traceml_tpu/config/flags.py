"""Single declared registry of every ``TRACEML_*`` environment flag.

Every kill switch, tuning knob, and launcher→child contract variable is
declared HERE — name, default, one-line doc — and read through the
:class:`Flag` accessors.  ``traceml lint``'s env-flag registry pass
(``traceml_tpu/analysis/flags_pass.py``) enforces the contract
mechanically:

* a ``TRACEML_*`` string literal anywhere else in the package that is
  not declared here is an error (``TLF001``);
* a declared flag with an empty doc line is an error (``TLF002``);
* a declared flag referenced nowhere outside this module is a dead
  flag (``TLF003``);
* an ``os.environ``/``getenv`` read of a ``TRACEML_*`` name outside
  this module bypasses the registry (``TLF004``) — call
  ``<FLAG>.raw()/enabled()/truthy()/get_*()`` instead.

``runtime/settings.py`` keeps its ``ENV_*`` aliases (the
launcher↔child env contract is expressed as plain names there) but
derives them from these declarations, so the name exists in exactly
one place.

The module is intentionally stdlib-only and import-cheap: it is read
on hot fail-open paths (sampler builds, transport setup) and by the
zero-dependency static analyzer.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional

# values meaning "explicitly off" / "explicitly on" — shared by every
# boolean flag so kill switches behave uniformly
_FALSY = ("0", "false", "off")
_TRUTHY = ("1", "true", "yes", "on")


@dataclasses.dataclass(frozen=True)
class Flag:
    """One declared ``TRACEML_*`` variable.

    ``default`` is the *raw string* default (or ``None`` for unset) so
    the declaration mirrors what a shell would export; typed accessors
    coerce on read and fall back to the default on malformed values
    (env flags must never raise into training code).
    """

    name: str
    default: Optional[str]
    doc: str

    def raw(self, env: Optional[Dict[str, str]] = None) -> Optional[str]:
        """The raw value, or the declared default when unset."""
        e = os.environ if env is None else env
        v = e.get(self.name)
        return self.default if v is None else v

    def is_set(self, env: Optional[Dict[str, str]] = None) -> bool:
        e = os.environ if env is None else env
        return self.name in e

    def enabled(self, env: Optional[Dict[str, str]] = None) -> bool:
        """Kill-switch reading: on unless explicitly ``0/false/off``."""
        v = self.raw(env)
        if v is None:
            return True
        return str(v).strip().lower() not in _FALSY

    def truthy(self, env: Optional[Dict[str, str]] = None) -> bool:
        """Opt-in reading: off unless explicitly ``1/true/yes/on``."""
        v = self.raw(env)
        if v is None:
            return False
        return str(v).strip().lower() in _TRUTHY

    def get_str(self, env: Optional[Dict[str, str]] = None) -> Optional[str]:
        return self.raw(env)

    def get_float(
        self, fallback: float, env: Optional[Dict[str, str]] = None
    ) -> float:
        v = self.raw(env)
        if v is None:
            return fallback
        try:
            return float(v)
        except (TypeError, ValueError):
            return fallback

    def get_int(
        self, fallback: int, env: Optional[Dict[str, str]] = None
    ) -> int:
        v = self.raw(env)
        if v is None:
            return fallback
        try:
            return int(str(v).strip())
        except (TypeError, ValueError):
            return fallback


REGISTRY: Dict[str, Flag] = {}


def declare(name: str, default: Optional[str], doc: str) -> Flag:
    """Register one flag.  ``traceml lint`` parses these calls, so the
    name and doc must be literals."""
    if name in REGISTRY:
        raise ValueError(f"duplicate flag declaration: {name}")
    flag = Flag(name=name, default=default, doc=doc)
    REGISTRY[name] = flag
    return flag


def get(name: str) -> Flag:
    """Look up a declared flag by env-var name (KeyError on undeclared
    names — an undeclared flag is a bug the lint gate also catches)."""
    return REGISTRY[name]


# --------------------------------------------------------------------
# launcher ↔ child contract (mirrored as ENV_* in runtime/settings.py)
# --------------------------------------------------------------------
SESSION_ID = declare(
    "TRACEML_SESSION_ID", "local",
    "session id: names <logs_dir>/<session> and every artifact in it")
LOGS_DIR = declare(
    "TRACEML_LOGS_DIR", "./traceml_logs",
    "root directory sessions are written under")
MODE = declare(
    "TRACEML_MODE", "cli",
    "display mode the launcher selected: cli | summary | dashboard")
AGGREGATOR_HOST = declare(
    "TRACEML_AGGREGATOR_HOST", "127.0.0.1",
    "address workers dial to reach the aggregator (owner node address)")
AGGREGATOR_BIND_HOST = declare(
    "TRACEML_AGGREGATOR_BIND_HOST", None,
    "address the aggregator binds (defaults to the connect host)")
AGGREGATOR_PORT = declare(
    "TRACEML_AGGREGATOR_PORT", "0",
    "aggregator TCP port; 0 = off/unassigned (ranks run untraced)")
SAMPLER_INTERVAL_SEC = declare(
    "TRACEML_SAMPLER_INTERVAL_SEC", "1.0",
    "seconds between sampler ticks on every rank")
TRACE_MAX_STEPS = declare(
    "TRACEML_TRACE_MAX_STEPS", None,
    "stop recording step telemetry after this many steps (unset = all)")
DISABLE = declare(
    "TRACEML_DISABLE", None,
    "master kill switch: 1 = run the script entirely untraced")
DISK_BACKUP = declare(
    "TRACEML_DISK_BACKUP", None,
    "1 = every rank also spools envelopes to per-rank msgpack backups")
CAPTURE_STDERR = declare(
    "TRACEML_CAPTURE_STDERR", "1",
    "mirror rank stderr into the stdout capture stream (0 to opt out)")
RUN_NAME = declare(
    "TRACEML_RUN_NAME", None,
    "human-readable run name recorded in the manifest and reports")
EXPECTED_WORLD_SIZE = declare(
    "TRACEML_EXPECTED_WORLD_SIZE", None,
    "world size the launcher promised; liveness flags ranks never seen")
FINALIZE_TIMEOUT_SEC = declare(
    "TRACEML_FINALIZE_TIMEOUT_SEC", "300.0",
    "seconds the launcher waits for the final drain + summary write")
SUMMARY_WINDOW_ROWS = declare(
    "TRACEML_SUMMARY_WINDOW_ROWS", "10000",
    "per-table per-rank row retention bound in the session DB")
SERVE_MAX_SESSIONS = declare(
    "TRACEML_SERVE_MAX_SESSIONS", "8",
    "serving tier: max concurrently-open session publishers (LRU bound)")
SCRIPT = declare(
    "TRACEML_SCRIPT", None,
    "path of the user training script the rank executor should run")
SCRIPT_ARGS = declare(
    "TRACEML_SCRIPT_ARGS", None,
    "shell-quoted argv tail for the user training script")

# --------------------------------------------------------------------
# transport tier (docs/developer_guide/native-transport.md)
# --------------------------------------------------------------------
TRANSPORT = declare(
    "TRACEML_TRANSPORT", "auto",
    "telemetry transport: auto | shm | uds | tcp (auto = same-host shm)")
TRANSPORT_COMPRESS = declare(
    "TRACEML_TRANSPORT_COMPRESS", "auto",
    "cross-host envelope compression: auto | zstd | zlib | 0 (off)")
SHM_RING_BYTES = declare(
    "TRACEML_SHM_RING_BYTES", "4194304",
    "per-rank shared-memory ring capacity in bytes (same-host transport)")
SHM_DIR = declare(
    "TRACEML_SHM_DIR", None,
    "directory for ring segment files (default /dev/shm, else rank dir)")
UDS_PATH = declare(
    "TRACEML_UDS_PATH", None,
    "Unix-domain socket path for the uds transport (default derived)")

# --------------------------------------------------------------------
# fleet federation (docs/developer_guide/federation.md)
# --------------------------------------------------------------------
FLEET_SHARDS = declare(
    "TRACEML_FLEET_SHARDS", None,
    "fleet router: comma-separated host:port shard list, or a shards.json path")
FLEET_PORT = declare(
    "TRACEML_FLEET_PORT", "0",
    "fleet router: HTTP port the router front-end binds (0 = ephemeral)")
FLEET_HOST = declare(
    "TRACEML_FLEET_HOST", "127.0.0.1",
    "fleet router: address the router front-end binds")
FLEET_CACHE_TTL = declare(
    "TRACEML_FLEET_CACHE_TTL", "0.5",
    "fleet tier: edge-cache + fleet-index reuse window in seconds")
FLEET_PROBE_S = declare(
    "TRACEML_FLEET_PROBE_S", "2.0",
    "fleet router: base shard health-probe interval (capped backoff on failure)")
FLEET_STATE_DIR = declare(
    "TRACEML_FLEET_STATE_DIR", None,
    "fleet router: directory fleet_router_ready.json is written to (launcher contract)")

# --------------------------------------------------------------------
# fault tolerance / liveness
# --------------------------------------------------------------------
AGG_MAX_RESTARTS = declare(
    "TRACEML_AGG_MAX_RESTARTS", "3",
    "bounded aggregator crash-resume: respawns before degrading untraced")
FAULT_PLAN = declare(
    "TRACEML_FAULT_PLAN", None,
    "JSON fault-injection plan for the deterministic chaos harness")
HEARTBEAT_INTERVAL_SEC = declare(
    "TRACEML_HEARTBEAT_INTERVAL_SEC", "3.0",
    "seconds between rank_heartbeat control messages (liveness input)")
LIVENESS_STALE_SEC = declare(
    "TRACEML_LIVENESS_STALE_SEC", "10.0",
    "silence age after which a rank is marked stale (~3 heartbeats)")
LIVENESS_LOST_SEC = declare(
    "TRACEML_LIVENESS_LOST_SEC", "30.0",
    "silence age after which a stale rank is marked lost")

# --------------------------------------------------------------------
# kill switches / opt-ins
# --------------------------------------------------------------------
COLLECTIVES = declare(
    "TRACEML_COLLECTIVES", "1",
    "0 turns every collectives-capture entry point into a no-op")
COLUMNAR_WINDOW = declare(
    "TRACEML_COLUMNAR_WINDOW", "1",
    "0 forces the scalar window-build reference path")
INCR_WINDOW = declare(
    "TRACEML_INCR_WINDOW", "1",
    "0 disables the incremental window caches (full rebuild every tick)")
SERVING = declare(
    "TRACEML_SERVING", "1",
    "0 turns every serving-capture entry point into a no-op")
VECTOR_DIAGNOSIS = declare(
    "TRACEML_VECTOR_DIAGNOSIS", "1",
    "0 forces the scalar rule-evaluation reference arm in diagnosis")
SERVING_QUEUE_MAX = declare(
    "TRACEML_SERVING_QUEUE_MAX", "8192",
    "serving domain: bounded request-event queue capacity per rank")
NO_NATIVE = declare(
    "TRACEML_NO_NATIVE", None,
    "1 skips the optional C framing extension (pure-Python fallback)")
NO_PPID_WATCH = declare(
    "TRACEML_NO_PPID_WATCH", None,
    "1 disarms the orphan watchdog (deliberate daemonization)")
NO_FLOPS_ESTIMATE = declare(
    "TRACEML_NO_FLOPS_ESTIMATE", None,
    "1 skips the one-time XLA cost-analysis FLOPs estimate at first step")
PIN_RANK_CPUS = declare(
    "TRACEML_PIN_RANK_CPUS", None,
    "1 pins each local rank to its own core slice (skew isolation)")
OVERHEAD_BUDGET = declare(
    "TRACEML_OVERHEAD_BUDGET", None,
    "tracer overhead budget as a fraction of step time (default 0.01)")
MESH = declare(
    "TRACEML_MESH", None,
    "mesh override grammar name:size[@kind],... for topology capture")
ROLLUP = declare(
    "TRACEML_ROLLUP", "1",
    "0 disables tiered rollup decay (watermark prunes discard history)")
ROLLUP_TIERS = declare(
    "TRACEML_ROLLUP_TIERS", None,
    "rollup tier grammar width[:horizon],... seconds (default 10:21600,60:1209600)")
BASELINE_MAX_RUNS = declare(
    "TRACEML_BASELINE_MAX_RUNS", "20",
    "cross-run baseline store: matching sessions kept per fingerprint")
