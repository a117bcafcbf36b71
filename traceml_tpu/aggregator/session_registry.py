"""Session registry: one aggregator process serving N sessions
(docs/developer_guide/serving-tier.md).

The registry maps validated session ids under one ``logs_dir`` to
serving-tier publishers (``renderers/serving.publisher_for`` — lazily
opened, keyed, LRU-bounded, so an idle session costs nothing and a
burst of sessions can't exhaust sqlite connections), and builds the
fleet index served at ``GET /api/sessions``: per session the rank
liveness summary, the primary diagnosis, and the last-update stamp.

Session ids come from URLs on the (unauthenticated) display port, so
they are validated against a strict charset BEFORE touching the
filesystem — both on lookup and during directory discovery; a hostile
directory name under ``logs_dir`` is skipped, never echoed.
"""

from __future__ import annotations

import re
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from traceml_tpu.renderers.serving import SessionPublisher, publisher_for

# no leading dot (also excludes "." / ".."), no separators — a session id
# must stay a single path component
_SESSION_ID_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9._\-]{0,127}$")


def valid_session_id(session_id: Any) -> bool:
    return bool(
        isinstance(session_id, str) and _SESSION_ID_RE.match(session_id)
    )


class SessionRegistry:
    """Thread-safe (shared by every HTTP handler thread)."""

    def __init__(
        self,
        logs_dir: Path,
        default_session: Optional[str] = None,
        window_steps: int = 150,
        max_sessions: int = 8,
        fleet_cache_ttl: float = 0.0,
        freshness: Optional[Any] = None,
    ) -> None:
        self.logs_dir = Path(logs_dir)
        #: the aggregator's FreshnessTracker, told about the default
        #: session's step_time publishes (aggregator/freshness.py)
        self.freshness = freshness
        self.default_session = default_session
        self.window_steps = window_steps
        self.max_sessions = max(1, int(max_sessions))
        #: whole-index reuse window — the federation rollup polls
        #: ``/api/sessions`` per shard per interval, multiplied by
        #: routers; 0 keeps the historical rebuild-every-call behavior
        self.fleet_cache_ttl = max(0.0, float(fleet_cache_ttl))
        self._lock = threading.Lock()
        # sessions opened THROUGH this registry — close() only touches
        # these, never publishers some other registry/test opened
        self._open: Dict[str, SessionPublisher] = {}
        # explicit shard locations (register()) — the aggregator context
        # may bind its own session to a DB outside logs_dir/<sid>/
        self._db_overrides: Dict[str, Path] = {}
        self._dir_overrides: Dict[str, Path] = {}
        # per-session entry cache keyed by an artifact stamp (mtimes +
        # sizes + live publisher token) — invalidation is the stamp
        # changing, so a TTL-cached index never shows an update later
        # than the artifacts it was built from
        self._entry_cache: Dict[str, Tuple[tuple, Dict[str, Any]]] = {}
        self._index_cache: Optional[Tuple[float, Dict[str, Any]]] = None
        self.entry_builds = 0  # observability: cache-effectiveness tests

    def register(
        self,
        session_id: str,
        db_path: Path,
        session_dir: Optional[Path] = None,
    ) -> None:
        """Pin a session to an explicit DB shard (and artifact dir),
        overriding the ``logs_dir/<sid>/`` convention.  Used by the
        display driver for the session its context already bound."""
        if not valid_session_id(session_id):
            raise KeyError(session_id)
        with self._lock:
            self._db_overrides[session_id] = Path(db_path)
            if session_dir is not None:
                self._dir_overrides[session_id] = Path(session_dir)
            # the binding changes where artifacts are read from — any
            # cached entry/index for this session is now misaddressed
            self._entry_cache.pop(session_id, None)
            self._index_cache = None

    # -- lookup ----------------------------------------------------------

    def resolve(self, session_id: Optional[str]) -> Optional[str]:
        """Requested session id → validated id (default when omitted),
        or None when invalid/unknown-default."""
        if session_id is None or session_id == "":
            session_id = self.default_session
        if not valid_session_id(session_id):
            return None
        return session_id

    def db_path(self, session_id: str) -> Path:
        with self._lock:
            override = self._db_overrides.get(session_id)
        if override is not None:
            return override
        return self.logs_dir / session_id / "telemetry.sqlite"

    def session_dir(self, session_id: str) -> Path:
        with self._lock:
            override = self._dir_overrides.get(session_id)
        if override is not None:
            return override
        return self.logs_dir / session_id

    def publisher(self, session_id: str) -> SessionPublisher:
        """The session's publisher (opened lazily; LRU-bounded by the
        serving-tier cache).  Caller must pass a validated id."""
        if not valid_session_id(session_id):
            raise KeyError(session_id)
        pub = publisher_for(
            self.db_path(session_id),
            session_id,
            window_steps=self.window_steps,
            max_publishers=self.max_sessions,
        )
        if self.freshness is not None and session_id == self.default_session:
            pub.freshness = self.freshness
        with self._lock:
            self._open[session_id] = pub
        return pub

    # -- fleet index -----------------------------------------------------

    def sessions(self) -> List[str]:
        """Valid session ids under logs_dir that have produced telemetry
        (DB shard or rank-status file), plus the default session even
        before its first write.  Invalid directory names are skipped —
        defense in depth ahead of client-side escaping."""
        found = set()
        try:
            for entry in self.logs_dir.iterdir():
                if not valid_session_id(entry.name):
                    continue
                if not entry.is_dir():
                    continue
                if (entry / "telemetry.sqlite").exists() or (
                    entry / "rank_status.json"
                ).exists():
                    found.add(entry.name)
        except OSError:
            pass
        if self.default_session and valid_session_id(self.default_session):
            found.add(self.default_session)
        return sorted(found)

    def _session_entry(self, session_id: str) -> Dict[str, Any]:
        from traceml_tpu.reporting.loaders import load_rank_status
        from traceml_tpu.sdk.protocol import get_final_summary_json_path
        from traceml_tpu.utils.atomic_io import read_json

        session_dir = self.session_dir(session_id)
        db = self.db_path(session_id)
        entry: Dict[str, Any] = {
            "session": session_id,
            "db_exists": db.exists(),
            "last_update_ts": None,
            "ranks": {},
            "finished": False,
            "primary_diagnosis": None,
        }
        try:
            entry["last_update_ts"] = db.stat().st_mtime
        except OSError:
            pass
        status = load_rank_status(session_dir)
        if status and isinstance(status.get("ranks"), dict):
            counts: Dict[str, int] = {}
            for info in status["ranks"].values():
                state = (info or {}).get("state") or "?"
                counts[state] = counts.get(state, 0) + 1
            entry["ranks"] = counts
            if status.get("ts"):
                entry["last_update_ts"] = status["ts"]
        summary_path = get_final_summary_json_path(session_dir)
        if summary_path.exists():
            entry["finished"] = True
            summary = read_json(summary_path)
            if isinstance(summary, dict):
                primary = summary.get("primary_diagnosis")
                if isinstance(primary, dict):
                    entry["primary_diagnosis"] = {
                        k: primary.get(k)
                        for k in ("kind", "severity", "summary")
                    }
                mesh = ((summary.get("meta") or {}).get("topology") or {}).get(
                    "mesh"
                )
                if mesh:
                    entry["mesh"] = mesh
                # workload kind for the fleet page: a serving section only
                # exists when the session recorded serving telemetry
                sections = summary.get("sections")
                if isinstance(sections, dict):
                    kinds = []
                    if (sections.get("step_time") or {}).get("status") == "OK":
                        kinds.append("training")
                    if "serving" in sections:
                        kinds.append("serving")
                    if kinds:
                        entry["workload"] = "+".join(kinds)
        else:
            # live session: peek at an already-open publisher's diagnosis
            # fragment — the index never force-opens a publisher (that
            # would let a fleet listing thrash the LRU bound)
            with self._lock:
                pub = self._open.get(session_id)
            if pub is not None and not pub.closed:
                diag = pub.fragment("diagnosis") or {}
                issue = diag.get("diagnosis")
                if isinstance(issue, dict):
                    entry["primary_diagnosis"] = {
                        k: issue.get(k)
                        for k in ("kind", "severity", "summary")
                    }
                mesh = (pub.fragment("meta") or {}).get("mesh")
                if mesh:
                    entry["mesh"] = mesh
                kinds = []
                if (pub.fragment("step_time") or {}).get("step_time"):
                    kinds.append("training")
                if (pub.fragment("serving") or {}).get("serving"):
                    kinds.append("serving")
                if kinds:
                    entry["workload"] = "+".join(kinds)
        return entry

    def _entry_stamp(self, session_id: str) -> tuple:
        """Cheap invalidation key for one session's index entry: the
        (mtime_ns, size) of each artifact the entry is derived from,
        plus the open publisher's version token for live sessions —
        any write that could change the entry changes the stamp."""
        from traceml_tpu.sdk.protocol import get_final_summary_json_path

        session_dir = self.session_dir(session_id)
        parts: list = []
        for path in (
            session_dir / "rank_status.json",
            get_final_summary_json_path(session_dir),
            self.db_path(session_id),
        ):
            try:
                st = path.stat()
                parts.append((st.st_mtime_ns, st.st_size))
            except OSError:
                parts.append(None)
        with self._lock:
            pub = self._open.get(session_id)
        if pub is not None and not pub.closed:
            try:
                parts.append(pub.poll())
            except Exception:
                parts.append(None)
        else:
            parts.append(None)
        return tuple(parts)

    def _entry_cached(self, session_id: str) -> Dict[str, Any]:
        stamp = self._entry_stamp(session_id)
        with self._lock:
            cached = self._entry_cache.get(session_id)
        if cached is not None and cached[0] == stamp:
            return cached[1]
        entry = self._session_entry(session_id)
        with self._lock:
            self.entry_builds += 1
            self._entry_cache[session_id] = (stamp, entry)
        return entry

    def fleet_index(self) -> Dict[str, Any]:
        now = time.monotonic()
        if self.fleet_cache_ttl > 0.0:
            with self._lock:
                cached_index = self._index_cache
            if (
                cached_index is not None
                and (now - cached_index[0]) <= self.fleet_cache_ttl
            ):
                return cached_index[1]
        index = {
            "version": 1,
            "ts": time.time(),
            "default_session": self.default_session
            if valid_session_id(self.default_session)
            else None,
            "sessions": [
                self._entry_cached(sid) for sid in self.sessions()
            ],
        }
        if self.fleet_cache_ttl > 0.0:
            with self._lock:
                self._index_cache = (now, index)
        return index

    def close(self) -> None:
        with self._lock:
            pubs = list(self._open.values())
            self._open.clear()
        for pub in pubs:
            pub.close()
