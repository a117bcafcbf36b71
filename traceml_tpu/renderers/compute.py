"""Per-tick data computation for live views
(reference pattern: renderers/<domain>/computer.py — SQLite → typed view,
cached per tick so multiple panels share one read).

``LiveComputer.payload()`` returns a dict holding BOTH the typed views
(``views.*``, the schema every surface renders from — see views.py) and
the per-domain diagnosis results.  Raw loader output is only kept where a
diagnostic consumes it directly.

Incremental read path: data comes from a :class:`LiveSnapshotStore`
(persistent read-only connection, per-table id cursors, decode-once
bounded deques) and each domain's views + diagnosis recompute ONLY when
the store's per-domain ``data_version`` advanced — replacing the seed's
blind 0.4 s TTL cache.  An idle tick (no new envelopes) performs zero
SQLite row reads and returns the identical cached payload object (only
``ts`` is refreshed in place).
"""

from __future__ import annotations

import threading
import time
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

from traceml_tpu.diagnostics.attribution import attribution_ns_total
from traceml_tpu.diagnostics.common import rule_eval_counts
from traceml_tpu.diagnostics.step_time.api import diagnose_window
from traceml_tpu.renderers import views as V
from traceml_tpu.reporting.snapshot_store import LiveSnapshotStore
from traceml_tpu.utils.columnar import vector_diagnosis_enabled

# payload domain → (store versions it depends on, views key or None)
# collectives also depends on step_time: COMM_BOUND needs the mean step
# duration as the denominator for the exposed-comm share
_DOMAIN_DEPS: Dict[str, Tuple[Tuple[str, ...], Optional[str]]] = {
    "topology": (("topology",), None),
    "step_time": (("step_time", "model_stats", "topology"), "step_time"),
    # memory/collectives also depend on topology: a late mesh_topology
    # message must re-run their diagnoses so attribution attaches
    "memory": (("step_memory", "topology"), "memory"),
    "collectives": (("collectives", "step_time", "topology"), "collectives"),
    # serving also depends on topology: REPLICA_SKEW attaches mesh
    # attribution, so a late mesh_topology message must re-run it
    "serving": (("serving", "topology"), "serving"),
    "system": (("system", "topology"), "system"),
    "process": (("process",), "process"),
    "stdout": (("stdout",), None),
    # full-run history strip: stitched rollup tiers + the raw step_time
    # tail (the stitch re-folds surviving raw rows, so new raw steps
    # move the series even between prunes)
    "history": (("rollup", "step_time"), None),
}


class LiveComputer:
    """Reads the session SQLite through an incremental snapshot store
    and produces the per-domain payloads the renderers consume; each
    domain recomputes only when its tables changed (dirty-gated)."""

    def __init__(self, db_path: Path, window_steps: int = 120) -> None:
        self.db_path = Path(db_path)
        self.window_steps = window_steps
        self._store = LiveSnapshotStore(self.db_path, window_steps=window_steps)
        self._lock = threading.RLock()
        self._cache: Optional[Dict[str, Any]] = None
        # domain → versions tuple the cached fragment was computed at
        self._computed_at: Dict[str, Tuple[int, ...]] = {}
        # domain → (payload updates, view object or None)
        self._fragments: Dict[str, Tuple[Dict[str, Any], Any]] = {}
        # per-(domain, version-key) diagnosis cache: a dirty DOMAIN tick
        # whose diagnosis INPUTS did not change (collectives re-runs on
        # every step_time advance, but its diagnosis only reads the
        # median step ms, which is usually bit-stable between ticks)
        # reuses the previous DiagnosticResult and runs ZERO rules.
        # Disabled with TRACEML_VECTOR_DIAGNOSIS=0 (legacy behavior).
        self._diag_cache: Dict[str, Tuple[Any, Any]] = {}
        # r20 O(Δ)-aware memos (also flag-gated): window OBJECTS per
        # store version (a model_stats-only tick must not re-construct
        # the 1024-rank step_time window, and _compute_collectives
        # shares the step_time window instead of building it twice),
        # the derived median step ms, the step_time view's per-rank
        # tables, and the whole collectives result (its re-dirty via
        # the step_time dep usually changes nothing it reads)
        self._window_memo: Dict[str, Tuple[Tuple, Any]] = {}
        self._step_ms_memo: Optional[Tuple[Tuple, Optional[float]]] = None
        self._st_tables_memo: Dict[str, Any] = {}
        self._coll_memo: Optional[Tuple[Tuple, Tuple[Dict[str, Any], Any]]] = None

    @property
    def store(self) -> LiveSnapshotStore:
        return self._store

    def close(self) -> None:
        self._store.close()

    def payload(self) -> Dict[str, Any]:
        with self._lock:
            prof = self._store.tick_profile
            try:
                t0 = time.perf_counter_ns()
                self._store.refresh()
                prof.note_stage("store", "refresh", time.perf_counter_ns() - t0)
            except Exception:
                pass
            if not self._store.connected:
                # DB not there yet (or vanished): cheap constant payload
                return {
                    "ts": time.time(),
                    "db_exists": self.db_path.exists(),
                    "views": {},
                }
            prof.note_tick()
            versions = self._store.versions
            dirty = [
                domain
                for domain, (deps, _) in _DOMAIN_DEPS.items()
                if self._computed_at.get(domain)
                != tuple(versions[d] for d in deps)
            ]
            if not dirty and self._cache is not None:
                self._cache["ts"] = time.time()  # idle tick: same object
                self._attach_rank_status(self._cache)
                return self._cache
            for domain in dirty:
                deps, _ = _DOMAIN_DEPS[domain]
                self._fragments[domain] = self._compute_domain(domain)
                self._computed_at[domain] = tuple(versions[d] for d in deps)
            out: Dict[str, Any] = {
                "ts": time.time(),
                "db_exists": True,
                "views": {},
            }
            for domain, (_, view_key) in _DOMAIN_DEPS.items():
                updates, view = self._fragments.get(domain, ({}, None))
                out.update(updates)
                if view is not None and view_key is not None:
                    out["views"][view_key] = view
            self._attach_rank_status(out)
            self._cache = out
            return out

    def payload_with_versions(
        self,
    ) -> Tuple[Dict[str, Any], Dict[str, int]]:
        """Payload plus the store versions it was computed at, read
        atomically under the lock — the serving tier keys its serialized
        fragment cache on these, so the pair must be consistent."""
        with self._lock:
            payload = self.payload()
            return payload, dict(self._store.versions)

    def _attach_rank_status(self, out: Dict[str, Any]) -> None:
        """Liveness strip, refreshed EVERY tick (never dirty-gated): a
        lost rank's state changes exactly when its DB writes stop, so
        gating on table versions would freeze the strip at ACTIVE.  The
        loader is (mtime, size)-cached, so idle ticks cost one stat."""
        try:
            from traceml_tpu.reporting.loaders import load_rank_status

            status = load_rank_status(self.db_path.parent)
            if status and isinstance(status.get("ranks"), dict):
                out["rank_status"] = {
                    "ts": status.get("ts"),
                    "thresholds": status.get("thresholds"),
                    "states": {
                        r: (info or {}).get("state")
                        for r, info in status["ranks"].items()
                        if isinstance(info, dict)
                    },
                }
        except Exception:
            pass

    def _mesh_topology(self):
        """The store's merged MeshTopology, or None — passed into every
        diagnose call so findings attach physical attribution when a
        mesh was captured (fail-open: attribution is garnish)."""
        try:
            return self._store.mesh_topology()
        except Exception:
            return None

    def _diagnose_cached(self, domain: str, cache_key: Tuple, build):
        """Run a pack's diagnose under the per-(domain, version-key)
        cache and the tick profiler.  ``cache_key`` must capture every
        diagnosis input that can change between ticks (store versions
        of the tables the pack reads, plus value-level inputs like the
        collectives step-time denominator); a key match returns the
        previous DiagnosticResult without evaluating a single rule.
        The profiler splits the pack's attribution time out of the
        diagnose stage via the module-level ns accumulator."""
        prof = self._store.tick_profile
        if vector_diagnosis_enabled():
            hit = self._diag_cache.get(domain)
            if hit is not None and hit[0] == cache_key:
                prof.bump("diag_cache_hits")
                return hit[1]
        r0 = sum(rule_eval_counts().values())
        a0 = attribution_ns_total()
        t0 = time.perf_counter_ns()
        result = build()
        total_ns = time.perf_counter_ns() - t0
        attr_ns = attribution_ns_total() - a0
        prof.note_stage(domain, "diagnose", max(0, total_ns - attr_ns))
        prof.note_stage(domain, "attribute", attr_ns)
        prof.bump("rule_evals", sum(rule_eval_counts().values()) - r0)
        if vector_diagnosis_enabled():
            prof.bump("diag_cache_misses")
            self._diag_cache[domain] = (cache_key, result)
        return result

    def _window_cached(self, domain: str, key: Tuple, build):
        """Build (and stage-time) a window object, memoized per store
        version — reused across ticks whose backing rows did not change
        and across the two call sites that read the step_time window.
        Safe because a version match means the ring buffers the window's
        arrays alias were not written since the build."""
        prof = self._store.tick_profile
        if vector_diagnosis_enabled():
            hit = self._window_memo.get(domain)
            if hit is not None and hit[0] == key:
                prof.bump("window_memo_hits")
                return hit[1]
        t0 = time.perf_counter_ns()
        window = build()
        prof.note_stage(domain, "build", time.perf_counter_ns() - t0)
        if vector_diagnosis_enabled():
            prof.bump("window_memo_misses")
            self._window_memo[domain] = (key, window)
        return window

    def _median_step_ms(self, versions: Dict[str, int]) -> Optional[float]:
        """Cross-rank median step ms (the collectives share denominator),
        memoized per step_time version — ``metric()`` re-reduces the
        whole cube on every call otherwise."""
        key = (versions["step_time"],)
        if (
            vector_diagnosis_enabled()
            and self._step_ms_memo is not None
            and self._step_ms_memo[0] == key
        ):
            return self._step_ms_memo[1]
        step_time_ms: Optional[float] = None
        try:
            st = self._window_cached(
                "step_time", key,
                lambda: self._store.build_step_time_window(
                    max_steps=self.window_steps
                ),
            )
            if st is not None:
                m = st.metric("step_time")
                if m is not None and m.median_ms > 0:
                    step_time_ms = m.median_ms
        except Exception:
            pass
        if vector_diagnosis_enabled():
            self._step_ms_memo = (key, step_time_ms)
        return step_time_ms

    # -- per-domain builders ---------------------------------------------
    # Each returns (top-level payload updates, typed view or None) and
    # mirrors the seed's error contract: a failing domain degrades to an
    # {"error": ...} marker without poisoning the other domains.

    def _compute_domain(self, domain: str) -> Tuple[Dict[str, Any], Any]:
        return getattr(self, f"_compute_{domain}")()

    def _compute_topology(self) -> Tuple[Dict[str, Any], Any]:
        try:
            return {"topology": self._store.topology()}, None
        except Exception:
            return {"topology": {}}, None

    def _compute_step_time(self) -> Tuple[Dict[str, Any], Any]:
        world = int((self._store.topology() or {}).get("world_size") or 0)
        prof = self._store.tick_profile
        try:
            versions = self._store.versions
            # columnar window build straight off the store's ring
            # buffers (scalar fallback inside the store when a rank's
            # buffer is flagged); no per-tick row-dict walk, and the
            # window OBJECT is version-memoized (a model_stats-only
            # tick reuses it outright)
            window = self._window_cached(
                "step_time", (versions["step_time"],),
                lambda: self._store.build_step_time_window(
                    max_steps=self.window_steps
                ),
            )
            # newest telemetry timestamp drives the staleness badge
            latest = self._store.latest_step_time_ts()
            try:
                model_stats = self._store.model_stats()
            except Exception:
                model_stats = {}
            # the view's per-rank tables are pure window functions —
            # memoize them per step_time version so a model_stats-only
            # tick rebuilds only the MFU block (scalar arm: None →
            # full legacy rebuild)
            table_cache = None
            if vector_diagnosis_enabled():
                tkey = (versions["step_time"],)
                if self._st_tables_memo.get("key") != tkey:
                    self._st_tables_memo = {"key": tkey}
                elif "tables" in self._st_tables_memo:
                    prof.bump("view_table_hits")
                table_cache = self._st_tables_memo
            t0 = time.perf_counter_ns()
            view = V.build_step_time_view(
                window, world_size=world, latest_ts=latest,
                model_stats=model_stats, table_cache=table_cache,
            )
            prof.note_stage("step_time", "view", time.perf_counter_ns() - t0)
            updates = {
                "latest_row_ts": latest,
                "step_time": {
                    "window": window,
                    # the diagnosis reads only the window + mesh, so it
                    # keys on those versions — a model_stats-only tick
                    # (the MFU block) reuses the cached result
                    "diagnosis": self._diagnose_cached(
                        "step_time",
                        (versions["step_time"], versions["topology"]),
                        lambda: diagnose_window(
                            window, mode="live",
                            topology=self._mesh_topology(),
                        ),
                    )
                    if self._store.has_step_time_rows()
                    else None,
                },
            }
            return updates, view
        except Exception as exc:
            return {"step_time": {"error": str(exc)}}, None

    def _compute_memory(self) -> Tuple[Dict[str, Any], Any]:
        prof = self._store.tick_profile
        try:
            versions = self._store.versions
            t0 = time.perf_counter_ns()
            mem_rows = self._store.step_memory_rows()
            mem_cols = self._store.step_memory_columns()
            prof.note_stage("memory", "build", time.perf_counter_ns() - t0)
            t0 = time.perf_counter_ns()
            view = V.build_memory_view(mem_rows, columns=mem_cols)
            prof.note_stage("memory", "view", time.perf_counter_ns() - t0)
            from traceml_tpu.diagnostics.step_memory.api import (
                diagnose_columns as diagnose_memory_columns,
                diagnose_rank_rows as diagnose_memory,
            )

            mesh = self._mesh_topology()
            key = (versions["step_memory"], versions["topology"])
            if mem_cols is not None:
                diagnosis = self._diagnose_cached(
                    "memory", key,
                    lambda: diagnose_memory_columns(mem_cols, topology=mesh),
                )
            else:
                diagnosis = (
                    self._diagnose_cached(
                        "memory", key,
                        lambda: diagnose_memory(mem_rows, topology=mesh),
                    )
                    if mem_rows else None
                )
            updates = {
                "step_memory": mem_rows,
                "step_memory_diagnosis": diagnosis,
            }
            return updates, view
        except Exception as exc:
            return {"step_memory": {"error": str(exc)}}, None

    def _compute_collectives(self) -> Tuple[Dict[str, Any], Any]:
        prof = self._store.tick_profile
        try:
            versions = self._store.versions
            # the share denominator first: dirty-gating re-runs this
            # domain on EVERY step_time advance, but everything below
            # only reads the MEDIAN step ms — so the whole result is
            # memoized on (collectives, topology, median) and a tick
            # that left those bit-stable returns the previous
            # (updates, view) pair without touching the window
            step_time_ms = self._median_step_ms(versions)
            rkey = (
                versions["collectives"],
                versions["topology"],
                step_time_ms,
            )
            if (
                vector_diagnosis_enabled()
                and self._coll_memo is not None
                and self._coll_memo[0] == rkey
            ):
                prof.bump("domain_memo_hits")
                return self._coll_memo[1]
            window = self._window_cached(
                "collectives", (versions["collectives"],),
                lambda: self._store.build_collectives_window(
                    max_steps=self.window_steps
                ),
            )
            t0 = time.perf_counter_ns()
            view = V.build_collectives_view(window, step_time_ms=step_time_ms)
            prof.note_stage("collectives", "view", time.perf_counter_ns() - t0)
            from traceml_tpu.diagnostics.collectives.api import (
                diagnose_collectives_window,
            )

            updates = {
                "collectives": {
                    "window": window,
                    "diagnosis": self._diagnose_cached(
                        "collectives",
                        rkey,
                        lambda: diagnose_collectives_window(
                            window, mode="live", step_time_ms=step_time_ms,
                            topology=self._mesh_topology(),
                        ),
                    )
                    if self._store.has_collectives_rows()
                    else None,
                },
            }
            if vector_diagnosis_enabled():
                self._coll_memo = (rkey, (updates, view))
            return updates, view
        except Exception as exc:
            return {"collectives": {"error": str(exc)}}, None

    def _compute_serving(self) -> Tuple[Dict[str, Any], Any]:
        prof = self._store.tick_profile
        try:
            versions = self._store.versions
            t0 = time.perf_counter_ns()
            window = self._store.build_serving_window(
                max_steps=self.window_steps
            )
            prof.note_stage("serving", "build", time.perf_counter_ns() - t0)
            t0 = time.perf_counter_ns()
            view = V.build_serving_view(
                window, latest_ts=self._store.latest_serving_ts()
            )
            prof.note_stage("serving", "view", time.perf_counter_ns() - t0)
            from traceml_tpu.diagnostics.serving.api import (
                diagnose_serving_window,
            )

            updates = {
                "serving": {
                    "window": window,
                    "diagnosis": self._diagnose_cached(
                        "serving",
                        (versions["serving"], versions["topology"]),
                        lambda: diagnose_serving_window(
                            window, mode="live",
                            topology=self._mesh_topology(),
                        ),
                    )
                    if self._store.has_serving_rows()
                    else None,
                },
            }
            return updates, view
        except Exception as exc:
            return {"serving": {"error": str(exc)}}, None

    def _compute_system(self) -> Tuple[Dict[str, Any], Any]:
        nodes = int((self._store.topology() or {}).get("nodes") or 0)
        try:
            host, devices = self._store.system_rows()
            view = V.build_system_view(host, devices, expected_nodes=nodes)
            from traceml_tpu.diagnostics.system.api import (
                diagnose as diagnose_system,
            )

            updates = {
                "system": {"host": host, "devices": devices},
                "system_diagnosis": diagnose_system(host, devices)
                if host or devices
                else None,
            }
            return updates, view
        except Exception as exc:
            return {"system": {"error": str(exc)}}, None

    def _compute_process(self) -> Tuple[Dict[str, Any], Any]:
        try:
            procs, pdevs = self._store.process_rows()
            view = V.build_process_view(procs)
            from traceml_tpu.diagnostics.process.api import (
                diagnose as diagnose_process,
            )

            updates = {
                "process": {"procs": procs, "devices": pdevs},
                "process_diagnosis": diagnose_process(procs, pdevs)
                if procs or pdevs
                else None,
            }
            return updates, view
        except Exception as exc:
            return {"process": {"error": str(exc)}}, None

    def _compute_stdout(self) -> Tuple[Dict[str, Any], Any]:
        try:
            return {"stdout": self._store.stdout_tail()}, None
        except Exception:
            return {"stdout": []}, None

    def _compute_history(self) -> Tuple[Dict[str, Any], Any]:
        """Full-run step-time history for the dashboard strip: stitched
        rank-grain series (raw tail + 10s + 1m tiers), downsampled to a
        cross-rank mean/min/max band per bucket.  {} until the first
        fold lands (short runs never show the strip)."""
        try:
            if not self._store.has_rollups():
                return {"history": {}}, None
            series = self._store.stitched_series(
                "step_time_samples", "step_ms"
            )
            if not series:
                return {"history": {}}, None
            band: Dict[float, Dict[str, Any]] = {}
            for points in series.values():
                for p in points:
                    if p.get("mean") is None:
                        continue
                    slot = band.get(p["t"])
                    if slot is None:
                        band[p["t"]] = {
                            "t": p["t"], "mean_sum": p["mean"], "ranks": 1,
                            "min": p["min"], "max": p["max"], "res": p["res"],
                        }
                    else:
                        slot["mean_sum"] += p["mean"]
                        slot["ranks"] += 1
                        slot["min"] = min(slot["min"], p["min"])
                        slot["max"] = max(slot["max"], p["max"])
            points = [
                {
                    "t": s["t"],
                    "mean_ms": s["mean_sum"] / s["ranks"],
                    "min_ms": s["min"],
                    "max_ms": s["max"],
                    "res": s["res"],
                }
                for s in (band[t] for t in sorted(band))
            ]
            return {
                "history": {
                    "step_time": {"points": points, "ranks": len(series)},
                }
            }, None
        except Exception as exc:
            return {"history": {"error": str(exc)}}, None
