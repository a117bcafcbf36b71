"""JSON-able live payload for the browser dashboard
(reference pattern: renderers/<domain>/dashboard_compute.py).

One pipeline, N surfaces: the payload is derived from the SAME
``LiveComputer`` the CLI renders from (one load→views→diagnose pass per
version change regardless of how many dashboard tabs poll), with the
typed views serialized verbatim via ``as_dict()``.

Since the serving-tier split (docs/developer_guide/serving-tier.md) the
payload is built as PER-DOMAIN FRAGMENTS: each fragment owns a disjoint
set of top-level payload keys (``_FRAGMENT_KEYS``) and recomputes only
when the snapshot-store versions it depends on (``FRAGMENT_DEPS``)
advance.  ``build_web_payload`` composes every fragment back into the
flat dict the dashboard has always consumed — same keys, same order —
while the delta/SSE endpoints ship fragments individually, serialized
once per (fragment, version) by ``renderers/serving.py``.

The old module-global ``_computers`` cache (which closed EVERY cached
computer whenever a different db_path polled — one session per process)
is gone: computers now live inside the serving tier's keyed, LRU-bounded
publisher cache, so N sessions polling concurrently keep N live sqlite
connections instead of thrashing each other's.
"""

from __future__ import annotations

from pathlib import Path
from typing import Any, Dict, Tuple

PAYLOAD_VERSION = 3

#: fragment name → top-level payload keys it owns, in payload key order
#: (``header`` first; the assembler splices ``ts`` between header and
#: the domain fragments to preserve the historical key order)
_FRAGMENT_KEYS: Dict[str, Tuple[str, ...]] = {
    "header": ("version", "session"),
    "step_time": ("step_time",),
    "memory": ("memory",),
    "collectives": ("collectives",),
    "serving": ("serving",),
    "system": ("system",),
    "process": ("process",),
    "stdout": ("stdout",),
    "history": ("history",),
    "diagnosis": ("diagnosis", "findings"),
    "meta": ("ingest", "rank_status", "mesh", "regressions"),
}

#: serving order — also the position of each counter in the version token
FRAGMENT_ORDER: Tuple[str, ...] = tuple(_FRAGMENT_KEYS)

#: fragment → snapshot-store domains whose ``data_version`` gates its
#: recompute.  ``diagnosis`` joins every diagnosing domain (the composed
#: findings list can reorder when any of them moves).  ``header`` is
#: constant and ``meta`` is file-backed (ingest_stats/rank_status json),
#: so both are content-compared instead of version-gated.
FRAGMENT_DEPS: Dict[str, Tuple[str, ...]] = {
    "step_time": ("step_time", "model_stats", "topology"),
    "memory": ("step_memory", "topology"),
    "collectives": ("collectives", "step_time", "topology"),
    "serving": ("serving", "topology"),
    "system": ("system", "topology"),
    "process": ("process",),
    "stdout": ("stdout",),
    "history": ("rollup", "step_time"),
    "diagnosis": (
        "step_time", "model_stats", "topology", "step_memory",
        "collectives", "serving", "system", "process",
    ),
}


def _issue_dict(issue: Any) -> Dict[str, Any]:
    from traceml_tpu.diagnostics.common import confidence_label

    out = {
        "kind": issue.kind,
        "severity": issue.severity,
        "summary": issue.summary,
        "action": issue.action,
        "confidence": getattr(issue, "confidence", None),
        "confidence_label": confidence_label(
            getattr(issue, "confidence", None)
        ),
    }
    # topology attribution rides only when present: pre-topology
    # sessions serialize the exact historical shape (back-compat pin)
    attribution = getattr(issue, "attribution", None)
    if attribution:
        out["attribution"] = attribution
    return out


def _view_fragment(payload: Dict[str, Any], key: str) -> Dict[str, Any]:
    view = (payload.get("views") or {}).get(key)
    return {key: view.as_dict() if view is not None else None}


def _serving_fragment(payload: Dict[str, Any]) -> Dict[str, Any]:
    """Unlike the training domains, ``serving`` omits its key entirely
    when the session recorded no serving rows: a training-only session's
    payload (and the final report derived from it) must stay
    byte-identical to the pre-serving-domain shape."""
    view = (payload.get("views") or {}).get("serving")
    if view is None:
        return {}
    return {"serving": view.as_dict()}


def _history_fragment(payload: Dict[str, Any]) -> Dict[str, Any]:
    """Full-run history strip (stitched rollup tiers).  Like
    ``serving``, the key is omitted entirely until the first fold lands
    — a short run's payload keeps the pre-rollup shape byte-identical."""
    history = payload.get("history")
    if not history or not isinstance(history, dict):
        return {}
    if not history.get("step_time"):
        return {}
    return {"history": history}


def _diagnosis_fragment(payload: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {"diagnosis": None, "findings": []}
    if not payload.get("db_exists"):
        return out
    st_result = (payload.get("step_time") or {}).get("diagnosis")
    if st_result is not None:
        out["diagnosis"] = _issue_dict(st_result.diagnosis)
    domain_results = {
        "step_time": st_result,
        "step_memory": payload.get("step_memory_diagnosis"),
        "collectives": (payload.get("collectives") or {}).get("diagnosis"),
        "serving": (payload.get("serving") or {}).get("diagnosis"),
        "system": payload.get("system_diagnosis"),
        "process": payload.get("process_diagnosis"),
    }
    try:
        from traceml_tpu.diagnostics.model_diagnostics import compose

        composed = compose(domain_results)
        out["findings"] = [
            dict(_issue_dict(i), domain=i.evidence.get("domain", "?"))
            for i in composed.issues[:8]
        ]
    except Exception:
        pass
    return out


def _meta_fragment(
    payload: Dict[str, Any], session_dir: Path
) -> Dict[str, Any]:
    """Aggregator self-metrics for the dashboard meta strip: backpressure
    (queue depth/hwm, per-domain sheds), writer latency, and the per-rank
    liveness strip — live, not just in the post-run summary."""
    out: Dict[str, Any] = {}
    if not payload.get("db_exists"):
        return out
    try:
        from traceml_tpu.reporting.loaders import (
            load_ingest_stats,
            load_rank_status,
        )

        stats = load_ingest_stats(session_dir)
        if stats:
            out["ingest"] = {
                k: stats[k]
                for k in (
                    "envelopes_ingested", "rows_dropped", "drop_warnings",
                    "dropped_by_domain", "unknown_domain_drops", "queues",
                    "group_commit", "prune", "corrupt_frame_drops",
                    "replay_duplicates",
                    "pending_frames_hwm", "producers", "transports", "ts",
                )
                if k in stats
            }
            # where the view's lag goes inside the aggregator: the
            # ingest and serve stages' summaries, without their samples
            if isinstance(stats.get("freshness"), dict):
                from traceml_tpu.aggregator.freshness import summaries

                out["ingest"]["freshness"] = summaries(stats["freshness"])
        # per-rank liveness strip (ACTIVE/STALE/LOST/FINISHED): the
        # dashboard shows which ranks a live dip is actually averaging
        status = load_rank_status(session_dir)
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
    # mesh strip: the compact axes/source/host-count block the topology
    # reader attached to the store snapshot — only when a mesh was
    # captured (the meta fragment is content-compared, so a late mesh
    # message republishes it; absent key == pre-topology shape)
    mesh = (payload.get("topology") or {}).get("mesh")
    if mesh:
        out["mesh"] = mesh
    # cross-run regression verdict (analytics/baselines.py): written at
    # finalize as regressions.json; served live so a dashboard left open
    # shows the verdict the moment the run completes.  Absent file ==
    # absent key (pre-baseline sessions keep their exact shape).
    try:
        from traceml_tpu.reporting.loaders import load_regressions

        regressions = load_regressions(session_dir)
        if regressions:
            out["regressions"] = regressions
    except Exception:
        pass
    return out


def build_fragment(
    name: str,
    payload: Dict[str, Any],
    *,
    session: str,
    db_path: Path,
) -> Dict[str, Any]:
    """One fragment's top-level payload keys, built from a
    ``LiveComputer.payload()`` result.  Fragments are plain JSON-able
    dicts — the serving tier serializes each exactly once per version."""
    if name == "header":
        return {"version": PAYLOAD_VERSION, "session": session}
    if name in ("step_time", "memory", "collectives", "system", "process"):
        return _view_fragment(payload, name)
    if name == "serving":
        return _serving_fragment(payload)
    if name == "stdout":
        return {
            "stdout": [
                {"stream": s, "line": l}
                for s, l in (payload.get("stdout") or [])
            ]
        }
    if name == "history":
        return _history_fragment(payload)
    if name == "diagnosis":
        return _diagnosis_fragment(payload)
    if name == "meta":
        return _meta_fragment(payload, Path(db_path).parent)
    raise KeyError(name)


def build_web_payload(
    db_path: Path, session: str, window_steps: int = 150
) -> Dict[str, Any]:
    """The flat full payload (legacy full-poll shape) — every fragment
    merged in historical key order, plus a fresh ``ts``.  Reads through
    the serving tier's publisher cache, so dashboard polls share the
    per-(fragment, version) work with the delta/SSE endpoints."""
    from traceml_tpu.renderers.serving import publisher_for

    pub = publisher_for(Path(db_path), session, window_steps=window_steps)
    return pub.full_payload_dict()
