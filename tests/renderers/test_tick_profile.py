"""End-to-end tick profiler + per-(domain, version) diagnosis cache.

Pins the r20 contracts (docs/developer_guide/diagnosis-engine.md):

* ``TICK_STAGES`` is a published vocabulary (dashboards and the bench
  key on the strings, like the INVALIDATE_* reasons);
* a tick whose diagnosis inputs did not change runs ZERO rules — the
  per-(domain, version) cache returns the previous DiagnosticResult
  object (``diag_cache_hits`` counts it, ``rule_eval_counts`` proves
  no rule evaluated);
* the profiler surfaces through ``window_build_stats()`` (and the final
  report's ``meta.window_build``), including the serving tier's
  per-fragment ``serialize`` stage — never through the live payload;
* with ``TRACEML_VECTOR_DIAGNOSIS=0`` the served payload bytes are
  byte-identical to the scalar legacy path (twin-session pin, same
  pattern as the ``TRACEML_INCR_WINDOW=0`` pin in
  tests/utils/test_incremental_window.py).
"""

import json
import random

from traceml_tpu.aggregator.sqlite_writer import SQLiteWriter
from traceml_tpu.diagnostics.common import rule_eval_counts
from traceml_tpu.renderers.compute import LiveComputer
from traceml_tpu.telemetry.envelope import (
    SenderIdentity,
    build_telemetry_envelope,
)
from traceml_tpu.utils import timing as T
from traceml_tpu.utils.columnar import TICK_STAGES, TickProfile
from traceml_tpu.utils.step_time_window import PHASES


# -- fixtures ------------------------------------------------------------


def _step_row(step, rng, clock="device"):
    step_ms = rng.uniform(40.0, 150.0)
    events = {
        T.STEP_TIME: {
            "cpu_ms": step_ms,
            "device_ms": step_ms * 0.97 if clock == "device" else None,
            "count": 1,
        }
    }
    for key, name in PHASES.items():
        if rng.random() < 0.15:
            continue
        v = rng.uniform(0.0, 25.0)
        events[name] = {
            "cpu_ms": v,
            "device_ms": v * 0.95 if key != "input" else None,
            "count": 1,
        }
    return {
        "step": step,
        "timestamp": 100.0 + step,
        "clock": clock,
        "late_markers": 0,
        "events": events,
    }


def _coll_rows(step, rng):
    rows = []
    for op in ("all_reduce", "all_gather", "reduce_scatter"):
        if rng.random() < 0.3:
            continue
        dur = rng.uniform(0.0, 8.0)
        rows.append({
            "step": step,
            "timestamp": 100.0 + step,
            "op": op,
            "dtype": rng.choice(("float32", "bfloat16")),
            "count": rng.randint(1, 4),
            "bytes": rng.randint(0, 1 << 22),
            "group_size": rng.choice((4, 8)),
            "duration_ms": dur,
            "exposed_ms": dur * rng.random(),
        })
    return rows


def _ident(rank=0, world=2):
    return SenderIdentity(
        session_id="s1",
        global_rank=rank,
        local_rank=rank,
        world_size=world,
        node_rank=0,
        hostname="host-0",
        pid=100 + rank,
    )


def _seed_session(db, steps=25):
    w = SQLiteWriter(db)
    w.start()
    for rank in (0, 1):
        w.ingest(build_telemetry_envelope(
            "step_time",
            {"step_time": [_step_row(s, random.Random(100 * rank + s))
                           for s in range(1, steps)]},
            _ident(rank),
        ))
        w.ingest(build_telemetry_envelope(
            "collectives",
            {"collectives": [row for s in range(1, steps)
                             for row in _coll_rows(s, random.Random(s))]},
            _ident(rank),
        ))
    assert w.force_flush()
    return w


def _model_stats_row(ts=200.0):
    return {
        "timestamp": ts,
        "flops_per_step": 1.0e12,
        "flops_source": "manual",
        "device_kind": "tpu-v4",
        "peak_flops": 2.75e14,
        "device_count": 2,
        "tokens_per_step": 1024.0,
    }


# -- stage vocabulary ----------------------------------------------------


def test_tick_stage_vocabulary_pinned():
    assert TICK_STAGES == (
        "refresh", "build", "diagnose", "attribute", "view", "serialize",
    )


def test_tick_profile_accumulates_and_snapshots():
    p = TickProfile()
    p.note_tick()
    p.note_stage("step_time", "build", 100)
    p.note_stage("step_time", "build", 50)
    p.note_stage("step_time", "diagnose", 7)
    p.bump("diag_cache_hits")
    p.bump("rule_evals", 3)
    snap = p.snapshot()
    assert snap["ticks"] == 1
    assert snap["stage_ns"]["step_time"] == {"build": 150, "diagnose": 7}
    assert snap["counters"] == {"diag_cache_hits": 1, "rule_evals": 3}


# -- diagnosis cache -----------------------------------------------------


def test_version_idle_tick_runs_zero_rules(tmp_path, monkeypatch):
    """A tick whose domain went dirty WITHOUT its diagnosis inputs
    changing (here: a model_stats-only ingest re-dirties step_time for
    the MFU block) must reuse the cached DiagnosticResult and evaluate
    zero rules."""
    monkeypatch.setenv("TRACEML_VECTOR_DIAGNOSIS", "1")
    db = tmp_path / "t.sqlite"
    w = _seed_session(db)
    computer = LiveComputer(db, window_steps=50)
    try:
        p1 = computer.payload()
        assert p1["step_time"]["diagnosis"] is not None
        prof = computer.store.tick_profile
        misses_before = prof.counters.get("diag_cache_misses", 0)
        assert misses_before > 0  # first tick diagnosed every domain

        w.ingest(build_telemetry_envelope(
            "step_time", {"model_stats": [_model_stats_row()]}, _ident(0),
        ))
        assert w.force_flush()

        evals_before = sum(rule_eval_counts().values())
        hits_before = prof.counters.get("diag_cache_hits", 0)
        p2 = computer.payload()
        assert p2 is not p1  # step_time went dirty → payload rebuilt
        # ... but its diagnosis is the SAME object, with zero rule runs
        assert p2["step_time"]["diagnosis"] is p1["step_time"]["diagnosis"]
        assert sum(rule_eval_counts().values()) == evals_before
        assert prof.counters.get("diag_cache_hits", 0) > hits_before
        assert prof.counters.get("diag_cache_misses", 0) == misses_before
    finally:
        computer.close()
        w.finalize()


def test_new_rows_invalidate_diagnosis_cache(tmp_path):
    db = tmp_path / "t.sqlite"
    w = _seed_session(db)
    computer = LiveComputer(db, window_steps=50)
    try:
        p1 = computer.payload()
        d1 = p1["step_time"]["diagnosis"]
        for rank in (0, 1):
            w.ingest(build_telemetry_envelope(
                "step_time",
                {"step_time": [_step_row(s, random.Random(999 + s))
                               for s in range(25, 30)]},
                _ident(rank),
            ))
        assert w.force_flush()
        evals_before = sum(rule_eval_counts().values())
        p2 = computer.payload()
        assert p2["step_time"]["diagnosis"] is not d1
        assert sum(rule_eval_counts().values()) > evals_before
    finally:
        computer.close()
        w.finalize()


def test_kill_switch_disables_diagnosis_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("TRACEML_VECTOR_DIAGNOSIS", "0")
    db = tmp_path / "t.sqlite"
    w = _seed_session(db)
    computer = LiveComputer(db, window_steps=50)
    try:
        p1 = computer.payload()
        w.ingest(build_telemetry_envelope(
            "step_time", {"model_stats": [_model_stats_row()]}, _ident(0),
        ))
        assert w.force_flush()
        p2 = computer.payload()
        # legacy behavior: the dirty domain re-diagnoses every tick
        assert p2["step_time"]["diagnosis"] is not p1["step_time"]["diagnosis"]
        prof = computer.store.tick_profile
        assert "diag_cache_hits" not in prof.counters
        assert "diag_cache_misses" not in prof.counters
    finally:
        computer.close()
        w.finalize()


# -- profiler surfacing --------------------------------------------------


def test_tick_profile_in_window_build_stats(tmp_path, monkeypatch):
    monkeypatch.setenv("TRACEML_VECTOR_DIAGNOSIS", "1")
    db = tmp_path / "t.sqlite"
    w = _seed_session(db)
    computer = LiveComputer(db, window_steps=50)
    try:
        computer.payload()
        stats = computer.store.window_build_stats()
        prof = stats["tick_profile"]
        assert prof["ticks"] >= 1
        assert set(prof["stage_ns"]["store"]) == {"refresh"}
        for domain in ("step_time", "collectives"):
            stages = prof["stage_ns"][domain]
            assert set(stages) <= set(TICK_STAGES)
            assert {"build", "diagnose", "attribute", "view"} <= set(stages)
        assert prof["counters"]["rule_evals"] > 0
        assert prof["counters"]["diag_cache_misses"] > 0
        # json-serializable end to end (meta fragment requirement)
        json.dumps(stats)
    finally:
        computer.close()
        w.finalize()


def test_serialize_stage_recorded_by_publisher(tmp_path):
    from traceml_tpu.renderers.serving import SessionPublisher

    db = tmp_path / "t.sqlite"
    w = _seed_session(db)
    pub = SessionPublisher(db, "s1", window_steps=50)
    try:
        pub.poll(force=True)
        prof = pub._computer.store.tick_profile.snapshot()
        ser_domains = [
            d for d, stages in prof["stage_ns"].items() if "serialize" in stages
        ]
        # every rebuilt fragment recorded its encode cost
        assert "step_time" in ser_domains and "meta" in ser_domains
    finally:
        pub.close()
        w.finalize()


# -- TRACEML_VECTOR_DIAGNOSIS=0 payload byte-pin -------------------------


def _payload_bytes(db):
    from traceml_tpu.renderers.web_payload import build_web_payload

    payload = build_web_payload(db, "s1")
    payload.pop("ts", None)  # wall-clock
    return json.dumps(payload, sort_keys=True).encode()


def test_vector_off_payload_bytes_identical(tmp_path, monkeypatch):
    """The vectorized arm must not change a single served byte: twin
    sessions, one polled with the kill switch off, one with it on —
    identical payloads (modulo wall-clock + the profiler block)."""
    db_a = tmp_path / "a" / "t.sqlite"
    db_b = tmp_path / "b" / "t.sqlite"
    db_a.parent.mkdir()
    db_b.parent.mkdir()
    _seed_session(db_a).finalize()
    _seed_session(db_b).finalize()

    monkeypatch.setenv("TRACEML_VECTOR_DIAGNOSIS", "0")
    off = _payload_bytes(db_a)
    monkeypatch.setenv("TRACEML_VECTOR_DIAGNOSIS", "1")
    on = _payload_bytes(db_b)
    assert off == on
