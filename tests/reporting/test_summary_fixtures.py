"""Summary fixture battery — end-state verdict coverage across many
injected data shapes (reference: tests/reporting/summary/
test_fixtures.py — schema stability under empty/partial/misaligned
inputs, single- and multi-rank coverage, per-section contracts).

Multi-rank is a DATA shape here (rows injected per rank through the
real SQLiteWriter), so the battery runs in milliseconds."""

import json

import pytest

from traceml_tpu.aggregator.sqlite_writer import SQLiteWriter
from traceml_tpu.reporting.final import generate_summary
from traceml_tpu.runtime.settings import TraceMLSettings
from traceml_tpu.telemetry.envelope import SenderIdentity, build_telemetry_envelope
from traceml_tpu.utils import timing as T

GiB = 1024**3

SECTIONS = ("system", "process", "step_time", "step_memory")


def _step_row(step, step_ms=100.0, input_ms=5.0, compute_ms=90.0,
              collective_ms=None, clock="device"):
    events = {
        T.STEP_TIME: {"cpu_ms": step_ms,
                      "device_ms": step_ms if clock == "device" else None,
                      "count": 1},
        T.DATALOADER_NEXT: {"cpu_ms": input_ms, "device_ms": None, "count": 1},
        T.COMPUTE_TIME: {"cpu_ms": 0.5,
                         "device_ms": compute_ms if clock == "device" else None,
                         "count": 1},
    }
    if collective_ms is not None:
        events[T.COLLECTIVE_TIME] = {
            "cpu_ms": collective_ms, "device_ms": collective_ms, "count": 1
        }
    return {"step": step, "timestamp": float(step), "clock": clock,
            "events": events}


class _Session:
    """One injected session: write envelopes, generate, read payload."""

    def __init__(self, tmp_path, session="fx"):
        self.dir = tmp_path
        self.session = session
        self.writer = SQLiteWriter(tmp_path / "telemetry.sqlite")
        self.writer.start()

    def ident(self, rank, world=1, node=0):
        return SenderIdentity(
            session_id=self.session, global_rank=rank, world_size=world,
            node_rank=node, hostname=f"host{node}", pid=100 + rank,
        )

    def inject(self, sampler, tables, ident):
        self.writer.ingest(build_telemetry_envelope(sampler, tables, ident))

    def payload(self):
        self.writer.force_flush()
        self.writer.finalize()
        settings = TraceMLSettings(session_id=self.session, logs_dir=self.dir)
        assert generate_summary(
            self.dir / "telemetry.sqlite", self.dir, settings, mode="summary"
        )
        return json.loads((self.dir / "final_summary.json").read_text())


def _assert_schema_stable(payload):
    """Every section exists with the status/diagnosis/issues contract
    regardless of what data arrived."""
    assert payload["schema"].startswith("traceml-tpu/")
    for key in SECTIONS:
        sec = payload["sections"][key]
        assert sec["status"] in ("OK", "NO_DATA")
        assert "issues" in sec
        if sec["status"] == "OK":
            assert sec["diagnosis"] is not None
            assert sec["issues"][0] == sec["diagnosis"]  # documented invariant
            assert "global" in sec


def test_empty_db_stable_schema(tmp_path):
    s = _Session(tmp_path)
    payload = s.payload()
    _assert_schema_stable(payload)
    assert all(
        payload["sections"][k]["status"] == "NO_DATA" for k in SECTIONS
    )
    assert payload["primary_diagnosis"]["kind"] == "INSUFFICIENT_STEP_TIME_DATA"


def test_step_time_only_other_sections_degrade(tmp_path):
    s = _Session(tmp_path)
    s.inject("step_time",
             {"step_time": [_step_row(i) for i in range(1, 61)]}, s.ident(0))
    payload = s.payload()
    _assert_schema_stable(payload)
    assert payload["sections"]["step_time"]["status"] == "OK"
    assert payload["sections"]["system"]["status"] == "NO_DATA"
    assert payload["sections"]["process"]["status"] == "NO_DATA"


def test_host_clock_run(tmp_path):
    """No device timing anywhere → host clock selected, still diagnosable."""
    s = _Session(tmp_path)
    rows = [_step_row(i, input_ms=60.0, clock="host") for i in range(1, 61)]
    s.inject("step_time", {"step_time": rows}, s.ident(0))
    payload = s.payload()
    g = payload["sections"]["step_time"]["global"]
    assert g["clock"] == "host"
    assert g["median_occupancy"] is None  # no device data → no occupancy
    assert payload["sections"]["step_time"]["diagnosis"]["kind"] == "INPUT_BOUND"


def test_misaligned_ranks_use_common_suffix(tmp_path):
    """Rank 1 joined late: the window is the common suffix only."""
    s = _Session(tmp_path)
    s.inject("step_time",
             {"step_time": [_step_row(i) for i in range(1, 81)]},
             s.ident(0, world=2))
    s.inject("step_time",
             {"step_time": [_step_row(i) for i in range(41, 81)]},
             s.ident(1, world=2))
    payload = s.payload()
    g = payload["sections"]["step_time"]["global"]
    assert g["step_range"][0] >= 41
    assert g["ranks"] == [0, 1]


def test_missing_rank_reported_in_topology(tmp_path):
    s = _Session(tmp_path)
    for rank in (0, 1, 3):  # rank 2 never reports
        s.inject("step_time",
                 {"step_time": [_step_row(i) for i in range(1, 41)]},
                 s.ident(rank, world=4))
    payload = s.payload()
    topo = payload["meta"]["topology"]
    assert topo["world_size"] == 4
    assert sorted(topo["ranks_seen"]) == [0, 1, 3]


def test_collective_phase_in_summary(tmp_path):
    s = _Session(tmp_path)
    rows = [_step_row(i, step_ms=120.0, compute_ms=60.0, collective_ms=50.0)
            for i in range(1, 61)]
    s.inject("step_time", {"step_time": rows}, s.ident(0))
    payload = s.payload()
    phases = payload["sections"]["step_time"]["global"]["phases"]
    assert phases["collective"]["median_ms"] == pytest.approx(50.0)
    assert phases["collective"]["share_of_step"] == pytest.approx(50.0 / 120.0)


def test_memory_without_limits(tmp_path):
    """CPU/remote runtimes have no bytes_limit — pressure is None, no
    pressure verdicts, schema intact."""
    s = _Session(tmp_path)
    mem = [{"step": i, "timestamp": float(i), "device_id": 0,
            "device_kind": "cpu", "current_bytes": 1 * GiB,
            "peak_bytes": 1 * GiB, "step_peak_bytes": 1 * GiB,
            "limit_bytes": None, "backend": "live_arrays"}
           for i in range(1, 61)]
    s.inject("step_memory", {"step_memory": mem}, s.ident(0))
    payload = s.payload()
    rank0 = payload["sections"]["step_memory"]["global"]["per_rank"]["0"]
    assert rank0["pressure"] is None
    kinds = {i["kind"] for i in payload["sections"]["step_memory"]["issues"]}
    assert "HIGH_MEMORY_PRESSURE" not in kinds


def test_multi_node_cluster_rollup_in_summary(tmp_path):
    s = _Session(tmp_path)
    for node, cpu in ((0, 20.0), (1, 80.0)):
        sysrows = [{"timestamp": float(i), "cpu_pct": cpu,
                    "memory_used_bytes": 4 * GiB, "memory_total_bytes": 16 * GiB,
                    "memory_pct": 25.0, "load_1m": 1.0}
                   for i in range(30)]
        s.inject("system", {"system": sysrows}, s.ident(node * 4, world=8, node=node))
    payload = s.payload()
    cluster = payload["sections"]["system"]["global"]["cluster"]
    assert cluster["n_nodes"] == 2
    assert cluster["cpu_pct_max"] == pytest.approx(80.0)
    assert cluster["busiest_node"] == "host1"


def test_per_rank_identity_blocks_two_nodes(tmp_path):
    """Section per-rank groups carry identity blocks (reference:
    SCHEMA.md groups.rows[*].identity) — hostname/node placement is
    readable straight off a rank row in a multi-node summary."""
    s = _Session(tmp_path)
    for rank, node in ((0, 0), (1, 0), (2, 1), (3, 1)):
        ident = s.ident(rank, world=4, node=node)
        s.inject(
            "step_time",
            {"step_time": [_step_row(i) for i in range(1, 25)]},
            ident,
        )
        s.inject(
            "process",
            {"process": [{"timestamp": 1.0, "cpu_pct": 10.0,
                          "rss_bytes": GiB, "num_threads": 8}]},
            ident,
        )
        s.inject(
            "step_memory",
            {"step_memory": [{"step": i, "timestamp": float(i),
                              "device_id": 0, "device_kind": "tpu",
                              "current_bytes": GiB, "peak_bytes": GiB,
                              "step_peak_bytes": GiB, "limit_bytes": 16 * GiB}
                             for i in range(1, 10)]},
            ident,
        )
    payload = s.payload()
    for section, rank_key in (
        ("step_time", "per_rank"),
        ("step_memory", "per_rank"),
        ("process", "per_rank"),
    ):
        per_rank = payload["sections"][section]["global"][rank_key]
        assert set(per_rank) == {"0", "1", "2", "3"}, section
        for rank, node in (("0", 0), ("2", 1)):
            ident = per_rank[rank]["identity"]
            assert ident is not None, (section, rank)
            assert ident["hostname"] == f"host{node}"
            assert ident["node_rank"] == node
            assert ident["world_size"] == 4
    # section-local text cards (reference SCHEMA `card`) carry the
    # per-rank detail including placement
    for section in ("step_time", "step_memory", "process"):
        card = payload["sections"][section]["card"]
        assert "rank 2" in card and "[host1#1]" in card, (section, card)


def test_mfu_in_step_time_section(tmp_path):
    """model_stats telemetry → achieved TFLOP/s + MFU in the summary.

    100 ms steps at 10 TFLOP/step → 100 TFLOP/s achieved; on a v5p
    (459 TFLOP/s peak) that is ~21.8% MFU."""
    s = _Session(tmp_path)
    ident = s.ident(0)
    s.inject(
        "step_time",
        {"step_time": [_step_row(i, step_ms=100.0) for i in range(1, 41)],
         "model_stats": [{"timestamp": 1.0, "flops_per_step": 10e12,
                          "flops_source": "cost_analysis",
                          "device_kind": "TPU v5p", "peak_flops": 459e12}]},
        ident,
    )
    payload = s.payload()
    eff = payload["sections"]["step_time"]["global"]["efficiency"]
    assert eff is not None
    assert eff["achieved_tflops_median"] == pytest.approx(100.0, rel=0.05)
    assert eff["mfu_median"] == pytest.approx(100.0 / 459.0, rel=0.05)
    assert eff["device_kind"] == "TPU v5p"
    txt = (tmp_path / "final_summary.txt").read_text()
    assert "TFLOP/s" in txt and "MFU" in txt


def test_no_model_stats_no_efficiency(tmp_path):
    s = _Session(tmp_path)
    s.inject("step_time", {"step_time": [_step_row(i) for i in range(1, 30)]},
             s.ident(0))
    payload = s.payload()
    assert payload["sections"]["step_time"]["global"]["efficiency"] is None


def test_garbage_rows_do_not_break_summary(tmp_path):
    """Rows with missing/None fields degrade gracefully, never throw."""
    s = _Session(tmp_path)
    rows = [
        {"step": 1, "timestamp": 1.0, "clock": "device", "events": {}},
        {"step": None, "timestamp": None, "clock": None, "events": None},
        _step_row(2),
    ]
    s.inject("step_time", {"step_time": rows}, s.ident(0))
    s.inject("step_memory", {"step_memory": [{"step": 1}]}, s.ident(0))
    payload = s.payload()
    _assert_schema_stable(payload)


def test_single_step_run(tmp_path):
    """One step: below every diagnosis gate, still schema-valid."""
    s = _Session(tmp_path)
    s.inject("step_time", {"step_time": [_step_row(1)]}, s.ident(0))
    payload = s.payload()
    _assert_schema_stable(payload)
    st = payload["sections"]["step_time"]
    assert st["global"]["n_steps"] == 1
    assert st["global"]["steady_state"] is None  # needs ≥12 steps
    assert payload["primary_diagnosis"]["kind"] in (
        "INSUFFICIENT_STEP_TIME_DATA", "NO_CLEAR_PERFORMANCE_BOTTLENECK",
        "HEALTHY", "COMPUTE_BOUND",
    )


def test_occupancy_low_run_yields_low_util_verdict(tmp_path):
    s = _Session(tmp_path)
    rows = []
    for i in range(1, 61):
        # chip busy = phase device (18) / host step (100) = 18%
        rows.append(_step_row(i, step_ms=100.0, compute_ms=18.0))
    s.inject("step_time", {"step_time": rows}, s.ident(0))
    payload = s.payload()
    g = payload["sections"]["step_time"]["global"]
    assert g["median_occupancy"] == pytest.approx(0.18)
    kinds = {i["kind"] for i in payload["sections"]["step_time"]["issues"]}
    assert "LOW_DEVICE_UTILIZATION" in kinds


# -- reference feature parity (VERDICT r3 item 3) --------------------------
# field-by-field against the reference builders' output features:
# sections/step_time/builder.py (card Stats/Ranks lines, BaseGlobal
# rollup), sections/step_memory/model.py (median/worst {value, idx}
# points with closest-rank-to-median), compare/verdict.py (ladder —
# covered by tests/reporting/test_compare_engine.py).  Intentional
# omissions are documented in PARITY.md §2.9.

def _multirank_session(tmp_path, n=4):
    s = _Session(tmp_path)
    for rank in range(n):
        rows = [
            _step_row(i, step_ms=100.0 + rank * 20, input_ms=5.0 + rank * 18)
            for i in range(1, 61)
        ]
        s.inject("step_time", {"step_time": rows}, s.ident(rank, world=n))
        s.inject("step_memory", {"step_memory": [
            {"step": 60, "timestamp": 60.0, "device_id": 0,
             "device_kind": "tpu", "current_bytes": (8 + rank) << 30,
             "peak_bytes": (9 + rank) << 30,
             "step_peak_bytes": (9 + rank) << 30,
             "limit_bytes": 16 << 30, "backend": "fake"}
        ]}, s.ident(rank, world=n))
        s.inject("process", {"process": [
            {"timestamp": 60.0, "pid": 100 + rank,
             "cpu_pct": 40.0 + rank * 10, "rss_bytes": (1 + rank) << 30,
             "num_threads": 5}
        ]}, s.ident(rank, world=n))
    return s.payload()


def test_step_time_rollup_has_median_and_worst_rank_attribution(tmp_path):
    payload = _multirank_session(tmp_path)
    rollup = payload["sections"]["step_time"]["global"]["rollup"]
    assert rollup["index_by"] == "global_rank"
    assert rollup["window"]["alignment"] == "common_steps"
    assert rollup["window"]["steps_analyzed"] > 0
    step = rollup["worst"]["step_time"]
    # rank 3 is slowest by construction; median idx must name a real rank
    assert step["idx"] == "3" and step["value"] > 150
    med = rollup["median"]["step_time"]
    assert med["idx"] in {"1", "2"} and med["value"] is not None
    assert rollup["average"]["step_time"] is not None


def test_step_time_card_has_stats_and_ranks_lines(tmp_path):
    payload = _multirank_session(tmp_path)
    card = payload["sections"]["step_time"]["card"]
    assert "stats (median/worst):" in card
    assert "ranks (median/worst):" in card
    # both ends name a concrete rank (rN/rM)
    import re
    assert re.search(r"step_time r\d+/r3", card), card


def test_step_memory_rollup_points(tmp_path):
    payload = _multirank_session(tmp_path)
    rollup = payload["sections"]["step_memory"]["global"]["rollup"]
    worst = rollup["worst"]["step_peak_bytes"]
    assert worst["idx"] == "3" and worst["value"] == (12 << 30)
    assert rollup["median"]["step_peak_bytes"]["idx"] is not None
    # pre-existing rollup fields retained alongside the uniform block
    assert rollup["max_peak_bytes"] == (12 << 30)


def test_process_rollup_points(tmp_path):
    payload = _multirank_session(tmp_path)
    rollup = payload["sections"]["process"]["global"]["rollup"]
    assert rollup["worst"]["rss_bytes"]["idx"] == "3"
    assert rollup["busiest_rank"] == "3"


def test_rollup_handles_missing_and_nonfinite():
    from traceml_tpu.reporting.rollup import build_rollup

    r = build_rollup({
        "m": {"0": 1.0, "1": float("nan"), "2": None, "3": 3.0},
        "empty": {},
    })
    assert r["worst"]["m"] == {"value": 3.0, "idx": "3"}
    assert r["average"]["m"] == 2.0
    assert r["median"]["empty"] == {"value": None, "idx": None}


def test_rollup_tie_breaks_deterministic():
    from traceml_tpu.reporting.rollup import build_rollup

    r = build_rollup({"m": {"5": 2.0, "1": 2.0, "3": 2.0}})
    # equal values: worst → smallest rank id; median idx likewise stable
    assert r["worst"]["m"]["idx"] == "1"
    assert r["median"]["m"]["idx"] == "1"


def test_tokens_per_step_flows_to_efficiency(tmp_path):
    """set_step_tokens → model_stats row → SQLite → loader → the
    efficiency block's tokens_per_sec_median (full pipeline)."""
    s = _Session(tmp_path)
    s.inject("step_time",
             {"step_time": [_step_row(i, step_ms=100.0) for i in range(1, 61)]},
             s.ident(0))
    s.inject("step_time", {"model_stats": [
        {"timestamp": 1.0, "flops_per_step": 50e12,
         "flops_source": "manual", "device_kind": "TPU v5p",
         "peak_flops": 459e12, "device_count": 1,
         "tokens_per_step": 8192.0}
    ]}, s.ident(0))
    payload = s.payload()
    eff = payload["sections"]["step_time"]["global"]["efficiency"]
    assert eff["tokens_per_step"] == 8192.0
    # steady-state median step is 100 ms → 81,920 tokens/s
    assert abs(eff["tokens_per_sec_median"] - 81920.0) < 1.0
    assert "tokens/s" in payload["sections"]["step_time"]["card"]
