"""Freshness stages inside the aggregator (aggregator/freshness.py): each
step_time envelope of rank 0 followed by its seq from frame arrival to
writer commit (ingest) to the first publish that shows its newest step
(serve); the block lands in ingest_stats.json and its summaries in the
dashboard's meta strip."""

import json
import time

import pytest

from traceml_tpu.aggregator.freshness import (
    FreshnessTracker,
    newest_step,
    summaries,
)
from traceml_tpu.aggregator.sqlite_writer import SQLiteWriter
from traceml_tpu.aggregator.trace_aggregator import TraceMLAggregator
from traceml_tpu.runtime.identity import RuntimeIdentity
from traceml_tpu.runtime.runtime import TraceMLRuntime
from traceml_tpu.runtime.settings import AggregatorEndpoint, TraceMLSettings
from traceml_tpu.sdk import state as state_mod
from traceml_tpu.sdk.instrumentation import trace_step
from traceml_tpu.telemetry.envelope import SenderIdentity, build_telemetry_envelope
from traceml_tpu.transport import TCPClient, TCPServer
from traceml_tpu.utils.step_memory import FakeMemoryBackend, StepMemoryTracker
from traceml_tpu.utils.timing import GLOBAL_STEP_QUEUE, drain_step_memory_rows


def _env(seq, steps, rank=0, sampler="step_time"):
    env = build_telemetry_envelope(
        sampler,
        {sampler: [{"step": s, "timestamp": float(s), "events": {}} for s in steps]},
        SenderIdentity(session_id="f", global_rank=rank),
    )
    env.meta["seq"] = seq
    return env


def test_newest_step():
    assert newest_step(_env(1, [4, 9, 7])) == 9
    assert newest_step(_env(1, [], sampler="system")) is None


def test_stages_follow_rank0_step_envelopes():
    tr = FreshnessTracker()
    a, b = _env(10, [1, 2]), _env(11, [3])
    other_rank, other_domain = _env(12, [3], rank=1), _env(13, [3], sampler="system")
    t0 = time.monotonic()
    for env in (a, b, other_rank, other_domain):
        if tr.follows(env):
            tr.arrived(env, t0)
    assert not tr.follows(other_rank) and not tr.follows(other_domain)
    tr.committed([a, b, other_rank])
    tr.published(2)  # shows a's newest step, not b's
    tr.published(3)
    tr.published(3)  # b already served: nothing more
    ingest = tr.rings["ingest"].samples()
    serve = tr.rings["serve"].samples()
    assert len(ingest) == 2 and len(serve) == 2
    assert all(ms >= 0 for _, ms in ingest + serve)
    block = tr.block()
    assert block["ingest"]["n"] == 2 and len(block["ingest"]["samples"]) == 2
    assert "samples" not in summaries(block)["serve"]
    assert summaries(block)["serve"]["n"] == 2


def test_publish_before_commit_serves_nothing():
    tr = FreshnessTracker()
    env = _env(5, [8])
    tr.arrived(env, time.monotonic())
    tr.published(8)
    assert tr.rings["serve"].samples() == []
    tr.committed([env])
    tr.published(8)
    assert len(tr.rings["serve"].samples()) == 1


def test_writer_commit_hook(tmp_path):
    seen = []
    w = SQLiteWriter(tmp_path / "t.sqlite", on_commit=lambda batch: seen.extend(batch))
    w.start()
    try:
        env = _env(3, [1])
        w.ingest(env)
        assert w.force_flush()
    finally:
        w.finalize()
    assert seen == [env]


def test_failing_hook_does_not_stop_the_writer(tmp_path):
    def boom(batch):
        raise RuntimeError("hook")

    w = SQLiteWriter(tmp_path / "t.sqlite", on_commit=boom)
    w.start()
    try:
        w.ingest(_env(3, [1]))
        w.ingest(_env(4, [2]))
        assert w.force_flush()
    finally:
        w.finalize()
    assert w.written == 2


def test_server_stamps_arrivals():
    server = TCPServer()
    server.start()
    try:
        client = TCPClient("127.0.0.1", server.port)
        before = time.monotonic()
        assert client.send_batch([{"x": 1}])
        assert client.send_batch([{"x": 2}])
        client.close()
        deadline = time.monotonic() + 5
        frames, times = [], []
        while len(frames) < 2 and time.monotonic() < deadline:
            server.wait_for_data(0.05)
            f, t = server.drain_timed()
            frames += f
            times += t
        assert len(frames) == len(times) == 2
        assert before <= times[0] <= times[1] <= time.monotonic()
    finally:
        server.stop()


@pytest.fixture()
def fresh_state():
    st = state_mod.reset_state_for_tests()
    st.mem_tracker = StepMemoryTracker(FakeMemoryBackend([[]]))
    GLOBAL_STEP_QUEUE.drain()
    drain_step_memory_rows()
    yield st
    GLOBAL_STEP_QUEUE.drain()
    drain_step_memory_rows()


def test_freshness_round_trip_over_loopback(tmp_path, fresh_state):
    """A rank's step rows through a real aggregator: the final
    ingest_stats.json carries both stages, each sample non-negative and
    stamped inside the run on this host's monotonic clock."""
    settings = TraceMLSettings(
        session_id="fresh", logs_dir=tmp_path, mode="summary",
        aggregator=AggregatorEndpoint(port=0), expected_world_size=1,
        finalize_timeout_sec=5.0,
    )
    t0 = time.monotonic()
    agg = TraceMLAggregator(settings)
    agg.start()
    runtime = TraceMLRuntime(
        TraceMLSettings(
            session_id="fresh", logs_dir=tmp_path, mode="summary",
            aggregator=AggregatorEndpoint(port=agg.port), sampler_interval_sec=0.05,
            transport="tcp",
        ),
        RuntimeIdentity(global_rank=0),
    )
    runtime.start()
    publisher = agg.registry.publisher(settings.session_id)
    try:
        for _ in range(12):
            with trace_step():
                time.sleep(0.02)
            publisher.poll(force=True)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not agg.freshness.rings["serve"].samples():
            time.sleep(0.05)
            publisher.poll(force=True)
    finally:
        runtime.stop()
        agg.stop()
    t1 = time.monotonic()
    block = json.loads((settings.session_dir / "ingest_stats.json").read_text())["freshness"]
    assert block["rank"] == 0
    for stage in ("ingest", "serve"):
        samples = block[stage]["samples"]
        assert samples and block[stage]["n"] == len(samples), stage
        assert all(t0 <= t <= t1 and ms >= 0 for t, ms in samples), stage
        assert 0 <= block[stage]["p50"] <= block[stage]["p95"]
    ring = agg.freshness.rings["ingest"]
    mid = block["ingest"]["samples"][len(block["ingest"]["samples"]) // 2][0]
    assert [list(s) for s in ring.samples(t0, mid)] == [
        s for s in block["ingest"]["samples"] if s[0] <= mid
    ]


def test_meta_strip_shows_stage_summaries(tmp_path):
    from traceml_tpu.renderers.web_payload import _meta_fragment

    tr = FreshnessTracker()
    env = _env(1, [1])
    tr.arrived(env, time.monotonic())
    tr.committed([env])
    tr.published(1)
    (tmp_path / "ingest_stats.json").write_text(
        json.dumps({"envelopes_ingested": 1, "freshness": tr.block()})
    )
    meta = _meta_fragment({"db_exists": True}, tmp_path)
    fresh = meta["ingest"]["freshness"]
    assert fresh["ingest"]["n"] == fresh["serve"]["n"] == 1
    assert "samples" not in fresh["ingest"] and "samples" not in fresh["serve"]
    assert "window_build" not in meta
