"""The tracer traced from inside, on the CPU: a tiny traced step loop
with the runtime agent ticking, under ``jax.profiler.trace``.  The trace
holds every ``traceml/`` span — the per-step ones on the training thread
with their step argument, the tick, sample and publish spans on the
runtime's thread — and ``TraceState`` counts the tracer's own host time
per step and each step row's emission wait."""

import time

import pytest

from traceml_tpu.runtime.identity import RuntimeIdentity
from traceml_tpu.runtime.runtime import TraceMLRuntime
from traceml_tpu.runtime.settings import AggregatorEndpoint, TraceMLSettings
from traceml_tpu.sdk import state as state_mod
from traceml_tpu.sdk.instrumentation import trace_step
from traceml_tpu.sdk.step_fn import wrap_step_fn
from traceml_tpu.utils.self_trace import SPAN_NAMES
from traceml_tpu.utils.timing import GLOBAL_STEP_QUEUE, drain_step_memory_rows

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

STEPS = 6
PER_STEP = ("traceml/step_enter", "traceml/wrap", "traceml/step_exit")
BACKGROUND = ("traceml/sampler_tick", "traceml/sample", "traceml/publish")


@pytest.fixture()
def fresh_state():
    st = state_mod.reset_state_for_tests()
    GLOBAL_STEP_QUEUE.drain()
    drain_step_memory_rows()
    yield st
    GLOBAL_STEP_QUEUE.drain()
    drain_step_memory_rows()


def _traced_loop(tmp_path):
    runtime = TraceMLRuntime(
        TraceMLSettings(
            session_id="spans", logs_dir=tmp_path / "logs", mode="summary",
            aggregator=AggregatorEndpoint(port=0), sampler_interval_sec=0.05,
        ),
        RuntimeIdentity(global_rank=0),
    )
    step = wrap_step_fn(lambda w, x: (w - 0.01 * jax.grad(lambda w: jnp.sum((x @ w) ** 2))(w),))
    w, x = jnp.ones((8, 8)), jnp.ones((4, 8))
    with trace_step():  # compile outside the trace
        (w,) = step(w, x)
    runtime.start()
    try:
        with jax.profiler.trace(str(tmp_path / "trace")):
            for _ in range(STEPS):
                with trace_step() as ts:
                    (w,) = step(w, x)
                float(w[0, 0])
                time.sleep(0.04)  # ~5 runtime ticks in all
                seen = ts.step
    finally:
        runtime.stop()
    return runtime, seen


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    (path,) = sorted(trace_dir.rglob("*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        # one line per thread; Python threads' lines share the name
        # "python", so a line is told apart by its place in the plane
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("traceml/"):
                    spans.append((ev.name, (plane.name, i), dict(ev.stats)))
    return spans


def test_trace_holds_every_span_with_its_step(tmp_path, fresh_state):
    runtime, last = _traced_loop(tmp_path)
    spans = _host_spans(tmp_path / "trace")
    assert {n for n, _, _ in spans} == set(SPAN_NAMES)
    first = last - STEPS + 1
    for name in PER_STEP:
        steps = sorted(a["step"] for n, _, a in spans if n == name)
        assert steps == list(range(first, last + 1)), name
    main = {line for n, line, _ in spans if n in PER_STEP}
    assert len(main) == 1
    assert {line for n, line, _ in spans if n in BACKGROUND}.isdisjoint(main)
    samplers = {a["sampler"] for n, _, a in spans if n == "traceml/sample"}
    assert {"step_time", "step_memory"} <= samplers
    mem = [a for n, line, a in spans if n == "traceml/mem_read" and line in main]
    assert mem and all(first <= a["step"] <= last for a in mem)


def test_host_time_and_emission_waits_counted(tmp_path, fresh_state):
    t0 = time.monotonic()
    runtime, last = _traced_loop(tmp_path)
    st = fresh_state
    assert st.host_steps == last == STEPS + 1
    assert st.host_ns_open == 0
    ring = st.host_ns_ring.samples()
    assert len(ring) == st.host_steps and sum(v for _, v in ring) == st.host_ns
    assert all(v > 0 for _, v in ring)
    waits = st.emit_waits.samples(t0, time.monotonic())
    assert len(waits) == STEPS + 1
    assert all(ms >= 0 for _, ms in waits)
    stats = runtime.publisher.stats()["tracer"]
    assert stats["host_steps"] == STEPS + 1
    assert stats["host_us_per_step"] == pytest.approx(st.host_ns / st.host_steps / 1e3)
    assert stats["emit_wait_ms_p95"] >= stats["emit_wait_ms_p50"] >= 0
