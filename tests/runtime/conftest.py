"""Fixtures of the runtime's step-row lane tests: a runtime whose full
tick is 5 s away, sending every frame to a :class:`FrameClient`."""

import threading
import time

import pytest

from traceml_tpu.runtime import runtime as runtime_mod
from traceml_tpu.runtime.identity import RuntimeIdentity
from traceml_tpu.runtime.runtime import TraceMLRuntime
from traceml_tpu.runtime.settings import AggregatorEndpoint, TraceMLSettings
from traceml_tpu.sdk import state as state_mod
from traceml_tpu.telemetry import normalize_telemetry_envelope
from traceml_tpu.utils import msgpack_codec
from traceml_tpu.utils.step_memory import FakeMemoryBackend, StepMemoryTracker
from traceml_tpu.utils.timing import GLOBAL_STEP_QUEUE, drain_step_memory_rows


class FrameClient:
    """Stands in for the transport client: keeps each frame's decoded
    payloads and its arrival time."""

    kind = "fake"

    def __init__(self):
        self.frames = []  # (monotonic arrival, [payload, ...])
        self._lock = threading.Lock()

    def send_batch(self, payloads):
        body = msgpack_codec.encode_batch(payloads)
        decoded, errors = msgpack_codec.decode_batch([body])
        assert errors == 0
        with self._lock:
            self.frames.append((time.monotonic(), decoded))
        return True

    def send_encoded_body(self, body):
        return True

    def close(self):
        pass

    def step_rows(self):
        """[(frame index, arrival, step, lane frame?)] over every
        step_time row sent; a lane frame carries step_time and no other
        sampler's envelope."""
        out = []
        with self._lock:
            frames = list(self.frames)
        for i, (at, payloads) in enumerate(frames):
            envs = [e for e in map(normalize_telemetry_envelope, payloads) if e]
            samplers = {e.sampler for e in envs}
            lane = samplers == {"step_time"}
            for e in envs:
                if e.sampler == "step_time":
                    out += [
                        (i, at, r["step"], lane)
                        for r in e.tables.get("step_time", [])
                    ]
        return out


@pytest.fixture
def lane_state():
    st = state_mod.reset_state_for_tests()
    st.mem_tracker = StepMemoryTracker(
        FakeMemoryBackend([[{"device_id": 0, "device_kind": "fake",
                             "current_bytes": 50, "peak_bytes": 60,
                             "limit_bytes": 100}]])
    )
    GLOBAL_STEP_QUEUE.drain()
    drain_step_memory_rows()
    yield st
    GLOBAL_STEP_QUEUE.drain()
    drain_step_memory_rows()


@pytest.fixture
def lane_runtime(tmp_path, monkeypatch, lane_state):
    """A started runtime and its FrameClient; stopped at teardown."""
    client = FrameClient()
    monkeypatch.setattr(
        runtime_mod, "create_transport_client",
        lambda settings, rank: (client, {"kind": "fake", "compression": None}),
    )
    rt = TraceMLRuntime(
        TraceMLSettings(session_id="lane", logs_dir=tmp_path, mode="summary",
                        aggregator=AggregatorEndpoint(port=1),
                        sampler_interval_sec=5.0),
        RuntimeIdentity(global_rank=0),
    )
    rt.start()
    try:
        yield rt, client
    finally:
        rt.stop()
