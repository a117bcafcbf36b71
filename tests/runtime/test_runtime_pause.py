import time
import pytest


def test_runtime_pause_resume(tmp_path):
    from traceml_tpu.runtime.runtime import TraceMLRuntime
    from traceml_tpu.runtime.identity import RuntimeIdentity
    from traceml_tpu.runtime.settings import AggregatorEndpoint, TraceMLSettings

    rt = TraceMLRuntime(
        TraceMLSettings(session_id="p", logs_dir=tmp_path, mode="summary",
                        aggregator=AggregatorEndpoint(port=1),
                        sampler_interval_sec=0.05),
        RuntimeIdentity(global_rank=0),
    )
    rt.start()
    try:
        time.sleep(0.3)
        step_sampler = next(s for s in rt.samplers if s.name == "system")
        # pause FIRST (it blocks on any in-flight tick), then read the
        # baseline — reading before pausing races the 50ms tick thread
        rt.pause()
        before = step_sampler.db.append_count("system")
        time.sleep(0.4)
        paused_count = step_sampler.db.append_count("system")
        assert paused_count == before  # no sampling while paused
        rt.resume()
        time.sleep(0.4)
        assert step_sampler.db.append_count("system") > paused_count
    finally:
        rt.stop()


def test_pause_holds_lane_rows_until_resume(lane_runtime):
    """Steps flushed while paused are neither sampled nor published by
    the step-row lane; resume() ships every one of them, in order."""
    from traceml_tpu.sdk.instrumentation import trace_step
    from traceml_tpu.utils.timing import GLOBAL_STEP_QUEUE

    rt, client = lane_runtime
    rt.pause()
    for _ in range(5):
        with trace_step():
            pass
    time.sleep(0.3)
    assert client.step_rows() == []
    assert GLOBAL_STEP_QUEUE.qsize() == 5  # not even drained
    rt.resume()
    deadline = time.monotonic() + 0.5
    while time.monotonic() < deadline and len(client.step_rows()) < 5:
        time.sleep(0.005)
    assert [step for _, _, step, _ in client.step_rows()] == [1, 2, 3, 4, 5]
    assert rt.publisher.stats()["ticks"] == 0  # the lane, not a tick
