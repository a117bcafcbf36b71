"""The runtime's step-row lane: a flushed, resolved step leaves on its own
frame within the lane gap instead of waiting for the next full tick, in
step order, exactly once, coalesced to one frame per ``LANE_GAP_S``."""

import time

from traceml_tpu.runtime.runtime import LANE_GAP_S
from traceml_tpu.sdk.instrumentation import trace_step


class ReadyFlag:
    """A device output whose readiness the test decides."""

    size = 1

    def __init__(self):
        self.ready = False

    def is_ready(self):
        return self.ready


def _sampler(rt, name):
    return next(s for s in rt.samplers if s.name == name)


def test_lane_ships_a_resolved_step_before_the_full_tick(lane_runtime):
    rt, client = lane_runtime
    t0 = time.monotonic()
    with trace_step():
        pass  # no device marker: resolved at flush
    deadline = t0 + 0.3
    while time.monotonic() < deadline and not client.step_rows():
        time.sleep(0.005)
    rows = client.step_rows()
    assert [step for _, _, step, _ in rows] == [1]
    _, at, _, lane = rows[0]
    assert lane and at - t0 < 0.3
    # the lane ran the step-time sampler alone
    for name in ("system", "process", "step_memory"):
        s = _sampler(rt, name)
        assert s.db.appended_total() == 0, name
    stats = rt.publisher.stats()
    assert stats["lane_publishes"] == 1 and stats["lane_rows"] == 1
    assert stats["ticks"] == 0  # no full tick yet


def test_lane_rows_are_complete_ordered_and_coalesced(lane_runtime):
    rt, client = lane_runtime
    late = {}
    t0 = time.monotonic()
    for i in range(50):
        with trace_step() as ts:
            if i % 7 == 3:
                flag = late[i + 1] = ReadyFlag()
                ts.mark(flag)
            time.sleep(0.004)
        # a marked step's device work finishes three steps later
        if i - 2 in late:
            late[i - 2].ready = True
        if i == 25:
            # a full tick in the middle, serialized with the lane
            rt._run_paused_aware(rt._tick)
    span = time.monotonic() - t0
    for flag in late.values():
        flag.ready = True
    rt.stop()

    rows = client.step_rows()
    steps = [step for _, _, step, _ in rows]
    assert steps == list(range(1, 51))  # each once, strictly rising
    stats = rt.publisher.stats()
    lane_rows = [r for r in rows if r[3]]
    lane_frames = {i for i, _, _, lane in rows if lane}
    assert stats["lane_rows"] == len(lane_rows) > 0
    assert stats["lane_publishes"] == len(lane_frames)
    # coalesced: one lane frame per LANE_GAP_S of flushes, plus the first
    assert stats["lane_publishes"] <= max(4, int(span / LANE_GAP_S) + 2)
    assert _sampler(rt, "step_time").steps_emitted == 50
    # the full tick and the stop() drain shipped the rest
    assert stats["lane_rows"] < 50


def test_lane_waits_for_an_unresolved_marker_and_keeps_fifo(lane_runtime):
    rt, client = lane_runtime
    flag = ReadyFlag()
    with trace_step() as ts:
        ts.mark(flag)
    with trace_step():
        pass
    time.sleep(2 * LANE_GAP_S)
    assert client.step_rows() == []  # step 2 is resolved but waits behind 1
    flag.ready = True
    with trace_step():
        pass  # next flush wakes the lane; the enter sweep stamped step 1
    deadline = time.monotonic() + 0.5
    while time.monotonic() < deadline and len(client.step_rows()) < 3:
        time.sleep(0.005)
    assert [step for _, _, step, _ in client.step_rows()] == [1, 2, 3]
    assert rt.publisher.stats()["ticks"] == 0


def test_stop_removes_the_lane_callback(lane_runtime, lane_state):
    rt, _ = lane_runtime
    assert rt._wake_lane in lane_state.on_step_flushed
    rt.stop()
    assert rt._wake_lane not in lane_state.on_step_flushed
    assert rt.recording.on_step_flushed not in lane_state.on_step_flushed


def test_no_wake_is_lost_under_fast_thread_switches(lane_runtime):
    """The flush-side check-then-set of the wake event races the lane's
    clear; with the interpreter switching threads every microsecond,
    every row still leaves on the lane, none lost or repeated."""
    import sys

    rt, client = lane_runtime
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(300):
            with trace_step():
                pass
    finally:
        sys.setswitchinterval(old)
    deadline = time.monotonic() + 2.0
    while time.monotonic() < deadline and len(client.step_rows()) < 300:
        time.sleep(0.01)
    assert [step for _, _, step, _ in client.step_rows()] == list(range(1, 301))
    stats = rt.publisher.stats()
    assert stats["ticks"] == 0 and stats["lane_rows"] == 300
