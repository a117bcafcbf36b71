"""Overhead-governor policy tests.

The governor is the TPU answer to the reference's fixed "<1% overhead"
claim: observation cost is runtime-dependent (local probe ≈ µs, remote
PJRT probe ≈ RPC), so the sampling schedule must adapt.  These tests pin
the policy: cheap probes + realistic steps → full sampling; expensive
probes or tiny steps → stride growth, inline sweeps off, resolver
cadence floor.
"""

import threading

from traceml_tpu.sdk.instrumentation import trace_step
from traceml_tpu.sdk.state import reset_state_for_tests
from traceml_tpu.utils import timing as T
from traceml_tpu.utils.overhead_governor import (
    OverheadGovernor,
    get_governor,
    reset_governor_for_tests,
)


def teardown_module():
    reset_governor_for_tests()
    reset_state_for_tests()


class TestPolicy:
    def test_cheap_probes_realistic_steps_full_sampling(self):
        g = OverheadGovernor(budget=0.01)
        g.observe_probe(20e-6, 10)  # 2 µs/probe
        for _ in range(10):
            g.observe_step(0.150)  # 150 ms steps
        assert g.marker_stride == 1
        assert g.allow_inline_sweep()
        assert all(g.begin_step() for _ in range(8))

    def test_rpc_probes_grow_stride_and_disable_inline(self):
        g = OverheadGovernor(budget=0.01)
        for _ in range(20):
            g.observe_probe(300e-6, 1)  # RPC-priced probe
            g.observe_step(0.001)  # 1 ms dispatch-bound steps
        # per-marker ≈ 15µs + 3×300µs ≈ 0.92ms; budget share 10µs → ~92
        assert g.marker_stride > 20
        assert not g.allow_inline_sweep()
        sampled = sum(g.begin_step() for _ in range(g.marker_stride * 3))
        assert sampled == 3

    def test_tiny_steps_alone_grow_stride(self):
        g = OverheadGovernor(budget=0.01)
        g.observe_probe(2e-6, 1)
        for _ in range(10):
            g.observe_step(100e-6)  # 0.1 ms steps: fixed 15µs > 1µs budget
        assert g.marker_stride > 1

    def test_stride_clamped(self):
        g = OverheadGovernor(budget=0.001)
        for _ in range(30):
            g.observe_probe(5e-3, 1)
            g.observe_step(1e-4)
        assert g.marker_stride <= 256

    def test_resolver_floor_scales_with_probe_cost(self):
        g = OverheadGovernor(budget=0.01)
        for _ in range(30):
            g.observe_probe(400e-6, 1)
        assert g.resolver_min_delay() >= 0.02
        g2 = OverheadGovernor(budget=0.01)
        g2.observe_probe(2e-6, 1)
        assert g2.resolver_min_delay() < 0.002

    def test_starvation_artifacts_clamped_not_discarded(self):
        """A sample above the ceiling is clamped, not ignored: a
        descheduled poller's 40 ms artifact cannot poison the EMA past
        the ceiling, but a runtime whose probes are GENUINELY that slow
        must still drive the governor into full backoff (discarding
        would freeze the maximum-overhead configuration — the failure
        direction must be over-throttling, never blindness)."""
        g = OverheadGovernor(budget=0.01)
        g.observe_probe(0.04, 1)  # 40 ms "probe": artifact or disaster
        assert g.probe_cost_ema <= 20e-3 + 1e-9  # bounded by the ceiling
        assert g.probe_cost_ema > 1e-3  # but definitely not ignored
        # sustained slow probes → inline sweeps off, resolver backs off
        for _ in range(30):
            g.observe_probe(0.04, 1)
        assert not g.allow_inline_sweep()
        assert g.resolver_min_delay() == 0.1  # capped floor

    def test_resolver_floor_capped(self):
        g = OverheadGovernor(budget=0.001)
        for _ in range(50):
            g.observe_probe(10e-3, 1)  # worst believable probe cost
        assert g.resolver_min_delay() <= 0.1

    def test_budget_env_override(self, monkeypatch):
        monkeypatch.setenv("TRACEML_OVERHEAD_BUDGET", "0.05")
        g = OverheadGovernor()
        assert g.budget == 0.05

    def test_snapshot_shape(self):
        g = OverheadGovernor()
        g.observe_step(0.01)
        snap = g.snapshot()
        assert set(snap) == {
            "budget", "probe_cost_ema_us", "step_ema_ms",
            "marker_stride", "inline_sweep",
        }

    def test_thread_safe_observations(self):
        g = OverheadGovernor()

        def pound():
            for _ in range(500):
                g.observe_probe(10e-6, 2)
                g.observe_step(0.01)

        ts = [threading.Thread(target=pound) for _ in range(4)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert g.marker_stride >= 1


class TestHotPathIntegration:
    def test_unsampled_steps_emit_hostonly_rows(self):
        """With stride>1 the envelope still flows, just without device
        markers — the window builder then selects the host clock."""
        st = reset_state_for_tests()
        gov = reset_governor_for_tests(budget=0.01)
        # force an expensive-probe regime before any steps run
        for _ in range(30):
            gov.observe_probe(1e-3, 1)
            gov.observe_step(1e-3)
        stride = gov.marker_stride
        assert stride > 1

        class Ready:
            size = 1

            def is_ready(self):
                return True

        batches = []
        st.on_batch_flushed.append(batches.append)
        for _ in range(stride * 2):
            with trace_step(st) as ts:
                ts.mark(Ready())
        with_marker = sum(
            1
            for b in batches
            for ev in b.events
            if ev.name == T.STEP_TIME and ev.marker is not None
        )
        assert with_marker == 2  # one marked step per stride cycle
        assert len(batches) == stride * 2  # every step still produced rows
        reset_governor_for_tests()
        reset_state_for_tests()

    def test_gate_resets_after_unsampled_step(self):
        """Out-of-step instrumentation (eval loops) must never inherit an
        unsampled step's gate (code-review finding)."""
        from traceml_tpu.sdk.wrappers import wrap_forward

        st = reset_state_for_tests()
        gov = reset_governor_for_tests(budget=0.01)
        for _ in range(30):
            gov.observe_probe(1e-3, 1)
            gov.observe_step(1e-3)
        assert gov.marker_stride > 1

        class Ready:
            size = 1

            def is_ready(self):
                return True

        with trace_step(st):
            pass  # an unsampled step (stride > 1, tick 1)
        assert st.sample_markers is True  # reset on exit

        captured = []
        st.buffer.add = lambda ev: captured.append(ev)  # type: ignore
        fwd = wrap_forward(lambda: Ready(), state=st)
        fwd()  # out-of-step: must carry a marker
        assert captured and captured[-1].marker is not None
        reset_governor_for_tests()
        reset_state_for_tests()

    def test_chokepoint_drops_markers_on_unsampled_step(self):
        """publish_region_marker is the single gate: any site's marker
        (h2d, trace_time, Lightning) is dropped on an unsampled step."""
        from traceml_tpu.sdk.wrappers import publish_region_marker
        from traceml_tpu.utils.timing import DeviceMarker, TimeEvent

        st = reset_state_for_tests()

        class Ready:
            def is_ready(self):
                return False  # pending: would need resolver probes

        st.tls.in_step = True
        st.sample_markers = False
        ev = TimeEvent("x", 1)
        ev.marker = DeviceMarker([Ready()])
        publish_region_marker(ev, st)
        assert ev.marker is None  # dropped, never submitted
        st.tls.in_step = False
        reset_state_for_tests()

    def test_marker_skipped_when_gate_off(self):
        st = reset_state_for_tests()
        st.sample_markers = False

        class Ready:
            size = 1

            def is_ready(self):
                raise AssertionError("probe must not run when gate is off")

        with trace_step(st) as ts:
            st.sample_markers = False  # enter() recomputed it; force off
            ts.mark(Ready())  # must be inert, not raise
        reset_governor_for_tests()
        reset_state_for_tests()
