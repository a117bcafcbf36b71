"""Process-wide trace state
(reference: src/traceml_ai/runtime/state.py:27-91 + sdk/instrumentation.py:104-137).

Holds the step counter, the per-step event buffer, the step-memory
tracker, and the TLS gates the auto-timers consult.  Everything is
RLock-guarded; the hot-path reads are plain attribute loads on a
``threading.local``.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, List, Optional

from traceml_tpu.utils.self_trace import TimedRing
from traceml_tpu.utils.step_memory import StepMemoryTracker
from traceml_tpu.utils.timing import (
    GLOBAL_STEP_QUEUE,
    StepEventBuffer,
    StepTimeBatch,
    TimeEvent,
)


class _TLS(threading.local):
    def __init__(self) -> None:
        self.in_step = False
        self.forward_depth = 0
        self.backward_depth = 0
        self.h2d_depth = 0
        self.dataloader_depth = 0
        self.collective_depth = 0
        self.checkpoint_depth = 0


class TraceState:
    """Singleton-ish process state (tests may construct their own)."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self.tls = _TLS()
        self.step_counter = 0
        self.buffer = StepEventBuffer()
        self.mem_tracker: Optional[StepMemoryTracker] = None
        self.initialized = False
        self.patch_mode: Optional[str] = None
        self.active_step_event: Optional[TimeEvent] = None
        self.compile_events_seen = 0  # bumped by the compile tracker
        # (compile end, total ms) of each counted compile, so a reader
        # can count the compiles of any window of the run
        self.compile_ring = TimedRing(1024)
        # the tracer's own host time on the training thread: ns inside
        # trace_step's enter and exit and the step wrapper outside its
        # jitted call.  host_ns_open gathers the open step's share;
        # exit folds it into the totals and rings (step exit time, ns)
        self.host_ns = 0
        self.host_steps = 0
        self.host_ns_open = 0
        self.host_ns_ring = TimedRing(8192)
        # (emission time, ms) per step row the step-time sampler emits:
        # the wait from the step's flush to its row (sampler tick + the
        # FIFO hold until its device markers resolve)
        self.emit_waits = TimedRing(8192)
        # wall-clock of the previous trace_step exit: successive steps
        # tile the wall clock, so inter-step host time (input fetch in the
        # idiomatic `for batch in loader: with trace_step():` pattern) is
        # attributed to the step that consumes the batch
        self.last_step_exit: Optional[float] = None
        # per-step device-marker gate, set by trace_step.__enter__ from
        # the overhead governor; marker creators (wrap_step_fn, phase
        # wrappers) consult it so a whole step is either marked or not —
        # mixed rows would skew the window's clock selection
        self.sample_markers = True
        # model FLOPs per training step (set_step_flops / wrap_step_fn's
        # cost-analysis estimate) — the MFU numerator.  flops_source is
        # "manual" | "cost_analysis"; device_kind pins the chip whose
        # peak is the denominator.
        self.flops_per_step: Optional[float] = None
        self.flops_source: Optional[str] = None
        self.flops_device_kind: Optional[str] = None
        # addressable devices behind this process's dispatches: lowered
        # cost_analysis() FLOPs are for the whole (global, pre-partition)
        # program, so with one process driving N chips the MFU
        # denominator must be N × chip peak or the ratio inflates N×
        self.flops_device_count: Optional[int] = None
        # tokens consumed per training step (set_step_tokens): the
        # tokens/s numerator — the throughput number LLM capacity plans
        # quote; optional, independent of FLOPs
        self.tokens_per_step: Optional[float] = None
        # called with the step number after each flush (max-steps lifecycle)
        self.on_step_flushed: List[Callable[[int], None]] = []
        # called with the StepTimeBatch after each non-empty flush
        # (ICI telemetry hook and other batch observers)
        self.on_batch_flushed: List[Callable[[StepTimeBatch], None]] = []

    # -- step lifecycle ------------------------------------------------
    def begin_step(self) -> int:
        with self._lock:
            self.step_counter += 1
            return self.step_counter

    @property
    def current_step(self) -> int:
        with self._lock:
            return self.step_counter

    def note_step_host_ns(self, ns: int, now: float) -> None:
        """Close the step's tracer host time (training thread only)."""
        total = self.host_ns_open + ns
        self.host_ns_open = 0
        self.host_ns += total
        self.host_steps += 1
        self.host_ns_ring.add(now, total)

    def tracer_stats(self) -> dict:
        """Host time per step and the emission waits, for the
        publisher's ``stats()``."""
        steps = self.host_steps
        waits = self.emit_waits.summary()
        return {
            "host_ns": self.host_ns,
            "host_steps": steps,
            "host_us_per_step": self.host_ns / steps / 1e3 if steps else None,
            "emit_wait_ms_p50": waits["p50"],
            "emit_wait_ms_p95": waits["p95"],
            "compile_events_seen": self.compile_events_seen,
        }

    def ensure_mem_tracker(self) -> StepMemoryTracker:
        mt = self.mem_tracker  # tracelint: unguarded(double-checked init fast path; None race falls through to the locked slow path)
        if mt is not None:
            return mt
        with self._lock:
            if self.mem_tracker is None:
                self.mem_tracker = StepMemoryTracker()
            return self.mem_tracker

    def markers_enabled(self) -> bool:
        """THE device-marker gating policy, in one place.

        Sample markers when the governor chose to for this step, and
        always for out-of-step dispatches (eval loops etc. are not under
        the per-step stride — they carry no step envelope to skew).
        Every marker creator (wrap_step_fn, phase wrappers, trace_time,
        dataloader/h2d patches) must route through this so a whole step
        is either marked or not — a policy fork at one site would
        produce the mixed marked/unmarked rows the window's clock
        selection cannot tolerate.
        """
        return self.sample_markers or not self.tls.in_step

    def mark_step_outputs(self, outputs: Any) -> None:
        """Point the open step envelope's device marker at ``outputs``.

        Called by wrap_step_fn / wrappers after each device dispatch; the
        last call before step exit wins, so the envelope's device end is
        the readiness of the final dispatched phase.  Inert on steps the
        overhead governor chose not to device-sample.
        """
        if not self.sample_markers:
            return
        ev = self.active_step_event
        if ev is not None:
            ev.attach_marker(outputs)

    def flush_step(self, step: int) -> Optional[StepTimeBatch]:
        batch = self.buffer.flush(step)
        if batch is not None:
            GLOBAL_STEP_QUEUE.put(batch)
            for cb in list(self.on_batch_flushed):
                try:
                    cb(batch)
                except Exception:
                    pass
        for cb in list(self.on_step_flushed):
            try:
                cb(step)
            except Exception:
                pass
        return batch


_state = TraceState()


def get_state() -> TraceState:
    return _state


def reset_state_for_tests() -> TraceState:
    """Replace global state (test isolation only).  Also resets the
    overhead governor: its step EMA changes the marker resolver's poll
    schedule, so leaking it across tests makes timing-sensitive suites
    order-dependent."""
    global _state
    from traceml_tpu.utils.overhead_governor import reset_governor_for_tests

    reset_governor_for_tests()
    _state = TraceState()
    return _state
