"""``trace_step`` — the per-step bracket
(reference: src/traceml_ai/sdk/instrumentation.py:140-233).

One ``with trace_step():`` per optimizer step:

* advances the step counter (outermost-only; nesting is a no-op),
* records the step-start memory edge,
* opens the ``step_time`` envelope region,
* arms the TLS gates the auto-timers consult,
* on exit: closes the envelope, records the step-end memory edge,
  flushes the step's events into the global queue, and submits device
  markers to the background resolver.

Enter and exit each run inside a ``traceml/step_enter`` /
``traceml/step_exit`` profiler span (utils/self_trace.py) and add their
host nanoseconds to the step's tracer cost on ``TraceState``.

Never raises into user code; a failure downgrades to a no-op step.
"""

from __future__ import annotations

import time
from typing import Any, Optional

from traceml_tpu.sdk.state import TraceState, get_state
from traceml_tpu.sdk.wrappers import publish_region_marker
from traceml_tpu.utils.error_log import get_error_log
from traceml_tpu.utils.marker_resolver import get_marker_resolver
from traceml_tpu.utils.overhead_governor import get_governor
from traceml_tpu.utils.self_trace import span
from traceml_tpu.utils.timing import STEP_TIME, TimeEvent, timed_region


class trace_step:
    """Context manager bracketing one optimizer step."""

    def __init__(self, state: Optional[TraceState] = None) -> None:
        self._state = state or get_state()
        self._region: Optional[timed_region] = None
        self._step: Optional[int] = None
        self._outermost = False

    @property
    def step(self) -> Optional[int]:
        return self._step

    def mark(self, outputs: Any) -> Any:
        """Attach the step's device-completion probe (explicit form).

        ``wrap_step_fn`` calls this automatically; manual loops may call
        ``ts.mark(new_state)`` themselves.
        """
        try:
            self._state.mark_step_outputs(outputs)
        except Exception as exc:
            get_error_log().warning("trace_step.mark failed", exc)
        return outputs

    def __enter__(self) -> "trace_step":
        st = self._state
        try:
            if st.tls.in_step:
                return self  # nested: inert (reference: outermost-only)
            t0 = time.perf_counter_ns()
            self._outermost = True
            with span("traceml/step_enter", st.current_step + 1):
                self._enter(st)
            st.host_ns_open += time.perf_counter_ns() - t0
        except Exception as exc:
            get_error_log().warning("trace_step enter failed", exc)
        return self

    def _enter(self, st: TraceState) -> None:
        gov = get_governor()
        # Stamp the previous step's markers from this thread before
        # opening a new step — see MarkerResolver.sweep_inline.  On
        # expensive-probe runtimes (remote PJRT: is_ready is an RPC)
        # the governor moves stamping off the critical path to the
        # background resolver instead.
        if gov.allow_inline_sweep():
            get_marker_resolver().sweep_inline()
        st.drain_counters(time.monotonic())
        st.sample_markers = gov.begin_step()
        st.tls.in_step = True
        self._step = st.begin_step()
        st.ensure_mem_tracker().reset(self._step)
        self._region = timed_region(STEP_TIME, self._step, sink=st.buffer.add)
        self._region.__enter__()
        # Back-date the envelope to the previous step's exit so steps
        # tile the wall clock: the inter-step gap (input fetch, logging)
        # lands in THIS step's envelope, where its dataloader_next /
        # user events already land via the flush ordering.
        if st.last_step_exit is not None:
            self._region.event.cpu_start = st.last_step_exit
        st.active_step_event = self._region.event

    def __exit__(self, exc_type, exc, tb) -> bool:
        if not self._outermost:
            return False
        t0 = time.perf_counter_ns()
        st = self._state
        step = self._step if self._step is not None else st.current_step
        try:
            with span("traceml/step_exit", step):
                self._exit(st, step, exc_type, exc, tb)
        except Exception as err:
            get_error_log().warning("trace_step exit failed", err)
        finally:
            # out-of-step instrumentation (eval loops) must never inherit
            # an unsampled step's gate
            st.sample_markers = True
            st.note_step_host_ns(time.perf_counter_ns() - t0, time.monotonic())
        return False

    def _exit(self, st: TraceState, step: int, exc_type, exc, tb) -> None:
        st.tls.in_step = False
        if self._region is not None:
            self._region.__exit__(exc_type, exc, tb)
            st.last_step_exit = self._region.event.cpu_end
            ev = self._region.event
            if ev.cpu_start is not None and ev.cpu_end is not None:
                get_governor().observe_step(ev.cpu_end - ev.cpu_start)
        st.active_step_event = None
        if exc_type is None:
            st.ensure_mem_tracker().record(step)
        batch = st.flush_step(step)
        if batch is not None:
            resolver = get_marker_resolver()
            for ev in batch.events:
                if ev.marker is not None and not ev.marker.resolved:
                    resolver.submit(ev.marker)


class trace_time:
    """Named user region inside a step
    (reference: sdk/instrumentation.py trace_time — user-visible custom
    phases land in the same event stream, prefixed ``user:``)."""

    def __init__(self, name: str, state: Optional[TraceState] = None) -> None:
        self._state = state or get_state()
        self._name = f"user:{name}"
        self._region: Optional[timed_region] = None

    def mark(self, outputs: Any) -> Any:
        st = self._state
        if self._region is not None and st.markers_enabled():
            self._region.mark(outputs)
        return outputs

    def __enter__(self) -> "trace_time":
        try:
            st = self._state
            self._region = timed_region(
                self._name, st.current_step, sink=st.buffer.add
            )
            self._region.__enter__()
        except Exception as exc:
            get_error_log().warning("trace_time enter failed", exc)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        try:
            if self._region is not None:
                self._region.__exit__(exc_type, exc, tb)
                # a marked user region behaves like every other phase
                # owner: envelope hand-off (a last-dispatch user region
                # must extend the step's device end) + dispatch-time
                # resolver submission
                publish_region_marker(self._region.event, self._state)
        except Exception as err:
            get_error_log().warning("trace_time exit failed", err)
        return False
