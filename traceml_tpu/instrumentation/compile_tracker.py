"""Process-wide XLA compile attribution via ``jax.monitoring``.

JAX emits duration events for every compilation: ``jaxpr_trace`` →
``jaxpr_to_mlir_module`` → ``backend_compile``.  A registered listener
turns each backend compile into a first-class ``compile_time`` event in
the step buffer (with a lowering/backend split in ``meta``), attributed
to whatever step is currently open.

This replaces an earlier AOT ``lower()/compile()`` wrapper design: the
listener keeps jit's C++ fast-path dispatch (the AOT ``Compiled.call``
re-flattens pytrees in Python — measured ~5 ms/step on a 65-leaf train
state) and it observes ALL compilations in the process, including ones
in code we never wrapped — exactly what a recompile-storm diagnosis
needs.

Each counted compile also lands in ``TraceState.compile_ring`` as
(monotonic end time, ms), so the compiles of any window can be counted.

Fail-open: listener errors are swallowed; events fire synchronously on
the dispatching thread, so the TLS step gate works unchanged.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from traceml_tpu.sdk.state import TraceState, get_state
from traceml_tpu.utils.error_log import get_error_log
from traceml_tpu.utils.timing import COMPILE_TIME, TimeEvent, _now

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_MLIR_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"

# Sub-threshold compiles (tiny op dispatches like a first jnp.ones) cost
# nothing and would flood the event stream; only meaningful compiles
# become step events.
MIN_COMPILE_MS = 2.0

_lock = threading.Lock()
_installed = False


# lowering durations older than this cannot belong to the backend
# compile that just fired (a lowering that never backend-compiled, e.g.
# a persistent-cache hit or bare AOT .lower(), must not leak into the
# next unrelated compile's attribution)
_LOWER_STALENESS_S = 30.0


class _PendingLower(threading.local):
    """Per-thread accumulator for lowering durations between backend
    compiles (the events arrive as a trace → mlir → backend sequence on
    the dispatching thread)."""

    def __init__(self) -> None:
        self.lower_s = 0.0
        self.first_ts = 0.0


_pending = _PendingLower()


def _listener(event: str, duration: float, **kwargs) -> None:
    try:
        if event in (_TRACE_EVENT, _MLIR_EVENT):
            if _pending.lower_s == 0.0:
                _pending.first_ts = _now()
            _pending.lower_s += float(duration)
            return
        if event != _BACKEND_EVENT:
            return
        lower_s, _pending.lower_s = _pending.lower_s, 0.0
        if lower_s and _now() - _pending.first_ts > _LOWER_STALENESS_S:
            lower_s = 0.0  # stale orphaned lowering; don't misattribute
        st: TraceState = get_state()
        total_s = float(duration) + lower_s
        if total_s * 1000.0 < MIN_COMPILE_MS:
            return
        ev = TimeEvent(COMPILE_TIME, st.current_step)
        # the compile just FINISHED; reconstruct the span
        ev.cpu_end = _now()
        ev.cpu_start = ev.cpu_end - total_s
        ev.meta = {
            "lower_ms": lower_s * 1000.0,
            "backend_compile_ms": float(duration) * 1000.0,
            "fun_name": str(kwargs.get("fun_name", "")),
        }
        st.buffer.add(ev)
        st.compile_events_seen += 1
        st.compile_ring.add(time.monotonic(), total_s * 1000.0)
    except Exception as exc:  # never raise into jax internals
        try:
            get_error_log().warning("compile listener failed", exc)
        except Exception:
            pass


def install_compile_tracker() -> bool:
    """Register the listener once per process.  Idempotent."""
    global _installed
    with _lock:
        if _installed:
            return True
        try:
            import jax.monitoring as mon

            mon.register_event_duration_secs_listener(_listener)
            _installed = True
            return True
        except Exception as exc:
            get_error_log().warning("compile tracker install failed", exc)
            return False


def compile_tracker_installed() -> bool:
    return _installed
