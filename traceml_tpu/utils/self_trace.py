"""The tracer's view of itself: host spans on the profiler's clock and
time-stamped sample rings.

``span(name, step)`` opens a ``jax.profiler.TraceAnnotation`` only while
a profiler session is recording (an on-demand capture from
``sdk/profile_capture.py``, or any ``jax.profiler.trace``), so the
tracer's own host work shows in the same trace as the device's
operations.  With no session recording it costs one ``is_enabled()``
check and builds no annotation, string or kwargs.  Every span name is
in :data:`SPAN_NAMES`, under the ``traceml/`` prefix.

:class:`TimedRing` keeps the last ``maxlen`` ``(time.monotonic(), value)``
samples of one quantity, so a reader can take the samples of any
window of the run after the fact.
"""

from __future__ import annotations

import statistics
import sys
import threading
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

#: every host span the tracer writes, in order of the path a step takes
SPAN_NAMES: Tuple[str, ...] = (
    "traceml/step_enter",     # trace_step.__enter__
    "traceml/wrap",           # WrappedStepFn.__call__ outside the jitted call
    "traceml/step_exit",      # trace_step.__exit__
    "traceml/mem_read",       # one memory backend sample
    "traceml/resolver_poll",  # one batch of device-marker polls
    "traceml/sampler_tick",   # TraceMLRuntime._tick
    "traceml/sample",         # one sampler's sample() inside a tick
    "traceml/publish",        # TelemetryPublisher.publish
)


class _NullSpan:
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False


_NULL = _NullSpan()
_annotation: Any = None  # jax.profiler.TraceAnnotation, once jax is imported


def _load_annotation() -> Any:
    """``TraceAnnotation`` once ``jax.profiler`` defines it, else None.
    Never imports: spans open on background threads too, and an import
    there can race the main thread's own ``import jax`` and leave jax
    half-initialized (see ``step_memory.jax_is_initialized``).  Without
    jax.profiler loaded no session can be recording anyway."""
    global _annotation
    ta = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)
    if ta is not None:
        _annotation = ta
    return ta


def span(
    name: str, step: Optional[int] = None, sampler: Optional[str] = None
) -> Any:
    """A context manager: a profiler span named ``name`` (one of
    :data:`SPAN_NAMES`), with ``step`` and ``sampler`` as its arguments
    where given, when a session is recording; else a shared no-op."""
    ta = _annotation or _load_annotation()
    if ta is None or not ta.is_enabled():
        return _NULL
    args: Dict[str, Any] = {}
    if step is not None:
        args["step"] = step
    if sampler is not None:
        args["sampler"] = sampler
    return ta(name, **args)


class TimedRing:
    """Bounded ring of ``(t, value)``; ``t`` is ``time.monotonic()``.
    Appends and reads are thread-safe."""

    def __init__(self, maxlen: int) -> None:
        self._items: "deque[Tuple[float, float]]" = deque(maxlen=maxlen)
        self._lock = threading.Lock()

    def add(self, t: float, value: float) -> None:
        with self._lock:
            self._items.append((t, value))

    def samples(
        self, t0: float = float("-inf"), t1: float = float("inf")
    ) -> List[Tuple[float, float]]:
        """The samples with ``t0 <= t <= t1``, oldest first."""
        with self._lock:
            items = list(self._items)
        return [(t, v) for t, v in items if t0 <= t <= t1]

    def summary(self) -> Dict[str, Any]:
        """``{"n", "p50", "p95"}`` over the samples the ring holds
        (quantiles interpolated between order statistics)."""
        values = [v for _, v in self.samples()]
        if len(values) < 2:
            v = values[0] if values else None
            return {"n": len(values), "p50": v, "p95": v}
        q = statistics.quantiles(values, n=20, method="inclusive")
        return {"n": len(values), "p50": q[9], "p95": q[18]}
