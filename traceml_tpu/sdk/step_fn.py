"""JAX step-function wrapper.

Brackets each dispatch of the training step in a ``compute_time`` region
whose device marker is the smallest output leaf — the readiness probe
that yields the fused fwd+bwd+opt device duration without ever blocking
(see utils/timing.py).  Dispatch goes through jit's C++ fast path
untouched.

Compile attribution is handled process-wide by
instrumentation/compile_tracker.py (a ``jax.monitoring`` listener that
emits exact ``compile_time`` events with a lowering/backend split);
``wrap_step_fn`` just makes sure the tracker is installed.  An earlier
design routed calls through AOT ``lower()/compile()`` objects for the
same information — scrapped because ``Compiled.call`` re-flattens the
arg pytree in Python (~5 ms/step on a 65-leaf train state) and misses
compiles outside the wrapped function.

The wrapper's own host work outside the jitted call (picking the
marker handles, the envelope hand-off) runs in a ``traceml/wrap``
profiler span and counts toward the step's tracer cost on
``TraceState``.  A step whose metrics carry a ``counters`` dict (the
program's own counts, e.g. ``make_train_step``'s ``expert_load``) hands
it to ``TraceState.note_counters``, which reads each array to the host
once its step has resolved (``TraceState.counter_rings``).

Fail-open: the wrapper never raises on its own behalf; user errors
propagate untouched.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

from traceml_tpu.sdk.state import TraceState, get_state
from traceml_tpu.sdk.wrappers import publish_region_marker
from traceml_tpu.utils.self_trace import span
from traceml_tpu.utils.timing import COMPUTE_TIME, DeviceMarker, timed_region


def _path_getter(path) -> Optional[Callable[[Any], Any]]:
    """Compile a jax key path into a direct extractor, or None when the
    path crosses an opaque node (custom pytrees without key info)."""
    import jax

    ops = []
    for key in path:
        if isinstance(key, jax.tree_util.DictKey):
            ops.append(("k", key.key))
        elif isinstance(key, jax.tree_util.SequenceKey):
            ops.append(("k", key.idx))
        elif isinstance(key, jax.tree_util.GetAttrKey):
            ops.append(("a", key.name))
        else:
            return None

    def get(obj):
        for kind, k in ops:
            obj = obj[k] if kind == "k" else getattr(obj, k)
        return obj

    return get


def _counters(out) -> Optional[dict]:
    """The ``counters`` dict of a ``(state, metrics)`` step output, or
    None."""
    if isinstance(out, tuple) and len(out) == 2 and isinstance(out[1], dict):
        counters = out[1].get("counters")
        if isinstance(counters, dict) and counters:
            return counters
    return None


class WrappedStepFn:
    """Callable wrapper; one instance per traced step function."""

    def __init__(
        self,
        fn: Callable,
        *,
        state: Optional[TraceState] = None,
        phase_name: str = COMPUTE_TIME,
        jit_kwargs: Optional[Dict[str, Any]] = None,
        estimate_flops: Optional[bool] = None,
    ) -> None:
        self._state = state or get_state()
        self._phase = phase_name
        if estimate_flops is None:
            from traceml_tpu.config import flags

            estimate_flops = not flags.NO_FLOPS_ESTIMATE.truthy()
        self._flops_pending = bool(estimate_flops)

        if hasattr(fn, "lower") and callable(getattr(fn, "lower")):
            # already a jax.jit-wrapped callable
            self._jfn = fn
        else:
            import jax

            self._jfn = jax.jit(fn, **(jit_kwargs or {}))
        self.__wrapped__ = fn

        from traceml_tpu.instrumentation.compile_tracker import (
            install_compile_tracker,
        )

        install_compile_tracker()
        # the listener always bumps the CURRENT global state, so the
        # snapshot and the later read must both come from get_state()
        self._compiles_at_start = get_state().compile_events_seen
        # smallest-leaf index per output treedef: the structure of a
        # jitted fn's output is stable, so the min-size scan runs once
        # and later dispatches index straight into the flat leaves
        self._leaf_idx: Dict[Any, int] = {}
        # direct key-path extractor for the chosen leaf: tree_flatten on
        # a ~35-leaf train state costs ~130 µs per call while the
        # dispatch is in flight (it contends with the backend's compute
        # threads) — ~1% of a 12 ms step; a few dict/tuple lookups cost
        # ~1 µs.  Kept as (output treedef, getter, jit cache size): an
        # output's treedef changes only with a retrace, which grows the
        # jitted callable's cache, so a grown cache (or, for a callable
        # without one, a different treedef) sends the call back through
        # the flatten path, as does a path that hits a non-array.
        self._leaf_getter: Optional[Tuple[Any, Callable[[Any], Any], Optional[int]]] = None

    @property
    def compile_count(self) -> int:
        """Process-wide compile events observed since this wrapper was
        created (a superset of this function's own compiles)."""
        return get_state().compile_events_seen - self._compiles_at_start

    def _traces(self) -> Optional[int]:
        """Entries in the jitted callable's cache, or None without one."""
        try:
            return self._jfn._cache_size()
        except Exception:
            return None

    def _pick_handles(self, out):
        """Smallest ready-able output leaf, extracted on the steady path
        by a cached key-path getter (NO per-call tree_flatten — see
        ``_leaf_getter``); the selection policy itself lives in
        timing.smallest_ready_index."""
        import jax

        cached = self._leaf_getter
        if cached is not None:
            treedef, getter, traces = cached
            now = self._traces()
            if (now == traces) if now is not None else (
                jax.tree_util.tree_structure(out) == treedef
            ):
                try:
                    leaf = getter(out)
                    if hasattr(leaf, "is_ready"):
                        return [leaf]
                except Exception:
                    pass
            self._leaf_getter = None  # structure changed: rebuild below
        try:
            from traceml_tpu.utils.timing import smallest_ready_index

            flat, treedef = jax.tree_util.tree_flatten_with_path(out)
            leaves = [leaf for _, leaf in flat]
            idx = self._leaf_idx.get(treedef)
            if (
                idx is None
                or idx >= len(leaves)
                or not hasattr(leaves[idx], "is_ready")
            ):
                idx = smallest_ready_index(leaves)
                if idx is None:
                    return []
                if len(self._leaf_idx) > 64:
                    self._leaf_idx.clear()
                self._leaf_idx[treedef] = idx
            getter = _path_getter(flat[idx][0])
            if getter is not None:
                self._leaf_getter = (treedef, getter, self._traces())
            return [leaves[idx]]
        except Exception:
            return []

    def estimate_flops(self, *args, **kwargs) -> Optional[float]:
        """Per-dispatch model FLOPs from XLA's cost analysis on the
        LOWERED (uncompiled) program — a trace, not a compile, so it
        costs milliseconds-to-seconds of host work once.  Publishes the
        estimate into TraceState (the MFU numerator; assumes one wrapped
        dispatch per step — grad-accum loops with K inner dispatches
        should call ``set_step_flops`` with the summed value instead).

        The estimate is for the whole (global, pre-partition) program:
        when this process drives N addressable chips (a pjit program
        over a local mesh), the matching MFU denominator is N × chip
        peak, so ``flops_device_count`` is published alongside and the
        efficiency formula (analytics/efficiency.py) scales by it.

        Fail-open: returns None (and publishes nothing) on any error.
        """
        try:
            import jax

            ca = self._jfn.lower(*args, **kwargs).cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            flops = float(ca.get("flops", 0.0))
            if flops <= 0:
                return None
            st = self._state
            st.flops_per_step = flops
            st.flops_source = "cost_analysis"
            try:
                st.flops_device_kind = str(jax.devices()[0].device_kind)
            except Exception:
                st.flops_device_kind = None
            try:
                # GLOBAL device count: cost_analysis() describes the
                # whole pre-partition SPMD program, so the MFU
                # denominator must span every chip that executes it —
                # local_device_count would inflate MFU by the process
                # count under multi-process meshes (advisor r3)
                st.flops_device_count = int(jax.device_count())
            except Exception:
                st.flops_device_count = None
            return flops
        except Exception:
            return None

    def __call__(self, *args, **kwargs):
        st = self._state
        if self._flops_pending and st.tls.in_step:
            # once, BEFORE the first IN-STEP dispatch (args not yet
            # donated): host-side trace only, overlapped with that
            # call's compile wait; never on the steady-state hot path.
            # In-step gating keeps a wrapped EVAL fn (dispatched outside
            # trace_step) from publishing its FLOPs as the step's MFU
            # numerator just because it ran first.
            self._flops_pending = False
            if st.flops_per_step is None:  # a manual value wins
                self.estimate_flops(*args, **kwargs)
        t0 = time.perf_counter_ns()
        step = st.current_step
        region = timed_region(self._phase, step, sink=st.buffer.add)
        tr = region.__enter__()
        t1 = time.perf_counter_ns()
        try:
            out = self._jfn(*args, **kwargs)
        except BaseException as exc:
            region.__exit__(type(exc), exc, exc.__traceback__)
            raise
        t2 = time.perf_counter_ns()
        with span("traceml/wrap", step):
            # ONE marker shared by the compute event and the open step
            # envelope (same handles, same dispatch instant) — a single
            # pytree flatten and a single resolver poll per step.  The
            # overhead governor gates the whole device-probe apparatus
            # per step (utils/overhead_governor.py).
            if st.markers_enabled():
                handles = self._pick_handles(out)
                if handles:
                    marker = DeviceMarker(handles)
                    # the fused fwd+bwd+opt spans ~the whole step: let
                    # the resolver sleep to the expected completion
                    # window instead of fine-polling from dispatch.
                    # In-step only: out-of-step dispatches (eval loops)
                    # queue behind each other, so their lifetimes
                    # measure queue depth, not one step's compute —
                    # they must not feed the lifetime EMA
                    marker.step_end_hint = st.tls.in_step
                    tr.event.marker = marker
            region.__exit__(None, None, None)
            if st.tls.in_step:
                counters = _counters(out)
                if counters is not None:
                    st.note_counters(counters)
            # envelope hand-off + dispatch-time resolver submission (the
            # fine-cadence stamping that intra-step device edges need) —
            # see publish_region_marker's docstring
            publish_region_marker(region.event, st)
        if st.tls.in_step:
            st.host_ns_open += (t1 - t0) + (time.perf_counter_ns() - t2)
        return out


def wrap_step_fn(
    fn: Callable,
    *,
    donate_argnums: Tuple[int, ...] = (),
    static_argnums: Tuple[int, ...] = (),
    estimate_flops: Optional[bool] = None,
    **jit_kwargs: Any,
) -> WrappedStepFn:
    """Wrap a JAX training-step function for tracing.

    ``fn`` may be a plain function (it will be ``jax.jit``-ed with the
    given options) or an existing jitted callable (used as-is).
    ``estimate_flops`` controls the one-time cost-analysis FLOPs
    estimate on first call (default on; env
    ``TRACEML_NO_FLOPS_ESTIMATE=1`` turns it off globally).
    """
    kw = dict(jit_kwargs)
    if donate_argnums:
        kw["donate_argnums"] = donate_argnums
    if static_argnums:
        kw["static_argnums"] = static_argnums
    return WrappedStepFn(fn, jit_kwargs=kw, estimate_flops=estimate_flops)
