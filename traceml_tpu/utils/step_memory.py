"""Per-step device memory tracking
(reference: src/traceml_ai/utils/step_memory.py:32-112).

The reference resets ``torch.cuda`` peak stats at step start and reads
``max_memory_allocated/reserved`` at step end.  TPU runtimes expose
``jax.Device.memory_stats()`` (libtpu-backed: ``bytes_in_use``,
``peak_bytes_in_use``, ``bytes_limit``, …) but **no per-step peak reset**
— the peak is cumulative.  So the tracker records, per step and device:

* ``current_bytes``   — bytes in use at step end
* ``peak_bytes``      — cumulative allocator peak (monotone)
* ``step_peak_bytes`` — max of the observations this tracker made during
  the step (start/end edges) — a lower bound on the true step peak
* ``limit_bytes``     — device capacity

Backends are pluggable because ``memory_stats()`` returns ``None`` on
some runtimes (CPU, remote devices): the live-arrays backend sums
``jax.live_arrays()`` nbytes per device, and tests inject a deterministic
fake (SURVEY.md §4 "fake device layer").  Each backend sample runs in a
``traceml/mem_read`` profiler span (utils/self_trace.py).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Protocol

from traceml_tpu.utils.error_log import get_error_log
from traceml_tpu.utils.self_trace import span
from traceml_tpu.utils.timing import push_step_memory_row


class DeviceMemorySample(dict):
    """Row shape: {device_id, device_kind, current_bytes, peak_bytes,
    limit_bytes} — plain dict subclass for codec friendliness."""


class MemoryBackend(Protocol):
    name: str

    def sample(self) -> List[Dict[str, Any]]: ...


class JaxMemoryStatsBackend:
    """libtpu allocator counters via ``jax.Device.memory_stats()``."""

    name = "jax_memory_stats"

    def __init__(self) -> None:
        import jax

        self._devices = jax.local_devices()
        # Probe once: some runtimes return None.
        probe = self._devices[0].memory_stats() if self._devices else None
        if not probe:
            raise RuntimeError("memory_stats unavailable on this runtime")

    def sample(self) -> List[Dict[str, Any]]:
        out: List[Dict[str, Any]] = []
        for d in self._devices:
            stats = d.memory_stats() or {}
            out.append(
                {
                    "device_id": int(d.id),
                    "device_kind": str(d.device_kind),
                    "current_bytes": int(stats.get("bytes_in_use", 0)),
                    "peak_bytes": int(
                        stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))
                    ),
                    "limit_bytes": int(stats.get("bytes_limit", 0)) or None,
                }
            )
        return out


class LiveArraysBackend:
    """Fallback: per-device sum of live ``jax.Array`` buffer sizes.

    Approximates allocated bytes (misses allocator overhead / temp
    buffers) but works on every backend, including CPU CI.

    CRITICAL: uses only array METADATA (nbytes, sharding, device ids).
    Touching ``shard.data`` marks buffers as externally referenced,
    which defeats XLA's buffer reuse and was measured to DOUBLE step
    time on the CPU backend — the observer must not perturb the
    allocator it observes.
    """

    name = "live_arrays"

    def __init__(self) -> None:
        import jax

        self._jax = jax
        self._kinds = {d.id: str(d.device_kind) for d in jax.local_devices()}
        self._pid = jax.process_index()
        # (sharding, shape, itemsize) → (local device ids, bytes/shard).
        # Training loops re-create arrays with identical layout every
        # step; memoizing turns the per-array device_set/shard_shape work
        # into one dict hit (holding the sharding key keeps it alive, so
        # ids can't be recycled under us).
        self._layout_cache: Dict[Any, Any] = {}

    def sample(self) -> List[Dict[str, Any]]:
        import math

        per_dev: Dict[int, int] = {}
        cache = self._layout_cache
        for arr in self._jax.live_arrays():
            try:
                key = (arr.sharding, arr.shape, arr.dtype.itemsize)
                hit = cache.get(key)
                if hit is None:
                    sharding = arr.sharding
                    # true per-device shard size from METADATA: replicated
                    # arrays cost full nbytes on every device, sharded
                    # ones cost their shard — shard_shape computes both
                    dev_ids = [
                        d.id
                        for d in sharding.device_set
                        if d.process_index == self._pid
                    ]
                    per_shard = int(
                        math.prod(sharding.shard_shape(arr.shape))
                        * arr.dtype.itemsize
                    )
                    if len(cache) > 4096:
                        cache.clear()
                    cache[key] = hit = (dev_ids, per_shard)
                dev_ids, per_shard = hit
                for did in dev_ids:
                    per_dev[did] = per_dev.get(did, 0) + per_shard
            except Exception:
                continue
        return [
            {
                "device_id": did,
                "device_kind": self._kinds.get(did, "unknown"),
                "current_bytes": n,
                "peak_bytes": n,  # no allocator peak; tracker maxes edges
                "limit_bytes": None,
            }
            for did, n in sorted(per_dev.items())
        ]


class FakeMemoryBackend:
    """Deterministic scripted backend for tests."""

    name = "fake"

    def __init__(self, script: Optional[List[List[Dict[str, Any]]]] = None):
        self._script = list(script or [])
        self._i = 0
        self.calls = 0

    def push(self, sample: List[Dict[str, Any]]) -> None:
        self._script.append(sample)

    def sample(self) -> List[Dict[str, Any]]:
        self.calls += 1
        if not self._script:
            return []
        sample = self._script[min(self._i, len(self._script) - 1)]
        self._i += 1
        return [dict(row) for row in sample]


class NullMemoryBackend:
    name = "null"

    def sample(self) -> List[Dict[str, Any]]:
        return []


def detect_backend() -> MemoryBackend:
    """Best available backend, fail-open to null.

    torch-xla wins when the process has torch_xla LOADED (explicit
    signal this is a torch-xla job — its lazy tensors never show up in
    jax's live-arrays view); detection is sys.modules-gated so this
    never imports a framework the job didn't choose."""
    import sys

    if "torch_xla" in sys.modules:
        try:
            from traceml_tpu.instrumentation.torch_xla_support import (
                XlaMemoryBackend,
            )

            return XlaMemoryBackend()
        except Exception:
            pass
    try:
        return JaxMemoryStatsBackend()
    except Exception:
        pass
    try:
        return LiveArraysBackend()
    except Exception:
        pass
    return NullMemoryBackend()


def jax_is_initialized() -> bool:
    """True only when a jax backend already exists in this process.

    Samplers MUST consult this before touching devices: triggering XLA
    backend init from a background thread before the user's own
    ``jax.distributed.initialize`` is the TPU analogue of the
    reference's touch-CUDA-before-init_process_group hazard
    (reference: process_sampler.py CUDA-safety gate).

    IMPORT-FREE on purpose: this runs on the sampler thread, and an
    ``import jax...`` here can race the MAIN thread's in-progress
    ``import jax`` (slow under CPU oversubscription), leaving jax's
    modules partially initialized and crashing unrelated user imports —
    observed as chex failing with "partially initialized module
    jax._src.xla_bridge".  Only ``sys.modules`` inspection is safe.
    """
    import sys

    m = sys.modules.get("jax")
    if m is None:
        return False
    spec = getattr(m, "__spec__", None)
    if spec is not None and getattr(spec, "_initializing", False):
        return False  # main thread is mid-import; hands off
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None:
        return False
    try:
        return bool(getattr(xb, "_backends", None))
    except Exception:
        return False


def device_memory_rows(backend_holder: Dict[str, Any], ts: float) -> List[Dict[str, Any]]:
    """Shared per-device row builder for the system/process samplers.

    ``backend_holder`` is a one-key dict {"backend": MemoryBackend|None}
    owned by the caller; detection is lazy and gated on jax being
    initialized so the sampler thread can never force backend init.
    """
    backend = backend_holder.get("backend")
    if backend is None:
        import sys

        # torch-xla jobs never initialize jax — their own loaded module
        # is the detection signal (sys.modules check only: this thread
        # must never import a framework)
        if not jax_is_initialized() and "torch_xla" not in sys.modules:
            return []
        try:
            backend = detect_backend()
        except Exception:
            return []
        backend_holder["backend"] = backend
    with span("traceml/mem_read"):
        sampled = backend.sample()
    return [
        {
            "timestamp": ts,
            "device_id": r["device_id"],
            "device_kind": r.get("device_kind", "unknown"),
            "memory_used_bytes": r.get("current_bytes"),
            "memory_peak_bytes": r.get("peak_bytes"),
            "memory_total_bytes": r.get("limit_bytes"),
        }
        for r in sampled
    ]


class StepMemoryTracker:
    """Records device memory at step edges and emits one row per
    (step, device) into the global step-memory queue."""

    def __init__(
        self,
        backend: Optional[MemoryBackend] = None,
        min_sample_interval_s: float = 0.2,
    ) -> None:
        self._backend = backend or detect_backend()
        self._step_start: Dict[int, Dict[str, Any]] = {}
        self._have_edge = False
        # Time-based throttle: sub-interval steps share one sample, so
        # memory sampling cost stays O(1/interval) per second instead of
        # O(1/step) — short-step jobs keep <1% overhead, and the creep/
        # pressure diagnostics are cadence-based, not per-step.  Rows
        # are simply sparse in `step`; every consumer iterates rows.
        self._min_interval = float(min_sample_interval_s)
        self._last_sample_mono = 0.0

    @property
    def backend_name(self) -> str:
        return getattr(self._backend, "name", "unknown")

    def reset(self, step: int) -> None:
        """Step-start edge (reference: reset_peak_memory_stats analogue).

        In a contiguous step loop the previous step's EXIT sample is this
        step's entry edge, so only the first step pays a sample here —
        one backend sample per step, not two.
        """
        if self._have_edge:
            return
        try:
            with span("traceml/mem_read", step):
                start_rows = self._backend.sample()
            self._step_start = {row["device_id"]: row for row in start_rows}
            self._have_edge = True
        except Exception as exc:
            get_error_log().warning("step memory reset failed", exc)
            self._step_start = {}

    def record(self, step: int, *, force: bool = False) -> List[Dict[str, Any]]:
        """Step-end edge; emits rows and returns them (for tests).
        Skipped (returns []) when inside the sampling throttle window,
        unless ``force`` — the shutdown path forces one last sample so
        a run shorter than the throttle interval still records its end
        state (a creep diagnosis needs first AND last; r4 fix)."""
        now = time.monotonic()
        if (
            not force
            and self._min_interval > 0
            and now - self._last_sample_mono < self._min_interval
        ):
            return []
        self._last_sample_mono = now
        rows: List[Dict[str, Any]] = []
        try:
            ts = time.time()
            with span("traceml/mem_read", step):
                end_rows = self._backend.sample()
            for row in end_rows:
                start = self._step_start.get(row["device_id"], {})
                step_peak = max(
                    int(row.get("current_bytes", 0)),
                    int(start.get("current_bytes", 0)),
                )
                out = {
                    "step": step,
                    "timestamp": ts,
                    "device_id": row["device_id"],
                    "device_kind": row.get("device_kind", "unknown"),
                    "current_bytes": int(row.get("current_bytes", 0)),
                    "peak_bytes": int(row.get("peak_bytes", 0)),
                    "step_peak_bytes": step_peak,
                    "limit_bytes": row.get("limit_bytes"),
                    "backend": self.backend_name,
                }
                rows.append(out)
                push_step_memory_row(out)
            # this exit sample becomes the next step's entry edge
            self._step_start = {r["device_id"]: r for r in end_rows}
            self._have_edge = True
        except Exception as exc:
            get_error_log().warning("step memory record failed", exc)
        return rows
