"""Columnar window engine: struct-of-arrays ring buffers + vectorized
window builds for the live analytics path.

The scalar pipeline in :mod:`traceml_tpu.utils.step_time_window` is the
golden reference; this module is a drop-in fast path that must produce
**byte-identical** payloads.  Three pieces:

* :class:`StepTimeColumns` / :class:`MemoryColumns` — per-rank numpy
  ring buffers (preallocated to 2x the retention bound, compacted with a
  memmove when the write head reaches the end) that the snapshot store
  fills in lockstep with its row deques.  Appends that the vectorized
  build cannot represent exactly (duplicate or out-of-order steps, a
  ``None`` step id, malformed event payloads, non-integer byte counts)
  set a sticky ``columnar_ok = False`` flag on the rank's buffer.
* :func:`build_columnar_step_time_window` — the vectorized equivalent of
  ``build_step_time_window``: suffix alignment via unique-counts +
  ``searchsorted``, clock selection as a boolean reduction, residual
  clamp and per-phase averages/medians as numpy reductions over a
  ``(rank, phase, aligned_step)`` cube.  Raises :class:`ColumnarFallback`
  when any participating rank is flagged, so the caller reruns the
  scalar reference on the row deques instead.
* :class:`ColumnarStepTimeWindow` — a ``StepTimeWindow`` whose
  ``rank_windows`` materialize per-rank lists lazily from the cube, so
  diagnosis rules that only touch a few phases never pay for the rest.

Exactness rules the implementation leans on (and the golden tests pin):

* ``np.cumsum(xs)[-1]`` reproduces Python's left-fold ``sum(xs)``
  exactly (``np.sum`` does NOT — it reduces pairwise);
* substituting ``0.0`` for a missing value is exact for the non-negative
  duration folds used here (``x + 0.0 == x``);
* ``np.median`` and ``statistics.median`` agree for float input (odd
  length picks the same element; even length computes ``(a + b) / 2``
  both ways);
* occupancy numerator/denominator pairs are precomputed at append time
  by the scalar :func:`row_occupancy_parts`, so the events-dict
  iteration order inside the fold is preserved by construction;
* every value escaping into a payload goes through ``.tolist()`` /
  ``float()`` first — ``np.float64`` is not JSON serializable and its
  ``__round__`` differs from the float one.

Round 19 adds the *incremental window engine* (the ``*WindowCache``
classes at the bottom of this module): each domain window keeps a
persistent aligned-cube / per-slot cache owned by the snapshot store,
and a dirty tick extends it by only the newly appended/aligned columns.
Any condition the delta path cannot represent exactly — realignment,
ring eviction crossing the window start, a clock flip, a flagged buffer
— invalidates back to the full build above, which stays the golden
reference: incremental output is bit-identical to a from-scratch build
every tick (pinned by tests/utils/test_incremental_window.py).

Kill switches: ``TRACEML_COLUMNAR_WINDOW=0`` forces the scalar path;
``TRACEML_INCR_WINDOW=0`` forces full rebuilds (cache never consulted);
``TRACEML_VECTOR_DIAGNOSIS=0`` forces the scalar rule-evaluation arm
(and disables the per-(domain, version) diagnosis cache).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Dict, List, Mapping, Optional

import numpy as np

from traceml_tpu.config import flags
from traceml_tpu.utils import timing as T
from traceml_tpu.utils.step_time_window import (
    ACCOUNTED_PHASES,
    ALL_KEYS,
    PHASES,
    RESIDUAL_KEY,
    STEP_KEY,
    RankWindow,
    StepCombinedTimeMetric,
    StepTimeWindow,
    row_occupancy_parts,
)

# event layout inside the value cube: 0 = the step envelope, 1.. = the
# accounted phases in PHASES order (the order the scalar fold uses)
EVENT_NAMES = (T.STEP_TIME,) + tuple(PHASES.values())
N_EVENTS = len(EVENT_NAMES)
_EVENT_INDEX = {name: i for i, name in enumerate(EVENT_NAMES)}
KEY_INDEX = {k: i for i, k in enumerate(ALL_KEYS)}

_NAN = float("nan")


def columnar_window_enabled() -> bool:
    return flags.COLUMNAR_WINDOW.enabled()


def incr_window_enabled() -> bool:
    return flags.INCR_WINDOW.enabled()


def vector_diagnosis_enabled() -> bool:
    return flags.VECTOR_DIAGNOSIS.enabled()


# vectorized-diagnosis fallback accounting: the vector arm never spams
# the log on a pathological session — the first fallback per domain is
# warned once, the rest are counted and surfaced through the tick
# profiler (the r09 shed-warning pattern)
_VECTOR_FALLBACKS: Dict[str, int] = {}
_VECTOR_FALLBACK_WARNED: set = set()


def note_vector_fallback(domain: str) -> None:
    _VECTOR_FALLBACKS[domain] = _VECTOR_FALLBACKS.get(domain, 0) + 1
    if domain not in _VECTOR_FALLBACK_WARNED:
        _VECTOR_FALLBACK_WARNED.add(domain)
        logging.getLogger(__name__).warning(
            "vectorized %s diagnosis fell back to the scalar arm "
            "(further fallbacks counted, not logged)", domain,
        )


def vector_fallback_counts() -> Dict[str, int]:
    return dict(_VECTOR_FALLBACKS)


class ColumnarFallback(Exception):
    """Raised when the columnar build cannot reproduce the scalar path
    exactly; the caller must rerun the scalar reference on the rows."""


class _CompactRing:
    """Arrays sized ``2 * cap`` with ``[start, end)`` live; when the
    write head hits ``2 * cap`` the live span is memmoved to the front.
    Appends beyond ``cap`` drop the oldest entry, mirroring the snapshot
    store's ``deque(maxlen=cap)`` exactly, so views are always
    contiguous and eviction is an O(1) ``start`` bump."""

    __slots__ = ("cap", "_start", "_end", "appended_total", "evicted_total")

    def __init__(self, cap: int) -> None:
        self.cap = max(1, int(cap))
        self._start = 0
        self._end = 0
        # monotone lifetime counters — the incremental window caches
        # compare these against their last-build values to detect new
        # rows and evictions without touching the arrays
        self.appended_total = 0
        self.evicted_total = 0

    def __len__(self) -> int:
        return self._end - self._start

    def _arrays(self):  # pragma: no cover - subclass responsibility
        raise NotImplementedError

    def _next_slot(self) -> int:
        if self._end == 2 * self.cap:
            n = len(self)
            lo = self._end - n
            for a in self._arrays():
                a[:n] = a[lo : self._end]
            self._start, self._end = 0, n
        if len(self) == self.cap:
            self._start += 1
            self.evicted_total += 1
        i = self._end
        self._end += 1
        self.appended_total += 1
        return i

    def evict_head(self, n: int) -> None:
        """Drop the oldest ``n`` entries (retention-trim lockstep with
        the snapshot store's deque eviction)."""
        if n > 0:
            dropped = min(n, len(self))
            self._start = min(self._start + n, self._end)
            self.evicted_total += dropped

    def _reset(self) -> None:
        self.evicted_total += len(self)
        self._start = 0
        self._end = 0


class StepTimeColumns(_CompactRing):
    """Per-rank step-time columns mirroring the store's row deque."""

    __slots__ = ("_steps", "_vals", "_clock_ok", "_occ", "_last_step", "columnar_ok")

    def __init__(self, cap: int) -> None:
        super().__init__(cap)
        n = 2 * self.cap
        self._steps = np.empty(n, dtype=np.int64)
        # (row, event, {cpu_ms, device_ms}); NaN == not reported
        self._vals = np.empty((n, N_EVENTS, 2), dtype=np.float64)
        self._clock_ok = np.empty(n, dtype=np.bool_)
        # (row, {device_busy_ms, host_ms}) from row_occupancy_parts;
        # NaN pair == parts unavailable for the row
        self._occ = np.empty((n, 2), dtype=np.float64)
        self._last_step: Optional[int] = None
        self.columnar_ok = True

    def _arrays(self):
        return (self._steps, self._vals, self._clock_ok, self._occ)

    def clear(self) -> None:
        self._reset()
        self._last_step = None
        self.columnar_ok = True

    def append(self, row: Mapping[str, Any]) -> None:
        # always consume a slot, even for rows we cannot represent, so
        # the ring stays 1:1 with the store's deque and eviction math
        # holds; a flagged rank's columns are never read
        i = self._next_slot()
        if not self.columnar_ok:
            return
        try:
            step = int(row["step"])
            if self._last_step is not None and step <= self._last_step:
                raise ColumnarFallback("duplicate or out-of-order step")
            events = row.get("events") or {}
            vals = self._vals[i]
            vals.fill(_NAN)
            for name, ev in events.items():
                j = _EVENT_INDEX.get(name)
                if j is None:
                    continue
                # scalar _row_value treats a truthy non-mapping as an
                # error; float() raises on non-numeric values
                cpu = ev.get("cpu_ms")
                if cpu is not None:
                    vals[j, 0] = float(cpu)
                dev = ev.get("device_ms")
                if dev is not None:
                    vals[j, 1] = float(dev)
            env = events.get(T.STEP_TIME) or {}
            self._clock_ok[i] = (
                row.get("clock") == "device" and env.get("device_ms") is not None
            )
            parts = row_occupancy_parts(events)
            if parts is None:
                self._occ[i, 0] = _NAN
                self._occ[i, 1] = _NAN
            else:
                self._occ[i, 0] = parts[0]
                self._occ[i, 1] = parts[1]
            self._steps[i] = step
            self._last_step = step
        except Exception:
            self.columnar_ok = False

    # live views — valid until the next append/evict/clear
    def steps_view(self) -> np.ndarray:
        return self._steps[self._start : self._end]

    def vals_view(self) -> np.ndarray:
        return self._vals[self._start : self._end]

    def occ_view(self) -> np.ndarray:
        return self._occ[self._start : self._end]

    def clock_all_device(self) -> bool:
        return bool(self._clock_ok[self._start : self._end].all())

    def clock_tail_device(self, k: int) -> bool:
        """True when the newest ``k`` live rows are all device-clocked —
        the incremental tick's O(new) "still all-device" check (old live
        rows were already all-device and rows never mutate)."""
        return bool(self._clock_ok[self._end - k : self._end].all())


# MemoryColumns layout: one int64 matrix, -1 == NULL.  Integer columns
# (not float) so byte counts survive exactly into view payloads
# (history / growth_bytes are ints in the scalar path).
C_STEP, C_DEV, C_CUR, C_PEAK, C_SPEAK, C_LIM = range(6)
_MEM_FIELDS = (
    ("step", C_STEP),
    ("device_id", C_DEV),
    ("current_bytes", C_CUR),
    ("peak_bytes", C_PEAK),
    ("step_peak_bytes", C_SPEAK),
    ("limit_bytes", C_LIM),
)
# int64 -> float64 is exact below 2**53; byte counts near that bound
# (8 PiB) flag the rank instead of silently losing precision
_MAX_EXACT_INT = 2 ** 53


class MemoryColumns(_CompactRing):
    """Per-rank step-memory columns mirroring the store's row deque."""

    __slots__ = ("_data", "columnar_ok")

    def __init__(self, cap: int) -> None:
        super().__init__(cap)
        self._data = np.empty((2 * self.cap, 6), dtype=np.int64)
        self.columnar_ok = True

    def _arrays(self):
        return (self._data,)

    def clear(self) -> None:
        self._reset()
        self.columnar_ok = True

    def append(self, row: Mapping[str, Any]) -> None:
        i = self._next_slot()
        if not self.columnar_ok:
            return
        try:
            out = self._data[i]
            for field, c in _MEM_FIELDS:
                if c == C_DEV:
                    # scalar context does int(row.get("device_id", 0));
                    # a None device would crash there, so fall back
                    v = row.get(field, 0)
                    if v is None or not isinstance(v, int):
                        raise ColumnarFallback(field)
                    out[c] = v
                    continue
                v = row.get(field)
                if v is None:
                    out[c] = -1
                elif isinstance(v, int) and not isinstance(v, bool):
                    # negatives would collide with the -1 NULL sentinel;
                    # huge ints would lose exactness in float64 math
                    if v < 0 or v >= _MAX_EXACT_INT:
                        raise ColumnarFallback(field)
                    out[c] = v
                else:
                    raise ColumnarFallback(field)
        except Exception:
            self.columnar_ok = False

    def data_view(self) -> np.ndarray:
        return self._data[self._start : self._end]

    def column(self, c: int) -> np.ndarray:
        return self._data[self._start : self._end, c]

    def last_used(self) -> float:
        """``step_peak_bytes or current_bytes or 0`` of the newest row —
        the scalar rules' ``rows[-1]`` read (-1 == NULL, falsy like the
        scalar ``or`` chain treats None and 0)."""
        d = self.data_view()
        if d.shape[0] == 0:
            return 0.0
        sp, cur = int(d[-1, C_SPEAK]), int(d[-1, C_CUR])
        return float(sp if sp > 0 else (cur if cur > 0 else 0))


class _ColumnarData:
    """Raw arrays behind a built window (the ``window.col`` namespace
    the renderers/diagnostics fast paths read).  ``medians`` are
    computed lazily from the cube on first access — diagnosis rules
    that never touch a median don't pay the (R, 11, S) partition, and
    the incremental tick skips it entirely unless a consumer asks."""

    __slots__ = (
        "ranks",
        "steps",
        "series_cube",
        "averages",
        "_medians",
        "occupancy",
        "occ_num",
        "occ_host",
    )

    def __init__(
        self, ranks, steps, series_cube, averages, medians, occupancy,
        occ_num=None, occ_host=None,
    ):
        self.ranks: List[int] = ranks
        self.steps: np.ndarray = steps  # (S,) int64 aligned step ids
        self.series_cube: np.ndarray = series_cube  # (R, 11, S) ALL_KEYS order
        self.averages: np.ndarray = averages  # (R, 11)
        self._medians: Optional[np.ndarray] = medians  # (R, 11) or lazy
        self.occupancy: np.ndarray = occupancy  # (R,), NaN == None
        # zero-filled occupancy numerator/denominator parts (R, S) — kept
        # so the incremental cache can re-fold occupancy after a window
        # slide without re-reading the rings
        self.occ_num: Optional[np.ndarray] = occ_num
        self.occ_host: Optional[np.ndarray] = occ_host

    @property
    def medians(self) -> np.ndarray:
        m = self._medians
        if m is None:
            m = self._medians = np.median(self.series_cube, axis=2)
        return m


class _LazySeries(dict):
    """``RankWindow.series`` stand-in: materializes a phase's list from
    the cube on first access.  Consumers only use ``series[key]`` /
    ``series.get``; iteration/equality materialize everything first so
    the dict contract still holds."""

    __slots__ = ("_cube",)

    def __init__(self, cube_r: np.ndarray) -> None:
        super().__init__()
        self._cube = cube_r  # (11, S)

    def __missing__(self, key: str) -> List[float]:
        ki = KEY_INDEX.get(key)
        if ki is None:
            raise KeyError(key)
        vals = self._cube[ki].tolist()
        dict.__setitem__(self, key, vals)
        return vals

    def _materialize_all(self) -> None:
        for k in ALL_KEYS:
            self[k]

    def get(self, key, default=None):
        try:
            return self[key]
        except KeyError:
            return default

    def __contains__(self, key) -> bool:
        return key in KEY_INDEX or dict.__contains__(self, key)

    def __iter__(self):
        self._materialize_all()
        return dict.__iter__(self)

    def __len__(self) -> int:
        self._materialize_all()
        return dict.__len__(self)

    def keys(self):
        self._materialize_all()
        return dict.keys(self)

    def values(self):
        self._materialize_all()
        return dict.values(self)

    def items(self):
        self._materialize_all()
        return dict.items(self)

    def __eq__(self, other):
        self._materialize_all()
        if isinstance(other, _LazySeries):
            other._materialize_all()
        return dict.__eq__(self, other)

    def __ne__(self, other):
        return not self.__eq__(other)

    __hash__ = None


class _LazyRankWindows(Mapping):
    """``StepTimeWindow.rank_windows`` stand-in: builds each rank's
    ``RankWindow`` from the cube on first access and caches it."""

    def __init__(self, col: _ColumnarData, steps_list: List[int], clock: str) -> None:
        self._col = col
        self._steps = steps_list
        self._clock = clock
        self._index = {r: i for i, r in enumerate(col.ranks)}
        self._cache: Dict[int, RankWindow] = {}

    def __getitem__(self, rank: int) -> RankWindow:
        w = self._cache.get(rank)
        if w is None:
            i = self._index[rank]
            col = self._col
            occ = float(col.occupancy[i])
            w = RankWindow(
                rank=rank,
                steps=self._steps,
                series=_LazySeries(col.series_cube[i]),
                averages=dict(zip(ALL_KEYS, col.averages[i].tolist())),
                medians=dict(zip(ALL_KEYS, col.medians[i].tolist())),
                clock=self._clock,
                occupancy=occ if occ == occ else None,
            )
            self._cache[rank] = w
        return w

    def __iter__(self):
        return iter(self._col.ranks)

    def __len__(self) -> int:
        return len(self._col.ranks)


class ColumnarStepTimeWindow(StepTimeWindow):
    """A ``StepTimeWindow`` carrying its backing arrays in ``col``."""

    def __init__(self, *, col: _ColumnarData, **kwargs) -> None:
        super().__init__(**kwargs)
        self.col = col

    @property
    def occupancy_by_rank(self) -> Dict[int, float]:
        out: Dict[int, float] = {}
        for r, v in zip(self.col.ranks, self.col.occupancy.tolist()):
            if v == v:  # not NaN
                out[r] = v
        return out


def _left_fold_last(cube: np.ndarray) -> np.ndarray:
    """Exact sequential left-fold sum along the LAST axis.
    ``np.cumsum`` runs ``np.add.accumulate`` — a strictly sequential
    left-to-right scan per lane (unlike ``np.sum``'s pairwise tree), so
    the last prefix IS the left fold, bit for bit, at C speed.  Shared
    by the full build and the incremental tick so their averages cannot
    diverge.  The ``.copy()`` frees the (…, S) prefix array instead of
    pinning it behind the returned view for the payload's lifetime."""
    if cube.shape[-1] == 1:
        return np.copy(cube[..., 0])
    return np.cumsum(cube, axis=-1)[..., -1].copy()


def _select_clamp_slab(cube_raw: np.ndarray, clock: str) -> np.ndarray:
    """(R, n, N_EVENTS, 2) raw gathered values → (R, 11, n) series slab:
    clock selection, missing → 0.0, residual clamp, explicit accounted
    left-fold in PHASES order (exactly the scalar accumulation).  Every
    output column depends only on its own raw column, which is what lets
    the incremental tick compute bit-identical columns one slab at a
    time."""
    if clock == "device":
        dev = cube_raw[..., 1]
        cpu = cube_raw[..., 0]
        sel = np.where(np.isnan(dev), cpu, dev)
    else:
        sel = cube_raw[..., 0]
    sel = np.where(np.isnan(sel), 0.0, sel)  # missing -> 0.0, like the scalar `or 0.0`
    step = sel[:, :, 0]  # (R, n)
    phases = sel[:, :, 1:]  # (R, n, 9)
    clamped = np.where(
        (step > 0)[:, :, None], np.minimum(phases, step[:, :, None]), phases
    )
    accounted = clamped[:, :, 0].copy()
    for k in range(1, len(ACCOUNTED_PHASES)):
        accounted += clamped[:, :, k]
    residual = np.maximum(0.0, step - accounted)
    slab = np.empty((step.shape[0], len(ALL_KEYS), step.shape[1]), dtype=np.float64)
    slab[:, 0] = step
    slab[:, 1 : 1 + len(ACCOUNTED_PHASES)] = np.moveaxis(clamped, 2, 1)
    slab[:, len(ALL_KEYS) - 1] = residual
    return slab


def _zeroed_occ_parts(occ_parts: np.ndarray) -> "tuple[np.ndarray, np.ndarray]":
    """(R, n, 2) raw occupancy parts → zero-filled (num, host) pair."""
    num = np.where(np.isnan(occ_parts[:, :, 0]), 0.0, occ_parts[:, :, 0])
    host = np.where(np.isnan(occ_parts[:, :, 1]), 0.0, occ_parts[:, :, 1])
    return num, host


def _occupancy_from_sums(
    num_sum: np.ndarray, host_sum: np.ndarray
) -> np.ndarray:
    """Shared tail of :func:`_occupancy_fold` and the incremental
    cache's mirror fold: sum/sum with the scalar path's 1.0 clamp, NaN
    where no host time."""
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(host_sum > 0, np.minimum(num_sum / host_sum, 1.0), np.nan)


def _occupancy_fold(num: np.ndarray, host: np.ndarray) -> np.ndarray:
    """Per-rank occupancy from zero-filled (R, S) part planes — the
    scalar fold's sum/sum with the 1.0 clamp, NaN where no host time."""
    return _occupancy_from_sums(_left_fold_last(num), _left_fold_last(host))


def _fold_step_major(arr_t: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """Exact left fold over ``[lo, hi)`` of a STEP-MAJOR mirror
    ``(step, ...)``.  Per lane this performs the identical add sequence
    as :func:`_left_fold_last` over the lane-major cube — same bits —
    but each ``arr_t[j]`` slice is contiguous, so the loop runs at
    memcpy speed instead of gathering one strided element per lane."""
    acc = arr_t[lo].copy()
    for j in range(lo + 1, hi):
        acc += arr_t[j]
    return acc


def _step_time_metrics(
    averages: np.ndarray, ranks: List[int]
) -> Dict[str, StepCombinedTimeMetric]:
    """Cross-rank metrics from the (R, 11) averages (native floats
    throughout; first-max tie-break matching the scalar ``max()``)."""
    metrics: Dict[str, StepCombinedTimeMetric] = {}
    avg_rows = averages.tolist()  # R x 11 native floats
    for ki, key in enumerate(ALL_KEYS):
        col_vals = [row[ki] for row in avg_rows]
        med = float(np.median(averages[:, ki]))
        wi = int(np.argmax(averages[:, ki]))  # first max == scalar max() tie-break
        worst = col_vals[wi]
        metrics[key] = StepCombinedTimeMetric(
            key=key,
            per_rank_avg_ms=dict(zip(ranks, col_vals)),
            median_ms=med,
            worst_ms=worst,
            worst_rank=ranks[wi],
            skew_pct=(worst - med) / med if med > 0 else 0.0,
        )
    return metrics


def build_columnar_step_time_window(
    rank_cols: Mapping[int, StepTimeColumns],
    max_steps: int,
) -> Optional[ColumnarStepTimeWindow]:
    """Vectorized ``build_step_time_window`` over per-rank columns.

    Raises :class:`ColumnarFallback` if any non-empty rank is flagged.
    """
    items = [(r, c) for r, c in sorted(rank_cols.items(), key=lambda kv: kv[0]) if len(c)]
    if not items:
        return None
    for _, c in items:
        if not c.columnar_ok:
            raise ColumnarFallback("flagged rank buffer")
    ranks = [int(r) for r, _ in items]
    R = len(items)

    # 1. suffix alignment: steps present in EVERY rank, last max_steps.
    # Per-rank step columns are strictly ascending and unique (flagged
    # otherwise), so counts==R identifies the intersection.
    step_views = [c.steps_view() for _, c in items]
    if R == 1:
        common = np.array(step_views[0][-max_steps:], dtype=np.int64)
    else:
        uniq, counts = np.unique(np.concatenate(step_views), return_counts=True)
        common = uniq[counts == R][-max_steps:]
    S = int(common.size)
    if S == 0:
        return None

    # 2. clock selection: "device" only if EVERY buffered row (not just
    # the aligned suffix — matching select_clock) is device-clocked
    clock = "device" if all(c.clock_all_device() for _, c in items) else "host"

    # 3. gather the aligned (rank, step, event, clock) values
    cube_raw = np.empty((R, S, N_EVENTS, 2), dtype=np.float64)
    occ_parts = np.empty((R, S, 2), dtype=np.float64)
    for i, (_, c) in enumerate(items):
        idx = np.searchsorted(c.steps_view(), common)
        cube_raw[i] = c.vals_view()[idx]
        occ_parts[i] = c.occ_view()[idx]

    # 4. clock select + residual clamp + accounted left-fold (shared
    # with the incremental tick — see _select_clamp_slab)
    series_cube = _select_clamp_slab(cube_raw, clock)

    # 5. per-rank averages: an exact left-fold sum (medians are lazy on
    # _ColumnarData — most consumers never touch them)
    averages = _left_fold_last(series_cube) / S

    # 6. occupancy: fold the precomputed (device_busy, host) parts
    occ_num, occ_host = _zeroed_occ_parts(occ_parts)
    occupancy = _occupancy_fold(occ_num, occ_host)

    # 7. cross-rank metrics (native floats throughout)
    metrics = _step_time_metrics(averages, ranks)

    phases_present = [
        k
        for j, k in enumerate(ACCOUNTED_PHASES)
        if bool((series_cube[:, 1 + j, :] > 0).any())
    ]

    steps_list = common.tolist()
    col = _ColumnarData(
        ranks=ranks,
        steps=common,
        series_cube=series_cube,
        averages=averages,
        medians=None,
        occupancy=occupancy,
        occ_num=occ_num,
        occ_host=occ_host,
    )
    return ColumnarStepTimeWindow(
        col=col,
        clock=clock,
        steps=steps_list,
        ranks=list(ranks),
        rank_windows=_LazyRankWindows(col, steps_list, clock),
        metrics=metrics,
        phases_present=phases_present,
        n_steps=S,
    )


def window_to_plain(w: Optional[StepTimeWindow]) -> Optional[Dict[str, Any]]:
    """Canonical plain-dict form of a window for golden comparisons
    (dataclass ``__eq__`` is class-sensitive, so a scalar and a columnar
    window never compare equal directly)."""
    if w is None:
        return None
    return {
        "clock": w.clock,
        "steps": list(w.steps),
        "ranks": list(w.ranks),
        "n_steps": w.n_steps,
        "phases_present": list(w.phases_present),
        "metrics": {k: dataclasses.asdict(m) for k, m in w.metrics.items()},
        "rank_windows": {
            r: {
                "rank": rw.rank,
                "steps": list(rw.steps),
                "series": {k: list(rw.series[k]) for k in ALL_KEYS},
                "averages": dict(rw.averages),
                "medians": dict(rw.medians),
                "clock": rw.clock,
                "occupancy": rw.occupancy,
            }
            for r, rw in w.rank_windows.items()
        },
    }


def window_series_cube(
    window: StepTimeWindow, key: str = STEP_KEY
) -> "tuple[List[int], np.ndarray]":
    """``(ranks, (rank × step) cube)`` for one series key of a window,
    rows in ``window.ranks`` order.  Columnar windows hand out a view of
    the value cube; scalar windows materialize the same array from their
    per-rank series lists, so the topology reduction below works on
    either path.  The cube is dense by construction — suffix alignment
    keeps only steps present in EVERY rank."""
    if key not in KEY_INDEX:
        raise KeyError(key)
    col = getattr(window, "col", None)
    if col is not None:
        return list(col.ranks), col.series_cube[:, KEY_INDEX[key], :]
    ranks = list(window.ranks)
    cube = np.array(
        [window.rank_windows[r].series[key] for r in ranks],
        dtype=np.float64,
    ).reshape(len(ranks), window.n_steps)
    return ranks, cube


def reduce_window_by_grouping(
    window: StepTimeWindow, grouping: Any, key: str = STEP_KEY
) -> Dict[str, Any]:
    """(rank × step) → (axis-group × step): reshape one series of a
    window along a topology grouping (``utils.topology.Grouping`` —
    host / axis-coordinate / DCN-side) and return per-group aggregates
    plus a per-step dispersion series.

    Ranks outside the grouping are masked out rather than folded into a
    catch-all group.  Output::

        {"kind", "axis", "steps": [...],
         "groups": [{"key", "ranks", "mean": [...S], "min": [...S],
                     "max": [...S]}, ...],       # grouping-key order
         "dispersion": [...S]}                   # max-min of group means

    ``dispersion`` is the step-wise spread of the group means — the
    signal the attribution scorer explains: near-zero means the grouping
    does not separate the ranks on this series.
    """
    from traceml_tpu.utils.topology import reduce_cube

    ranks, cube = window_series_cube(window, key)
    row_of = {int(r): i for i, r in enumerate(ranks)}
    keys = sorted(grouping.groups, key=lambda k: str(k))
    group_index = np.zeros(len(ranks), dtype=np.int64)
    member = np.zeros(len(ranks), dtype=bool)
    for g, k in enumerate(keys):
        for r in grouping.groups[k]:
            i = row_of.get(int(r))
            if i is not None:
                group_index[i] = g
                member[i] = True
    mask = np.broadcast_to(member[:, None], cube.shape)
    red = reduce_cube(cube, group_index, len(keys), mask=mask)
    means = red["mean"]
    with np.errstate(invalid="ignore"):
        spread = np.nanmax(means, axis=0) - np.nanmin(means, axis=0)
    return {
        "kind": grouping.kind,
        "axis": grouping.axis,
        "steps": list(window.steps),
        "groups": [
            {
                "key": str(k),
                "ranks": sorted(int(r) for r in grouping.groups[k]),
                "mean": means[g].tolist(),
                "min": red["min"][g].tolist(),
                "max": red["max"][g].tolist(),
            }
            for g, k in enumerate(keys)
        ],
        "dispersion": np.where(np.isfinite(spread), spread, 0.0).tolist(),
    }


class MemorySeries:
    """One (rank, device) step-memory series, sorted by step — the
    single representation every step-memory rule consumes, buildable
    from row dicts (scalar reference) or from :class:`MemoryColumns`.

    Values are float64 with NaN for NULL; both construction paths yield
    bit-identical arrays for the same data (int64 -> float64 is exact
    below 2**53, and MemoryColumns flags anything larger)."""

    __slots__ = ("rank", "dev", "steps", "current", "peak", "step_peak", "limit")

    def __init__(self, rank, dev, steps, current, peak, step_peak, limit):
        # stable sort by (step or 0), matching the scalar context's
        # rows.sort(key=lambda r: (r.get("step") or 0))
        order = np.argsort(np.where(np.isnan(steps), 0.0, steps), kind="stable")
        self.rank = rank
        self.dev = dev
        self.steps = steps[order]
        self.current = current[order]
        self.peak = peak[order]
        self.step_peak = step_peak[order]
        self.limit = limit[order]

    @classmethod
    def from_rows(cls, rank: int, dev: int, rows: List[Mapping[str, Any]]) -> "MemorySeries":
        def col(key: str) -> np.ndarray:
            return np.array(
                [
                    float(r[key]) if r.get(key) is not None else _NAN
                    for r in rows
                ],
                dtype=np.float64,
            )

        return cls(
            rank,
            dev,
            col("step"),
            col("current_bytes"),
            col("peak_bytes"),
            col("step_peak_bytes"),
            col("limit_bytes"),
        )

    @classmethod
    def from_int_columns(
        cls, rank: int, dev: int, data: np.ndarray
    ) -> "MemorySeries":
        """``data``: the (n, 6) int64 slice of a MemoryColumns buffer
        already filtered to one device; -1 == NULL."""

        def col(c: int) -> np.ndarray:
            a = data[:, c].astype(np.float64)
            a[data[:, c] == -1] = _NAN
            return a

        return cls(rank, dev, col(C_STEP), col(C_CUR), col(C_PEAK), col(C_SPEAK), col(C_LIM))

    def __len__(self) -> int:
        return int(self.steps.shape[0])

    @staticmethod
    def _opt(v: float) -> Optional[float]:
        return None if v != v else v

    def last_values(self):
        """(step_peak, current, limit) of the final (sorted) row as
        Optional floats — what the scalar rules read via rows[-1]."""
        return (
            self._opt(float(self.step_peak[-1])),
            self._opt(float(self.current[-1])),
            self._opt(float(self.limit[-1])),
        )

    def used_series(self) -> np.ndarray:
        """Per-row ``step_peak or current or 0`` (NaN-aware truthiness,
        so NULL and 0 both fall through, like the scalar `or` chain)."""
        sp, cur = self.step_peak, self.current
        sp_ok = ~np.isnan(sp) & (sp != 0)
        cur_ok = ~np.isnan(cur) & (cur != 0)
        return np.where(sp_ok, sp, np.where(cur_ok, cur, 0.0))

    def latest_pressure(self) -> Optional[float]:
        """used/limit of the newest row where both are truthy."""
        used = self.used_series()
        lim = self.limit
        ok = (used != 0) & ~np.isnan(lim) & (lim != 0)
        idx = np.flatnonzero(ok)
        if idx.size == 0:
            return None
        i = int(idx[-1])
        return float(used[i]) / float(lim[i])

    def last_used(self) -> float:
        sp, cur, _ = self.last_values()
        return float(sp or cur or 0)

    def current_list(self) -> List[float]:
        """``float(current_bytes or 0)`` per row — the creep series."""
        cur = self.current
        return np.where(np.isnan(cur), 0.0, cur).tolist()


# ---------------------------------------------------------------------------
# Collectives domain (round 11): per-rank (step, op, dtype) rows →
# per-step overlap-efficiency window.
# ---------------------------------------------------------------------------

# canonical op vocabulary — mirrors instrumentation/collectives.OP_KINDS
# (pinned equal by tests/utils/test_collectives_window.py so the two
# layers can't silently fork)
COLLECTIVE_OPS = (
    "all_reduce",
    "all_gather",
    "reduce_scatter",
    "all_to_all",
    "p2p",
    "other",
)
_COLL_OP_INDEX = {op: i for i, op in enumerate(COLLECTIVE_OPS)}
_COLL_DTYPE_VOCAB_MAX = 64  # per-buffer dtype vocabulary bound

# int column layout
CC_STEP, CC_COUNT, CC_BYTES, CC_GROUP = range(4)


class CollectivesColumns(_CompactRing):
    """Per-rank collectives columns mirroring the store's row deque.

    One appended row per (step, op, dtype) aggregate from the sampler;
    steps are non-decreasing (several op/dtype rows share a step) —
    anything else flags the buffer for the scalar reference path."""

    __slots__ = (
        "_ints",
        "_floats",
        "_ops",
        "_dtypes",
        "_dtype_vocab",
        "_dtype_index",
        "_last_step",
        "columnar_ok",
    )

    def __init__(self, cap: int) -> None:
        super().__init__(cap)
        n = 2 * self.cap
        self._ints = np.empty((n, 4), dtype=np.int64)
        self._floats = np.empty((n, 2), dtype=np.float64)  # duration, exposed
        self._ops = np.empty(n, dtype=np.int8)
        self._dtypes = np.empty(n, dtype=np.int16)
        self._dtype_vocab: List[str] = []
        self._dtype_index: Dict[str, int] = {}
        self._last_step: Optional[int] = None
        self.columnar_ok = True

    def _arrays(self):
        return (self._ints, self._floats, self._ops, self._dtypes)

    def clear(self) -> None:
        self._reset()
        self._last_step = None
        self.columnar_ok = True
        # the dtype vocab survives a clear on purpose: codes in the ring
        # are gone, and re-coding the same strings is stable either way

    def append(self, row: Mapping[str, Any]) -> None:
        # always consume a slot (ring stays 1:1 with the row deque)
        i = self._next_slot()
        if not self.columnar_ok:
            return
        try:
            step = int(row["step"])
            if isinstance(row["step"], bool):
                raise ColumnarFallback("bool step")
            if self._last_step is not None and step < self._last_step:
                raise ColumnarFallback("out-of-order step")
            op = row.get("op")
            oi = _COLL_OP_INDEX.get(op)
            if oi is None:
                oi = _COLL_OP_INDEX["other"]
            dtype = str(row.get("dtype", "") or "")
            di = self._dtype_index.get(dtype)
            if di is None:
                if len(self._dtype_vocab) >= _COLL_DTYPE_VOCAB_MAX:
                    raise ColumnarFallback("dtype vocabulary overflow")
                di = len(self._dtype_vocab)
                self._dtype_vocab.append(dtype)
                self._dtype_index[dtype] = di
            ints = self._ints[i]
            for c, key in ((CC_COUNT, "count"), (CC_BYTES, "bytes"), (CC_GROUP, "group_size")):
                v = row.get(key, 0)
                if v is None:
                    v = 0
                if not isinstance(v, int) or isinstance(v, bool):
                    raise ColumnarFallback(key)
                if v < 0 or v >= _MAX_EXACT_INT:
                    raise ColumnarFallback(key)
                ints[c] = v
            ints[CC_STEP] = step
            dur = float(row.get("duration_ms", 0.0) or 0.0)
            exp = float(row.get("exposed_ms", 0.0) or 0.0)
            if dur < 0.0 or exp < 0.0 or exp > dur:
                raise ColumnarFallback("exposure outside duration")
            self._floats[i, 0] = dur
            self._floats[i, 1] = exp
            self._ops[i] = oi
            self._dtypes[i] = di
            self._last_step = step
        except Exception:
            self.columnar_ok = False

    # live views — valid until the next append/evict/clear
    def steps_view(self) -> np.ndarray:
        return self._ints[self._start : self._end, CC_STEP]

    def ints_view(self) -> np.ndarray:
        return self._ints[self._start : self._end]

    def floats_view(self) -> np.ndarray:
        return self._floats[self._start : self._end]

    def ops_view(self) -> np.ndarray:
        return self._ops[self._start : self._end]

    def dtypes_view(self) -> np.ndarray:
        return self._dtypes[self._start : self._end]

    def dtype_name(self, code: int) -> str:
        return self._dtype_vocab[code]


def _overlap_efficiency(total_ms: float, exposed_ms: float) -> float:
    """Share of comm time hidden behind compute: ``1 − exposed/total``.
    A zero-comm step is perfectly hidden by definition → 1.0, not NaN."""
    if total_ms > 0.0:
        return 1.0 - exposed_ms / total_ms
    return 1.0


@dataclasses.dataclass
class CollectivesWindow:
    """Cross-rank collectives aggregate over the last ``n_steps`` steps.

    Steps are the UNION of the ranks' steps (ragged participation — a
    rank that skips a collective still leaves the step in the window).
    ``per_step`` series are aligned to ``steps``; ``overlap_efficiency``
    is ``1 − exposed/total`` with zero-comm steps defined as 1.0."""

    steps: List[int]
    n_steps: int
    ranks: List[int]
    group_size: int
    per_step: Dict[str, List[float]]
    per_op: Dict[str, Dict[str, float]]
    per_rank: Dict[int, Dict[str, float]]
    totals: Dict[str, float]


def build_collectives_window_rows(
    rank_rows: Mapping[int, Any],
    max_steps: int,
) -> Optional[CollectivesWindow]:
    """Scalar reference fold over row dicts — the golden path the
    columnar build below must reproduce bit-identically.  Ranks are
    folded in sorted order, rows in arrival order."""
    items = [(r, list(rows)) for r, rows in sorted(rank_rows.items()) if rows]
    if not items:
        return None
    all_steps = sorted({int(row["step"]) for _, rows in items for row in rows})
    steps = all_steps[-max_steps:]
    lo = steps[0]
    idx = {s: i for i, s in enumerate(steps)}
    S = len(steps)

    count = [0] * S
    nbytes = [0] * S
    dur = [0.0] * S
    exp = [0.0] * S
    ar_fp32 = [0] * S
    per_op: Dict[str, Dict[str, float]] = {}
    per_rank: Dict[int, Dict[str, float]] = {}
    group = 1
    for rank, rows in items:
        r_dur = 0.0
        r_exp = 0.0
        r_bytes = 0
        for row in rows:
            s = int(row["step"])
            if s < lo:
                continue
            i = idx[s]
            c = int(row.get("count", 0) or 0)
            b = int(row.get("bytes", 0) or 0)
            d = float(row.get("duration_ms", 0.0) or 0.0)
            e = float(row.get("exposed_ms", 0.0) or 0.0)
            op = row.get("op") if row.get("op") in _COLL_OP_INDEX else "other"
            count[i] += c
            nbytes[i] += b
            dur[i] += d
            exp[i] += e
            if op == "all_reduce" and str(row.get("dtype", "")) == "float32":
                ar_fp32[i] += b
            slot = per_op.get(op)
            if slot is None:
                slot = per_op[op] = {
                    "count": 0, "bytes": 0, "duration_ms": 0.0, "exposed_ms": 0.0,
                }
            slot["count"] += c
            slot["bytes"] += b
            slot["duration_ms"] += d
            slot["exposed_ms"] += e
            group = max(group, int(row.get("group_size", 1) or 1))
            r_dur += d
            r_exp += e
            r_bytes += b
        per_rank[rank] = {
            "duration_ms": r_dur,
            "exposed_ms": r_exp,
            "bytes": r_bytes,
            "overlap_efficiency": _overlap_efficiency(r_dur, r_exp),
        }

    total_dur = 0.0
    total_exp = 0.0
    for v in dur:
        total_dur += v
    for v in exp:
        total_exp += v
    return CollectivesWindow(
        steps=steps,
        n_steps=S,
        ranks=[r for r, _ in items],
        group_size=group,
        per_step={
            "count": count,
            "bytes": nbytes,
            "duration_ms": dur,
            "exposed_ms": exp,
            "overlap_efficiency": [
                _overlap_efficiency(dur[i], exp[i]) for i in range(S)
            ],
            "allreduce_fp32_bytes": ar_fp32,
        },
        per_op=per_op,
        per_rank=per_rank,
        totals={
            "count": sum(count),
            "bytes": sum(nbytes),
            "duration_ms": total_dur,
            "exposed_ms": total_exp,
            "overlap_efficiency": _overlap_efficiency(total_dur, total_exp),
        },
    )


def build_columnar_collectives_window(
    rank_cols: Mapping[int, CollectivesColumns],
    max_steps: int,
) -> Optional[CollectivesWindow]:
    """Vectorized ``build_collectives_window_rows`` over per-rank columns.

    Exactness: per-slot accumulation uses ``np.add.at`` — unbuffered,
    element-order application, so repeated step slots accumulate in row
    order exactly like the scalar ``acc[i] += v`` fold; ranks are
    processed in sorted order, matching the scalar traversal.  Raises
    :class:`ColumnarFallback` if any non-empty rank is flagged."""
    items = [
        (r, c) for r, c in sorted(rank_cols.items(), key=lambda kv: kv[0]) if len(c)
    ]
    if not items:
        return None
    for _, c in items:
        if not c.columnar_ok:
            raise ColumnarFallback("flagged rank buffer")

    uniq = np.unique(np.concatenate([c.steps_view() for _, c in items]))
    common = uniq[-max_steps:]
    S = int(common.size)
    lo = int(common[0])

    count = np.zeros(S, dtype=np.int64)
    nbytes = np.zeros(S, dtype=np.int64)
    dur = np.zeros(S, dtype=np.float64)
    exp = np.zeros(S, dtype=np.float64)
    ar_fp32 = np.zeros(S, dtype=np.int64)
    n_ops = len(COLLECTIVE_OPS)
    op_count = np.zeros(n_ops, dtype=np.int64)
    op_bytes = np.zeros(n_ops, dtype=np.int64)
    op_dur = np.zeros(n_ops, dtype=np.float64)
    op_exp = np.zeros(n_ops, dtype=np.float64)
    op_seen = np.zeros(n_ops, dtype=np.bool_)
    per_rank: Dict[int, Dict[str, float]] = {}
    group = 1
    ar_code = _COLL_OP_INDEX["all_reduce"]

    for rank, c in items:
        steps = c.steps_view()
        mask = steps >= lo
        slots = np.searchsorted(common, steps[mask])
        ints = c.ints_view()[mask]
        floats = c.floats_view()[mask]
        ops = c.ops_view()[mask].astype(np.int64)
        np.add.at(count, slots, ints[:, CC_COUNT])
        np.add.at(nbytes, slots, ints[:, CC_BYTES])
        np.add.at(dur, slots, floats[:, 0])
        np.add.at(exp, slots, floats[:, 1])
        np.add.at(op_count, ops, ints[:, CC_COUNT])
        np.add.at(op_bytes, ops, ints[:, CC_BYTES])
        np.add.at(op_dur, ops, floats[:, 0])
        np.add.at(op_exp, ops, floats[:, 1])
        op_seen[ops] = True
        try:
            fp32_code = c._dtype_index["float32"]
        except KeyError:
            fp32_code = -1
        fp32_mask = (ops == ar_code) & (c.dtypes_view()[mask] == fp32_code)
        if fp32_mask.any():
            np.add.at(ar_fp32, slots[fp32_mask], ints[fp32_mask, CC_BYTES])
        if ints.shape[0]:
            group = max(group, int(ints[:, CC_GROUP].max()))
            r_dur = float(np.cumsum(floats[:, 0])[-1])
            r_exp = float(np.cumsum(floats[:, 1])[-1])
            r_bytes = int(np.cumsum(ints[:, CC_BYTES])[-1])
        else:
            r_dur = r_exp = 0.0
            r_bytes = 0
        per_rank[rank] = {
            "duration_ms": r_dur,
            "exposed_ms": r_exp,
            "bytes": r_bytes,
            "overlap_efficiency": _overlap_efficiency(r_dur, r_exp),
        }

    dur_l = dur.tolist()
    exp_l = exp.tolist()
    # totals fold over the per-step series, matching the scalar loop
    total_dur = float(np.cumsum(dur)[-1]) if S else 0.0
    total_exp = float(np.cumsum(exp)[-1]) if S else 0.0
    per_op: Dict[str, Dict[str, float]] = {}
    for oi, op in enumerate(COLLECTIVE_OPS):
        if not op_seen[oi]:
            continue
        per_op[op] = {
            "count": int(op_count[oi]),
            "bytes": int(op_bytes[oi]),
            "duration_ms": float(op_dur[oi]),
            "exposed_ms": float(op_exp[oi]),
        }
    return CollectivesWindow(
        steps=common.tolist(),
        n_steps=S,
        ranks=[r for r, _ in items],
        group_size=group,
        per_step={
            "count": count.tolist(),
            "bytes": nbytes.tolist(),
            "duration_ms": dur_l,
            "exposed_ms": exp_l,
            "overlap_efficiency": [
                _overlap_efficiency(dur_l[i], exp_l[i]) for i in range(S)
            ],
            "allreduce_fp32_bytes": ar_fp32.tolist(),
        },
        per_op=per_op,
        per_rank=per_rank,
        totals={
            "count": int(np.cumsum(count)[-1]) if S else 0,
            "bytes": int(np.cumsum(nbytes)[-1]) if S else 0,
            "duration_ms": total_dur,
            "exposed_ms": total_exp,
            "overlap_efficiency": _overlap_efficiency(total_dur, total_exp),
        },
    )


def collectives_window_to_plain(
    w: Optional[CollectivesWindow],
) -> Optional[Dict[str, Any]]:
    """Canonical plain-dict form for golden comparisons."""
    if w is None:
        return None
    return {
        "steps": list(w.steps),
        "n_steps": w.n_steps,
        "ranks": list(w.ranks),
        "group_size": w.group_size,
        "per_step": {k: list(v) for k, v in w.per_step.items()},
        "per_op": {k: dict(v) for k, v in sorted(w.per_op.items())},
        "per_rank": {r: dict(v) for r, v in sorted(w.per_rank.items())},
        "totals": dict(w.totals),
    }


# ---------------------------------------------------------------------------
# Serving domain (round 16): ragged per-request populations → per-window
# TTFT / e2e percentile window.  Requests are variable-length where steps
# were regular, so the ring grows a CSR companion: per-row (offset, len)
# into shared value buffers.
# ---------------------------------------------------------------------------

# int column layout
(
    SV_STEP,
    SV_ENQ,
    SV_DONE,
    SV_ACTIVE,
    SV_QDEPTH,
    SV_DTOK,
    SV_KVB,
    SV_KVL,
) = range(8)
_SV_COUNT_FIELDS = (
    (SV_ENQ, "requests_enqueued"),
    (SV_DONE, "requests_completed"),
    (SV_ACTIVE, "requests_active"),
    (SV_QDEPTH, "queue_depth"),
    (SV_DTOK, "decode_tokens"),
)
# float column layout
SF_PREFILL, SF_DECODE, SF_TPS, SF_OCC, SF_KVH = range(5)
# ragged column layout (CSR offset/len per row into one buffer each)
RG_TTFT, RG_E2E, RG_TOK = range(3)
_RG_FIELDS = ((RG_TTFT, "ttft_ms_list"), (RG_E2E, "e2e_ms_list"), (RG_TOK, "tokens_list"))

_EMPTY_F64 = np.empty(0, dtype=np.float64)


def parse_float_list(s: Optional[str]) -> List[float]:
    """Parse a ``%.3f`` comma-packed population (the serving sampler's
    ``pack_floats`` format).  THE one parser both the scalar reference
    fold and :class:`RaggedEventColumns` use, so parse(pack(x)) yields
    bit-identical floats on both paths.  Raises on malformed tokens —
    the ring turns that into :class:`ColumnarFallback`, the scalar fold
    treats the row's population as empty."""
    if not s:
        return []
    return [float(tok) for tok in s.split(",")]


def _parse_float_list_safe(s: Optional[str]) -> List[float]:
    try:
        return parse_float_list(s)
    except (TypeError, ValueError):
        return []


def _population_percentile(sorted_vals, q: float) -> float:
    """Index-style percentile (no interpolation) over an ascending
    sequence — same element selection for a Python list and an ndarray,
    and the same formula samplers/serving_sampler.percentile uses."""
    n = len(sorted_vals)
    if n == 0:
        return 0.0
    return float(sorted_vals[min(n - 1, int(n * q))])


class _RaggedBuffer:
    """Growable value store behind one CSR column.

    Rows address their values by *virtual* offset — a monotone counter
    over everything ever appended — so row eviction is free (the head
    values just go dead) and compaction only rebases ``_virt0``, never
    touches the offsets stored in the ring.  Because rows append values
    contiguously and are only evicted from the head, any suffix of live
    rows maps to ONE contiguous physical slice (zero-copy reads)."""

    __slots__ = ("_vals", "_virt0", "_virt_end")

    def __init__(self, cap_hint: int) -> None:
        self._vals = np.empty(max(16, int(cap_hint)), dtype=np.float64)
        self._virt0 = 0  # virtual offset of physical index 0
        self._virt_end = 0  # next virtual offset

    def append(self, vals: List[float], live_min_virt: int) -> int:
        """Store ``vals``; returns their virtual offset.  ``live_min_virt``
        is the oldest live row's offset — everything before it is dead
        and reclaimable when the buffer needs room."""
        n = len(vals)
        end_phys = self._virt_end - self._virt0
        if end_phys + n > self._vals.shape[0]:
            live_phys = live_min_virt - self._virt0
            if live_phys > 0:  # memmove live span to the front, rebase
                live_n = end_phys - live_phys
                self._vals[:live_n] = self._vals[live_phys:end_phys]
                self._virt0 = live_min_virt
                end_phys = live_n
            if end_phys + n > self._vals.shape[0]:
                grown = np.empty(
                    max(2 * self._vals.shape[0], end_phys + n), dtype=np.float64
                )
                grown[:end_phys] = self._vals[:end_phys]
                self._vals = grown
        off = self._virt_end
        if n:
            self._vals[end_phys : end_phys + n] = vals
        self._virt_end += n
        return off

    def view_span(self, virt_a: int, virt_b: int) -> np.ndarray:
        return self._vals[virt_a - self._virt0 : virt_b - self._virt0]

    @property
    def virt_end(self) -> int:
        return self._virt_end


class RaggedEventColumns(_CompactRing):
    """Per-replica serving columns mirroring the store's row deque.

    Scalar columns ride the usual 2x-cap compacted arrays; the ragged
    per-request populations (TTFT ms, e2e ms, tokens) live in CSR form —
    per-row (virtual offset, length) pairs in ``_ragged`` pointing into
    three :class:`_RaggedBuffer` value stores.  Row eviction (ring full
    or retention trim) keeps the two in lockstep for free: offsets are
    virtual, so dead head values are reclaimed lazily on the buffers'
    next compaction.  Appends the vectorized build cannot reproduce
    exactly — bool/duplicate/out-of-order window seq, non-int counts,
    counts outside [0, 2**53), negative phase times, malformed packed
    lists, or a population length disagreeing with
    ``requests_completed`` — set sticky ``columnar_ok = False``."""

    __slots__ = (
        "_ints",
        "_floats",
        "_ragged",
        "_bufs",
        "_last_step",
        "columnar_ok",
    )

    def __init__(self, cap: int) -> None:
        super().__init__(cap)
        n = 2 * self.cap
        self._ints = np.empty((n, 8), dtype=np.int64)
        self._floats = np.empty((n, 5), dtype=np.float64)
        self._ragged = np.empty((n, 3, 2), dtype=np.int64)  # (row, col, {off, len})
        # value capacity hint: ~8 completed requests per window row
        self._bufs = tuple(_RaggedBuffer(8 * self.cap) for _ in range(3))
        self._last_step: Optional[int] = None
        self.columnar_ok = True

    def _arrays(self):
        return (self._ints, self._floats, self._ragged)

    def clear(self) -> None:
        self._reset()
        self._last_step = None
        self.columnar_ok = True
        # value buffers rebase lazily; virtual offsets of cleared rows
        # are simply never read again

    def _live_min_virt(self, col: int, newest: int) -> int:
        """Oldest live row's virtual offset for ``col`` (the compaction
        floor), excluding the not-yet-filled slot ``newest``."""
        if self._start < newest:
            return int(self._ragged[self._start, col, 0])
        return self._bufs[col].virt_end

    def append(self, row: Mapping[str, Any]) -> None:
        # always consume a slot (ring stays 1:1 with the row deque)
        i = self._next_slot()
        if not self.columnar_ok:
            return
        try:
            if isinstance(row["step"], bool):
                raise ColumnarFallback("bool step")
            step = int(row["step"])
            if self._last_step is not None and step <= self._last_step:
                raise ColumnarFallback("duplicate or out-of-order window seq")
            ints = self._ints[i]
            for c, key in _SV_COUNT_FIELDS:
                v = row.get(key, 0)
                if v is None:
                    v = 0
                if not isinstance(v, int) or isinstance(v, bool):
                    raise ColumnarFallback(key)
                if v < 0 or v >= _MAX_EXACT_INT:
                    raise ColumnarFallback(key)
                ints[c] = v
            for c, key in ((SV_KVB, "kv_bytes"), (SV_KVL, "kv_limit_bytes")):
                v = row.get(key, -1)
                if v is None:
                    v = -1
                if not isinstance(v, int) or isinstance(v, bool):
                    raise ColumnarFallback(key)
                if v < -1 or v >= _MAX_EXACT_INT:
                    raise ColumnarFallback(key)
                ints[c] = v
            flts = self._floats[i]
            pre = float(row.get("prefill_ms", 0.0) or 0.0)
            dec = float(row.get("decode_ms", 0.0) or 0.0)
            if pre < 0.0 or dec < 0.0:
                raise ColumnarFallback("negative phase time")
            flts[SF_PREFILL] = pre
            flts[SF_DECODE] = dec
            flts[SF_TPS] = float(row.get("tokens_per_s", 0.0) or 0.0)
            flts[SF_OCC] = float(row.get("batch_occupancy", 0.0) or 0.0)
            kvh = row.get("kv_headroom")
            flts[SF_KVH] = float(kvh) if kvh is not None else -1.0
            done = int(ints[SV_DONE])
            for c, key in _RG_FIELDS:
                vals = parse_float_list(row.get(key))  # raises → fallback
                if len(vals) != done:
                    raise ColumnarFallback(f"{key} length != requests_completed")
                off = self._bufs[c].append(vals, self._live_min_virt(c, i))
                self._ragged[i, c, 0] = off
                self._ragged[i, c, 1] = len(vals)
            ints[SV_STEP] = step
            self._last_step = step
        except Exception:
            self.columnar_ok = False

    # live views — valid until the next append/evict/clear
    def steps_view(self) -> np.ndarray:
        return self._ints[self._start : self._end, SV_STEP]

    def ints_view(self) -> np.ndarray:
        return self._ints[self._start : self._end]

    def floats_view(self) -> np.ndarray:
        return self._floats[self._start : self._end]

    def ragged_suffix(self, col: int, k: int) -> np.ndarray:
        """Concatenated population of live rows ``k..`` for ragged
        column ``col``.  Window seqs are strictly increasing, so every
        window tail is a row suffix — and a row suffix is ONE contiguous
        physical slice (values were appended in row order and only the
        head is ever evicted)."""
        n = len(self)
        if n == 0 or k >= n:
            return _EMPTY_F64
        i0 = self._start + k
        i1 = self._end - 1
        a = int(self._ragged[i0, col, 0])
        b = int(self._ragged[i1, col, 0] + self._ragged[i1, col, 1])
        return self._bufs[col].view_span(a, b)


@dataclasses.dataclass
class ServingWindow:
    """Cross-replica serving aggregate over the last ``n_steps`` window
    seqs.  Steps are the UNION of the replicas' window seqs; ``per_step``
    series align to ``steps``.  Latency percentiles re-rank the
    concatenated RAW per-request populations — never percentiles of the
    row-level percentiles."""

    steps: List[int]
    n_steps: int
    ranks: List[int]
    per_step: Dict[str, List[float]]
    per_rank: Dict[int, Dict[str, float]]
    totals: Dict[str, float]


def _serving_totals(
    enq, done, dtok, qd, pre, dec, per_rank, kv_min, ttft_sorted, e2e_sorted
) -> Dict[str, float]:
    """Shared totals assembly: every fold here is over per-step series
    or already-identical per-rank values, so the scalar and columnar
    builds compute bit-identical totals by construction."""
    total_pre = 0.0
    total_dec = 0.0
    for v in pre:
        total_pre += v
    for v in dec:
        total_dec += v
    phase = total_pre + total_dec
    tps = 0.0
    for r in per_rank:
        tps += per_rank[r]["tokens_per_s"]
    return {
        "requests_enqueued": sum(enq),
        "requests_completed": sum(done),
        "decode_tokens": sum(dtok),
        "queue_depth_last": sum(per_rank[r]["queue_depth"] for r in per_rank),
        "queue_depth_max": max(qd) if qd else 0,
        "prefill_ms": total_pre,
        "decode_ms": total_dec,
        "decode_share": (total_dec / phase) if phase > 0.0 else 0.0,
        "tokens_per_s": tps,
        "kv_headroom_min": kv_min,
        "ttft_p50_ms": _population_percentile(ttft_sorted, 0.50),
        "ttft_p95_ms": _population_percentile(ttft_sorted, 0.95),
        "ttft_p99_ms": _population_percentile(ttft_sorted, 0.99),
        "e2e_p50_ms": _population_percentile(e2e_sorted, 0.50),
        "e2e_p95_ms": _population_percentile(e2e_sorted, 0.95),
        "e2e_p99_ms": _population_percentile(e2e_sorted, 0.99),
    }


def build_serving_window_rows(
    rank_rows: Mapping[int, Any],
    max_steps: int,
) -> Optional[ServingWindow]:
    """Scalar reference fold over serving row dicts — the golden path
    the columnar build below must reproduce bit-identically.  Ranks in
    sorted order, rows in arrival order; malformed packed lists count
    as empty populations (the columnar ring would have flagged them)."""
    items = [(r, list(rows)) for r, rows in sorted(rank_rows.items()) if rows]
    if not items:
        return None
    all_steps = sorted({int(row["step"]) for _, rows in items for row in rows})
    steps = all_steps[-max_steps:]
    lo = steps[0]
    idx = {s: i for i, s in enumerate(steps)}
    S = len(steps)

    enq = [0] * S
    done = [0] * S
    qd = [0] * S
    dtok = [0] * S
    tps = [0.0] * S
    pre = [0.0] * S
    dec = [0.0] * S
    ttft_all: List[float] = []
    e2e_all: List[float] = []
    per_rank: Dict[int, Dict[str, float]] = {}
    kv_min = -1.0
    for rank, rows in items:
        r_done = 0
        r_tok = 0
        r_tps = 0.0
        r_rows = 0
        r_ttft: List[float] = []
        r_qd = 0
        r_active = 0
        r_kvh = -1.0
        for row in rows:
            s = int(row["step"])
            if s < lo:
                continue
            i = idx[s]
            e = int(row.get("requests_enqueued", 0) or 0)
            d = int(row.get("requests_completed", 0) or 0)
            q = int(row.get("queue_depth", 0) or 0)
            t = int(row.get("decode_tokens", 0) or 0)
            v_tps = float(row.get("tokens_per_s", 0.0) or 0.0)
            enq[i] += e
            done[i] += d
            qd[i] += q
            dtok[i] += t
            tps[i] += v_tps
            pre[i] += float(row.get("prefill_ms", 0.0) or 0.0)
            dec[i] += float(row.get("decode_ms", 0.0) or 0.0)
            t_vals = _parse_float_list_safe(row.get("ttft_ms_list"))
            e_vals = _parse_float_list_safe(row.get("e2e_ms_list"))
            ttft_all.extend(t_vals)
            e2e_all.extend(e_vals)
            r_ttft.extend(t_vals)
            r_done += d
            r_tok += t
            r_tps += v_tps
            r_rows += 1
            r_qd = q
            r_active = int(row.get("requests_active", 0) or 0)
            kvh = row.get("kv_headroom")
            kvh = float(kvh) if kvh is not None else -1.0
            if kvh >= 0.0:
                r_kvh = kvh
                kv_min = kvh if kv_min < 0.0 else min(kv_min, kvh)
        r_ttft.sort()
        per_rank[rank] = {
            "requests_completed": r_done,
            "requests_active": r_active,
            "decode_tokens": r_tok,
            "tokens_per_s": (r_tps / r_rows) if r_rows else 0.0,
            "queue_depth": r_qd,
            "ttft_p99_ms": _population_percentile(r_ttft, 0.99),
            "kv_headroom": r_kvh,
        }

    ttft_all.sort()
    e2e_all.sort()
    return ServingWindow(
        steps=steps,
        n_steps=S,
        ranks=[r for r, _ in items],
        per_step={
            "requests_enqueued": enq,
            "requests_completed": done,
            "queue_depth": qd,
            "decode_tokens": dtok,
            "tokens_per_s": tps,
            "prefill_ms": pre,
            "decode_ms": dec,
        },
        per_rank=per_rank,
        totals=_serving_totals(
            enq, done, dtok, qd, pre, dec, per_rank, kv_min, ttft_all, e2e_all
        ),
    )


def build_columnar_serving_window(
    rank_cols: Mapping[int, RaggedEventColumns],
    max_steps: int,
) -> Optional[ServingWindow]:
    """Vectorized ``build_serving_window_rows`` over per-replica ragged
    columns.  Per-slot accumulation uses ``np.add.at`` in sorted-rank
    order (the scalar traversal); window seqs are strictly increasing
    per replica, so the ``>= lo`` tail is a row suffix and each ragged
    population is ONE contiguous slice.  Raises :class:`ColumnarFallback`
    if any non-empty replica buffer is flagged."""
    items = [
        (r, c) for r, c in sorted(rank_cols.items(), key=lambda kv: kv[0]) if len(c)
    ]
    if not items:
        return None
    for _, c in items:
        if not c.columnar_ok:
            raise ColumnarFallback("flagged replica buffer")

    uniq = np.unique(np.concatenate([c.steps_view() for _, c in items]))
    common = uniq[-max_steps:]
    S = int(common.size)
    lo = int(common[0])

    enq = np.zeros(S, dtype=np.int64)
    done = np.zeros(S, dtype=np.int64)
    qd = np.zeros(S, dtype=np.int64)
    dtok = np.zeros(S, dtype=np.int64)
    tps = np.zeros(S, dtype=np.float64)
    pre = np.zeros(S, dtype=np.float64)
    dec = np.zeros(S, dtype=np.float64)
    ttft_parts: List[np.ndarray] = []
    e2e_parts: List[np.ndarray] = []
    per_rank: Dict[int, Dict[str, float]] = {}
    kv_min = -1.0
    for rank, c in items:
        steps = c.steps_view()
        k = int(np.searchsorted(steps, lo, side="left"))
        slots = np.searchsorted(common, steps[k:])
        ints = c.ints_view()[k:]
        flts = c.floats_view()[k:]
        np.add.at(enq, slots, ints[:, SV_ENQ])
        np.add.at(done, slots, ints[:, SV_DONE])
        np.add.at(qd, slots, ints[:, SV_QDEPTH])
        np.add.at(dtok, slots, ints[:, SV_DTOK])
        np.add.at(tps, slots, flts[:, SF_TPS])
        np.add.at(pre, slots, flts[:, SF_PREFILL])
        np.add.at(dec, slots, flts[:, SF_DECODE])
        r_ttft = c.ragged_suffix(RG_TTFT, k)
        ttft_parts.append(r_ttft)
        e2e_parts.append(c.ragged_suffix(RG_E2E, k))
        n_rows = int(ints.shape[0])
        if n_rows:
            r_done = int(np.cumsum(ints[:, SV_DONE])[-1])
            r_tok = int(np.cumsum(ints[:, SV_DTOK])[-1])
            r_tps = float(np.cumsum(flts[:, SF_TPS])[-1]) / n_rows
            r_qd = int(ints[-1, SV_QDEPTH])
            r_active = int(ints[-1, SV_ACTIVE])
        else:
            r_done = r_tok = r_qd = r_active = 0
            r_tps = 0.0
        kvh = flts[:, SF_KVH]
        kv_ok = kvh >= 0.0
        r_kvh = -1.0
        if kv_ok.any():
            r_kvh = float(kvh[np.flatnonzero(kv_ok)[-1]])
            m = float(kvh[kv_ok].min())
            kv_min = m if kv_min < 0.0 else min(kv_min, m)
        per_rank[rank] = {
            "requests_completed": r_done,
            "requests_active": r_active,
            "decode_tokens": r_tok,
            "tokens_per_s": r_tps,
            "queue_depth": r_qd,
            "ttft_p99_ms": _population_percentile(np.sort(r_ttft), 0.99),
            "kv_headroom": r_kvh,
        }

    ttft_sorted = np.sort(np.concatenate(ttft_parts)) if ttft_parts else _EMPTY_F64
    e2e_sorted = np.sort(np.concatenate(e2e_parts)) if e2e_parts else _EMPTY_F64
    enq_l = enq.tolist()
    done_l = done.tolist()
    qd_l = qd.tolist()
    dtok_l = dtok.tolist()
    return ServingWindow(
        steps=common.tolist(),
        n_steps=S,
        ranks=[r for r, _ in items],
        per_step={
            "requests_enqueued": enq_l,
            "requests_completed": done_l,
            "queue_depth": qd_l,
            "decode_tokens": dtok_l,
            "tokens_per_s": tps.tolist(),
            "prefill_ms": pre.tolist(),
            "decode_ms": dec.tolist(),
        },
        per_rank=per_rank,
        totals=_serving_totals(
            enq_l,
            done_l,
            dtok_l,
            qd_l,
            pre.tolist(),
            dec.tolist(),
            per_rank,
            kv_min,
            ttft_sorted,
            e2e_sorted,
        ),
    )


def serving_window_to_plain(w: Optional[ServingWindow]) -> Optional[Dict[str, Any]]:
    """Canonical plain-dict form for golden comparisons."""
    if w is None:
        return None
    return {
        "steps": list(w.steps),
        "n_steps": w.n_steps,
        "ranks": list(w.ranks),
        "per_step": {k: list(v) for k, v in w.per_step.items()},
        "per_rank": {r: dict(v) for r, v in sorted(w.per_rank.items())},
        "totals": dict(w.totals),
    }


# ---------------------------------------------------------------------------
# Incremental window engine (round 19): persistent per-domain caches that
# turn a steady-state dirty tick into O(Δ) work.  The full builds above
# stay the golden reference — every code path below either reproduces
# their output bit-identically or invalidates back to them.
# ---------------------------------------------------------------------------

_EMPTY_I64 = np.empty(0, dtype=np.int64)

# invalidation reasons (the observability vocabulary surfaced through
# WindowBuildStats; tests pin these strings)
INVALIDATE_COLD = "cold_start"
INVALIDATE_RANKS = "rank_set_changed"
INVALIDATE_CLOCK = "clock_flip"
INVALIDATE_EVICTED = "window_evicted"
INVALIDATE_SIZE = "window_size_changed"
INVALIDATE_REALIGNED = "realigned"
INVALIDATE_FALLBACK = "fallback"


class _CacheInvalid(Exception):
    """Internal: the delta path cannot represent this tick exactly —
    fall back to a full rebuild (carrying the reason for the stats)."""

    def __init__(self, reason: str) -> None:
        super().__init__(reason)
        self.reason = reason


class WindowBuildStats:
    """Per-domain window-build counters: how many ticks were served
    incrementally vs full rebuilds, why the cache invalidated, and the
    last build's wall time.  Surfaced through the snapshot store →
    ``payload_with_versions`` meta → dashboard / final report, so a
    session silently degrading to full rebuilds is visible."""

    __slots__ = (
        "incr_ticks", "full_rebuilds", "invalidations",
        "last_build_ms", "last_path",
    )

    def __init__(self) -> None:
        self.incr_ticks = 0
        self.full_rebuilds = 0
        self.invalidations: Dict[str, int] = {}
        self.last_build_ms = 0.0
        self.last_path = ""

    def note_incr(self, ms: float) -> None:
        self.incr_ticks += 1
        self.last_build_ms = ms
        self.last_path = "incremental"

    def note_full(self, ms: float, reason: str) -> None:
        self.full_rebuilds += 1
        self.invalidations[reason] = self.invalidations.get(reason, 0) + 1
        self.last_build_ms = ms
        self.last_path = "full"

    def snapshot(self) -> Dict[str, Any]:
        return {
            "incr_ticks": self.incr_ticks,
            "full_rebuilds": self.full_rebuilds,
            "invalidations": dict(self.invalidations),
            "last_build_ms": self.last_build_ms,
            "last_path": self.last_path,
        }


#: tick-profiler stage vocabulary (docs/developer_guide/diagnosis-engine.md);
#: tests pin these strings the same way they pin INVALIDATE_*
TICK_STAGES = (
    "refresh", "build", "diagnose", "attribute", "view", "serialize",
)


class TickProfile:
    """Per-stage warm-tick profiler: cumulative nanoseconds per
    (domain, stage) plus counters (diagnosis cache hits/misses, rule
    evaluations, vector fallbacks, attribution grouping reuse).

    Extends r19's :class:`WindowBuildStats` from "where did the window
    build go" to "where did the whole tick go": refresh → build →
    diagnose → attribute → view → serialize.  Lives on the snapshot
    store and is read through ``LiveSnapshotStore.window_build_stats()``
    and the final report's ``meta.window_build``, so per-stage overhead
    is visible without attaching a profiler (the T3 motivation: the
    observer's own cost must itself be observable)."""

    __slots__ = ("ticks", "stage_ns", "counters")

    def __init__(self) -> None:
        self.ticks = 0
        self.stage_ns: Dict[str, Dict[str, int]] = {}
        self.counters: Dict[str, int] = {}

    def note_tick(self) -> None:
        self.ticks += 1

    def note_stage(self, domain: str, stage: str, ns: int) -> None:
        per = self.stage_ns.setdefault(domain, {})
        per[stage] = per.get(stage, 0) + int(ns)

    def bump(self, counter: str, n: int = 1) -> None:
        self.counters[counter] = self.counters.get(counter, 0) + n

    def snapshot(self) -> Dict[str, Any]:
        return {
            "ticks": self.ticks,
            "stage_ns": {
                d: {s: per[s] for s in sorted(per)}
                for d, per in sorted(self.stage_ns.items())
            },
            "counters": {k: self.counters[k] for k in sorted(self.counters)},
        }


class _WindowCacheBase:
    """Shared build shell: try the delta tick, invalidate to the full
    (golden) build on any condition the delta path cannot represent
    exactly, re-prime the cache from the full result, and keep the
    counters honest.  ``ColumnarFallback`` propagates to the caller
    (the store runs the scalar reference) after noting the reason."""

    def __init__(self) -> None:
        self.stats = WindowBuildStats()
        self._valid = False

    def invalidate(self) -> None:
        self._valid = False

    # subclass hooks -----------------------------------------------------
    def _tick(self, rank_cols, max_steps):  # pragma: no cover - abstract
        raise NotImplementedError

    def _full_build(self, rank_cols, max_steps):  # pragma: no cover
        raise NotImplementedError

    def _prime(self, window, rank_cols, max_steps):  # pragma: no cover
        raise NotImplementedError

    # --------------------------------------------------------------------
    def build(self, rank_cols, max_steps: int):
        t0 = time.perf_counter()
        try:
            window = self._tick(rank_cols, max_steps)
        except _CacheInvalid as inv:
            self._valid = False
            try:
                window = self._full_build(rank_cols, max_steps)
            except ColumnarFallback:
                self.stats.note_full(
                    (time.perf_counter() - t0) * 1000.0, INVALIDATE_FALLBACK
                )
                raise
            self._prime(window, rank_cols, max_steps)
            self.stats.note_full(
                (time.perf_counter() - t0) * 1000.0, inv.reason
            )
            return window
        except ColumnarFallback:
            self._valid = False
            self.stats.note_full(
                (time.perf_counter() - t0) * 1000.0, INVALIDATE_FALLBACK
            )
            raise
        self.stats.note_incr((time.perf_counter() - t0) * 1000.0)
        return window

    @staticmethod
    def _sorted_items(rank_cols):
        items = [
            (int(r), c)
            for r, c in sorted(rank_cols.items(), key=lambda kv: kv[0])
            if len(c)
        ]
        for _, c in items:
            if not c.columnar_ok:
                raise ColumnarFallback("flagged rank buffer")
        return items


class StepTimeWindowCache(_WindowCacheBase):
    """Persistent aligned-cube cache for the step_time window.

    The cache owns a (rank, 11, step) series-cube buffer with slack
    along the step axis (2x the window, compacted with a memmove like
    :class:`_CompactRing`).  A dirty tick:

    * gathers/clamps ONLY the newly-common aligned columns and appends
      them (each column depends only on its own raw values, so a column
      built at tick t is bit-identical to the same column inside a
      from-scratch cube);
    * slides the window head past ring-evicted steps (head-only
      eviction + strictly-ascending per-rank steps mean a surviving
      cached step is still common — mid-window membership changes are
      impossible, so a slide is exact, not an approximation);
    * re-folds averages/occupancy over the cached cube with the same
      exact left-fold the full build uses (float window sums cannot be
      delta-updated bit-exactly — ``(a+b)-a != b`` in IEEE — but the
      fold over the cached cube is cheap); medians stay lazy.

    Invalidation → full rebuild: rank-set change, clock flip, window
    length change, the whole cache evicted, or ``ColumnarFallback``.

    Aliasing contract: emitted windows hand out views into the cache
    buffers and are valid until the next ``build()`` — the same
    lifetime the ring views already have.  Consumers (LiveComputer)
    serialize within the tick.
    """

    def __init__(self) -> None:
        super().__init__()
        self._max_steps = 0
        self._ranks: List[int] = []
        self._clock = "host"
        self._last_aligned = 0
        self._cap = 0
        self._lo = 0
        self._hi = 0
        self._steps: Optional[np.ndarray] = None
        self._cube: Optional[np.ndarray] = None
        self._num: Optional[np.ndarray] = None
        self._host: Optional[np.ndarray] = None
        self._phase_any: Optional[np.ndarray] = None
        # step-major mirrors of cube/num/host: the per-tick re-folds
        # walk contiguous (R, …) slices instead of strided lanes
        # (same adds, same order, same bits — see _fold_step_major)
        self._cube_t: Optional[np.ndarray] = None
        self._num_t: Optional[np.ndarray] = None
        self._host_t: Optional[np.ndarray] = None
        # per-rank (sorted order) bookkeeping that lets the warm tick
        # skip binary searches: appended_total snapshot at last tick,
        # and whether the rank's newest row WAS the aligned tail
        self._seen_appended: List[int] = []
        self._aligned: List[bool] = []

    def _full_build(self, rank_cols, max_steps):
        return build_columnar_step_time_window(rank_cols, max_steps)

    def _tick(self, rank_cols, max_steps):
        if not self._valid:
            raise _CacheInvalid(INVALIDATE_COLD)
        if int(max_steps) != self._max_steps:
            raise _CacheInvalid(INVALIDATE_SIZE)
        items = self._sorted_items(rank_cols)
        if [r for r, _ in items] != self._ranks:
            raise _CacheInvalid(INVALIDATE_RANKS)
        R = len(items)
        la = self._last_aligned
        dev_cached = self._clock == "device"

        head_floor = None
        svs: List[np.ndarray] = []
        tails: List[np.ndarray] = []
        lasts: List[int] = []
        new_app: List[int] = []
        any_empty_tail = False
        for i, (_, c) in enumerate(items):
            sv = c.steps_view()
            svs.append(sv)
            first = int(sv[0])
            if head_floor is None or first > head_floor:
                head_floor = first
            lasts.append(int(sv[-1]))
            n_new = c.appended_total - self._seen_appended[i]
            new_app.append(c.appended_total)
            k = n_new if n_new < sv.size else sv.size
            if k > 0:
                if int(sv[sv.size - k]) <= la:
                    # a cleared-and-restarted rank re-reported an old
                    # step; the intersection delta cannot express that
                    raise _CacheInvalid(INVALIDATE_REALIGNED)
                if dev_cached and not c.clock_tail_device(k):
                    raise _CacheInvalid(INVALIDATE_CLOCK)
            if self._aligned[i]:
                # the rank's newest row WAS the aligned tail, so its
                # candidate rows are exactly the surviving appends since
                # last tick (strict per-rank ascent puts them above la)
                # — no binary search needed on the warm path
                t = sv[sv.size - k :] if k > 0 else _EMPTY_I64
            else:
                # rank ran ahead of the aligned tail last tick: older
                # rows above la are candidates too
                t = sv[int(np.searchsorted(sv, la, side="right")):]
            tails.append(t)
            if t.size == 0:
                any_empty_tail = True
        if dev_cached:
            clock = "device"
        else:
            # host → device flips only when every host-clocked row has
            # evicted; the scan short-circuits on the first host row
            for _, c in items:
                if not c.clock_all_device():
                    break
            else:
                raise _CacheInvalid(INVALIDATE_CLOCK)
            clock = "host"
        # every check that can invalidate has passed — commit counters
        self._seen_appended = new_app

        # newly-common steps: present in EVERY rank's post-cache tail
        # (a step at or below the cached tail cannot gain membership —
        # per-rank steps are strictly ascending)
        if any_empty_tail:
            new_common = _EMPTY_I64
        elif R == 1:
            new_common = tails[0]
        else:
            uniq, counts = np.unique(np.concatenate(tails), return_counts=True)
            new_common = uniq[counts == R]

        if new_common.size:
            new_common = new_common[-self._max_steps:]
            n_new = int(new_common.size)
            self._ensure_capacity(n_new)
            hi = self._hi
            cube_raw = np.empty((R, n_new, N_EVENTS, 2), dtype=np.float64)
            occ_parts = np.empty((R, n_new, 2), dtype=np.float64)
            for i, (_, c) in enumerate(items):
                t = tails[i]
                base = svs[i].size - t.size
                if t.size == n_new:
                    # new_common ⊆ every tail, so equal size means equal
                    # content — the gather is a plain tail slice (the
                    # warm steady-state path: one new step, all ranks)
                    cube_raw[i] = c.vals_view()[base:]
                    occ_parts[i] = c.occ_view()[base:]
                else:
                    idx = base + np.searchsorted(t, new_common)
                    cube_raw[i] = c.vals_view()[idx]
                    occ_parts[i] = c.occ_view()[idx]
            slab = _select_clamp_slab(cube_raw, clock)
            self._cube[:, :, hi : hi + n_new] = slab
            num, host = _zeroed_occ_parts(occ_parts)
            self._num[:, hi : hi + n_new] = num
            self._host[:, hi : hi + n_new] = host
            self._cube_t[hi : hi + n_new] = np.moveaxis(slab, 2, 0)
            self._num_t[hi : hi + n_new] = num.T
            self._host_t[hi : hi + n_new] = host.T
            for j in range(len(ACCOUNTED_PHASES)):
                self._phase_any[j, hi : hi + n_new] = (
                    slab[:, 1 + j, :] > 0
                ).any(axis=0)
            self._steps[hi : hi + n_new] = new_common
            self._hi = hi + n_new
            self._last_aligned = int(new_common[-1])
        new_la = self._last_aligned
        self._aligned = [l == new_la for l in lasts]

        # slide the head past evicted steps, then clamp to the window
        lo = self._lo + int(
            np.searchsorted(
                self._steps[self._lo : self._hi], head_floor, side="left"
            )
        )
        self._lo = max(lo, self._hi - self._max_steps)
        if self._hi == self._lo:
            return None  # intersection empty — matches the full build
        return self._emit(clock)

    def _ensure_capacity(self, n_new: int) -> None:
        if self._hi + n_new <= self._cap:
            return
        live = self._hi - self._lo
        lo, hi = self._lo, self._hi
        # live + n_new <= 2*max_steps == cap by construction (new
        # columns are pre-clamped to the window length)
        self._steps[:live] = self._steps[lo:hi]
        self._cube[:, :, :live] = self._cube[:, :, lo:hi]
        self._num[:, :live] = self._num[:, lo:hi]
        self._host[:, :live] = self._host[:, lo:hi]
        self._cube_t[:live] = self._cube_t[lo:hi]
        self._num_t[:live] = self._num_t[lo:hi]
        self._host_t[:live] = self._host_t[lo:hi]
        self._phase_any[:, :live] = self._phase_any[:, lo:hi]
        self._lo, self._hi = 0, live

    def _emit(self, clock: str) -> ColumnarStepTimeWindow:
        lo, hi = self._lo, self._hi
        S = hi - lo
        steps = self._steps[lo:hi]
        cube = self._cube[:, :, lo:hi]
        averages = _fold_step_major(self._cube_t, lo, hi) / S
        occupancy = _occupancy_from_sums(
            _fold_step_major(self._num_t, lo, hi),
            _fold_step_major(self._host_t, lo, hi),
        )
        metrics = _step_time_metrics(averages, self._ranks)
        phases_present = [
            k
            for j, k in enumerate(ACCOUNTED_PHASES)
            if bool(self._phase_any[j, lo:hi].any())
        ]
        steps_list = steps.tolist()
        col = _ColumnarData(
            ranks=list(self._ranks),
            steps=steps,
            series_cube=cube,
            averages=averages,
            medians=None,
            occupancy=occupancy,
        )
        return ColumnarStepTimeWindow(
            col=col,
            clock=clock,
            steps=steps_list,
            ranks=list(self._ranks),
            rank_windows=_LazyRankWindows(col, steps_list, clock),
            metrics=metrics,
            phases_present=phases_present,
            n_steps=S,
        )

    def _prime(self, window, rank_cols, max_steps) -> None:
        if window is None:
            self._valid = False
            return
        col = window.col
        R = len(col.ranks)
        S = window.n_steps
        self._max_steps = int(max_steps)
        self._cap = 2 * self._max_steps
        self._steps = np.empty(self._cap, dtype=np.int64)
        self._cube = np.empty((R, len(ALL_KEYS), self._cap), dtype=np.float64)
        self._num = np.empty((R, self._cap), dtype=np.float64)
        self._host = np.empty((R, self._cap), dtype=np.float64)
        self._cube_t = np.empty(
            (self._cap, R, len(ALL_KEYS)), dtype=np.float64
        )
        self._num_t = np.empty((self._cap, R), dtype=np.float64)
        self._host_t = np.empty((self._cap, R), dtype=np.float64)
        self._phase_any = np.empty(
            (len(ACCOUNTED_PHASES), self._cap), dtype=np.bool_
        )
        self._steps[:S] = col.steps
        self._cube[:, :, :S] = col.series_cube
        self._num[:, :S] = col.occ_num
        self._host[:, :S] = col.occ_host
        self._cube_t[:S] = np.moveaxis(col.series_cube, 2, 0)
        self._num_t[:S] = col.occ_num.T
        self._host_t[:S] = col.occ_host.T
        for j in range(len(ACCOUNTED_PHASES)):
            self._phase_any[j, :S] = (col.series_cube[:, 1 + j, :] > 0).any(
                axis=0
            )
        self._lo, self._hi = 0, S
        self._ranks = list(col.ranks)
        self._clock = window.clock
        self._last_aligned = int(col.steps[-1])
        items = self._sorted_items(rank_cols)
        self._seen_appended = [c.appended_total for _, c in items]
        self._aligned = [
            int(c.steps_view()[-1]) == self._last_aligned for _, c in items
        ]
        self._valid = True


class _SlotWindowCacheBase(_WindowCacheBase):
    """Shared machinery for the union-aligned (collectives/serving)
    caches: per-step slot arrays with 2x slack, a delta scan that
    classifies newly appended rows against the cached window, and a
    conservative eviction guard keyed on the rings' monotone
    ``appended_total``/``evicted_total`` counters.

    Slot exactness: a cached slot value equals the fold (in sorted-rank,
    row-order — ``np.add.at`` element order) over ALL live rows carrying
    that step.  A new row landing on a cached step makes the slot
    "touched"; touched and new slots are recomputed from scratch from
    the raw rows, so partial-sum merging (which would change IEEE fold
    grouping) never happens.

    Invalidation: a mid-window union insert (new step ≤ cached max not
    already cached) or a below-window insert while the union is still
    shorter than the window ("realigned"), and any eviction whose
    surviving head sits at/above the cached window start
    ("window_evicted" — the evicted rows might have contributed to
    cached slots).  Evictions strictly below the window are provably
    harmless: head-only eviction + non-decreasing steps mean every
    evicted step ≤ the surviving oldest step < window start."""

    def __init__(self) -> None:
        super().__init__()
        self._max_steps = 0
        self._cap = 0
        self._lo = 0
        self._hi = 0
        self._steps: Optional[np.ndarray] = None
        self._ranks: List[int] = []
        self._seen_appended: List[int] = []
        self._seen_evicted: List[int] = []

    def _slot_arrays(self):  # pragma: no cover - abstract
        raise NotImplementedError

    def _scan_delta(self, items, max_steps: int):
        """Classify each rank's newly appended rows.  Returns
        ``(touched_steps, new_union_steps)`` or raises
        :class:`_CacheInvalid`.  Seen counters advance only when every
        check passed (a raise re-primes them anyway)."""
        if [r for r, _ in items] != self._ranks:
            raise _CacheInvalid(INVALIDATE_RANKS)
        lo_step = int(self._steps[self._lo])
        cached_max = int(self._steps[self._hi - 1])
        cached_S = self._hi - self._lo
        cs = self._steps[self._lo : self._hi]
        touched: set = set()
        tail_parts: List[np.ndarray] = []
        new_app: List[int] = []
        new_ev: List[int] = []
        for i, (_, c) in enumerate(items):
            new_app.append(c.appended_total)
            new_ev.append(c.evicted_total)
            sv = c.steps_view()
            if c.evicted_total != self._seen_evicted[i] and int(sv[0]) >= lo_step:
                raise _CacheInvalid(INVALIDATE_EVICTED)
            n_new = c.appended_total - self._seen_appended[i]
            if n_new <= 0:
                continue
            ns = sv[max(0, sv.size - n_new):]
            pos = int(np.searchsorted(ns, cached_max, side="right"))
            old_part = ns[:pos]
            if old_part.size:
                below = old_part[old_part < lo_step]
                if below.size and cached_S < max_steps:
                    # the union (== cached window) would grow downward
                    raise _CacheInvalid(INVALIDATE_REALIGNED)
                within = old_part[old_part >= lo_step]
                if within.size:
                    at = np.searchsorted(cs, within)
                    if bool((cs[at] != within).any()):
                        # mid-window union insert
                        raise _CacheInvalid(INVALIDATE_REALIGNED)
                    touched.update(int(x) for x in within)
            if pos < ns.size:
                tail_parts.append(ns[pos:])
        if tail_parts:
            if len(tail_parts) > 1:
                new_steps = np.unique(np.concatenate(tail_parts))
            else:
                new_steps = np.unique(tail_parts[0])
        else:
            new_steps = _EMPTY_I64
        self._seen_appended = new_app
        self._seen_evicted = new_ev
        return touched, new_steps

    def _ensure_slot_capacity(self, n_new: int) -> None:
        if self._hi + n_new <= self._cap:
            return
        live = self._hi - self._lo
        lo, hi = self._lo, self._hi
        self._steps[:live] = self._steps[lo:hi]
        for a in self._slot_arrays():
            a[:live] = a[lo:hi]
        self._lo, self._hi = 0, live

    def _append_and_touch(self, touched, new_steps):
        """Append zeroed slots for the new union steps (pre-clamped to
        the window), slide the window, and mark them for recompute."""
        if new_steps.size:
            new_steps = new_steps[-self._max_steps:]
            n_new = int(new_steps.size)
            self._ensure_slot_capacity(n_new)
            hi = self._hi
            self._steps[hi : hi + n_new] = new_steps
            for a in self._slot_arrays():
                a[hi : hi + n_new] = 0
            self._hi = hi + n_new
            self._lo = max(self._lo, self._hi - self._max_steps)
            touched.update(int(x) for x in new_steps)
        return touched

    def _prime_common(self, window, rank_cols) -> None:
        self._lo, self._hi = 0, window.n_steps
        items = self._sorted_items(rank_cols)
        self._ranks = [r for r, _ in items]
        self._seen_appended = [c.appended_total for _, c in items]
        self._seen_evicted = [c.evicted_total for _, c in items]
        self._valid = True


class CollectivesWindowCache(_SlotWindowCacheBase):
    """Incremental collectives window: per-step count/bytes/duration/
    exposed/allreduce-fp32 slots are cached and delta-maintained; the
    per-op / per-rank / group aggregates are re-folded each tick over
    the live row suffixes with the exact full-build fold (their fold
    start moves with the window head, so a cached partial sum cannot be
    reused bit-exactly — the refold over ring views is still far
    cheaper than the full gather + per-slot scatter)."""

    def __init__(self) -> None:
        super().__init__()
        self._count: Optional[np.ndarray] = None
        self._bytes: Optional[np.ndarray] = None
        self._ar: Optional[np.ndarray] = None
        self._dur: Optional[np.ndarray] = None
        self._exp: Optional[np.ndarray] = None

    def _slot_arrays(self):
        return (self._count, self._bytes, self._ar, self._dur, self._exp)

    def _full_build(self, rank_cols, max_steps):
        return build_columnar_collectives_window(rank_cols, max_steps)

    def _tick(self, rank_cols, max_steps):
        if not self._valid:
            raise _CacheInvalid(INVALIDATE_COLD)
        if int(max_steps) != self._max_steps:
            raise _CacheInvalid(INVALIDATE_SIZE)
        items = self._sorted_items(rank_cols)
        if not items:
            raise _CacheInvalid(INVALIDATE_RANKS)
        touched, new_steps = self._scan_delta(items, self._max_steps)
        touched = self._append_and_touch(touched, new_steps)
        lo_step = int(self._steps[self._lo])
        buf = self._steps[: self._hi]
        for s in sorted(touched):
            if s < lo_step:
                continue
            self._recompute_slot(int(np.searchsorted(buf, s)), s, items)
        return self._emit(items)

    def _recompute_slot(self, j: int, s: int, items) -> None:
        # from-scratch fold over ALL live rows carrying step s, in
        # sorted-rank row order — np.add.at element order, so the slot
        # is bit-identical to the full build's scatter
        ar_code = _COLL_OP_INDEX["all_reduce"]
        cnt = 0
        byt = 0
        ar = 0
        d_acc = 0.0
        e_acc = 0.0
        for _, c in items:
            sv = c.steps_view()
            a = int(np.searchsorted(sv, s, side="left"))
            b = int(np.searchsorted(sv, s, side="right"))
            if a == b:
                continue
            ints = c.ints_view()
            flts = c.floats_view()
            ops = c.ops_view()
            dts = c.dtypes_view()
            fp32 = c._dtype_index.get("float32", -1)
            for t in range(a, b):
                cnt += int(ints[t, CC_COUNT])
                byt += int(ints[t, CC_BYTES])
                d_acc += float(flts[t, 0])
                e_acc += float(flts[t, 1])
                if int(ops[t]) == ar_code and int(dts[t]) == fp32:
                    ar += int(ints[t, CC_BYTES])
        self._count[j] = cnt
        self._bytes[j] = byt
        self._ar[j] = ar
        self._dur[j] = d_acc
        self._exp[j] = e_acc

    def _emit(self, items) -> CollectivesWindow:
        lo, hi = self._lo, self._hi
        S = hi - lo
        common = self._steps[lo:hi]
        lo_step = int(common[0])
        n_ops = len(COLLECTIVE_OPS)
        op_count = np.zeros(n_ops, dtype=np.int64)
        op_bytes = np.zeros(n_ops, dtype=np.int64)
        op_dur = np.zeros(n_ops, dtype=np.float64)
        op_exp = np.zeros(n_ops, dtype=np.float64)
        op_seen = np.zeros(n_ops, dtype=np.bool_)
        per_rank: Dict[int, Dict[str, float]] = {}
        group = 1
        for rank, c in items:
            sv = c.steps_view()
            k = int(np.searchsorted(sv, lo_step, side="left"))
            ints = c.ints_view()[k:]
            floats = c.floats_view()[k:]
            ops = c.ops_view()[k:].astype(np.int64)
            np.add.at(op_count, ops, ints[:, CC_COUNT])
            np.add.at(op_bytes, ops, ints[:, CC_BYTES])
            np.add.at(op_dur, ops, floats[:, 0])
            np.add.at(op_exp, ops, floats[:, 1])
            op_seen[ops] = True
            if ints.shape[0]:
                group = max(group, int(ints[:, CC_GROUP].max()))
                r_dur = float(np.cumsum(floats[:, 0])[-1])
                r_exp = float(np.cumsum(floats[:, 1])[-1])
                r_bytes = int(np.cumsum(ints[:, CC_BYTES])[-1])
            else:
                r_dur = r_exp = 0.0
                r_bytes = 0
            per_rank[rank] = {
                "duration_ms": r_dur,
                "exposed_ms": r_exp,
                "bytes": r_bytes,
                "overlap_efficiency": _overlap_efficiency(r_dur, r_exp),
            }

        count = self._count[lo:hi]
        nbytes = self._bytes[lo:hi]
        dur = self._dur[lo:hi]
        exp = self._exp[lo:hi]
        dur_l = dur.tolist()
        exp_l = exp.tolist()
        total_dur = float(np.cumsum(dur)[-1]) if S else 0.0
        total_exp = float(np.cumsum(exp)[-1]) if S else 0.0
        per_op: Dict[str, Dict[str, float]] = {}
        for oi, op in enumerate(COLLECTIVE_OPS):
            if not op_seen[oi]:
                continue
            per_op[op] = {
                "count": int(op_count[oi]),
                "bytes": int(op_bytes[oi]),
                "duration_ms": float(op_dur[oi]),
                "exposed_ms": float(op_exp[oi]),
            }
        return CollectivesWindow(
            steps=common.tolist(),
            n_steps=S,
            ranks=list(self._ranks),
            group_size=group,
            per_step={
                "count": count.tolist(),
                "bytes": nbytes.tolist(),
                "duration_ms": dur_l,
                "exposed_ms": exp_l,
                "overlap_efficiency": [
                    _overlap_efficiency(dur_l[i], exp_l[i]) for i in range(S)
                ],
                "allreduce_fp32_bytes": self._ar[lo:hi].tolist(),
            },
            per_op=per_op,
            per_rank=per_rank,
            totals={
                "count": int(np.cumsum(count)[-1]) if S else 0,
                "bytes": int(np.cumsum(nbytes)[-1]) if S else 0,
                "duration_ms": total_dur,
                "exposed_ms": total_exp,
                "overlap_efficiency": _overlap_efficiency(total_dur, total_exp),
            },
        )

    def _prime(self, window, rank_cols, max_steps) -> None:
        if window is None:
            self._valid = False
            return
        self._max_steps = int(max_steps)
        self._cap = 2 * self._max_steps
        S = window.n_steps
        self._steps = np.empty(self._cap, dtype=np.int64)
        self._count = np.empty(self._cap, dtype=np.int64)
        self._bytes = np.empty(self._cap, dtype=np.int64)
        self._ar = np.empty(self._cap, dtype=np.int64)
        self._dur = np.empty(self._cap, dtype=np.float64)
        self._exp = np.empty(self._cap, dtype=np.float64)
        self._steps[:S] = np.asarray(window.steps, dtype=np.int64)
        ps = window.per_step
        self._count[:S] = np.asarray(ps["count"], dtype=np.int64)
        self._bytes[:S] = np.asarray(ps["bytes"], dtype=np.int64)
        self._ar[:S] = np.asarray(ps["allreduce_fp32_bytes"], dtype=np.int64)
        self._dur[:S] = np.asarray(ps["duration_ms"], dtype=np.float64)
        self._exp[:S] = np.asarray(ps["exposed_ms"], dtype=np.float64)
        self._prime_common(window, rank_cols)


class ServingWindowCache(_SlotWindowCacheBase):
    """Incremental serving window: per-seq enqueue/complete/queue-depth/
    decode-token/tps/prefill/decode slots are cached and delta-
    maintained; per-replica aggregates, KV headroom, and the latency
    percentiles (order statistics over RAW populations — value-
    determined, so a refold over the ragged CSR suffixes reproduces the
    full build's bits) are re-folded each tick."""

    def __init__(self) -> None:
        super().__init__()
        self._enq: Optional[np.ndarray] = None
        self._done: Optional[np.ndarray] = None
        self._qd: Optional[np.ndarray] = None
        self._dtok: Optional[np.ndarray] = None
        self._tps: Optional[np.ndarray] = None
        self._pre: Optional[np.ndarray] = None
        self._dec: Optional[np.ndarray] = None

    def _slot_arrays(self):
        return (
            self._enq, self._done, self._qd, self._dtok,
            self._tps, self._pre, self._dec,
        )

    def _full_build(self, rank_cols, max_steps):
        return build_columnar_serving_window(rank_cols, max_steps)

    def _tick(self, rank_cols, max_steps):
        if not self._valid:
            raise _CacheInvalid(INVALIDATE_COLD)
        if int(max_steps) != self._max_steps:
            raise _CacheInvalid(INVALIDATE_SIZE)
        items = self._sorted_items(rank_cols)
        if not items:
            raise _CacheInvalid(INVALIDATE_RANKS)
        touched, new_steps = self._scan_delta(items, self._max_steps)
        touched = self._append_and_touch(touched, new_steps)
        lo_step = int(self._steps[self._lo])
        buf = self._steps[: self._hi]
        for s in sorted(touched):
            if s < lo_step:
                continue
            self._recompute_slot(int(np.searchsorted(buf, s)), s, items)
        return self._emit(items)

    def _recompute_slot(self, j: int, s: int, items) -> None:
        e_acc = 0
        d_acc = 0
        q_acc = 0
        t_acc = 0
        tps_acc = 0.0
        pre_acc = 0.0
        dec_acc = 0.0
        for _, c in items:
            sv = c.steps_view()
            a = int(np.searchsorted(sv, s, side="left"))
            b = int(np.searchsorted(sv, s, side="right"))
            if a == b:
                continue
            ints = c.ints_view()
            flts = c.floats_view()
            for t in range(a, b):
                e_acc += int(ints[t, SV_ENQ])
                d_acc += int(ints[t, SV_DONE])
                q_acc += int(ints[t, SV_QDEPTH])
                t_acc += int(ints[t, SV_DTOK])
                tps_acc += float(flts[t, SF_TPS])
                pre_acc += float(flts[t, SF_PREFILL])
                dec_acc += float(flts[t, SF_DECODE])
        self._enq[j] = e_acc
        self._done[j] = d_acc
        self._qd[j] = q_acc
        self._dtok[j] = t_acc
        self._tps[j] = tps_acc
        self._pre[j] = pre_acc
        self._dec[j] = dec_acc

    def _emit(self, items) -> ServingWindow:
        lo, hi = self._lo, self._hi
        S = hi - lo
        common = self._steps[lo:hi]
        lo_step = int(common[0])
        ttft_parts: List[np.ndarray] = []
        e2e_parts: List[np.ndarray] = []
        per_rank: Dict[int, Dict[str, float]] = {}
        kv_min = -1.0
        for rank, c in items:
            sv = c.steps_view()
            k = int(np.searchsorted(sv, lo_step, side="left"))
            ints = c.ints_view()[k:]
            flts = c.floats_view()[k:]
            r_ttft = c.ragged_suffix(RG_TTFT, k)
            ttft_parts.append(r_ttft)
            e2e_parts.append(c.ragged_suffix(RG_E2E, k))
            n_rows = int(ints.shape[0])
            if n_rows:
                r_done = int(np.cumsum(ints[:, SV_DONE])[-1])
                r_tok = int(np.cumsum(ints[:, SV_DTOK])[-1])
                r_tps = float(np.cumsum(flts[:, SF_TPS])[-1]) / n_rows
                r_qd = int(ints[-1, SV_QDEPTH])
                r_active = int(ints[-1, SV_ACTIVE])
            else:
                r_done = r_tok = r_qd = r_active = 0
                r_tps = 0.0
            kvh = flts[:, SF_KVH]
            kv_ok = kvh >= 0.0
            r_kvh = -1.0
            if kv_ok.any():
                r_kvh = float(kvh[np.flatnonzero(kv_ok)[-1]])
                m = float(kvh[kv_ok].min())
                kv_min = m if kv_min < 0.0 else min(kv_min, m)
            per_rank[rank] = {
                "requests_completed": r_done,
                "requests_active": r_active,
                "decode_tokens": r_tok,
                "tokens_per_s": r_tps,
                "queue_depth": r_qd,
                "ttft_p99_ms": _population_percentile(np.sort(r_ttft), 0.99),
                "kv_headroom": r_kvh,
            }

        ttft_sorted = (
            np.sort(np.concatenate(ttft_parts)) if ttft_parts else _EMPTY_F64
        )
        e2e_sorted = (
            np.sort(np.concatenate(e2e_parts)) if e2e_parts else _EMPTY_F64
        )
        enq_l = self._enq[lo:hi].tolist()
        done_l = self._done[lo:hi].tolist()
        qd_l = self._qd[lo:hi].tolist()
        dtok_l = self._dtok[lo:hi].tolist()
        return ServingWindow(
            steps=common.tolist(),
            n_steps=S,
            ranks=list(self._ranks),
            per_step={
                "requests_enqueued": enq_l,
                "requests_completed": done_l,
                "queue_depth": qd_l,
                "decode_tokens": dtok_l,
                "tokens_per_s": self._tps[lo:hi].tolist(),
                "prefill_ms": self._pre[lo:hi].tolist(),
                "decode_ms": self._dec[lo:hi].tolist(),
            },
            per_rank=per_rank,
            totals=_serving_totals(
                enq_l,
                done_l,
                dtok_l,
                qd_l,
                self._pre[lo:hi].tolist(),
                self._dec[lo:hi].tolist(),
                per_rank,
                kv_min,
                ttft_sorted,
                e2e_sorted,
            ),
        )

    def _prime(self, window, rank_cols, max_steps) -> None:
        if window is None:
            self._valid = False
            return
        self._max_steps = int(max_steps)
        self._cap = 2 * self._max_steps
        S = window.n_steps
        self._steps = np.empty(self._cap, dtype=np.int64)
        self._enq = np.empty(self._cap, dtype=np.int64)
        self._done = np.empty(self._cap, dtype=np.int64)
        self._qd = np.empty(self._cap, dtype=np.int64)
        self._dtok = np.empty(self._cap, dtype=np.int64)
        self._tps = np.empty(self._cap, dtype=np.float64)
        self._pre = np.empty(self._cap, dtype=np.float64)
        self._dec = np.empty(self._cap, dtype=np.float64)
        self._steps[:S] = np.asarray(window.steps, dtype=np.int64)
        ps = window.per_step
        self._enq[:S] = np.asarray(ps["requests_enqueued"], dtype=np.int64)
        self._done[:S] = np.asarray(ps["requests_completed"], dtype=np.int64)
        self._qd[:S] = np.asarray(ps["queue_depth"], dtype=np.int64)
        self._dtok[:S] = np.asarray(ps["decode_tokens"], dtype=np.int64)
        self._tps[:S] = np.asarray(ps["tokens_per_s"], dtype=np.float64)
        self._pre[:S] = np.asarray(ps["prefill_ms"], dtype=np.float64)
        self._dec[:S] = np.asarray(ps["decode_ms"], dtype=np.float64)
        self._prime_common(window, rank_cols)
