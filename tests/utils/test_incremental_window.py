"""Incremental window engine: the delta tick is bit-identical, always.

Contract (docs/developer_guide/columnar-window-engine.md): the per-domain
window caches (``StepTimeWindowCache`` / ``CollectivesWindowCache`` /
``ServingWindowCache``) either produce a window bit-identical to the
from-scratch columnar build — itself golden-pinned against the scalar
reference — or invalidate back to that full build.  The randomized suite
below drives ~200 seeded interleavings of append / ring-eviction /
retention-trim / clock-flip / ragged-arrival / fallback across all three
domains through ONE persistent cache per run, comparing

    incremental == full rebuild == scalar reference

(plain-dict forms) after EVERY operation.  Deterministic fixtures then
pin each invalidation reason, the build-stats counters, and the
``TRACEML_INCR_WINDOW=0`` payload byte-pin.
"""

import json
import random

import pytest

from traceml_tpu.aggregator.sqlite_writer import SQLiteWriter
from traceml_tpu.samplers.serving_sampler import pack_floats
from traceml_tpu.telemetry.envelope import SenderIdentity, build_telemetry_envelope
from traceml_tpu.utils import timing as T
from traceml_tpu.utils.columnar import (
    CollectivesColumns,
    CollectivesWindowCache,
    ColumnarFallback,
    RaggedEventColumns,
    ServingWindowCache,
    StepTimeColumns,
    StepTimeWindowCache,
    build_collectives_window_rows,
    build_columnar_collectives_window,
    build_columnar_serving_window,
    build_columnar_step_time_window,
    build_serving_window_rows,
    collectives_window_to_plain,
    incr_window_enabled,
    serving_window_to_plain,
    window_to_plain,
)
from traceml_tpu.utils.step_time_window import PHASES, build_step_time_window


# -- row factories -------------------------------------------------------


def _step_row(step, rng, clock="device"):
    step_ms = rng.uniform(40.0, 150.0)
    events = {
        T.STEP_TIME: {
            "cpu_ms": step_ms,
            "device_ms": step_ms * 0.97 if clock == "device" else None,
            "count": 1,
        }
    }
    for key, name in PHASES.items():
        if rng.random() < 0.15:
            continue  # phase missing on this rank/step
        v = rng.uniform(0.0, 25.0)
        events[name] = {
            "cpu_ms": v,
            "device_ms": v * 0.95 if key != "input" else None,
            "count": 1,
        }
    return {
        "step": step,
        "timestamp": 100.0 + step,
        "clock": clock,
        "late_markers": 0,
        "events": events,
    }


def _coll_rows(step, rng):
    rows = []
    for op in ("all_reduce", "all_gather", "reduce_scatter"):
        if rng.random() < 0.3:
            continue
        dur = rng.uniform(0.0, 8.0)
        rows.append({
            "step": step,
            "timestamp": 100.0 + step,
            "op": op,
            "dtype": rng.choice(("float32", "bfloat16")),
            "count": rng.randint(1, 4),
            "bytes": rng.randint(0, 1 << 22),
            "group_size": rng.choice((4, 8)),
            "duration_ms": dur,
            "exposed_ms": dur * rng.random(),
        })
    return rows


def _serving_row(step, rng):
    done = rng.randint(0, 5)
    ttft = [rng.uniform(1.0, 500.0) for _ in range(done)]
    e2e = [rng.uniform(1.0, 1000.0) for _ in range(done)]
    kvh = rng.uniform(0.0, 0.9) if rng.random() < 0.5 else None
    return {
        "step": step,
        "timestamp": 100.0 + step,
        "requests_enqueued": rng.randint(0, 6),
        "requests_completed": done,
        "requests_active": rng.randint(0, 4),
        "queue_depth": rng.randint(0, 8),
        "decode_tokens": rng.randint(0, 256),
        "prefill_ms": rng.uniform(0.0, 50.0),
        "decode_ms": rng.uniform(0.0, 200.0),
        "tokens_per_s": rng.uniform(0.0, 500.0),
        "batch_occupancy": 0.4,
        "kv_bytes": -1 if kvh is None else 1 << 30,
        "kv_limit_bytes": -1 if kvh is None else 2 << 30,
        "kv_headroom": -1.0 if kvh is None else kvh,
        "ttft_ms_list": pack_floats(ttft),
        "e2e_ms_list": pack_floats(e2e),
        "tokens_list": ",".join("16" for _ in range(done)),
    }


# -- domain harnesses ----------------------------------------------------
#
# Each harness mirrors the snapshot store's lockstep (row deque, columnar
# ring) pair per rank plus ONE persistent incremental cache, and knows
# how to compare the three paths after an operation.


class _Domain:
    ring_cls = None
    cache_cls = None

    def __init__(self, ranks, cap, rng):
        self.rng = rng
        self.cap = cap
        self.rows = {r: [] for r in ranks}
        self.cols = {r: self.ring_cls(cap) for r in ranks}
        self.cache = self.cache_cls()
        self.gstep = 0

    def _mirror_append(self, rank, row):
        self.rows[rank].append(row)
        if len(self.rows[rank]) > self.cap:  # deque(maxlen=cap) semantics
            self.rows[rank] = self.rows[rank][-self.cap:]
        self.cols[rank].append(row)

    def append_step(self, ranks):
        raise NotImplementedError

    def evict(self, rank, n):
        self.rows[rank] = self.rows[rank][n:]
        self.cols[rank].evict_head(n)

    def clear(self, rank):
        self.rows[rank] = []
        self.cols[rank].clear()

    def poison(self, rank):
        """Append a row the ring cannot represent (flags the buffer)."""
        raise NotImplementedError

    def scalar(self, max_steps):
        raise NotImplementedError

    def full(self, max_steps):
        raise NotImplementedError

    def plain(self, w):
        raise NotImplementedError

    def tick_assert(self, max_steps, compare_scalar=True):
        live = {r: c for r, c in self.cols.items() if len(c)}
        try:
            full_plain = self.plain(self.full(live, max_steps))
            full_raised = False
        except ColumnarFallback:
            full_raised = True
        try:
            inc_plain = self.plain(self.cache.build(live, max_steps))
            inc_raised = False
        except ColumnarFallback:
            inc_raised = True
        assert inc_raised == full_raised
        if full_raised:
            return
        assert inc_plain == full_plain
        if compare_scalar:
            assert inc_plain == self.plain(self.scalar(max_steps))

    def scalar_rows(self):
        return {r: list(rows) for r, rows in self.rows.items() if rows}


class _StepTimeDomain(_Domain):
    ring_cls = StepTimeColumns
    cache_cls = StepTimeWindowCache

    def __init__(self, ranks, cap, rng):
        super().__init__(ranks, cap, rng)
        self.clock = "device"

    def append_step(self, ranks):
        self.gstep += 1
        for r in ranks:
            self._mirror_append(r, _step_row(self.gstep, self.rng, self.clock))

    def poison(self, rank):
        # duplicate step: ring flags, sticky
        last = self.rows[rank][-1]["step"] if self.rows[rank] else 1
        row = _step_row(last, self.rng, self.clock)
        self.rows[rank].append(row)
        self.cols[rank].append(row)

    def scalar(self, max_steps):
        return build_step_time_window(self.scalar_rows(), max_steps=max_steps)

    def full(self, live, max_steps):
        return build_columnar_step_time_window(live, max_steps)

    def plain(self, w):
        return window_to_plain(w)


class _CollectivesDomain(_Domain):
    ring_cls = CollectivesColumns
    cache_cls = CollectivesWindowCache

    def append_step(self, ranks):
        self.gstep += 1
        for r in ranks:
            for row in _coll_rows(self.gstep, self.rng):
                self._mirror_append(r, row)

    def poison(self, rank):
        last = self.rows[rank][-1]["step"] if self.rows[rank] else 5
        row = _coll_rows(last, self.rng) or _coll_rows(last, random.Random(0))
        row = dict(row[0], step=last - 3)  # out-of-order step
        self.rows[rank].append(row)
        self.cols[rank].append(row)

    def scalar(self, max_steps):
        return build_collectives_window_rows(
            self.scalar_rows(), max_steps=max_steps
        )

    def full(self, live, max_steps):
        return build_columnar_collectives_window(live, max_steps)

    def plain(self, w):
        return collectives_window_to_plain(w)


class _ServingDomain(_Domain):
    ring_cls = RaggedEventColumns
    cache_cls = ServingWindowCache

    def append_step(self, ranks):
        self.gstep += 1
        for r in ranks:
            self._mirror_append(r, _serving_row(self.gstep, self.rng))

    def poison(self, rank):
        last = self.rows[rank][-1]["step"] if self.rows[rank] else 5
        row = _serving_row(last, self.rng)  # duplicate window seq
        self.rows[rank].append(row)
        self.cols[rank].append(row)

    def scalar(self, max_steps):
        return build_serving_window_rows(self.scalar_rows(), max_steps=max_steps)

    def full(self, live, max_steps):
        return build_columnar_serving_window(live, max_steps)

    def plain(self, w):
        return serving_window_to_plain(w)


def _run_interleaving(domain_cls, seed):
    rng = random.Random(seed)
    R = rng.randint(1, 4)
    cap = rng.randint(8, 24)
    max_steps = rng.randint(4, 12)
    dom = domain_cls(list(range(R)), cap, rng)

    # warm up with a few aligned steps so the first tick has a window
    for _ in range(rng.randint(1, 6)):
        dom.append_step(range(R))
    dom.tick_assert(max_steps)

    for _ in range(22):
        op = rng.random()
        if op < 0.45:
            # append; sometimes ragged (a strict subset of ranks)
            if R > 1 and rng.random() < 0.35:
                ranks = rng.sample(range(R), rng.randint(1, R - 1))
            else:
                ranks = range(R)
            dom.append_step(ranks)
        elif op < 0.60:
            # burst of aligned appends (drives ring eviction past cap)
            for _ in range(rng.randint(2, cap)):
                dom.append_step(range(R))
        elif op < 0.75:
            # retention trim (head eviction, deque/ring lockstep)
            r = rng.randrange(R)
            dom.evict(r, rng.randint(1, max(1, len(dom.rows[r]) or 1)))
        elif op < 0.80 and isinstance(dom, _StepTimeDomain):
            dom.clock = "host" if dom.clock == "device" else "device"
            dom.append_step(range(R))
        elif op < 0.85:
            # empty-delta double tick (idle rebuild must also match)
            dom.tick_assert(max_steps)
        elif op < 0.90:
            # window resize mid-run
            dom.tick_assert(max(2, max_steps // 2))
        elif op < 0.95:
            r = rng.randrange(R)
            dom.poison(r)
            dom.tick_assert(max_steps, compare_scalar=False)
            dom.clear(r)  # store-reconnect semantics: ring + deque reset
            dom.append_step(range(R))
        else:
            r = rng.randrange(R)
            dom.clear(r)
            dom.append_step(range(R))
        dom.tick_assert(max_steps)

    stats = dom.cache.stats.snapshot()
    assert stats["incr_ticks"] + stats["full_rebuilds"] > 0


# ~200 seeded interleavings across the three domains
@pytest.mark.parametrize("seed", range(67))
def test_step_time_interleavings(seed):
    _run_interleaving(_StepTimeDomain, 1000 + seed)


@pytest.mark.parametrize("seed", range(67))
def test_collectives_interleavings(seed):
    _run_interleaving(_CollectivesDomain, 2000 + seed)


@pytest.mark.parametrize("seed", range(66))
def test_serving_interleavings(seed):
    _run_interleaving(_ServingDomain, 3000 + seed)


# -- invalidation-reason fixtures ---------------------------------------


def _aligned_step_time(n, ranks=2, cap=64, clock="device", start=1):
    rng = random.Random(7)
    cols = {r: StepTimeColumns(cap) for r in range(ranks)}
    for s in range(start, start + n):
        for c in cols.values():
            c.append(_step_row(s, rng, clock))
    return cols


def test_cold_start_then_steady_incremental_ticks():
    cache = StepTimeWindowCache()
    cols = _aligned_step_time(10)
    cache.build(cols, 8)
    assert cache.stats.invalidations == {"cold_start": 1}
    rng = random.Random(9)
    for s in range(11, 31):
        for c in cols.values():
            c.append(_step_row(s, rng))
        cache.build(cols, 8)
    st = cache.stats.snapshot()
    assert st["full_rebuilds"] == 1 and st["incr_ticks"] == 20
    assert st["last_path"] == "incremental" and st["last_build_ms"] >= 0.0


def test_window_size_change_invalidates():
    cache = StepTimeWindowCache()
    cols = _aligned_step_time(10)
    cache.build(cols, 8)
    cache.build(cols, 4)
    assert cache.stats.invalidations.get("window_size_changed") == 1


def test_rank_set_change_invalidates():
    cache = StepTimeWindowCache()
    cols = _aligned_step_time(10, ranks=2)
    cache.build(cols, 8)
    rng = random.Random(3)
    extra = StepTimeColumns(64)
    for s in range(1, 11):
        extra.append(_step_row(s, rng))
    cols[2] = extra
    cache.build(cols, 8)
    assert cache.stats.invalidations.get("rank_set_changed") == 1


def test_clock_flip_invalidates():
    cache = StepTimeWindowCache()
    cols = _aligned_step_time(10, clock="device")
    cache.build(cols, 8)
    rng = random.Random(5)
    for c in cols.values():
        c.append(_step_row(11, rng, clock="host"))
    w = cache.build(cols, 8)
    assert w.clock == "host"
    assert cache.stats.invalidations.get("clock_flip") == 1


def test_eviction_into_window_invalidates():
    cache = CollectivesWindowCache()
    rng = random.Random(13)
    cols = {0: CollectivesColumns(64), 1: CollectivesColumns(64)}
    for s in range(1, 11):
        for c in cols.values():
            c.append({"step": s, "timestamp": 1.0, "op": "all_reduce",
                      "dtype": "float32", "count": 1, "bytes": 100,
                      "group_size": 2, "duration_ms": 1.0,
                      "exposed_ms": 0.5})
    cache.build(cols, 4)  # window = steps 7..10
    cols[0].evict_head(8)  # surviving head step 9 >= window lo 7
    cache.build(cols, 4)
    assert cache.stats.invalidations.get("window_evicted") == 1
    # eviction strictly below the window is absorbed incrementally
    cols[1].evict_head(2)  # steps 1..2 < lo — harmless, no invalidation
    cache.build(cols, 4)
    assert cache.stats.snapshot()["incr_ticks"] >= 1
    assert rng  # silence unused warning on minimal interpreters


def test_mid_window_union_insert_realigns():
    cache = CollectivesWindowCache()

    def _row_at(s):
        return {"step": s, "timestamp": 1.0, "op": "all_gather",
                "dtype": "bfloat16", "count": 1, "bytes": 10,
                "group_size": 2, "duration_ms": 1.0, "exposed_ms": 0.0}

    cols = {0: CollectivesColumns(64), 1: CollectivesColumns(64)}
    for s in (2, 4):
        cols[0].append(_row_at(s))
    for s in (2, 4, 6):
        cols[1].append(_row_at(s))
    cache.build(cols, 8)  # union {2, 4, 6}
    cols[0].append(_row_at(5))  # lands inside the cached union
    w = cache.build(cols, 8)
    assert w.steps == [2, 4, 5, 6]
    assert cache.stats.invalidations.get("realigned") == 1
    ref = build_columnar_collectives_window(cols, 8)
    assert collectives_window_to_plain(w) == collectives_window_to_plain(ref)


def test_fallback_counts_and_propagates():
    cache = StepTimeWindowCache()
    cols = _aligned_step_time(10)
    cache.build(cols, 8)
    rng = random.Random(2)
    cols[0].append(_step_row(5, rng))  # duplicate step → sticky flag
    with pytest.raises(ColumnarFallback):
        cache.build(cols, 8)
    assert cache.stats.invalidations.get("fallback") == 1
    assert cache.stats.snapshot()["last_path"] == "full"


def test_kill_switch_bypasses_cache(monkeypatch):
    monkeypatch.setenv("TRACEML_INCR_WINDOW", "0")
    assert not incr_window_enabled()
    monkeypatch.setenv("TRACEML_INCR_WINDOW", "1")
    assert incr_window_enabled()


# -- TRACEML_INCR_WINDOW=0 payload byte-pin ------------------------------


def _ident(rank=0, world=2):
    return SenderIdentity(
        session_id="s1",
        global_rank=rank,
        local_rank=rank,
        world_size=world,
        node_rank=0,
        hostname="host-0",
        pid=100 + rank,
    )


def _seed_session(db):
    rng = random.Random(21)
    w = SQLiteWriter(db)
    w.start()
    for rank in (0, 1):
        w.ingest(build_telemetry_envelope(
            "step_time",
            {"step_time": [_step_row(s, random.Random(100 * rank + s))
                           for s in range(1, 25)]},
            _ident(rank),
        ))
        w.ingest(build_telemetry_envelope(
            "collectives",
            {"collectives": [row for s in range(1, 25)
                             for row in _coll_rows(s, random.Random(s))]},
            _ident(rank),
        ))
    assert w.force_flush()
    w.finalize()
    assert rng  # deterministic seeds only


def _payload_bytes(db):
    from traceml_tpu.renderers.web_payload import build_web_payload

    payload = build_web_payload(db, "s1")
    payload.pop("ts", None)  # wall-clock
    return json.dumps(payload, sort_keys=True).encode()


def _window_build_stats(db):
    from traceml_tpu.renderers.serving import publisher_for

    return publisher_for(db, "s1")._computer.store.window_build_stats()


def test_incr_off_payload_bytes_identical(tmp_path, monkeypatch):
    """With the kill switch off the served payload must be byte-identical
    to the full-rebuild (pre-r19) output, every window identical.  The
    engine's build counters live on the store (``window_build_stats``,
    and the final report's meta), never in the live payload."""
    db_a = tmp_path / "a" / "t.sqlite"
    db_b = tmp_path / "b" / "t.sqlite"
    db_a.parent.mkdir()
    db_b.parent.mkdir()
    _seed_session(db_a)
    _seed_session(db_b)

    monkeypatch.setenv("TRACEML_INCR_WINDOW", "0")
    off = _payload_bytes(db_a)
    assert b"window_build" not in off

    monkeypatch.setenv("TRACEML_INCR_WINDOW", "1")
    on = _payload_bytes(db_b)
    assert set(_window_build_stats(db_b)) >= {"step_time", "collectives"}
    assert off == on
