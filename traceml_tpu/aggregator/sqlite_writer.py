"""Async SQLite writer
(reference: src/traceml_ai/aggregator/sqlite_writer.py:112-647).

One dedicated writer thread owns the connection (sqlite is
single-writer anyway).  The high-rank write path is built from three
pieces that keep every stage of drain → project → commit → prune
bounded (no stage ever stalls the pipe):

* **Prioritized backpressure** — the ingest queue is split by domain
  priority: step_time / step_memory (the rows diagnosis depends on)
  get their own large queue, system / process / stdout share a smaller
  one.  Overload sheds the low-value domains first instead of whatever
  arrives last, with per-domain shed counters, queue high-water marks,
  and a rate-limited producer-visible warning on drop.

* **Group-commit scheduling** — drained envelopes coalesce into one
  transaction per size-or-interval threshold (``_GROUP_COMMIT_ENVS`` /
  ``_GROUP_COMMIT_INTERVAL_S``), with ``writer_for``/``insert_sql``
  lookups cached per sampler/table instead of re-resolved per envelope.
  Flush barriers stay read-your-writes correct: a barrier forces the
  pending group to commit before its event fires.

* **O(new) watermark retention** — the writer tracks per
  ``(table, session_id, global_rank)`` row counts from its own inserts;
  when a partition overflows ``retention + slack`` it is queued for
  pruning, and each commit cycle prunes a bounded slice of partitions
  via an indexed range delete: the watermark id comes from
  ``ORDER BY id DESC LIMIT 1 OFFSET retention`` on that partition only,
  then ``DELETE … WHERE id <= watermark``.  No commit ever absorbs a
  full-table ``ROW_NUMBER()`` scan (the seed design stalled for
  hundreds of ms at 256+ ranks).  Every prune is journaled to the
  ``retention_watermarks`` table so the live snapshot store can evict
  exactly the deleted rows per rank (per-partition deletes do not move
  the global ``MIN(id)`` the old trim detection watched).

``finalize()`` = drain → prune every overflowing partition to exactly
``retention`` rows (same survivors the seed's windowed prune kept) →
``wal_checkpoint(TRUNCATE)`` → close.
"""

from __future__ import annotations

import queue
import sqlite3
import threading
import time
from collections import deque
from pathlib import Path
from typing import Callable, Deque, Dict, List, Optional, Tuple

from traceml_tpu.aggregator import rollup
from traceml_tpu.aggregator.sqlite_writers import ALL_WRITERS, writer_for
from traceml_tpu.telemetry.envelope import TelemetryEnvelope
from traceml_tpu.utils.error_log import get_error_log

# queue capacities per priority class (high + low ≈ the seed's single
# 50k queue, but a low-domain flood can no longer evict step telemetry)
_QUEUE_HIGH_MAX = 40_000
_QUEUE_LOW_MAX = 10_000

# samplers whose rows drive diagnosis — everything else (system, process,
# stdout_stderr, unknown samplers) sheds first under overload.  Control
# messages never reach this queue: the aggregator handles them inline,
# ahead of any telemetry backpressure.
HIGH_PRIORITY_SAMPLERS = frozenset(
    {"step_time", "step_memory", "collectives", "serving"}
)
PRIORITY_NAMES = ("high", "low")

# group-commit thresholds: commit when this many envelopes are pending,
# or when the oldest pending envelope has waited this long
_GROUP_COMMIT_ENVS = 512
_GROUP_COMMIT_INTERVAL_S = 0.2

# bounded prune slice per commit cycle (partitions per slice); the
# backlog queue carries the rest to the next cycle
_PRUNE_PARTITIONS_PER_SLICE = 8

# journal self-trim: cap the watermark journal's size (deleting old
# journal rows is invisible to store cursors, which only move forward)
_JOURNAL_MAX_ROWS = 4096

_DROP_WARN_INTERVAL_S = 5.0

WATERMARK_TABLE = "retention_watermarks"

# durable-replay dedup: per (session, rank, lane) max committed seq.
# Lane-scoped because FIFO commit order is only guaranteed WITHIN a
# priority queue — a low-lane envelope with a smaller seq legitimately
# commits after a high-lane envelope with a larger one, and a single
# (session, rank) max would swallow it as a duplicate.
RANK_SEQ_TABLE = "rank_seq"

_MISSING = object()


def _p99(values: Deque[float]) -> Optional[float]:
    if not values:
        return None
    s = sorted(values)
    return s[min(len(s) - 1, int(len(s) * 0.99))]


def ingest_priority(sampler: str) -> int:
    return 0 if sampler in HIGH_PRIORITY_SAMPLERS else 1


class _FlushBarrier:
    __slots__ = ("event",)

    def __init__(self) -> None:
        self.event = threading.Event()


class SQLiteWriter:
    def __init__(
        self,
        db_path: Path,
        summary_window_rows: int = 10_000,
        retention_factor: float = 1.5,
        queue_max_high: int = _QUEUE_HIGH_MAX,
        queue_max_low: int = _QUEUE_LOW_MAX,
        group_commit_envelopes: int = _GROUP_COMMIT_ENVS,
        group_commit_interval_s: float = _GROUP_COMMIT_INTERVAL_S,
        prune_partitions_per_slice: int = _PRUNE_PARTITIONS_PER_SLICE,
        on_commit: Optional[Callable[[List[TelemetryEnvelope]], None]] = None,
    ) -> None:
        self.db_path = Path(db_path)
        self._retention_rows = int(summary_window_rows * retention_factor)
        # hysteresis: a partition is pruned online once it overflows
        # retention by this slack (so steady trickle doesn't prune one
        # row per batch, and disk stays bounded at ~2x the cap during a
        # long run); finalize() still trims every partition to exactly
        # retention (the seed-prune-equivalent final state), which is
        # where short runs — and the golden tests — see their only prune
        self._prune_slack = max(256, self._retention_rows)
        self._group_envs = int(group_commit_envelopes)
        self._group_interval = float(group_commit_interval_s)
        self._prune_slice_max = int(prune_partitions_per_slice)
        # called on the writer thread with each batch right after its
        # transaction commits (the freshness tracker's ingest stage)
        self._on_commit = on_commit

        self._queues: Tuple["queue.Queue[object]", ...] = (
            queue.Queue(maxsize=queue_max_high),
            queue.Queue(maxsize=queue_max_low),
        )
        self._work = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()
        self._finalized = threading.Event()

        # public counters (back-compat: ingest_stats.json / tests)
        self.enqueued = 0
        self.dropped = 0
        self.written = 0
        self._batches = 0
        # envelopes permanently resolved: group-committed (including
        # dedup'd replays / unknown domains — they will never be
        # retried) plus queue-full drops.  The aggregator gates shm
        # ring-tail commits on this watermark: a ring frame's space is
        # reclaimable only once every envelope drained before it can no
        # longer be lost by a crash.
        self._settled = 0

        self._stats_lock = threading.Lock()
        self._enq_by_domain: Dict[str, int] = {}
        self._drop_by_domain: Dict[str, int] = {}
        self._queue_hwm = [0, 0]
        self._last_drop_warn = 0.0
        self._drops_since_warn = 0
        self.drop_warnings = 0

        # envelopes whose sampler has no registered projection writer —
        # counted and surfaced instead of silently skipped (a version-skewed
        # producer shipping a new domain must be visible in ingest stats)
        self._unknown_by_domain: Dict[str, int] = {}
        self._last_unknown_warn = 0.0
        self._unknown_since_warn = 0

        # retention bookkeeping (writer thread only)
        self._part_counts: Dict[Tuple[str, str, int], int] = {}
        self._prune_due: Deque[Tuple[str, str, int]] = deque()
        self._prune_due_set: set = set()
        self._retention_tables = frozenset(
            t for w in ALL_WRITERS for t in getattr(w, "RETENTION_TABLES", ())
        )
        self._journal_rows = 0

        # lookup caches (satellite: never re-resolve per envelope)
        self._writer_cache: Dict[str, object] = {}
        self._sql_cache: Dict[str, str] = {}

        # latency telemetry (writer thread appends; stats() reads)
        self._commit_lat_ms: Deque[float] = deque(maxlen=512)
        self._prune_lat_ms: Deque[float] = deque(maxlen=512)
        self._commit_max_ms = 0.0
        self._prune_max_ms = 0.0
        self.prunes = 0
        self.rows_pruned = 0

        # replay dedup state (writer thread only): seeded from the
        # rank_seq table on (re)open so a restarted aggregator keeps
        # rejecting envelopes its previous incarnation already committed
        self._seq_max: Dict[Tuple[str, int, str], int] = {}
        self.replay_duplicates = 0

        # tiered rollup decay: folds each prune's doomed id-range into
        # rollup_samples_10s/_1m inside the same transaction as the
        # delete (None when TRACEML_ROLLUP=0 — prunes discard history)
        self._rollup = rollup.build_engine()

    # -- producer side (aggregator loop) --------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self.db_path.parent.mkdir(parents=True, exist_ok=True)
        self._thread = threading.Thread(
            target=self._run, name="traceml-sqlite-writer", daemon=True
        )
        self._thread.start()

    def ingest(self, env: TelemetryEnvelope) -> bool:
        pri = ingest_priority(env.sampler)
        q = self._queues[pri]
        try:
            q.put_nowait(env)
        except queue.Full:
            self._record_drop(env.sampler, pri)
            return False
        self.enqueued += 1
        with self._stats_lock:
            self._enq_by_domain[env.sampler] = (
                self._enq_by_domain.get(env.sampler, 0) + 1
            )
            depth = q.qsize()
            if depth > self._queue_hwm[pri]:
                self._queue_hwm[pri] = depth
        self._work.set()
        return True

    def _record_drop(self, sampler: str, pri: int) -> None:
        """Count the shed envelope per domain and warn (rate-limited) —
        a silent counter bump only discovered in ingest_stats.json after
        the run is not a producer-visible signal."""
        warn_count = 0
        with self._stats_lock:
            self.dropped += 1
            self._settled += 1  # shed = resolved: it will never be written
            self._drop_by_domain[sampler] = (
                self._drop_by_domain.get(sampler, 0) + 1
            )
            self._drops_since_warn += 1
            now = time.monotonic()
            if now - self._last_drop_warn >= _DROP_WARN_INTERVAL_S:
                self._last_drop_warn = now
                warn_count = self._drops_since_warn
                self._drops_since_warn = 0
                totals = dict(self._drop_by_domain)
        if warn_count:
            self.drop_warnings += 1
            get_error_log().warning(
                f"ingest queue ({PRIORITY_NAMES[pri]}) full: shed "
                f"{warn_count} envelope(s) in the last "
                f"{_DROP_WARN_INTERVAL_S:.0f}s (latest sampler="
                f"{sampler}); dropped by domain so far: {totals}"
            )

    def settled_envelopes(self) -> int:
        """Cumulative envelopes permanently resolved (committed batches
        + queue-full drops).  Monotonic; safe to read from any thread."""
        with self._stats_lock:
            return self._settled

    def _record_unknown_domain(self, sampler: str) -> None:
        """An envelope named a table with no registered writer.  Neither
        raise nor vanish: count it per domain for ingest_stats.json and
        warn rate-limited (the producer may be a newer version shipping a
        domain this aggregator doesn't know)."""
        warn_count = 0
        with self._stats_lock:
            self._unknown_by_domain[sampler] = (
                self._unknown_by_domain.get(sampler, 0) + 1
            )
            self._unknown_since_warn += 1
            now = time.monotonic()
            if now - self._last_unknown_warn >= _DROP_WARN_INTERVAL_S:
                self._last_unknown_warn = now
                warn_count = self._unknown_since_warn
                self._unknown_since_warn = 0
                totals = dict(self._unknown_by_domain)
        if warn_count:
            get_error_log().warning(
                f"no projection writer for telemetry domain {sampler!r}: "
                f"dropped {warn_count} envelope(s) in the last "
                f"{_DROP_WARN_INTERVAL_S:.0f}s; unknown-domain drops so "
                f"far: {totals}"
            )

    def force_flush(self, timeout: float = 10.0) -> bool:
        """Barrier: returns once everything enqueued so far is committed
        (reference: sqlite_writer.py:168).  One barrier per priority
        queue — each guarantees the items ahead of it in its own queue;
        waiting on both covers everything enqueued before this call."""
        if self._thread is None or self._finalized.is_set():
            return False
        deadline = time.monotonic() + timeout
        barriers: List[_FlushBarrier] = []
        ok = True
        for q in self._queues:
            b = _FlushBarrier()
            try:
                q.put(b, timeout=max(0.0, deadline - time.monotonic()))
                barriers.append(b)
            except queue.Full:
                ok = False
        self._work.set()
        for b in barriers:
            ok &= b.event.wait(max(0.01, deadline - time.monotonic()))
        return ok

    def finalize(self, timeout: float = 30.0) -> bool:
        """Drain, prune, checkpoint, close (reference: 206-272, 554-622)."""
        if self._thread is None:
            return True
        ok = self.force_flush(timeout)
        self._stop_evt.set()
        self._work.set()
        self._thread.join(timeout=timeout)
        alive = self._thread.is_alive()
        self._thread = None
        return ok and not alive

    # -- observability ---------------------------------------------------
    def stats(self) -> Dict[str, object]:
        """Backpressure / group-commit / retention self-metrics for
        ingest_stats.json and the live UI meta."""
        with self._stats_lock:
            enq = dict(self._enq_by_domain)
            drop = dict(self._drop_by_domain)
            unknown = dict(self._unknown_by_domain)
            hwm = list(self._queue_hwm)
            dropped = self.dropped
        queues = {}
        for pri, name in enumerate(PRIORITY_NAMES):
            q = self._queues[pri]
            queues[name] = {
                "depth": q.qsize(),
                "hwm": hwm[pri],
                "capacity": q.maxsize,
            }
        return {
            "enqueued": self.enqueued,
            "dropped": dropped,
            "written": self.written,
            "enqueued_by_domain": enq,
            "dropped_by_domain": drop,
            "unknown_domain_drops": unknown,
            "drop_warnings": self.drop_warnings,
            "replay_duplicates": self.replay_duplicates,
            "queues": queues,
            "group_commit": {
                "commits": self._batches,
                "commit_p99_ms": _p99(self._commit_lat_ms),
                "commit_max_ms": round(self._commit_max_ms, 3),
            },
            "prune": {
                "prunes": self.prunes,
                "rows_pruned": self.rows_pruned,
                "partitions_tracked": len(self._part_counts),
                "partitions_due": len(self._prune_due),
                "prune_p99_ms": _p99(self._prune_lat_ms),
                "prune_max_ms": round(self._prune_max_ms, 3),
                "retention_rows": self._retention_rows,
            },
            "rollup": (
                self._rollup.stats()
                if self._rollup is not None
                else {"enabled": False}
            ),
        }

    # -- writer thread ---------------------------------------------------
    def _connect(self) -> sqlite3.Connection:
        conn = sqlite3.connect(str(self.db_path))
        conn.execute("PRAGMA journal_mode=WAL")
        conn.execute("PRAGMA synchronous=NORMAL")
        # 64 MiB page cache: at 1k+ ranks the live window alone is
        # ranks x retention rows (~hundreds of MB of B-tree pages), and
        # the default ~2 MiB cache thrashes on the rank-interleaved
        # index inserts and partition-scoped prune scans
        conn.execute("PRAGMA cache_size=-65536")
        # rank-interleaved commits rewrite the same hot index pages over
        # and over; the default 1000-page autocheckpoint re-copies them
        # into the main DB every ~4 MiB of WAL.  A 10x window dedups
        # those copies and keeps checkpoint stalls off the commit path
        # (finalize still runs wal_checkpoint(TRUNCATE))
        conn.execute("PRAGMA wal_autocheckpoint=10000")
        for w in ALL_WRITERS:
            w.init_schema(conn)
        conn.execute(
            f"""CREATE TABLE IF NOT EXISTS {WATERMARK_TABLE} (
                id INTEGER PRIMARY KEY AUTOINCREMENT,
                table_name TEXT,
                session_id TEXT,
                global_rank INTEGER,
                watermark_id INTEGER,
                deleted_rows INTEGER,
                ts REAL
            )"""
        )
        conn.execute(
            f"""CREATE TABLE IF NOT EXISTS {RANK_SEQ_TABLE} (
                session_id TEXT,
                global_rank INTEGER,
                lane TEXT,
                max_seq INTEGER,
                PRIMARY KEY (session_id, global_rank, lane)
            )"""
        )
        for table in self._retention_tables:
            # the watermark SELECT and the range DELETE both need a
            # (session_id, global_rank) prefix to stay partition-scoped
            # (rowid is the implicit tiebreaker, so ORDER BY id comes
            # free).  Most tables already carry one for the read path —
            # duplicating it would tax EVERY insert with a second
            # B-tree (measured ~25% throughput loss), so only tables
            # without one (stdout, model_stats) get a new index.
            if not self._has_partition_index(conn, table):
                conn.execute(
                    f"CREATE INDEX IF NOT EXISTS idx_{table}_retention"
                    f" ON {table} (session_id, global_rank)"
                )
        if self._rollup is not None:
            self._rollup.init_schema(conn)
        conn.commit()
        self._seed_partition_counts(conn)
        self._seed_seq_max(conn)
        return conn

    @staticmethod
    def _has_partition_index(conn: sqlite3.Connection, table: str) -> bool:
        for idx in conn.execute(f"PRAGMA index_list({table})").fetchall():
            cols = [
                r[2]
                for r in conn.execute(f"PRAGMA index_info({idx[1]})")
            ]
            if cols[:2] == ["session_id", "global_rank"]:
                return True
        return False

    def _seed_partition_counts(self, conn: sqlite3.Connection) -> None:
        """Resumed/pre-existing DB: learn current per-partition row
        counts once so retention stays O(new) from the first batch."""
        for table in self._retention_tables:
            try:
                rows = conn.execute(
                    f"SELECT session_id, global_rank, COUNT(*) FROM {table}"
                    " GROUP BY session_id, global_rank"
                ).fetchall()
            except sqlite3.Error:
                continue
            for session_id, rank, n in rows:
                key = (table, str(session_id), int(rank))
                self._part_counts[key] = int(n)
                self._note_overflow(key, int(n))

    def _seed_seq_max(self, conn: sqlite3.Connection) -> None:
        """Crash-resume: reload committed per-lane seq maxima so a
        restarted aggregator dedups the ranks' reconnect replay against
        everything its previous incarnation durably wrote."""
        try:
            rows = conn.execute(
                f"SELECT session_id, global_rank, lane, max_seq"
                f" FROM {RANK_SEQ_TABLE}"
            ).fetchall()
        except sqlite3.Error:
            return
        for session_id, rank, lane, mx in rows:
            self._seq_max[(str(session_id), int(rank), str(lane))] = int(mx)

    def _note_overflow(self, key: Tuple[str, str, int], count: int) -> None:
        if (
            count >= self._retention_rows + self._prune_slack
            and key not in self._prune_due_set
        ):
            self._prune_due_set.add(key)
            self._prune_due.append(key)

    def _queues_empty(self) -> bool:
        return all(q.empty() for q in self._queues)

    def _run(self) -> None:
        try:
            conn = self._connect()
        except Exception as exc:
            get_error_log().error("sqlite writer failed to open db", exc)
            self._finalized.set()
            return
        pending: List[TelemetryEnvelope] = []
        barriers: List[_FlushBarrier] = []
        pending_since: Optional[float] = None
        try:
            while True:
                if pending_since is not None:
                    timeout = min(
                        0.25,
                        max(
                            0.005,
                            self._group_interval
                            - (time.monotonic() - pending_since),
                        ),
                    )
                else:
                    timeout = 0.25
                if self._work.wait(timeout):
                    self._work.clear()
                # pop everything currently queued, high priority first
                for q in self._queues:
                    while True:
                        try:
                            item = q.get_nowait()
                        except queue.Empty:
                            break
                        if item is None:
                            continue
                        if isinstance(item, _FlushBarrier):
                            barriers.append(item)
                        else:
                            pending.append(item)
                now = time.monotonic()
                if pending and pending_since is None:
                    pending_since = now
                # group-commit gate: barriers and shutdown flush
                # immediately; otherwise wait for size or interval
                flush_now = (
                    bool(barriers)
                    or self._stop_evt.is_set()
                    or len(pending) >= self._group_envs
                    or (
                        pending_since is not None
                        and now - pending_since >= self._group_interval
                    )
                )
                if pending and flush_now:
                    # _write_batch folds the retention prune slice into
                    # the same transaction
                    self._write_batch(conn, pending)
                    pending = []
                    pending_since = None
                if barriers and not pending:
                    for b in barriers:
                        b.event.set()
                    barriers = []
                if (
                    self._stop_evt.is_set()
                    and not pending
                    and self._queues_empty()
                ):
                    break
            self._prune_all(conn)
            try:
                conn.execute("PRAGMA wal_checkpoint(TRUNCATE)")
                conn.commit()
            except sqlite3.Error:
                pass
        except Exception as exc:  # pragma: no cover
            get_error_log().error("sqlite writer thread crashed", exc)
        finally:
            try:
                conn.close()
            except Exception:
                pass
            self._finalized.set()

    def _write_batch(
        self, conn: sqlite3.Connection, batch: List[TelemetryEnvelope]
    ) -> None:
        # Build parameter tuples for the WHOLE batch first, grouped by
        # insert statement, so each (table, batch) costs exactly one
        # executemany inside one transaction — never per-row, and never
        # per-envelope when many ranks ship the same table.
        grouped: Dict[str, List[tuple]] = {}
        touched: Dict[Tuple[str, str, int], int] = {}
        seq_touched: Dict[Tuple[str, int, str], int] = {}
        for env in batch:
            seq = env.seq
            if seq is not None:
                # dedup replayed envelopes: the spool re-delivers
                # anything sent-but-unacked around a link failure, so a
                # seq at or below the lane's committed max is a replay
                # of a row already in the DB.  seq_touched covers dups
                # landing inside this same batch (original + replay
                # drained together).
                skey = (
                    str(env.meta.get("session_id", "unknown")),
                    env.global_rank,
                    PRIORITY_NAMES[ingest_priority(env.sampler)],
                )
                cur_max = seq_touched.get(skey, self._seq_max.get(skey, -1))
                if seq <= cur_max:
                    self.replay_duplicates += 1
                    continue
                seq_touched[skey] = seq
            writer = self._writer_cache.get(env.sampler, _MISSING)
            if writer is _MISSING:
                writer = writer_for(env.sampler)
                self._writer_cache[env.sampler] = writer
            if writer is None:
                self._record_unknown_domain(env.sampler)
                continue
            try:
                table_rows = writer.build_rows(env)
            except Exception as exc:
                get_error_log().warning(
                    f"projection build failed for {env.sampler}", exc
                )
                continue
            for table, rows in table_rows.items():
                if not rows:
                    continue
                sql = self._sql_cache.get(table)
                if sql is None:
                    sql = self._sql_cache[table] = writer.insert_sql(table)
                grouped.setdefault(sql, []).extend(rows)
                if table in self._retention_tables:
                    # every row of an envelope shares one identity tuple
                    # (session_id, global_rank lead each row), so the
                    # partition count costs O(1) per (envelope, table)
                    key = (table, rows[0][0], rows[0][1])
                    touched[key] = touched.get(key, 0) + len(rows)
        t0 = time.perf_counter()
        try:
            conn.execute("BEGIN")
            for sql, rows in grouped.items():
                conn.executemany(sql, rows)
                self.written += len(rows)
            if seq_touched:
                # the new maxima commit atomically with the rows they
                # cover: a crash between the two can never produce an
                # aggregator that drops a replay it didn't persist
                conn.executemany(
                    f"INSERT OR REPLACE INTO {RANK_SEQ_TABLE}"
                    " (session_id, global_rank, lane, max_seq)"
                    " VALUES (?,?,?,?)",
                    [(k[0], k[1], k[2], mx) for k, mx in seq_touched.items()],
                )
            for key, n in touched.items():
                count = self._part_counts.get(key, 0) + n
                self._part_counts[key] = count
                self._note_overflow(key, count)
            # retention deletes ride the batch transaction: one commit
            # per cycle instead of two, and the journal row lands
            # atomically with the inserts that triggered it
            self._prune_slice(conn, commit=False)
            conn.commit()
            # in-memory maxima advance only after the commit lands —
            # on rollback the rows are gone, so their replay must pass
            self._seq_max.update(seq_touched)
            if self._on_commit is not None:
                try:
                    self._on_commit(batch)
                except Exception as exc:
                    get_error_log().warning("on_commit hook failed", exc)
        except sqlite3.Error as exc:
            get_error_log().warning("sqlite batch write failed", exc)
            try:
                conn.rollback()
            except sqlite3.Error:
                pass
            return
        finally:
            self._batches += 1
            # the whole batch is resolved — committed, dedup'd, unknown,
            # or (on the rollback path above) permanently lost; none of
            # it will ever be retried, so the watermark may advance
            with self._stats_lock:
                self._settled += len(batch)
        lat = (time.perf_counter() - t0) * 1000.0
        self._commit_lat_ms.append(lat)
        if lat > self._commit_max_ms:
            self._commit_max_ms = lat

    # -- retention (O(new) watermark deletes) ----------------------------
    def _prune_slice(
        self, conn: sqlite3.Connection, commit: bool = True
    ) -> int:
        """Prune a bounded number of due partitions — amortized so no
        commit cycle ever absorbs a full-scan spike.  With
        ``commit=False`` the deletes join the caller's open
        transaction (the batch-write path)."""
        if not self._prune_due:
            return 0
        pruned = 0
        budget = min(self._prune_slice_max, len(self._prune_due))
        for _ in range(budget):
            key = self._prune_due.popleft()
            self._prune_due_set.discard(key)
            pruned += self._prune_partition(conn, key)
        if commit:
            try:
                conn.commit()
            except sqlite3.Error as exc:
                get_error_log().warning("prune commit failed", exc)
        return pruned

    def _prune_all(self, conn: sqlite3.Connection) -> None:
        """Finalize path: trim EVERY partition holding more than
        ``retention`` rows (not just those past the hysteresis slack) so
        the final DB matches the seed windowed prune row-for-row."""
        self._prune_due.clear()
        self._prune_due_set.clear()
        for key, count in list(self._part_counts.items()):
            if count > self._retention_rows:
                self._prune_partition(conn, key)
        try:
            conn.commit()
        except sqlite3.Error as exc:
            get_error_log().warning("final prune commit failed", exc)

    def _prune_partition(
        self, conn: sqlite3.Connection, key: Tuple[str, str, int]
    ) -> int:
        """Delete one partition's overflow via an indexed range delete.

        The watermark — the id of the (retention+1)-th newest row — is
        found by an index-only walk over this partition (O(retention)),
        and the DELETE removes only ids at or below it (O(deleted)).
        The journal row commits atomically with the delete so readers
        observe the trim exactly (reporting/snapshot_store.py).
        """
        table, session_id, rank = key
        t0 = time.perf_counter()
        try:
            row = conn.execute(
                f"SELECT id FROM {table} WHERE session_id=? AND"
                " global_rank=? ORDER BY id DESC LIMIT 1 OFFSET ?",
                (session_id, rank, self._retention_rows),
            ).fetchone()
            if row is None:
                # fewer rows than retention: the count was stale (e.g.
                # seeded upper bound) — clamp it so we don't re-queue
                self._part_counts[key] = self._retention_rows
                return 0
            watermark = int(row[0])
            # fold the doomed id-range into the rollup tiers BEFORE the
            # delete, inside this same transaction: commit lands
            # fold+delete+journal together, rollback restores all-raw —
            # a crash can never leave rows neither raw nor rolled up.
            # A fold failure degrades to plain (history-discarding)
            # retention rather than blocking the prune: partial tier
            # upserts still cover only doomed rows, so deleting keeps
            # the invariant while double-fold on retry would not.
            if self._rollup is not None and table in self._rollup.sources:
                try:
                    self._rollup.fold_doomed(
                        conn, table, session_id, rank, watermark
                    )
                except Exception as exc:
                    get_error_log().warning(
                        f"rollup fold failed for {table}", exc
                    )
            cur = conn.execute(
                f"DELETE FROM {table} WHERE session_id=? AND global_rank=?"
                " AND id <= ?",
                (session_id, rank, watermark),
            )
            deleted = cur.rowcount if cur.rowcount is not None else 0
            conn.execute(
                f"INSERT INTO {WATERMARK_TABLE} (table_name, session_id,"
                " global_rank, watermark_id, deleted_rows, ts)"
                " VALUES (?,?,?,?,?,?)",
                (table, session_id, rank, watermark, deleted, time.time()),
            )
        except sqlite3.Error as exc:
            get_error_log().warning(f"prune failed for {table}", exc)
            return 0
        self._part_counts[key] = self._retention_rows
        self.prunes += 1
        self.rows_pruned += deleted
        lat = (time.perf_counter() - t0) * 1000.0
        self._prune_lat_ms.append(lat)
        if lat > self._prune_max_ms:
            self._prune_max_ms = lat
        self._journal_rows += 1
        if self._journal_rows >= _JOURNAL_MAX_ROWS:
            self._trim_journal(conn)
        return deleted

    def _trim_journal(self, conn: sqlite3.Connection) -> None:
        """Keep the watermark journal bounded.  Store cursors only move
        forward, so deleting old journal rows is invisible to any live
        reader; a reader attaching later never held the trimmed data
        rows in the first place."""
        try:
            row = conn.execute(
                f"SELECT MAX(id) FROM {WATERMARK_TABLE}"
            ).fetchone()
            if row and row[0]:
                conn.execute(
                    f"DELETE FROM {WATERMARK_TABLE} WHERE id <= ?",
                    (int(row[0]) - _JOURNAL_MAX_ROWS // 2,),
                )
            self._journal_rows = 0
        except sqlite3.Error as exc:
            get_error_log().warning("journal trim failed", exc)
