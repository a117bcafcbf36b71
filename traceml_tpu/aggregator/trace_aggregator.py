"""The aggregator
(reference: src/traceml_ai/aggregator/trace_aggregator.py:89-586).

Owns the TCP ingest server, the SQLite writer, the final-summary
service, and a display driver.  Event-driven loop: block on
``wait_for_data`` (bounded by the render interval), split telemetry from
control messages, ingest, rate-limited UI tick + summary poll.

Shutdown (``stop()``): settle late telemetry until every expected rank
sent ``rank_finished`` or the deadline passes (writing a
``finalization_warning.json`` naming missing ranks), budgeted SQLite
finalize, then generate the final summary and write artifacts.

Fault tolerance (docs/developer_guide/fault-tolerance.md): every
envelope and control message feeds the rank liveness tracker
(``rank_status.json``, ACTIVE→STALE→LOST); a restarted aggregator
re-seeds finished ranks and last-seen from that file, and the SQLite
writer's per-lane seq table dedups the ranks' reconnect replay.
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Set

from traceml_tpu.aggregator.display_drivers import resolve_display_driver
from traceml_tpu.aggregator.freshness import FreshnessTracker
from traceml_tpu.aggregator.liveness import RankLivenessTracker
from traceml_tpu.aggregator.session_registry import SessionRegistry
from traceml_tpu.aggregator.sqlite_writer import SQLiteWriter
from traceml_tpu.aggregator.summary_service import FinalSummaryService
from traceml_tpu.runtime.settings import TraceMLSettings
from traceml_tpu.sdk import protocol
from traceml_tpu.telemetry.control import (
    MESH_TOPOLOGY,
    PRODUCER_STATS,
    RANK_FINISHED,
    RANK_HEARTBEAT,
    TRANSPORT_HELLO,
    control_kind,
    is_control_message,
)
from traceml_tpu.telemetry.envelope import TelemetryEnvelope, normalize_telemetry_envelope
from traceml_tpu.transport.select import server_transport_config
from traceml_tpu.transport.tcp_transport import TCPServer
from traceml_tpu.utils.atomic_io import atomic_write_json
from traceml_tpu.utils.error_log import get_error_log

_RENDER_INTERVAL = 0.5
_SETTLE_POLL = 0.1
# max frames decoded per _drain_once call: a backlog burst (slow UI
# tick, hundreds of ranks reconnecting) is worked off in bounded slices
# so the loop can interleave UI ticks instead of decoding for seconds
_DRAIN_BATCH_FRAMES = 512


class TraceMLAggregator:
    def __init__(self, settings: TraceMLSettings) -> None:
        self.settings = settings
        # transport tier: in auto mode the ingest server also stands up
        # a UDS listener and polls same-host shm rings; TRACEML_TRANSPORT
        # =tcp yields exactly the plain pre-transport-tier TCPServer
        # (docs/developer_guide/native-transport.md)
        transport_cfg = server_transport_config(settings)
        self.server = TCPServer(
            host=settings.aggregator.bind_host,
            port=settings.aggregator.port,
            uds_path=transport_cfg.get("uds_path"),
        )
        self.ring_registry = None
        if transport_cfg.get("enable_rings"):
            try:
                from traceml_tpu.transport.shm_ring import ShmRingRegistry

                self.ring_registry = ShmRingRegistry(settings.session_dir)
                self.server.attach_ring_registry(self.ring_registry)
            except Exception as exc:
                get_error_log().warning("shm ring registry unavailable", exc)
        self.db_path = settings.session_dir / "telemetry.sqlite"
        # rank 0's step rows followed from frame arrival to commit to
        # the first publish that shows them (aggregator/freshness.py)
        self.freshness = FreshnessTracker()
        self.writer = SQLiteWriter(
            self.db_path,
            summary_window_rows=settings.summary_window_rows,
            on_commit=self.freshness.committed,
        )
        self.display = resolve_display_driver(settings.mode)
        # serving tier: the display driver reads THROUGH this registry,
        # so one aggregator process can serve sibling sessions under the
        # same logs_dir (fleet index + per-session publishers)
        self.registry = SessionRegistry(
            settings.logs_dir,
            default_session=settings.session_id,
            max_sessions=settings.serve_max_sessions,
            freshness=self.freshness,
        )
        self.summary_service = FinalSummaryService(
            settings,
            generate=self.generate_final_summary,
            settle=self.settle_telemetry,
        )
        self._thread: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()
        self._finished_ranks: Set[int] = set()
        self._seen_ranks: Set[int] = set()
        self.liveness = RankLivenessTracker()
        # latest producer_stats snapshot per rank (publisher self-
        # observability: collect/encode/flush cost, idle-tick ratio)
        self._producer_stats: Dict[int, Dict[str, Any]] = {}
        # per-rank transport_hello announcements (kind + codec chosen)
        self._transport_hellos: Dict[int, Dict[str, Any]] = {}
        # _drain_lock now guards ONLY the frame handoff (server.drain +
        # ticket issue); decode runs unlocked and ingest is ordered by
        # ticket under _ingest_cond — see _drain_once
        self._drain_lock = threading.Lock()
        self._ingest_cond = threading.Condition()
        self._drain_ticket = 0
        self._ingest_next = 0
        # shm durable-consumption watermarks: ring tails advance only
        # after the writer settles the envelopes drained up to a cursor
        # snapshot, so an aggregator kill -9 between drain and commit
        # re-delivers the window to the next incarnation (seq dedup
        # absorbs the overlap).  Guarded by _ingest_cond; the drained-
        # frame counter by _drain_lock.
        self._shm_frames_drained = 0
        self._ring_watermarks: "deque" = deque()
        self._last_drain_frames = 0
        self._last_ui_tick = 0.0
        self._last_stats_write = 0.0
        # periodic ingest_stats.json cadence (instance attr so tests and
        # embedders can tighten it)
        self._stats_interval = 5.0
        self.envelopes_ingested = 0
        self.started = False
        self.port: Optional[int] = None

    # -- lifecycle -------------------------------------------------------
    def start(self) -> None:
        if self.started:
            return
        self.started = True
        get_error_log().set_path(self.settings.session_dir / "aggregator_error.log")
        self.settings.session_dir.mkdir(parents=True, exist_ok=True)
        self._reseed_from_prior_run()
        self.server.start()
        self.port = self.server.port
        self.writer.start()
        try:
            self.display.start(self)
        except Exception as exc:
            get_error_log().warning("display start failed", exc)
        self._stop_evt.clear()
        self._thread = threading.Thread(
            target=self._loop, name="traceml-aggregator", daemon=True
        )
        self._thread.start()

    def stop(self, finalize_timeout: Optional[float] = None) -> None:
        if not self.started:
            return
        self.started = False
        budget = (
            finalize_timeout
            if finalize_timeout is not None
            else self.settings.finalize_timeout_sec
        )
        deadline = time.monotonic() + max(1.0, budget)
        try:
            self._settle_end_of_run(deadline)
        except Exception as exc:
            get_error_log().warning("end-of-run settle failed", exc)
        self._stop_evt.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        self.server.stop()
        try:
            self.display.stop()
        except Exception as exc:
            get_error_log().warning("display stop failed", exc)
        try:
            self.registry.close()
        except Exception as exc:
            get_error_log().warning("session registry close failed", exc)
        ok = self.writer.finalize(timeout=max(5.0, deadline - time.monotonic()))
        if not ok:
            get_error_log().warning("sqlite finalize incomplete within budget")
        # self-metrics for the summary meta (reference parity: SQLite
        # writer counters enqueued/dropped/written, now with queue /
        # group-commit / prune detail)
        try:
            self._write_ingest_stats(final=True)
        except Exception as exc:
            get_error_log().warning("ingest stats write failed", exc)
        try:
            if not self.generate_final_summary():
                atomic_write_json(
                    self.settings.session_dir / "finalization_error.json",
                    {"error": "final summary generation failed", "ts": time.time()},
                )
        except Exception as exc:
            get_error_log().error("final summary at shutdown failed", exc)
            atomic_write_json(
                self.settings.session_dir / "finalization_error.json",
                {"error": str(exc), "ts": time.time()},
            )

    def _reseed_from_prior_run(self) -> None:
        """Crash-resume: a restarted aggregator (same session dir) picks
        up where its predecessor left off.  The SQLite writer re-seeds
        its partition counts and seq-dedup table from the reopened DB;
        here we restore what only lived in aggregator memory — which
        ranks already finished, and their liveness history — from the
        last persisted ``rank_status.json``."""
        path = self.settings.session_dir / "rank_status.json"
        if not path.exists():
            return
        try:
            snap = json.loads(path.read_text())
        except (OSError, ValueError) as exc:
            get_error_log().warning("rank_status reseed failed", exc)
            return
        if snap.get("session_id") not in (None, self.settings.session_id):
            return  # stale file from a different session sharing the dir
        self.liveness.seed(snap)
        ranks = snap.get("ranks")
        if isinstance(ranks, dict):
            for rank_s, info in ranks.items():
                try:
                    rank = int(rank_s)
                except (TypeError, ValueError):
                    continue
                self._seen_ranks.add(rank)
                if isinstance(info, dict) and info.get("finished"):
                    self._finished_ranks.add(rank)

    # -- ingest ----------------------------------------------------------
    def _drain_once(self, max_frames: Optional[int] = _DRAIN_BATCH_FRAMES) -> int:
        # Three stages, pipelined across callers (aggregator loop and the
        # summary-service thread via settle_telemetry):
        #   1. frame handoff under _drain_lock (cheap list splice + a
        #      monotonically increasing ticket),
        #   2. msgpack decode with NO lock held — the expensive part, so
        #      settle_telemetry never blocks behind another caller's
        #      decode slice; concurrent slices decode in parallel,
        #   3. ingest in ticket order under _ingest_cond, preserving the
        #      seed's strict frame ordering into the writer queues.
        with self._drain_lock:
            frames, arrivals = self.server.drain_timed(max_frames)
            ticket = self._drain_ticket
            self._drain_ticket += 1
            cursors = None
            if self.ring_registry is not None and frames:
                shm_n = sum(1 for tag, _f in frames if tag.startswith("shm:"))
                if shm_n:
                    self._shm_frames_drained += shm_n
                    # newest ring-cursor snapshot fully covered by the
                    # frames this (and earlier) drain slices pulled out
                    cursors = self.ring_registry.take_marks(
                        self._shm_frames_drained
                    )
        payloads: List[Any] = []
        payload_arrivals: List[float] = []
        try:
            # tagged decode, frame by frame: a corrupt frame is counted
            # against its peer and skipped instead of poisoning the
            # whole batch, and each payload keeps its frame's arrival
            for frame, t in zip(frames, arrivals):
                decoded = self.server.decode_tagged([frame])
                payloads.extend(decoded)
                payload_arrivals.extend([t] * len(decoded))
        finally:
            n = 0
            with self._ingest_cond:
                while ticket != self._ingest_next:
                    self._ingest_cond.wait(1.0)
                try:
                    for p, t in zip(payloads, payload_arrivals):
                        self._chaos_ingest_hook()
                        if is_control_message(p):
                            self._handle_control(p)
                            continue
                        env = normalize_telemetry_envelope(p)
                        if env is None:
                            continue
                        if self.freshness.follows(env):
                            self.freshness.arrived(env, t)
                        self._seen_ranks.add(env.global_rank)
                        self.liveness.observe(
                            env.global_rank,
                            progress=env.sampler == "step_time",
                        )
                        self.writer.ingest(env)
                        n += 1
                    self.envelopes_ingested += n
                    self._last_drain_frames = len(frames)
                    if cursors:
                        # ticket ordering guarantees envelopes_ingested
                        # now covers every frame drained before this
                        # cursor snapshot — commit the tails once the
                        # writer has settled that many envelopes
                        self._ring_watermarks.append(
                            (self.envelopes_ingested, cursors)
                        )
                finally:
                    # the ticket advances even when decode/ingest raised,
                    # or every later caller would deadlock at the gate
                    self._ingest_next += 1
                    self._ingest_cond.notify_all()
        return n

    @staticmethod
    def _chaos_ingest_hook() -> None:
        """Fault-injection point: fires ``aggregator.ingest`` once per
        drained payload (kill9 rules SIGKILL this process inside fire —
        the chaos e2e suite uses that to crash the aggregator at a
        deterministic envelope count)."""
        try:
            from traceml_tpu.dev import chaos

            if chaos.active():
                chaos.fire("aggregator.ingest")
        except ImportError:  # pragma: no cover
            pass

    def _commit_rings(self) -> None:
        """Advance shm ring tails for every watermark the writer has
        settled (see _ring_watermarks).  Cheap when nothing is eligible;
        called from the loop tick and after each force_flush."""
        if self.ring_registry is None:
            return
        with self._ingest_cond:
            if not self._ring_watermarks:
                return
            settled = self.writer.settled_envelopes()
            cursors = None
            while self._ring_watermarks and self._ring_watermarks[0][0] <= settled:
                cursors = self._ring_watermarks.popleft()[1]
        if cursors:
            self.ring_registry.commit(cursors)

    def _drain_all(self) -> int:
        """Drain to empty in bounded slices (settle/shutdown path: no UI
        between batches, but each slice stays interruptible by the GIL)."""
        total = self._drain_once()
        while self._last_drain_frames >= _DRAIN_BATCH_FRAMES:  # tracelint: unguarded(single int read; a stale value only defers or adds one bounded drain slice)
            total += self._drain_once()
        return total

    def _write_ingest_stats(self, final: bool = False) -> None:
        """Self-metrics snapshot — written periodically from the loop
        (every ``_stats_interval`` seconds) so a live observer sees
        backpressure building, not just the post-mortem at stop()."""
        wstats = self.writer.stats()
        with self._ingest_cond:
            ingested = self.envelopes_ingested
        atomic_write_json(
            self.settings.session_dir / "ingest_stats.json",
            {
                "envelopes_ingested": ingested,
                "frames_received": self.server.frames_received,
                "decode_errors": self.server.decode_errors,
                "corrupt_frame_drops": dict(self.server.corrupt_frame_drops),
                "pending_frames_hwm": self.server.pending_hwm,
                "rows_written": self.writer.written,
                "rows_enqueued": self.writer.enqueued,
                "rows_dropped": self.writer.dropped,
                "enqueued_by_domain": wstats["enqueued_by_domain"],
                "dropped_by_domain": wstats["dropped_by_domain"],
                "unknown_domain_drops": wstats["unknown_domain_drops"],
                "drop_warnings": wstats["drop_warnings"],
                "replay_duplicates": wstats["replay_duplicates"],
                "queues": wstats["queues"],
                "group_commit": wstats["group_commit"],
                "prune": wstats["prune"],
                "finished_ranks": sorted(self._finished_ranks),
                "producers": {
                    str(rank): stats
                    for rank, stats in sorted(self._producer_stats.items())
                },
                "transports": self._transport_stats(),
                "freshness": self.freshness.block(),
                "final": final,
                "ts": time.time(),
            },
        )
        self._write_rank_status()

    def _transport_stats(self) -> Dict[str, Any]:
        """Transport-tier observability: frames per arrival path, the
        decompression counters, shm ring registry health, and each
        rank's announced (kind, codec)."""
        out: Dict[str, Any] = {
            "frames_by_kind": dict(self.server.frames_by_transport),
            "compression": {
                "envelopes": self.server.compressed_envelopes,
                "bytes_in": self.server.compressed_bytes_in,
                "bytes_decoded": self.server.decompressed_bytes,
                "errors": self.server.decompress_errors,
            },
            "ranks": {
                str(rank): hello
                for rank, hello in sorted(self._transport_hellos.items())
            },
        }
        if self.ring_registry is not None:
            out["shm"] = self.ring_registry.stats()
        return out

    def _write_rank_status(self) -> None:
        """Persist the liveness snapshot.  Written on the stats cadence
        and at settle-end; readers (report, web payload, a restarted
        aggregator) use the states as written — re-deriving them after
        the run would mark every silent-because-done rank LOST."""
        snap = self.liveness.snapshot()
        snap["session_id"] = self.settings.session_id
        snap["expected_world_size"] = self.expected_world_size()
        atomic_write_json(self.settings.session_dir / "rank_status.json", snap)

    def _handle_control(self, payload: Dict[str, Any]) -> None:
        kind = control_kind(payload)
        if kind == RANK_FINISHED:
            meta = payload.get("meta") or {}
            rank = meta.get("global_rank", meta.get("rank"))
            try:
                rank = int(rank)
            except (TypeError, ValueError):
                # a garbled marker must NOT default to rank 0 — that
                # falsely settles rank 0 and can unblock shutdown with
                # real telemetry still in flight; drop it loudly instead
                get_error_log().warning(
                    f"rank_finished with invalid global_rank {rank!r}; dropped"
                )
                return
            self._finished_ranks.add(rank)
            self.liveness.mark_finished(rank)
        elif kind == RANK_HEARTBEAT:
            meta = payload.get("meta") or {}
            try:
                rank = int(meta.get("global_rank", meta.get("rank")))
            except (TypeError, ValueError):
                return
            self._seen_ranks.add(rank)
            self.liveness.observe(rank)
        elif kind == PRODUCER_STATS:
            meta = payload.get("meta") or {}
            stats = payload.get("stats")
            if not isinstance(stats, dict):
                return
            try:
                rank = int(meta.get("global_rank", meta.get("rank")))
            except (TypeError, ValueError):
                return
            # later snapshots are cumulative — keep only the latest
            self._producer_stats[rank] = stats
            self.liveness.observe(rank)
        elif kind == TRANSPORT_HELLO:
            meta = payload.get("meta") or {}
            try:
                rank = int(meta.get("global_rank", meta.get("rank")))
            except (TypeError, ValueError):
                return
            self._seen_ranks.add(rank)
            self.liveness.observe(rank)
            # keep-latest: a restarted rank may re-announce with a
            # different tier (e.g. fell back from shm to tcp)
            hello = {
                "transport": payload.get("transport"),
                "compression": payload.get("compression"),
            }
            if payload.get("fallback_from"):
                hello["fallback_from"] = payload.get("fallback_from")
            self._transport_hellos[rank] = hello
        elif kind == MESH_TOPOLOGY:
            meta = payload.get("meta") or {}
            topo = payload.get("topology")
            if not isinstance(topo, dict):
                return
            try:
                rank = int(meta.get("global_rank", meta.get("rank")))
            except (TypeError, ValueError):
                return
            self._seen_ranks.add(rank)
            self.liveness.observe(rank)
            # persist through the normal writer path: the control meta is
            # already identity-shaped, and carrying NO seq bypasses the
            # writer's dedup lane (spool replay may re-deliver this;
            # readers keep the latest row per rank, so appends are
            # idempotent at read time)
            try:
                env_meta = dict(meta)
                env_meta.pop("seq", None)
                env_meta["sampler"] = "mesh_topology"
                row = {
                    "timestamp": float(payload.get("timestamp") or time.time()),
                    "source": str(topo.get("source") or "mesh"),
                    "axes_json": json.dumps(topo.get("axes") or []),
                    "coords_json": json.dumps(topo.get("coords")),
                }
                self.writer.ingest(
                    TelemetryEnvelope(
                        meta=env_meta, tables={"mesh_topology": [row]}
                    )
                )
            except Exception as exc:
                get_error_log().warning("mesh_topology persist failed", exc)

    # -- loop ------------------------------------------------------------
    def _loop(self) -> None:
        while not self._stop_evt.is_set():
            try:
                self.server.wait_for_data(_RENDER_INTERVAL)
                # re-loop until the backlog is gone, giving the UI a
                # chance to tick between bounded decode batches — the
                # loop never parks in wait_for_data with frames pending
                while True:
                    self._drain_once()
                    self._commit_rings()
                    now = time.monotonic()
                    if now - self._last_ui_tick >= _RENDER_INTERVAL:
                        self._last_ui_tick = now
                        self.summary_service.poll()
                        try:
                            self.display.tick(self)
                        except Exception as exc:
                            get_error_log().warning("display tick failed", exc)
                    if now - self._last_stats_write >= self._stats_interval:
                        self._last_stats_write = now
                        try:
                            self._write_ingest_stats()
                        except Exception as exc:
                            get_error_log().warning(
                                "periodic ingest stats write failed", exc
                            )
                    if (
                        self._last_drain_frames < _DRAIN_BATCH_FRAMES  # tracelint: unguarded(single int read; a stale value only defers backlog catch-up to the next loop tick)
                        or self._stop_evt.is_set()
                    ):
                        break
            except Exception as exc:  # keep the loop alive
                get_error_log().warning("aggregator loop error", exc)
                time.sleep(0.1)

    # -- settle / finalize ------------------------------------------------
    def expected_world_size(self) -> int:
        if self.settings.expected_world_size:
            return self.settings.expected_world_size
        return max(len(self._seen_ranks), 1)

    def settle_telemetry(self, timeout: float = 5.0) -> None:
        """Drain whatever is in flight and wait for it to be committed
        (reference: trace_aggregator.py:518)."""
        deadline = time.monotonic() + timeout
        self._drain_all()
        self.writer.force_flush(timeout=max(0.5, deadline - time.monotonic()))
        self._commit_rings()

    def _settle_end_of_run(self, deadline: float) -> None:
        """Wait for all expected rank_finished markers or the deadline
        (reference: trace_aggregator.py:440-499)."""
        expected = self.expected_world_size()
        while time.monotonic() < deadline:
            self._drain_all()
            if len(self._finished_ranks) >= expected:
                break
            time.sleep(_SETTLE_POLL)
        self._drain_all()
        self.writer.force_flush(timeout=max(1.0, deadline - time.monotonic()))
        self._commit_rings()
        missing = sorted(
            set(range(expected)) - self._finished_ranks
        )
        if missing:
            # per-missing-rank liveness verdicts ride along: the report
            # distinguishes a rank that died mid-run (LOST, telemetry
            # data gap) from one that merely lost its finish marker
            now = time.time()
            atomic_write_json(
                self.settings.session_dir / "finalization_warning.json",
                {
                    "missing_ranks": missing,
                    "missing_rank_states": {
                        str(r): self.liveness.state_of(r, now) for r in missing
                    },
                    "finished_ranks": sorted(self._finished_ranks),
                    "expected_world_size": expected,
                    "ts": now,
                },
            )

    # -- summary ----------------------------------------------------------
    def generate_final_summary(self) -> bool:
        """Build final_summary artifacts from the SQLite DB."""
        from traceml_tpu.reporting.final import generate_summary

        return generate_summary(
            db_path=self.db_path,
            session_dir=self.settings.session_dir,
            settings=self.settings,
        )


def write_ready_file(
    settings: TraceMLSettings,
    port: int,
    display_port: Optional[int] = None,
) -> None:
    """The launcher polls this to learn the bound ports (ingest always;
    the dashboard's HTTP port when a browser driver is serving)."""
    payload: Dict[str, Any] = {
        "port": port,
        "pid": __import__("os").getpid(),
        "ts": time.time(),
    }
    if display_port is not None:
        payload["display_port"] = display_port
    atomic_write_json(
        settings.session_dir / "aggregator_ready.json", payload
    )
