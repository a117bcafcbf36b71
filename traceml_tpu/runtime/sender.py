"""Telemetry publisher (reference: src/traceml_ai/runtime/sender.py:17-174).

Per tick: collect each sampler sender's incremental payload, encode it
ONCE, hand the same bytes to the TCP batch and the disk backup, ship ONE
frame.  Best-effort all the way down.

Single-encode contract (r10, docs/developer_guide/rank-producer-path.md):

    payload = sender.collect_payload()        # columnar fast path
    enc = msgpack_codec.preencode(payload)    # THE encode
    batch.append(enc)                         # wire splices enc.raw
    writer.append_envelope(enc)               # disk splices enc.raw

Idle ticks take an O(#samplers) gate — ``sender.dirty()`` (one int
compare each) plus ``writer.has_pending()`` — and return without
building a payload, touching the disk, or taking the client lock.

Fault tolerance (docs/developer_guide/fault-tolerance.md):

* every outgoing payload is stamped with a per-rank monotonic ``seq``
  (``time_ns`` base, so a restarted rank resumes above its previous
  range without persisting a counter);
* with a spool directory configured, sends go through
  :class:`~traceml_tpu.transport.spool.DurableSender` — failed batches
  land in a bounded on-disk replay queue and drain on reconnect, with
  the aggregator deduping by seq;
* a ``rank_heartbeat`` control message ships every
  ``heartbeat_interval_s`` even across idle ticks (transient — never
  spooled), keeping the aggregator's liveness tracker fed.

The publisher also self-observes: per-sampler collect/encode/flush
nanoseconds, idle-tick ratio, the rank tracer's host time per step and
its step rows' emission waits (``TraceState.tracer_stats``), and the
transport/spool counters
(``reconnects``, ``replayed_envelopes``, ``spool_bytes``), exposed via
:meth:`stats` and shipped to the aggregator as a ``producer_stats``
control message (piggybacked on a non-idle batch at most every
``stats_interval_s``).
"""

from __future__ import annotations

import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

from traceml_tpu.samplers.base_sampler import BaseSampler
from traceml_tpu.sdk.state import get_state
from traceml_tpu.telemetry.control import (
    build_producer_stats,
    build_rank_heartbeat,
    build_transport_hello,
)
from traceml_tpu.telemetry.envelope import SenderIdentity
from traceml_tpu.transport import compression as transport_compression
from traceml_tpu.transport.spool import DurableSender, ReplaySpool
from traceml_tpu.transport.tcp_transport import TCPClient
from traceml_tpu.utils import msgpack_codec
from traceml_tpu.utils.error_log import get_error_log
from traceml_tpu.utils.self_trace import span

DEFAULT_HEARTBEAT_INTERVAL_S = 3.0


class TelemetryPublisher:
    def __init__(
        self,
        samplers: List[BaseSampler],
        client: Optional[TCPClient],
        identity: SenderIdentity,
        stats_interval_s: float = 10.0,
        spool_dir: Optional[Path] = None,
        heartbeat_interval_s: float = DEFAULT_HEARTBEAT_INTERVAL_S,
        transport_info: Optional[Dict[str, Any]] = None,
    ) -> None:
        self._samplers = samplers
        self._client = client
        self._identity = identity
        # transport-tier selection ({"kind", "compression", ...}) — see
        # transport/select.py; announced once via transport_hello and
        # reported in stats()
        self._transport_info = transport_info or {}
        self._hello_pending = client is not None
        codec = self._transport_info.get("compression")
        # wire/spool compression: the disk backup keeps the plain enc
        # (local reads should not pay a decompress), while the batch —
        # and therefore the spool, whose frames store exactly the wire
        # body — carries the compressed carrier
        self._compressor = (
            transport_compression.EnvelopeCompressor(codec) if codec else None
        )
        for s in samplers:
            s.sender.set_identity(identity)
            # the publisher owns collection; the writer must never fall
            # back to its legacy self-collecting row path (double-write)
            s.writer.mark_envelope_mode()
        self.ticks = 0
        self.idle_ticks = 0
        self.payloads_sent = 0
        # frames and step rows the step-row lane shipped (publish_units)
        self.lane_publishes = 0
        self.lane_rows = 0
        self._stats_interval = stats_interval_s
        self._last_stats_emit = time.monotonic()
        self._heartbeat_interval = max(0.25, float(heartbeat_interval_s))
        self._last_heartbeat = 0.0  # monotonic; 0 → first tick sends one
        # per-rank monotonic seq: time_ns base means a restarted rank
        # (same session, same global_rank) resumes strictly above every
        # seq its previous incarnation could have stamped, so the
        # aggregator's max-seq dedup never swallows fresh telemetry
        self._seq = time.time_ns()
        self._durable: Optional[DurableSender] = None
        if client is not None and spool_dir is not None:
            try:
                self._durable = DurableSender(client, ReplaySpool(spool_dir))
            except Exception as exc:
                get_error_log().warning("replay spool unavailable", exc)
        self._sampler_stats: Dict[str, Dict[str, int]] = {
            s.name: {
                "envelopes": 0,
                "bytes": 0,
                "collect_ns": 0,
                "encode_ns": 0,
                "flush_ns": 0,
            }
            for s in samplers
        }
        # (sender, writer, stats) resolved once: the publish tick is the
        # producer hot path and skips per-tick attribute/dict lookups
        self._units = [
            (s, s.sender, s.writer, self._sampler_stats[s.name])
            for s in samplers
        ]

    def _idle(self) -> bool:
        for s in self._samplers:
            if s.sender.dirty() or s.writer.has_pending():
                return False
        return True

    def _stamp_seq(self, payload: Any) -> None:
        """Stamp the next per-rank seq into ``payload["meta"]``.  Control
        messages get one too — the spool frames every payload uniformly
        (their handlers are idempotent, so they skip the dedup table)."""
        self._seq += 1
        try:
            payload["meta"]["seq"] = self._seq
        except (TypeError, KeyError):
            pass

    def publish(
        self, extra_payloads: Optional[List[Any]] = None, final: bool = False
    ) -> int:
        """Collect + send; returns number of payloads in the batch.  Runs
        in one ``traceml/publish`` profiler span."""
        with span("traceml/publish"):
            return self._publish(extra_payloads, final)

    def _publish(self, extra_payloads: Optional[List[Any]], final: bool) -> int:
        self.ticks += 1
        if not final and not extra_payloads and self._idle():
            self.idle_ticks += 1
            self._maybe_heartbeat()
            return 0
        batch: List[Any] = []
        self._collect(self._units, batch, final, backup_flush=True)
        if extra_payloads:
            for p in extra_payloads:
                self._stamp_seq(p)
            batch.extend(extra_payloads)
        return self._send(batch, final)

    def publish_units(self, names: Sequence[str]) -> int:
        """Collect + send only the named samplers' units: the runtime's
        step-row lane (``TraceMLRuntime``) ships resolved step rows
        between full ticks through here.  Same single encode, seq
        stamping, durable sender and per-sampler stats as
        :meth:`publish`; not a tick (``ticks`` / ``idle_ticks`` keep
        counting full ticks only), and the disk backup is appended but
        not flushed, so its ``flush_every`` throttle keeps counting full
        ticks.  Returns the number of payloads sent."""
        with span("traceml/publish"):
            units = [u for u in self._units if u[0].name in names]
            batch: List[Any] = []
            rows = self._collect(units, batch, False, backup_flush=False)
            if not batch:
                return 0
            self.lane_publishes += 1
            self.lane_rows += rows
            return self._send(batch, False)

    def _collect(
        self, units: List[tuple], batch: List[Any], final: bool, backup_flush: bool
    ) -> int:
        """Append each unit's encoded payload to ``batch`` and its disk
        backup; returns the rows of each sampler's own table (the
        ``step_time`` table for the step-time sampler)."""
        rows = 0
        perf = time.perf_counter_ns
        for s, sender, writer, st in units:
            try:
                t0 = perf()
                payload = sender.collect_payload()
                t1 = perf()
                st["collect_ns"] += t1 - t0
                if payload is not None:
                    own = payload["body"]["tables"].get(s.name)
                    if own is not None:
                        rows += own["n"]
                    self._stamp_seq(payload)
                    enc = msgpack_codec.preencode(payload)
                    t2 = perf()
                    st["encode_ns"] += t2 - t1
                    st["envelopes"] += 1
                    st["bytes"] += enc.size()
                    if self._compressor is not None:
                        batch.append(self._compressor.wrap(enc))
                    else:
                        batch.append(enc)
                    writer.append_envelope(enc)
                    if backup_flush:
                        t3 = perf()
                        writer.flush(force=final)
                        st["flush_ns"] += perf() - t3
                elif backup_flush and (final or writer.has_pending()):
                    # nothing collected but buffered backup frames (or a
                    # final drain) still need the flush throttle to run
                    t3 = perf()
                    writer.flush(force=final)
                    st["flush_ns"] += perf() - t3
            except Exception as exc:
                get_error_log().warning(
                    f"collect failed for sampler {s.name}", exc
                )
        return rows

    def _send(self, batch: List[Any], final: bool) -> int:
        """Hello and stats piggyback, then one frame; returns its size."""
        if batch:
            hello = self._take_hello()
            if hello is not None:
                self._stamp_seq(hello)
                batch.insert(0, hello)
            stats_msg = self._maybe_stats_message(final)
            if stats_msg is not None:
                self._stamp_seq(stats_msg)
                batch.append(stats_msg)
        if batch and self._client is not None:
            if self._durable is not None:
                if self._durable.send(batch):
                    self.payloads_sent += len(batch)
                self._last_heartbeat = time.monotonic()
            elif self._client.send_batch(batch):
                self.payloads_sent += len(batch)
                self._last_heartbeat = time.monotonic()
        return len(batch)

    def _take_hello(self) -> Optional[Dict[str, Any]]:
        """The send-once transport_hello announcement (observability:
        which tier and codec this rank selected)."""
        if not self._hello_pending:
            return None
        self._hello_pending = False
        try:
            return build_transport_hello(
                self._identity.to_meta(),
                self._transport_info.get("kind")
                or getattr(self._client, "kind", "tcp"),
                self._transport_info.get("compression"),
                self._transport_info.get("fallback_from"),
            )
        except Exception:
            return None

    def _maybe_heartbeat(self) -> None:
        """Liveness beacon on idle ticks.  Transient (never spooled — a
        replayed heartbeat carries no liveness information), but it
        kicks the durable sender's replay so an idle rank still drains
        its spool the moment the link heals."""
        if self._client is None:
            return
        now = time.monotonic()
        if now - self._last_heartbeat < self._heartbeat_interval:
            return
        self._last_heartbeat = now
        try:
            hb = build_rank_heartbeat(self._identity.to_meta())
            self._stamp_seq(hb)
            msgs = [hb]
            # a fully idle rank still announces its transport once
            hello = self._take_hello()
            if hello is not None:
                self._stamp_seq(hello)
                msgs.insert(0, hello)
            if self._durable is not None:
                self._durable.send_transient(msgs)
            else:
                self._client.send_batch(msgs)
        except Exception as exc:
            get_error_log().warning("heartbeat send failed", exc)

    def _maybe_stats_message(self, final: bool) -> Optional[Dict[str, Any]]:
        """Producer self-observability, piggybacked on a batch that is
        shipping anyway (never turns an idle tick into traffic)."""
        now = time.monotonic()
        if not final and now - self._last_stats_emit < self._stats_interval:
            return None
        self._last_stats_emit = now
        try:
            return build_producer_stats(self._identity.to_meta(), self.stats())
        except Exception:
            return None

    def drain_spool(self, wait_s: float) -> bool:
        """Exit drain: replay what failed sends left in the spool, waiting
        up to ``wait_s`` for the aggregator; True when nothing is left."""
        if self._durable is None:
            return True
        return self._durable.drain_before_exit(wait_s)

    def close(self) -> None:
        if self._durable is not None:
            self._durable.close()

    def stats(self) -> Dict[str, Any]:
        """Per-sampler producer-path cost (microseconds) + idle ratio +
        transport/spool health."""
        samplers: Dict[str, Any] = {}
        for name, st in self._sampler_stats.items():
            samplers[name] = {
                "envelopes": st["envelopes"],
                "bytes": st["bytes"],
                "collect_us": st["collect_ns"] // 1000,
                "encode_us": st["encode_ns"] // 1000,
                "flush_us": st["flush_ns"] // 1000,
            }
        out: Dict[str, Any] = {
            "ticks": self.ticks,
            "idle_ticks": self.idle_ticks,
            "idle_ratio": (self.idle_ticks / self.ticks) if self.ticks else 0.0,
            "payloads_sent": self.payloads_sent,
            "lane_publishes": self.lane_publishes,
            "lane_rows": self.lane_rows,
            "samplers": samplers,
            "tracer": get_state().tracer_stats(),
        }
        transport: Dict[str, Any] = {}
        if self._client is not None:
            # getattr: embedders pass client doubles that predate these
            # counters; stats must never take down the publish tick
            transport = {
                "kind": self._transport_info.get("kind")
                or getattr(self._client, "kind", "tcp"),
                "reconnects": getattr(self._client, "reconnects", 0),
                "batches_sent": getattr(self._client, "batches_sent", 0),
                "batches_dropped": getattr(self._client, "batches_dropped", 0),
            }
            ring_full = getattr(self._client, "ring_full_drops", None)
            if ring_full is not None:
                transport["ring_full_drops"] = ring_full
        if self._compressor is not None:
            transport["compression"] = self._compressor.stats()
        if self._durable is not None:
            transport.update(self._durable.stats())
        if transport:
            out["transport"] = transport
        return out
