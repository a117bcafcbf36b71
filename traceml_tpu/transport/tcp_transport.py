"""Length-prefixed TCP transport
(reference: src/traceml_ai/transport/tcp_transport.py:21-268).

Frames: 4-byte big-endian length + codec body (see utils/msgpack_codec).
One ``send_batch`` call encodes a *list* of payloads into ONE frame and one
``sendall`` — the per-tick batching contract that keeps syscall count O(1)
per sampler tick.

Differences from the reference, chosen for the TPU build:

* the server is a **single selector-driven thread** (accept + read for all
  clients) instead of thread-per-client — hundreds of ranks on a pod slice
  must not mean hundreds of threads in the aggregator;
* the receive path drains complete frames in O(bytes) with a rolling
  buffer offset (the reference ships an O(N) drain too, proved by its
  bench tests/benchmarks/bench_tcp_drain.py);
* the selector thread only **splits frames** — msgpack decode happens on
  the consumer's thread (``drain()`` returns raw frames;
  ``decode_frames``/``drain_decoded`` do the decode), so one rank sending
  a huge batch can never stall accepts/reads for every other rank.

Frame bodies carry telemetry envelopes in schema v1 (row-list) or
schema v2 (columnar struct-of-arrays) — layout and negotiation are
documented in docs/developer_guide/wire-schema-v2.md.

The client is best-effort and NEVER raises into training code: lazy
connect, drop-on-failure, bounded reconnect backoff
(reference contract: tcp_transport.py:182-268).
"""

from __future__ import annotations

import os
import random
import selectors
import socket
import struct
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from traceml_tpu.transport import compression
from traceml_tpu.utils import msgpack_codec
from traceml_tpu.utils.error_log import get_error_log

# fault-injection harness (no-op unless TRACEML_FAULT_PLAN is set; the
# module is stdlib-only and its fire() is one None check when inactive)
try:
    from traceml_tpu.dev import chaos as _chaos
except Exception:  # pragma: no cover
    _chaos = None

_LEN = struct.Struct(">I")
MAX_FRAME_BYTES = 256 * 1024 * 1024  # sanity bound against corrupt lengths

# optional C fast path (traceml_tpu/native/framing.c); None → pure Python
try:
    from traceml_tpu.native import get_framing

    _native = get_framing()
except Exception:  # pragma: no cover
    _native = None


class _ClientBuffer:
    """Incremental frame decoder with O(total bytes) drain (C fast path
    when the native extension built; identical framing either way)."""

    __slots__ = ("buf", "offset")

    def __init__(self) -> None:
        self.buf = bytearray()
        self.offset = 0  # consumed prefix

    def feed(self, data: bytes) -> List[bytes]:
        self.buf.extend(data)
        if _native is not None:
            # Pass the bytearray itself (y* accepts any buffer object) —
            # bytes(self.buf) would copy the whole rolling buffer per recv,
            # degrading a large multi-recv frame to O(buffered bytes/recv).
            frames, consumed = _native.drain_frames(
                self.buf, self.offset, MAX_FRAME_BYTES
            )
            self.offset = consumed
        else:
            frames = []
            while True:
                avail = len(self.buf) - self.offset
                if avail < _LEN.size:
                    break
                (n,) = _LEN.unpack_from(self.buf, self.offset)
                if n > MAX_FRAME_BYTES:
                    raise ValueError(f"frame length {n} exceeds bound")
                if avail < _LEN.size + n:
                    break
                start = self.offset + _LEN.size
                frames.append(bytes(self.buf[start : start + n]))
                self.offset = start + n
        # Compact once consumed prefix dominates — amortized O(1) per byte.
        if self.offset > 65536 and self.offset * 2 > len(self.buf):
            del self.buf[: self.offset]
            self.offset = 0
        return frames


def encode_frame(payload: Any) -> bytes:
    body = msgpack_codec.encode(payload)
    if _native is not None:
        return _native.pack_frames([body])
    return _LEN.pack(len(body)) + body


class TCPServer:
    """Aggregator-side ingest server.

    Raw frames are appended to an internal thread-safe queue; the
    aggregator loop blocks on :meth:`wait_for_data`, pulls frames with
    :meth:`drain`, and decodes them on its own thread via
    :meth:`decode_frames` (reference: tcp_transport.py:119-178).  Callers
    that don't care about the split can use :meth:`drain_decoded`.
    """

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        uds_path: Optional[str] = None,
    ) -> None:
        self._host = host
        self._requested_port = port
        self._sock: Optional[socket.socket] = None
        # optional extra AF_UNIX listener on the same selector (the uds
        # transport tier, docs/developer_guide/native-transport.md);
        # peers accepted there are tagged "uds:<n>"
        self._uds_path = uds_path
        self._uds_sock: Optional[socket.socket] = None
        self._uds_accepts = 0
        self._selector: Optional[selectors.DefaultSelector] = None
        self._thread: Optional[threading.Thread] = None
        self._running = threading.Event()
        self._wake_r, self._wake_w = socket.socketpair()
        self._lock = threading.Lock()
        # (peer, frame) tuples: the peer tag ("ip:port" at accept) lets
        # the consumer attribute corrupt frames to the client that sent
        # them instead of one server-wide counter
        self._pending: List[Tuple[str, bytes]] = []
        # monotonic arrival time of each pending frame, in lockstep
        # (the aggregator's freshness tracker starts its clock here)
        self._arrivals: List[float] = []
        self._data_event = threading.Event()
        self._clients: Dict[int, _ClientBuffer] = {}
        self._peers: Dict[int, str] = {}
        # shm ring registry polled on the serve tick (attach_ring_registry);
        # written before start() or from the serve thread only
        self._rings = None
        self._stopped = False
        self.port: Optional[int] = None
        self.frames_received = 0
        self.decode_errors = 0
        # frames by arrival path ("tcp" | "uds" | "shm"): the transport
        # observability strip in ingest_stats.json reads this
        self.frames_by_transport: Dict[str, int] = {}
        # compressed-carrier accounting (decode-side of the zstd tier)
        self.compressed_envelopes = 0
        self.compressed_bytes_in = 0
        self.decompressed_bytes = 0
        self.decompress_errors = 0
        # per-peer count of frames that arrived but could not be decoded
        # (body corruption) or desynced the stream (length corruption);
        # the connection survives body corruption — only a framing
        # desync still evicts that one client
        self.corrupt_frame_drops: Dict[str, int] = {}
        # deepest the undrained-frame buffer ever got: a proxy for how
        # far the consumer fell behind the selector thread
        self.pending_hwm = 0

    def attach_ring_registry(self, registry) -> None:
        """Attach a :class:`~traceml_tpu.transport.shm_ring.ShmRingRegistry`
        the serve loop polls each tick (call before :meth:`start`)."""
        self._rings = registry

    # -- lifecycle -----------------------------------------------------
    def start(self) -> None:
        if self._stopped:
            raise RuntimeError(
                "TCPServer is single-use: construct a new instance after stop()"
            )
        if self._thread is not None:
            return
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        sock.bind((self._host, self._requested_port))
        sock.listen(128)
        sock.setblocking(False)
        self._sock = sock
        self.port = sock.getsockname()[1]
        self._selector = selectors.DefaultSelector()
        self._selector.register(sock, selectors.EVENT_READ, ("accept", None))
        self._selector.register(self._wake_r, selectors.EVENT_READ, ("wake", None))
        if self._uds_path:
            try:
                uds = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                try:
                    os.unlink(self._uds_path)
                except OSError:
                    pass
                uds.bind(self._uds_path)
                uds.listen(128)
                uds.setblocking(False)
                self._uds_sock = uds
                self._selector.register(
                    uds, selectors.EVENT_READ, ("accept_uds", None)
                )
            except OSError as exc:
                # the TCP listener is the golden path; a UDS bind failure
                # (path too long, stale dir perms) degrades, not aborts
                get_error_log().warning("uds listener bind failed", exc)
                self._uds_sock = None
        self._running.set()
        self._thread = threading.Thread(
            target=self._serve, name="traceml-tcp-server", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        """Stop and release every fd.  A stopped server is single-use."""
        if self._thread is None:
            return
        self._stopped = True
        self._running.clear()
        try:
            self._wake_w.send(b"x")
        except OSError:
            pass
        self._thread.join(timeout=5)
        self._thread = None
        try:
            if self._selector:
                for key in list(self._selector.get_map().values()):
                    try:
                        self._selector.unregister(key.fileobj)
                        if key.fileobj not in (self._sock, self._wake_r):
                            key.fileobj.close()
                    except Exception:
                        pass
                self._selector.close()
        except Exception:
            pass
        self._clients.clear()
        self._peers.clear()
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None
        if self._uds_sock is not None:
            try:
                self._uds_sock.close()
            except OSError:
                pass
            self._uds_sock = None
            try:
                os.unlink(self._uds_path)
            except (OSError, TypeError):
                pass
        if self._rings is not None:
            try:
                self._rings.close()
            except Exception:
                pass
        for s in (self._wake_r, self._wake_w):
            try:
                s.close()
            except OSError:
                pass

    # -- consumer API --------------------------------------------------
    def wait_for_data(self, timeout: float) -> bool:
        fired = self._data_event.wait(timeout)
        if fired:
            self._data_event.clear()
        return fired

    def drain(self, max_frames: Optional[int] = None) -> List[bytes]:
        """Pull raw frames accumulated by the selector thread.

        With ``max_frames`` set, hands over at most that many frames and
        leaves the rest pending (the data event stays observable via
        :meth:`pending_frames`), so one drain call can't hold the caller
        hostage decoding an unbounded backlog.
        """
        return [frame for _peer, frame in self.drain_timed(max_frames)[0]]

    def drain_timed(
        self, max_frames: Optional[int] = None
    ) -> Tuple[List[Tuple[str, bytes]], List[float]]:
        """:meth:`drain`, keeping each frame's peer tag ("ip:port") so
        the consumer can attribute decode failures per client, and its
        arrival time (``time.monotonic()`` when the serve thread queued
        it)."""
        with self._lock:
            if max_frames is None or len(self._pending) <= max_frames:
                out, times = self._pending, self._arrivals
                self._pending, self._arrivals = [], []
            else:
                out = self._pending[:max_frames]
                times = self._arrivals[:max_frames]
                del self._pending[:max_frames]
                del self._arrivals[:max_frames]
        return out, times

    def pending_frames(self) -> int:
        """Frames buffered by the selector thread, awaiting drain()."""
        with self._lock:
            return len(self._pending)

    def decode_frames(self, frames: List[bytes]) -> List[Any]:
        """Decode raw frames into a flat payload list on the CALLER's
        thread (batch frames are flattened); bumps ``decode_errors``."""
        payloads, errors = msgpack_codec.decode_batch(frames)
        if errors:
            self.decode_errors += errors
            get_error_log().warning(
                f"dropped {errors} undecodable frame(s) during drain"
            )
        return self._unwrap_compressed(payloads, "unknown")

    def decode_tagged(self, tagged: List[Tuple[str, bytes]]) -> List[Any]:
        """Per-frame decode of :meth:`drain_timed`'s frames.  A corrupt
        frame is skipped (its whole batch of envelopes is lost — msgpack
        cannot partially decode) and counted against the peer that sent
        it in ``corrupt_frame_drops``; the connection stays up."""
        payloads: List[Any] = []
        for peer, frame in tagged:
            try:
                decoded = msgpack_codec.decode(frame)
            except msgpack_codec.CodecError:
                self.decode_errors += 1
                self._count_corrupt(peer)
                continue
            if isinstance(decoded, list):
                payloads.extend(self._unwrap_compressed(decoded, peer))
            else:
                payloads.extend(self._unwrap_compressed([decoded], peer))
        return payloads

    def _unwrap_compressed(self, payloads: List[Any], peer: str) -> List[Any]:
        """Restore compressed carrier envelopes in place (consumer
        thread).  Downstream of this point the pipeline sees payloads
        byte-identical to the uncompressed arm; a corrupt carrier is
        dropped like any other undecodable body, attributed to its
        peer."""
        out: List[Any] = []
        for payload in payloads:
            if not compression.is_compressed_payload(payload):
                out.append(payload)
                continue
            z_len = len(payload.get("z") or b"")
            try:
                inner = compression.unwrap_payload(payload)
            except compression.CompressionError:
                self.decompress_errors += 1
                self.decode_errors += 1
                self._count_corrupt(peer)
                continue
            self.compressed_envelopes += 1
            self.compressed_bytes_in += z_len
            self.decompressed_bytes += payload.get("n") or 0
            out.append(inner)
        return out

    def _count_corrupt(self, peer: str) -> None:
        # called from the consumer thread; _read (selector thread) also
        # mutates this dict, so both sides take the lock
        with self._lock:
            n = self.corrupt_frame_drops.get(peer, 0) + 1
            self.corrupt_frame_drops[peer] = n
        get_error_log().warning(
            f"undecodable frame from {peer} skipped "
            f"({n} corrupt frame(s) from this client so far)"
        )

    def drain_decoded(self) -> List[Any]:
        """Convenience: :meth:`drain` + :meth:`decode_frames`."""
        return self.decode_frames(self.drain())

    # -- server thread -------------------------------------------------
    def _serve(self) -> None:
        assert self._selector is not None and self._sock is not None
        # with a ring registry attached the select timeout drops so the
        # ring poll below stays sub-tick without any futex/eventfd
        # machinery — rings piggyback on the existing selector tick
        timeout = 0.05 if self._rings is not None else 0.5
        while self._running.is_set():
            try:
                events = self._selector.select(timeout=timeout)
            except OSError:
                break
            for key, _mask in events:
                kind, _ = key.data
                if kind == "wake":
                    try:
                        self._wake_r.recv(4096)
                    except OSError:
                        pass
                elif kind == "accept":
                    self._accept()
                elif kind == "accept_uds":
                    self._accept_uds()
                else:
                    self._read(key.fileobj)
            if self._rings is not None:
                self._poll_rings()

    def _poll_rings(self) -> None:
        """Drain every attached shm ring into the pending queue (serve
        thread only; frames are tagged "shm:<rank>")."""
        try:
            tagged = self._rings.poll()
        except Exception as exc:  # registry scan/attach trouble
            get_error_log().warning("shm ring poll failed", exc)
            return
        if not tagged:
            return
        now = time.monotonic()
        with self._lock:
            self.frames_received += len(tagged)
            self.frames_by_transport["shm"] = (
                self.frames_by_transport.get("shm", 0) + len(tagged)
            )
            self._pending.extend(tagged)
            self._arrivals.extend([now] * len(tagged))
            if len(self._pending) > self.pending_hwm:
                self.pending_hwm = len(self._pending)
        self._data_event.set()

    def _accept(self) -> None:
        assert self._sock is not None and self._selector is not None
        try:
            while True:
                conn, addr = self._sock.accept()
                conn.setblocking(False)
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                fileno = conn.fileno()
                self._clients[fileno] = _ClientBuffer()
                try:
                    self._peers[fileno] = f"{addr[0]}:{addr[1]}"
                except (TypeError, IndexError):
                    self._peers[fileno] = "unknown"
                self._selector.register(conn, selectors.EVENT_READ, ("client", None))
        except BlockingIOError:
            return
        except OSError:
            return

    def _accept_uds(self) -> None:
        assert self._uds_sock is not None and self._selector is not None
        try:
            while True:
                conn, _addr = self._uds_sock.accept()
                conn.setblocking(False)
                fileno = conn.fileno()
                self._clients[fileno] = _ClientBuffer()
                # AF_UNIX peers have no address; number them at accept
                self._uds_accepts += 1
                self._peers[fileno] = f"uds:{self._uds_accepts}"
                self._selector.register(
                    conn, selectors.EVENT_READ, ("client", None)
                )
        except (BlockingIOError, OSError):
            return

    def _read(self, conn: socket.socket) -> None:
        assert self._selector is not None
        fileno = conn.fileno()
        try:
            data = conn.recv(1 << 20)
        except (BlockingIOError, InterruptedError):
            return
        except OSError:
            data = b""
        if not data:
            try:
                self._selector.unregister(conn)
            except Exception:
                pass
            self._clients.pop(fileno, None)
            self._peers.pop(fileno, None)
            try:
                conn.close()
            except OSError:
                pass
            return
        buf = self._clients.get(fileno)
        if buf is None:
            return
        peer = self._peers.get(fileno, "unknown")
        try:
            frames = buf.feed(data)
        except ValueError as exc:
            # a corrupt LENGTH field desyncs the stream — nothing after
            # it can be reframed, so this one client is evicted (and the
            # loss attributed to it); a corrupt BODY with intact framing
            # survives to decode_tagged, which skips just that frame
            get_error_log().warning(f"dropping client with bad frame: {exc}")
            with self._lock:
                self.corrupt_frame_drops[peer] = (
                    self.corrupt_frame_drops.get(peer, 0) + 1
                )
            try:
                self._selector.unregister(conn)
            except Exception:
                pass
            self._clients.pop(fileno, None)
            self._peers.pop(fileno, None)
            try:
                conn.close()
            except OSError:
                pass
            return
        if not frames:
            return
        # NO decode here: this is the selector thread, shared by every
        # client.  Frames are handed to the consumer as-is.
        kind = "uds" if peer.startswith("uds:") else "tcp"
        now = time.monotonic()
        with self._lock:
            self.frames_received += len(frames)
            self.frames_by_transport[kind] = (
                self.frames_by_transport.get(kind, 0) + len(frames)
            )
            for frame in frames:
                self._pending.append((peer, frame))
            self._arrivals.extend([now] * len(frames))
            if len(self._pending) > self.pending_hwm:
                self.pending_hwm = len(self._pending)
        self._data_event.set()


class TCPClient:
    """Best-effort sender: never raises, lazily connects, drops on failure.

    Reconnect policy: capped exponential backoff with full jitter.
    ``reconnect_backoff`` is the BASE delay (kwarg name kept for
    back-compat with callers tuning it); consecutive dial failures
    double the window up to ``backoff_cap``, and the actual wait is
    drawn uniformly from [window/2, window] so a thousand ranks losing
    one aggregator never re-dial in lockstep.  Any successful dial
    resets the window to zero (the first retry after a blip is
    immediate).
    """

    #: transport kind reported in producer stats / transport_hello
    kind = "tcp"

    def __init__(
        self,
        host: str,
        port: int,
        connect_timeout: float = 2.0,
        reconnect_backoff: float = 1.0,
        backoff_cap: float = 15.0,
    ) -> None:
        self._host = host
        self._port = port
        self._timeout = connect_timeout
        self._backoff_base = max(0.001, float(reconnect_backoff))
        self._backoff_cap = max(self._backoff_base, float(backoff_cap))
        self._backoff_cur = 0.0  # jittered wait before the next dial
        self._fail_streak = 0
        self._connected_once = False
        self.reconnects = 0  # successful dials after the first
        self._sock: Optional[socket.socket] = None
        self._last_fail = 0.0
        self._lock = threading.Lock()
        # Serializes dialers; held WITHOUT self._lock during the blocking
        # create_connection so close() / a concurrent sender on an
        # established socket never waits behind a stalled connect.
        self._connect_lock = threading.Lock()
        self._gen = 0  # bumped by close(); a dial that straddles it is discarded
        # reusable frame buffer: steady-state sends assemble the length
        # prefix + body into one persistent bytearray instead of
        # allocating a fresh frame per tick.  Guarded by its own lock
        # (ordering: _framebuf_lock → _lock) so frame assembly — cheap
        # concatenation of pre-encoded bodies — never waits behind a
        # stalled sendall from the socket lock's perspective alone.
        self._framebuf = bytearray()
        self._framebuf_lock = threading.Lock()
        self.batches_sent = 0
        self.batches_dropped = 0

    def _dial(self) -> socket.socket:
        """Open one connected socket (raises OSError on failure).  The
        transport-specific seam: :class:`UDSClient` overrides only this."""
        sock = socket.create_connection(
            (self._host, self._port), timeout=self._timeout
        )
        try:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError:
            pass
        return sock

    def _note_dial_failure_locked(self) -> None:
        self._last_fail = time.monotonic()
        self._fail_streak += 1
        window = min(
            self._backoff_cap,
            self._backoff_base * (2 ** (self._fail_streak - 1)),
        )
        self._backoff_cur = random.uniform(window / 2.0, window)

    def _ensure_connected(self) -> Optional[socket.socket]:
        with self._lock:
            if self._sock is not None:
                return self._sock
            if time.monotonic() - self._last_fail < self._backoff_cur:
                return None
            gen = self._gen
        with self._connect_lock:
            with self._lock:
                if self._sock is not None:
                    return self._sock
                if self._gen != gen:
                    return None
            try:
                sock = self._dial()
            except OSError:
                with self._lock:
                    self._note_dial_failure_locked()
                return None
            try:
                sock.settimeout(self._timeout)
            except OSError:
                pass
            with self._lock:
                if self._gen != gen:
                    # close() raced the dial; don't resurrect the socket
                    try:
                        sock.close()
                    except OSError:
                        pass
                    return None
                self._sock = sock
                self._fail_streak = 0
                self._backoff_cur = 0.0
                if self._connected_once:
                    self.reconnects += 1
                self._connected_once = True
                return sock

    def send_batch(self, payloads: List[Any]) -> bool:
        """Encode ``payloads`` as ONE frame, one sendall. True on success.

        Members may be :class:`msgpack_codec.EncodedPayload` — their
        pre-encoded bodies are spliced into the batch array with zero
        re-encode (the producer's single-encode contract; see
        docs/developer_guide/rank-producer-path.md) — or plain objects,
        encoded here.  Encoding happens before the socket lock is taken
        — a large batch being msgpack'd must not block a concurrent
        close() or sender.
        """
        if not payloads:
            return True
        try:
            body = msgpack_codec.encode_batch(payloads)
        except Exception:
            with self._lock:
                self.batches_dropped += 1
            return False
        return self.send_encoded_body(body)

    def send_encoded_body(self, body: bytes) -> bool:
        """Send an already-assembled wire body as one frame.  The replay
        path (transport/spool.py) splices spooled raw envelope bytes
        into a batch body itself and ships it through here — same
        framing, same counters, same failure semantics as send_batch."""
        fault = _chaos.fire("client.send") if _chaos is not None else None
        if fault is not None:
            if fault.action == "stall":
                time.sleep(float(fault.arg or 0.2))
                fault = None
            elif fault.action == "reset":
                with self._lock:
                    self._teardown_locked()
                    self.batches_dropped += 1
                return False
        if self._ensure_connected() is None:
            with self._lock:
                self.batches_dropped += 1
            return False
        with self._framebuf_lock:
            buf = self._framebuf
            del buf[:]
            buf += _LEN.pack(len(body))
            buf += body
            if fault is not None and fault.action == "corrupt":
                # flip one byte past the length prefix: framing stays
                # intact, the receiver's decode fails (per-client
                # corrupt_frame_drops path, connection survives)
                idx = 4 + (len(body) // 2)
                buf[idx] ^= 0xFF
            with self._lock:
                if self._sock is None:  # torn down between connect and send
                    self.batches_dropped += 1
                    return False
                try:
                    if fault is not None and fault.action == "truncate":
                        # ship a prefix then reset: receiver-side stream
                        # desync (bad length next), client evicted there
                        self._sock.sendall(bytes(buf[: max(5, len(buf) // 2)]))
                        self.batches_dropped += 1
                        self._teardown_locked()
                        return False
                    self._sock.sendall(buf)
                    self.batches_sent += 1
                    return True
                except Exception:
                    self.batches_dropped += 1
                    self._teardown_locked()
                    return False

    def _teardown_locked(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None
            self._last_fail = time.monotonic()

    def close(self) -> None:
        """Drop the current socket (a later send_batch may redial)."""
        with self._lock:
            self._gen += 1
            self._teardown_locked()


class UDSClient(TCPClient):
    """Unix-domain-socket variant of the best-effort sender.

    Same framing, batching, backoff, chaos point (``client.send``), and
    durable-sender integration as TCP — only the dial differs, so the
    whole send path (including fault injection and replay splicing)
    is exercised identically on both stream transports.
    """

    kind = "uds"

    def __init__(
        self,
        path: str,
        connect_timeout: float = 2.0,
        reconnect_backoff: float = 1.0,
        backoff_cap: float = 15.0,
    ) -> None:
        super().__init__(
            host="",
            port=0,
            connect_timeout=connect_timeout,
            reconnect_backoff=reconnect_backoff,
            backoff_cap=backoff_cap,
        )
        self._path = str(path)

    def _dial(self) -> socket.socket:
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            sock.settimeout(self._timeout)
            sock.connect(self._path)
        except OSError:
            try:
                sock.close()
            except OSError:
                pass
            raise
        return sock
