"""Per-rank runtime agent
(reference: src/traceml_ai/runtime/runtime.py:40-258).

Owns the samplers, the TCP client, and a daemon tick thread at
``sampler_interval_sec``.  Each tick runs in a ``traceml/sampler_tick``
profiler span, each sampler's ``sample()`` in a ``traceml/sample`` span
inside it (utils/self_trace.py).  Lifecycle: start → tick loop → (max-steps
DRAINING) → stop: final drain + ``rank_finished`` control marker.
"""

from __future__ import annotations

import threading
from typing import List, Optional

from traceml_tpu.config import flags
from traceml_tpu.runtime.identity import RuntimeIdentity, resolve_runtime_identity
from traceml_tpu.runtime.sampler_registry import build_samplers
from traceml_tpu.runtime.sender import TelemetryPublisher
from traceml_tpu.runtime.settings import TraceMLSettings
from traceml_tpu.runtime.state import RecordingState
from traceml_tpu.runtime.stdout_capture import StreamCapture
from traceml_tpu.samplers.base_sampler import BaseSampler
from traceml_tpu.sdk.state import get_state
from traceml_tpu.telemetry.control import build_mesh_topology, build_rank_finished
from traceml_tpu.transport.select import create_transport_client
from traceml_tpu.transport.tcp_transport import TCPClient
from traceml_tpu.utils.error_log import get_error_log
from traceml_tpu.utils.self_trace import span


class TraceMLRuntime:
    def __init__(
        self,
        settings: TraceMLSettings,
        identity: Optional[RuntimeIdentity] = None,
    ) -> None:
        self.settings = settings
        self.identity = identity or resolve_runtime_identity()
        self.recording = RecordingState(settings.trace_max_steps)
        self.capture: Optional[StreamCapture] = None
        if settings.mode in ("cli", "dashboard"):
            self.capture = StreamCapture(capture_stderr=settings.capture_stderr)
        self.samplers: List[BaseSampler] = []
        self.client: Optional[TCPClient] = None
        # transport-tier selection result ({"kind", "compression", ...});
        # the publisher announces it via a transport_hello control message
        self.transport_info: dict = {}
        self.publisher: Optional[TelemetryPublisher] = None
        self._thread: Optional[threading.Thread] = None
        self._profile_service = None
        self._stop_evt = threading.Event()
        self._started = False
        self._finished_sent = False
        self._mesh_sent = False
        self._paused = threading.Event()
        self._tick_lock = threading.Lock()  # pause() waits on in-flight ticks
        self._lock = threading.Lock()

    # -- lifecycle -----------------------------------------------------
    def start(self) -> None:
        with self._lock:
            if self._started:
                return
            self._started = True
        try:
            get_error_log().set_path(
                self.settings.rank_dir(self.identity.global_rank) / "error.log"
            )
        except Exception:
            pass
        if self.capture is not None:
            self.capture.start()
        self.samplers = build_samplers(self.settings, self.identity, self.capture)
        if self.settings.aggregator.port:
            # transport tier: shm ring on the same host, UDS when a path
            # is given, TCP as the golden fallback (TRACEML_TRANSPORT
            # overrides; docs/developer_guide/native-transport.md)
            self.client, self.transport_info = create_transport_client(
                self.settings, self.identity.global_rank
            )
        sender_identity = self.identity.to_sender_identity(self.settings.session_id)
        heartbeat_s = flags.HEARTBEAT_INTERVAL_SEC.get_float(3.0)
        self.publisher = TelemetryPublisher(
            self.samplers,
            self.client,
            sender_identity,
            # durable replay spool under the rank dir: failed sends are
            # retained on disk and replayed on reconnect (seq-deduped
            # aggregator-side; docs/developer_guide/fault-tolerance.md)
            spool_dir=(
                self.settings.rank_dir(self.identity.global_rank) / "spool"
                if self.client is not None
                else None
            ),
            heartbeat_interval_s=heartbeat_s,
            transport_info=self.transport_info,
        )
        # max-steps lifecycle: observe sdk step flushes
        get_state().on_step_flushed.append(self.recording.on_step_flushed)
        # on-demand XLA profiler capture (control-file protocol)
        try:
            from traceml_tpu.sdk.profile_capture import ProfileCaptureService

            self._profile_service = ProfileCaptureService(
                self.settings.session_dir,
                rank=self.identity.global_rank,
                world_size=self.identity.world_size,
            )
            get_state().on_step_flushed.append(
                self._profile_service.on_step_flushed
            )
        except Exception as exc:
            get_error_log().warning("profile capture unavailable", exc)
            self._profile_service = None
        self._stop_evt.clear()
        self._thread = threading.Thread(
            target=self._sampler_loop, name="traceml-runtime", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        with self._lock:
            if not self._started:
                return
            self._started = False
        self._stop_evt.set()
        if self._thread is not None:
            self._thread.join(timeout=max(5.0, self.settings.sampler_interval_sec * 3))
            self._thread = None
        try:
            self._final_drain()
        except Exception as exc:
            get_error_log().warning("final drain failed", exc)
        if self.capture is not None:
            self.capture.stop()
        for s in self.samplers:
            s.stop()
        if self.publisher is not None:
            try:
                self.publisher.close()
            except Exception:
                pass
        if self.client is not None:
            self.client.close()
        try:
            get_state().on_step_flushed.remove(self.recording.on_step_flushed)
        except ValueError:
            pass
        if getattr(self, "_profile_service", None) is not None:
            try:
                get_state().on_step_flushed.remove(
                    self._profile_service.on_step_flushed
                )
            except ValueError:
                pass
            try:
                # finish any in-flight capture: never leave the XLA
                # profiler tracing through teardown or the operator
                # CLI waiting on a response that will never come
                self._profile_service.close()
            except Exception as exc:
                get_error_log().warning("profile capture close failed", exc)

    def _take_rank_finished(self) -> Optional[list]:
        """The send-once rank_finished marker, or None if already sent.
        Lock-guarded: the tick thread and stop()'s final drain can race
        when the join times out."""
        with self._lock:
            if self._finished_sent:
                return None
            self._finished_sent = True
        return [
            build_rank_finished(
                self.identity.to_sender_identity(self.settings.session_id).to_meta()
            )
        ]

    def _take_mesh_topology(self) -> Optional[list]:
        """The send-once mesh_topology control message, or None while no
        mesh is discoverable (the user may build the mesh any number of
        steps into the run, so every tick retries until capture
        succeeds, then latches)."""
        with self._lock:
            if self._mesh_sent:
                return None
        try:
            from traceml_tpu.utils.topology import capture_local_topology

            topo = capture_local_topology(
                self.identity.global_rank, self.identity.world_size
            )
        except Exception as exc:
            get_error_log().warning("mesh topology capture failed", exc)
            with self._lock:
                self._mesh_sent = True  # broken capture: stop retrying
            return None
        if topo is None:
            return None
        with self._lock:
            if self._mesh_sent:
                return None
            self._mesh_sent = True
        return [
            build_mesh_topology(
                self.identity.to_sender_identity(self.settings.session_id).to_meta(),
                topo,
            )
        ]

    # -- pause (measurement quiescence) --------------------------------
    def pause(self) -> None:
        """Suspend tick work (sampling + publishing) without tearing the
        runtime down.  For measurement windows that must exclude the
        tracer's own background activity (bench.py quiesces the traced
        stack while timing the UNTRACED arm in-process on
        device-exclusive backends).  Blocks until any in-flight tick
        completes — the window starts truly quiet."""
        self._paused.set()
        with self._tick_lock:
            pass

    def resume(self) -> None:
        self._paused.clear()

    # -- tick loop -----------------------------------------------------
    def _tick(self) -> None:
        with span("traceml/sampler_tick"):
            self._tick_body()

    def _tick_body(self) -> None:
        try:
            from traceml_tpu.dev import chaos

            if chaos.active():
                chaos.fire("rank.tick")  # kill9 executes inside fire()
        except ImportError:  # pragma: no cover
            pass
        phase = self.recording.phase
        # RECORDING: everyone samples.  DRAINING: only drain samplers, via
        # their (possibly heavier) drain() path.  COMPLETE: nobody samples
        # — the rank goes quiet (--trace-max-steps contract).
        if phase == "RECORDING":
            for s in self.samplers:
                with span("traceml/sample", sampler=s.name):
                    s.sample()
        elif phase == "DRAINING":
            for s in self.samplers:
                if getattr(getattr(s, "_spec", None), "drain_on_recording_stop", False):
                    s.drain()
            self.recording.mark_drained()
        extra: Optional[list] = None
        mesh = self._take_mesh_topology()
        if mesh:
            extra = mesh
        if self.recording.phase == "COMPLETE":
            finished = self._take_rank_finished()
            if finished:
                extra = (extra or []) + finished
        if self.publisher is not None and (
            self.recording.phase != "COMPLETE" or extra
        ):
            self.publisher.publish(extra)

    def _sampler_loop(self) -> None:
        interval = max(0.05, self.settings.sampler_interval_sec)
        while not self._stop_evt.wait(interval):
            if self._paused.is_set():
                continue
            try:
                with self._tick_lock:
                    self._tick()
            except Exception as exc:  # belt+braces; samplers fail-open anyway
                get_error_log().warning("runtime tick failed", exc)

    def _final_drain(self) -> None:
        """Shutdown: drain every sampler, publish leftovers + rank_finished."""
        try:
            # force one last memory sample past the tracker's throttle:
            # a run shorter than the throttle window would otherwise end
            # with a single row, and growth (last − first) would read 0
            st = get_state()
            if st.mem_tracker is not None:
                st.mem_tracker.record(st.current_step, force=True)
        except Exception as exc:
            get_error_log().warning("final memory sample failed", exc)
        for s in self.samplers:
            s.drain()
        if self.publisher is not None:
            # final=True force-flushes every writer (even throttled ones)
            # so the disk backup holds the full run, and ships the last
            # producer_stats snapshot
            extra = (self._take_mesh_topology() or []) + (
                self._take_rank_finished() or []
            )
            self.publisher.publish(extra or None, final=True)


class NoOpRuntime:
    """Fail-open stand-in (reference: lifecycle.py:29): every method is a
    no-op so a broken runtime can never break training."""

    settings = None
    identity = None

    def start(self) -> None: ...

    def stop(self) -> None: ...

    def pause(self) -> None: ...

    def resume(self) -> None: ...
