"""Per-rank runtime agent
(reference: src/traceml_ai/runtime/runtime.py:40-258).

Owns the samplers, the TCP client, and one daemon thread with two kinds
of work (docs/developer_guide/rank-producer-path.md):

* the **full tick**, on a fixed schedule of ``sampler_interval_sec``:
  every sampler, the mesh topology, ``rank_finished``, the heartbeat;
* the **step-row lane**, woken by each step flush: only the step-time
  sampler, then only its rows, at most once per :data:`LANE_GAP_S`.

Each tick runs in a ``traceml/sampler_tick`` profiler span, each
sampler's ``sample()`` in a ``traceml/sample`` span (utils/self_trace.py).
Lifecycle: start → loop → (max-steps DRAINING) → stop: final drain +
``rank_finished`` control marker.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, List, Optional

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

#: Least time between two step-row lane frames.  The live view refreshes
#: at most every 0.2 s (``renderers/serving.py`` ``min_poll_interval``)
#: and the aggregator commits every 0.2 s, so a finer rank cadence cannot
#: make the view fresher; it only adds frames.  Rows that resolve inside
#: the gap share the next frame.
LANE_GAP_S = 0.1

#: Longest the exit drain waits for the aggregator to take what failed
#: sends left in the spool: the client's backoff cap, so a rank that
#: exits waits no longer than a rank that kept training would have gone
#: between two dials.  Long enough for the launcher's restart of a dead
#: aggregator; a healthy link spends nothing here.
EXIT_REPLAY_WAIT_S = 15.0


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
        # step-row lane: set by every step flush (training thread), waited
        # on by the runtime thread; everything else of the lane lives on
        # the runtime thread
        self._step_flushed = threading.Event()
        self._step_sampler: Optional[BaseSampler] = None
        self._lane_sent_at = float("-inf")

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
        self._step_sampler = next(
            (s for s in self.samplers if s.name == "step_time"), None
        )
        self._step_flushed.clear()
        get_state().on_step_flushed.append(self._wake_lane)
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
        self._step_flushed.set()
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
        for cb in (self.recording.on_step_flushed, self._wake_lane):
            try:
                get_state().on_step_flushed.remove(cb)
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
        # rows held through the pause leave on the lane, not a tick later
        self._step_flushed.set()

    # -- step-row lane -------------------------------------------------
    def _wake_lane(self, step: int) -> None:
        """``TraceState.on_step_flushed`` callback (training thread): wake
        the runtime thread and nothing else.  A set event skips the lock
        inside ``Event.set``; the lane clears it before draining, so a
        step queued before this check is drained by that lane pass."""
        if not self._step_flushed.is_set():
            self._step_flushed.set()

    def _lane(self) -> None:
        """Emit the step rows that resolved since the last pass and ship
        them alone: ``StepTimeSampler.sample()`` (drain, resolve in FIFO
        order, emit), then the step-time unit only.  Runs in RECORDING
        only; never polls a marker itself."""
        sampler = self._step_sampler
        if sampler is None or self.recording.phase != "RECORDING":
            return
        with span("traceml/sample", sampler=sampler.name):
            sampler.sample()
        if self.publisher.publish_units((sampler.name,)):
            self._lane_sent_at = time.monotonic()

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
        """Full ticks on a fixed deadline schedule; between them, wait on
        step flushes and run the lane, coalesced to one frame per
        :data:`LANE_GAP_S`.  Lane wakes never move a tick's deadline."""
        interval = max(0.05, self.settings.sampler_interval_sec)
        clock = time.monotonic
        next_tick = clock() + interval
        while True:
            # sleep out the gap since the last lane frame first: a flush
            # inside it only sets the event, and wakes no thread
            hold = min(self._lane_sent_at + LANE_GAP_S, next_tick) - clock()
            if hold > 0 and self._stop_evt.wait(hold):
                return
            woke = self._step_flushed.wait(max(0.0, next_tick - clock()))
            if self._stop_evt.is_set():
                return
            # clear before sampling: a flush after this wakes the next pass
            self._step_flushed.clear()
            now = clock()
            if now >= next_tick:
                while next_tick <= now:
                    next_tick += interval
                self._run_paused_aware(self._tick)
            elif woke:
                self._run_paused_aware(self._lane)

    def _run_paused_aware(self, work: Callable[[], None]) -> None:
        """Run a tick or a lane pass under ``_tick_lock`` unless paused
        (checked under the lock, so nothing starts once ``pause()``
        returns)."""
        try:
            with self._tick_lock:
                if self._paused.is_set():
                    return
                work()
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
            if not self.publisher.drain_spool(EXIT_REPLAY_WAIT_S):
                get_error_log().warning("telemetry left in the replay spool at exit")


class NoOpRuntime:
    """Fail-open stand-in (reference: lifecycle.py:29): every method is a
    no-op so a broken runtime can never break training."""

    settings = None
    identity = None

    def start(self) -> None: ...

    def stop(self) -> None: ...

    def pause(self) -> None: ...

    def resume(self) -> None: ...
