"""How fresh the live view is, stage by stage, inside the aggregator.

Each ``step_time`` envelope of rank 0 is followed as one request,
identified by its ``seq``, on this process's ``time.monotonic()`` clock
(no clock shared with the rank is needed):

* **ingest** — from the frame's arrival at the transport to the commit
  of the writer transaction that holds the envelope's rows;
* **serve** — from that commit to the first ``SessionPublisher.poll``
  that publishes a ``step_time`` fragment showing the envelope's newest
  step.

Both stages keep bounded rings of ``(t, ms)`` and are written as the
``freshness`` block of ``ingest_stats.json``; the dashboard's meta strip
shows their summaries.  The rank's share (flush to row) is
``TraceState.emit_waits``; see docs/developer_guide/self-tracing.md.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, Optional, Tuple

from traceml_tpu.utils.self_trace import TimedRing

#: the rank whose envelopes are followed
RANK = 0
STAGES: Tuple[str, ...] = ("ingest", "serve")
_RING = 4096
_PENDING_MAX = 1024


def newest_step(env: Any) -> Optional[int]:
    """The largest ``step`` among a step_time envelope's rows."""
    view = env.column_view("step_time")
    if view is None:
        return None
    steps = [s for s in view.ints("step") if s is not None]
    return max(steps) if steps else None


class FreshnessTracker:
    """Thread-safe: the aggregator loop reports arrivals, the writer
    thread commits, HTTP handler threads publishes."""

    def __init__(self, maxlen: int = _RING) -> None:
        self.rings: Dict[str, TimedRing] = {s: TimedRing(maxlen) for s in STAGES}
        self._lock = threading.Lock()
        # seq → (arrival, newest step), until the writer commits it
        self._arrived: Dict[int, Tuple[float, int]] = {}
        # (newest step, commit time), until a publish shows the step
        self._committed: "deque[Tuple[int, float]]" = deque(maxlen=_PENDING_MAX)

    @staticmethod
    def follows(env: Any) -> bool:
        return env.sampler == "step_time" and env.global_rank == RANK

    def arrived(self, env: Any, t: float) -> None:
        """A followed envelope was decoded from a frame that reached the
        transport at ``t``."""
        seq, step = env.seq, newest_step(env)
        if seq is None or step is None:
            return
        with self._lock:
            if len(self._arrived) >= _PENDING_MAX:
                self._arrived.pop(next(iter(self._arrived)))
            self._arrived[seq] = (t, step)

    def committed(self, batch: Any) -> None:
        """Writer hook: ``batch`` was just committed."""
        now = time.monotonic()
        ring = self.rings["ingest"]
        with self._lock:
            for env in batch:
                if not self.follows(env) or env.seq is None:
                    continue
                hit = self._arrived.pop(env.seq, None)
                if hit is None:
                    continue
                t, step = hit
                ring.add(now, (now - t) * 1e3)
                self._committed.append((step, now))

    def published(self, last_step: Optional[int]) -> None:
        """Publisher hook: a step_time fragment showing steps up to
        ``last_step`` was just published."""
        if last_step is None:
            return
        now = time.monotonic()
        ring = self.rings["serve"]
        with self._lock:
            while self._committed and self._committed[0][0] <= last_step:
                _step, t = self._committed.popleft()
                ring.add(now, (now - t) * 1e3)

    def block(self) -> Dict[str, Any]:
        """The ``freshness`` block of ``ingest_stats.json``: per stage,
        its summary and its samples as ``[t, ms]`` pairs."""
        out: Dict[str, Any] = {"rank": RANK}
        for stage, ring in self.rings.items():
            out[stage] = dict(
                ring.summary(), samples=[[t, ms] for t, ms in ring.samples()]
            )
        return out


def summaries(block: Dict[str, Any]) -> Dict[str, Any]:
    """``block`` without its samples (the dashboard's meta strip)."""
    return {
        k: {s: v for s, v in stage.items() if s != "samples"}
        if isinstance(stage, dict) else stage
        for k, stage in block.items()
    }
