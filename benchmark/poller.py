"""The live-view poller: a process of its own, with no JAX, that GETs
``/api/live`` every ``--interval`` seconds on average as a dashboard tab
does (each wait drawn uniformly from 0.5 to 1.5 intervals, from
``--seed``, so polls fall at every phase of the 1 s sampler tick instead
of locking onto ten of them), and
writes one JSON line per poll to ``--out`` when it is told to stop
(SIGTERM): send time and receive time (monotonic seconds), HTTP status,
and the newest step the view shows (``step_time.coverage.last_step``).
It writes ``<out>.ready`` after the first answer that carries a
``step_time`` view, so the harness knows the dashboard is serving.
"""

from __future__ import annotations

import argparse
import json
import random
import signal
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path


def last_step(payload: dict):
    view = payload.get("step_time") or {}
    return (view.get("coverage") or {}).get("last_step")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--url", required=True)
    ap.add_argument("--interval", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    rng = random.Random(args.seed)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    out = Path(args.out)
    polls = []
    ready = False
    next_t = time.monotonic()
    while not stop.is_set():
        t_send = time.monotonic()
        status, step = 0, None
        try:
            with urllib.request.urlopen(args.url, timeout=10) as resp:
                status = resp.status
                payload = json.loads(resp.read())
            step = last_step(payload)
            if not ready and "step_time" in payload:
                ready = True
                Path(str(out) + ".ready").write_text("1")
        except (urllib.error.URLError, OSError, ValueError) as exc:
            status = -1
            print(f"[poller] {type(exc).__name__}: {exc}", flush=True)
        polls.append([t_send, time.monotonic(), status, step])
        next_t += args.interval * rng.uniform(0.5, 1.5)
        stop.wait(max(0.0, next_t - time.monotonic()))
    tmp = out.with_suffix(".tmp")
    tmp.write_text("\n".join(json.dumps(p) for p in polls) + "\n")
    tmp.replace(out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
