"""From a profiler trace (``.xplane.pb``) to device busy time, idle share,
the heaviest device operations and what the host did in the idle gaps.

Device operations are the events of each TPU plane's ``XLA Ops`` line.
The window is the host span the harness names ``WINDOW``; the host spans
that explain gaps are the harness's own ``TraceAnnotation`` names.
Busy time is the union of operation intervals inside the window, per
device, averaged over devices; idle share is 1 minus busy over window.
A gap is an interval of the window in which a device runs no operation;
it goes to the host span that overlaps it most, else to ``other``.
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

WINDOW = "bench_window"
HOST_SPANS = ("trace_step_enter", "dispatch", "trace_step_exit", "wait_loss")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP_N = 10

Interval = Tuple[float, float]


def op_name(hlo: str) -> str:
    """``fusion f32[49152,2048]`` from the trace's HLO text
    ``%fusion.14 = (f32[49152,2048]{...}, ...) fusion(...)``: the
    instruction's name without its number, and its first result's type,
    so that the same operation of every layer adds up under one name."""
    name, _, rest = hlo.partition(" = ")
    m = re.match(r"\(?(\w+\[[\d,]*\])", rest)
    base = re.sub(r"\.\d+$", "", name.lstrip("%"))
    return base + (f" {m.group(1)}" if m else "")


def load(path: Path) -> Dict:
    """``{"devices": {plane: [(name, start_ns, end_ns)]}, "host":
    [(name, start_ns, end_ns)]}`` from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices: Dict[str, List] = {}
    host: List = []
    wanted = set(HOST_SPANS) | {WINDOW}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(
                        (op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events
                    )
        else:
            for line in plane.lines:
                host.extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events
                    if ev.name in wanted
                )
    return {"devices": devices, "host": host}


def union(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """Merged intervals, clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def attribute(gap: Interval, spans: List[Tuple[str, float, float]]) -> str:
    best, name = 0.0, "other"
    for n, a, b in spans:
        ov = _overlap(gap, (a, b))
        if ov > best:
            best, name = ov, n
    return name


def reduce(events: Dict) -> Dict:
    """Busy and window seconds, idle share, and the breakdown lists."""
    windows = [(a, b) for n, a, b in events["host"] if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found {len(windows)}")
    lo, hi = windows[0]
    spans = [s for s in events["host"] if s[0] != WINDOW]
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    busy_ns = 0.0
    op_ns: Dict[str, float] = defaultdict(float)
    gap_ns: Dict[str, float] = defaultdict(float)
    for ops in devices.values():
        merged = union(((a, b) for _, a, b in ops), lo, hi)
        busy_ns += sum(b - a for a, b in merged)
        for name, a, b in ops:
            op_ns[name] += _overlap((a, b), (lo, hi))
        for g in gaps(merged, lo, hi):
            gap_ns[attribute(g, spans)] += g[1] - g[0]
    n = len(devices)
    window_s = (hi - lo) / 1e9
    busy_s = busy_ns / n / 1e9

    def top(d):
        return [
            [k, v / n / 1e9]
            for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP_N]
        ]

    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "device_ops": top(op_ns),
        "idle_gaps": top(gap_ns),
    }


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {found}")
    return found[0]
