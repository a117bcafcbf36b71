"""What the program's own scopes and spans say in a profiler trace
(``.xplane.pb``): device time by train-step phase, and the device's idle
time set against the tracer's ``traceml/`` host spans.

Events are read with ``ProfileData``, as ``trace_reduce`` reads them.
``ProfileData`` does not carry an operation's name stack, so a small
protobuf wire reader takes it from each TPU plane's event metadata
(``XSpace`` → ``XPlane`` → ``event_metadata`` → the ``tf_op`` stat).
An operation's phase comes from that name stack, which
``make_train_step``'s scopes write:
``…/jvp(forward)/…`` is ``forward``, ``…/transpose(jvp(forward))/…``
is ``backward``, ``…/optimizer/…`` is ``optimizer``, anything else
``other``.

The window, the operations and the gaps are ``trace_reduce``'s; a gap
goes to the ``traceml/`` span that overlaps it most, else to the
harness span that does (``trace_reduce.attribute``), else ``other``.
"""

from __future__ import annotations

from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterator, List, Tuple

from benchmark import trace_reduce as tr

PREFIX = "traceml/"
PHASES = ("forward", "backward", "optimizer", "other")
TOP_N = 10

_Span = Tuple[str, float, float]


# -- the wire reader ----------------------------------------------------------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int) -> Iterator[Tuple[int, object]]:
    """(field number, value): an int for varints, a (lo, hi) slice for
    length-delimited fields, None for fixed-width ones."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield field, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, (i, i + n)
            i += n
        elif wire in (1, 5):
            yield field, None
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _str(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _entry(buf: bytes, span) -> Tuple[object, object]:
    """A map entry: (key, value)."""
    key = value = None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def name_stacks(path: Path) -> Dict[str, Dict[str, str]]:
    """``{device plane: {operation's event name: its tf_op name stack}}``,
    from each TPU plane's event metadata; lines and events are skipped
    (``ProfileData`` reads those)."""
    buf = Path(path).read_bytes()
    out: Dict[str, Dict[str, str]] = {}
    for field, pspan in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(buf, *pspan):
            if f == 2:
                name = _str(buf, v)
            elif f == 4:
                metas.append(v)
            elif f == 5:
                sid, sspan = _entry(buf, v)
                stat_names[sid] = next(
                    (_str(buf, sv) for sf, sv in _fields(buf, *sspan) if sf == 2), ""
                )
        if not name.startswith(tr.DEVICE_PREFIX):
            continue
        tf_op = next((k for k, n in stat_names.items() if n == "tf_op"), None)
        stacks = out.setdefault(name, {})
        for m in metas:
            _mid, mspan = _entry(buf, m)
            ename, stack = "", None
            for f, v in _fields(buf, *mspan):
                if f == 2:
                    ename = _str(buf, v)
                elif f == 5:  # an XStat: metadata_id, then its value
                    sid = sval = None
                    for sf, sv in _fields(buf, *v):
                        if sf == 1:
                            sid = sv
                        elif sf == 5:
                            sval = _str(buf, sv)
                        elif sf == 7:
                            sval = stat_names.get(sv)
                    if sid == tf_op:
                        stack = sval
            stacks[ename] = stack
    return out


# -- the reduction ------------------------------------------------------------


def phase_of(name_stack: str) -> str:
    """The train-step phase of an operation from its name stack."""
    for part in (name_stack or "").rstrip(":").split("/"):
        core = part.replace("transpose(", "").replace("jvp(", "").rstrip(")")
        if core == "forward":
            return "backward" if part.startswith("transpose(") else "forward"
        if core == "optimizer":
            return "optimizer"
    return "other"


def load(path: Path) -> Dict:
    """``{"devices": {plane: [(op, start_ns, end_ns, phase)]}, "host":
    [(name, start_ns, end_ns)], "args": {span name: [stats, ...]}}``:
    host events are the window, the harness spans and every
    ``traceml/`` span, on any thread."""
    from jax.profiler import ProfileData

    stacks = name_stacks(path)
    devices: Dict[str, List] = {}
    host: List[_Span] = []
    args: Dict[str, List[Dict]] = defaultdict(list)
    wanted = set(tr.HOST_SPANS) | {tr.WINDOW}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            stack = stacks.get(plane.name, {})
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    ops.extend(
                        (tr.op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns,
                         phase_of(stack.get(ev.name)))
                        for ev in line.events
                    )
            continue
        for line in plane.lines:
            for ev in line.events:
                n = ev.name
                if n.startswith(PREFIX) or n in wanted:
                    host.append((n, ev.start_ns, ev.start_ns + ev.duration_ns))
                    if n.startswith(PREFIX):
                        args[n].append(dict(ev.stats))
    return {"devices": devices, "host": host, "args": dict(args)}


def _attribute(gaps: List[Tuple[float, float]], spans: List[_Span]) -> List[str]:
    """``trace_reduce.attribute`` for every gap of one time-sorted list,
    in one sweep: each gap's span that overlaps it most, else
    ``other``."""
    spans = sorted(spans, key=lambda s: s[1])
    out, active, j = [], [], 0
    for g in gaps:
        while j < len(spans) and spans[j][1] < g[1]:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s[2] > g[0]]
        best, name = 0.0, "other"
        for n, a, b in active:
            ov = tr._overlap(g, (a, b))
            if ov > best:
                best, name = ov, n
        out.append(name)
    return out


def reduce(events: Dict) -> Dict:
    """Per chip, averaged over chips, inside the window: seconds of
    device operations by phase (``phase_s``) and in all (``ops_s``), the
    heaviest ``other`` operations, idle seconds and the share of them
    that a ``traceml/`` span overlaps, and the idle time by the span
    each gap goes to.  Host side: each ``traceml/`` span's count and
    seconds inside the window (``spans``)."""
    windows = [(a, b) for n, a, b in events["host"] if n == tr.WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {tr.WINDOW!r} span, found {len(windows)}")
    lo, hi = windows[0]
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    ours = [s for s in events["host"] if s[0].startswith(PREFIX)]
    harness = [s for s in events["host"] if s[0] in tr.HOST_SPANS]
    ours_union = tr.union(((a, b) for _, a, b in ours), lo, hi)
    phase_ns: Dict[str, float] = {p: 0.0 for p in PHASES}
    other_ns: Dict[str, float] = defaultdict(float)
    gap_ns: Dict[str, float] = defaultdict(float)
    idle_ns = covered_ns = 0.0
    for ops in devices.values():
        for name, a, b, phase in ops:
            ns = tr._overlap((a, b), (lo, hi))
            phase_ns[phase] += ns
            if phase == "other":
                other_ns[name] += ns
        idle = tr.gaps(tr.union(((a, b) for _, a, b, _ in ops), lo, hi), lo, hi)
        idle_ns += sum(b - a for a, b in idle)
        covered_ns += sum(tr._overlap(u, g) for u, g in _pairs(ours_union, idle))
        by_ours, by_harness = _attribute(idle, ours), _attribute(idle, harness)
        for g, mine, theirs in zip(idle, by_ours, by_harness):
            gap_ns[mine if mine != "other" else theirs] += g[1] - g[0]
    n = len(devices)
    span_ns: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for name, a, b in ours:
        ns = tr._overlap((a, b), (lo, hi))
        if ns > 0:
            span_ns[name][0] += 1
            span_ns[name][1] += ns

    def top(d):
        return [[k, v / n / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP_N]]

    return {
        "phase_s": {p: v / n / 1e9 for p, v in phase_ns.items()},
        "ops_s": sum(phase_ns.values()) / n / 1e9,
        "other_ops": top(other_ns),
        "idle_s": idle_ns / n / 1e9,
        "idle_overlap_pct": 100.0 * covered_ns / idle_ns if idle_ns else 0.0,
        "idle_gaps": top(gap_ns),
        "spans": {k: [c, ns / 1e9] for k, (c, ns) in sorted(span_ns.items())},
    }


def _pairs(xs: List[Tuple[float, float]], ys: List[Tuple[float, float]]):
    """The overlapping pairs of two sorted lists of disjoint intervals."""
    i = j = 0
    while i < len(xs) and j < len(ys):
        if xs[i][1] > ys[j][0] and ys[j][1] > xs[i][0]:
            yield xs[i], ys[j]
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
