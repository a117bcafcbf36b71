"""Mean over the window's steps of the tracer's own host time on the
training thread: microseconds inside ``trace_step``'s enter and exit and
the step wrapper outside its jitted call, as the program counts them
(``TraceState.host_ns_ring``: one sample per step, at its exit)."""


def read(rec):
    from traceml_tpu.sdk.state import get_state

    ring = getattr(get_state(), "host_ns_ring", None)
    if ring is None:
        return None
    w = rec["window"]
    ns = [v for _, v in ring.samples(w["start"], w["stop"])]
    return sum(ns) / len(ns) / 1e3 if ns else None
