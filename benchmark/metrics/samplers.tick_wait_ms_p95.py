"""95th percentile over the step rows emitted in the window of each
step's wait from its flush at ``trace_step`` exit to its row in the
step-time sampler: the sampler tick plus the hold until the step's
device markers resolve (``TraceState.emit_waits``)."""

from benchmark.viewlag import p95


def read(rec):
    from traceml_tpu.sdk.state import get_state

    ring = getattr(get_state(), "emit_waits", None)
    if ring is None:
        return None
    w = rec["window"]
    ms = [v for _, v in ring.samples(w["start"], w["stop"])]
    return p95(ms) if ms else None
