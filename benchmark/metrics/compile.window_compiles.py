"""Compiles the program's compile tracker counted between the window's
start and its end (``TraceState.compile_ring``: one sample per compile,
at its end)."""


def read(rec):
    from traceml_tpu.sdk.state import get_state

    ring = getattr(get_state(), "compile_ring", None)
    if ring is None:
        return None
    w = rec["window"]
    return len(ring.samples(w["start"], w["stop"]))
