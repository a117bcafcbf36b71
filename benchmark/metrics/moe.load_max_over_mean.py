"""Over the window's steps and every routed layer, the mean of the
busiest expert's tokens over the mean tokens per expert, all experts
of the router (the program's ``expert_load`` counter, read to the host
as each step resolves: ``TraceState.counter_rings``)."""


def read(rec):
    from traceml_tpu.sdk.state import get_state

    ring = getattr(get_state(), "counter_rings", {}).get("expert_load")
    if ring is None:
        return None
    w = rec["window"]
    ratios = [
        float(row.max() / row.mean())
        for _, load in ring.samples(w["start"], w["stop"])
        for row in load
        if row.mean() > 0
    ]
    return sum(ratios) / len(ratios) if ratios else None
