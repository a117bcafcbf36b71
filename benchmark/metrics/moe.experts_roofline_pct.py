"""The held experts' grouped matmuls against their roofline, in %: the
least time the chip could take for them (the larger of their FLOPs over
the bf16 peak and their bytes over the HBM bandwidth, ``peaks.json``),
from the family's ``routed_expert_cost`` at each routed layer's mean
held (token, expert) pairs a step, over their device time a step
(``moe.experts_ms``). Both are of the steps the trace profiled: the
profile follows the window, and each step's ``expert_load`` counter
(``TraceState.counter_rings``) is read at the next step's enter, so the
ring's last ``steps - 1`` readings are the profiled steps but the
last."""

from benchmark import discovery


def read(rec):
    from traceml_tpu.sdk.state import get_state

    ring = getattr(get_state(), "counter_rings", {}).get("expert_load")
    peaks, model, t = rec.get("peaks"), rec["spec"]["model"], rec.get("trace")
    ms = discovery.reader("moe.experts_ms")(rec)
    if ring is None or peaks is None or not ms or "experts_held" not in model:
        return None
    n = t["steps"] - 1
    loads = [v for _, v in ring.samples(rec["window"]["stop"])][-n:] if n > 0 else []
    if not loads:
        return None
    lo, hi = model["experts_held"]
    cost = discovery.load_family(model["family"]).reference.routed_expert_cost
    seconds = 0.0
    for layer in range(len(loads[0])):
        pairs = sum(float(v[layer][lo:hi].sum()) for v in loads) / len(loads)
        flops, nbytes = cost(model, pairs)
        seconds += max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * seconds / (ms / 1e3)
