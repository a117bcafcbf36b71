"""Device milliseconds per profiled step of the operations under the
train step's ``forward`` scope in the backward pass, under
``transpose(…)`` (``trace_program``'s ``scope_s``, per chip)."""


def read(rec):
    t = rec.get("trace")
    s = t and t["program"]["scope_s"].get("forward")
    return None if not s else 1e3 * s["bwd"] / t["steps"]
