"""Device milliseconds per profiled step of the operations under the
routed expert layer's ``moe_route`` (router, top-k, sort and gather)
and ``moe_combine`` (weighted sum back per token) scopes, forward,
recomputation and backward (``trace_program``'s ``scope_s``, per
chip)."""


def read(rec):
    t = rec.get("trace")
    scopes = t and t["program"]["scope_s"]
    parts = [scopes[k] for k in ("moe_route", "moe_combine") if scopes and k in scopes]
    return None if not parts else 1e3 * sum(s["fwd"] + s["bwd"] for s in parts) / t["steps"]
