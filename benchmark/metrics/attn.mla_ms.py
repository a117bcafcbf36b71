"""Device milliseconds per profiled step of the operations under the
latent attention's ``mla`` scope, forward, recomputation and backward
(``trace_program``'s ``scope_s``, per chip)."""


def read(rec):
    t = rec.get("trace")
    s = t and t["program"]["scope_s"].get("mla")
    return None if not s else 1e3 * (s["fwd"] + s["bwd"]) / t["steps"]
