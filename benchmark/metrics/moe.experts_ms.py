"""Device milliseconds per profiled step of the operations under the
routed expert layer's ``moe_experts`` scope: the held experts' grouped
matmuls (the megablox ``gmm`` / ``tgmm`` kernels on a TPU), their
SwiGLU and the masking of rows past the held pairs, forward,
recomputation and backward (``trace_program``'s ``scope_s``, per
chip)."""


def read(rec):
    t = rec.get("trace")
    s = t and t["program"]["scope_s"].get("moe_experts")
    return None if not s else 1e3 * (s["fwd"] + s["bwd"]) / t["steps"]
