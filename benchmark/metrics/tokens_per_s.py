"""Training tokens (batch x (seq - 1) per step) of every step completed
in the window, over the window's whole length to the last loss read."""


def read(rec):
    w = rec["window"]
    return len(w["step_s"]) * rec["tokens_per_step"] / (w["stop"] - w["start"])
