"""Model FLOPs of a step (the family's ``train_step_flops``) times
attached steps per second in the window, over chips times the bf16 peak
(``peaks.json``), in %."""


def read(rec):
    if rec.get("peaks") is None:
        return None
    w = rec["window"]
    steps_per_s = len(w["step_s"]) / (w["stop"] - w["start"])
    return 100.0 * rec["flops_per_step"] * steps_per_s / (rec["chips"] * rec["peaks"]["bf16_flops"])
