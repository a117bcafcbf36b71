"""The publisher's collect + encode + flush time, summed over samplers,
over the window, per second of window."""


def read(rec):
    w = rec["window"]
    return rec["sampler_us"] / 1e3 / (w["stop"] - w["start"])
