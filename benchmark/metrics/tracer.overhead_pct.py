"""Mean attached step over mean bare step, minus one, in percent, over
all steps of the alternating chunks of a traced run."""

from statistics import fmean


def read(rec):
    o = rec.get("overhead")
    if not o:
        return None
    bare = fmean(o["bare_s"])
    return 100.0 * (fmean(o["attached_s"]) - bare) / bare
