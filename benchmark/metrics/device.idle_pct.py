"""1 minus the union of device-operation intervals over the profiled
sub-window, averaged over chips, in percent (``trace_reduce.py``)."""


def read(rec):
    t = rec.get("trace")
    return None if t is None else t["idle_pct"]
