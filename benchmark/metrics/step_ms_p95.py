"""95th percentile of every window step's wall time, from before
``trace_step`` opens to the loss on the host."""

from benchmark.viewlag import p95


def read(rec):
    return 1e3 * p95(rec["window"]["step_s"])
