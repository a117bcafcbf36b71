"""95th percentile over the window's polls of how long the oldest
completed step the live view does not show yet has been complete."""

from benchmark.viewlag import p95, poll_lags


def read(rec):
    w = rec["window"]
    polls = [(p[0], p[3]) for p in rec["polls"] if w["start"] <= p[0] <= w["stop"]]
    if not polls:
        return None
    return 1e3 * p95(poll_lags(rec["completions"], polls))
