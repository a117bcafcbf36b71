"""Median over the window's polls of the poller's own timing of one
``GET /api/live``, request to parsed body."""

from statistics import median


def read(rec):
    w = rec["window"]
    ms = [1e3 * (p[1] - p[0]) for p in rec["polls"] if w["start"] <= p[0] <= w["stop"]]
    return median(ms) if ms else None
