"""Harness clock from the aggregator's spawn to its ready file."""


def read(rec):
    return rec["aggregator_ready_s"]
