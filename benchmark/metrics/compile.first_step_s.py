"""Harness clock around the first call of the attached step: compile
(from the cache after a cell's first run) plus one step."""


def read(rec):
    return rec["first_step_s"]
