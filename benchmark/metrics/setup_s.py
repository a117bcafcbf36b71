"""Process start to the first timed step: imports, aggregator ready,
init on the device, compile (from the cache), the checked and warm-up
steps, the poller's first answer."""


def read(rec):
    return rec["setup_s"]
