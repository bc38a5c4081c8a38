"""1 - (union of the intervals in which an operation ran) / window, on
the chip that idles most, from the device trace."""


def read(trace, counters, record):
    share = trace.idle_share()
    return None if share is None else 100.0 * share
