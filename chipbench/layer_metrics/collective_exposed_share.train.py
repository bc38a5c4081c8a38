"""The share of the window in which a collective (the gradient
all-reduce over ICI) ran on a chip while no other operation did, on the
chip where that is longest. Nothing to read on one chip."""


def read(trace, counters, record):
    exposed = trace.exposed_collective_s()
    if exposed is None or trace.window_s <= 0:
        return None
    return 100.0 * exposed / trace.window_s
