"""Mean share of the decode lanes that held a live request, over the
decode visits of the window (``ServeMetrics.itl_samples()``: one (ms,
live) pair a visit)."""


def read(trace, counters, record):
    itl = counters.get("itl_window")
    if not itl or not counters.get("num_slots"):
        return None
    live = sum(l for _, l in itl) / len(itl)
    return 100.0 * live / counters["num_slots"]
