"""Median time a request of the window waited in the engine's admission
queue (``ServeMetrics``' queue samples)."""
import statistics


def read(trace, counters, record):
    q = counters.get("queue_ms_window")
    return statistics.median(q) if q else None
