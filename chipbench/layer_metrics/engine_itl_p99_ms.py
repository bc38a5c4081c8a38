"""The engine's own inter-token latency, 99th percentile over the decode
visits of the window (``ServeMetrics.itl_samples()``)."""
import harness


def read(trace, counters, record):
    itl = counters.get("itl_window")
    if not itl:
        return None
    return harness.percentile([ms for ms, _ in itl], 99)
