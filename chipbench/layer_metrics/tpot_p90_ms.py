"""90th percentile, over the requests that completed in the window, of
the time per output token after the first: (client latency - the
result's ``ttft_ms``) / (tokens - 1). The engine hands back no per-token
times, so this is a request's mean gap, not an inter-token tail."""
import harness


def read(trace, counters, record):
    tpot = record.get("tpot_ms")
    return harness.percentile(tpot, 90) if tpot else None
