"""Median time to first token over the requests that completed in the
window, as the engine says it of itself (the result's ``ttft_ms``: from
the batcher's enqueue to the first sampled token)."""
import harness


def read(trace, counters, record):
    ttft = record.get("ttft_ms")
    return harness.percentile(ttft, 50) if ttft else None
