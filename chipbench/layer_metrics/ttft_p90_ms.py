"""90th percentile of time to first token over the requests that
completed in the window, as the engine says it of itself (the result's
``ttft_ms``). Only one slot prefills a chunk in a scheduler iteration, so
this tail is made of the requests whose prefill met another's."""
import harness


def read(trace, counters, record):
    ttft = record.get("ttft_ms")
    return harness.percentile(ttft, 90) if ttft else None
