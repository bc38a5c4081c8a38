"""Median device time of one call of the decode executable: the
executable inside which the decode-attention kernel runs."""
import statistics

# How the device trace marks the Pallas kernel of
# ops/pallas/decode_attention.py: the program gives it no name (the
# compiler calls it ``f``, ``f.10`` ..., one a layer), but it is the only
# Mosaic custom call of the serving step (PERF.md, PR 26).
KERNEL = r"custom-call:tpu_custom_call"


def read(trace, counters, record):
    with_kernel, _ = trace.module_ms_by_kernel(KERNEL)
    calls = [ms for v in with_kernel.values() for ms in v]
    return statistics.median(calls) if calls else None
