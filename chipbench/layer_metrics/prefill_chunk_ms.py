"""Median device time of one call of the prefill-chunk executable: the
engine's other step executable, the one of the decode executable's name
inside which the decode-attention kernel does not run."""
import re
import statistics

KERNEL = r"custom-call:tpu_custom_call"


def base(name):
    return re.sub(r"\(.*$", "", name)


def read(trace, counters, record):
    with_kernel, without = trace.module_ms_by_kernel(KERNEL)
    names = {base(n) for n in with_kernel}
    calls = [ms for n, v in without.items() if base(n) in names for ms in v]
    return statistics.median(calls) if calls else None
