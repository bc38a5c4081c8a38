"""The chip's idle time under ``serve.settle``: the emit loop and the
ITL and TTFT observations; per cent of the traced window."""
import program_spans

UNDER = ("serve.settle",)


def read(trace, counters, record):
    return program_spans.idle_share(trace, UNDER)
