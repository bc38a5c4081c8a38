"""The chip's idle time under ``serve.sample``: the second small
executable (argmax) and the blocking fetch of its result; per cent of
the traced window."""
import program_spans

UNDER = ("serve.sample",)


def read(trace, counters, record):
    return program_spans.idle_share(trace, UNDER)
