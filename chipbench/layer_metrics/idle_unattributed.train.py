"""The chip's idle time under no ``mxnet_tpu.`` span (the benchmark's own
``device_put`` and ``fetch``, the edges of the window); per cent of the
traced window."""
import program_spans

UNDER = ("unattributed",)


def read(trace, counters, record):
    return program_spans.idle_share(trace, UNDER)
