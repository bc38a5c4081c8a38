"""The chip's idle time under no ``mxnet_tpu.`` span, the engine's
``serve.idle_wait`` (nothing to do) counted with it; per cent of the
traced window."""
import program_spans

UNDER = ("unattributed", "serve.idle_wait")


def read(trace, counters, record):
    return program_spans.idle_share(trace, UNDER)
