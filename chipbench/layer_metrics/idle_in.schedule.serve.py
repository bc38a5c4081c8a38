"""The chip's idle time while the engine's thread schedules: under
``serve.retire``, ``serve.admit`` and ``serve.gauges``, and under
``serve.step``, ``serve.prefill`` and ``serve.decode`` themselves (what
their children leave of them); per cent of the traced window."""
import program_spans

UNDER = ("serve.retire", "serve.admit", "serve.gauges", "serve.step",
         "serve.prefill", "serve.decode")


def read(trace, counters, record):
    return program_spans.idle_share(trace, UNDER)
