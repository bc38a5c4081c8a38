"""The chip's idle time while the engine's thread gets a step under way:
under ``serve.build_inputs``, ``serve.to_device``, ``serve.dispatch`` and
``serve.pool_update``; per cent of the traced window."""
import program_spans

UNDER = ("serve.build_inputs", "serve.to_device", "serve.dispatch",
         "serve.pool_update")


def read(trace, counters, record):
    return program_spans.idle_share(trace, UNDER)
