"""The chip's idle time under ``trainer.commit`` (the parameters' and the
optimizer state's update, the loss wrapped); per cent of the traced
window."""
import program_spans

UNDER = ("trainer.commit",)


def read(trace, counters, record):
    return program_spans.idle_share(trace, UNDER)
