"""The chip's idle time under ``trainer.call`` (the compiled executable's
call until it returns, argument handling included) and
``trainer.compile``; per cent of the traced window."""
import program_spans

UNDER = ("trainer.call", "trainer.compile")


def read(trace, counters, record):
    return program_spans.idle_share(trace, UNDER)
