"""The chip's idle time while ``ShardedTrainer.step`` prepares its call:
under ``trainer.unwrap``, ``trainer.optimizer_scalars``,
``trainer.rng_split`` and ``trainer.gather_args``, and under
``trainer.step`` itself (what its children leave of it); per cent of the
traced window."""
import program_spans

UNDER = ("trainer.unwrap", "trainer.optimizer_scalars", "trainer.rng_split",
         "trainer.gather_args", "trainer.step")


def read(trace, counters, record):
    return program_spans.idle_share(trace, UNDER)
