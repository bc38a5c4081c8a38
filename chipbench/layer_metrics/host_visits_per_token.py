"""Scheduler iterations a sampled token: the ``serve.step`` spans inside
the traced window over the sum of ``tokens`` on the ``serve.settle`` spans
inside it (each visit's span says how many tokens it sampled)."""
import program_spans


def read(trace, counters, record):
    spans = program_spans.spans_of(trace)
    if spans is None:
        return None
    steps = program_spans.inside(trace, spans, "serve.step")
    tokens = sum(s.stats.get("tokens", 0) for s in
                 program_spans.inside(trace, spans, "serve.settle"))
    return len(steps) / tokens if steps and tokens else None
