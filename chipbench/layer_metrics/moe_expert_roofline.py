"""The routed experts' share of their roofline: the least time the chip
could take to read the weights of the experts that the traced window's
tokens picked (bandwidth-bound: a decode step multiplies a few rows with
each expert it reads), over the device time of the operations that read an
expert weight array. Bytes come from the program's own count of experts
hit (the ``experts_hit`` stat of the ``serve.route`` spans inside the
window: summed over the calls and the layers, each expert that got a token
once a call) and the configuration's shapes
(``flops_mellum2.moe_expert_bytes``), never from the compiler. A step
that reads experts nobody picked reads more than is counted, so its share
errs low.

An operation reads an expert weight array if one of its operands has the
type of the stacked ``gate``/``up`` (experts, hidden, expert width) or
``down`` (experts, expert width, hidden) arrays, whole or a slice of
them: the products themselves and every copy of a weight around them.
Like ``ssm_state_roofline`` this reader goes back to the run's own
``.xplane.pb``, where every event carries its whole HLO line, and holds
the file to the trace it was handed by its window."""
import re

import harness
import program_spans
import trace_reduce


def pattern(cfg):
    """The types of the expert weight arrays, any number of experts."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return re.compile(rf"f32\[(\d+,)*{h},{f}\]|f32\[(\d+,)*{f},{h}\]")


def operands(line):
    """What an operation reads: its HLO line after the type it writes."""
    rest = line.partition(" = ")[2]
    written = harness.load_module(
        "layer_metrics", "ssm_state_roofline").written(line)
    return rest[len(written):]


def expert_seconds(events, cfg, t0, t1):
    """Seconds, inside the window, in which an operation that reads an
    expert weight array ran; ``events`` are one chip's ``(HLO line,
    start, end)``."""
    rx = pattern(cfg)
    return trace_reduce.total(trace_reduce.union(trace_reduce.clip(
        [(s, e) for line, s, e in events if rx.search(operands(line))],
        t0, t1))) / 1e9


def experts_hit(trace, spans):
    return sum(s.stats.get("experts_hit", 0) for s in
               program_spans.inside(trace, spans, "serve.route"))


def share(seconds, hit, record):
    """Per cent of the roofline; None where either side is missing."""
    if not seconds or not hit:
        return None
    cfg = record["config"]
    nbytes = harness.count_fn(cfg, "moe_expert_bytes")(
        cfg, hit, record["kv_itemsize"])
    return 100.0 * nbytes / record["peaks"]["hbm_bytes_per_s"] / seconds


def read(trace, counters, record):
    cfg = record.get("config") or {}
    if record.get("peaks") is None or "moe_intermediate_size" not in cfg \
            or "moe_expert_bytes" not in cfg.get("flops", {}):
        return None
    spans = program_spans.spans_of(trace)
    path = program_spans.newest_trace()
    if spans is None or path is None:
        return None
    events = harness.load_module(
        "layer_metrics", "ssm_state_roofline").device_events(path)
    return share(expert_seconds(events, cfg, trace.t0, trace.t1),
                 experts_hit(trace, spans), record)
