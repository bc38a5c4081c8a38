"""The held routed experts' share of their roofline in a model that
holds a range of a layer's experts (one chip's share of an
expert-parallel deployment): the least time the chip could take to read
the weights of the held experts that the traced window's tokens picked
(bandwidth-bound), over the device time of the operations that read the
routed experts' weight stack. Bytes come from the program's own count
(the ``experts_hit`` stat of the ``serve.route`` spans inside the window:
summed over the calls and the layers, each held expert that got a token
once a call) and the configuration's shapes
(``flops_command_a_plus.held_expert_bytes``), never from the compiler. A
decode step that multiplies its rows with every held expert reads more
than is counted, so its share errs low: that gap is the point.

An operation reads the routed stack if one of its operands has the type
of the stacked ``gate``/``up`` (held, hidden, width) or ``down`` (held,
width, hidden) arrays, or of one expert sliced out of them ((1, ...) or
rank 2: the prefill chunk's loop slices an expert before it multiplies).
The shared experts are a stack of another leading size
(``shared_expert_roofline``); where both sizes are equal, or the shared
stack holds one expert, an operand's type does not tell the two apart and
this reader reads nothing. The attention's and the head's weights have
other shapes. Like ``ssm_state_roofline`` this reader goes back to the
run's own ``.xplane.pb``, where every event carries its whole HLO line,
and holds the file to the trace it was handed by its window."""
import re

import harness
import program_spans
import trace_reduce

BYTES = "held_expert_bytes"


def pattern(cfg):
    """The types of the routed experts' weight arrays: the stack of those
    held, and one expert of it. None where the shared stack's type could
    be the same."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    held, shared = cfg["num_experts"], cfg.get("num_shared_experts", 0)
    if shared in (held, 1):
        return None
    lead = rf"({held},|1,)?"
    return re.compile(rf"f32\[{lead}{h},{f}\]|f32\[{lead}{f},{h}\]")


def _siblings():
    return (harness.load_module("layer_metrics", "moe_expert_roofline"),
            harness.load_module("layer_metrics", "ssm_state_roofline"))


def seconds_reading(events, rx, t0, t1):
    """Seconds, inside the window, in which an operation one of whose
    operands matches ``rx`` ran; ``events`` are one chip's ``(HLO line,
    start, end)``."""
    operands = _siblings()[0].operands
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
    nbytes = harness.count_fn(cfg, BYTES)(cfg, hit, record["kv_itemsize"])
    return 100.0 * nbytes / record["peaks"]["hbm_bytes_per_s"] / seconds


def read(trace, counters, record):
    cfg = record.get("config") or {}
    if record.get("peaks") is None or BYTES not in cfg.get("flops", {}):
        return None
    rx = pattern(cfg)
    spans = program_spans.spans_of(trace)
    path = program_spans.newest_trace()
    if rx is None or spans is None or path is None:
        return None
    events = _siblings()[1].device_events(path)
    return share(seconds_reading(events, rx, trace.t0, trace.t1),
                 experts_hit(trace, spans), record)
