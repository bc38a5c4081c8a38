"""The shared experts' share of their roofline: the least time the chip
could take to read the shared experts' weights once a call a layer
(bandwidth-bound at a decode step's few rows; a prefill chunk of 128 rows
at full float32 precision is bound by the matrix unit instead, so its
calls pull the share down), over the device time of the operations that
read the shared experts' weight stack. Bytes come from the program's own
count of calls (the ``calls`` stat of the ``serve.route`` spans inside the
window: every call of a step executable whose load was read) times the
layers held and the configuration's shapes
(``flops_command_a_plus.shared_expert_bytes``), never from the compiler.

An operation reads the shared stack if one of its operands has the type
of the stacked ``shared_gate``/``shared_up`` (shared, hidden, width) or
``shared_down`` (shared, width, hidden) arrays: the routed experts are a
stack of another leading size (``held_expert_roofline``, which also says
when the two cannot be told apart). Like ``ssm_state_roofline`` this
reader goes back to the run's own ``.xplane.pb``."""
import re

import harness
import program_spans

BYTES = "shared_expert_bytes"


def pattern(cfg):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    held, shared = cfg["num_experts"], cfg.get("num_shared_experts", 0)
    if shared in (0, 1, held):
        return None
    return re.compile(rf"f32\[{shared},{h},{f}\]|f32\[{shared},{f},{h}\]")


def layer_calls(trace, spans, cfg):
    """Calls of a step executable inside the window, times the layers."""
    return cfg["num_hidden_layers"] * sum(
        s.stats.get("calls", 0) for s in
        program_spans.inside(trace, spans, "serve.route"))


def share(seconds, calls, record):
    """Per cent of the roofline; None where either side is missing."""
    if not seconds or not calls:
        return None
    cfg = record["config"]
    nbytes = harness.count_fn(cfg, BYTES)(cfg, calls, record["kv_itemsize"])
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
    held = harness.load_module("layer_metrics", "held_expert_roofline")
    events = harness.load_module(
        "layer_metrics", "ssm_state_roofline").device_events(path)
    return share(held.seconds_reading(events, rx, trace.t0, trace.t1),
                 layer_calls(trace, spans, cfg), record)
