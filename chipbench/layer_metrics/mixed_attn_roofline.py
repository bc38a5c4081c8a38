"""The paged decode-attention kernel's share of its roofline in a model
whose layers are of two kinds, full and bounded by a window: the least
time the chip could take to read the K and V rows that the traced
window's decode steps attend to (bandwidth-bound), over the device time
of the kernel's calls. Bytes come from the program's own count of
positions read (the ``kv_positions_full`` and ``kv_positions_window``
stats of the ``serve.decode`` spans inside the window: summed over the
live lanes and the layers of each kind, ``t + 1`` and ``min(t + 1,
window)``) and the configuration's shapes
(``flops_mellum2.attention_bytes``), never from the compiler:
``decode_attn_roofline`` is handed the sum of contexts alone, which counts
a window layer as if it were full.

The kernel's calls are the Mosaic custom calls one of whose operands is a
page pool, (pages, KV heads, page, head size): another kernel of the step
(a grouped product) reads no such array. Like ``ssm_state_roofline`` this
reader goes back to the run's own ``.xplane.pb``."""
import re

import harness
import program_spans
import trace_reduce

KERNEL = "tpu_custom_call"


def pattern(cfg):
    """The type of a K or V page pool, any number of pages and any page."""
    return re.compile(rf"f32\[\d+,{cfg['num_key_value_heads']},\d+,"
                      rf"{cfg['head_dim']}\]")


def kernel_seconds(events, cfg, t0, t1):
    rx = pattern(cfg)
    return trace_reduce.total(trace_reduce.union(trace_reduce.clip(
        [(s, e) for line, s, e in events
         if KERNEL in line and rx.search(line.partition(" = ")[2])],
        t0, t1))) / 1e9


def positions(trace, spans):
    """``(full, window)`` K/V positions the window's decode steps read."""
    inside = program_spans.inside(trace, spans, "serve.decode")
    return (sum(s.stats.get("kv_positions_full", 0) for s in inside),
            sum(s.stats.get("kv_positions_window", 0) for s in inside))


def share(seconds, full, window, record):
    if not seconds or not full + window:
        return None
    cfg = record["config"]
    nbytes = harness.count_fn(cfg, "attention_bytes")(
        cfg, full, window, record["kv_itemsize"])
    return 100.0 * nbytes / record["peaks"]["hbm_bytes_per_s"] / seconds


def read(trace, counters, record):
    cfg = record.get("config") or {}
    if record.get("peaks") is None \
            or "attention_bytes" not in cfg.get("flops", {}):
        return None
    spans = program_spans.spans_of(trace)
    path = program_spans.newest_trace()
    if spans is None or path is None:
        return None
    events = harness.load_module(
        "layer_metrics", "ssm_state_roofline").device_events(path)
    full, window = positions(trace, spans)
    return share(kernel_seconds(events, cfg, trace.t0, trace.t1), full,
                 window, record)
