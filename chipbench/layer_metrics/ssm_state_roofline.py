"""The recurrent state's share of its roofline: the least time the chip
could take to read and write the conv and scan state that the traced
window's live lane-steps advance (bandwidth-bound: the update does a few
operations a byte), over the device time of the operations that write
state rows. Bytes come from the requests' lengths and the configuration's
shapes (``flops_falcon_h1.ssm_state_bytes``), never from the compiler: a
decoded token is one advance of one lane's state, and a prompt's
positions are ``positions / mamba_chunk_size`` advances (the chunked scan
takes the state in and gives it back once a chunk; a chunk that is not
full counts as its fraction, so the share errs low).

An operation writes state rows if one of the arrays it writes has the
scan state's (rows, heads, head, state) or the conv state's (rows,
d_conv - 1, channels) shape: the update itself, and every copy of a
state array the step makes around it. The two step executables give
different operations the same short name, so ``trace.labels`` (one label
a short name) cannot tell them apart: like ``program_spans`` this reader
goes back to the run's own ``.xplane.pb``, where every event carries its
whole HLO line, and holds the file to the trace it was handed by its
window."""
import re

import harness
import program_spans
import trace_reduce


def pattern(cfg):
    """The types of the two state arrays, any number of rows."""
    heads, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], \
        cfg["mamba_d_state"]
    conv_dim = cfg["mamba_d_ssm"] + 2 * cfg["mamba_n_groups"] * n
    return re.compile(rf"f32\[\d+,{heads},{p},{n}\]"
                      rf"|f32\[\d+,{cfg['mamba_d_conv'] - 1},{conv_dim}\]")


def written(line):
    """The types an operation writes, from its HLO line ``%name = <type
    or (tuple of types)> kind(operands)``."""
    rest = line.partition(" = ")[2]
    if not rest.startswith("("):
        return rest.split(" ", 1)[0]
    depth = 0
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rest[:i + 1]
    return rest


def state_seconds(events, cfg, t0, t1):
    """Seconds, inside the window, in which an operation that writes
    state rows ran; ``events`` are one chip's ``(HLO line, start, end)``."""
    rx = pattern(cfg)
    return trace_reduce.total(trace_reduce.union(trace_reduce.clip(
        [(s, e) for line, s, e in events if rx.search(written(line))],
        t0, t1))) / 1e9


def device_events(path):
    """``(HLO line, start, end)`` of the operations of the chip that ran
    most of them."""
    from jax.profiler import ProfileData

    chips = []
    for plane in ProfileData.from_file(path).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE:
                chips.append([(ev.name, int(ev.start_ns),
                               int(ev.start_ns + ev.duration_ns))
                              for ev in line.events])
    return max(chips, key=len) if chips else []


def share(seconds, record):
    """Per cent of the roofline, from the time found and the window's
    work; None where either is missing."""
    w, cfg = record["traced_work"], record["config"]
    steps = w["decode_tokens"] + w["prefill_positions"] \
        / cfg["mamba_chunk_size"]
    if not seconds or not steps:
        return None
    nbytes = harness.count_fn(cfg, "ssm_state_bytes")(
        cfg, steps, record["kv_itemsize"])
    return 100.0 * nbytes / record["peaks"]["hbm_bytes_per_s"] / seconds


def read(trace, counters, record):
    cfg = record.get("config") or {}
    if record.get("peaks") is None or not record.get("traced_work") \
            or "mamba_d_state" not in cfg:
        return None
    path = program_spans.newest_trace()
    if path is None or program_spans.read_file(path)[0] != (trace.t0,
                                                            trace.t1):
        return None
    return share(state_seconds(device_events(path), cfg, trace.t0, trace.t1),
                 record)
