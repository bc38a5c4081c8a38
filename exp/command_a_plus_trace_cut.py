#!/usr/bin/env python3
"""After a ``--trace 1`` run of ``command_a_plus_rag_closed_c8`` in the
same checkout: where the two step executables' time goes, and a slice of
the trace in the form ``chipbench/tests/data/`` keeps for the two readers
this cell brings (``held_expert_roofline``, ``shared_expert_roofline``).

    python3 exp/command_a_plus_trace_cut.py [out.json]

Prints, for each executable, its calls' median time and the device time
inside its calls by kind of operation (routed experts, shared experts,
attention kernel, head, projections, page writes and the chunk's
attention, the rest). With ``out.json`` writes ``CUT_MS`` of the window
from ``CUT_FROM_MS`` after the prefill executable's first call: every
device event's HLO line (cut to ``LINE`` characters, operands' types
kept), the ``serve.route`` and ``serve.decode`` spans with their stats,
and what the readers find in the slice.
"""
import json
import os
import re
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
for p in (ROOT, BENCH):
    sys.path.insert(0, p)
import harness  # noqa: E402
import program_spans  # noqa: E402
import trace_reduce  # noqa: E402

CELL = "command_a_plus_rag_closed_c8"
CUT_FROM_MS, CUT_MS, LINE = 20, 110, 700


def kind_of(line, cfg, held, shared, attn, operands):
    rest = line.partition(" = ")[2]
    if attn.KERNEL in line and attn.pattern(cfg).search(rest):
        return "attention kernel"
    if held.pattern(cfg).search(operands(line)):
        return "routed experts"
    if shared.pattern(cfg).search(operands(line)):
        return "shared experts"
    if re.search(rf"f32\[{cfg['vocab_size']},{cfg['hidden_size']}\]", rest):
        return "head and embedding"
    if attn.pattern(cfg).search(rest):
        return "pages (writes, gathers, prefill attention)"
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    if re.search(rf"f32\[({q},{cfg['hidden_size']}|{cfg['hidden_size']},{q}"
                 rf"|\d+,{cfg['hidden_size']})\]", rest):
        return "projections"
    return "rest"


def main(argv):
    cfg = harness.load_json("configs", "command_a_plus_05_2026.json")
    held = harness.load_module("layer_metrics", "held_expert_roofline")
    shared = harness.load_module("layer_metrics", "shared_expert_roofline")
    attn = harness.load_module("layer_metrics", "mixed_attn_roofline")
    operands = harness.load_module("layer_metrics",
                                   "moe_expert_roofline").operands
    ssm = harness.load_module("layer_metrics", "ssm_state_roofline")
    path = program_spans.newest_trace()
    if path is None or CELL not in path:
        raise SystemExit(f"the newest trace is not {CELL}'s: {path}")
    reduced = trace_reduce.reduce_file(path, 1)
    _, spans = program_spans.read_file(path)
    events = ssm.device_events(path)
    t0, t1 = reduced.t0, reduced.t1

    def found(evs, lo, hi, red, sp):
        return {"held_seconds": held.seconds_reading(
                    evs, held.pattern(cfg), lo, hi),
                "shared_seconds": held.seconds_reading(
                    evs, shared.pattern(cfg), lo, hi),
                "kernel_seconds": attn.kernel_seconds(evs, cfg, lo, hi),
                "experts_hit": held.experts_hit(red, sp),
                "layer_calls": shared.layer_calls(red, sp, cfg),
                "kv_positions": list(attn.positions(red, sp))}

    print(json.dumps({"window_s": reduced.window_s, "busy_s": reduced.busy_s,
                      **found(events, t0, t1, reduced, spans)}))
    calls = reduced.module_calls()
    leaf = sorted((s, e, line) for line, s, e in events
                  if not re.search(r" (while|conditional|call)\(", line))
    for name in sorted({n for n, _, _ in calls}):
        mine = [(s, e) for n, s, e in calls if n == name]
        by_kind = {}
        for s, e, line in leaf:
            if any(a <= s and e <= b for a, b in mine):
                k = kind_of(line, cfg, held, shared, attn, operands)
                by_kind[k] = by_kind.get(k, 0) + (e - s)
        print(json.dumps({
            "executable": name[:60], "calls": len(mine),
            "median_ms": statistics.median((e - s) / 1e6 for s, e in mine),
            "ms_a_call_by_kind": {k: round(v / 1e6 / len(mine), 3)
                                  for k, v in sorted(by_kind.items())}}))
    route = program_spans.inside(reduced, spans, "serve.route")
    if route:
        tot = {k: sum(s.stats.get(k, 0) for s in route)
               for k in ("experts_hit", "experts_held", "assignments",
                         "assignments_held", "calls")}
        print(json.dumps({"route_spans": len(route), "route_ms_mean":
                          statistics.mean((s.end - s.start) / 1e6
                                          for s in route), **tot}))
    if not argv:
        return
    name = max(calls, key=lambda c: c[2] - c[1])[0]
    lo = min(s for n, s, e in calls if n == name) + CUT_FROM_MS * 1_000_000
    hi = lo + CUT_MS * 1_000_000
    cut = [(line[:LINE], s, e) for line, s, e in events if s >= lo and e <= hi]
    kept = [(s.name, s.thread, s.start, s.end, s.stats) for s in spans
            if s.start >= lo and s.end <= hi
            and s.name in ("mxnet_tpu.serve.route", "mxnet_tpu.serve.decode")]
    small = trace_reduce.Reduced([[]], [[]],
                                 [(trace_reduce.WINDOW_SPAN, lo, hi)])
    sp = [program_spans.Span(*k) for k in kept]
    out = {"events": cut, "window": [lo, hi], "program_spans": kept,
           **found(cut, lo, hi, small, sp),
           "note": f"{CUT_MS} ms of a chip run of {CELL} (TPU v5e): each "
                   f"operation's HLO line cut to {LINE} characters"}
    with open(argv[0], "w") as f:
        json.dump(out, f)
    print("wrote", argv[0], os.path.getsize(argv[0]), "bytes", len(cut),
          "events", len(kept), "spans")


if __name__ == "__main__":
    main(sys.argv[1:])
