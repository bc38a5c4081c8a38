#!/usr/bin/env python3
"""Look at a trace by hand: ``python3 chipbench/trace_dump.py <workload>
[out.json]`` reads the trace the last ``--trace 1`` run of that workload
left under ``chipbench/.trace/`` and prints its planes and lines, the
executables and the operations that took most time, and the host spans;
with ``out.json`` it also writes ``CUT_MS`` of the window, from the first
whole call of its longest executable, in the form ``tests/data/`` keeps
(``trace_reduce.Reduced.from_json``)."""
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import trace_reduce  # noqa: E402

CUT_MS = 120


def main(argv):
    found = glob.glob(os.path.join(HERE, ".trace", argv[0], "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not found:
        raise SystemExit(f"no trace of {argv[0]} under chipbench/.trace/")
    r = trace_reduce.reduce_file(found[0], None)
    print(json.dumps({"planes": r.seen}, indent=1))
    print("window_s", r.window_s, "busy_by_chip_s", r.busy_by_chip_s,
          "idle_share", r.idle_share(),
          "exposed_collective_s", r.exposed_collective_s())
    mods = {}
    for n, s, e in r.module_calls():
        mods.setdefault(n, []).append((e - s) / 1e6)
    for n, v in sorted(mods.items(), key=lambda kv: -sum(kv[1]))[:12]:
        print("module", n, "calls", len(v), "total_ms", round(sum(v), 3),
              "median_ms", round(sorted(v)[len(v) // 2], 4))
    for n, sec in r.top_ops():
        print("op", n, round(sec, 5))
    names = {}
    for chip in r.ops[:1]:
        for n, s, e in r.in_window(chip):
            d = names.setdefault(n, [0, 0])
            d[0] += 1
            d[1] += e - s
    print("distinct op names on chip 0:", len(names))
    for n, (c, d) in sorted(names.items(), key=lambda kv: -kv[1][1])[:40]:
        print("  ", n, c, round(d / 1e6, 3), "ms")
    for n, lab in sorted(r.labels.items()):
        if "custom-call" in lab or n.split(".")[0] in ("f", "custom-call"):
            print("  label", n, "->", lab)
    spans = {}
    for n, s, e in r.spans:
        d = spans.setdefault(n, [0, 0])
        d[0] += 1
        d[1] += e - s
    print("spans", {n: (c, round(d / 1e6, 2)) for n, (c, d) in spans.items()})
    print("breakdown", json.dumps(r.breakdown()))
    if len(argv) > 1:
        # from just before the first whole call of the executable that
        # takes longest, so that the slice holds one
        calls = r.module_calls()
        if not calls:
            raise SystemExit("no executable ran on a device in the window")
        name = max(calls, key=lambda c: c[2] - c[1])[0]
        lo = min(s for n, s, e in calls if n == name) - 2_000_000
        hi = lo + CUT_MS * 1_000_000
        cut = lambda evs: [e for e in evs if e[1] >= lo and e[2] <= hi]  # noqa: E731
        small = trace_reduce.Reduced(
            [cut(c) for c in r.ops], [cut(c) for c in r.modules],
            [(trace_reduce.WINDOW_SPAN, lo, hi)]
            + [(n, max(s, lo), min(e, hi)) for n, s, e in r.spans
               if e > lo and s < hi and n != trace_reduce.WINDOW_SPAN])
        with open(argv[1], "w") as f:
            f.write(small.to_json())
        print("wrote", argv[1], os.path.getsize(argv[1]), "bytes")


if __name__ == "__main__":
    main(sys.argv[1:])
