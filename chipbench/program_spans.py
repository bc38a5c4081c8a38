#!/usr/bin/env python3
"""The program's own host spans (``mxnet_tpu.<layer>.<phase>``,
``profiler.core.host_span``) laid against the chip's idle time. A helper
of the ``idle_in.*`` readers under ``layer_metrics/``; no metric of its own.

The record a reader gets carries neither the trace's path nor the cell's
name, so :func:`spans_of` takes the newest ``.xplane.pb`` under
``chipbench/.trace/`` and holds it to the trace it was handed: the file's
``chipbench.window`` span must equal ``trace.t0, trace.t1`` to the
nanosecond. It reads the file once (``jax.profiler.ProfileData`` alone)
and keeps every host event whose name starts with ``mxnet_tpu.``. Where
there is no such file or no such event (a program from before the spans)
it gives ``None``, and so does every reader: never 0.

:func:`idle_by_span` cuts each idle interval of the chip that idles most
at span edges and gives every piece to the innermost span that covers it
(the covering span that started last, whatever its thread), so the parts
add up to the idle time exactly; what no span covers is ``unattributed``.

By hand: ``python3 chipbench/program_spans.py <workload> [out.json]``
prints the split of that workload's last traced run and, with
``out.json``, cuts ``trace_dump.CUT_MS`` of it into the form
``tests/data/`` keeps, the program's spans beside the device's events.
"""
import glob
import json
import os
import sys
from collections import namedtuple

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import trace_reduce  # noqa: E402

PREFIX = "mxnet_tpu."
UNATTRIBUTED = "unattributed"

Span = namedtuple("Span", "name thread start end stats")


def read_file(path):
    """``(window, spans)`` of an ``.xplane.pb``: the extent of its
    ``chipbench.window`` spans as ``trace_reduce`` reads it (None without
    one) and the program's spans off the device planes."""
    from jax.profiler import ProfileData

    window, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                name = ev.name
                if name.startswith(PREFIX):
                    spans.append(Span(
                        name, f"{line.name}#{k}", int(ev.start_ns),
                        int(ev.start_ns + ev.duration_ns), dict(ev.stats)))
                elif name == trace_reduce.WINDOW_SPAN:
                    window.append((int(ev.start_ns),
                                   int(ev.start_ns + ev.duration_ns)))
    if not window:
        return None, spans
    return (min(s for s, _ in window), max(e for _, e in window)), spans


def newest_trace():
    found = glob.glob(os.path.join(HERE, ".trace", "*", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def spans_of(trace):
    """The program's spans of the run ``trace`` was reduced from, read
    once and kept on it; None where they cannot be told to be its."""
    if not hasattr(trace, "program_spans"):
        trace.program_spans = None
        path = newest_trace()
        if path is not None:
            window, spans = read_file(path)
            if window == (trace.t0, trace.t1) and spans:
                trace.program_spans = spans
    return trace.program_spans


def owners(spans):
    """Disjoint ``(start, end, name)`` pieces in time order: between two
    neighbouring span edges, the span that covers the piece and started
    last. Pieces no span covers are left out."""
    edges = sorted({t for s in spans for t in (s.start, s.end)})
    opening = sorted((s for s in spans if s.end > s.start),
                     key=lambda s: s.start)
    out, active, k = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while k < len(opening) and opening[k].start <= a:
            active.append(opening[k])
            k += 1
        active = [s for s in active if s.end >= b]
        if active:
            # the last to start; of two that start together, the shorter
            inner = max(active, key=lambda s: (s.start, -s.end))
            if out and out[-1][2] == inner.name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, inner.name)
            else:
                out.append((a, b, inner.name))
    return out


def idle_intervals(trace):
    """The idle intervals of the chip that idles most (the chip
    ``Reduced.idle_gaps`` picks); None without a device event."""
    if not trace.busy or not any(trace.busy):
        return None
    chip = min(range(len(trace.busy)),
               key=lambda c: trace_reduce.total(trace.busy[c]))
    return trace_reduce.complement(trace.busy[chip], trace.t0, trace.t1)


def idle_by_span(trace, spans):
    """``{span name: idle nanoseconds}`` with ``unattributed`` for what
    no span covers; the values add up to the chip's idle time. None
    where the trace has no device event."""
    idle = idle_intervals(trace)
    if idle is None:
        return None
    owned = owners(spans)
    sums, j = {}, 0
    for gs, ge in idle:
        left = ge - gs
        while j < len(owned) and owned[j][1] <= gs:
            j += 1
        i = j
        while i < len(owned) and owned[i][0] < ge:
            a, b, name = owned[i]
            cover = min(b, ge) - max(a, gs)
            if cover > 0:
                sums[name] = sums.get(name, 0) + cover
                left -= cover
            i += 1
        if left:
            sums[UNATTRIBUTED] = sums.get(UNATTRIBUTED, 0) + left
    return sums


def split_of(trace):
    """``idle_by_span`` of the trace's own run, computed once and kept on
    it; None where either side is missing."""
    if not hasattr(trace, "program_idle"):
        spans = spans_of(trace)
        trace.program_idle = None if spans is None \
            else idle_by_span(trace, spans)
    return trace.program_idle


def idle_share(trace, names):
    """What the readers return: the idle time under the spans called
    ``names`` (``mxnet_tpu.`` left off; ``unattributed`` for none), in
    per cent of the traced window."""
    split = split_of(trace)
    if split is None or trace.t1 <= trace.t0:
        return None
    full = {n if n == UNATTRIBUTED else PREFIX + n for n in names}
    under = sum(ns for n, ns in split.items() if n in full)
    return 100.0 * under / (trace.t1 - trace.t0)


def inside(trace, spans, name):
    """The spans called ``name`` that lie wholly inside the window."""
    return [s for s in spans if s.name == PREFIX + name
            and s.start >= trace.t0 and s.end <= trace.t1]


# -- by hand -----------------------------------------------------------------

def cut(reduced, spans, ms):
    """``ms`` of the window from just before the first whole call of the
    executable that takes longest, as ``trace_dump.py`` cuts it, with the
    program's spans clipped to it: the dict ``tests/data/`` keeps
    (``Reduced.from_json`` reads it; ``program_spans`` rides beside)."""
    calls = reduced.module_calls()
    if not calls:
        raise SystemExit("no executable ran on a device in the window")
    name = max(calls, key=lambda c: c[2] - c[1])[0]
    lo = min(s for n, s, e in calls if n == name) - 2_000_000
    hi = lo + ms * 1_000_000
    whole = lambda evs: [e for e in evs if e[1] >= lo and e[2] <= hi]  # noqa: E731
    return {
        "ops": [whole(c) for c in reduced.ops],
        "modules": [whole(c) for c in reduced.modules],
        "spans": [(trace_reduce.WINDOW_SPAN, lo, hi)]
        + [(n, max(s, lo), min(e, hi)) for n, s, e in reduced.spans
           if e > lo and s < hi and n != trace_reduce.WINDOW_SPAN],
        "program_spans": [
            (s.name, s.thread, max(s.start, lo), min(s.end, hi), s.stats)
            for s in spans if s.end > lo and s.start < hi],
    }


def from_cut(text, **kw):
    """``(Reduced, spans)`` of a file ``cut`` made."""
    spans = [Span(n, th, s, e, st)
             for n, th, s, e, st in json.loads(text)["program_spans"]]
    return trace_reduce.Reduced.from_json(text, **kw), spans


def main(argv):
    import trace_dump

    found = glob.glob(os.path.join(HERE, ".trace", argv[0], "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not found:
        raise SystemExit(f"no trace of {argv[0]} under chipbench/.trace/")
    reduced = trace_reduce.reduce_file(found[0], None)
    window, spans = read_file(found[0])
    print("window", window, "program spans", len(spans),
          "threads", sorted({s.thread for s in spans}))
    split = idle_by_span(reduced, spans) or {}
    idle = sum(split.values())
    print("idle_share", reduced.idle_share(), "idle_s", idle / 1e9)
    for n, ns in sorted(split.items(), key=lambda kv: -kv[1]):
        print(f"  {n:44s} {ns / 1e9:9.4f} s "
              f"{100.0 * ns / max(reduced.t1 - reduced.t0, 1):6.2f}%")
    totals = {}
    for s in spans:
        if s.start >= reduced.t0 and s.end <= reduced.t1:
            c = totals.setdefault(s.name, [0, 0])
            c[0] += 1
            c[1] += s.end - s.start
    for n, (c, ns) in sorted(totals.items(), key=lambda kv: -kv[1][1]):
        print(f"  span {n:40s} calls {c:5d} total_ms {ns / 1e6:10.3f} "
              f"mean_ms {ns / c / 1e6:8.4f}")
    if len(argv) > 1:
        with open(argv[1], "w") as f:
            json.dump(cut(reduced, spans, trace_dump.CUT_MS), f)
        print("wrote", argv[1], os.path.getsize(argv[1]), "bytes")


if __name__ == "__main__":
    main(sys.argv[1:])
