"""From a profiler trace to device times: the benchmark's own reduction.

``reduce_file`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
(with ``jax.profiler.ProfileData``, nothing else) into plain lists of
events and hands them to :class:`Reduced`, which knows nothing of the
profiler and is what the tests drive with a small recorded trace.

Times are nanoseconds on the trace's clock. An interval is ``(start,
end)``. Busy time is the union of the intervals in which an operation
ran on a chip, so nested and overlapping events count once.
"""
import json
import re

COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast|send|recv)")
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
TOP = 10


# -- intervals ---------------------------------------------------------------

def union(intervals):
    """Merged, sorted, disjoint intervals covering the same points."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(merged):
    return sum(e - s for s, e in merged)


def intersect(a, b):
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def complement(merged, lo, hi):
    out, at = [], lo
    for s, e in merged:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events):
    """``[(name, self ns)]``: each event's duration less the part its
    nested events cover, so that a loop and its body count once."""
    out, stack = [], []   # stack of [name, end, self]
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out.extend((n, d) for n, _, d in stack)
    return out


# -- the reduced trace -------------------------------------------------------

class Reduced:
    """``ops`` and ``modules``: one list a chip of ``(name, start, end)``;
    ``spans``: the benchmark's host spans ``(name, start, end)``. The
    window is the ``chipbench.window`` span, or where that is missing the
    extent of the device events."""

    def __init__(self, ops, modules, spans, uncovered="unannotated",
                 ignore=()):
        self.ops, self.modules, self.spans = ops, modules, spans
        self.uncovered, self.ignore = uncovered, tuple(ignore)
        self.labels = {}
        win = [s for s in spans if s[0] == WINDOW_SPAN]
        if win:
            self.t0 = min(s[1] for s in win)
            self.t1 = max(s[2] for s in win)
        else:
            every = [e for chip in ops for e in chip]
            self.t0 = min((e[1] for e in every), default=0)
            self.t1 = max((e[2] for e in every), default=0)
        self.busy = [union(clip([(s, e) for _, s, e in chip],
                                self.t0, self.t1)) for chip in ops]

    @property
    def window_s(self):
        return (self.t1 - self.t0) / 1e9

    @property
    def busy_by_chip_s(self):
        return [total(b) / 1e9 for b in self.busy]

    @property
    def busy_s(self):
        """Averaged over the chips used."""
        by = self.busy_by_chip_s
        return sum(by) / len(by) if by else 0.0

    def idle_share(self):
        """1 - busy / window on the chip that idles most; None without
        a device event to read."""
        if not self.busy or not any(self.busy) or self.window_s <= 0:
            return None
        return 1.0 - min(self.busy_by_chip_s) / self.window_s

    def in_window(self, events):
        return [(n, max(s, self.t0), min(e, self.t1)) for n, s, e in events
                if min(e, self.t1) > max(s, self.t0)]

    def exposed_collective_s(self):
        """On the chip where it is longest: the time a collective runs
        while no other operation does. None where no collective ran."""
        worst = None
        for chip in self.ops:
            ev = self.in_window(chip)
            coll = union((s, e) for n, s, e in ev if COLLECTIVE.match(n))
            if not coll:
                continue
            comp = union((s, e) for n, s, e in ev if not COLLECTIVE.match(n))
            exposed = (total(coll) - total(intersect(coll, comp))) / 1e9
            worst = exposed if worst is None else max(worst, exposed)
        return worst

    def op_seconds(self, pattern):
        """Seconds of the operations whose name (with the type and the
        custom-call target the trace gives it) matches, a chip's mean;
        None where none matched."""
        rx = re.compile(pattern)
        per_chip = []
        for chip in self.ops:
            per_chip.append(total(union(
                (s, e) for n, s, e in self.in_window(chip)
                if rx.search(self.labels.get(n, n)))))
        if not any(per_chip):
            return None
        return sum(per_chip) / len(per_chip) / 1e9

    def module_calls(self, chip=0):
        """``[(name, start, end)]`` of the executables run on a chip, whole
        ones inside the window only."""
        if chip >= len(self.modules):
            return []
        return [(n, s, e) for n, s, e in self.modules[chip]
                if s >= self.t0 and e <= self.t1]

    def module_ms_by_kernel(self, pattern, chip=0):
        """The executables run on a chip, split by whether an operation
        whose name matches ran inside them: ``(with, without)``, each
        ``{executable name: [milliseconds of each call]}``."""
        import bisect

        rx = re.compile(pattern)
        hits = sorted(s for n, s, e in self.ops[chip]
                      if rx.search(self.labels.get(n, n))) \
            if chip < len(self.ops) else []
        yes, no = {}, {}
        for name, s, e in self.module_calls(chip):
            i = bisect.bisect_left(hits, s)
            side = yes if i < len(hits) and hits[i] < e else no
            side.setdefault(name, []).append((e - s) / 1e6)
        return yes, no

    def top_ops(self):
        """The operations that took most device time (self time, summed
        by name, a chip's mean)."""
        sums = {}
        for chip in self.ops:
            for name, d in self_times(self.in_window(chip)):
                sums[name] = sums.get(name, 0) + d
        chips = max(len(self.ops), 1)
        ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:TOP]
        return [[self.labels.get(n, n), d / chips / 1e9] for n, d in ranked]

    def idle_gaps(self):
        """The idle time of the chip that idles most, summed by the host
        span that covered most of each gap."""
        if not self.busy:
            return []
        chip = min(range(len(self.busy)), key=lambda c: total(self.busy[c]))
        spans = [s for s in self.spans
                 if s[0] != WINDOW_SPAN and s[0] not in self.ignore]
        sums = {}
        for gs, ge in complement(self.busy[chip], self.t0, self.t1):
            best, name = 0, self.uncovered
            for n, s, e in spans:
                cover = min(e, ge) - max(s, gs)
                if cover > best:
                    best, name = cover, n
            sums[name] = sums.get(name, 0) + (ge - gs)
        ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:TOP]
        return [[n, d / 1e9] for n, d in ranked]

    def breakdown(self):
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}

    def to_json(self):
        return json.dumps({"ops": self.ops, "modules": self.modules,
                           "spans": self.spans})

    @classmethod
    def from_json(cls, text, **kw):
        d = json.loads(text)
        tup = lambda lst: [tuple(e) for e in lst]   # noqa: E731
        return cls([tup(c) for c in d["ops"]], [tup(c) for c in d["modules"]],
                   tup(d["spans"]), **kw)


# -- reading the profiler's file ----------------------------------------------

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def short(name):
    """The trace names an operation by its whole HLO line (``%fusion.5 =
    bf16[...] fusion(...)``): keep what stands before the ``=``."""
    return name.split(" = ", 1)[0].lstrip("%")


def label(name):
    """``fusion.5 bf16[30522,768]``: the short name with the type of what
    the operation writes, for a reader of the breakdown."""
    head, _, rest = name.partition(" = ")
    kind = rest.split("{", 1)[0].split(" ", 1)[0].lstrip("(")
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    if target:
        kind += " custom-call:" + target.group(1)
    return (head.lstrip("%") + " " + kind).strip()


def read_planes(path):
    """``(ops, modules, spans, seen, labels)`` from an ``.xplane.pb``;
    ``seen`` maps every plane to its lines' names and event counts, for a
    reader who meets a trace laid out otherwise; ``labels`` maps an
    operation's short name to its name with the type it writes."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips, spans, seen, labels = {}, [], {}, {}
    for plane in data.planes:
        lines = {}
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            events = None
            if m and line.name in (OPS_LINE, MODULES_LINE):
                events = []
                for ev in line.events:
                    events.append((short(ev.name), int(ev.start_ns),
                                   int(ev.start_ns + ev.duration_ns)))
                    if " = " in ev.name:
                        labels.setdefault(events[-1][0], label(ev.name))
                chips.setdefault(int(m.group(1)), {})[line.name] = events
                lines[line.name] = len(events)
                continue
            count = 0
            for ev in line.events:
                count += 1
                if not m and ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns)))
            lines[line.name] = count
        seen[plane.name] = lines
    order = sorted(chips)
    ops = [chips[c].get(OPS_LINE, []) for c in order]
    modules = [chips[c].get(MODULES_LINE, []) for c in order]
    return ops, modules, spans, seen, labels


def reduce_file(path, chips, **kw):
    ops, modules, spans, seen, labels = read_planes(path)
    if chips is not None and len(ops) not in (0, chips):
        # every chip of the machine is traced; keep those that did work
        busiest = sorted(range(len(ops)), key=lambda c: -len(ops[c]))[:chips]
        ops = [ops[c] for c in sorted(busiest)]
        modules = [modules[c] for c in sorted(busiest)]
    out = Reduced(ops, modules, spans, **kw)
    out.seen, out.labels = seen, labels
    return out
