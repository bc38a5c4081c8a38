#!/usr/bin/env python3
"""Where a step executable's device time goes, by the program's own names.

The program names what it compiles (``mxnet_tpu/profiler/core.py``,
``OBSERVABILITY.md`` section 7): a step scope around each executable
(``serve_step.decode``, ``serve_step.prefill``, ``train_step.grad``,
``train_step.optimizer``), a block scope for every ``gluon.Block`` called
under a trace (the name its parent registered it under), and op scopes
where a block is too coarse (``attn.kernel``, ``kv.write`` ...). XLA keeps
the path as each instruction's ``op_name`` (a fusion takes its root's), and
the profiler's file holds every executable's compiled module beside the
device events, so the two are joined on executable and instruction name.

This module reads one ``.xplane.pb`` and gives every device operation's
*self* time (``trace_reduce.self_times``: a ``while`` and its body count
once) to the executable call it ran inside and to a **part**, by the scope
path of its event. The tables from scope to part are the data below, and
nothing else decides. An operation under no scope they know is ``other``,
so the parts of an executable add up to the mean device-busy time of one of
its calls by construction. What an executable is (decode step, prefill
chunk, training step) is read from the step scope of its operations.

    python3 chipbench/device_scopes.py <workload> [cut.json.gz]

prints the per-call table of the last ``--trace 1`` run of that workload
in this checkout, and with a second argument writes ``CUT_MS`` of it in
the form ``tests/data/*.scopes.v5e.json.gz`` keeps (:func:`from_cut`).
A per-layer metric ``<kind>_ms_in.<part>`` is :func:`metric`.
"""
import bisect
import functools
import glob
import gzip
import json
import os
import re
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import trace_reduce  # noqa: E402

# -- the tables ----------------------------------------------------------------
# step scope -> the kind of executable it marks
KINDS = {"serve_step.decode": "decode", "serve_step.prefill": "prefill",
         "train_step.grad": "train", "train_step.optimizer": "train"}
# A path is read innermost first, twice: for an op scope that decides, then
# for a block's name. ``norm`` decides nothing (a norm belongs to the block
# it stands in: the mixer's gated norm is the mixer's, a layer's own norm is
# ``other``), and a name the tables do not hold is skipped.
SERVE_OPS = {
    "attn.rope": "attn_proj",
    "attn.kernel": "attn_core", "attn.scores": "attn_core",
    "kv.gather": "attn_core",
    "kv.write": "kv_write",
    "experts.router": "experts_routed", "experts.routed": "experts_routed",
    "experts.shared": "experts_shared",
    "ssm.conv": "ssm", "ssm.scan": "ssm", "ssm.state": "ssm",
    "head": "head", "embed": "head",
}
SERVE_BLOCKS = {
    "attention": "attn_proj",   # q, k, v, o products and the rotation
    "ffn": "ffn",               # the dense feed-forward block
    "mixer": "ssm",             # the Mamba-2 mixer and its projections
    "lm_head": "head", "embed": "head",
}
TRAIN_OPS = {"loss": "head_loss"}
TRAIN_BLOCKS = {
    "encoder": "encoder",
    "pooler": "head_loss", "mlm_dense": "head_loss", "mlm_norm": "head_loss",
    "nsp": "head_loss",
    "bert": "other",        # the backbone outside its encoder: embeddings
    "model": "head_loss",   # the pretraining model outside its backbone:
                            # the tied decoder's product
}
OTHER = "other"
CUT_MS = 150
_FOLLOW = 4

_STEP = re.compile("|".join(re.escape(k) for k in KINDS))
_JIT = re.compile(r"(?:^|/)p?jit\([^/()]*\)")
_TRANSFORM = re.compile(r"[A-Za-z_]+\(|\)")


def tokens_of(op_name):
    """``(step scope or None, the names after it, outermost first)``.
    ``jit(step)/train_step.grad/transpose(jvp(model))/bert/encoder/add``
    gives ``("train_step.grad", ["model", "bert", "encoder", "add"])``: a
    ``jit(...)`` segment names a function and is dropped, a transform's
    wrapper (``jvp(``, ``transpose(``, ``vmap(``) is taken off."""
    m = _STEP.search(op_name or "")
    if not m:
        return None, []
    rest = _TRANSFORM.sub("", _JIT.sub("", op_name[m.end():]))
    return m.group(0), [t for t in rest.split("/") if t]


def part_of(op_name, hlo_name=""):
    """``(kind, part, scoped)`` of one device operation: the executable
    kind its step scope marks (None without one), the part the tables
    give it, and whether any block or op scope stood under the step's."""
    return _part_of(op_name, bool(trace_reduce.COLLECTIVE.match(hlo_name)))


@functools.lru_cache(maxsize=None)
def _part_of(op_name, collective):
    step, toks = tokens_of(op_name)
    if step is None:
        return None, OTHER, False
    kind = KINDS[step]
    if step == "train_step.optimizer":
        return kind, "optimizer", True
    ops, blocks = (TRAIN_OPS, TRAIN_BLOCKS) if kind == "train" \
        else (SERVE_OPS, SERVE_BLOCKS)
    # the last name is the primitive's; what stands before it are scopes
    scoped = len(toks) > 1
    if kind == "train" and collective:
        return kind, OTHER, scoped
    for table in (ops, blocks):
        for t in reversed(toks):
            if t in table:
                return kind, table[t], scoped
    return kind, OTHER, scoped


# -- the events ------------------------------------------------------------------
# A device event carries its HLO line and its time, and no op_name (TPU v5e,
# jax 0.9: the stats of an ``XLA Ops`` event are its offset and duration).
# The same file holds every executable's optimised HLO module, metadata and
# all, in the plane ``/host:metadata`` (one ``Hlo Proto`` a program, under
# the executable's name as the ``XLA Modules`` line has it), which
# ``jax.profiler.ProfileData`` does not reach: these few functions walk the
# protobuf's wire format to it, and join on executable and instruction name.

def _varint(b, i):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        if c < 0x80:
            return r, i
        s += 7


def _fields(b, lo, hi):
    """``(field, value)`` of the message in ``b[lo:hi]``: an int, or the
    ``(lo, hi)`` of a length-delimited value."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        else:
            n = 8 if wire == 1 else 4
            v, i = int.from_bytes(b[i:i + n], "little"), i + n
        yield key >> 3, v


def _sub(b, span, field):
    return (v for f, v in _fields(b, *span) if f == field)


def _text(b, span):
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _instruction_op_names(b, hlo_proto):
    """``{instruction name: op_name}`` of an ``HloProto``: hlo_module = 1,
    computations = 3, instructions = 2, name = 1, metadata = 7 (op_name =
    2), id = 35, operand_ids = 36 (xla/service/hlo.proto, xla_data.proto).
    An instruction the compiler made carries no metadata (the
    ``copy-start``/``copy-done`` and ``slice-start``/``slice-done`` of a
    weight fetched ahead of its use, a layout's ``copy``): it takes the
    name of the operation it feeds, its first user's, followed through
    at most ``_FOLLOW`` of them, and failing that its first operand's."""
    out = {}
    for module in _sub(b, hlo_proto, 1):
        for comp in _sub(b, module, 3):
            insts = []    # (name, op_name, id, operand ids), program order
            for inst in _sub(b, comp, 2):
                name, op_name, uid, operands = None, "", None, []
                for f, v in _fields(b, *inst):
                    if f == 1:
                        name = _text(b, v)
                    elif f == 7:
                        for span in _sub(b, v, 2):
                            op_name = _text(b, span)
                    elif f == 35:
                        uid = v
                    elif f == 36:
                        # packed, or one varint a field
                        if isinstance(v, tuple):
                            i = v[0]
                            while i < v[1]:
                                o, i = _varint(b, i)
                                operands.append(o)
                        else:
                            operands.append(v)
                insts.append((name, op_name, uid, operands))
            named = {uid: op for _, op, uid, _ in insts}
            users, feeds = {}, {}
            for _, _, uid, operands in insts:
                feeds[uid] = operands
                for o in operands:
                    users.setdefault(o, []).append(uid)
            for name, op_name, uid, _ in insts:
                for graph in (users, feeds):
                    at = uid
                    for _ in range(_FOLLOW):
                        if op_name or not graph.get(at):
                            break
                        at = graph[at][0]
                        op_name = named.get(at, "")
                out[name] = op_name
    return out


def hlo_op_names(path):
    """``{executable name: {instruction name: op_name}}`` from the
    ``/host:metadata`` plane of an ``.xplane.pb`` (XSpace.planes = 1;
    XPlane.name = 2, event_metadata = 4, a map whose value = 2 is an
    XEventMetadata with name = 2 and stats = 5; XStat.bytes_value = 6)."""
    with open(path, "rb") as f:
        b = f.read()
    out = {}
    for plane in _sub(b, (0, len(b)), 1):
        if not any(_text(b, v) == "/host:metadata"
                   for v in _sub(b, plane, 2)):
            continue
        for entry in _sub(b, plane, 4):
            for meta in _sub(b, entry, 2):
                name = "".join(_text(b, v) for v in _sub(b, meta, 2))
                for stat in _sub(b, meta, 5):
                    for proto in _sub(b, stat, 6):
                        out[name] = _instruction_op_names(b, proto)
    return out


def read_events(path):
    """``(window, chips)`` of an ``.xplane.pb``: the extent of its
    ``chipbench.window`` spans (None without one), and a chip at a time
    ``{"ops": [(hlo name, start, end, op_name)], "modules": [(name, start,
    end)]}`` of the device planes that ran anything. ``op_name`` is the
    instruction's in the module of the executable call the event lies in
    (``""`` where the file holds no module of that name)."""
    from jax.profiler import ProfileData

    window, chips = [], {}
    for plane in ProfileData.from_file(path).planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (trace_reduce.OPS_LINE,
                                   trace_reduce.MODULES_LINE):
                chips.setdefault(int(m.group(1)), {})[line.name] = [
                    (ev.name, int(ev.start_ns),
                     int(ev.start_ns + ev.duration_ns)) for ev in line.events]
            elif not m:
                for ev in line.events:
                    if ev.name == trace_reduce.WINDOW_SPAN:
                        window.append((int(ev.start_ns),
                                       int(ev.start_ns + ev.duration_ns)))
    names = hlo_op_names(path)
    out = []
    for _, c in sorted(chips.items()):
        if not c.get(trace_reduce.OPS_LINE):
            continue
        modules = sorted(c.get(trace_reduce.MODULES_LINE, []),
                         key=lambda e: e[1])
        starts = [e[1] for e in modules]
        ops = []
        for line, s, e in c[trace_reduce.OPS_LINE]:
            j = bisect.bisect_right(starts, s) - 1
            table = names.get(modules[j][0], {}) \
                if j >= 0 and e <= modules[j][2] else {}
            hlo = trace_reduce.short(line)
            ops.append((hlo, s, e, table.get(hlo, "")))
        out.append({"ops": ops, "modules": modules})
    if not window:
        return None, out
    return (min(s for s, _ in window), max(e for _, e in window)), out


def calls_of(chip, t0, t1):
    """One chip's whole executable calls inside the window, each with the
    self time of the operations that ran inside it: ``[(module name,
    start, end, {(kind, part, scoped): ns})]``."""
    calls = sorted((s, e, n) for n, s, e in chip["modules"]
                   if s >= t0 and e <= t1)
    starts = [c[0] for c in calls]
    inside = [[] for _ in calls]
    for i, (_, s, e, _) in enumerate(chip["ops"]):
        j = bisect.bisect_right(starts, s) - 1
        if j >= 0 and e <= calls[j][1]:
            inside[j].append((i, s, e))
    out = []
    for (s, e, name), evs in zip(calls, inside):
        split = {}
        for i, self_ns in trace_reduce.self_times(evs):
            hlo, _, _, op_name = chip["ops"][i]
            key = part_of(op_name, hlo)
            split[key] = split.get(key, 0) + self_ns
        out.append((name, s, e, split))
    return out


class Split:
    """The per-call split of every kind of step executable found in the
    window: ``table[kind]`` holds ``calls``, ``busy_ms`` (the mean device
    time a call's operations took, self times summed), ``call_ms_median``
    and ``call_ms_mean`` (the executable events' own durations: the median
    is what ``decode_step_ms`` / ``prefill_chunk_ms`` read, the mean what
    ``busy_ms`` lies just under), ``parts`` (mean ms a call, by
    part; they add up to ``busy_ms``), ``scoped_share`` (the share of
    ``busy_ms`` under a block or op scope) and ``modules`` (the
    executables' names). A chip's mean where several ran."""

    def __init__(self, chips, window):
        self.window = window
        per_kind = {}
        for chip in chips:
            t0, t1 = window if window else (0, float("inf"))
            for name, s, e, split in calls_of(chip, t0, t1):
                # an executable is what most of its device time says it is
                by_kind = {}
                for (kind, _, _), ns in split.items():
                    by_kind[kind] = by_kind.get(kind, 0) + ns
                kind = max(by_kind, key=by_kind.get) if by_kind else None
                if kind is None:
                    continue
                per_kind.setdefault(kind, []).append((name, e - s, split))
        self.table = {}
        for kind, calls in per_kind.items():
            n = len(calls)
            parts, scoped, busy = {}, 0, 0
            for _, _, split in calls:
                for (_, part, has), ns in split.items():
                    parts[part] = parts.get(part, 0) + ns
                    busy += ns
                    scoped += ns if has else 0
            self.table[kind] = {
                "calls": n,
                "busy_ms": busy / n / 1e6,
                "call_ms_median": statistics.median(
                    d for _, d, _ in calls) / 1e6,
                "call_ms_mean": sum(d for _, d, _ in calls) / n / 1e6,
                "parts": {p: ns / n / 1e6 for p, ns in sorted(parts.items())},
                "scoped_share": scoped / busy if busy else 0.0,
                "modules": sorted({trace_reduce.short(nm).split("(")[0]
                                   for nm, _, _ in calls}),
            }

    def ms(self, kind, part, listed=None):
        """Mean ms a call of ``kind`` in ``part``; None where no such
        executable ran. ``listed`` names the parts the cell reports: the
        others count as ``other``, so that what is reported adds up."""
        row = self.table.get(kind)
        if row is None:
            return None
        if part != OTHER:
            return row["parts"].get(part, 0.0)
        keep = set(listed or row["parts"]) - {OTHER}
        return row["busy_ms"] - sum(v for p, v in row["parts"].items()
                                    if p in keep)


# -- the benchmark's side -----------------------------------------------------------

def newest_trace(workload=None):
    found = glob.glob(os.path.join(HERE, ".trace", workload or "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=None)
def listed_parts(workload, kind):
    """The parts of ``kind`` that ``BENCHMARK.json`` has this cell report."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    head = f"{kind}_ms_in."
    return [m["name"][len(head):] for m in bench["per_layer"]
            if m["name"].startswith(head)
            and workload in m.get("workloads", ())]


def split_of(trace):
    """The split of the run ``trace`` (a ``trace_reduce.Reduced``) came
    from, read once and kept on it; None where the newest trace cannot be
    told to be its (another window), or holds no scoped executable: a
    program from before the scopes."""
    if not hasattr(trace, "device_scopes"):
        trace.device_scopes = None
        path = newest_trace()
        if path is not None:
            window, chips = read_events(path)
            if window == (trace.t0, trace.t1):
                split = Split(chips, window)
                if split.table:
                    split.workload = os.path.relpath(
                        path, os.path.join(HERE, ".trace")).split(os.sep)[0]
                    trace.device_scopes = split
    return trace.device_scopes


def metric(trace, kind, part):
    """The per-layer metric ``<kind>_ms_in.<part>``."""
    split = split_of(trace)
    if split is None:
        return None
    return split.ms(kind, part, listed_parts(split.workload, kind))


# -- by hand --------------------------------------------------------------------------

def cut(chips, window, ms=CUT_MS):
    """``ms`` of the window from just before the first whole call of the
    executable that takes longest, as JSON text (:func:`from_cut`)."""
    t0, t1 = window
    calls = [(n, s, e) for n, s, e in chips[0]["modules"]
             if s >= t0 and e <= t1]
    name = max(calls, key=lambda c: c[2] - c[1])[0]
    lo = min(s for n, s, e in calls if n == name) - 1_000_000
    hi = lo + ms * 1_000_000
    keep = lambda evs: [e for e in evs if e[1] >= lo and e[2] <= hi]  # noqa: E731
    return json.dumps({
        "window": [lo, hi],
        "chips": [{"ops": keep(c["ops"]), "modules": keep(c["modules"])}
                  for c in chips]})


def from_cut(text):
    d = json.loads(text)
    chips = [{"ops": [tuple(e) for e in c["ops"]],
              "modules": [tuple(e) for e in c["modules"]]}
             for c in d["chips"]]
    return Split(chips, tuple(d["window"]))


def render(split, workload):
    lines = []
    for kind, row in sorted(split.table.items()):
        listed = listed_parts(workload, kind)
        lines.append(
            f"{workload} {kind}: {row['calls']} calls of "
            f"{', '.join(row['modules'])}; busy {row['busy_ms']:.3f} ms a "
            f"call (the executable's own events: mean "
            f"{row['call_ms_mean']:.3f}, median "
            f"{row['call_ms_median']:.3f} ms); under a block or op scope "
            f"{100 * row['scoped_share']:.2f}%")
        for part, v in sorted(row["parts"].items(), key=lambda kv: -kv[1]):
            mark = "" if part in listed or not listed else \
                "   (no metric of this cell: reported in other)"
            lines.append(f"    {part:<16}{v:>10.3f} ms"
                         f"{100 * v / row['busy_ms']:>8.2f}%{mark}")
        if listed:
            lines.append(f"    {'other, reported':<16}"
                         f"{split.ms(kind, OTHER, listed):>10.3f} ms")
    return "\n".join(lines)


def main(argv):
    if not argv:
        raise SystemExit(__doc__)
    path = newest_trace(argv[0])
    if path is None:
        raise SystemExit(f"no trace of {argv[0]} under chipbench/.trace/")
    window, chips = read_events(path)
    if window is None:
        every = [e for c in chips for e in c["modules"]]
        window = (min(e[1] for e in every), max(e[2] for e in every))
    split = Split(chips, window)
    if not split.table:
        raise SystemExit("no device operation of this trace carries a step "
                         "scope: a program from before the scopes, or an "
                         "executable the compile cache kept from one")
    print(render(split, argv[0]))
    if len(argv) > 1:
        with gzip.open(argv[1], "wt") as f:
            f.write(cut(chips, window))
        print("wrote", argv[1], os.path.getsize(argv[1]), "bytes")


if __name__ == "__main__":
    main(sys.argv[1:])
