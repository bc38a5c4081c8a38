"""XLA-side capture: ``jax.profiler`` traces and per-op device tables.

``jax.profiler`` produces XPlane/perfetto traces of XLA execution (the role
of the reference engine's ``ProfileOperator``); the host event bus in
``core.py`` cannot see inside compiled programs, so device-time attribution
comes from here: :func:`device_op_stats` parses the chrome trace a capture
wrote (device pid rows carry ``device_duration_ps`` / ``model_flops`` /
``bytes_accessed`` per XLA op) into per-op tables — the role of the
reference's ``src/profiler/aggregate_stats.cc``.
"""
from __future__ import annotations

import os

from ..base import MXNetError

_trace_dir = None
_tracing = False


def trace_dir():
    """Directory of the last ``jax.profiler`` capture (None if never run)."""
    return _trace_dir


def start_trace(base_filename):
    """Start a ``jax.profiler`` trace next to ``base_filename``."""
    global _trace_dir, _tracing
    import jax

    if _tracing:
        return _trace_dir
    d = os.path.splitext(base_filename)[0] + "_trace"
    jax.profiler.start_trace(d)
    # published only on success: callers swallow start failures, and a
    # pre-assigned dir would make device_op_stats serve a STALE capture
    _trace_dir = d
    _tracing = True
    return _trace_dir


def stop_trace():
    global _tracing
    if not _tracing:
        return
    import jax

    jax.profiler.stop_trace()
    _tracing = False


def is_tracing():
    return _tracing


def scope_path(op_name):
    """An XLA ``op_name`` as a path of scopes: ``jit(...)`` segments (the
    names of traced functions) and the primitive at its end taken off."""
    import re

    parts = [p for p in re.sub(r"(?:^|/)p?jit\([^/()]*\)", "",
                               op_name).split("/") if p]
    return "/".join(parts[:-1])


def device_op_stats(trace_dir_=None):
    """Per-op DEVICE time table from a captured trace.

    Parses the chrome-trace the ``jax.profiler`` run wrote (device pid rows
    carry ``device_duration_ps``/``model_flops``/``bytes_accessed`` per XLA
    op) and aggregates by op name and scope. Returns rows sorted by total
    device time: ``{"name", "scope", "category", "calls", "total_us",
    "avg_us", "flops", "bytes_accessed", "tflops_s", "gb_s"}``. ``scope`` is
    the path of the program's own names the op was compiled under (the
    event's ``tf_op``: ``serve_step.decode/model/layer3/attention/
    attn.kernel``, see ``core.DEVICE_SCOPES``), without the primitive's
    name; two executables that reuse an op name are rows of their own.

    ``trace_dir_`` defaults to the directory of the last XLA capture. Empty
    list when the backend recorded no device events (pure-CPU runs expose
    host events only).
    """
    import glob
    import gzip
    import json

    d = trace_dir_ or _trace_dir
    if d is None:
        raise MXNetError(
            "no trace captured: run set_config(profile_xla=True); "
            "set_state('run') ... set_state('stop') first")
    paths = sorted(glob.glob(os.path.join(d, "**", "*.trace.json.gz"),
                             recursive=True))
    if not paths:
        return []
    with gzip.open(paths[-1], "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    # device pids are announced by process_name metadata like '/device:TPU:0'
    dev_pids = {e.get("pid") for e in events
                if e.get("ph") == "M" and e.get("name") == "process_name"
                and "/device:" in str(e.get("args", {}).get("name", ""))}
    agg = {}
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in dev_pids:
            continue
        args = e.get("args", {})
        if "device_duration_ps" not in args:
            continue
        name = e.get("name", "?")
        scope = scope_path(args.get("tf_op") or args.get("op_name") or "")
        row = agg.setdefault((name, scope), {
            "name": name,
            "scope": scope,
            "category": args.get("hlo_category", ""),
            "calls": 0, "total_us": 0.0, "flops": 0, "bytes_accessed": 0})
        row["calls"] += 1
        row["total_us"] += float(args["device_duration_ps"]) / 1e6
        row["flops"] += int(args.get("model_flops", 0) or 0)
        row["bytes_accessed"] += int(args.get("bytes_accessed", 0) or 0)
    rows = sorted(agg.values(), key=lambda r: -r["total_us"])
    for r in rows:
        r["avg_us"] = r["total_us"] / max(r["calls"], 1)
        secs = r["total_us"] / 1e6
        r["tflops_s"] = r["flops"] / secs / 1e12 if secs else 0.0
        r["gb_s"] = r["bytes_accessed"] / secs / 1e9 if secs else 0.0
    return rows


def device_op_table(trace_dir_=None, by_category=False, top=30,
                    by_scope=False):
    """Formatted per-op (or per-category, or per-scope: by block) device-time
    table; the printable analog of ``MXAggregateProfileStatsPrint``."""
    rows = device_op_stats(trace_dir_)
    if by_category or by_scope:
        key = "scope" if by_scope else "category"
        cats = {}
        for r in rows:
            c = cats.setdefault(r[key] or "other", {
                "name": r[key] or "other", "calls": 0,
                "total_us": 0.0, "flops": 0, "bytes_accessed": 0})
            c["calls"] += r["calls"]
            c["total_us"] += r["total_us"]
            c["flops"] += r["flops"]
            c["bytes_accessed"] += r["bytes_accessed"]
        rows = sorted(cats.values(), key=lambda r: -r["total_us"])
        for r in rows:
            secs = r["total_us"] / 1e6
            r["tflops_s"] = r["flops"] / secs / 1e12 if secs else 0.0
            r["gb_s"] = r["bytes_accessed"] / secs / 1e9 if secs else 0.0
    width = 64 if by_scope else 32
    lines = [f"{'Name':<{width}}{'Calls':>7}{'Total(us)':>12}"
             f"{'TFLOP/s':>9}{'GB/s':>8}"]
    for r in rows[:top]:
        # a scope's tail says most (the block), an op's name its head
        shown = r["name"][-(width - 1):] if by_scope else r["name"][:31]
        lines.append(f"{shown:<{width}}{r['calls']:>7}"
                     f"{r['total_us']:>12.1f}{r['tflops_s']:>9.1f}"
                     f"{r['gb_s']:>8.0f}")
    return "\n".join(lines)
