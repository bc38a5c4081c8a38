#!/usr/bin/env python
"""Per-operator micro-benchmark harness (reference ``benchmark/opperf/``).

Times forward (and, for differentiable ops, forward+backward) of
registered ops on synthetic inputs and prints a table + JSON. The
reference runs each op through its imperative path with the profiler;
here each op runs through the same `mx.np`/`npx` dispatch the user calls,
timed with the two-loop difference method (see bench.py) so the numbers
hold on runtimes that return before execution ends too.

Usage::

    python benchmark/opperf.py                 # default op set
    python benchmark/opperf.py --ops add,dot,tanh --shape 512,512
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_OPS = ("add multiply divide dot tanh exp log sqrt sum mean max "
               "argsort softmax relu sigmoid matmul transpose concatenate "
               "where clip")


def _timed(fn, fetch, k1=5, k2=25):
    from bench import _timed_diff  # repo-root bench.py: shared timer

    return _timed_diff(fn, fetch, k1, k2)


def bench_op(name, shape):
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu import np as mnp
    from mxnet_tpu import npx

    rng = onp.random.RandomState(0)
    a = mnp.array(rng.uniform(0.5, 2, shape).astype("float32"))
    b = mnp.array(rng.uniform(0.5, 2, shape).astype("float32"))

    fn = getattr(mnp, name, None) or getattr(npx, name, None)
    if fn is None:
        return None
    try:
        sig_args = (a, b) if name in (
            "add", "multiply", "divide", "dot", "matmul",
        ) else (a,)
        if name == "concatenate":
            sig_args = ([a, b],)
        if name == "where":
            sig_args = (a > 1, a, b)
        if name == "clip":
            sig_args = (a, 0.8, 1.5)
        fn(*sig_args).wait_to_read()
    except Exception as e:  # noqa: BLE001
        return {"op": name, "error": f"{type(e).__name__}: {e}"}

    fwd = _timed(lambda: fn(*sig_args), lambda r: r.asnumpy())

    bwd = None
    try:
        a.attach_grad()
        with autograd.record():
            out = fn(*sig_args)
        out.backward()

        def step():
            with autograd.record():
                o = fn(*sig_args)
            o.backward()
            return a.grad

        bwd = _timed(step, lambda r: r.asnumpy())
    except Exception:  # non-differentiable / int-valued
        bwd = None
    row = {"op": name, "shape": list(shape),
           "fwd_us": round(fwd * 1e6, 1)}
    if bwd is not None:
        row["fwd_bwd_us"] = round(bwd * 1e6, 1)
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description="per-op perf harness")
    ap.add_argument("--ops", default=None,
                    help="comma-separated op names (default: common set)")
    ap.add_argument("--shape", default="256,256")
    ap.add_argument("--json", action="store_true", help="JSON lines only")
    args = ap.parse_args(argv)
    ops = (args.ops.split(",") if args.ops else DEFAULT_OPS.split())
    shape = tuple(int(x) for x in args.shape.split(","))
    rows = []
    for name in ops:
        row = bench_op(name, shape)
        if row is None:
            continue
        rows.append(row)
        if args.json:
            print(json.dumps(row), flush=True)
        else:
            err = row.get("error")
            msg = (f"{row['op']:<14} " +
                   (f"ERROR {err}" if err else
                    f"fwd {row['fwd_us']:>9.1f} us" +
                    (f"   fwd+bwd {row['fwd_bwd_us']:>9.1f} us"
                     if "fwd_bwd_us" in row else "")))
            print(msg, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
