"""Microbenchmark of the routed-expert products on the chip (issue 33):
the candidates of ``ops.nn`` at the Mellum-2 cell's shapes over 64
experts of 2304 x 896, float32; with ``--cell command_a_plus`` at that
cell's: 128 experts of 4096 x 4096 of which the first 8 are
held, so that 15 of 16 assignments are computed by nobody here.

    python3 exp/moe_products_bench.py [--tiny] [--cell command_a_plus]
                                      [--sweep | --tune]

Prints one JSON line a candidate and shape: milliseconds a call (median
of ``reps`` timed calls that end in ``block_until_ready``), the bytes of
expert weights the tokens' experts hold, and what share of 819 GB/s that
is. ``--tiny`` runs small shapes on any platform (a rehearsal).

``--sweep`` (issue 36) is a decode step's row sweep at the cell's
widths: 1 to 32 rows of one position, the forms
``ops.nn.routed_experts`` has, and after each row count a line with the
measured winner beside what ``ops.nn.expert_form`` chooses and the share
of the held experts it reckoned with.

The kernel (``ops/pallas/expert_products.py``) stands beside
them: ``kernel`` as ``ops.grouped_expert_products`` takes it on a TPU,
``grouped_tile32`` the XLA loop it replaced, kept as the fallback off the
TPU. A call's rows are 16 and 144 at Mellum-2's widths (a decode step's
lanes, and a prefill chunk of 128 with the decode lanes it carries) and
8 and 136 at Command A+'s; the call of a chunk runs twice, routed evenly
and ``skewed`` (its 128 tokens lean to a quarter of the experts, as one
sequence's tokens do in the cells: an expert takes up to all of them).
``--tune`` adds the kernel with widest tiles of 8 and of 128 rows, and
with no remainder rounded up to a wider tile (``_PAD`` 0: tiles of 32,
then at most one of 16 and one of 8).
"""
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from mxnet_tpu.ops import nn as ops  # noqa: E402
from mxnet_tpu.ops.pallas import expert_products as kern  # noqa: E402

HBM = 819e9


def timed(fn, args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    ms = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append((time.perf_counter() - t) * 1e3)
    return statistics.median(ms), out


def main():
    tiny = "--tiny" in sys.argv
    share = "command_a_plus" in sys.argv
    sweep = "--sweep" in sys.argv
    tune = "--tune" in sys.argv
    if tiny:
        kern.use_interpret(jax.default_backend() != "tpu")
    # e experts are held of e_all that the router scores
    e_all, h, f, k = (8, 128, 256, 2) if tiny else (64, 2304, 896, 8)
    e, rows = e_all, (4, 16) if tiny else (16, 144)
    if share:
        e_all, e, h, f, k = (16, 4, 128, 256, 4) if tiny \
            else (128, 8, 4096, 4096, 8)
        rows = (4, 16) if tiny else (8, 136)
    if sweep:
        rows = (1, 2, 4) if tiny else (1, 2, 4, 8, 16, 32)
    # (tokens, skewed): a chunk's call also routed as one sequence's is
    rows = [(n, False) for n in rows] + [(n, True) for n in rows if n > 64]
    key = jax.random.key(0)
    ks = jax.random.split(key, 5)
    gate = 0.02 * jax.random.normal(ks[0], (e, h, f), jnp.float32)
    up = 0.02 * jax.random.normal(ks[1], (e, h, f), jnp.float32)
    down = 0.02 * jax.random.normal(ks[2], (e, f, h), jnp.float32)
    prec = jax.lax.Precision.HIGHEST
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    for n, skewed in rows:
        x = jax.random.normal(ks[3], (n, h), jnp.float32)
        logits = jax.random.normal(jax.random.fold_in(ks[4], n), (n, e_all))
        if skewed:   # the chunk's 128 tokens lean to a quarter of them
            lean = jnp.zeros((e_all,)).at[::4].set(3.0)
            logits = logits.at[:128].add(lean)
        w, idx = ops.route_top_k(logits, k,
                                 score="sigmoid" if share else "softmax")
        # an assignment to an expert held elsewhere is computed by nobody
        # here: index e, weight 0 (ops.nn.routed_experts does the same)
        w = jnp.where(idx < e, w, 0.0)
        idx = jnp.where(idx < e, idx, e)
        picked = np.asarray(idx)[np.asarray(idx) < e]
        hit = int(np.unique(picked).size)
        most = int(np.bincount(picked, minlength=e).max()) if hit else 0
        nbytes = hit * 3 * h * f * 4

        def combine(prod):
            return jnp.sum(prod * w[:, :, None], axis=1)

        # the weights are arguments: closed over, they would be constants
        # of the executable (4 GB each)
        def products(tile=32, loop=False, widest=None, pad=None):
            """``ops.grouped_expert_products`` traced with the XLA loop
            (``loop``) or with the kernel at a widest tile and a rounding
            of the remainder of its own."""
            def fn(x, i, gate, up, down):
                saved = kern.supports, kern._ROW_TILE, kern._PAD
                if loop:
                    kern.supports = lambda *a: False
                kern._ROW_TILE = widest or saved[1]
                kern._PAD = saved[2] if pad is None else pad
                try:
                    return combine(ops.grouped_expert_products(
                        x, i, gate, up, down, tile)[0])
                finally:
                    kern.supports, kern._ROW_TILE, kern._PAD = saved
            return jax.jit(fn)

        cands = {"kernel": products()}
        if tune:
            cands["kernel_pad0"] = products(pad=0)
            for widest in (8, 128):
                cands[f"kernel_widest{widest}"] = products(widest=widest)
        for tile in (8, 32) if not (sweep or tune) else (32,):
            cands[f"grouped_tile{tile}"] = products(tile, loop=True)

        def ragged(x, i, gate, up, down):
            a = n * k
            flat = i.reshape(a)
            order = jnp.argsort(flat, stable=True)
            gs = jnp.zeros((e + 1,), jnp.int32).at[flat].add(1)[:e]
            xs = x[order // k]
            hid = jax.nn.silu(jax.lax.ragged_dot(xs, gate, gs, precision=prec)) \
                * jax.lax.ragged_dot(xs, up, gs, precision=prec)
            ys = jax.lax.ragged_dot(hid, down, gs, precision=prec)
            back = jnp.zeros((a,), jnp.int32).at[order].set(
                jnp.arange(a, dtype=jnp.int32))
            return combine(ys[back].reshape(n, k, h))

        if not (sweep or tune):   # it lost at both cells' shapes (PERF.md)
            cands["ragged_dot"] = jax.jit(ragged)

        def weights_of(i):
            return jnp.zeros((n, e + 1), jnp.float32).at[
                jnp.arange(n)[:, None], i].add(w)[:, :e]

        def dense(x, i, gate, up, down):
            return jnp.einsum("enh,ne->nh",
                              ops.dense_expert_products(x, gate, up, down),
                              weights_of(i), precision=prec)

        cands["dense_masked"] = jax.jit(dense)
        ref, took = None, {}
        for name, fn in cands.items():
            try:
                ms, out = timed(fn, (x, idx, gate, up, down), reps=10)
            except Exception as exc:  # a candidate the compiler refuses
                print(json.dumps({"candidate": name, "tokens": n,
                                  "skewed": skewed,
                                  "error": repr(exc)[:300]}), flush=True)
                continue
            out = np.asarray(out)
            ref = out if ref is None else ref
            took[name] = ms
            print(json.dumps({
                "candidate": name, "tokens": n, "skewed": skewed,
                "assignments": n * k,
                "experts_hit": hit, "max_load": most, "ms": ms,
                "expert_bytes": nbytes,
                "share_of_hbm_peak": nbytes / HBM / (ms / 1e3),
                "max_abs_diff_from_first": float(np.abs(out - ref).max()),
                "out_scale": float(np.abs(ref).max())}), flush=True)
        if sweep and took:
            print(json.dumps({
                "tokens": n, "experts_hit": hit, "experts_held": e,
                "winner": min(took, key=took.get),
                "rule": ops.expert_form(n, 1, k, e_all,
                                        ops.grouped_in_kernel(x, gate)),
                "share_reckoned": ops.expected_hit_share(n, k, e_all),
                "ms": took}), flush=True)


if __name__ == "__main__":
    main()
