"""Microbenchmark of the routed-expert products on the chip (issue 33):
the candidates of ``ops.nn`` at the Mellum-2 cell's two shapes, 16 x 8
and 128 x 8 assignments over 64 experts of 2304 x 896, float32; with
``--cell command_a_plus`` (issue 35) at that cell's: 8 x 8 and 128 x 8
assignments over 128 experts of 4096 x 4096 of which the first 8 are
held, so that 15 of 16 assignments are computed by nobody here.

    python3 exp/moe_products_bench.py [--tiny] [--cell command_a_plus]
                                      [--sweep]

Prints one JSON line a candidate and shape: milliseconds a call (median
of ``reps`` timed calls that end in ``block_until_ready``), the bytes of
expert weights the tokens' experts hold, and what share of 819 GB/s that
is. ``--tiny`` runs small shapes on any platform (a rehearsal).

``--sweep`` (issue 36) is a decode step's row sweep at the cell's
widths: 1 to 32 rows of one position, the two forms
``ops.nn.routed_experts`` has, the tiles at 8 rows and a loop over the
held experts under conditions, and after each row count a line with the
measured winner beside what ``ops.nn.expert_form`` chooses and the share
of the held experts it reckoned with.
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
    # e experts are held of e_all that the router scores
    e_all, h, f, k = (8, 64, 32, 2) if tiny else (64, 2304, 896, 8)
    e, rows = e_all, (4, 16) if tiny else (16, 128)
    if share:
        e_all, e, h, f, k = (16, 4, 64, 64, 4) if tiny \
            else (128, 8, 4096, 4096, 8)
        rows = (4, 16) if tiny else (8, 128)
    if sweep:
        rows = (1, 2, 4) if tiny else (1, 2, 4, 8, 16, 32)
    key = jax.random.key(0)
    ks = jax.random.split(key, 5)
    gate = 0.02 * jax.random.normal(ks[0], (e, h, f), jnp.float32)
    up = 0.02 * jax.random.normal(ks[1], (e, h, f), jnp.float32)
    down = 0.02 * jax.random.normal(ks[2], (e, f, h), jnp.float32)
    prec = jax.lax.Precision.HIGHEST
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    for n in rows:
        x = jax.random.normal(ks[3], (n, h), jnp.float32)
        logits = jax.random.normal(jax.random.fold_in(ks[4], n), (n, e_all))
        w, idx = ops.route_top_k(logits, k,
                                 score="sigmoid" if share else "softmax")
        # an assignment to an expert held elsewhere is computed by nobody
        # here: index e, weight 0 (ops.nn.routed_experts does the same)
        w = jnp.where(idx < e, w, 0.0)
        idx = jnp.where(idx < e, idx, e)
        hit = int(np.unique(np.asarray(idx)[np.asarray(idx) < e]).size)
        nbytes = hit * 3 * h * f * 4

        def combine(prod):
            return jnp.sum(prod * w[:, :, None], axis=1)

        # the weights are arguments: closed over, they would be constants
        # of the executable (4 GB each)
        cands = {}
        for tile in (8, 32):
            cands[f"grouped_tile{tile}"] = jax.jit(
                lambda x, i, gate, up, down, t=tile: combine(
                    ops.grouped_expert_products(x, i, gate, up, down, t)[0]))

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

        if not sweep:   # it lost at both cells' shapes (PERF.md, PR 33, 35)
            cands["ragged_dot"] = jax.jit(ragged)

        def weights_of(i):
            return jnp.zeros((n, e + 1), jnp.float32).at[
                jnp.arange(n)[:, None], i].add(w)[:, :e]

        def dense(x, i, gate, up, down):
            return jnp.einsum("enh,ne->nh",
                              ops.dense_expert_products(x, gate, up, down),
                              weights_of(i), precision=prec)

        def by_expert(x, i, gate, up, down):
            # the held experts one at a time by static index (a view of
            # the stack), each under a condition on its count of rows:
            # tried for the decode step in PR 36 and left here, a tie with
            # the tiles in the sweep and in the cell (PERF.md, PR 36)
            counts = jnp.zeros((e + 1,), jnp.int32).at[
                i.reshape(-1)].add(1)[:e]
            cw = weights_of(i)
            out = jnp.zeros_like(x)
            for j in range(e):
                out = jax.lax.cond(
                    counts[j] > 0,
                    lambda o, j=j: o + cw[:, j:j + 1] * ops._swiglu_rows(
                        x, gate[j], up[j], down[j], prec),
                    lambda o: o, out)
            return out

        cands["dense_masked"] = jax.jit(dense)
        if sweep:
            cands["by_expert_cond"] = jax.jit(by_expert)
        ref, took = None, {}
        for name, fn in cands.items():
            try:
                ms, out = timed(fn, (x, idx, gate, up, down), reps=10)
            except Exception as exc:  # a candidate the compiler refuses
                print(json.dumps({"candidate": name, "tokens": n,
                                  "error": repr(exc)[:300]}), flush=True)
                continue
            out = np.asarray(out)
            ref = out if ref is None else ref
            took[name] = ms
            print(json.dumps({
                "candidate": name, "tokens": n, "assignments": n * k,
                "experts_hit": hit, "ms": ms,
                "expert_bytes": nbytes,
                "share_of_hbm_peak": nbytes / HBM / (ms / 1e3),
                "max_abs_diff_from_first": float(np.abs(out - ref).max()),
                "out_scale": float(np.abs(ref).max())}), flush=True)
        if sweep and took:
            print(json.dumps({
                "tokens": n, "experts_hit": hit, "experts_held": e,
                "winner": min(took, key=took.get),
                "rule": ops.expert_form(n, 1, k, e_all),
                "share_reckoned": ops.expected_hit_share(n, k, e_all),
                "ms": took}), flush=True)


if __name__ == "__main__":
    main()
