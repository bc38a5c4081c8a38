#!/usr/bin/env python3
"""How often the program routes a token to other experts than the plain
reference does, and what that does to the token's logits (PERF.md
section 2); no benchmark run calls this.

    python3 exp/mellum2_route_flips.py --seeds 1,2 [--rows 2] [--len 1536]
                                       [--eager] [--rehearse]

For each seed: ``mellum2_12b_a2_5b``'s weights, ``rows`` sequences of
``len`` random ids (``len`` a whole number of prefill chunks). The
program's side is the serving path: the prompts go through
``serve.ContinuousEngine`` as the cell builds it, one prefill chunk a
step, and ``ops.nn.route_top_k`` is watched (an ordered host callback
inside the step) for the experts every prompt position picks in every
layer. The reference's side is ``reference/mellum2.py`` a layer at a
time, with each token's gap between its k-th and (k+1)-th router
probability. A (token, layer) whose two sets of experts differ is a
flipped route. Printed a seed, one JSON line: visits, flips, the
reference's margins at the flips, and how many visits have a margin
under 1e-6, 1e-5 and 1e-4.

``--eager`` reads the program's side from ``MellumModel``'s normal path
run eagerly instead (on a TPU its ``Dense`` layers multiply at the
backend's default precision, one bfloat16 pass: many routes flip), and
then also prints, for every flipped token, the gap by which the
program's first choice lies under the reference's best at that position
and the largest move of any logit there: what a flipped route does to a
token's logits (in logit units, beside the logits' spread).
"""
import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
for p in (ROOT, BENCH):
    sys.path.insert(0, p)
import harness  # noqa: E402


SEEN = []       # what the watched router picked, in the order it ran


def watch_routes():
    """Put an ordered host callback behind ``ops.nn.route_top_k``. Once a
    process: the op that calls it is jitted by its shapes and traced once
    for all the layers of every engine, so the layers tell themselves
    apart by the order in which their callbacks arrive."""
    import jax
    from mxnet_tpu.ops import nn as ops

    real = ops.route_top_k

    def watched(logits, top_k, renormalize=True):
        w, idx = real(logits, top_k, renormalize)
        jax.debug.callback(lambda i: SEEN.append(np.sort(i, axis=-1)), idx,
                           ordered=True)
        return w, idx

    ops.route_top_k = watched


def engine_side(cfg, maker, toks, on_chip):
    """``picked (layers, N, k) sorted`` of the prompts' positions, served:
    chunked prefill through the engine's in-place step."""
    import jax
    from mxnet_tpu import serve
    from mxnet_tpu.ops.pallas import decode_attention as da

    adapter = harness.load_module("adapters", cfg["adapter"])
    net = adapter.build(cfg, on_chip)
    harness.load_weights(net, adapter.name_map(cfg), maker)
    layers = cfg["num_hidden_layers"]
    if not on_chip:
        da.use_interpret(True)
    kw = dict(cfg["serve"])
    page = kw.pop("page_size", None)
    if page:
        kw.update(page_size=page, prefill_chunk=page)
    eng = serve.ContinuousEngine(net, **kw)
    try:
        eng.warmup()
        jax.effects_barrier()
        SEEN.clear()
        calls, run = [], eng._run_step

        def logged(tokens, start_pos, last_idx, table, lanes):
            calls.append((np.asarray(tokens).shape[1], int(lanes[0]),
                          int(start_pos[0])))
            return run(tokens, start_pos, last_idx, table, lanes)

        eng._run_step = logged
        futs = [eng.submit(row.tolist(), max_new_tokens=1) for row in toks]
        while not all(f.done() for f in futs):
            eng.step()
        jax.effects_barrier()
    finally:
        eng.close()
    n_rows, length = toks.shape
    chunk = eng.prefill_chunk
    slot_of = {}                        # slots in the order they were given
    out = np.full((layers, n_rows * length, cfg["num_experts_per_tok"]), -1)
    prefills = [c for c in calls if c[0] > 1]
    got = [a for a in SEEN if a.shape[0] == chunk]    # a decode step's: slots
    assert len(got) == layers * len(prefills), (len(got), len(prefills))
    for j, idx in enumerate(got):
        (_, slot, start), layer = prefills[j // layers], j % layers
        row = slot_of.setdefault(slot, len(slot_of))
        out[layer, row * length + start:row * length + start + chunk] = idx
    assert (out >= 0).all()
    del net, eng
    gc.collect()
    return out


def eager_side(cfg, maker, toks, on_chip):
    """``(picked (layers, N, k) sorted, logits (B, T, vocab) on the
    host)`` of the program's normal path, run eagerly."""
    import mxnet_tpu as mx
    from mxnet_tpu.ops import nn as ops

    adapter = harness.load_module("adapters", cfg["adapter"])
    net = adapter.build(cfg, on_chip)
    harness.load_weights(net, adapter.name_map(cfg), maker)
    seen, real = [], ops.route_top_k

    def watched(logits, top_k, renormalize=True):
        w, idx = real(logits, top_k, renormalize)
        seen.append(np.sort(np.asarray(idx), axis=-1))
        return w, idx

    ops.route_top_k = watched
    try:
        # eager: the arrays the model makes on its way (rope tables, the
        # window's band) go to the default device, so name it
        ctx = mx.tpu() if on_chip else mx.cpu()
        with ctx, mx.autograd.predict_mode():
            logits = net(mx.np.array(toks, ctx=ctx)).asnumpy()
    finally:
        ops.route_top_k = real
    del net
    gc.collect()
    return np.stack(seen), logits


def reference_side(cfg, ref, maker, toks):
    """``(picked (layers, N, k) sorted, margin (layers, N), logits)`` of
    the reference's full pass."""
    import jax
    import jax.numpy as jnp

    k, eps = cfg["num_experts_per_tok"], cfg["rms_norm_eps"]
    top = maker.group(-1)

    @jax.jit
    def layer(x, p):
        x = x + ref.attention(ref._rms_norm(x, p["attn_norm"], eps), p, cfg,
                              ref.EXACT, ref.kind_of(p))
        h = ref._rms_norm(x, p["ffn_norm"], eps)
        hf = h.reshape(-1, h.shape[-1])
        w = ref.route(hf, p["router"], cfg, ref.EXACT)
        prob = jax.nn.softmax(ref._mm(hf, p["router"], ref.EXACT), axis=-1)
        best = jax.lax.top_k(prob, k + 1)[0]
        picked = jnp.sort(jnp.argsort(-(w > 0).astype(jnp.int32), axis=-1,
                                      stable=True)[:, :k], axis=-1)
        return x + ref.experts(h, p, cfg, ref.EXACT), picked, \
            best[:, k - 1] - best[:, k]

    x = ref.embed(jnp.asarray(toks), top["embed"])
    picked, margin = [], []
    for i in range(cfg["num_hidden_layers"]):
        p = {n.split(".", 1)[1]: v for n, v in maker.group(i).items()}
        x, pk, mg = layer(x, p)
        picked.append(np.asarray(pk))
        margin.append(np.asarray(mg))
        del p
    logits = np.asarray(ref.logits(x, top["norm"], top["head"], cfg))
    return np.stack(picked), np.stack(margin), logits


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--len", type=int, default=1536)
    ap.add_argument("--eager", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax

    cfg = harness.load_json("configs", "mellum2_12b_a2_5b.json")
    if args.rehearse:
        cfg = harness.merged(cfg, cfg["rehearse"])
    on_chip = jax.devices()[0].platform == "tpu"
    ref = harness.load_module("reference", cfg["reference"])
    weights = harness.load_module(".", "weights")
    if not args.eager:
        watch_routes()
    for seed in (int(s) for s in args.seeds.split(",")):
        maker = weights.Maker(ref.param_shapes(cfg), seed,
                              cfg["initializer_range"])
        toks = np.random.RandomState(seed % 2**31).randint(
            0, cfg["vocab_size"], (args.rows, args.len)).astype(np.int32)
        if args.eager:
            got, logits = eager_side(cfg, maker, toks, on_chip)
        else:
            got = engine_side(cfg, maker, toks, on_chip)
        with jax.default_matmul_precision("highest"):
            want, margin, ref_logits = reference_side(cfg, ref, maker, toks)
        flipped = (got != want).any(-1)                      # (layers, N)
        out = {"seed": seed, "path": "eager" if args.eager else "engine",
               "visits": int(flipped.size), "flips": int(flipped.sum()),
               "margins_at_flips": [float(m) for m in margin[flipped]],
               "visits_with_margin_under": {
                   str(e): int((margin < e).sum())
                   for e in (1e-7, 1e-6, 1e-5, 1e-4)}}
        if not args.eager:
            print(json.dumps(out), flush=True)
            del ref_logits, maker
            gc.collect()
            continue
        rows = []
        for n in sorted(set(np.nonzero(flipped)[1].tolist())):
            b, t = divmod(n, args.len)
            at, mine = ref_logits[b, t], logits[b, t]
            rows.append({
                "row": b, "position": t,
                "layers": np.nonzero(flipped[:, n])[0].tolist(),
                "gap": float(at.max() - at[mine.argmax()]),
                "largest_move": float(np.abs(mine - at).max())})
        others = np.ones(logits.shape[:2], bool).reshape(-1)
        others[[r["row"] * args.len + r["position"] for r in rows]] = False
        move = np.abs(logits - ref_logits).max(-1).reshape(-1)
        out.update(flipped_tokens=rows,
                   largest_move_elsewhere=float(move[others].max()),
                   logit_spread=float(ref_logits.std()))
        print(json.dumps(out), flush=True)
        del logits, ref_logits, maker
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
