#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process drives both halves of the main path through the entry points
a user calls, at published model widths, on one TPU v5e:

* *train* — BERT-base (``bert_12_768_12``, batch 64, seq 128, Adam, bf16
  AMP: the ``bench.py:_bert_setup`` configuration) under a
  ``ShardedTrainer`` for twelve steps on one fixed batch;
* *serve* — Llama-3-8B widths (units 4096, hidden 14336, 32/8 heads,
  vocab 128256) with the depth cut to what one chip holds, through
  ``serve.Generator`` on the Pallas decode rung, checked against the
  strict baseline rung, then through ``serve.ContinuousEngine``.

Any failed check raises, so the exit code is non-zero and the last line
is never printed. Sizes, losses, tokens, timings (host wall-clock of a
phase, compilation included — not a rate) and compile-cache counters go
on earlier lines; the last line of stdout is exactly::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage::

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --dp4      # four chips: dp=4 training against dp=1
    python chip_smoke.py --rehearse [--dp4]
        # sandbox rehearsal: tiny presets, the kernel in interpret mode,
        # any platform allowed; same code paths, never reports "tpu"
        # unless it really ran there

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``.jax_cache`` next to this script; a second run in the same checkout
reports ``disk_hits`` and names every executable that still missed.
"""
import argparse
import gc
import json
import logging
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# Llama-3-8B at f32 is 0.81 GiB a layer beside 3.9 GiB of embedding and
# head; one batch-8, 2048-long f32 KV ring set is 0.125 GiB a layer and
# three are alive inside a decode step (the shared zero rings, the step's
# input and its output), beside ~1.2 GiB of prefill temporaries. A v5e
# gives 15.75 GiB: six layers peaked at 11.84 GiB and seven at 13.16 GiB
# (measured); eight would come to ~14.4 GiB, too close to be safe.
FULL = dict(
    bert=dict(), batch=64, seq=128, vocab=30000,
    llama="llama3_8b", llama_over=dict(num_layers=7), max_seq=2048,
    ref_max_seq=256, serve_batch=8, prompt_bucket=128, new_tokens=32,
    interpret=False)
REHEARSE = dict(
    bert=dict(units=64, hidden_size=128, num_layers=2, num_heads=4,
              vocab_size=1000, max_length=32),
    batch=8, seq=16, vocab=1000,
    llama="llama_tiny_test", llama_over={}, max_seq=64, ref_max_seq=32,
    serve_batch=8, prompt_bucket=16, new_tokens=8, interpret=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def say(**fields):
    print(json.dumps(fields), flush=True)


def platform_of(array):
    return sorted({d.platform for d in array.devices()})


class CacheMisses(logging.Filter):
    """Names of the executables the persistent cache did not hold. JAX
    says them only in debug records of its compiler's logger, so that
    logger is opened to them and this filter, having read the names,
    drops whatever the logger would not have passed before."""

    def __init__(self):
        super().__init__()
        self.names = []
        log = logging.getLogger("jax._src.compiler")
        self.level = log.getEffectiveLevel()
        log.setLevel(logging.DEBUG)
        log.addFilter(self)

    def filter(self, record):
        if "CACHE MISS for" in str(record.msg):
            self.names.append(str(record.args[0]))
        return record.levelno >= self.level


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def bert_pretrain(sz, seed):
    """BERT MLM+NSP pretraining pieces, as ``bench.py:_bert_setup``."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu import np as mnp
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.models.bert import BERTForPretrain, get_bert_model

    class PretrainStep(HybridBlock):
        def __init__(self, model):
            super().__init__()
            self.model = model

        def forward(self, tokens):
            valid_length = (tokens != 0).sum(axis=1)
            return self.model(tokens, valid_length=valid_length)

    mx.random.seed(seed)
    rng = np.random.RandomState(seed)
    batch, seq, vocab = sz["batch"], sz["seq"], sz["vocab"]
    net = PretrainStep(BERTForPretrain(
        get_bert_model("bert_12_768_12", **sz["bert"])))
    net.initialize()
    tokens = rng.randint(1, vocab, (batch, seq)).astype("int32")
    tokens[::4, seq - seq // 8:] = 0  # padded tails: the valid-length mask
    with autograd.predict_mode():
        net(mnp.array(tokens[:1, :16]))  # materializes deferred shapes
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def loss_fn(outs, labels):
        return ce(outs[0], labels[0]).mean() + ce(outs[1], labels[1]).mean()

    labels = (rng.randint(1, vocab, (batch, seq)).astype("int32"),
              rng.randint(0, 2, (batch,)).astype("int32"))
    return net, loss_fn, tokens, labels


# Twelve steps, not three: the bench configuration has no learning-rate
# warm-up, so Adam's first update moves every weight by the full 1e-4 and
# the loss overshoots before it falls (the host CPU backend at this size
# gives the same shape: 12.1, 14.0, 11.7, 11.9, 11.5, 11.5, 11.3, 11.3,
# 11.3, 11.1, 11.0, 11.0).
TRAIN_STEPS = 12


def falling(losses):
    """On one fixed batch every one of the last three losses is below the
    first (step-to-step monotony is not a property of Adam here)."""
    return max(losses[-3:]) < losses[0]


def train_steps(sz, seed, dp, steps=TRAIN_STEPS):
    """``steps`` ShardedTrainer steps on one fixed batch over a dp-wide
    mesh; returns (losses, last loss, trainer, placed batch)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu.parallel import ShardedTrainer, ShardingRules, make_mesh

    net, loss_fn, tokens, labels = bert_pretrain(sz, seed)
    mesh = make_mesh({"dp": dp})
    trainer = ShardedTrainer(net, loss_fn, "adam", {"learning_rate": 1e-4},
                             mesh=mesh,
                             rules=ShardingRules(default_axis=None),
                             dtype="bfloat16")
    sh = NamedSharding(mesh, P("dp"))
    data = jax.device_put(tokens, sh)
    labels = tuple(jax.device_put(a, sh) for a in labels)
    losses, last = [], None
    for _ in range(steps):
        last = trainer.step(data, labels)
        losses.append(float(last.asnumpy().reshape(-1)[0]))
    return losses, last, trainer, data


def phase_train(sz, seed):
    t0 = time.perf_counter()
    losses, last, trainer, _ = train_steps(sz, seed, dp=1)
    say(phase="train", model="bert_12_768_12", overrides=sz["bert"],
        batch=sz["batch"], seq=sz["seq"], optimizer="adam", amp="bfloat16",
        losses=losses, loss_platform=platform_of(last._data),
        wall_s=round(time.perf_counter() - t0, 1))
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(falling(losses), f"loss not falling: {losses}")
    return platform_of(last._data)


def phase_dp4(sz, seed):
    """Data-parallel training across four chips against one chip."""
    import jax

    t0 = time.perf_counter()
    l4, _, tr4, data = train_steps(sz, seed, dp=4)
    param = next(iter(tr4.params.values()))
    held = {str(d): (d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()[:4]}
    spread = dict(batch_devices=len(data.sharding.device_set),
                  param_devices=len(param.sharding.device_set),
                  batch_shard_shape=list(
                      data.addressable_shards[0].data.shape),
                  bytes_in_use=held)
    del tr4, data, param
    gc.collect()
    l1, _, _, _ = train_steps(sz, seed, dp=1)
    rel = [abs(a - b) / abs(b) for a, b in zip(l4, l1)]
    say(phase="dp4", losses_dp4=l4, losses_dp1=l1, rel_diff=rel, **spread,
        wall_s=round(time.perf_counter() - t0, 1))
    check(all(np.isfinite(l4 + l1)), "non-finite loss")
    # bf16 AMP: 8 mantissa bits, and a 4-way split reorders every batch
    # reduction and draws each shard's dropout mask separately
    check(max(rel) < 2 ** -8, f"dp=4 losses leave dp=1 beyond bf16: {rel}")
    check(falling(l4), f"dp=4 loss not falling: {l4}")
    check(spread["batch_devices"] == 4 and spread["param_devices"] == 4,
          f"batch/params do not span 4 devices: {spread}")
    check(spread["batch_shard_shape"][0] == sz["batch"] // 4,
          f"batch not split four ways: {spread}")
    # the CPU backend (rehearsal) keeps no memory statistics; a chip does,
    # and one that reports none has not been shown to hold anything
    if jax.devices()[0].platform != "cpu":
        check(all((b or 0) > 0 for b in held.values()),
              f"a device holds nothing: {held}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

# The fast rung against the strict one, max |logit difference|: the rung's
# documented tolerance (tests/test_decode_paths.py, verify skill round 10),
# on the chip as on the host. A float32 model multiplies in float32 on
# every rung (``ops.nn.stored_precision``).
RUNG_TOL = 1e-4
REF_STEPS = 4   # decode steps held to the reference beside the prefill


def phase_serve(sz, seed):
    import mxnet_tpu as mx
    from mxnet_tpu import serve
    from mxnet_tpu.models.llama import get_llama
    from mxnet_tpu.ops.pallas import decode_attention as da
    from mxnet_tpu.profiler import core as prof

    t0 = time.perf_counter()
    da.use_interpret(sz["interpret"])
    ctx = mx.tpu() if mx.num_tpus() else mx.cpu()
    mx.random.seed(seed)
    net = get_llama(sz["llama"], **sz["llama_over"])
    net.collect_params().setattr("grad_req", "null")  # inference: no grads
    net.initialize(ctx=ctx)
    params = list(net.collect_params().values())
    n_layers = len(net._blocks)
    say(phase="serve", model=sz["llama"], L=n_layers,
        reduced=sz["llama_over"], dtype=str(params[0].dtype),
        param_bytes=int(sum(np.prod(p.shape) * np.dtype(p.dtype).itemsize
                            for p in params)),
        max_seq=sz["max_seq"], batch=sz["serve_batch"],
        prompt_bucket=sz["prompt_bucket"], new_tokens=sz["new_tokens"],
        init_s=round(time.perf_counter() - t0, 1))

    rng = np.random.RandomState(seed)
    vocab = net.embed.weight.shape[0]
    bucket, b = sz["prompt_bucket"], sz["serve_batch"]
    prompts = [rng.randint(1, vocab, n).tolist()
               for n in rng.randint(bucket // 8, bucket + 1, b)]
    toks = np.zeros((b, bucket), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    lens = np.array([len(p) for p in prompts], np.int32)

    def fallbacks():
        return dict(fallback_count=da.fallback_count(),
                    decode_fallbacks=prof.get_counter(
                        "serve.decode_fallbacks"))

    def generator(path, max_seq):
        return serve.Generator(net, max_seq=max_seq, batch_buckets=(b,),
                               prompt_buckets=(bucket,), decode_path=path,
                               name=f"smoke_{path}")

    def forced_logits(g):
        """First-step logits of ``g`` and of REF_STEPS decode steps, each
        fed the token the Pallas rung generated there."""
        logits, cache = g.prefill(toks, lens, g._fresh_cache(b))
        trail = [logits.asnumpy()]
        for t in range(REF_STEPS):
            logits, cache = g.decode_step(
                np.array([o[t] for o in outs], np.int32), lens + t, cache)
            trail.append(logits.asnumpy())
        return np.stack(trail), logits, cache

    # -- the Pallas rung: warm up, generate, nothing recompiles -------------
    t1 = time.perf_counter()
    da.reset_fallbacks()
    gen = generator("pallas", sz["max_seq"])
    warm = gen.warmup()
    t2 = time.perf_counter()
    outs, _ = gen.generate(prompts, max_new_tokens=sz["new_tokens"])
    t3 = time.perf_counter()
    fast, logits, cache = forced_logits(gen)
    gen.assert_no_recompiles()
    kv = cache.flat()[0]._data
    kernel = dict(last_path=da.last_path(), **fallbacks())
    placed = platform_of(kv) + platform_of(logits._data)
    say(phase="serve.generator", decode_path=gen.decode_path,
        signatures=warm["signatures"], warmup_s=round(t2 - t1, 1),
        generate_s=round(t3 - t2, 2), kv_bytes=cache.nbytes(),
        tokens=[len(o) for o in outs], first_tokens=[o[:4] for o in outs],
        kv_platform=platform_of(kv), logits_platform=platform_of(logits._data),
        logits_shape=list(fast[0].shape), **kernel)
    check(all(len(o) == sz["new_tokens"] for o in outs), "short generation")
    check(fast[0].shape == (b, vocab) and np.isfinite(fast).all(),
          "logits not finite of shape (batch, vocab)")
    check(kernel == dict(last_path="pallas", fallback_count=0,
                         decode_fallbacks=0),
          f"the decode kernel did not serve: {kernel}")
    del cache, logits, kv
    gc.collect()

    # -- against the strict rung: prefill and decode-step logits ------------
    # The prefill of the Pallas rung is the einsum path (T > 1); its decode
    # steps are the kernel, so both are held to the reference. The
    # reference ring is short: the position mask makes everything past the
    # sequence unreadable, so logits do not depend on ring length, and the
    # strict rung's mul+reduce attention costs O(T*S*D) on the vector unit.
    t1 = time.perf_counter()
    ref = forced_logits(generator("baseline", sz["ref_max_seq"]))[0]
    diffs = [float(d) for d in np.abs(fast - ref).max(axis=(1, 2))]
    agree = int((fast.argmax(-1) == ref.argmax(-1)).sum())
    say(phase="serve.reference", decode_path="baseline",
        ref_max_seq=sz["ref_max_seq"], max_abs_diff_by_step=diffs,
        logit_scale=float(np.abs(ref).max()), tol=RUNG_TOL,
        argmax_agree=f"{agree}/{ref.shape[0] * b}",
        wall_s=round(time.perf_counter() - t1, 1))
    check(max(diffs) <= RUNG_TOL,
          f"pallas rung leaves baseline: {max(diffs)} > {RUNG_TOL}")
    gc.collect()

    # -- continuous batching on the same rung --------------------------------
    t1 = time.perf_counter()
    n_sub = 4
    with serve.ContinuousEngine(net, max_seq=sz["max_seq"], num_slots=b,
                                decode_path="pallas",
                                name="smoke_cb") as eng:
        futs = [eng.submit(p, max_new_tokens=sz["new_tokens"])
                for p in prompts[:n_sub]]
        got = [f.result(600)["tokens"] for f in futs]
        eng.assert_no_recompiles()
        pool_platform = platform_of(eng.pool.flat()[0]._data)
    # (last_path is not read here: it names the last attention *traced*,
    # and the engine's decode step reuses the Generator's traced kernel)
    kernel = fallbacks()
    say(phase="serve.continuous", submits=n_sub,
        identical=[g == o for g, o in zip(got, outs)],
        pool_platform=pool_platform, **kernel,
        wall_s=round(time.perf_counter() - t1, 1))
    check(got == outs[:n_sub], "ContinuousEngine tokens differ from Generator")
    check(kernel == dict(fallback_count=0, decode_fallbacks=0),
          f"the decode kernel gave way in the engine: {kernel}")
    return placed + pool_platform


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dp4", action="store_true",
                    help="four chips: dp=4 training against dp=1, only")
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox rehearsal: tiny sizes, any platform")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    device = dict(platform=devices[0].platform, kind=devices[0].device_kind,
                  count=len(devices))
    say(phase="device", **device)
    need = 4 if args.dp4 else 1
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: no TPU here (found {device}); the smoke test "
              "does not fall back to the host", file=sys.stderr)
        return 1
    if len(devices) < need:
        print(f"chip_smoke: need {need} device(s), found {len(devices)}",
              file=sys.stderr)
        return 1

    from mxnet_tpu import compile_cache

    misses = CacheMisses()
    compile_cache.enable(os.path.join(HERE, ".jax_cache"))
    sz = REHEARSE if args.rehearse else FULL
    t0 = time.perf_counter()
    if args.dp4:
        phase_dp4(sz, args.seed)
    else:
        placed = phase_train(sz, args.seed)
        gc.collect()
        placed += phase_serve(sz, args.seed)
        check(set(placed) == {device["platform"]},
              f"something computed off the {device['platform']}: {placed}")
    say(phase="memory", **{k: v for k, v in
                           (devices[0].memory_stats() or {}).items()
                           if k in ("peak_bytes_in_use", "bytes_limit")})
    say(phase="compile_cache", **compile_cache.stats(),
        missed=sorted(set(misses.names)),
        total_wall_s=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
