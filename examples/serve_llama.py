#!/usr/bin/env python
"""Serve a llama-family LM with the `mxnet_tpu.serve` stack.

Demonstrates the full serving vertical slice (SERVING.md):

* ``Generator`` — bucketed KV-cache autoregressive decode: prefill runs
  once per prompt bucket, then every generated token replays ONE
  compiled T=1 executable (no O(n^2) re-prefill);
* warmup compiles the whole (batch x prompt) bucket lattice up front, so
  the traffic loop below triggers **zero** XLA recompiles (asserted);
* ``DynamicBatcher`` — concurrent clients coalesce into batched
  generation calls, with deadline flush and admission control;
* ``serve::*`` SLO metrics — p50/p99 latency, tokens/s, occupancy.

Runs on TPU when a chip is visible, else CPU (~a minute for warmup on a
laptop-class CPU: 2 batch buckets x 2 prompt buckets + decode steps).

    python examples/serve_llama.py --max-new-tokens 24 --temperature 0.8
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu.models.llama import get_llama
from mxnet_tpu.serve import DynamicBatcher, Generator


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="llama_serve_12l_test",
                    help="model config name from models/llama.py")
    ap.add_argument("--max-new-tokens", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 samples through mx.random")
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--clients", type=int, default=8,
                    help="concurrent requests pushed through the batcher")
    args = ap.parse_args()

    mx.random.seed(0)
    net = get_llama(args.config)
    # the chip when there is one; the Generator serves where the model is
    net.initialize(ctx=mx.tpu() if mx.num_tpus() else mx.cpu())
    gen = Generator(net, max_seq=64, batch_buckets=(1, 4),
                    prompt_buckets=(16,))

    print(f"warming the bucket lattice "
          f"(batch {gen.batch_buckets} x prompt {gen.prompt_buckets})...")
    info = gen.warmup()
    print(f"  compiled {info['signatures']} executables "
          f"in {info['wall_s']:.1f}s\n")

    # -- single batched generate call -----------------------------------
    rng = onp.random.RandomState(0)
    vocab = net.embed.weight.shape[0]  # keep prompts in-vocabulary
    prompts = [rng.randint(1, vocab, size=n).tolist() for n in (5, 9, 12, 7)]
    outs, stats = gen.generate(prompts,
                               max_new_tokens=args.max_new_tokens,
                               temperature=args.temperature,
                               top_k=args.top_k)
    for p, o in zip(prompts, outs):
        print(f"  prompt {p[:4]}...({len(p)} toks) -> {o}")
    print(f"  prefill {stats['prefill_ms']:.1f}ms, "
          f"decode {stats['decode_ms']:.1f}ms "
          f"({stats['tokens_s']:.1f} tokens/s)\n")

    # -- concurrent clients through the DynamicBatcher ------------------
    def runner(batch_prompts):
        outs, _ = gen.generate(list(batch_prompts),
                               max_new_tokens=args.max_new_tokens,
                               temperature=args.temperature,
                               top_k=args.top_k)
        return outs

    t0 = time.perf_counter()
    with DynamicBatcher(runner, max_batch_size=4, timeout_ms=10.0,
                        max_queue=64, metrics=gen.metrics,
                        name="llama") as batcher:
        futs = [batcher.submit(
                    rng.randint(1, vocab,
                                size=int(rng.randint(4, 14))).tolist())
                for _ in range(args.clients)]
        done = [f.result(timeout=300) for f in futs]
    wall = time.perf_counter() - t0
    print(f"served {len(done)} concurrent requests in {wall:.1f}s")

    gen.assert_no_recompiles()  # steady state never compiled
    snap = gen.stats()
    print(f"  p50 {snap['p50_ms']:.1f}ms  p99 {snap['p99_ms']:.1f}ms  "
          f"occupancy {snap['batch_occupancy']:.2f}  "
          f"tokens/s {snap['tokens_s']:.1f}")
    print(f"  cache: {snap['cache']['signatures']} signatures, "
          f"{snap['cache']['serve_hits']} warm serve hits, "
          f"0 recompiles after warmup")
    return 0


if __name__ == "__main__":
    sys.exit(main())
