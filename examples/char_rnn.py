#!/usr/bin/env python
"""Character-level RNN language model (the reference ``example/rnn``
workflow on the Gluon API): embedding → LSTM → per-step Dense, trained
with truncated BPTT over a synthetic corpus with learnable structure
(repeating key phrases), then sampled autoregressively.

    python examples/char_rnn.py --steps 60
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu import np as mnp
from mxnet_tpu.gluon import nn, rnn

CORPUS = ("the quick brown fox jumps over the lazy dog. "
          "pack my box with five dozen liquor jugs. ") * 40


class CharRNN(gluon.block.HybridBlock):
    def __init__(self, vocab, hidden=64, layers=1, **kwargs):
        super().__init__(**kwargs)
        self.embed = nn.Embedding(vocab, 16)
        self.lstm = rnn.LSTM(hidden, num_layers=layers)
        self.head = nn.Dense(vocab, flatten=False)

    def forward(self, x, state=None):
        # x: (T, B) int tokens -> logits (T, B, vocab)
        e = self.embed(x)
        if state is None:
            out = self.lstm(e)
        else:
            out, state = self.lstm(e, state)
        return self.head(out), state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--bptt", type=int, default=32)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU (eager per-op dispatch on a TPU "
                         "is dispatch-bound)")
    args = ap.parse_args()

    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")

    chars = sorted(set(CORPUS))
    stoi = {c: i for i, c in enumerate(chars)}
    data = onp.array([stoi[c] for c in CORPUS], onp.int32)

    net = CharRNN(len(chars))
    net.initialize(init=mx.init.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 5e-3})

    rng = onp.random.RandomState(0)
    first = last = None
    for step in range(args.steps):
        starts = rng.randint(0, len(data) - args.bptt - 1, args.batch)
        x = onp.stack([data[s:s + args.bptt] for s in starts], axis=1)
        y = onp.stack([data[s + 1:s + args.bptt + 1] for s in starts],
                      axis=1)
        with autograd.record():
            logits, _ = net(mnp.array(x))
            loss = loss_fn(logits.reshape(-1, len(chars)),
                           mnp.array(y.reshape(-1))).mean()
        loss.backward()
        trainer.step(args.batch)
        v = float(loss.asnumpy())
        first = v if first is None else first
        last = v
        if step % 10 == 0:
            print(f"step {step:3d} ppl {onp.exp(v):8.2f}")

    print(f"loss {first:.3f} -> {last:.3f}")
    assert last < first * 0.8, "char LM failed to learn"

    # autoregressive sampling: warm the state on the seed once, then feed
    # ONE token per step with the carried LSTM state — fixed (1, 1) input
    # shape means one compile, not one per sequence length
    seed = "the "
    idx = [stoi[c] for c in seed]
    with autograd.predict_mode():
        logits, state = net(mnp.array(
            onp.array(idx, onp.int32).reshape(-1, 1)))
        nxt = int(logits.asnumpy()[-1, 0].argmax())
        for _ in range(40):
            idx.append(nxt)
            logits, state = net(
                mnp.array(onp.array([[nxt]], onp.int32)), state)
            nxt = int(logits.asnumpy()[-1, 0].argmax())
    text = "".join(chars[i] for i in idx)
    print("sample:", repr(text))


if __name__ == "__main__":
    main()
