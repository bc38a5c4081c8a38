#!/usr/bin/env python
"""Fast Gradient Sign Method adversarial examples (the reference
``example/adversary`` notebook workflow): train a small classifier, then
take the gradient OF THE LOSS WITH RESPECT TO THE INPUT
(``x.attach_grad()`` — inputs are first-class tape leaves, same as
parameters) and perturb along its sign to flip predictions.

    python examples/adversary_fgsm.py
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
from mxnet_tpu.gluon import nn


def make_data(rng, n):
    """Two gaussian blobs rendered as 8x8 'images' (top vs bottom lit)."""
    imgs = rng.rand(n, 1, 8, 8).astype("float32") * 0.2
    labels = rng.randint(0, 2, n)
    for i, l in enumerate(labels):
        rows = slice(0, 4) if l == 0 else slice(4, 8)
        imgs[i, 0, rows] += 0.5
    return imgs, labels.astype("float32")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--epsilon", type=float, default=0.5)
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU (eager per-op dispatch on a TPU "
                         "is dispatch-bound)")
    args = ap.parse_args()

    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")

    rng = onp.random.RandomState(0)
    net = nn.HybridSequential()
    # Flatten, not global pooling: the class signal is WHERE the light is,
    # which a global average erases
    net.add(nn.Conv2D(8, 3, padding=1, activation="relu"),
            nn.MaxPool2D(2), nn.Flatten(), nn.Dense(2))
    net.initialize(init=mx.init.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-2})

    for step in range(args.steps):
        imgs, labels = make_data(rng, 64)
        x, y = mnp.array(imgs), mnp.array(labels)
        with autograd.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
        trainer.step(64)
    imgs, labels = make_data(rng, 256)
    with autograd.predict_mode():
        acc = (net(mnp.array(imgs)).asnumpy().argmax(1) == labels).mean()
    print(f"clean accuracy: {acc:.3f}")
    assert acc > 0.95, "classifier failed to train"

    # FGSM: x_adv = x + eps * sign(dL/dx)
    x = mnp.array(imgs)
    x.attach_grad()
    with autograd.record():
        loss = loss_fn(net(x), mnp.array(labels)).mean()
    loss.backward()
    x_adv = x + args.epsilon * mx.nd.sign(x.grad)
    with autograd.predict_mode():
        adv_acc = (net(x_adv).asnumpy().argmax(1) == labels).mean()
    print(f"adversarial accuracy (eps={args.epsilon}): {adv_acc:.3f}")
    assert adv_acc < acc - 0.2, (
        "FGSM failed to find adversarial directions — input gradients "
        "may be broken")
    print(f"FGSM dropped accuracy by {acc - adv_acc:.3f}")


if __name__ == "__main__":
    main()
