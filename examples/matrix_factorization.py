#!/usr/bin/env python
"""Matrix-factorization recommender (the reference
``example/recommenders`` workflow): user/item embeddings with
``sparse_grad=True`` — each step's gradient and update touch only the
rows in the batch (the O(nnz) row_sparse path, tests/test_sparse_compute
contract) — trained on a synthetic low-rank rating matrix.

    python examples/matrix_factorization.py --steps 150
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


class MFNet(gluon.block.HybridBlock):
    def __init__(self, n_users, n_items, k=16, **kwargs):
        super().__init__(**kwargs)
        self.user = nn.Embedding(n_users, k, sparse_grad=True)
        self.item = nn.Embedding(n_items, k, sparse_grad=True)

    def forward(self, users, items):
        u = self.user(users)
        v = self.item(items)
        return (u * v).sum(axis=-1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--users", type=int, default=100)
    ap.add_argument("--items", type=int, default=80)
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU (eager per-op dispatch on a TPU "
                         "is dispatch-bound)")
    args = ap.parse_args()

    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    rng = onp.random.RandomState(0)
    # ground-truth rank-4 ratings
    gu = rng.randn(args.users, 4).astype("float32")
    gi = rng.randn(args.items, 4).astype("float32")

    net = MFNet(args.users, args.items)
    net.initialize(init=mx.init.Normal(0.1))
    l2 = gluon.loss.L2Loss()
    # lazy_update: only rows present in the batch get momentum/updates
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.05, "lazy_update": True})

    first = last = None
    for step in range(args.steps):
        # sample WITHOUT replacement: the row_sparse gradient's nnz (the
        # unique-index count) is then the full batch size every step, so
        # the O(nnz) kernels keep ONE static shape and compile once —
        # varying nnz would recompile per step (TPU-first discipline:
        # static shapes; same reason detection ops pad to -1)
        u = rng.choice(args.users, args.batch, replace=False)
        i = rng.choice(args.items, args.batch, replace=False)
        r = (gu[u] * gi[i]).sum(axis=1)
        with autograd.record():
            pred = net(mnp.array(u.astype("int64")),
                       mnp.array(i.astype("int64")))
            loss = l2(pred, mnp.array(r)).mean()
        loss.backward()
        g = net.user.weight.grad()
        trainer.step(args.batch)
        v = float(loss.asnumpy())
        first = v if first is None else first
        last = v
        if step % 20 == 0:
            from mxnet_tpu.ndarray.sparse import RowSparseNDArray

            kind = ("row_sparse"
                    if isinstance(g, RowSparseNDArray) else "dense")
            print(f"step {step:3d} loss {v:8.4f}  user-grad: {kind}")

    print(f"loss {first:.4f} -> {last:.4f}")
    assert last < first * 0.5, "MF failed to learn the rating structure"

    # the gradient really is row-sparse and O(nnz)
    from mxnet_tpu.ndarray.sparse import RowSparseNDArray

    assert isinstance(net.user.weight.grad(), RowSparseNDArray)
    assert not net.user.weight.grad().is_materialized()
    print("sparse-grad contract held: grads stayed row_sparse")


if __name__ == "__main__":
    main()
