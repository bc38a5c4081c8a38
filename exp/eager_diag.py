"""Diagnose the eager per-op cost: python dispatch vs runtime vs device."""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as onp

sys.path.insert(0, "/root/repo")


def rate(fn, n=300, drain=None):
    fn()
    (drain or (lambda: None))()
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn()
    (drain or (lambda: onp.asarray(jax.tree_util.tree_leaves(r)[0]) if r is not None else None))()
    return (time.perf_counter() - t0) / n


x = jnp.ones((64, 128), jnp.float32)
f = jax.jit(lambda a: a + 1)

# 1. raw jitted-call dispatch rate, NO sync until end
per = rate(lambda: f(x), 300, drain=None)
print(f"jit call (async, drain at end): {per*1e6:.0f} us/call")

# 2. with a sync every call
per = rate(lambda: onp.asarray(f(x)[0, 0]), 30)
print(f"jit call + fetch every call:    {per*1e6:.0f} us/call")

# 3. the repo's registry.apply path (eager NDArray op)
from mxnet_tpu import np as mnp  # noqa: E402

a = mnp.ones((64, 128))
per = rate(lambda: a + 1, 300)
print(f"mx eager op (async):            {per*1e6:.0f} us/call")

# 4. LeNet fwd+bwd+step op count estimate: time one full eager step,
#    counting registry.apply invocations
from mxnet_tpu.ops import registry  # noqa: E402

count = [0]
orig = registry.apply


def counting_apply(*args, **kw):
    count[0] += 1
    return orig(*args, **kw)


registry.apply = counting_apply
from mxnet_tpu import autograd, gluon  # noqa: E402

net = gluon.nn.HybridSequential()
net.add(gluon.nn.Conv2D(6, 5, activation="relu"), gluon.nn.MaxPool2D(2),
        gluon.nn.Conv2D(16, 5, activation="relu"), gluon.nn.MaxPool2D(2),
        gluon.nn.Flatten(), gluon.nn.Dense(120, activation="relu"),
        gluon.nn.Dense(84, activation="relu"), gluon.nn.Dense(10))
net.initialize()
xx = mnp.array(onp.random.randn(64, 1, 28, 28).astype("float32"))
yy = mnp.array(onp.random.randint(0, 10, (64,)))
with autograd.predict_mode():
    net(xx)
loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.05})


def step():
    with autograd.record():
        l = loss_fn(net(xx), yy).mean()
    l.backward()
    tr.step(1)
    return l


float(step().asnumpy())
count[0] = 0
t0 = time.perf_counter()
l = step()
n_ops = count[0]
t_host = time.perf_counter() - t0
float(l.asnumpy())
t_total = time.perf_counter() - t0
print(f"lenet step: {n_ops} registry.apply calls, host-side {t_host*1e3:.1f} "
      f"ms, total w/ drain {t_total*1e3:.1f} ms")
registry.apply = orig
