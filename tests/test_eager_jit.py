"""Eager per-op jit cache (SURVEY §7 hard part 2: the `SetShapeType`
signature-cache role, done the XLA way — one compiled executable per
(op, static config), reused across imperative calls)."""
import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu import np
from mxnet_tpu.ops import registry


def _cache_delta(fn, *calls):
    before = registry.eager_jit_cache_size()
    outs = [fn(*c) for c in calls]
    return registry.eager_jit_cache_size() - before, outs


def test_repeat_op_hits_cache():
    a = np.array(onp.random.randn(8, 8).astype("float32"))
    registry._EAGER_JIT_CACHE.clear()
    np.tanh(a)
    n1 = registry.eager_jit_cache_size()
    assert n1 >= 1
    for _ in range(5):
        np.tanh(a)
    assert registry.eager_jit_cache_size() == n1  # no growth: hits
    out = np.tanh(a).asnumpy()
    onp.testing.assert_allclose(out, onp.tanh(a.asnumpy()), rtol=1e-6)


def test_distinct_static_config_distinct_entries():
    a = np.array(onp.random.randn(4, 6).astype("float32"))
    registry._EAGER_JIT_CACHE.clear()
    s0 = np.sum(a, axis=0)
    n1 = registry.eager_jit_cache_size()
    s1 = np.sum(a, axis=1)
    n2 = registry.eager_jit_cache_size()
    assert n2 > n1  # axis is static config -> its own executable
    onp.testing.assert_allclose(s0.asnumpy(), a.asnumpy().sum(0),
                                rtol=1e-6)
    onp.testing.assert_allclose(s1.asnumpy(), a.asnumpy().sum(1),
                                rtol=1e-6)


def test_rng_ops_never_cached_and_stay_random():
    """Dropout draws a key per call; a cached trace would freeze the mask."""
    from mxnet_tpu.ops import nn as _nn

    a = np.ones((64, 64))
    with autograd.train_mode():
        d1 = _nn.dropout(a, p=0.5).asnumpy()
        d2 = _nn.dropout(a, p=0.5).asnumpy()
    assert (d1 != d2).any(), "dropout mask froze: RNG op was jit-cached"


def test_grad_through_cached_op():
    a = np.array(onp.random.randn(5, 5).astype("float32"))
    a.attach_grad()
    np.exp(a)  # populate cache
    with autograd.record():
        y = np.exp(a)
    y.backward()
    onp.testing.assert_allclose(a.grad.asnumpy(),
                                onp.exp(a.asnumpy()), rtol=1e-5)


def test_disable_flag():
    registry.set_eager_jit(False)
    try:
        registry._EAGER_JIT_CACHE.clear()
        a = np.array(onp.ones((3, 3), "float32"))
        np.tanh(a)
        assert registry.eager_jit_cache_size() == 0
    finally:
        registry.set_eager_jit(True)


def test_cached_vjp_matches_eager_backward():
    """A verified-cacheable op's backward runs through the compiled-vjp
    cache (registry._EAGER_BWD_CACHE); gradients must match the eager
    jax.vjp path bit-for-bit-ish across repeated steps."""
    from mxnet_tpu import gluon

    def run_steps(flag):
        import mxnet_tpu as mx

        registry.set_eager_jit(flag)
        registry._EAGER_JIT_CACHE.clear()
        registry._EAGER_BWD_CACHE.clear()
        mx.random.seed(11)  # identical init weights across both runs
        rng = onp.random.RandomState(7)
        net = gluon.nn.Dense(4)
        net.initialize()
        x = np.array(rng.randn(8, 6).astype("float32"))
        grads = []
        for _ in range(3):  # step 1 = first-encounter path, 2-3 = cached
            with autograd.record():
                l = (net(x) ** 2).sum()
            l.backward()
            grads.append(net.weight.grad().asnumpy().copy())
        return grads

    try:
        cached = run_steps(True)
        # the cached-vjp path must actually have been exercised
        assert len(registry._EAGER_BWD_CACHE) > 0
        eager = run_steps(False)
    finally:
        registry.set_eager_jit(True)
    for c, e in zip(cached, eager):
        onp.testing.assert_allclose(c, e, rtol=1e-5, atol=1e-6)


def test_cached_vjp_int_input_gets_no_cotangent():
    """float0 cotangents (int inputs) must not leak out of the compiled
    vjp — embedding-style gather: grad flows to the table, not indices."""
    emb = np.array(onp.random.randn(10, 4).astype("float32"))
    idx = np.array(onp.array([1, 3, 3], "int64"))
    emb.attach_grad()
    for _ in range(2):  # second pass hits the cached fwd + compiled vjp
        with autograd.record():
            y = np.take(emb, idx, axis=0)
        y.backward()
    g = emb.grad.asnumpy()
    assert g[3].sum() != 0 and g[0].sum() == 0
