"""Engine facade: wait points, the eager jit cache's clear counter, and
``bulk`` / ``set_bulk_size`` as the hints they are upstream.

``engine.bulk(N)`` is a hint to the reference's threaded engine
(``engine.h:311-317``); XLA's async dispatch replaces that engine, so a
scope keeps a per-thread integer and changes how no op runs: the same
seeded program gives bit-identical values and makes the same number of
dispatches inside a scope and outside it.
"""
import contextlib
import threading

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, engine, gluon
from mxnet_tpu import np
from mxnet_tpu.ops import registry
from mxnet_tpu.resilience import faults


# ---------------------------------------------------------------------------
# A bulk scope changes nothing a program can observe
# ---------------------------------------------------------------------------


def _forward_chain(scope):
    a = np.array(onp.arange(6.0, dtype="float32").reshape(2, 3))
    with scope():
        b = np.tanh((a + 1) * 2)
        c = (b @ b.T).sum(axis=0)
        return [b.asnumpy(), c.asnumpy()]


def _lenet_sgd_step(scope):
    rng = onp.random.RandomState(0)
    x = np.array(rng.randn(8, 1, 28, 28).astype("float32"))
    y = np.array(rng.randint(0, 10, (8,)).astype("int64"))
    mx.random.seed(7)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(6, 5, activation="relu"), gluon.nn.MaxPool2D(2),
            gluon.nn.Conv2D(16, 5, activation="relu"), gluon.nn.MaxPool2D(2),
            gluon.nn.Flatten(), gluon.nn.Dense(120, activation="relu"),
            gluon.nn.Dense(84, activation="relu"), gluon.nn.Dense(10))
    net.initialize()
    with autograd.predict_mode():
        net(x)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.05})
    with scope():
        with autograd.record():
            l = loss_fn(net(x), y).mean()
        l.backward()
        tr.step(1)
        loss = l.asnumpy()
    return [loss] + [v.data().asnumpy()
                     for _, v in sorted(net.collect_params().items())]


def _seeded_rng_draws(scope):
    from mxnet_tpu.ops import nn as _nn

    mx.random.seed(123)
    a = np.ones((16, 16))
    with scope():
        with autograd.train_mode():
            d = _nn.dropout(a * 1.0, p=0.5).asnumpy()
        r = np.random.uniform(size=(8,)).asnumpy()
    return [d, r]


def _write_through_slice_view(scope):
    a = np.array(onp.arange(12.0, dtype="float32").reshape(3, 4))
    with scope():
        v = a[1:3]
        v[:] = 7.0
        v += 1.0
        a[0, 1:3] = -1.0
        return [a.asnumpy(), v.asnumpy()]


def _pause_inside_record(scope):
    xv = onp.random.RandomState(5).rand(4).astype("float32") + 0.5
    x = np.array(xv)
    x.attach_grad()
    with scope():
        with autograd.record():
            y = x * x
            with autograd.pause():
                s = y * 3.0  # a constant on the tape
            z = (y * s).sum()
        z.backward()
        g = x.grad.asnumpy()
    # d/dx (y * const) = 2x * (3x^2) = 6x^3
    onp.testing.assert_allclose(g, 6 * xv ** 3, rtol=1e-5)
    return [g]


def _wait_all_beside_a_scope(scope):
    """Another thread is inside the scope while this one drains."""
    inside, drained, out = threading.Event(), threading.Event(), {}

    def worker():
        with scope():
            b = np.array(onp.ones((4,), "float32")) + 5
            inside.set()
            drained.wait(timeout=10)
            out["v"] = (b * 2).asnumpy()

    t = threading.Thread(target=worker)
    t.start()
    assert inside.wait(timeout=10)
    engine.wait_all()
    drained.set()
    t.join()
    onp.testing.assert_array_equal(out["v"], onp.full((4,), 12.0, "f4"))
    return [out["v"]]


@pytest.mark.parametrize("program", [
    _forward_chain, _lenet_sgd_step, _seeded_rng_draws,
    _write_through_slice_view, _pause_inside_record,
    _wait_all_beside_a_scope], ids=lambda f: f.__name__.lstrip("_"))
def test_bulk_scope_changes_nothing(program):
    def run(scope):
        before = engine.dispatch_count()
        values = program(scope)
        return values, engine.dispatch_count() - before

    run(contextlib.nullcontext)  # first visit of each op, on either arm
    plain, n_plain = run(contextlib.nullcontext)
    bulked, n_bulked = run(lambda: engine.bulk(16))
    assert n_plain > 0 and n_bulked == n_plain
    assert len(plain) == len(bulked)
    for p, b in zip(plain, bulked):
        onp.testing.assert_array_equal(p, b)


def test_bulk_size_is_thread_local():
    """A scope on one thread does not change what another thread's
    ``set_bulk_size`` returns."""
    seen = {}
    barrier = threading.Barrier(2)

    def bulky():
        with engine.bulk(64):
            barrier.wait()
            barrier.wait()
            seen["bulky"] = engine.set_bulk_size(64)

    def plain():
        engine.set_bulk_size(3)
        barrier.wait()          # the other thread is inside bulk(64)
        seen["plain"] = engine.set_bulk_size(0)
        barrier.wait()

    t1 = threading.Thread(target=bulky)
    t2 = threading.Thread(target=plain)
    t1.start(); t2.start(); t1.join(); t2.join()
    assert seen["bulky"] == 64, "another thread's size leaked in"
    assert seen["plain"] == 3, "bulk scope leaked across threads"


def test_set_bulk_size_returns_previous():
    prev = engine.set_bulk_size(32)
    try:
        assert engine.set_bulk_size(prev) == 32
    finally:
        engine.set_bulk_size(prev)


# ---------------------------------------------------------------------------
# Wait points surface injected async errors
# ---------------------------------------------------------------------------


def test_wait_for_var_fires_engine_wait_fault_site():
    """wait_for_var and wait_all both surface injected async errors
    (contract (c) of the engine's docstring)."""
    plan = faults.install_plan({"seed": 1, "rules": [
        {"site": "engine:wait", "kind": "fatal", "times": 1}]})
    try:
        a = np.array(onp.ones((2,), "float32"))
        with pytest.raises(mx.base.MXNetError):
            a.wait_to_read()
        assert plan.stats()[0]["fired"] == 1
    finally:
        faults.clear_plan()


# ---------------------------------------------------------------------------
# Registry cache-clear observability
# ---------------------------------------------------------------------------


def test_eager_jit_clear_counter_and_warning():
    stats = registry.cache_stats()
    assert set(stats) == {"size", "bwd_size", "skips", "clears", "limit"}
    before = stats["clears"]
    saved_max = registry._EAGER_JIT_MAX
    saved_clears = registry._EAGER_JIT_CLEARS
    try:
        registry._EAGER_JIT_MAX = registry.eager_jit_cache_size() + 1
        registry._EAGER_JIT_CLEARS = 0
        a = np.array(onp.ones((3,), "float32"))
        with pytest.warns(RuntimeWarning, match="runaway"):
            for i in range(4):  # distinct static configs force new entries
                np.sum(a * 1.0, axis=0)
                np.clip(a, 0.0, float(i + 2))
        assert registry.cache_stats()["clears"] >= 1
    finally:
        registry._EAGER_JIT_MAX = saved_max
        registry._EAGER_JIT_CLEARS = max(saved_clears, before)
