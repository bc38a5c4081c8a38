"""The warm call of ``CachedOp``: what is a function of the model alone (the
parameter walk, the parameter half of the signature key, the buffers) is
derived when something changed, not per call, and a program that reads no
RNG key is not made one. Every way the model can change has to show in the
next call, and every seeded stream has to stay what it was: the values
pinned below were printed by the tree before this change (commit 755bfa7)
running the same lines.
"""
import threading

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, cachedop, gluon, np, serve
from mxnet_tpu import random as mxrandom
from mxnet_tpu.cachedop import CachedOp, CachedOpThreadSafe
from mxnet_tpu.gluon.parameter import Parameter

# printed by commit 755bfa7 running the lines of tests (a) and (b)
PARENT_MARKS = (5, 5)
PARENT_DRAW = [0.8047661781311035, 0.833848237991333, 0.08779501914978027]
PARENT_MASKED = [
    [[0.48000001907348633] * 3, [1.3199999332427979] * 3],
    [[0.8400000333786011] * 3, [1.3199999332427979] * 3],
]
PARENT_DRAW_AFTER_DROPOUT = [0.4946770668029785, 0.9748251438140869,
                             0.48479974269866943]


def mlp(dropout=0.0, batchnorm=False):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, in_units=4))
    if batchnorm:
        net.add(gluon.nn.BatchNorm(in_channels=8))
    if dropout:
        net.add(gluon.nn.Dropout(dropout))
    net.add(gluon.nn.Dense(3, in_units=8))
    net.initialize(mx.init.Constant(0.1))
    net.hybridize()
    return net


def x_of(rows=2):
    return np.arange(rows * 4).reshape(rows, 4).astype("float32")


def stats(net):
    return net._cached_op.cache_stats()


# -- (a) no key for a program that draws none -----------------------------------

def test_warm_call_makes_no_key_and_streams_stay(monkeypatch):
    folds = [0]
    real = mxrandom.RandomState.next_key

    def counting(self):
        folds[0] += 1
        return real(self)

    monkeypatch.setattr(mxrandom.RandomState, "next_key", counting)
    mx.random.seed(7)
    consumed = mxrandom.consume_count()     # of the process: never reset
    net, x = mlp(), x_of()
    net(x)                      # builds: the trace is where a draw would show
    assert folds[0] == 1
    for _ in range(4):
        net(x)
    assert folds[0] == 1, "a warm call of a key-free program made a key"
    # ... while the stream advanced as if each call had made one
    assert (mxrandom.consume_count() - consumed,
            mxrandom._global_state._counter) == PARENT_MARKS
    assert stats(net)["keys_skipped"] == 4
    draw = np.random.uniform(size=(3,)).asnumpy().tolist()
    assert draw == PARENT_DRAW


# -- (b) a program that draws gets a fresh key each call --------------------------

def test_dropout_in_train_mode_redraws_and_matches_parent():
    mx.random.seed(7)
    net, x = mlp(dropout=0.5), x_of()
    for _ in range(5):
        net(x)                  # predict mode: dropout is the identity
    with autograd.train_mode():
        a, b = net(x).asnumpy(), net(x).asnumpy()
        c = net(x).asnumpy()
    assert not onp.array_equal(a, b) or not onp.array_equal(b, c)
    assert a.tolist() == PARENT_MASKED[0]
    assert b.tolist() == PARENT_MASKED[1]
    s = stats(net)
    assert s["keys_skipped"] == 4          # the predict-mode warm calls alone
    # two draws ago the parent's stream stood where this one stands
    mx.random.seed(7)
    net = mlp(dropout=0.5)
    for _ in range(5):
        net(x)
    with autograd.train_mode():
        net(x), net(x)
    assert np.random.uniform(size=(3,)).asnumpy().tolist() \
        == PARENT_DRAW_AFTER_DROPOUT


# -- (c) every way the model can change shows in the next call --------------------

def _set_data(net, x):
    net[0].weight.set_data(net[0].weight.data() * 2)


def _set_data_internal(net, x):
    # what an optimizer step and a written-back state do: the handle's
    # buffer is rebound with no Parameter method in between
    h = net[1].bias.data()
    h._set_data_internal((h + 1)._data)


def _cast(net, x):
    net.cast("float16")
    return x.astype("float16")


def _grad_req(net, x):
    net[0].weight.grad_req = "null"


def _load_parameters(net, x, tmp_path):
    other = mlp()
    other[1].weight.set_data(other[1].weight.data() * -3)
    f = str(tmp_path / "w.params")
    other.save_parameters(f)
    net.load_parameters(f)


def _force_reinit(net, x):
    net.initialize(mx.init.Constant(0.3), force_reinit=True)


def _child_added_with_deferred_init(net, x):
    net.add(gluon.nn.Dense(2))          # in_units unknown: init is deferred
    net.initialize(mx.init.Constant(0.2))
    net(x)                              # the eager pass that finishes it


def _reset_ctx(net, x):
    net.reset_ctx(mx.cpu(1))
    return x.as_in_context(mx.cpu(1))


def _share_parameters(net, x):
    donor = gluon.nn.Dense(8, in_units=4)
    donor.initialize(mx.init.Constant(0.7))
    net[0].share_parameters(donor.collect_params())


SITES = [_set_data, _set_data_internal, _cast, _grad_req, _load_parameters,
         _force_reinit, _child_added_with_deferred_init, _reset_ctx,
         _share_parameters]


@pytest.mark.parametrize("site", SITES, ids=lambda f: f.__name__.strip("_"))
def test_a_change_to_the_model_is_served_by_the_next_call(site, tmp_path):
    net, x = mlp(), x_of()
    for _ in range(3):
        net(x)
    op = net._cached_op
    assert stats(net)["fast_calls"] == 2
    args = (net, x, tmp_path) if site is _load_parameters else (net, x)
    moved = site(*args)
    x = x if moved is None else moved
    with autograd.pause():
        want = net.forward(x).asnumpy()         # the eager truth, afterwards
    before = stats(net)["fast_calls"]
    assert net._cached_op is op
    got = net(x).asnumpy()
    onp.testing.assert_allclose(got, want, rtol=1e-3 if site is _cast else 1e-6)
    assert got.shape == want.shape
    # that call derived the parameters anew; the one after it does not
    assert stats(net)["fast_calls"] == before
    net(x)
    assert stats(net)["fast_calls"] == before + 1


def test_a_warm_swap_is_served_by_the_next_visit():
    a, b, x = mlp(), mlp(), x_of()
    b[0].weight.set_data(b[0].weight.data() * 5)
    sess = serve.InferenceSession(a, batch_buckets=(2,), name="warm_swap")
    sess.warmup(x.asnumpy())
    for _ in range(3):
        sess.run(x)
    fast = sess.cache_stats()["fast_calls"]
    assert fast >= 3
    with autograd.predict_mode():
        want = b.forward(x).asnumpy()
    assert sess.swap(b) == "warm"
    onp.testing.assert_allclose(sess.run(x).asnumpy(), want, rtol=1e-6)
    sess.assert_no_recompiles()
    assert sess.cache_stats()["fast_calls"] == fast     # derived anew, once
    sess.run(x)
    assert sess.cache_stats()["fast_calls"] == fast + 1


def test_a_replica_scope_steps_around_the_snapshot():
    # inside replica_context Parameter.data() resolves by the thread's
    # scope: the snapshot of the first replica must not answer for it
    net, x = mlp(), x_of()
    net.reset_ctx([mx.cpu(0), mx.cpu(1)])
    net[0].weight._data[mx.cpu(1)]._set_data_internal(
        (net[0].weight._data[mx.cpu(1)] * 3)._data)
    first = net(x).asnumpy()
    net(x)
    with gluon.replica_context(mx.cpu(1)):
        x1 = x.as_in_context(mx.cpu(1))
        with autograd.pause():
            want = net.forward(x1).asnumpy()
        got = net(x1).asnumpy()
    onp.testing.assert_allclose(got, want, rtol=1e-6)
    assert not onp.allclose(got, first)
    onp.testing.assert_allclose(net(x).asnumpy(), first, rtol=1e-6)


def test_a_recording_call_leaves_no_snapshot_behind():
    # its weights are about to be stepped: a snapshot would keep the old
    # buffers alive beside the new ones until the next call
    net, x = mlp(), x_of()
    net(x), net(x)
    assert net._cached_op in cachedop._snapshots
    with autograd.record():
        loss = net(x).sum()
    assert net._cached_op not in cachedop._snapshots
    loss.backward()
    assert float(net[0].weight.grad().asnumpy().sum()) != 0.0


# -- (d) a written-back state is the next call's input ----------------------------

def test_batchnorm_running_stats_feed_the_next_call():
    hybrid, eager = mlp(batchnorm=True), mlp(batchnorm=True)
    eager.hybridize(False)
    rng = onp.random.RandomState(3)
    for _ in range(4):
        x = np.array(rng.randn(6, 4).astype("float32"))
        with autograd.train_mode():
            got, want = hybrid(x).asnumpy(), eager(x).asnumpy()
        onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        for name in ("running_mean", "running_var"):
            onp.testing.assert_allclose(
                getattr(hybrid[1], name).data().asnumpy(),
                getattr(eager[1], name).data().asnumpy(),
                rtol=1e-5, atol=1e-7)
    # the stats moved four times, so predict mode reads the fourth
    x = x_of(6)
    onp.testing.assert_allclose(hybrid(x).asnumpy(), eager(x).asnumpy(),
                                rtol=1e-5, atol=1e-6)
    assert float(abs(hybrid[1].running_mean.data().asnumpy()).sum()) > 0


# -- (e) unwarmed threads still trace once ----------------------------------------

def test_four_unwarmed_threads_trace_once():
    class Counted(gluon.nn.Dense):
        traces = 0

        def forward(self, x):
            type(self).traces += 1
            return super().forward(x)

    for _ in range(3):
        Counted.traces = 0
        net = Counted(2, in_units=2)
        net.initialize()
        op = CachedOpThreadSafe(net)
        gate = threading.Barrier(4)
        outs, errors = [], []

        def worker():
            try:
                gate.wait()
                with autograd.predict_mode():
                    outs.append(op(np.ones((1, 2))).asnumpy())
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[0]
        assert Counted.traces == 1
        assert op.cache_stats()["signatures"] == 1
        for o in outs[1:]:
            onp.testing.assert_allclose(o, outs[0], rtol=1e-6)


# -- (f) what a warm call may not do ----------------------------------------------

def test_warm_call_reads_no_parameter_and_walks_no_block(monkeypatch):
    calls = {"data": 0, "split": 0, "collect": 0}
    real_data, real_split = Parameter.data, CachedOp._split_params

    def data(self, ctx=None):
        calls["data"] += 1
        return real_data(self, ctx)

    def split(self):
        calls["split"] += 1
        return real_split(self)

    net, x = mlp(batchnorm=True), x_of()
    op = CachedOpThreadSafe(net)
    with autograd.predict_mode():
        op(x), op(x)
        monkeypatch.setattr(Parameter, "data", data)
        monkeypatch.setattr(CachedOp, "_split_params", split)
        for _ in range(5):
            op(x)
    assert calls == {"data": 0, "split": 0, "collect": 0}
    s = op.cache_stats()
    assert s["fast_calls"] == 6 and s["keys_skipped"] == 6
    assert s["hits"] == 6 and s["misses"] == 1
    # the process-wide aggregate carries both counters
    agg = cachedop.cache_stats()
    assert agg["fast_calls"] >= 6 and agg["keys_skipped"] >= 6


# -- (g) a weight written while other threads serve -------------------------------

def test_a_served_call_never_reads_weights_older_than_the_last_write():
    """One writer steps a one-parameter model through generations (the
    weight of generation k is k) while more readers than cores serve it
    through one thread-safe op. A call that began after generation g was
    written may see g or a later one, never an earlier: a snapshot that
    outlived a write, or one derived during a write and kept, would."""
    import sys
    import time

    net = gluon.nn.Dense(1, in_units=1, use_bias=False)
    net.initialize(mx.init.Constant(0.0))
    op = CachedOpThreadSafe(net)
    x = np.ones((1, 1))
    with autograd.predict_mode():
        op(x)
    gen, last, errors = [0], 150, []
    deadline = time.monotonic() + 60

    def reader():
        try:
            with autograd.predict_mode():
                while gen[0] < last and time.monotonic() < deadline:
                    g0 = gen[0]
                    got = float(op(x).asnumpy()[0, 0])
                    assert g0 <= got <= gen[0] + 1, (g0, got, gen[0])
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def writer():
        try:
            for k in range(1, last + 1):
                net.weight.set_data(np.full((1, 1), float(k)))
                gen[0] = k
        except Exception as e:  # noqa: BLE001
            errors.append(e)
            gen[0] = last

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=reader) for _ in range(16)]
        threads.append(threading.Thread(target=writer))
        for t in threads:
            t.start()
        for t in threads:
            t.join(90)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[0]
    assert gen[0] == last
    with autograd.predict_mode():
        assert float(op(x).asnumpy()[0, 0]) == last
    assert op.cache_stats()["signatures"] == 1
