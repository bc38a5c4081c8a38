"""Gluon Block/HybridBlock/Parameter/Trainer tests (reference test_gluon.py)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, np


def _mlp():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    return net


def test_parameter_lifecycle():
    p = gluon.Parameter("weight", shape=(3, 0))
    p.initialize()  # deferred: shape incomplete
    with pytest.raises(mx.gluon.parameter.DeferredInitializationError):
        p.data()
    p.shape = (3, 5)
    assert p.data().shape == (3, 5)
    assert p.grad().shape == (3, 5)
    p.set_data(np.ones((3, 5)))
    onp.testing.assert_allclose(p.data().asnumpy(), 1)


def test_collect_params_names():
    net = _mlp()
    names = list(net.collect_params())
    assert names == ["0.weight", "0.bias", "1.weight", "1.bias"]


def test_deferred_shape_inference():
    net = _mlp()
    net.initialize()
    out = net(np.ones((2, 7)))
    assert out.shape == (2, 4)
    assert net[0].weight.shape == (16, 7)


def test_hybridize_consistency():
    net = _mlp()
    net.initialize()
    x = np.array(onp.random.rand(3, 5).astype("float32"))
    eager = net(x).asnumpy()
    net.hybridize()
    hybrid = net(x).asnumpy()
    onp.testing.assert_allclose(eager, hybrid, rtol=1e-5, atol=1e-6)
    # gradient agreement
    w = net[0].weight
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    g_hybrid = w.grad().asnumpy().copy()
    net.hybridize(False)
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    onp.testing.assert_allclose(w.grad().asnumpy(), g_hybrid, rtol=1e-4,
                                atol=1e-6)


def test_hybridize_polymorphic_shapes():
    net = _mlp()
    net.initialize()
    net.hybridize()
    assert net(np.ones((2, 5))).shape == (2, 4)  # eager: finalizes shapes
    assert net(np.ones((8, 5))).shape == (8, 4)
    assert net(np.ones((3, 5))).shape == (3, 4)
    assert len(net._cached_op._cache) >= 2  # one compiled entry per signature


def test_batchnorm_state_updates_in_hybrid():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8), gluon.nn.BatchNorm())
    net.initialize()
    net.hybridize()
    x = np.array(onp.random.rand(4, 3).astype("float32"))
    with autograd.record():
        net(x)
    bn = net[1]
    rm = bn.running_mean.data().asnumpy()
    assert onp.abs(rm).sum() > 0


def test_trainer_sgd_momentum_matches_manual():
    w0 = onp.array([[1.0, 2.0]], dtype="float32")
    p = gluon.Parameter("w", shape=(1, 2))
    p.initialize()
    p.set_data(np.array(w0))
    tr = gluon.Trainer([p], "sgd", {"learning_rate": 0.1, "momentum": 0.9})
    g = onp.array([[0.5, -0.5]], dtype="float32")
    mom = onp.zeros_like(w0)
    w = w0.copy()
    for _ in range(3):
        p.grad()._set_data_internal(np.array(g)._data)
        tr.step(1)
        mom = 0.9 * mom - 0.1 * g
        w = w + mom
    onp.testing.assert_allclose(p.data().asnumpy(), w, rtol=1e-5)


def test_save_load_parameters(tmp_path):
    net = _mlp()
    net.initialize()
    net(np.ones((1, 6)))
    f = str(tmp_path / "mlp.params")
    net.save_parameters(f)
    net2 = _mlp()
    net2.initialize()
    net2(np.ones((1, 6)))
    net2.load_parameters(f)
    x = np.array(onp.random.rand(2, 6).astype("float32"))
    onp.testing.assert_allclose(net(x).asnumpy(), net2(x).asnumpy(), rtol=1e-6)


def test_losses_against_reference_math():
    pred = onp.random.randn(4, 5).astype("float32")
    label = onp.array([0, 2, 1, 4])
    l = gluon.loss.SoftmaxCrossEntropyLoss()(np.array(pred), np.array(label))
    # manual
    e = onp.exp(pred - pred.max(1, keepdims=True))
    p = e / e.sum(1, keepdims=True)
    want = -onp.log(p[onp.arange(4), label])
    onp.testing.assert_allclose(l.asnumpy(), want, rtol=1e-5)

    a = onp.random.rand(3, 2).astype("float32")
    b = onp.random.rand(3, 2).astype("float32")
    l2 = gluon.loss.L2Loss()(np.array(a), np.array(b)).asnumpy()
    onp.testing.assert_allclose(l2, ((a - b) ** 2 / 2).mean(1), rtol=1e-5)
    l1 = gluon.loss.L1Loss()(np.array(a), np.array(b)).asnumpy()
    onp.testing.assert_allclose(l1, onp.abs(a - b).mean(1), rtol=1e-5)


def test_metrics():
    m = gluon.metric.Accuracy()
    m.update(np.array([0, 1, 1]), np.array([[0.9, 0.1], [0.3, 0.7], [0.8, 0.2]]))
    assert m.get()[1] == pytest.approx(2 / 3)
    rmse = gluon.metric.RMSE()
    rmse.update(np.array([1.0, 2.0]), np.array([1.0, 4.0]))
    assert rmse.get()[1] == pytest.approx(onp.sqrt(2.0))
    comp = gluon.metric.create(["accuracy", "crossentropy"])
    comp.update(np.array([1]), np.array([[0.2, 0.8]]))
    names, vals = comp.get()
    assert len(names) == 2


def test_convergence_mlp():
    """End-to-end convergence (reference tests/python/train style)."""
    onp.random.seed(0)
    X = onp.random.randn(256, 10).astype("float32")
    w = onp.random.randn(10).astype("float32")
    y = (X @ w > 0).astype("float32")
    net = _mlp()
    net.initialize(init=mx.init.Xavier())
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tr = gluon.Trainer(net.collect_params(), "adam", {"learning_rate": 0.01})
    xb, yb = np.array(X), np.array(y)
    for _ in range(60):
        with autograd.record():
            l = loss_fn(net(xb), yb)
        l.backward()
        tr.step(256)
    acc = (net(xb).asnumpy().argmax(1) == y).mean()
    assert acc > 0.95


def test_conv_layers_shapes():
    x = np.ones((2, 3, 16, 16))
    c = gluon.nn.Conv2D(8, 3, padding=1)
    c.initialize()
    assert c(x).shape == (2, 8, 16, 16)
    ct = gluon.nn.Conv2DTranspose(4, 2, strides=2)
    ct.initialize()
    assert ct(c(x)).shape == (2, 4, 32, 32)
    p = gluon.nn.MaxPool2D(2)
    assert p(x).shape == (2, 3, 8, 8)
    g = gluon.nn.GlobalAvgPool2D()
    assert g(x).shape == (2, 3, 1, 1)


def test_summary_and_repr():
    net = _mlp()
    net.initialize()
    net(np.ones((1, 4)))
    text = net.summary(np.ones((1, 4)))
    assert "Dense" in text
    assert "Dense" in repr(net)


def test_export_symbolblock_roundtrip(tmp_path):
    net = _mlp()
    net.initialize()
    net.hybridize()
    x = np.array(onp.random.rand(2, 6).astype("float32"))
    want = net(x).asnumpy()
    prefix = str(tmp_path / "model")
    sym_file, param_file = net.export(prefix)
    loaded = gluon.SymbolBlock.imports(sym_file, param_file=param_file)
    got = loaded(x).asnumpy()
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_gluon_contrib_nn_layers():
    """contrib.nn: Concurrent branches, Identity, SparseEmbedding,
    PixelShuffle (reference gluon/contrib/nn/basic_layers.py)."""
    from mxnet_tpu.gluon.contrib import nn as cnn
    from mxnet_tpu.ndarray.sparse import RowSparseNDArray

    con = cnn.HybridConcurrent(axis=-1)
    con.add(gluon.nn.Dense(3), cnn.Identity(), gluon.nn.Dense(2))
    con.initialize()
    x = np.array(onp.random.randn(4, 5).astype("float32"))
    out = con(x)
    assert out.shape == (4, 3 + 5 + 2)

    ps = cnn.PixelShuffle2D(2)
    y = ps(np.array(onp.arange(32, dtype="float32").reshape(1, 8, 2, 2)))
    assert y.shape == (1, 2, 4, 4)
    # channel blocks interleave into space: exact layout oracle
    xin = onp.arange(16, dtype="float32").reshape(1, 4, 2, 2)
    got = cnn.PixelShuffle2D(2)(np.array(xin)).asnumpy()
    assert got.shape == (1, 1, 4, 4)
    # out[0,0,h*2+i, w*2+j] == xin[0, i*2+j, h, w]
    for h in range(2):
        for w in range(2):
            for i in range(2):
                for j in range(2):
                    assert got[0, 0, h * 2 + i, w * 2 + j] == \
                        xin[0, i * 2 + j, h, w]

    emb = cnn.SparseEmbedding(50, 4)
    emb.initialize()
    with autograd.record():
        emb(np.array(onp.array([1, 2], "int64"))).sum().backward()
    assert isinstance(emb.weight.grad(), RowSparseNDArray)


def test_hybridized_call_hands_back_only_rebound_state():
    """A frozen parameter (grad_req='null') is an input of the compiled
    forward, not an output: a call must not copy it (at Llama-8B widths
    that copy is the whole model, per call). State the forward does
    rebind — BatchNorm running statistics in train mode — still comes
    back and is written through."""
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(4, in_units=3), gluon.nn.BatchNorm(in_channels=4))
    net.initialize()
    net[0].collect_params().setattr("grad_req", "null")
    net.hybridize()
    x = mx.np.array(onp.arange(6, dtype="float32").reshape(2, 3))

    def buffers():
        return [p.data()._data.unsafe_buffer_pointer()
                for p in (net[0].weight, net[0].bias, net[1].running_mean)]

    before = buffers()
    with autograd.predict_mode():
        net(x).wait_to_read()
    assert buffers() == before
    mean = net[1].running_mean.data().asnumpy().copy()
    with autograd.record():
        net(x).wait_to_read()
    assert buffers()[:2] == before[:2]
    assert not onp.allclose(net[1].running_mean.data().asnumpy(), mean)
