"""Aux subsystem tests: symbol, custom ops, test_utils, amp, profiler,
runtime, dlpack, image, probability, estimator (SURVEY.md §2.4/§5 parity)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu import np as mnp


# -- symbol ---------------------------------------------------------------

def test_symbol_compose_eval():
    a, b = mx.sym.var("a"), mx.sym.var("b")
    c = (a + b) * a
    out = c.eval(a=mnp.array([1.0, 2.0]), b=mnp.array([3.0, 4.0]))
    np.testing.assert_allclose(out[0].asnumpy(), [4.0, 12.0])


def test_symbol_infer_shape_and_bind_backward():
    d = mx.sym.FullyConnected(mx.sym.var("a"), mx.sym.var("w"),
                              mx.sym.var("bias"), num_hidden=3)
    _, out_shapes, _ = d.infer_shape(a=(2, 4), w=(3, 4), bias=(3,))
    assert out_shapes == [(2, 3)]
    ex = d.bind(args={"a": mnp.array(np.ones((2, 4), "float32")),
                      "w": mnp.array(np.ones((3, 4), "float32")),
                      "bias": mnp.array(np.zeros(3, "float32"))})
    ex.forward(is_train=True)
    ex.backward()
    np.testing.assert_allclose(ex.grad_dict["a"].asnumpy(),
                               np.full((2, 4), 3.0))


def test_symbol_unknown_op():
    with pytest.raises(AttributeError):
        mx.sym.DefinitelyNotAnOp


# -- custom python ops ----------------------------------------------------

def test_custom_op_forward_backward():
    from mxnet_tpu import operator as op_mod

    @op_mod.register("test_square")
    class SquareProp(op_mod.CustomOpProp):
        def create_operator(self, ctx, shapes, dtypes):
            class Sq(op_mod.CustomOp):
                def forward(self, is_train, req, in_data, out_data, aux):
                    self.assign(out_data[0], req[0],
                                in_data[0] * in_data[0])

                def backward(self, req, out_grad, in_data, out_data,
                             in_grad, aux):
                    self.assign(in_grad[0], req[0],
                                2.0 * in_data[0] * out_grad[0])
            return Sq()

    x = mnp.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = op_mod.invoke("test_square", x)
    y.backward()
    np.testing.assert_allclose(y.asnumpy(), [1.0, 4.0, 9.0])
    np.testing.assert_allclose(x.grad.asnumpy(), [2.0, 4.0, 6.0])

    # non-uniform cotangent: catches element-wise iteration of the bare
    # single-output cotangent array
    x2 = mnp.array([1.0, 2.0, 3.0])
    x2.attach_grad()
    with autograd.record():
        y2 = op_mod.invoke("test_square", x2)
        l = (y2 * mnp.array([1.0, 10.0, 100.0])).sum()
    l.backward()
    np.testing.assert_allclose(x2.grad.asnumpy(), [2.0, 40.0, 600.0])


# -- test_utils -----------------------------------------------------------

def test_test_utils_assert_and_gradient():
    from mxnet_tpu import test_utils as tu

    tu.assert_almost_equal(np.array([1.0]), np.array([1.0]))
    with pytest.raises(AssertionError):
        tu.assert_almost_equal(np.array([1.0]), np.array([2.0]))
    tu.check_numeric_gradient(lambda a: (a * a).sum(),
                              [np.random.rand(3, 2)])
    tu.check_consistency(lambda a: (a * 2).sum(), [np.random.rand(4)])


# -- amp ------------------------------------------------------------------

def test_amp_convert_and_loss_scaler():
    from mxnet_tpu import amp

    net = gluon.nn.Dense(4)
    net.initialize()
    x = mnp.array(np.ones((2, 3), "float32"))
    net(x)
    wrapped = amp.convert_hybrid_block(net, "bfloat16")
    out = wrapped(x)
    assert str(out.dtype) == "float32"  # fp32 out, bf16 compute

    cast_net = gluon.nn.Dense(4)
    cast_net.initialize()
    cast_net(x)
    amp.convert_hybrid_block(cast_net, "bfloat16", cast_params=True)
    assert str(cast_net.weight.data().dtype) == "bfloat16"

    sc = amp.LossScaler(init_scale=8.0, scale_window=2)
    assert sc.update(overflow=True) and sc.loss_scale == 4.0
    assert not sc.update(False)
    assert not sc.update(False)
    assert sc.loss_scale == 8.0  # doubled after window clean steps


# -- profiler / runtime / dlpack / image ---------------------------------

def test_profiler_scope_and_dumps():
    from mxnet_tpu import profiler

    with profiler.scope("unit_test_op"):
        (mnp.ones((4, 4)) * 2).wait_to_read()
    table = profiler.dumps()
    assert "unit_test_op" in table


def test_runtime_features():
    from mxnet_tpu import runtime

    feats = runtime.Features()
    assert feats.is_enabled("XLA")
    assert feats.is_enabled("RING_ATTENTION")
    assert not feats.is_enabled("CUDA")


def test_dlpack_roundtrip():
    from mxnet_tpu import dlpack

    x = mnp.array(np.arange(6, dtype="float32").reshape(2, 3))
    back = dlpack.from_dlpack(x._data)
    np.testing.assert_allclose(back.asnumpy(), x.asnumpy())


def test_image_namespace(tmp_path):
    from mxnet_tpu import image, recordio

    img = (np.random.rand(20, 30, 3) * 255).astype("uint8")
    packed = recordio.pack_img(recordio.IRHeader(0, 1.0, 0, 0), img,
                               img_fmt=".png")
    _, payload = recordio.unpack(packed)
    dec = image.imdecode(payload)
    np.testing.assert_array_equal(dec.asnumpy(), img)
    resized = image.imresize(dec, 15, 10)
    assert resized.shape == (10, 15, 3)
    short = image.resize_short(dec, 10)
    assert min(short.shape[:2]) == 10
    normed = image.color_normalize(dec, mean=(127.5,) * 3, std=(127.5,) * 3)
    assert abs(float(normed.asnumpy().mean())) < 1.0


# -- probability ----------------------------------------------------------

def test_distributions_against_scipy():
    from scipy import stats

    from mxnet_tpu.gluon import probability as prob

    n = prob.Normal(loc=mnp.array([0.0, 1.0]), scale=mnp.array([1.0, 2.0]))
    np.testing.assert_allclose(
        n.log_prob(mnp.array([0.5, 0.5])).asnumpy(),
        stats.norm.logpdf([0.5, 0.5], [0, 1], [1, 2]), rtol=1e-5)
    g = prob.Gamma(shape=2.0, scale=3.0)
    np.testing.assert_allclose(
        float(g.log_prob(mnp.array(4.0)).asnumpy()),
        stats.gamma.logpdf(4.0, 2.0, scale=3.0), rtol=1e-5)
    mvn = prob.MultivariateNormal(
        loc=mnp.array([0.0, 0.0]),
        cov=mnp.array([[2.0, 0.3], [0.3, 1.0]]))
    np.testing.assert_allclose(
        float(mvn.log_prob(mnp.array([0.5, -0.2])).asnumpy()),
        stats.multivariate_normal.logpdf([0.5, -0.2], [0, 0],
                                         [[2, 0.3], [0.3, 1]]), rtol=1e-5)


def test_distribution_sampling_moments():
    from mxnet_tpu.gluon import probability as prob

    mx.random.seed(7)
    s = prob.Normal(2.0, 0.5).sample((4000,)).asnumpy()
    assert abs(s.mean() - 2.0) < 0.05
    assert abs(s.std() - 0.5) < 0.05
    b = prob.Bernoulli(prob=0.3).sample((4000,)).asnumpy()
    assert abs(b.mean() - 0.3) < 0.05


def test_kl_divergence_and_grad():
    from mxnet_tpu.gluon import probability as prob

    kl = prob.kl_divergence(prob.Normal(0.0, 1.0),
                            prob.Normal(0.0, 1.0))
    assert abs(float(kl.asnumpy())) < 1e-6
    x = mnp.array([0.5])
    x.attach_grad()
    with autograd.record():
        l = prob.Normal(0.0, 1.0).log_prob(x).sum()
    l.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), [-0.5], rtol=1e-5)
    with pytest.raises(mx.MXNetError):
        prob.kl_divergence(prob.Normal(0.0, 1.0),
                           prob.Gamma(1.0, 1.0))


def test_stochastic_block_collects_losses():
    from mxnet_tpu.gluon import probability as prob

    class VAEBlock(prob.StochasticBlock):
        def __init__(self):
            super().__init__()
            self.dense = gluon.nn.Dense(4, flatten=False)

        def forward(self, x):
            h = self.dense(x)
            self.add_loss(h.sum())
            return h

    blk = VAEBlock()
    blk.initialize()
    out = blk(mnp.array(np.ones((2, 3), "float32")))
    assert out.shape == (2, 4)
    assert len(blk.losses) == 1


# -- estimator ------------------------------------------------------------

def test_estimator_fit_and_early_stop():
    from mxnet_tpu.gluon.contrib.estimator import (EarlyStoppingHandler,
                                                   Estimator)
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    np.random.seed(0)
    X = np.random.randn(64, 10).astype("float32")
    Y = (X.sum(1) > 0).astype("int32")
    loader = DataLoader(ArrayDataset(X, Y), batch_size=16)
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(2))
    net.initialize()
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss())
    est.fit(loader, epochs=3)
    name, acc = est.train_metrics[0].get()
    assert name == "accuracy" and acc > 0.5

    stopper = EarlyStoppingHandler(monitor=est.train_loss_metric, patience=1)
    est.fit(loader, epochs=2, event_handlers=[stopper])


def test_estimator_checkpoint(tmp_path):
    from mxnet_tpu.gluon.contrib.estimator import (CheckpointHandler,
                                                   Estimator)
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    X = np.random.randn(32, 6).astype("float32")
    Y = np.random.randint(0, 2, (32,)).astype("int32")
    loader = DataLoader(ArrayDataset(X, Y), batch_size=8)
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(2))
    net.initialize()
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss())
    ckpt = CheckpointHandler(str(tmp_path), epoch_period=1)
    est.fit(loader, epochs=2, event_handlers=[ckpt])
    import os

    assert any(f.endswith(".params") for f in os.listdir(tmp_path))


def test_profiler_device_op_stats_parses_trace(tmp_path):
    """Per-op device table (reference aggregate_stats.cc role): parse a
    chrome trace with device pid rows carrying device_duration_ps /
    model_flops / bytes_accessed."""
    import gzip
    import json

    from mxnet_tpu import profiler

    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    events = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 3, "tid": 1, "ts": 0, "dur": 5, "name": "fusion.1",
         "args": {"device_duration_ps": "5000000",
                  "model_flops": "1000000", "bytes_accessed": "2048",
                  "hlo_category": "convolution fusion"}},
        {"ph": "X", "pid": 3, "tid": 1, "ts": 9, "dur": 5, "name": "fusion.1",
         "args": {"device_duration_ps": "5000000",
                  "model_flops": "1000000", "bytes_accessed": "2048",
                  "hlo_category": "convolution fusion"}},
        {"ph": "X", "pid": 9, "tid": 1, "ts": 0, "dur": 99,
         "name": "host_thing", "args": {}},
    ]
    with gzip.open(d / "vm.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    rows = profiler.device_op_stats(str(tmp_path))
    assert len(rows) == 1  # host events excluded
    r = rows[0]
    assert r["name"] == "fusion.1" and r["calls"] == 2
    assert abs(r["total_us"] - 10.0) < 1e-9
    assert r["flops"] == 2000000
    assert r["tflops_s"] > 0 and r["gb_s"] > 0
    table = profiler.device_op_table(str(tmp_path), by_category=True)
    assert "convolution fusion" in table


def test_profiler_device_op_stats_rows_carry_the_scope(tmp_path):
    """Two executables reuse an op name: the rows are kept apart by the
    scope path of the program's own names, and the table reads by block."""
    import gzip
    import json

    from mxnet_tpu import profiler

    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)

    def op(ts, dur_ps, tf_op):
        return {"ph": "X", "pid": 3, "tid": 1, "ts": ts, "dur": 1,
                "name": "fusion.140",
                "args": {"device_duration_ps": str(dur_ps), "tf_op": tf_op}}

    dec = "jit(fwd)/serve_step.decode/model/layer3/mixer/jit(f)/ssm.scan/mul"
    pre = "jit(fwd)/serve_step.prefill/model/layer3/attention/o_proj/" \
        "jit(f)/dot_general"
    events = [{"ph": "M", "pid": 3, "name": "process_name",
               "args": {"name": "/device:TPU:0"}},
              op(0, 2_000_000, dec), op(3, 2_000_000, dec),
              op(6, 7_000_000, pre),
              {"ph": "X", "pid": 3, "tid": 1, "ts": 9, "dur": 1,
               "name": "copy.1", "args": {"device_duration_ps": "1000000"}}]
    with gzip.open(d / "vm.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    rows = profiler.device_op_stats(str(tmp_path))
    assert [(r["name"], r["scope"], r["calls"]) for r in rows] == [
        ("fusion.140", "serve_step.prefill/model/layer3/attention/o_proj", 1),
        ("fusion.140", "serve_step.decode/model/layer3/mixer/ssm.scan", 2),
        ("copy.1", "", 1)]
    table = profiler.device_op_table(str(tmp_path), by_scope=True)
    assert "serve_step.decode/model/layer3/mixer/ssm.scan" in table
    assert "other" in table   # the event under no scope
