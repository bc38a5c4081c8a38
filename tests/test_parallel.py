"""Parallelism tests on the virtual 8-device mesh: ShardedTrainer (dp/tp),
ring attention (sp). The SURVEY.md §2.3 'absent in reference' list — built
fresh here."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.parallel import (ShardedTrainer, ShardingRules, make_mesh)
from mxnet_tpu.parallel.ring_attention import ring_attention, sequence_sharded
from mxnet_tpu.ops.pallas.flash_attention import _reference_attention


def _mlp():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(64, activation="relu"),
            gluon.nn.BatchNorm(),
            gluon.nn.Dense(32, activation="relu"),
            gluon.nn.Dense(10))
    net.initialize()
    with autograd.predict_mode():
        net(mx.np.array(np.zeros((2, 20), dtype="float32")))
    return net


def test_sharded_trainer_dp_tp_converges():
    mesh = make_mesh({"dp": 4, "tp": 2})
    rules = ShardingRules([(r"2\.weight", P("tp", None))], default_axis=None)
    net = _mlp()
    tr = ShardedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                        {"learning_rate": 1e-2}, mesh=mesh, rules=rules)
    np.random.seed(0)
    X = np.random.randn(32, 20).astype("float32")
    Y = np.random.randint(0, 10, (32,))
    losses = [float(tr.step(X, Y).asnumpy()) for _ in range(15)]
    assert losses[-1] < losses[0] * 0.5
    p = tr.params["2.weight"]
    assert p.sharding.spec == P("tp", None)
    assert p.addressable_shards[0].data.shape == (16, 64)
    tr.sync_to_block()  # weights flow back into the Block
    assert np.allclose(np.asarray(tr.params["2.weight"]),
                       net.collect_params()["2.weight"].data().asnumpy())


def test_sharded_trainer_matches_eager_sgd():
    """One SPMD sgd step == one eager Trainer step (same weights/batch)."""
    mesh = make_mesh({"dp": 8})
    net_a = _mlp()
    net_b = _mlp()
    # copy a's weights into b
    pa, pb = net_a.collect_params(), net_b.collect_params()
    for n in pa:
        pb[n].set_data(pa[n].data())
    X = np.random.randn(16, 20).astype("float32")
    Y = np.random.randint(0, 10, (16,))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    tr_a = ShardedTrainer(net_a, loss_fn, "sgd", {"learning_rate": 0.1},
                          mesh=mesh, rules=ShardingRules(default_axis=None))
    tr_a.step(X, Y)
    tr_a.sync_to_block()

    tr_b = gluon.Trainer(net_b.collect_params(), "sgd",
                         {"learning_rate": 0.1})
    with autograd.record():
        # eager loss uses mean to match the SPMD step's jnp.mean
        l = loss_fn(net_b(mx.np.array(X)), mx.np.array(Y)).mean()
    l.backward()
    tr_b.step(1)

    for n in pa:
        np.testing.assert_allclose(pa[n].data().asnumpy(),
                                   pb[n].data().asnumpy(), rtol=2e-5,
                                   atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    mesh = make_mesh({"sp": 8})
    np.random.seed(1)
    q = np.random.randn(2, 4, 64, 16).astype("float32")
    k = np.random.randn(2, 4, 64, 16).astype("float32")
    v = np.random.randn(2, 4, 64, 16).astype("float32")
    qs = sequence_sharded(jnp.asarray(q), mesh)
    ks = sequence_sharded(jnp.asarray(k), mesh)
    vs = sequence_sharded(jnp.asarray(v), mesh)
    out = ring_attention(qs, ks, vs, mesh=mesh, causal=causal)
    ref = _reference_attention(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), causal=causal)
    assert out.sharding.spec == P(None, None, "sp", None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


def test_ring_attention_grad_flows():
    mesh = make_mesh({"sp": 4})
    q = sequence_sharded(jnp.asarray(
        np.random.randn(1, 2, 32, 8).astype("float32")), mesh)

    def loss(q_):
        return ring_attention(q_, q_, q_, mesh=mesh, causal=True).sum()

    g = jax.grad(loss)(q)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).sum()) > 0


def test_ring_attention_rejects_bad_axis():
    mesh = make_mesh({"dp": 8})
    x = jnp.zeros((1, 1, 8, 4))
    with pytest.raises(mx.MXNetError):
        ring_attention(x, x, x, mesh=mesh, axis="sp")


def test_fsdp_zero_shards_memory_and_matches_dp():
    """ZeRO/fsdp (SURVEY §2.3 'design fresh'): params + optimizer state
    sharded over the data axis, XLA all-gathers weights at their use sites
    and reduce-scatters grads into the sharded update. Asserts (a) the
    collectives are really in the compiled step, (b) per-device param+state
    memory drops ~N×, (c) the loss trajectory matches pure dp."""
    def make_net():
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(256, activation="relu", use_bias=False),
                gluon.nn.Dense(256, activation="relu", use_bias=False),
                gluon.nn.Dense(8, use_bias=False))
        net.initialize()
        with autograd.predict_mode():
            net(mx.np.array(np.zeros((2, 64), dtype="float32")))
        return net

    np.random.seed(2)
    net_dp = make_net()
    net_fs = make_net()
    pd, pf = net_dp.collect_params(), net_fs.collect_params()
    for n in pd:
        pf[n].set_data(pd[n].data())
    X = np.random.randn(16, 64).astype("float32")
    Y = np.random.randint(0, 8, (16,))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = make_mesh({"dp": 8})

    tr_dp = ShardedTrainer(net_dp, loss_fn, "adam", {"learning_rate": 1e-2},
                           mesh=mesh, rules=ShardingRules(default_axis=None))
    # fsdp = the default rule sharding every param's largest dim over dp
    tr_fs = ShardedTrainer(net_fs, loss_fn, "adam", {"learning_rate": 1e-2},
                           mesh=mesh, rules=ShardingRules(default_axis="dp"))

    losses_dp = [float(tr_dp.step(X, Y).asnumpy()) for _ in range(5)]
    losses_fs = [float(tr_fs.step(X, Y).asnumpy()) for _ in range(5)]
    np.testing.assert_allclose(losses_dp, losses_fs, rtol=1e-4, atol=1e-5)

    # (a) gather-for-compute / scatter-for-update in the compiled program.
    # The CPU backend lowers reduce-scatter as all-reduce + dynamic-slice
    # (same sharded-grad semantics); TPU emits the fused reduce-scatter.
    hlo = tr_fs.step_hlo
    assert "all-gather" in hlo
    assert "reduce-scatter" in hlo or (
        "all-reduce" in hlo and "dynamic-slice" in hlo)
    # (b) params + adam (m, v) state per device: dp holds full copies,
    # fsdp holds 1/8 shards (all dims here divide 8)
    mem_dp = tr_dp.device_memory_bytes()
    mem_fs = tr_fs.device_memory_bytes()
    assert mem_fs < mem_dp / 6
    # (c) a param really is sharded
    w = tr_fs.params["0.weight"]
    assert w.addressable_shards[0].data.shape[0] * 8 == w.shape[0]


def test_step_n_matches_sequential_steps():
    """One fused scan window == the same steps dispatched one by one
    (bulk-exec semantics, engine.h:311-317)."""
    np.random.seed(4)
    net_a = _mlp()
    net_b = _mlp()
    pa, pb = net_a.collect_params(), net_b.collect_params()
    for n in pa:
        pb[n].set_data(pa[n].data())
    mesh = make_mesh({"dp": 8})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tr_a = ShardedTrainer(net_a, loss_fn, "sgd",
                          {"learning_rate": 0.05, "momentum": 0.9},
                          mesh=mesh, rules=ShardingRules(default_axis=None))
    tr_b = ShardedTrainer(net_b, loss_fn, "sgd",
                          {"learning_rate": 0.05, "momentum": 0.9},
                          mesh=mesh, rules=ShardingRules(default_axis=None))
    X = np.random.randn(4, 16, 20).astype("float32")
    Y = np.random.randint(0, 10, (4, 16))
    losses_fused = tr_a.step_n(X, Y).asnumpy()
    losses_seq = [float(tr_b.step(X[i], Y[i]).asnumpy()) for i in range(4)]
    np.testing.assert_allclose(losses_fused, losses_seq, rtol=1e-5,
                               atol=1e-6)
    for n in tr_a.params:
        np.testing.assert_allclose(
            np.asarray(tr_a.params[n]), np.asarray(tr_b.params[n]),
            rtol=2e-5, atol=2e-5)


def test_step_n_then_step_interleave():
    """step_n and step share optimizer bookkeeping (update counts)."""
    net = _mlp()
    mesh = make_mesh({"dp": 8})
    tr = ShardedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                        {"learning_rate": 1e-2}, mesh=mesh,
                        rules=ShardingRules(default_axis=None))
    X = np.random.randn(3, 8, 20).astype("float32")
    Y = np.random.randint(0, 10, (3, 8))
    tr.step_n(X, Y)
    loss = tr.step(X[0], Y[0])
    assert np.isfinite(float(loss.asnumpy()))
    assert tr._step_count == 4


def test_step_n_validates_num_steps_and_keeps_flops_per_step():
    net = _mlp()
    mesh = make_mesh({"dp": 8})
    tr = ShardedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                        {"learning_rate": 0.05}, mesh=mesh,
                        rules=ShardingRules(default_axis=None))
    X = np.random.randn(3, 8, 20).astype("float32")
    Y = np.random.randint(0, 10, (3, 8))
    with pytest.raises(mx.MXNetError, match="num_steps"):
        tr.step_n(X, Y, num_steps=5)  # only 3 stacked batches
    with pytest.raises(mx.MXNetError, match="num_steps"):
        tr.step_n(X, Y, num_steps=0)
    tr.step_n(X, Y, num_steps=2)
    assert tr._step_count == 2
    flops_window = tr.step_flops
    tr.step(X[0], Y[0])
    # the property stays per-step across both paths
    assert abs(tr.step_flops - flops_window) / tr.step_flops < 0.2


def test_ulysses_attention_matches_reference():
    """All-to-all sequence parallelism == single-device attention, incl.
    causal; sharding preserved (T stays sharded on sp)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas.flash_attention import _reference_attention
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.ring_attention import (
        sequence_sharded,
        ulysses_attention,
    )

    mesh = make_mesh({"sp": 4})
    rng = np.random.RandomState(0)
    B, H, T, D = 2, 8, 32, 16
    q = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.3)
    for causal in (False, True):
        qs = sequence_sharded(q, mesh)
        ks = sequence_sharded(k, mesh)
        vs = sequence_sharded(v, mesh)
        got = ulysses_attention(qs, ks, vs, mesh=mesh, causal=causal)
        want = _reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                    rtol=2e-4, atol=2e-5)


def test_sharded_trainer_checkpoint_resume(tmp_path):
    """save_checkpoint/load_checkpoint: bit-exact resume of the SPMD
    training trajectory (params + Adam state + step count) across a new
    trainer instance, with shardings restored."""
    mesh = make_mesh({"dp": 2, "fsdp": 4})
    rng = np.random.RandomState(4)
    X = rng.randn(8, 16).astype("float32")
    Y = rng.randn(8, 8).astype("float32")

    def build():
        mx.random.seed(17)
        net = gluon.nn.Dense(8, flatten=False)
        net.initialize()
        with autograd.predict_mode():
            net(mx.np.array(np.zeros((1, 16), "float32")))
        return ShardedTrainer(net, gluon.loss.L2Loss(), "adam",
                              {"learning_rate": 1e-2}, mesh=mesh,
                              rules=ShardingRules())

    tr = build()
    for _ in range(2):
        tr.step(X, Y)
    ckpt = str(tmp_path / "state.ckpt")
    tr.save_checkpoint(ckpt)
    cont = [float(tr.step(X, Y).asnumpy().reshape(-1)[0])
            for _ in range(2)]

    tr2 = build()
    tr2.load_checkpoint(ckpt)
    resumed = [float(tr2.step(X, Y).asnumpy().reshape(-1)[0])
               for _ in range(2)]
    np.testing.assert_allclose(resumed, cont, rtol=1e-6)
    # shardings restored, not gathered-to-one-device
    any_sharded = any(
        len(a.sharding.device_set) > 1 for a in tr2.params.values())
    assert any_sharded


def test_checkpoint_rejects_mismatched_optimizer():
    mesh = make_mesh({"dp": 8})

    def build(opt):
        mx.random.seed(17)
        net = gluon.nn.Dense(8, flatten=False)
        net.initialize()
        with autograd.predict_mode():
            net(mx.np.array(np.zeros((1, 16), "float32")))
        return ShardedTrainer(net, gluon.loss.L2Loss(), opt,
                              {"learning_rate": 1e-2}, mesh=mesh,
                              rules=ShardingRules(default_axis=None))

    import pytest as _pytest

    tr = build("adam")
    tr.step(np.zeros((8, 16), "float32"), np.zeros((8, 8), "float32"))
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        ckpt = d + "/s.ckpt"
        tr.save_checkpoint(ckpt)
        tr2 = build("sgd")
        with _pytest.raises(mx.MXNetError, match="optimizer"):
            tr2.load_checkpoint(ckpt)


def test_checkpoint_restores_rng_stream(tmp_path):
    """A model WITH dropout resumes the exact loss trajectory: the RNG
    key is part of the checkpoint."""
    mesh = make_mesh({"dp": 2})
    X = np.random.RandomState(1).randn(8, 16).astype("float32")
    Y = np.zeros((8, 8), "float32")

    def build():
        mx.random.seed(23)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(32, flatten=False), gluon.nn.Dropout(0.5),
                gluon.nn.Dense(8, flatten=False))
        net.initialize()
        with autograd.predict_mode():
            net(mx.np.array(np.zeros((1, 16), "float32")))
        return ShardedTrainer(net, gluon.loss.L2Loss(), "sgd",
                              {"learning_rate": 1e-2}, mesh=mesh,
                              rules=ShardingRules(default_axis=None))

    tr = build()
    for _ in range(2):
        tr.step(X, Y)
    ckpt = str(tmp_path / "rng.ckpt")
    tr.save_checkpoint(ckpt)
    cont = [float(tr.step(X, Y).asnumpy().reshape(-1)[0]) for _ in range(3)]
    tr2 = build()
    tr2.load_checkpoint(ckpt)
    resumed = [float(tr2.step(X, Y).asnumpy().reshape(-1)[0])
               for _ in range(3)]
    np.testing.assert_allclose(resumed, cont, rtol=1e-6)


# -- the optimizer's scalars reach the compiled step as two float32 arrays --

class _RecordingAdam(mx.optimizer.Adam):
    """Adam whose state also keeps the gradient its last update saw."""

    def create_state(self, index, weight):
        return super().create_state(index, weight) + (
            mx.nd.zeros(weight.shape, dtype=weight.dtype),)

    def _update_raw(self, p, g, states, lr, wd, t):
        p_new, (m, v) = super()._update_raw(p, g, states[:2], lr, wd, t)
        return p_new, (m, v, g)


class _HalvedFrom:
    """A scheduler: the optimizer's rate (it sets ``base_lr``) before
    update ``at``, half of it from there."""

    def __init__(self, at):
        self.base_lr, self.at = None, at

    def __call__(self, num_update):
        return self.base_lr if num_update < self.at else self.base_lr / 2


def _sq_loss(out, label):
    d = out - label
    return d * d


def _scalars_trainer(builder, optimizer, optimizer_params=None, seed=3,
                     dtype=None, mults=(), abstract=False):
    """Two Dense layers from ``seed`` under the pjit step (dp=4) or the
    shard_map step (dp=2 x tp=2, the first weight split over tp)."""
    from mxnet_tpu.parallel import ParallelConfig

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu", in_units=20),
            gluon.nn.Dense(8, in_units=16))
    net.initialize()
    rng = np.random.RandomState(seed)
    for p in net.collect_params().values():
        p.set_data(mx.nd.array(
            (0.3 * rng.randn(*p.shape)).astype("float32")))
    if dtype is not None:
        net.cast(dtype)
    for name, (lr_mult, wd_mult) in dict(mults).items():
        net.collect_params()[name].lr_mult = lr_mult
        net.collect_params()[name].wd_mult = wd_mult
    if builder == "shard_map":
        how = dict(parallel=ParallelConfig(dp=2, tp=2),
                   rules=ShardingRules([(r"0\.weight", P("tp", None))],
                                       default_axis=None))
    else:
        how = dict(mesh=make_mesh({"dp": 4}),
                   rules=ShardingRules(default_axis=None))
    tr = ShardedTrainer(net, _sq_loss, optimizer, optimizer_params,
                        abstract=abstract, **how)
    assert tr._use_shard_map == (builder == "shard_map")
    return tr


def _scalars_batches(n, dtype="float32"):
    rng = np.random.RandomState(11)
    return (rng.randn(n, 8, 20).astype(dtype),
            rng.randn(n, 8, 8).astype(dtype))


def _one(tr, X, Y, i, fused):
    """Step ``i`` of the batches: ``step``, or a ``step_n`` window of one."""
    if fused:
        return tr.step_n(X[i:i + 1], Y[i:i + 1])
    return tr.step(X[i], Y[i])


def _host(tree):
    return jax.tree_util.tree_map(np.asarray, dict(tree))


def _case_mults(builder, fused):
    """lr_mult 0 holds a parameter still, lr_mult 2 moves it twice as far
    (SGD: the first step's gradient is the same on both trainers)."""
    X, Y = _scalars_batches(1)
    plain = _scalars_trainer(builder, "sgd", {"learning_rate": 0.1})
    mult = _scalars_trainer(builder, "sgd", {"learning_rate": 0.1},
                            mults={"0.weight": (0.0, 1.0),
                                   "1.weight": (2.0, 1.0)})
    start = _host(plain.params)
    _one(plain, X, Y, 0, fused)
    _one(mult, X, Y, 0, fused)
    a, b = _host(plain.params), _host(mult.params)
    np.testing.assert_array_equal(b["0.weight"], start["0.weight"])
    assert np.abs(a["0.weight"] - start["0.weight"]).max() > 1e-4
    np.testing.assert_allclose(b["1.weight"] - start["1.weight"],
                               2 * (a["1.weight"] - start["1.weight"]),
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_array_equal(b["1.bias"], a["1.bias"])


def _case_new_rate(builder, fused, through):
    """A rate that changes between steps (a scheduler's, or
    ``set_learning_rate``) is the rate of that very step, on the one
    executable: the second step moves half as far as at the old rate."""
    X, Y = _scalars_batches(2)
    steady = _scalars_trainer(builder, "sgd", {"learning_rate": 0.1})
    if through == "scheduler":
        moved = _scalars_trainer(
            builder, "sgd", {"learning_rate": 0.1,
                             "lr_scheduler": _HalvedFrom(at=2)})
    else:
        moved = _scalars_trainer(builder, "sgd", {"learning_rate": 0.1})
    _one(steady, X, Y, 0, fused)
    _one(moved, X, Y, 0, fused)
    mid = _host(steady.params)
    for n, a in _host(moved.params).items():
        np.testing.assert_array_equal(a, mid[n])
    if through == "set_learning_rate":
        moved.optimizer.set_learning_rate(0.05)
    _one(steady, X, Y, 1, fused)
    _one(moved, X, Y, 1, fused)
    assert len(moved._compiled) == 1 and len(steady._compiled) == 1
    a, b = _host(steady.params), _host(moved.params)
    for n in a:
        assert np.abs(a[n] - mid[n]).max() > 1e-5
        np.testing.assert_allclose(2 * (b[n] - mid[n]), a[n] - mid[n],
                                   rtol=1e-4, atol=1e-7)


def _case_stored_dtype(builder, fused, dtype):
    """Parameters stored in bf16 or f16, and Adam's moments beside them,
    keep that dtype through a step (a float32 rate must not promote
    them), and move."""
    X, Y = _scalars_batches(2, dtype)
    tr = _scalars_trainer(builder, "adam",
                          {"learning_rate": 1e-2, "wd": 1e-2, "epsilon": 1e-3},
                          dtype=dtype)
    start = _host(tr.params)
    for i in range(2):
        loss = _one(tr, X, Y, i, fused)
    assert np.isfinite(loss.asnumpy().astype("float32")).all()
    for n, a in tr.params.items():
        assert a.dtype == jnp.dtype(dtype), (n, a.dtype)
        assert (np.asarray(a) != start[n]).any(), n
    for n, st in tr._opt_states.items():
        assert len(st) == 2
        assert all(s.dtype == jnp.dtype(dtype) for s in st), n


def _case_adam_parity(builder, fused):
    """Three Adam steps on float32 parameters against the same three done
    leaf by leaf with ``Adam._update_raw`` and Python scalars (how the
    step took its rates before they were arrays) on the gradients the
    trainer saw, each from the state the trainer had: bit for bit under
    the pjit step on the CPU backend. Under the shard_map step XLA folds
    the step's own ``g / mesh.size`` into the update, so the parameters
    agree to 1 ulp and the moments to 8 (5 read); with Python scalars in
    the step it reads the same ulps, leaf for leaf."""
    X, Y = _scalars_batches(3)
    opt = _RecordingAdam(learning_rate=3e-3, wd=1e-2)
    tr = _scalars_trainer(builder, opt,
                          mults={"0.bias": (0.5, 0.0), "1.weight": (2.0, 3.0)})
    rule = jax.jit(lambda p, g, m, v, lr, wd, t: mx.optimizer.Adam._update_raw(
        opt, p, g, (m, v), lr, wd, t))
    ulps = (0, 0, 0) if builder == "pjit" else (1, 8, 8)
    for step in range(3):
        before_p = _host(tr.params)
        before_s = {n: tuple(np.asarray(s) for s in st[:2])
                    for n, st in tr._opt_states.items()}
        _one(tr, X, Y, step, fused)
        for i, n in enumerate(tr._train_keys):
            m, v, g = (np.asarray(s) for s in tr._opt_states[n])
            assert np.abs(g).max() > 0
            want = rule(before_p[n], g, *before_s[n], opt._get_lr(i),
                        opt._get_wd(i), step + 1)
            for got, w, ulp in zip((np.asarray(tr.params[n]), m, v),
                                   jax.tree_util.tree_leaves(want), ulps):
                np.testing.assert_array_max_ulp(got, np.asarray(w),
                                                maxulp=ulp)
    assert len(tr._compiled) == 1


_SCALAR_CASES = {
    "lr_mult": _case_mults,
    "scheduler": lambda b, f: _case_new_rate(b, f, "scheduler"),
    "set_learning_rate":
        lambda b, f: _case_new_rate(b, f, "set_learning_rate"),
    "bfloat16": lambda b, f: _case_stored_dtype(b, f, "bfloat16"),
    "float16": lambda b, f: _case_stored_dtype(b, f, "float16"),
    "adam_parity": _case_adam_parity,
}


@pytest.mark.parametrize("fused", [False, True], ids=["step", "step_n"])
@pytest.mark.parametrize("builder", ["pjit", "shard_map"])
@pytest.mark.parametrize("case", list(_SCALAR_CASES))
def test_optimizer_scalars_reach_the_step_as_arrays(case, builder, fused):
    _SCALAR_CASES[case](builder, fused)


@pytest.mark.parametrize("builder", ["pjit", "shard_map"])
def test_abstract_trainer_lowers_the_arguments_the_step_takes(builder):
    """``aot_lowered`` of an ``abstract=True`` trainer takes what the
    running step is called with: the two float32 arrays, the Python step
    count, and no other host value."""
    X, Y = _scalars_batches(1)
    live = _scalars_trainer(builder, "adam", {"learning_rate": 1e-2})
    live.step(X[0], Y[0])
    (compiled, _), = live._compiled.values()
    dry = _scalars_trainer(builder, "adam", {"learning_rate": 1e-2},
                           abstract=True)
    lowered = dry.aot_lowered(jax.ShapeDtypeStruct(X[0].shape, X.dtype),
                              jax.ShapeDtypeStruct(Y[0].shape, Y.dtype))
    assert lowered.in_tree == compiled.in_tree

    def flat(avals):
        return [(a.shape, str(a.dtype))
                for a in jax.tree_util.tree_leaves(avals)]

    assert flat(lowered.in_avals) == flat(compiled.in_avals)
    lrs, wds = dry._optimizer_scalars()
    assert lrs.shape == wds.shape == (len(dry._train_keys),)
    assert lrs.dtype == wds.dtype == np.float32
