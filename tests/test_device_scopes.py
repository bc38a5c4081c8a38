"""The names a compiled program carries into the device trace
(``profiler.core``: step scopes, ``gluon.Block.__call__``'s block scopes,
op scopes, the Pallas kernels' names), read back from the compiled text of
the two serving executables of four model families and of the training
step. The models are the benchmark's own at the widths of their
``rehearse`` groups, through its adapters, as ``tests/test_mellum.py``
builds them; the executables are the ones a ``ContinuousEngine`` runs,
lowered from the signatures it called them with.
"""
import contextlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import cachedop, serve
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops.pallas import decode_attention as da
from mxnet_tpu.profiler import core

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")

ATTENTION = {"attn.rope", "kv.write", "head", "embed", "norm"}
DECODE = ATTENTION | {"attn.kernel"}
PREFILL = ATTENTION | {"attn.scores", "kv.gather"}
# configuration -> the op scopes its model adds to the attention's
FAMILIES = {
    "mistral_7b_v01": set(),
    "falcon_h1_34b": {"ssm.conv", "ssm.scan"},
    "mellum2_12b_a2_5b": {"experts.router", "experts.routed"},
    "command_a_plus_05_2026": {"experts.router", "experts.routed",
                               "experts.shared"},
}


def _harness():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import harness

    return harness


def build(config):
    """``(net, loss_fn or None)`` of a configuration at rehearsal size."""
    h = _harness()
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        published = json.load(f)
    cfg = h.merged(published, published["rehearse"])
    out = h.load_module("adapters", cfg["adapter"]).build(cfg, False)
    return out if isinstance(out, tuple) else (out, None)


def op_names(compiled_text):
    return set(re.findall(r'op_name="([^"]+)"', compiled_text))


def scopes_in(names):
    """The names of the constant table that stand in any path."""
    found = set()
    for n in names:
        found.update(t for t in re.split(r"[/()]", n)
                     if t in core.DEVICE_SCOPES)
    return found


@pytest.fixture(scope="module")
def executables():
    """``{configuration: {positions a row: compiled text}}`` of the two
    executables an engine runs, each lowered once from the call the engine
    made (no second trace of its own: the names are those of the program
    that ran)."""
    texts = {}
    real = cachedop.CachedOpThreadSafe._run_fwd
    seen = {}

    def spy(self, entry, snap, rng_key, arg_datas):
        t = arg_datas[0].shape[1]
        if t not in seen:
            spec = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                (snap.tp_datas, snap.st_datas, rng_key, tuple(arg_datas)))
            seen[t] = entry["fwd"].lower(*spec[:3], *spec[3])
        return real(self, entry, snap, rng_key, arg_datas)

    da.use_interpret(True)   # the paged kernel, interpreted on the CPU
    cachedop.CachedOpThreadSafe._run_fwd = spy
    try:
        for config in FAMILIES:
            seen.clear()
            net, _ = build(config)
            net.initialize(mx.init.Normal(0.05))
            eng = serve.ContinuousEngine(
                net, max_seq=64, num_slots=2, page_size=8, prefill_chunk=8,
                decode_path="pallas", name=f"scopes_{config}")
            fut = eng.submit(list(range(1, 12)), max_new_tokens=3)
            while not fut.done():
                eng.step()
            eng.close()
            texts[config] = {t: low.compile().as_text()
                             for t, low in seen.items()}
    finally:
        cachedop.CachedOpThreadSafe._run_fwd = real
        da.use_interpret(False)
    return texts


@pytest.mark.parametrize("config", sorted(FAMILIES))
@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_serving_executable_carries_its_names(executables, config, kind):
    text = executables[config][1 if kind == "decode" else 8]
    names = op_names(text)
    step = f"serve_step.{kind}"
    other = "serve_step.prefill" if kind == "decode" else "serve_step.decode"
    under_model = [n for n in names if "/model/" in n]
    assert under_model
    # the step's scope is outermost, on everything the model does, and the
    # other executable's stands nowhere
    assert all(n.startswith(f"jit(fwd)/{step}/model/") for n in under_model)
    assert not any(other in n for n in names)
    # a block's path is its registered names, no model's own string
    assert any(re.search(r"/model/layer0/attention/q_proj/", n)
               for n in names)
    assert any(re.search(r"/model/layer1/attention/jit\([^/]*\)/kv\.write", n)
               for n in names)
    want = (DECODE if kind == "decode" else PREFILL) | FAMILIES[config]
    if config == "falcon_h1_34b" and kind == "prefill":
        want = want | {"ssm.state"}   # one row's state, taken and put back
    assert scopes_in(names) == want | {step}
    # the kernel has a name of its own (interpreted here, its name is a
    # scope of the operations it turns into; on a chip, the custom call's)
    assert any("attn.kernel/paged_decode_attention" in n for n in names) \
        == (kind == "decode")
    # the routed experts' products stand inside the router's scope, the
    # sort inside the products': innermost decides
    if "experts.routed" in want:
        assert any("experts.routed/while/body" in n for n in names)
        assert any("experts.routed/experts.router/" in n for n in names)


def test_ring_kernel_is_named():
    """``Generator``'s ring decode step: the other ``pallas_call``."""
    da.use_interpret(True)
    try:
        q = jnp.ones((2, 4, 1, 16), jnp.float32)
        k = jnp.ones((2, 2, 128, 16), jnp.float32)
        sp = jnp.asarray([3, 5], jnp.int32)
        text = jax.jit(da.decode_attention).lower(q, k, k, sp) \
            .compile().as_text()
    finally:
        da.use_interpret(False)
    assert da.last_path() == "pallas"
    assert any("attn.kernel/decode_attention" in n for n in op_names(text))


def test_training_step_carries_its_names():
    from mxnet_tpu.parallel import ShardedTrainer, ShardingRules, make_mesh

    net, loss_fn = build("bert_base")
    trainer = ShardedTrainer(
        net, loss_fn, "adam", {"learning_rate": 1e-4},
        mesh=make_mesh({"dp": 1}, devices=jax.devices()[:1]),
        rules=ShardingRules(default_axis=None), dtype="bfloat16")
    tokens = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    labels = (tokens, jax.ShapeDtypeStruct((4,), jnp.int32))
    names = op_names(trainer.aot_lowered(tokens, labels).compile().as_text())
    scoped = [n for n in names if "train_step." in n]
    assert all(n.startswith(("jit(step)/train_step.grad",
                             "jit(step)/train_step.optimizer"))
               for n in scoped)
    fwd = "jit(step)/train_step.grad/jvp(model)/bert/encoder/layer0/"
    bwd = "jit(step)/train_step.grad/transpose(jvp(model))/bert/encoder/" \
        "layer0/"
    for path in (fwd + "attention/query_proj", bwd + "ffn/ffn_1",
                 "train_step.grad/jvp(loss)",
                 "train_step.grad/transpose(jvp(loss))",
                 "jvp(model)/mlm_dense", "jvp(model)/nsp"):
        assert any(path in n for n in names), path
    assert {"train_step.grad", "train_step.optimizer", "loss", "norm",
            "embed"} <= scopes_in(names)
    # the optimizer's update is under its own scope and no block's
    assert not any("train_step.optimizer" in n and "model" in n
                   for n in names)


def test_an_eager_call_enters_no_scope(monkeypatch):
    entered = []

    def named_scope(name):
        entered.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(jax, "named_scope", named_scope)
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=3), nn.Dense(2, in_units=4))
    net.initialize()
    x = mx.np.array(np.ones((2, 3), "float32"))
    net(x)
    assert entered == []
    assert net[0].trace_scope() is net.trace_scope()   # the shared no-op
    net.hybridize()
    net(x)
    assert entered == ["0", "1"]   # children by registered name; no root


def test_the_table_is_closed():
    assert core.DEVICE_SCOPES == set(core.STEP_SCOPES) | set(core.OP_SCOPES)
    with pytest.raises(KeyError):
        core.device_scope("attn.kernal")
