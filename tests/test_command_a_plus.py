"""Command A+ (a parallel block under one LayerNorm; 8 of 128
sigmoid-routed experts beside averaged shared experts; window layers that
rotate beside full layers that do not; a tied head) through the normal
path and ``serve.ContinuousEngine``, against the plain reference
``chipbench/reference/command_a_plus.py``: the benchmark's configuration
at the tiny widths of its ``rehearse`` group (4 held of 16 experts, top 4,
2 shared, a window of 16 over pages of 8, 16 query heads on 2 KV heads),
with the benchmark's seeded weights. Logits are compared, never tokens.

Tolerances. Everything is float32 and every matrix product runs at full
precision, so the program and the reference differ by the order of their
sums alone (gemm against einsum, sorted tiles against a loop over the
experts, pages against one score matrix). ``TOL`` is 1e-4 of the logits'
spread: the full pass reads 7e-6 here and the engine's steps 2e-5; with
bfloat16 operands the reference itself moves by 0.4 of that spread and
fails it, and the least planted fault by 1.2, which
``test_bf16_operands_fail`` and ``test_planted_faults_fail`` hold. The
tiny model's weights are drawn wide (``initializer_range`` 0.2), so that
no token's fourth and fifth router scores lie within round-off of each
other on these seeds: a flipped route would read far over ``TOL``.
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import llama
from mxnet_tpu.models.command_a_plus import CommandAPlusModel
from mxnet_tpu.models.mellum import RoutedFFN
from mxnet_tpu.ops import nn as ops
from mxnet_tpu.ops.pallas import decode_attention as da
from mxnet_tpu.profiler import core as prof
from mxnet_tpu.serve.generate import CacheLayout

from test_mellum import (ROOT, Bundle, Spy, _harness, close, gap_of,
                         serve_all, tokens_of)

TOL = 1e-4
SEED = 5
PAGE = 8


@pytest.fixture(scope="module")
def bundle():
    da.use_interpret(True)   # the paged kernel, interpreted on the CPU
    yield Bundle("command_a_plus_05_2026.json")
    da.use_interpret(False)


# -- the model on the normal path ------------------------------------------------

def test_full_pass_matches_reference(bundle):
    toks = np.asarray(tokens_of(1, 70, 70), np.int32)   # 4 windows and more
    with mx.autograd.predict_mode():
        got = bundle.net(mx.np.array(toks)).asnumpy()
    close(got, bundle.reference(toks), TOL)


def test_bf16_operands_fail(bundle):
    toks = np.asarray(tokens_of(1, 70, 70), np.int32)
    low = bundle.reference(toks, bundle.ref.controls("float32")["bfloat16"])
    with pytest.raises(AssertionError):
        close(low, bundle.reference(toks), TOL)


@pytest.mark.parametrize("fault", [
    "softmax_for_sigmoid", "shared_summed", "rope_on_full",
    "sequential_block", "window_off_by_one", "renormalised_over_held"])
def test_planted_faults_fail(bundle, fault):
    """Each fault the reference can plant (the score function, the shared
    experts' mean, the layer kind without rotation, the parallel block,
    the window's edge, the share's normalisation) moves the logits far
    over the tolerance: the comparison sees every mechanism."""
    assert fault in bundle.ref.FAULTS
    toks = np.asarray(tokens_of(1, 70), np.int32)
    bad = bundle.reference(toks, bundle.ref.controls("float32")
                           ["fault_" + fault])
    with mx.autograd.predict_mode():
        got = bundle.net(mx.np.array(toks)).asnumpy()
    assert gap_of(got, bad) > 1000 * TOL


def test_the_tied_head_reads_the_embeddings_array(bundle):
    """One parameter, not two: the model has no head of its own, the
    logits move with the embedding's array, and the benchmark's second
    leaf name is the same draw."""
    net = bundle.net
    names = sorted(net.collect_params())
    assert "embed.weight" in names
    assert not [n for n in names if "head" in n]
    assert len({id(p) for p in net.collect_params().values()}) == len(names)
    np.testing.assert_array_equal(np.asarray(bundle.w["embed"]),
                                  np.asarray(bundle.w["head"]))
    small = CommandAPlusModel(
        vocab_size=32, units=16, num_heads=4, num_kv_heads=2, head_dim=4,
        layer_types=["full_attention"], sliding_window=8, rope_theta=1e4,
        expert_size=8, num_experts=4, num_experts_per_tok=2,
        num_shared_experts=1, logit_scale=0.5)
    mx.random.seed(3)
    small.initialize(mx.init.Normal(0.3))
    toks = mx.np.array(np.asarray(tokens_of(2, 9, vocab=32), np.int32))
    with mx.autograd.predict_mode():
        before = small(toks).asnumpy()
        table = small.embed.weight.data().asnumpy().copy()
        # a row no token of the input looks up
        row = next(r for r in range(32) if r not in toks.asnumpy())
        table[row, 0] += 1.0
        small.embed.weight.set_data(mx.np.array(table))
        after = small(toks).asnumpy()
    moved = np.abs(after - before).max(axis=(0, 1))
    assert moved[row] > 1e-3 and np.delete(moved, row).max() == 0.0


# -- chunked prefill and decode through the engine -------------------------------

def test_engine_matches_reference_past_the_window(bundle):
    """One request of 45 + 25 positions: 9 pages through a ring of 3
    columns, so every column is written over at least twice, chunks and
    decode steps alike; the normal path (two signatures, the paged kernel
    with 8 query heads to a KV head, no fallback); every served position's
    logits against the reference's one full pass."""
    da.reset_fallbacks()
    eng = bundle.engine(slots=1)
    eng.warmup()
    spy = Spy(eng)
    prompt = tokens_of(2, 45)[0]
    res = serve_all(eng, [prompt], 25)[0]
    assert len(res["tokens"]) == 25
    want = bundle.reference([prompt + res["tokens"]])[0]
    worst, n = spy.worst({0: want})
    assert n == 6 + 24 and worst <= TOL, worst
    assert eng.session.signature_count() == 2
    eng.assert_no_recompiles()
    assert da.last_path() == "pallas_paged" and da.fallback_count() == 0
    st = eng.stats()
    assert st["pool"]["window_columns"] == 3
    assert st["window_pages_recycled"] == 70 // PAGE + 1 - 3
    eng.close()


def test_two_lanes_at_different_positions(bundle):
    """The second request arrives while the first decodes: its lane is
    dead in the first's decode steps while its chunks are written, then
    both decode at different positions, each in its own ring."""
    eng = bundle.engine(slots=2)
    eng.warmup()
    spy = Spy(eng)
    prompts = tokens_of(3, 37, 52)
    res = serve_all(eng, prompts, 20, stagger=9)
    wants = {i: bundle.reference([p + r["tokens"]])[0]
             for i, (p, r) in enumerate(zip(prompts, res))}
    alone = [c for c in spy.calls if c[0] == 1 and c[3] == [0]]
    both = [c for c in spy.calls if c[0] == 1 and c[3] == [0, 1]]
    assert alone and both
    worst, _ = spy.worst(wants)
    assert worst <= TOL, worst
    eng.close()


# -- the layer kind without rotation ---------------------------------------------

@pytest.mark.parametrize("kind,moves", [("full_attention", False),
                                        ("sliding_attention", True)])
def test_a_full_layer_carries_no_position(kind, moves, monkeypatch):
    """Every position relabelled ``p -> 2p + 3`` (a plain shift leaves a
    rotary layer's scores unchanged too: they depend on ``t - s`` alone):
    a model of full layers gives the same logits, because such a layer
    never asks for a table; a model of window layers does not."""
    net = CommandAPlusModel(
        vocab_size=64, units=32, num_heads=4, num_kv_heads=2, head_dim=8,
        layer_types=[kind, kind], sliding_window=64, rope_theta=50.0,
        expert_size=16, num_experts=4, num_experts_per_tok=2,
        num_shared_experts=2)
    mx.random.seed(11)
    net.initialize(mx.init.Normal(0.3))
    toks = mx.np.array(np.asarray(tokens_of(5, 30, vocab=64), np.int32))
    with mx.autograd.predict_mode():
        want = net(toks).asnumpy()
    real, asked = llama._rope_tables, []

    def stretched(t, dim, theta=10000.0, scaling=None):
        asked.append(t)
        cos, sin = real(2 * t + 3, dim, theta, scaling)
        return cos[3::2][:t], sin[3::2][:t]

    monkeypatch.setattr(llama, "_rope_tables", stretched)
    with mx.autograd.predict_mode():
        got = net(toks).asnumpy()
    if moves:
        assert asked and gap_of(got, want) > 100 * TOL
    else:
        assert not asked and gap_of(got, want) == 0.0


def test_rotation_on_every_layer_stays_the_default():
    att = llama.LlamaAttention(32, 4, 2)
    assert att._theta == 10000.0
    assert llama.LlamaAttention(32, 4, 2, theta=None)._theta is None


# -- routing ----------------------------------------------------------------------

def test_sigmoid_routing_sums_to_one_and_ties_go_to_the_lower_index():
    import jax.numpy as jnp

    logits = jnp.asarray(np.random.RandomState(0).randn(50, 16), jnp.float32)
    w, idx = ops.route_top_k(logits, 4, score="sigmoid")
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    assert all(len(set(r)) == 4 for r in np.asarray(idx))
    # the weights are the chosen experts' sigmoids over their sum
    s = 1 / (1 + np.exp(-np.asarray(logits, np.float64)))
    pick = np.asarray(idx)
    want = np.take_along_axis(s, pick, 1)
    np.testing.assert_allclose(np.asarray(w), want / want.sum(-1)[:, None],
                               atol=1e-6)
    tie = jnp.asarray([[1.0, 3.0, 3.0, 3.0, 0.0, 3.0]], jnp.float32)
    _, idx = ops.route_top_k(tie, 2, score="sigmoid")
    assert np.asarray(idx).tolist() == [[1, 2]]
    _, idx = ops.route_top_k(jnp.zeros((1, 6), jnp.float32), 3,
                             score="sigmoid")
    assert np.asarray(idx).tolist() == [[0, 1, 2]]
    # not renormalised: the sigmoids themselves
    w, _ = ops.route_top_k(tie, 2, renormalize=False, score="sigmoid")
    np.testing.assert_allclose(np.asarray(w), 1 / (1 + np.exp(-3.0)),
                               atol=1e-6)
    with pytest.raises(MXNetError, match="router score"):
        ops.route_top_k(tie, 2, score="tanh")


def test_softmax_stays_the_default_score():
    import jax
    import jax.numpy as jnp

    logits = jnp.asarray(np.random.RandomState(1).randn(20, 8), jnp.float32)
    w, idx = ops.route_top_k(logits, 3)
    p = np.asarray(jax.nn.softmax(logits, axis=-1))
    want = np.take_along_axis(p, np.asarray(idx), 1)
    np.testing.assert_allclose(np.asarray(w), want / want.sum(-1)[:, None],
                               atol=1e-6)
    ws, _ = ops.route_top_k(logits, 3, score="sigmoid")
    assert np.abs(np.asarray(ws) - np.asarray(w)).max() > 1e-3
    assert RoutedFFN(16, 12, 8, 3)._score == "softmax"
    assert RoutedFFN(16, 12, 8, 3)._shared == 0


# -- the sixteen shares -------------------------------------------------------------

def test_the_sixteen_shares_add_up_to_the_uncut_layer(bundle):
    """One layer at 128 experts of which a token takes 8: the routed parts
    of ``experts_held = (8 i, 8)``, i = 0 .. 15, with attention and the
    shared experts counted once, equal the uncut reference's layer. Each
    share normalises a token's weights over its 8 experts wherever they
    are held, never over those it holds."""
    ref, h = bundle.ref, bundle.h
    cfg = dict(bundle.cfg, num_hidden_layers=1, router_experts=128,
               num_experts=128, num_experts_per_tok=8,
               layer_types=["sliding_attention"])
    w = h.load_module(".", "weights").Maker(
        ref.param_shapes(cfg), 9, cfg["initializer_range"]).all()
    leaves = {k.split(".", 1)[1]: v for k, v in w.items()
              if k.startswith("layer0.")}
    x = np.random.RandomState(4).randn(2, 24, cfg["hidden_size"]) \
        .astype("float32")
    want = np.asarray(ref.layer(bundle.jnp.asarray(x), leaves, cfg))

    def block(share):
        """The program's block holding ``share`` of the routed experts."""
        net = bundle.adapter.build(
            dict(cfg, num_experts=share[1], experts_held=share), False)
        sl = slice(share[0], share[0] + share[1])
        cut = dict(w)
        for n in ("gate", "up", "down"):
            cut["layer0." + n] = w["layer0." + n][sl]
        params = net.collect_params()
        for prog, name in bundle.adapter.name_map(cfg).items():
            params[prog].set_data(mx.np.array(np.asarray(cut[name])))
        return net._blocks[0]

    with mx.autograd.predict_mode():
        first = block((0, 8))
        xs = mx.np.array(x)
        y = first.norm(xs)
        total = (xs + first.attention(y) + first.ffn(y)).asnumpy()
        shared = ops.shared_experts(
            y, first.ffn.shared_gate_weight.data(),
            first.ffn.shared_up_weight.data(),
            first.ffn.shared_down_weight.data()).asnumpy()
        hit = 0
        for i in range(1, 16):
            blk = block((8 * i, 8))
            part = blk.ffn(y).asnumpy() - shared     # its routed part alone
            hit += int(np.abs(part).max() > 0)
            total = total + part
    assert hit == 15                 # every share had tokens of its own
    close(total, want, 1e-5)
    # and one share alone is far from the whole layer
    assert gap_of((xs + first.attention(y) + first.ffn(y)).asnumpy(),
                  want) > 1000 * TOL


# -- the spans, counters and stats of the routed layers ------------------------------

def test_route_loads_report_what_is_held(bundle, monkeypatch):
    """``serve.route`` and ``stats()["moe"]``: of the live tokens' k
    assignments each, those that fell on one of the 4 of 16 experts held
    here, and the experts held, a call and a layer."""
    for name in ("serve.moe_assignments", "serve.moe_assignments_held",
                 "serve.moe_experts_hit", "serve.moe_experts_read"):
        prof.set_counter(name, 0)
    import mxnet_tpu.serve.scheduler as sched

    spans = []

    class Span:
        """Every host span of the scheduler, keeping ``serve.route``'s
        stats."""

        def __init__(self, name, **kw):
            self.route = name == "mxnet_tpu.serve.route"

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **kw):
            if self.route:
                spans.append(kw)

    monkeypatch.setattr(sched, "host_span", Span)
    eng = bundle.engine(slots=2)
    eng.warmup()
    prompts = tokens_of(9, 21, 30)
    res = serve_all(eng, prompts, 9)
    moe = eng.stats()["moe"]
    k, layers = bundle.cfg["num_experts_per_tok"], 4
    fed = sum(len(p) + len(r["tokens"]) - 1 for p, r in zip(prompts, res))
    assert moe["assignments"] == fed * k * layers
    assert 0 < moe["assignments_held"] < moe["assignments"]
    assert moe["assignments_held_share"] == pytest.approx(
        moe["assignments_held"] / moe["assignments"])
    assert moe["experts_held"] == moe["calls"] * layers * 4
    assert 0 < moe["experts_hit"] <= moe["experts_held"]
    assert spans and all(
        {"experts_hit", "experts_held", "assignments", "assignments_held",
         "max_load", "calls", "experts_read"} <= set(s) for s in spans)
    assert sum(s["assignments_held"] for s in spans) \
        == moe["assignments_held"]
    assert sum(s["experts_held"] for s in spans) == moe["experts_held"]
    assert prof.get_counter("serve.moe_assignments") == moe["assignments"]
    assert prof.get_counter("serve.moe_assignments_held") \
        == moe["assignments_held"]
    # two lanes of 4 of 16 at top 4 can hit 0.44 of the held experts: the
    # decode step walks the tiles as the chunk does, and no call reads an
    # expert that none of its tokens picked
    assert sum(s["experts_read"] for s in spans) == moe["experts_read"] \
        == moe["experts_hit"] == prof.get_counter("serve.moe_experts_read")
    assert moe["picked_share"] == 1.0
    eng.close()


# -- the side of ``ops.nn.expert_form`` a served model lands on -----------------------

def _command_like():
    """2 of 16 experts held at top 4, four lanes: a decode step can hit
    0.68 of the held experts."""
    h = _harness()
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "command_a_plus_05_2026.json")) as f:
        pub = json.load(f)
    cfg = dict(h.merged(pub, pub["rehearse"]), num_experts=2,
               experts_held=(0, 2))
    return h.load_module("adapters", cfg["adapter"]).build(cfg, False), 4


def _mellum_like():
    """All 8 experts held at top 2, six lanes: 0.82."""
    return _mellum()[0], 6


@pytest.mark.parametrize("build,form", [(_command_like, "grouped"),
                                        (_mellum_like, "dense")],
                         ids=["command_like", "mellum_like"])
def test_a_served_model_lands_on_its_side_of_the_rule(monkeypatch, build,
                                                      form):
    """Through ``ContinuousEngine``: the decode step's form is the one the
    rule names for the engine's lanes and the model's router (the chunk
    walks tiles on both sides), ``picked_share`` says how much of what
    was read had been picked, and the served tokens are those of the same
    model with its decode step forced to the other form."""
    da.use_interpret(True)
    try:
        net, slots = build()
        net.initialize(mx.init.Normal(0.1))
        prompts = tokens_of(21, 11, 19, 9, 14, 17, 12)[:slots]

        def served(force=None):
            seen, real = set(), ops.expert_form

            def rule(rows, positions, k, e_all):
                got = real(rows, positions, k, e_all)
                if positions == 1:
                    seen.add(got)
                    return force or got
                return got

            monkeypatch.setattr(ops, "expert_form", rule)
            eng = serve.ContinuousEngine(
                net, max_seq=64, num_slots=slots, page_size=PAGE,
                prefill_chunk=PAGE, decode_path="pallas")
            eng.warmup()
            res = serve_all(eng, prompts, 7)
            moe = eng.stats()["moe"]
            eng.close()
            return [r["tokens"] for r in res], moe, seen

        tokens, moe, seen = served()
        assert seen == {form}
        other = "dense" if form == "grouped" else "grouped"
        forced, theirs, _ = served(force=other)
        assert forced == tokens
        assert theirs["experts_hit"] == moe["experts_hit"] > 0
        tiles, whole = (moe, theirs) if form == "grouped" else (theirs, moe)
        assert tiles["picked_share"] == 1.0
        assert tiles["experts_read"] == tiles["experts_hit"]
        # the one product reads every held expert in every decode call
        assert whole["experts_hit"] < whole["experts_read"] \
            <= whole["experts_held"]
        assert whole["picked_share"] == pytest.approx(
            whole["experts_hit"] / whole["experts_read"])
    finally:
        da.use_interpret(False)


# -- what refuses a bounded layer ------------------------------------------------------

def test_what_cannot_serve_a_bounded_layer_says_so(bundle):
    net = bundle.net
    kw = dict(max_seq=128, page_size=PAGE, prefill_chunk=PAGE)
    with pytest.raises(MXNetError, match="bounded by a window"):
        serve.Generator(net, max_seq=128)
    with pytest.raises(MXNetError, match="bounded by a window"):
        serve.SpeculativeGenerator(net, net, max_seq=128)
    with pytest.raises(MXNetError, match="prefix cache.*bounded by a window"):
        serve.ContinuousEngine(net, decode_path="pallas", prefix_cache=True,
                               **kw)
    with pytest.raises(MXNetError, match="multi-step.*bounded by a window"):
        serve.ContinuousEngine(net, decode_path="pallas", multistep=True,
                               **kw)
    with pytest.raises(MXNetError, match="bounded by a window"):
        serve.ContinuousEngine(net, decode_path="baseline", **kw)
    with pytest.raises(MXNetError, match="float32 page pools"):
        serve.ContinuousEngine(net, decode_path="int8", **kw)


# -- the other serving models' step is what it was --------------------------------------

def _mellum():
    h = _harness()
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "mellum2_12b_a2_5b.json")) as f:
        pub = json.load(f)
    cfg = h.merged(pub, pub["rehearse"])
    net = h.load_module("adapters", cfg["adapter"]).build(cfg, False)
    return net, 512


def _mistral():
    return llama.get_llama("llama_tiny_test"), 64


def _falcon():
    from mxnet_tpu.models.falcon_h1 import FalconH1Model

    return FalconH1Model(
        vocab_size=64, units=32, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=8, mamba_d_ssm=32, mamba_d_state=8,
        mamba_n_heads=4, mamba_d_head=8, mamba_chunk_size=8), 64


@pytest.mark.parametrize("build,routed", [(_mellum, True), (_mistral, False),
                                          (_falcon, False)],
                         ids=["mellum2", "mistral", "falcon_h1"])
def test_the_other_models_step_arguments_and_results(build, routed):
    """The step of the three serving models that were there takes and
    hands back what it did: tokens, start_pos, last_idx, the page table
    (a ring table for a model with a window, lanes for one with a state),
    keep and ids, the stores; logits, the routed layers' load for a model
    that has them (softmax scores, no shared branch: six numbers a layer
    now, the first three what they were), ids, the stores."""
    da.use_interpret(True)
    try:
        net, vocab = build()
        net.initialize(mx.init.Normal(0.1))
        layout = CacheLayout(net)
        eng = serve.ContinuousEngine(net, max_seq=64, num_slots=2,
                                     page_size=8, prefill_chunk=8,
                                     decode_path="pallas")
        eng.warmup()
        seen, loads = [], []
        real = eng.session.run

        def run(*args):
            out = real(*args)
            seen.append((len(args), len(out)))
            if routed:
                loads.append(out[1].shape)
            return out

        eng.session.run = run
        serve_all(eng, tokens_of(8, 11, 19, vocab=vocab), 6)
        windowed = layout.window is not None
        n_args = 6 + int(windowed) + int(layout.has_state) + len(layout)
        assert set(seen) == {(n_args, 2 + int(routed) + len(layout))}
        # one row a layer (the tiny Mellum-2 has four)
        assert set(loads) == ({(4, 6)} if routed else set())
        assert ("moe" in eng.stats()) == routed
        if routed:
            moe = eng.stats()["moe"]
            # every expert is held: nothing of a token's sum is elsewhere
            assert moe["assignments_held"] == moe["assignments"] > 0
            assert moe["assignments_held_share"] == 1.0
        eng.close()
    finally:
        da.use_interpret(False)
