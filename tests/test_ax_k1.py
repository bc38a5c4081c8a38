"""A.X-K1 (latent attention served from pages of one array a position; a
leading dense layer; sigmoid-routed experts chosen inside the best groups,
times a factor, beside one shared expert) through the normal path and
``serve.ContinuousEngine``, against the plain reference
``chipbench/reference/ax_k1.py``, which is non-absorbed: the benchmark's
configuration at the tiny widths of its ``rehearse`` group (4 heads, a
latent of 32 beside a rope key of 8, 4 held of 16 experts in 4 groups of
which a token keeps 2, top 4, one dense and two routed layers), with the
benchmark's seeded weights. Logits are compared, never tokens.

Tolerances are ``tests/test_mellum.py``'s: everything is float32 at full
precision, so the program and the reference differ by the order of their
sums alone (the absorbed products against decompressed keys and values,
pages against one score matrix, sorted tiles against a loop over the
experts): the full pass reads 1e-5 of the logits' spread here and the
engine's steps 7e-6, the bfloat16 control 2.0 and the least planted fault
2.1.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models.ax_k1 import AxK1Model, yarn_mscale
from mxnet_tpu.models.llama import LayerCache, LlamaFFN
from mxnet_tpu.models.mellum import RoutedFFN
from mxnet_tpu.ops import nn as ops
from mxnet_tpu.ops.pallas import decode_attention as da
from mxnet_tpu.profiler import core as prof
from mxnet_tpu.serve.generate import CacheLayout, KVCache

from test_host_spans import drive, traced
from test_mellum import (Bundle, Spy, _harness, close, gap_of, serve_all,
                         tokens_of)

TOL = 1e-4
PAGE = 8
FAULTS = ("rope_key_unrotated", "latent_norm_left_out", "mscale_left_out",
          "group_limit_left_out", "routed_scale_left_out",
          "shared_expert_left_out", "first_layer_at_expert_width")


@pytest.fixture(scope="module")
def bundle():
    da.use_interpret(True)   # the paged kernel, interpreted on the CPU
    yield Bundle("ax_k1.json")
    da.use_interpret(False)
    da.force_path(None)


# -- the model on the normal path -------------------------------------------------

def test_full_pass_matches_reference(bundle):
    toks = np.asarray(tokens_of(1, 70, 70), np.int32)
    with mx.autograd.predict_mode():
        got = bundle.net(mx.np.array(toks)).asnumpy()
    close(got, bundle.reference(toks), TOL)


def test_bf16_operands_fail(bundle):
    toks = np.asarray(tokens_of(1, 70, 70), np.int32)
    low = bundle.reference(toks, bundle.ref.controls("float32")["bfloat16"])
    with pytest.raises(AssertionError):
        close(low, bundle.reference(toks), TOL)


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_faults_fail(bundle, fault):
    """Each fault the reference can plant moves the logits far over the
    tolerance: the comparison sees the shared key's rotation, the
    latent's norm, YaRN's factor in the scale, the group limit, the
    routed factor, the shared expert and the dense layer's width."""
    assert set(FAULTS) == set(bundle.ref.FAULTS)
    toks = np.asarray(tokens_of(1, 70), np.int32)
    bad = bundle.reference(toks, bundle.ref.controls("float32")
                           ["fault_" + fault])
    with mx.autograd.predict_mode():
        got = bundle.net(mx.np.array(toks)).asnumpy()
    assert gap_of(got, bad) > 1000 * TOL


def test_the_first_layer_is_dense_and_the_others_routed(bundle):
    kinds = [type(blk.ffn) for blk in bundle.net._blocks]
    assert kinds == [LlamaFFN, RoutedFFN, RoutedFFN]
    assert bundle.cfg["first_k_dense_replace"] == 1
    ffn = bundle.net._blocks[1].ffn
    assert ffn._groups == (4, 2) and ffn._scale == 2.5
    assert ffn._score == "sigmoid" and ffn._shared == 1
    # the softmax scale carries YaRN's factor squared, the tables none
    m = yarn_mscale(32, 1)
    assert m == pytest.approx(1.34657, abs=1e-5)
    att = bundle.net._blocks[0].attention
    assert att._scale == pytest.approx(24 ** -0.5 * m * m)
    assert att._rope_scaling[-1] == 1.0


# -- chunked prefill and decode through latent pages --------------------------------

@pytest.mark.parametrize("path", ["pallas", "xla"])
def test_engine_matches_reference(bundle, path):
    """Two requests of 45 + 25 and 23 + 25 positions, prefilled in chunks
    of a page and decoded through the latent pages, every served
    position's logits against the reference's one full (non-absorbed)
    pass: the interpreted paged kernel, and the loop over pages in plain
    XLA that a decode step falls back to (counted as a fallback)."""
    da.force_path(None if path == "pallas" else "xla")
    da.reset_fallbacks()
    try:
        eng = bundle.engine(slots=2, name=f"axk1_{path}")
        eng.warmup()
        spy = Spy(eng)
        prompts = tokens_of(2, 45, 23)
        res = serve_all(eng, prompts, 25)
        wants = {i: bundle.reference([prompts[i] + res[i]["tokens"]])[0]
                 for i in range(2)}
        worst, n = spy.worst(wants)
        assert n >= 6 + 3 + 2 * 24 and worst <= TOL, worst
        assert eng.session.signature_count() == 2
        eng.assert_no_recompiles()
        if path == "pallas":
            assert da.last_path() == "pallas_paged"
            assert da.fallback_count() == 0
        else:
            assert da.last_path() == "xla_blocks"
            assert da.fallback_count() > 0
        eng.close()
    finally:
        da.force_path(None)
        da.reset_fallbacks()


def test_the_absorbed_form_equals_the_non_absorbed(bundle):
    """The program against itself: the normal path decompresses keys and
    values a head, the engine's step never does (one array a position,
    the queries carried into the latent)."""
    eng = bundle.engine(slots=1, name="axk1_absorbed")
    eng.warmup()
    spy = Spy(eng)
    prompt = tokens_of(7, 37)[0]
    res = serve_all(eng, [prompt], 12)[0]
    with mx.autograd.predict_mode():
        want = bundle.net(mx.np.array(
            np.asarray([prompt + res["tokens"]], np.int32))).asnumpy()[0]
    worst, n = spy.worst({0: want})
    assert n == 5 + 11 and worst <= TOL, worst
    eng.close()


def test_the_prefix_cache_shares_latent_pages(bundle):
    """An unbounded layer without state: the prefix cache serves it. The
    second request's first two pages are the first's, its tokens what
    they are without the cache."""
    shared = tokens_of(3, 2 * PAGE + 3)[0]
    prompts = [shared + [5, 6, 7], shared + [9, 8]]
    outs = {}
    for on in (False, True):
        eng = bundle.engine(slots=2, prefix_cache=on, name=f"axk1_pc{on}")
        eng.warmup()
        first = serve_all(eng, prompts[:1], 6)
        outs[on] = [r["tokens"] for r in first + serve_all(eng, prompts[1:],
                                                           6)]
        if on:
            assert eng.metrics.snapshot()["prefix_hit_rate"] > 0
            st = eng.stats()
            assert st["pool"]["pages_owned"] == 0
            assert st["pool"]["pages_used"] == st["prefix"]["pages_held"] > 0
        eng.close()
    assert outs[True] == outs[False]


# -- the one description of a latent layer's cache -----------------------------------

def test_a_latent_layer_keeps_one_array_a_position(bundle):
    net, cfg = bundle.net, bundle.cfg
    spec = net.cache_spec()
    wide = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]          # 40
    stored = da.latent_width(wide)                                # 128
    assert spec == [LayerCache(1, stored, (), None, cfg["kv_lora_rank"])] * 3
    assert da.latent_width(576) == 640 and da.latent_width(640) == 640
    layout = CacheLayout(net)
    assert layout.kinds == ["latent"] * 3 and len(layout) == 3
    assert layout.has_latent and not layout.has_state \
        and layout.window is None
    pool = serve.PagedKVPool(net, num_slots=2, max_seq=64, page_size=PAGE)
    assert [a.shape for a in pool.flat()] == [(17, 1, PAGE, stored)] * 3
    assert pool.latent_nbytes() == pool.nbytes() == 3 * 17 * PAGE * stored * 4
    assert pool.latent_bytes_per_position() == 3 * stored * 4
    assert pool.window_nbytes() == 0 and pool.state_nbytes() == 0
    st = pool.stats()
    assert st["latent_nbytes"] == pool.nbytes()
    # the flat order holds the one array where another layer holds two
    cache = KVCache.from_flat(pool.flat(), 64, layout=layout)
    assert cache._v == [None] * 3
    assert [a.shape for a in cache.flat()] == [a.shape for a in pool.flat()]
    # a K/V model's layout is what it was
    from mxnet_tpu.models.llama import get_llama

    other = CacheLayout(get_llama("llama_tiny_test"))
    assert other.kinds == ["kv"] * 4 and not other.has_latent
    assert LayerCache(2, 16, ()).latent is None


def test_stats_gauge_and_spans_say_latent(bundle, tmp_path):
    eng = bundle.engine(slots=2, name="axk1_spans")
    eng.warmup()
    requests = [(tokens_of(4, 19)[0], 4), (tokens_of(5, 11)[0], 3)]
    (results, _), spans = traced(tmp_path, lambda: drive(eng, requests))
    st = eng.stats()
    nbytes = eng.pool.nbytes()
    assert st["kv_pool_bytes_latent"] == nbytes
    assert st["latent_bytes_per_position"] == 3 * 128 * 4
    assert st["pool"]["latent_nbytes"] == nbytes
    assert prof.get_counter("serve.kv_pool_bytes_latent") == nbytes
    assert eng.metrics.snapshot()["latent_pool_bytes"] == nbytes
    assert "kv_pool_bytes_window" not in st
    prefills = [s["stats"] for s in spans if s["name"] == "serve.prefill"]
    # chunks of 8 from 0, 8, 16 (3 real positions) and 0, 8 (3 real)
    want = [(0, 8), (8, 8), (16, 3), (0, 8), (8, 3)]
    assert sorted(s["kv_pairs_latent"] for s in prefills) == sorted(
        3 * (n * at + n * (n + 1) // 2) for at, n in want)
    assert all(s["kv_keys_visited"] > 0 and s["kv_keys_held"] == 3 * 128
               for s in prefills)
    decodes = [s["stats"] for s in spans if s["name"] == "serve.decode"]
    assert decodes and all(s["kv_positions_latent"] % 3 == 0
                           and "kv_positions_window" not in s
                           for s in decodes)
    # every live lane's every position, a layer: the first visit decodes
    # the lanes whose prompts have ended
    assert min(s["kv_positions_latent"] for s in decodes) >= 3 * 12
    assert [len(r["tokens"]) for r in results] == [4, 3]
    eng.close()


# -- what refuses a latent layer ----------------------------------------------------

def test_what_cannot_serve_a_latent_layer_says_so(bundle):
    net = bundle.net
    kw = dict(max_seq=128, page_size=PAGE, prefill_chunk=PAGE)
    with pytest.raises(MXNetError, match="Generator.*one latent array"):
        serve.Generator(net, max_seq=128)
    with pytest.raises(MXNetError, match="speculative.*one latent array"):
        serve.SpeculativeGenerator(net, net, max_seq=128)
    with pytest.raises(MXNetError, match="multi-step.*one latent array"):
        serve.ContinuousEngine(net, decode_path="pallas", multistep=True,
                               **kw)
    with pytest.raises(MXNetError, match="ring caches.*one latent array"):
        serve.ContinuousEngine(net, decode_path="baseline", **kw)
    with pytest.raises(MXNetError, match="no int8"):
        serve.ContinuousEngine(net, decode_path="int8", **kw)
    # the ops refuse what the layout let through
    x = mx.np.zeros((1, 4, 1, 40))
    with pytest.raises(MXNetError, match="float32 page pools alone"):
        ops.cached_attention(x, x, None, mx.np.zeros((1,), dtype="int32"),
                             path="baseline", v_width=32)
    q = jnp.zeros((1, 4, 1, 40))
    pool = jnp.zeros((3, 1, PAGE, 40))
    table, sp = jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32)
    for bad in (dict(v_width=None), dict(v_width=41), dict(v_width=32,
                                                           window=8)):
        with pytest.raises(ValueError, match="latent form"):
            da.paged_decode_attention(q, pool, None, table, sp, **bad)
    with pytest.raises(MXNetError, match="latent <= head_dim"):
        class Bad:
            def cache_spec(self):
                return [LayerCache(2, 40, (), None, 32)]
        CacheLayout(Bad())


# -- the router's group limit and factor ----------------------------------------------

def _old_route_top_k(logits, top_k, renormalize=True, score="softmax"):
    """``ops.nn.route_top_k`` as it stood before the group limit."""
    z = logits.astype(jnp.float32)
    p = jax.nn.softmax(z, axis=-1) if score == "softmax" \
        else jax.nn.sigmoid(z)
    w, idx = jax.lax.top_k(p, int(top_k))
    if renormalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, idx.astype(jnp.int32)


@pytest.mark.parametrize("score,k,experts", [("softmax", 8, 64),
                                             ("sigmoid", 8, 128)],
                         ids=["mellum2", "command_a_plus"])
def test_the_defaults_leave_the_other_models_routes_bit_for_bit(score, k,
                                                                experts):
    """Mellum-2's and Command A+'s routes: with no group limit and no
    factor the router traces the program it traced, and gives the bits it
    gave."""
    logits = jnp.asarray(np.random.RandomState(1).randn(37, experts),
                         jnp.float32)
    new = jax.make_jaxpr(lambda z: ops.route_top_k(z, k, True, score))(logits)
    old = jax.make_jaxpr(lambda z: _old_route_top_k(z, k, True, score))(
        logits)
    assert str(new) == str(old)
    w, idx = ops.route_top_k(logits, k, True, score)
    w0, idx0 = _old_route_top_k(logits, k, True, score)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w0))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx0))
    ffn = RoutedFFN(16, 12, experts, k, score=score)
    assert ffn._groups is None and ffn._scale is None
    # the factor multiplies the routed sum and nothing else
    mx.random.seed(2)
    ffn.initialize(mx.init.Normal(0.3))
    x = mx.np.array(np.random.RandomState(3).randn(2, 5, 16)
                    .astype("float32"))
    with mx.autograd.predict_mode():
        plain = ffn(x).asnumpy()
        ffn._scale = 2.5
        np.testing.assert_allclose(ffn(x).asnumpy(), 2.5 * plain, rtol=1e-5)


def _rank_pick(s, k, n_group, keep):
    """The group limit by an explicit ranking, in numpy."""
    n, e = s.shape
    per = e // n_group
    out = np.zeros((n, k), np.int64)
    for t in range(n):
        score = [np.sort(s[t, g * per:(g + 1) * per])[-2:].sum()
                 for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: (-score[g], g))[:keep]
        allowed = [j for j in range(e) if j // per in kept]
        out[t] = sorted(allowed, key=lambda j: (-s[t, j], j))[:k]
    return out


def test_the_group_limit_keeps_a_tokens_experts_in_its_best_groups():
    rs = np.random.RandomState(6)
    z = rs.randn(50, 24).astype(np.float32)
    z[0] = 0.0                      # every score equal: the lowest indices
    z[1, :] = -3.0
    z[1, [5, 11, 17, 23]] = 4.0     # one high expert a group: groups tie
    w, idx = ops.route_top_k(jnp.asarray(z), 4, True, "sigmoid", (4, 2))
    s = 1.0 / (1.0 + np.exp(-z.astype(np.float64)))
    want = _rank_pick(np.asarray(jax.nn.sigmoid(jnp.asarray(z))), 4, 4, 2)
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(want, -1))
    assert sorted(np.asarray(idx)[0]) == [0, 1, 2, 3]
    assert set(np.asarray(idx)[1]) >= {5, 11}       # groups 0 and 1 kept
    groups = np.asarray(idx) // 6
    assert all(len(set(g)) <= 2 for g in groups)
    # without the limit some token takes more than two groups
    _, free = ops.route_top_k(jnp.asarray(z), 4, True, "sigmoid")
    assert any(len(set(g)) > 2 for g in np.asarray(free) // 6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    picked = np.take_along_axis(s, np.asarray(idx, np.int64), 1)
    np.testing.assert_allclose(np.asarray(w),
                               picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    for bad in ((5, 2), (4, 5), (4, 0), (24, 4)):
        with pytest.raises(MXNetError, match="router groups"):
            ops.route_top_k(jnp.asarray(z), 4, True, "sigmoid", bad)
    with pytest.raises(MXNetError, match="router groups"):
        ops.route_top_k(jnp.asarray(z), 13, True, "sigmoid", (4, 2))


# -- the twenty-four shares ------------------------------------------------------------

def test_the_24_shares_add_up_to_the_uncut_layer(bundle):
    """One routed layer at 192 experts in 8 groups of which a token keeps
    4 and takes 8: the routed parts of ``experts_held = (8 i, 8)``, i = 0
    .. 23, with attention and the shared expert counted once, equal the
    uncut reference's layer. Three shares make a group, so a token's 8
    experts lie in at most 12 of the 24 shares."""
    ref, h = bundle.ref, bundle.h
    cfg = dict(bundle.cfg, num_hidden_layers=1, first_k_dense_replace=0,
               router_experts=192, n_routed_experts=192, n_group=8,
               topk_group=4, num_experts_per_tok=8)
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
            dict(cfg, n_routed_experts=share[1], experts_held=share), False)
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
        hid = xs + first.attention(first.attn_norm(xs))
        z = first.ffn_norm(hid)
        total = (hid + first.ffn(z)).asnumpy()
        shared = ops.shared_experts(
            z, first.ffn.shared_gate_weight.data(),
            first.ffn.shared_up_weight.data(),
            first.ffn.shared_down_weight.data()).asnumpy()
        gave = np.zeros((24,) + x.shape[:2], bool)
        gave[0] = np.abs(first.ffn(z).asnumpy() - shared).max(-1) > 0
        for i in range(1, 24):
            part = block((8 * i, 8)).ffn(z).asnumpy() - shared
            gave[i] = np.abs(part).max(-1) > 0
            total = total + part
    close(total, want, 1e-5)
    per_token = gave.sum(0)
    assert 1 <= per_token.min() and per_token.max() <= 8
    # the shares that gave a token anything lie in at most 4 groups of 3
    groups = gave.reshape(8, 3, *x.shape[:2]).any(1).sum(0)
    assert groups.max() <= 4 and groups.min() >= 1
    assert gave.any((1, 2)).all()      # every share had tokens of its own
    # and one share alone is far from the whole layer
    assert gap_of((hid + first.ffn(z)).asnumpy(), want) > 1000 * TOL
