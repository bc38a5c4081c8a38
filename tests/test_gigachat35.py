"""GigaChat 3.5 (Gated DeltaNet layers that keep a recurrent state alone,
beside gated latent attention served from latent pages, in one cache;
8 of the router's experts held, chosen by score plus a correction bias,
every SwiGLU clamped) through the normal path and
``serve.ContinuousEngine``, against the plain reference
``chipbench/reference/gigachat35.py``, which runs the delta rule position
by position and latent attention non-absorbed: the benchmark's
configuration at the tiny widths of its ``rehearse`` group (2 key heads
and 4 value heads of 8 in the linear layers, 4 latent heads, 4 held of 16
experts, top 4; a linear dense layer, a linear routed one, a full routed
one), with the benchmark's seeded weights. Logits are compared, never
tokens.

Tolerances are ``tests/test_ax_k1.py``'s: everything is float32 at full
precision, so the program and the reference differ by the order of their
sums alone (the chunked rule's triangular solve against the recurrence,
the absorbed products against decompressed keys and values): the full
pass reads 4e-5 of the logits' spread here and the engine's steps 1e-5,
the bfloat16 control 1.8 and the least planted fault that can show 1.6.
"""
import hashlib

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve
from mxnet_tpu.models.gigachat35 import GatedDeltaNet, GigaChat35Model
from mxnet_tpu.models.llama import LayerCache, LlamaFFN
from mxnet_tpu.models.mellum import RoutedFFN
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import nn as ops
from mxnet_tpu.ops.pallas import decode_attention as da
from mxnet_tpu.profiler import core as prof
from mxnet_tpu.serve.generate import CacheLayout, KVCache

from test_mellum import Bundle, Spy, close, gap_of, serve_all, tokens_of

TOL = 1e-4
PAGE = 8
CONFIG = "gigachat3_5_432b_a28b.json"


@pytest.fixture(scope="module")
def bundle():
    da.use_interpret(True)   # the paged kernel, interpreted on the CPU
    yield Bundle(CONFIG)
    da.use_interpret(False)


def mamba2_init(b, seed=3):
    """Mamba-2's initialisation of each linear layer's decay, in the
    program and in the reference's weights alike: ``A_log = log U(1,
    16)``, ``dt_bias`` the inverse softplus of a ``dt`` log-uniform in
    [0.001, 0.1], so that some heads keep their state for hundreds of
    positions."""
    rs = np.random.RandomState(seed)
    params = b.net.collect_params()
    for i, blk in enumerate(b.net._blocks):
        if blk._latent:
            continue
        hv = blk.mixer._hv
        a_log = np.log(rs.uniform(1, 16, hv)).astype(np.float32)
        dt = np.exp(rs.uniform(np.log(1e-3), np.log(0.1), hv))
        dt_bias = (dt + np.log(-np.expm1(-dt))).astype(np.float32)
        for prog, ref, val in (("a_log", "a_log", a_log),
                               ("dt_bias", "dt_bias", dt_bias)):
            params[f"layer{i}.mixer.{prog}"].set_data(NDArray(
                jax.numpy.asarray(val)))
            b.w[f"layer{i}.{ref}"] = jax.numpy.asarray(val)
    return b


@pytest.fixture(scope="module")
def remembers():
    """The bundle with Mamba-2's decay initialisation."""
    da.use_interpret(True)
    yield mamba2_init(Bundle(CONFIG))
    da.use_interpret(False)


# -- the model on the normal path ---------------------------------------------

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


SEEN = ("lane_not_reset", "decay_left_out", "beta_left_out",
        "qk_not_normalised", "conv_state_lost_at_chunk_edges",
        "attention_gate_left_out", "bias_in_weights")


@pytest.mark.parametrize("fault", SEEN)
def test_planted_faults_fail(bundle, monkeypatch, fault):
    """Each fault that changes the mathematics moves the logits far over
    the tolerance: the comparison sees a lane's reset, the decay, beta,
    the norms of q and k, the conv's inputs carried across a chunk's edge
    (here the test's chunk of 16; the chip's is a page of 128), the
    attention's gate and where the correction bias goes."""
    assert set(SEEN) | {"gate_factor_left_out"} == set(bundle.ref.FAULTS)
    monkeypatch.setattr(bundle.ref, "CHUNK", 16)
    toks = np.asarray(tokens_of(1, 70, 70), np.int32)
    bad = bundle.reference(toks, bundle.ref.controls("float32")
                           ["fault_" + fault])
    with mx.autograd.predict_mode():
        got = bundle.net(mx.np.array(toks)).asnumpy()
    assert gap_of(got, bad) > 1000 * TOL


def test_the_gates_factor_is_divided_out_by_the_post_norm(bundle):
    """The Gated DeltaNet gate's factor of 2 multiplies the mixer's
    output by a constant, and the block's post-norm divides any constant
    out (to its epsilon): left out, it moves no logit past the rounding.
    A planted fault that cannot show, kept so that its reading says so."""
    toks = np.asarray(tokens_of(1, 70), np.int32)
    bad = bundle.reference(toks, bundle.ref.controls("float32")
                           ["fault_gate_factor_left_out"])
    close(bad, bundle.reference(toks), TOL)


def test_the_layers_kinds(bundle):
    blocks = bundle.net._blocks
    assert [blk._latent for blk in blocks] == [False, False, True]
    assert [type(blk.ffn) for blk in blocks] == [LlamaFFN, RoutedFFN,
                                                 RoutedFFN]
    assert isinstance(blocks[0].mixer, GatedDeltaNet)
    ffn = blocks[1].ffn
    assert ffn._biased and ffn._groups is None and ffn._scale == 2.5
    assert ffn._limit == 10.0 and blocks[0].ffn._limit == 10
    att = blocks[2].attention
    assert att._gated
    # YaRN factor 8: the softmax scale carries m^2, m = 0.1 ln 8 + 1
    m = 0.1 * np.log(8) + 1
    assert att._scale == pytest.approx(24 ** -0.5 * m * m)


# -- chunked prefill and decode through the pool ------------------------------

def test_engine_matches_reference(bundle):
    """Two requests of 45 + 25 and 23 + 25 positions (neither prompt a
    whole number of chunks), prefilled in chunks of a page and decoded
    through the pool: every served position's logits against the
    reference's one full pass, the delta rule position by position."""
    da.reset_fallbacks()
    eng = bundle.engine(slots=2, name="gigachat_engine")
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
    assert da.last_path() == "pallas_paged" and da.fallback_count() == 0
    # a model with state never carries decode lanes in a chunk's call
    assert eng.stats()["mixed"]["calls"] == 0
    eng.close()


def test_a_lane_that_changes_tenant_starts_from_zero(remembers):
    """One lane, two tenants, with decays that keep state for hundreds of
    positions: the second request's logits are the reference's for it
    alone, and the reference whose second row starts from the state the
    first ended on is far from them, so a lane not reset would show."""
    b = remembers
    eng = b.engine(slots=1, name="gigachat_tenants")
    eng.warmup()
    spy = Spy(eng)
    first, second = tokens_of(4, 30, 21)
    done = serve_all(eng, [first], 9)[0]["tokens"]
    resets = prof.get_counter("serve.state_resets")
    spy.calls.clear()
    got = serve_all(eng, [second], 12)[0]["tokens"]
    assert prof.get_counter("serve.state_resets") == resets + 1
    alone = b.reference([second + got])[0]
    worst, n = spy.worst({0: alone})
    assert n == 3 + 11 and worst <= TOL, worst
    rows = [first + done, second + got]
    width = max(map(len, rows))
    toks = np.zeros((2, width), np.int32)
    for i, r in enumerate(rows):
        toks[i, :len(r)] = r
    carried = b.reference(toks, b.ref.controls("float32")
                          ["fault_lane_not_reset"])
    # row 0 ends on its padding: the state it hands row 1 is the lane's
    # after more positions than the engine's, which changes nothing of
    # what is asserted: that any carried state shows
    assert gap_of(carried[1][:len(rows[1])], alone) > 1000 * TOL
    eng.close()


def test_the_chunk_call_counts_the_state_positions(bundle):
    """The positions the linear layers advance: a chunk's real positions
    and a decode step's live lanes, in each of the layers that keep
    state, beside ``serve.state_lane_steps``."""
    eng = bundle.engine(slots=2, name="gigachat_counts")
    eng.warmup()
    before = {c: prof.get_counter(c) for c in ("serve.state_positions",
                                               "serve.state_lane_steps")}
    serve_all(eng, tokens_of(6, 19), 5)
    got = {c: prof.get_counter(c) - v for c, v in before.items()}
    # 3 chunks (8 + 8 + 3 positions) and 4 decode steps of one lane, in
    # every layer: the latent one keeps no state and counts in none
    assert eng._n_state_layers == 2
    assert got == {"serve.state_positions": 2 * (19 + 4),
                   "serve.state_lane_steps": 3 + 4}
    assert eng._chunk_keys(8, 3)["state_positions"] == 2 * 3
    eng.close()


# -- the rule's two forms -----------------------------------------------------

def _recurrence(q, k, v, g, beta, s0):
    """The rule position by position in float64."""
    b, t, hk, dk = q.shape
    h = v.shape[2]
    rep = h // hk

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q, k = unit(q) * dk ** -0.5, unit(k)
    s = s0.astype(np.float64).copy()
    o = np.zeros(v.shape)
    for i in range(t):
        kt, qt = np.repeat(k[:, i], rep, 1), np.repeat(q[:, i], rep, 1)
        ks = np.einsum("bhk,bhkv->bhv", kt, s)
        s = np.exp(g[:, i])[..., None, None] * (
            s - beta[:, i][..., None, None] * kt[..., None] * ks[:, :, None])\
            + beta[:, i][..., None, None] * kt[..., None] \
            * v[:, i][:, :, None]
        o[:, i] = np.einsum("bhk,bhkv->bhv", qt, s)
    return o, s


def _rule_inputs(seed, b=3, t=37, hk=2, h=4, dk=8, dv=6):
    rs = np.random.RandomState(seed)
    return (rs.randn(b, t, hk, dk), rs.randn(b, t, hk, dk) + 0.5,
            rs.randn(b, t, h, dv), -0.3 * rs.rand(b, t, h),
            1 / (1 + np.exp(-rs.randn(b, t, h))), 0.2 * rs.randn(b, h, dk, dv))


def _f32(*xs):
    return [mx.np.array(np.asarray(x, np.float32)) for x in xs]


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_the_chunked_rule_is_the_recurrence(chunk):
    """Across chunk edges, from a start state, with a padded tail and a
    dead lane: row 0 starts from its state (start 5) and has 30 real
    positions, row 1 starts afresh (start 0: its state is ignored), row 2
    is not live and hands its state back bit for bit."""
    q, k, v, g, beta, s0 = _rule_inputs(0)
    sp = mx.np.array(np.array([5, 0, 9], np.int32))
    vl = mx.np.array(np.array([30, 37, 37], np.int32))
    live = mx.np.array(np.array([True, True, False]))
    o, s = ops.gated_delta_rule(*_f32(q, k, v, g, beta, s0), sp, vl, live,
                                chunk=chunk)
    o, s = o.asnumpy(), s.asnumpy()
    want0, end0 = _recurrence(q[:1, :30], k[:1, :30], v[:1, :30],
                              g[:1, :30], beta[:1, :30], s0[:1])
    want1, end1 = _recurrence(q[1:2], k[1:2], v[1:2], g[1:2], beta[1:2],
                              np.zeros_like(s0[1:2]))
    np.testing.assert_allclose(o[0, :30], want0[0], atol=2e-6)
    np.testing.assert_allclose(o[1], want1[0], atol=2e-6)
    np.testing.assert_allclose(s[0], end0[0], atol=2e-6)
    np.testing.assert_allclose(s[1], end1[0], atol=2e-6)
    assert np.array_equal(s[2], s0[2].astype(np.float32))


def test_the_one_step_rule_is_the_recurrence():
    """Position after position through the decode form, the state handed
    from call to call, against the same recurrence."""
    q, k, v, g, beta, s0 = _rule_inputs(1, b=2, t=12)
    want, end = _recurrence(q, k, v, g, beta, s0)
    s = _f32(s0)[0]
    sp = mx.np.array(np.array([3, 3], np.int32))
    for i in range(12):
        o, s = ops.gated_delta_rule(
            *_f32(q[:, i:i + 1], k[:, i:i + 1], v[:, i:i + 1],
                  g[:, i:i + 1], beta[:, i:i + 1]), s, sp)
        np.testing.assert_allclose(o.asnumpy()[:, 0], want[:, i], atol=2e-6)
    np.testing.assert_allclose(s.asnumpy(), end, atol=2e-6)


# -- experts: the shares, the bias, the clamp ---------------------------------

def test_the_32_shares_and_the_shared_expert_add_up_to_the_layer():
    """64 experts in 32 shares of 2, each share's routed part with the
    correction bias and the clamp, and the shared expert counted once,
    add up to the uncut layer."""
    rs = np.random.RandomState(7)
    e, hid, f, k = 64, 16, 12, 8
    x = mx.np.array(rs.randn(2, 5, hid).astype(np.float32))
    router = mx.np.array(rs.randn(e, hid).astype(np.float32))
    bias = mx.np.array(0.05 * rs.randn(e).astype(np.float32))
    gate, up = (mx.np.array(3 * rs.randn(e, hid, f).astype(np.float32))
                for _ in range(2))
    down = mx.np.array(rs.randn(e, f, hid).astype(np.float32))
    shared = [mx.np.array(3 * rs.randn(1, *s).astype(np.float32))
              for s in ((hid, f), (hid, f), (f, hid))]

    def part(first, count):
        out, _ = ops.routed_experts(
            x, router, gate[first:first + count], up[first:first + count],
            down[first:first + count], k, held=(first, count),
            score="sigmoid", scale=2.5, bias=bias, limit=10.0, impl="dense")
        return out.asnumpy()

    whole = part(0, e) + ops.shared_experts(x, *shared, limit=10.0).asnumpy()
    parts = sum(part(2 * i, 2) for i in range(32)) \
        + ops.shared_experts(x, *shared, limit=10.0).asnumpy()
    np.testing.assert_allclose(parts, whole, rtol=1e-5, atol=1e-5)


def test_the_bias_chooses_and_the_scores_weigh():
    """Scores 0.9, 0.8, 0.7, 0.6 of four experts, top 2: a bias of 0.5
    on the last takes it past the second, and the weights are the chosen
    experts' own scores, renormalised. In groups of two, the bias scores
    the groups too: the second group's 0.7 + 1.1 beats the first's 1.7."""
    import jax.numpy as jnp

    p = jnp.asarray([[0.9, 0.8, 0.7, 0.6]], jnp.float32)
    logits = jnp.log(p / (1 - p))
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.5], jnp.float32)
    w, idx = ops.route_top_k(logits, 2, score="sigmoid", bias=bias)
    assert idx.tolist() == [[3, 0]]
    np.testing.assert_allclose(w, [[0.6 / 1.5, 0.9 / 1.5]], rtol=1e-6)
    _, idx = ops.route_top_k(logits, 2, score="sigmoid")
    assert idx.tolist() == [[0, 1]]
    w, idx = ops.route_top_k(logits, 2, score="sigmoid", bias=bias,
                             groups=(2, 1))
    assert idx.tolist() == [[3, 2]]
    np.testing.assert_allclose(w, [[0.6 / 1.3, 0.7 / 1.3]], rtol=1e-6)
    _, idx = ops.route_top_k(logits, 2, score="sigmoid", groups=(2, 1))
    assert idx.tolist() == [[0, 1]]


def test_the_swiglu_clamp():
    g = np.asarray([-20.0, -3.0, 0.5, 12.0], np.float32)
    u = np.asarray([-15.0, 2.0, -11.0, 30.0], np.float32)
    got = np.asarray(ops.swiglu(g, u, 10.0))
    gc, uc = np.minimum(g, 10.0), np.clip(u, -10.0, 10.0)
    np.testing.assert_allclose(got, gc / (1 + np.exp(-gc)) * uc, rtol=1e-6)
    assert np.array_equal(np.asarray(ops.swiglu(g, u)),
                          np.asarray(jax.nn.silu(g) * u))


# -- a layer that keeps no position -------------------------------------------

def test_a_state_only_layer_allocates_no_pool_array(bundle):
    net, cfg = bundle.net, bundle.cfg
    spec = net.cache_spec()
    conv = 2 * 2 * 8 + 4 * 8
    assert spec[:2] == [LayerCache(0, 0, ((3, conv), (4, 8, 8)))] * 2
    assert spec[2].latent == cfg["kv_lora_rank"]
    layout = CacheLayout(net)
    assert layout.kinds == ["state"] * 4 + ["latent"]
    assert layout.has_state and layout.has_latent
    pool = serve.PagedKVPool(net, num_slots=2, max_seq=64, page_size=PAGE)
    shapes = [a.shape for a in pool.flat()]
    stored = da.latent_width(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    assert shapes == [(2, 3, conv), (2, 4, 8, 8)] * 2 \
        + [(17, 1, PAGE, stored)]
    assert pool.latent_nbytes() == 17 * PAGE * stored * 4
    assert pool.state_nbytes() == 2 * 2 * (3 * conv + 4 * 8 * 8) * 4
    assert pool.nbytes() == pool.latent_nbytes() + pool.state_nbytes()
    # only the latent layer holds positions
    assert pool.latent_bytes_per_position() == stored * 4
    cache = KVCache.from_flat(pool.flat(), 64, layout=layout)
    assert cache._k[:2] == [None, None] and cache._v == [None] * 3
    assert [a.shape for a in cache.flat()] == shapes
    eng = bundle.engine(slots=2, name="gigachat_layers")
    assert (eng._n_full_layers, eng._n_latent_layers,
            eng._n_window_layers, eng._n_state_layers) == (1, 1, 0, 2)
    eng.close()


def test_a_model_of_state_alone():
    """Every layer linear: no page pool at all, and the engine serves it."""
    net = GigaChat35Model(
        vocab_size=64, units=16, num_layers=2, full_attention=(),
        first_dense=1, num_heads=2, q_rank=8, kv_rank=8, nope_dim=4,
        rope_dim=4, v_dim=4,
        linear=dict(num_k_heads=1, num_v_heads=2, k_dim=4, v_dim=4),
        hidden_size=24, moe=dict(expert_size=8, num_experts=4, top_k=2,
                                 score="sigmoid", num_shared=1,
                                 score_bias=True), swiglu_limit=10.0)
    net.initialize(mx.init.Normal(0.3))
    layout = CacheLayout(net)
    assert layout.kinds == ["state"] * 4 and not layout.has_latent
    eng = serve.ContinuousEngine(net, max_seq=32, num_slots=2, page_size=8,
                                 prefill_chunk=8, decode_path="pallas",
                                 name="gigachat_state_alone")
    assert eng.pool.nbytes() == eng.pool.state_nbytes()
    eng.warmup()
    prompt = tokens_of(9, 11, vocab=64)[0]
    got = serve_all(eng, [prompt], 4)[0]["tokens"]
    with mx.autograd.predict_mode():
        for t in got:
            logits = net(mx.np.array(np.asarray([prompt], np.int32)))
            assert int(logits.asnumpy()[0, -1].argmax()) == t
            prompt = prompt + [t]
    eng.close()


# -- shared code stays as it was ----------------------------------------------

# sha256 of every served position's logits (float32 bytes, call order) of
# one request through the engine at the configuration's rehearsal sizes,
# read on the tree before the gate, the bias, the clamp and the layer
# without positions were added to the shared code
SERVED = {
    "ax_k1.json":
        "6601a666d5f2554c4d9433ed39e540d8041853ae3b99f30abd6863ac4560273d",
    "falcon_h1_34b.json":
        "169015e235ce3a5892744df0178d1e8c6cfa48d3c395355fd4d75e2c72ff012f",
    "mistral_7b_v01.json":
        "a4f0a3ec58166ac7e1d016751a6ac7476152bbb08a261b148061b9b84c0838c7",
    "mellum2_12b_a2_5b.json":
        "92c4d73b6e932dfad8267b5ed32f8b5ef8db8cea6f8b460921e720124b22f421",
    "command_a_plus_05_2026.json":
        "bf2fa6edf0fc6b22731b3596057b8a69c992aa68820728cb9321cff2400034f4",
}


def served_digest(config):
    b = Bundle(config)
    eng = b.engine(slots=2, name="digest_" + config.split(".")[0])
    eng.warmup()
    spy = Spy(eng)
    serve_all(eng, tokens_of(11, 21, 13), 7)
    eng.close()
    h = hashlib.sha256()
    for *_, out in spy.calls:
        h.update(np.ascontiguousarray(out, np.float32).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("config", sorted(SERVED))
def test_shared_code_serves_the_same_logits(config):
    da.use_interpret(True)
    try:
        assert served_digest(config) == SERVED[config]
    finally:
        da.use_interpret(False)


# -- the names in the two step executables ------------------------------------

def test_both_executables_carry_the_rules_scope(bundle):
    """The engine's decode step and prefill chunk, lowered from the calls
    the engine made: the rule stands under ``delta.rule`` inside each
    linear layer's ``mixer`` block, the conv under ``ssm.conv``; the
    latent layer's absorbed products and the gate under ``attention``."""
    from mxnet_tpu import cachedop
    from test_device_scopes import op_names, scopes_in

    seen, real = {}, cachedop.CachedOpThreadSafe._run_fwd

    def spy(self, entry, snap, rng_key, arg_datas):
        t = arg_datas[0].shape[1]
        if t not in seen:
            spec = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                (snap.tp_datas, snap.st_datas, rng_key, tuple(arg_datas)))
            seen[t] = entry["fwd"].lower(*spec[:3], *spec[3])
        return real(self, entry, snap, rng_key, arg_datas)

    cachedop.CachedOpThreadSafe._run_fwd = spy
    try:
        eng = bundle.engine(slots=2, name="gigachat_scopes")
        serve_all(eng, tokens_of(8, 11), 3)
        eng.close()
    finally:
        cachedop.CachedOpThreadSafe._run_fwd = real
    assert set(seen) == {1, PAGE}
    for t, kind in ((1, "decode"), (PAGE, "prefill")):
        names = op_names(seen[t].compile().as_text())
        found = scopes_in(names)
        assert {f"serve_step.{kind}", "delta.rule", "ssm.conv", "attn.absorb",
                "experts.router", "experts.routed", "experts.shared"} <= found
        for layer in (0, 1):
            assert any(f"/model/layer{layer}/mixer/" in n
                       and "delta.rule" in n for n in names)
        assert not any("/layer2/mixer/" in n for n in names)
        assert any("/model/layer2/attention/gate_proj/" in n for n in names)
        # the prefill chunk takes and puts back its one row of the state
        assert ("ssm.state" in found) == (kind == "prefill")
