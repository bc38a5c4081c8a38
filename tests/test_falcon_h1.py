"""Falcon-H1 (a Mamba-2 mixer beside GQA attention in every block) through
the normal path, ``serve.Generator`` and ``serve.ContinuousEngine``,
against the plain reference ``chipbench/reference/falcon_h1.py``: the
benchmark's configuration at the tiny widths of its ``rehearse`` group,
with the benchmark's seeded weights.

Tolerances. Everything is float32 and every matrix product runs at full
precision, so the program and the reference differ by the order of their
sums alone: the chunked scan against the position-by-position recurrence,
gemm against einsum. ``TOL`` is 1e-4 of the logits' spread (the full
pass reads 1.2e-6 here); with bfloat16 operands the reference itself moves
by 1.3e-2 of that spread and fails it, which ``test_bf16_operands_fail``
holds.
Rows served by the same executables in another lane, or beside other
tenants, must agree far closer (``SAME``, 1e-6 of the spread): only the
batch row differs.
"""
import importlib.util
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models.llama import LlamaModel, get_llama
from mxnet_tpu.ops import nn as ops
from mxnet_tpu.serve.generate import CacheLayout, KVCache
from mxnet_tpu.serve.kv_blocks import PagedKVPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL, SAME = 1e-4, 1e-6
SEED = 5


def _harness():
    spec = importlib.util.spec_from_file_location(
        "chipbench_harness_for_tests",
        os.path.join(ROOT, "chipbench", "harness.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bundle:
    """The program's model and the reference over the same weights."""

    def __init__(self):
        import jax.numpy as jnp

        h = _harness()
        with open(os.path.join(ROOT, "chipbench", "configs",
                               "falcon_h1_34b.json")) as f:
            cfg = json.load(f)
        self.cfg = cfg = h.merged(cfg, cfg["rehearse"])
        self.ref = h.load_module("reference", cfg["reference"])
        adapter = h.load_module("adapters", cfg["adapter"])
        maker = h.load_module(".", "weights").Maker(
            self.ref.param_shapes(cfg), SEED, cfg["initializer_range"])
        self.net = adapter.build(cfg, False)
        h.load_weights(self.net, adapter.name_map(cfg), maker)
        self.w = maker.all()
        self.jnp = jnp

    def reference(self, tokens, num=None):
        """(B, T, vocab) logits of the reference's full pass."""
        ref, cfg, w = self.ref, self.cfg, self.w
        num = num or ref.EXACT
        x = ref.embed(self.jnp.asarray(np.asarray(tokens, np.int32)),
                      w["embed"])
        for i in range(cfg["num_hidden_layers"]):
            p = {k.split(".", 1)[1]: v for k, v in w.items()
                 if k.startswith(f"layer{i}.")}
            x = ref.layer(x, p, cfg, num)
        return np.asarray(ref.logits(x, w["norm"], w["head"], cfg, num))


@pytest.fixture(scope="module")
def bundle():
    return Bundle()


def tokens_of(seed, *lengths, vocab=512):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, vocab, n).tolist() for n in lengths]


def close(got, want, tol):
    """Largest gap in units of the wanted logits' spread, under ``tol``."""
    got, want = np.asarray(got), np.asarray(want)
    gap = float(np.abs(got - want).max() / want.std())
    assert gap <= tol, f"gap {gap:.3g} of the spread, tolerance {tol:g}"
    return gap


# -- the model on the normal path ---------------------------------------------

def test_full_pass_matches_reference(bundle):
    toks = np.asarray(tokens_of(1, 37, 37), np.int32)   # 4 chunks of 8 + 5
    with mx.autograd.predict_mode():
        got = bundle.net(mx.np.array(toks)).asnumpy()
    close(got, bundle.reference(toks), TOL)


def test_bf16_operands_fail(bundle):
    toks = np.asarray(tokens_of(1, 37, 37), np.int32)
    ref = bundle.ref
    low = bundle.reference(toks, ref.controls("float32")["bfloat16"])
    with pytest.raises(AssertionError):
        close(low, bundle.reference(toks), TOL)


def test_llama_theta_reaches_attention():
    kw = dict(vocab_size=64, units=32, hidden_size=64, num_layers=1,
              num_heads=2, num_kv_heads=1)
    outs = []
    for theta in (10000.0, 500000.0):
        mx.random.seed(3)
        net = LlamaModel(theta=theta, **kw)
        net.initialize(mx.init.Normal(0.3))
        assert net._blocks[0].attention._theta == theta
        with mx.autograd.predict_mode():
            outs.append(net(mx.np.array(
                np.asarray(tokens_of(2, 24, vocab=64), np.int32))).asnumpy())
    assert np.abs(outs[0] - outs[1]).max() > 1e-4


# -- the scan and the conv: chunked against step by step -----------------------

def scan_inputs(seed, b=4, t=21, h=4, p=6, g=2, n=5):
    rs = np.random.RandomState(seed)
    f = lambda *s: mx.np.array(rs.randn(*s).astype("float32"))   # noqa: E731
    return dict(
        x=f(b, t, h, p),
        dt=mx.np.array(np.log1p(np.exp(rs.randn(b, t, h))).astype("float32")),
        a=mx.np.array(-np.exp(0.3 * rs.randn(h)).astype("float32")),
        b_mat=f(b, t, g, n), c_mat=f(b, t, g, n),
        d=f(h), state=f(b, h, p, n))


# a full row, a padded tail past a chunk edge (chunk 8, 13 valid), a row
# with nothing valid, a dead row; rows 0 and 3 start a request
START = np.asarray([0, 7, 3, 0], np.int32)
VALID = np.asarray([21, 13, 0, 21], np.int32)
LIVE = np.asarray([True, True, True, False])


def test_chunked_scan_matches_the_recurrence():
    k = scan_inputs(4)
    lane = dict(start_pos=mx.np.array(START), valid_len=mx.np.array(VALID),
                live=mx.np.array(LIVE))
    y, s = ops.ssd_scan(chunk=8, **k, **lane)
    y, s = y.asnumpy(), s.asnumpy()
    # the recurrence, a position at a time, through the same op at T = 1
    state, ys = k["state"], []
    for t in range(21):
        step = {n: k[n][:, t:t + 1] for n in ("x", "dt", "b_mat", "c_mat")}
        y_t, state = ops.ssd_scan(
            **step, a=k["a"], d=k["d"], state=state,
            start_pos=mx.np.array(START + t),
            valid_len=mx.np.array((t < VALID).astype(np.int32)),
            live=mx.np.array(LIVE))
        ys.append(y_t.asnumpy())
    y_seq, s_seq = np.concatenate(ys, axis=1), state.asnumpy()
    for row in (0, 1):
        v = VALID[row]
        assert np.abs(y[row, :v] - y_seq[row, :v]).max() < 2e-5
    assert np.abs(s - s_seq).max() < 2e-5
    given = k["state"].asnumpy()
    # (a) nothing valid: the state as it came; (b) a dead row: bit for bit
    assert np.array_equal(s[2], given[2])
    assert s[3].tobytes() == given[3].tobytes()
    # (c) a row whose request starts here began from zero, not from what
    # the lane held
    zeroed = dict(k, state=mx.np.array(np.zeros_like(given)))
    s0 = ops.ssd_scan(chunk=8, **zeroed, **lane)[1].asnumpy()
    assert np.array_equal(s[0], s0[0]) and not np.array_equal(s[1], s0[1])


def test_conv_carries_its_context_across_calls():
    rs = np.random.RandomState(6)
    b, t, c, k = 4, 21, 10, 4
    x = rs.randn(b, t, c).astype("float32")
    w, bias = rs.randn(c, k).astype("float32"), rs.randn(c).astype("float32")
    given = rs.randn(b, k - 1, c).astype("float32")
    out, new = ops.causal_conv1d(
        mx.np.array(x), mx.np.array(w), mx.np.array(bias),
        mx.np.array(given), mx.np.array(START), mx.np.array(VALID),
        mx.np.array(LIVE))
    out, new = out.asnumpy(), new.asnumpy()
    for row in (0, 1):
        before = np.zeros((k - 1, c), "float32") if START[row] == 0 \
            else given[row]
        win = np.concatenate([before, x[row]])
        want = bias + sum(win[j:j + t] * w[:, j] for j in range(k))
        assert np.abs(out[row] - want).max() < 1e-5
        v = VALID[row]
        assert np.array_equal(new[row], win[v:v + k - 1])
    assert np.array_equal(new[2], given[2])
    assert new[3].tobytes() == given[3].tobytes()


# -- one cache description, two kinds of state ----------------------------------

def test_rings_and_pool_are_built_from_the_models_description(bundle):
    net, cfg = bundle.net, bundle.cfg
    layout = CacheLayout(net)
    assert layout.has_state and layout.kinds == ["kv", "kv", "state",
                                                 "state"] * 2
    conv = cfg["mamba_d_ssm"] + 2 * cfg["mamba_n_groups"] \
        * cfg["mamba_d_state"]
    state = [(cfg["mamba_d_conv"] - 1, conv),
             (cfg["mamba_n_heads"], cfg["mamba_d_head"],
              cfg["mamba_d_state"])]
    kv = (cfg["num_key_value_heads"], cfg["head_dim"])
    rings = KVCache.alloc(net, 3, 32)
    assert [a.shape for a in rings.flat()[:4]] == [
        (3, kv[0], 32, kv[1])] * 2 + [(3,) + s for s in state]
    per_row = 4 * sum(int(np.prod(s)) for s in state)
    assert rings.state_nbytes() == 2 * 3 * per_row
    pool = PagedKVPool(net, 3, 32, page_size=8)
    assert [a.shape for a in pool.flat()[:4]] == [
        (pool.num_pages, kv[0], 8, kv[1])] * 2 + [(3,) + s for s in state]
    assert pool.state_nbytes() == 2 * 3 * per_row
    assert pool.stats()["state_nbytes"] == pool.state_nbytes()
    assert pool.nbytes() == pool.state_nbytes() \
        + 2 * 2 * 4 * pool.num_pages * kv[0] * 8 * kv[1]


def test_a_kv_only_model_keeps_the_convention_it_had():
    net = get_llama("llama_tiny_test")
    net.initialize()
    layout = CacheLayout(net)
    assert not layout.has_state and layout.kinds == ["kv"] * 4
    pool = PagedKVPool(net, 2, 32, page_size=8)
    assert len(pool.flat()) == 4 and pool.state_nbytes() == 0
    with pytest.raises(MXNetError, match="cache_spec"):
        CacheLayout(mx.gluon.nn.Dense(3))


# -- Generator: prefill, then decode, position by position ---------------------

@pytest.mark.parametrize("path", ["baseline", "pallas"])
def test_generator_prefill_then_decode_matches_reference(bundle, path):
    seqs = tokens_of(7, 40, 40)
    lens = np.asarray([11, 21], np.int32)   # 21: a padded tail in chunk 3
    want = bundle.reference(seqs)
    gen = serve.Generator(bundle.net, max_seq=64, batch_buckets=(2,),
                          prompt_buckets=(32,), decode_path=path,
                          name=f"fh1_gen_{path}")
    padded = np.zeros((2, 32), np.int32)
    for i, n in enumerate(lens):
        padded[i, :n] = seqs[i][:n]
    cache = gen._fresh_cache(2)
    logits, cache = gen.prefill(padded, lens, cache)
    got, ref = [logits.asnumpy()], [want[[0, 1], lens - 1]]
    pos = lens.copy()
    for _ in range(17):
        toks = [seqs[i][pos[i]] for i in range(2)]
        logits, cache = gen.decode_step(toks, pos, cache)
        got.append(logits.asnumpy())
        ref.append(want[[0, 1], pos])
        pos = pos + 1
    close(np.stack(got), np.stack(ref), TOL)
    assert gen.metrics.snapshot()["state_pool_bytes"] == cache.state_nbytes()


# -- ContinuousEngine: every request as if it were served alone ----------------

def engine_of(net, name, **kw):
    args = dict(max_seq=64, num_slots=3, page_size=8, prefill_chunk=8,
                decode_path="pallas", name=name)
    args.update(kw)
    return serve.ContinuousEngine(net, **args)


def drive(eng, waves, monkeypatch, check_dead_lanes=False):
    """Submit each wave of (prompt, max_new) and step a few times between
    waves, then to the end; returns ``{prompt: [logits of each sampled
    token]}`` as the scheduler saw them, and how many dead lanes' states
    were held to their bytes."""
    seen = {}
    real = eng._run_step

    def spy(tokens, start_pos, last_idx, table, lanes, keep):
        # the in-place step samples inside itself: the logits a token is
        # sampled from are read off the call that made them
        logits = real(tokens, start_pos, last_idx, table, lanes, keep)
        arr = logits.asnumpy()
        if np.shape(tokens)[1] == 1:
            rows = [(j, arr[j]) for j in lanes if j >= 0]
        else:
            # a prefill chunk: its one slot, if it is the prompt's last
            (j,) = lanes
            done = start_pos[0] + last_idx[0] + 1
            rows = ([(j, arr[0])] if j >= 0
                    and done == len(eng._slots[j].prompt) else [])
        for j, row in rows:
            seen.setdefault(tuple(eng._slots[j].prompt), []).append(row)
        return logits

    held = [0]
    if check_dead_lanes:
        decode = eng._decode_once

        def states():
            return [a.asnumpy() for a, k in zip(eng.pool.flat(),
                                                eng.pool.layout.kinds)
                    if k == "state"]

        def checked():
            dead = [j for j, s in enumerate(eng._slots)
                    if s is None or not s.decoding]
            before = states()
            decode()
            for b, a in zip(before, states()):
                for j in dead:
                    assert b[j].tobytes() == a[j].tobytes(), j
                    held[0] += bool(np.any(b[j]))
        monkeypatch.setattr(eng, "_decode_once", checked)
    eng.warmup()
    monkeypatch.setattr(eng, "_run_step", spy)
    futs = []
    for wave in waves:
        futs += [eng.submit(p, max_new_tokens=n) for p, n in wave]
        for _ in range(3):
            eng.step()
    for _ in range(400):
        if all(f.done() for f in futs):
            break
        eng.step()
    out = [f.result(0) for f in futs]
    eng.assert_no_recompiles()
    return seen, out, held[0]


def test_engine_serves_each_request_as_if_alone(bundle, monkeypatch):
    # six requests on three slots, staggered: lanes are re-used, the
    # 19- and 26-token prompts take 3 and 4 chunks of 8 while their
    # neighbours decode, and slots stand empty at both ends
    prompts = tokens_of(9, 5, 19, 11, 3, 26, 9)
    new = [9, 7, 10, 6, 8, 7]
    reqs = list(zip(prompts, new))
    eng = engine_of(bundle.net, "fh1_cb")
    together, out, held = drive(eng, [reqs[:2], reqs[2:5], reqs[5:]],
                                monkeypatch, check_dead_lanes=True)
    assert held > 0          # dead lanes that held a state, unchanged
    assert eng.session.signature_count() == 2
    st = eng.stats()
    assert st["state_pool_bytes"] == eng.pool.state_nbytes() > 0
    assert st["state_bytes_per_lane"] * 3 == st["state_pool_bytes"]
    assert st["pool"]["nbytes"] > st["state_pool_bytes"]
    assert eng.metrics.snapshot()["state_pool_bytes"] \
        == st["state_pool_bytes"]
    alone_eng = engine_of(bundle.net, "fh1_alone")
    alone, out_alone, _ = drive(alone_eng, [[r] for r in reqs], monkeypatch)
    # the reference's full pass over prompt + served, all rows at once
    # (causal: the zeros behind a shorter row change nothing before them)
    seqs = [p + r["tokens"] for p, r in zip(prompts, out)]
    padded = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, seq in enumerate(seqs):
        padded[i, :len(seq)] = seq
    want = bundle.reference(padded)
    for i, ((prompt, n), res, res_alone) in enumerate(
            zip(reqs, out, out_alone)):
        key = tuple(prompt)
        assert res["tokens"] == res_alone["tokens"] and len(res["tokens"]) == n
        assert len(together[key]) == len(alone[key]) == n
        close(together[key], alone[key], SAME)
        close(together[key],
              want[i, len(prompt) - 1:len(seqs[i]) - 1], TOL)


def test_engine_counts_resets_and_lane_steps(bundle, monkeypatch):
    from mxnet_tpu.profiler import core as prof

    resets0 = prof.get_counter("serve.state_resets")
    steps0 = prof.get_counter("serve.state_lane_steps")
    reqs = list(zip(tokens_of(11, 5, 19), [4, 3]))
    eng = engine_of(bundle.net, "fh1_count")
    _, out, _ = drive(eng, [reqs], monkeypatch)
    assert prof.get_counter("serve.state_resets") - resets0 == 2
    # one lane-step a prefill chunk (1 + 3) and a decoded token (3 + 2)
    assert prof.get_counter("serve.state_lane_steps") - steps0 == 4 + 5


# -- what cannot be done to a recurrent state is refused, loudly --------------

def _refused(bundle, what):
    net = bundle.net
    if what == "generator_prefix":
        serve.Generator(net, max_seq=64, prefix_cache=True, page_size=8)
    elif what == "engine_prefix":
        engine_of(net, "fh1_r1", prefix_cache=True)
    elif what == "generator_multistep":
        serve.Generator(net, max_seq=64, multistep=True)
    elif what == "engine_multistep":
        engine_of(net, "fh1_r2", multistep=True)
    else:
        draft = get_llama("llama_tiny_test", vocab_size=512)
        draft.initialize()
        pair = (net, draft) if what == "speculative_target" else (draft, net)
        serve.SpeculativeGenerator(*pair, k=2, max_seq=64)


@pytest.mark.parametrize("what,missing", [
    ("generator_prefix", "snapshots"), ("engine_prefix", "snapshots"),
    ("generator_multistep", "freeze"), ("engine_multistep", "freeze"),
    ("speculative_target", "rollback"), ("speculative_draft", "rollback")])
def test_refusals_say_what_is_missing(bundle, what, missing):
    with pytest.raises(MXNetError, match="recurrent state") as err:
        _refused(bundle, what)
    assert missing in str(err.value)
