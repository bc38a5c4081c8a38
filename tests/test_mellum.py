"""Mellum-2 (routed experts in every block's feed-forward slot, window
layers beside full ones) through the normal path and
``serve.ContinuousEngine``, against the plain reference
``chipbench/reference/mellum2.py``: the benchmark's configuration at the
tiny widths of its ``rehearse`` group (8 experts, top 2, a window of 16
over pages of 8), with the benchmark's seeded weights. Logits are
compared, never tokens.

Tolerances. Everything is float32 and every matrix product runs at full
precision, so the program and the reference differ by the order of their
sums alone (gemm against einsum, sorted tiles against a loop over the
experts, pages against one score matrix). ``TOL`` is 1e-4 of the logits'
spread: the full pass reads 6e-6 here and the engine's steps 1e-5; with
bfloat16 operands the reference itself moves by 3 times that spread and
fails it, which ``test_bf16_operands_fail`` holds. The tiny model's
weights are drawn wide (``initializer_range`` 0.2), so that no token's
second and third router probabilities lie within round-off of each other
on these seeds: a flipped route would read far over ``TOL``.
``SAME`` (1e-6 of the spread) is for results that only a masked-out
position or another order of the same sums can tell apart.
"""
import importlib.util
import json
import math
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import llama
from mxnet_tpu.models.mellum import MellumModel, RoutedFFN
from mxnet_tpu.ops import nn as ops
from mxnet_tpu.ops.pallas import decode_attention as da
from mxnet_tpu.ops.pallas import expert_products as kern
from mxnet_tpu.profiler import core as prof
from mxnet_tpu.serve.generate import CacheLayout

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL, SAME = 1e-4, 1e-6
SEED = 5
PAGE = 8


def _harness():
    spec = importlib.util.spec_from_file_location(
        "chipbench_harness_for_tests",
        os.path.join(ROOT, "chipbench", "harness.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bundle:
    """The program's model and the reference over the same weights."""

    def __init__(self, config="mellum2_12b_a2_5b.json"):
        import jax.numpy as jnp

        self.h = h = _harness()
        with open(os.path.join(ROOT, "chipbench", "configs", config)) as f:
            self.published = json.load(f)
        self.cfg = cfg = h.merged(self.published, self.published["rehearse"])
        self.ref = h.load_module("reference", cfg["reference"])
        self.adapter = adapter = h.load_module("adapters", cfg["adapter"])
        maker = h.load_module(".", "weights").Maker(
            self.ref.param_shapes(cfg), SEED, cfg["initializer_range"])
        self.net = adapter.build(cfg, False)
        h.load_weights(self.net, adapter.name_map(cfg), maker)
        self.w = maker.all()
        self.jnp = jnp

    def leaves(self, i):
        return {k.split(".", 1)[1]: v for k, v in self.w.items()
                if k.startswith(f"layer{i}.")}

    def reference(self, tokens, num=None):
        """(B, T, vocab) logits of the reference's full pass."""
        ref, cfg, w = self.ref, self.cfg, self.w
        num = num or ref.EXACT
        x = ref.embed(self.jnp.asarray(np.asarray(tokens, np.int32)),
                      w["embed"])
        for i in range(cfg["num_hidden_layers"]):
            x = ref.layer(x, self.leaves(i), cfg, num)
        return np.asarray(ref.logits(x, w["norm"], w["head"], cfg, num))

    def engine(self, net=None, slots=2, **kw):
        return serve.ContinuousEngine(
            net or self.net, max_seq=128, num_slots=slots, page_size=PAGE,
            prefill_chunk=PAGE, decode_path="pallas", **kw)


@pytest.fixture(scope="module")
def bundle():
    da.use_interpret(True)   # the paged kernel, interpreted on the CPU
    yield Bundle()
    da.use_interpret(False)


def tokens_of(seed, *lengths, vocab=512):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, vocab, n).tolist() for n in lengths]


def gap_of(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float(np.abs(got - want).max() / want.std())


def close(got, want, tol):
    """Largest gap in units of the wanted logits' spread, under ``tol``."""
    gap = gap_of(got, want)
    assert gap <= tol, f"gap {gap:.3g} of the spread, tolerance {tol:g}"
    return gap


class Spy:
    """Every call of an engine's step: what went in and the logits that
    came out, so that each served position's logits can be held to the
    reference's row."""

    def __init__(self, eng):
        self.calls, real = [], eng._run_step

        def run(tokens, start_pos, last_idx, table, lanes, keep, ride=None):
            out = real(tokens, start_pos, last_idx, table, lanes, keep,
                       ride=ride)
            got = out.asnumpy()
            self.calls.append((np.asarray(tokens).shape[1],
                               np.array(start_pos), np.array(last_idx),
                               [int(s) for s in lanes if s >= 0],
                               got[:1] if ride is not None else got))
            if ride is not None:
                # a chunk's call that carried the decode visit: the
                # visit's rows after the chunk's, row = slot
                _, pos, _, riders = ride
                riders = [int(s) for s in riders if s >= 0]
                if riders:
                    self.calls.append((1, np.array(pos),
                                       np.zeros(len(pos), np.int32), riders,
                                       got[1:]))
            return out

        eng._run_step = run

    def worst(self, wants):
        """Largest gap of any served position's logits to ``wants[slot]``
        ((T, vocab) a slot), and how many positions were held to it."""
        worst, n = 0.0, 0
        for t_len, sp, li, slots, out in self.calls:
            for row, s in enumerate(slots):
                if t_len > 1:             # a prefill chunk: its one row
                    at, pos = out[0], int(sp[0]) + int(li[0])
                else:                     # a decode step: row = slot
                    at, pos = out[s], int(sp[s])
                worst = max(worst, gap_of(at, wants[s][pos]))
                n += 1
        return worst, n


def serve_all(eng, prompts, max_new, stagger=0):
    """Submit ``prompts`` (the later ones after ``stagger`` steps each)
    and step the engine by hand until all are answered."""
    futs, pending = [], list(prompts)
    futs.append(eng.submit(pending.pop(0), max_new_tokens=max_new))
    steps = 0
    while not (not pending and all(f.done() for f in futs)):
        eng.step()
        steps += 1
        if pending and steps >= stagger * len(futs):
            futs.append(eng.submit(pending.pop(0), max_new_tokens=max_new))
    return [f.result() for f in futs]


# -- (a) the model on the normal path ------------------------------------------

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


@pytest.mark.parametrize("fault", ["no_renormalisation", "top_k_less_one",
                                   "window_left_out", "plain_rope_on_full"])
def test_planted_faults_fail(bundle, fault):
    """Each fault the reference can plant (the router's renormalisation,
    its k, the window, the full layers' table) moves the logits far over
    the tolerance: the comparison sees both mechanisms. The last one is
    (e)'s: a full layer turned by the plain table fails."""
    toks = np.asarray(tokens_of(1, 70), np.int32)
    bad = bundle.reference(toks, bundle.ref.controls("float32")
                           ["fault_" + fault])
    with mx.autograd.predict_mode():
        got = bundle.net(mx.np.array(toks)).asnumpy()
    assert gap_of(got, bad) > 1000 * TOL


# -- (b), (c) chunked prefill and decode through the engine ---------------------

def test_engine_matches_reference_past_the_window(bundle):
    """One request of 45 + 25 positions: 9 pages through a ring of 3
    columns, so every column is written over at least twice, chunks and
    decode steps alike; the normal path (two signatures, the paged kernel,
    no fallback); every served position's logits against the reference's
    one full pass."""
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
    assert st["kv_pool_bytes_window"] > 0 and st["kv_pool_bytes_full"] > 0
    eng.close()


def test_the_engine_counts_the_keys_its_chunks_visit(bundle, monkeypatch):
    """A prompt of 45 in chunks of 8 at a block of two pages: the full
    layers' table of 16 pages is walked to the chunk's last page (1, 1,
    2, 2, 3, 3 turns of 16 keys where 128 are held), the window layers'
    ring of 3 columns from the page of the first visible key (1, 1, 2, 2,
    2, 2 turns where 24 are held: a turn of two pages over a ring of
    three visits more than is held). The spans' stats are these numbers
    a chunk (``tests/test_host_spans.py``)."""
    monkeypatch.setattr(da, "_BLOCK_KEYS", 2 * PAGE)
    eng = bundle.engine(slots=1)
    spy = Spy(eng)
    prompt = tokens_of(2, 45)[0]
    res = serve_all(eng, [prompt], 2)[0]
    want = bundle.reference([prompt + res["tokens"]])[0]
    assert spy.worst({0: want})[0] <= TOL
    full, ring = eng._n_full_layers, eng._n_window_layers
    assert full and ring
    keys = eng.stats()["prefill_keys"]
    assert keys["visited"] == (12 * full + 10 * ring) * 2 * PAGE
    assert keys["held"] == 6 * (16 * full + 3 * ring) * PAGE
    assert keys["visited_share"] == keys["visited"] / keys["held"]
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
    # lane 1 was dead in decode steps of lane 0 during its own prefill
    alone = [c for c in spy.calls if c[0] == 1 and c[3] == [0]]
    both = [c for c in spy.calls if c[0] == 1 and c[3] == [0, 1]]
    assert alone and both
    worst, _ = spy.worst(wants)
    assert worst <= TOL, worst
    eng.close()


def test_a_ring_column_written_over_a_page_early_is_seen(bundle,
                                                         monkeypatch):
    """The fault a ring can have, planted in the program: a ring one
    column short (two pages for a window of two) writes over a page that
    later queries still see, and the logits leave the reference's."""
    monkeypatch.setattr(CacheLayout, "window_columns",
                        lambda self, page: self.window // page)
    monkeypatch.setattr(da, "paged_decode_attention",
                        _unchecked(da.paged_decode_attention))
    eng = bundle.engine(slots=1)
    eng.warmup()
    spy = Spy(eng)
    prompt = tokens_of(2, 45)[0]
    res = serve_all(eng, [prompt], 25)[0]
    want = bundle.reference([prompt + res["tokens"]])[0]
    assert spy.worst({0: want})[0] > 100 * TOL
    eng.close()


def _unchecked(fn):
    """``paged_decode_attention`` without its check of the ring's size
    (the planted fault is a ring too short)."""
    def run(q, k_pool, v_pool, page_table, start_pos, scale=None,
            k_scale=None, v_scale=None, window=None):
        import jax.numpy as jnp

        if window is not None:
            return da._xla_blocks(q, k_pool, v_pool, page_table,
                                  start_pos.astype(jnp.int32),
                                  scale or 1.0 / math.sqrt(q.shape[-1]),
                                  window)
        return fn(q, k_pool, v_pool, page_table, start_pos, scale, k_scale,
                  v_scale)
    return run


# -- (d) the window's edge ------------------------------------------------------

def test_the_windows_edge(bundle):
    """One window layer alone (through further layers a position's
    influence travels on): the logits at position t move when the token at
    t - window + 1 changes and do not when the one at t - window does.
    Served: the prompt's last position, chunks through the ring."""
    cfg = bundle.cfg
    w = cfg["sliding_window"]
    net = MellumModel(
        vocab_size=64, units=32, num_heads=4, num_kv_heads=2, head_dim=8,
        layer_types=["sliding_attention"], sliding_window=w, expert_size=16,
        num_experts=4, num_experts_per_tok=2,
        rope=bundle.adapter.rope_of(cfg))
    mx.random.seed(7)
    net.initialize(mx.init.Normal(0.3))
    t = 40
    base = tokens_of(4, t + 1, vocab=64)[0]

    def last_logits(prompt):
        eng = bundle.engine(net=net, slots=1)
        eng.warmup()
        spy = Spy(eng)
        serve_all(eng, [prompt], 1)
        eng.close()
        return spy.calls[-1][4][0]

    def changed(at):
        out = list(base)
        out[at] = (out[at] + 7) % 63 + 1
        return out

    want = last_logits(base)
    assert gap_of(last_logits(changed(t - w)), want) <= SAME
    assert gap_of(last_logits(changed(t - w + 1)), want) > 100 * TOL


# -- (e) the rope tables ---------------------------------------------------------

def test_yarn_tables_follow_the_formulas(bundle):
    """``_rope_tables`` with the published YaRN parameters against the
    formulas of the configuration's source, computed here; the plain
    table is what it always was."""
    p = bundle.published["rope_parameters"]["full_attention"]
    dim, theta, t = 128, float(p["rope_theta"]), 300

    def pair_of(turns):
        return dim * math.log(p["original_max_position_embeddings"]
                              / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(pair_of(p["beta_fast"])), 0)
    high = min(math.ceil(pair_of(p["beta_slow"])), dim - 1)
    assert (low, high) == (18, 35)
    i = np.arange(dim // 2, dtype=np.float64)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    inv = theta ** (-2 * i / dim)
    inv = (1 - ramp) * inv + ramp * inv / p["factor"]
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None]
    assert p["attention_factor"] == pytest.approx(0.1 * math.log(16) + 1)
    scaling = bundle.adapter.rope_of(bundle.published)["full_attention"][1]
    cos, sin = llama._rope_tables(t, dim, theta, scaling)
    np.testing.assert_allclose(cos, p["attention_factor"] * np.cos(ang),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(sin, p["attention_factor"] * np.sin(ang),
                               rtol=0, atol=1e-6)
    rcos, rsin = bundle.ref.rope_tables(t, dim, p)
    np.testing.assert_array_equal(cos, rcos)
    np.testing.assert_array_equal(sin, rsin)
    plain = llama._rope_tables(t, dim, theta)
    ang0 = np.arange(t)[:, None] * theta ** (-2 * i / dim)[None]
    np.testing.assert_allclose(plain[0], np.cos(ang0), rtol=0, atol=1e-6)
    assert np.abs(plain[0] - cos).max() > 0.1


# -- (f) routing ----------------------------------------------------------------

def test_routing_weights_sum_to_one_and_ties_go_to_the_lower_index():
    import jax.numpy as jnp

    logits = jnp.asarray(np.random.RandomState(0).randn(50, 16), jnp.float32)
    w, idx = ops.route_top_k(logits, 4)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    assert all(len(set(r)) == 4 for r in np.asarray(idx))
    tie = jnp.asarray([[1.0, 3.0, 3.0, 3.0, 0.0, 3.0]], jnp.float32)
    _, idx = ops.route_top_k(tie, 2)
    assert np.asarray(idx).tolist() == [[1, 2]]
    _, idx = ops.route_top_k(jnp.zeros((1, 6), jnp.float32), 3)
    assert np.asarray(idx).tolist() == [[0, 1, 2]]


def _experts(seed, e=8, h=16, f=12):
    rs = np.random.RandomState(seed)
    a = lambda *s: mx.np.array((0.4 * rs.randn(*s)).astype("float32"))   # noqa: E731
    return dict(router=a(e, h), gate=a(e, h, f), up=a(e, h, f),
                down=a(e, f, h))


# the forms of the expert products: the grouped form's XLA loop over
# tiles (what a platform without the kernel runs), the grouped form's
# kernel (through the Pallas interpreter) and the one product over the
# stack
FORMS = ["grouped", "kernel", "dense"]


def _routed(x, p, k, impl, held=None, **kw):
    first, count = held or (0, p["gate"].shape[0])
    sl = slice(first, first + count)
    kern.use_interpret(impl == "kernel")
    try:
        out, load = ops.routed_experts(
            x, p["router"], p["gate"][sl], p["up"][sl], p["down"][sl], k,
            held=held, impl="dense" if impl == "dense" else "grouped",
            tile=4, **kw)
    finally:
        kern.use_interpret(False)
    return out.asnumpy(), load.asnumpy()


def _reads(impl, load, counts=None, tile=4):
    """The load's seventh number for ``impl``: one read an expert hit
    under the kernel, one a tile of ``tile`` rows under the loop (the
    held experts' ``counts`` of rows), every held expert for ``dense``."""
    if impl == "kernel":
        return int(load[0])
    if impl == "dense":
        return int(load[4])
    return sum(-(-int(c) // tile) for c in counts)


def _by_hand(x, p, k, held=None):
    """Every token's k experts, one token and one expert at a time; with
    ``held`` (first, count) the part of the sum those experts give."""
    x = x.asnumpy().reshape(-1, x.shape[-1]).astype(np.float64)
    r, g, u, d = (p[n].asnumpy().astype(np.float64)
                  for n in ("router", "gate", "up", "down"))
    first, count = held or (0, len(g))
    out = np.zeros_like(x)
    for n, row in enumerate(x):
        z = r @ row
        prob = np.exp(z - z.max())
        prob /= prob.sum()
        pick = sorted(range(len(prob)), key=lambda e: (-prob[e], e))[:k]
        for e in pick:
            if not first <= e < first + count:
                continue
            a = row @ g[e]
            out[n] += prob[e] / prob[pick].sum() \
                * ((a / (1 + np.exp(-a)) * (row @ u[e])) @ d[e])
    return out


@pytest.mark.parametrize("impl", FORMS)
def test_every_token_to_the_same_experts_loses_none(impl):
    """No capacity: 40 tokens that all pick experts 2 and 5 get both
    (40 rows on each of two experts, none on the other six), and the
    result is the one computed a token at a time."""
    p = _experts(1)
    router = np.zeros((8, 16), np.float32)
    router[2, 0], router[5, 0] = 4.0, 3.0
    p["router"] = mx.np.array(router)
    x = np.random.RandomState(2).randn(2, 20, 16).astype("float32")
    x[..., 0] = 1.0 + np.abs(x[..., 0])         # every token: 2, then 5
    x = mx.np.array(x)
    out, load = _routed(x, p, 2, impl)
    # the tiles read the two experts hit, the one product all eight; the
    # loop reads each of the two once a tile of 4 of its 40 rows
    assert load.tolist() == [2, 40, 80, 80, 8, 8 if impl == "dense" else 2,
                             {"grouped": 20, "kernel": 2, "dense": 8}[impl]]
    close(out.reshape(-1, 16), _by_hand(x, p, 2), 1e-5)


@pytest.mark.parametrize("impl", FORMS)
def test_tokens_that_are_not_live_go_to_no_expert(impl):
    p = _experts(3)
    x = mx.np.array(np.random.RandomState(4).randn(2, 6, 16)
                    .astype("float32"))
    live = np.zeros((2, 6), bool)
    live[0, :4] = True
    out, load = _routed(x, p, 2, impl, token_live=mx.np.array(live))
    assert load[2] == load[3] == 8 and not out[1].any() and not out[0, 4:].any()
    close(out[0, :4], _by_hand(x, p, 2)[:4], 1e-5)


# -- (g) the held experts' share -------------------------------------------------

@pytest.mark.parametrize("impl", FORMS)
def test_the_four_quarters_add_up_to_the_whole_layer(impl):
    """``experts_held`` set to each quarter of the experts in turn: the
    router keeps its width, each share computes its own experts' part,
    and the four parts add up to the whole layer's result (there is no
    shared expert to count once)."""
    p = _experts(5)
    x = mx.np.array(np.random.RandomState(6).randn(3, 11, 16)
                    .astype("float32"))
    whole, load = _routed(x, p, 3, impl)
    parts = [_routed(x, p, 3, impl, held=(q, 2)) for q in (0, 2, 4, 6)]
    close(sum(o for o, _ in parts), whole, 1e-5)
    assert sum(int(l[2]) for _, l in parts) == int(load[2]) == 33 * 3
    close(whole.reshape(-1, 16), _by_hand(x, p, 3), 1e-5)


def test_a_block_that_holds_a_quarter():
    moe = RoutedFFN(16, 12, 8, 3, experts_held=(2, 2))
    assert moe.gate_weight.shape == (2, 16, 12)
    assert moe.router.weight.shape == (8, 16)
    with pytest.raises(MXNetError, match="holding"):
        RoutedFFN(16, 12, 8, 3, experts_held=(7, 2))


# -- (g') a decode step's rows: one position a lane ------------------------------

def _steered(seed, first_choice, e=16):
    """Experts whose router sends every token with a positive first
    feature to ``first_choice`` before any other."""
    p = _experts(seed, e=e)
    router = 0.05 * np.random.RandomState(seed).randn(e, 16).astype("float32")
    router[:, 0] = 0.0
    router[first_choice, 0] = 6.0
    p["router"] = mx.np.array(router)
    return p


def _lanes(seed, n=8):
    x = np.random.RandomState(seed).randn(n, 1, 16).astype("float32")
    x[..., 0] = 1.0 + np.abs(x[..., 0])
    return mx.np.array(x)


@pytest.mark.parametrize("impl", FORMS)
@pytest.mark.parametrize("case", ["nobody_picked_a_held_expert",
                                  "dead_lanes", "one_expert_takes_every_row"])
def test_a_decode_steps_rows(impl, case):
    """(8, 1) calls, the shape the tiles had never run at: every form
    against the sum computed a token and an expert at a time, and the
    load's sixth number: the held experts whose weights the call read
    (those hit for the tiles and the kernel, all of them for the one
    product)."""
    held, k = (4, 4), 1
    read = (lambda hit: held[1]) if impl == "dense" else (lambda hit: hit)
    x = _lanes(11)
    if case == "nobody_picked_a_held_expert":
        # every row's one expert is held elsewhere: no tile, zeros
        out, load = _routed(x, _steered(12, 9), k, impl, held=held)
        assert not out.any()
        assert load.tolist() == [0, 0, 0, 8, 4, read(0),
                                 4 if impl == "dense" else 0]
    elif case == "dead_lanes":
        p = _experts(13, e=16)
        live = np.array([1, 0, 1, 0, 0, 1, 0, 0], bool).reshape(8, 1)
        out, load = _routed(x, p, 4, impl, held=held,
                            token_live=mx.np.array(live))
        want = _by_hand(x, p, 4, held=held)
        assert not out[~live[:, 0]].any()
        close(out[live[:, 0], 0], want[live[:, 0]], 1e-5)
        on_held = int(load[2])
        assert 0 < on_held <= 12 and load[3] == 12 and load[4] == 4
        assert 0 < load[0] <= min(4, on_held) and load[5] == read(load[0])
        if impl != "grouped":
            assert load[6] == read(load[0])
    else:
        p = _steered(14, 6)
        out, load = _routed(x, p, k, impl, held=held)
        assert load.tolist() == [1, 8, 8, 8, 4, read(1),
                                 _reads(impl, load, [8])]
        close(out[:, 0], _by_hand(x, p, k, held=held), 1e-5)


@pytest.mark.parametrize("impl", FORMS)
def test_a_decode_steps_forms_agree(impl):
    """Eight rows over 4 held of 16 experts at top 4 (about eight
    assignments fall on a held expert): the form against the plain loop,
    and the forms' loads alike but for the sixth and seventh numbers."""
    p, x, held = _experts(15, e=16), _lanes(16), (8, 4)
    out, load = _routed(x, p, 4, impl, held=held)
    close(out[:, 0], _by_hand(x, p, 4, held=held), 1e-5)
    for form in FORMS:
        other, theirs = _routed(x, p, 4, form, held=held)
        close(out, other, 1e-5)
        assert load[:5].tolist() == theirs[:5].tolist()
    assert load[5] == (4 if impl == "dense" else load[0])
    if impl == "dense":
        assert load[6] == 4
    else:   # at most 8 rows an expert: one tile of 4 or two, for the loop
        assert load[0] <= load[6] <= (2 if impl == "grouped" else 1) * load[0]


# -- (g''') the kernel of the grouped form -----------------------------------------

@pytest.mark.parametrize("case", [
    "one_expert_takes_every_row", "experts_with_no_rows",
    "counts_not_a_multiple_of_eight", "held_elsewhere_and_dead_lanes",
    "swiglu_limit", "one_row", "a_chunk_and_its_riders",
    "two_blocks_of_the_expert_width", "widest_tiles_of_sixteen_rows"])
def test_the_kernel_against_the_one_product(monkeypatch, case):
    """``grouped_expert_products`` through the kernel (the Pallas
    interpreter) against ``dense_expert_products`` picked by the route:
    every assignment's product, zeros where it is not computed, the rows
    of each expert, one weight read a hit expert, and the sum weighted by
    the route."""
    import jax.numpy as jnp

    e, h, f, n, k, limit = 8, 16, 12, 21, 2, None
    rs = np.random.RandomState(sum(map(ord, case)))
    if case == "experts_with_no_rows":
        e, n = 16, 3
    elif case == "counts_not_a_multiple_of_eight":
        e, n, k = 4, 21, 3
    elif case == "swiglu_limit":
        limit = 0.3
    elif case == "one_row":
        n = 1
    elif case == "a_chunk_and_its_riders":
        n = 128 + 16
    elif case == "two_blocks_of_the_expert_width":
        h, f = 128, 256
        monkeypatch.setattr(kern, "_WEIGHT_VMEM", 2 * 3 * h * 128 * 4)
        assert kern.block_width(h, f, 4) == 128
    elif case == "widest_tiles_of_sixteen_rows":
        monkeypatch.setattr(kern, "_ROW_TILE", 16)
    w = lambda *s: jnp.asarray((0.4 * rs.randn(*s)).astype("float32"))  # noqa: E731
    gate, up, down, x = w(e, h, f), w(e, h, f), w(e, f, h), w(n, h)
    logits = w(n, e)
    if case == "one_expert_takes_every_row":
        logits = logits.at[:, 3].add(100.0)
    elif case == "a_chunk_and_its_riders":   # the chunk's rows lean to one
        logits = logits.at[:128, 3].add(100.0)
    weights, local = ops.route_top_k(logits, k)
    if case == "held_elsewhere_and_dead_lanes":
        held = (local >= 2) & (local < 6)               # 4 of 8 here
        held &= jnp.asarray(rs.rand(n, 1) < 0.7)        # lanes not live
        local = jnp.where(held, local - 2, 4)
        gate, up, down = gate[2:6], up[2:6], down[2:6]
        e = 4
    kern.use_interpret(True)
    try:
        prod, counts, reads = ops.grouped_expert_products(
            x, local, gate, up, down, limit=limit)
    finally:
        kern.use_interpret(False)
    loc = np.asarray(local)
    every = np.asarray(ops.dense_expert_products(x, gate, up, down, limit))
    want = np.zeros((n, k, h), np.float32)
    for i, j in zip(*np.nonzero(loc < e)):
        want[i, j] = every[loc[i, j], i]
    close(np.asarray(prod), want, 1e-5)
    assert not np.asarray(prod)[loc == e].any()
    assert np.asarray(counts).tolist() \
        == np.bincount(loc[loc < e], minlength=e).tolist()
    assert int(reads) == int((np.asarray(counts) > 0).sum())
    if case == "one_expert_takes_every_row":
        assert int(reads) == k and np.asarray(counts)[3] == n
    elif case == "a_chunk_and_its_riders":   # whole widest tiles, and more
        assert np.asarray(counts)[3] > 4 * kern.tile_plan(n, 8)[0]
    w_here = np.where(loc < e, np.asarray(weights), 0.0)
    close(np.einsum("nkh,nk->nh", np.asarray(prod), w_here),
          np.einsum("nkh,nk->nh", want, w_here), 1e-5)


@pytest.mark.parametrize("experts,hidden,width,rows,fits", [
    (64, 2304, 896, 144, True), (8, 4096, 4096, 136, True),
    (8, 7168, 2048, 160, True), (8, 7168, 2048, 912, True),
    (8, 7168, 2048, 913, False), (8, 7168, 2048, 10880, False)],
    ids=["mellum2_mixed", "command_a_mixed", "gigachat_mixed",
         "ax_k1_most_rows", "ax_k1_a_row_more", "ax_k1_whole_context"])
def test_the_kernel_takes_the_rows_that_fit_vmem(experts, hidden, width, rows,
                                                 fits):
    """``supports`` holds a call's weight blocks, rows' scratch and stack
    room to a v5e's 128 MiB of VMEM: the cells' mixed calls fit with room
    to spare, and at 7168 x 2048 float32 (84 KiB of scratch a row) 912
    rows fit and one more does not."""
    import jax
    import jax.numpy as jnp

    x = jax.ShapeDtypeStruct((rows, hidden), jnp.float32)
    gate = jax.ShapeDtypeStruct((experts, hidden, width), jnp.float32)
    kern.use_interpret(True)
    try:
        assert kern.supports(x, gate) == fits
    finally:
        kern.use_interpret(False)
    assert (kern.vmem_bytes(rows, hidden, width, x.dtype, gate.dtype)
            <= kern._VMEM) == fits


def test_a_call_whose_rows_do_not_fit_takes_the_loop(monkeypatch):
    """A call whose rows would pass ``_VMEM`` gets the same products from
    the XLA loop, which reads an expert once a tile of rows."""
    import jax.numpy as jnp

    e, h, f, n, k = 8, 16, 12, 40, 2
    rs = np.random.RandomState(41)
    w = lambda *s: jnp.asarray((0.4 * rs.randn(*s)).astype("float32"))  # noqa: E731
    gate, up, down, x = w(e, h, f), w(e, h, f), w(e, f, h), w(n, h)
    _, local = ops.route_top_k(w(n, e), k)
    kern.use_interpret(True)
    try:
        fit, counts, hit = ops.grouped_expert_products(x, local, gate, up,
                                                       down, tile=4)
        monkeypatch.setattr(kern, "_VMEM", kern.vmem_bytes(
            n - kern.sublane_rows(x.dtype), h, f, x.dtype, gate.dtype))
        assert not kern.supports(x, gate)
        loop, counts2, tiles = ops.grouped_expert_products(x, local, gate, up,
                                                           down, tile=4)
    finally:
        kern.use_interpret(False)
    close(np.asarray(loop), np.asarray(fit), 1e-5)
    assert np.asarray(counts2).tolist() == np.asarray(counts).tolist()
    assert int(hit) == int((np.asarray(counts) > 0).sum())
    assert int(tiles) == sum(-(-int(c) // 4) for c in np.asarray(counts))
    assert int(tiles) > int(hit)


# -- (g'') which form a cached call takes ------------------------------------------

@pytest.mark.parametrize("rows,positions,k,e_all,kernel,form", [
    (8, 1, 8, 128, True, "grouped"),     # Command A+'s decode step: 0.40
    (16, 1, 8, 64, True, "grouped"),     # Mellum-2's: 0.88, under 0.95
    (32, 1, 8, 64, True, "dense"),       # twice its lanes: 0.986
    (128, 128, 8, 128, True, "grouped"),  # both cells' prefill chunks
    (128, 128, 8, 64, True, "grouped"),
    (16, 2, 8, 8, True, "grouped"),      # more than one position
    (1, 1, 8, 8, True, "dense"),         # one row that takes every expert
    (1, 1, 8, 64, True, "grouped"),      # one row that takes an eighth
    (8, 1, 8, 128, False, "grouped"),    # the loop: 0.40, under 0.7
    (16, 1, 8, 64, False, "dense"),      # the loop: 0.88, over 0.7
    (128, 128, 8, 64, False, "grouped"),  # a chunk, whatever the form
], ids=["command_decode", "mellum2_decode", "mellum2_32_lanes",
        "command_chunk", "mellum2_chunk", "two_positions",
        "one_row_every_expert", "one_row_an_eighth", "command_decode_loop",
        "mellum2_decode_loop", "mellum2_chunk_loop"])
def test_the_form_a_cached_call_takes(rows, positions, k, e_all, kernel,
                                      form):
    assert ops.expert_form(rows, positions, k, e_all, kernel) == form


@pytest.mark.parametrize("k,e_all", [(8, 128), (8, 64), (2, 8), (4, 16),
                                     (1, 256)])
def test_more_rows_never_turn_a_decode_step_back_to_the_tiles(k, e_all):
    turns = []
    for kernel in (False, True):
        forms = [ops.expert_form(n, 1, k, e_all, kernel)
                 for n in range(1, 1025)]
        turn = forms.index("dense")
        assert turn > 0 and set(forms[:turn]) == {"grouped"}
        assert set(forms[turn:]) == {"dense"}
        turns.append(turn)
    assert turns[0] <= turns[1]   # the kernel keeps more steps grouped


@pytest.mark.parametrize("shape,num,cached,kernel,form", [
    ((8, 1), 128, True, False, "grouped"), ((32, 1), 64, True, False, "dense"),
    ((1, 5), 64, True, False, "grouped"), ((16, 1), 128, False, False, "dense"),
    ((1, 5), 128, False, False, "dense"), ((16, 1), 64, True, True, "grouped"),
    ((16, 1), 64, True, False, "dense")],
    ids=["few_rows_of_many_experts", "many_rows_of_few_experts", "a_chunk",
         "no_cache_one_position", "no_cache_a_sequence",
         "sixteen_rows_of_64_in_the_kernel", "sixteen_rows_of_64_in_the_loop"])
def test_the_block_asks_the_rule(monkeypatch, shape, num, cached, kernel,
                                 form):
    """``RoutedFFN.forward`` hands ``routed_experts`` the form that
    ``expert_form`` names for the call's shapes and for the grouped form's
    kernel or loop (16 rows of 8 of 64 hit 0.88: under the kernel's 0.95,
    over the loop's 0.7), and ``dense`` (the differentiable one) on the
    normal path whatever the shapes."""
    seen, real = [], ops.routed_experts

    def spy(*args, **kw):
        seen.append(kw["impl"])
        return real(*args, **kw)

    class View:
        """What a layer's cache view is asked by the feed-forward."""
        def token_live(self, t_len):
            return None

        def note_route(self, load):
            assert load.shape == (7,)

    monkeypatch.setattr(ops, "routed_experts", spy)
    moe = RoutedFFN(16, 12, num, 8, experts_held=(0, 8))
    moe.initialize(mx.init.Normal(0.1))
    x = mx.np.array(np.random.RandomState(3).randn(*shape, 16)
                    .astype("float32"))
    kern.use_interpret(kernel)
    try:
        with mx.autograd.predict_mode():
            moe(x, cache=View()) if cached else moe(x)
    finally:
        kern.use_interpret(False)
    assert seen == [form]


# -- (h) a model with no window and no routed layer is served as it was ----------

@pytest.mark.parametrize("model", ["llama", "falcon_h1"])
def test_dense_unbounded_models_keep_their_step(model):
    """No ring table in the call, no load among the results, no stat, span
    stat or counter of this PR: the step's signature and results are what
    they were (on the chip the two dense cells are held to their step
    times, PERF.md)."""
    from mxnet_tpu.models.falcon_h1 import FalconH1Model

    if model == "llama":
        net = llama.get_llama("llama_tiny_test")
    else:
        net = FalconH1Model(
            vocab_size=64, units=32, hidden_size=64, num_layers=2,
            num_heads=4, num_kv_heads=2, head_dim=8, mamba_d_ssm=32,
            mamba_d_state=8, mamba_n_heads=4, mamba_d_head=8,
            mamba_chunk_size=8)
    net.initialize(mx.init.Normal(0.1))
    for name in ("serve.moe_assignments", "serve.moe_experts_hit",
                 "serve.window_pages_recycled"):
        prof.set_counter(name, 0)
    layout = CacheLayout(net)
    assert layout.window is None and set(layout.windows) == {None}
    eng = serve.ContinuousEngine(net, max_seq=64, num_slots=2, page_size=8,
                                 prefill_chunk=8, decode_path="pallas")
    eng.warmup()
    seen = []
    real = eng.session.run

    def run(*args):
        out = real(*args)
        seen.append((len(args), len(out)))
        return out

    eng.session.run = run
    serve_all(eng, tokens_of(8, 11, 19, vocab=64), 6)
    # tokens, start_pos, last_idx, the page table, lanes for a state, and
    # the in-place step's keep and ids (the ids come back after the logits)
    n_args = 6 + int(layout.has_state) + len(layout)
    # ... and a chunk's call that carries the decode lanes (PR 40: their
    # tokens, positions and page table rows, after the stores) where the
    # layers keep K/V alone
    want = {(n_args, 2 + len(layout))}
    if not layout.has_state:
        want.add((n_args + 3, 2 + len(layout)))
    assert set(seen) == want
    st = eng.stats()
    assert not {"moe", "kv_pool_bytes_window", "kv_pool_bytes_full",
                "window_pages_recycled"} & set(st)
    assert st["pool"]["window_columns"] == 0
    assert st["pool"]["window_nbytes"] == 0
    assert eng._step_block.donate_args == tuple(
        range(n_args - len(layout), n_args))
    for name in ("serve.moe_assignments", "serve.moe_experts_hit",
                 "serve.window_pages_recycled"):
        assert prof.get_counter(name) == 0
    eng.close()


# -- (i) what refuses a bounded layer --------------------------------------------

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
    with pytest.raises(MXNetError, match="must divide the KV page"):
        serve.ContinuousEngine(net, max_seq=128, page_size=PAGE,
                               prefill_chunk=3, decode_path="pallas")


# -- the spans, counters and stats of the routed layers ---------------------------

def test_route_loads_are_read_back_and_counted(bundle):
    for name in ("serve.moe_assignments", "serve.moe_experts_hit",
                 "serve.moe_experts_read"):
        prof.set_counter(name, 0)
    eng = bundle.engine(slots=2)
    eng.warmup()
    prompts = tokens_of(9, 21, 30)
    res = serve_all(eng, prompts, 9)
    moe = eng.stats()["moe"]
    k, layers = bundle.cfg["num_experts_per_tok"], 4
    # every real position of every call, in every layer: prompt positions
    # and the decode steps' tokens (the last sampled token is never fed)
    fed = sum(len(p) + len(r["tokens"]) - 1 for p, r in zip(prompts, res))
    assert moe["assignments"] == fed * k * layers
    assert 0 < moe["experts_hit"] <= moe["calls"] * layers * 8
    assert 1 <= moe["max_load"] <= PAGE
    assert moe["load_max_over_mean"] >= 1.0
    assert prof.get_counter("serve.moe_assignments") == moe["assignments"]
    assert prof.get_counter("serve.moe_experts_hit") == moe["experts_hit"]
    # two lanes of 8 experts at top 2 can hit 0.44 of them: tiles in both
    # executables, so what a call read is what its tokens picked
    assert prof.get_counter("serve.moe_experts_read") \
        == moe["experts_read"] == moe["experts_hit"]
    assert moe["picked_share"] == 1.0
    # and no expert has more than one tile's rows in a call: the loop too
    # reads each hit expert once
    assert moe["expert_reads"] == moe["experts_hit"]
    assert prof.get_counter("serve.moe_load_max_over_mean") \
        == pytest.approx(moe["load_max_over_mean"])
    assert not eng._route_pending
    eng.close()


def test_the_kernel_reads_each_hit_expert_once_a_call(bundle, monkeypatch):
    """Through ``ContinuousEngine`` with the grouped form's kernel (the
    Pallas interpreter): every ``serve.route`` span's ``expert_reads`` is
    its ``experts_hit`` (one read a hit expert a call, chunk or decode
    step), the stats add the spans up, and the served tokens are those of
    the XLA loop over tiles."""
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
    prompts = tokens_of(9, 21, 30)

    def served():
        eng = bundle.engine(slots=2)
        eng.warmup()
        res = serve_all(eng, prompts, 9)
        moe = eng.stats()["moe"]
        eng.close()
        return [r["tokens"] for r in res], moe

    tiles, _ = served()
    spans.clear()
    kern.use_interpret(True)
    try:
        tokens, moe = served()
    finally:
        kern.use_interpret(False)
    assert tokens == tiles
    assert spans and all(s["expert_reads"] == s["experts_hit"] > 0
                         for s in spans)
    assert sum(s["expert_reads"] for s in spans) == moe["expert_reads"] \
        == moe["experts_hit"]
