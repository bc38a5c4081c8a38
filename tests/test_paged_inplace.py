"""The serving step that leaves its K/V in the pool (PR 32): the paged
decode kernel against the XLA attention over the gathered ring, the
in-place page write against the scatter it replaces, the engine on the
in-place step against the gathered step, ``CachedOp``'s donation of call
arguments, and the engine's rule for a call that took the pool with it.

CPU: the kernel runs in the Pallas interpreter. This backend honours
donation (a donated buffer reads ``is_deleted()``), and nothing here
depends on it except the test that says so.
"""
import importlib.util
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import np as mnp
from mxnet_tpu import serve
from mxnet_tpu.cachedop import CachedOp
from mxnet_tpu.gluon.block import HybridBlock
from mxnet_tpu.models.llama import get_llama
from mxnet_tpu.ops import nn as ops
from mxnet_tpu.ops.pallas import decode_attention as da
from mxnet_tpu.profiler import core as prof
from mxnet_tpu.resilience import faults
from mxnet_tpu.serve import scheduler as sched
from mxnet_tpu.serve.generate import _CacheForward

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def interpreted():
    da.use_interpret(True)
    da.force_path(None)
    da.reset_fallbacks()
    yield
    da.use_interpret(False)
    da.force_path(None)
    faults.clear_plan()


# -- the kernel ----------------------------------------------------------------

def _pools(rs, lanes, heads, kv, d, page, n_pages, int8=False, dead=()):
    """Random pools with a shuffled page table; ``dead`` lanes' table rows
    are all null. Returns q, k, v, table, scales (or Nones)."""
    pages = lanes * n_pages + 1
    table = 1 + rs.permutation(lanes * n_pages).reshape(lanes, n_pages)
    table[list(dead)] = 0
    shape = (pages, kv, page, d)
    if int8:
        k, v = (rs.randint(-127, 128, shape).astype(np.int8) for _ in "kv")
        ks, vs = (rs.uniform(0.01, 0.1, shape[:3]).astype(np.float32)
                  for _ in "kv")
    else:
        k, v = (rs.randn(*shape).astype(np.float32) for _ in "kv")
        ks = vs = None
    for a in (k, v, ks, vs):
        if a is not None:
            a[0] = 0        # the null page reads zero
    q = rs.randn(lanes, heads, 1, d).astype(np.float32)
    return q, k, v, table.astype(np.int32), ks, vs


def _both(q, k, v, table, sp, ks, vs):
    """(paged result, XLA attention over the gathered ring)."""
    j = [None if a is None else jnp.asarray(a)
         for a in (q, k, v, table, np.asarray(sp, np.int32), ks, vs)]
    q, k, v, table, sp, ks, vs = j
    got = da.paged_decode_attention(q, k, v, table, sp, k_scale=ks,
                                    v_scale=vs)
    path = da.last_path()
    ring = [None if a is None else ops.gather_pages(a, table)
            for a in (k, v, ks, vs)]
    want = da._xla_decode(q, ring[0], ring[1], sp, q.shape[-1] ** -0.5,
                          ring[2], ring[3])
    return np.asarray(got), np.asarray(want), path


@pytest.mark.parametrize("page,n_pages,sp", [
    (128, 4, [0, 127, 128, 511]),          # the edges of a block, and S - 1
    (128, 4, [37, 300, 5, 255]),           # ragged
    (256, 2, [0, 255, 256, 511]),          # a page of two blocks
    (256, 2, [130, 17, 400, 128]),
    (16, 4, [0, 15, 16, 63]),              # a page under the block
], ids=["edges128", "ragged128", "edges256", "ragged256", "page16"])
@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_paged_kernel_matches_xla_over_the_gathered_ring(page, n_pages, sp,
                                                         int8):
    rs = np.random.RandomState(page + n_pages + int8)
    q, k, v, table, ks, vs = _pools(rs, 4, 6, 2, 32, page, n_pages, int8)
    got, want, path = _both(q, k, v, table, sp, ks, vs)
    assert path == "pallas_paged" and da.fallback_count() == 0
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_paged_kernel_dead_lanes_read_the_null_page():
    """All-null table rows at position 0: finite output (zeros: the one
    position they attend is the null page's), live lanes untouched."""
    rs = np.random.RandomState(3)
    q, k, v, table, _, _ = _pools(rs, 4, 4, 2, 32, 128, 2, dead=(1, 3))
    got, want, _ = _both(q, k, v, table, [200, 0, 90, 0], None, None)
    assert np.all(got[[1, 3]] == 0.0)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_paged_kernel_is_the_ring_kernel_block_for_block():
    """Page = block: a lane's result comes from the same blocks in the
    same order as the ring kernel's, so the two agree to the bit."""
    rs = np.random.RandomState(4)
    q, k, v, table, _, _ = _pools(rs, 3, 8, 2, 128, 128, 3)
    sp = jnp.asarray([5, 380, 129], jnp.int32)
    paged = da.paged_decode_attention(*map(jnp.asarray, (q, k, v, table)),
                                      sp)
    ring = da._pallas_decode(jnp.asarray(q), ops.gather_pages(k, table),
                             ops.gather_pages(v, table), sp, 128 ** -0.5,
                             None, None)
    assert np.asarray(paged).tobytes() == np.asarray(ring).tobytes()


def test_what_the_paged_kernel_cannot_tile_falls_back_and_counts():
    """A page of 192 is neither blocks of 128 nor under one: float32
    pages are walked by blocks in XLA, int8 pages are gathered for the
    ring kernel, and either fallback is counted. A block of several
    positions (T > 1) is no fallback."""
    rs = np.random.RandomState(5)
    before = prof.get_counter("serve.decode_fallbacks")
    q, k, v, table, _, _ = _pools(rs, 2, 4, 2, 32, 192, 2)
    got, want, path = _both(q, k, v, table, [190, 300], None, None)
    assert path == "xla_blocks" and da.fallback_count() == 1
    assert prof.get_counter("serve.decode_fallbacks") == before + 1
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    q5 = jnp.asarray(rs.randn(2, 4, 5, 32).astype(np.float32))
    da.paged_decode_attention(q5, jnp.asarray(k), jnp.asarray(v),
                              jnp.asarray(table), jnp.asarray([3, 9]))
    assert da.last_path() == "xla_blocks" and da.fallback_count() == 1
    q, k, v, table, ks, vs = _pools(rs, 2, 4, 2, 32, 192, 2, int8=True)
    got, want, path = _both(q, k, v, table, [190, 300], ks, vs)
    assert path == "pallas" and da.fallback_count() == 2
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# -- the write -----------------------------------------------------------------

def _written(pool, table, new, sp):
    return np.asarray(ops.write_pages(*map(jnp.asarray, (
        pool, table, new, np.asarray(sp, np.int32)))))


@pytest.mark.parametrize("t_len,sp", [(1, [0, 15, 16, 40]),
                                      (5, [0, 14, 30, 7]),
                                      (16, [16, 0, 32, 48])])
def test_write_pages_is_the_ring_write_and_scatter_it_replaces(t_len, sp):
    """Bit for bit what gather -> kv_cache_write -> paged_kv_scatter
    leaves in the pool, 4-D pools and 3-D scale pools alike."""
    rs = np.random.RandomState(t_len)
    for shape in [(17, 2, 16, 8), (17, 2, 16)]:
        pool = rs.randn(*shape).astype(np.float32)
        pool[0] = 0
        table = (1 + rs.permutation(16).reshape(4, 4)).astype(np.int32)
        new = rs.randn(4, 2, t_len, *shape[3:]).astype(np.float32)
        spv = mnp.array(np.asarray(sp, np.int32))
        ring = ops.paged_kv_gather(mnp.array(pool), mnp.array(table))
        if len(shape) == 4:
            ring = ops.kv_cache_write(ring, mnp.array(new), spv)
        else:
            r = ring.asnumpy().copy()
            for b, s in enumerate(sp):
                r[b, :, s:s + t_len] = new[b]
            ring = mnp.array(r)
        want = ops.paged_kv_scatter(mnp.array(pool), mnp.array(table), ring,
                                    spv, t_len).asnumpy()
        assert _written(pool, table, new, sp).tobytes() == want.tobytes()


def test_write_pages_dead_lanes_and_overruns_leave_the_null_page_zero():
    rs = np.random.RandomState(7)
    pool = rs.randn(9, 2, 16, 8).astype(np.float32)
    pool[0] = 0
    table = np.array([[1, 2, 3, 4], [0, 0, 0, 0], [5, 6, 0, 0]], np.int32)
    new = rs.randn(3, 2, 8, 8).astype(np.float32)
    # lane 0 runs off the ring's end, lane 1 is dead, lane 2 runs off the
    # pages it owns: all of that lands on page 0 and is wiped
    out = _written(pool, table, new, [60, 0, 28])
    assert not out[0].any()
    np.testing.assert_array_equal(out[4, :, 12:], new[0][:, :4])
    np.testing.assert_array_equal(out[6, :, 12:], new[2][:, :4])
    untouched = [1, 2, 3, 5, 7, 8]
    np.testing.assert_array_equal(out[untouched], pool[untouched])


def test_write_pages_leaves_a_shared_prefix_page_bit_for_bit():
    """Two slots share page 3 as their first logical page and write past
    it (the prefix cache's invariant: writes go to positions at or past
    start_pos, which lie in pages the slot owns alone)."""
    rs = np.random.RandomState(8)
    pool = rs.randn(9, 2, 16, 8).astype(np.float32)
    pool[0] = 0
    table = np.array([[3, 1, 2, 0], [3, 4, 5, 0]], np.int32)
    new = rs.randn(2, 2, 4, 8).astype(np.float32)
    out = _written(pool, table, new, [16, 30])
    assert out[3].tobytes() == pool[3].tobytes()
    np.testing.assert_array_equal(out[1, :, :4], new[0])
    np.testing.assert_array_equal(out[4, :, 14:], new[1][:, :2])
    np.testing.assert_array_equal(out[5, :, :2], new[1][:, 2:])


# -- CachedOp donates what the block says it consumes ---------------------------

class _Consumes(HybridBlock):
    """Adds ``x`` into the first row of ``buf`` and hands ``buf`` back."""

    donate_args = (2,)

    def forward(self, x, scale, buf):
        return x.sum(), mx.nd.NDArray(
            buf._data.at[0].add(x._data * scale))


def test_cachedop_donates_the_positions_the_block_names():
    """Position 2 of the call (the static ``scale`` before it is not a
    traced argument) is donated and comes back updated; a block that
    names nothing donates nothing."""
    op = CachedOp(_Consumes())
    x, buf = mnp.ones((4,)), mnp.zeros((3, 4))
    held = buf._data
    total, out = op(x, 2.0, buf)
    np.testing.assert_array_equal(out.asnumpy()[0], np.full(4, 2.0))
    assert float(total.asnumpy()) == 4.0
    if held.is_deleted():        # this backend honours donation
        assert not out._data.is_deleted() and not x._data.is_deleted()
    total, out2 = op(x, 2.0, out)    # the returned array goes back in
    np.testing.assert_array_equal(out2.asnumpy()[0], np.full(4, 4.0))

    class Keeps(_Consumes):
        donate_args = ()

    kept = mnp.zeros((3, 4))
    CachedOp(Keeps())(x, 2.0, kept)
    assert not kept._data.is_deleted()


# -- the engine ---------------------------------------------------------------

def _gathered_step(monkeypatch):
    """Engines built under this run the fused gather/scatter step that
    the in-place one replaced (``_CacheForward(paged=True)`` alone)."""
    def step(*a, **kw):
        kw["inplace"] = False
        return _CacheForward(*a, **kw)
    monkeypatch.setattr(sched, "_CacheForward", step)


def _logits_spy(eng, seen):
    """``eng._run_step`` that files, under each request's prompt, the
    logits of every call a token of its is sampled from: a decode
    visit's row of each live lane, and the row of a prompt's last chunk.
    (The in-place step samples inside itself, so nothing on the host
    sees these logits unless it looks.)"""
    real = eng._run_step

    def run(tokens, start_pos, last_idx, table, lanes, keep):
        logits = real(tokens, start_pos, last_idx, table, lanes, keep)
        arr = logits.asnumpy()
        if arr.shape[0] == eng.num_slots and np.shape(tokens)[1] == 1:
            rows = [(j, arr[j]) for j in lanes if j >= 0]
        else:
            (j,) = lanes
            s = eng._slots[j]
            last = start_pos[0] + last_idx[0] + 1 == len(s.prompt)
            rows = [(j, arr[0])] if last else []
        for j, row in rows:
            seen.setdefault(tuple(eng._slots[j].prompt), []).append(row)
        return logits

    return run


def _drive(eng, waves, monkeypatch):
    """Each wave of (prompt, max_new) submitted a few steps apart, then
    stepped to the end: ``({prompt: [logits of each sampled token]},
    results)``."""
    seen = {}
    eng.warmup()
    monkeypatch.setattr(eng, "_run_step", _logits_spy(eng, seen))
    futs = []
    for wave in waves:
        futs += [eng.submit(p, max_new_tokens=n) for p, n in wave]
        for _ in range(3):
            eng.step()
    for _ in range(600):
        if all(f.done() for f in futs):
            break
        eng.step()
    out = [f.result(0) for f in futs]
    eng.assert_no_recompiles()
    return seen, out


def _falcon_rehearsal():
    spec = importlib.util.spec_from_file_location(
        "chipbench_harness_for_inplace",
        os.path.join(ROOT, "chipbench", "harness.py"))
    h = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(h)
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "falcon_h1_34b.json")) as f:
        cfg = json.load(f)
    cfg = h.merged(cfg, cfg["rehearse"])
    ref = h.load_module("reference", cfg["reference"])
    adapter = h.load_module("adapters", cfg["adapter"])
    maker = h.load_module(".", "weights").Maker(
        ref.param_shapes(cfg), 5, cfg["initializer_range"])
    net = adapter.build(cfg, False)
    h.load_weights(net, adapter.name_map(cfg), maker)
    return net


@pytest.fixture(scope="module")
def nets():
    llama = get_llama("llama_serve_12l_test")
    llama.initialize()
    return {"llama12": llama, "falcon": _falcon_rehearsal()}


@pytest.mark.parametrize("model,path", [
    ("llama12", "pallas"), ("llama12", "int8"), ("falcon", "pallas")])
def test_engine_in_place_serves_what_the_gathered_step_served(
        nets, model, path, monkeypatch):
    """Six requests over three lanes, staggered: lanes change tenants
    (resets), 19- and 26-token prompts take several chunks of 8 while
    their neighbours decode (dead lanes), every prompt hands over from
    prefill to decode. Same tokens; every sampled token's logits within
    1e-5 of their spread of the gathered step's."""
    rs = np.random.RandomState(9)
    prompts = [rs.randint(1, 500, n).tolist() for n in (5, 19, 11, 3, 26, 9)]
    reqs = list(zip(prompts, [9, 7, 10, 6, 8, 7]))
    waves = [reqs[:2], reqs[2:5], reqs[5:]]
    kw = dict(max_seq=64, num_slots=3, page_size=8, prefill_chunk=8,
              decode_path=path)
    eng = serve.ContinuousEngine(nets[model], name=f"ip_{model}_{path}",
                                 **kw)
    steps0 = prof.get_counter("serve.pool_inplace_steps")
    got, out = _drive(eng, waves, monkeypatch)
    st = eng.stats()
    calls = st["cache"]["hits"] + st["cache"]["misses"]
    assert st["pool_inplace_steps"] == calls > st["steps"] > 0
    assert prof.get_counter("serve.pool_inplace_steps") - steps0 == calls
    assert st["pool_reallocations"] == 0
    assert da.last_path() == "pallas_paged" and da.fallback_count() == 0
    assert eng.session.signature_count() == 2

    _gathered_step(monkeypatch)
    old = serve.ContinuousEngine(nets[model], name=f"ga_{model}_{path}",
                                 **kw)
    assert old._step_block.donate_args == ()
    want, out_old = _drive(old, waves, monkeypatch)
    assert old.stats()["pool_inplace_steps"] == 0
    for (prompt, n), a, b in zip(reqs, out, out_old):
        assert a["tokens"] == b["tokens"] and len(a["tokens"]) == n
        g, w = np.asarray(got[tuple(prompt)]), np.asarray(want[tuple(prompt)])
        assert g.shape == w.shape == (n,) + g.shape[1:]
        assert np.abs(g - w).max() / w.std() <= 1e-5


def test_baseline_rung_consumes_nothing(nets):
    eng = serve.ContinuousEngine(nets["llama12"], max_seq=64, num_slots=2,
                                 page_size=8, prefill_chunk=8,
                                 decode_path="baseline", name="ip_base")
    steps0 = prof.get_counter("serve.pool_inplace_steps")
    eng.warmup()
    held = [a._data for a in eng.pool.flat()]
    f = eng.submit([5, 6, 7], max_new_tokens=3)
    while not f.done():
        eng.step()
    assert len(f.result(0)["tokens"]) == 3
    assert eng.stats()["pool_inplace_steps"] == 0
    assert prof.get_counter("serve.pool_inplace_steps") == steps0
    assert not any(a.is_deleted() for a in held)


# -- a call that fails: before dispatch, and with the pool gone -----------------

def _engine(net, name, **kw):
    args = dict(max_seq=64, num_slots=3, page_size=8, prefill_chunk=8,
                decode_path="pallas", name=name)
    args.update(kw)
    eng = serve.ContinuousEngine(net, **args)
    eng.warmup()
    return eng


def _until(eng, cond, n=400):
    for _ in range(n):
        if cond():
            return
        eng.step()
    raise AssertionError("the engine never got there")


def test_a_fault_before_dispatch_costs_one_slot_and_no_pool(nets):
    """``serve:execute`` fires inside ``session.run`` before the
    executable is called: the pool's buffers stay, the one slot inside
    the call (a prefill chunk's) fails, its neighbour decodes on."""
    eng = _engine(nets["llama12"], "ip_fault")
    f1 = eng.submit([5, 6, 7], max_new_tokens=12)
    _until(eng, lambda: eng._slots[0] is not None and eng._slots[0].decoding)
    faults.install_plan({"seed": 0, "rules": [
        {"site": "serve:execute", "kind": "fatal", "times": 1}]})
    f2 = eng.submit([9, 8, 7, 6], max_new_tokens=4)
    _until(eng, f2.done)
    faults.clear_plan()
    with pytest.raises(Exception):
        f2.result(0)
    assert not eng.pool.lost()
    _until(eng, f1.done)
    alone = _engine(nets["llama12"], "ip_fault_alone")
    g = alone.submit([5, 6, 7], max_new_tokens=12)
    _until(alone, g.done)
    assert f1.result(0)["tokens"] == g.result(0)["tokens"]
    assert eng.stats()["pool_reallocations"] == 0


@pytest.mark.parametrize("prefix", [False, True], ids=["plain", "prefix"])
def test_a_call_that_took_the_pool_settles_every_lane_and_starts_again(
        nets, prefix, monkeypatch):
    """The executable ran (the pool's buffers were donated to it) and the
    call still failed: every live lane is settled with that error, the
    pool starts from zeros, the prefix trie is emptied, and the next
    request is served as a fresh engine serves it."""
    eng = _engine(nets["llama12"], f"ip_lost_{prefix}", prefix_cache=prefix)
    if prefix:   # a retired request leaves its prompt's pages in the trie
        f0 = eng.submit(list(range(1, 20)), max_new_tokens=2)
        _until(eng, f0.done)
        assert eng.prefix.pages_held > 0
    f1 = eng.submit([5, 6, 7], max_new_tokens=30)
    f2 = eng.submit([9, 8, 7, 6, 5, 4, 3, 2, 1, 2, 3], max_new_tokens=30)
    _until(eng, lambda: all(s is not None and s.decoding
                            for s in eng._slots[:2]))
    run = eng.session.run

    def run_then_fail(*args):
        run(*args)                       # consumes the pool arrays
        raise RuntimeError("the answer never came back")

    monkeypatch.setattr(eng.session, "run", run_then_fail)
    before = prof.get_counter("serve.pool_reallocations")
    eng.step()
    monkeypatch.setattr(eng.session, "run", run)
    for f in (f1, f2):
        with pytest.raises(RuntimeError, match="never came back"):
            f.result(0)
    st = eng.stats()
    assert st["pool_reallocations"] == 1 and st["slots_live"] == 0
    assert prof.get_counter("serve.pool_reallocations") == before + 1
    assert not eng.pool.lost() and st["pool"]["pages_owned"] == 0
    assert all(not a.asnumpy().any() for a in eng.pool.flat())
    if prefix:
        assert eng.prefix.pages_held == 0
        assert st["pool"]["pages_used"] == 0
    prompt = list(range(1, 20))
    f3 = eng.submit(prompt, max_new_tokens=6)
    _until(eng, f3.done)
    fresh = _engine(nets["llama12"], f"ip_fresh_{prefix}")
    g = fresh.submit(prompt, max_new_tokens=6)
    _until(fresh, g.done)
    assert f3.result(0)["tokens"] == g.result(0)["tokens"]
    eng.assert_no_recompiles()
