"""One decode visit in flight: ``ContinuousEngine`` on the in-place step
enqueues visit N+1 before it fetches visit N's greedy ids, which the step
samples inside itself and keeps on the device. Held here, on a dense
Llama, on ``falcon_h1`` (recurrent state beside K/V) and on ``mellum``
(a window's ring of pages, routed experts) at their tiny test widths:
the tokens, stamps and step counts are those of a loop that fetches
first; nothing blocks between a prompt's last chunk and the visit that
consumes its first token; a stop id learned one visit late costs one
lane-step and no token, and the slot's next tenant answers as alone; a
sampled lane makes the loop fetch first and still reproduces under
``mx.random.seed``; a deadline, a failure, ``close()`` and ``drain()``
with a visit in flight settle every request exactly once.

States and counts only: the tests drive ``step()`` themselves, so the
host's speed decides nothing.
"""
import importlib.util
import json
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve
from mxnet_tpu.models.llama import get_llama
from mxnet_tpu.ops.pallas import decode_attention as da
from mxnet_tpu.profiler import core as prof
from mxnet_tpu.resilience import faults
from mxnet_tpu.serve import DeadlineExceeded, ServiceUnavailable
from mxnet_tpu.serve import scheduler as sched

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = ["llama", "falcon_h1", "mellum"]


def _rehearsal(config):
    """The benchmark's configuration at the widths of its ``rehearse``
    group, with the benchmark's seeded weights (wide enough that a state
    or a ring column gone wrong changes tokens)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_harness_for_ahead",
        os.path.join(ROOT, "chipbench", "harness.py"))
    h = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(h)
    with open(os.path.join(ROOT, "chipbench", "configs",
                           config + ".json")) as f:
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
    da.use_interpret(True)   # the paged kernel, interpreted on the CPU
    llama = get_llama("llama_tiny_test")
    llama.initialize()
    yield {"llama": llama, "falcon_h1": _rehearsal("falcon_h1_34b"),
           "mellum": _rehearsal("mellum2_12b_a2_5b")}
    da.use_interpret(False)


@pytest.fixture(autouse=True)
def no_faults():
    faults.clear_plan()
    yield
    faults.clear_plan()


_names = iter(range(10 ** 6))


def engine_of(nets, model, **kw):
    """Pages of 8 and chunks of 8: a 26-token prompt takes four chunks,
    and ``mellum``'s window of 16 turns its ring of three columns."""
    args = dict(max_seq=64, num_slots=3, page_size=8, prefill_chunk=8,
                decode_path="pallas", name=f"ahead_{model}_{next(_names)}")
    args.update(kw)
    eng = serve.ContinuousEngine(nets[model], **args)
    eng.warmup()
    return eng


def requests_of(seed=9):
    rs = np.random.RandomState(seed)
    prompts = [rs.randint(1, 200, n).tolist() for n in (5, 19, 11, 3, 26, 9)]
    return list(zip(prompts, [9, 7, 10, 6, 8, 7]))


def until(eng, cond, n=600):
    for _ in range(n):
        if cond():
            return
        eng.step()
    raise AssertionError("the engine never got there")


def serve_waves(eng, waves, **submit):
    """Each wave of (prompt, max_new) submitted a few steps apart, then
    stepped to the end."""
    futs = []
    for wave in waves:
        futs += [eng.submit(p, max_new_tokens=n, **submit) for p, n in wave]
        for _ in range(3):
            eng.step()
    until(eng, lambda: all(f.done() for f in futs))
    return [f.result(0) for f in futs]


def alone(nets, model, prompt, n, **submit):
    eng = engine_of(nets, model)
    (out,) = serve_waves(eng, [[(prompt, n)]], **submit)
    return out["tokens"]


class Calls:
    """Every call of an engine's step, by kind, with the engine step it
    was made in and the decode visits that were unfetched then."""

    def __init__(self, eng):
        self.visits, self.chunks, real = [], [], eng._run_step

        def run(tokens, start_pos, last_idx, table, lanes, keep):
            tokens = np.asarray(tokens)
            if tokens.shape[1] == 1:
                self.visits.append({
                    "step": eng._steps, "tokens": tokens[:, 0].copy(),
                    "riders": [int(j) for j in lanes if j >= 0],
                    "behind": sum(f.decode for f in eng._flights)})
            else:
                self.chunks.append({"step": eng._steps, "slot": int(lanes[0]),
                                    "last": int(keep[0]) >= 0,
                                    "flights": list(eng._flights)})
            return real(tokens, start_pos, last_idx, table, lanes, keep)

        eng._run_step = run

    def busy_periods(self):
        """Maximal runs of engine steps that each made a decode visit."""
        steps = [v["step"] for v in self.visits]
        return 1 + sum(b - a > 1 for a, b in zip(steps, steps[1:]))


def stamps_hold(out):
    assert out["token_ms"][0] == out["ttft_ms"]
    assert all(b >= a for a, b in zip(out["token_ms"], out["token_ms"][1:]))
    assert len(out["token_ms"]) == len(out["tokens"])


# -- (a) the same answers as a loop that fetches first -------------------------

@pytest.mark.parametrize("model,drained_by", [
    ("llama", "sampled"), ("llama", "strict"), ("falcon_h1", "sampled"),
    ("mellum", "sampled")])
def test_ahead_serves_what_the_drained_loop_serves(nets, model, drained_by):
    reqs = requests_of()
    waves = [reqs[:2], reqs[2:5], reqs[5:]]
    eng = engine_of(nets, model)
    calls = Calls(eng)
    got = serve_waves(eng, waves)
    # a second busy period: the engine has stood idle in between
    assert eng._idle() and not eng._flights
    got += serve_waves(eng, [reqs[:1]])
    eng.assert_no_recompiles()
    pipe = eng.stats()["pipeline"]
    periods = calls.busy_periods()
    assert periods >= 2
    # every decode visit but the first of each busy period ran ahead
    assert pipe["visits_ahead"] == len(calls.visits) - periods
    assert pipe["visits_ahead"] == sum(v["behind"] for v in calls.visits)
    assert pipe["visits_drained"] == 0 and pipe["overrun_lane_steps"] == 0
    assert pipe["in_flight"] == 0

    if drained_by == "strict":
        slow = engine_of(nets, model, decode_path="baseline")
        want = serve_waves(slow, waves) + serve_waves(slow, [reqs[:1]])
    else:
        # one lane samples with a temperature for as long as the others
        # live: every visit it rides has to be fetched first
        slow = engine_of(nets, model, num_slots=4)
        mx.random.seed(7)
        hot = slow.submit([7, 8, 9], max_new_tokens=60, temperature=0.8)
        until(slow, lambda: slow._slots[0] is not None
              and slow._slots[0].decoding)
        ahead0 = slow.stats()["pipeline"]["visits_ahead"]
        want = serve_waves(slow, waves) + serve_waves(slow, [reqs[:1]])
        assert not hot.done()
        assert slow.stats()["pipeline"]["visits_ahead"] == ahead0
    pipe = slow.stats()["pipeline"]
    assert pipe["drained_by"][drained_by] > 0
    assert pipe["visits_drained"] == sum(pipe["drained_by"].values())
    for (prompt, n), a, b in zip(reqs + reqs[:1], got, want):
        assert a["tokens"] == b["tokens"] and len(a["tokens"]) == n
        assert a["decode_steps"] == b["decode_steps"] == n - 1
        stamps_hold(a)
        stamps_hold(b)


# -- (b) nothing blocks between a last chunk and the visit that takes its token -

@pytest.mark.parametrize("model", MODELS)
def test_no_fetch_between_a_last_chunk_and_its_visit(nets, model,
                                                    monkeypatch):
    eng = engine_of(nets, model)
    events, real_run = [], eng._run_step

    def run(tokens, start_pos, last_idx, table, lanes, keep):
        if np.shape(tokens)[1] == 1:
            events.append(("visit", {int(j): int(np.asarray(tokens)[j, 0])
                                     for j in lanes if j >= 0}))
        elif keep[0] >= 0:
            events.append(("last_chunk", int(keep[0])))
        return real_run(tokens, start_pos, last_idx, table, lanes, keep)

    def spied(name):
        real = getattr(sched, name)

        def fetch(*a, **kw):
            events.append(("fetch", name))
            return real(*a, **kw)
        monkeypatch.setattr(sched, name, fetch)

    eng._run_step = run
    spied("fetch_ids")
    spied("sample_tokens")
    reqs = requests_of()
    out = serve_waves(eng, [reqs[:2], reqs[2:5], reqs[5:]])
    assert [len(o["tokens"]) for o in out] == [n for _, n in reqs]
    chunks = [k for k, e in enumerate(events) if e[0] == "last_chunk"]
    assert len(chunks) == len(reqs)
    for k in chunks:
        slot = events[k][1]
        kind, riders = events[k + 1]
        # the very next thing the host does is enqueue the visit, and the
        # lane's token is the one the device kept (no host value)
        assert kind == "visit" and riders[slot] == -1, events[k:k + 3]
    # the eager argmax is gone from a greedy visit: ids alone are fetched
    assert {e[1] for e in events if e[0] == "fetch"} == {"fetch_ids"}
    assert eng.session.signature_count() == 2
    eng.assert_no_recompiles()


# -- (c) a stop id is learned one visit late ------------------------------------

@pytest.mark.parametrize("model", MODELS)
def test_a_stop_id_costs_one_lane_step_and_no_token(nets, model):
    (p_stop, _), (p_long, _), (p_next, _) = requests_of(21)[:3]
    free = alone(nets, model, p_stop, 12)
    # a token that ends the request in the middle of what it would say
    j = next(k for k in range(3, 12) if free[k] not in free[:k])
    want_next = alone(nets, model, p_next, 8)

    eng = engine_of(nets, model, num_slots=2)
    calls = Calls(eng)
    before = prof.get_counter("serve.overrun_lane_steps")
    f_long = eng.submit(p_long, max_new_tokens=30)
    f_stop = eng.submit(p_stop, max_new_tokens=12, stop_ids=[free[j]])
    f_next = eng.submit(p_next, max_new_tokens=8)    # waits for a slot
    until(eng, lambda: all(f.done() for f in (f_long, f_stop, f_next)))
    assert f_stop.result(0)["tokens"] == free[:j]
    assert f_stop.result(0)["decode_steps"] == j
    assert eng.stats()["pipeline"]["overrun_lane_steps"] == 1
    assert prof.get_counter("serve.overrun_lane_steps") == before + 1
    # the slot's next tenant was admitted, and its first chunk enqueued,
    # while the visit that the stopped lane rode in vain was unfetched
    first = [c for c in calls.chunks if c["slot"] == 1][1]
    assert any(f.decode and any(i == 1 and s.finished for i, s in f.riders)
               for f in first["flights"])
    assert f_next.result(0)["tokens"] == want_next
    assert f_long.result(0)["tokens"] == alone(nets, model, p_long, 30)
    st = eng.stats()
    assert st["pool"]["pages_owned"] == 0 and st["pipeline"]["in_flight"] == 0
    eng.assert_no_recompiles()


# -- (d) a sampled lane joins greedy ones ----------------------------------------

@pytest.mark.parametrize("model", MODELS)
def test_a_sampled_lane_drains_and_reproduces(nets, model):
    (p_a, _), (p_b, _), (p_hot, _) = requests_of(33)[:3]

    def run():
        eng = engine_of(nets, model)
        mx.random.seed(11)
        greedy = [eng.submit(p_a, max_new_tokens=24),
                  eng.submit(p_b, max_new_tokens=24)]
        until(eng, lambda: eng.stats()["pipeline"]["visits_ahead"] >= 3)
        assert eng.stats()["pipeline"]["visits_drained"] == 0
        hot = eng.submit(p_hot, max_new_tokens=6, temperature=0.9, top_k=20)
        until(eng, hot.done)
        pipe = eng.stats()["pipeline"]
        # one visit a token the sampled lane decoded, each fetched first
        assert pipe["drained_by"]["sampled"] == pipe["visits_drained"] == 5
        ahead = pipe["visits_ahead"]
        until(eng, lambda: all(f.done() for f in greedy))
        assert eng.stats()["pipeline"]["visits_ahead"] > ahead
        eng.assert_no_recompiles()
        return hot.result(0)["tokens"], [f.result(0)["tokens"]
                                         for f in greedy]

    hot1, greedy1 = run()
    hot2, greedy2 = run()
    assert hot1 == hot2 and len(hot1) == 6
    assert greedy1 == greedy2 == [alone(nets, model, p_a, 24),
                                  alone(nets, model, p_b, 24)]


# -- (e) a deadline runs out with a visit in flight ------------------------------

class _Clock:
    """``time`` as the scheduler sees it, ``ahead`` seconds on."""
    ahead = 0.0

    def monotonic(self):
        return time.monotonic() + self.ahead

    def __getattr__(self, name):
        return getattr(time, name)


@pytest.mark.parametrize("model", MODELS)
def test_a_deadline_with_a_visit_in_flight_keeps_what_was_fetched(
        nets, model, monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(sched, "time", clock)
    eng = engine_of(nets, model, num_slots=2)
    (p_a, _), (p_b, _) = requests_of(5)[:2]
    f = eng.submit(p_a, max_new_tokens=40, deadline_ms=600_000)
    g = eng.submit(p_b, max_new_tokens=12)
    until(eng, lambda: eng._slots[0] is not None
          and len(eng._slots[0].tokens) >= 3)
    s = eng._slots[0]
    assert s.inflight == 1 and any(fl.decode for fl in eng._flights)
    fetched = list(s.tokens)
    clock.ahead = 3600.0          # the budget is gone, the visit is not back
    eng.step()
    clock.ahead = 0.0
    with pytest.raises(DeadlineExceeded) as err:
        f.result(0)
    assert err.value.partial == fetched
    assert err.value.partial == alone(nets, model, p_a, 40)[:len(fetched)]
    until(eng, g.done)
    assert g.result(0)["tokens"] == alone(nets, model, p_b, 12)
    st = eng.stats()
    assert st["pipeline"]["overrun_lane_steps"] == 0   # no stop id: not one
    assert st["pool"]["pages_owned"] == 0 and st["pipeline"]["in_flight"] == 0


# -- (f) a failure with a visit in flight ----------------------------------------

@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("how", ["decode", "execute", "pool", "fetch"])
def test_a_failure_with_a_visit_in_flight_settles_each_lane_once(
        nets, model, how, monkeypatch):
    eng = engine_of(nets, model)
    settled, settle = [], eng._batcher.settle_one

    def counting(p, result=None, error=None):
        settled.append(p)
        return settle(p, result=result, error=error)

    monkeypatch.setattr(eng._batcher, "settle_one", counting)
    (p_a, _), (p_b, _), (p_c, _) = requests_of(13)[:3]
    f_short = eng.submit(p_a, max_new_tokens=9)
    f_b = eng.submit(p_b, max_new_tokens=30)
    f_c = eng.submit(p_c, max_new_tokens=30)
    # the short request's last token is in flight: it rides no further visit
    until(eng, lambda: eng._slots[0] is not None
          and len(eng._slots[0].tokens) == 8 and eng._slots[0].inflight == 1)
    assert all(s.decoding for s in eng._slots)
    assert sum(fl.decode for fl in eng._flights) == 1
    before = prof.get_counter("serve.pool_reallocations")
    run, fetch = eng.session.run, sched.fetch_ids

    def never(*args):
        if how == "pool":
            run(*args)                       # consumes the pool arrays
        raise RuntimeError("the answer never came back")

    if how in ("decode", "execute"):
        faults.install_plan({"seed": 0, "rules": [
            {"site": f"serve:{how}", "kind": "fatal", "times": 1}]})
    elif how == "pool":
        monkeypatch.setattr(eng.session, "run", never)
    else:
        monkeypatch.setattr(sched, "fetch_ids", never)
    eng.step()
    faults.clear_plan()
    monkeypatch.setattr(eng.session, "run", run)
    monkeypatch.setattr(sched, "fetch_ids", fetch)
    assert not eng._flights
    for f in (f_b, f_c):
        with pytest.raises(Exception):
            f.result(0)
    lost_pool = how in ("pool", "fetch")
    if lost_pool:
        # the call was dispatched: every lane's cache went with the pool
        with pytest.raises(RuntimeError, match="never came back"):
            f_short.result(0)
    else:
        # what was in flight before the failed call is whole
        eng.step()
        assert f_short.result(0)["tokens"] == alone(nets, model, p_a, 9)
    st = eng.stats()
    assert st["pool_reallocations"] == int(lost_pool)
    assert prof.get_counter("serve.pool_reallocations") \
        == before + int(lost_pool)
    assert not eng.pool.lost()
    # the next request is served as a fresh engine serves it, and the
    # first visit after the failure had nothing to run ahead of
    f_next = eng.submit(p_c, max_new_tokens=6)
    until(eng, f_next.done)
    assert f_next.result(0)["tokens"] == alone(nets, model, p_c, 6)
    st = eng.stats()
    assert st["pipeline"]["drained_by"]["failure"] == 1
    assert st["pool"]["pages_owned"] == 0 and st["pipeline"]["in_flight"] == 0
    assert len(settled) == len({id(p) for p in settled}) == 4
    eng.assert_no_recompiles()


# -- (g) close() and drain() with a visit in flight ------------------------------

@pytest.mark.parametrize("model", MODELS)
def test_close_with_a_visit_in_flight_settles_everything(nets, model):
    eng = engine_of(nets, model)
    reqs = requests_of(3)[:4]
    futs = [eng.submit(p, max_new_tokens=30) for p, _ in reqs]
    until(eng, lambda: any(fl.decode for fl in eng._flights))
    eng.close()
    assert not eng._flights and not eng._live()
    for f in futs:
        with pytest.raises(ServiceUnavailable):
            f.result(0)
    assert eng.stats()["pool"]["pages_owned"] == 0


@pytest.mark.parametrize("model", MODELS)
def test_drain_waits_for_what_is_in_flight(nets, model):
    reqs = requests_of(3)
    with engine_of(nets, model) as eng:
        futs = [eng.submit(p, max_new_tokens=n) for p, n in reqs]
        assert eng.drain(timeout=120)
        assert all(f.done() for f in futs)
        deadline = time.monotonic() + 30
        while not eng._idle() and time.monotonic() < deadline:
            time.sleep(0.01)     # the loop's last step is landing
        st = eng.stats()
        assert st["pipeline"]["in_flight"] == 0 and st["slots_live"] == 0
        assert st["pool"]["pages_owned"] == 0
        assert st["pipeline"]["visits_ahead"] > 0
        eng.resume()
        for (p, n), f in zip(reqs, futs):
            assert len(f.result(0)["tokens"]) == n


# -- a request that ends by count leaves its slot one visit late ------------------

@pytest.mark.parametrize("model", MODELS)
def test_a_request_that_ends_by_count_rides_no_visit_in_vain(nets, model):
    """Its last token is in flight: the next visit goes out without it
    (no row is wasted, no overrun counted), its future settles at the
    retire after the fetch, and a tenant that was waiting in the queue
    takes the slot then, its first token read from the device."""
    eng = engine_of(nets, model, num_slots=2)
    calls = Calls(eng)
    (p_a, _), (p_b, _), (p_c, _) = requests_of(17)[:3]
    f_long = eng.submit(p_b, max_new_tokens=30)
    f_a = eng.submit(p_a, max_new_tokens=6)
    f_c = eng.submit(p_c[:7], max_new_tokens=5)    # one chunk, and waits
    until(eng, lambda: eng._slots[1] is not None
          and len(eng._slots[1].tokens) == 5 and eng._slots[1].inflight == 1)
    seen = len(calls.visits)
    eng.step()      # rides no further visit; its last token is fetched
    assert calls.visits[seen]["riders"] == [0]
    assert eng._slots[1].finished and not f_a.done()
    eng.step()      # retired; the waiting tenant admitted, prefilled, riding
    assert f_a.done() and eng._slots[1].p is not eng._slots[0].p
    assert calls.visits[seen + 1]["riders"] == [0, 1]
    assert calls.visits[seen + 1]["tokens"][1] == -1
    until(eng, lambda: f_c.done() and f_long.done())
    assert f_a.result(0)["tokens"] == alone(nets, model, p_a, 6)
    assert f_c.result(0)["tokens"] == alone(nets, model, p_c[:7], 5)
    assert eng.stats()["pipeline"]["overrun_lane_steps"] == 0
    eng.assert_no_recompiles()


# -- what comes from outside the program is checked ------------------------------

def test_negative_token_ids_are_refused(nets):
    """A negative token tells the in-place step to take the lane's carried
    id: neither a prompt nor the pad id may hold one."""
    eng = engine_of(nets, "llama")
    with pytest.raises(mx.MXNetError, match="negative token id"):
        eng.submit([3, -1, 4], max_new_tokens=2)
    with pytest.raises(mx.MXNetError, match="pad_id"):
        serve.ContinuousEngine(nets["llama"], max_seq=64, num_slots=2,
                               page_size=8, pad_id=-1, name="ahead_bad_pad")
    (out,) = serve_waves(eng, [[([3, 1, 4], 2)]])
    assert len(out["tokens"]) == 2


# -- a drained visit is drawn by the host's sampler on every rung -----------------

def test_a_sampled_lane_on_the_strict_rung_is_not_greedy(nets):
    """On the strict rung every visit is fetched first, and its rows are
    still sampled as their requests ask: a lane with a temperature
    reproduces under ``mx.random.seed``, moves with the seed, and leaves
    its greedy neighbour's tokens alone."""
    (p_a, _), (p_hot, _) = requests_of(41)[:2]

    def run(seed):
        eng = engine_of(nets, "llama", decode_path="baseline")
        mx.random.seed(seed)
        hot = eng.submit(p_hot, max_new_tokens=16, temperature=1.5)
        cold = eng.submit(p_a, max_new_tokens=16)
        until(eng, lambda: hot.done() and cold.done())
        pipe = eng.stats()["pipeline"]
        assert pipe["visits_ahead"] == 0 and pipe["drained_by"]["strict"] > 0
        return hot.result(0)["tokens"], cold.result(0)["tokens"]

    hot1, cold1 = run(3)
    hot2, cold2 = run(3)
    hot3, cold3 = run(4)
    greedy = alone(nets, "llama", p_hot, 16)
    assert hot1 == hot2 and cold1 == cold2 == cold3
    assert cold1 == alone(nets, "llama", p_a, 16)
    # sixteen draws at a temperature of 1.5: not the argmax every time
    assert hot1 != greedy and hot3 != greedy and hot1 != hot3
