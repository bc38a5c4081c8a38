"""Decode critical-path attribution (PR 16 tentpole, layer 1): the
four-way phase ledger (host / dispatch / device / wait partitioning each
``serve::decode_step`` span), phase-tagged ``engine:wait`` accounting,
per-request ``attribution.report(trace_id)`` over a live
ContinuousEngine, the ``ServeMetrics`` ``(ms, live)`` ITL pairs +
attribution gauges, and the disabled path's cost contract (no call
added, nothing recorded)."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import np as mnp
from mxnet_tpu import profiler
from mxnet_tpu.profiler import attribution, core, export, trace


@pytest.fixture(autouse=True)
def _clean_attribution_state():
    profiler.set_state("stop")
    profiler.reset()
    trace.disable()
    trace.reset()
    attribution.disable()
    attribution.reset()
    yield
    profiler.set_state("stop")
    profiler.reset()
    trace.disable()
    trace.reset()
    attribution.disable()
    attribution.reset()


# -- phase scopes + wait capture ---------------------------------------------


def test_phase_scope_nests_and_restores():
    assert attribution.current_phase() == "other"
    with attribution.phase_scope("decode"):
        assert attribution.current_phase() == "decode"
        with attribution.phase_scope("prefill"):
            assert attribution.current_phase() == "prefill"
        assert attribution.current_phase() == "decode"
    assert attribution.current_phase() == "other"


def test_note_wait_buckets_by_phase_and_thread_total():
    attribution.enable()
    w0 = attribution.thread_wait_ns()
    with attribution.phase_scope("decode"):
        attribution.note_wait(2_000_000)          # 2 ms, tagged decode
    attribution.note_wait(1_000_000, "train")     # explicit phase wins
    attribution.note_wait(500_000)                # unlabeled -> other
    by_phase = attribution.wait_ms_by_phase()
    assert by_phase["decode"] == pytest.approx(2.0)
    assert by_phase["train"] == pytest.approx(1.0)
    assert by_phase["other"] == pytest.approx(0.5)
    # the thread accumulator is monotone (loops difference snapshots)
    assert attribution.thread_wait_ns() - w0 == 3_500_000
    # disabled note_wait is a no-op
    attribution.disable()
    attribution.note_wait(10_000_000, "decode")
    assert attribution.wait_ms_by_phase()["decode"] == pytest.approx(2.0)


def test_engine_wait_hook_feeds_phase_tagged_ledger():
    """A real blocking engine wait inside a phase scope lands in that
    phase's bucket via the ``engine._ATTR`` slot."""
    attribution.enable()
    x = mnp.ones((64, 64))
    with attribution.phase_scope("decode"):
        y = (x @ x).sum()
        y.wait_to_read()
    assert attribution.wait_ms_by_phase().get("decode", 0.0) >= 0.0
    assert attribution.thread_wait_ns() > 0


# -- the Ledger --------------------------------------------------------------


def test_ledger_math_and_bounds():
    led = attribution.Ledger("t", window=4)
    assert led.host_overhead_fraction() == 0.0
    assert led.device_ms_per_token() == 0.0
    led.observe_step(1.0, 2.0, 6.0, 1.0, live=2)
    led.observe_step(0.0, 1.0, 7.0, 0.0, live=2)
    led.observe_schedule(2.0)
    snap = led.snapshot()
    # hof = (sched + host + dispatch + wait) / total
    assert snap["host_overhead_fraction"] == pytest.approx(7.0 / 20.0)
    assert snap["device_ms_per_token"] == pytest.approx(13.0 / 4.0)
    assert snap["steps"] == 2 and snap["tokens"] == 4
    assert 0.0 <= snap["host_overhead_fraction"] <= 1.0
    # bounded window: old rows fall out, lifetime step count doesn't
    for _ in range(6):
        led.observe_step(0.0, 0.0, 1.0, 0.0, live=1)
    snap = led.snapshot()
    assert snap["window"] == 4 and snap["steps"] == 8
    assert snap["device_ms"] == pytest.approx(4.0)


def test_ledger_exports_through_snapshot_and_serve_gauges():
    from mxnet_tpu.serve.metrics import ServeMetrics

    attribution.enable()
    led = attribution.Ledger("exp_test")
    led.observe_step(1.0, 1.0, 8.0, 0.0, live=2)
    m = ServeMetrics("exp_test")
    m.set_attribution(led.host_overhead_fraction(),
                      led.device_ms_per_token())
    snap = export.snapshot()
    assert snap["attribution.exp_test.device_ms_per_token"] == \
        pytest.approx(4.0)
    assert snap["serve.exp_test.host_overhead_fraction"] == \
        pytest.approx(0.2)
    assert 0.0 <= snap["attribution.exp_test.host_overhead_fraction"] <= 1.0


# -- ServeMetrics (ms, live) ITL pairs ---------------------------------------


def test_observe_itl_records_live_pairs_backward_compatible():
    from mxnet_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics("itl_pairs")
    m.observe_itl(5.0)            # old single-arg call keeps working
    m.observe_itl(7.0, live=4)
    assert m.itl_samples() == [(5.0, 1), (7.0, 4)]
    snap = m.snapshot()
    assert snap["itl_p50_ms"] > 0.0          # percentile surface intact
    assert snap["itl_live_mean"] == pytest.approx(2.5)


# -- end to end over a live ContinuousEngine ---------------------------------


def _tiny_engine(**over):
    from mxnet_tpu.models.llama import get_llama
    from mxnet_tpu.serve import ContinuousEngine

    net = get_llama("llama_tiny_test")
    net.initialize()
    kw = dict(max_seq=64, num_slots=4, page_size=16, prefill_chunk=16,
              decode_path="baseline", name="attr_cb")
    kw.update(over)
    return ContinuousEngine(net, **kw)


@pytest.mark.serial
def test_report_e2e_continuous_engine():
    """The acceptance path: a traced request through the iteration-level
    scheduler yields a critical-path report whose decode phase carries
    ledger args summing within 10% of the span walls."""
    attribution.enable()
    trace.enable()
    with _tiny_engine() as eng:
        futs = [eng.submit([5, 6, 7], max_new_tokens=8),
                eng.submit([9, 10, 11, 12], max_new_tokens=8)]
        for f in futs:
            assert len(f.result(timeout=60)["tokens"]) == 8
        snap = eng.ledger.snapshot()
        assert snap["steps"] > 0
        assert 0.0 < snap["host_overhead_fraction"] <= 1.0
        assert snap["device_ms_per_token"] > 0.0
        ms = eng.metrics.snapshot()
        assert ms["device_ms_per_token"] > 0.0
        assert ms["itl_live_mean"] >= 1.0

    tid = [s["trace_id"] for s in trace.summaries(limit=50)
           if s["name"].startswith("serve.request")][-1]
    rep = attribution.report(tid)
    assert rep is not None and rep["finished"]
    assert rep["decode_steps"] > 0
    assert rep["ledger_steps"] == rep["decode_steps"]
    assert rep["prefill_chunks"] >= 1
    lsum = sum(rep["phase_ledger"].values())
    assert lsum == pytest.approx(rep["decode_ms"],
                                 rel=0.10, abs=1.0)
    # every decode_step span's four args reconcile with ITS wall
    for sp in trace.summary(tid)["spans"]:
        if sp["name"] != "serve::decode_step":
            continue
        a = sp["args"]
        s = sum(a[k] for k in ("host_ms", "dispatch_ms", "device_ms",
                               "wait_ms"))
        assert abs(s - sp["dur_ms"]) <= max(0.10 * sp["dur_ms"], 0.05), \
            (s, sp["dur_ms"], a)


def test_report_unknown_trace_is_none():
    assert attribution.report(999_999) is None


def test_disabled_engine_records_nothing():
    """ENABLED=False: no span args, empty ledger, zero cost branches."""
    trace.enable()
    with _tiny_engine(name="attr_off") as eng:
        eng.submit([5, 6, 7], max_new_tokens=4).result(timeout=60)
        assert eng.ledger.snapshot()["steps"] == 0
    tid = [s["trace_id"] for s in trace.summaries(limit=50)
           if s["name"].startswith("serve.request")][-1]
    rep = attribution.report(tid)
    assert rep["decode_steps"] > 0 and rep["ledger_steps"] == 0


# -- the disabled path's cost ------------------------------------------------


def test_disabled_attribution_adds_no_call_and_records_nothing():
    """Eager microloop with the attribution slot installed but ENABLED
    False: the same cost contract as the profiler/trace hooks, held as a
    count and a state (ROADMAP D9: a CPU timing is not a gate). The loop
    makes not one Python or C call more than with the slot removed, and
    no wait is recorded."""
    import sys

    from mxnet_tpu import engine

    x = mnp.ones((4,))

    def loop(n=500):
        y = x
        for _ in range(n):
            y = y + 1.0
        y.wait_to_read()

    def calls(fn):
        seen = [0]

        def count(_frame, event, _arg):
            if event in ("call", "c_call"):
                seen[0] += 1

        sys.setprofile(count)
        try:
            fn()
        finally:
            sys.setprofile(None)
        return seen[0]

    saved = engine._ATTR
    try:
        loop(200)  # warm caches before either arm
        engine._ATTR = None
        base = calls(loop)
        attribution._install_engine_slot()
        attribution.disable()  # slot present, ledger off
        attribution.reset()
        hooked = calls(loop)
        assert base > 500 and hooked == base, (base, hooked)
        assert attribution.wait_ms_by_phase() == {}
        assert attribution.thread_wait_ns() == 0
    finally:
        engine._ATTR = saved
