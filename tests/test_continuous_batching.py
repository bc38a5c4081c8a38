"""Conformance tests for continuous batching
(``mxnet_tpu/serve/scheduler.py``): iteration-level admission/retirement
over the fixed slot lattice, chunked prefill, trace-static steady state
(>= 100 admit/retire cycles with zero recompiles), PR-6 deadline and
priority semantics through the scheduler, pool-exhaustion backpressure,
and the TTFT/ITL + kv-page metrics surface.
"""
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models.llama import get_llama
from mxnet_tpu.resilience import faults
from mxnet_tpu.serve import ContinuousEngine, DeadlineExceeded, Generator, \
    ServiceUnavailable


def _tiny_llama(config="llama_tiny_test", **over):
    net = get_llama(config, **over)
    net.initialize()
    return net


@pytest.fixture
def no_faults():
    yield
    faults.clear_plan()


@pytest.fixture(scope="module")
def net():
    return _tiny_llama()


def _engine(net, **over):
    kw = dict(max_seq=64, num_slots=4, page_size=16, prefill_chunk=16,
              decode_path="baseline")
    kw.update(over)
    return ContinuousEngine(net, **kw)


class TestScheduler:
    def test_two_signatures_and_token_parity(self, net):
        """The engine compiles exactly TWO executables — one chunked
        prefill, one full-width decode — and its greedy output matches
        the plain Generator token-for-token (short, long, and
        multi-chunk prompts)."""
        with _engine(net, name="cb_parity") as eng:
            assert eng.session.signature_count() == 2
            ref = Generator(net, max_seq=64, batch_buckets=(1,),
                            prompt_buckets=(16, 32),
                            decode_path="baseline", name="cb_ref")
            prompts = [[5, 6, 7], [9, 10, 11, 12, 13], [3] * 20]
            futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
            for p, f in zip(prompts, futs):
                want, _ = ref.generate([p], max_new_tokens=6)
                assert f.result(timeout=60)["tokens"] == want[0]
            eng.assert_no_recompiles()
            assert eng.session.signature_count() == 2

    def test_hundred_admit_retire_cycles_zero_recompiles(self, net):
        """THE acceptance invariant: >= 100 admit/retire cycles through
        every occupancy (the engine has 2 slots, requests of varying
        prompt/output lengths churn constantly) and the signature set
        never grows."""
        with _engine(net, num_slots=2, name="cb_churn",
                     max_queue=128) as eng:
            futs = [eng.submit([1 + i % 50, 2 + i % 30],
                               max_new_tokens=1 + i % 4)
                    for i in range(110)]
            for i, f in enumerate(futs):
                r = f.result(timeout=120)
                assert len(r["tokens"]) == 1 + i % 4
            eng.assert_no_recompiles()
            st = eng.stats()
            assert st["pool"]["pages_owned"] == 0  # all recycled
            assert st["requests"] >= 110

    def test_interactive_preempts_queued_batch_work(self, net):
        """PR-6 class semantics at the iteration boundary: with one slot
        and a backlog of batch-class work, an interactive arrival is
        admitted before every queued batch request."""
        with _engine(net, num_slots=1, name="cb_prio") as eng:
            order = []
            lock = threading.Lock()

            def tag(name):
                def cb(_f):
                    with lock:
                        order.append(name)
                return cb

            # slot occupied by a long batch job; more batch work queued
            eng.submit([5] * 8, max_new_tokens=40,
                       priority="batch").add_done_callback(tag("b0"))
            time.sleep(0.05)  # let it occupy the slot
            for i in range(3):
                eng.submit([6, 7], max_new_tokens=4,
                           priority="batch").add_done_callback(
                               tag(f"b{i + 1}"))
            fi = eng.submit([8, 9], max_new_tokens=2,
                            priority="interactive")
            fi.add_done_callback(tag("i"))
            fi.result(timeout=60)
            eng.drain(timeout=60)
            with lock:
                # the interactive request finished before every QUEUED
                # batch request (b0 already held the slot)
                assert order.index("i") < order.index("b1")
                assert order.index("i") < order.index("b2")
                assert order.index("i") < order.index("b3")
            eng.resume()
            eng.assert_no_recompiles()

    def test_deadline_mid_decode_is_504_with_partial(self, net,
                                                     monkeypatch):
        """The deadline lapses between two decode steps. The test drives
        the engine's steps itself and moves the clock the scheduler
        reads, so it asserts a state and no rate: the host's speed
        decides nothing."""
        from mxnet_tpu.serve import scheduler

        class _Clock:
            """``time`` as the scheduler sees it, ``ahead`` seconds on."""
            ahead = 0.0

            def monotonic(self):
                return time.monotonic() + self.ahead

            def __getattr__(self, name):
                return getattr(time, name)

        clock = _Clock()
        monkeypatch.setattr(scheduler, "time", clock)
        eng = _engine(net, num_slots=2, name="cb_dl")
        try:
            eng.warmup()
            f = eng.submit([9, 9, 9], max_new_tokens=40,
                           deadline_ms=600_000)
            for _ in range(3):      # admit, prefill, the first tokens
                eng.step()
            assert not f.done()
            clock.ahead = 3600.0    # the budget is gone
            eng.step()              # retires the lane
            with pytest.raises(DeadlineExceeded) as ei:
                f.result(timeout=0)
            assert ei.value.status == 504
            assert 0 < len(ei.value.partial) < 40
            snap = eng.metrics.snapshot()
            assert snap["deadline_expired"].get("decode", 0) >= 1
            eng.assert_no_recompiles()
        finally:
            eng.close()

    def test_pool_exhaustion_queues_not_crashes(self, net):
        """Undersized pool (pages for ~1 request): admissions beyond
        capacity wait for retirements to recycle pages; every request
        still completes and the exhaustion shows in pool stats."""
        with _engine(net, num_slots=2, num_pages=4,
                     name="cb_tight") as eng:
            futs = [eng.submit([3, 4, 5], max_new_tokens=30)
                    for _ in range(4)]
            for f in futs:
                assert len(f.result(timeout=120)["tokens"]) == 30
            st = eng.stats()
            assert st["pool"]["exhausted_count"] > 0
            assert st["pool"]["pages_owned"] == 0
            eng.assert_no_recompiles()

    def test_submit_validation(self, net):
        with _engine(net, name="cb_val") as eng:
            with pytest.raises(MXNetError, match="empty prompt"):
                eng.submit([])
            with pytest.raises(MXNetError, match="exceeds max_seq"):
                eng.submit([1] * 40, max_new_tokens=40)
            with pytest.raises(MXNetError, match="max_new_tokens"):
                eng.submit([1], max_new_tokens=0)

    def test_close_fails_live_and_queued_with_503(self, net):
        eng = _engine(net, num_slots=1, name="cb_close")
        eng.start()
        f_live = eng.submit([5] * 8, max_new_tokens=40)
        time.sleep(0.05)
        f_q = eng.submit([6, 7], max_new_tokens=4)
        eng.close()
        for f in (f_live, f_q):
            with pytest.raises(ServiceUnavailable):
                f.result(timeout=5)

    def test_decode_fault_fails_requests_not_engine(self, net, no_faults):
        """An injected serve:decode fault is a per-request 5xx; the
        scheduler keeps serving the next submission."""
        with _engine(net, num_slots=2, name="cb_fault") as eng:
            faults.install_plan({"seed": 0, "rules": [
                {"site": "serve:decode", "kind": "fatal", "times": 1}]})
            f = eng.submit([5, 6], max_new_tokens=8)
            with pytest.raises(Exception):
                f.result(timeout=60)
            faults.clear_plan()
            r = eng.submit([5, 6], max_new_tokens=4).result(timeout=60)
            assert len(r["tokens"]) == 4
            st = eng.stats()
            assert st["pool"]["pages_owned"] == 0  # fault freed its pages

    def test_idempotency_key_exactly_once(self, net):
        with _engine(net, name="cb_key") as eng:
            f1 = eng.submit([5, 6, 7], max_new_tokens=4, key="req-1")
            f2 = eng.submit([5, 6, 7], max_new_tokens=4, key="req-1")
            assert f1 is f2
            f1.result(timeout=60)
            assert eng.stats()["duplicate_submits"] == 1


class TestServeMetricsCB:
    def test_ttft_itl_and_gauges_flow_to_export(self, net):
        from mxnet_tpu.profiler import export

        with _engine(net, name="cb_metrics") as eng:
            futs = [eng.submit([1 + i, 2], max_new_tokens=4)
                    for i in range(6)]
            results = [f.result(timeout=60) for f in futs]
            assert all(r["ttft_ms"] > 0 for r in results)
            snap = eng.metrics.snapshot()
            assert snap["ttft_p99_ms"] > 0
            assert snap["itl_p99_ms"] > 0
            assert snap["itl_p50_ms"] <= snap["itl_p99_ms"]
            assert snap["slots_total"] == 4
            assert snap["kv_pages_used"] == 0  # all retired by now
            assert snap["kv_pages_free"] == eng.pool.pages_total
            # unified export surface: serve.<name>.* flattening
            flat = export.snapshot()
            assert flat["serve.cb_metrics.ttft_p99_ms"] == \
                snap["ttft_p99_ms"]
            assert flat["serve.cb_metrics.itl_p99_ms"] == \
                snap["itl_p99_ms"]
            assert "serve.cb_metrics.kv_pages_free" in flat
            assert "serve.cb_metrics.slot_occupancy" in flat

    def test_admit_wait_bounded_by_one_step_with_free_slots(self, net):
        """The headline scheduling property: while a long decode holds
        one slot, a short request entering a FREE slot waits at most one
        scheduler iteration for admission."""
        with _engine(net, num_slots=4, name="cb_wait") as eng:
            f_long = eng.submit([5] * 8, max_new_tokens=48)
            time.sleep(0.05)  # the long decode is mid-flight
            shorts = [eng.submit([6, 7], max_new_tokens=2)
                      for _ in range(3)]
            waits = [f.result(timeout=60)["admit_wait_steps"]
                     for f in shorts]
            assert all(w <= 1 for w in waits), waits
            assert not f_long.done()  # they finished UNDER the long one
            f_long.result(timeout=120)
            eng.assert_no_recompiles()
