"""Conformance tests for the inference serving subsystem
(``mxnet_tpu/serve/``): KV-cache decode parity, dynamic batching,
admission control, zero-recompile steady state, fault isolation, and the
serve metrics surface.
"""
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu import numpy as mnp
from mxnet_tpu.models.llama import get_llama
from mxnet_tpu.resilience import faults
from mxnet_tpu.serve import (DynamicBatcher, Generator, InferenceSession,
                             KVCache, ServeError, ServeMetrics,
                             ServiceUnavailable, pick_bucket, sample_tokens)


def _tiny_llama(config="llama_tiny_test", **over):
    net = get_llama(config, **over)
    net.initialize()
    return net


@pytest.fixture
def no_faults():
    yield
    faults.clear_plan()


# ---------------------------------------------------------------------------
# KV-cache decode parity
# ---------------------------------------------------------------------------


class TestDecodeParity:
    def test_decode_matches_full_prefill_bitwise_12l(self):
        """THE acceptance invariant: >= 32 greedily generated tokens on
        the 12-layer llama config, each decode step's logits bitwise
        equal to re-running the full prefill (same cache path) over the
        whole prefix."""
        net = _tiny_llama("llama_serve_12l_test")
        max_seq = 64
        # the bitwise contract is the strict rung's; the fast rungs
        # (default decode_path) carry tolerance parity instead
        # (tests/test_decode_paths.py)
        gen = Generator(net, max_seq=max_seq, batch_buckets=(1,),
                        prompt_buckets=(max_seq,), decode_path="baseline")
        prompt = [3, 141, 59, 26, 5]
        n_new = 32

        tokens = list(prompt)
        lens = np.array([len(prompt)], np.int32)
        cache = KVCache.alloc(net, 1, max_seq)
        toks = np.zeros((1, max_seq), np.int32)
        toks[0, :len(prompt)] = prompt
        logits, cache = gen.prefill(toks, lens, cache)

        for step in range(n_new):
            nxt = int(np.argmax(logits.asnumpy()[0]))
            tokens.append(nxt)
            pos = np.array([len(tokens) - 1], np.int32)
            logits, cache = gen.decode_step(np.array([nxt], np.int32),
                                            pos, cache)
            # full prefill of the whole prefix, fresh cache, same bucket
            ref_cache = KVCache.alloc(net, 1, max_seq)
            ref_toks = np.zeros((1, max_seq), np.int32)
            ref_toks[0, :len(tokens)] = tokens
            ref_logits, _ = gen.prefill(
                ref_toks, np.array([len(tokens)], np.int32), ref_cache)
            a = logits.asnumpy()
            b = ref_logits.asnumpy()
            assert np.array_equal(a, b), (
                f"step {step}: decode logits diverge from full prefill "
                f"(max abs diff {np.abs(a - b).max()})")

    def test_cache_prefill_matches_standard_forward(self):
        """The cache path is numerically the same model as the training
        path: cache-prefill last-position logits ~= plain forward."""
        net = _tiny_llama()
        t = 6
        prompt = np.array([[7, 3, 250, 11, 99, 42]], np.int32)
        with autograd.predict_mode():
            ref = net(mnp.array(prompt)).asnumpy()[0, t - 1]
        gen = Generator(net, max_seq=16, batch_buckets=(1,),
                        prompt_buckets=(8,))
        cache = KVCache.alloc(net, 1, 16)
        toks = np.zeros((1, 8), np.int32)
        toks[0, :t] = prompt[0]
        logits, _ = gen.prefill(toks, np.array([t], np.int32), cache)
        np.testing.assert_allclose(logits.asnumpy()[0], ref,
                                   rtol=2e-4, atol=2e-4)

    def test_batched_mixed_length_decode_parity(self):
        """Rows with different prompt lengths share one decode executable;
        each row still bitwise-matches its own full prefill."""
        net = _tiny_llama()
        max_seq = 32
        gen = Generator(net, max_seq=max_seq, batch_buckets=(2,),
                        prompt_buckets=(max_seq,), decode_path="baseline")
        prompts = [[5, 6, 7], [9, 3, 4, 4, 8, 1, 2]]
        outs, _ = gen.generate(prompts, max_new_tokens=4, temperature=0.0)
        for i, p in enumerate(prompts):
            seq = list(p)
            for tok in outs[i]:
                ref_cache = KVCache.alloc(net, 2, max_seq)
                ref_toks = np.zeros((2, max_seq), np.int32)
                ref_toks[i, :len(seq)] = seq
                ref_toks[1 - i, 0] = 1
                lens = np.ones(2, np.int32)
                lens[i] = len(seq)
                ref_logits, _ = gen.prefill(ref_toks, lens, ref_cache)
                assert int(np.argmax(ref_logits.asnumpy()[i])) == tok
                seq.append(tok)

    def test_generate_greedy_deterministic(self):
        net = _tiny_llama()
        gen = Generator(net, max_seq=32, batch_buckets=(1,),
                        prompt_buckets=(8,))
        o1, _ = gen.generate([[5, 6, 7]], max_new_tokens=6)
        o2, _ = gen.generate([[5, 6, 7]], max_new_tokens=6)
        assert o1 == o2
        assert len(o1[0]) == 6

    def test_generate_skips_trailing_decode_step(self):
        """Sampling token k uses the logits from step k-1, so max_new
        tokens need only max_new - 1 decode steps — the final step's
        logits would be discarded."""
        net = _tiny_llama()
        gen = Generator(net, max_seq=32, batch_buckets=(1,),
                        prompt_buckets=(8,))
        outs, info = gen.generate([[4, 5]], max_new_tokens=4)
        assert len(outs[0]) == 4
        assert info["decode_steps"] == 3

    def test_kv_cache_nbytes_tracks_dtype(self):
        net = _tiny_llama()
        f32 = KVCache.alloc(net, 1, 16)
        bf16 = KVCache.alloc(net, 1, 16, dtype="bfloat16")
        assert bf16.nbytes() * 2 == f32.nbytes()

    def test_kv_cache_geometry(self):
        net = _tiny_llama()
        cache = KVCache.alloc(net, 2, 16)
        assert cache.num_layers == 2
        assert cache.batch == 2
        # kv_heads=2, head_dim=64/4=16
        assert cache.layer(0).k.shape == (2, 2, 16, 16)
        flat = cache.flat()
        assert len(flat) == 4
        rt = KVCache.from_flat(flat, 16)
        assert rt.max_seq == 16 and rt.num_layers == 2


# ---------------------------------------------------------------------------
# Sampling
# ---------------------------------------------------------------------------


class TestSampling:
    def test_greedy_is_argmax(self):
        logits = mnp.array(np.array([[0.1, 3.0, -1.0], [9.0, 0.0, 1.0]],
                                    np.float32))
        out = sample_tokens(logits, temperature=0.0)
        assert out.tolist() == [1, 0]

    def test_topk_restricts_support(self):
        mx.random.seed(3)
        logits = mnp.array(
            np.array([[5.0, 4.0, -50.0, -50.0]] * 8, np.float32))
        for _ in range(16):
            out = sample_tokens(logits, temperature=1.0, top_k=2)
            assert set(out.tolist()) <= {0, 1}

    def test_seeded_sampling_reproduces(self):
        logits = mnp.array(np.random.randn(4, 32).astype(np.float32))
        mx.random.seed(11)
        a = sample_tokens(logits, temperature=0.8)
        mx.random.seed(11)
        b = sample_tokens(logits, temperature=0.8)
        assert a.tolist() == b.tolist()


# ---------------------------------------------------------------------------
# Zero recompiles after warmup
# ---------------------------------------------------------------------------


class TestNoRecompiles:
    def test_mixed_traffic_zero_recompiles_after_warmup(self):
        """100 mixed-length requests after warmup: signature_count() is
        frozen and every call lands as a serve-path cache hit."""
        net = _tiny_llama()
        gen = Generator(net, max_seq=32, batch_buckets=(1, 2),
                        prompt_buckets=(8, 16))
        gen.warmup()
        sigs = gen.session.signature_count()
        hits0 = gen.session.cache_stats()["serve_hits"]
        rng = np.random.RandomState(0)
        for i in range(100):
            n_prompts = int(rng.randint(1, 3))
            prompts = [rng.randint(1, 255,
                                   size=int(rng.randint(1, 15))).tolist()
                       for _ in range(n_prompts)]
            gen.generate(prompts, max_new_tokens=2)
        gen.assert_no_recompiles()
        stats = gen.session.cache_stats()
        assert stats["signatures"] == sigs
        # every post-warmup execution was a warm serve hit
        assert stats["serve_hits"] > hits0
        assert stats["misses"] == sigs  # only warmup compiled

    def test_warmup_compiles_full_lattice(self):
        net = _tiny_llama()
        gen = Generator(net, max_seq=32, batch_buckets=(1, 2),
                        prompt_buckets=(8, 16))
        info = gen.warmup()
        # per batch bucket: one prefill per prompt bucket + one decode
        assert info["signatures"] == 2 * (2 + 1)

    def test_assert_no_recompiles_catches_cold_bucket(self):
        net = _tiny_llama()
        gen = Generator(net, max_seq=32, batch_buckets=(1, 2),
                        prompt_buckets=(8,))
        # warm only bucket (1, 8)
        gen.generate([[4, 5]], max_new_tokens=1)
        gen.session.freeze_signatures()
        gen.generate([[4, 5], [6]], max_new_tokens=1)  # cold batch=2
        with pytest.raises(Exception, match="recompiled after warmup"):
            gen.assert_no_recompiles()

    def test_bucket_keys_exposed(self):
        net = _tiny_llama()
        gen = Generator(net, max_seq=16, batch_buckets=(1,),
                        prompt_buckets=(8,))
        gen.generate([[4, 5]], max_new_tokens=2)  # prefill + one decode
        keys = gen.session._op.bucket_keys()
        assert len(keys) == gen.session.signature_count() == 2


# ---------------------------------------------------------------------------
# InferenceSession generic bucketing
# ---------------------------------------------------------------------------


def _make_classifier():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"))
    net.add(gluon.nn.Dense(4))
    net.initialize()
    return net


class TestInferenceSession:
    def test_pick_bucket(self):
        assert pick_bucket(1, (1, 2, 4)) == 1
        assert pick_bucket(3, (1, 2, 4)) == 4
        with pytest.raises(Exception, match="exceeds the largest"):
            pick_bucket(5, (1, 2, 4))

    def test_predict_pads_and_slices(self):
        net = _make_classifier()
        sess = InferenceSession(net, batch_buckets=(4,))
        x = np.random.randn(3, 8).astype(np.float32)
        out = sess.predict(x)
        assert out.shape == (3, 4)
        with autograd.predict_mode():
            ref = net(mnp.array(x)).asnumpy()
        np.testing.assert_array_equal(out.asnumpy(), ref)

    def test_predict_unpads_seq_axis(self):
        """A seq-bucketed predict must not hand back pad-position rows:
        outputs that preserve the padded seq extent are sliced to the
        real length."""
        net = _tiny_llama()
        sess = InferenceSession(net, batch_buckets=(2,), seq_buckets=(16,))
        x = np.random.randint(1, 255, size=(2, 10)).astype(np.int32)
        out = sess.predict(x)
        assert out.shape[:2] == (2, 10)
        # same executable, unsliced: predict must return its [:, :10]
        ref = sess.run(mnp.array(np.pad(x, [(0, 0), (0, 6)]))).asnumpy()
        assert ref.shape[:2] == (2, 16)
        np.testing.assert_array_equal(out.asnumpy(), ref[:, :10])

    def test_warmup_then_zero_recompiles(self):
        net = _make_classifier()
        sess = InferenceSession(net, batch_buckets=(1, 2, 4))
        sess.warmup(np.random.randn(1, 8).astype(np.float32))
        for b in (1, 2, 3, 4):
            sess.predict(np.random.randn(b, 8).astype(np.float32))
        sess.assert_no_recompiles()
        assert sess.cache_stats()["serve_hits"] >= 4

    def test_breaker_opens_and_fast_rejects(self, no_faults):
        net = _make_classifier()
        sess = InferenceSession(net, batch_buckets=(1,), name="brk")
        sess.warmup(np.random.randn(1, 8).astype(np.float32))
        faults.install_plan({"seed": 0, "rules": [
            {"site": "serve:execute", "kind": "fatal", "times": 3}]})
        x = np.random.randn(1, 8).astype(np.float32)
        for _ in range(3):
            with pytest.raises(Exception):
                sess.predict(x)
        assert sess.breaker.state == "open"
        with pytest.raises(ServiceUnavailable, match="circuit breaker"):
            sess.predict(x)
        faults.clear_plan()
        # cooldown: open denials advance the call count, then half-open
        for _ in range(16):
            try:
                sess.predict(x)
            except ServiceUnavailable:
                continue
            break
        assert sess.breaker.state == "closed"
        assert sess.predict(x).shape == (1, 4)


# ---------------------------------------------------------------------------
# DynamicBatcher
# ---------------------------------------------------------------------------


class TestDynamicBatcher:
    def test_flush_on_full(self):
        seen = []

        def runner(batch):
            seen.append(len(batch))
            return batch

        with DynamicBatcher(runner, max_batch_size=4, timeout_ms=10_000.0,
                            max_queue=64) as b:
            futs = [b.submit(i) for i in range(4)]
            assert [f.result(timeout=5) for f in futs] == [0, 1, 2, 3]
        assert seen == [4]  # one full batch, no deadline needed

    def test_flush_on_deadline(self):
        seen = []

        def runner(batch):
            seen.append(len(batch))
            return batch

        with DynamicBatcher(runner, max_batch_size=64, timeout_ms=30.0,
                            max_queue=64) as b:
            t0 = time.monotonic()
            f = b.submit("only")
            assert f.result(timeout=5) == "only"
            waited = time.monotonic() - t0
        assert seen == [1]
        assert waited >= 0.02  # the deadline, not an immediate flush

    def test_fast_reject_when_queue_full(self):
        release = threading.Event()

        def runner(batch):
            release.wait(5)
            return batch

        b = DynamicBatcher(runner, max_batch_size=1, timeout_ms=0.0,
                           max_queue=2, name="rej")
        try:
            futs = [b.submit(0)]
            deadline = time.monotonic() + 5
            while b.queue_depth() > 0:  # wait until 0 is in flight
                assert time.monotonic() < deadline
                time.sleep(0.005)
            futs += [b.submit(i) for i in (1, 2)]  # fills the queue
            with pytest.raises(ServiceUnavailable, match="queue is full"):
                b.submit(99)
            assert b.metrics.rejects == 1
            release.set()
            for f in futs:
                f.result(timeout=5)
        finally:
            release.set()
            b.close()

    def test_runner_error_is_per_request_not_fatal(self):
        calls = {"n": 0}

        def runner(batch):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("boom")
            return batch

        with DynamicBatcher(runner, max_batch_size=2, timeout_ms=5.0,
                            max_queue=8) as b:
            f1 = b.submit("a")
            with pytest.raises(RuntimeError, match="boom"):
                f1.result(timeout=5)
            # the server survived: next request succeeds
            f2 = b.submit("b")
            assert f2.result(timeout=5) == "b"
        assert b.metrics.errors >= 1

    def test_injected_dispatch_fault_is_per_request_error(self, no_faults):
        """An op:dispatch fault inside the runner surfaces on the affected
        request's future; the flusher keeps serving."""
        faults.install_plan({"seed": 0, "rules": [
            {"site": "op:dispatch", "kind": "transient", "at": [0]}]})

        def runner(batch):
            x = mnp.array(np.asarray(batch, np.float32))
            return (x * 2).asnumpy().tolist()

        with DynamicBatcher(runner, max_batch_size=4, timeout_ms=5.0,
                            max_queue=8) as b:
            f1 = b.submit(1.0)
            with pytest.raises(Exception, match="injected"):
                f1.result(timeout=5)
            faults.clear_plan()
            f2 = b.submit(2.0)
            assert f2.result(timeout=5) == 4.0

    def test_zero_max_queue_rejects_every_submit(self):
        """max_queue=0 is a real reject-all configuration, not a falsy
        value silently replaced by the config default."""
        with DynamicBatcher(lambda b: b, max_batch_size=2, timeout_ms=5.0,
                            max_queue=0) as b:
            with pytest.raises(ServiceUnavailable, match="queue is full"):
                b.submit("x")

    def test_zero_max_batch_size_rejected_loudly(self):
        with pytest.raises(ServeError, match="max_batch_size"):
            DynamicBatcher(lambda b: b, max_batch_size=0, timeout_ms=5.0)

    def test_close_drains_and_rejects_late_submit(self):
        with DynamicBatcher(lambda b: b, max_batch_size=2,
                            timeout_ms=5.0) as b:
            f = b.submit("x")
            assert f.result(timeout=5) == "x"
        with pytest.raises(ServiceUnavailable, match="shut down"):
            b.submit("late")


# ---------------------------------------------------------------------------
# End-to-end: batcher over a session, concurrent clients
# ---------------------------------------------------------------------------


class TestServeEndToEnd:
    def test_concurrent_requests_through_batched_session(self):
        net = _make_classifier()
        sess = InferenceSession(net, batch_buckets=(1, 2, 4, 8),
                                name="e2e")
        sess.warmup(np.random.randn(1, 8).astype(np.float32))

        def runner(payloads):
            out = sess.predict(np.stack(payloads))
            arr = out.asnumpy()
            sess.metrics.observe_batch(len(payloads), 8)
            return [arr[i] for i in range(len(payloads))]

        with DynamicBatcher(runner, max_batch_size=8, timeout_ms=5.0,
                            max_queue=64, metrics=sess.metrics) as b:
            rng = np.random.RandomState(1)
            xs = [rng.randn(8).astype(np.float32) for _ in range(32)]
            futs = [b.submit(x) for x in xs]
            outs = [f.result(timeout=30) for f in futs]
        with autograd.predict_mode():
            ref = net(mnp.array(np.stack(xs))).asnumpy()
        np.testing.assert_allclose(np.stack(outs), ref, rtol=1e-5,
                                   atol=1e-6)
        sess.assert_no_recompiles()
        snap = sess.metrics.snapshot()
        assert snap["requests"] == 32
        assert snap["errors"] == 0
        assert snap["p99_ms"] >= snap["p50_ms"] >= 0
        assert 0 < snap["batch_occupancy"] <= 1.0


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------


class TestServeMetrics:
    def test_percentiles(self):
        m = ServeMetrics("t", window=128)
        for v in range(1, 101):
            m.observe_request(queue_ms=0.0, exec_ms=float(v))
        p = m.latency_percentiles()
        assert p["p50_ms"] == 50.0
        assert p["p95_ms"] == 95.0
        assert p["p99_ms"] == 99.0

    def test_percentile_nearest_rank_table(self):
        """Table-driven pin of ceil-based nearest-rank percentiles.
        ``int(round(...))`` banker's rounding put even-window ranks off
        by one (p50 of [1, 2] came out 2); the definition is rank
        ``ceil(pct/100 * n)``, 1-based."""
        from mxnet_tpu.serve import percentile

        cases = [
            # (samples, pct, expected)
            ([1, 2], 50, 1),          # THE regression: round() gave 2
            ([1, 2], 51, 2),
            ([1, 2], 100, 2),
            ([1, 2, 3, 4], 25, 1),    # round(1.0)=1 was right by luck
            ([1, 2, 3, 4], 50, 2),    # round(2.0)=2 ok; ceil agrees
            ([1, 2, 3, 4], 75, 3),
            ([1, 2, 3, 4], 76, 4),
            ([15, 20, 35, 40, 50], 30, 20),  # classic nearest-rank table
            ([15, 20, 35, 40, 50], 40, 20),
            ([15, 20, 35, 40, 50], 50, 35),
            ([15, 20, 35, 40, 50], 100, 50),
            ([7], 1, 7),
            ([7], 99, 7),
            ([3, 1, 2], 50, 2),       # unsorted input
            ([], 99, 0.0),            # empty window -> dashboard zero
        ]
        for samples, pct, want in cases:
            got = percentile(samples, pct)
            assert got == want, (samples, pct, got, want)

    def test_snapshot_counts(self):
        m = ServeMetrics("t", window=8)
        m.observe_request(1.0, 2.0, ok=True)
        m.observe_request(1.0, 2.0, ok=False)
        m.observe_batch(3, 4)
        m.observe_reject()
        m.observe_tokens(30, 1.5)
        m.set_queue_depth(5)
        s = m.snapshot()
        assert s["requests"] == 2 and s["errors"] == 1
        assert s["rejects"] == 1 and s["batches"] == 1
        assert s["mean_batch_size"] == 3 and s["batch_occupancy"] == 0.75
        assert s["tokens"] == 30 and abs(s["tokens_s"] - 20.0) < 1e-9
        assert s["queue_depth"] == 5

    def test_serve_events_on_profiler_bus(self):
        from mxnet_tpu import profiler
        from mxnet_tpu.profiler import core as _prof_core

        net = _make_classifier()
        sess = InferenceSession(net, batch_buckets=(1,), name="prof")
        profiler.set_state("run")
        try:
            sess.predict(np.random.randn(1, 8).astype(np.float32))
            sess.metrics.observe_request(0.5, 1.0)
            sess.metrics.set_queue_depth(2)
            names = [e.get("name", "")
                     for e in _prof_core.snapshot_events()]
        finally:
            profiler.set_state("stop")
        assert any(n.startswith("serve::execute") for n in names)
        assert any(n.startswith("serve::request") for n in names)
        assert any(n.startswith("serve.queue_depth") for n in names)


# ---------------------------------------------------------------------------
# Timeout -> 503
# ---------------------------------------------------------------------------


class TestServeTimeout:
    def test_hung_execution_becomes_503(self, no_faults, monkeypatch):
        net = _make_classifier()
        sess = InferenceSession(net, batch_buckets=(1,), name="hang")
        x = np.random.randn(1, 8).astype(np.float32)
        sess.warmup(x)
        monkeypatch.setenv("MXNET_SERVE_TIMEOUT_MS", "50")
        faults.install_plan({"seed": 0, "rules": [
            {"site": "serve:execute", "kind": "delay", "seconds": 1.0,
             "times": 1}]})
        t0 = time.monotonic()
        with pytest.raises(ServiceUnavailable, match="MXNET_SERVE_TIMEOUT"):
            sess.predict(x)
        assert time.monotonic() - t0 < 0.9  # fast 503, not the full hang


# ---------------------------------------------------------------------------
# Device placement: serving computes where the model's parameters live
# ---------------------------------------------------------------------------


def _device_of(nd):
    (dev,) = nd._data.devices()
    return dev


class TestServePlacement:
    """The device is observed from the block (``Parameter.list_ctx``),
    not from the caller's thread-local default context — so a model on
    device 1 is served on device 1, worker threads included, with no
    ``with ctx:`` around the caller."""

    CTX = mx.cpu(1)

    def _net(self):
        net = get_llama("llama_tiny_test")
        net.initialize(ctx=self.CTX)
        return net

    @pytest.mark.parametrize("kw", [
        dict(decode_path="baseline"),
        dict(decode_path="pallas"),
        dict(decode_path="int8"),
        dict(decode_path="baseline", paged=True),
        dict(decode_path="pallas", multistep=True),
    ], ids=["baseline", "pallas", "int8", "paged", "multistep"])
    def test_generator_rings_and_logits_follow_the_block(self, kw):
        want = self.CTX.jax_device()
        gen = Generator(self._net(), max_seq=32, batch_buckets=(2,),
                        prompt_buckets=(8,), name="place_gen", **kw)
        assert gen.ctx == self.CTX
        cache = gen._fresh_cache(2)
        assert {_device_of(a) for a in cache.flat()} == {want}
        toks = np.zeros((2, 8), np.int32)
        logits, cache = gen.prefill(toks, np.array([3, 3], np.int32), cache)
        assert _device_of(logits) == want
        assert {_device_of(a) for a in cache.flat()} == {want}
        out, _ = gen.generate([[1, 2, 3], [4, 5]], max_new_tokens=4)
        assert [len(o) for o in out] == [4, 4]

    def test_session_and_batcher_thread_follow_the_block(self):
        want = self.CTX.jax_device()
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
        net.initialize(ctx=self.CTX)
        sess = InferenceSession(net, batch_buckets=(1, 4), name="place_cls")
        assert sess.ctx == self.CTX
        seen = []

        def runner(payloads):
            out = sess.predict(np.stack(payloads))
            seen.append(_device_of(out))
            return list(out.asnumpy())

        with DynamicBatcher(runner, max_batch_size=4, timeout_ms=5,
                            name="place_b") as b:
            b.submit(np.ones(16, np.float32)).result(10)
        assert seen == [want]

    def test_continuous_engine_thread_follows_the_block(self):
        from mxnet_tpu.serve import ContinuousEngine

        want = self.CTX.jax_device()
        net = self._net()
        ref, _ = Generator(net, max_seq=32, batch_buckets=(1,),
                           prompt_buckets=(8,), decode_path="baseline",
                           name="place_ref").generate([[1, 2, 3]],
                                                      max_new_tokens=4)
        with ContinuousEngine(net, max_seq=32, num_slots=2, page_size=8,
                              prefill_chunk=8, decode_path="baseline",
                              name="place_cb") as eng:
            assert eng.ctx == self.CTX
            got = eng.submit([1, 2, 3], max_new_tokens=4).result(30)
            assert {_device_of(a) for a in eng.pool.flat()} == {want}
        assert got["tokens"] == ref[0]
