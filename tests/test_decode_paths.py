"""Decode-rung conformance (PR 10): the fused Pallas decode-attention
kernel, the int8 KV-cache/weight rung, speculative decoding, and the
strict-parity pin.

Contract ladder:

* ``baseline`` keeps the PR-5 bitwise prefill/decode parity (tested in
  tests/test_serve.py); ``MXNET_SERVE_STRICT_PARITY=1`` pins every
  Generator to it regardless of arguments.
* ``pallas`` / ``int8`` carry tolerance-based per-token parity against
  the strict path over >= 32 teacher-forced tokens on the 12-layer
  serve config.
* Speculative greedy decoding is token-identical to non-speculative
  greedy for ANY draft model.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models.llama import get_llama
from mxnet_tpu.ops.pallas import decode_attention as da
from mxnet_tpu.profiler import core as _prof
from mxnet_tpu.serve import (Generator, KVCache, SpeculativeGenerator,
                             resolve_decode_path)


def _llama(config="llama_tiny_test", **over):
    net = get_llama(config, **over)
    net.initialize()
    return net


# ---------------------------------------------------------------------------
# Kernel-level: interpret-mode Pallas vs the XLA fallback
# ---------------------------------------------------------------------------


def _rand_decode(b=3, h=8, kv=2, s=40, d=24, quant=False, t=1, seed=0):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, h, t, d).astype(np.float32))
    if quant:
        k = jnp.asarray(rng.randint(-127, 128, size=(b, kv, s, d),
                                    dtype=np.int32).astype(np.int8))
        v = jnp.asarray(rng.randint(-127, 128, size=(b, kv, s, d),
                                    dtype=np.int32).astype(np.int8))
        ks = jnp.asarray(rng.uniform(1e-3, 2e-2,
                                     size=(b, kv, s)).astype(np.float32))
        vs = jnp.asarray(rng.uniform(1e-3, 2e-2,
                                     size=(b, kv, s)).astype(np.float32))
    else:
        k = jnp.asarray(rng.randn(b, kv, s, d).astype(np.float32))
        v = jnp.asarray(rng.randn(b, kv, s, d).astype(np.float32))
        ks = vs = None
    # mixed valid lengths, including the start_pos=0 edge
    sp = jnp.asarray(np.array([0, 7, s - 1][:b], np.int32))
    return q, k, v, sp, ks, vs


class TestDecodeKernel:
    @pytest.mark.parametrize("quant", [False, True])
    def test_interpret_kernel_matches_xla(self, quant):
        """The Pallas kernel (interpreter mode) and the einsum fallback
        are the same function, f32 and int8-dequant variants alike."""
        q, k, v, sp, ks, vs = _rand_decode(quant=quant)
        scale = 1.0 / np.sqrt(q.shape[-1])
        ref = da._xla_decode(q, k, v, sp, scale, ks, vs)
        da.use_interpret(True)
        try:
            out = da.decode_attention(q, k, v, sp, k_scale=ks, v_scale=vs)
            assert da.last_path() == "pallas"
        finally:
            da.use_interpret(False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6, rtol=2e-5)

    def test_verify_block_routes_xla_without_fallback_note(self):
        """T > 1 (the speculative verify block) is fallback-by-design:
        it must NOT count as a decode fallback."""
        q, k, v, sp, _, _ = _rand_decode(t=5)
        n0 = da.fallback_count()
        out = da.decode_attention(q, k, v, sp)
        assert da.last_path() == "xla"
        assert da.fallback_count() == n0
        assert out.shape == q.shape

    def test_decode_shaped_cpu_fallback_is_counted(self):
        """A T=1 call that misses the kernel (CPU, interpreter off) bumps
        both the module counter and the serve.decode_fallbacks gauge."""
        q, k, v, sp, _, _ = _rand_decode()
        n0 = da.fallback_count()
        c0 = _prof.get_counter("serve.decode_fallbacks")
        da.decode_attention(q, k, v, sp)
        assert da.last_path() == "xla"
        assert da.fallback_count() == n0 + 1
        assert _prof.get_counter("serve.decode_fallbacks") == c0 + 1

    def test_force_path_xla_overrides_and_records(self):
        q, k, v, sp, _, _ = _rand_decode()
        da.use_interpret(True)  # pallas would be eligible...
        da.force_path("xla")    # ...but the override wins
        n0 = da.fallback_count()
        try:
            da.decode_attention(q, k, v, sp)
            assert da.last_path() == "xla"
            assert da.fallback_count() == n0 + 1
        finally:
            da.force_path(None)
            da.use_interpret(False)

    def test_force_path_pallas_rejects_unsupported_shape(self):
        q, k, v, sp, _, _ = _rand_decode(t=5)  # T>1 never fits the kernel
        da.force_path("pallas")
        try:
            with pytest.raises(ValueError, match="unsupported decode"):
                da.decode_attention(q, k, v, sp)
        finally:
            da.force_path(None)


# ---------------------------------------------------------------------------
# Rung-level: tolerance parity vs the strict path (12L, >= 32 tokens)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serve12l():
    """The strict-rung reference trajectory on the 12-layer serve config:
    32 greedy tokens plus the per-step logits, teacher-forced against by
    every fast rung (and bitwise-pinned by the strict-mode test)."""
    mx.random.seed(0)
    net = _llama("llama_serve_12l_test")
    base = Generator(net, max_seq=64, batch_buckets=(1,),
                     prompt_buckets=(16,), name="rung_base",
                     decode_path="baseline")
    prompt = [3, 141, 59, 26, 5]
    toks = np.zeros((1, 16), np.int32)
    toks[0, :len(prompt)] = prompt
    lens = np.array([len(prompt)], np.int32)
    logits, cache = base.prefill(toks, lens, base._fresh_cache(1))
    seq, traj = list(prompt), []
    for _ in range(32):
        a = logits.asnumpy()[0].copy()
        traj.append(a)
        nxt = int(np.argmax(a))
        pos = np.array([len(seq)], np.int32)
        seq.append(nxt)
        logits, cache = base.decode_step(np.array([nxt], np.int32), pos,
                                         cache)
    return net, prompt, seq[len(prompt):], np.stack(traj)


def _dot_precisions(jaxpr):
    """The ``precision`` of every dot_general in ``jaxpr``, nested
    jaxprs (jit, custom_jvp, the Pallas kernel body) included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn.params["precision"])
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _dot_precisions(inner)
    return found


class TestRungParity:
    @pytest.mark.parametrize("path,tol,min_agree", [
        # measured: pallas ~1e-6 (same f32 math, different op order);
        # int8 ~1.4e-2 of a ~1.4-magnitude logit scale (quant noise)
        ("pallas", 1e-4, 32),
        ("int8", 0.15, 28),
    ])
    def test_fast_rung_tracks_strict_logits(self, serve12l, path, tol,
                                            min_agree):
        net, prompt, ref_tokens, ref_logits = serve12l
        fast = Generator(net, max_seq=64, batch_buckets=(1,),
                         prompt_buckets=(16,), name=f"rung_{path}",
                         decode_path=path)
        toks = np.zeros((1, 16), np.int32)
        toks[0, :len(prompt)] = prompt
        lens = np.array([len(prompt)], np.int32)
        logits, cache = fast.prefill(toks, lens, fast._fresh_cache(1))
        seq, diffs, agree = list(prompt), [], 0
        for step in range(32):
            b = logits.asnumpy()[0]
            diffs.append(float(np.abs(ref_logits[step] - b).max()))
            agree += int(np.argmax(b) == ref_tokens[step])
            pos = np.array([len(seq)], np.int32)
            seq.append(ref_tokens[step])  # teacher-force the strict chain
            logits, cache = fast.decode_step(
                np.array([ref_tokens[step]], np.int32), pos, cache)
        assert max(diffs) < tol, f"per-token logit drift {max(diffs)}"
        assert agree >= min_agree, f"argmax agreement {agree}/32"

    @pytest.mark.parametrize("t,routed", [(1, "pallas"), (16, "xla")],
                             ids=["decode-kernel", "prefill-einsum"])
    def test_float32_model_multiplies_in_float32(self, t, routed):
        """Every dot of a fast-rung step over float32 weights and rings —
        the seven projections and two attention dots a layer, and the
        head — asks for full precision. The TPU's default is one bfloat16
        pass, which left the rung 6.7% of logit scale from the strict one
        at Llama-3-8B widths; the host backend cannot show that, the
        traced request it can."""
        import jax
        import jax.numpy as jnp

        from mxnet_tpu.ndarray.ndarray import NDArray

        net = _llama()
        cache = KVCache.alloc(net, 2, 64)
        cache.path = "pallas"

        def step(toks, sp):
            return net(NDArray(toks), cache=cache,
                       start_pos=NDArray(sp))._data

        da.use_interpret(True)
        try:
            jaxpr = jax.make_jaxpr(step)(jnp.zeros((2, t), jnp.int32),
                                         jnp.zeros((2,), jnp.int32))
            assert da.last_path() == routed
        finally:
            da.use_interpret(False)
        dots = _dot_precisions(jaxpr.jaxpr)
        assert len(dots) == len(net._blocks) * 9 + 1
        assert set(dots) == {(jax.lax.Precision.HIGHEST,) * 2}

    @pytest.mark.parametrize("ring", ["bfloat16", "int8"])
    def test_narrower_storage_keeps_the_default_precision(self, ring):
        """Operands stored below float32 were rounded harder than a
        bfloat16 pass already: they do not pay for six."""
        import jax
        import jax.numpy as jnp

        from mxnet_tpu.ndarray.ndarray import NDArray
        from mxnet_tpu.ops import nn as ops

        q, k, v, sp, ks, vs = _rand_decode(quant=ring == "int8")
        if ring == "bfloat16":
            k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)

        scales = [] if ks is None else [ks, vs]

        def attend(impl):
            return lambda q, k, v, sp, *sc: impl(q, k, v, sp, 0.2,
                                                 *(sc or (None, None)))

        def dense(x, w):
            return ops.serving_dense(NDArray(x), NDArray(w))._data

        da.use_interpret(True)
        try:
            traced = [
                jax.make_jaxpr(attend(da._pallas_decode))(q, k, v, sp,
                                                          *scales),
                jax.make_jaxpr(attend(da._xla_decode))(q, k, v, sp, *scales),
                jax.make_jaxpr(dense)(q[0, 0], q[0, 0].astype(jnp.bfloat16)),
            ]
        finally:
            da.use_interpret(False)
        for jaxpr in traced:
            dots = _dot_precisions(jaxpr.jaxpr)
            assert dots and set(dots) == {None}, dots

    def test_strict_parity_env_pins_baseline_bitwise(self, serve12l,
                                                     monkeypatch):
        """MXNET_SERVE_STRICT_PARITY=1 overrides any decode_path argument
        and reproduces the PR-5 strict logits bitwise."""
        net, prompt, ref_tokens, ref_logits = serve12l
        monkeypatch.setenv("MXNET_SERVE_STRICT_PARITY", "1")
        assert resolve_decode_path("int8") == "baseline"
        pinned = Generator(net, max_seq=64, batch_buckets=(1,),
                           prompt_buckets=(16,), name="rung_pin",
                           decode_path="int8")
        assert pinned.decode_path == "baseline"
        toks = np.zeros((1, 16), np.int32)
        toks[0, :len(prompt)] = prompt
        lens = np.array([len(prompt)], np.int32)
        logits, _ = pinned.prefill(toks, lens, pinned._fresh_cache(1))
        assert np.array_equal(logits.asnumpy()[0], ref_logits[0])
        outs, _ = pinned.generate([prompt], max_new_tokens=32)
        assert outs[0] == ref_tokens

    def test_resolve_decode_path(self, monkeypatch):
        assert resolve_decode_path() == "pallas"          # auto
        assert resolve_decode_path("baseline") == "baseline"
        monkeypatch.setenv("MXNET_SERVE_DECODE_PATH", "int8")
        assert resolve_decode_path() == "int8"            # env default
        assert resolve_decode_path("pallas") == "pallas"  # arg wins
        with pytest.raises(MXNetError, match="decode_path"):
            resolve_decode_path("spec")


# ---------------------------------------------------------------------------
# Speculative decoding: greedy token identity for any draft
# ---------------------------------------------------------------------------


class TestSpeculative:
    @pytest.mark.parametrize("path", ["baseline", "pallas"])
    def test_greedy_token_identical_to_nonspeculative(self, path):
        """The acceptance invariant: an INDEPENDENTLY-initialized (i.e.
        bad) draft changes speed only — the emitted tokens equal
        non-speculative greedy decoding token for token."""
        mx.random.seed(0)
        net = _llama()
        mx.random.seed(99)
        draft = _llama(num_layers=1)  # random, unrelated to the target
        ref = Generator(net, max_seq=48, batch_buckets=(2,),
                        prompt_buckets=(8,), name=f"spec_ref_{path}",
                        decode_path=path)
        spec = SpeculativeGenerator(net, draft, k=3, max_seq=48,
                                    batch_buckets=(2,), prompt_buckets=(8,),
                                    name=f"spec_{path}", decode_path=path)
        spec.warmup()
        prompts = [[5, 9, 2], [7, 3, 3, 1]]
        o_ref, _ = ref.generate(prompts, max_new_tokens=12)
        o_spec, info = spec.generate(prompts, max_new_tokens=12)
        assert o_spec == o_ref
        spec.assert_no_recompiles()
        assert 0.0 <= info["acceptance_rate"] <= 1.0
        assert info["verify_steps"] == info["rounds"]

    def test_sampled_decoding_rejected(self):
        net = _llama()
        draft = _llama(num_layers=1)
        spec = SpeculativeGenerator(net, draft, k=2, max_seq=48,
                                    batch_buckets=(1,), prompt_buckets=(8,),
                                    name="spec_temp")
        with pytest.raises(MXNetError, match="greedy-only"):
            spec.generate([[4, 5]], max_new_tokens=4, temperature=0.8)

    def test_headroom_guard(self):
        net = _llama()
        draft = _llama(num_layers=1)
        spec = SpeculativeGenerator(net, draft, k=4, max_seq=16,
                                    batch_buckets=(1,), prompt_buckets=(8,),
                                    name="spec_head")
        # 5 + 8 + (4+1) > 16: the last round's verify block would write
        # past the ring
        with pytest.raises(MXNetError, match="headroom"):
            spec.generate([[1, 2, 3, 4, 5]], max_new_tokens=8)


# ---------------------------------------------------------------------------
# int8 footprint + gauges
# ---------------------------------------------------------------------------


class TestInt8AndGauges:
    def test_int8_cache_more_than_halves_ring_bytes(self):
        net = _llama()
        f32 = KVCache.alloc(net, 1, 16)
        q8 = KVCache.alloc(net, 1, 16, quant="int8")
        assert q8.quant == "int8"
        assert q8.nbytes() <= f32.nbytes() / 2

    def test_gauges_reach_export_snapshot(self):
        from mxnet_tpu.profiler import export

        net = _llama()
        gen = Generator(net, max_seq=32, batch_buckets=(1,),
                        prompt_buckets=(8,), name="gauge_int8",
                        decode_path="int8")
        gen.warmup()
        snap = gen.metrics.snapshot()
        assert snap["decode_path"] == "int8"
        assert snap["kv_cache_bytes"] > 0
        flat = export.snapshot()
        assert flat["serve.gauge_int8.decode_path"] == "int8"
        assert flat["serve.gauge_int8.kv_cache_bytes"] == \
            snap["kv_cache_bytes"]
