"""The serving step's warm call (``ContinuousEngine._run_step`` ->
``InferenceSession.run`` -> ``CachedOpThreadSafe.__call__``): after
``warmup()`` every call of a step executable is a fast call (nothing derived
anew from the model) that makes no RNG key, and the tokens served are the
ones the tree before this change served (commit 755bfa7, the same lines, the
same seed): greedy rows, and sampled rows whose ``sample_tokens`` draws from
the stream the skipped keys still advance.

The models are the benchmark's two serving configurations at the tiny widths
of their ``rehearse`` groups, with the benchmark's seeded weights.
"""
import importlib.util
import json
import os

import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve
from mxnet_tpu.ops.pallas import decode_attention as da

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 5

# (prompt, max_new_tokens, temperature)
REQUESTS = [([5, 6, 7, 8, 9], 7, 0.0), ([11] * 19, 9, 0.9),
            ([3, 1, 4, 1, 5, 9, 2, 6], 6, 0.7), ([40, 41], 8, 0.0),
            ([17, 23, 29], 5, 1.3)]

# printed by commit 755bfa7
PARENT_TOKENS = {
    "mistral_7b_v01": [
        [168, 435, 232, 326, 158, 74, 12],
        [323, 335, 110, 106, 437, 242, 104, 420, 227],
        [209, 198, 99, 17, 18, 285],
        [165, 207, 207, 98, 207, 462, 319, 165],
        [110, 399, 158, 357, 425]],
    "falcon_h1_34b": [
        [201, 177, 479, 188, 347, 190, 1],
        [323, 335, 110, 106, 437, 242, 378, 420, 227],
        [209, 502, 99, 17, 18, 285],
        [344, 140, 31, 28, 170, 110, 312, 322],
        [110, 399, 158, 357, 425]],
}


def _harness():
    spec = importlib.util.spec_from_file_location(
        "chipbench_harness_for_warm_call",
        os.path.join(ROOT, "chipbench", "harness.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build(config):
    h = _harness()
    with open(os.path.join(ROOT, "chipbench", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    cfg = h.merged(cfg, cfg["rehearse"])
    ref = h.load_module("reference", cfg["reference"])
    adapter = h.load_module("adapters", cfg["adapter"])
    maker = h.load_module(".", "weights").Maker(
        ref.param_shapes(cfg), SEED, cfg["initializer_range"])
    net = adapter.build(cfg, False)
    h.load_weights(net, adapter.name_map(cfg), maker)
    return net


def serve_all(config, decode_path="pallas"):
    """Tokens of REQUESTS, the step executable's calls after warm-up, and
    the engine's counters before and after them."""
    da.use_interpret(True)
    net = build(config)
    mx.random.seed(11)
    eng = serve.ContinuousEngine(net, max_seq=64, num_slots=3, page_size=8,
                                 prefill_chunk=8, decode_path=decode_path,
                                 name=f"warm_{decode_path}_{config}")
    eng.warmup()
    before = eng.stats()
    calls = [0]
    run_step = eng._run_step

    def counted(*a, **k):
        calls[0] += 1
        return run_step(*a, **k)

    eng._run_step = counted
    futs = [eng.submit(p, max_new_tokens=n, temperature=t)
            for p, n, t in REQUESTS]
    for _ in range(400):
        if all(f.done() for f in futs):
            break
        eng.step()
    tokens = [f.result(0)["tokens"] for f in futs]
    eng.assert_no_recompiles()
    return tokens, calls[0], before, eng.stats()


@pytest.mark.parametrize("config", ["mistral_7b_v01", "falcon_h1_34b"])
def test_every_step_after_warmup_is_a_fast_call(config):
    tokens, calls, before, after = serve_all(config)
    assert calls >= 20
    # the warm-up's own two calls built the two signatures; of what came
    # after, every call was fast and made no key
    for k in ("fast_calls", "keys_skipped"):
        assert after[k] - before[k] == calls, (k, before[k], after[k])
        assert after[k] == after["cache"][k]
    assert after["cache"]["serve_hits"] - before["cache"]["serve_hits"] \
        == calls
    assert after["cache"]["signatures"] == 2
    assert tokens == PARENT_TOKENS[config]


@pytest.mark.parametrize("config", ["mistral_7b_v01", "falcon_h1_34b"])
def test_the_strict_rung_draws_its_sampled_rows(config):
    """The strict rung keeps no ids on the device, so every visit is
    fetched first, and a row with a temperature is drawn by the host's
    sampler there as on the fast rungs: the tokens commit 82d78c8 printed
    on the ``baseline`` rung, which are those above."""
    tokens, _, _, after = serve_all(config, decode_path="baseline")
    assert tokens == PARENT_TOKENS[config]
    pipe = after["pipeline"]
    assert pipe["visits_ahead"] == 0
    assert pipe["visits_drained"] == pipe["drained_by"]["strict"] > 0
