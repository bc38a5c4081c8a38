"""``flops.py`` against counts made by hand."""
import json
import os

import flops
from conftest import BENCH


def cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_bert_base_step_matches_the_compilers_count_within_2_percent():
    c = cfg("bert_base")
    # 12 x (4 x 768^2 + 2 x 768 x 3072) + 768^2 + 768 x 30522
    assert flops.bert_matmul_params(c) == 12 * 7077888 + 589824 + 23440896
    step = flops.bert_train_flops_per_token(c, 128) * 64 * 128
    # XLA's own count of the 64 x 128 step compiled for a v5e: 5.53 TFLOP
    assert abs(step / 5.53e12 - 1) < 0.02
    # and of the 128 x 128 step this benchmark times: 11.05 TFLOP
    # (ShardedTrainer.step_flops on the chip, PR 26)
    assert abs(step * 2 / 11.049e12 - 1) < 0.02


def test_mistral_layer_and_token():
    c = cfg("mistral_7b_v01")
    # q, o: 4096^2 each; k, v: 4096 x 1024 each; gate, up, down: 4096 x 14336
    assert flops.decoder_layer_params(c) == 218103808
    L = c["num_hidden_layers"]
    one = flops.decoder_flops(c, positions=1, sampled=1, context_sum=100)
    by_hand = 2 * L * 218103808 + L * 4 * 4096 * 100 + 2 * 4096 * 32000
    assert one == by_hand
    # K and V of 100 live positions, L layers, 8 heads of 128, float32
    assert flops.decode_attention_bytes(c, 100, 4) == L * 2 * 8 * 128 * 4 * 100
