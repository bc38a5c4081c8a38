"""Operations and bytes the algorithms need, from shapes alone.

These counts are the yardstick's: no figure here comes from the compiler
(``ShardedTrainer.step_flops`` counts what was emitted, recompute and
all, and moves when a PR changes the program). A matrix multiplication
of (m, k) by (k, n) is 2*m*k*n operations; the backward pass of a
matmul costs twice its forward pass.
"""


def bert_matmul_params(cfg):
    """Parameters that sit in matrix multiplications applied to every
    token: the encoder's projections and the MLM head with its tied
    decoder. (Embedding lookups, the pooler and NSP, applied once a
    sequence, are left out: under 0.1% at 128 tokens.)"""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    layer = 4 * h * h + 2 * h * f
    head = h * h + h * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * layer + head


def bert_forward_flops_per_token(cfg, seq):
    """Forward pass, one token of a sequence of ``seq``: the matmuls
    above and the two attention products (scores and weighted values)."""
    attention = cfg["num_hidden_layers"] * 2 * 2 * seq * cfg["hidden_size"]
    return 2 * bert_matmul_params(cfg) + attention


def bert_train_flops_per_token(cfg, seq):
    """Forward and backward, no recompute: three forward passes' worth."""
    return 3 * bert_forward_flops_per_token(cfg, seq)


def decoder_layer_params(cfg):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    head_dim = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * head_dim
    return 2 * h * h + 2 * h * kv + 3 * h * f


def decoder_flops(cfg, positions, sampled, context_sum):
    """Forward pass of a rotary-GQA + SwiGLU decoder over ``positions``
    token positions, of which ``sampled`` need logits (the last prompt
    position and every decoded token), with ``context_sum`` the sum
    over those positions of the keys each attends to."""
    h = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    body = 2 * layers * decoder_layer_params(cfg) * positions
    attention = layers * 2 * 2 * h * context_sum
    head = 2 * h * cfg["vocab_size"] * sampled
    return body + attention + head


def decode_attention_bytes(cfg, context_sum, itemsize):
    """Bytes of K and V that decode steps must read: for every decoded
    token, every live position of its sequence, in every layer."""
    head_dim = cfg["hidden_size"] // cfg["num_attention_heads"]
    per_position = 2 * cfg["num_key_value_heads"] * head_dim * itemsize
    return cfg["num_hidden_layers"] * per_position * context_sum


def decode_attention_flops(cfg, context_sum):
    """Scores and weighted values for every decoded token."""
    return cfg["num_hidden_layers"] * 2 * 2 * cfg["hidden_size"] * context_sum
