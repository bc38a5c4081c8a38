"""Operations and bytes a Falcon-H1 block needs, from the configuration's
shapes alone (``flops.py``'s rules: nothing here comes from the compiler;
a matrix multiplication of (m, k) by (k, n) is 2*m*k*n operations).

A block is a Mamba-2 mixer beside GQA attention, then SwiGLU. The
attention's heads have the configuration's own ``head_dim`` (20 x 128 on
a 5120-wide stream), which ``flops.py``'s ``hidden_size // heads`` would
get wrong.
"""


def _mixer(cfg):
    heads, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], \
        cfg["mamba_d_state"]
    conv_dim = cfg["mamba_d_ssm"] + 2 * cfg["mamba_n_groups"] * n
    return heads, p, n, conv_dim


def layer_matmul_params(cfg):
    """Parameters of one block that sit in matrix multiplications applied
    to every token: the mixer's two projections, attention's four, the
    feed-forward's three."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    heads, _, _, conv_dim = _mixer(cfg)
    d_ssm = cfg["mamba_d_ssm"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    mixer = h * (d_ssm + conv_dim + heads) + d_ssm * h
    return mixer + 2 * h * q + 2 * h * kv + 3 * h * f


def scan_flops_per_position(cfg):
    """The recurrence of one block at one position: the state decayed,
    the outer product dt*x (x) B formed and added (3 operations an
    element of the (heads, head, state) matrix), the output S C (2 an
    element); the depthwise conv beside it."""
    heads, p, n, conv_dim = _mixer(cfg)
    return 5 * heads * p * n + 2 * cfg["mamba_d_conv"] * conv_dim


def serve_flops(cfg, positions, sampled, context_sum):
    """Forward pass over ``positions`` token positions, of which
    ``sampled`` need logits, with ``context_sum`` the sum over those
    positions of the keys each attends to."""
    layers = cfg["num_hidden_layers"]
    body = layers * positions * (2 * layer_matmul_params(cfg)
                                 + scan_flops_per_position(cfg))
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    attention = layers * 2 * 2 * q * context_sum
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"] * sampled
    return body + attention + head


def decode_attention_bytes(cfg, context_sum, itemsize):
    """Bytes of K and V that decode steps must read: for every decoded
    token, every live position of its sequence, in every layer."""
    per_position = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize
    return cfg["num_hidden_layers"] * per_position * context_sum


def decode_attention_flops(cfg, context_sum):
    """Scores and weighted values for every decoded token."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return cfg["num_hidden_layers"] * 2 * 2 * q * context_sum


def ssm_state_bytes(cfg, lane_steps, itemsize):
    """Bytes of recurrent state that ``lane_steps`` advances of one
    sequence's state must move: in every layer the scan's (heads, head,
    state) matrix and the conv's last ``d_conv - 1`` inputs, read once
    and written once. A decoded token is one advance; so is a prefill
    chunk, whatever its length (the chunked scan takes the state in and
    gives it back once a chunk)."""
    heads, p, n, conv_dim = _mixer(cfg)
    row = heads * p * n + (cfg["mamba_d_conv"] - 1) * conv_dim
    return cfg["num_hidden_layers"] * 2 * row * itemsize * lane_steps
