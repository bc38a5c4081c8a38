"""Operations and bytes a Mellum-2 block needs, from the configuration's
shapes alone (``flops.py``'s rules: nothing here comes from the compiler;
a matrix multiplication of (m, k) by (k, n) is 2*m*k*n operations).

A block is rotary GQA attention (a window layer or a full layer) and a
feed-forward of routed experts of which a token uses
``num_experts_per_tok``: the experts are counted at what the tokens use,
never at all that are held.
"""

WINDOW = "sliding_attention"


def kinds(cfg):
    """``(window layers, full layers)`` among the layers held."""
    held = cfg["layer_types"][:cfg["num_hidden_layers"]]
    n_window = sum(k == WINDOW for k in held)
    return n_window, len(held) - n_window


def attention_params(cfg):
    """One layer's four projections."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return 2 * h * q + 2 * h * kv


def expert_params(cfg):
    """One expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params_per_token(cfg):
    """Parameters of one block that a token is multiplied with: the
    attention's projections, the router at its full width, and
    ``num_experts_per_tok`` experts."""
    return attention_params(cfg) \
        + cfg["hidden_size"] * cfg["num_experts"] \
        + cfg["num_experts_per_tok"] * expert_params(cfg)


def serve_flops(cfg, positions, sampled, context_sum):
    """Forward pass over ``positions`` token positions, of which
    ``sampled`` need logits, with ``context_sum`` the sum over those
    positions of the keys before each. A full layer attends to all of
    them. A window layer attends to ``min(context, sliding_window)``,
    which the sum alone does not give: it is counted at ``context_sum *
    sliding_window / max_seq``, the least it can be for contexts that all
    lie under ``max_seq`` (``min(c, w) >= c * w / max_seq`` for ``c <=
    max_seq``), so the count errs low."""
    n_window, n_full = kinds(cfg)
    layers = n_window + n_full
    body = 2 * layers * positions * layer_params_per_token(cfg)
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    bound = min(1.0, cfg["sliding_window"] / cfg["serve"]["max_seq"])
    attention = 2 * 2 * q * context_sum * (n_full + n_window * bound)
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"] * sampled
    return body + attention + head


def attention_bytes(cfg, positions_full, positions_window, itemsize):
    """Bytes of K and V that decode steps must read: ``positions_full``
    and ``positions_window`` are the K/V positions read, summed over the
    lanes, the steps and the layers of each kind (the decode spans'
    ``kv_positions_*``)."""
    per_position = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize
    return per_position * (positions_full + positions_window)


def moe_expert_bytes(cfg, experts_hit, itemsize):
    """Bytes of expert weights that must be read: ``experts_hit`` counts,
    over the calls and the layers, the experts that got a token (each is
    read once a call whatever its load)."""
    return experts_hit * expert_params(cfg) * itemsize
