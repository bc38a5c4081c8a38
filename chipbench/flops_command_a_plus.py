"""Operations and bytes a Command A+ block needs, from the configuration's
shapes alone (``flops.py``'s rules: nothing here comes from the compiler;
a matrix multiplication of (m, k) by (k, n) is 2*m*k*n operations).

A block is GQA attention (a window layer or a full layer) beside a
feed-forward of routed experts, of which a token uses
``num_experts_per_tok`` of ``router_experts`` and this chip holds
``num_experts``, and ``num_shared_experts`` shared experts that every
token takes. The routed experts are counted at the assignments that fall
on an expert held here, never at all that are held.
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
    """One expert, routed or shared: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def held_share(cfg):
    """The share of a token's assignments that fall on an expert held
    here if routing is even: experts held over experts routed."""
    return cfg["num_experts"] / cfg["router_experts"]


def layer_params_per_token(cfg, assignments_held=None):
    """Parameters of one block that a token is multiplied with: the
    attention's projections, the router at its full width, the shared
    experts, and the routed experts at ``assignments_held`` a token (the
    run's own count where it has one, else ``num_experts_per_tok`` times
    :func:`held_share`)."""
    if assignments_held is None:
        assignments_held = cfg["num_experts_per_tok"] * held_share(cfg)
    return attention_params(cfg) \
        + cfg["hidden_size"] * cfg["router_experts"] \
        + (cfg["num_shared_experts"] + assignments_held) * expert_params(cfg)


def serve_flops(cfg, positions, sampled, context_sum, assignments_held=None):
    """Forward pass over ``positions`` token positions, of which
    ``sampled`` need logits, with ``context_sum`` the sum over those
    positions of the keys before each. A full layer attends to all of
    them; a window layer to ``min(context, sliding_window)``, counted at
    ``context_sum * sliding_window / max_seq`` as ``flops_mellum2`` counts
    it, which errs low. ``assignments_held``: a token's assignments that
    fell on an expert held here, a layer (None: even routing)."""
    n_window, n_full = kinds(cfg)
    layers = n_window + n_full
    body = 2 * layers * positions * layer_params_per_token(
        cfg, assignments_held)
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


def held_expert_bytes(cfg, experts_hit, itemsize):
    """Bytes of the held routed experts' weights that must be read:
    ``experts_hit`` counts, over the calls and the layers, the held
    experts that got a token (each is read once a call whatever its
    load)."""
    return experts_hit * expert_params(cfg) * itemsize


def shared_expert_bytes(cfg, layer_calls, itemsize):
    """Bytes of the shared experts' weights that must be read:
    ``layer_calls`` counts calls times layers (every call reads every
    shared expert of every layer once)."""
    return layer_calls * cfg["num_shared_experts"] * expert_params(cfg) \
        * itemsize
