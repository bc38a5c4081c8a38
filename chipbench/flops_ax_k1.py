"""Operations and bytes an A.X-K1 block needs, from the configuration's
shapes alone (``flops.py``'s rules: nothing here comes from the compiler;
a matrix multiplication of (m, k) by (k, n) is 2*m*k*n operations).

A block is latent attention and a feed-forward: dense in the first
``first_k_dense_replace`` layers, else routed experts, of which a token
uses ``num_experts_per_tok`` of ``router_experts`` and this chip holds
``n_routed_experts``, beside ``n_shared_experts`` that every token takes.
The routed experts are counted at the assignments that fall on an expert
held here, never at all that are held.

The attention is counted in its **absorbed** form, the cheaper of the two
at every width this benchmark serves: a query position's 64 heads each
multiply a ``kv_lora_rank + qk_rope_head_dim`` wide query with a key
position's one cached array and weigh its first ``kv_lora_rank`` channels,
2 * 64 * (576 + 512) = 139,264 operations a pair against the pair's share
of 2,304 bytes; the two absorbed products (``q_nope W_uk``, ``o_lat
W_uv``) are ``W_kvb``'s own size a position and are counted with the
projections. Decompressing keys and values instead would cost 2 * 512 *
64 * 256 = 16.8 M operations a key position and 2 * 64 * (192 + 128) a
pair: cheaper only where more than about 170 queries share one read of
the keys (PERF.md section 6, PR 39).
"""


def attention_params(cfg):
    """One layer's projections: ``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb``
    (in the absorbed form too: its two halves are the absorbed products'
    weights) and ``W_o``."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    n, r, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return h * qr + qr * heads * (n + r) + h * (kvr + r) \
        + kvr * heads * (n + v) + heads * v * h


def expert_params(cfg):
    """One expert, routed or shared: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_ffn_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layers(cfg):
    """``(dense layers, routed layers)`` among the layers held."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def held_share(cfg):
    """The share of a token's assignments that fall on an expert held
    here if routing is even: experts held over experts routed."""
    return cfg["n_routed_experts"] / cfg["router_experts"]


def params_per_token(cfg, assignments_held=None):
    """Parameters of all the held layers that a token is multiplied
    with: every layer's attention projections, the dense layers'
    feed-forward, and a routed layer's router at its full width, its
    shared experts and its routed experts at ``assignments_held`` a token
    (None: ``num_experts_per_tok`` times :func:`held_share`)."""
    if assignments_held is None:
        assignments_held = cfg["num_experts_per_tok"] * held_share(cfg)
    dense, routed = layers(cfg)
    return (dense + routed) * attention_params(cfg) \
        + dense * dense_ffn_params(cfg) \
        + routed * (cfg["hidden_size"] * cfg["router_experts"]
                    + (cfg["n_shared_experts"] + assignments_held)
                    * expert_params(cfg))


def pair_flops(cfg):
    """One query position against one key position in one layer, all
    heads, absorbed: the score over the cached array's whole width and
    the weighted sum over its latent part."""
    wide = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return 2 * cfg["num_attention_heads"] * (wide + cfg["kv_lora_rank"])


def serve_flops(cfg, positions, sampled, context_sum, assignments_held=None):
    """Forward pass over ``positions`` token positions, of which
    ``sampled`` need logits, with ``context_sum`` the sum over those
    positions of the keys before each (every layer attends to all of
    them)."""
    body = 2 * positions * params_per_token(cfg, assignments_held)
    attention = cfg["num_hidden_layers"] * pair_flops(cfg) * context_sum
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"] * sampled
    return body + attention + head


def latent_attention_flops(cfg, pairs):
    """``pairs``: (query position, key position) pairs attended, summed
    over the layers (a decode visit's ``kv_positions_latent``, a chunk's
    ``kv_pairs_latent``)."""
    return pair_flops(cfg) * pairs


def latent_attention_bytes(cfg, positions, itemsize):
    """Bytes of latent pages that must be read: ``positions`` cached
    positions, summed over the layers (a decode visit reads every lane's
    every position; a chunk's queries share one read of its keys)."""
    return positions * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        * itemsize
