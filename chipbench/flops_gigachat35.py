"""Operations and bytes a GigaChat 3.5 block needs, from the
configuration's shapes alone (``flops.py``'s rules: nothing here comes
from the compiler; a matrix multiplication of (m, k) by (k, n) is 2*m*k*n
operations).

A block is Gated DeltaNet or gated latent attention, then a dense or a
routed feed-forward. Latent attention and the experts are counted as
``flops_ax_k1.py`` counts them, the latent layer's output gate with its
projections. The delta rule is counted as the recurrence needs it, a
position at a time: in each of ``Hv`` value heads, ``S^T k`` and ``S^T
q`` (2 K V operations each) and the update ``exp(g) S + k (x) w`` (3 K V):
7 K V a head a position. Its bytes are the state's, read and written once
an advance (a decode step advances a lane by one position; a chunk's call
takes the state in and gives it back once, whatever its length), and the
rows it reads and writes a position: q, k, v, g, beta and the output.
"""
import flops_ax_k1
from flops_ax_k1 import dense_ffn_params, expert_params, \
    latent_attention_bytes, latent_attention_flops, pair_flops

__all__ = ["dense_ffn_params", "expert_params", "latent_attention_bytes",
           "latent_attention_flops", "pair_flops"]


def _linear(cfg):
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return hk, hv, dk, dv, 2 * hk * dk + hv * dv


def attention_params(cfg):
    """One latent layer's projections, its output gate among them."""
    gate = cfg["hidden_size"] * cfg["num_attention_heads"] \
        * cfg["v_head_dim"]
    return flops_ax_k1.attention_params(cfg) + gate


def linear_params(cfg):
    """One linear layer's projections: ``W_qkvz``, ``W_ba``, ``W_out``."""
    h = cfg["hidden_size"]
    _, hv, _, dv, conv = _linear(cfg)
    return h * (conv + hv * dv) + h * 2 * hv + hv * dv * h


def layers(cfg):
    """``(linear, full, dense, routed)`` counts among the layers held."""
    full = len(cfg["full_attention_layers"])
    dense = sum(s < cfg["first_k_dense_replace"]
                for s in cfg["source_layers"])
    n = cfg["num_hidden_layers"]
    return n - full, full, dense, n - dense


def held_share(cfg):
    return cfg["n_routed_experts"] / cfg["router_experts"]


def params_per_token(cfg, assignments_held=None):
    """Parameters of all the held layers that a token is multiplied
    with: each layer's mixer projections, the dense layers' feed-forward,
    a routed layer's router at its full width, its shared expert and its
    routed experts at ``assignments_held`` a token (None: the even
    share)."""
    if assignments_held is None:
        assignments_held = cfg["num_experts_per_tok"] * held_share(cfg)
    linear, full, dense, routed = layers(cfg)
    return linear * linear_params(cfg) + full * attention_params(cfg) \
        + dense * dense_ffn_params(cfg) \
        + routed * (cfg["hidden_size"] * cfg["router_experts"]
                    + (cfg["n_shared_experts"] + assignments_held)
                    * expert_params(cfg))


def delta_rule_flops(cfg, positions):
    """The recurrence at ``positions`` positions, summed over the linear
    layers (a span's ``state_positions``): 7 K V a value head."""
    _, hv, dk, dv, _ = _linear(cfg)
    return 7 * hv * dk * dv * positions


def delta_rule_bytes(cfg, advances, positions, itemsize):
    """The state read and written once an advance, ``advances`` summed
    over the linear layers, and the rows of ``positions`` positions: q
    and k (Hk K each), v and the output (Hv V each), g and beta (Hv
    each)."""
    hk, hv, dk, dv, _ = _linear(cfg)
    state = hv * dk * dv
    row = 2 * hk * dk + 2 * hv * dv + 2 * hv
    return (2 * state * advances + row * positions) * itemsize


def serve_flops(cfg, positions, sampled, context_sum, assignments_held=None):
    """Forward pass over ``positions`` token positions, of which
    ``sampled`` need logits, with ``context_sum`` the sum over those
    positions of the keys before each (every latent layer attends to all
    of them); each linear layer's recurrence and conv at every
    position."""
    linear, full, _, _ = layers(cfg)
    conv = 2 * cfg["linear_conv_kernel_dim"] * _linear(cfg)[4]
    body = 2 * positions * params_per_token(cfg, assignments_held)
    rule = delta_rule_flops(cfg, linear * positions) \
        + linear * positions * conv
    attention = full * pair_flops(cfg) * context_sum
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"] * sampled
    return body + rule + attention + head
