"""Mellum-2 through ``models/mellum.py:MellumModel`` on the chip,
inference only, and the names its parameters have in
``reference/mellum2.py``. Every size is the configuration's; the layers
held are the first ``num_hidden_layers`` of ``layer_types``."""


def rope_of(cfg):
    """``{kind: (theta, scaling)}`` as ``models.llama._rope_tables`` takes
    them, from the configuration's ``rope_parameters``."""
    out = {}
    for kind, p in cfg["rope_parameters"].items():
        if p["rope_type"] == "default":
            scaling = None
        elif p["rope_type"] == "yarn":
            scaling = ("yarn", p["factor"],
                       p["original_max_position_embeddings"],
                       p["beta_fast"], p["beta_slow"], p["attention_factor"])
        else:
            raise ValueError(f"rope_type {p['rope_type']!r}")
        out[kind] = (float(p["rope_theta"]), scaling)
    return out


def build(cfg, on_chip):
    import mxnet_tpu as mx
    from mxnet_tpu.models.mellum import MellumModel

    layers = cfg["num_hidden_layers"]
    if cfg["tie_word_embeddings"] or cfg["attention_bias"] \
            or cfg["hidden_act"] != "silu" or not cfg["use_sliding_window"] \
            or set(cfg["mlp_layer_types"][:layers]) != {"sparse"}:
        raise ValueError("models/mellum.py computes the published variant "
                         "alone: untied head, no bias, silu, a routed-expert "
                         "feed-forward in every layer")
    net = MellumModel(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_types=cfg["layer_types"][:layers],
        sliding_window=cfg["sliding_window"],
        expert_size=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        experts_held=cfg.get("experts_held"), rope=rope_of(cfg),
        norm_eps=cfg["rms_norm_eps"])
    # inference: no gradient buffer beside every weight
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.tpu() if on_chip else mx.cpu())
    return net


def name_map(cfg):
    """{the program's parameter name: the reference's leaf name}"""
    m = {"embed.weight": "embed", "norm.gamma": "norm",
         "lm_head.weight": "head"}
    for i in range(cfg["num_hidden_layers"]):
        kind = cfg["layer_types"][i]
        parts = {"attn_norm.gamma": "attn_norm",
                 "attention.q_proj.weight": kind + ".q",
                 "attention.k_proj.weight": kind + ".k",
                 "attention.v_proj.weight": kind + ".v",
                 "attention.o_proj.weight": kind + ".o",
                 "ffn_norm.gamma": "ffn_norm",
                 "ffn.router.weight": "router",
                 "ffn.gate_weight": "gate", "ffn.up_weight": "up",
                 "ffn.down_weight": "down"}
        for a, b in parts.items():
            m[f"layer{i}.{a}"] = f"layer{i}.{b}"
    return m
