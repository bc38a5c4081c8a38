"""A.X-K1 through ``models/ax_k1.py:AxK1Model`` on the chip, inference
only, and the names its parameters have in ``reference/ax_k1.py``. Every
size is the configuration's; the routed experts held are the first
``n_routed_experts`` of ``router_experts`` (``experts_held`` names another
range)."""


def build(cfg, on_chip):
    import mxnet_tpu as mx
    from mxnet_tpu.models.ax_k1 import AxK1Model

    rs = cfg["rope_scaling"]
    if cfg["tie_word_embeddings"] or cfg["attention_bias"] \
            or cfg["hidden_act"] != "silu" \
            or cfg["scoring_func"] != "sigmoid" \
            or cfg["topk_method"] != "none" or cfg["moe_layer_freq"] != 1 \
            or cfg["n_shared_experts"] != 1 \
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"] \
            or rs["type"] != "yarn" or rs["mscale"] != rs["mscale_all_dim"]:
        raise ValueError(
            "models/ax_k1.py computes the published variant alone: an "
            "untied head, no bias, silu, sigmoid scores with no correction "
            "bias, every layer past the dense ones routed beside one shared "
            "expert, latent attention "
            "on as many key heads as query heads, YaRN tables that carry no "
            "factor of their own")
    # the tables' own factor is mscale / mscale_all_dim's, 1
    yarn = ("yarn", rs["factor"], rs["original_max_position_embeddings"],
            rs["beta_fast"], rs["beta_slow"], 1.0)
    net = AxK1Model(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        hidden_size=cfg["intermediate_size"],
        expert_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["n_shared_experts"],
        first_dense=cfg["first_k_dense_replace"],
        groups=(cfg["n_group"], cfg["topk_group"]),
        routed_scale=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        experts_held=cfg.get("experts_held") or (0, cfg["n_routed_experts"]),
        rope_theta=cfg["rope_theta"], rope_scaling=yarn,
        mscale_all_dim=rs["mscale_all_dim"], norm_eps=cfg["rms_norm_eps"])
    # inference: no gradient buffer beside every weight
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.tpu() if on_chip else mx.cpu())
    return net


def name_map(cfg):
    m = {"embed.weight": "embed", "norm.gamma": "norm",
         "lm_head.weight": "head"}
    attention = {"attn_norm.gamma": "norm1", "ffn_norm.gamma": "norm2",
                 "attention.q_a_proj.weight": "q_a",
                 "attention.q_norm.gamma": "q_norm",
                 "attention.q_b_proj.weight": "q_b",
                 "attention.kv_a_proj.weight": "kv_a",
                 "attention.kv_norm.gamma": "kv_norm",
                 "attention.kv_b_proj.weight": "kv_b",
                 "attention.o_proj.weight": "o"}
    dense = {"ffn.gate_proj.weight": "dense.gate",
             "ffn.up_proj.weight": "dense.up",
             "ffn.down_proj.weight": "dense.down"}
    routed = {"ffn.router.weight": "router",
              "ffn.gate_weight": "gate", "ffn.up_weight": "up",
              "ffn.down_weight": "down",
              "ffn.shared_gate_weight": "shared_gate",
              "ffn.shared_up_weight": "shared_up",
              "ffn.shared_down_weight": "shared_down"}
    for i in range(cfg["num_hidden_layers"]):
        ffn = dense if i < cfg["first_k_dense_replace"] else routed
        for a, b in {**attention, **ffn}.items():
            m[f"layer{i}.{a}"] = f"layer{i}.{b}"
    return m
