"""GigaChat 3.5 through ``models/gigachat35.py:GigaChat35Model`` on the
chip, inference only, and the names its parameters have in
``reference/gigachat35.py``. Every size is the configuration's; the
routed experts held are the first ``n_routed_experts`` of
``router_experts`` (``experts_held`` names another range), the dense
layers those of ``source_layers`` under ``first_k_dense_replace``."""


def _dense_layers(cfg):
    import reference.gigachat35 as ref

    return ref.dense_layers(cfg)


def build(cfg, on_chip):
    import mxnet_tpu as mx
    from mxnet_tpu.models.gigachat35 import GigaChat35Model

    rs = cfg["rope_scaling"]
    if cfg["tie_word_embeddings"] or cfg["attention_bias"] \
            or cfg["hidden_act"] != "silu" or not cfg["gated_attention"] \
            or cfg["norm_type"] != "ZeroCenteredGatedNorm" \
            or cfg["layernorm_type"] != "pre_post" \
            or cfg["linear_attention_type"] != "GigaChat35GatedDeltaNet" \
            or cfg["linear_gating_type"] \
            != "gated_rmsnorm_sigmoid_zero_centered" \
            or cfg["use_shared_expert_sigmoid"] \
            or not cfg["use_mla_scaling_factor"] \
            or cfg["n_group"] != 1 or cfg["n_shared_experts"] != 1 \
            or cfg["num_nextn_predict_layers"] \
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"] \
            or rs["type"] != "yarn" or rs["mscale"] != rs["mscale_all_dim"]:
        raise ValueError(
            "models/gigachat35.py computes the published variant alone: an "
            "untied head, no bias, silu, gated latent attention with YaRN's "
            "factor squared in its scale, Gated DeltaNet with the zero-"
            "centred sigmoid-gated norm, zero-centred pre and post norms, "
            "sigmoid-routed experts with no group limit beside one shared "
            "expert, and no next-token modules")
    # the tables' own factor is mscale / mscale_all_dim's, 1
    yarn = ("yarn", rs["factor"], rs["original_max_position_embeddings"],
            rs["beta_fast"], rs["beta_slow"], 1.0)
    net = GigaChat35Model(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        full_attention=cfg["full_attention_layers"],
        first_dense=_dense_layers(cfg),
        num_heads=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        linear=dict(num_k_heads=cfg["linear_num_key_heads"],
                    num_v_heads=cfg["linear_num_value_heads"],
                    k_dim=cfg["linear_key_head_dim"],
                    v_dim=cfg["linear_value_head_dim"],
                    conv_kernel=cfg["linear_conv_kernel_dim"],
                    gate_scale=cfg["linear_sigmoid_gate_scale"],
                    norm_eps=cfg["linear_attn_o_norm_eps"]),
        hidden_size=cfg["intermediate_size"],
        moe=dict(expert_size=cfg["moe_intermediate_size"],
                 num_experts=cfg["router_experts"],
                 top_k=cfg["num_experts_per_tok"],
                 experts_held=cfg.get("experts_held")
                 or (0, cfg["n_routed_experts"]),
                 norm_topk_prob=cfg["norm_topk_prob"], score="sigmoid",
                 num_shared=cfg["n_shared_experts"],
                 routed_scale=cfg["routed_scaling_factor"], score_bias=True),
        swiglu_limit=cfg["swiglu_limit"], rope_theta=cfg["rope_theta"],
        rope_scaling=yarn, mscale_all_dim=rs["mscale_all_dim"],
        norm_eps=cfg["rms_norm_eps"])
    # inference: no gradient buffer beside every weight
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.tpu() if on_chip else mx.cpu())
    return net


def name_map(cfg):
    """{the program's parameter name: the reference's leaf name}"""
    m = {"embed.weight": "embed", "norm.gamma": "norm",
         "lm_head.weight": "head"}
    norms = {f"{n}.gamma": n for n in ("input_norm", "post_mixer_norm",
                                       "ffn_norm", "post_ffn_norm")}
    latent = {"attention.q_a_proj.weight": "q_a",
              "attention.q_norm.gamma": "q_norm",
              "attention.q_b_proj.weight": "q_b",
              "attention.kv_a_proj.weight": "kv_a",
              "attention.kv_norm.gamma": "kv_norm",
              "attention.kv_b_proj.weight": "kv_b",
              "attention.gate_proj.weight": "attn_gate",
              "attention.o_proj.weight": "o"}
    linear = {"mixer.in_proj_qkvz.weight": "qkvz",
              "mixer.in_proj_ba.weight": "ba",
              "mixer.conv_weight": "conv", "mixer.a_log": "a_log",
              "mixer.dt_bias": "dt_bias", "mixer.norm_gamma": "mixer_norm",
              "mixer.out_proj.weight": "out"}
    dense = {"ffn.gate_proj.weight": "dense.gate",
             "ffn.up_proj.weight": "dense.up",
             "ffn.down_proj.weight": "dense.down"}
    routed = {"ffn.router.weight": "router",
              "ffn.e_score_correction_bias": "score_bias",
              "ffn.gate_weight": "gate", "ffn.up_weight": "up",
              "ffn.down_weight": "down",
              "ffn.shared_gate_weight": "shared_gate",
              "ffn.shared_up_weight": "shared_up",
              "ffn.shared_down_weight": "shared_down"}
    for i in range(cfg["num_hidden_layers"]):
        mix = latent if i in cfg["full_attention_layers"] else linear
        ffn = dense if i < _dense_layers(cfg) else routed
        for a, b in {**norms, **mix, **ffn}.items():
            m[f"layer{i}.{a}"] = f"layer{i}.{b}"
    return m
