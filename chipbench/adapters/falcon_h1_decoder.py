"""Falcon-H1 through ``models/falcon_h1.py:FalconH1Model`` on the chip,
inference only, and the names its parameters have in
``reference/falcon_h1.py``. Every size and multiplier is the
configuration's."""


def build(cfg, on_chip):
    import mxnet_tpu as mx
    from mxnet_tpu.models.falcon_h1 import FalconH1Model

    if cfg["tie_word_embeddings"] or cfg["mamba_norm_before_gate"] \
            or not cfg["mamba_rms_norm"] or not cfg["mamba_conv_bias"] \
            or cfg["mamba_proj_bias"] or cfg["attention_bias"] \
            or cfg["mlp_bias"]:
        raise ValueError("models/falcon_h1.py computes the published "
                         "variant alone: untied head, gated norm after the "
                         "gate, a conv bias and no projection bias")
    net = FalconH1Model(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        hidden_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mamba_d_ssm=cfg["mamba_d_ssm"], mamba_d_state=cfg["mamba_d_state"],
        mamba_n_heads=cfg["mamba_n_heads"], mamba_d_head=cfg["mamba_d_head"],
        mamba_n_groups=cfg["mamba_n_groups"],
        mamba_d_conv=cfg["mamba_d_conv"],
        mamba_chunk_size=cfg["mamba_chunk_size"],
        norm_eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
        embedding_multiplier=cfg["embedding_multiplier"],
        lm_head_multiplier=cfg["lm_head_multiplier"],
        key_multiplier=cfg["key_multiplier"],
        mlp_multipliers=tuple(cfg["mlp_multipliers"]),
        ssm_multipliers=tuple(cfg["ssm_multipliers"]),
        ssm_in_multiplier=cfg["ssm_in_multiplier"],
        ssm_out_multiplier=cfg["ssm_out_multiplier"],
        attention_in_multiplier=cfg["attention_in_multiplier"],
        attention_out_multiplier=cfg["attention_out_multiplier"])
    # inference: no gradient buffer beside every weight
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.tpu() if on_chip else mx.cpu())
    return net


def name_map(cfg):
    """{the program's parameter name: the reference's leaf name}"""
    m = {"embed.weight": "embed", "norm.gamma": "norm",
         "lm_head.weight": "head"}
    parts = {"input_norm.gamma": "input_norm",
             "mixer.in_proj.weight": "in_proj",
             "mixer.conv_weight": "conv_w", "mixer.conv_bias": "conv_b",
             "mixer.dt_bias": "dt_bias", "mixer.a_log": "a_log",
             "mixer.d": "d", "mixer.norm_gamma": "mixer_norm",
             "mixer.out_proj.weight": "out_proj",
             "attention.q_proj.weight": "q", "attention.k_proj.weight": "k",
             "attention.v_proj.weight": "v", "attention.o_proj.weight": "o",
             "ffn_norm.gamma": "ffn_norm", "ffn.gate_proj.weight": "gate",
             "ffn.up_proj.weight": "up", "ffn.down_proj.weight": "down"}
    for i in range(cfg["num_hidden_layers"]):
        for a, b in parts.items():
            m[f"layer{i}.{a}"] = f"layer{i}.{b}"
    return m
