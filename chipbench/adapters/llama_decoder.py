"""A rotary-GQA + SwiGLU decoder through ``models/llama.py:LlamaModel``
on the chip, inference only, and the names its parameters have in
``reference/decoder.py``."""


def build(cfg, on_chip):
    import mxnet_tpu as mx
    from mxnet_tpu.models.llama import LlamaModel

    if cfg["rope_theta"] != 10000.0:
        raise ValueError("models/llama.py always rotates at theta 10,000")
    net = LlamaModel(vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
                     hidden_size=cfg["intermediate_size"],
                     num_heads=cfg["num_attention_heads"],
                     num_kv_heads=cfg["num_key_value_heads"],
                     num_layers=cfg["num_hidden_layers"],
                     norm_eps=cfg["rms_norm_eps"],
                     tie_embeddings=cfg["tie_word_embeddings"])
    # inference: no gradient buffer beside every weight
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.tpu() if on_chip else mx.cpu())
    return net


def name_map(cfg):
    """{the program's parameter name: the reference's leaf name}"""
    m = {"embed.weight": "embed", "norm.gamma": "norm",
         "lm_head.weight": "head"}
    parts = {"attn_norm.gamma": "attn_norm", "attention.q_proj.weight": "q",
             "attention.k_proj.weight": "k", "attention.v_proj.weight": "v",
             "attention.o_proj.weight": "o", "ffn_norm.gamma": "ffn_norm",
             "ffn.gate_proj.weight": "gate", "ffn.up_proj.weight": "up",
             "ffn.down_proj.weight": "down"}
    for i in range(cfg["num_hidden_layers"]):
        for a, b in parts.items():
            m[f"layer{i}.{a}"] = f"layer{i}.{b}"
    return m
