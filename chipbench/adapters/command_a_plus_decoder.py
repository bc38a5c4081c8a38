"""Command A+ through ``models/command_a_plus.py:CommandAPlusModel`` on the
chip, inference only, and the names its parameters have in
``reference/command_a_plus.py``. Every size is the configuration's; the
layers held are the first ``num_hidden_layers`` of ``layer_types``, the
routed experts held the first ``num_experts`` of ``router_experts``
(``experts_held`` names another range)."""


def build(cfg, on_chip):
    import mxnet_tpu as mx
    from mxnet_tpu.models.command_a_plus import CommandAPlusModel

    if not cfg["tie_word_embeddings"] or cfg["attention_bias"] \
            or cfg["hidden_act"] != "silu" or cfg["use_qk_norm"] \
            or not cfg["use_parallel_block"] or cfg["first_k_dense_replace"] \
            or cfg["expert_selection_fn"] != "sigmoid" \
            or cfg["shared_expert_combination_strategy"] != "average" \
            or cfg["position_embedding_type"] != "rope_gptj" \
            or cfg["rotary_pct"] != 1 \
            or cfg["rope_parameters"]["rope_type"] != "default":
        raise ValueError(
            "models/command_a_plus.py computes the published variant alone: "
            "a parallel block, a tied head, no bias, no q/k norm, silu, "
            "sigmoid selection, averaged shared experts, plain adjacent-"
            "pair rope on the window layers, no leading dense layer")
    layers = cfg["num_hidden_layers"]
    net = CommandAPlusModel(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_types=cfg["layer_types"][:layers],
        sliding_window=cfg["sliding_window"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        expert_size=cfg["intermediate_size"],
        num_experts=cfg["router_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"],
        norm_topk_prob=cfg["norm_topk_prob"],
        experts_held=cfg.get("experts_held") or (0, cfg["num_experts"]),
        norm_eps=cfg["layer_norm_eps"], logit_scale=cfg["logit_scale"])
    # inference: no gradient buffer beside every weight
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.tpu() if on_chip else mx.cpu())
    return net


class NameMap(dict):
    """``{the program's parameter name: the reference's leaf name}``, and
    beside it ``tied``: further leaves that are a program parameter's
    under a second name (``serve_closed`` asks the weights for a ``head``;
    this model's is its embedding)."""

    tied = ()

    def items(self):
        yield from super().items()
        yield from self.tied


def name_map(cfg):
    m = NameMap({"embed.weight": "embed", "norm.gamma": "norm"})
    m.tied = (("embed.weight", "head"),)
    for i in range(cfg["num_hidden_layers"]):
        kind = cfg["layer_types"][i]
        parts = {"norm.gamma": "norm",
                 "attention.q_proj.weight": kind + ".q",
                 "attention.k_proj.weight": kind + ".k",
                 "attention.v_proj.weight": kind + ".v",
                 "attention.o_proj.weight": kind + ".o",
                 "ffn.router.weight": "router",
                 "ffn.gate_weight": "gate", "ffn.up_weight": "up",
                 "ffn.down_weight": "down",
                 "ffn.shared_gate_weight": "shared_gate",
                 "ffn.shared_up_weight": "shared_up",
                 "ffn.shared_down_weight": "shared_down"}
        for a, b in parts.items():
            m[f"layer{i}.{a}"] = f"layer{i}.{b}"
    return m
