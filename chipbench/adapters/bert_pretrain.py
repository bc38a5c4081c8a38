"""BERT pretraining through the program's own entry points, as
``chip_smoke.py:bert_pretrain`` sets it up (copied, not imported, so that
the smoke may change and the yardstick not), and the names its parameters
have in ``reference/bert.py``."""


def build(cfg, on_chip):
    """The network (tokens -> (MLM scores, NSP scores)) and the loss."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu import np as mnp
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.models.bert import BERTForPretrain, BERTModel

    import numpy as np

    class PretrainStep(HybridBlock):
        def __init__(self, model):
            super().__init__()
            self.model = model

        def forward(self, tokens):
            valid_length = (tokens != 0).sum(axis=1)
            return self.model(tokens, valid_length=valid_length)

    if cfg["hidden_dropout_prob"] != cfg["attention_probs_dropout_prob"]:
        raise ValueError("models/bert.py has one dropout rate")
    net = PretrainStep(BERTForPretrain(BERTModel(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        hidden_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        max_length=cfg["max_position_embeddings"],
        token_types=cfg["type_vocab_size"],
        dropout=cfg["hidden_dropout_prob"],
        layer_norm_eps=cfg["train"]["layer_norm_eps"])))
    net.initialize(ctx=mx.tpu() if on_chip else mx.cpu())
    with autograd.predict_mode():
        net(mnp.array(np.ones((1, 8), "int32"),
                      ctx=mx.tpu() if on_chip else mx.cpu()))  # materializes shapes
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def loss_fn(outs, labels):
        return ce(outs[0], labels[0]).mean() + ce(outs[1], labels[1]).mean()

    return net, loss_fn


def name_map(cfg):
    """{the program's parameter name: the reference's leaf name}"""
    m = {"model.bert.word_embed.weight": "embed.word",
         "model.bert.token_type_embed.weight": "embed.type",
         "model.bert.pos_embed.weight": "embed.pos",
         "model.bert.embed_layer_norm.gamma": "embed.ln.g",
         "model.bert.embed_layer_norm.beta": "embed.ln.b",
         "model.bert.pooler.weight": "pooler.w",
         "model.bert.pooler.bias": "pooler.b",
         "model.mlm_dense.weight": "mlm.dense.w",
         "model.mlm_dense.bias": "mlm.dense.b",
         "model.mlm_norm.gamma": "mlm.ln.g",
         "model.mlm_norm.beta": "mlm.ln.b",
         "model.nsp.weight": "nsp.w", "model.nsp.bias": "nsp.b"}
    parts = {"attention.query_proj": "q", "attention.key_proj": "k",
             "attention.value_proj": "v", "attention.out_proj": "o",
             "ffn.ffn_1": "ffn1", "ffn.ffn_2": "ffn2"}
    norms = {"layer_norm_att": "ln1", "layer_norm_ffn": "ln2"}
    for i in range(cfg["num_hidden_layers"]):
        src, dst = f"model.bert.encoder.layer{i}.", f"layer{i}."
        for a, b in parts.items():
            m[src + a + ".weight"] = dst + b + ".w"
            m[src + a + ".bias"] = dst + b + ".b"
        for a, b in norms.items():
            m[src + a + ".gamma"] = dst + b + ".g"
            m[src + a + ".beta"] = dst + b + ".b"
    return m
