"""Command A+ (``cohere2_moe``): a GQA decoder whose block runs attention
and a routed-expert feed-forward side by side under one LayerNorm, with
shared experts beside the routed ones and a tied head.

Every block, on the stream ``x``::

    y  = LayerNorm(x)              mean subtracted, a gain and no bias
    x' = x + Attn(y) + FFN(y)      (``use_parallel_block``)
    FFN(y) = sum over the token's k experts held here of w_e * E_e(y)
             + mean over the shared experts of S_j(y)

``layer_types`` names each layer's attention: ``"sliding_attention"``
sees the ``sliding_window`` newest keys (the query's own among them) and
turns q and k at ``rope_theta``; ``"full_attention"`` sees every key and
applies no rotation at all. The router scores each of the
``num_experts`` experts by the sigmoid of its own logit, the
``num_experts_per_tok`` best share a weight of 1 between them
(``ops.nn.routed_experts``), and no token is dropped. The head is the
embedding's own array (``tie_word_embeddings``), times ``logit_scale``.

``experts_held`` is :class:`~.mellum.RoutedFFN`'s: the range of routed
experts whose weights are here. The router keeps its width, a token's
weights are normalised over its k experts wherever they are held, and
the shared experts are what every share computes alike: over the shares
of one layer the routed parts add up, attention and the shared experts
count once.

Serving is :class:`~.mellum.MellumModel`'s: ``cache_spec()`` gives each
layer's K/V geometry and a window layer's window, the continuous
engine's in-place step serves the model, and what shares or replays
cache positions refuses it.
"""
from __future__ import annotations

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ops import nn as _ops
from .llama import LlamaAttention, _dense_on
from .mellum import FULL, WINDOW, RoutedFFN, windowed_cache_spec


class GainLayerNorm(HybridBlock):
    """``(x - mean) / sqrt(var + eps) * gamma``: no bias."""

    def __init__(self, units, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._eps = epsilon
        self.gamma = Parameter("gamma", shape=(units,), init="ones")

    def forward(self, x):
        return _ops.layer_norm(x, self.gamma.data(), eps=self._eps)


class CommandAPlusBlock(HybridBlock):
    def __init__(self, units, num_heads, num_kv_heads, head_dim, ffn,
                 norm_eps=1e-5, theta=None, window=None, **kwargs):
        super().__init__(**kwargs)
        self.norm = GainLayerNorm(units, norm_eps)
        self.attention = LlamaAttention(
            units, num_heads, num_kv_heads, theta=theta, head_dim=head_dim,
            window=window)
        self.ffn = RoutedFFN(units, **ffn)

    def forward(self, x, cache=None, start_pos=None):
        y = self.norm(x)
        return x + self.attention(y, cache=cache, start_pos=start_pos) \
            + self.ffn(y, cache=cache)


class CommandAPlusModel(HybridBlock):
    """Decoder-only LM; forward returns logits (B, T, vocab).

    ``layer_types`` has one entry a layer; a window layer turns at
    ``rope_theta``, a full layer not at all."""

    def __init__(self, vocab_size, units, num_heads, num_kv_heads, head_dim,
                 layer_types, sliding_window, rope_theta, expert_size,
                 num_experts, num_experts_per_tok, num_shared_experts,
                 norm_topk_prob=True, experts_held=None, norm_eps=1e-5,
                 logit_scale=1.0, **kwargs):
        super().__init__(**kwargs)
        ffn = dict(expert_size=expert_size, num_experts=num_experts,
                   top_k=num_experts_per_tok, experts_held=experts_held,
                   norm_topk_prob=norm_topk_prob, score="sigmoid",
                   num_shared=num_shared_experts)
        self._logit_scale = float(logit_scale)
        self.embed = nn.Embedding(vocab_size, units)
        self._blocks = []
        for i, kind in enumerate(layer_types):
            if kind not in (WINDOW, FULL):
                raise MXNetError(f"layer {i}: attention kind {kind!r}")
            bounded = kind == WINDOW
            blk = CommandAPlusBlock(
                units, num_heads, num_kv_heads, head_dim, ffn,
                norm_eps=norm_eps,
                theta=float(rope_theta) if bounded else None,
                window=int(sliding_window) if bounded else None)
            self._blocks.append(blk)
            self.register_child(blk, f"layer{i}")
        self.norm = GainLayerNorm(units, norm_eps)

    def cache_spec(self):
        return windowed_cache_spec(self._blocks)

    def forward(self, input_ids, cache=None, start_pos=None):
        x = self.embed(input_ids)
        if cache is None:
            for blk in self._blocks:
                x = blk(x)
            logits = _ops.fully_connected(
                self.norm(x), self.embed.weight.data(), no_bias=True,
                flatten=False)
        else:
            for i, blk in enumerate(self._blocks):
                x = blk(x, cache=cache.layer(i), start_pos=start_pos)
            # the tied head: the embedding's array, as a projection
            logits = _dense_on(cache)(self.norm(x), self.embed)
        return logits if self._logit_scale == 1.0 \
            else logits * self._logit_scale
