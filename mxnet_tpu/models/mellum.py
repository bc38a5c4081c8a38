"""Mellum-2: a rotary GQA decoder whose feed-forward is a layer of routed
experts, and whose attention layers are of two kinds.

Every block is :class:`~.llama.LlamaAttention` under an RMSNorm and a
routed-expert feed-forward under a second one::

    h = h + Attn(RMSNorm(h))
    h = h + sum over the token's k experts of  w_e * E_e(RMSNorm(h))
    E_e(y) = (silu(y G_e) * (y U_e)) D_e

``layer_types`` names each layer's attention: ``"sliding_attention"``
sees the ``sliding_window`` newest keys (the query's own among them) and
turns at the plain rope frequencies; ``"full_attention"`` sees every key
and turns at its own table (YaRN in the published model). The router
scores all ``num_experts`` experts, the ``num_experts_per_tok`` best share
the token's softmax mass renormalised to 1, and no token is ever dropped:
there is no capacity (``ops.nn.routed_experts``).

``experts_held`` (``(first, count)``; None: all) is the range of the
experts whose weights this model holds: expert parallelism, or one chip's
share of a deployment. The router keeps its full width and a block adds
the part of the sum that its own experts give; the parts of ranges that
cover all the experts add up to the whole layer.

Serving: ``cache_spec()`` gives each layer's K/V geometry and, for a
window layer, its window: ``serve.PagedKVPool`` keeps such a layer's K/V
in a ring of ``window / page + 1`` pages a sequence, beside the full
layers' pages. The continuous engine's in-place step serves the model;
what shares or replays cache positions (prefix cache, multi-step decode,
speculation) and the ring caches of ``serve.Generator`` refuse it (a page
of a bounded layer may already be written over). With a cache the block
also notes, for the step to hand back, how many experts its tokens hit.
"""
from __future__ import annotations

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ops import nn as _ops
from .llama import LayerCache, LlamaAttention, _dense_on

WINDOW, FULL = "sliding_attention", "full_attention"


def windowed_cache_spec(blocks):
    """``cache_spec()`` of a model whose blocks each hold a
    :class:`~.llama.LlamaAttention` as ``attention``: every layer keeps
    K/V rows; a window layer's are bounded."""
    return [LayerCache(*blk.attention.cache_geometry(), (),
                       blk.attention.window) for blk in blocks]


class RoutedFFN(HybridBlock):
    """Routed SwiGLU experts with no dropped token. ``gate_weight`` and
    ``up_weight`` are (held, units, expert_size), ``down_weight`` (held,
    expert_size, units); the router is as wide as all the experts and
    scores them by ``score`` (``"softmax"`` over all, or each expert's own
    ``"sigmoid"``).

    ``num_shared`` (0: none) adds shared experts of the same size, which
    every token takes and every share of the routed experts computes
    alike: their mean is added to the routed sum. They are stored stacked
    as the routed ones are, ``shared_*_weight`` (num_shared, ...).

    ``groups`` (``(n_group, topk_group)``) limits a token's experts to
    its best groups and ``routed_scale`` multiplies the routed sum
    (``ops.nn.route_top_k``, ``routed_experts``); None, the default,
    limits and multiplies nothing."""

    def __init__(self, units, expert_size, num_experts, top_k,
                 experts_held=None, norm_topk_prob=True, score="softmax",
                 num_shared=0, groups=None, routed_scale=None, **kwargs):
        super().__init__(**kwargs)
        first, count = (0, num_experts) if experts_held is None \
            else (int(experts_held[0]), int(experts_held[1]))
        if not 0 < top_k <= num_experts or first < 0 or count < 1 \
                or first + count > num_experts:
            raise MXNetError(
                f"experts: top {top_k} of {num_experts}, holding "
                f"{count} from {first}")
        self._top_k, self._held = int(top_k), (first, count)
        self._renorm, self._score = bool(norm_topk_prob), score
        self._groups = None if groups is None else \
            (int(groups[0]), int(groups[1]))
        self._scale = None if routed_scale is None else float(routed_scale)
        self.router = nn.Dense(num_experts, flatten=False, use_bias=False,
                               in_units=units)
        self.gate_weight = Parameter("gate_weight",
                                     shape=(count, units, expert_size))
        self.up_weight = Parameter("up_weight",
                                   shape=(count, units, expert_size))
        self.down_weight = Parameter("down_weight",
                                     shape=(count, expert_size, units))
        self._shared = int(num_shared)
        if self._shared:
            n = self._shared
            self.shared_gate_weight = Parameter(
                "shared_gate_weight", shape=(n, units, expert_size))
            self.shared_up_weight = Parameter(
                "shared_up_weight", shape=(n, units, expert_size))
            self.shared_down_weight = Parameter(
                "shared_down_weight", shape=(n, expert_size, units))

    def forward(self, x, cache=None):
        # the normal path multiplies every token with every held expert
        # (differentiable); a cached call takes the form its shapes ask
        # for (``ops.nn.expert_form``): a chunk walks tiles of its sorted
        # assignments, and so does a decode step whose few rows can pick
        # a minority of the held experts (one nobody picked is not read);
        # a decode step whose rows pick nearly all of them multiplies with
        # the whole stack at once (PERF.md, PR 33 and PR 36)
        live = None if cache is None else cache.token_live(x.shape[1])
        impl = "dense" if cache is None else _ops.expert_form(
            x.shape[0] * x.shape[1], x.shape[1], self._top_k,
            self.router.weight.shape[0])
        out, load = _ops.routed_experts(
            x, self.router.weight.data(), self.gate_weight.data(),
            self.up_weight.data(), self.down_weight.data(), self._top_k,
            held=self._held, token_live=live, renormalize=self._renorm,
            impl=impl, score=self._score, groups=self._groups,
            scale=self._scale)
        if cache is not None:
            cache.note_route(load)
        if self._shared:
            out = out + _ops.shared_experts(
                x, self.shared_gate_weight.data(),
                self.shared_up_weight.data(), self.shared_down_weight.data())
        return out


class MellumBlock(HybridBlock):
    def __init__(self, units, num_heads, num_kv_heads, head_dim, moe,
                 norm_eps=1e-6, theta=10000.0, window=None,
                 rope_scaling=None, **kwargs):
        super().__init__(**kwargs)
        self.attn_norm = nn.RMSNorm(epsilon=norm_eps, in_channels=units)
        self.attention = LlamaAttention(
            units, num_heads, num_kv_heads, theta=theta, head_dim=head_dim,
            window=window, rope_scaling=rope_scaling)
        self.ffn_norm = nn.RMSNorm(epsilon=norm_eps, in_channels=units)
        self.ffn = RoutedFFN(units, **moe)

    def forward(self, x, cache=None, start_pos=None):
        x = x + self.attention(self.attn_norm(x), cache=cache,
                               start_pos=start_pos)
        return x + self.ffn(self.ffn_norm(x), cache=cache)


class MellumModel(HybridBlock):
    """Decoder-only LM; forward returns logits (B, T, vocab).

    ``layer_types`` has one entry a layer; ``rope`` maps each kind to
    ``(theta, scaling)`` with ``scaling`` what
    :func:`~.llama._rope_tables` takes (None, or YaRN's parameters as a
    tuple)."""

    def __init__(self, vocab_size, units, num_heads, num_kv_heads, head_dim,
                 layer_types, sliding_window, expert_size, num_experts,
                 num_experts_per_tok, rope, norm_topk_prob=True,
                 experts_held=None, norm_eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        moe = dict(expert_size=expert_size, num_experts=num_experts,
                   top_k=num_experts_per_tok, experts_held=experts_held,
                   norm_topk_prob=norm_topk_prob)
        self.embed = nn.Embedding(vocab_size, units)
        self._blocks = []
        for i, kind in enumerate(layer_types):
            if kind not in (WINDOW, FULL):
                raise MXNetError(f"layer {i}: attention kind {kind!r}")
            theta, scaling = rope[kind]
            blk = MellumBlock(
                units, num_heads, num_kv_heads, head_dim, moe,
                norm_eps=norm_eps, theta=float(theta), rope_scaling=scaling,
                window=int(sliding_window) if kind == WINDOW else None)
            self._blocks.append(blk)
            self.register_child(blk, f"layer{i}")
        self.norm = nn.RMSNorm(epsilon=norm_eps, in_channels=units)
        self.lm_head = nn.Dense(vocab_size, flatten=False, use_bias=False,
                                in_units=units)

    def cache_spec(self):
        return windowed_cache_spec(self._blocks)

    def forward(self, input_ids, cache=None, start_pos=None):
        x = self.embed(input_ids)
        if cache is None:
            for blk in self._blocks:
                x = blk(x)
            return self.lm_head(self.norm(x))
        for i, blk in enumerate(self._blocks):
            x = blk(x, cache=cache.layer(i), start_pos=start_pos)
        return _dense_on(cache)(self.norm(x), self.lm_head)
