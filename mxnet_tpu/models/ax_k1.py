"""A.X-K1 (``axk1``): a decoder whose attention is latent (MLA, as the
DeepSeek-V2/V3 papers give it), whose first layer's feed-forward is dense
and whose others are routed experts chosen inside the best groups, beside
one shared expert.

Every block, on the stream ``x`` (RMSNorm before each half)::

    h = x + Attn(norm1(x))
    y = h + FFN(norm2(h))

**Latent attention.** Queries and keys are projected in two stages with
an RMSNorm between, and the keys and values of all heads come from one
narrow latent a position::

    c_q = RMSNorm(x W_qa)                     (q_rank)
    [q_nope | q_pe] = c_q W_qb                a head: nope + rope
    [c_kv | k_pe]   = x W_kva                 kv_rank + rope, k_pe one head
    c_kv = RMSNorm(c_kv)
    [k_nope | v] = c_kv W_kvb                 a head: nope + v
    score = (q_nope . k_nope + rot(q_pe) . rot(k_pe)) * scale
    out = concat_h(softmax(score) v) W_o

``scale`` is ``(nope + rope) ** -0.5`` times the square of YaRN's
``mscale`` (:func:`yarn_mscale`); the rotation turns adjacent channel
pairs (:func:`~.llama.apply_rope`) at YaRN's frequencies.

The normal path computes exactly that. **The serving path computes the
absorbed form** and never makes ``k_nope`` or ``v``: with ``W_kvb`` split
a head into ``W_uk`` and ``W_uv`` (each (n, kv_rank)),

    q_lat = q_nope W_uk                       (kv_rank)
    score = [q_lat | rot(q_pe)] . [c_kv | rot(k_pe)]
    o     = (softmax(score) c_kv) W_uv^T

so that a position's cache is ``[c_kv | rot(k_pe)]``: ONE array of
``kv_rank + rope`` channels that every head reads as its key and whose
first ``kv_rank`` channels are also its value (stored at the next whole
number of lane tiles, zeros after: ``decode_attention.latent_width``, 576
-> 640; the queries are padded alike). ``cache_spec()`` says so
(``LayerCache.latent``); ``serve.PagedKVPool`` keeps one pool a layer, the
in-place step writes the row into its page (``kv.write``) and the paged
kernel or the chunk's loop reads the pages where they lie
(``ops.pallas.decode_attention``, the latent form). The two small
batched products a head stand under the op scope ``attn.absorb``.

**Feed-forward.** Layers before ``first_dense`` are :class:`~.llama.
LlamaFFN`; the others :class:`~.mellum.RoutedFFN` with sigmoid scores, a
group limit, renormalised weights times ``routed_scale`` and shared
experts; ``experts_held`` is RoutedFFN's (one chip's share).

Serving: the continuous engine's in-place float32 step alone
(``decode_path="pallas"``), with or without the prefix cache; ring
caches, the strict and int8 rungs, multi-step decode and speculation
refuse a latent layer by name (``serve.generate.require_kv_pairs``).
"""
from __future__ import annotations

import math

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ops import nn as _ops
from .llama import LayerCache, LlamaFFN, _dense_on, _rope_tables, apply_rope
from .mellum import RoutedFFN


def yarn_mscale(factor, mscale):
    """YaRN's attention factor, ``0.1 mscale ln(factor) + 1`` (1 for a
    factor of at most 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


class LatentAttention(HybridBlock):
    """Causal latent attention (the module's docstring). ``rope_scaling``
    is what :func:`~.llama._rope_tables` takes; ``scale_factor``
    multiplies the softmax scale ``(nope + rope) ** -0.5``."""

    def __init__(self, units, num_heads, q_rank, kv_rank, nope_dim, rope_dim,
                 v_dim, theta=10000.0, rope_scaling=None, scale_factor=1.0,
                 norm_eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._kv_rank = int(num_heads), int(kv_rank)
        self._nope, self._rope, self._v = int(nope_dim), int(rope_dim), \
            int(v_dim)
        self._theta = float(theta)
        self._rope_scaling = None if rope_scaling is None \
            else tuple(rope_scaling)
        self._scale = (self._nope + self._rope) ** -0.5 * float(scale_factor)
        from ..ops.pallas.decode_attention import latent_width

        # zeros stored after a position's last real channel
        self._pad = latent_width(self._kv_rank + self._rope) \
            - self._kv_rank - self._rope

        def dense(out, inp):
            return nn.Dense(out, flatten=False, use_bias=False, in_units=inp)

        h = self._heads
        self.q_a_proj = dense(q_rank, units)
        self.q_norm = nn.RMSNorm(epsilon=norm_eps, in_channels=q_rank)
        self.q_b_proj = dense(h * (self._nope + self._rope), q_rank)
        self.kv_a_proj = dense(self._kv_rank + self._rope, units)
        self.kv_norm = nn.RMSNorm(epsilon=norm_eps, in_channels=kv_rank)
        self.kv_b_proj = dense(h * (self._nope + self._v), kv_rank)
        self.o_proj = dense(units, h * self._v)

    def cache_geometry(self):
        """What a position of this layer's cache holds: one array of
        ``kv_rank + rope`` channels and the padding to a whole lane tile,
        the first ``kv_rank`` its values."""
        return LayerCache(1, self._kv_rank + self._rope + self._pad, (),
                          None, self._kv_rank)

    def _tables(self, t, start_pos, length):
        from .. import numpy as mnp

        cos_t, sin_t = _rope_tables(t, self._rope, self._theta,
                                    self._rope_scaling)
        cos, sin = mnp.array(cos_t), mnp.array(sin_t)
        if start_pos is not None:
            cos, sin = _ops.rope_positions(cos, sin, start_pos, length)
        return cos, sin

    def _project(self, x, dense, t_table, start_pos):
        """``(q_nope (B, H, T, nope), rot(q_pe) (B, H, T, rope), c_kv
        (B, T, kv_rank) normalised, rot(k_pe) (B, 1, T, rope))``."""
        b, t, _ = x.shape
        h, n, r = self._heads, self._nope, self._rope
        q = dense(self.q_norm(dense(x, self.q_a_proj)), self.q_b_proj)
        q = q.reshape(b, t, h, n + r).transpose(0, 2, 1, 3)
        kv = dense(x, self.kv_a_proj)
        c_kv = self.kv_norm(kv[:, :, :self._kv_rank])
        k_pe = kv[:, :, self._kv_rank:].reshape(b, 1, t, r)
        cos, sin = self._tables(t_table, start_pos, t)
        return q[..., :n], apply_rope(q[..., n:], cos, sin), c_kv, \
            apply_rope(k_pe, cos, sin)

    def forward(self, x, cache=None, start_pos=None):
        from .. import numpy as mnp

        b, t, _ = x.shape
        h, n, v = self._heads, self._nope, self._v
        if cache is not None:
            return self._forward_cached(x, cache, start_pos)
        q_nope, q_pe, c_kv, k_pe = self._project(
            x, lambda y, layer: layer(y), t, None)
        kvb = self.kv_b_proj(c_kv).reshape(b, t, h, n + v) \
            .transpose(0, 2, 1, 3)
        q = mnp.concatenate([q_nope, q_pe], axis=-1)
        k = mnp.concatenate(
            [kvb[..., :n], mnp.broadcast_to(k_pe, (b, h, t, self._rope))],
            axis=-1)
        # values padded to the keys' width: one head size for the kernel
        vals = mnp.pad(kvb[..., n:],
                       ((0, 0), (0, 0), (0, 0), (0, n + self._rope - v)))
        out = _ops.attention(q, k, vals, causal=True,
                             scale=self._scale)[..., :v]
        return self.o_proj(out.transpose(0, 2, 1, 3).reshape(b, t, h * v))

    def _forward_cached(self, x, cache, start_pos):
        """The absorbed form over the layer's latent pages."""
        from .. import numpy as mnp

        b, t, _ = x.shape
        n, v = self._nope, self._v
        path = getattr(cache, "path", "baseline")
        table = getattr(cache, "page_table", None)
        if start_pos is None or path == "baseline" or table is None \
                or getattr(cache, "quant", None) is not None:
            raise MXNetError(
                "latent attention is served from float32 latent pages "
                "alone (the continuous engine's in-place step, "
                "decode_path 'pallas')")
        q_nope, q_pe, c_kv, k_pe = self._project(
            x, _dense_on(cache), cache.max_seq, start_pos)
        # zeros after the last real channel, of the row and of the queries
        widen = ((0, 0), (0, 0), (0, 0), (0, self._pad))
        row = mnp.pad(mnp.concatenate(
            [c_kv.reshape(b, 1, t, self._kv_rank), k_pe], axis=-1), widen)
        pool = _ops.kv_cache_write(cache.k, row, start_pos, page_table=table)
        cache.update(pool, None)
        w_kvb = self.kv_b_proj.weight.data()
        q = mnp.pad(mnp.concatenate(
            [_ops.latent_absorb(q_nope, w_kvb, slice(0, n), True), q_pe],
            axis=-1), widen)
        o_lat = _ops.cached_attention(
            q, pool, None, start_pos, scale=self._scale, path=path,
            page_table=table, v_width=self._kv_rank)
        out = _ops.latent_absorb(o_lat, w_kvb, slice(n, n + v), False)
        out = out.transpose(0, 2, 1, 3).reshape(b, t, self._heads * v)
        return _dense_on(cache)(out, self.o_proj)


class AxK1Block(HybridBlock):
    def __init__(self, units, attention, ffn, norm_eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        self.attn_norm = nn.RMSNorm(epsilon=norm_eps, in_channels=units)
        self.attention = attention
        self.ffn_norm = nn.RMSNorm(epsilon=norm_eps, in_channels=units)
        self.ffn = ffn

    def forward(self, x, cache=None, start_pos=None):
        x = x + self.attention(self.attn_norm(x), cache=cache,
                               start_pos=start_pos)
        return x + self.ffn(self.ffn_norm(x), cache=cache)


class AxK1Model(HybridBlock):
    """Decoder-only LM; forward returns logits (B, T, vocab). The first
    ``first_dense`` layers' feed-forward is dense (``hidden_size`` wide),
    the others' routed (``expert_size`` wide experts). ``rope_scaling``
    is what :func:`~.llama._rope_tables` takes; ``mscale_all_dim`` gives
    the softmax scale its YaRN factor squared."""

    def __init__(self, vocab_size, units, num_layers, num_heads, q_rank,
                 kv_rank, nope_dim, rope_dim, v_dim, hidden_size,
                 expert_size, num_experts, num_experts_per_tok,
                 num_shared_experts, first_dense=1, groups=None,
                 routed_scale=None, norm_topk_prob=True, experts_held=None,
                 rope_theta=10000.0, rope_scaling=None, mscale_all_dim=0.0,
                 norm_eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        factor = 1.0
        if rope_scaling is not None and mscale_all_dim:
            factor = yarn_mscale(rope_scaling[1], mscale_all_dim) ** 2
        self.embed = nn.Embedding(vocab_size, units)
        self._blocks = []
        for i in range(num_layers):
            attention = LatentAttention(
                units, num_heads, q_rank, kv_rank, nope_dim, rope_dim, v_dim,
                theta=rope_theta, rope_scaling=rope_scaling,
                scale_factor=factor, norm_eps=norm_eps)
            if i < first_dense:
                ffn = LlamaFFN(units, hidden_size)
            else:
                ffn = RoutedFFN(
                    units, expert_size, num_experts, num_experts_per_tok,
                    experts_held=experts_held, norm_topk_prob=norm_topk_prob,
                    score="sigmoid", num_shared=num_shared_experts,
                    groups=groups, routed_scale=routed_scale)
            blk = AxK1Block(units, attention, ffn, norm_eps=norm_eps)
            self._blocks.append(blk)
            self.register_child(blk, f"layer{i}")
        self.norm = nn.RMSNorm(epsilon=norm_eps, in_channels=units)
        self.lm_head = nn.Dense(vocab_size, flatten=False, use_bias=False,
                                in_units=units)

    def cache_spec(self):
        return [blk.attention.cache_geometry() for blk in self._blocks]

    def forward(self, input_ids, cache=None, start_pos=None):
        x = self.embed(input_ids)
        if cache is None:
            for blk in self._blocks:
                x = blk(x)
            return self.lm_head(self.norm(x))
        for i, blk in enumerate(self._blocks):
            x = blk(x, cache=cache.layer(i), start_pos=start_pos)
        return _dense_on(cache)(self.norm(x), self.lm_head)
