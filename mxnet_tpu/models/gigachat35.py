"""GigaChat 3.5 (``gigachat3_5``): a decoder whose layers are Gated
DeltaNet linear attention but for every fourth, which is gated latent
attention (MLA); a few leading dense feed-forward layers, then routed
experts chosen with a score-correction bias beside one shared expert.

Every block norms both ends of each half, zero-centred (``RMSNorm(x) *
(1 + w)``)::

    h = x + post_mixer_norm(Mix(input_norm(x)))
    y = h + post_ffn_norm(FFN(ffn_norm(h)))

**Gated DeltaNet** (Yang et al., arXiv:2412.06464), ``Hk`` key heads and
``Hv`` value heads of ``K`` and ``V`` channels, value head ``h`` reading
key head ``h // (Hv / Hk)``::

    [q | k | v | z] = x W_qkvz               Hk K, Hk K, Hv V, Hv V
    [b | a] = x W_ba                         Hv, Hv
    [q | k | v] = silu(causal_conv1d([q | k | v]))   no bias
    beta = sigmoid(b),  g = -exp(A_log) * softplus(a + dt_bias)
    S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                          q, k unit a head, q / sqrt(K)
    out = (RMSNorm_head(o) * (1 + w) * gate_scale * sigmoid(z)) W_out

(``ops.nn.gated_delta_rule``, under the op scope ``delta.rule``; the
convolution is Falcon-H1's, ``ssm.conv``). A linear layer keeps no
position at all: ``cache_spec()`` gives it the convolution's last inputs
and the (Hv, K, V) state alone (``LayerCache`` with ``kv_heads`` 0), one
row a lane, and the pool no page of it.

**Gated latent attention** is :class:`~.ax_k1.LatentAttention` with its
output gate, served from latent pages as A.X-K1's is; its softmax scale
carries YaRN's factor squared.

**Feed-forward.** Layers before ``first_dense`` are
:class:`~.llama.LlamaFFN`; the others :class:`~.mellum.RoutedFFN` with
sigmoid scores, the choice by score plus ``e_score_correction_bias``, the
weights by score renormalised and times ``routed_scale``, one shared
expert; every SwiGLU is clamped by ``swiglu_limit``.

Serving: the continuous engine's in-place float32 step alone
(``decode_path="pallas"``), a chunk in a call of its own (a model with
state never mixes); the prefix cache, multi-step decode and speculation
refuse the state, ring caches the latent layer.
"""
from __future__ import annotations

from .. import numpy as mnp
from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ops import nn as _ops
from .ax_k1 import LatentAttention, yarn_mscale
from .llama import LayerCache, LlamaFFN, _dense_on
from .mellum import RoutedFFN


class ZeroCenteredRMSNorm(HybridBlock):
    """RMSNorm whose gain is ``1 + gamma``: ``gamma`` is stored about 0."""

    def __init__(self, units, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._eps = epsilon
        self.gamma = Parameter("gamma", shape=(units,), init="zeros")

    def forward(self, x):
        return _ops.rms_norm(x, 1.0 + self.gamma.data(), eps=self._eps)


class GatedDeltaNet(HybridBlock):
    """The linear-attention mixer of the module's docstring."""

    def __init__(self, units, num_k_heads, num_v_heads, k_dim, v_dim,
                 conv_kernel=4, gate_scale=2.0, norm_eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        if num_v_heads % num_k_heads:
            raise MXNetError(f"{num_v_heads} value heads on {num_k_heads} "
                             "key heads")
        self._hk, self._hv, self._dk, self._dv = num_k_heads, num_v_heads, \
            k_dim, v_dim
        self._kernel = int(conv_kernel)
        self._gate_scale, self._eps = float(gate_scale), norm_eps
        self._conv_dim = 2 * num_k_heads * k_dim + num_v_heads * v_dim
        wide = num_v_heads * v_dim
        self.in_proj_qkvz = nn.Dense(self._conv_dim + wide, flatten=False,
                                     use_bias=False, in_units=units)
        self.in_proj_ba = nn.Dense(2 * num_v_heads, flatten=False,
                                   use_bias=False, in_units=units)
        self.conv_weight = Parameter("conv_weight",
                                     shape=(self._conv_dim, conv_kernel))
        self.a_log = Parameter("a_log", shape=(num_v_heads,), init="zeros")
        self.dt_bias = Parameter("dt_bias", shape=(num_v_heads,),
                                 init="zeros")
        self.norm_gamma = Parameter("norm_gamma", shape=(v_dim,),
                                    init="zeros")
        self.out_proj = nn.Dense(units, flatten=False, use_bias=False,
                                 in_units=wide)

    def state_shapes(self):
        """Per-sequence shapes of what the mixer carries between steps:
        the conv's last ``conv_kernel - 1`` inputs and the rule's state."""
        return ((self._kernel - 1, self._conv_dim),
                (self._hv, self._dk, self._dv))

    def forward(self, u, cache=None, start_pos=None):
        b, t, _ = u.shape
        hk, hv, dk, dv = self._hk, self._hv, self._dk, self._dv
        dense = _dense_on(cache)
        if cache is None:
            # a whole sequence from zero state, every position valid
            conv_state = rule_state = start_pos = valid_len = live = None
        else:
            conv_state, rule_state = cache.state
            valid_len, live = cache.valid_len, cache.live
        qkvz = dense(u, self.in_proj_qkvz)
        ba = dense(u, self.in_proj_ba)
        qkv, conv_state = _ops.causal_conv1d(
            qkvz[:, :, :self._conv_dim], self.conv_weight.data(), None,
            conv_state, start_pos, valid_len, live)
        qkv = _ops.activation(qkv, "silu")
        nk = hk * dk
        beta = _ops.activation(ba[:, :, :hv], "sigmoid")
        g = -mnp.exp(self.a_log.data()) * _ops.activation(
            ba[:, :, hv:] + self.dt_bias.data(), "softrelu")
        o, rule_state = _ops.gated_delta_rule(
            qkv[:, :, :nk].reshape(b, t, hk, dk),
            qkv[:, :, nk:2 * nk].reshape(b, t, hk, dk),
            qkv[:, :, 2 * nk:].reshape(b, t, hv, dv), g, beta, rule_state,
            start_pos, valid_len, live)
        if cache is not None:
            cache.update_state((conv_state, rule_state))
        z = qkvz[:, :, self._conv_dim:].reshape(b, t, hv, dv)
        o = _ops.rms_norm(o, 1.0 + self.norm_gamma.data(), eps=self._eps) \
            * (self._gate_scale * _ops.activation(z, "sigmoid"))
        return dense(o.reshape(b, t, hv * dv), self.out_proj)


class GigaChat35Block(HybridBlock):
    """One block; ``mixer`` is registered as ``attention`` (latent) or
    ``mixer`` (linear), the names the device trace's parts know."""

    def __init__(self, units, mixer, ffn, norm_eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        self.input_norm = ZeroCenteredRMSNorm(units, norm_eps)
        self._latent = isinstance(mixer, LatentAttention)
        if self._latent:
            self.attention = mixer
        else:
            self.mixer = mixer
        self.post_mixer_norm = ZeroCenteredRMSNorm(units, norm_eps)
        self.ffn_norm = ZeroCenteredRMSNorm(units, norm_eps)
        self.ffn = ffn
        self.post_ffn_norm = ZeroCenteredRMSNorm(units, norm_eps)

    def forward(self, x, cache=None, start_pos=None):
        mix = self.attention if self._latent else self.mixer
        x = x + self.post_mixer_norm(mix(self.input_norm(x), cache=cache,
                                         start_pos=start_pos))
        return x + self.post_ffn_norm(self.ffn(self.ffn_norm(x),
                                               cache=cache))


class GigaChat35Model(HybridBlock):
    """Decoder-only LM; forward returns logits (B, T, vocab). Layer ``i``
    is latent attention if ``i`` is in ``full_attention``, else Gated
    DeltaNet (``linear``: :class:`GatedDeltaNet`'s arguments past
    ``units``); its feed-forward is dense (``hidden_size`` wide) if ``i <
    first_dense``, else routed (``moe``: :class:`~.mellum.RoutedFFN`'s
    arguments past ``units``). ``rope_scaling`` is what
    :func:`~.llama._rope_tables` takes; ``mscale_all_dim`` gives the
    softmax scale its YaRN factor squared."""

    def __init__(self, vocab_size, units, num_layers, full_attention,
                 first_dense, num_heads, q_rank, kv_rank, nope_dim, rope_dim,
                 v_dim, linear, hidden_size, moe, swiglu_limit=None,
                 rope_theta=10000.0, rope_scaling=None, mscale_all_dim=0.0,
                 norm_eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        factor = 1.0
        if rope_scaling is not None and mscale_all_dim:
            factor = yarn_mscale(rope_scaling[1], mscale_all_dim) ** 2
        full = set(full_attention)
        self.embed = nn.Embedding(vocab_size, units)
        self._blocks = []
        for i in range(num_layers):
            if i in full:
                mixer = LatentAttention(
                    units, num_heads, q_rank, kv_rank, nope_dim, rope_dim,
                    v_dim, theta=rope_theta, rope_scaling=rope_scaling,
                    scale_factor=factor, norm_eps=norm_eps, gated=True)
            else:
                mixer = GatedDeltaNet(units, **linear)
            if i < first_dense:
                ffn = LlamaFFN(units, hidden_size, swiglu_limit=swiglu_limit)
            else:
                ffn = RoutedFFN(units, swiglu_limit=swiglu_limit, **moe)
            blk = GigaChat35Block(units, mixer, ffn, norm_eps=norm_eps)
            self._blocks.append(blk)
            self.register_child(blk, f"layer{i}")
        self.norm = ZeroCenteredRMSNorm(units, norm_eps)
        self.lm_head = nn.Dense(vocab_size, flatten=False, use_bias=False,
                                in_units=units)

    def cache_spec(self):
        """A latent layer keeps its one array a position; a linear layer
        keeps no position, its conv and rule state alone."""
        return [blk.attention.cache_geometry() if blk._latent
                else LayerCache(0, 0, blk.mixer.state_shapes())
                for blk in self._blocks]

    def forward(self, input_ids, cache=None, start_pos=None):
        x = self.embed(input_ids)
        if cache is None:
            for blk in self._blocks:
                x = blk(x)
            return self.lm_head(self.norm(x))
        for i, blk in enumerate(self._blocks):
            x = blk(x, cache=cache.layer(i), start_pos=start_pos)
        return _dense_on(cache)(self.norm(x), self.lm_head)
