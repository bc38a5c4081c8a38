"""Falcon-H1: a Mamba-2 mixer beside grouped-query attention in every block.

Each block reads one RMSNorm of the residual stream and adds two branches
to it, a state-space mixer and a rotary GQA attention, each scaled on the
way in and on the way out; a SwiGLU feed-forward follows under its own
norm. The multipliers (muP-style scalars of the published configuration)
are constructor arguments::

    x0 = embedding_multiplier * E[tokens]
    y  = RMSNorm(h)
    h  = h + ssm_out_multiplier * Mixer(ssm_in_multiplier * y)
           + attention_out_multiplier * Attn(attention_in_multiplier * y)
    h  = h + MLP(RMSNorm(h))
    logits = lm_head_multiplier * W_head RMSNorm(h)

Attention and feed-forward are :class:`~.llama.LlamaAttention` and
:class:`~.llama.LlamaFFN` with their general arguments (``head_dim``,
``theta``, the key, gate and down multipliers); the mixer is new.

Serving: ``cache_spec()`` lists, for every layer, K/V rows *and* a
recurrent state (the convolution's last inputs, the scan's state matrix).
``serve.KVCache`` and ``serve.PagedKVPool`` build both from it, and the
compiled step hands the mixer its lane contract (``ops/nn.py``, the
recurrent-state section) through the cache view. The baseline rung's
bitwise prefill/decode contract is Llama's: a chunked scan and the
one-step recurrence round differently, so this model agrees across the
two to tolerance on every rung.
"""
from __future__ import annotations

import numpy as _onp

from .. import numpy as mnp
from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ops import nn as _ops
from .llama import LayerCache, LlamaAttention, LlamaFFN, _dense_on


class Mamba2Mixer(HybridBlock):
    """``in_proj`` -> causal depthwise conv -> selective scan -> gated
    grouped RMSNorm -> ``out_proj`` (Mamba-2, ``norm_before_gate`` false).

    ``in_proj`` yields ``[gate (d_ssm) | x (d_ssm), B, C (groups x
    d_state each) | dt (heads)]``; ``multipliers`` scales those five
    segments, in that order. The conv runs over ``x, B, C`` together.
    """

    def __init__(self, units, d_ssm, d_state, num_heads, head_dim,
                 num_groups=1, d_conv=4, chunk_size=128, norm_eps=1e-5,
                 multipliers=None, **kwargs):
        super().__init__(**kwargs)
        if num_heads * head_dim != d_ssm or num_heads % num_groups \
                or d_ssm % num_groups:
            raise MXNetError(
                f"mixer geometry: {num_heads} heads x {head_dim} must be "
                f"d_ssm {d_ssm}, and {num_groups} groups must divide both")
        self._d_ssm, self._d_state = d_ssm, d_state
        self._heads, self._head_dim = num_heads, head_dim
        self._groups, self._d_conv = num_groups, d_conv
        self._chunk, self._eps = chunk_size, norm_eps
        bc = num_groups * d_state
        self._conv_dim = d_ssm + 2 * bc
        self._splits = (d_ssm, d_ssm, bc, bc, num_heads)
        self._mults = None
        if multipliers is not None:
            self._mults = _onp.concatenate(
                [_onp.full(n, m, "float32")
                 for n, m in zip(self._splits, multipliers)])
        self.in_proj = nn.Dense(sum(self._splits), flatten=False,
                                use_bias=False, in_units=units)
        self.conv_weight = Parameter("conv_weight",
                                     shape=(self._conv_dim, d_conv))
        self.conv_bias = Parameter("conv_bias", shape=(self._conv_dim,),
                                   init="zeros")
        self.dt_bias = Parameter("dt_bias", shape=(num_heads,),
                                 init="zeros")
        self.a_log = Parameter("a_log", shape=(num_heads,), init="zeros")
        self.d = Parameter("d", shape=(num_heads,), init="ones")
        self.norm_gamma = Parameter("norm_gamma", shape=(d_ssm,),
                                    init="ones")
        self.out_proj = nn.Dense(units, flatten=False, use_bias=False,
                                 in_units=d_ssm)

    def state_shapes(self):
        """Per-sequence shapes of what the mixer carries between steps:
        the conv's last ``d_conv - 1`` inputs and the scan's state."""
        return ((self._d_conv - 1, self._conv_dim),
                (self._heads, self._head_dim, self._d_state))

    def forward(self, u, cache=None, start_pos=None):
        b, t, _ = u.shape
        dense = _dense_on(cache)
        if cache is None:
            # the normal path: a whole sequence from zero state, every
            # position valid; the states it ends on are dropped
            conv_state = ssm_state = start_pos = valid_len = live = None
        else:
            conv_state, ssm_state = cache.state
            valid_len, live = cache.valid_len, cache.live
        proj = dense(u, self.in_proj)
        if self._mults is not None:
            proj = proj * mnp.array(self._mults)
        d_ssm, bc = self._d_ssm, self._groups * self._d_state
        gate = proj[:, :, :d_ssm]
        xbc = proj[:, :, d_ssm:d_ssm + self._conv_dim]
        dt = proj[:, :, d_ssm + self._conv_dim:]
        xbc, conv_state = _ops.causal_conv1d(
            xbc, self.conv_weight.data(), self.conv_bias.data(), conv_state,
            start_pos, valid_len, live)
        xbc = _ops.activation(xbc, "silu")
        x = xbc[:, :, :d_ssm].reshape(b, t, self._heads, self._head_dim)
        b_mat = xbc[:, :, d_ssm:d_ssm + bc].reshape(
            b, t, self._groups, self._d_state)
        c_mat = xbc[:, :, d_ssm + bc:].reshape(
            b, t, self._groups, self._d_state)
        dt = _ops.activation(dt + self.dt_bias.data(), "softrelu")
        y, ssm_state = _ops.ssd_scan(
            x, dt, -mnp.exp(self.a_log.data()), b_mat, c_mat, self.d.data(),
            ssm_state, start_pos, valid_len, live, chunk=self._chunk)
        if cache is not None:
            cache.update_state((conv_state, ssm_state))
        y = y.reshape(b, t, d_ssm) * _ops.activation(gate, "silu")
        y = _ops.grouped_rms_norm(y, self.norm_gamma.data(),
                                  groups=self._groups, eps=self._eps)
        return dense(y, self.out_proj)


class FalconH1Block(HybridBlock):
    def __init__(self, units, hidden_size, num_heads, num_kv_heads, head_dim,
                 mixer, norm_eps=1e-5, theta=10000.0, key_multiplier=None,
                 mlp_multipliers=(None, None), ssm_in_multiplier=1.0,
                 ssm_out_multiplier=1.0, attention_in_multiplier=1.0,
                 attention_out_multiplier=1.0, **kwargs):
        super().__init__(**kwargs)
        self._ssm_in, self._ssm_out = ssm_in_multiplier, ssm_out_multiplier
        self._attn_in = attention_in_multiplier
        self._attn_out = attention_out_multiplier
        self.input_norm = nn.RMSNorm(epsilon=norm_eps, in_channels=units)
        self.mixer = Mamba2Mixer(units, norm_eps=norm_eps, **mixer)
        self.attention = LlamaAttention(
            units, num_heads, num_kv_heads, theta=theta, head_dim=head_dim,
            key_multiplier=key_multiplier)
        self.ffn_norm = nn.RMSNorm(epsilon=norm_eps, in_channels=units)
        self.ffn = LlamaFFN(units, hidden_size,
                            gate_multiplier=mlp_multipliers[0],
                            down_multiplier=mlp_multipliers[1])

    def forward(self, x, cache=None, start_pos=None):
        y = self.input_norm(x)
        x = x + self.mixer(y * self._ssm_in, cache=cache,
                           start_pos=start_pos) * self._ssm_out \
            + self.attention(y * self._attn_in, cache=cache,
                             start_pos=start_pos) * self._attn_out
        return x + self.ffn(self.ffn_norm(x), cache=cache)


class FalconH1Model(HybridBlock):
    """Decoder-only LM; forward returns logits (B, T, vocab). Served by
    ``serve.Generator`` and ``serve.ContinuousEngine`` like
    :class:`~.llama.LlamaModel`: the same ``cache=`` / ``start_pos=``
    forward, and a ``cache_spec()`` that lists both kinds of state."""

    def __init__(self, vocab_size, units, hidden_size, num_layers, num_heads,
                 num_kv_heads, head_dim, mamba_d_ssm, mamba_d_state,
                 mamba_n_heads, mamba_d_head, mamba_n_groups=1,
                 mamba_d_conv=4, mamba_chunk_size=128, norm_eps=1e-5,
                 theta=10000.0, embedding_multiplier=1.0,
                 lm_head_multiplier=1.0, key_multiplier=None,
                 mlp_multipliers=(None, None), ssm_multipliers=None,
                 ssm_in_multiplier=1.0, ssm_out_multiplier=1.0,
                 attention_in_multiplier=1.0, attention_out_multiplier=1.0,
                 **kwargs):
        super().__init__(**kwargs)
        self._embed_mult = embedding_multiplier
        self._head_mult = lm_head_multiplier
        self._tie = False  # the published head is untied
        mixer = dict(d_ssm=mamba_d_ssm, d_state=mamba_d_state,
                     num_heads=mamba_n_heads, head_dim=mamba_d_head,
                     num_groups=mamba_n_groups, d_conv=mamba_d_conv,
                     chunk_size=mamba_chunk_size, multipliers=ssm_multipliers)
        self.embed = nn.Embedding(vocab_size, units)
        self._blocks = []
        for i in range(num_layers):
            blk = FalconH1Block(
                units, hidden_size, num_heads, num_kv_heads, head_dim, mixer,
                norm_eps=norm_eps, theta=theta, key_multiplier=key_multiplier,
                mlp_multipliers=mlp_multipliers,
                ssm_in_multiplier=ssm_in_multiplier,
                ssm_out_multiplier=ssm_out_multiplier,
                attention_in_multiplier=attention_in_multiplier,
                attention_out_multiplier=attention_out_multiplier)
            self._blocks.append(blk)
            self.register_child(blk, f"layer{i}")
        self.norm = nn.RMSNorm(epsilon=norm_eps, in_channels=units)
        self.lm_head = nn.Dense(vocab_size, flatten=False, use_bias=False,
                                in_units=units)

    def cache_spec(self):
        """Every layer keeps K/V rows and the mixer's recurrent state."""
        return [LayerCache(*blk.attention.cache_geometry(),
                           blk.mixer.state_shapes())
                for blk in self._blocks]

    def forward(self, input_ids, cache=None, start_pos=None):
        x = self.embed(input_ids) * self._embed_mult
        if cache is None:
            for blk in self._blocks:
                x = blk(x)
            return self.lm_head(self.norm(x)) * self._head_mult
        for i, blk in enumerate(self._blocks):
            x = blk(x, cache=cache.layer(i), start_pos=start_pos)
        return _dense_on(cache)(self.norm(x), self.lm_head) * self._head_mult
