"""Fused multi-layer RNN/LSTM/GRU (reference op: ``src/operator/rnn.cc`` —
the cuDNN-backed fused ``RNN`` op behind ``gluon.rnn.{RNN,LSTM,GRU}``).

TPU design: per layer/direction, the input projection is hoisted out of the
time loop as ONE large ``(T*N, C) @ (C, G*H)`` matmul (MXU-sized), and only
the recurrent ``h @ Whh`` stays inside a ``lax.scan`` — one XLA while-loop
whose compile time is independent of sequence length.
"""
from __future__ import annotations

from ..base import MXNetError


def _gate_counts(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


def _layer_scan(x, h0, c0, wih, whh, bih, bhh, mode, reverse=False):
    """One direction of one layer. x: (T, N, C) -> (T, N, H)."""
    import jax
    import jax.numpy as jnp

    H = whh.shape[1]
    gx = jnp.einsum("tnc,gc->tng", x, wih) + bih  # hoisted input projection

    if mode == "lstm":
        def step(carry, g_t):
            h, c = carry
            gates = g_t + h @ whh.T + bhh
            i = jax.nn.sigmoid(gates[:, 0:H])
            f = jax.nn.sigmoid(gates[:, H:2 * H])
            g = jnp.tanh(gates[:, 2 * H:3 * H])
            o = jax.nn.sigmoid(gates[:, 3 * H:4 * H])
            c = f * c + i * g
            h = o * jnp.tanh(c)
            return (h, c), h

        (h_T, c_T), out = jax.lax.scan(step, (h0, c0), gx, reverse=reverse)
        return out, h_T, c_T
    if mode == "gru":
        def step(h, g_t):
            hh = h @ whh.T + bhh
            r = jax.nn.sigmoid(g_t[:, 0:H] + hh[:, 0:H])
            z = jax.nn.sigmoid(g_t[:, H:2 * H] + hh[:, H:2 * H])
            n = jnp.tanh(g_t[:, 2 * H:3 * H] + r * hh[:, 2 * H:3 * H])
            h = (1.0 - z) * n + z * h
            return h, h

        h_T, out = jax.lax.scan(step, h0, gx, reverse=reverse)
        return out, h_T, None
    act = jnp.tanh if mode == "rnn_tanh" else jax.nn.relu

    def step(h, g_t):
        h = act(g_t + h @ whh.T + bhh)
        return h, h

    h_T, out = jax.lax.scan(step, h0, gx, reverse=reverse)
    return out, h_T, None


def rnn_fused(data, h0, c0, weights, mode, num_layers, bidirectional,
              dropout=0.0, train=False, rng_key=None):
    """Run the fused stack. ``data``: (T, N, C) raw jax array.

    ``weights``: flat list ordered [wih, whh, bih, bhh] per (layer,
    direction), directions l then r within a layer (reference param naming
    ``{l,r}{i}_i2h_weight`` — ``python/mxnet/gluon/rnn/rnn_layer.py``).
    ``h0``/``c0``: (L*D, N, H). Returns (out, h_T, c_T or None).
    """
    import jax
    import jax.numpy as jnp

    D = 2 if bidirectional else 1
    x = data
    h_outs, c_outs = [], []
    for layer in range(num_layers):
        outs = []
        for d in range(D):
            idx = (layer * D + d) * 4
            wih, whh, bih, bhh = weights[idx:idx + 4]
            s = layer * D + d
            out, h_T, c_T = _layer_scan(
                x, h0[s], c0[s] if c0 is not None else None,
                wih, whh, bih, bhh, mode, reverse=(d == 1))
            outs.append(out)
            h_outs.append(h_T)
            if c_T is not None:
                c_outs.append(c_T)
        x = outs[0] if D == 1 else jnp.concatenate(outs, axis=-1)
        if dropout > 0 and train and layer < num_layers - 1:
            if rng_key is None:
                raise MXNetError("dropout inside fused rnn needs an rng key")
            keep = 1.0 - dropout
            mask = jax.random.bernoulli(  # f32: see ops.nn.dropout
                jax.random.fold_in(rng_key, layer), jnp.float32(keep),
                x.shape)
            x = jnp.where(mask, x / keep, 0.0)
    h_stack = jnp.stack(h_outs)
    c_stack = jnp.stack(c_outs) if c_outs else None
    return x, h_stack, c_stack
