"""A rotary-GQA + SwiGLU decoder (Mistral-7B, Llama-class) in plain
float32 ``jax.numpy``: RMSNorm, grouped-query causal attention with
rotary embeddings on adjacent channel pairs (``rope_theta`` from the
configuration) and the configuration's sliding window, a SwiGLU
feed-forward, a final RMSNorm and an untied head. One full forward pass
over a whole sequence: no cache, no kernel, no batching tricks.

Nothing here imports the program. ``num`` says how matrices are
multiplied: ``EXACT`` for the reference, one of ``controls()`` (the
nearest precision below the one the configuration states) for the
control of ``correct``.
"""
import jax
import jax.numpy as jnp


def param_shapes(cfg):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    kv = cfg["num_key_value_heads"] * (h // cfg["num_attention_heads"])
    s = {"embed": ((cfg["vocab_size"], h), "normal"),
         "norm": ((h,), "ones_normal"),
         "head": ((cfg["vocab_size"], h), "normal")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}."
        s[p + "attn_norm"] = ((h,), "ones_normal")
        s[p + "q"] = ((h, h), "normal")
        s[p + "k"] = ((kv, h), "normal")
        s[p + "v"] = ((kv, h), "normal")
        s[p + "o"] = ((h, h), "normal")
        s[p + "ffn_norm"] = ((h,), "ones_normal")
        s[p + "gate"] = ((f, h), "normal")
        s[p + "up"] = ((f, h), "normal")
        s[p + "down"] = ((h, f), "normal")
    return s


class Numerics:
    """``cast`` is applied to both operands of every matrix
    multiplication, which then runs at ``precision``."""

    def __init__(self, cast, precision="highest"):
        self.cast, self.precision = cast, precision


def identity(x):
    return x


def to(dtype):
    def cast(x):
        return x.astype(dtype).astype(jnp.float32)
    return cast


EXACT = Numerics(identity)


def controls(precision):
    """The control's numerics for a configuration that states
    ``precision``. Below float32 stands bfloat16; ``high`` (three bf16
    passes) is read beside it, being what stands below float32 at
    ``highest``."""
    return {"float32": {"bfloat16": Numerics(to(jnp.bfloat16)),
                        "high": Numerics(identity, "high")}}[precision]


def _mm(x, w, num):
    # weights are (out, in), as the published checkpoints store them
    return jnp.einsum("...i,oi->...o", num.cast(x), num.cast(w),
                      precision=num.precision)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (B, H, T, D); rotates channel pairs (2i, 2i+1) by t * theta^(-2i/D)."""
    t, d = x.shape[2], x.shape[3]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def embed(tokens, table):
    return table[tokens]


def layer(x, p, cfg, num=EXACT):
    """One decoder layer over (B, T, hidden); ``p`` holds the layer's
    leaves without their ``layer<i>.`` prefix."""
    b, t, h = x.shape
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // heads
    eps = cfg["rms_norm_eps"]
    y = _rms_norm(x, p["attn_norm"], eps)
    q = _mm(y, p["q"], num).reshape(b, t, heads, d).transpose(0, 2, 1, 3)
    k = _mm(y, p["k"], num).reshape(b, t, kvh, d).transpose(0, 2, 1, 3)
    v = _mm(y, p["v"], num).reshape(b, t, kvh, d).transpose(0, 2, 1, 3)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, heads // kvh, axis=1)
    v = jnp.repeat(v, heads // kvh, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", num.cast(q), num.cast(k),
                   precision=num.precision) / (d ** 0.5)
    qi = jnp.arange(t)[:, None]
    ki = jnp.arange(t)[None, :]
    ok = ki <= qi
    if cfg.get("sliding_window"):
        ok = ok & (ki > qi - cfg["sliding_window"])
    w = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
    a = jnp.einsum("bhqk,bhkd->bhqd", num.cast(w), num.cast(v),
                   precision=num.precision)
    a = a.transpose(0, 2, 1, 3).reshape(b, t, h)
    x = x + _mm(a, p["o"], num)
    y = _rms_norm(x, p["ffn_norm"], eps)
    g = jax.nn.silu(_mm(y, p["gate"], num)) * _mm(y, p["up"], num)
    return x + _mm(g, p["down"], num)


def logits(x, norm, head, cfg, num=EXACT):
    return _mm(_rms_norm(x, norm, cfg["rms_norm_eps"]), head, num)
