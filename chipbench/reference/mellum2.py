"""Mellum-2 (rotary GQA attention of two kinds, window and full, and a
feed-forward of routed experts in every block) in plain float32
``jax.numpy``: one full forward pass over whole sequences. No cache, no
pages, no sort, no grouped product, no kernel: the window is a mask on a
full score matrix and the experts are a loop over all of them with a 0/1
mask. ``W`` of the projections are stored (out, in); the experts' ``gate``
and ``up`` are (experts, hidden, expert width), ``down`` (experts, expert
width, hidden).

For layer l of kind ``layer_types[l]``, stream x of shape (T, hidden):

    h = RMSNorm(x; attn_norm);  q = h Wq, k = h Wk, v = h Wv
    q, k turned by the kind's rope table (below)
    key s is visible to query t iff 0 <= t - s            (full_attention)
                                iff 0 <= t - s < window   (sliding_attention)
    x = x + softmax(q k^T / sqrt(head_dim)) v Wo      (KV head j serves
                                                       query heads 8j..8j+7)
    h = RMSNorm(x; ffn_norm);  p = softmax(h Wr) over all experts
    S = the k largest p (a tie goes to the lower index)
    w_e = p_e / sum of p over S, for e in S
    x = x + sum over e in S of w_e (silu(h G_e) * (h U_e)) D_e

then ``RMSNorm(x; norm)`` and the head. Rope tables, made in float64 on
the host: ``rope_type`` default is ``inv_freq_i = theta^(-2i/d)``; yarn
blends, by channel pair, between those frequencies and the same divided by
``factor`` (``ramp_i = clip((i - low) / (high - low), 0, 1)`` with ``low``
and ``high`` the pairs that turn ``beta_fast`` and ``beta_slow`` times
over ``original_max_position_embeddings``) and multiplies cos and sin by
``attention_factor``. Pairs of channels are adjacent (2i, 2i+1), as the
program's ``apply_rope`` has them (the configuration's ``notes``).

The work is done in blocks so that the check's sizes fit one chip beside
nothing else: the scores a row and a KV head's group of query heads at a
time, the experts one at a time.

Nothing here imports the program. The driver calls ``layer(x, p, cfg,
num)`` without saying which layer it is, under one ``jax.jit`` for all of
them, so a layer's kind has to be told from what it is handed: **the
attention's leaves carry the kind in their names**
(``layer3.full_attention.q``), which also makes the two kinds two traces.

``num`` says how matrices are multiplied and whether a fault is planted:
``EXACT`` for the reference, ``controls()`` for the control of ``correct``
(the nearest precision below the configuration's) and for the planted
faults that show the check sees the router, the window and the two rope
tables.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

WINDOW, FULL = "sliding_attention", "full_attention"


def held(cfg):
    """``(first, count)`` of the experts whose weights are held: all of
    them unless the configuration gives a range (a chip's share)."""
    first, count = cfg.get("experts_held") or (0, cfg["num_experts"])
    return int(first), int(count)


def param_shapes(cfg):
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    hd = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    e = held(cfg)[1]
    s = {"embed": ((cfg["vocab_size"], h), "normal"),
         "norm": ((h,), "ones_normal"),
         "head": ((cfg["vocab_size"], h), "normal")}
    for i in range(cfg["num_hidden_layers"]):
        p, a = f"layer{i}.", f"layer{i}.{cfg['layer_types'][i]}."
        s[p + "attn_norm"] = ((h,), "ones_normal")
        s[a + "q"] = ((q, h), "normal")
        s[a + "k"] = ((kv, h), "normal")
        s[a + "v"] = ((kv, h), "normal")
        s[a + "o"] = ((h, q), "normal")
        s[p + "ffn_norm"] = ((h,), "ones_normal")
        s[p + "router"] = ((cfg["num_experts"], h), "normal")
        s[p + "gate"] = ((e, h, f), "normal")
        s[p + "up"] = ((e, h, f), "normal")
        s[p + "down"] = ((e, f, h), "normal")
    return s


class Numerics:
    """``cast`` is applied to both operands of every matrix
    multiplication, which then runs at ``precision``. ``fault`` plants
    one of ``FAULTS``."""

    def __init__(self, cast, precision="highest", fault=None):
        self.cast, self.precision, self.fault = cast, precision, fault


def identity(x):
    return x


def to(dtype):
    def cast(x):
        return x.astype(dtype).astype(jnp.float32)
    return cast


EXACT = Numerics(identity)

# What a serving stack can get wrong in the two mechanisms, planted in the
# reference's full pass: the chosen experts' weights not renormalised to 1;
# one expert too few; the window layers seeing every key (a cache that
# never bounds them); the full layers turned by the plain table.
FAULTS = ("no_renormalisation", "top_k_less_one", "window_left_out",
          "plain_rope_on_full")


def controls(precision):
    """The control's numerics for a configuration that states
    ``precision`` (below float32 stands bfloat16), and the planted
    faults, each the exact reference but for its fault."""
    out = {"float32": {"bfloat16": Numerics(to(jnp.bfloat16))}}[precision]
    out.update({"fault_" + f: Numerics(identity, fault=f) for f in FAULTS})
    return out


def _mm(x, w, num):
    return jnp.einsum("...i,oi->...o", num.cast(x), num.cast(w),
                      precision=num.precision)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def rope_tables(t, dim, params):
    """``(cos, sin)`` (T, dim / 2) float32 for one kind's
    ``rope_parameters``, in float64 on the host."""
    theta = float(params["rope_theta"])
    pair = np.arange(dim // 2, dtype=np.float64)
    inv = theta ** (-2.0 * pair / dim)
    scale = 1.0
    if params["rope_type"] == "yarn":
        orig = params["original_max_position_embeddings"]

        def pair_of(turns):
            return dim * math.log(orig / (2 * math.pi * turns)) \
                / (2 * math.log(theta))

        low = max(math.floor(pair_of(params["beta_fast"])), 0)
        high = min(math.ceil(pair_of(params["beta_slow"])), dim - 1)
        ramp = np.clip((pair - low) / (high - low), 0.0, 1.0)
        inv = (1.0 - ramp) * inv + ramp * inv / params["factor"]
        scale = params["attention_factor"]
    elif params["rope_type"] != "default":
        raise ValueError(f"rope_type {params['rope_type']!r}")
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    return (scale * np.cos(ang)).astype(np.float32), \
        (scale * np.sin(ang)).astype(np.float32)


def _rope(x, cos, sin):
    """x: (B, H, T, D); turns channel pairs (2i, 2i+1)."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def embed(tokens, table):
    return table[tokens]


def attention(u, p, cfg, num, kind):
    b, t, _ = u.shape
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, g = cfg["head_dim"], heads // kvh
    q = _mm(u, p[kind + ".q"], num).reshape(b, t, heads, d)
    k = _mm(u, p[kind + ".k"], num).reshape(b, t, kvh, d)
    v = _mm(u, p[kind + ".v"], num).reshape(b, t, kvh, d)
    table = WINDOW if num.fault == "plain_rope_on_full" else kind
    cos, sin = rope_tables(t, d, cfg["rope_parameters"][table])
    q = _rope(q.transpose(0, 2, 1, 3), cos, sin)            # (B, H, T, D)
    k = _rope(k.transpose(0, 2, 1, 3), cos, sin)            # (B, KV, T, D)
    gap = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]   # t - s
    seen = gap >= 0
    if kind == WINDOW and num.fault != "window_left_out":
        seen = seen & (gap < cfg["sliding_window"])

    def group(qkv):
        """One row's one KV head: its g query heads over all keys."""
        qg, kg, vg = qkv                       # (g, T, D), (T, D), (T, D)
        s = jnp.einsum("gqd,kd->gqk", num.cast(qg), num.cast(kg),
                       precision=num.precision) / (d ** 0.5)
        w = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        return jnp.einsum("gqk,kd->gqd", num.cast(w), num.cast(vg),
                          precision=num.precision)

    out = jax.lax.map(group, (q.reshape(b * kvh, g, t, d),
                              k.reshape(b * kvh, t, d),
                              v.transpose(0, 2, 1, 3).reshape(b * kvh, t, d)))
    out = out.reshape(b, heads, t, d).transpose(0, 2, 1, 3)
    return _mm(out.reshape(b, t, heads * d), p[kind + ".o"], num)


def route(h, router, cfg, num):
    """``(N, experts)`` weights: 0 for an expert the token did not pick."""
    k = cfg["num_experts_per_tok"] - (num.fault == "top_k_less_one")
    prob = jax.nn.softmax(_mm(h, router, num), axis=-1)          # (N, E)
    # an expert's rank: how many come before it, the larger first and of
    # equals the lower index first
    e = jnp.arange(prob.shape[-1])
    before = (prob[:, None, :] > prob[:, :, None]) | (
        (prob[:, None, :] == prob[:, :, None]) & (e[None, :] < e[:, None]))
    picked = (before.sum(-1) < k).astype(prob.dtype)             # 0 / 1
    w = prob * picked
    if cfg["norm_topk_prob"] and num.fault != "no_renormalisation":
        w = w / w.sum(-1, keepdims=True)
    return w


def experts(h, p, cfg, num):
    """The held experts' part of every token's sum: each expert over every
    token, times the token's weight for it (0 where it was not picked)."""
    b, t, width = h.shape
    hf = h.reshape(b * t, width)
    first, count = held(cfg)
    w = route(hf, p["router"], cfg, num)[:, first:first + count]

    def one(acc, at):
        gate, up, down, w_e = at
        hid = jax.nn.silu(jnp.einsum("ni,if->nf", num.cast(hf),
                                     num.cast(gate),
                                     precision=num.precision)) \
            * jnp.einsum("ni,if->nf", num.cast(hf), num.cast(up),
                         precision=num.precision)
        y = jnp.einsum("nf,fi->ni", num.cast(hid), num.cast(down),
                       precision=num.precision)
        return acc + w_e[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(hf),
                          (p["gate"], p["up"], p["down"], w.T))
    return out.reshape(b, t, width)


def kind_of(p):
    return FULL if FULL + ".q" in p else WINDOW


def layer(x, p, cfg, num=EXACT):
    """One block over (B, T, hidden); ``p`` holds the layer's leaves
    without their ``layer<i>.`` prefix."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(_rms_norm(x, p["attn_norm"], eps), p, cfg, num,
                      kind_of(p))
    return x + experts(_rms_norm(x, p["ffn_norm"], eps), p, cfg, num)


def logits(x, norm, head, cfg, num=EXACT):
    return _mm(_rms_norm(x, norm, cfg["rms_norm_eps"]), head, num)
