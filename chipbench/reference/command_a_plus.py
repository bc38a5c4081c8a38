"""Command A+ (``cohere2_moe``: a parallel block under one LayerNorm,
GQA attention of two kinds, 8 of 128 sigmoid-routed experts beside four
averaged shared experts, a tied head) in plain float32 ``jax.numpy``: one
full forward pass over whole sequences. No cache, no pages, no sort, no
grouped product, no kernel: the window is a mask on a full score matrix,
the routed experts are a loop over those held with a 0/1 mask, the shared
experts are four separate SwiGLUs whose outputs are averaged. ``W`` of the
projections are stored (out, in); ``gate`` and ``up`` are (experts,
hidden, width), ``down`` (experts, width, hidden), the shared ones alike.

For layer l of kind ``layer_types[l]``, stream x of shape (T, hidden):

    y = (x - mean(x)) / sqrt(var(x) + layer_norm_eps) * g
    q = y Wq, k = y Wk, v = y Wv      128 heads, 8 KV heads, no q/k norm
    sliding_attention: q, k turned at rope_theta, pairs (2i, 2i+1);
                       key s visible to query t iff 0 <= t - s < window
    full_attention:    no rotation; key s visible iff 0 <= t - s
    attn = softmax(q k^T / sqrt(head_dim)) v Wo   (KV head j serves query
                                                   heads 16j .. 16j+15)
    s = sigmoid(y Wr) over all experts, float32
    S = the k largest s (a tie goes to the lower index)
    w_e = s_e / sum of s over S, for e in S        (norm_topk_prob)
    routed = sum over e in S that are held of w_e (silu(y G_e) * (y U_e)) D_e
    shared = 1/4 sum over the four shared experts of (silu(y G) * (y U)) D
    x' = x + attn + routed + shared                (use_parallel_block)

then ``LayerNorm(x; norm)`` and the head, which is the embedding's own
table (``tie_word_embeddings``) times ``logit_scale``.

**Departures from the published model**, each in the configuration's
``assumed`` or ``notes``: "average" is read as the mean of the shared
experts added to the routed sum; no router bias or score correction;
rotary pairs adjacent (``rope_gptj``); the held experts are a range
``(first, count)`` of the 128 (``experts_held``: one chip's share), a
token's weights are normalised over its k experts wherever they are held,
and what the absent experts would add is left out; the vocabulary is the
rows held.

The work is done in blocks so that the check's sizes fit one chip beside
nothing else: the scores one query head at a time, the experts one at a
time.

Nothing here imports the program. The driver calls ``layer(x, p, cfg,
num)`` without saying which layer it is, under one ``jax.jit`` for all of
them, so a layer's kind has to be told from what it is handed: **the
attention's leaves carry the kind in their names**
(``layer3.full_attention.q``), which also makes the two kinds two traces.
The driver also asks the weights for a leaf ``head``: for this tied model
that leaf is the embedding's draw under a second name (:class:`Tied`).

``num`` says how matrices are multiplied and whether a fault is planted:
``EXACT`` for the reference, ``controls()`` for the control of ``correct``
(the nearest precision below the configuration's) and for the planted
faults that show the check sees the score function, the shared branch,
the layer kind without rotation, the parallel block, the window's edge
and the share's normalisation.
"""
import jax
import jax.numpy as jnp
import numpy as np

WINDOW, FULL = "sliding_attention", "full_attention"


def held(cfg):
    """``(first, count)`` of the routed experts whose weights are held:
    the first ``num_experts`` (the file's count of experts held here)
    unless the configuration gives a range."""
    first, count = cfg.get("experts_held") or (0, cfg["num_experts"])
    return int(first), int(count)


def router_width(cfg):
    """The router scores all the experts of the layer (``router_experts``:
    the published ``num_experts``), whatever is held here."""
    return int(cfg["router_experts"])


class Tied(str):
    """A leaf that is another leaf's draw under a second name:
    ``weights.py`` salts a draw with its leaf's encoded name."""

    def __new__(cls, name, of):
        self = super().__new__(cls, name)
        self.of = of
        return self

    def encode(self, *args):
        return self.of.encode(*args)


def param_shapes(cfg):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    e, sh = held(cfg)[1], cfg["num_shared_experts"]
    if not cfg["tie_word_embeddings"]:
        raise ValueError("this reference computes the tied head alone")
    table = ((cfg["vocab_size"], h), "normal")
    s = {"embed": table, "norm": ((h,), "ones_normal"),
         Tied("head", "embed"): table}
    for i in range(cfg["num_hidden_layers"]):
        p, a = f"layer{i}.", f"layer{i}.{cfg['layer_types'][i]}."
        s[p + "norm"] = ((h,), "ones_normal")
        s[a + "q"] = ((q, h), "normal")
        s[a + "k"] = ((kv, h), "normal")
        s[a + "v"] = ((kv, h), "normal")
        s[a + "o"] = ((h, q), "normal")
        s[p + "router"] = ((router_width(cfg), h), "normal")
        s[p + "gate"] = ((e, h, f), "normal")
        s[p + "up"] = ((e, h, f), "normal")
        s[p + "down"] = ((e, f, h), "normal")
        s[p + "shared_gate"] = ((sh, h, f), "normal")
        s[p + "shared_up"] = ((sh, h, f), "normal")
        s[p + "shared_down"] = ((sh, f, h), "normal")
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

# What a serving stack can get wrong in this model's mechanisms, planted
# in the reference's full pass: the experts scored by a softmax over all
# of them; the shared experts summed and not averaged; the full layers
# turned like the window layers; the block run sequentially (the
# feed-forward on the norm of x + attn); the window one key short; a
# token's weights renormalised over the experts held here alone.
FAULTS = ("softmax_for_sigmoid", "shared_summed", "rope_on_full",
          "sequential_block", "window_off_by_one", "renormalised_over_held")


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


def _layer_norm(x, g, eps):
    c = x - x.mean(-1, keepdims=True)
    return c * jax.lax.rsqrt((c * c).mean(-1, keepdims=True) + eps) * g


def rope_tables(t, dim, theta):
    """``(cos, sin)`` (T, dim / 2) float32, in float64 on the host."""
    inv = float(theta) ** (-2.0 * np.arange(dim // 2, dtype=np.float64) / dim)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def _rope(x, cos, sin):
    """x: (B, H, T, D); turns channel pairs (2i, 2i+1)."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def embed(tokens, table):
    return table[tokens]


def attention(y, p, cfg, num, kind):
    b, t, _ = y.shape
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, g = cfg["head_dim"], heads // kvh
    q = _mm(y, p[kind + ".q"], num).reshape(b, t, heads, d)
    k = _mm(y, p[kind + ".k"], num).reshape(b, t, kvh, d)
    v = _mm(y, p[kind + ".v"], num).reshape(b, t, kvh, d)
    q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
    if kind == WINDOW or num.fault == "rope_on_full":
        cos, sin = rope_tables(t, d, cfg["rope_parameters"]["rope_theta"])
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    gap = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]   # t - s
    seen = gap >= 0
    if kind == WINDOW:
        seen = seen & (gap < cfg["sliding_window"]
                       - (num.fault == "window_off_by_one"))

    def group(qkv):
        """One row's one KV head, its g query heads one at a time."""
        qg, kg, vg = qkv                       # (g, T, D), (T, D), (T, D)

        def head(qh):
            s = jnp.einsum("qd,kd->qk", num.cast(qh), num.cast(kg),
                           precision=num.precision) / (d ** 0.5)
            w = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            return jnp.einsum("qk,kd->qd", num.cast(w), num.cast(vg),
                              precision=num.precision)

        return jax.lax.map(head, qg)

    out = jax.lax.map(group, (q.reshape(b * kvh, g, t, d),
                              k.reshape(b * kvh, t, d),
                              v.transpose(0, 2, 1, 3).reshape(b * kvh, t, d)))
    out = out.reshape(b, heads, t, d).transpose(0, 2, 1, 3)
    return _mm(out.reshape(b, t, heads * d), p[kind + ".o"], num)


def route(y, router, cfg, num):
    """``(N, experts)`` weights: 0 for an expert the token did not pick."""
    k = cfg["num_experts_per_tok"]
    z = _mm(y, router, num)
    s = jax.nn.softmax(z, axis=-1) if num.fault == "softmax_for_sigmoid" \
        else jax.nn.sigmoid(z)                                   # (N, E)
    # an expert's rank: how many come before it, the larger first and of
    # equals the lower index first
    e = jnp.arange(s.shape[-1])
    before = (s[:, None, :] > s[:, :, None]) | (
        (s[:, None, :] == s[:, :, None]) & (e[None, :] < e[:, None]))
    w = s * (before.sum(-1) < k).astype(s.dtype)
    if num.fault == "renormalised_over_held":
        first, count = held(cfg)
        here = (e >= first) & (e < first + count)
        return w / jnp.maximum((w * here).sum(-1, keepdims=True), 1e-30)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return w


def _experts(yf, gate, up, down, w, num):
    """``sum_e w[:, e] * E_e(yf)`` over the stacked experts, one at a
    time."""
    def one(acc, at):
        g, u, d, w_e = at
        hid = jax.nn.silu(jnp.einsum("ni,if->nf", num.cast(yf), num.cast(g),
                                     precision=num.precision)) \
            * jnp.einsum("ni,if->nf", num.cast(yf), num.cast(u),
                         precision=num.precision)
        out = jnp.einsum("nf,fi->ni", num.cast(hid), num.cast(d),
                         precision=num.precision)
        return acc + w_e[:, None] * out, None

    return jax.lax.scan(one, jnp.zeros_like(yf), (gate, up, down, w.T))[0]


def feed_forward(y, p, cfg, num):
    """The held routed experts' part of every token's sum, and the mean
    of the shared experts."""
    b, t, width = y.shape
    yf = y.reshape(b * t, width)
    first, count = held(cfg)
    w = route(yf, p["router"], cfg, num)[:, first:first + count]
    routed = _experts(yf, p["gate"], p["up"], p["down"], w, num)
    n_shared = p["shared_gate"].shape[0]
    each = 1.0 if num.fault == "shared_summed" else 1.0 / n_shared
    shared = _experts(yf, p["shared_gate"], p["shared_up"], p["shared_down"],
                      jnp.full((yf.shape[0], n_shared), each, yf.dtype), num)
    return (routed + shared).reshape(b, t, width)


def kind_of(p):
    return FULL if FULL + ".q" in p else WINDOW


def layer(x, p, cfg, num=EXACT):
    """One block over (B, T, hidden); ``p`` holds the layer's leaves
    without their ``layer<i>.`` prefix."""
    eps = cfg["layer_norm_eps"]
    y = _layer_norm(x, p["norm"], eps)
    attn = attention(y, p, cfg, num, kind_of(p))
    if num.fault == "sequential_block":
        y = _layer_norm(x + attn, p["norm"], eps)
    return x + attn + feed_forward(y, p, cfg, num)


def logits(x, norm, head, cfg, num=EXACT):
    """``head`` is the embedding's table (:class:`Tied`)."""
    return _mm(_layer_norm(x, norm, cfg["layer_norm_eps"]), head, num) \
        * cfg["logit_scale"]
