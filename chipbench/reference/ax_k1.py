"""A.X-K1 (``axk1``: latent attention, a leading dense layer, 8 of 192
sigmoid-routed experts chosen inside 4 of 8 groups beside one shared
expert, an untied head) in plain float32 ``jax.numpy``: one full forward
pass over whole sequences, in the **non-absorbed** form. No cache, no
pages, no absorbed product, no sort, no grouped product, no kernel: every
head's keys and values are decompressed from the latent, the scores are a
full matrix under a causal mask, the routed experts are a loop over those
held with a 0/1 mask, the groups and the experts are chosen by an explicit
ranking. ``W`` of the projections are stored (out, in); ``gate`` and
``up`` are (experts, hidden, width), ``down`` (experts, width, hidden),
the shared ones alike.

For every layer, stream x of shape (T, hidden), RMSNorm ``rms_norm_eps``::

    y    = RMSNorm(x; norm1)
    c_q  = RMSNorm(y W_qa; q_norm)                      q_lora_rank
    [q_nope | q_pe] = c_q W_qb          a head: qk_nope + qk_rope
    [c_kv | k_pe]   = y W_kva           kv_lora_rank + qk_rope, k_pe shared
    c_kv = RMSNorm(c_kv; kv_norm)
    [k_nope | v] = c_kv W_kvb           a head: qk_nope + v_head
    q_pe, k_pe turned at YaRN's frequencies, pairs (2i, 2i+1)
    score = (q_nope . k_nope + q_pe . k_pe) * (qk_nope + qk_rope)^-1/2 * m^2
    m = 0.1 mscale_all_dim ln(factor) + 1
    h = x + concat_h(softmax_causal(score) v) W_o
    z = RMSNorm(h; norm2)
    layer < first_k_dense_replace:  x' = h + (silu(z G) * (z U)) D
    else:
      s = sigmoid(z W_r) over all experts, float32
      a group (n_routed / n_group consecutive experts) scores the sum of
        its two largest s; the topk_group best groups are kept (a tie
        goes to the lower index)
      S = the k largest s among the kept groups' experts (tie: lower index)
      w_e = routed_scaling_factor * s_e / sum of s over S   (norm_topk_prob)
      x' = h + sum over e in S that are held of w_e E_e(z) + shared(z)

then ``RMSNorm(x; norm)`` and the untied head.

**Departures from the published model**, each in the configuration's
``assumed`` or ``notes``: ``topk_method`` ``"none"`` is read as "no score
correction bias" with the group limit that ``n_group`` and ``topk_group``
state; rotary pairs adjacent (the source interleaves: a fixed permutation
of ``W_qb``'s and ``W_kva``'s columns); the held experts are a range
``(first, count)`` of the 192 (``experts_held``: one chip's share), a
token's weights are normalised over its k experts wherever they are held,
and what the absent experts would add is left out; the vocabulary is the
rows held.

The work is done in blocks so that the check's sizes fit one chip beside
nothing else: a sequence at a time, the scores one query head at a time
with that head's keys and values decompressed for it alone, the experts
one at a time, the ranking a sequence at a time.

Nothing here imports the program. The driver calls ``layer(x, p, cfg,
num)`` without saying which layer it is, so a layer's kind is told from
what it is handed: **the dense layer's feed-forward leaves are named
``dense.*``**, which also makes the two kinds two traces.

``num`` says how matrices are multiplied and whether a fault is planted:
``EXACT`` for the reference, ``controls()`` for the control of ``correct``
(the nearest precision below the configuration's) and for the planted
faults that show the check sees the rotation of the shared key, the
latent's norm, YaRN's factor in the scale, the group limit, the routed
factor, the shared expert and the dense layer's width.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np


def held(cfg):
    """``(first, count)`` of the routed experts whose weights are held:
    the first ``n_routed_experts`` (the file's count of experts held
    here) unless the configuration gives a range."""
    first, count = cfg.get("experts_held") or (0, cfg["n_routed_experts"])
    return int(first), int(count)


def router_width(cfg):
    """The router scores all the experts of the layer (``router_experts``:
    the published ``n_routed_experts``), whatever is held here."""
    return int(cfg["router_experts"])


def is_dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def param_shapes(cfg):
    h = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    n, r, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    f, e, sh = cfg["moe_intermediate_size"], held(cfg)[1], \
        cfg["n_shared_experts"]
    if cfg["tie_word_embeddings"]:
        raise ValueError("this reference computes the untied head alone")
    table = ((cfg["vocab_size"], h), "normal")
    s = {"embed": table, "norm": ((h,), "ones_normal"), "head": table}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}."
        s[p + "norm1"] = ((h,), "ones_normal")
        s[p + "q_a"] = ((qr, h), "normal")
        s[p + "q_norm"] = ((qr,), "ones_normal")
        s[p + "q_b"] = ((heads * (n + r), qr), "normal")
        s[p + "kv_a"] = ((kvr + r, h), "normal")
        s[p + "kv_norm"] = ((kvr,), "ones_normal")
        s[p + "kv_b"] = ((heads * (n + v), kvr), "normal")
        s[p + "o"] = ((h, heads * v), "normal")
        s[p + "norm2"] = ((h,), "ones_normal")
        if is_dense(cfg, i):
            wide = cfg["intermediate_size"]
            s[p + "dense.gate"] = ((wide, h), "normal")
            s[p + "dense.up"] = ((wide, h), "normal")
            s[p + "dense.down"] = ((h, wide), "normal")
            continue
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
# in the reference's full pass: the shared rope key cached unrotated; the
# latent cached without its RMSNorm; YaRN's factor squared left out of the
# softmax scale; the 8 taken from all 192 with no group limit; the routed
# sum not multiplied by routed_scaling_factor; the shared expert left out;
# the first layer's feed-forward run at an expert's width, as the every-
# token part of a routed layer is (first_k_dense_replace ignored: the
# reading of "layer 0 routed like the others" that needs no router leaf,
# which a dense layer has not got).
FAULTS = ("rope_key_unrotated", "latent_norm_left_out",
          "mscale_left_out", "group_limit_left_out",
          "routed_scale_left_out", "shared_expert_left_out",
          "first_layer_at_expert_width")


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


def mscale(cfg):
    """YaRN's attention factor ``m``: the softmax scale carries its
    square; the tables carry ``mscale / mscale_all_dim``'s, which is 1
    for this model."""
    rs = cfg["rope_scaling"]
    if rs["factor"] <= 1:
        return 1.0
    return 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0


def rope_tables(t, dim, cfg):
    """``(cos, sin)`` (T, dim / 2) float32 at YaRN's frequencies, in
    float64 on the host: a pair that turns more than ``beta_fast`` times
    over the original context keeps its frequency, one that turns fewer
    than ``beta_slow`` times has it divided by ``factor``, a linear blend
    by pair between."""
    theta, rs = float(cfg["rope_theta"]), cfg["rope_scaling"]
    if rs["type"] != "yarn" or rs["mscale"] != rs["mscale_all_dim"]:
        raise ValueError("YaRN tables with no factor of their own alone")
    pair = np.arange(dim // 2, dtype=np.float64)
    inv = theta ** (-2.0 * pair / dim)
    orig = rs["original_max_position_embeddings"]

    def pair_of(turns):
        return dim * math.log(orig / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rs["beta_slow"])), dim - 1)
    ramp = np.clip((pair - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = (1.0 - ramp) * inv + ramp * inv / rs["factor"]
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def _rope(x, cos, sin):
    """x: (..., T, D); turns channel pairs (2i, 2i+1)."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def embed(tokens, table):
    return table[tokens]


def attention(y, p, cfg, num):
    """(B, T, hidden) -> (B, T, hidden), non-absorbed: a sequence at a
    time, a head at a time with its own decompressed keys and values."""
    t = y.shape[1]
    heads, kvr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    n, r, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    eps = cfg["rms_norm_eps"]
    cos, sin = rope_tables(t, r, cfg)
    scale = (n + r) ** -0.5
    if num.fault != "mscale_left_out":
        scale *= mscale(cfg) ** 2
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    w_kvb = p["kv_b"].reshape(heads, n + v, kvr)

    def sequence(ys):                                        # (T, hidden)
        c_q = _rms_norm(_mm(ys, p["q_a"], num), p["q_norm"], eps)
        q = _mm(c_q, p["q_b"], num).reshape(t, heads, n + r) \
            .transpose(1, 0, 2)                              # (H, T, n + r)
        kv = _mm(ys, p["kv_a"], num)
        c_kv, k_pe = kv[:, :kvr], kv[:, kvr:]
        if num.fault != "latent_norm_left_out":
            c_kv = _rms_norm(c_kv, p["kv_norm"], eps)
        if num.fault != "rope_key_unrotated":
            k_pe = _rope(k_pe, cos, sin)

        def head(at):
            qh, w = at                          # (T, n + r), (n + v, kvr)
            kvh = _mm(c_kv, w, num)                          # (T, n + v)
            k = jnp.concatenate([kvh[:, :n], k_pe], axis=-1)
            qh = jnp.concatenate([qh[:, :n], _rope(qh[:, n:], cos, sin)],
                                 axis=-1)
            s = jnp.einsum("qd,kd->qk", num.cast(qh), num.cast(k),
                           precision=num.precision) * scale
            w_ = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            return jnp.einsum("qk,kd->qd", num.cast(w_),
                              num.cast(kvh[:, n:]), precision=num.precision)

        out = jax.lax.map(head, (q, w_kvb))                  # (H, T, v)
        return _mm(out.transpose(1, 0, 2).reshape(t, heads * v), p["o"],
                   num)

    return jax.lax.map(sequence, y)


def _rank(s):
    """How many entries of the last axis come before each: the larger
    first and of equals the lower index first."""
    e = jnp.arange(s.shape[-1])
    before = (s[..., None, :] > s[..., :, None]) | (
        (s[..., None, :] == s[..., :, None]) & (e[None, :] < e[:, None]))
    return before.sum(-1)


def route(z, router, cfg, num):
    """``(N, experts)`` weights: 0 for an expert the token did not pick."""
    k, groups, keep = cfg["num_experts_per_tok"], cfg["n_group"], \
        cfg["topk_group"]
    s = jax.nn.sigmoid(_mm(z, router, num))                    # (N, E)
    n_tok, e = s.shape
    allowed = jnp.ones_like(s, bool)
    if num.fault != "group_limit_left_out":
        by_group = s.reshape(n_tok, groups, e // groups)
        score = jnp.sort(by_group, axis=-1)[..., -2:].sum(-1)  # (N, groups)
        allowed = jnp.repeat(_rank(score) < keep, e // groups, axis=-1)
    chosen = allowed & (_rank(jnp.where(allowed, s, -1.0)) < k)
    w = s * chosen.astype(s.dtype)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    if num.fault != "routed_scale_left_out":
        w = w * cfg["routed_scaling_factor"]
    return w


def _experts(zf, gate, up, down, w, num):
    """``sum_e w[:, e] * E_e(zf)`` over the stacked experts, one at a
    time."""
    def one(acc, at):
        g, u, d, w_e = at
        hid = jax.nn.silu(jnp.einsum("ni,if->nf", num.cast(zf), num.cast(g),
                                     precision=num.precision)) \
            * jnp.einsum("ni,if->nf", num.cast(zf), num.cast(u),
                         precision=num.precision)
        out = jnp.einsum("nf,fi->ni", num.cast(hid), num.cast(d),
                         precision=num.precision)
        return acc + w_e[:, None] * out, None

    return jax.lax.scan(one, jnp.zeros_like(zf), (gate, up, down, w.T))[0]


def routed_feed_forward(z, p, cfg, num):
    """A sequence at a time: the held routed experts' part of every
    token's sum, and the shared experts' sum."""
    first, count = held(cfg)

    def sequence(zs):
        w = route(zs, p["router"], cfg, num)[:, first:first + count]
        out = _experts(zs, p["gate"], p["up"], p["down"], w, num)
        if num.fault == "shared_expert_left_out":
            return out
        n_shared = p["shared_gate"].shape[0]
        return out + _experts(
            zs, p["shared_gate"], p["shared_up"], p["shared_down"],
            jnp.ones((zs.shape[0], n_shared), zs.dtype), num)

    return jax.lax.map(sequence, z)


def dense_feed_forward(z, p, cfg, num):
    gate, up, down = p["dense.gate"], p["dense.up"], p["dense.down"]
    if num.fault == "first_layer_at_expert_width":
        f = cfg["moe_intermediate_size"]
        gate, up, down = gate[:f], up[:f], down[:, :f]

    def sequence(zs):
        return _mm(jax.nn.silu(_mm(zs, gate, num)) * _mm(zs, up, num), down,
                   num)

    return jax.lax.map(sequence, z)


def layer(x, p, cfg, num=EXACT):
    """One block over (B, T, hidden); ``p`` holds the layer's leaves
    without their ``layer<i>.`` prefix."""
    eps = cfg["rms_norm_eps"]
    h = x + attention(_rms_norm(x, p["norm1"], eps), p, cfg, num)
    z = _rms_norm(h, p["norm2"], eps)
    if "dense.gate" in p:
        return h + dense_feed_forward(z, p, cfg, num)
    return h + routed_feed_forward(z, p, cfg, num)


def logits(x, norm, head, cfg, num=EXACT):
    return _mm(_rms_norm(x, norm, cfg["rms_norm_eps"]), head, num)
