"""GigaChat 3.5 (``gigachat3_5``: Gated DeltaNet linear attention in three
layers of four, gated latent attention in the fourth, leading dense
feed-forward layers, then 8 of 256 sigmoid-routed experts chosen with a
score-correction bias beside one shared expert, an untied head) in plain
float32 ``jax.numpy``: one full forward pass over whole sequences. The
delta rule runs **position by position** (``lax.scan`` over time, the
recurrence as written), never in chunks; latent attention is
**non-absorbed** (every head's keys and values decompressed from the
latent); no cache, no pages, no kernel, no sort. ``W`` of the projections
are stored (out, in); ``gate`` and ``up`` are (experts, hidden, width),
``down`` (experts, width, hidden), the shared ones alike.

Every norm of a block and the last one are zero-centred, ``N(x; w) =
x / sqrt(mean(x^2) + eps) * (1 + w)`` (``rms_norm_eps``)::

    h = x + N(Mix(N(x; input_norm)); post_mixer_norm)
    x' = h + N(FFN(N(h; ffn_norm)); post_ffn_norm)

A linear layer's Mix (``linear_*`` keys: Hk key heads, Hv value heads of
K and V, value head h on key head h // (Hv / Hk))::

    [q | k | v | z] = y W_qkvz,  [b | a] = y W_ba
    [q | k | v] = silu(causal depthwise conv, width linear_conv_kernel_dim,
                       no bias, over the 2 Hk K + Hv V channels)
    q, k divided by their norm a head (sqrt(sum x^2 + 1e-6)), q / sqrt(K)
    beta = sigmoid(b),  g = -exp(A_log) softplus(a + dt_bias)
    S_t = exp(g_t) (S_{t-1} - beta_t k_t (k_t^T S_{t-1})) + beta_t k_t v_t^T
    o_t = S_t^T q_t                       S_0 = 0 at a sequence's start
    Mix = (N_head(o; mixer_norm, linear_attn_o_norm_eps)
           * linear_sigmoid_gate_scale * sigmoid(z)) W_out

The full layer's Mix is A.X-K1's latent attention (``reference/ax_k1.py``:
YaRN tables, scale (qk_nope + qk_rope)^-1/2 m^2) with its heads' output
times ``sigmoid(y W_gate)`` before ``W_o``. The feed-forward: layer < the
dense count, ``(silu(min(z G, L)) * clip(z U, -L, L)) D`` with ``L =
swiglu_limit``; else ``s = sigmoid(z W_r)`` over all experts, the 8
largest of ``s + score_bias`` chosen (a tie: the lower index), ``w_e =
routed_scaling_factor s_e / sum of s over the chosen`` and ``sum over the
held chosen e of w_e E_e(z) + E_shared(z)``, each expert clamped alike.

Departures, each in the configuration's ``assumed`` or ``notes``: the held
experts are a range of the 256 (``experts_held``), what the absent experts
would add is left out; the vocabulary is the rows held; rotary pairs are
adjacent (``rope_interleave``).

The work is done in blocks so that the check's sizes fit one chip: the
scores a head and a block of queries at a time, the experts one at a time.

Nothing here imports the program. ``layer(x, p, cfg, num)`` is not told
which layer it is: a linear layer's leaves hold ``qkvz``, a dense
layer's ``dense.*``.

``num`` says how matrices are multiplied and whether a fault is planted:
``EXACT`` for the reference, ``controls()`` for the control of ``correct``
(bfloat16 operands) and the planted faults.
"""
import jax
import jax.numpy as jnp

from reference.ax_k1 import Numerics, _mm, _rank, _rope, held, identity, \
    mscale, rope_tables, router_width, to

EXACT = Numerics(identity)

# What a serving stack can get wrong in this model's mechanisms, planted
# in the reference's full pass: a row starting from the state the row
# before it ended on (a lane not reset at a new tenant); the decay left
# out; beta left out (1); q and k not divided by their norms; the conv's
# inputs lost where the engine's calls meet (every CHUNK positions); the
# gate's factor of 2 left out; the attention's output gate left out; the
# correction bias put in the weights as well as the choice.
FAULTS = ("lane_not_reset", "decay_left_out", "beta_left_out",
          "qk_not_normalised", "conv_state_lost_at_chunk_edges",
          "gate_factor_left_out", "attention_gate_left_out",
          "bias_in_weights")

# the engine's prefill chunk: one page of 128 positions
CHUNK = 128
# queries a block of the attention's scores
QUERY_BLOCK = 1024


def controls(precision):
    """The control's numerics for a configuration that states
    ``precision`` (below float32 stands bfloat16), and the planted
    faults, each the exact reference but for its fault."""
    out = {"float32": {"bfloat16": Numerics(to(jnp.bfloat16))}}[precision]
    out.update({"fault_" + f: Numerics(identity, fault=f) for f in FAULTS})
    return out


def linear_sizes(cfg):
    """``(Hk, Hv, K, V, conv channels)``."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return hk, hv, dk, dv, 2 * hk * dk + hv * dv


def is_full(cfg, i):
    return i in cfg["full_attention_layers"]


def dense_layers(cfg):
    """How many of the cut's layers lead with a dense feed-forward: those
    whose source layer (``source_layers``) is under
    ``first_k_dense_replace``."""
    src = cfg["source_layers"]
    n = sum(s < cfg["first_k_dense_replace"] for s in src)
    if any(s >= cfg["first_k_dense_replace"] for s in src[:n]):
        raise ValueError("the dense layers lead the cut")
    return n


def param_shapes(cfg):
    h = cfg["hidden_size"]
    hk, hv, dk, dv, conv = linear_sizes(cfg)
    heads = cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    n, r, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    f, e, sh = cfg["moe_intermediate_size"], held(cfg)[1], \
        cfg["n_shared_experts"]
    if cfg["tie_word_embeddings"]:
        raise ValueError("this reference computes the untied head alone")
    table = ((cfg["vocab_size"], h), "normal")
    # a zero-centred gain is drawn about 0 (the gain itself about 1)
    s = {"embed": table, "norm": ((h,), "normal"), "head": table}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}."
        for norm in ("input_norm", "post_mixer_norm", "ffn_norm",
                     "post_ffn_norm"):
            s[p + norm] = ((h,), "normal")
        if is_full(cfg, i):
            s[p + "q_a"] = ((qr, h), "normal")
            s[p + "q_norm"] = ((qr,), "ones_normal")
            s[p + "q_b"] = ((heads * (n + r), qr), "normal")
            s[p + "kv_a"] = ((kvr + r, h), "normal")
            s[p + "kv_norm"] = ((kvr,), "ones_normal")
            s[p + "kv_b"] = ((heads * (n + v), kvr), "normal")
            s[p + "attn_gate"] = ((heads * v, h), "normal")
            s[p + "o"] = ((h, heads * v), "normal")
        else:
            s[p + "qkvz"] = ((conv + hv * dv, h), "normal")
            s[p + "ba"] = ((2 * hv, h), "normal")
            s[p + "conv"] = ((conv, cfg["linear_conv_kernel_dim"]),
                             "normal")
            s[p + "a_log"] = ((hv,), "normal")
            s[p + "dt_bias"] = ((hv,), "normal")
            s[p + "mixer_norm"] = ((dv,), "normal")
            s[p + "out"] = ((h, hv * dv), "normal")
        if i < dense_layers(cfg):
            wide = cfg["intermediate_size"]
            s[p + "dense.gate"] = ((wide, h), "normal")
            s[p + "dense.up"] = ((wide, h), "normal")
            s[p + "dense.down"] = ((h, wide), "normal")
            continue
        s[p + "router"] = ((router_width(cfg), h), "normal")
        s[p + "score_bias"] = ((router_width(cfg),), "normal")
        s[p + "gate"] = ((e, h, f), "normal")
        s[p + "up"] = ((e, h, f), "normal")
        s[p + "down"] = ((e, f, h), "normal")
        s[p + "shared_gate"] = ((sh, h, f), "normal")
        s[p + "shared_up"] = ((sh, h, f), "normal")
        s[p + "shared_down"] = ((sh, f, h), "normal")
    return s


def _norm(x, w, eps):
    """Zero-centred RMSNorm: the gain is ``1 + w``."""
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * (1.0 + w)


def _plain_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def embed(tokens, table):
    return table[tokens]


# -- Gated DeltaNet -----------------------------------------------------------

def _conv(x, w, hist, fault):
    """Causal depthwise conv of (B, T, C) with ``hist`` (B, K-1, C) the
    inputs before the first; no bias. With the fault the inputs before
    the CHUNK a position lies in are taken as 0."""
    t, k = x.shape[1], w.shape[1]
    win = jnp.concatenate([hist, x], axis=1)
    out = jnp.zeros_like(x)
    pos = jnp.arange(t)
    for j in range(k):
        term = win[:, j:j + t] * w[:, j]
        if fault == "conv_state_lost_at_chunk_edges":
            src = pos - (k - 1) + j
            term = jnp.where((src >= pos // CHUNK * CHUNK)[None, :, None],
                             term, 0.0)
        out = out + term
    return out, win[:, t:]


def _rule(q, k, v, g, beta, s0, num):
    """The recurrence, position by position: q, k (B, T, H, K) already
    normalised and repeated to the value heads, v (B, T, H, V), g, beta
    (B, T, H), s0 (B, H, K, V). Returns ``(o (B, T, H, V), S_T)``."""
    c = num.cast

    def step(s, at):
        qt, kt, vt, gt, bt = at
        ks = jnp.sum(c(kt)[..., None] * c(s), axis=2)          # k^T S
        s = jnp.exp(gt)[..., None, None] * (
            s - bt[..., None, None] * kt[..., :, None] * ks[:, :, None, :]) \
            + bt[..., None, None] * kt[..., :, None] * vt[:, :, None, :]
        return s, jnp.sum(c(qt)[..., None] * c(s), axis=2)     # S^T q

    tm = (q, k, v, g, beta)
    s, o = jax.lax.scan(step, s0, tuple(jnp.moveaxis(a, 1, 0) for a in tm))
    return jnp.moveaxis(o, 0, 1), s


def linear_mixer(y, p, cfg, num):
    """(B, T, hidden) -> (B, T, hidden)."""
    b, t, _ = y.shape
    hk, hv, dk, dv, conv = linear_sizes(cfg)
    fault = num.fault
    qkvz = _mm(y, p["qkvz"], num)
    ba = _mm(y, p["ba"], num)
    z = qkvz[..., conv:].reshape(b, t, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    if fault == "decay_left_out":
        g = jnp.zeros_like(g)
    if fault == "beta_left_out":
        beta = jnp.ones_like(beta)

    def unit(x):
        if fault == "qk_not_normalised":
            return x
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def rows(x_in, g_rows, beta_rows, hist, s0):
        """The conv and the rule over rows that start from ``hist``
        and ``s0``."""
        xc, hist = _conv(x_in, p["conv"], hist, fault)
        xc = jax.nn.silu(xc)
        nb = x_in.shape[0]
        q = unit(xc[..., :hk * dk].reshape(nb, t, hk, dk)) * dk ** -0.5
        k = unit(xc[..., hk * dk:2 * hk * dk].reshape(nb, t, hk, dk))
        rep = hv // hk
        return (*_rule(jnp.repeat(q, rep, 2), jnp.repeat(k, rep, 2),
                       xc[..., 2 * hk * dk:].reshape(nb, t, hv, dv),
                       g_rows, beta_rows, s0, num), hist)

    k_conv = cfg["linear_conv_kernel_dim"]
    x_in = qkvz[..., :conv]
    if fault == "lane_not_reset":
        # a row starts from the conv inputs and the state the row
        # before it ended on
        hist = jnp.zeros((1, k_conv - 1, conv), y.dtype)
        s0 = jnp.zeros((1, hv, dk, dv), y.dtype)
        outs = []
        for i in range(b):
            o_i, s0, hist = rows(x_in[i:i + 1], g[i:i + 1], beta[i:i + 1],
                                 hist, s0)
            outs.append(o_i)
        o = jnp.concatenate(outs, axis=0)
    else:
        o, _, _ = rows(x_in, g, beta,
                       jnp.zeros((b, k_conv - 1, conv), y.dtype),
                       jnp.zeros((b, hv, dk, dv), y.dtype))
    scale = cfg["linear_sigmoid_gate_scale"]
    if fault == "gate_factor_left_out":
        scale = 1.0
    o = _norm(o, p["mixer_norm"], cfg["linear_attn_o_norm_eps"]) \
        * scale * jax.nn.sigmoid(z)
    return _mm(o.reshape(b, t, hv * dv), p["out"], num)


# -- gated latent attention ---------------------------------------------------

def latent_attention(y, p, cfg, num):
    """(B, T, hidden) -> (B, T, hidden), non-absorbed: a sequence at a
    time, a head at a time with its own decompressed keys and values,
    the scores a block of queries at a time."""
    t = y.shape[1]
    heads, kvr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    n, r, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    eps = cfg["rms_norm_eps"]
    cos, sin = rope_tables(t, r, cfg)
    scale = (n + r) ** -0.5 * mscale(cfg) ** 2
    w_kvb = p["kv_b"].reshape(heads, n + v, kvr)
    blk = min(QUERY_BLOCK, t)

    def sequence(ys):                                        # (T, hidden)
        c_q = _plain_norm(_mm(ys, p["q_a"], num), p["q_norm"], eps)
        q = _mm(c_q, p["q_b"], num).reshape(t, heads, n + r) \
            .transpose(1, 0, 2)                              # (H, T, n + r)
        kv = _mm(ys, p["kv_a"], num)
        c_kv = _plain_norm(kv[:, :kvr], p["kv_norm"], eps)
        k_pe = _rope(kv[:, kvr:], cos, sin)

        def head(at):
            qh, w = at                          # (T, n + r), (n + v, kvr)
            kvh = _mm(c_kv, w, num)                          # (T, n + v)
            k = jnp.concatenate([kvh[:, :n], k_pe], axis=-1)
            qh = jnp.concatenate([qh[:, :n], _rope(qh[:, n:], cos, sin)],
                                 axis=-1)
            outs = []
            for lo in range(0, t, blk):
                hi = min(lo + blk, t)
                s = jnp.einsum("qd,kd->qk", num.cast(qh[lo:hi]), num.cast(k),
                               precision=num.precision) * scale
                seen = jnp.arange(lo, hi)[:, None] >= jnp.arange(t)[None, :]
                w_ = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
                outs.append(jnp.einsum("qk,kd->qd", num.cast(w_),
                                       num.cast(kvh[:, n:]),
                                       precision=num.precision))
            return jnp.concatenate(outs, axis=0)

        out = jax.lax.map(head, (q, w_kvb)).transpose(1, 0, 2) \
            .reshape(t, heads * v)                           # (T, H v)
        if num.fault != "attention_gate_left_out":
            out = out * jax.nn.sigmoid(_mm(ys, p["attn_gate"], num))
        return _mm(out, p["o"], num)

    return jax.lax.map(sequence, y)


# -- feed-forward -------------------------------------------------------------

def _swiglu(zf, gate, up, down, limit, num):
    """``(silu(min(z G, L)) * clip(z U, -L, L)) D`` with ``G``, ``U``
    (hidden, width) and ``D`` (width, hidden)."""
    def mm(a, w):
        return jnp.einsum("ni,if->nf", num.cast(a), num.cast(w),
                          precision=num.precision)

    hid = jax.nn.silu(jnp.minimum(mm(zf, gate), limit)) \
        * jnp.clip(mm(zf, up), -limit, limit)
    return mm(hid, down)


def _experts(zf, gate, up, down, w, limit, num):
    """``sum_e w[:, e] * E_e(zf)`` over the stacked experts, one at a
    time."""
    def one(acc, at):
        g, u, d, w_e = at
        return acc + w_e[:, None] * _swiglu(zf, g, u, d, limit, num), None

    return jax.lax.scan(one, jnp.zeros_like(zf), (gate, up, down, w.T))[0]


def route(z, router, bias, cfg, num):
    """``(N, experts)`` weights: 0 for an expert the token did not pick."""
    s = jax.nn.sigmoid(_mm(z, router, num))                    # (N, E)
    chosen = _rank(s + bias) < cfg["num_experts_per_tok"]
    w = (s + bias if num.fault == "bias_in_weights" else s) \
        * chosen.astype(s.dtype)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return w * cfg["routed_scaling_factor"]


def routed_feed_forward(z, p, cfg, num):
    """A sequence at a time: the held routed experts' part of every
    token's sum, and the shared expert's."""
    first, count = held(cfg)
    limit = cfg["swiglu_limit"]

    def sequence(zs):
        w = route(zs, p["router"], p["score_bias"], cfg, num)[
            :, first:first + count]
        out = _experts(zs, p["gate"], p["up"], p["down"], w, limit, num)
        n_shared = p["shared_gate"].shape[0]
        return out + _experts(
            zs, p["shared_gate"], p["shared_up"], p["shared_down"],
            jnp.ones((zs.shape[0], n_shared), zs.dtype), limit, num)

    return jax.lax.map(sequence, z)


def dense_feed_forward(z, p, cfg, num):
    def sequence(zs):
        return _swiglu(zs, p["dense.gate"].T, p["dense.up"].T,
                       p["dense.down"].T, cfg["swiglu_limit"], num)

    return jax.lax.map(sequence, z)


def layer(x, p, cfg, num=EXACT):
    """One block over (B, T, hidden); ``p`` holds the layer's leaves
    without their ``layer<i>.`` prefix."""
    eps = cfg["rms_norm_eps"]
    mix = linear_mixer if "qkvz" in p else latent_attention
    h = x + _norm(mix(_norm(x, p["input_norm"], eps), p, cfg, num),
                  p["post_mixer_norm"], eps)
    z = _norm(h, p["ffn_norm"], eps)
    ffn = dense_feed_forward if "dense.gate" in p else routed_feed_forward
    return h + _norm(ffn(z, p, cfg, num), p["post_ffn_norm"], eps)


def logits(x, norm, head, cfg, num=EXACT):
    return _mm(_norm(x, norm, cfg["rms_norm_eps"]), head, num)
