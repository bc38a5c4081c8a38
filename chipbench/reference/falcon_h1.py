"""Falcon-H1 (a Mamba-2 mixer beside rotary GQA attention in every block,
SwiGLU under a second norm) in plain float32 ``jax.numpy``: one full
forward pass over a whole sequence, the state-space recurrence computed
position by position (``lax.scan`` over time). No cache, no chunked
scan, no kernel, no batching tricks; every multiplier comes from the
configuration. ``W`` are stored (out, in).

    x0 = embedding_multiplier * E[tokens]
    y  = RMSNorm(h; input_norm)
    h  = h + ssm_out_multiplier * Mixer(ssm_in_multiplier * y)
           + attention_out_multiplier * Attn(attention_in_multiplier * y)
    h  = h + MLP(RMSNorm(h; ffn_norm))
    logits = lm_head_multiplier * W_head RMSNorm(h; norm)

Nothing here imports the program. ``drivers/serve_closed.py`` calls
``embed(tokens, table)`` without the configuration, and
``embedding_multiplier`` has to be applied all the same: **this module
keeps the configuration that ``param_shapes(cfg)`` was last given** (the
driver calls it first, to draw the weights) and ``embed`` reads the
multiplier from it; no driver file was added.

Departures from the published model, both listed in the configuration's
file: rotary embeddings turn adjacent channel pairs (2i, 2i+1), as the
program's ``apply_rope`` does, where the published code pairs (i, i +
d/2): with weights drawn at random the two differ by a fixed permutation
of the rows of ``W_q`` and ``W_k``; the mixer's small leaves are drawn
from the three kinds ``weights.py`` has (``assumed`` in the file).

``num`` says how matrices are multiplied and whether a fault is planted:
``EXACT`` for the reference, ``controls()`` for the control of ``correct``
(the nearest precision below the configuration's) and for the three
planted faults that show the check sees the mixer's state.
"""
import jax
import jax.numpy as jnp
import numpy as np

_CFG = {}   # the configuration param_shapes() was last given


def _mixer_sizes(cfg):
    d_ssm = cfg["mamba_d_ssm"]
    bc = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return d_ssm, bc, d_ssm + 2 * bc, cfg["mamba_n_heads"]


def param_shapes(cfg):
    _CFG.clear()
    _CFG.update(cfg)
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    d_ssm, bc, conv, heads = _mixer_sizes(cfg)
    s = {"embed": ((cfg["vocab_size"], h), "normal"),
         "norm": ((h,), "ones_normal"),
         "head": ((cfg["vocab_size"], h), "normal")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}."
        s[p + "input_norm"] = ((h,), "ones_normal")
        s[p + "in_proj"] = ((2 * d_ssm + 2 * bc + heads, h), "normal")
        s[p + "conv_w"] = ((conv, cfg["mamba_d_conv"]), "normal")
        s[p + "conv_b"] = ((conv,), "normal")
        s[p + "dt_bias"] = ((heads,), "normal")
        s[p + "a_log"] = ((heads,), "normal")
        s[p + "d"] = ((heads,), "ones_normal")
        s[p + "mixer_norm"] = ((d_ssm,), "ones_normal")
        s[p + "out_proj"] = ((h, d_ssm), "normal")
        s[p + "q"] = ((q, h), "normal")
        s[p + "k"] = ((kv, h), "normal")
        s[p + "v"] = ((kv, h), "normal")
        s[p + "o"] = ((h, q), "normal")
        s[p + "ffn_norm"] = ((h,), "ones_normal")
        s[p + "gate"] = ((f, h), "normal")
        s[p + "up"] = ((f, h), "normal")
        s[p + "down"] = ((h, f), "normal")
    return s


class Numerics:
    """``cast`` is applied to both operands of every matrix
    multiplication, which then runs at ``precision``. ``fault`` plants
    one of ``FAULTS`` in the mixer's state handling."""

    def __init__(self, cast, precision="highest", fault=None):
        self.cast, self.precision, self.fault = cast, precision, fault


def identity(x):
    return x


def to(dtype):
    def cast(x):
        return x.astype(dtype).astype(jnp.float32)
    return cast


EXACT = Numerics(identity)

# What a serving stack can do wrong to a recurrent state, planted in the
# reference's full pass: the state lost where the engine's calls meet
# (every ``mamba_chunk_size`` positions, the prefill chunk's length; the
# reference is handed tokens alone and cannot know where a row's prompt
# ends, so the hand-over to decode is planted in the program, by the
# tests), the mixer's branch left out, and a row starting from the state
# the row before it ended on (a lane that was not reset).
FAULTS = ("state_lost_at_chunk_edges", "mixer_left_out",
          "lane_not_reset")


def controls(precision):
    """The control's numerics for a configuration that states
    ``precision`` (below float32 stands bfloat16; ``high``, three bf16
    passes, is read beside it), and the planted faults, each the exact
    reference but for its fault."""
    out = {"float32": {"bfloat16": Numerics(to(jnp.bfloat16)),
                       "high": Numerics(identity, "high")}}[precision]
    out.update({"fault_" + f: Numerics(identity, fault=f) for f in FAULTS})
    return out


def _mm(x, w, num):
    return jnp.einsum("...i,oi->...o", num.cast(x), num.cast(w),
                      precision=num.precision)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (B, H, T, D); rotates channel pairs (2i, 2i+1) by
    t * theta^(-2i/D). The table is made in float64 on the host: at
    theta 1e11 the smallest frequencies are under float32's reach."""
    t, d = x.shape[2], x.shape[3]
    freqs = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(t, dtype=np.float64)[:, None] * freqs[None, :]
    cos, sin = np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def embed(tokens, table):
    return _CFG["embedding_multiplier"] * table[tokens]


def attention(u, p, cfg, num):
    b, t, _ = u.shape
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    q = _mm(u, p["q"], num).reshape(b, t, heads, d).transpose(0, 2, 1, 3)
    k = cfg["key_multiplier"] * _mm(u, p["k"], num)
    k = k.reshape(b, t, kvh, d).transpose(0, 2, 1, 3)
    v = _mm(u, p["v"], num).reshape(b, t, kvh, d).transpose(0, 2, 1, 3)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, heads // kvh, axis=1)
    v = jnp.repeat(v, heads // kvh, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", num.cast(q), num.cast(k),
                   precision=num.precision) / (d ** 0.5)
    ok = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    w = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
    a = jnp.einsum("bhqk,bhkd->bhqd", num.cast(w), num.cast(v),
                   precision=num.precision)
    return _mm(a.transpose(0, 2, 1, 3).reshape(b, t, heads * d), p["o"], num)


def mixer(u, p, cfg, num):
    """The Mamba-2 mixer over (B, T, hidden), position by position."""
    b, t, _ = u.shape
    d_ssm, bc, conv, heads = _mixer_sizes(cfg)
    groups, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    hd, k = cfg["mamba_d_head"], cfg["mamba_d_conv"]
    m_g, m_x, m_b, m_c, m_dt = cfg["ssm_multipliers"]
    proj = _mm(u, p["in_proj"], num)
    gate = m_g * proj[..., :d_ssm]
    xbc = proj[..., d_ssm:d_ssm + conv] * jnp.concatenate(
        [jnp.full(d_ssm, m_x), jnp.full(bc, m_b), jnp.full(bc, m_c)])
    dt = m_dt * proj[..., d_ssm + conv:]
    # causal depthwise conv: weight[:, K-1] meets the current position
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = p["conv_b"] + sum(pad[:, j:j + t] * p["conv_w"][:, j]
                            for j in range(k))
    xbc = jax.nn.silu(xbc)
    x = xbc[..., :d_ssm].reshape(b, t, heads, hd)
    bm = xbc[..., d_ssm:d_ssm + bc].reshape(b, t, groups, n)
    cm = xbc[..., d_ssm + bc:].reshape(b, t, groups, n)
    bm = jnp.repeat(bm, heads // groups, axis=2)            # (B,T,H,N)
    cm = jnp.repeat(cm, heads // groups, axis=2)
    delta = jax.nn.softplus(dt + p["dt_bias"])              # (B,T,H)
    a = -jnp.exp(p["a_log"])                                # (H,)
    lost = num.fault == "state_lost_at_chunk_edges"
    edge = (jnp.arange(t) % cfg["mamba_chunk_size"] == 0) & lost

    def step(s, at):
        x_t, b_t, c_t, d_t, edge_t = at
        s = jnp.where(edge_t, 0.0, s)
        s = jnp.exp(d_t * a)[..., None, None] * s + jnp.einsum(
            "bhp,bhn->bhpn", num.cast(d_t[..., None] * x_t), num.cast(b_t),
            precision=num.precision)
        y_t = jnp.einsum("bhpn,bhn->bhp", num.cast(s), num.cast(c_t),
                         precision=num.precision)
        return s, y_t

    def run(s0):
        return jax.lax.scan(step, s0, (
            x.transpose(1, 0, 2, 3), bm.transpose(1, 0, 2, 3),
            cm.transpose(1, 0, 2, 3), delta.transpose(1, 0, 2), edge))

    s0 = jnp.zeros((b, heads, hd, n), jnp.float32)
    if num.fault == "lane_not_reset":
        # every row starts from what the row before it ended on
        s0 = jnp.roll(run(s0)[0], 1, axis=0)
    y = run(s0)[1].transpose(1, 0, 2, 3) + p["d"][:, None] * x
    y = y.reshape(b, t, d_ssm) * jax.nn.silu(gate)
    yg = y.reshape(b, t, groups, d_ssm // groups)
    yg = yg * jax.lax.rsqrt((yg * yg).mean(-1, keepdims=True)
                            + cfg["rms_norm_eps"])
    return _mm(yg.reshape(b, t, d_ssm) * p["mixer_norm"], p["out_proj"], num)


def layer(x, p, cfg, num=EXACT):
    """One block over (B, T, hidden); ``p`` holds the layer's leaves
    without their ``layer<i>.`` prefix."""
    eps = cfg["rms_norm_eps"]
    y = _rms_norm(x, p["input_norm"], eps)
    x = x + cfg["attention_out_multiplier"] * attention(
        cfg["attention_in_multiplier"] * y, p, cfg, num)
    if num.fault != "mixer_left_out":
        x = x + cfg["ssm_out_multiplier"] * mixer(
            cfg["ssm_in_multiplier"] * y, p, cfg, num)
    z = _rms_norm(x, p["ffn_norm"], eps)
    m_gate, m_down = cfg["mlp_multipliers"]
    g = jax.nn.silu(m_gate * _mm(z, p["gate"], num)) * _mm(z, p["up"], num)
    return x + m_down * _mm(g, p["down"], num)


def logits(x, norm, head, cfg, num=EXACT):
    return cfg["lm_head_multiplier"] * _mm(
        _rms_norm(x, norm, cfg["rms_norm_eps"]), head, num)
