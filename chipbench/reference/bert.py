"""BERT pretraining step in plain float32 ``jax.numpy``.

Devlin et al. 2018: learned word, position and segment embeddings, a
post-norm encoder (attention, add & norm, GELU feed-forward, add & norm),
a tanh pooler, the MLM head (dense + GELU + LayerNorm, decoder tied to the
word embedding) and the NSP head; loss = mean MLM cross-entropy + mean NSP
cross-entropy; Adam with bias correction. Departures from the paper are
the configuration file's ``assumed`` entries (labels on every position,
no decoder bias, constant learning rate).

Nothing here imports the program. ``num`` says how matrices are
multiplied: ``EXACT`` for the reference, one of ``controls()`` (the
nearest precision below the one the configuration states) for the
control of ``correct``.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp


class Numerics:
    """``cast`` is applied to both operands of every matrix
    multiplication, which then runs at ``precision``; ``out`` is applied
    to its result."""

    def __init__(self, cast, precision="highest", out=None):
        self.cast, self.precision = cast, precision
        self.out = out or identity


def identity(x):
    return x


def through(dtype):
    """Round to ``dtype`` and back on the way forward, pass the gradient
    through unrounded: the mildest way to compute in a lower precision."""
    def cast(x):
        return x + jax.lax.stop_gradient(
            x.astype(dtype).astype(x.dtype) - x)
    return cast


def held_in(dtype):
    """Round to ``dtype`` and back, the value on the way forward and the
    gradient on the way back: what a tensor held in ``dtype`` goes
    through under mixed precision."""
    @jax.custom_vjp
    def cast(x):
        return x.astype(dtype).astype(x.dtype)

    def fwd(x):
        return cast(x), None

    def bwd(_, g):
        return (g.astype(dtype).astype(g.dtype),)

    cast.defvjp(fwd, bwd)
    return cast


EXACT = Numerics(identity)


def controls(precision):
    """The control's numerics for a configuration that states
    ``precision``. Below bfloat16 stands fp8: ``float8_e4m3fn`` holds
    every operand and result of a matrix multiplication, and its
    gradient, in e4m3 as the program's AMP holds them in bfloat16 (no
    loss scaling: the program has none); ``float8_e4m3fn_operands``
    rounds only the operands, forward, and is read beside it."""
    e4m3 = jnp.float8_e4m3fn
    return {"bfloat16": {
        "float8_e4m3fn": Numerics(held_in(e4m3), out=held_in(e4m3)),
        "float8_e4m3fn_operands": Numerics(through(e4m3)),
    }}[precision]


def param_shapes(cfg):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    s = {
        "embed.word": ((cfg["vocab_size"], h), "normal"),
        "embed.type": ((cfg["type_vocab_size"], h), "normal"),
        "embed.pos": ((cfg["max_position_embeddings"], h), "normal"),
        "embed.ln.g": ((h,), "ones_normal"),
        "embed.ln.b": ((h,), "normal"),
        "pooler.w": ((h, h), "normal"), "pooler.b": ((h,), "normal"),
        "mlm.dense.w": ((h, h), "normal"), "mlm.dense.b": ((h,), "normal"),
        "mlm.ln.g": ((h,), "ones_normal"), "mlm.ln.b": ((h,), "normal"),
        "nsp.w": ((2, h), "normal"), "nsp.b": ((2,), "normal"),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}."
        for n in "qkvo":
            s[p + n + ".w"] = ((h, h), "normal")
            s[p + n + ".b"] = ((h,), "normal")
        s[p + "ln1.g"] = ((h,), "ones_normal")
        s[p + "ln1.b"] = ((h,), "normal")
        s[p + "ffn1.w"] = ((f, h), "normal")
        s[p + "ffn1.b"] = ((f,), "normal")
        s[p + "ffn2.w"] = ((h, f), "normal")
        s[p + "ffn2.b"] = ((h,), "normal")
        s[p + "ln2.g"] = ((h,), "ones_normal")
        s[p + "ln2.b"] = ((h,), "normal")
    return s


def _dense(x, w, b, num):
    # weights are (out, in), as the published checkpoints store them
    return num.out(jnp.einsum("...i,oi->...o", num.cast(x), num.cast(w),
                              precision=num.precision)) + b


def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(p, tokens, cfg, eps, num):
    """(MLM logits (B, T, V), NSP logits (B, 2)); id 0 is padding and its
    positions are masked as keys."""
    b, t = tokens.shape
    heads = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // heads
    valid = (tokens != 0).sum(axis=1)
    key_ok = jnp.arange(t)[None, :] < valid[:, None]          # (B, T)

    def split(y):
        return y.reshape(b, t, heads, d).transpose(0, 2, 1, 3)

    x = p["embed.word"][tokens] + p["embed.type"][0] + p["embed.pos"][:t]
    x = _layer_norm(x, p["embed.ln.g"], p["embed.ln.b"], eps)
    for i in range(cfg["num_hidden_layers"]):
        q_ = f"layer{i}."
        q = split(_dense(x, p[q_ + "q.w"], p[q_ + "q.b"], num))
        k = split(_dense(x, p[q_ + "k.w"], p[q_ + "k.b"], num))
        v = split(_dense(x, p[q_ + "v.w"], p[q_ + "v.b"], num))
        s = num.out(jnp.einsum("bhqd,bhkd->bhqk", num.cast(q), num.cast(k),
                               precision=num.precision)) / math.sqrt(d)
        s = jnp.where(key_ok[:, None, None, :], s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        a = num.out(jnp.einsum("bhqk,bhkd->bhqd", num.cast(w), num.cast(v),
                               precision=num.precision))
        a = a.transpose(0, 2, 1, 3).reshape(b, t, heads * d)
        a = _dense(a, p[q_ + "o.w"], p[q_ + "o.b"], num)
        x = _layer_norm(x + a, p[q_ + "ln1.g"], p[q_ + "ln1.b"], eps)
        f = _gelu(_dense(x, p[q_ + "ffn1.w"], p[q_ + "ffn1.b"], num))
        f = _dense(f, p[q_ + "ffn2.w"], p[q_ + "ffn2.b"], num)
        x = _layer_norm(x + f, p[q_ + "ln2.g"], p[q_ + "ln2.b"], eps)
    pooled = jnp.tanh(_dense(x[:, 0], p["pooler.w"], p["pooler.b"], num))
    m = _gelu(_dense(x, p["mlm.dense.w"], p["mlm.dense.b"], num))
    m = _layer_norm(m, p["mlm.ln.g"], p["mlm.ln.b"], eps)
    mlm = num.out(jnp.einsum("bti,vi->btv", num.cast(m),
                             num.cast(p["embed.word"]),
                             precision=num.precision))
    nsp = _dense(pooled, p["nsp.w"], p["nsp.b"], num)
    return mlm, nsp


def _xent(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - picked


def rows_loss_sum(p, tokens, mlm_labels, nsp_labels, cfg, eps, num):
    """Sum over the rows given of each row's loss (its mean MLM
    cross-entropy over positions plus its NSP cross-entropy): the batch
    loss is this over all rows, divided by their number."""
    mlm, nsp = forward(p, tokens, cfg, eps, num)
    return (_xent(mlm, mlm_labels).mean(axis=1)
            + _xent(nsp, nsp_labels)).sum()


def leaf_norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for n, a in tree.items()}


_COMPILED = {}   # one trace of each program a process, whatever the seed


def _programs(cfg, train, num):
    key = (json.dumps(cfg, sort_keys=True, default=str),
           json.dumps(train, sort_keys=True), id(num))
    if key not in _COMPILED:
        grad_fn = jax.jit(jax.value_and_grad(functools.partial(
            rows_loss_sum, cfg=cfg, eps=train["layer_norm_eps"], num=num)))
        b1, b2, aeps = train["beta1"], train["beta2"], train["epsilon"]

        @jax.jit
        def adam(p, g, m, v, t, lr):
            m = {n: b1 * m[n] + (1 - b1) * g[n] for n in p}
            v = {n: b2 * v[n] + (1 - b2) * g[n] * g[n] for n in p}
            new = {n: p[n] - lr * (m[n] / (1 - b1 ** t))
                   / (jnp.sqrt(v[n] / (1 - b2 ** t)) + aeps) for n in p}
            return new, m, v

        add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
        _COMPILED[key] = (grad_fn, adam, add)
    return _COMPILED[key]


def train_steps(params, batches, cfg, train, rows_block, num=EXACT,
                take_rows=None, learning_rate=None):
    """Drive ``len(batches)`` Adam steps from ``params`` and return
    ``{"losses", "grad_norms", "change_norms", "first_grads"}``: each
    step's loss, every leaf's norm of the first step's gradient, every
    leaf's norm of its change over all the steps, and the first gradient
    itself. Each batch is walked in blocks of ``rows_block`` rows so that
    float32 activations fit. ``take_rows`` (a slice) plants the fault of
    a batch partly left out: the mean is then taken over those rows
    alone; ``learning_rate`` 0 plants a step that leaves its state
    unchanged."""
    grad_fn, adam, add = _programs(cfg, train, num)
    lr = train["learning_rate"] if learning_rate is None else learning_rate
    p = dict(params)
    m = {n: jnp.zeros_like(a) for n, a in p.items()}
    v = {n: jnp.zeros_like(a) for n, a in p.items()}
    losses, grad_norms, first = [], None, None
    for t, (tokens, (mlm_labels, nsp_labels)) in enumerate(batches, 1):
        if take_rows is not None:
            tokens, mlm_labels, nsp_labels = (
                tokens[take_rows], mlm_labels[take_rows],
                nsp_labels[take_rows])
        rows = tokens.shape[0]
        total, grads = 0.0, None
        for r in range(0, rows, rows_block):
            sl = slice(r, r + rows_block)
            val, g = grad_fn(p, jnp.asarray(tokens[sl]),
                             jnp.asarray(mlm_labels[sl]),
                             jnp.asarray(nsp_labels[sl]))
            total = total + val
            grads = g if grads is None else add(grads, g)
        grads = {n: g / rows for n, g in grads.items()}
        losses.append(float(total) / rows)
        if grad_norms is None:
            first = grads
            grad_norms = {n: float(x) for n, x in leaf_norms(grads).items()}
        p, m, v = adam(p, grads, m, v, jnp.float32(t), jnp.float32(lr))
    change = leaf_norms({n: p[n] - params[n] for n in p})
    return {"losses": losses, "grad_norms": grad_norms, "first_grads": first,
            "change_norms": {n: float(x) for n, x in change.items()}}


def diff_norms(a, b):
    """Every leaf's norm of ``a - b``."""
    return {n: float(x) for n, x in jax.jit(lambda a, b: leaf_norms(
        {n: a[n] - b[n] for n in b}))(a, b).items()}
