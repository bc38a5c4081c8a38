"""Neural-network operators (the ``npx`` op family), TPU-first.

Reference: ``src/operator/nn/`` (31k LoC of hand-written CPU/cuDNN/oneDNN
kernels — convolution, fully_connected, batch_norm, pooling, softmax,
dropout, ...; e.g. ``fully_connected.cc:251`` registers ``_npx_fully_connected``).

TPU design: every op is a pure JAX function lowering to ``lax`` primitives —
XLA maps conv/matmul onto the MXU and fuses the elementwise epilogues, which
is the role cuDNN autotuning + pointwise fusion (``src/operator/fusion/``)
play in the reference. Layout is NCHW at the API (reference default) but
convolutions compute through XLA's layout-agnostic ``conv_general_dilated``
so the compiler picks the MXU-friendly internal layout.

All public functions accept NDArray (or raw jax arrays) and route through the
dispatch layer for autograd.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import numpy as _onp

from .. import autograd
from .. import random as _rng
from ..base import MXNetError
from ..profiler.core import device_scope as _device_scope
from .registry import apply as _apply
from .registry import register as _register


def _jnp():
    import jax.numpy as jnp

    return jnp


def _lax():
    import jax.lax as lax

    return lax


def _scoped(name):
    """Decorator: a function on raw arrays whose work a compiled program
    names ``name`` (``profiler.core.OP_SCOPES``). A plain closure, so the
    eager jit cache keys the result as it keys the function."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with _device_scope(name):
                return fn(*args, **kwargs)

        return scoped

    return wrap


def _tup(v, n):
    if v is None:
        return (1,) * n
    if isinstance(v, int):
        return (v,) * n
    v = tuple(v)
    if len(v) == 1:
        return v * n
    return v


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------


def _j_relu(x):
    return _jnp().maximum(x, 0)


def _j_sigmoid(x):
    import jax

    return jax.nn.sigmoid(x)


def _j_softrelu(x):
    import jax

    return jax.nn.softplus(x)


def _j_softsign(x):
    return x / (1 + _jnp().abs(x))


_ACTS = {}


def _act_fn(name):
    import jax

    if not _ACTS:
        _ACTS.update(
            relu=_j_relu,
            sigmoid=_j_sigmoid,
            log_sigmoid=jax.nn.log_sigmoid,
            tanh=_jnp().tanh,
            softrelu=_j_softrelu,
            softsign=_j_softsign,
            silu=jax.nn.silu,
            swish=jax.nn.silu,
            mish=lambda x: x * _jnp().tanh(jax.nn.softplus(x)),
            gelu=jax.nn.gelu,
            gelu_tanh=lambda x: jax.nn.gelu(x, approximate=True),
            erf_gelu=lambda x: jax.nn.gelu(x, approximate=False),
            identity=lambda x: x,
        )
    try:
        return _ACTS[name]
    except KeyError:
        raise MXNetError(f"unknown activation {name!r}") from None


def activation(data, act_type="relu", **kwargs):  # pylint: disable=unused-argument
    fn = _act_fn(act_type)
    return _apply(fn, (data,), name=f"activation:{act_type}")


def relu(data):
    return _apply(_j_relu, (data,), name="relu")


def sigmoid(data):
    return _apply(_j_sigmoid, (data,), name="sigmoid")


def tanh(data):
    return _apply(_jnp().tanh, (data,), name="tanh")


def softsign(data):
    return _apply(_j_softsign, (data,), name="softsign")


def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334, **kwargs):  # pylint: disable=unused-argument
    """LeakyReLU family (reference ``src/operator/leaky_relu.cc``)."""
    import jax

    jnp = _jnp()
    if act_type == "leaky":
        return _apply(lambda x: jnp.where(x >= 0, x, slope * x), (data,),
                      name="leaky_relu")
    if act_type == "elu":
        return _apply(lambda x: jax.nn.elu(x, alpha=slope), (data,), name="elu")
    if act_type == "selu":
        return _apply(jax.nn.selu, (data,), name="selu")
    if act_type == "gelu":
        return _apply(jax.nn.gelu, (data,), name="gelu")
    if act_type == "prelu":
        return _apply(lambda x, g: jnp.where(x >= 0, x, g * x), (data, gamma),
                      name="prelu")
    if act_type == "rrelu":
        if autograd.is_training():
            import jax.random as jr

            key = _rng.next_key()
            def f(x):
                s = jr.uniform(key, x.shape, x.dtype, lower_bound, upper_bound)
                return jnp.where(x >= 0, x, s * x)
            return _apply(f, (data,), name="rrelu")
        mid = (lower_bound + upper_bound) / 2
        return _apply(lambda x: jnp.where(x >= 0, x, mid * x), (data,), name="rrelu")
    raise MXNetError(f"unknown leaky_relu act_type {act_type!r}")


# ---------------------------------------------------------------------------
# softmax family (reference src/operator/nn/softmax.cc, log_softmax.cc)
# ---------------------------------------------------------------------------


def softmax(data, axis=-1, length=None, temperature=None, use_length=False, dtype=None):
    import jax

    jnp = _jnp()

    def f(x, *rest):
        xx = x if temperature in (None, 1.0) else x / temperature
        if use_length and rest:
            ln = rest[0]
            idx = jnp.arange(xx.shape[axis])
            shape = [1] * xx.ndim
            shape[axis] = xx.shape[axis]
            mask = idx.reshape(shape) < jnp.expand_dims(ln, axis=axis)
            xx = jnp.where(mask, xx, -jnp.inf)
            out = jax.nn.softmax(xx, axis=axis)
            out = jnp.where(mask, out, 0.0)
        else:
            out = jax.nn.softmax(xx, axis=axis)
        return out.astype(dtype) if dtype else out

    args = (data, length) if (use_length and length is not None) else (data,)
    return _apply(f, args, name="softmax")


def log_softmax(data, axis=-1, temperature=None, dtype=None, use_length=False, length=None):  # pylint: disable=unused-argument
    import jax

    def f(x):
        xx = x if temperature in (None, 1.0) else x / temperature
        out = jax.nn.log_softmax(xx, axis=axis)
        return out.astype(dtype) if dtype else out

    return _apply(f, (data,), name="log_softmax")


def masked_softmax(data, mask, axis=-1, temperature=1.0):
    import jax

    jnp = _jnp()

    def f(x, m):
        xx = x / temperature if temperature != 1.0 else x
        xx = jnp.where(m, xx, -1e30)
        out = jax.nn.softmax(xx, axis=axis)
        return jnp.where(m, out, 0.0)

    return _apply(f, (data, mask), name="masked_softmax")


def masked_log_softmax(data, mask, axis=-1, temperature=1.0):
    import jax

    jnp = _jnp()

    def f(x, m):
        xx = x / temperature if temperature != 1.0 else x
        xx = jnp.where(m, xx, -1e30)
        return jax.nn.log_softmax(xx, axis=axis)

    return _apply(f, (data, mask), name="masked_log_softmax")


# ---------------------------------------------------------------------------
# dense / conv / pooling
# ---------------------------------------------------------------------------


def fully_connected(x, weight, bias=None, num_hidden=None, no_bias=False,
                    flatten=True):
    """y = x @ W^T + b (reference ``src/operator/nn/fully_connected.cc``).

    ``flatten=True`` collapses all non-batch dims (reference semantics);
    ``flatten=False`` applies to the trailing dim.
    """
    jnp = _jnp()

    def f(xx, ww, *mb):
        if flatten and xx.ndim > 2:
            xx = xx.reshape(xx.shape[0], -1)
        out = jnp.matmul(xx, ww.T)
        if mb:
            out = out + mb[0]
        return out

    args = (x, weight) if (no_bias or bias is None) else (x, weight, bias)
    return _apply(f, args, name="fully_connected")


_CONV_LAYOUTS = {
    1: ("NCW", "OIW", "NCW"),
    2: ("NCHW", "OIHW", "NCHW"),
    3: ("NCDHW", "OIDHW", "NCDHW"),
}


def convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=None, num_group=1, no_bias=False,
                layout=None, **kwargs):  # pylint: disable=unused-argument
    """N-D convolution via ``lax.conv_general_dilated`` (MXU path).

    Reference: ``src/operator/nn/convolution.cc`` + cuDNN wrappers. XLA owns
    algorithm choice/layout; grouped conv maps to ``feature_group_count``.
    """
    lax = _lax()
    ksize = len(kernel) if kernel is not None else None

    def f(x, w, *mb):
        nd = x.ndim - 2
        lhs_spec, rhs_spec, out_spec = _CONV_LAYOUTS[nd]
        strides = _tup(stride, nd)
        dil = _tup(dilate, nd)
        pads = _tup(pad, nd) if pad is not None else (0,) * nd
        padding = [(p, p) for p in pads]
        out = lax.conv_general_dilated(
            x, w, window_strides=strides, padding=padding,
            rhs_dilation=dil, feature_group_count=num_group,
            dimension_numbers=(lhs_spec, rhs_spec, out_spec),
        )
        if mb:
            b = mb[0].reshape((1, -1) + (1,) * nd)
            out = out + b
        return out

    del ksize
    args = (data, weight) if (no_bias or bias is None) else (data, weight, bias)
    return _apply(f, args, name="convolution")


def deconvolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                  pad=None, adj=None, num_filter=None, num_group=1,
                  no_bias=True, layout=None, target_shape=None, **kwargs):  # pylint: disable=unused-argument
    """Transposed convolution (reference ``src/operator/nn/deconvolution.cc``).

    Implemented as the gradient of convolution (``lax.conv_transpose`` with
    IOW-spec weights), matching the reference's definition.
    """
    lax = _lax()

    def f(x, w, *mb):
        nd = x.ndim - 2
        strides = _tup(stride, nd)
        dil = _tup(dilate, nd)
        pads = _tup(pad, nd) if pad is not None else (0,) * nd
        adjs = _tup(adj, nd) if adj is not None else (0,) * nd
        # output padding handled by asymmetric padding on the transpose
        padding = []
        kernel_shape = w.shape[2:]
        for i in range(nd):
            k = (kernel_shape[i] - 1) * dil[i] + 1
            lo = k - 1 - pads[i]
            hi = k - 1 - pads[i] + adjs[i]
            padding.append((lo, hi))
        lhs_spec, rhs_spec, out_spec = _CONV_LAYOUTS[nd]
        # IOW-style spec: swap I/O in rhs for transpose semantics
        rhs_spec_t = rhs_spec.replace("O", "X").replace("I", "O").replace("X", "I")
        out = lax.conv_general_dilated(
            x, _jnp().flip(w, axis=tuple(range(2, w.ndim))),
            window_strides=(1,) * nd, padding=padding,
            lhs_dilation=strides, rhs_dilation=dil,
            feature_group_count=num_group,
            dimension_numbers=(lhs_spec, rhs_spec_t, out_spec),
        )
        if mb:
            out = out + mb[0].reshape((1, -1) + (1,) * nd)
        return out

    args = (data, weight) if (no_bias or bias is None) else (data, weight, bias)
    return _apply(f, args, name="deconvolution")


def pooling(data, kernel=None, pool_type="max", global_pool=False, stride=None,
            pad=None, pooling_convention="valid", count_include_pad=True,
            layout=None, **kwargs):  # pylint: disable=unused-argument
    """Pooling via ``lax.reduce_window`` (reference ``src/operator/nn/pooling.cc``)."""
    lax = _lax()
    jnp = _jnp()

    def f(x):
        nd = x.ndim - 2
        if global_pool:
            axes = tuple(range(2, x.ndim))
            if pool_type == "max":
                return jnp.max(x, axis=axes, keepdims=True)
            if pool_type == "sum":
                return jnp.sum(x, axis=axes, keepdims=True)
            return jnp.mean(x, axis=axes, keepdims=True)
        ker = _tup(kernel, nd)
        strides = _tup(stride, nd) if stride is not None else ker
        pads = _tup(pad, nd) if pad is not None else (0,) * nd
        window = (1, 1) + ker
        wstrides = (1, 1) + strides
        if pooling_convention == "full":
            # ceil-mode: pad high side enough to cover a final partial window
            wpad = [(0, 0), (0, 0)]
            for i in range(nd):
                size = x.shape[2 + i] + 2 * pads[i]
                out_f = max(0, math.ceil((size - ker[i]) / strides[i])) + 1
                needed = (out_f - 1) * strides[i] + ker[i] - size
                wpad.append((pads[i], pads[i] + max(0, needed)))
        else:
            wpad = [(0, 0), (0, 0)] + [(p, p) for p in pads]
        if pool_type == "max":
            init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
            return lax.reduce_window(x, init, lax.max, window, wstrides, wpad)
        if pool_type in ("avg", "sum"):
            s = lax.reduce_window(x, 0.0, lax.add, window, wstrides, wpad)
            if pool_type == "sum":
                return s
            if count_include_pad:
                denom = float(_onp.prod(ker))
                return s / denom
            ones = jnp.ones_like(x)
            cnt = lax.reduce_window(ones, 0.0, lax.add, window, wstrides, wpad)
            return s / cnt
        if pool_type == "lp":
            p = kwargs.get("p_value", 2)
            s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window, wstrides, wpad)
            return s ** (1.0 / p)
        raise MXNetError(f"unknown pool_type {pool_type!r}")

    return _apply(f, (data,), name=f"pooling:{pool_type}")


def adaptive_avg_pooling(data, output_size=1):
    """``_contrib_AdaptiveAvgPooling2D`` analog."""
    jnp = _jnp()
    if isinstance(output_size, int):
        output_size = (output_size, output_size)

    def f(x):
        n, c, h, w = x.shape
        oh, ow = output_size
        if h % oh == 0 and w % ow == 0:
            x4 = x.reshape(n, c, oh, h // oh, ow, w // ow)
            return x4.mean(axis=(3, 5))
        import jax

        x_resized = jax.image.resize(x, (n, c, oh, ow), method="linear")
        return x_resized

    return _apply(f, (data,), name="adaptive_avg_pooling")


# ---------------------------------------------------------------------------
# normalization (reference src/operator/nn/{batch_norm,layer_norm,...}.cc)
# ---------------------------------------------------------------------------


def batch_norm(x, gamma, beta, running_mean, running_var, eps=1e-5,
               momentum=0.9, fix_gamma=False, use_global_stats=False,
               output_mean_var=False, axis=1, cudnn_off=False, **kwargs):  # pylint: disable=unused-argument
    """Batch normalization.

    Training mode (autograd.is_training() and not use_global_stats): uses
    batch statistics and returns updated running stats via the layer (see
    ``gluon.nn.BatchNorm`` which rebinds its state params — the reference
    mutates aux states inside the op instead).
    """
    jnp = _jnp()
    training = autograd.is_training() and not use_global_stats

    def f_train(xx, g, b):
        axes = tuple(i for i in range(xx.ndim) if i != axis)
        if jnp.dtype(xx.dtype).itemsize <= 2:
            # bf16/fp16 AMP path: one-pass fp32 stats (E[x], E[x^2]).
            # jnp.var's two-pass form costs an extra HBM sweep of the
            # activation per BN — measured ~6% of the whole ResNet-50 train
            # step on v5e (BN fusions run at the HBM roofline, see
            # profiler.device_op_table). fp32 accumulation is strictly more
            # accurate than two-pass arithmetic in the input's own 16-bit
            # dtype; the clamp guards E[x^2]-E[x]^2 cancellation.
            x32 = xx.astype(jnp.float32)
            mean32 = jnp.mean(x32, axis=axes)
            var32 = jnp.maximum(
                jnp.mean(jnp.square(x32), axis=axes) - jnp.square(mean32),
                0.0)
            # stats stay fp32: they feed the running-stat update, and the
            # reference keeps BN aux states fp32 under AMP — only the
            # normalization arithmetic below casts down
            mean, var = mean32, var32
            inv_c = 1.0 / jnp.sqrt(var32 + eps)
        else:
            # fp32/fp64: keep the exact two-pass form — one-pass
            # cancellation at |mean| >> std would be a precision regression
            # with no bandwidth story (full-precision nets are not the
            # perf-critical path)
            mean = jnp.mean(xx, axis=axes)
            var = jnp.var(xx, axis=axes)
            inv_c = 1.0 / jnp.sqrt(var + eps)
        shape = [1] * xx.ndim
        shape[axis] = xx.shape[axis]
        gg = jnp.ones_like(g) if fix_gamma else g
        inv = (gg.astype(inv_c.dtype) * inv_c).astype(xx.dtype).reshape(shape)
        out = ((xx - mean.astype(xx.dtype).reshape(shape)) * inv
               + b.reshape(shape))
        return out, mean, var

    def f_eval(xx, g, b, rm, rv):
        shape = [1] * xx.ndim
        shape[axis] = xx.shape[axis]
        gg = jnp.ones_like(g) if fix_gamma else g
        inv = gg.reshape(shape) / jnp.sqrt(rv.reshape(shape) + eps)
        return (xx - rm.reshape(shape)) * inv + b.reshape(shape)

    if training:
        out, mean, var = _apply(f_train, (x, gamma, beta), name="batch_norm")
        # state update is the caller's job (the layer folds batch stats into
        # its running_* parameters), so stats are only returned on request
        if output_mean_var:
            return out, mean, var
        return out
    out = _apply(f_eval, (x, gamma, beta, running_mean, running_var),
                 name="batch_norm_inference")
    if output_mean_var:
        return out, running_mean, running_var
    return out


def layer_norm(data, gamma, beta=None, axis=-1, eps=1e-5):
    """``beta=None``: a gain and no bias."""
    jnp = _jnp()

    @_scoped("norm")
    def f(x, g, *b):
        mean = jnp.mean(x, axis=axis, keepdims=True)
        var = jnp.var(x, axis=axis, keepdims=True)
        out = (x - mean) / jnp.sqrt(var + eps)
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        out = out * g.reshape(shape)
        return out + b[0].reshape(shape) if b else out

    args = (data, gamma) if beta is None else (data, gamma, beta)
    return _apply(f, args, name="layer_norm")


def rms_norm(data, gamma, axis=-1, eps=1e-6):
    """RMSNorm (no reference analog; required by the Llama model family)."""
    jnp = _jnp()

    @_scoped("norm")
    def f(x, g):
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axis, keepdims=True)
        out = x * (1.0 / jnp.sqrt(ms + eps)).astype(x.dtype)
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        return out * g.reshape(shape)

    return _apply(f, (data, gamma), name="rms_norm")


def group_norm(data, gamma, beta, num_groups=1, eps=1e-5):
    jnp = _jnp()

    def f(x, g, b):
        n, c = x.shape[:2]
        rest = x.shape[2:]
        xg = x.reshape((n, num_groups, c // num_groups) + rest)
        axes = tuple(range(2, xg.ndim))
        mean = jnp.mean(xg, axis=axes, keepdims=True)
        var = jnp.var(xg, axis=axes, keepdims=True)
        out = ((xg - mean) / jnp.sqrt(var + eps)).reshape(x.shape)
        shape = (1, c) + (1,) * len(rest)
        return out * g.reshape(shape) + b.reshape(shape)

    return _apply(f, (data, gamma, beta), name="group_norm")


def instance_norm(data, gamma, beta, eps=1e-5):
    jnp = _jnp()

    def f(x, g, b):
        axes = tuple(range(2, x.ndim))
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.var(x, axis=axes, keepdims=True)
        out = (x - mean) / jnp.sqrt(var + eps)
        shape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
        return out * g.reshape(shape) + b.reshape(shape)

    return _apply(f, (data, gamma, beta), name="instance_norm")


def l2_normalization(data, eps=1e-10, mode="instance"):
    jnp = _jnp()

    def f(x):
        if mode == "instance":
            axes = tuple(range(1, x.ndim))
        elif mode == "channel":
            axes = (1,)
        else:
            axes = tuple(range(x.ndim))
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
        return x / norm

    return _apply(f, (data,), name="l2_normalization")


# ---------------------------------------------------------------------------
# dropout (reference src/operator/nn/dropout.cc; RNG via engine resource)
# ---------------------------------------------------------------------------


def dropout(data, p=0.5, mode="training", axes=(), **kwargs):  # pylint: disable=unused-argument
    if p <= 0 or (mode == "training" and not autograd.is_training()):
        return data if hasattr(data, "_data") else data
    import jax.random as jr

    jnp = _jnp()
    key = _rng.next_key()

    def f(x):
        shape = list(x.shape)
        for ax in axes:
            shape[ax] = 1
        keep = 1.0 - p
        # float32 probability: under jax_enable_x64 a bare Python float
        # makes bernoulli draw float64 uniforms from 64-bit random bits —
        # twice the RNG work on one chip, and a dp-sharded 64-bit
        # RngBitGenerator crashes the TPU compiler (PERF.md, PR 23)
        mask = jr.bernoulli(key, jnp.float32(keep),
                            tuple(shape)).astype(x.dtype)
        return x * mask / keep

    return _apply(f, (data,), name="dropout")


# ---------------------------------------------------------------------------
# embedding / one-hot / indexing ops
# ---------------------------------------------------------------------------


def embedding(data, weight, input_dim=None, output_dim=None, dtype=None,
              sparse_grad=False, **kwargs):  # pylint: disable=unused-argument
    """Embedding lookup (reference ``src/operator/tensor/indexing_op.cc``).

    ``sparse_grad=True``: the weight's gradient is produced as a
    ``RowSparseNDArray`` holding only the touched rows (unique indices,
    duplicate contributions segment-summed) — O(nnz) end to end, the
    reference's ``SparseEmbedding`` backward contract. Sparse production
    needs concrete indices, so inside a jit/hybridize trace the dense
    gradient path is used instead.
    """
    jnp = _jnp()

    @_scoped("embed")
    def f(idx, w):
        return jnp.take(w, idx.astype(jnp.int32), axis=0)

    if sparse_grad and autograd.is_recording() and not _rng.in_trace():
        import jax

        from ..ndarray.ndarray import NDArray, _slot_of, _tracked
        from ..ndarray.sparse import RowSparseNDArray, _unique_static

        idx_nd = data if isinstance(data, NDArray) else NDArray(data)
        w_nd = weight if isinstance(weight, NDArray) else NDArray(weight)
        if isinstance(idx_nd._data, jax.core.Tracer) \
                or isinstance(w_nd._data, jax.core.Tracer):
            return _apply(f, (data, weight), name="embedding")
        out_data = f(idx_nd._data, w_nd._data)
        out = NDArray(out_data)
        if _tracked(w_nd):
            idx_flat = idx_nd._data.reshape(-1).astype(jnp.int64)
            vocab, dim = w_nd.shape
            uniq, inv = _unique_static(idx_flat)

            def vjp_fn(ct, _u=uniq, _i=inv, _v=vocab, _d=dim):
                ctf = ct.reshape(-1, _d)
                vals = jnp.zeros((_u.shape[0], _d),
                                 ctf.dtype).at[_i].add(ctf)
                return (None,
                        RowSparseNDArray(NDArray(vals), NDArray(_u),
                                         (_v, _d)))

            node = autograd.TapeNode(
                vjp_fn, [_slot_of(idx_nd), _slot_of(w_nd)],
                [(out.shape, out.dtype)], name="embedding_sparse")
            out._tape = (node, 0)
        return out

    return _apply(f, (data, weight), name="embedding")


def one_hot(data, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    import jax

    def f(idx):
        oh = jax.nn.one_hot(idx, depth, dtype=dtype)
        if on_value != 1.0 or off_value != 0.0:
            oh = oh * (on_value - off_value) + off_value
        return oh

    return _apply(f, (data,), name="one_hot", record=False)


def pick(data, index, axis=-1, keepdims=False, mode="clip"):  # pylint: disable=unused-argument
    """Pick per-row elements by index (reference ``pick`` op)."""
    jnp = _jnp()

    def f(x, idx):
        out = jnp.take_along_axis(
            x, jnp.expand_dims(idx.astype(jnp.int32), axis=axis), axis=axis)
        return out if keepdims else jnp.squeeze(out, axis=axis)

    return _apply(f, (data, index), name="pick")


def gather_nd(data, indices):
    jnp = _jnp()

    def f(x, idx):
        idx = idx.astype(jnp.int32)
        return x[tuple(idx[i] for i in range(idx.shape[0]))]

    return _apply(f, (data, indices), name="gather_nd")


def scatter_nd(data, indices, shape):
    jnp = _jnp()

    def f(v, idx):
        idx = idx.astype(jnp.int32)
        out = jnp.zeros(shape, v.dtype)
        return out.at[tuple(idx[i] for i in range(idx.shape[0]))].set(v)

    return _apply(f, (data, indices), name="scatter_nd")


def topk(data, k=1, axis=-1, ret_typ="indices", is_ascend=False, dtype="float32"):
    """Top-k (reference ``src/operator/tensor/ordering_op.cc``)."""
    import jax

    jnp = _jnp()

    def f(x):
        xm = jnp.moveaxis(x, axis, -1)
        vals, idx = jax.lax.top_k(-xm if is_ascend else xm, k)
        if is_ascend:
            vals = -vals
        vals = jnp.moveaxis(vals, -1, axis)
        idx = jnp.moveaxis(idx, -1, axis)
        if ret_typ == "value":
            return vals
        if ret_typ == "both":
            return vals, idx.astype(dtype)
        return idx.astype(dtype)

    return _apply(f, (data,), name="topk", record=(ret_typ == "value"))


# ---------------------------------------------------------------------------
# sequence ops (reference src/operator/sequence_*.cc)
# ---------------------------------------------------------------------------


def sequence_mask(data, sequence_length=None, use_sequence_length=False,
                  value=0.0, axis=0):
    jnp = _jnp()
    if not use_sequence_length or sequence_length is None:
        return data

    def f(x, slen):
        idx = jnp.arange(x.shape[axis])
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        batch_axis = 1 if axis == 0 else 0
        bshape = [1] * x.ndim
        bshape[batch_axis] = x.shape[batch_axis]
        mask = idx.reshape(shape) < slen.reshape(bshape)
        return jnp.where(mask, x, value)

    return _apply(f, (data, sequence_length), name="sequence_mask")


def sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0):
    jnp = _jnp()

    def f(x, *rest):
        if rest:
            idx = (rest[0].astype(jnp.int32) - 1)
            return jnp.take_along_axis(
                x, idx.reshape((1, -1) + (1,) * (x.ndim - 2)), axis=axis
            ).squeeze(axis)
        return jnp.take(x, x.shape[axis] - 1, axis=axis)

    args = (data, sequence_length) if (use_sequence_length and sequence_length is not None) else (data,)
    return _apply(f, args, name="sequence_last")


def sequence_reverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    jnp = _jnp()

    def f(x, *rest):
        if not rest:
            return jnp.flip(x, axis=axis)
        slen = rest[0].astype(jnp.int32)
        t = x.shape[axis]
        idx = jnp.arange(t)
        rev = slen[None, :] - 1 - idx[:, None]
        rev = jnp.where(rev >= 0, rev, idx[:, None])
        return jnp.take_along_axis(x, rev.reshape((t, -1) + (1,) * (x.ndim - 2)), axis=0)

    args = (data, sequence_length) if (use_sequence_length and sequence_length is not None) else (data,)
    return _apply(f, args, name="sequence_reverse")


# ---------------------------------------------------------------------------
# losses as ops
# ---------------------------------------------------------------------------


def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             use_data_lengths=False, use_label_lengths=False, blank_label="first"):
    """CTC loss (reference ``src/operator/nn/ctc_loss.cc`` / WarpCTC).

    Lowered through optax's ctc_loss (pure-JAX forward-backward) with
    logit layout conversion: reference layout is (seq, batch, alphabet).
    """
    import optax

    jnp = _jnp()

    def f(logits, labels, *rest):
        sl, b, a = logits.shape
        lg = jnp.transpose(logits, (1, 0, 2))  # (B, T, A)
        lab = labels.astype(jnp.int32)
        if blank_label == "first":
            # optax uses blank=0 by default; reference 'first' means blank==0
            blank_id = 0
        else:
            blank_id = a - 1
        if rest and use_data_lengths:
            dl = rest[0].astype(jnp.int32)
        else:
            dl = jnp.full((b,), sl, jnp.int32)
        logit_pad = (jnp.arange(sl)[None, :] >= dl[:, None]).astype(jnp.float32)
        if use_label_lengths and len(rest) > (1 if use_data_lengths else 0):
            ll = rest[-1].astype(jnp.int32)
        else:
            ll = jnp.sum((lab > 0).astype(jnp.int32), axis=-1)
        label_pad = (jnp.arange(lab.shape[1])[None, :] >= ll[:, None]).astype(jnp.float32)
        return optax.ctc_loss(lg, logit_pad, lab, label_pad, blank_id=blank_id)

    args = [data, label]
    if use_data_lengths and data_lengths is not None:
        args.append(data_lengths)
    if use_label_lengths and label_lengths is not None:
        args.append(label_lengths)
    return _apply(f, tuple(args), name="ctc_loss")


def smooth_l1(data, scalar=1.0):
    jnp = _jnp()

    def f(x):
        s2 = scalar * scalar
        return jnp.where(jnp.abs(x) < 1.0 / s2, 0.5 * s2 * x * x,
                         jnp.abs(x) - 0.5 / s2)

    return _apply(f, (data,), name="smooth_l1")


# ---------------------------------------------------------------------------
# attention (TPU flagship path — Pallas flash attention with XLA fallback)
# ---------------------------------------------------------------------------


def attention(query, key, value, mask=None, causal=False, scale=None,
              use_flash=True, valid_length=None):
    """Scaled dot-product attention over (B, H, T, D) tensors.

    Replaces the reference's fused matmul helpers
    (``src/operator/contrib/transformer.cc`` interleaved_matmul_selfatt_*)
    with a real attention op: Pallas flash-attention kernel on TPU,
    XLA-fused reference path elsewhere. ``valid_length`` (B,) key lengths
    are masked inside the flash kernel (no dense mask materialized); a
    dense ``mask`` forces the XLA path. See
    ``mxnet_tpu/ops/pallas/flash_attention.py``.
    """
    from .pallas import flash_attention as fa

    n_extra = (mask is not None, valid_length is not None)
    # routing globals must live in f's CLOSURE: the eager jit cache keys
    # ops on (code, closure values), and a cached executable replays its
    # traced path — without these cells a force_path()/use_interpret()
    # flip would silently keep serving the previously-traced kernel
    routing = (fa._FORCE_PATH, fa._INTERPRET)

    def f(q, k, v, *extra):
        assert routing == (fa._FORCE_PATH, fa._INTERPRET)
        it = iter(extra)
        m = next(it) if n_extra[0] else None
        vl = next(it) if n_extra[1] else None
        return fa.attention(q, k, v, m, causal=causal, scale=scale,
                            use_flash=use_flash, valid_length=vl)

    args = (query, key, value)
    if mask is not None:
        args = args + (mask,)
    if valid_length is not None:
        args = args + (valid_length,)
    return _apply(f, args, name="attention")


# ---------------------------------------------------------------------------
# KV-cache serving ops (mxnet_tpu.serve)
#
# These four ops are the compute core of autoregressive decode. They are
# deliberately written in a *shape-stable* formulation: every reduction
# (score dot products, softmax statistics, the value-weighted sum) runs
# over the LAST axis of a tensor whose reduced extent is fixed by the
# cache length, never by the query length. On the XLA CPU/TPU backends
# this makes the per-position arithmetic bitwise identical whether the
# query block is a full prefill (T = bucket) or a single decode token
# (T = 1) — the property tests/test_serve.py asserts. A batched
# dot_general here would NOT have it (its tiling changes with T; measured
# ~1e-5 drift on CPU), which is why these do not reuse ``attention``.
# ---------------------------------------------------------------------------


def kv_cache_write(cache, new, start_pos, page_table=None, window=None):
    """Write ``new`` (B, H, T, D) into the ring ``cache`` (B, H, S, D) at
    per-row positions ``start_pos[b] + [0..T)``.

    Gather+select formulation (``take_along_axis`` + ``where``) instead of
    a scatter: deterministic, differentiable-free, and exact — selected
    elements are copied, not arithmetically merged, so ``-0.0`` and
    payload bits survive untouched.

    With a ``page_table`` (B, N), ``cache`` is a page pool (P, H, page, D)
    and the rows go straight into their pages (:func:`write_pages`); with
    a ``window`` besides, the table's N columns are a ring of pages
    (logical page j in column j mod N).
    """
    if page_table is not None:
        if window is not None:
            return _apply(functools.partial(write_pages, ring=True),
                          (cache, page_table, new, start_pos),
                          name="paged_kv_write_window")
        return _apply(write_pages, (cache, page_table, new, start_pos),
                      name="paged_kv_write")
    if window is not None:
        raise MXNetError("a layer bounded by a window keeps its K/V in a "
                         "ring of pages (the continuous engine's in-place "
                         "step): it has no contiguous ring to write")

    @_scoped("kv.write")
    def f(c, n, sp):
        jnp = _jnp()
        s_len = c.shape[2]
        t_len = n.shape[2]
        s_idx = jnp.arange(s_len, dtype=jnp.int32)[None, :]      # (1, S)
        sp_ = sp.astype(jnp.int32)[:, None]                      # (B, 1)
        in_window = (s_idx >= sp_) & (s_idx < sp_ + t_len)       # (B, S)
        src = jnp.clip(s_idx - sp_, 0, t_len - 1)                # (B, S)
        gathered = jnp.take_along_axis(n, src[:, None, :, None], axis=2)
        return jnp.where(in_window[:, None, :, None], gathered, c)

    return _apply(f, (cache, new, start_pos), name="kv_cache_write")


def _quantize_rows(n):
    """Per token per head symmetric int8: ``(rows int8, scale f32)``."""
    jnp = _jnp()
    amax = jnp.max(jnp.abs(n), axis=-1)                          # (B, H, T)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    nq = jnp.clip(jnp.round(n / scale[..., None]),
                  -127, 127).astype(jnp.int8)
    return nq, scale


def kv_cache_write_q(cache_q, cache_scale, new, start_pos, page_table=None):
    """Quantize-on-write into an int8 KV ring: ``new`` (B, H, T, D) f32 is
    symmetric-quantized per token per head (scale = max|row| / 127 over D)
    and written into ``cache_q`` (B, H, S, D) int8 with its scale row into
    ``cache_scale`` (B, H, S) f32, at positions ``start_pos[b] + [0..T)``.

    Same gather+select window as ``kv_cache_write`` — untouched ring slots
    are copied, not merged. Returns ``(new_cache_q, new_cache_scale)``;
    dequantization happens inside ``cached_attention``'s fast path. With
    a ``page_table`` the two are page pools, written like
    ``kv_cache_write``'s.
    """
    if page_table is not None:
        @_scoped("kv.write")
        def paged(pq, ps, t, n, sp):
            nq, scale = _quantize_rows(n)
            return write_pages(pq, t, nq, sp), write_pages(ps, t, scale, sp)

        return _apply(paged, (cache_q, cache_scale, page_table, new,
                              start_pos), name="paged_kv_write_q")

    @_scoped("kv.write")
    def f(cq, cs, n, sp):
        jnp = _jnp()
        s_len = cq.shape[2]
        t_len = n.shape[2]
        nq, scale = _quantize_rows(n)
        s_idx = jnp.arange(s_len, dtype=jnp.int32)[None, :]      # (1, S)
        sp_ = sp.astype(jnp.int32)[:, None]                      # (B, 1)
        in_window = (s_idx >= sp_) & (s_idx < sp_ + t_len)       # (B, S)
        src = jnp.clip(s_idx - sp_, 0, t_len - 1)                # (B, S)
        gq = jnp.take_along_axis(nq, src[:, None, :, None], axis=2)
        gs = jnp.take_along_axis(scale, src[:, None, :], axis=2)
        return (jnp.where(in_window[:, None, :, None], gq, cq),
                jnp.where(in_window[:, None, :], gs, cs))

    return _apply(f, (cache_q, cache_scale, new, start_pos),
                  name="kv_cache_write_q")


def quantized_dense(data, qweight, scale, bias=None):
    """int8 fully-connected: ``data`` (..., U) f32 against a pre-quantized
    ``qweight`` (O, U) int8 with per-output-channel ``scale`` (O,) f32.

    On TPU, activations are quantized dynamically per row (symmetric,
    max|x|/127 over U) so the inner product runs int8 x int8 -> int32 on
    the MXU's 394 TOP/s int8 units, then rescales to f32. XLA CPU has no
    int8 gemm worth using (the s8 dot lowers to a scalar loop — measured
    slower than the f32 path it replaces), so there the op is weight-only
    quantization: dequantize ``qweight`` inline and run the f32 gemm —
    weights still live at half size, activations stay f32. Serving
    fast-path only: ~1e-2 relative error vs the f32 gemm, covered by the
    tolerance parity suite, never by the bitwise contract.
    """
    import jax

    int8_dot = jax.default_backend() == "tpu"

    def f(x, w, s, *b):
        import jax

        jnp = _jnp()
        if int8_dot:
            amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
            sx = jnp.maximum(amax / 127.0, 1e-8)
            xq = jnp.clip(jnp.round(x / sx), -127, 127).astype(jnp.int8)
            acc = jax.lax.dot_general(
                xq, w, (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
            out = acc.astype(jnp.float32) * sx * s
        else:
            # per-output-channel scale is a column scale of the gemm, so
            # it commutes to the output: scaling (..., O) activations is
            # U-times cheaper than scaling the (O, U) weight, and the
            # int8->f32 convert fuses into the gemm's weight read
            out = jax.lax.dot_general(
                x, w.astype(jnp.float32),
                (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * s
        return out + b[0] if b else out

    args = (data, qweight, scale)
    if bias is not None:
        args = args + (bias,)
    return _apply(f, args, name="quantized_dense")


def cached_attention(query, key, value, start_pos, scale=None,
                     path="baseline", k_scale=None, v_scale=None,
                     page_table=None, window=None, v_width=None):
    """Causal attention of ``query`` (B, H, T, D) — absolute positions
    ``start_pos[b] + t`` — over a KV ring (B, H, S, D).

    Positions ``> start_pos[b] + t`` (future tokens, unwritten or padded
    ring slots) are masked to ``-inf`` before the softmax; their
    probabilities are exactly 0.0, so ring garbage contributes exact zeros
    to the value sum. See the section comment for why this is a
    mul+reduce, not a dot.

    ``path`` selects the formulation: "baseline" is the shape-stable
    mul+reduce above (the bitwise prefill/decode contract); any other
    value routes to the fused decode-attention kernel
    (``ops/pallas/decode_attention``), which takes *unexpanded* GQA K/V of
    shape (B, KV, S, D) — optionally int8 with (B, KV, S)
    ``k_scale``/``v_scale`` rings dequantized in-kernel — and carries a
    tolerance (not bitwise) parity contract. With a ``page_table`` (B, N)
    (fast rungs only) key/value and their scales are page pools that
    already hold this call's rows, and the kernel reads them in place
    (``decode_attention.paged_decode_attention``). With a ``window``
    besides, a query at position ``t`` sees the keys at ``t - window + 1
    .. t`` alone and the table's columns are a ring of pages
    (:func:`write_pages`, ``ring``); ``None`` bounds nothing and traces
    what it always traced.

    ``value=None`` with ``v_width`` is the latent form (paged fast rungs,
    float32 alone): ``key`` is a pool of one array a position, (P, 1,
    page, D), that all H query heads read, and its first ``v_width``
    channels are the values; the result is (B, H, T, ``v_width``).
    """
    d = query.shape[-1]
    sc = float(scale) if scale is not None else 1.0 / math.sqrt(d)
    if window is not None and (path == "baseline" or page_table is None):
        raise MXNetError("a window bounds the keys of the paged fast rungs "
                         "alone (the continuous engine's in-place step)")
    if value is None:
        if path == "baseline" or page_table is None or k_scale is not None:
            raise MXNetError(
                "a latent layer's one array a position is read from float32 "
                "page pools alone (the continuous engine's in-place step)")
        from .pallas import decode_attention as da

        routing = (da._FORCE_PATH, da._INTERPRET)

        def latent(q, pool, sp, table):
            assert routing == (da._FORCE_PATH, da._INTERPRET)
            return da.paged_decode_attention(q, pool, None, table, sp,
                                             scale=sc, v_width=v_width)

        return _apply(latent, (query, key, start_pos, page_table),
                      name="cached_attention_latent")

    if path != "baseline":
        from .pallas import decode_attention as da

        # routing globals must live in f's closure (see attention())
        routing = (da._FORCE_PATH, da._INTERPRET)
        has_scales = k_scale is not None

        paged = page_table is not None

        def f(q, k, v, sp, *extra):
            assert routing == (da._FORCE_PATH, da._INTERPRET)
            extra = list(extra)
            table = extra.pop() if paged else None
            ks, vs = extra if has_scales else (None, None)
            if paged:
                return da.paged_decode_attention(q, k, v, table, sp, scale=sc,
                                                 k_scale=ks, v_scale=vs,
                                                 window=window)
            return da.decode_attention(q, k, v, sp, scale=sc,
                                       k_scale=ks, v_scale=vs)

        args = (query, key, value, start_pos)
        if has_scales:
            args = args + (k_scale, v_scale)
        if paged:
            args = args + (page_table,)
        return _apply(f, args, name="cached_attention_fast")

    @_scoped("attn.scores")
    def f(q, k, v, sp):
        jnp = _jnp()
        t_len = q.shape[2]
        s_len = k.shape[2]
        pos = sp.astype(jnp.int32)[:, None] \
            + jnp.arange(t_len, dtype=jnp.int32)[None, :]        # (B, T)
        valid = jnp.arange(s_len, dtype=jnp.int32)[None, None, :] \
            <= pos[:, :, None]                                   # (B, T, S)
        s = jnp.sum(q[:, :, :, None, :] * k[:, :, None, :, :],
                    axis=-1) * sc                                # (B, H, T, S)
        s = jnp.where(valid[:, None], s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        p = e / jnp.sum(e, axis=-1, keepdims=True)
        return jnp.sum(p[:, :, :, :, None] * v[:, :, None, :, :], axis=-2)

    return _apply(f, (query, key, value, start_pos), name="cached_attention")


def latent_absorb(data, up_weight, rows, into_latent):
    """Latent attention's absorbed products (``models.ax_k1``), a head at
    a time, at :func:`stored_precision`. ``up_weight`` is the latent's
    up-projection as a ``Dense`` stores it, (H * R, C): head ``h``'s rows
    ``h * R + rows`` (``rows`` a ``slice``) are its keys' (or its
    values') part, (n, C). ``into_latent``: ``data`` (B, H, T, n) ->
    (B, H, T, C), the queries carried into the latent's space (``q W``);
    else ``data`` (B, H, T, C) -> (B, H, T, n), the attended latent
    carried out to the head's values (``o W^T``)."""
    heads = data.shape[1]

    @_scoped("attn.absorb")
    def f(x, w):
        jnp = _jnp()
        w3 = w.reshape(heads, w.shape[0] // heads, w.shape[1])[:, rows]
        return jnp.einsum("bhtn,hnc->bhtc" if into_latent
                          else "bhtc,hnc->bhtn", x, w3,
                          precision=stored_precision(x, w))

    return _apply(f, (data, up_weight), name="latent_absorb")


def rope_positions(cos_table, sin_table, start_pos, length):
    """Gather per-row RoPE rows for positions ``start_pos[b] + [0..length)``
    from (S, D/2) tables; returns a ``(cos, sin)`` pair shaped
    (B, 1, length, D/2) — broadcastable over the head axis."""

    @_scoped("attn.rope")
    def f(ct, st, sp):
        jnp = _jnp()
        pos = sp.astype(jnp.int32)[:, None] \
            + jnp.arange(length, dtype=jnp.int32)[None, :]       # (B, T)
        return jnp.take(ct, pos, axis=0)[:, None], \
            jnp.take(st, pos, axis=0)[:, None]

    return _apply(f, (cos_table, sin_table, start_pos),
                  name="rope_positions")


def stable_dense(data, weight, bias=None):
    """Shape-stable fully-connected: ``data`` (..., U) x ``weight`` (O, U)
    -> (..., O), reducing over the last axis with the same mul+reduce
    formulation as ``cached_attention``.

    XLA CPU's gemm/gemv dispatch accumulates in an M-dependent order once
    the intra-op thread pool partitions the work (measured 1e-5 drift
    between the T=1 and T=64 rows of the SAME projection under the test
    mesh), so a ``dot``-based projection breaks the decode-vs-prefill
    bitwise contract. Here every output element is one sequential chain
    over U regardless of the leading shape. Serving-path only: training
    keeps ``fully_connected``'s gemm (MXU/BLAS) throughput.
    """

    def f(x, w, *b):
        jnp = _jnp()
        out = jnp.sum(x[..., None, :] * w, axis=-1)
        return out + b[0] if b else out

    args = (data, weight) if bias is None else (data, weight, bias)
    return _apply(f, args, name="stable_dense")


def stored_precision(*operands):
    """The dot precision of the serving fast rungs: what the operands are
    stored at. XLA:TPU and Mosaic multiply float32 matrices in single
    bfloat16 passes unless asked otherwise, which put a float32 model's
    logits 5-7% of their scale from the strict rung at Llama-3-8B widths
    (PERF.md, PR 23); float32 operands therefore ask for the full passes.
    Anything stored narrower (bf16 weights, int8 rings) was rounded harder
    than a bf16 pass already and keeps the backend's default: serving at
    the matrix unit's rate is a matter of the model's dtype, not of a
    silent downgrade."""
    import jax

    jnp = _jnp()
    if all(o.dtype == jnp.float32 for o in operands):
        return jax.lax.Precision.HIGHEST
    return None


def serving_dense(data, weight):
    """Gemm projection of the serving fast rungs: ``data`` (..., U) x
    ``weight`` (O, U) -> (..., O) on the matrix unit, at
    :func:`stored_precision` — never ``stable_dense``, whose mul+reduce
    is the baseline rung's bitwise-parity tax."""

    def f(x, w):
        return _jnp().matmul(x, w.T, precision=stored_precision(x, w))

    return _apply(f, (data, weight), name="serving_dense")


def fusion_fence(data):
    """Identity that pins ``data`` as an XLA fusion boundary
    (``optimization_barrier``). The serving decode path threads one
    between decoder layers: without it XLA fuses reductions across layer
    boundaries differently for the T=1 and T=bucket executables (measured
    ~4 ulp logits drift on the 12-layer config), which would break the
    decode-vs-prefill bitwise contract the shape-stable ops above
    establish per layer."""

    def f(x):
        import jax

        return jax.lax.optimization_barrier(x)

    return _apply(f, (data,), name="fusion_fence")


def gather_positions(data, indices):
    """Per-row gather along axis 1: ``data`` (B, T, ...) at ``indices``
    (B,) -> (B, ...). Serving uses it to pick each request's last-real-
    position logits out of a padded prefill block."""

    @_scoped("head")
    def f(x, i):
        jnp = _jnp()
        idx = i.astype(jnp.int32).reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.take_along_axis(x, idx, axis=1)[:, 0]

    return _apply(f, (data, indices), name="gather_positions")


def sample_step(logits, temperature, top_k, seeds, positions, key_bits):
    """In-trace next-token sampling for the multi-step decode super-step
    (``serve.generate._MultiStepForward``).

    ``logits`` (B, V) f32; per-row ``temperature`` (B,) f32 (<= 0 means
    greedy argmax — matching ``serve.generate.sample_tokens``), ``top_k``
    (B,) int32 (0 or >= V means no truncation), ``seeds`` (B,) int32 (one
    stream per serving slot) and ``positions`` (B,) int32 (the absolute
    decode position being sampled). ``key_bits`` is a (2,) uint32 raw
    threefry2x32 key — an ordinary traced input, NOT a baked constant, so
    one compiled executable serves every reseed.

    Keying is counter-based, not stateful: row ``b``'s key is
    ``fold_in(fold_in(key_bits, seeds[b]), positions[b])`` — a pure
    function of (base, slot stream, position). That is what makes the
    token stream invariant to super-step boundaries: running N=8
    iterations per compiled loop or degrading the same executable to
    N=1 draws the identical key for every position, so sampled output
    is token-identical across ``steps_limit`` choices (a stateful
    ``mx.random`` draw would advance once per TRACE, not per iteration,
    and every loop iteration would reuse one key).

    Returns (B,) int32 sampled token ids.
    """

    def f(lg, temp, tk, sd, pos, kb):
        import jax

        jnp = _jnp()
        v = lg.shape[-1]
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        base = jax.random.wrap_key_data(kb.astype(jnp.uint32),
                                        impl="threefry2x32")

        def row(l, t, k, s, p):
            key = jax.random.fold_in(
                jax.random.fold_in(base, s.astype(jnp.int32)),
                p.astype(jnp.int32))
            scaled = l / jnp.maximum(t, 1e-6)
            # per-row dynamic top-k: threshold at the k-th largest logit
            # (descending sort; same tie semantics as sample_tokens'
            # static jax.lax.top_k truncation — values >= kth survive)
            srt = jnp.sort(scaled)[::-1]
            kth = srt[jnp.clip(k, 1, v) - 1]
            keep = jnp.where((k > 0) & (k < v), scaled >= kth, True)
            return jax.random.categorical(
                key, jnp.where(keep, scaled, -jnp.inf))

        def drawn(_):
            sampled = jax.vmap(row)(
                lg, temp.astype(jnp.float32), tk.astype(jnp.int32),
                sd, pos).astype(jnp.int32)
            return jnp.where(temp > 0.0, sampled, greedy)

        # lax.cond, not where: an all-greedy batch (the bench rungs, every
        # temperature-0 request mix) must not pay the per-row vocab sort +
        # categorical draw on its decode critical path — the sampled
        # branch only executes when some lane actually wants it
        return jax.lax.cond(jnp.any(temp > 0.0), drawn,
                            lambda _: greedy, 0)

    return _apply(f, (logits, temperature, top_k, seeds, positions,
                      key_bits), name="sample_step")


# ---------------------------------------------------------------------------
# Paged KV-cache ops (mxnet_tpu.serve.kv_blocks / serve.scheduler)
#
# The continuous-batching decode loop stores every request's KV rows in
# one device-resident *page pool* per layer — (P, KV, page, D) for the
# rings, (P, KV, page) for the int8 scale pools — instead of per-bucket
# contiguous rings. A per-slot page table (B, N) of pool page ids maps
# each slot's logical ring onto its owned pages; page id 0 is the
# reserved NULL page (dead/idle slots point every entry at it).
#
# Both ops below are pure data movement (jnp.take / scatter-set — never
# an arithmetic merge): gather(pool) -> kv_cache_write/cached_attention
# -> scatter reads and writes exactly the bytes the contiguous path
# would. The strict baseline rung runs them as standalone eager ops
# around the unchanged ring executable, which keeps its bitwise decode
# contract; compiled INTO the step (fast rungs), XLA partitions the
# attention loops differently for a gather-fed ring than for an entry
# parameter, which drifts ulps — tolerance parity only.
# ---------------------------------------------------------------------------


@_scoped("kv.gather")
def gather_pages(p, t):
    """:func:`paged_kv_gather` on raw arrays."""
    jnp = _jnp()
    g = jnp.take(p, t.astype(jnp.int32), axis=0)      # (B, N, KV, pg[, D])
    if p.ndim == 4:
        g = g.transpose(0, 2, 1, 3, 4)
        b, kv, n, pg, d = g.shape
        return g.reshape(b, kv, n * pg, d)
    g = g.transpose(0, 2, 1, 3)
    b, kv, n, pg = g.shape
    return g.reshape(b, kv, n * pg)


@_scoped("kv.write")
def write_pages(p, t, new, sp, ring=False):
    """``new`` (B, KV, T, D) — or (B, KV, T) scale rows — written
    straight into the pool ``p`` (P, KV, page[, D]) at positions
    ``sp[b] + [0..T)`` of each row's logical ring, through the page table
    ``t`` (B, N), on raw arrays. No ring is built: where the pool is a
    donated argument of the executable this is an update in place.

    The scatter runs over the pool viewed as rows, ``(P * KV * page[,
    D])`` with one indexed dimension. Indexed as ``p.at[pid, :, off]``
    XLA:TPU lays the operand out with the two indexed dimensions
    outermost and copies the whole pool there and back around the
    scatter, donated or not (tests/test_chip_compile.py holds the
    compiled step to having no such copy).

    The invariants are :func:`paged_kv_scatter`'s: a scatter-``set`` of
    copied rows; only positions at or past ``sp`` are written (pages a
    prefix shares stay as they are); rows of all-null table rows (dead
    lanes) and positions past the ring's end land on page 0, which is
    zeroed again at the end.

    ``ring``: the table's N columns are a ring of pages, logical page j
    of a row in column ``j mod N`` (a layer whose keys are bounded by a
    window): a position is written over the one N pages before it, and no
    position is past the end.
    """
    jnp = _jnp()
    kv, page = p.shape[1], p.shape[2]
    n_pages, t_len = t.shape[1], new.shape[2]
    pos = sp.astype(jnp.int32)[:, None] \
        + jnp.arange(t_len, dtype=jnp.int32)[None, :]               # (B, T)
    if ring:
        pid = jnp.take_along_axis(
            t.astype(jnp.int32), (pos // page) % n_pages, axis=1)
    else:
        pid = jnp.take_along_axis(
            t.astype(jnp.int32), jnp.clip(pos // page, 0, n_pages - 1),
            axis=1)
        pid = jnp.where(pos < n_pages * page, pid, 0)
    head = jnp.arange(kv, dtype=jnp.int32)[None, :, None]
    row = (pid[:, None, :] * kv + head) * page + (pos % page)[:, None, :]
    rows = p.reshape((-1,) + p.shape[3:])                 # (P*KV*page[, D])
    out = rows.at[row].set(new.astype(p.dtype)).reshape(p.shape)
    return out.at[0].set(jnp.zeros_like(out[0]))


def paged_kv_gather(pool, page_table):
    """Materialize per-slot contiguous KV rings from a paged pool.

    ``pool`` is (P, KV, page, D) — or (P, KV, page) for a scale pool —
    and ``page_table`` (B, N) int32 maps slot ``b``'s logical page ``i``
    to a pool page id (0 = the reserved null page, which the serving
    step keeps zeroed). Returns the (B, KV, N*page, D) ring — an exact
    copy (``jnp.take``), bit-preserving by construction. Positions the
    slot does not own read null-page zeros; the attention position mask
    (``s <= start_pos + t``) guarantees they are never attended before
    being overwritten.
    """

    return _apply(gather_pages, (pool, page_table), name="paged_kv_gather")


def paged_kv_scatter(pool, page_table, ring, start_pos, length):
    """Write the ``length`` freshly-written ring rows at positions
    ``start_pos[b] + [0..length)`` of ``ring`` (B, KV, S, D) back into the
    paged ``pool`` through ``page_table`` (B, N). 3-D scale rings
    (B, KV, S) scatter into (P, KV, page) pools the same way.

    Exact copy in both directions: rows are extracted with
    ``take_along_axis`` and written with a scatter-``set`` (copied, not
    merged). Slots whose table rows are all-null (dead/idle lanes of a
    fixed-width decode step) land their writes on page 0; page 0 is
    re-zeroed at the end of the op, so the null page reads as zeros on
    every gather — dead lanes can never feed garbage back to themselves
    across steps.
    """

    def f(p, t, r, sp):
        jnp = _jnp()
        page = p.shape[2]
        n_pages = t.shape[1]
        s_len = r.shape[2]
        pos = sp.astype(jnp.int32)[:, None] \
            + jnp.arange(length, dtype=jnp.int32)[None, :]          # (B, L)
        pos = jnp.clip(pos, 0, s_len - 1)
        pid = jnp.take_along_axis(
            t.astype(jnp.int32),
            jnp.clip(pos // page, 0, n_pages - 1), axis=1)          # (B, L)
        off = pos % page                                            # (B, L)
        if r.ndim == 4:
            rows = jnp.take_along_axis(r, pos[:, None, :, None], axis=2)
            vals = rows.transpose(0, 2, 1, 3)                # (B, L, KV, D)
        else:
            rows = jnp.take_along_axis(r, pos[:, None, :], axis=2)
            vals = rows.transpose(0, 2, 1)                   # (B, L, KV)
        out = p.at[pid, :, off].set(vals)
        # keep the null-page invariant: page 0 always reads as zeros
        return out.at[0].set(jnp.zeros_like(out[0]))

    return _apply(f, (pool, page_table, ring, start_pos),
                  name="paged_kv_scatter")


# ---------------------------------------------------------------------------
# Recurrent-state serving ops (the Mamba-2 mixer of models/falcon_h1.py,
# the Gated DeltaNet mixer of models/gigachat35.py)
#
# A state-space layer keeps, for every sequence, a fixed-size state that
# every position advances: the last K-1 inputs of its causal convolution
# and the (H, P, N) matrix of the selective scan, or the (H, K, V) matrix
# of the gated delta rule. Unlike a KV ring there
# is no position mask to hide stale or padded rows behind, so the two ops
# below take the step's lane contract as arguments and keep it themselves:
#
# (a) positions ``t >= valid_len[b]`` (the padded tail of a prefill chunk)
#     leave both states as the last valid position left them;
# (b) rows with ``live[b]`` false (an empty slot, a slot whose neighbour
#     is being prefilled) hand their state back bit for bit;
# (c) rows with ``start_pos[b] == 0`` (a request's first call) start from
#     zero, whatever the lane's last tenant left.
#
# Both are plain XLA operations inside the step executable. Outside a
# serving step (a whole sequence from zero state, every position valid,
# every row live) the four lane arguments are left ``None``.
# ---------------------------------------------------------------------------


def _lane_defaults(batch, t_len, state_shape, state, start_pos, valid_len,
                   live):
    """The lane arguments with what ``None`` stands for filled in."""
    jnp = _jnp()
    if state is None:
        state = jnp.zeros((batch,) + state_shape, jnp.float32)
    if start_pos is None:
        start_pos = jnp.zeros((batch,), jnp.int32)
    if valid_len is None:
        valid_len = jnp.full((batch,), t_len, jnp.int32)
    if live is None:
        live = jnp.ones((batch,), bool)
    return state, start_pos, valid_len, live


def state_rows_gather(store, lanes):
    """The rows of a recurrent-state array (R, ...) that a call's batch
    rows read: ``lanes`` (B,) int32 names each batch row's row of
    ``store``, negative for a row that is not live. A call as wide as
    the store (a decode step over every slot; a ring cache) maps row i to
    row i and moves nothing; a narrower one (the (1, chunk) prefill)
    takes its rows, an exact copy."""
    if store.shape[0] == lanes.shape[0]:
        return store

    @_scoped("ssm.state")
    def f(st, ln):
        jnp = _jnp()
        return jnp.take(st, jnp.maximum(ln.astype(jnp.int32), 0), axis=0)

    return _apply(f, (store, lanes), name="state_rows_gather")


def state_rows_scatter(store, lanes, rows):
    """The inverse of :func:`state_rows_gather`: ``store`` with ``rows``
    written back at ``lanes``. Rows that are not live are dropped, so the
    store keeps theirs untouched."""
    if store.shape[0] == lanes.shape[0]:
        return rows

    @_scoped("ssm.state")
    def f(st, ln, new):
        jnp = _jnp()
        ln = ln.astype(jnp.int32)
        at = jnp.where(ln >= 0, ln, st.shape[0])    # out of range: dropped
        return st.at[at].set(new, mode="drop")

    return _apply(f, (store, lanes, rows), name="state_rows_scatter")


# ---------------------------------------------------------------------------
# The greedy ids a serving step keeps on the device. ``ids`` (R,) int32
# holds, one row a slot, the argmax of the last logits a call made for
# that slot; ``rows`` (B,) int32 names each batch row's row of ``ids``,
# negative for a batch row that is bound to none in this call.
# ---------------------------------------------------------------------------


def carried_tokens(tokens, ids, rows):
    """``tokens`` (B, T) with every negative entry replaced by its batch
    row's carried id: the per-row choice between a token the host sent
    and the one the call before left on the device."""

    @_scoped("embed")
    def f(tok, kept, at):
        jnp = _jnp()
        mine = jnp.take(kept, jnp.maximum(at.astype(jnp.int32), 0))
        return jnp.where(tok < 0, mine[:, None].astype(tok.dtype), tok)

    return _apply(f, (tokens, ids, rows), name="carried_tokens")


def keep_greedy_ids(ids, rows, logits):
    """``ids`` with the argmax of each bound batch row's ``logits``
    (B, V) written at its row, as int32, ties to the lowest index (what
    ``serve.generate.sample_tokens`` picks from the same row); a batch
    row bound to none is dropped."""

    @_scoped("head")
    def f(kept, at, lg):
        jnp = _jnp()
        at = at.astype(jnp.int32)
        best = jnp.argmax(lg, axis=-1).astype(kept.dtype)
        at = jnp.where(at >= 0, at, kept.shape[0])   # out of range: dropped
        return kept.at[at].set(best, mode="drop")

    return _apply(f, (ids, rows, logits), name="keep_greedy_ids")


def grouped_rms_norm(data, gamma, groups=1, eps=1e-6):
    """RMSNorm over each of ``groups`` equal slices of the last axis
    (Mamba-2's gated norm with ``n_groups`` > 1), then the gain."""

    @_scoped("norm")
    def f(x, g):
        jnp = _jnp()
        xg = x.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
        ms = jnp.mean(jnp.square(xg), axis=-1, keepdims=True)
        return (xg * (1.0 / jnp.sqrt(ms + eps))).reshape(x.shape) * g

    return _apply(f, (data, gamma), name="grouped_rms_norm")


def causal_conv1d(data, weight, bias, state=None, start_pos=None,
                  valid_len=None, live=None):
    """Depthwise causal convolution along time with carried context.

    ``data`` (B, T, C), ``weight`` (C, K) with ``weight[:, K-1]`` on the
    current position, ``bias`` (C,) or None, ``state`` (B, K-1, C): the
    K-1 inputs before ``data[:, 0]``. Returns ``(out (B, T, C), new_state)`` under the
    lane contract of the section comment: ``new_state`` holds the last
    K-1 inputs at or before position ``valid_len[b] - 1``.
    """

    @_scoped("ssm.conv")
    def f(x, w, b, st, sp, vl, lv):
        jnp = _jnp()
        t_len, k = x.shape[1], w.shape[1]
        st, sp, vl, lv = _lane_defaults(
            x.shape[0], t_len, (k - 1, x.shape[2]), st, sp, vl, lv)
        fresh = (sp.astype(jnp.int32) == 0)[:, None, None]
        win = jnp.concatenate([jnp.where(fresh, 0.0, st), x], axis=1)
        out = b
        for j in range(k):
            term = win[:, j:j + t_len, :] * w[:, j]
            out = term if out is None else out + term
        # window row i holds input i - (K-1): the last K-1 valid inputs
        # are rows valid_len .. valid_len + K - 2
        if t_len == 1:
            # valid_len is 0 or 1: a slice, where a gather would lay the
            # state out anew (a copy of it a step on a TPU)
            new = jnp.where((vl.astype(jnp.int32) > 0)[:, None, None],
                            win[:, 1:], win[:, :-1])
        else:
            rows = vl.astype(jnp.int32)[:, None] \
                + jnp.arange(k - 1, dtype=jnp.int32)[None, :]
            new = jnp.take_along_axis(win, rows[:, :, None], axis=1)
        return out, jnp.where(lv.astype(bool)[:, None, None], new, st)

    return _apply(f, (data, weight, bias, state, start_pos, valid_len, live),
                  name="causal_conv1d")


def ssd_scan(x, dt, a, b_mat, c_mat, d, state=None, start_pos=None,
             valid_len=None, live=None, chunk=128):
    """Mamba-2 selective scan (state-space duality form) with carried state.

    For every head ``h`` (scalar decay), ``S_t = exp(dt_t a) S_{t-1} +
    dt_t x_t (x) B_t`` and ``y_t = S_t C_t + d x_t``. ``x`` (B, T, H, P),
    ``dt`` (B, T, H) (after softplus), ``a`` (H,) negative, ``b_mat`` /
    ``c_mat`` (B, T, G, N) with H / G heads to a group, ``d`` (H,),
    ``state`` (B, H, P, N). Returns ``(y (B, T, H, P), new_state)`` under
    the lane contract of the section comment.

    T = 1 is the recurrence itself, one pass over the state. T > 1 runs
    in chunks of ``chunk`` positions: inside a chunk the outputs are a
    masked (T, T) product and the state enters and leaves once, so a
    prefill chunk costs matrix products, not T passes over the state.
    The two are the same mathematics (tests/test_falcon_h1.py holds them
    to each other across a chunk edge and a padded tail).
    """
    chunk = int(chunk)

    @_scoped("ssm.scan")
    def f(xv, dtv, av, bv, cv, dv, st, sp, vl, lv):
        jnp = _jnp()
        t_len, heads = xv.shape[1], xv.shape[2]
        st, sp, vl, lv = _lane_defaults(
            xv.shape[0], t_len, xv.shape[2:] + bv.shape[3:], st, sp, vl, lv)
        rep = heads // bv.shape[2]
        prec = stored_precision(xv, st)
        alive = lv.astype(bool)[:, None, None, None]
        fresh = (sp.astype(jnp.int32) == 0)[:, None, None, None]
        s0 = jnp.where(fresh, 0.0, st)
        valid = jnp.arange(t_len, dtype=jnp.int32)[None, :] \
            < vl.astype(jnp.int32)[:, None]                      # (B, T)
        # a position past the row's valid length neither decays the
        # state nor adds to it
        dtv = jnp.where(valid[:, :, None], dtv, 0.0)
        bh = jnp.repeat(bv, rep, axis=2)                         # (B,T,H,N)
        ch = jnp.repeat(cv, rep, axis=2)
        xdt = xv * dtv[..., None]                                # (B,T,H,P)
        if t_len == 1:
            decay = jnp.exp(dtv[:, 0] * av)                      # (B, H)
            s1 = s0 * decay[:, :, None, None] \
                + xdt[:, 0, :, :, None] * bh[:, 0, :, None, :]
            y = jnp.sum(s1 * ch[:, 0, :, None, :], axis=-1) \
                + dv[:, None] * xv[:, 0]
            return y[:, None], jnp.where(alive, s1, st)
        q = min(chunk, t_len)
        ys, s = [], s0
        for lo in range(0, t_len, q):
            hi = min(lo + q, t_len)
            la = jnp.cumsum(dtv[:, lo:hi] * av, axis=1)          # (B,q,H)
            # W[b,h,t,s] = (C_t . B_s) exp(L_t - L_s) for s <= t
            cb = jnp.einsum("btgn,bsgn->bgts", cv[:, lo:hi], bv[:, lo:hi],
                            precision=prec)
            seg = la[:, :, None, :] - la[:, None, :, :]          # (B,t,s,H)
            causal = jnp.tril(jnp.ones((hi - lo, hi - lo), bool))
            m = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
            w = jnp.repeat(cb, rep, axis=1) * m.transpose(0, 3, 1, 2)
            y = jnp.einsum("bhts,bshp->bthp", w, xdt[:, lo:hi],
                           precision=prec)
            y = y + jnp.exp(la)[..., None] * jnp.einsum(
                "bthn,bhpn->bthp", ch[:, lo:hi], s, precision=prec)
            ys.append(y + dv[:, None] * xv[:, lo:hi])
            to_end = jnp.exp(la[:, -1:, :] - la)                 # (B,q,H)
            s = s * jnp.exp(la[:, -1])[:, :, None, None] + jnp.einsum(
                "bshp,bshn->bhpn", xdt[:, lo:hi] * to_end[..., None],
                bh[:, lo:hi], precision=prec)
        return jnp.concatenate(ys, axis=1), jnp.where(alive, s, st)

    return _apply(f, (x, dt, a, b_mat, c_mat, d, state, start_pos,
                      valid_len, live), name="ssd_scan")


def gated_delta_rule(q, k, v, g, beta, state=None, start_pos=None,
                     valid_len=None, live=None, chunk=64):
    """The gated delta rule (Gated DeltaNet) with carried state.

    For every value head ``h`` and its key head ``h // (H / Hk)``, with q
    and k L2-normalised a head and q scaled by ``K ** -0.5``::

        S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t

    ``q``, ``k`` (B, T, Hk, K), ``v`` (B, T, H, V), ``g`` (B, T, H) the
    log decay (at most 0), ``beta`` (B, T, H) in (0, 1), ``state`` (B, H,
    K, V). Returns ``(o (B, T, H, V), new_state)`` under the lane contract
    of the section comment.

    T = 1 is the recurrence itself: one pass reads the state for ``S^T q``
    and ``S^T k``, a second writes the new state, and the output is put
    together from the two products without reading it again. T > 1 runs
    in chunks of ``chunk`` positions in the WY form of Yang et al. (Gated
    Delta Networks, arXiv:2412.06464): inside a chunk the updates are one
    unit lower triangular solve and masked (T, T) products, and the state
    enters and leaves once. The two are the same mathematics
    (tests/test_gigachat35.py holds them to the plain recurrence across a
    chunk edge, a start state, a padded tail and dead lanes).
    """
    chunk = int(chunk)

    @_scoped("delta.rule")
    def f(qv, kv, vv, gv, bv, st, sp, vl, lv):
        import jax

        jnp = _jnp()
        b, t_len, heads, dv = vv.shape
        dk = qv.shape[-1]
        rep = heads // qv.shape[2]
        st, sp, vl, lv = _lane_defaults(b, t_len, (heads, dk, dv), st, sp,
                                        vl, lv)
        prec = stored_precision(vv, st)

        def unit(x):
            return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                     + 1e-6)

        qh = jnp.repeat(unit(qv) * dk ** -0.5, rep, axis=2)     # (B,T,H,K)
        kh = jnp.repeat(unit(kv), rep, axis=2)
        # a position past the row's valid length neither decays the state
        # nor writes to it
        valid = (jnp.arange(t_len, dtype=jnp.int32)[None, :]
                 < vl.astype(jnp.int32)[:, None])[:, :, None]  # (B, T, 1)
        gv = jnp.where(valid, gv, 0.0)
        bv = jnp.where(valid, bv, 0.0)
        alive = lv.astype(bool)[:, None, None, None]
        fresh = (sp.astype(jnp.int32) == 0)[:, None, None, None]
        s0 = jnp.where(fresh, 0.0, st)
        if t_len == 1:
            q0, k0, v0 = qh[:, 0], kh[:, 0], vv[:, 0]           # (B, H, .)
            decay = jnp.exp(gv[:, 0])[..., None]                 # (B, H, 1)
            be = bv[:, 0][..., None]
            sk = jnp.sum(k0[..., None] * s0, axis=2)             # S^T k
            sq = jnp.sum(q0[..., None] * s0, axis=2)             # S^T q
            kq = jnp.sum(k0 * q0, axis=-1, keepdims=True)
            new = be * (v0 - decay * sk)                         # (B, H, V)
            s1 = decay[..., None] * s0 + k0[..., None] * new[:, :, None, :]
            o = decay * sq + kq * new
            return o[:, None], jnp.where(alive, s1, st)
        heads_first = (0, 2, 1, 3)
        qh, kh = qh.transpose(heads_first), kh.transpose(heads_first)
        vh = vv.transpose(heads_first)                           # (B,H,T,V)
        gh, bh = gv.transpose(0, 2, 1), bv.transpose(0, 2, 1)    # (B, H, T)
        c = min(chunk, t_len)
        os, s = [], s0
        for lo in range(0, t_len, c):
            hi = min(lo + c, t_len)
            qc, kc, vc = qh[:, :, lo:hi], kh[:, :, lo:hi], vh[:, :, lo:hi]
            bc = bh[:, :, lo:hi]
            cum = jnp.cumsum(gh[:, :, lo:hi], axis=-1)           # (B,H,n)
            n = hi - lo
            inside = jnp.tril(jnp.ones((n, n), bool))
            # exp(G_t - G_s) for s <= t, 0 above
            ratio = jnp.exp(jnp.where(inside, cum[..., :, None]
                                      - cum[..., None, :], -jnp.inf))
            kk = jnp.einsum("bhtd,bhsd->bhts", kc, kc, precision=prec)
            a = jnp.where(jnp.tril(inside, -1),
                          bc[..., :, None] * ratio * kk, 0.0)
            # (I + A) [U | W_k] = [beta v | beta exp(G) k]
            rhs = jnp.concatenate(
                [bc[..., None] * vc,
                 (bc * jnp.exp(cum))[..., None] * kc], axis=-1)
            sol = jax.lax.linalg.triangular_solve(
                a, rhs, left_side=True, lower=True, unit_diagonal=True)
            w = sol[..., :dv] - jnp.einsum("bhtk,bhkv->bhtv",
                                           sol[..., dv:], s, precision=prec)
            qk = jnp.einsum("bhtd,bhsd->bhts", qc, kc, precision=prec) \
                * ratio
            os.append(jnp.exp(cum)[..., None] * jnp.einsum(
                "bhtk,bhkv->bhtv", qc, s, precision=prec)
                + jnp.einsum("bhts,bhsv->bhtv", qk, w, precision=prec))
            to_end = jnp.exp(cum[..., -1:] - cum)                # (B,H,n)
            s = jnp.exp(cum[..., -1])[..., None, None] * s + jnp.einsum(
                "bhsk,bhsv->bhkv", kc * to_end[..., None], w, precision=prec)
        o = jnp.concatenate(os, axis=2).transpose(heads_first)
        return o, jnp.where(alive, s, st)

    return _apply(f, (q, k, v, g, beta, state, start_pos, valid_len, live),
                  name="gated_delta_rule")


# ---------------------------------------------------------------------------
# Routed experts (the feed-forward of models/mellum.py and
# models/command_a_plus.py)
#
# A router scores every token against all ``E`` experts (``score``: the
# softmax over them, or each expert's own sigmoid); the token's ``k`` best
# (a tie goes to the lower index) share their scores' sum, renormalised
# to 1, and the layer's output is the weighted sum of those experts'
# SwiGLU products. There is no capacity: a token gets all k of its
# experts whatever the load. A score-correction bias (DeepSeek-V3's
# ``e_score_correction_bias``) moves which experts are chosen and not
# their weights; a SwiGLU limit clamps the gate from above and the up
# product on both sides before they meet.
#
# The layer may hold a range of the experts (``held``: expert parallelism,
# or the chip's share of a deployment). The router keeps its full width
# and the result is the part of the sum that the held experts give; the
# parts of all the ranges add up to the whole layer.
#
# Two formulations of the expert products, the same mathematics; which
# one a cached call takes is ``expert_form``'s to say, from the call's
# shapes and whether the kernel covers it (``grouped_in_kernel``).
# ``grouped`` sorts the (token, expert) assignments by expert and
# multiplies each expert hit with its own rows (an expert nobody picked is
# never read, N x k rows of work whatever E is): on a TPU in one Pallas
# kernel that reads each hit expert once a call (ops/pallas/
# expert_products.py, where its rows fit), elsewhere in a while loop over
# tiles of
# ``tile`` sorted rows, each against the one expert it belongs to (an
# expert read once a tile); a call of many positions, and a decode step
# whose rows can hit fewer than nearly all the held experts. ``dense``
# multiplies every token with every held expert in one product over the
# stack and weights the products (differentiable, E / k times the work):
# the normal path, and a decode step whose rows hit nearly all the held
# experts.
# ---------------------------------------------------------------------------

# The share of the held experts a decode step can expect to hit under
# which it takes the grouped form, with the kernel and with the XLA loop.
# Properties of the formulations on a v5e, from the row sweeps of
# exp/moe_products_bench.py (PERF.md): the kernel reads each hit expert
# once at the bandwidth the one product over the stack reaches, so it
# wins at 16 rows of 8 of 64 experts of 2304 x 896 (0.88 reckoned, 57
# hit) and ties at 32 (0.986, all 64 hit), and at 8 of 128 experts of
# 4096 x 4096 wins at every row count the sweep ran; the loop's tiles
# read an expert at 1.5 times the one product's cost at 64 experts of
# 2304 x 896 (0.054 against 0.035 ms: a tie at 0.66) and 1.09 times at 8
# of 4096 x 4096, and the lower crossover stands for both.
GROUPED_UNDER_SHARE = 0.95
TILED_UNDER_SHARE = 0.7


def expected_hit_share(rows, top_k, num_experts):
    """The share of the held experts that ``rows`` tokens can expect to
    hit when each takes ``top_k`` of ``num_experts`` and routing is even
    (an expert is missed by one token with probability 1 - k / E)."""
    return 1.0 - (1.0 - top_k / num_experts) ** rows


def expert_form(rows, positions, top_k, num_experts, kernel):
    """Which formulation of the expert products (the section comment) a
    cached call of ``rows`` tokens (lanes x positions) takes, from its
    static shapes alone and ``kernel``, whether its grouped form would run
    in the kernel (:func:`grouped_in_kernel`). ``"grouped"`` for a call
    of more than one position (N x k rows of work against N x E), and for
    a decode step whose ``expected_hit_share`` is under
    ``GROUPED_UNDER_SHARE`` (the kernel) or ``TILED_UNDER_SHARE`` (the
    loop); else ``"dense"``."""
    under = GROUPED_UNDER_SHARE if kernel else TILED_UNDER_SHARE
    if positions > 1 or \
            expected_hit_share(rows, top_k, num_experts) < under:
        return "grouped"
    return "dense"


def grouped_in_kernel(x, gate):
    """Whether the grouped form of a call over ``x`` (..., H) with the
    stack ``gate`` (E, H, F) runs in the kernel of
    ``ops.pallas.expert_products`` (its ``supports``: a TPU or the
    interpreter, widths it tiles, rows that fit VMEM) rather than the XLA
    loop; arrays or NDArrays."""
    from .pallas import expert_products as kern

    return kern.supports(getattr(x, "_data", x), getattr(gate, "_data", gate))


@_scoped("experts.router")
def route_top_k(logits, top_k, renormalize=True, score="softmax",
                groups=None, bias=None):
    """``(weights (N, k), experts (N, k) int32)`` of router ``logits``
    (N, E), on raw arrays: the scores in float32 (``score``: ``"softmax"``
    over all E, or ``"sigmoid"`` of each expert's own logit), the k
    largest (``lax.top_k``: of equal values the lower index first),
    renormalised to sum to 1.

    ``groups`` (``(n_group, topk_group)``; None: no limit) keeps a
    token's experts inside its ``topk_group`` best of ``n_group`` groups
    of ``E / n_group`` consecutive experts: a group's score is the sum of
    its two largest scores, of equal groups the lower index first, and
    the k largest are then taken among the kept groups' experts alone
    (``k <= topk_group * E / n_group``).

    ``bias`` (E,) (None: none) is added to the scores for the choice
    alone, of the groups and of the experts: the weights are the chosen
    experts' own scores."""
    import jax

    jnp = _jnp()
    if score not in ("softmax", "sigmoid"):
        raise MXNetError(f"router score {score!r}")
    z = logits.astype(jnp.float32)
    p = jax.nn.softmax(z, axis=-1) if score == "softmax" \
        else jax.nn.sigmoid(z)
    if groups is None and bias is None:
        w, idx = jax.lax.top_k(p, int(top_k))
    else:
        sel = p if bias is None else p + bias.astype(jnp.float32)
        # scores are positive: an expert outside the kept groups, at -1,
        # comes after every expert inside them; a bias may take a chosen
        # score under 0, so there it is -inf
        if groups is None:
            _, idx = jax.lax.top_k(sel, int(top_k))
        else:
            idx = _top_k_in_groups(sel, int(top_k), groups,
                                   -1.0 if bias is None else -jnp.inf)
        w = jnp.take_along_axis(p, idx, axis=1)
    if renormalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, idx.astype(jnp.int32)


def _top_k_in_groups(s, top_k, groups, outside):
    """The experts (N, k) of :func:`route_top_k`'s group limit over the
    scores ``s`` (N, E); an expert outside the kept groups counts as
    ``outside``."""
    import jax

    jnp = _jnp()
    n_group, keep = int(groups[0]), int(groups[1])
    n, e = s.shape
    per = e // n_group
    if e % n_group or not 0 < keep <= n_group or per < 2 \
            or top_k > keep * per:
        raise MXNetError(
            f"router groups: the best {keep} of {n_group} groups of "
            f"{e} experts cannot give a token {top_k}")
    best2, _ = jax.lax.top_k(s.reshape(n, n_group, per), 2)
    _, kept = jax.lax.top_k(jnp.sum(best2, axis=-1), keep)  # (N, keep)
    in_kept = jnp.any(
        kept[:, :, None] == jnp.arange(n_group, dtype=kept.dtype),
        axis=1)                                              # (N, n_group)
    _, idx = jax.lax.top_k(
        jnp.where(jnp.repeat(in_kept, per, axis=1), s, outside), top_k)
    return idx


def swiglu(gate, up, limit=None):
    """``silu(gate) * up`` on raw arrays; with ``limit`` (the
    configuration's ``swiglu_limit``) ``silu(min(gate, limit)) *
    clip(up, -limit, limit)``."""
    import jax

    jnp = _jnp()
    if limit is not None:
        gate = jnp.minimum(gate, limit)
        up = jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def _swiglu_rows(rows, g, u, d, prec, limit=None):
    jnp = _jnp()
    h = swiglu(jnp.dot(rows, g, precision=prec),
               jnp.dot(rows, u, precision=prec), limit)
    return jnp.dot(h, d, precision=prec)


def grouped_expert_products(x, local, gate, up, down, tile=32, limit=None):
    """``E_e(x_n)`` for every assignment, on raw arrays: ``x`` (N, H),
    ``local`` (N, k) int32 expert of each assignment among the ``E`` held
    (``E`` itself for one that is not computed: an expert held elsewhere,
    a token that is not live), weights ``gate``/``up`` (E, H, F) and
    ``down`` (E, F, H), ``limit`` :func:`swiglu`'s. Returns ``((N, k, H)
    products, (E,) int32 rows of each expert, the weight reads of the
    stack)``; an assignment that is not computed gives zeros.

    Where the kernel covers the call (:func:`grouped_in_kernel`) it reads
    each hit expert once; elsewhere a loop walks tiles of ``tile`` sorted
    rows and reads an expert once a tile."""
    from .pallas import expert_products as kern

    jnp = _jnp()
    n, k = local.shape
    e_held = gate.shape[0]
    a = n * k
    flat = local.reshape(a)
    with _device_scope("experts.router"):   # the sort by expert
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        edges = jnp.searchsorted(
            flat[order], jnp.arange(e_held + 1, dtype=jnp.int32),
            side="left").astype(jnp.int32)                       # (E + 1,)
        counts = edges[1:] - edges[:-1]
    if not grouped_in_kernel(x, gate):
        ys, reads = _tiled_expert_products(x, order, edges, counts, k, gate,
                                           up, down, int(tile), limit)
        with _device_scope("experts.router"):   # back to the tokens' order
            back = jnp.zeros((a,), jnp.int32).at[order].set(
                jnp.arange(a, dtype=jnp.int32))
            return ys[back].reshape(n, k, -1), counts, reads
    with _device_scope("experts.router"):   # the padded layout, the hit list
        sub = kern.sublane_rows(x.dtype)
        starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(
            (counts + sub - 1) // sub * sub).astype(jnp.int32)])  # (E + 1,)
        # sorted row i of expert e lands at starts[e] + i - edges[e]; the
        # rows that are not computed (e = E) land past every expert's
        sorted_pos = jnp.arange(a, dtype=jnp.int32) \
            + (starts - edges)[flat[order]]
        pos = jnp.zeros((a,), jnp.int32).at[order].set(sorted_pos)
        p = kern.padded_rows(a, e_held, sub)
        xs = jnp.zeros((p, x.shape[1]), x.dtype).at[pos].set(
            x[jnp.arange(a, dtype=jnp.int32) // k], mode="drop")
        nhit = jnp.sum(counts > 0).astype(jnp.int32)
        hit = jnp.sort(jnp.where(counts > 0,
                                 jnp.arange(e_held, dtype=jnp.int32),
                                 jnp.int32(e_held)))
        hit = jnp.where(hit < e_held, hit,
                        hit[jnp.maximum(nhit - 1, 0)] % e_held)
    ys = kern.expert_products(xs, starts, counts, hit, nhit.reshape(1), gate,
                              up, down, n, limit, stored_precision(x, gate))
    with _device_scope("experts.router"):   # back to the tokens' order
        mine = (flat < e_held)[:, None]
        ys = jnp.where(mine, ys[jnp.minimum(pos, p - 1)], 0)
        return ys.reshape(n, k, -1), counts, nhit


def _tiled_expert_products(x, order, edges, counts, k, gate, up, down, tile,
                           limit):
    """The sorted rows' products by tiles of ``tile`` rows, none across two
    experts, in a while loop that slices each tile's expert out of the
    stacks: ``((A + tile, H) products in sorted order, tiles walked)``."""
    import jax

    jnp = _jnp()
    a, h = order.shape[0], x.shape[1]
    with _device_scope("experts.router"):
        # tiles of ``tile`` sorted rows, none across two experts: expert e
        # has ceil(count / tile), in expert order
        ends = jnp.cumsum((counts + tile - 1) // tile).astype(jnp.int32)
        # slices never clamp: a tile may start on the last row
        xs = jnp.concatenate([x[order // k], jnp.zeros((tile, h), x.dtype)])
    prec = stored_precision(x, gate)
    row_in_tile = jnp.arange(tile, dtype=jnp.int32)
    zero = jnp.int32(0)   # jax_enable_x64 is on: a bare 0 would be an i64

    def body(t, ys):
        e = jnp.searchsorted(ends, t, side="right").astype(jnp.int32)
        first = edges[e] + (t - (ends[e] - (counts[e] + tile - 1) // tile)) \
            * tile
        rows = jax.lax.dynamic_slice(xs, (first, zero), (tile, h))
        at = lambda w: jax.lax.dynamic_index_in_dim(w, e, 0, keepdims=False)  # noqa: E731
        y = _swiglu_rows(rows, at(gate), at(up), at(down), prec, limit)
        mine = (first + row_in_tile < edges[e + 1])[:, None]
        old = jax.lax.dynamic_slice(ys, (first, zero), (tile, h))
        return jax.lax.dynamic_update_slice(
            ys, jnp.where(mine, y, old), (first, zero))

    ys = jax.lax.fori_loop(zero, ends[-1], body,
                           jnp.zeros((a + tile, h), x.dtype))
    return ys, ends[-1]


def dense_expert_products(x, gate, up, down, limit=None):
    """``E_e(x_n)`` for every token and every held expert, (E, N, H), on
    raw arrays."""
    jnp = _jnp()
    prec = stored_precision(x, gate)
    hid = swiglu(jnp.einsum("nh,ehf->enf", x, gate, precision=prec),
                 jnp.einsum("nh,ehf->enf", x, up, precision=prec), limit)
    return jnp.einsum("enf,efh->enh", hid, down, precision=prec)


def shared_experts(data, gate, up, down, limit=None):
    """The mean of ``S`` SwiGLU experts that every token takes: ``data``
    (B, T, H), ``gate`` / ``up`` (S, H, F), ``down`` (S, F, H), stacked as
    the routed ones are (the same three products, every token with every
    expert), so that a trace tells the two branches apart by their
    weights' leading size. ``limit`` is :func:`swiglu`'s."""
    @_scoped("experts.shared")
    def f(x, g, u, d):
        jnp = _jnp()
        b, t, h = x.shape
        y = dense_expert_products(x.reshape(b * t, h), g, u, d, limit)
        return jnp.mean(y, axis=0).reshape(b, t, h)

    return _apply(f, (data, gate, up, down), name="shared_experts")


def routed_experts(data, router_weight, gate, up, down, top_k, held=None,
                   token_live=None, renormalize=True, impl="grouped",
                   tile=32, score="softmax", groups=None, scale=None,
                   bias=None, limit=None):
    """The routed-expert feed-forward of the section comment: ``data``
    (B, T, H), ``router_weight`` (E_all, H), the held experts' ``gate`` /
    ``up`` (E, H, F) and ``down`` (E, F, H); ``held`` is ``(first, count)``
    of the experts these are among all ``E_all`` (None: all of them).
    ``token_live`` (B, T) bool says which tokens are real (None: all); the
    others are given to no expert and come back as zeros. ``groups`` and
    ``bias`` (E_all,) are :func:`route_top_k`'s; ``scale`` (None: none)
    multiplies every token's weights after they are renormalised;
    ``limit`` is :func:`swiglu`'s.

    Returns ``(out (B, T, H), load (7,) int32)``: the held experts' part
    of every token's sum, and ``[held experts that got a live token, the
    most tokens one of them got, assignments computed (the live tokens'
    that fell on a held expert), the live tokens' assignments (k each,
    held here or not), experts held, held experts whose weights the call
    read (all of them for ``dense``, those hit for ``grouped``), the
    reads of an expert's weights the call made (``E`` for ``dense``; for
    ``grouped`` one a hit expert in the kernel, one a tile in the loop:
    :func:`grouped_expert_products`)]``.
    """
    first, count = (0, gate.shape[0]) if held is None else \
        (int(held[0]), int(held[1]))
    if count != gate.shape[0]:
        raise MXNetError(f"held {held} names {count} experts, the weights "
                         f"hold {gate.shape[0]}")
    if impl not in ("grouped", "dense"):
        raise MXNetError(f"routed_experts impl {impl!r}")
    from .pallas import expert_products as kern

    # routing globals must live in f's closure (see attention())
    routing = kern._INTERPRET

    @_scoped("experts.router")
    def f(x, rw, g, u, d, live, eb):
        assert routing == kern._INTERPRET
        jnp = _jnp()
        b, t, h = x.shape
        xf = x.reshape(b * t, h)
        logits = jnp.dot(xf, rw.T, precision=stored_precision(xf, rw))
        w, idx = route_top_k(logits, top_k, renormalize, score, groups, eb)
        if scale is not None:
            w = w * scale
        local = idx - first
        here = (local >= 0) & (local < count)
        asked = b * t * idx.shape[1]
        if live is not None:
            here = here & live.reshape(b * t, 1)
            asked = jnp.sum(live) * idx.shape[1]
        local = jnp.where(here, local, count)
        w = jnp.where(here, w, 0.0)
        # under this function's scope all is the router's (scores, top-k,
        # the sort and the combine) but the experts' own products
        if impl == "grouped":
            with _device_scope("experts.routed"):
                prod, counts, reads = grouped_expert_products(
                    xf, local, g, u, d, tile, limit)
            out = jnp.sum(prod * w[:, :, None].astype(prod.dtype), axis=1)
        else:
            combine = jnp.zeros((b * t, count + 1), w.dtype).at[
                jnp.arange(b * t, dtype=jnp.int32)[:, None], local].add(w)
            with _device_scope("experts.routed"):
                prod = dense_expert_products(xf, g, u, d, limit)
            out = jnp.einsum("enh,ne->nh", prod,
                             combine[:, :count].astype(xf.dtype),
                             precision=stored_precision(xf))
            counts = jnp.zeros((count + 1,), jnp.int32).at[
                local.reshape(-1)].add(1)[:count]
            reads = count
        hit = jnp.sum(counts > 0)
        load = jnp.stack([hit, jnp.max(counts), jnp.sum(counts),
                          jnp.asarray(asked), jnp.asarray(count),
                          jnp.asarray(count) if impl == "dense" else hit,
                          jnp.asarray(reads)])
        return out.reshape(b, t, h), load.astype(jnp.int32)

    return _apply(f, (data, router_weight, gate, up, down, token_live, bias),
                  name="routed_experts")


# ---------------------------------------------------------------------------
# misc framework extras
# ---------------------------------------------------------------------------


def reshape(data, newshape, reverse=False, order="C"):  # pylint: disable=unused-argument
    return data.reshape(newshape)


def shape_array(data):
    from ..ndarray.ndarray import NDArray

    return NDArray(_onp.asarray(data.shape, _onp.int64))


def cast(data, dtype):
    return data.astype(dtype)


def slice(data, begin, end, step=None):  # pylint: disable=redefined-builtin
    import builtins

    nd = len(begin)
    step = step or (1,) * nd
    idx = tuple(builtins.slice(b, e, s) for b, e, s in zip(begin, end, step))
    return data[idx]


def slice_axis(data, axis, begin, end):
    import builtins

    idx = [builtins.slice(None)] * data.ndim
    idx[axis] = builtins.slice(begin, end)
    return data[tuple(idx)]


def slice_like(data, shape_like, axes=None):
    import builtins

    target = shape_like.shape
    idx = [builtins.slice(None)] * data.ndim
    for ax in (axes if axes is not None else range(data.ndim)):
        idx[ax] = builtins.slice(0, target[ax])
    return data[tuple(idx)]


def broadcast_like(lhs, rhs, lhs_axes=None, rhs_axes=None):  # pylint: disable=unused-argument
    return lhs.broadcast_to(rhs.shape)


def batch_dot(lhs, rhs, transpose_a=False, transpose_b=False):
    jnp = _jnp()

    def f(a, b):
        if transpose_a:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_b:
            b = jnp.swapaxes(b, -1, -2)
        return jnp.matmul(a, b)

    return _apply(f, (lhs, rhs), name="batch_dot")


def concat(*data, dim=1):
    """Concatenate along ``dim`` (reference op ``Concat``/``concat``,
    ``src/operator/nn/concat.cc``). Delegates to the numpy namespace so
    there is a single concat implementation."""
    from .. import numpy as _mxnp

    return _mxnp.concatenate(list(data), axis=dim)


def arange_like(data, start=0.0, step=1.0, repeat=1, axis=None):  # pylint: disable=unused-argument
    jnp = _jnp()
    from ..ndarray.ndarray import NDArray

    n = data.size if axis is None else data.shape[axis]
    return NDArray(jnp.arange(n) * step + start)


def reshape_like(lhs, rhs, lhs_begin=None, lhs_end=None, rhs_begin=None,
                 rhs_end=None):
    """Reshape ``lhs`` to ``rhs``'s shape (reference
    ``src/operator/tensor/elemwise_unary_op_basic.cc`` reshape_like);
    the begin/end variants splice a sub-range of rhs dims."""
    shape = list(rhs.shape)
    if any(v is not None for v in (lhs_begin, lhs_end, rhs_begin, rhs_end)):
        lb = 0 if lhs_begin is None else lhs_begin
        le = len(lhs.shape) if lhs_end is None else lhs_end
        rb = 0 if rhs_begin is None else rhs_begin
        re_ = len(shape) if rhs_end is None else rhs_end
        shape = list(lhs.shape[:lb]) + shape[rb:re_] + list(lhs.shape[le:])
    t = tuple(int(s) for s in shape)
    return _apply(lambda x: x.reshape(t), (lhs,), name="reshape_like")


def stop_gradient(data):
    """Identity whose gradient is blocked (reference ``BlockGrad``)."""
    return _apply(lambda x: x, (data,), name="stop_gradient", record=False)


def cast_storage(data, stype="default"):
    """Convert between dense and sparse storage (reference
    ``src/operator/tensor/cast_storage.cc``)."""
    from ..ndarray.ndarray import NDArray
    from ..ndarray.sparse import BaseSparseNDArray, dense_to_sparse

    if isinstance(data, BaseSparseNDArray):
        return data.tostype(stype)
    nd = data if isinstance(data, NDArray) else NDArray(data)
    if stype == "default":
        return nd
    return dense_to_sparse(nd, stype)


def depth_to_space(data, block_size):
    """(B, C·b², H, W) → (B, C, H·b, W·b) (reference
    ``src/operator/tensor/matrix_op.cc`` DepthToSpace: DCR order)."""
    b = int(block_size)

    def f(x):
        n, c, h, w = x.shape
        x = x.reshape(n, b, b, c // (b * b), h, w)
        x = x.transpose(0, 3, 4, 1, 5, 2)
        return x.reshape(n, c // (b * b), h * b, w * b)

    return _apply(f, (data,), name="depth_to_space")


def space_to_depth(data, block_size):
    """(B, C, H·b, W·b) → (B, C·b², H, W) — exact inverse of
    ``depth_to_space``."""
    b = int(block_size)

    def f(x):
        n, c, hb, wb = x.shape
        h, w = hb // b, wb // b
        x = x.reshape(n, c, h, b, w, b)
        x = x.transpose(0, 3, 5, 1, 2, 4)
        return x.reshape(n, c * b * b, h, w)

    return _apply(f, (data,), name="space_to_depth")


def im2col(data, kernel, stride=(1, 1), dilate=(1, 1), pad=(0, 0)):
    """Sliding-window patch extraction (reference
    ``src/operator/nn/im2col.h`` semantics): (B, C, H, W) →
    (B, C·kh·kw, OH·OW) with (C, kh, kw) channel-major patch order."""
    kh, kw = _tup(kernel, 2)
    sh, sw = _tup(stride, 2)
    dh, dw = _tup(dilate, 2)
    ph, pw = _tup(pad, 2)

    def f(x):
        import jax

        n, c = x.shape[0], x.shape[1]
        patches = jax.lax.conv_general_dilated_patches(
            x, (kh, kw), (sh, sw), [(ph, ph), (pw, pw)],
            rhs_dilation=(dh, dw),
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        # (B, C*kh*kw, OH, OW) with channel-major order already
        return patches.reshape(n, c * kh * kw, -1)

    return _apply(f, (data,), name="im2col")


def col2im(data, output_size, kernel, stride=(1, 1), dilate=(1, 1),
           pad=(0, 0)):
    """Inverse of :func:`im2col`: overlapping patches scatter-ADD back
    into the (B, C, H, W) image (reference ``col2im`` in
    ``src/operator/nn/im2col.h``). Implemented as the exact vjp of the
    patch extraction — transposes are the compiler's problem."""
    oh, ow = _tup(output_size, 2)
    kh, kw = _tup(kernel, 2)
    sh, sw = _tup(stride, 2)
    dh, dw = _tup(dilate, 2)
    ph, pw = _tup(pad, 2)

    def f(cols):
        import jax

        n = cols.shape[0]
        c = cols.shape[1] // (kh * kw)

        def fwd(img):
            p = jax.lax.conv_general_dilated_patches(
                img, (kh, kw), (sh, sw), [(ph, ph), (pw, pw)],
                rhs_dilation=(dh, dw),
                dimension_numbers=("NCHW", "OIHW", "NCHW"))
            return p.reshape(n, c * kh * kw, -1)

        zero = _jnp().zeros((n, c, oh, ow), cols.dtype)
        _, vjp = jax.vjp(fwd, zero)
        (img,) = vjp(cols)
        return img

    return _apply(f, (data,), name="col2im")


def adaptive_avg_pooling2d(data, output_size=1):
    """Adaptive average pooling (reference
    ``src/operator/contrib/adaptive_avg_pooling.cc``): output bin (i, j)
    averages input span [floor(i·H/oh), ceil((i+1)·H/oh)) — the
    overlapping-span geometry, computed as two masked mean reductions
    (static shapes; the spans are compile-time constants)."""
    jnp = _jnp()
    oh, ow = (output_size if isinstance(output_size, (tuple, list))
              else (output_size, output_size))

    def f(x):
        import math as _m

        B, C, H, W = x.shape

        def masks(n, o):
            m = _onp.zeros((o, n), "float32")
            for b in range(o):
                lo = _m.floor(b * n / o)
                hi = _m.ceil((b + 1) * n / o)
                m[b, lo:hi] = 1.0 / (hi - lo)
            return jnp.asarray(m)

        mh = masks(H, oh)  # (oh, H), rows sum to 1
        mw = masks(W, ow)  # (ow, W)
        t = jnp.einsum("bchw,ow->bcho", x, mw)
        return jnp.einsum("bcho,ph->bcpo", t, mh)

    return _apply(f, (data,), name="adaptive_avg_pooling2d")


def hard_sigmoid(data, alpha=0.2, beta=0.5):
    """Piecewise-linear sigmoid (reference ``HardSigmoid`` in
    ``src/operator/nn/activation``-adjacent LeakyReLU family)."""
    jnp = _jnp()
    return _apply(lambda x: jnp.clip(alpha * x + beta, 0.0, 1.0), (data,),
                  name="hard_sigmoid")


def gamma(data):
    """Elementwise gamma function Γ(x) (reference ``nd.gamma``,
    ``src/operator/tensor/elemwise_unary_op``)."""

    def f(x):
        import jax.scipy.special as jsp

        jnp = _jnp()
        # Γ via lgamma: |Γ(x)| = exp(lgamma(x)); the sign alternates on
        # the negative axis: Γ(x) < 0 iff floor(x) is odd for x < 0
        # (poles at non-positive integers are ±inf either way)
        mag = jnp.exp(jsp.gammaln(x))
        if hasattr(jsp, "gammasgn"):
            sign = jsp.gammasgn(x)
        else:
            sign = jnp.where(
                (x < 0) & (jnp.floor(x) % 2 != 0), -1.0, 1.0
            ).astype(x.dtype)
        return sign * mag

    return _apply(f, (data,), name="gamma")


def gammaln(data):
    def f(x):
        import jax.scipy.special as jsp

        return jsp.gammaln(x)

    return _apply(f, (data,), name="gammaln")


def erfinv(data):
    import jax

    return _apply(jax.lax.erf_inv, (data,), name="erfinv")


def index_copy(old_tensor, index_vector, new_tensor):
    """Copy rows of ``new_tensor`` into ``old_tensor`` at ``index_vector``
    (reference ``src/operator/contrib/index_copy.cc``)."""
    jnp = _jnp()

    def f(old, idx, new):
        return old.at[idx.astype(jnp.int32)].set(new)

    return _apply(f, (old_tensor, index_vector, new_tensor),
                  name="index_copy")


def index_array(data, axes=None):
    """Element-index grid of ``data``'s shape (reference
    ``src/operator/contrib/index_array.cc``): out[..., k] = index along
    the k-th listed axis."""
    jnp = _jnp()
    axes_t = tuple(axes) if axes is not None else None

    def f(x):
        sel = axes_t if axes_t is not None else tuple(range(x.ndim))
        grids = [jnp.broadcast_to(
            jnp.arange(x.shape[a]).reshape(
                [-1 if i == a else 1 for i in range(x.ndim)]), x.shape)
            for a in sel]
        return jnp.stack(grids, axis=-1).astype(jnp.int64)

    return _apply(f, (data,), name="index_array", record=False)


def boolean_mask(data, index, axis=0):
    """Select slices where ``index`` is nonzero (reference
    ``src/operator/contrib/boolean_mask.cc``). Output size is
    data-dependent, so this op is EAGER-ONLY (SURVEY §7 hard part 3) —
    inside jit use ``jnp.where``-style masking instead."""
    import jax
    import numpy as onp

    from ..base import MXNetError
    from ..ndarray.ndarray import NDArray

    d = data._data if isinstance(data, NDArray) else data
    m = index._data if isinstance(index, NDArray) else index
    if isinstance(d, jax.core.Tracer) or isinstance(m, jax.core.Tracer):
        raise MXNetError(
            "boolean_mask has a data-dependent output shape and cannot run "
            "under jit/hybridize; use arithmetic masking inside traces")
    keep = onp.nonzero(onp.asarray(m) != 0)[0]
    jnp = _jnp()
    return _apply(
        lambda x: jnp.take(x, jnp.asarray(keep), axis=axis), (data,),
        name="boolean_mask", cacheable=False)


# register the public ops in the global registry for list_ops parity
for _name in (
    "activation", "fully_connected", "convolution", "deconvolution", "pooling",
    "batch_norm", "layer_norm", "group_norm", "instance_norm", "rms_norm",
    "dropout", "softmax", "log_softmax", "masked_softmax", "embedding",
    "one_hot", "pick", "topk", "sequence_mask", "sequence_last",
    "sequence_reverse", "ctc_loss", "attention", "leaky_relu", "relu",
    "sigmoid", "tanh", "batch_dot", "gather_nd", "scatter_nd", "concat",
    "hard_sigmoid", "gamma", "gammaln", "erfinv", "index_copy",
    "adaptive_avg_pooling2d", "reshape_like", "stop_gradient",
    "cast_storage", "depth_to_space", "space_to_depth", "im2col", "col2im",
    "index_array", "boolean_mask",
):
    _register(_name, globals()[_name], wrapper=True)
