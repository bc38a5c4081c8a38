"""Llama-family decoder LM on the Gluon API (BASELINE.json stretch config:
"Llama-3-8B — stretch the Gluon API to modern LLM").

No reference analog (the reference predates LLMs); built TPU-first:
- RMSNorm pre-normalization (``gluon.nn.RMSNorm``)
- rotary position embeddings applied to Q/K
- grouped-query attention (n_kv_heads < n_heads) through the Pallas flash
  kernel (causal), or ring attention when a sequence-parallel mesh axis is
  active
- SwiGLU feed-forward
- weight-tied or separate LM head

``llama_sharding_rules`` lays qkv/gate/up column-parallel and o/down
row-parallel over ``tp`` (Megatron layout), embeddings over ``tp``, and the
ShardedTrainer shards the batch over ``dp``; long sequences shard over
``sp`` with ring attention.
"""
from __future__ import annotations

import collections
import functools

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ops import nn as _ops


@functools.lru_cache(maxsize=64)
def _rope_tables(t, dim, theta=10000.0, scaling=None):
    # cached: the serving hot loop recomputes the same (t, dim) table
    # every decode step — one continuous-batching iteration calls this
    # num_layers times with identical args. Callers must not mutate the
    # returned arrays (they are shared across calls).
    #
    # ``scaling`` (hashable, so that the cache keys on it): None, or
    # ``("yarn", factor, original_max_positions, beta_fast, beta_slow,
    # attention_factor)``. YaRN leaves the frequencies that turn more
    # than ``beta_fast`` times over the original context as they are,
    # divides those that turn fewer than ``beta_slow`` times by
    # ``factor``, blends linearly (by channel pair) between, and scales
    # cos and sin by ``attention_factor``. Made in float64 on the host.
    import math

    import numpy as onp

    pos = onp.arange(t)[:, None]
    freqs = 1.0 / (theta ** (onp.arange(0, dim, 2)[None] / dim))
    mscale = 1.0
    if scaling is not None:
        kind, factor, orig, beta_fast, beta_slow, mscale = scaling
        if kind != "yarn":
            raise MXNetError(f"rope scaling {kind!r}: only 'yarn' is known")

        def pair_of(turns):
            return dim * math.log(orig / (2 * math.pi * turns)) \
                / (2 * math.log(theta))

        low = max(math.floor(pair_of(beta_fast)), 0)
        high = min(math.ceil(pair_of(beta_slow)), dim - 1)
        ramp = onp.clip((onp.arange(dim // 2) - low) / max(high - low, 1e-3),
                        0.0, 1.0)[None]
        freqs = (1.0 - ramp) * freqs + ramp * freqs / factor
    ang = pos * freqs  # (T, dim/2)
    return (mscale * onp.cos(ang)).astype("float32"), \
        (mscale * onp.sin(ang)).astype("float32")


@functools.lru_cache(maxsize=16)
def _band(t, window):
    """(T, T) bool: query ``i`` sees key ``j`` iff ``0 <= i - j < window``."""
    import numpy as onp

    gap = onp.arange(t)[:, None] - onp.arange(t)[None, :]
    return (gap >= 0) & (gap < window)


def apply_rope(x, cos, sin):
    """Rotate pairs of channels: x is (B, H, T, D); cos/sin are (T, D/2)."""
    from .. import numpy as mnp

    d = x.shape[-1]
    x1 = x[..., 0:d:2]
    x2 = x[..., 1:d:2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    # re-interleave (..., D/2, 2) -> (..., D)
    stacked = mnp.stack([r1, r2], axis=-1)
    return stacked.reshape(*x.shape)


# What one layer keeps between serving steps, as the model's
# ``cache_spec()`` lists it a layer: the geometry of its K/V rows
# (``kv_heads`` x ``head_dim`` a position, paged or in rings) and the shapes
# of its recurrent state, one row a sequence (``state``: a tuple of
# per-sequence shapes, empty for a layer that keeps none). ``serve.KVCache``
# and ``serve.PagedKVPool`` size themselves from this and nothing else.
# ``window``: a query sees the ``window`` newest keys alone (itself among
# them), so the layer's K/V is bounded and the pool keeps it in a ring of
# pages; None for a layer that sees every key.
# ``latent``: the layer keeps ONE array a position and no pair: ``kv_heads``
# (1) x ``head_dim`` channels that every query head reads as its key, the
# first ``latent`` of which are also its value (latent attention in its
# absorbed form, ``models.ax_k1``); None for a layer that keeps K and V.
LayerCache = collections.namedtuple(
    "LayerCache", ["kv_heads", "head_dim", "state", "window", "latent"],
    defaults=(None, None))


def row_rotation(cos, sin, cache, start_pos, length):
    """The rotation table rows of each position of a cached call, (B, 1,
    ``length``, D/2): positions ``start_pos[b] + [0..length)`` of the
    (S, D/2) tables ``cos`` and ``sin``. A call that carries riders
    (``cache.riders``: a prefill chunk's call with the step's decode lanes
    as rows after its own) has one batch row; its chunk's positions come
    first and each rider's one position after them, in lane order."""
    from .. import numpy as mnp

    riders = getattr(cache, "riders", None)
    if riders is None:
        return _ops.rope_positions(cos, sin, start_pos, length)
    lanes = riders.start_pos.shape[0]
    head = _ops.rope_positions(cos, sin, start_pos, length - lanes)
    tail = _ops.rope_positions(cos, sin, riders.start_pos, 1)
    return tuple(mnp.concatenate([h, t.reshape(1, 1, lanes, -1)], axis=2)
                 for h, t in zip(head, tail))


def attend_by_segment(cache, start_pos, attend, *rows):
    """``attend(kv, start_pos, *rows)`` of a cached call: ``rows`` are
    (B, heads, T, width) arrays, positions on axis 2, and ``attend``
    writes this call's rows into ``kv``'s pages and attends through
    ``kv.page_table``, giving (B, heads, T, width). A call that carries
    riders (``cache.riders``) is two segments of one row: the chunk's
    T - lanes positions at ``start_pos``, then one position a decode lane
    at the lane's own position and pages (``attend`` sees them as (lanes,
    heads, 1, width)). Each is attended on its own, the chunk first, and
    the results are put back in row order."""
    from .. import numpy as mnp

    riders = getattr(cache, "riders", None)
    if riders is None:
        return attend(cache, start_pos, *rows)
    c = rows[0].shape[2] - riders.start_pos.shape[0]
    head = attend(cache, start_pos, *(r[:, :, :c] for r in rows))
    tail = attend(riders, riders.start_pos,
                  *(r[:, :, c:].transpose(2, 1, 0, 3) for r in rows))
    return mnp.concatenate([head, tail.transpose(2, 1, 0, 3)], axis=2)


def _serving_dense(x, weight, cache):
    """Projection by the parameter ``weight`` on the serving fast rungs:
    the int8 rung looks it up in the cache's pre-quantized side table
    (``ops.nn.quantized_dense``); otherwise the gemm at the precision the
    weight is stored at (``ops.nn.serving_dense``)."""
    qw = getattr(cache, "quant_weights", None)
    entry = qw.get(id(weight)) if qw else None
    if entry is not None:
        return _ops.quantized_dense(x, entry[0], entry[1])
    return _ops.serving_dense(x, weight.data())


def _dense_on(cache):
    """How a block's projections are computed on the path ``cache``
    stands for: ``dense(h, layer)``. No cache is the normal path (the
    layer itself); the baseline rung's cache the shape-stable
    ``ops.nn.stable_dense``, which keeps T=1 decode bitwise equal to
    T=bucket prefill; a fast rung's the gemm, or int8 through the cache's
    quant side table (:func:`_serving_dense`)."""
    if cache is None:
        return lambda h, layer: layer(h)
    baseline = getattr(cache, "path", "baseline") == "baseline"

    def dense(h, layer):
        # the layer is not called, so its scope is entered for it
        with layer.trace_scope():
            if baseline:
                return _ops.stable_dense(h, layer.weight.data())
            return _serving_dense(h, layer.weight, cache)

    return dense


class LlamaAttention(HybridBlock):
    """Causal GQA attention with RoPE; ``theta=None`` is a layer that
    applies no rotation at all (its queries and keys carry no position)."""

    def __init__(self, units, num_heads, num_kv_heads=None, theta=10000.0,
                 head_dim=None, key_multiplier=None, window=None,
                 rope_scaling=None, **kwargs):
        super().__init__(**kwargs)
        num_kv_heads = num_kv_heads or num_heads
        if (head_dim is None and units % num_heads) \
                or num_heads % num_kv_heads:
            raise MXNetError(
                f"units {units} / heads {num_heads} / kv {num_kv_heads} "
                "must divide")
        self._heads = num_heads
        self._kv_heads = num_kv_heads
        # head_dim=None is Llama's units // heads; a model whose heads
        # project to another width (20 heads of 128 on a 5120 stream)
        # says so
        self._head_dim = int(head_dim) if head_dim else units // num_heads
        self._theta = theta
        # key_multiplier=None multiplies nothing (Llama): the traced
        # program is then the one it always was
        self._key_mult = key_multiplier
        # window=None sees every key and rope_scaling=None turns at
        # theta's own frequencies (Llama): nothing is masked or scaled
        self._window = None if window is None else int(window)
        self._rope_scaling = None if rope_scaling is None \
            else tuple(rope_scaling)
        q_units = self._q_units = self._head_dim * num_heads
        kv_units = self._head_dim * num_kv_heads
        # explicit in_units: static shapes at construction, required by
        # the abstract (compile-only) functionalize path used for the 8B
        # AOT memory proof (parallel/functional.functionalize_abstract)
        self.q_proj = nn.Dense(q_units, flatten=False, use_bias=False,
                               in_units=units)
        self.k_proj = nn.Dense(kv_units, flatten=False, use_bias=False,
                               in_units=units)
        self.v_proj = nn.Dense(kv_units, flatten=False, use_bias=False,
                               in_units=units)
        self.o_proj = nn.Dense(units, flatten=False, use_bias=False,
                               in_units=q_units)

    def cache_geometry(self):
        """(kv_heads, head_dim): what a position of this layer's K/V
        cache holds."""
        return self._kv_heads, self._head_dim

    @property
    def window(self):
        return self._window

    def _rotate(self, q, k, t, start_pos=None, cache=None):
        """``q`` and ``k`` turned by this layer's table: positions ``0 ..
        t - 1``, or with ``start_pos`` the rows' own ``start_pos[b] + i``
        out of a table of ``t`` (each row's own, :func:`row_rotation`, in
        a call that carries riders). No table, no turn."""
        from .. import numpy as mnp

        if self._theta is None:
            return q, k
        cos_t, sin_t = _rope_tables(t, self._head_dim, self._theta,
                                    self._rope_scaling)
        cos, sin = mnp.array(cos_t), mnp.array(sin_t)
        if start_pos is not None:
            cos, sin = row_rotation(cos, sin, cache, start_pos, q.shape[2])
        return apply_rope(q, cos, sin), apply_rope(k, cos, sin)

    def _keys(self, k):
        return k if self._key_mult is None else k * self._key_mult

    def _heads_split(self, x, n):
        b, t, _ = x.shape
        return x.reshape(b, t, n, self._head_dim).transpose(0, 2, 1, 3)

    def forward(self, x, cache=None, start_pos=None):
        """Causal attention; ``cache=`` switches to the serving decode path.

        Training/prefill-without-cache (``cache is None``) is the original
        path — flash kernel on TPU, unchanged numerics. With ``cache`` (a
        per-layer KV slot from :class:`mxnet_tpu.serve.KVCache`) and
        ``start_pos`` ((B,) absolute position of ``x[:, 0]``), the new
        K/V rows are RoPE-rotated, written into the preallocated ring,
        and attention runs over the full ring through the shape-stable
        ``cached_attention`` op — per-token decode logits are bitwise
        identical to a full re-prefill through this same path.
        """
        from .. import numpy as mnp

        b, t, _ = x.shape
        rep = self._heads // self._kv_heads
        if cache is None:
            q = self._heads_split(self.q_proj(x), self._heads)
            k = self._heads_split(self._keys(self.k_proj(x)),
                                  self._kv_heads)
            v = self._heads_split(self.v_proj(x), self._kv_heads)
            q, k = self._rotate(q, k, t)
            if rep > 1:  # expand kv heads for the attention kernel
                k = mnp.repeat(k, rep, axis=1)
                v = mnp.repeat(v, rep, axis=1)
            if self._window is None:
                out = _ops.attention(q, k, v, causal=True)
            else:
                out = _ops.attention(q, k, v,
                                     mask=mnp.array(_band(t, self._window)))
        else:
            if start_pos is None:
                raise MXNetError("cache= requires start_pos (the (B,) "
                                 "absolute position of x[:, 0])")
            path = getattr(cache, "path", "baseline")
            if path != "baseline":
                return self._forward_cached_fast(x, cache, start_pos, path)
            if self._window is not None:
                raise MXNetError(
                    "a layer bounded by a window is served by the "
                    "continuous engine's fast rungs alone")
            # stable_dense, not Dense: the whole cache path must be
            # shape-stable so T=1 decode bitwise-matches T=bucket prefill
            dense = _dense_on(cache)
            q = self._heads_split(dense(x, self.q_proj), self._heads)
            k = self._heads_split(self._keys(dense(x, self.k_proj)),
                                  self._kv_heads)
            v = self._heads_split(dense(x, self.v_proj), self._kv_heads)
            q, k = self._rotate(q, k, cache.max_seq, start_pos)
            k_all = _ops.kv_cache_write(cache.k, k, start_pos)
            v_all = _ops.kv_cache_write(cache.v, v, start_pos)
            cache.update(k_all, v_all)
            if rep > 1:  # expand the (unrepeated) cached kv heads at use
                k_all = mnp.repeat(k_all, rep, axis=1)
                v_all = mnp.repeat(v_all, rep, axis=1)
            out = _ops.cached_attention(q, k_all, v_all, start_pos)
            out = out.transpose(0, 2, 1, 3).reshape(b, t, self._q_units)
            return dense(out, self.o_proj)
        out = out.transpose(0, 2, 1, 3).reshape(b, t, self._q_units)
        return self.o_proj(out)

    def _forward_cached_fast(self, x, cache, start_pos, path):
        """Serving fast rungs ("pallas"/"int8"): gemm (or int8) projections
        and the fused decode-attention kernel, which consumes the GQA K/V
        rings *unexpanded* — tolerance parity, not the bitwise contract.
        The projections read their weights once for every row of the
        call; the rows are written and attended by segment
        (:func:`attend_by_segment`)."""
        b, t, _ = x.shape
        dense = _dense_on(cache)
        q = self._heads_split(dense(x, self.q_proj), self._heads)
        k = self._heads_split(self._keys(dense(x, self.k_proj)),
                              self._kv_heads)
        v = self._heads_split(dense(x, self.v_proj), self._kv_heads)
        q, k = self._rotate(q, k, cache.max_seq, start_pos, cache)
        quant = getattr(cache, "quant", None)
        # with a window the table is the ring of this kind's pages
        # (serve.kv_blocks); None bounds nothing
        window = self._window
        if window is not None and (getattr(cache, "page_table", None) is None
                                   or quant is not None):
            raise MXNetError(
                "a layer bounded by a window is served from float32 page "
                "pools alone (the continuous engine's in-place step)")

        def attend(kv, sp, q, k, v):
            # a layer whose K/V are pages (the engine's step) says so with
            # its page table: rows are written into their pages and read
            # there
            table = getattr(kv, "page_table", None)
            if quant == "int8":
                k_all, k_s = _ops.kv_cache_write_q(kv.k, kv.k_scale, k, sp,
                                                   page_table=table)
                v_all, v_s = _ops.kv_cache_write_q(kv.v, kv.v_scale, v, sp,
                                                   page_table=table)
                kv.update(k_all, v_all, k_s, v_s)
                return _ops.cached_attention(q, k_all, v_all, sp, path=path,
                                             k_scale=k_s, v_scale=v_s,
                                             page_table=table)
            k_all = _ops.kv_cache_write(kv.k, k, sp, page_table=table,
                                        window=window)
            v_all = _ops.kv_cache_write(kv.v, v, sp, page_table=table,
                                        window=window)
            kv.update(k_all, v_all)
            return _ops.cached_attention(q, k_all, v_all, sp, path=path,
                                         page_table=table, window=window)

        out = attend_by_segment(cache, start_pos, attend, q, k, v)
        out = out.transpose(0, 2, 1, 3).reshape(b, t, self._q_units)
        return dense(out, self.o_proj)


class LlamaFFN(HybridBlock):
    """SwiGLU: down(silu(gate(x)) * up(x)). ``gate_multiplier`` scales
    the gate's pre-activation and ``down_multiplier`` the output; None
    (Llama) multiplies nothing. ``swiglu_limit`` clamps the gate from
    above and ``up`` on both sides before they meet (``ops.nn.swiglu``)."""

    def __init__(self, units, hidden_size, gate_multiplier=None,
                 down_multiplier=None, swiglu_limit=None, **kwargs):
        super().__init__(**kwargs)
        self._gate_mult = gate_multiplier
        self._down_mult = down_multiplier
        self._limit = swiglu_limit
        self.gate_proj = nn.Dense(hidden_size, flatten=False,
                                  use_bias=False, in_units=units)
        self.up_proj = nn.Dense(hidden_size, flatten=False, use_bias=False,
                                in_units=units)
        self.down_proj = nn.Dense(units, flatten=False, use_bias=False,
                                  in_units=hidden_size)

    def forward(self, x, cache=None):
        from .. import numpy as mnp

        dense = _dense_on(cache)
        gate = dense(x, self.gate_proj)
        if self._gate_mult is not None:
            gate = gate * self._gate_mult
        if self._limit is not None:
            gate = mnp.minimum(gate, self._limit)
        gate = _ops.activation(gate, "silu")
        up = dense(x, self.up_proj)
        if self._limit is not None:
            up = mnp.clip(up, -self._limit, self._limit)
        out = dense(gate * up, self.down_proj)
        return out if self._down_mult is None else out * self._down_mult


class LlamaBlock(HybridBlock):
    def __init__(self, units, hidden_size, num_heads, num_kv_heads,
                 norm_eps=1e-5, theta=10000.0, **kwargs):
        super().__init__(**kwargs)
        self.attn_norm = nn.RMSNorm(epsilon=norm_eps, in_channels=units)
        self.attention = LlamaAttention(units, num_heads, num_kv_heads,
                                        theta=theta)
        self.ffn_norm = nn.RMSNorm(epsilon=norm_eps, in_channels=units)
        self.ffn = LlamaFFN(units, hidden_size)

    def forward(self, x, cache=None, start_pos=None):
        x = x + self.attention(self.attn_norm(x), cache=cache,
                               start_pos=start_pos)
        return x + self.ffn(self.ffn_norm(x), cache=cache)


class LlamaModel(HybridBlock):
    """Decoder-only LM; forward returns logits (B, T, vocab)."""

    # ShardedTrainer protocol: the model casts params to the AMP dtype
    # inside its own remat boundary (cast-at-use; see forward) instead of
    # the trainer pre-casting the whole tree
    supports_inner_amp = True

    def __init__(self, vocab_size=32000, units=4096, hidden_size=11008,
                 num_layers=32, num_heads=32, num_kv_heads=None,
                 norm_eps=1e-5, tie_embeddings=False, remat=False,
                 layer_barrier=False, theta=10000.0, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._tie = tie_embeddings
        # layer_barrier: thread each layer's params through an
        # optimization_barrier with the incoming activation, so a
        # layer's weight all-gathers (fsdp/ZeRO sharding) and AMP casts
        # cannot be scheduled before the previous layer finishes.
        # Without it the heap simulator hoists EVERY layer's gather to
        # the front of the step (measured: full 32 GiB unsharded param
        # set live at once on the fsdp8 8B lowering, exp/llama8b_aot).
        # Trade-off: also forbids one-layer-ahead gather prefetch, so
        # leave it off for tp-sharded runs where nothing is gathered.
        if layer_barrier and not remat:
            # the barrier is threaded inside the per-layer checkpoint;
            # without remat it would silently never exist
            raise MXNetError(
                "layer_barrier=True requires remat=True (the barrier "
                "lives inside the per-layer jax.checkpoint trace)")
        self._layer_barrier = layer_barrier
        # remat: re-compute each decoder layer in backward instead of
        # saving its activations (jax.checkpoint) — HBM-for-FLOPs trade
        # that makes 8B training fit a v5e's 16 GB (exp/llama8b_aot.py)
        self._remat = remat
        self.embed = nn.Embedding(vocab_size, units)
        self._blocks = []
        for i in range(num_layers):
            blk = LlamaBlock(units, hidden_size, num_heads, num_kv_heads,
                             norm_eps, theta=theta)
            self._blocks.append(blk)
            self.register_child(blk, f"layer{i}")
        self.norm = nn.RMSNorm(epsilon=norm_eps, in_channels=units)
        if not tie_embeddings:
            self.lm_head = nn.Dense(vocab_size, flatten=False,
                                    use_bias=False, in_units=units)

    def cache_spec(self):
        """What each layer keeps between serving steps (see
        :data:`LayerCache`): K/V rows and no recurrent state."""
        return [LayerCache(*blk.attention.cache_geometry(), ())
                for blk in self._blocks]

    def forward(self, input_ids, cache=None, start_pos=None):
        x = self.embed(input_ids)
        from ..cachedop import in_trace

        if cache is not None:
            # serving decode path: per-layer KV rings, no remat (inference
            # saves no activations, so recompute would be pure waste).
            # Every matmul on this path is ops.nn.stable_dense — that
            # makes the T=1 decode executable bitwise equal, per
            # position, to the T=bucket prefill executable (the serve
            # parity contract);
            # the fusion_fence additionally pins each layer boundary so
            # the contract can't regress via cross-layer fusion choices
            fast = getattr(cache, "path", "baseline") != "baseline"
            for i, blk in enumerate(self._blocks):
                x = blk(x, cache=cache.layer(i), start_pos=start_pos)
                if not fast:
                    # the fence exists for the bitwise contract; the fast
                    # rungs want cross-layer fusion
                    x = _ops.fusion_fence(x)
            return _dense_on(cache)(
                self.norm(x), self.embed if self._tie else self.lm_head)
        if self._remat and in_trace():
            # only under a functionalized trace (ShardedTrainer/CachedOp):
            # the eager tape records per-op and cannot see through
            # jax.checkpoint, so eager mode keeps the plain loop
            import jax
            import jax.numpy as jnp

            from ..cachedop import _ParamBinding
            from ..ndarray.ndarray import NDArray

            # inner AMP (see ShardedTrainer supports_inner_amp): cast
            # params to the compute dtype INSIDE the checkpointed layer,
            # with the fp32 masters as the closed-over residuals — the
            # bf16 copies are transient and re-materialize in backward,
            # so AMP costs zero extra live parameter bytes (a pre-cast
            # outside the checkpoint keeps a full bf16 param copy alive
            # through the whole step; measured 3.5 GiB/device on the 8B
            # proof, exp/llama8b_aot.py)
            amp = getattr(self, "_amp_dtype", None)
            if amp is not None:
                x = x.astype(amp)

            barrier = self._layer_barrier
            for blk in self._blocks:
                # params enter as closed-over tracers (functionalize's
                # _ParamBinding); jax.checkpoint differentiates through
                # the closure, so grads still flow to every weight
                def layer_fn(xd, _blk=blk):
                    if amp is None and not barrier:
                        return _blk(NDArray(xd))._data
                    ps = list(_blk.collect_params().values())
                    arrays = [p.data() for p in ps]
                    datas = [a._data for a in arrays]
                    if barrier:
                        xd, *datas = jax.lax.optimization_barrier(
                            (xd, *datas))
                    if amp is not None:
                        datas = [
                            d.astype(amp)
                            if jnp.issubdtype(d.dtype, jnp.floating)
                            else d for d in datas]
                    with _ParamBinding(arrays, datas):
                        return _blk(NDArray(xd))._data

                x = NDArray(jax.checkpoint(layer_fn)(x._data))
            if amp is not None:
                # final norm + lm_head + loss run at master precision
                x = x.astype(jnp.float32)
        else:
            for blk in self._blocks:
                x = blk(x)
        x = self.norm(x)
        if self._tie:
            w = self.embed.weight.data()
            return _ops.fully_connected(x, w, None, num_hidden=w.shape[0],
                                        no_bias=True, flatten=False)
        return self.lm_head(x)


# canonical configs (vocab 32000 for llama-2 sizes, 128256 for llama-3-8b)
_LLAMA_CONFIGS = {
    "llama_tiny_test": dict(units=64, hidden_size=128, num_layers=2,
                            num_heads=4, num_kv_heads=2, vocab_size=256),
    # the 12-layer serving-parity config (tests/test_serve.py, bench
    # llama_decode): full 12-deep residual/cache stack at widths a CPU
    # tier-1 run can decode in seconds
    "llama_serve_12l_test": dict(units=128, hidden_size=256, num_layers=12,
                                 num_heads=4, num_kv_heads=2,
                                 vocab_size=512),
    "llama2_7b": dict(units=4096, hidden_size=11008, num_layers=32,
                      num_heads=32, num_kv_heads=32, vocab_size=32000),
    "llama3_8b": dict(units=4096, hidden_size=14336, num_layers=32,
                      num_heads=32, num_kv_heads=8, vocab_size=128256),
}


def get_llama(config="llama3_8b", **overrides):
    if config not in _LLAMA_CONFIGS:
        raise MXNetError(f"unknown llama config {config!r}; options "
                         f"{sorted(_LLAMA_CONFIGS)}")
    cfg = dict(_LLAMA_CONFIGS[config])
    cfg.update(overrides)
    return LlamaModel(**cfg)


def llama_sharding_rules():
    """Megatron tp layout for the Llama param tree."""
    from jax.sharding import PartitionSpec as P

    return [
        (r"(q_proj|k_proj|v_proj|gate_proj|up_proj)\.weight", P("tp", None)),
        (r"(o_proj|down_proj)\.weight", P(None, "tp")),
        (r"(embed|lm_head)\.weight", P("tp", None)),
        (r".*(gamma|beta)$", P()),
    ]
