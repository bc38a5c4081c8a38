"""Autoregressive decode with a real KV cache.

Without this module, generating token ``t`` re-runs the full prefill over
``t`` positions — O(n^2) work per sequence. :class:`KVCache` preallocates
per-layer K/V rings to ``max_seq`` and the decode step feeds exactly one
new token through the model (``cache=`` / ``start_pos=`` path in
``models/llama.py``), so each generated token costs one T=1 executable
replay.

Parity contract (asserted per-token in ``tests/test_serve.py``): the
decode path's logits are **bitwise identical** to re-running the full
prefill through the same cache-mode path. Both arms compile through the
shape-stable serving ops in ``ops/nn.py`` (see the section comment there)
— the KV cache is a pure work-skipping transform, not an approximation.

Shapes are bucketed the serving way: one decode executable per batch
bucket (T=1 is constant), one prefill executable per (batch, prompt)
bucket; after :meth:`Generator.warmup` a decode stream of any admitted
shape triggers zero XLA compiles.

Sampling (``greedy``, temperature, top-k) draws its keys from
``mxnet_tpu.random`` — seeded, reproducible streams, same as training.
"""
from __future__ import annotations

import functools
import time

import numpy as _onp

from .. import random as _rng
from ..base import MXNetError
from ..profiler import attribution as _attr
from ..profiler import trace as _trace
from ..profiler.core import device_scope
from ..gluon.block import HybridBlock
from ..ops import nn as _ops
from ..resilience import faults as _faults
from .engine import InferenceSession, PoolExhausted, block_context, \
    on_block_context, pick_bucket


class _LayerKV:
    """One layer's view of the cache: read k/v (plus int8 scale rings when
    quantized), write back the updated rings (functional update — inside a
    trace these are tracers)."""

    __slots__ = ("_cache", "_idx")

    def __init__(self, cache, idx):
        self._cache = cache
        self._idx = idx

    @property
    def k(self):
        return self._cache._k[self._idx]

    @property
    def v(self):
        return self._cache._v[self._idx]

    @property
    def k_scale(self):
        return self._cache._ks[self._idx]

    @property
    def v_scale(self):
        return self._cache._vs[self._idx]

    @property
    def max_seq(self):
        return self._cache.max_seq

    @property
    def quant(self):
        return self._cache.quant

    @property
    def path(self):
        return self._cache.path

    @property
    def quant_weights(self):
        return self._cache.quant_weights

    @property
    def window(self):
        return self._cache._windows[self._idx]

    @property
    def page_table(self):
        """The table of this layer's kind of pages: the ring of a layer
        bounded by a window, else the whole sequence's."""
        if self.window is not None:
            return self._cache.window_table
        return self._cache.page_table

    def token_live(self, t_len):
        return self._cache.token_live(t_len)

    def note_route(self, load):
        self._cache.route_loads.append(load)

    # the layer's recurrent state (one row a sequence) and the step's
    # lane contract for it (ops/nn.py, the recurrent-state section)
    @property
    def state(self):
        return self._cache._state[self._idx]

    @property
    def valid_len(self):
        return self._cache.valid_len

    @property
    def live(self):
        return self._cache.live

    def update_state(self, new_state):
        self._cache._state[self._idx] = tuple(new_state)

    def update(self, new_k, new_v, new_k_scale=None, new_v_scale=None):
        # a latent layer's one array is ``k``; its ``v`` is and stays None
        self._cache._k[self._idx] = new_k
        self._cache._v[self._idx] = new_v
        if new_k_scale is not None:
            self._cache._ks[self._idx] = new_k_scale
        if new_v_scale is not None:
            self._cache._vs[self._idx] = new_v_scale


class CacheLayout:
    """What a model keeps between serving steps, read from its
    ``cache_spec()``: for each layer a K/V geometry and the per-sequence
    shapes of its recurrent state (``models.llama.LayerCache``). The one
    description that :class:`KVCache` (rings), ``kv_blocks.PagedKVPool``
    (pages, and state rows a slot) and the compiled step's calling
    convention are all built from; a model with another kind of state
    says so there and nowhere else.

    The flat order is a layer at a time: ``k, v`` (``k, k_scale, v,
    v_scale`` when quantized; the one array of a latent layer), then the
    layer's state arrays. ``kinds`` names each flat position ``"kv"``
    (indexed by position: rings or pages), ``"latent"`` (indexed by
    position too, the one array of a layer that keeps no pair:
    ``LayerCache.latent``; pages alone) or ``"state"`` (one row a
    sequence, never paged). ``windows`` gives, for each flat position,
    the window that bounds its layer's keys (None: unbounded, and for
    state): a bounded layer's K/V are pages of a second kind, a ring of
    :meth:`window_columns` a sequence under a table of their own.
    """

    def __init__(self, model, quant=None):
        spec = getattr(model, "cache_spec", None)
        if spec is None:
            raise MXNetError(
                f"{type(model).__name__} cannot be served from a cache: it "
                "has no cache_spec() (a list, one models.llama.LayerCache "
                "a layer, of what the layer keeps between steps)")
        if quant not in (None, "int8"):
            raise MXNetError(f"unknown KV cache quant {quant!r}")
        self.layers = list(spec())
        self.quant = quant
        self.kinds, self.windows = [], []
        for lay in self.layers:
            n_kv = 1 if lay.latent else 4 if quant else 2
            self.kinds += ["latent" if lay.latent else "kv"] * n_kv
            self.kinds += ["state"] * len(lay.state)
            self.windows += [lay.window] * n_kv + [None] * len(lay.state)
        self.has_state = "state" in self.kinds
        self.has_latent = "latent" in self.kinds
        if self.has_latent and (quant or any(
                lay.latent and (lay.window is not None or lay.kv_heads != 1
                                or not 0 < lay.latent <= lay.head_dim)
                for lay in self.layers)):
            raise MXNetError(
                "a latent layer keeps one float32 array a position on one "
                "head, unbounded, its values inside its keys: no int8 "
                f"pools (quant {quant!r}), no window, latent <= head_dim")
        bounds = {lay.window for lay in self.layers} - {None}
        if len(bounds) > 1:
            raise MXNetError(
                f"layers bounded by different windows ({sorted(bounds)}): "
                "the pool keeps one ring table for all bounded layers")
        self.window = bounds.pop() if bounds else None
        if self.window is not None and (self.window < 1 or quant):
            raise MXNetError(
                "a layer bounded by a window is served from float32 page "
                f"pools (window {self.window}, quant {quant!r})")

    def __len__(self):
        return len(self.kinds)

    def window_columns(self, page_size):
        """Pages in a bounded layer's ring: a window of positions and the
        page being written."""
        return -(-self.window // int(page_size)) + 1

    def alloc(self, zeros, kv_lead, kv_seq, state_rows, dtype="float32",
              window_lead=None):
        """The zeroed flat arrays: K/V of shape ``(kv_lead, kv_heads,
        kv_seq, head_dim)`` (rings: batch and max_seq; pools: pages and
        page size; ``window_lead`` pages for a bounded layer), state
        arrays with ``state_rows`` leading rows."""
        out = []
        for lay in self.layers:
            lead = kv_lead if lay.window is None else window_lead
            if lead is None:
                raise MXNetError(
                    "a layer bounded by a window keeps its K/V in page "
                    "pools alone (serve.PagedKVPool)")
            shape = (int(lead), lay.kv_heads, int(kv_seq), lay.head_dim)
            if lay.latent:
                out.append(zeros(shape, dtype=dtype))
            elif self.quant:
                # four arrays, not two listed twice: a step that consumes
                # its stores cannot be handed one buffer at two positions
                out += [zeros(sh, dtype=dt) for _ in range(2)
                        for sh, dt in ((shape, "int8"), (shape[:3], "float32"))]
            else:
                out += [zeros(shape, dtype=dtype), zeros(shape, dtype=dtype)]
            out += [zeros((int(state_rows),) + tuple(s), dtype="float32")
                    for s in lay.state]
        return out

    def state_nbytes(self, arrays):
        """Bytes of the state arrays among the flat ``arrays``."""
        return self.kind_nbytes(arrays, "state")

    def kind_nbytes(self, arrays, kind):
        """Bytes of the flat ``arrays`` of one of :attr:`kinds`."""
        return sum(_nbytes(a) for a, k in zip(arrays, self.kinds)
                   if k == kind)


def _nbytes(a):
    return int(_onp.prod(a.shape)) * _onp.dtype(a.dtype).itemsize


def require_kv_only(model, what, missing):
    """``what`` (a serving feature that shares or rewinds cache
    positions) cannot serve a model that keeps recurrent state: refuse
    loudly, naming what is ``missing``, rather than serve it wrongly."""
    if CacheLayout(model).has_state:
        raise MXNetError(
            f"{what} cannot serve {type(model).__name__}: its layers keep "
            f"recurrent state beside K/V, and {missing}")


def require_unbounded(model, what, missing):
    """``what`` cannot serve a model with a layer whose K/V is bounded by
    a window: refuse loudly, naming what is ``missing``."""
    if CacheLayout(model).window is not None:
        raise MXNetError(
            f"{what} cannot serve {type(model).__name__}: a layer's keys "
            f"are bounded by a window, and {missing}")


def require_kv_pairs(model, what, missing):
    """``what`` cannot serve a model with a latent layer (one array a
    position and no K/V pair): refuse loudly, naming what is ``missing``."""
    if CacheLayout(model).has_latent:
        raise MXNetError(
            f"{what} cannot serve {type(model).__name__}: a layer keeps "
            f"one latent array a position and no K/V pair, and {missing}")


# missing of :func:`require_kv_pairs` for what reads a layer's cache as
# rings
LATENT_PAGES_ALONE = (
    "its attention reads that array where it lies in the float32 page "
    "pool, through the page table (the continuous engine's in-place step, "
    "decode_path 'pallas'); no ring form of it exists")


# what and missing of :func:`require_unbounded` for what shares or replays
# cache positions
WINDOW_PAGES_GO = (
    "its K/V lives in a ring of pages that are written over as the "
    "sequence grows: a shared or replayed page may already hold later "
    "positions (no snapshots of bounded layers)")


# what and missing of :func:`require_kv_only` for the prefix cache, which
# Generator and ContinuousEngine both refuse
PREFIX_CACHE_NEEDS = (
    "the prefix cache (prefix_cache=True)",
    "a prefix hit skips prefill by sharing pages, while the state after "
    "the shared prefix was never kept (no state snapshots)")


def gather_rings(layout, stores, table):
    """The strict rung's standalone bracket before the ring executable:
    every paged K/V pool of ``stores`` as per-row rings through
    ``table`` (exact copies); state arrays pass as they are (the step
    takes their rows itself, by ``lanes``)."""
    return [_ops.paged_kv_gather(a, table) if kind == "kv" else a
            for a, kind in zip(stores, layout.kinds)]


def scatter_rings(layout, stores, table, returned, start_pos, t_len):
    """The bracket after it: the freshly written ring rows back into
    their pools; state arrays come back whole."""
    return [_ops.paged_kv_scatter(a, table, r, start_pos, t_len)
            if kind == "kv" else r
            for a, r, kind in zip(stores, returned, layout.kinds)]


class KVCache:
    """Preallocated per-layer K/V rings for autoregressive decode.

    Layout: ``num_layers`` pairs of (batch, kv_heads, max_seq, head_dim)
    NDArrays, zero-initialized. With ``quant="int8"`` the rings are int8
    and each carries a (batch, kv_heads, max_seq) f32 scale ring
    (per-token-per-head symmetric quantization, written by
    ``ops.nn.kv_cache_write_q``) — half the HBM of the f32 rings.
    Position accounting lives with the caller (per-row ``start_pos``
    vectors) — the cache itself is pure storage, so one compiled
    executable serves every decode step.

    ``path`` / ``quant_weights`` are trace-time routing attributes set by
    the serving step before the model forward: which ``cached_attention``
    formulation the layers should compile, and (int8 rung) the
    ``{id(param): (int8_weight, scale)}`` side table for
    ``ops.nn.quantized_dense``. ``page_table`` is set the same way by a
    step whose K/V arrays are page pools and not rings (the engine's
    in-place step): the layers then write a position into its page and
    read the pages through the table.
    """

    def __init__(self, keys, values, max_seq, key_scales=None,
                 value_scales=None, quant=None, states=None):
        if len(keys) != len(values):
            raise MXNetError("KVCache needs one value ring per key ring")
        self._k = list(keys)
        self._v = list(values)
        self._ks = list(key_scales) if key_scales is not None else None
        self._vs = list(value_scales) if value_scales is not None else None
        if quant is not None and (self._ks is None or self._vs is None):
            raise MXNetError("quantized KVCache needs scale rings")
        # per layer, the tuple of its recurrent-state arrays (one row a
        # sequence; empty for a layer that keeps none)
        self._state = ([tuple(st) for st in states] if states is not None
                       else [()] * len(self._k))
        if len(self._state) != len(self._k):
            raise MXNetError("KVCache needs one state tuple per layer")
        self.quant = quant
        self.max_seq = int(max_seq)
        self.path = "baseline"
        self.quant_weights = None
        self.page_table = None
        # per layer, the window that bounds its keys (None: unbounded),
        # and the ring table of the bounded layers' pages; both set by a
        # step that serves such a model
        self._windows = [None] * len(self._k)
        self.window_table = None
        # the step's last real position a row (``token_live``), and what
        # the routed-expert layers noted of their load in this call
        self.last_idx = None
        self.route_loads = []
        # the step's lane contract for the recurrent state, set by the
        # serving step before the model forward like ``path``: valid
        # positions of each row in this call, and which rows are live
        self.valid_len = None
        self.live = None

    @classmethod
    def alloc(cls, model, batch, max_seq, dtype="float32", quant=None):
        """Zeroed rings (and one state row a sequence) sized from the
        model's own cache description (:class:`CacheLayout`)."""
        from .. import numpy as mnp

        layout = CacheLayout(model, quant)
        zeros = functools.partial(mnp.zeros, ctx=block_context(model))
        return cls.from_flat(
            layout.alloc(zeros, batch, max_seq, batch, dtype), max_seq,
            quant=quant, layout=layout)

    @property
    def num_layers(self):
        return len(self._k)

    def token_live(self, t_len):
        """(B, T) bool, which tokens of this call are real: a row whose
        pages are the null page is a dead lane, a position past
        ``last_idx`` is a chunk's padding. None outside the engine's
        in-place step (every token is real)."""
        if self.page_table is None or self.last_idx is None:
            return None
        from .. import numpy as mnp

        lane = self.page_table[:, 0:1] != 0                       # (B, 1)
        real = mnp.arange(t_len, dtype="int32").reshape(1, -1) \
            <= self.last_idx.reshape(-1, 1)                       # (B, T)
        return lane * real

    @property
    def batch(self):
        return self._k[0].shape[0]

    def layer(self, i) -> _LayerKV:
        return _LayerKV(self, i)

    def flat(self):
        """The executable's calling convention for cache state, a layer
        at a time (:class:`CacheLayout`): [k0, v0, *state0, k1, v1, ...];
        quantized caches put each scale ring right after its int8 ring
        ([k0, ks0, v0, vs0, *state0, ...])."""
        out = []
        for i, (k, v) in enumerate(zip(self._k, self._v)):
            if self.quant is not None:
                out.extend((k, self._ks[i], v, self._vs[i]))
            elif v is None:       # a latent layer's one array
                out.append(k)
            else:
                out.extend((k, v))
            out.extend(self._state[i])
        return out

    @classmethod
    def from_flat(cls, arrays, max_seq, quant=None, layout=None):
        """The cache over ``arrays`` in :meth:`flat` order. ``layout``
        says how many state arrays follow each layer's rings; without
        one there are none."""
        arrays = list(arrays)
        per = 4 if quant is not None else 2
        if layout is None:
            if len(arrays) % per:
                raise MXNetError(
                    "flat quantized KVCache needs 4 arrays per layer"
                    if quant is not None else
                    "flat KVCache needs an even array count")
            counts = [(per, 0)] * (len(arrays) // per)
        else:
            counts = [(1 if lay.latent else per, len(lay.state))
                      for lay in layout.layers]
            if len(arrays) != len(layout):
                raise MXNetError(
                    f"flat KVCache: got {len(arrays)} arrays, the model's "
                    f"cache description has {len(layout)}")
        rings, states, at = [], [], 0
        for per_layer, n in counts:
            # a latent layer's one array stands as its ``k``, beside no ``v``
            rings.append(arrays[at:at + per_layer] + [None] * (per - per_layer))
            states.append(tuple(arrays[at + per_layer:at + per_layer + n]))
            at += per_layer + n
        if quant is not None:
            return cls([r[0] for r in rings], [r[2] for r in rings], max_seq,
                       [r[1] for r in rings], [r[3] for r in rings], quant,
                       states=states)
        out = cls([r[0] for r in rings], [r[1] for r in rings], max_seq,
                  states=states)
        if layout is not None:
            out._windows = [lay.window for lay in layout.layers]
        return out

    def nbytes(self):
        return sum(_nbytes(a) for a in self.flat())

    def state_nbytes(self):
        """The recurrent state's part of :meth:`nbytes`."""
        return sum(_nbytes(a) for st in self._state for a in st)


class _CacheForward(HybridBlock):
    """The compiled serving step: (tokens, start_pos, last_idx, *rings) ->
    (last-position logits, *updated rings).

    One forward serves both phases — prefill (T = prompt bucket,
    start_pos = 0, last_idx = prompt_len - 1) and decode (T = 1,
    start_pos = per-row position, last_idx = 0). The phases differ only
    by shape, i.e. by CachedOp signature, never by code path: that shared
    path is what makes the bitwise decode-vs-prefill parity hold.

    ``paged=True`` switches the cache-state calling convention from
    contiguous rings to page pools: the call grows a ``page_table``
    (B, N) arg after ``last_idx``, the per-layer arrays are
    (P, KV, page, D) pools, and the step brackets the UNCHANGED model
    cache path with ``ops.nn.paged_kv_gather`` (pool -> per-slot ring)
    and ``ops.nn.paged_kv_scatter`` (freshly written rows -> pool).
    The fused form is for the fast rungs (pallas/int8, tolerance
    parity): fusing the brackets into the step lets XLA pick different
    loop partitions for the model subgraph, which drifts ulps from the
    ring executable. The strict baseline rung therefore never compiles
    ``paged=True`` — its callers run the same brackets as standalone
    exact-copy device ops around the unchanged *ring* executable, so
    paged baseline decode is bitwise identical to ring decode because
    it literally replays the same compiled step
    (tests/test_kv_blocks.py asserts it).

    ``inplace=True`` (with ``paged``; the continuous engine's fast rungs)
    leaves K/V in the pool: no ring is gathered and none scattered. The
    layers are handed the pools and the page table; each writes its new
    rows straight into their pages (``ops.nn.write_pages``) and attends
    through the table — a decode step (T == 1) in the paged Pallas
    kernel, a prefill chunk over its one row's gathered pages in XLA.
    The step CONSUMES its cache arguments: ``donate_args`` names their
    positions in the call, ``CachedOp`` donates them to the executable,
    and every one comes back as an output in the same order, so the
    writes are updates in place and a caller must rebind to the outputs
    (``PagedKVPool.update_from_flat``) and never read the arrays it
    passed again.

    The calling convention follows the model's :class:`CacheLayout`. A
    model whose layers keep recurrent state adds a ``lanes`` (B,) int32
    arg before the cache arrays (after ``page_table`` when paged): the
    row of the state arrays that each batch row reads and writes, -1
    for a row that is not live in this call (its state comes back bit
    for bit). State arrays are one row a sequence and never paged; a
    call as wide as they are (a decode step over every slot, a ring
    cache) maps row i to row i, a narrower one (the (1, chunk) prefill)
    takes and returns its rows by ``lanes``. ``last_idx`` doubles as the
    count of real positions (``last_idx + 1``), past which a padded
    prefill chunk leaves the state alone. A model with K/V alone keeps
    the convention, and the traced program, it always had.

    A model with a latent layer (``LayerCache.latent``; in-place only)
    passes that layer's one pool where another passes two, and nothing
    else of the convention moves.

    A model with a layer bounded by a window (in-place only) adds a
    ``window_table`` (B, C) arg right after ``page_table``: the ring of
    the bounded layers' pages, ``C`` columns a row
    (``CacheLayout.window_columns``); each layer is handed its kind's
    table. A model with routed-expert layers returns, right after the
    logits, one (layers, 3) int32 array of what each such layer noted of
    its load (experts hit, most tokens on one expert, assignments) over
    the call's real tokens; a model with neither adds no argument and no output.

    The in-place step also samples greedily inside itself and keeps the
    ids on the device. It takes two more arguments before the cache
    stores, ``keep`` (B,) int32 and ``ids`` (rows,) int32, and hands
    ``ids`` back right before them: the argmax of each batch row's last
    logits (``jnp.argmax`` over the same float32 row, ties to the lowest
    index: the token ``sample_tokens`` picks), written at row
    ``keep[b]`` of ``ids``, a row whose ``keep`` is negative writing
    none. A negative entry of ``tokens`` stands for "this row's carried
    id", ``ids[keep[b]]``: so the call that consumes a token can be
    enqueued before the call that made it has been fetched. ``ids`` is
    **not** donated: the host reads a call's ``ids`` after later calls
    have been handed them.
    """

    def __init__(self, model, max_seq, path="baseline", quant=None,
                 qindex=(), all_logits=False, paged=False, inplace=False,
                 **kwargs):
        super().__init__(**kwargs)
        self.model = model  # child registration shares the params
        self._max_seq = int(max_seq)
        self._path = path
        self._quant = quant
        self._qindex = list(qindex)
        self._all_logits = bool(all_logits)
        self._paged = bool(paged)
        self._inplace = bool(inplace)
        if self._inplace and (not self._paged or path == "baseline"):
            raise MXNetError("the in-place step is the paged fast rungs'")
        self._layout = CacheLayout(model, quant)
        self._windowed = self._layout.window is not None
        if self._windowed and not self._inplace:
            raise MXNetError(
                f"{type(model).__name__} has a layer whose keys are bounded "
                "by a window: its K/V lives in a ring of pages, which the "
                "continuous engine's in-place step (decode_path 'pallas') "
                "alone serves; ring caches and the strict rung do not")
        if not self._inplace:
            require_kv_pairs(model, "a step over ring caches (serve."
                             "Generator, the strict 'baseline' rung)",
                             LATENT_PAGES_ALONE)
        # the call's positions that the step consumes and returns (read
        # by CachedOp): the cache stores, after tokens, start_pos,
        # last_idx, the page table(s), the lanes, and keep and ids
        first = 3 + int(self._paged) + int(self._windowed) \
            + int(self._layout.has_state) + 2 * int(self._inplace)
        self.donate_args = (tuple(range(first, first + len(self._layout)))
                            if self._inplace else ())

    def forward(self, tokens, start_pos, last_idx, *rest):
        # the executable's name in the device trace, by what tells the two
        # apart already: one position a row, or more
        with device_scope("serve_step.decode" if tokens.shape[1] == 1
                          else "serve_step.prefill"):
            return self._step(tokens, start_pos, last_idx, *rest)

    def _step(self, tokens, start_pos, last_idx, *rest):
        layout = self._layout
        page_table = window_table = lanes = None
        if self._paged:
            page_table, rest = rest[0], rest[1:]
        if self._windowed:
            window_table, rest = rest[0], rest[1:]
        if layout.has_state:
            lanes, rest = rest[0], rest[1:]
        if self._inplace:
            keep, ids, rest = rest[0], rest[1], rest[2:]
            tokens = _ops.carried_tokens(tokens, ids, keep)
        stores = rest[:len(layout)]
        qflat = rest[len(layout):]
        ringed = self._paged and not self._inplace
        # what the model's cache path reads: K/V as per-row rings (gathered
        # through the page table when paged; the pools themselves, with
        # the table beside them, in place), state as one row a batch row
        flat_cache = [
            _ops.state_rows_gather(a, lanes) if kind == "state"
            else _ops.paged_kv_gather(a, page_table) if ringed else a
            for a, kind in zip(stores, layout.kinds)]
        cache = KVCache.from_flat(flat_cache, self._max_seq,
                                  quant=self._quant, layout=layout)
        cache.path = self._path
        if self._inplace:
            cache.page_table = page_table
            cache.window_table = window_table
            cache.last_idx = last_idx
        if layout.has_state:
            # the lane contract of the recurrent-state ops: the real
            # positions of a row are 0 .. last_idx, and a row whose lane
            # is negative is not live in this call
            cache.valid_len = last_idx + 1
            cache.live = lanes >= 0
        if qflat:
            # int8 weight side table: quantized weights enter as two packed
            # traced call args (appended after the rings by Generator._run),
            # so they are neither jit-captured constants nor extra
            # Parameters; reslice them by the static qindex offsets
            packed_w, packed_s = qflat
            table, woff, soff = {}, 0, 0
            for pid, (o, u) in self._qindex:
                table[pid] = (packed_w[woff:woff + o * u].reshape(o, u),
                              packed_s[soff:soff + o])
                woff += o * u
                soff += o
            cache.quant_weights = table
        logits = self.model(tokens, cache=cache, start_pos=start_pos)
        t_len = tokens.shape[1]
        updated = tuple(
            _ops.state_rows_scatter(a, lanes, new) if kind == "state"
            else _ops.paged_kv_scatter(a, page_table, new, start_pos, t_len)
            if ringed else new
            for a, new, kind in zip(stores, cache.flat(), layout.kinds))
        if self._all_logits:
            # speculative verify step: the caller scores every position of
            # the (k+1)-token block, not just the last real one
            return (logits,) + updated
        last = _ops.gather_positions(logits, last_idx)
        if not self._inplace:
            return (last,) + updated
        head = (last,)
        if cache.route_loads:
            from .. import numpy as mnp

            head += (mnp.stack(cache.route_loads),)
        return head + (_ops.keep_greedy_ids(ids, keep, last),) + updated


def sample_tokens(logits, temperature=0.0, top_k=None):
    """Next-token choice from (B, vocab) logits.

    ``temperature <= 0`` is greedy argmax; otherwise softmax sampling at
    the given temperature, optionally truncated to the ``top_k`` largest
    logits. Randomness comes from ``mxnet_tpu.random``'s key stream, so
    ``mx.random.seed(n)`` reproduces a generation exactly.
    Returns a host numpy (B,) int32 array.
    """
    import jax
    import jax.numpy as jnp

    from ..ndarray.ndarray import NDArray

    data = logits._data if isinstance(logits, NDArray) else jnp.asarray(logits)
    if temperature is None or temperature <= 0.0:
        return _onp.asarray(jnp.argmax(data, axis=-1)).astype(_onp.int32)
    scaled = data / float(temperature)
    if top_k is not None and 0 < int(top_k) < scaled.shape[-1]:
        kth = jax.lax.top_k(scaled, int(top_k))[0][..., -1:]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    key = _rng.next_key()
    return _onp.asarray(
        jax.random.categorical(key, scaled, axis=-1)).astype(_onp.int32)


# stop-token matrix width of the multi-step super-step: per-lane stop
# sets are padded/truncated to this many int32 entries (-1 = unused).
# Requests with more stop ids than this still stop correctly — the host
# settle replay checks the FULL stop set — the device loop just cannot
# freeze the lane early on the overflowed ids (graceful degradation:
# extra iterations, never wrong output).
_STOP_WIDTH = 8


def _stop_matrix(rows, stop_sets):
    """(len(rows), _STOP_WIDTH) int32 stop matrix, padded with -1."""
    m = _onp.full((rows, _STOP_WIDTH), -1, _onp.int32)
    for i, st in enumerate(stop_sets):
        ids = sorted(int(t) for t in st)[:_STOP_WIDTH]
        m[i, :len(ids)] = ids
    return m


def _fresh_key_bits():
    """(2,) uint32 threefry2x32 key data drawn from ``mxnet_tpu.random``'s
    seeded stream — the traced base-key input of the multi-step
    super-step (see ``ops.nn.sample_step``)."""
    import jax

    return _onp.asarray(
        jax.random.key_data(_rng.as_threefry(_rng.next_key()))
    ).astype(_onp.uint32).reshape(2)


class _MultiStepForward(HybridBlock):
    """The compiled decode super-step: up to N decode iterations in ONE
    executable (ROADMAP item 3 — the host round-trip killer).

    Calling convention::

        (tokens (S,1), start_pos (S,), steps_limit (1,), remaining (S,),
         seeds (S,), temps (S,), top_ks (S,), stops (S, _STOP_WIDTH),
         key_bits (2,), [page_table (S,P),] *rings)
        -> (block (S,N), valid (S,), done (S,), *rings)

    The body is a ``lax.while_loop`` whose iteration feeds each lane's
    pending token through the UNCHANGED model cache path (same
    layers/ops as the single-step executable — Pallas decode attention,
    int8 rings, fusion fences all compile per iteration with the
    loop-carried ``start_pos``), samples the successor in-trace
    (``ops.nn.sample_step``: greedy + per-lane temperature/top-k off
    counter-based threefry keys), records it in the (S, N) token block,
    and advances. ``steps_limit`` is a *traced* ceiling: the cond is
    ``(i < steps_limit) & ~all(done)``, so the host degrades N down to 1
    (tight deadlines) through the SAME executable, and the loop exits
    early the moment every lane is done.

    Finished lanes FREEZE instead of masking: a lane that hit a stop id
    or its token budget stops advancing ``(token, position)``, so each
    further iteration recomputes and rewrites byte-identical K/V at its
    frozen position — idempotent by induction (every input of the write
    is unchanged), which is why no masked cache-write variant is needed
    and dead lanes idle harmlessly at full batch width.

    Paged mode hoists the brackets: ONE ``paged_kv_gather`` before the
    loop, rings carried through it, ONE ``paged_kv_scatter`` of length N
    after. Rows past a lane's write extent scatter back the exact bytes
    the gather produced (no-op), and positions past its page budget
    clip onto the null page — both established-safe. Note this fuses
    the brackets into the executable on EVERY rung, including baseline:
    a compiled loop cannot run eager brackets per iteration, so
    multi-step baseline carries greedy token-identity (not the PR-5
    bitwise-vs-ring contract).
    """

    def __init__(self, model, max_seq, steps, path="baseline", quant=None,
                 qindex=(), paged=False, **kwargs):
        super().__init__(**kwargs)
        self.model = model  # child registration shares the params
        self._max_seq = int(max_seq)
        self._steps = int(steps)
        self._path = path
        self._quant = quant
        self._qindex = list(qindex)
        self._paged = bool(paged)
        require_unbounded(model, "multi-step decode (multistep=True)",
                          WINDOW_PAGES_GO)
        require_kv_pairs(model, "multi-step decode (multistep=True)",
                         LATENT_PAGES_ALONE + " for the compiled loop's "
                         "gather and scatter brackets")
        require_kv_only(
            model, "multi-step decode (multistep=True)",
            "a lane that finished inside the compiled loop would have to "
            "freeze its state as it freezes its position (the loop "
            "rewrites frozen K/V, which is idempotent; a state is not)")
        self._n_cache = len(CacheLayout(model, quant))

    def forward(self, tokens, start_pos, steps_limit, remaining, seeds,
                temps, top_ks, stops, key_bits, *rest):
        import jax
        import jax.numpy as jnp

        from ..ndarray.ndarray import NDArray

        def raw(x):
            return x._data if isinstance(x, NDArray) else jnp.asarray(x)

        page_table = None
        if self._paged:
            page_table, rest = rest[0], rest[1:]
        flat_cache = rest[:self._n_cache]
        qflat = rest[self._n_cache:]
        pools = None
        if self._paged:
            pools = flat_cache
            flat_cache = [_ops.paged_kv_gather(p, page_table)
                          for p in pools]
        quant_weights = None
        if qflat:
            # same packed int8 side-table reslice as _CacheForward; the
            # slices are loop-invariant captures of the while body
            packed_w, packed_s = qflat
            quant_weights, woff, soff = {}, 0, 0
            for pid, (o, u) in self._qindex:
                quant_weights[pid] = (
                    packed_w[woff:woff + o * u].reshape(o, u),
                    packed_s[soff:soff + o])
                woff += o * u
                soff += o

        n = self._steps
        lanes = tokens.shape[0]
        limit = raw(steps_limit).astype(jnp.int32)[0]
        rem = raw(remaining).astype(jnp.int32)
        stop_m = raw(stops).astype(jnp.int32)
        temps_r = raw(temps).astype(jnp.float32)
        tks_r = raw(top_ks).astype(jnp.int32)
        seeds_r = raw(seeds).astype(jnp.int32)
        kb = raw(key_bits)
        max_seq, quant, path = self._max_seq, self._quant, self._path
        model = self.model

        def body(carry):
            it, cur, pos, done, emitted, block = carry[:6]
            rings = carry[6:]
            cache = KVCache.from_flat([NDArray(r) for r in rings],
                                      max_seq, quant=quant)
            cache.path = path
            cache.quant_weights = quant_weights
            logits = model(NDArray(cur), cache=cache,
                           start_pos=NDArray(pos))
            new_rings = tuple(raw(a) for a in cache.flat())
            lg = raw(logits)[:, 0]  # T = 1: the only position's logits
            nxt = raw(_ops.sample_step(
                NDArray(lg), NDArray(temps_r), NDArray(tks_r),
                NDArray(seeds_r), NDArray(pos), NDArray(kb)))
            active = ~done
            block = block.at[:, it].set(jnp.where(active, nxt, -1))
            emitted = emitted + active.astype(jnp.int32)
            is_stop = jnp.any(stop_m == nxt[:, None], axis=1)
            done = done | (active & is_stop) | (emitted >= rem)
            # advance only lanes still alive AFTER this emission: newly
            # finished lanes freeze at their last written position, so
            # subsequent iterations are byte-identical rewrites
            adv = active & ~done
            cur = jnp.where(adv[:, None], nxt[:, None], cur)
            pos = jnp.where(adv, pos + 1, pos)
            return (it + 1, cur, pos, done, emitted, block) + new_rings

        def cond(carry):
            return (carry[0] < limit) & ~jnp.all(carry[3])

        init = ((jnp.int32(0),
                 raw(tokens).astype(jnp.int32),
                 raw(start_pos).astype(jnp.int32),
                 rem <= 0,
                 jnp.zeros((lanes,), jnp.int32),
                 jnp.full((lanes, n), -1, jnp.int32))
                + tuple(raw(r) for r in flat_cache))
        out = jax.lax.while_loop(cond, body, init)
        done, emitted, block = out[3], out[4], out[5]
        rings = [NDArray(r) for r in out[6:]]
        if self._paged:
            rings = [_ops.paged_kv_scatter(p, page_table, r, start_pos, n)
                     for p, r in zip(pools, rings)]
        return (NDArray(block), NDArray(emitted),
                NDArray(done.astype(jnp.int32))) + tuple(rings)


_DECODE_PATHS = ("baseline", "pallas", "int8")


def resolve_decode_path(decode_path=None):
    """The decode rung a Generator compiles. ``MXNET_SERVE_STRICT_PARITY``
    pins "baseline" (the PR-5 bitwise contract) over everything; otherwise
    an explicit ``decode_path`` argument wins over the
    ``MXNET_SERVE_DECODE_PATH`` flag, and "auto" means the fused-kernel
    "pallas" rung."""
    from .. import config

    if config.get("MXNET_SERVE_STRICT_PARITY"):
        return "baseline"
    path = decode_path
    if path is None:
        path = config.get("MXNET_SERVE_DECODE_PATH")
    if path in (None, "auto"):
        path = "pallas"
    if path not in _DECODE_PATHS:
        raise MXNetError(
            f"decode_path {path!r} not in {_DECODE_PATHS} "
            "(speculative decoding is serve.SpeculativeGenerator, not a "
            "KV-cache path)")
    return path


def _int8_weights_enabled():
    """Resolve MXNET_SERVE_DECODE_INT8_WEIGHTS for the int8 rung. "auto"
    enables int8 weights only where the backend has int8 matrix units
    (tpu — the 394 TOP/s path); on CPU the per-step int8->f32 weight
    convert costs more than the f32 gemm saves, so auto keeps weights f32
    there and the rung's win is the halved KV-ring traffic."""
    import jax

    from .. import config

    flag = str(config.get("MXNET_SERVE_DECODE_INT8_WEIGHTS")).strip().lower()
    if flag == "auto":
        return jax.default_backend() == "tpu"
    return flag in ("1", "true", "yes", "on")


def _quantize_serving_weights(model):
    """Pre-quantize the model's serving projections to per-output-channel
    int8 for ``ops.nn.quantized_dense``: returns ``(qindex, qflat)`` — an
    ordered ``(id(param), shape)`` list and exactly two packed NDArrays
    (all int8 weights concatenated flat, all scales concatenated flat)
    that the serving step threads through as call args. Packing keeps the
    per-step call-arg count flat in depth (2, not 2 x 8 x layers); the
    step reslices by the static offsets ``qindex`` implies, which XLA
    fuses away. Models without the llama projection layout fall back to
    KV-only quantization (with a flight-recorder note, so the silent-f32
    case is diagnosable)."""
    from .. import numpy as mnp
    from ..profiler import core as _prof
    from ..profiler import recorder as _recorder

    try:
        params = []
        for blk in model._blocks:
            attn, ffn = blk.attention, blk.ffn
            params += [attn.q_proj.weight, attn.k_proj.weight,
                       attn.v_proj.weight, attn.o_proj.weight,
                       ffn.gate_proj.weight, ffn.up_proj.weight,
                       ffn.down_proj.weight]
        params.append(model.embed.weight if model._tie
                      else model.lm_head.weight)
    except AttributeError:
        _recorder.note("fallback", "serve.decode_fallback",
                       {"reason": "quant_weights_unsupported_model",
                        "model": type(model).__name__})
        _prof.incr_counter("serve.decode_fallbacks", cat="serve")
        return [], []
    qindex, wchunks, schunks = [], [], []
    for p in params:
        w = p.data().asnumpy()
        scale = _onp.maximum(_onp.abs(w).max(axis=1) / 127.0,
                             1e-8).astype(_onp.float32)
        qw = _onp.clip(_onp.round(w / scale[:, None]),
                       -127, 127).astype(_onp.int8)
        qindex.append((id(p), qw.shape))
        wchunks.append(qw.reshape(-1))
        schunks.append(scale)
    ctx = block_context(model)
    qflat = [mnp.array(_onp.concatenate(wchunks), ctx=ctx),
             mnp.array(_onp.concatenate(schunks), ctx=ctx)]
    return qindex, qflat


class Generator:
    """Bucketed KV-cache generation server for decoder LMs.

    Wraps the model into a :class:`_CacheForward` step compiled through an
    :class:`InferenceSession` (breaker, watchdog, fault site, serve-hit
    accounting all apply to every prefill and every decode step).

    Parameters
    ----------
    model : a block with a ``cache=``/``start_pos=`` forward and a
        ``cache_spec()`` saying what each layer keeps between steps
        (:class:`CacheLayout`): ``LlamaModel``, ``FalconH1Model``.
    max_seq : ring length — prompt + generated tokens must fit.
    batch_buckets / prompt_buckets : the compiled shape lattice.
    decode_path : which rung this generator compiles (see
        :func:`resolve_decode_path`): "baseline" keeps the PR-5 bitwise
        prefill/decode contract; "pallas" routes attention through the
        fused decode kernel (tolerance parity); "int8" adds int8 KV rings
        and (by default) int8 projection weights.
    paged : back the KV state with a :class:`~.kv_blocks.PagedKVPool`
        per batch bucket instead of contiguous rings (``None`` reads
        ``MXNET_SERVE_KV_PAGED``). The pool is fully assigned
        (exhaustion-free) with identity page tables and persists across
        requests — stale pages need no zeroing (the attention position
        mask plus prefill's exact overwrite make them unreadable), but
        that persistence also means paged generates on one batch bucket
        must not run concurrently. The baseline rung stays bitwise
        identical to the ring path; dynamic tables, admission, and
        recycling live in :class:`~.scheduler.ContinuousEngine`.
    page_size / kv_pages : pool geometry overrides (see
        :class:`~.kv_blocks.PagedKVPool`).
    """

    def __init__(self, model, max_seq=128, batch_buckets=(1, 2, 4),
                 prompt_buckets=None, pad_id=0, name="llama_decode",
                 decode_path=None, paged=None, page_size=None,
                 kv_pages=None, prefix_cache=None, multistep=None,
                 decode_steps=None):
        from .. import config

        self.model = model
        self.max_seq = int(max_seq)
        self.batch_buckets = tuple(sorted(int(b) for b in batch_buckets))
        if prompt_buckets is None:
            prompt_buckets, p = [], 16
            while p < self.max_seq:
                prompt_buckets.append(p)
                p *= 2
            prompt_buckets.append(self.max_seq)
        self.prompt_buckets = tuple(sorted(set(int(p)
                                               for p in prompt_buckets)))
        if self.prompt_buckets[-1] > self.max_seq:
            raise MXNetError("prompt bucket exceeds max_seq")
        self.pad_id = int(pad_id)
        self.decode_path = resolve_decode_path(decode_path)
        self._quant = "int8" if self.decode_path == "int8" else None
        self._qindex, self._qflat = [], []
        if self._quant and _int8_weights_enabled():
            self._qindex, self._qflat = _quantize_serving_weights(model)
        if prefix_cache is None:
            prefix_cache = bool(config.get("MXNET_SERVE_PREFIX_CACHE"))
        self._prefix_on = bool(prefix_cache)
        self._layout = CacheLayout(model, self._quant)
        # rings hold a position for good: a bounded layer's K/V is a ring
        # of pages, which the continuous engine alone serves
        require_unbounded(
            model, "serve.Generator",
            "its ring caches keep every position of a sequence: the "
            "continuous engine (serve.ContinuousEngine, decode_path "
            "'pallas') serves such a model from rings of pages")
        require_kv_pairs(model, "serve.Generator", LATENT_PAGES_ALONE)
        if self._prefix_on:
            require_kv_only(model, *PREFIX_CACHE_NEEDS)
        if self._prefix_on and paged is False:
            raise MXNetError(
                "prefix_cache requires the paged KV pool (prefix pages "
                "are shared pool pages); don't pass paged=False with "
                "prefix_cache on")
        self._paged = (bool(config.get("MXNET_SERVE_KV_PAGED"))
                       if paged is None else bool(paged)) or self._prefix_on
        self._page_size = page_size
        self._kv_pages = kv_pages
        self._prefix = {}  # batch bucket -> PrefixCache over its pool
        # speculative decoding sets this to k+1: its verify/draft rounds
        # write that many ring positions past the accepted prefix, so
        # per-request page budgets must cover them
        self._budget_headroom = 0
        # fast rungs fuse the paging brackets into the step; the strict
        # baseline rung keeps the RING executable and runs the brackets
        # as standalone exact copies in _run — that's what makes paged
        # baseline decode bitwise identical to ring decode
        self._fused_paged = self._paged and self.decode_path != "baseline"
        self._step = _CacheForward(model, self.max_seq,
                                   path=self.decode_path,
                                   quant=self._quant, qindex=self._qindex,
                                   paged=self._fused_paged)
        # bucketing is done here (cache shapes are part of the lattice);
        # the session provides the protected raw-run path
        self.session = InferenceSession(
            self._step, batch_buckets=self.batch_buckets,
            seq_buckets=self.prompt_buckets, pad_value=self.pad_id,
            name=name)
        self.ctx = self.session.ctx  # the model's device: inputs go there
        self.metrics = self.session.metrics
        self.metrics.set_decode_path(self.decode_path)
        # decode critical-path ledger (tentpole PR 16): observations
        # gated on _attr.ENABLED, the object always present for readout
        self.ledger = _attr.Ledger(name)
        self._zero_caches = {}  # batch bucket -> shared zeroed rings
        # multi-step decode (tentpole PR 19): the super-step lives in its
        # own InferenceSession (one more compiled signature per batch
        # bucket, frozen at warmup like everything else). The single-step
        # session stays — parity tests and the N=1 overhead bound compare
        # against it, and prefill always runs through it.
        if multistep is None:
            multistep = bool(config.get("MXNET_SERVE_MULTISTEP"))
        self._multistep = bool(multistep)
        if decode_steps is None:
            decode_steps = int(config.get("MXNET_SERVE_DECODE_STEPS"))
        self.decode_steps = max(1, int(decode_steps))
        self._msession = None
        self._itl_est = None  # EMA seconds per decode iteration
        if self._multistep:
            # paged=self._paged (not _fused_paged): a compiled loop cannot
            # run eager brackets per iteration, so the super-step fuses
            # them on every rung including baseline (greedy token-identity
            # contract, see _MultiStepForward)
            self._mstep = _MultiStepForward(
                model, self.max_seq, self.decode_steps,
                path=self.decode_path, quant=self._quant,
                qindex=self._qindex, paged=self._paged)
            self._msession = InferenceSession(
                self._mstep, batch_buckets=self.batch_buckets,
                seq_buckets=(1,), pad_value=self.pad_id,
                name=f"{name}_multi")

    def _set_cache_gauges(self):
        caches = self._zero_caches.values()
        self.metrics.set_kv_cache_bytes(
            sum(c.nbytes() for c in caches),
            state=sum(c.state_nbytes() for c in caches))

    def _fresh_cache(self, batch_bucket):
        """Zeroed rings for one batch bucket, allocated once and shared
        by every request: device arrays are immutable and prefill/decode
        return functionally-updated rings without touching their input
        cache, so reuse is safe — and the serving hot path skips
        2 x num_layers allocations + zero-fills per request.

        Paged mode returns the bucket's persistent
        :class:`~.kv_blocks.PagedKVPool` instead — fully assigned with
        identity page tables (slot ``s`` owns pages ``[1 + s*N,
        1 + (s+1)*N)``), mutated in place by :meth:`_run`. Stale page
        contents between requests are safe for the same reason ring
        garbage is: the attention mask only admits positions the current
        request has actually written."""
        if self._paged:
            from .kv_blocks import PagedKVPool
            from .prefix_cache import PrefixCache

            pool = self._zero_caches.get(batch_bucket)
            if pool is None:
                pool = PagedKVPool(self.model, batch_bucket, self.max_seq,
                                   page_size=self._page_size,
                                   num_pages=self._kv_pages,
                                   quant=self._quant)
                if self._prefix_on:
                    # prefix mode: slots are assigned per generate()
                    # (per-request budgets + trie-matched prefix pages)
                    # instead of pinned identity tables, and the bucket
                    # gets its radix trie over this pool
                    self._prefix[batch_bucket] = PrefixCache(
                        pool, name=f"{self.session.name}_prefix")
                else:
                    for s in range(batch_bucket):
                        pool.assign(s, self.max_seq)
                self._zero_caches[batch_bucket] = pool
                self._set_cache_gauges()
                self.metrics.set_kv_pages(pool.pages_used,
                                          pool.pages_free)
            return pool
        cache = self._zero_caches.get(batch_bucket)
        if cache is None:
            cache = self._zero_caches.setdefault(
                batch_bucket,
                KVCache.alloc(self.model, batch_bucket, self.max_seq,
                              quant=self._quant))
            self._set_cache_gauges()
        return cache

    # -- phase helpers (also the parity-test surface) -----------------------
    @on_block_context
    def _run(self, tokens, start_pos, last_idx, cache):
        from .. import numpy as mnp

        toks = mnp.array(_onp.asarray(tokens, _onp.int32))
        sp = mnp.array(_onp.asarray(start_pos, _onp.int32))
        li = mnp.array(_onp.asarray(last_idx, _onp.int32))
        # every row of a Generator call is live and owns state row i
        lanes = ([mnp.array(_onp.arange(toks.shape[0], dtype=_onp.int32))]
                 if self._layout.has_state else [])
        if self._paged:
            if not self._fused_paged:
                # strict rung: run the paging brackets as standalone
                # exact-copy device ops around the UNCHANGED ring
                # executable -> bitwise identical to ring decode
                table = cache.table_nd()
                rings = gather_rings(self._layout, cache.flat(), table)
                out = self.session.run(toks, sp, li, *lanes, *rings,
                                       *self._qflat)
                cache.update_from_flat(scatter_rings(
                    self._layout, cache.flat(), table, out[1:], sp,
                    toks.shape[1]))
                return out[0], cache
            out = self.session.run(toks, sp, li, cache.table_nd(), *lanes,
                                   *cache.flat(), *self._qflat)
            cache.update_from_flat(out[1:])
            return out[0], cache
        out = self.session.run(toks, sp, li, *lanes, *cache.flat(),
                               *self._qflat)
        logits, flat = out[0], out[1:]
        return logits, KVCache.from_flat(flat, self.max_seq,
                                         quant=self._quant,
                                         layout=self._layout)

    def prefill(self, prompts, prompt_lens, cache):
        """Run the prompt block through the cache path. ``prompts`` is a
        host (B, T_bucket) int array (already padded), ``prompt_lens`` the
        (B,) real lengths. Returns ((B, vocab) last-real-position logits,
        updated cache)."""
        b = len(prompt_lens)
        zeros = _onp.zeros(b, _onp.int32)
        last = _onp.asarray(prompt_lens, _onp.int32) - 1
        return self._run(prompts, zeros, last, cache)

    def decode_step(self, tokens, positions, cache):
        """One T=1 decode step: ``tokens`` (B,) the just-sampled ids,
        ``positions`` (B,) their absolute positions. Returns the next
        (B, vocab) logits and the updated cache. The ``serve:decode``
        fault site fires once per step, so the chaos harness can kill a
        generation stream mid-decode (distinct from ``serve:execute``,
        which also covers prefill)."""
        _faults.fault_point("serve:decode",
                            {"session": self.session.name})
        toks = _onp.asarray(tokens, _onp.int32).reshape(-1, 1)
        zeros = _onp.zeros(len(toks), _onp.int32)
        return self._run(toks, _onp.asarray(positions, _onp.int32),
                         zeros, cache)

    @on_block_context
    def decode_super(self, tokens, positions, steps_limit, remaining,
                     seeds, temps, top_ks, stops, key_bits, cache,
                     stamps=None):
        """One multi-step super-step: up to ``steps_limit`` decode
        iterations inside the compiled loop (see
        :class:`_MultiStepForward`). Returns ``(block, valid, done,
        cache)`` as host numpy — the (B, N) token block, per-lane valid
        counts and done flags the caller settles in one pass. Fires the
        same ``serve:decode`` fault site as :meth:`decode_step` (once
        per super-step — the host-visit granularity).

        ``stamps``: optional list; one ``(perf_counter, thread_wait_ns)``
        pair is appended right after the executable dispatch returns
        (before the blocking block fetch), so callers can split
        dispatch from device time in the attribution ledger without
        reimplementing the call."""
        from .. import numpy as mnp

        if self._msession is None:
            raise MXNetError(
                "decode_super needs multistep=True (or "
                "MXNET_SERVE_MULTISTEP=1) at construction")
        _faults.fault_point("serve:decode",
                            {"session": self._msession.name})
        b = len(positions)
        args = [
            mnp.array(_onp.asarray(tokens, _onp.int32).reshape(b, 1)),
            mnp.array(_onp.asarray(positions, _onp.int32)),
            mnp.array(_onp.asarray([steps_limit], _onp.int32)),
            mnp.array(_onp.asarray(remaining, _onp.int32)),
            mnp.array(_onp.asarray(seeds, _onp.int32)),
            mnp.array(_onp.asarray(temps, _onp.float32)),
            mnp.array(_onp.asarray(top_ks, _onp.int32)),
            mnp.array(_onp.asarray(stops, _onp.int32)),
            mnp.array(_onp.asarray(key_bits, _onp.uint32)),
        ]
        if self._paged:
            out = self._msession.run(*args, cache.table_nd(),
                                     *cache.flat(), *self._qflat)
            cache.update_from_flat(out[3:])
        else:
            out = self._msession.run(*args, *cache.flat(), *self._qflat)
            cache = KVCache.from_flat(out[3:], self.max_seq,
                                      quant=self._quant)
        if stamps is not None:
            stamps.append((time.perf_counter(), _attr.thread_wait_ns()))
        block = _onp.asarray(out[0].asnumpy(), _onp.int32)
        valid = _onp.asarray(out[1].asnumpy(), _onp.int32)
        done = _onp.asarray(out[2].asnumpy(), _onp.int32)
        return block, valid, done, cache

    # -- the serving API ----------------------------------------------------
    def _pad_prompts(self, prompts):
        lens = _onp.asarray([len(p) for p in prompts], _onp.int32)
        if int(lens.min()) < 1:
            raise MXNetError("empty prompt (need >= 1 token)")
        t_bucket = pick_bucket(int(lens.max()), self.prompt_buckets)
        b_bucket = pick_bucket(len(prompts), self.batch_buckets)
        toks = _onp.full((b_bucket, t_bucket), self.pad_id, _onp.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
        full_lens = _onp.ones(b_bucket, _onp.int32)
        full_lens[:len(prompts)] = lens
        # dead batch lanes replay prompt 0's first token at length 1
        toks[len(prompts):, 0] = toks[0, 0]
        return toks, full_lens, b_bucket

    # -- prefix-cache plumbing (PR 14) --------------------------------------
    def _prefix_begin(self, prompts, toks, lens, b_bucket, max_new):
        """Reserve the batch's slots in the bucket's pool. With the
        prefix trie on, each real row's longest cached prefix arrives as
        shared (refcounted) pages at the front of its table row and its
        ``matched`` count says how many prompt tokens skip prefill; pool
        pressure LRU-evicts cached prefixes (never the pages just
        matched) before surfacing :class:`PoolExhausted`. Returns
        ``(cache, matched)``; non-prefix mode returns the persistent
        fully-assigned pool and all-zero ``matched``."""
        cache = self._fresh_cache(b_bucket)
        matched = _onp.zeros(b_bucket, _onp.int32)
        if not self._prefix_on:
            return cache, matched
        trie = self._prefix[b_bucket]
        try:
            for s in range(b_bucket):
                if s < len(prompts):
                    row = [int(t) for t in prompts[s]]
                    m, pages = trie.match(row)
                else:  # dead padding lane: 1-token prompt, never cached
                    row, m, pages = [int(toks[s, 0])], 0, ()
                budget = min(len(row) + int(max_new)
                             + self._budget_headroom, self.max_seq)
                try:
                    cache.assign_with_prefix(s, budget, pages)
                except PoolExhausted:
                    shortfall = (cache.pages_for(budget) - len(pages)
                                 - cache.pages_free)
                    if trie.reclaim(max(shortfall, 1),
                                    exclude=pages) == 0:
                        raise
                    cache.assign_with_prefix(s, budget, pages)
                matched[s] = m
                if s < len(prompts):
                    self.metrics.observe_prefix(m)
        except BaseException:
            for s in range(b_bucket):
                cache.release(s)
            raise
        return cache, matched

    def _prefix_prefill(self, toks, lens, matched, cache):
        """Prefill only each row's un-cached tail: row ``s``'s tokens
        ``[matched[s]:lens[s]]`` at ``start_pos=matched[s]`` (per-row).
        Chunked prefill at an arbitrary start_pos is bit-identical to
        full prefill (the PR-5 parity contract), and the tail bucket
        comes from the same prompt lattice warmup compiled — zero new
        signatures. All-miss batches take the unchanged full path."""
        if not matched.any():
            return self.prefill(toks, lens, cache)
        tail_lens = (_onp.asarray(lens, _onp.int32)
                     - _onp.asarray(matched, _onp.int32))
        t_bucket = pick_bucket(int(tail_lens.max()), self.prompt_buckets)
        tails = _onp.full((len(lens), t_bucket), self.pad_id, _onp.int32)
        for s in range(len(lens)):
            tails[s, :tail_lens[s]] = toks[s, matched[s]:lens[s]]
        return self._run(tails, matched, tail_lens - 1, cache)

    def _prefix_release(self, prompts, b_bucket, cache, ok):
        """Retire the batch's slots. On a clean run the trie first
        adopts each real prompt's full pages (increfs while the slot
        still pins them) so later requests sharing the prefix skip that
        much prefill; then every slot's references drop — pages the trie
        kept survive, the rest recycle."""
        if not self._prefix_on:
            return
        trie = self._prefix[b_bucket]
        if ok:
            table = cache.table()
            for s, p in enumerate(prompts):
                trie.insert([int(t) for t in p], table[s])
        for s in range(b_bucket):
            cache.release(s)
        self.metrics.set_prefix_gauges(cache.pages_shared,
                                       trie.pages_held, trie.evictions)
        self.metrics.set_kv_pages(cache.pages_used, cache.pages_free)

    def generate(self, prompts, max_new_tokens=32, temperature=0.0,
                 top_k=None, stop_ids=(), deadlines=None):
        """Traced entry point: when request tracing is on and no ambient
        trace is active (a direct ``generate()`` call, not one under a
        traced batcher runner), open a ``serve.generate[<name>]`` lane so
        the prefill/decode-step spans land somewhere; under a batcher the
        representative request's lane is already active and is used
        instead. See :meth:`_generate` for the actual semantics."""
        own = None
        if _trace.ENABLED and _trace.current() is None:
            own = _trace.start_trace(f"serve.generate[{self.session.name}]",
                                     args={"prompts": len(prompts)})
        try:
            with _trace.activate(own):
                out = self._generate(prompts, max_new_tokens=max_new_tokens,
                                     temperature=temperature, top_k=top_k,
                                     stop_ids=stop_ids, deadlines=deadlines)
        except Exception as exc:
            if own is not None:
                own.finish(error=exc)
            raise
        if own is not None:
            own.finish()
        return out

    def _generate(self, prompts, max_new_tokens=32, temperature=0.0,
                  top_k=None, stop_ids=(), deadlines=None):
        """Generate continuations for a batch of prompts (lists of ids).

        ``deadlines`` (optional) carries absolute ``time.monotonic()``
        deadlines — one scalar for the whole batch or one per prompt. A
        row whose deadline passes is **retired between decode steps**: it
        stops consuming decode work, keeps the tokens generated so far,
        and lands in ``info["deadline_expired"]`` so the serving layer can
        settle its future with :class:`~.engine.DeadlineExceeded` instead
        of delivering late. When every live row has expired the whole
        decode loop exits early. ``None`` (default) checks nothing — the
        original semantics, bitwise included.

        Returns ``(outputs, info)``: per-prompt generated id lists (stop
        token excluded) and a stats dict (tokens/s, per-phase wall time,
        expired row indices).
        """
        t_start = time.perf_counter()
        toks, lens, b_bucket = self._pad_prompts(prompts)
        n_real = len(prompts)
        max_new = int(max_new_tokens)
        if int(lens.max()) + max_new > self.max_seq:
            raise MXNetError(
                f"prompt ({int(lens.max())}) + max_new_tokens ({max_new}) "
                f"exceeds max_seq ({self.max_seq})")
        if deadlines is not None:
            try:
                deadlines = [float(d) for d in deadlines]
            except TypeError:
                deadlines = [float(deadlines)] * n_real
            if len(deadlines) != n_real:
                raise MXNetError(
                    f"generate() got {len(deadlines)} deadlines for "
                    f"{n_real} prompts")
        cache, matched = self._prefix_begin(prompts, toks, lens, b_bucket,
                                            max_new)
        run_ok = False
        try:
            with _attr.phase_scope("prefill"), \
                    _trace.span("serve::prefill", {"batch": n_real}):
                logits, cache = self._prefix_prefill(toks, lens, matched,
                                                     cache)
                # the step-0 sample blocks on the PREFILL logits: its
                # device time is prefill wall — the steady-state decode
                # rate and the attribution ledger both exclude it (same
                # call order as before — one sample per entered step, so
                # the RNG key stream is unchanged)
                next_ids = sample_tokens(logits, temperature=temperature,
                                         top_k=top_k)
            t_prefill = time.perf_counter()

            out = [[] for _ in range(n_real)]
            stopped = [False] * n_real
            expired = [False] * n_real
            positions = lens.copy()  # next write position per row
            stop = set(int(s) for s in stop_ids)
            n_decoded = 0
            n_visits = 0
            if self._multistep:
                cache, n_decoded, n_visits = self._decode_loop_multi(
                    next_ids, positions, out, stopped, expired, stop,
                    max_new, temperature, top_k, deadlines, cache,
                    n_real, b_bucket)
            # multistep consumed the whole budget above; the single-step
            # loop below then runs zero iterations
            for step in range(0 if self._multistep else max_new):
                th0 = time.perf_counter()
                for i in range(n_real):
                    if stopped[i]:
                        continue
                    tid = int(next_ids[i])
                    if tid in stop:
                        stopped[i] = True
                    else:
                        out[i].append(tid)
                if deadlines is not None:
                    # retire expired rows at the step boundary: their
                    # decode budget is spent — burning further T=1 passes
                    # for output nobody will read is the overload failure
                    # mode
                    now = time.monotonic()
                    for i in range(n_real):
                        if not stopped[i] and now >= deadlines[i]:
                            stopped[i] = True
                            expired[i] = True
                            self.metrics.observe_deadline("decode")
                if all(stopped) or step == max_new - 1:
                    # the last sampled token needs no successor logits —
                    # running decode_step here would be a discarded T=1
                    # pass
                    break
                live = n_real - sum(stopped)
                attributing = _attr.ENABLED
                if attributing:
                    # per-step token accounting above is host work
                    # between device calls: the schedule bucket
                    self.ledger.observe_schedule(
                        (time.perf_counter() - th0) * 1e3)
                args = {"step": step, "live": live}
                with _attr.phase_scope("decode"):
                    t1 = time.perf_counter()
                    w1 = _attr.thread_wait_ns() if attributing else 0
                    with _trace.span("serve::decode_step", args):
                        logits, cache = self.decode_step(next_ids,
                                                         positions, cache)
                        t2 = time.perf_counter()
                        w2 = _attr.thread_wait_ns() if attributing else 0
                        # the next step's sample is THIS step's blocking
                        # device fetch — inside the span, so the four
                        # phases partition the span wall
                        next_ids = sample_tokens(logits,
                                                 temperature=temperature,
                                                 top_k=top_k)
                        t3 = time.perf_counter()
                        if attributing:
                            w3 = _attr.thread_wait_ns()
                            dispatch_ms = max(
                                0.0, (t2 - t1) * 1e3 - (w2 - w1) / 1e6)
                            device_ms = (t3 - t2) * 1e3
                            wait_ms = max(0.0, (w2 - w1) / 1e6)
                            args.update(host_ms=0.0,
                                        dispatch_ms=round(dispatch_ms, 4),
                                        device_ms=round(device_ms, 4),
                                        wait_ms=round(wait_ms, 4))
                            self.ledger.observe_step(0.0, dispatch_ms,
                                                     device_ms, wait_ms,
                                                     live=live)
                self.metrics.observe_itl((t3 - t1) * 1e3, live=live)
                positions = positions + 1
                n_decoded += 1
                n_visits += 1
            run_ok = True
        finally:
            self._prefix_release(prompts, b_bucket, cache, run_ok)
        t_done = time.perf_counter()
        decode_s = t_done - t_prefill
        n_tokens = sum(len(o) for o in out)
        self.metrics.observe_tokens(n_tokens, decode_s)
        if _attr.ENABLED:
            self.metrics.set_attribution(
                self.ledger.host_overhead_fraction(),
                self.ledger.device_ms_per_token())
        info = {
            "prefill_ms": (t_prefill - t_start) * 1e3,
            "decode_ms": decode_s * 1e3,
            "decode_steps": n_decoded,
            "decode_visits": n_visits,
            "tokens_s": n_tokens / decode_s if decode_s > 0 else 0.0,
            "total_ms": (t_done - t_start) * 1e3,
            "deadline_expired": [i for i in range(n_real) if expired[i]],
        }
        return out, info

    def _steps_limit(self, deadlines, stopped, n_real):
        """The next super-step's dynamic iteration ceiling: N, degraded
        to 1 when some live row's deadline could not survive a full
        N-iteration super-step (estimated off the per-iteration EMA) —
        the PR-6 504 retirement latency stays bounded by about one
        decode iteration, through the SAME compiled executable
        (``steps_limit`` is a traced input, never a new signature)."""
        n = self.decode_steps
        if deadlines is None or self._itl_est is None:
            return n
        now = time.monotonic()
        slack = min((deadlines[i] - now for i in range(n_real)
                     if not stopped[i]), default=None)
        if slack is not None and slack < self._itl_est * n:
            return 1
        return n

    def _decode_loop_multi(self, next_ids, positions, out, stopped,
                           expired, stop, max_new, temperature, top_k,
                           deadlines, cache, n_real, b_bucket):
        """The multi-step decode loop behind :meth:`_generate`: the
        step-0 token is emitted host-side (exactly like single-step),
        then every further token comes out of compiled super-steps —
        one host visit per block of up to ``decode_steps`` tokens,
        settled by replaying :class:`_Slot`-style emission over the
        returned token block. Token streams are invariant to the
        super-step boundary (counter-based in-trace keys), so N=8 and
        N=1 multistep output is identical, and greedy output matches
        the single-step loop token for token."""
        # step-0 emission: the prefill-sampled token, one per row
        for i in range(n_real):
            tid = int(next_ids[i])
            if tid in stop:
                stopped[i] = True
            else:
                out[i].append(tid)
                if len(out[i]) >= max_new:
                    stopped[i] = True
        pending = _onp.zeros(b_bucket, _onp.int32)
        pending[:len(next_ids)] = _onp.asarray(next_ids, _onp.int32)
        temp = float(temperature) if temperature is not None else 0.0
        # greedy runs never consume a host RNG draw (matching the
        # single-step loop, whose greedy path draws no keys either)
        key_bits = (_fresh_key_bits() if temp > 0.0
                    else _onp.zeros(2, _onp.uint32))
        seeds = _onp.arange(b_bucket, dtype=_onp.int32)
        temps = _onp.full(b_bucket, max(temp, 0.0), _onp.float32)
        tks = _onp.full(b_bucket, int(top_k) if top_k else 0, _onp.int32)
        stops_m = _stop_matrix(b_bucket, [stop] * b_bucket)
        n_decoded = n_visits = 0
        while True:
            if deadlines is not None:
                now = time.monotonic()
                for i in range(n_real):
                    if not stopped[i] and now >= deadlines[i]:
                        stopped[i] = True
                        expired[i] = True
                        self.metrics.observe_deadline("decode")
            if all(stopped):
                break
            th0 = time.perf_counter()
            remaining = _onp.zeros(b_bucket, _onp.int32)
            for i in range(n_real):
                if not stopped[i]:
                    remaining[i] = max_new - len(out[i])
            limit = self._steps_limit(deadlines, stopped, n_real)
            live = n_real - sum(stopped)
            attributing = _attr.ENABLED
            if attributing:
                self.ledger.observe_schedule(
                    (time.perf_counter() - th0) * 1e3)
            args = {"steps": limit, "live": live}
            with _attr.phase_scope("decode"):
                t1 = time.perf_counter()
                w1 = _attr.thread_wait_ns() if attributing else 0
                with _trace.span("serve::decode_step", args):
                    stamps = []
                    block, valid, _done, cache = self.decode_super(
                        pending, positions, limit, remaining, seeds,
                        temps, tks, stops_m, key_bits, cache,
                        stamps=stamps)
                    t3 = time.perf_counter()
                    w3 = _attr.thread_wait_ns() if attributing else 0
                    steps_run = int(valid.max()) if valid.size else 0
                    n_tok = 0
                    for i in range(n_real):
                        if stopped[i]:
                            continue
                        k = int(valid[i])
                        n_tok += k
                        for j in range(k):
                            tid = int(block[i, j])
                            if tid in stop:
                                stopped[i] = True
                                break
                            out[i].append(tid)
                            pending[i] = tid
                            if len(out[i]) >= max_new:
                                stopped[i] = True
                                break
                        positions[i] += k
                    if attributing:
                        t4 = time.perf_counter()
                        w4 = _attr.thread_wait_ns()
                        t2, w2 = stamps[0]
                        dispatch_ms = max(
                            0.0, (t2 - t1) * 1e3 - (w2 - w1) / 1e6)
                        device_ms = (t3 - t2) * 1e3
                        host_ms = max(
                            0.0, (t4 - t3) * 1e3 - (w4 - w3) / 1e6)
                        wait_ms = max(
                            0.0, ((w2 - w1) + (w4 - w3)) / 1e6)
                        args.update(host_ms=round(host_ms, 4),
                                    dispatch_ms=round(dispatch_ms, 4),
                                    device_ms=round(device_ms, 4),
                                    wait_ms=round(wait_ms, 4),
                                    tokens=n_tok)
                        self.ledger.observe_step(
                            host_ms, dispatch_ms, device_ms, wait_ms,
                            live=live, tokens=n_tok)
            if steps_run > 0:
                # k amortized token-to-token gaps, not one giant gap
                self.metrics.observe_itl((t3 - t1) * 1e3, live=live,
                                         tokens=steps_run)
                est = (t3 - t1) / steps_run
                self._itl_est = (est if self._itl_est is None
                                 else 0.5 * self._itl_est + 0.5 * est)
            n_decoded += steps_run
            n_visits += 1
        return cache, n_decoded, n_visits

    # -- warmup / invariants -------------------------------------------------
    def warmup(self):
        """Compile every (batch bucket x prompt bucket) prefill and every
        batch bucket's decode step — plus, in multistep mode, every batch
        bucket's super-step; freezes the signature sets so
        ``assert_no_recompiles`` guards steady state."""
        t0 = time.perf_counter()
        for bb in self.batch_buckets:
            for pb in self.prompt_buckets:
                cache = self._fresh_cache(bb)
                toks = _onp.zeros((bb, pb), _onp.int32)
                lens = _onp.ones(bb, _onp.int32)
                logits, cache = self.prefill(toks, lens, cache)
                if pb == self.prompt_buckets[0]:
                    ids = _onp.zeros(bb, _onp.int32)
                    self.decode_step(ids, lens, cache)
                    if self._multistep:
                        # remaining=0: the loop replays zero iterations
                        # but the body still traces/compiles in full
                        self.decode_super(
                            ids, lens, self.decode_steps,
                            _onp.zeros(bb, _onp.int32),
                            _onp.zeros(bb, _onp.int32),
                            _onp.zeros(bb, _onp.float32),
                            _onp.zeros(bb, _onp.int32),
                            _onp.full((bb, _STOP_WIDTH), -1, _onp.int32),
                            _onp.zeros(2, _onp.uint32), cache)
        self.session.freeze_signatures()
        sigs = self.session.signature_count()
        if self._msession is not None:
            self._msession.freeze_signatures()
            sigs += self._msession.signature_count()
        return {"signatures": sigs,
                "wall_s": time.perf_counter() - t0}

    def assert_no_recompiles(self):
        self.session.assert_no_recompiles()
        if self._msession is not None:
            self._msession.assert_no_recompiles()

    def stats(self):
        out = self.session.stats()
        if self._msession is not None:
            out["multistep"] = self._msession.stats()
            out["decode_steps"] = self.decode_steps
        return out


class SpeculativeGenerator:
    """Speculative decoding (Leviathan et al.): a cheap draft model
    proposes ``k`` tokens per round, the target model scores the whole
    block in ONE (k+1)-wide step, and the longest proposal prefix that
    matches the target's greedy choices is accepted plus one
    correction/bonus token — so each target pass emits between 1 and k+1
    tokens instead of exactly 1.

    Greedy-only by construction: with argmax acceptance the emitted
    sequence is **token-identical** to non-speculative greedy decoding for
    *any* draft model (a bad draft only costs speed, never output). The
    proof is inductive: the accepted prefix always equals the target's own
    greedy chain, and the correction token is the target's argmax
    conditioned on exactly that chain.

    No cache rollback is needed on rejection: ``cached_attention`` masks
    ring positions ``> start_pos + t``, so the K/V of rejected proposals
    is dead weight that the next round's writes overwrite before any read
    reaches it. Everything reuses the bucketed session machinery — the
    target and draft are plain :class:`Generator` s, the verify step is a
    third :class:`InferenceSession` compiled at T = k+1, and
    :meth:`assert_no_recompiles` spans all three.
    """

    def __init__(self, model, draft_model, k=None, max_seq=128,
                 batch_buckets=(1, 2, 4), prompt_buckets=None, pad_id=0,
                 name="llama_spec", decode_path=None, paged=None,
                 page_size=None, kv_pages=None, prefix_cache=None,
                 multistep=None):
        from .. import config

        self.k = int(k) if k is not None else int(
            config.get("MXNET_SERVE_SPEC_TOKENS"))
        if self.k < 1:
            raise MXNetError("speculative decoding needs k >= 1")
        for m in (model, draft_model):
            require_unbounded(
                m, "speculative decoding (SpeculativeGenerator)",
                WINDOW_PAGES_GO)
            require_kv_pairs(
                m, "speculative decoding (SpeculativeGenerator)",
                LATENT_PAGES_ALONE)
            require_kv_only(
                m, "speculative decoding (SpeculativeGenerator)",
                "a rejected proposal rolls a row's position back, which a "
                "K/V ring forgives and a state that has already advanced "
                "does not (no rollback without state snapshots)")
        if multistep is None:
            multistep = bool(config.get("MXNET_SERVE_MULTISTEP"))
        self._multistep = bool(multistep)
        self.target = Generator(
            model, max_seq=max_seq, batch_buckets=batch_buckets,
            prompt_buckets=prompt_buckets, pad_id=pad_id, name=name,
            decode_path=decode_path, paged=paged, page_size=page_size,
            kv_pages=kv_pages, prefix_cache=prefix_cache,
            multistep=False)
        # multistep: the whole draft-propose phase of a round IS one
        # super-step — k proposal iterations plus the (k+1)-th that
        # writes d_k's K/V run inside the draft's compiled loop, so a
        # round costs 2 host visits (draft block + verify) instead of
        # k+2. The target stays single-step (prefill + verify are its
        # only executables; it never runs a token loop here).
        self.draft = Generator(
            draft_model, max_seq=max_seq, batch_buckets=batch_buckets,
            prompt_buckets=prompt_buckets, pad_id=pad_id,
            name=f"{name}_draft", decode_path=decode_path, paged=paged,
            page_size=page_size, kv_pages=kv_pages,
            prefix_cache=prefix_cache, multistep=self._multistep,
            decode_steps=self.k + 1)
        # draft rounds write k+1 positions past the accepted prefix and
        # the verify block writes k+1 target positions — per-request
        # page budgets in prefix mode must cover that overhang
        self.target._budget_headroom = self.k + 1
        self.draft._budget_headroom = self.k + 1
        self.decode_path = self.target.decode_path
        self.max_seq = self.target.max_seq
        self.batch_buckets = self.target.batch_buckets
        self.pad_id = self.target.pad_id
        self._verify_step = _CacheForward(
            model, self.max_seq, path=self.decode_path,
            quant=self.target._quant, qindex=self.target._qindex,
            all_logits=True)
        self._verify = InferenceSession(
            self._verify_step, batch_buckets=self.batch_buckets,
            seq_buckets=(self.k + 1,), pad_value=self.pad_id,
            name=f"{name}_verify")
        self.ctx = self._verify.ctx
        self.metrics = self.target.metrics

    @on_block_context
    def _verify_run(self, tokens_blk, start_pos, cache):
        """One target pass over the (B, k+1) block [pending, d_1..d_k] at
        per-row ``start_pos``; returns the full (B, k+1, vocab) logits and
        the updated target cache. A paged target pool is bracketed with
        the standalone exact-copy gather/scatter ops around the
        ring-shaped verify executable (the strict-rung pattern from
        :meth:`Generator._run`), writing k+1 rows at per-row start_pos —
        so draft and target share the same prefix pages the trie
        handed out at admission."""
        from .. import numpy as mnp

        blk = _onp.asarray(tokens_blk, _onp.int32)
        toks = mnp.array(blk)
        sp = mnp.array(_onp.asarray(start_pos, _onp.int32))
        li = mnp.array(_onp.zeros(len(blk), _onp.int32))
        if self.target._paged:
            table = cache.table_nd()
            rings = [_ops.paged_kv_gather(p, table)
                     for p in cache.flat()]
            out = self._verify.run(toks, sp, li, *rings,
                                   *self.target._qflat)
            cache.update_from_flat([
                _ops.paged_kv_scatter(p, table, r, sp, blk.shape[1])
                for p, r in zip(cache.flat(), out[1:])])
            return out[0], cache
        out = self._verify.run(toks, sp, li, *cache.flat(),
                               *self.target._qflat)
        logits, flat = out[0], out[1:]
        return logits, KVCache.from_flat(flat, self.max_seq,
                                         quant=self.target._quant)

    def generate(self, prompts, max_new_tokens=32, temperature=0.0,
                 top_k=None, stop_ids=(), deadlines=None):
        """Same contract as :meth:`Generator.generate` (greedy only):
        per-prompt generated id lists plus a stats dict — with
        ``rounds``, ``draft_steps``, ``verify_steps`` and the measured
        ``acceptance_rate`` added."""
        if temperature is not None and temperature > 0.0:
            raise MXNetError(
                "SpeculativeGenerator is greedy-only: sampled acceptance "
                "needs the rejection-sampling correction this build does "
                "not implement (temperature must be 0)")
        t_start = time.perf_counter()
        toks, lens, b_bucket = self.target._pad_prompts(prompts)
        n_real = len(prompts)
        max_new = int(max_new_tokens)
        # +k+1 headroom: the last round's verify block writes k+1 ring
        # positions past the accepted prefix
        if int(lens.max()) + max_new + self.k + 1 > self.max_seq:
            raise MXNetError(
                f"prompt ({int(lens.max())}) + max_new_tokens ({max_new}) "
                f"+ speculative headroom ({self.k + 1}) exceeds max_seq "
                f"({self.max_seq})")
        if deadlines is not None:
            try:
                deadlines = [float(d) for d in deadlines]
            except TypeError:
                deadlines = [float(deadlines)] * n_real
            if len(deadlines) != n_real:
                raise MXNetError(
                    f"generate() got {len(deadlines)} deadlines for "
                    f"{n_real} prompts")
        tcache, tmatched = self.target._prefix_begin(
            prompts, toks, lens, b_bucket, max_new)
        try:
            dcache, dmatched = self.draft._prefix_begin(
                prompts, toks, lens, b_bucket, max_new)
        except BaseException:
            self.target._prefix_release(prompts, b_bucket, tcache, False)
            raise
        run_ok = False
        try:
            with _trace.span("serve::prefill", {"batch": n_real}):
                logits, tcache = self.target._prefix_prefill(
                    toks, lens, tmatched, tcache)
                _, dcache = self.draft._prefix_prefill(
                    toks, lens, dmatched, dcache)
            t_prefill = time.perf_counter()

            pending = sample_tokens(logits)  # (b_bucket,) greedy
            out = [[] for _ in range(n_real)]
            stopped = [False] * b_bucket
            for i in range(n_real, b_bucket):
                stopped[i] = True  # dead padding lanes ride along frozen
            expired = [False] * n_real
            stop = set(int(s) for s in stop_ids)
            # the prefill-sampled token is the first emission (exactly
            # like Generator._generate's step-0 sample)
            for i in range(n_real):
                tid = int(pending[i])
                if tid in stop:
                    stopped[i] = True
                else:
                    out[i].append(tid)
                    if len(out[i]) >= max_new:
                        stopped[i] = True
            positions = lens.copy()  # write position of row's `pending`
            rounds = draft_steps = verify_steps = 0
            proposed = accepted = 0
            proposals = _onp.zeros((b_bucket, self.k), _onp.int32)
            while not all(stopped):
                rounds += 1
                # draft proposes d_1..d_k; the extra (k+1)-th step writes
                # d_k's K/V into the draft ring so a fully-accepted round
                # leaves no hole at position + k
                if self._multistep:
                    # one compiled super-step runs all k+1 draft
                    # iterations: iteration j feeds d_j at pos+j, writes
                    # its K/V and greedily samples d_{j+1} — identical to
                    # the sequential loop below, one host visit instead
                    # of k+1. No stops, no budget: every lane runs the
                    # full k+1 iterations (spare proposals for frozen
                    # lanes are ignored at settle, same as sequential).
                    with _trace.span("serve::draft_step",
                                     {"steps": self.k + 1}):
                        blk_d, _, _, dcache = self.draft.decode_super(
                            pending, positions, self.k + 1,
                            _onp.full(b_bucket, self.k + 2, _onp.int32),
                            _onp.arange(b_bucket, dtype=_onp.int32),
                            _onp.zeros(b_bucket, _onp.float32),
                            _onp.zeros(b_bucket, _onp.int32),
                            _onp.full((b_bucket, _STOP_WIDTH), -1,
                                      _onp.int32),
                            _onp.zeros(2, _onp.uint32), dcache)
                    draft_steps += self.k + 1
                    proposals[:, :] = blk_d[:, :self.k]
                else:
                    cur = pending.copy()
                    dpos = positions.copy()
                    for j in range(self.k + 1):
                        with _trace.span("serve::draft_step", {"j": j}):
                            dlog, dcache = self.draft.decode_step(
                                cur, dpos, dcache)
                        dpos = dpos + 1
                        draft_steps += 1
                        if j < self.k:
                            cur = sample_tokens(dlog)
                            proposals[:, j] = cur
                blk = _onp.concatenate(
                    [_onp.asarray(pending).reshape(-1, 1), proposals],
                    axis=1)
                with _trace.span("serve::verify_step", {"k": self.k}):
                    vlogits, tcache = self._verify_run(blk, positions,
                                                       tcache)
                verify_steps += 1
                greedy = sample_tokens(
                    vlogits.reshape(-1, vlogits.shape[-1]))
                greedy = greedy.reshape(b_bucket, self.k + 1)
                for i in range(b_bucket):
                    if stopped[i]:
                        continue
                    a = 0
                    while a < self.k and proposals[i, a] == greedy[i, a]:
                        a += 1
                    proposed += self.k
                    accepted += a
                    emit = [int(t) for t in proposals[i, :a]]
                    emit.append(int(greedy[i, a]))
                    for tid in emit:
                        if tid in stop:
                            stopped[i] = True
                            break
                        out[i].append(tid)
                        if len(out[i]) >= max_new:
                            stopped[i] = True
                            break
                    pending[i] = greedy[i, a]
                    positions[i] += a + 1
                if deadlines is not None:
                    now = time.monotonic()
                    for i in range(n_real):
                        if not stopped[i] and now >= deadlines[i]:
                            stopped[i] = True
                            expired[i] = True
                            self.metrics.observe_deadline("decode")
            run_ok = True
        finally:
            self.target._prefix_release(prompts, b_bucket, tcache, run_ok)
            self.draft._prefix_release(prompts, b_bucket, dcache, run_ok)
        t_done = time.perf_counter()
        decode_s = t_done - t_prefill
        n_tokens = sum(len(o) for o in out)
        self.metrics.observe_tokens(n_tokens, decode_s)
        info = {
            "prefill_ms": (t_prefill - t_start) * 1e3,
            "decode_ms": decode_s * 1e3,
            "rounds": rounds,
            "draft_steps": draft_steps,
            "verify_steps": verify_steps,
            "acceptance_rate": accepted / proposed if proposed else 0.0,
            "tokens_s": n_tokens / decode_s if decode_s > 0 else 0.0,
            "total_ms": (t_done - t_start) * 1e3,
            "deadline_expired": [i for i in range(n_real) if expired[i]],
        }
        return out, info

    # -- warmup / invariants -------------------------------------------------
    def warmup(self):
        """Warm all three sessions: the target and draft lattices plus one
        verify signature per batch bucket."""
        t0 = time.perf_counter()
        self.target.warmup()
        self.draft.warmup()
        for bb in self.batch_buckets:
            cache = self.target._fresh_cache(bb)
            blk = _onp.zeros((bb, self.k + 1), _onp.int32)
            self._verify_run(blk, _onp.zeros(bb, _onp.int32), cache)
        self._verify.freeze_signatures()
        return {"signatures": (self.target.session.signature_count()
                               + self.draft.session.signature_count()
                               + self._verify.signature_count()),
                "wall_s": time.perf_counter() - t0}

    def assert_no_recompiles(self):
        self.target.assert_no_recompiles()
        self.draft.assert_no_recompiles()
        self._verify.assert_no_recompiles()

    def stats(self):
        return {"target": self.target.stats(), "draft": self.draft.stats(),
                "verify": self._verify.stats()}
