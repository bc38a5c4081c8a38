"""CachedOp: the compiled executor behind ``HybridBlock.hybridize()``.

Reference: ``src/imperative/cached_op.cc`` — wraps an nnvm graph, re-plans or
reuses static buffers per call (``DynamicForward``/``StaticForward``), and
registers itself as a single ``_CachedOp`` node on the autograd tape with a
matching ``Backward`` executor.

TPU design: the "graph" is obtained by *replaying the block's forward* with
tracer-backed NDArrays inside ``jax.jit`` (the deferred-compute move of
Gluon 2, ``python/mxnet/_deferred_compute.py``, collapsed onto jax tracing).
Per input signature (shapes/dtypes/train-mode/grad-mode) we build and cache:

  * ``fwd_jit(param_data, state_data, key, *args) -> (outs, new_states, vjp)``
    — one XLA executable containing the whole forward (+ residual saving
    when grads are needed). ``vjp`` is a ``jax.tree_util.Partial`` pytree of
    residual arrays.
  * ``bwd_jit(vjp, cotangents) -> (param_grads, arg_grads)`` — one XLA
    executable for the whole backward, compiled on first backward call.

Static buffer reuse, memory planning, and op fusion — the reason the
reference has ``static_alloc``/``static_shape`` (``cached_op.h:415-436``) —
are XLA's job; ``static_alloc`` maps to donating the state buffers. A block
may also consume call arguments: ``block.donate_args`` names the positions
of its call that it takes over and returns (a serving step's cache arrays),
and those are donated to the executable too. The caller must not read an
array it passed at such a position again.

Mutable state (BatchNorm running stats, any ``grad_req='null'`` parameter a
layer rebinds during forward) is handled structurally: state params enter as
traced inputs and their (possibly rebound) values are returned as extra
outputs, then written back after the call — giving the reference's
aux-state mutation semantics without mutation inside the compiled graph.
"""
from __future__ import annotations

import hashlib as _hashlib
import json as _json
import threading
import time
import warnings
import weakref
from typing import List, Sequence

from . import autograd
from . import random as _rng
from .base import MXNetError
from .ndarray.ndarray import NDArray, _slot_of, _tracked
from .profiler import core as _prof

_trace_state = threading.local()

# fault-injection hot-state (resilience.faults.FaultPlan slot, see
# ops/registry.py): None until a plan installs
_FAULTS = None

# live CachedOp instances, for the process-wide cache_stats() aggregate
# (profiler.export pulls it); weak so the registry never pins an executor
_instances: "weakref.WeakSet" = weakref.WeakSet()


def cache_stats():
    """Process-wide signature-cache telemetry: the per-instance
    :meth:`CachedOp.cache_stats` fields summed over every live CachedOp
    (plus the instance count and the persistent compile cache's
    disk_hits/disk_misses — see :mod:`mxnet_tpu.compile_cache`)."""
    agg = {"instances": 0, "hits": 0, "misses": 0, "signatures": 0,
           "serve_hits": 0, "compile_ms": 0.0, "fast_calls": 0,
           "keys_skipped": 0}
    for op in list(_instances):
        s = op.cache_stats()
        agg["instances"] += 1
        for k in s:
            agg[k] += s[k]
    from . import compile_cache as _cc

    agg["disk_hits"] = _cc.disk_hits()
    agg["disk_misses"] = _cc.disk_misses()
    return agg

# sentinel marking a traced (array) position in a CachedOp call signature
_TRACED = object()


def _stable_form(x):
    """Recursively normalize one signature-key element to a
    JSON-serializable, process-independent form. The sentinel and any
    exotic hashable static arg map to type-tagged strings — never to
    ``repr`` (which can leak ``0x...`` object ids)."""
    if x is _TRACED:
        return "<traced>"
    if x is None or isinstance(x, (bool, int, float, str)):
        return x
    if isinstance(x, (bytes, bytearray)):
        return "bytes:" + bytes(x).hex()
    if isinstance(x, (tuple, list)):
        return [_stable_form(e) for e in x]
    if isinstance(x, (frozenset, set)):
        return sorted((_stable_form(e) for e in x), key=_json_sort_key)
    if isinstance(x, dict):
        return {str(k): _stable_form(v) for k, v in sorted(x.items())}
    return f"<{type(x).__name__}>"


def _json_sort_key(e):
    return _json.dumps(e, sort_keys=True)


def stable_signature_key(key, compiler_options=None):
    """Process-independent serialized form of one CachedOp signature key:
    canonical JSON of the normalized key (+ sorted compiler options),
    SHA-256 hexdigest. Two processes tracing the same model over the
    same bucket lattice produce identical digests — the contract disk-
    level caches key on (regression-pinned in tests/test_compile_cache)."""
    doc = {"key": _stable_form(key),
           "compiler_options": _stable_form(
               dict(compiler_options) if compiler_options else {})}
    blob = _json.dumps(doc, sort_keys=True, separators=(",", ":"))
    return _hashlib.sha256(blob.encode()).hexdigest()


def _sig_limit():
    # read per miss, not cached: a build is orders slower than an env read,
    # and tests tune the threshold via the env var
    from . import config

    return config.get("MXNET_CACHEDOP_SIG_LIMIT")


def _wrap_data(d):
    w = NDArray.__new__(NDArray)
    w._view_parent = None
    w._view_key = None
    w._view_pver = 0
    w._data = d
    w._tape = None
    w._leaf = None
    w._version = 0
    w._stype = "default"
    return w


def in_trace() -> bool:
    return getattr(_trace_state, "depth", 0) > 0


class _ParamBinding:
    """Temporarily rebind parameter NDArrays to tracers during tracing."""

    def __init__(self, arrays: Sequence[NDArray], tracers):
        self.arrays = arrays
        self.tracers = tracers
        self.saved = None

    def __enter__(self):
        self.saved = [(a._data, a._tape, a._leaf) for a in self.arrays]
        for a, t in zip(self.arrays, self.tracers):
            a._data = t
        _trace_state.depth = getattr(_trace_state, "depth", 0) + 1
        return self

    def __exit__(self, *exc):
        _trace_state.depth -= 1
        for a, (data, tape, leaf) in zip(self.arrays, self.saved):
            a._data = data
            a._tape = tape
            a._leaf = leaf
        return False


class CachedOp:
    """Compiled, signature-cached executor for a HybridBlock."""

    def __init__(self, block, static_alloc=False, static_shape=False,
                 flags=(), compiler_options=None):  # pylint: disable=unused-argument
        self.block = block
        self.static_alloc = static_alloc
        self.static_shape = static_shape
        # per-executable XLA overrides (jax.jit compiler_options)
        self._compiler_options = dict(compiler_options) \
            if compiler_options else None
        self._cache = {}
        self._bwd_cache = {}
        # telemetry (always maintained — int increments on an already-
        # expensive path): per-instance cache traffic + compile wall time
        self._hits = self._misses = self._serve_hits = 0
        self._fast_calls = self._keys_skipped = 0
        self._compile_ns = 0
        self._storm_warned = False
        self._call_tls = threading.local()
        _instances.add(self)

    def cache_stats(self):
        """Signature-cache telemetry: hits/misses/signatures/compile time,
        ``serve_hits`` (warm calls issued through ``mxnet_tpu.serve``),
        ``fast_calls`` (warm calls that derived nothing anew from the
        model), ``keys_skipped`` (calls whose program reads no RNG key)."""
        return {"hits": self._hits, "misses": self._misses,
                "signatures": len(self._cache),
                "serve_hits": self._serve_hits, "fast_calls": self._fast_calls,
                "keys_skipped": self._keys_skipped,
                "compile_ms": self._compile_ns / 1e6}

    def signature_count(self) -> int:
        """Number of distinct compiled signatures (executables) held.

        The serving engine's "no recompiles after warmup" assertion is
        exactly: this count does not move between two points in time.
        """
        return len(self._cache)

    def bucket_keys(self):
        """The cached signature keys themselves — each is one compiled
        bucket: (arg shapes/dtypes, param shapes/dtypes, state
        shapes/dtypes, train-mode, grad-mode, tracked-args, static args).
        Exposed so ``serve.engine`` (and users) can see exactly which
        padded shapes are resident."""
        return list(self._cache.keys())

    def signature_keys(self):
        """Stable, process-independent serialized signature keys (sorted
        SHA-256 hexdigests via :func:`stable_signature_key`, compiler
        options folded in). Raw ``bucket_keys()`` contain the ``_TRACED``
        sentinel — an object whose identity (and thus repr) differs per
        process; these digests do not, so two processes warming the same
        model over the same bucket lattice report identical keys (the
        disk compile cache's keying contract)."""
        return sorted(
            stable_signature_key(k, self._compiler_options)
            for k in self._cache)

    def record_serve_hit(self, n=1):
        """Count ``n`` warm serve-path executions into ``cache_stats()``.
        Called by ``serve.engine.InferenceSession`` after a call that hit
        an already-compiled signature."""
        self._serve_hits += int(n)

    def begin_serve_call(self):
        """Arm per-thread warm-call tracking: after the next call on this
        thread, :meth:`call_was_warm` reports whether it compiled. Thread-
        local, so concurrent serving threads can't misattribute another
        thread's cold compile to their own warm call (a global
        misses-delta snapshot would)."""
        self._call_tls.compiled = False

    def call_was_warm(self):
        """True if no signature was compiled on THIS thread since
        :meth:`begin_serve_call`."""
        return not getattr(self._call_tls, "compiled", True)

    # -- helpers ----------------------------------------------------------
    def _lookup_or_build(self, key, grad_mode, args_tracked, static_args):
        entry = self._cache.get(key)
        if entry is not None:
            self._hits += 1
            return entry
        self._misses += 1
        self._call_tls.compiled = True
        # every signature miss routes its jax.jit lowering through the
        # persistent disk cache when MXNET_COMPILE_CACHE_DIR is set —
        # enable() is an idempotent no-op otherwise
        from . import compile_cache as _cc

        _cc.enable()
        t0 = time.perf_counter_ns()
        entry = self._build_with_retry(key, grad_mode, args_tracked,
                                       static_args)
        self._cache[key] = entry
        t1 = time.perf_counter_ns()
        self._compile_ns += t1 - t0
        nsig = len(self._cache)
        blk = type(self.block).__name__
        if _prof.ENABLED:
            _prof.record_duration(f"CachedOp::compile({blk})", "cachedop",
                                  t0, t1,
                                  args={"signatures": nsig,
                                        "grad_mode": bool(grad_mode)})
            _prof.incr_counter("cachedop.compiles", cat="cachedop")
        limit = _sig_limit()
        if nsig > limit and not self._storm_warned:
            # recompile storm: something varies per call (shapes, dtypes,
            # unhashable static args) and defeats the executable cache —
            # the silent perf failure this counter exists to surface
            self._storm_warned = True
            _prof.incr_counter("cachedop.recompile_storms", cat="cachedop")
            from .profiler import recorder as _recorder

            _recorder.note("warn", "cachedop.recompile_storm",
                           {"block": str(blk), "signatures": nsig,
                            "limit": limit})
            warnings.warn(
                f"CachedOp({blk}) compiled {nsig} distinct signatures "
                f"(> MXNET_CACHEDOP_SIG_LIMIT={limit}); likely a recompile "
                "storm — per-call varying shapes, dtypes or static args "
                "defeat the executable cache", RuntimeWarning, stacklevel=4)
        return entry

    def _build_with_retry(self, key, grad_mode, args_tracked, static_args):
        """Trace/compile under the resilience retry policy: a transient
        XLA compile failure (dropped connection, RESOURCE_EXHAUSTED from a
        concurrent compile) backs off and retries instead of failing the
        training step; real trace errors re-raise on the first attempt."""
        from .resilience import retry as _retry

        def build():
            flt = _FAULTS
            if flt is not None:
                flt.check("cachedop:compile",
                          {"block": type(self.block).__name__})
            return self._build(key, grad_mode, args_tracked, static_args)

        return _retry.call_with_retry(
            build, site=f"CachedOp::compile({type(self.block).__name__})",
            policy=_retry.compile_policy())

    def _write_back_state(self, state_handles, new_states):
        """Write back mutated state (BatchNorm running stats etc.); a
        state the forward left alone came back as None."""
        for arr, ns in zip(state_handles, new_states):
            if ns is not None and arr._data is not ns:
                arr._set_data_internal(ns)

    def _split_params(self):
        params = list(self.block.collect_params().values())
        train = [p for p in params if p.grad_req != "null"]
        state = [p for p in params if p.grad_req == "null"]
        return train, state

    def _params(self, recording):
        """The block's parameters as this call needs them, and whether
        they had to be derived anew (see :class:`_ParamSnapshot`)."""
        store = _snapshots  # before any read: see params_changed()
        snap = store.get(self)
        scoped = getattr(_trace_state, "replica_ctx", None) is not None
        fresh = snap is None or scoped or not snap.stands()
        if fresh:
            snap = self._take_snapshot()
        if recording or scoped or not snap.plain:
            store.pop(self, None)
        elif fresh:
            store[self] = snap
        return snap, fresh

    def _build(self, key, grad_mode, args_tracked, static_args):
        import jax

        train_params, state_params = self._split_params()
        train_arrays = [p.data() for p in train_params]
        state_arrays = [p.data() for p in state_params]
        block = self.block
        is_training = autograd.is_training()
        donate_states = self.static_alloc and not grad_mode
        out_tree_box = {}

        def replay(tp_datas, st_datas, rng_key, arg_datas):
            """Re-run block.forward with tracer-backed NDArrays; static
            (non-array) call args are spliced back into their positions."""
            all_arrays = train_arrays + state_arrays
            all_tracers = list(tp_datas) + list(st_datas)
            wrapped = iter([_wrap_data(d) for d in arg_datas])
            wrapped_args = [next(wrapped) if s is _TRACED else s
                            for s in static_args]
            with _ParamBinding(all_arrays, all_tracers):
                _rng.push_trace_rng(rng_key)
                prev_rec = autograd.set_recording(False)
                prev_train = autograd.set_training(is_training)
                try:
                    outs = block.forward(*wrapped_args)
                finally:
                    autograd.set_training(prev_train)
                    autograd.set_recording(prev_rec)
                    out_tree_box["draws"] = _rng.pop_trace_rng().counter
                # only a state the forward rebound (BatchNorm running
                # stats) is an output. An untouched one would come back
                # as its own input tracer, and XLA copies a passed-through
                # input: every frozen weight, on every call. Donated
                # state buffers (static_alloc) alias their outputs, and
                # must all come back or the call would consume them.
                new_states = [
                    a._data if donate_states or a._data is not t else None
                    for a, t in zip(state_arrays, st_datas)]
            flat_outs, tree = jax.tree_util.tree_flatten(
                outs, is_leaf=lambda x: isinstance(x, NDArray))
            out_tree_box["tree"] = tree
            out_datas = [o._data if isinstance(o, NDArray) else o for o in flat_outs]
            return out_datas, new_states

        # subgraph-backend passes (optimize_for): fn->fn transforms over
        # the replayed forward — remat, dtype autocast, custom rewrites
        # (the SubgraphProperty partition hook done the trace-once way)
        for graph_pass in getattr(block, "_graph_passes", ()) or ():
            replay = graph_pass(replay)

        diff_arg_idx = [i for i, t in enumerate(args_tracked) if t]

        if grad_mode:
            def fwd(tp_datas, st_datas, rng_key, *arg_datas):
                diff_args = tuple(arg_datas[i] for i in diff_arg_idx)

                def for_vjp(tp, *dargs):
                    full_args = list(arg_datas)
                    for i, d in zip(diff_arg_idx, dargs):
                        full_args[i] = d
                    return replay(tp, st_datas, rng_key, full_args)

                (out_datas, new_states), vjp = jax.vjp(for_vjp, tuple(tp_datas), *diff_args)
                return out_datas, new_states, vjp

            fwd_jit = jax.jit(fwd, compiler_options=self._compiler_options)
        else:
            def fwd(tp_datas, st_datas, rng_key, *arg_datas):
                out_datas, new_states = replay(tp_datas, st_datas, rng_key,
                                               list(arg_datas))
                return out_datas, new_states, None

            # call arguments the block says it consumes and returns:
            # positions of the call -> positions among fwd's arguments
            traced_at = [i for i, a in enumerate(static_args)
                         if a is _TRACED]
            donate = tuple(3 + traced_at.index(i)
                           for i in getattr(block, "donate_args", ()))
            if donate_states:
                donate = (1,) + donate
            fwd_jit = jax.jit(fwd, donate_argnums=donate,
                              compiler_options=self._compiler_options)

        def bwd(vjp, out_cts, state_shapes_dtypes):
            import jax.numpy as jnp

            zero_states = [None if sd is None else jnp.zeros(*sd)
                           for sd in state_shapes_dtypes]
            grads = vjp((list(out_cts), zero_states))
            return grads  # (param_grads_tuple, *diff_arg_grads)

        bwd_jit = jax.jit(bwd, static_argnums=(2,))
        return {
            "fwd": fwd_jit,
            "bwd": bwd_jit,
            "out_tree": out_tree_box,
            "diff_arg_idx": diff_arg_idx,
        }

    @staticmethod
    def _sig_of(datas):
        return tuple((tuple(d.shape), str(d.dtype)) for d in datas)

    def _take_snapshot(self):
        """Walk the block and read every parameter's buffer: the per-call
        work of old, now done when :meth:`_params` finds no snapshot that
        stands. A hook so the thread-safe subclass can exclude this read
        from trace windows (an active trace rebinds the SHARED Parameter
        NDArrays to tracers; a concurrent reader would leak them into its
        own jit)."""
        train, state = self._split_params()
        handles = [p.data() for p in train + state]
        # versions before buffers: a rebind in between must read as stale
        versions = tuple([h._version for h in handles])
        return _ParamSnapshot(len(train), handles, versions,
                              tuple(h._data for h in handles))

    def _run_fwd(self, entry, snap, rng_key, arg_datas):
        """Call the entry's executable (a hook for the thread-safe
        subclass, whose first call of a jax signature holds a lock)."""
        return entry["fwd"](snap.tp_datas, snap.st_datas, rng_key, *arg_datas)

    # -- call -------------------------------------------------------------
    def __call__(self, *args):
        args = list(args)
        # NDArrays (and raw arrays) become traced inputs; None/bools/ints and
        # other non-array values are static and baked into the cache key —
        # the role op attrs play in the reference's CachedOp signature
        arg_datas = []
        traced_args = []
        static_template = []
        for a in args:
            if isinstance(a, NDArray):
                arg_datas.append(a._data)
                traced_args.append(a)
                static_template.append(_TRACED)
            elif hasattr(a, "shape") and hasattr(a, "dtype"):
                nd = NDArray(a)
                arg_datas.append(nd._data)
                traced_args.append(nd)
                static_template.append(_TRACED)
            elif (isinstance(a, (list, tuple)) and a
                  and all(isinstance(e, (bool, int, float)) for e in a)):
                # numeric sequence: array-convert (pre-static-args behavior,
                # e.g. net([1.0, 2.0]))
                nd = NDArray(a)
                arg_datas.append(nd._data)
                traced_args.append(nd)
                static_template.append(_TRACED)
            else:
                try:
                    hash(a)
                except TypeError:
                    raise MXNetError(
                        f"hybridized call got unhashable non-array argument "
                        f"of type {type(a).__name__}; pass NDArrays or "
                        f"hashable static values") from None
                static_template.append(a)
        static_args = tuple(static_template)

        grad_mode = autograd.is_recording()
        args_tracked = tuple(
            _tracked(a) for a in traced_args
        ) if grad_mode else tuple(False for _ in traced_args)

        snap, fresh = self._params(grad_mode)
        key = (self._sig_of(arg_datas), snap.train_sig, snap.state_sig,
               autograd.is_training(), grad_mode, args_tracked, static_args)
        entry = self._cache.get(key)
        if entry is None:
            entry = self._lookup_or_build(key, grad_mode, args_tracked,
                                          static_args)
        else:
            self._hits += 1
            if not fresh:
                self._fast_calls += 1

        # an entry whose trace drew no key is handed the key of its first
        # call again (the key stays an argument: the HLO does not change)
        # and the stream's counters advance as if one had been made, so
        # every later seeded draw in the process is what it was
        rng_key = entry.get("free_key")
        drew = rng_key is None
        if drew:
            rng_key = _rng.next_key()
        else:
            _rng.skip_key()
            self._keys_skipped += 1

        t0 = _prof.begin() if _prof.ENABLED else 0
        out_datas, new_states, vjp = self._run_fwd(entry, snap, rng_key,
                                                   arg_datas)
        if t0:
            # host-side dispatch window (XLA executes async; device time
            # comes from profiler.device_op_stats)
            _prof.record_duration(
                f"CachedOp::forward({type(self.block).__name__})",
                "cachedop", t0)

        if (drew and entry["out_tree"].get("draws") == 0
                and not _rng.in_trace()):  # an outer trace's key is a tracer
            entry["free_key"] = rng_key
        self._write_back_state(snap.state_handles, new_states)

        wrapped = [NDArray(d) for d in out_datas]

        if grad_mode and vjp is not None:
            state_sd = tuple(
                None if s is None else (tuple(s.shape), str(s.dtype))
                for s in new_states)
            bwd_jit = entry["bwd"]
            diff_arg_idx = entry["diff_arg_idx"]

            def vjp_fn(cts):
                if not isinstance(cts, tuple):
                    cts = (cts,)
                grads = bwd_jit(vjp, tuple(cts), state_sd)
                param_grads = grads[0]
                arg_grads = grads[1:]
                return tuple(param_grads) + tuple(arg_grads)

            in_slots = [_slot_of(h) for h in snap.train_handles]
            in_slots += [_slot_of(traced_args[i]) for i in diff_arg_idx]
            node = autograd.TapeNode(
                vjp_fn,
                in_slots,
                [(tuple(d.shape), d.dtype) for d in out_datas],
                name=f"CachedOp({type(self.block).__name__})",
            )
            for i, w in enumerate(wrapped):
                w._tape = (node, i)

        tree = entry["out_tree"].get("tree")
        if tree is None:
            return wrapped[0] if len(wrapped) == 1 else tuple(wrapped)
        import jax

        return jax.tree_util.tree_unflatten(tree, wrapped)


# CachedOp -> its _ParamSnapshot (weak keys: the store never pins an
# executor, nor a dead one's weights)
_snapshots = weakref.WeakKeyDictionary()


def params_changed():
    """Throw every CachedOp's parameter snapshot away. ``gluon`` calls it
    AFTER any change to what ``block.collect_params()`` or
    ``Parameter.data()`` resolve to (a parameter initialised, moved, cast,
    loaded, its ``grad_req`` set; a child or parameter registered on a
    Block). The whole store is replaced, and a call takes hold of the store
    before it reads a parameter, so a snapshot derived while a change was
    under way lands in a store nobody reads. Also frees, at once, the
    buffers the snapshots held. A buffer rebound through its NDArray handle
    (an optimizer step, a written-back state) needs no call: the handle's
    ``_version`` says it."""
    global _snapshots
    _snapshots = weakref.WeakKeyDictionary()


def _weak_of(datas):
    """The positions among ``datas`` whose jax type is weak."""
    return tuple(i for i, d in enumerate(datas)
                 if getattr(d, "weak_type", False))


class _ParamSnapshot:
    """What a call needs of the block's parameters, a function of the
    model alone and so derived once, not per call: the train/state split,
    the handles ``Parameter.data()`` resolved to, their buffers, the
    parameter half of the signature key. It stands until
    :func:`params_changed` throws it away or a handle is rebound. A
    recording call leaves none behind: its weights are about to be
    stepped, and the snapshot would keep the old buffers alive beside the
    new ones. Nor does a call inside a ``replica_context``, where
    ``Parameter.data()`` resolves by the thread's scope."""

    __slots__ = ("handles", "versions", "train_handles", "state_handles",
                 "tp_datas", "st_datas", "train_sig", "state_sig", "weak",
                 "plain")

    def __init__(self, n, handles, versions, datas):
        """``n``: how many of ``handles`` (and ``datas``) are trainable;
        the rest are state."""
        self.handles, self.versions = handles, versions
        self.train_handles, self.state_handles = handles[:n], handles[n:]
        self.tp_datas, self.st_datas = datas[:n], datas[n:]
        self.train_sig = CachedOp._sig_of(self.tp_datas)
        self.state_sig = CachedOp._sig_of(self.st_datas)
        self.weak = _weak_of(datas)
        # reading a view resyncs it and moves its version: never kept
        self.plain = all(getattr(h, "_view_parent", None) is None
                         for h in handles)

    def stands(self):
        return tuple([h._version for h in self.handles]) == self.versions


class CachedOpThreadSafe(CachedOp):
    """Lock-protected CachedOp for multi-threaded inference.

    Reference: ``src/imperative/cached_op_threadsafe.h:82`` — the C-predict
    path serializes graph creation and state write-back behind a mutex so
    concurrent threads can share one executor. Here the jit executables are
    themselves thread-safe, so only those two sections lock: cache-hit
    calls execute concurrently.
    """

    # ONE process-wide trace lock. A first-call jit trace rebinds the
    # SHARED Parameter NDArrays to tracers (_ParamBinding), so a
    # concurrent param read from ANY op over the same block — not just
    # this instance — leaks them (e.g. a live ContinuousEngine decode
    # thread plus a fresh Generator tracing its first signature on the
    # same model). Per-instance locks only close the same-op race, so
    # trace windows and param snapshots serialize on this class lock;
    # warm known-signature calls stay lock-free.
    _TRACE_LOCK = threading.RLock()

    def __init__(self, block, static_alloc=False, static_shape=False,
                 flags=(), compiler_options=None):
        super().__init__(block, static_alloc=static_alloc,
                         static_shape=static_shape, flags=flags,
                         compiler_options=compiler_options)
        self._lock = threading.RLock()

    def record_serve_hit(self, n=1):
        with self._lock:  # += is not atomic; concurrent flushers race
            super().record_serve_hit(n)

    def _lookup_or_build(self, key, grad_mode, args_tracked, static_args):
        entry = self._cache.get(key)
        if entry is not None:
            self._hits += 1
            return entry
        with self._lock:  # double-checked: one thread traces/compiles
            entry = self._cache.get(key)
            if entry is None:
                entry = super()._lookup_or_build(
                    key, grad_mode, args_tracked, static_args)
            else:
                # raced build won while we waited: still a cache hit for
                # cache_stats accounting
                self._hits += 1
            return entry

    def _run_fwd(self, entry, snap, rng_key, arg_datas):
        """jax.jit traces on FIRST INVOCATION PER JAX SIGNATURE, and the
        trace rebinds the shared Parameter NDArrays to tracers
        (_ParamBinding); a concurrent p.data() read would leak them (the
        round-4 cold-start probe: 4 unwarmed threads ->
        UnexpectedTracerError). Any call whose jax-level signature hasn't
        completed yet holds the process-wide ``_TRACE_LOCK`` (the
        rebinding hits every op that shares the params, not just this
        one); known-signature calls run lock-free. The entry's key already
        says every shape and dtype; what it does NOT capture is weak_type
        (jnp scalars are weak), so that is all a call adds: the explicit
        arguments' here, the parameters' from the snapshot."""
        sig = (snap.weak, _weak_of(arg_datas))
        traced = entry.get("traced")
        if traced is None:
            traced = entry.setdefault("traced", set())
        if sig in traced:
            return super()._run_fwd(entry, snap, rng_key, arg_datas)
        with CachedOpThreadSafe._TRACE_LOCK:
            out = super()._run_fwd(entry, snap, rng_key, arg_datas)
            traced.add(sig)
            return out

    def _take_snapshot(self):
        # excluded from trace windows: the class trace lock is held by
        # any in-flight first-call trace of ANY op over these params
        with CachedOpThreadSafe._TRACE_LOCK:
            return super()._take_snapshot()

    def _write_back_state(self, state_handles, new_states):
        if any(ns is not None for ns in new_states):
            with self._lock:
                super()._write_back_state(state_handles, new_states)
