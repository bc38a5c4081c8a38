"""Operator dispatch: the TPU analog of the imperative invoke path.

Reference call stack (SURVEY.md §3.1): Python op → FFI → ``Imperative::Invoke``
→ shape/type inference → ``PushFCompute`` closure → engine → kernel.

TPU call stack: Python op → :func:`apply` → (optionally ``jax.vjp`` for
autograd) → XLA async dispatch. Shape/dtype inference, memory planning and
kernel selection are XLA's job; what remains here is (a) unwrap/wrap of the
mutable NDArray handles, (b) tape recording, (c) the NaiveEngine sync hook.

Ops are plain JAX-traceable functions. :func:`register` places them in a
global table by name — the analog of ``NNVM_REGISTER_OP`` — which the
``mx.np``/``mx.npx``/``mx.nd`` namespace generators read at import, the way
the reference synthesizes its Python op modules from the C registry
(``python/mxnet/ndarray/register.py:115-265``).
"""
from __future__ import annotations

import os
from typing import Callable, Dict, Optional

from .. import autograd, engine
from ..base import MXNetError

# global op table: name -> Op
_OPS: Dict[str, "Op"] = {}

# telemetry hot-state (mxnet_tpu.profiler.core), installed by the first
# profiler.set_state('run') and never imported on the dispatch path: a
# session that never profiles pays exactly one `is None` test per apply()
_PROF = None

# fault-injection hot-state (mxnet_tpu.resilience.faults.FaultPlan),
# installed by faults.install_plan() the same way: one `is None` test per
# apply() when no plan is active
_FAULTS = None

# ---------------------------------------------------------------------------
# Eager per-op jit cache (SURVEY.md §7 hard part 2)
#
# The reference keeps eager dispatch cheap by caching shape/dtype inference
# per op signature (`SetShapeType`, `src/imperative/imperative.cc:117`). The
# TPU analog: cache a `jax.jit` of the op callable keyed on everything
# static — the function's code + closure values, non-array args, kwargs —
# and let jit's own signature cache handle shapes/dtypes. One compiled
# executable per (op, static config) replaces a fresh trace through op
# Python + per-primitive dispatch on every imperative call.
# ---------------------------------------------------------------------------

_EAGER_JIT_CACHE: Dict[tuple, Callable] = {}
_EAGER_BWD_CACHE: Dict[tuple, Callable] = {}  # same keys: compiled vjp
_EAGER_JIT_SKIP = set()  # keys whose trace consumed RNG: never cache
_KEPT_CALLABLES: Dict[int, Callable] = {}  # id-keyed pins (see _static_key)
_EAGER_JIT_MAX = 4096  # runaway guard: clear rather than evict
_EAGER_JIT_CLEARS = 0  # how often the runaway guard wiped the cache
_eager_jit_enabled = os.environ.get("MXNET_EAGER_JIT_CACHE", "1") != "0"


def set_eager_jit(flag: bool) -> None:
    """Enable/disable the eager per-op jit cache (MXNET_EAGER_JIT_CACHE)."""
    global _eager_jit_enabled
    _eager_jit_enabled = bool(flag)


def eager_jit_cache_size() -> int:
    return len(_EAGER_JIT_CACHE)


def cache_stats():
    """Eager jit-cache telemetry (the registry analog of
    ``CachedOp.cache_stats()``): entry counts, RNG-skip count, and how
    often the runaway guard cleared everything — a nonzero ``clears``
    rate in a steady-state loop means static keys are churning (cache
    thrash) and the clear is silently re-paying compile cost."""
    return {"size": len(_EAGER_JIT_CACHE),
            "bwd_size": len(_EAGER_BWD_CACHE),
            "skips": len(_EAGER_JIT_SKIP),
            "clears": _EAGER_JIT_CLEARS,
            "limit": _EAGER_JIT_MAX}


def _note_cache_clear(count):
    """Account (and rate-limitedly warn about) a runaway-guard clear of
    the eager jit cache — previously silent, so cache-thrash regressions
    in BENCH rounds were unattributable."""
    prof = _PROF
    if prof is not None:
        prof.set_counter("registry.eager_jit_clears", count, cat="registry")
    if count == 1 or count % 10 == 0:
        import warnings

        warnings.warn(
            f"eager jit cache hit its {_EAGER_JIT_MAX}-entry runaway "
            f"guard and was cleared (clear #{count}); something is "
            f"generating unbounded distinct op signatures (varying "
            f"shapes/static args) and re-paying compiles — see "
            f"registry.cache_stats()", RuntimeWarning, stacklevel=3)


def _static_key(v, depth=0):
    """Hashable identity of a static value; TypeError means 'don't cache'.

    Functions key on (code object, closure values) so the per-call inner
    closures in ops/nn.py (same code, different stride/pad cells) cache
    correctly instead of colliding or leaking.
    """
    if depth > 6:
        raise TypeError("static key too deep")
    if v is None or isinstance(v, (str, bytes, type)):
        return v
    if isinstance(v, (bool, int, float, complex)):
        # type-tagged: True==1==1.0 and 0.0==-0.0 hash-collide, but pick
        # different weak-type/sign behavior under jax — must not share a key
        return (type(v).__name__, repr(v))
    if isinstance(v, (tuple, list)):
        return (type(v).__name__,) + tuple(
            _static_key(x, depth + 1) for x in v)
    if isinstance(v, slice):
        # unhashable before Python 3.12 — without this branch every basic
        # __getitem__/__setitem__ closure (jkey) is uncacheable
        return ("slice", _static_key(v.start, depth + 1),
                _static_key(v.stop, depth + 1),
                _static_key(v.step, depth + 1))
    if isinstance(v, dict):
        return tuple(sorted(
            (k, _static_key(x, depth + 1)) for k, x in v.items()))
    import types

    if isinstance(v, types.ModuleType):
        return ("module", v.__name__)
    if isinstance(v, types.MethodType):
        # bound method: the receiver is part of the identity — two
        # instances sharing a class must not share a cache entry
        return ("method", v.__func__.__code__,
                _static_key(v.__self__, depth + 1))
    if callable(v) and hasattr(v, "__code__"):
        return (v.__code__,) + tuple(
            _static_key(c.cell_contents, depth + 1)
            for c in (v.__closure__ or ()))
    if callable(v):
        # opaque long-lived callables (jnp ufunc / PjitFunction objects):
        # key by identity, pinning a reference so the id is never reused
        _KEPT_CALLABLES.setdefault(id(v), v)
        return ("callable", type(v).__name__, id(v))
    import numpy as _onp

    if isinstance(v, _onp.dtype) or (isinstance(v, type(_onp.float32))):
        return str(v)
    if isinstance(v, _onp.ndarray) or hasattr(v, "__jax_array__") or \
            hasattr(v, "_data"):
        raise TypeError(f"array-valued static arg {type(v).__name__}")
    try:
        hash(v)
    except TypeError:
        raise TypeError(
            f"unhashable static arg {type(v).__name__}") from None
    # value-hashable objects (PyTreeDef, dtypes, enums) key directly; the
    # cache tuple keeps `v` alive, so id-hashed objects can't be recycled
    # into false hits
    return v


class Op:
    """A registered operator.

    ``wrapper=False`` (default): ``fn`` is a raw JAX-traceable callable and
    calls dispatch through :func:`apply`. ``wrapper=True``: ``fn`` is a
    public NDArray-level function that does its own dispatch (the ops in
    ``ops/nn.py``) and is invoked directly — routing it through ``apply``
    again would nest dispatch and leak NDArrays into jax.vjp.
    """

    __slots__ = ("name", "fn", "wrapper", "doc")

    def __init__(self, name: str, fn: Callable, wrapper=False, doc=""):
        self.name = name
        self.fn = fn
        self.wrapper = wrapper
        self.doc = doc or fn.__doc__

    def __call__(self, *args, **kwargs):
        if self.wrapper:
            return self.fn(*args, **kwargs)
        return apply(self.fn, args, kwargs, name=self.name)


def register(name: str, fn: Optional[Callable] = None, **meta):
    """Register an op (decorator or direct). Analog of NNVM_REGISTER_OP."""
    if fn is None:
        def deco(f):
            _OPS[name] = Op(name, f, **meta)
            return f
        return deco
    _OPS[name] = Op(name, fn, **meta)
    return fn


def get(name: str) -> Op:
    try:
        return _OPS[name]
    except KeyError:
        raise MXNetError(f"operator {name!r} is not registered") from None


def list_ops():
    """All registered op names (``MXListAllOpNames`` analog)."""
    return sorted(_OPS)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


def _ndarray_cls():
    from ..ndarray.ndarray import NDArray

    return NDArray


def _make_cached_vjp(inner_fn, datas, key):
    """Tape-node backward as ONE compiled executable per op key.

    The naive eager tape stores the closure ``jax.vjp`` returns and calls
    it at backward time — which interprets the transposed jaxpr in Python,
    primitive by primitive, every step (measured ~120 ms of a ~145 ms
    eager LeNet step). Here backward is ``jit(cts, xs -> vjp(f, xs)(cts))``
    cached under the SAME static key as the forward executable:
    recompute-in-backward (the forward re-runs inside the compiled vjp, a
    remat the compiler fuses) in exchange for zero per-step retracing and
    no Python-held residuals.
    """

    def vjp_fn(cts):
        import jax

        bwd = _EAGER_BWD_CACHE.get(key)
        if bwd is None:
            def bwd_fn(cts_, xs):
                _, vjp = jax.vjp(inner_fn, *xs)
                out = vjp(cts_)
                # int/bool inputs get float0 cotangents, which jit cannot
                # return — drop them to None leaves (ignored by the walk)
                return tuple(
                    None if (hasattr(c, "dtype")
                             and c.dtype == jax.dtypes.float0) else c
                    for c in out)

            bwd = jax.jit(bwd_fn)
            _EAGER_BWD_CACHE[key] = bwd
        return bwd(cts, datas)

    return vjp_fn


def _op_static_key(fn, args, kwargs, arr_pos, static_key):
    """The (op, static config) identity used by the per-op jit cache.
    Raises TypeError for unhashable static config (array-valued kwargs
    etc.)."""
    if static_key is not None:
        return static_key
    pos_set = set(arr_pos)
    return (
        _static_key(fn),
        tuple(arr_pos),
        len(args),
        tuple(_static_key(a) for i, a in enumerate(args)
              if i not in pos_set),
        _static_key(kwargs),
    )


def apply(fn, args, kwargs=None, name="", record=True, sync_outputs=True,
          static_key=None, cacheable=True):
    """Invoke ``fn`` on a mix of NDArray / scalar / array args.

    NDArray positions become differentiable primal inputs; everything else is
    closed over as a constant. When autograd is recording and any NDArray
    input is tracked, forward runs under ``jax.vjp`` and a tape node is
    created (``Imperative::RecordOp`` analog).

    ``static_key`` — optional precomputed hashable identity of everything
    static about this call (op + config). When given, the eager jit cache
    uses it directly instead of walking ``fn``'s closure, which keeps the
    per-call overhead down on hot namespace ops.
    """
    import jax

    prof = _PROF
    if prof is not None and prof.IMPERATIVE:
        # opt-in per-op call counters (profile_imperative): the role of the
        # reference's imperative API events, without the always-on cost
        prof.count_op(name or getattr(fn, "__name__", "op"))

    NDArray = _ndarray_cls()
    kwargs = kwargs or {}
    arr_pos = [i for i, a in enumerate(args) if isinstance(a, NDArray)]
    arrays = [args[i] for i in arr_pos]

    flt = _FAULTS
    if flt is not None:
        # injected transient dispatch error (resilience.faults): raised
        # BEFORE any tape/cache mutation so a caller-level retry sees a
        # clean slate. No info payload — building one per dispatch would
        # cost more than the site check itself
        flt.check("op:dispatch")

    engine._count_dispatch()
    datas = tuple(a._data for a in arrays)

    if arr_pos and len(arr_pos) == len(args) and not kwargs:
        closed = fn
    else:
        template = list(args)

        def closed(*xs):
            for pos, x in zip(arr_pos, xs):
                template[pos] = x
            return fn(*template, **kwargs)

    cache_key = None
    cache_candidate = None
    rng_mark = 0
    jit_hit_key = None  # verified-cacheable op: fast fwd AND cached-vjp bwd
    if _eager_jit_enabled and cacheable:
        try:
            key = _op_static_key(fn, args, kwargs, arr_pos, static_key)
            if key not in _EAGER_JIT_SKIP:
                jitted = _EAGER_JIT_CACHE.get(key)
                if jitted is not None:
                    closed = jitted
                    jit_hit_key = key
                else:
                    from .. import random as _rng

                    # jit now, publish to the cache only after the call
                    # traced without drawing an RNG key (a cached trace
                    # would replay the same baked key forever)
                    rng_mark = _rng.consume_count()
                    cache_key = key
                    _uncached_closed = closed
                    cache_candidate = jax.jit(closed)
                    closed = cache_candidate
        except TypeError:
            pass  # unhashable static config (e.g. array-valued kwargs)

    from ..ndarray.ndarray import _tracked, _slot_of

    recording = (
        record
        and autograd.is_recording()
        and any(_tracked(a) for a in arrays)
    )
    was_list = False

    def normalized(*xs):
        # multi-output ops (split, qr, slogdet...) may return lists or
        # namedtuples; the tape's cotangent convention is plain tuples, so
        # normalize at the vjp boundary (remembering listness so the caller
        # sees the same container type with or without recording)
        nonlocal was_list
        r = closed(*xs)
        if isinstance(r, list):
            was_list = True
            return tuple(r)
        if isinstance(r, tuple) and hasattr(r, "_fields"):
            return tuple(r)
        return r

    try:
        if recording and jit_hit_key is not None:
            # verified-cacheable op (cache hit => its trace is RNG-free and
            # jit-compatible): run the compiled forward directly — no
            # per-call jax.vjp retrace — and defer backward to the cached
            # compiled vjp. First encounters and RNG ops keep the eager
            # jax.vjp path (an RNG op's backward replay would re-draw keys
            # and mismatch the forward's masks).
            outs = normalized(*datas)
            vjp_fn = _make_cached_vjp(normalized, datas, jit_hit_key)
        elif recording:
            outs, vjp_fn = jax.vjp(normalized, *datas)
        else:
            outs = normalized(*datas)
    except Exception:
        if cache_candidate is None:
            raise
        # maybe jit-specific (value-dependent Python: dynamic output
        # shapes, host reads) — retry eagerly; only a SUCCESSFUL retry
        # proves jit-incompatibility and justifies skipping the cache
        # forever (a plain user error must not poison the key)
        closed = _uncached_closed
        cache_candidate = None
        if recording:
            outs, vjp_fn = jax.vjp(normalized, *datas)
        else:
            outs = normalized(*datas)
        _EAGER_JIT_SKIP.add(cache_key)

    if cache_candidate is not None:
        from .. import random as _rng

        if _rng.consume_count() == rng_mark:
            if len(_EAGER_JIT_CACHE) >= _EAGER_JIT_MAX:
                global _EAGER_JIT_CLEARS

                _EAGER_JIT_CACHE.clear()
                _EAGER_BWD_CACHE.clear()
                _EAGER_JIT_CLEARS += 1
                _note_cache_clear(_EAGER_JIT_CLEARS)
            _EAGER_JIT_CACHE[cache_key] = cache_candidate
        else:
            _EAGER_JIT_SKIP.add(cache_key)

    single = not isinstance(outs, (tuple, list))
    flat = [outs] if single else list(outs)
    wrapped = [NDArray(o) for o in flat]

    if recording:
        if not single and len(flat) == 1:
            # the tape walk hands a bare leaf when there's one output, but
            # jax.vjp of a 1-tuple-returning fn wants a 1-tuple cotangent
            raw_vjp = vjp_fn
            vjp_fn = (lambda ct, _raw=raw_vjp:
                      _raw(ct if isinstance(ct, tuple) else (ct,)))
        node = autograd.TapeNode(
            vjp_fn,
            [_slot_of(a) for a in arrays],
            [(o.shape, o.dtype) for o in flat],
            name=name or getattr(fn, "__name__", "op"),
            # saved for create_graph=True: the backward walk re-linearizes
            # this op as a recorded op (higher-order autograd)
            fwd_fn=normalized,
            in_arrays=list(arrays),
        )
        # create_graph's replay must hand jax.vjp a cotangent matching the
        # forward's output structure: bare leaf vs 1-tuple
        node.out_container = not single
        for i, w in enumerate(wrapped):
            w._tape = (node, i)

    if sync_outputs:
        engine.maybe_sync(flat)
    if single:
        return wrapped[0]
    return list(wrapped) if was_list else type(outs)(wrapped)


def apply_out(fn, args, kwargs=None, out=None, name=""):
    """Like :func:`apply` but honoring an ``out=`` destination NDArray."""
    res = apply(fn, args, kwargs, name=name)
    if out is None:
        return res
    if isinstance(out, (tuple, list)):
        for o, r in zip(out, res):
            o._set_data_internal(r._data)
        return out
    out._set_data_internal(res._data)
    out._tape = getattr(res, "_tape", None)
    return out
