"""Gluon Parameter / ParameterDict.

Reference: ``python/mxnet/gluon/parameter.py`` (759 LoC: deferred init,
``grad_req``, per-context replicas, ``row_sparse`` params).

TPU redesign notes:
  * a Parameter's payload is one NDArray per Context — but on TPU the
    multi-device story is a *single sharded* ``jax.Array`` over a mesh
    (SURVEY.md §2.3), so multi-context replica lists exist for API parity
    (``list_data``) while ``shard_spec`` + ``mxnet_tpu.parallel`` provide the
    native path.
  * gradients attach through the autograd tape (``mark_variables``), exactly
    the reference contract (``Parameter._init_grad`` →
    ``autograd.mark_variables``, reference ``parameter.py``).
"""
from __future__ import annotations

from collections import OrderedDict

import numpy as _onp

from .. import autograd, initializer as _init_mod
from ..base import MXNetError
from ..cachedop import _trace_state, params_changed
from ..device import Context, cpu, current_context
from ..ndarray.ndarray import NDArray


class DeferredInitializationError(MXNetError):
    """Parameter accessed before its shape is fully known."""


class replica_context:
    """``with replica_context(ctx):`` — within the scope, ``p.data()`` /
    ``p.grad()`` with no explicit context resolve to the replica on
    ``ctx`` (when the parameter has one) instead of the first replica.

    This is the reference's per-device forward convention (classic gluon
    blocks call ``param.data(x.context)``) expressed as a scope, so every
    existing ``p.data()`` call site — Dense/Conv forwards, the v1
    ``hybrid_forward`` binding — becomes replica-aware without threading
    a context argument through each one. The elastic data-parallel batch
    processor (``resilience.elastic``) wraps each per-replica
    forward/backward in one. Zero cost outside a scope beyond a
    thread-local attribute probe; parameters without a replica on ``ctx``
    fall back to their first replica unchanged."""

    def __init__(self, ctx):
        self._ctx = ctx
        self._prev = None

    def __enter__(self):
        # on CachedOp's thread-local: its parameter snapshot holds what
        # ``data()`` resolves to outside any scope, and has to see a scope
        # to step aside for it
        self._prev = getattr(_trace_state, "replica_ctx", None)
        _trace_state.replica_ctx = self._ctx
        return self._ctx

    def __exit__(self, *exc):
        _trace_state.replica_ctx = self._prev
        return False


def _active_replica_ctx():
    return getattr(_trace_state, "replica_ctx", None)


def _shape_complete(shape):
    return shape is not None and all(isinstance(s, int) and s > 0 for s in shape)


class Parameter:
    """A weight/state tensor of a Block."""

    def __init__(self, name="param", grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=True,
                 differentiable=True, stype="default", grad_stype="default"):
        self._name = name
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = _onp.dtype(dtype) if dtype is not None else None
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        if not differentiable:
            grad_req = "null"
        self._grad_req = grad_req
        self._stype = stype
        self._grad_stype = grad_stype
        self._ctx_list = None
        self._data = None  # OrderedDict[Context, NDArray]
        self._grad = None  # OrderedDict[Context, NDArray]
        self._deferred_init = None  # (init, ctx_list, default_init)
        self.shard_spec = None  # optional jax PartitionSpec for mesh sharding
        self._structure = None  # (block, attr-name) backref set by Block

    # -- identity ---------------------------------------------------------
    @property
    def name(self):
        return self._name

    def __repr__(self):
        return f"Parameter {self._name} (shape={self._shape}, dtype={self.dtype})"

    # -- shape ------------------------------------------------------------
    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if new_shape is None:
            return
        if self._shape is not None:
            if len(self._shape) != len(new_shape):
                raise MXNetError(
                    f"{self._name}: cannot change ndim {self._shape}->{new_shape}")
            merged = []
            for old, new in zip(self._shape, new_shape):
                if old and old > 0 and new and new > 0 and old != new:
                    raise MXNetError(
                        f"{self._name}: inconsistent shape {self._shape} vs {new_shape}")
                merged.append(old if (old and old > 0) else new)
            self._shape = tuple(merged)
        else:
            self._shape = tuple(new_shape)
        if _shape_complete(self._shape) and self._deferred_init is not None:
            self._finish_deferred_init()

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        if req not in ("write", "add", "null"):
            raise MXNetError(f"invalid grad_req {req!r}")
        if not self._differentiable:
            req = "null"
        if self._grad_req == req:
            return
        self._grad_req = req
        if req == "null":
            self._grad = None
            if self._data:
                for arr in self._data.values():
                    arr._leaf = None
        elif self._data is not None:
            self._init_grad()
        params_changed()  # the train/state split of every CachedOp over it

    # -- initialization ---------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None, force_reinit=False):
        if getattr(self, "_abstract_placeholder", False):
            # placeholder installed by functionalize_abstract (compile-only
            # proofs): silently "already initialized" would leave 0-element
            # weights in play — a real init must be explicit
            if not force_reinit:
                raise MXNetError(
                    f"Parameter {self._name} holds an abstract (compile-only)"
                    " placeholder from functionalize_abstract; pass "
                    "force_reinit=True to materialize real weights")
            self._abstract_placeholder = False
            self._data = None
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        self._ctx_list = list(ctx)
        if init is None:
            init = self.init if self.init is not None else (default_init or _init_mod.Uniform())
        if not _shape_complete(self._shape):
            if not self.allow_deferred_init:
                raise MXNetError(
                    f"{self._name}: shape {self._shape} incomplete and deferred "
                    "init not allowed")
            self._deferred_init = (init, list(ctx))
            return
        self._init_impl(init, ctx)

    def _init_impl(self, init, ctx_list):
        import jax

        initializer = _init_mod.create(init) if not isinstance(init, _init_mod.Initializer) else init
        # materialize once on host-side default device, then replicate
        proto = NDArray(_onp.zeros(self._shape, self.dtype))
        initializer(self._name, proto)
        self._data = OrderedDict()
        for ctx in ctx_list:
            data = jax.device_put(proto._data, ctx.jax_device())
            self._data[ctx] = NDArray(data)
        self._deferred_init = None
        if self._grad_req != "null":
            self._init_grad()
        params_changed()  # new handles (a deferred init finishes here too)

    def _finish_deferred_init(self):
        if self._deferred_init is None:
            return
        init, ctx_list = self._deferred_init
        self._init_impl(init, ctx_list)

    def _init_grad(self):
        import jax.numpy as jnp

        self._grad = OrderedDict()
        for ctx, data in self._data.items():
            import jax

            if self._grad_stype == "row_sparse":
                # O(nnz) gradient buffer: starts with zero stored rows;
                # each backward adopts the produced (indices, values)
                # without ever materializing the (vocab, dim) dense grad
                from ..ndarray.sparse import RowSparseNDArray

                g = RowSparseNDArray(
                    NDArray(jnp.zeros((0,) + data.shape[1:], data.dtype)),
                    NDArray(jnp.zeros((0,), jnp.int64)), data.shape)
            else:
                g = NDArray(jax.device_put(jnp.zeros(data.shape, data.dtype),
                                           ctx.jax_device()))
            self._grad[ctx] = g
            autograd.mark_variables([data], [g], self._grad_req)

    # -- access -----------------------------------------------------------
    def _check_initialized(self, ctx=None):
        if self._data is None:
            if self._deferred_init is not None:
                raise DeferredInitializationError(
                    f"Parameter {self._name} has not been initialized yet: "
                    "shape is incomplete (deferred init pending first forward)")
            raise MXNetError(
                f"Parameter {self._name} has not been initialized. "
                "Call .initialize() on the Block first")
        if ctx is not None and ctx not in self._data:
            raise MXNetError(
                f"Parameter {self._name} was not initialized on {ctx}; "
                f"it lives on {list(self._data)}")

    def data(self, ctx=None):
        if getattr(self, "_abstract_placeholder", False):
            from ..cachedop import in_trace

            # inside a functionalized trace the slot is rebound to the
            # trace's tracer (that is its whole job); anywhere else the
            # 0-element placeholder must not masquerade as weights
            if not in_trace():
                raise MXNetError(
                    f"Parameter {self._name} belongs to an abstract "
                    "(compile-only) functionalization and has no real "
                    "data; re-initialize with force_reinit=True to train")
        self._check_initialized(ctx)
        if ctx is None:
            act = _active_replica_ctx()
            if act is not None and act in self._data:
                return self._data[act]
            return next(iter(self._data.values()))
        return self._data[ctx]

    def list_data(self):
        self._check_initialized()
        return list(self._data.values())

    def grad(self, ctx=None):
        if self._grad is None:
            raise MXNetError(
                f"Parameter {self._name} has no gradient (grad_req={self._grad_req!r})")
        if ctx is None:
            act = _active_replica_ctx()
            if act is not None and act in self._grad:
                return self._grad[act]
            return next(iter(self._grad.values()))
        return self._grad[ctx]

    def list_grad(self):
        if self._grad is None:
            raise MXNetError(f"Parameter {self._name} has no gradient")
        return list(self._grad.values())

    def list_ctx(self):
        if self._data is None:
            if self._deferred_init is not None:
                return self._deferred_init[1]
            raise MXNetError(f"Parameter {self._name} not initialized")
        return list(self._data)

    def set_data(self, data):
        """Overwrite the value on every context (reference ``set_data``)."""
        import jax

        # real data cures an abstract (compile-only) placeholder
        self._abstract_placeholder = False
        self.shape = tuple(data.shape)
        if self._data is None:
            if self._deferred_init is not None:
                self._finish_deferred_init()
            else:
                self._ctx_list = [current_context()]
                self._data = OrderedDict({self._ctx_list[0]: NDArray(_onp.zeros(data.shape, self.dtype))})
                if self._grad_req != "null":
                    self._init_grad()
        src = data._data if isinstance(data, NDArray) else None
        for ctx, arr in self._data.items():
            val = src if src is not None else _onp.asarray(data)
            arr._set_data_internal(
                jax.device_put(val.astype(arr.dtype) if val.dtype != arr.dtype else val,
                               ctx.jax_device()),
                keep_tape=False)
        # the handles' versions say it too, but only at a CachedOp's next
        # call: until then its snapshot would keep the old buffers alive
        # beside the new ones, a whole model loaded twice
        params_changed()

    def zero_grad(self):
        if self._grad is None:
            return
        import jax.numpy as jnp

        from ..ndarray.sparse import RowSparseNDArray

        for g in self._grad.values():
            if isinstance(g, RowSparseNDArray):
                # reset to zero stored rows — never a (vocab, dim) dense
                g._set_sparse(RowSparseNDArray(
                    NDArray(jnp.zeros((0,) + g.shape[1:], g.dtype)),
                    NDArray(jnp.zeros((0,), jnp.int64)), g.shape))
            else:
                g._set_data_internal(jnp.zeros(g.shape, g.dtype))

    def reset_ctx(self, ctx):
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data is not None:
            import jax

            proto = next(iter(self._data.values()))
            self._data = OrderedDict(
                (c, NDArray(jax.device_put(proto._data, c.jax_device()))) for c in ctx)
            self._ctx_list = list(ctx)
            if self._grad_req != "null":
                self._init_grad()
            params_changed()
        elif self._deferred_init is not None:
            init, _ = self._deferred_init
            self._deferred_init = (init, list(ctx))

    def cast(self, dtype):
        self.dtype = _onp.dtype(dtype)
        if self._data is None:
            return
        for arr in self._data.values():
            arr._set_data_internal(arr._data.astype(dtype))
        if self._grad is not None:
            for ctx, g in self._grad.items():
                g._set_data_internal(g._data.astype(dtype))
                autograd.mark_variables([self._data[ctx]], [g], self._grad_req)
        params_changed()

    # row_sparse API parity ------------------------------------------------
    def row_sparse_data(self, row_id):
        if self._stype != "row_sparse":
            raise MXNetError(f"Parameter {self._name} is not row_sparse")
        return self.data().tostype("row_sparse").retain(row_id)

    def var(self):  # legacy symbol API surface
        from ..symbol import var

        return var(self._name, shape=self._shape, dtype=self.dtype)


class Constant(Parameter):
    """Non-differentiable constant parameter (reference ``gluon.Constant``)."""

    def __init__(self, value, name="const"):
        if not isinstance(value, NDArray):
            value = NDArray(_onp.asarray(value))
        super().__init__(name=name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=_init_mod.Constant(value),
                         differentiable=False)
        self._value = value


class ParameterDict(OrderedDict):
    """Dict of name->Parameter with batched ops (reference ParameterDict)."""

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):  # pylint: disable=unused-argument
        for p in self.values():
            p.initialize(init=None, ctx=ctx, default_init=init, force_reinit=force_reinit)

    def zero_grad(self):
        for p in self.values():
            p.zero_grad()

    def reset_ctx(self, ctx):
        for p in self.values():
            p.reset_ctx(ctx)

    def setattr(self, name, value):
        for p in self.values():
            setattr(p, name, value)

    def save(self, fname, strip_prefix=""):
        from ..ndarray.utils import save as nd_save

        arg = {}
        for name, p in self.items():
            if strip_prefix and name.startswith(strip_prefix):
                name = name[len(strip_prefix):]
            arg[name] = p.data()
        nd_save(fname, arg)

    def load(self, fname, ctx=None, allow_missing=False, ignore_extra=False,
             restore_prefix=""):
        from ..ndarray.utils import load as nd_load

        loaded = nd_load(fname)
        if restore_prefix:
            loaded = {restore_prefix + k: v for k, v in loaded.items()}
        for name, p in self.items():
            if name in loaded:
                p.set_data(loaded[name])
            elif not allow_missing:
                raise MXNetError(f"parameter {name} missing in file {fname}")
        if not ignore_extra:
            extra = set(loaded) - set(self)
            if extra:
                raise MXNetError(f"file {fname} has extra parameters {sorted(extra)}")
