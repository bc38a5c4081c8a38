"""NDArray: the mutable n-dimensional array handle over ``jax.Array``.

TPU-native re-design of the reference NDArray (``include/mxnet/ndarray.h:82-399``,
``src/ndarray/``). The reference NDArray is a ref-counted storage ``Chunk``
plus an engine variable; mutation is serialized through the dependency engine
and tracked by ``Var::version_`` (``include/mxnet/engine.h:44-61``).

Here the underlying buffer is an immutable ``jax.Array``; *mutation rebinds*
the handle to a new buffer and bumps ``_version`` — the same observable
semantics (in-place ops, ``x[...] = y``, ``kvstore.pushpull(out=w)``) without
needing hazard tracking, because XLA's SSA dataflow orders everything exactly.
Async execution comes from XLA async dispatch; ``wait_to_read`` maps to
``block_until_ready`` (reference ``WaitToRead``, ``ndarray.h:346``).

Autograd wiring: ``_tape`` points at the producing tape node (the reference's
``autograd_entry_``), ``_leaf`` marks a differentiable variable
(``MarkVariables``, ``src/imperative/imperative.cc:134``).
"""
from __future__ import annotations

import numpy as _np

from .. import autograd, engine
from ..base import MXNetError
from ..device import Context, current_context, from_jax_device


def _jnp():
    import jax.numpy as jnp

    return jnp


def _tracked(a) -> bool:
    return isinstance(a, NDArray) and (
        getattr(a, "_tape", None) is not None
        or getattr(a, "_leaf", None) is not None)


def _slot_of(a):
    if not isinstance(a, NDArray):
        return None
    if getattr(a, "_leaf", None) is not None:
        return a._leaf
    return getattr(a, "_tape", None)


def _apply(fn, args, kwargs=None, name=""):
    from ..ops.registry import apply

    return apply(fn, args, kwargs, name=name)


def _to_jax(value, dtype=None, ctx: Context = None):
    """Convert arbitrary input to a jax.Array on ``ctx`` (default current)."""
    import jax

    if isinstance(value, NDArray):
        data = value._data
        if dtype is not None and data.dtype != _np.dtype(dtype):
            data = data.astype(dtype)
        if ctx is not None:
            data = jax.device_put(data, ctx.jax_device())
        return data
    host = _np.asarray(value, dtype=dtype)
    # default-dtype rule for python floats, float lists AND scalars alike
    # (asarray gives float64 for both): float32 unless set_np(dtype=True),
    # so mx.np.array(1.5) and mx.np.array([1.5]) always agree
    if host.dtype == _np.float64 and dtype is None:
        from ..base import _thread_state
        if not _thread_state.np_dtype:  # set_np(dtype=True) keeps float64
            host = host.astype(_np.float32)  # MXNet default is float32
    dev = (ctx or current_context()).jax_device()
    return jax.device_put(host, dev)


# per-function signature facts for __array_function__ kwarg screening:
# (has_varkw, parameter-name set)
_SIG_CACHE = {}


class NDArray:
    """Mutable array handle; also serves as ``mx.np.ndarray``."""

    __slots__ = ("_buf", "_tape", "_leaf", "_version", "_stype",
                 "_view_parent", "_view_key", "_view_pver", "__weakref__")

    # make NumPy defer binary-op dispatch to us (ndarray.py reference sets
    # __array_priority__ on mx.nd.NDArray similarly)
    __array_priority__ = 1000.0

    def __init__(self, data, ctx: Context = None, dtype=None, stype="default"):
        import jax

        # view linkage must exist before the first _data property access
        self._view_parent = None
        self._view_key = None
        self._view_pver = 0
        if isinstance(data, jax.Array):
            if dtype is not None and data.dtype != _np.dtype(dtype):
                data = data.astype(dtype)
            if ctx is not None:
                dev = ctx.jax_device()
                if dev not in data.devices():
                    data = jax.device_put(data, dev)
            self._data = data
        else:
            self._data = _to_jax(data, dtype=dtype, ctx=ctx)
        self._tape = None
        self._leaf = None
        self._version = 0
        self._stype = stype

    # -- jax interop ------------------------------------------------------
    def __jax_array__(self):
        """Let jax/jnp functions consume NDArray directly (no autograd)."""
        return self._data

    # -- buffer / view core -----------------------------------------------
    # Reference basic contiguous slicing and reshape return VIEWS that
    # share memory with the parent (``ndarray.py`` ``__getitem__``
    # "contiguous" examples, ``MXNDArrayReshape64``): writes through a
    # view appear in the parent and vice versa.  jax buffers are
    # immutable, so views are modeled as (parent, key) linkage with lazy
    # resync: reads refresh from the parent when its version moved, and
    # rebinds push the updated region back up the parent chain.
    @property
    def _data(self):
        p = getattr(self, "_view_parent", None)
        if p is not None:
            src = p._data  # refresh the whole parent chain first
            if self._view_pver != p._version:
                key = self._view_key
                if key is None:
                    self._buf = src.reshape(self._buf.shape)
                elif isinstance(key, tuple) and len(key) == 2 \
                        and key[0] == "flip":
                    self._buf = _jnp().flip(src, key[1])
                elif isinstance(key, tuple) and len(key) == 3 \
                        and key[0] == "sliceshape":
                    self._buf = src[key[1]].reshape(self._buf.shape)
                else:
                    self._buf = src[key]
                self._view_pver = p._version
                self._version += 1  # children of this view refresh too
        return self._buf

    @_data.setter
    def _data(self, v):
        self._buf = v

    # -- mutation core ----------------------------------------------------
    def _set_data_internal(self, new_data, keep_tape=False):
        """Rebind the buffer (engine Var version bump analog)."""
        self._data = new_data
        self._version += 1
        if not keep_tape:
            self._tape = None
        p = getattr(self, "_view_parent", None)
        if p is not None:
            key = self._view_key
            if key is None:  # reshape view: write the whole array back
                newp = new_data.reshape(p.shape).astype(p.dtype)
            elif isinstance(key, tuple) and len(key) == 2 \
                    and key[0] == "flip":  # self-inverse transform
                newp = _jnp().flip(new_data, key[1]).astype(p.dtype)
            elif isinstance(key, tuple) and len(key) == 3 \
                    and key[0] == "sliceshape":  # reshaped slice view
                newp = p._data.at[key[1]].set(
                    new_data.reshape(key[2]).astype(p.dtype))
            else:
                newp = p._data.at[key].set(new_data.astype(p.dtype))
            p._set_data_internal(newp, keep_tape=keep_tape)
            self._view_pver = p._version  # buffer already current

    # -- basic properties -------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @shape.setter
    def shape(self, new_shape):
        # numpy in-place reshape (``a.shape = (8, 3)``): same id, new view
        # of the same data
        if autograd.is_recording() and _tracked(self):
            # keep the tape connected: record a real reshape op, then
            # rebind (mirrors the recording branch of __setitem__)
            res = _apply(lambda x: x.reshape(new_shape), (self,),
                         name="reshape")
            self._set_data_internal(res._data, keep_tape=True)
            self._tape = res._tape
            return
        key = None if getattr(self, "_view_parent", None) is None \
            else self._view_key
        if isinstance(key, tuple) and key and key[0] == "flip":
            # reshaping a flip alias: materialize and detach (rare)
            self._buf = self._data
            self._view_parent = None
        elif key is not None and not (isinstance(key, tuple) and
                                      key and key[0] == "sliceshape"):
            # slice view: remember the slice's own shape so write-backs
            # can un-reshape into the parent slot
            self._view_key = ("sliceshape", key, self.shape)
        old = self._data
        self._buf = old.reshape(new_shape)
        self._version += 1

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def size(self):
        return int(self._data.size)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def itemsize(self):
        return self.dtype.itemsize

    @property
    def nbytes(self):
        return self.size * self.itemsize

    @property
    def stype(self):
        return self._stype

    @property
    def ctx(self) -> Context:
        devs = list(self._data.devices())
        if len(devs) > 1:
            # sharded array: report the mesh's first device's context
            devs.sort(key=lambda d: d.id)
        return from_jax_device(devs[0])

    context = ctx
    device = ctx

    @property
    def T(self):
        return self.transpose()

    @property
    def grad(self):
        leaf = self._leaf
        return leaf.grad_array if leaf is not None else None

    @property
    def is_sharded(self):
        return len(self._data.devices()) > 1

    @property
    def sharding(self):
        return self._data.sharding

    # -- sync / conversion ------------------------------------------------
    def wait_to_read(self):
        engine.wait_for_var(self._data)
        return self

    wait_to_write = wait_to_read

    def asnumpy(self) -> _np.ndarray:
        try:
            return _np.asarray(self._data)
        except Exception as e:  # surface async device errors MXNet-style
            raise MXNetError(f"async execution failed: {e}") from e

    def item(self):
        return self.asnumpy().item()

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("the array is not a scalar")
        # reference returns self.asnumpy()[0]: a NUMPY scalar whose type
        # carries the array dtype (``type(x.asscalar()) -> numpy.float32``)
        return self.asnumpy().reshape(())[()]

    def slice_assign_scalar(self, value, begin, end, step):
        """Assign ``value`` into the cropped region; mutates and returns
        self (reference ``ndarray.py slice_assign_scalar``)."""
        key = tuple(slice(b, e, s) for b, e, s in zip(begin, end, step))
        self[key] = value
        return self

    def slice_assign(self, rhs, begin, end, step):
        """Assign ``rhs`` into the cropped region; mutates and returns
        self (reference ``ndarray.py slice_assign``)."""
        key = tuple(slice(b, e, s) for b, e, s in zip(begin, end, step))
        self[key] = rhs
        return self

    def tolist(self):
        return self.asnumpy().tolist()

    def astype(self, dtype, copy=True):
        if not copy and self.dtype == _np.dtype(dtype):
            return self
        return _apply(lambda x: x.astype(dtype), (self,), name="astype")

    def copy(self):
        return _apply(lambda x: _jnp().copy(x), (self,), name="copy")

    def copyto(self, other):
        """Copy into another NDArray (write) or to a Context (new array)."""
        import jax

        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device()))
        if not isinstance(other, NDArray):
            raise MXNetError("copyto target must be NDArray or Context")
        data = self._data
        if data.dtype != other.dtype:
            data = data.astype(other.dtype)
        if data.shape != other.shape:
            raise MXNetError(
                f"copyto shape mismatch {data.shape} vs {other.shape}")
        dev = list(other._data.devices())[0]
        other._set_data_internal(jax.device_put(data, dev))
        return other

    def as_in_context(self, ctx: Context):
        if ctx == self.ctx:
            return self
        return self.copyto(ctx)

    as_in_ctx = as_in_context

    def to_device(self, device):
        return self.as_in_context(device)

    def as_np_ndarray(self):
        return self

    def as_nd_ndarray(self):
        return self

    def tostype(self, stype):
        if stype == "default":
            return self
        from .sparse import dense_to_sparse

        return dense_to_sparse(self, stype)

    def detach(self):
        out = NDArray(self._data)
        out._stype = self._stype
        return out

    # -- autograd ---------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):  # pylint: disable=unused-argument
        grad = NDArray(_jnp().zeros(self.shape, self.dtype))
        autograd.mark_variables([self], [grad], grad_req)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad], retain_graph, train_mode)

    def zero_grad(self):
        if self.grad is not None:
            g = self.grad
            g._set_data_internal(_jnp().zeros(g.shape, g.dtype))

    # -- indexing ---------------------------------------------------------
    @staticmethod
    def _prep_index(key):
        """Unwrap NDArray indices to jax arrays; pass through the rest.
        Python lists become integer/bool index arrays (the reference's
        advanced-indexing contract; jax itself rejects raw sequences)."""
        def conv(k):
            if isinstance(k, NDArray):
                return k._data
            if isinstance(k, list):
                return _np.asarray(k)
            return k

        if isinstance(key, tuple):
            return tuple(conv(k) for k in key)
        return conv(key)

    @staticmethod
    def _is_contiguous_basic(key, shape):
        """True when ``key`` selects a row-major-contiguous region the
        reference would hand out as a shared-memory view
        (``ndarray.py _basic_indexing`` contiguity check): leading
        integer indexes, then at most one partial step-1 slice, then
        only full slices.  Conservative — advanced keys never view."""
        if not isinstance(key, tuple):
            key = (key,)
        if any(k is None or k is Ellipsis for k in key):
            return False
        state = "ints"  # -> "tail" after the first (partial) slice
        for k, dim in zip(key, shape):
            if isinstance(k, (bool, _np.bool_)):
                return False  # bool scalar keys are ADVANCED indexing
            if isinstance(k, (int, _np.integer)):
                if state != "ints":
                    return False
            elif isinstance(k, slice):
                if k.step not in (None, 1):
                    return False
                if state == "tail":
                    start = k.start or 0
                    full = start == 0 and (k.stop is None or k.stop >= dim)
                    if not full:
                        return False
                else:
                    state = "tail"
            else:
                return False  # array/bool index: advanced indexing
        return True

    def __getitem__(self, key):
        jkey = self._prep_index(key)
        res = _apply(lambda x: x[jkey], (self,), name="getitem")
        if type(self) is NDArray and not autograd.is_recording() \
                and self._is_contiguous_basic(jkey, self.shape):
            res._view_parent = self
            res._view_key = jkey
            res._view_pver = self._version
        return res

    def __setitem__(self, key, value):
        jkey = self._prep_index(key)
        if isinstance(value, NDArray) and autograd.is_recording() and (
            _tracked(self) or _tracked(value)
        ):
            res = _apply(
                lambda x, v: x.at[jkey].set(v.astype(x.dtype)),
                (self, value),
                name="setitem",
            )
            self._set_data_internal(res._data, keep_tape=True)
            self._tape = res._tape
            return
        val = value._data if isinstance(value, NDArray) else value
        if hasattr(val, "astype") and getattr(val, "dtype", None) != self.dtype:
            val = val.astype(self.dtype)
        self._set_data_internal(self._data.at[jkey].set(val))

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # -- python protocol --------------------------------------------------
    def __bool__(self):
        if self.size != 1:
            raise ValueError(
                "The truth value of an array with more than one element is "
                "ambiguous")
        return bool(self.item())

    def __float__(self):
        return float(self.item())

    def __int__(self):
        return int(self.item())

    def __index__(self):
        if self.ndim != 0 or not _np.issubdtype(self.dtype, _np.integer):
            raise TypeError("only integer scalar arrays can be used as index")
        return int(self.item())

    def __hash__(self):
        return id(self)

    def __repr__(self):
        arr = self.asnumpy()
        body = _np.array2string(arr, separator=", ")
        ctx = self.ctx
        suffix = f", device={ctx}" if ctx.device_type != "cpu" else ""
        dt = f", dtype={self.dtype}" if self.dtype not in (_np.dtype("float32"),) else ""
        return f"array({body}{dt}{suffix})"

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def __array_function__(self, func, types, args, kwargs):
        """NumPy dispatch protocol (reference
        ``python/mxnet/numpy_dispatch_protocol.py``): ``numpy.<fn>(nd)``
        routes to the ``mx.np`` implementation when one exists — staying
        on-device and returning NDArray — else falls back to real numpy
        on host copies."""
        from .. import numpy as mnp

        ours = getattr(mnp, func.__name__, None)
        if ours is not None and callable(ours):
            # fall back to host numpy ONLY for kwargs our implementation
            # doesn't take (out=/where=/order=...), decided up front — a
            # blanket TypeError catch would silently recompute genuine
            # user errors on host and hand back a numpy array. Signature
            # facts are cached per function: this is a hot interop path.
            facts = _SIG_CACHE.get(ours)
            if facts is None:
                import inspect

                try:
                    sig = inspect.signature(ours)
                    has_varkw = any(
                        p.kind is inspect.Parameter.VAR_KEYWORD
                        for p in sig.parameters.values())
                    facts = (has_varkw, frozenset(sig.parameters))
                except (TypeError, ValueError):  # builtins w/o signatures
                    facts = (True, frozenset())
                _SIG_CACHE[ours] = facts
            has_varkw, param_names = facts
            unsupported = not has_varkw and any(
                k not in param_names for k in kwargs)
            if not unsupported:
                return ours(*args, **kwargs)
        host = [a.asnumpy() if isinstance(a, NDArray) else a for a in args]
        return func(*host, **kwargs)

    def __array_ufunc__(self, ufunc, method, *args, **kwargs):
        """NumPy ufunc protocol: same routing as __array_function__ for
        the plain-call case; other methods (reduce/accumulate/at) fall
        back to host numpy."""
        if method != "__call__" or kwargs.get("out") is not None:
            host = [a.asnumpy() if isinstance(a, NDArray) else a
                    for a in args]
            return getattr(ufunc, method)(*host, **kwargs)
        from .. import numpy as mnp

        ours = getattr(mnp, ufunc.__name__, None)
        if ours is not None and callable(ours):
            try:
                return ours(*args, **kwargs)
            except TypeError:
                pass
        host = [a.asnumpy() if isinstance(a, NDArray) else a for a in args]
        return ufunc(*host, **kwargs)

    def __dlpack__(self, stream=None):  # pylint: disable=unused-argument
        return self._data.__dlpack__()

    def __dlpack_device__(self):
        return self._data.__dlpack_device__()

    # -- arithmetic -------------------------------------------------------
    def _binop(self, other, fn, name, reverse=False):
        if isinstance(other, NDArray) or _np.isscalar(other) or isinstance(
            other, (_np.ndarray, list, tuple, bool, int, float)
        ):
            args = (other, self) if reverse else (self, other)
            return _apply(fn, args, name=name)
        return NotImplemented

    def __add__(self, o):
        return self._binop(o, _jnp().add, "add")

    def __radd__(self, o):
        return self._binop(o, _jnp().add, "add", reverse=True)

    def __sub__(self, o):
        return self._binop(o, _jnp().subtract, "subtract")

    def __rsub__(self, o):
        return self._binop(o, _jnp().subtract, "subtract", reverse=True)

    def __mul__(self, o):
        return self._binop(o, _jnp().multiply, "multiply")

    def __rmul__(self, o):
        return self._binop(o, _jnp().multiply, "multiply", reverse=True)

    def __truediv__(self, o):
        return self._binop(o, _jnp().true_divide, "true_divide")

    def __rtruediv__(self, o):
        return self._binop(o, _jnp().true_divide, "true_divide", reverse=True)

    def __floordiv__(self, o):
        return self._binop(o, _jnp().floor_divide, "floor_divide")

    def __rfloordiv__(self, o):
        return self._binop(o, _jnp().floor_divide, "floor_divide", reverse=True)

    def __mod__(self, o):
        return self._binop(o, _jnp().mod, "mod")

    def __rmod__(self, o):
        return self._binop(o, _jnp().mod, "mod", reverse=True)

    def __pow__(self, o):
        return self._binop(o, _jnp().power, "power")

    def __rpow__(self, o):
        return self._binop(o, _jnp().power, "power", reverse=True)

    def __matmul__(self, o):
        return self._binop(o, _jnp().matmul, "matmul")

    def __rmatmul__(self, o):
        return self._binop(o, _jnp().matmul, "matmul", reverse=True)

    def __neg__(self):
        return _apply(_jnp().negative, (self,), name="negative")

    def __pos__(self):
        return self

    def __abs__(self):
        return _apply(_jnp().abs, (self,), name="abs")

    def __invert__(self):
        return _apply(_jnp().invert, (self,), name="invert")

    # in-place ops rebind (recording-safe: produces a new tape entry)
    def _inplace(self, other, fn, name):
        res = self._binop(other, fn, name)
        self._set_data_internal(res._data, keep_tape=True)
        self._tape = res._tape
        return self

    def __iadd__(self, o):
        return self._inplace(o, _jnp().add, "add")

    def __isub__(self, o):
        return self._inplace(o, _jnp().subtract, "subtract")

    def __imul__(self, o):
        return self._inplace(o, _jnp().multiply, "multiply")

    def __itruediv__(self, o):
        return self._inplace(o, _jnp().true_divide, "true_divide")

    # py2-era spellings the reference still defines on NDArray
    def __div__(self, o):
        return self.__truediv__(o)

    def __rdiv__(self, o):
        return self.__rtruediv__(o)

    def __imod__(self, o):
        return self._inplace(o, _jnp().mod, "mod")

    # comparisons (not differentiable; registry records nothing since
    # integer/bool outputs get zero cotangents anyway — skip recording)
    def _cmp(self, other, fn, name):
        from ..ops.registry import apply
        from ..util import is_np_array

        if not (isinstance(other, NDArray) or _np.isscalar(other)
                or isinstance(other, (_np.ndarray, list, tuple))):
            return NotImplemented
        res = apply(fn, (self, other), name=name, record=False)
        if not is_np_array() and str(res.dtype) == "bool":
            # legacy NDArray comparisons return input-dtype 0/1 values,
            # not bool (reference ndarray.py ``equal`` docstring)
            res = res.astype(self.dtype)
        return res

    def __eq__(self, o):
        return self._cmp(o, _jnp().equal, "equal")

    def __ne__(self, o):
        return self._cmp(o, _jnp().not_equal, "not_equal")

    def __lt__(self, o):
        return self._cmp(o, _jnp().less, "less")

    def __le__(self, o):
        return self._cmp(o, _jnp().less_equal, "less_equal")

    def __gt__(self, o):
        return self._cmp(o, _jnp().greater, "greater")

    def __ge__(self, o):
        return self._cmp(o, _jnp().greater_equal, "greater_equal")

    # -- shape ops --------------------------------------------------------
    def _link_reshape_view(self, res):
        """Reference reshape/flatten/expand_dims share memory with the
        source (``MXNDArrayReshape64``); link as a whole-array view."""
        if type(self) is NDArray and not autograd.is_recording():
            res._view_parent = self
            res._view_key = None
            res._view_pver = self._version
        return res

    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        elif not shape:
            shape = kwargs.get("shape")
            if not shape:
                raise ValueError("Shape must be provided.")
        bad = [k for k in kwargs if k not in ("shape", "reverse", "order")]
        if bad:
            raise TypeError(f"Got unknown keywords in reshape: {bad}. "
                            "Accepted keyword arguments are 'shape', "
                            "'reverse' and 'order'.")
        if kwargs.get("order", "C") != "C":
            raise NotImplementedError(
                "reshape(order='F') is not supported on this build; "
                "transpose first for Fortran-order traversal")
        from ..util import is_np_array
        if any(int(s) < -1 for s in shape) or kwargs.get("reverse", False) \
                or (not is_np_array() and any(int(s) == 0 for s in shape)):
            # the reference's special values 0/-2/-3/-4 (+ reverse) are
            # legacy-only; in numpy mode 0 is a genuine zero-size dim
            # (values < -1 are invalid in numpy, so always legacy)
            from ..ops.legacy import infer_reshape_shape
            shape = infer_reshape_shape(shape, self.shape,
                                        kwargs.get("reverse", False))
        res = _apply(lambda x: x.reshape(tuple(shape)), (self,),
                     name="reshape")
        return self._link_reshape_view(res)

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        ax = axes if axes else None
        return _apply(lambda x: _jnp().transpose(x, ax), (self,), name="transpose")

    def swapaxes(self, a, b):
        return _apply(lambda x: _jnp().swapaxes(x, a, b), (self,), name="swapaxes")

    def flatten(self, order="C", inplace=False):
        """numpy semantics (1-D copy) under ``is_np_array()``; the legacy
        2-D collapse ``(d1, d2*...*dk)`` under ``set_np(array=False)`` or
        whenever the legacy-only ``inplace`` flag is passed (reference
        ``ndarray.py flatten``: ``op.flatten`` / ``reshape((0, -1))``)."""
        from ..util import is_np_array
        if inplace or not is_np_array():
            # reference Flatten: (d0, prod(rest)) — 1-D gives (d, 1)
            res = self.reshape((self.shape[0], -1)) if self.ndim >= 1 \
                else self.reshape((1, 1))
            if not inplace:  # reference op.flatten copies; only the
                res._view_parent = None  # inplace form is a view
            return res
        src = self
        if order == "F":
            src = self.transpose(*reversed(range(self.ndim)))
        elif order != "C":
            raise ValueError(f"order must be 'C' or 'F', got {order!r}")
        res = src.reshape((-1,))
        res._view_parent = None  # numpy .flatten() contract is a copy
        return res

    def __getattr__(self, name):
        """Reference codegen parity: the registry's op surface is exposed
        as bound NDArray methods (``x.exp()``, ``x.log_softmax()``,
        ``x.topk()`` — reference ``ndarray/register.py`` synthesizes these
        from the C op registry at import).  Resolution is restricted to
        the registered-op table (``legacy.resolve_method``): namespace
        utilities never bind as methods, and typos raise AttributeError."""
        if name.startswith("_"):
            raise AttributeError(name)
        from ..ops import legacy
        try:
            fn = legacy.resolve_method(name)
        except AttributeError:
            raise AttributeError(
                f"'NDArray' object has no attribute {name!r}") from None
        if not callable(fn):
            raise AttributeError(
                f"'NDArray' object has no attribute {name!r}")
        import functools
        return functools.partial(fn, self)

    def nonzero(self):
        """Indices of nonzero elements, one array per dimension (numpy
        method contract)."""
        host = _np.nonzero(self.asnumpy())
        return tuple(NDArray(h) for h in host)

    def ravel(self, order="C"):
        """1-D view of the array (numpy contract; the reshape view links
        back to the parent like ``reshape``)."""
        if order == "F":
            return self.transpose(*reversed(range(self.ndim))).reshape((-1,))
        return self.reshape((-1,))

    def squeeze(self, axis=None):
        return _apply(lambda x: _jnp().squeeze(x, axis), (self,), name="squeeze")

    def expand_dims(self, axis, inplace=False):
        res = _apply(lambda x: _jnp().expand_dims(x, axis), (self,),
                     name="expand_dims")
        if inplace:
            res = self._link_reshape_view(res)
        return res

    def broadcast_to(self, shape):
        return _apply(lambda x: _jnp().broadcast_to(x, shape), (self,), name="broadcast_to")

    def broadcast_like(self, other):
        return self.broadcast_to(other.shape)

    def repeat(self, repeats, axis=None):
        return _apply(lambda x: _jnp().repeat(x, repeats, axis), (self,), name="repeat")

    def tile(self, reps):
        return _apply(lambda x: _jnp().tile(x, reps), (self,), name="tile")

    def flip(self, axis=None):
        return _apply(lambda x: _jnp().flip(x, axis), (self,), name="flip")

    def split(self, indices_or_sections, axis=0):
        return _apply(
            lambda x: tuple(_jnp().split(x, indices_or_sections, axis)),
            (self,), name="split")

    def take(self, indices, axis=None, mode="clip"):
        idx = indices._data if isinstance(indices, NDArray) else indices
        return _apply(lambda x: _jnp().take(x, idx, axis=axis, mode=mode),
                      (self,), name="take")

    def diag(self, k=0):
        return _apply(lambda x: _jnp().diag(x, k), (self,), name="diag")

    # -- reductions -------------------------------------------------------
    def _reduce(self, fn, name, axis=None, keepdims=False, **kw):
        return _apply(lambda x: fn(x, axis=axis, keepdims=keepdims, **kw),
                      (self,), name=name)

    def sum(self, axis=None, dtype=None, keepdims=False):
        return self._reduce(_jnp().sum, "sum", axis, keepdims, dtype=dtype)

    def mean(self, axis=None, dtype=None, keepdims=False):
        return self._reduce(_jnp().mean, "mean", axis, keepdims, dtype=dtype)

    def prod(self, axis=None, keepdims=False):
        return self._reduce(_jnp().prod, "prod", axis, keepdims)

    def max(self, axis=None, keepdims=False):
        return self._reduce(_jnp().max, "max", axis, keepdims)

    def min(self, axis=None, keepdims=False):
        return self._reduce(_jnp().min, "min", axis, keepdims)

    def std(self, axis=None, ddof=0, keepdims=False):
        return self._reduce(_jnp().std, "std", axis, keepdims, ddof=ddof)

    def var(self, axis=None, ddof=0, keepdims=False):
        return self._reduce(_jnp().var, "var", axis, keepdims, ddof=ddof)

    def argmax(self, axis=None):
        from ..ops.registry import apply

        return apply(lambda x: _jnp().argmax(x, axis), (self,), name="argmax",
                     record=False)

    def argmin(self, axis=None):
        from ..ops.registry import apply

        return apply(lambda x: _jnp().argmin(x, axis), (self,), name="argmin",
                     record=False)

    def argsort(self, axis=-1):
        from ..ops.registry import apply

        return apply(lambda x: _jnp().argsort(x, axis=axis), (self,),
                     name="argsort", record=False)

    def sort(self, axis=-1):
        return _apply(lambda x: _jnp().sort(x, axis=axis), (self,), name="sort")

    def cumsum(self, axis=None, dtype=None):
        return _apply(lambda x: _jnp().cumsum(x, axis=axis, dtype=dtype),
                      (self,), name="cumsum")

    def clip(self, a_min=None, a_max=None):
        return _apply(lambda x: _jnp().clip(x, a_min, a_max), (self,), name="clip")

    def round(self, decimals=0):
        return _apply(lambda x: _jnp().round(x, decimals), (self,), name="round")

    def dot(self, other):
        # sparse operands route to the O(nnz) kernels (reference mx.nd.dot
        # dispatches on stype the same way, src/operator/tensor/dot-inl.h)
        if getattr(self, "_stype", "default") != "default" or \
                getattr(other, "_stype", "default") != "default":
            from .sparse import dot as _sparse_dot

            return _sparse_dot(self, other)
        return self._binop(other, _jnp().dot, "dot")

    def norm(self, ord=None, axis=None, keepdims=False):
        return _apply(
            lambda x: _jnp().linalg.norm(x, ord=ord, axis=axis, keepdims=keepdims),
            (self,), name="norm")

    def abs(self):
        return self.__abs__()

    def sqrt(self):
        return _apply(_jnp().sqrt, (self,), name="sqrt")

    def square(self):
        return _apply(_jnp().square, (self,), name="square")

    def all(self, axis=None, keepdims=False):
        from ..ops.registry import apply

        return apply(lambda x: _jnp().all(x, axis=axis, keepdims=keepdims),
                     (self,), name="all", record=False)

    def any(self, axis=None, keepdims=False):
        from ..ops.registry import apply

        return apply(lambda x: _jnp().any(x, axis=axis, keepdims=keepdims),
                     (self,), name="any", record=False)

    # -- persistence ------------------------------------------------------
    def save(self, fname):
        from .utils import save

        save(fname, self)


# ``mx.np.ndarray`` is this class
def indexing_key_expand_implicit_axes(key, shape):
    """Make implicit axes explicit (``slice(None)`` fill), expand
    ``Ellipsis``, and convert boolean index arrays to integer arrays via
    ``nonzero`` (reference ``ndarray/ndarray.py
    indexing_key_expand_implicit_axes``)."""
    if not isinstance(key, tuple):
        key = (key,)
    ell_idx = None
    nonell = []
    for idx in key:
        if idx is Ellipsis:
            if ell_idx is not None:
                raise IndexError(
                    "Cannot use more than one ellipsis (`...`) for indexing")
            ell_idx = len(nonell)
            continue
        if isinstance(idx, NDArray):
            idx = idx.asnumpy()
        if isinstance(idx, _np.ndarray) and idx.dtype == _np.bool_:
            nonell.extend(_np.nonzero(idx))
        else:
            nonell.append(idx)
    consumed = sum(1 for k in nonell if k is not None)
    pad = [slice(None)] * (len(shape) - consumed)
    if ell_idx is None:
        expanded = nonell + pad
    else:
        expanded = nonell[:ell_idx] + pad + nonell[ell_idx:]
    return tuple(expanded)


ndarray = NDArray
