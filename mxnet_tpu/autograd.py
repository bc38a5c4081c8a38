"""Autograd: tape-based reverse-mode differentiation over JAX vjps.

TPU-native re-design of the reference's autograd (``src/imperative/
imperative.cc`` ``MarkVariables:134`` / ``RecordOp:204`` / ``Backward:385``
and Python ``python/mxnet/autograd.py:121-519``).

Reference mechanism: every recorded op attaches an ``AGInfo`` node to an nnvm
graph; ``Backward`` runs the nnvm ``Gradient`` pass and executes the grad
graph through the engine.

TPU mechanism: every recorded op is dispatched through ``jax.vjp`` — the
forward runs once (XLA, async) and the returned vjp closure *is* the gradient
graph node. ``backward()`` walks the tape in reverse sequence order calling
the stored vjp closures and accumulates cotangents into the arrays registered
by ``mark_variables`` honoring ``grad_req`` write/add/null — the same
contract ``Imperative::Backward`` honors (``imperative.cc:630``).

Hybridized blocks contribute a *single* tape node whose forward and backward
are each one compiled XLA computation (see ``mxnet_tpu.cachedop``) — the
analog of a ``_CachedOp`` node on the reference tape
(``src/imperative/cached_op.cc:836-845``).
"""
from __future__ import annotations

import threading
from typing import List, Optional, Sequence

from .base import MXNetError

# ---------------------------------------------------------------------------
# Thread-local recording / training state
# (reference: Imperative's thread-local is_recording/is_training,
#  include/mxnet/imperative.h:51-335)
# ---------------------------------------------------------------------------


class _State(threading.local):
    def __init__(self):
        super().__init__()
        self.recording = False
        self.training = False
        self.seq = 0


_state = _State()


def is_recording() -> bool:
    return _state.recording


def is_training() -> bool:
    return _state.training


def set_recording(is_record: bool) -> bool:
    prev, _state.recording = _state.recording, bool(is_record)
    return prev


def set_training(train_mode: bool) -> bool:
    prev, _state.training = _state.training, bool(train_mode)
    return prev


class _RecordingStateScope:
    """Scope guard mirroring ``autograd.py:121`` in the reference."""

    def __init__(self, is_record: Optional[bool], train_mode: Optional[bool]):
        self._enter_record = is_record
        self._enter_train = train_mode
        self._prev_record = None
        self._prev_train = None

    def __enter__(self):
        if self._enter_record is not None:
            self._prev_record = set_recording(self._enter_record)
        if self._enter_train is not None:
            self._prev_train = set_training(self._enter_train)
        return self

    def __exit__(self, *exc):
        if self._enter_record is not None:
            set_recording(self._prev_record)
        if self._enter_train is not None:
            set_training(self._prev_train)
        return False


def record(train_mode: bool = True):
    """``with autograd.record():`` — turn on recording (and train mode)."""
    return _RecordingStateScope(True, train_mode)


def pause(train_mode: bool = False):
    """``with autograd.pause():`` — turn off recording."""
    return _RecordingStateScope(False, train_mode)


def train_mode():
    return _RecordingStateScope(None, True)


def predict_mode():
    return _RecordingStateScope(None, False)


# ---------------------------------------------------------------------------
# Tape structures
# ---------------------------------------------------------------------------


class Leaf:
    """A differentiable variable registered via ``mark_variables``.

    Holds the gradient buffer and the grad_req, the role of the reference's
    variable ``AGInfo`` + pre-registered grad array (``imperative.cc:134``).
    """

    __slots__ = ("grad_array", "grad_req", "_accum")

    def __init__(self, grad_array, grad_req: str = "write"):
        if grad_req not in ("write", "add", "null"):
            raise MXNetError(f"invalid grad_req {grad_req!r}")
        self.grad_array = grad_array  # NDArray or None (for grad() API use)
        self.grad_req = grad_req
        self._accum = None  # transient cotangent during a backward walk


class TapeNode:
    """One recorded op: a vjp closure plus wiring to producers/leaves.

    ``in_slots[i]`` is either a :class:`Leaf`, a ``(TapeNode, out_idx)``
    pair, or ``None`` (constant / untracked input).

    ``fwd_fn``/``in_arrays`` (optional) let ``create_graph=True`` rebuild
    the vjp *differentiably*: the backward walk re-linearizes ``fwd_fn`` at
    the saved inputs as a recorded op, so grad-of-grad sees the full input
    dependence (the reference builds the grad graph symbolically for the
    same reason, ``src/nnvm/gradient.cc``).
    """

    __slots__ = ("vjp_fn", "in_slots", "out_avals", "seq", "name",
                 "fwd_fn", "in_arrays", "out_container", "__weakref__")

    def __init__(self, vjp_fn, in_slots, out_avals, name="",
                 fwd_fn=None, in_arrays=None):
        self.vjp_fn = vjp_fn
        self.in_slots = in_slots
        self.out_avals = out_avals  # list of (shape, dtype) per output leaf
        _state.seq += 1
        self.seq = _state.seq
        self.name = name
        self.fwd_fn = fwd_fn
        self.in_arrays = in_arrays
        self.out_container = False  # fwd returns a tuple even when len==1


def mark_variables(variables, gradients, grad_reqs="write"):
    """Associate gradient buffers with variables (``autograd.py:196``)."""
    if not isinstance(variables, (list, tuple)):
        variables = [variables]
        gradients = [gradients]
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for var, grad, req in zip(variables, gradients, grad_reqs):
        var._leaf = Leaf(grad, req)
        var._tape = None


# ---------------------------------------------------------------------------
# Backward walk
# ---------------------------------------------------------------------------


def _collect_nodes(head_arrays):
    """Reachable tape nodes from the heads, returned sorted by seq desc."""
    seen = set()
    stack = []
    for a in head_arrays:
        t = getattr(a, "_tape", None)
        if t is not None and id(t[0]) not in seen:
            seen.add(id(t[0]))
            stack.append(t[0])
    nodes = []
    while stack:
        node = stack.pop()
        nodes.append(node)
        for slot in node.in_slots:
            if isinstance(slot, tuple):
                prod = slot[0]
                if id(prod) not in seen:
                    seen.add(id(prod))
                    stack.append(prod)
    nodes.sort(key=lambda n: n.seq, reverse=True)
    return nodes


def _zeros_like_aval(aval):
    import jax.numpy as jnp

    shape, dtype = aval
    return jnp.zeros(shape, dtype)


def _add_ct(table, key, val):
    cur = table.get(key)
    table[key] = val if cur is None else cur + val


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):  # pylint: disable=unused-argument
    """Run backward from ``heads``, writing gradients into marked variables.

    Mirrors ``mxnet.autograd.backward`` (``autograd.py:245``) →
    ``Imperative::Backward`` (``imperative.cc:385``).
    """
    import jax.numpy as jnp

    from .ndarray.ndarray import NDArray

    if isinstance(heads, NDArray):
        heads = [heads]
        if head_grads is not None and not isinstance(head_grads, (list, tuple)):
            head_grads = [head_grads]
    if head_grads is None:
        head_grads = [None] * len(heads)

    leaves = _run_backward(heads, head_grads, retain_graph)
    # write into registered grad buffers honoring grad_req
    from .ndarray.sparse import RowSparseNDArray

    for leaf in leaves:
        ct = leaf._accum
        leaf._accum = None
        if ct is None or leaf.grad_req == "null" or leaf.grad_array is None:
            continue
        ga = leaf.grad_array
        if isinstance(ct, RowSparseNDArray):
            # sparse cotangent (embedding sparse_grad): keep it O(nnz)
            # when the grad buffer is row_sparse; storage-fallback to
            # dense otherwise (exec_utils.h:138 role)
            if isinstance(ga, RowSparseNDArray):
                if leaf.grad_req == "add":
                    ga._set_sparse(ga + ct)
                else:
                    ga._set_sparse(ct)
            elif leaf.grad_req == "add":
                ga._set_data_internal(ga._data + ct._data)
            else:
                ga._set_data_internal(ct._data)
        elif leaf.grad_req == "add":
            ga._set_data_internal(ga._data + ct)
        else:
            ga._set_data_internal(jnp.asarray(ct, ga.dtype) if ct.dtype != ga.dtype else ct)


def _node_vjp_recorded(node, cts):
    """create_graph=True step: re-linearize ``node.fwd_fn`` at the saved
    inputs *as a recorded op*, so the produced input-cotangents carry tape
    links to both the cotangents and the original inputs — grad-of-grad
    sees d(residual)/dx, which the stored first-order vjp closure cannot
    provide (its residuals are baked constants)."""
    from .ndarray.ndarray import NDArray
    from .ops import registry

    if node.fwd_fn is None or node.in_arrays is None:
        raise MXNetError(
            f"create_graph=True is not supported through node "
            f"{node.name!r} (hybridized CachedOp or custom Function); "
            f"compute the inner function imperatively for higher-order "
            f"gradients")
    n_out = len(node.out_avals)
    as_tuple = n_out > 1 or node.out_container

    def hfn(*args):
        import jax

        cs, xs = args[:n_out], args[n_out:]
        _, vjp = jax.vjp(node.fwd_fn, *xs)
        r = vjp(tuple(cs) if as_tuple else cs[0])
        return tuple(r)

    all_args = tuple(cts) + tuple(node.in_arrays)
    out = registry.apply(hfn, all_args, name=(node.name or "op") + "_grad",
                         sync_outputs=False, cacheable=False)
    return out if isinstance(out, (list, tuple)) else (out,)


def _run_backward(heads, head_grads, retain_graph, create_graph=False):
    """Shared tape walk. Returns the list of leaves touched (with _accum).

    ``create_graph=True`` runs the walk with NDArray cotangents and records
    every vjp application back onto the tape (the reference's re-recorded
    grad graph, ``python/mxnet/autograd.py:309``).
    """
    import jax.numpy as jnp

    from . import engine
    from .ndarray.ndarray import NDArray

    def lift(x):
        return NDArray(x) if create_graph and not isinstance(x, NDArray) else x

    node_cts = {}  # (id(node), out_idx) -> cotangent (jax array / NDArray)
    touched_leaves = []

    def touch(leaf, ct):
        if leaf._accum is None:
            touched_leaves.append(leaf)
            leaf._accum = ct
        else:
            leaf._accum = leaf._accum + ct

    any_graph = False
    for arr, hg in zip(heads, head_grads):
        tape = getattr(arr, "_tape", None)
        leaf = getattr(arr, "_leaf", None)
        if hg is None:
            # MXNet semantics: default head gradient is ones_like(head)
            ct = lift(jnp.ones(arr.shape, arr.dtype))
        elif create_graph:
            ct = hg if isinstance(hg, NDArray) else NDArray(jnp.asarray(hg))
        else:
            ct = hg._data if hasattr(hg, "_data") else jnp.asarray(hg)
        if tape is not None:
            any_graph = True
            _add_ct(node_cts, (id(tape[0]), tape[1]), ct)
        elif leaf is not None:
            any_graph = True
            touch(leaf, ct)
    if not any_graph:
        raise MXNetError(
            "cannot differentiate: none of the heads is connected to the "
            "autograd tape (did you compute them inside autograd.record()?)"
        )

    nodes = _collect_nodes(heads)
    for node in nodes:
        cts = []
        has_any = False
        for i, aval in enumerate(node.out_avals):
            ct = node_cts.pop((id(node), i), None)
            if ct is None:
                ct = lift(_zeros_like_aval(aval))
            else:
                has_any = True
                if not create_graph and hasattr(ct, "_stype"):
                    # a sparse cotangent reaching a dense vjp: the
                    # storage-fallback boundary — densify here
                    ct = ct._data
            cts.append(ct)
        if not has_any:
            continue
        if create_graph:
            in_cts = _node_vjp_recorded(node, cts)
        else:
            engine._count_dispatch()  # one backward executable per node
            in_cts = node.vjp_fn(tuple(cts) if len(cts) > 1 else cts[0])
        if not isinstance(in_cts, (tuple, list)):
            in_cts = (in_cts,)
        for slot, ict in zip(node.in_slots, in_cts):
            if slot is None or ict is None:
                continue
            if isinstance(slot, Leaf):
                touch(slot, ict)
            else:
                _add_ct(node_cts, (id(slot[0]), slot[1]), ict)
        if not retain_graph and not create_graph:
            # free residuals AND the saved forward inputs eagerly — the
            # higher-order bookkeeping must not raise ordinary training's
            # peak activation memory
            node.vjp_fn = None
            node.fwd_fn = None
            node.in_arrays = None
    return touched_leaves


def grad(heads, variables, head_grads=None, retain_graph=None, create_graph=False,
         train_mode=True):  # pylint: disable=unused-argument
    """Return gradients of heads w.r.t. variables (``autograd.py:309``).

    ``create_graph=True`` re-records every vjp application onto the tape
    (via the saved forward functions), so the returned gradients are
    themselves differentiable — ``grad(grad(f))`` works, matching the
    reference's re-recorded grad graph and its
    ``test_higher_order_grad.py`` contract.
    """
    from .ndarray.ndarray import NDArray

    if isinstance(heads, NDArray):
        heads = [heads]
    if isinstance(variables, NDArray):
        variables = [variables]
    if retain_graph is None:
        retain_graph = create_graph
    if head_grads is not None and not isinstance(head_grads, (list, tuple)):
        head_grads = [head_grads]
    if head_grads is None:
        head_grads = [None] * len(heads)

    # temporarily mark: ensure each variable has a leaf
    tmp_leaves = []
    for v in variables:
        if getattr(v, "_leaf", None) is None:
            v._leaf = Leaf(None, "write")
            tmp_leaves.append(v)
    prev_rec = None
    if create_graph:
        # the walk's vjp applications must themselves be recorded
        prev_rec = set_recording(True)
    try:
        touched = _run_backward(heads, head_grads, retain_graph,
                                create_graph=create_graph)
        out = []
        for v in variables:
            ct = v._leaf._accum
            v._leaf._accum = None
            if ct is None:
                import jax.numpy as jnp

                ct = jnp.zeros(v.shape, v.dtype)
            out.append(ct if isinstance(ct, NDArray) else NDArray(ct))
        # leaves the walk touched but the caller didn't ask about (e.g.
        # network params during a grad-penalty grad-wrt-input) must not
        # keep stale accumulators — they'd poison the next backward()
        for leaf in touched:
            leaf._accum = None
        return out
    finally:
        if prev_rec is not None:
            set_recording(prev_rec)
        for v in tmp_leaves:
            v._leaf = None


def get_symbol(x):  # pragma: no cover - legacy API surface
    """Reference returns the recorded Symbol; here tracing is jax-side."""
    raise NotImplementedError(
        "autograd.get_symbol is a legacy-graph API; use HybridBlock.export "
        "for a serialized compiled graph"
    )


# ---------------------------------------------------------------------------
# Custom differentiable Function (reference autograd.Function,
# python/mxnet/autograd.py:369 + src/c_api/c_api_function.cc)
# ---------------------------------------------------------------------------


class Function:
    """User-defined differentiable operation.

    Subclass and implement ``forward(self, *inputs)`` and
    ``backward(self, *output_grads)`` with NDArray in/out, then call the
    instance. Matches the reference contract: ``save_for_backward`` style
    state can simply be attached to ``self``.
    """

    def __init__(self):
        self._in_slots = None

    def save_for_backward(self, *arrays):
        self.saved_tensors = arrays

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray, _tracked, _slot_of

        with pause():
            outputs = self.forward(*inputs)
        single = not isinstance(outputs, (list, tuple))
        outs = [outputs] if single else list(outputs)
        if is_recording() and any(_tracked(a) for a in inputs):
            func = self

            def vjp_fn(cts):
                if not isinstance(cts, tuple):
                    cts = (cts,)
                with pause():
                    grads = func.backward(*[NDArray(c) for c in cts])
                if not isinstance(grads, (list, tuple)):
                    grads = (grads,)
                return tuple(g._data if g is not None else None for g in grads)

            node = TapeNode(
                vjp_fn,
                [_slot_of(a) for a in inputs],
                [(o.shape, o.dtype) for o in outs],
                name=type(self).__name__,
            )
            for i, o in enumerate(outs):
                o._tape = (node, i)
                o._leaf = None
        return outs[0] if single else outs
