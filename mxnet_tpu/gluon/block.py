"""Gluon Block / HybridBlock / SymbolBlock.

Reference: ``python/mxnet/gluon/block.py`` (1,776 LoC): parameter
registration via ``__setattr__``, forward hooks, ``hybridize()`` building a
CachedOp from a deferred-compute trace (``_build_cache:994-1085``), ``export``
(``:1300``) and ``SymbolBlock.imports`` (``:1500``).

TPU redesign: ``hybridize`` swaps the call path to
:class:`mxnet_tpu.cachedop.CachedOp` — jax tracing of ``forward`` compiled to
one XLA executable per input signature (SURVEY.md §3.2 mapping). ``export``
serializes the traced computation with ``jax.export`` (StableHLO) plus a
parameter archive, and ``SymbolBlock.imports`` reloads it without the Python
definition — the role of ``model-symbol.json`` + ``model-0000.params``.
"""
from __future__ import annotations

import contextlib
import json
import re
from collections import OrderedDict

from .. import autograd
from ..base import MXNetError
from ..cachedop import CachedOp, in_trace, params_changed
from ..device import Context, cpu, current_context
from ..ndarray.ndarray import NDArray
from .parameter import Parameter, ParameterDict


_NO_SCOPE = contextlib.nullcontext()


class Block:
    """Base class for all neural-network layers and models."""

    # the name the parent registered this block under: its scope in a
    # compiled program's metadata (``__call__``); None for a root
    _scope_name = None

    def __init__(self):
        self._children = OrderedDict()
        self._reg_params = OrderedDict()
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()
        self._hook_id = 0

    # -- attribute registration ------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
                value._scope_name = name
                params_changed()
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
                value._structure = (self, name)
                params_changed()
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block
        block._scope_name = name
        super().__setattr__(f"_child_{name}", block)
        params_changed()
        return block

    def register_forward_hook(self, hook):
        self._hook_id += 1
        self._forward_hooks[self._hook_id] = hook
        return _HookHandle(self._forward_hooks, self._hook_id)

    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return _HookHandle(self._forward_pre_hooks, self._hook_id)

    def register_op_hook(self, callback, monitor_all=False):  # pragma: no cover
        raise NotImplementedError(
            "per-op monitoring inside compiled graphs is exposed via "
            "mxnet_tpu.profiler instead")

    # -- parameter access -------------------------------------------------
    @property
    def params(self):
        return ParameterDict(self._reg_params)

    def collect_params(self, select=None) -> ParameterDict:
        out = ParameterDict()
        self._collect_params(out, prefix="")
        if select is not None:
            pat = re.compile(select)
            out = ParameterDict(
                (k, v) for k, v in out.items() if pat.search(k))
        return out

    def _collect_params(self, out, prefix):
        for name, p in self._reg_params.items():
            out[prefix + name] = p
        for cname, child in self._children.items():
            child._collect_params(out, prefix + cname + ".")

    def initialize(self, init=None, device=None, ctx=None, verbose=False,
                   force_reinit=False):  # pylint: disable=unused-argument
        self.collect_params().initialize(init=init, ctx=ctx or device,
                                         force_reinit=force_reinit)
        return self

    def setattr(self, name, value):
        for p in self.collect_params().values():
            setattr(p, name, value)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for p in self._reg_params.values():
            p.cast(dtype)
        return self

    def zero_grad(self):
        self.collect_params().zero_grad()

    def reset_ctx(self, ctx):
        self.collect_params().reset_ctx(ctx)

    reset_device = reset_ctx

    # -- persistence ------------------------------------------------------
    def _params_data(self):
        """name -> NDArray dict of every parameter's current buffer — THE
        serialization view of this block, shared by save_parameters, the
        estimator CheckpointHandler and resilience.checkpoint so the three
        on-disk params payloads can never diverge."""
        return {k: v.data() for k, v in self.collect_params().items()}

    def save_parameters(self, filename, deduplicate=False):  # pylint: disable=unused-argument
        from ..ndarray.utils import save

        save(filename, self._params_data())

    def load_parameters(self, filename, device=None, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False, dtype_source="current"):  # pylint: disable=unused-argument
        from ..ndarray.utils import load

        loaded = load(filename)
        params = self.collect_params()
        for name, p in params.items():
            if name in loaded:
                p.set_data(loaded[name])
            elif not allow_missing:
                raise MXNetError(f"parameter {name!r} missing in {filename}")
        if not ignore_extra:
            extra = set(loaded) - set(params)
            if extra:
                raise MXNetError(f"{filename} contains extra params {sorted(extra)}")

    save = save_parameters
    load = load_parameters

    def share_parameters(self, shared: dict):
        params = self.collect_params()
        for name, p in shared.items():
            if name in params:
                holder, attr = params[name]._structure or (None, None)
                if holder is not None:
                    setattr(holder, attr, p)
        return self

    # -- call -------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        with self.trace_scope():
            out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def trace_scope(self):
        """What this block's work is named in a compiled program: under a
        trace (a ``CachedOp``'s, or a ``functionalize``d replay's) the
        scope of the name its parent registered it under, so that a device
        event reads ``layer3/attention/...``; eagerly, and for a root,
        nothing. A caller that computes with a child's parameters without
        calling the child enters it for the child."""
        if self._scope_name is None or not in_trace():
            return _NO_SCOPE
        from jax import named_scope

        return named_scope(self._scope_name)

    def forward(self, *args):
        raise NotImplementedError

    def hybridize(self, active=True, **kwargs):
        """Recursively activate compiled execution on HybridBlock children."""
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def summary(self, *inputs):
        """Print a per-layer summary by running a forward with hooks."""
        rows = []

        def add_hooks(block, prefix):
            def hook(b, _in, out):
                shape = out.shape if isinstance(out, NDArray) else "-"
                nparam = sum(
                    int(p.data().size) for p in b._reg_params.values()
                    if p._data is not None)
                rows.append((prefix or type(b).__name__, type(b).__name__,
                             shape, nparam))
            handles.append(block.register_forward_hook(hook))
            for name, c in block._children.items():
                add_hooks(c, f"{prefix}.{name}" if prefix else name)

        handles = []
        add_hooks(self, "")
        try:
            with autograd.predict_mode():
                self(*inputs)
        finally:
            for h in handles:
                h.detach()
        header = f"{'Layer':<40}{'Type':<20}{'Output':<24}{'Params':<12}"
        lines = [header, "-" * len(header)]
        for name, typ, shape, nparam in rows:
            lines.append(f"{name:<40}{typ:<20}{str(shape):<24}{nparam:<12}")
        print("\n".join(lines))
        return "\n".join(lines)

    def __repr__(self):
        lines = [type(self).__name__ + "("]
        for name, child in self._children.items():
            child_repr = repr(child).replace("\n", "\n  ")
            lines.append(f"  ({name}): {child_repr}")
        lines.append(")")
        return "\n".join(lines) if len(lines) > 2 else type(self).__name__ + "()"


class _HookHandle:
    def __init__(self, table, hid):
        self._table = table
        self._hid = hid

    def detach(self):
        self._table.pop(self._hid, None)


class HybridBlock(Block):
    """Block that can be compiled to a single XLA executable per signature."""

    def __init__(self):
        super().__init__()
        self._active = False
        self._cached_op = None
        self._flags = {}

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):  # pylint: disable=unused-argument
        self._active = active
        self._flags = dict(static_alloc=static_alloc, static_shape=static_shape)
        self._cached_op = None
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def infer_shape(self, *args):
        """Finalize deferred parameter shapes from example inputs.

        The reference runs symbolic shape inference; here layers resolve
        their own shapes at first forward, so a single paused eager forward
        is the inference pass.
        """
        with autograd.pause():
            self.forward(*args)

    def optimize_for(self, x, *args, backend=None, clear=True, partition_if_dynamic=True,
                     static_alloc=False, static_shape=False, **kwargs):
        """Reference ``optimize_for`` (subgraph backend partition + build,
        ``subgraph_property.h:86-385`` / ``MXOptimizeForBackend``).

        TPU redesign: a backend is a named bundle of function-transform
        passes from :mod:`mxnet_tpu.subgraph` (``remat``, ``bf16``, or
        user-registered via ``subgraph.register_backend``). The passes wrap
        the traced forward before jit; then one warm-up call builds the
        executable.
        """
        del partition_if_dynamic, kwargs
        changed = False
        if clear and getattr(self, "_graph_passes", None):
            # reference semantics: clear=True drops prior backend state
            # even when no new backend is given
            self._graph_passes = []
            changed = True
        if backend is not None:
            from ..subgraph import get_backend_passes

            passes = get_backend_passes(backend)  # validate + fetch
            self._graph_passes = list(
                getattr(self, "_graph_passes", ()) or ()) + passes
            changed = True
        if changed and getattr(self, "_cached_op", None) is not None:
            self._cached_op = None  # rebuild with the new pass set
        self.hybridize(True, static_alloc=static_alloc, static_shape=static_shape)
        self(x, *args)

    # -- export -----------------------------------------------------------
    def export(self, path, epoch=0, remove_amp_cast=True):  # pylint: disable=unused-argument
        """Serialize compiled graph + params: ``path-symbol.mxir`` +
        ``path-%04d.params`` (reference writes symbol.json + params)."""
        import jax
        import jax.export as jexport

        if not getattr(self, "_example_args", None):
            raise MXNetError(
                "export requires at least one forward call (to fix the input "
                "signature) before exporting")
        args = self._example_args
        params = self.collect_params()
        names = list(params)
        datas = [params[n].data()._data for n in names]

        def fn(param_datas, *arg_datas):
            from ..cachedop import _ParamBinding

            arrays = [params[n].data() for n in names]
            wrapped = [NDArray(a) for a in arg_datas]
            with _ParamBinding(arrays, list(param_datas)):
                prev = autograd.set_recording(False)
                try:
                    out = self.forward(*wrapped)
                finally:
                    autograd.set_recording(prev)
            flat, _ = jax.tree_util.tree_flatten(
                out, is_leaf=lambda x: isinstance(x, NDArray))
            return [o._data for o in flat]

        exported = jexport.export(jax.jit(fn))(
            tuple(datas), *[a._data for a in args])
        blob = exported.serialize()
        with open(f"{path}-symbol.mxir", "wb") as f:
            f.write(blob)
        from ..ndarray.utils import save

        save(f"{path}-{epoch:04d}.params", {n: params[n].data() for n in names})
        meta = {
            "format": "mxnet_tpu-export-v1",
            "param_names": names,
            "input_sig": [(list(a.shape), str(a.dtype)) for a in args],
        }
        with open(f"{path}-meta.json", "w") as f:
            json.dump(meta, f)
        return f"{path}-symbol.mxir", f"{path}-{epoch:04d}.params"

    def forward(self, *args):
        # Gluon-v1 compatibility (reference block.py:574 "v1 style"):
        # subclasses that define hybrid_forward(self, F, x, <param>...)
        # get it called with F = the legacy nd op namespace (which works
        # identically eager and under trace — tracing lives inside
        # NDArray) and this block's registered Parameters passed by name,
        # the reference's weight-forwarding convention.
        hf = getattr(type(self), "hybrid_forward", None)
        if hf is not None:
            from ..gluon.parameter import DeferredInitializationError
            from .. import ndarray as F

            try:
                params = {n: p.data() for n, p in self._reg_params.items()}
            except DeferredInitializationError:
                # deferred-shape params: the reference 2.x contract
                # (gluon/block.py _deferred_infer_shape) — the block's
                # infer_shape(*args) sets param shapes from the inputs,
                # then init completes and the forward retries
                infer = getattr(type(self), "infer_shape", None)
                if infer is None or infer is HybridBlock.infer_shape:
                    # the base infer_shape runs a paused forward — for a
                    # hybrid_forward block that recurses right back here
                    raise MXNetError(
                        f"{type(self).__name__} has deferred-shape "
                        "parameters; implement infer_shape(self, *args) "
                        "to derive them from the inputs, or construct "
                        "the Parameters with complete shapes") from None
                infer(self, *args)
                for p in self._reg_params.values():
                    p._finish_deferred_init()
                params = {n: p.data() for n, p in self._reg_params.items()}
            return hf(self, F, *args, **params)
        raise NotImplementedError(
            f"{type(self).__name__} defines neither forward() nor the "
            "legacy hybrid_forward()")

    def __call__(self, *args, **kwargs):  # noqa: F811 - final definition above
        # remember example args for export
        if args and all(isinstance(a, NDArray) for a in args):
            self._example_args = args
        if self._active and not in_trace() and not kwargs:
            params = self.collect_params().values()
            if all(p._data is not None for p in params):
                for hook in self._forward_pre_hooks.values():
                    hook(self, args)
                if self._cached_op is None:
                    self._cached_op = CachedOp(self, **self._flags)
                out = self._cached_op(*args)
                for hook in self._forward_hooks.values():
                    hook(self, args, out)
                return out
        return Block.__call__(self, *args, **kwargs)


def _register_param_arrays(block, param_arrays):
    """Bind a name->NDArray dict as initialized Parameters p0..pN on a
    block (shared by SymbolBlock and _LegacySymbolBlock)."""
    out = {}
    for i, (name, arr) in enumerate(param_arrays.items()):
        p = Parameter(name=name, shape=arr.shape, dtype=arr.dtype)
        p.initialize(init="zeros", ctx=getattr(arr, "ctx", None))
        p.set_data(arr)
        block._reg_params[f"p{i}"] = p
        object.__setattr__(block, f"p{i}", p)
        out[name] = p
    params_changed()
    return out


class SymbolBlock(Block):
    """Runs a previously exported compiled graph (reference SymbolBlock)."""

    def __init__(self, exported, param_arrays, input_sig):
        super().__init__()
        self._exported = exported
        self._param_names = list(param_arrays)
        _register_param_arrays(self, param_arrays)
        self._input_sig = input_sig

    @staticmethod
    def imports(symbol_file, input_names=None, param_file=None, ctx=None,
                allow_missing=False, ignore_extra=False):  # pylint: disable=unused-argument
        import jax.export as jexport

        from ..ndarray.utils import load

        if str(symbol_file).endswith(".json"):
            # REFERENCE artifact pair (model-symbol.json +
            # model-0000.params): replay the nnvm graph through the
            # legacy Symbol DAG (symbol.fromjson upgrade path) with the
            # arg:/aux:-prefixed reference checkpoint bound as params
            return _LegacySymbolBlock.imports(symbol_file, input_names,
                                              param_file)

        with open(symbol_file, "rb") as f:
            exported = jexport.deserialize(f.read())
        meta_file = symbol_file.replace("-symbol.mxir", "-meta.json")
        with open(meta_file) as f:
            meta = json.load(f)
        params = load(param_file) if param_file else {}
        ordered = OrderedDict((n, params[n]) for n in meta["param_names"])
        return SymbolBlock(exported, ordered, meta["input_sig"])

    def forward(self, *args):
        datas = tuple(
            self._reg_params[f"p{i}"].data()._data
            for i in range(len(self._param_names)))
        arg_datas = [a._data if isinstance(a, NDArray) else a for a in args]
        outs = self._exported.call(datas, *arg_datas)
        wrapped = [NDArray(o) for o in outs]
        return wrapped[0] if len(wrapped) == 1 else tuple(wrapped)


class _LegacySymbolBlock(Block):
    """SymbolBlock over a REFERENCE model-symbol.json: replays the nnvm
    graph through the legacy Symbol DAG. The reference loads such pairs
    via ``SymbolBlock.imports`` (gluon/block.py:1500 there); this is the
    same user contract on the TPU build's replay executor."""

    def __init__(self, sym, params, input_names):
        super().__init__()
        self._sym = sym
        self._input_names = list(input_names)
        self._sym_params = _register_param_arrays(self, params)

    @staticmethod
    def imports(symbol_file, input_names=None, param_file=None):
        from .. import symbol as sym_mod
        from ..ndarray.utils import load

        sym = sym_mod.load(symbol_file)
        raw = load(param_file) if param_file else {}
        if isinstance(raw, list):
            raise MXNetError(
                "reference param file has no names; save with keys "
                "(arg:<name>/aux:<name>) to bind into a SymbolBlock")
        # reference checkpoints prefix arg:/aux: (ndarray.cc Save via
        # mx.model save_checkpoint); strip to the graph's variable names
        params = {k.split(":", 1)[1] if k.startswith(("arg:", "aux:"))
                  else k: v for k, v in raw.items()}
        if input_names is None:
            input_names = ["data"]
        input_names = [str(n) for n in (
            input_names if isinstance(input_names, (list, tuple))
            else [input_names])]
        free = [n for n in sym.list_arguments()
                if n not in params and n not in input_names]
        if free:
            raise MXNetError(
                f"symbol arguments {free} have no parameter in "
                f"{param_file!r} and are not inputs {input_names}")
        return _LegacySymbolBlock(sym, params, input_names)

    def forward(self, *args):
        bindings = {n: p.data() for n, p in self._sym_params.items()}
        for name, arr in zip(self._input_names, args):
            bindings[name] = arr
        return self._sym._eval_with(bindings)
