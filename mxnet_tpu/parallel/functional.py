"""Functionalization + SPMD sharded training step.

The reference's distributed step (SURVEY.md §3.4) is imperative: per-param
``kvstore.pushpull`` after backward, optimizer on worker or server. The
TPU-native step is one compiled SPMD program: params/optimizer state laid out
over a ``jax.sharding.Mesh`` by named rules, batch sharded over ``dp``(+``sp``),
gradients reduced by XLA-inserted collectives over ICI, update fused into the
same executable. This module provides:

* :func:`functionalize` — pure ``fn(params, *args)`` view of any Gluon
  ``Block`` (the deferred-compute trace collapsed onto jax tracing).
* sharding rules — regex → ``PartitionSpec`` tables with an fsdp-style
  default, the declarative replacement for ps-lite key sharding
  (``EncodeDefaultKey``, ``src/kvstore/kvstore_dist.h:621``).
* :class:`ShardedTrainer` — the ``gluon.Trainer`` analog whose ``step`` is a
  single pjit'd (loss, grads, allreduce, update) program.
"""
from __future__ import annotations

import re
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..base import MXNetError
from ..profiler.core import device_scope, host_span


def _jax():
    import jax

    return jax


def _P():
    from jax.sharding import PartitionSpec

    return PartitionSpec


# ---------------------------------------------------------------------------
# functionalize
# ---------------------------------------------------------------------------


def functionalize(block, train_mode=False):
    """Return ``(apply_fn, params)`` for a Gluon block.

    ``apply_fn(params_dict, *args)`` is pure and jittable: it replays
    ``block.forward`` with the dict's arrays bound to the block's parameters
    (the CachedOp trick, ``mxnet_tpu/cachedop.py``). Outputs are raw jax
    arrays. Parameter shapes must already be materialized (run one eager
    forward first for deferred-shape layers).

    When ``train_mode`` and the block holds mutable state (BatchNorm running
    stats — ``grad_req='null'`` parameters), ``apply_fn`` returns
    ``(outputs, new_state_dict)`` so callers can carry state functionally.
    """
    from .. import autograd
    from .. import random as _rng
    from ..cachedop import _ParamBinding
    from ..ndarray.ndarray import NDArray

    params_od = block.collect_params()
    names = list(params_od)
    arrays = [params_od[n].data() for n in names]
    state_names = [n for n in names if params_od[n].grad_req == "null"]

    def apply_fn(param_datas, *arg_datas, rng_key=None):
        import jax

        tracers = [param_datas[n] for n in names]
        wrapped_args = [NDArray(d) for d in arg_datas]
        with _ParamBinding(arrays, tracers):
            if rng_key is None:
                rng_key = _rng.next_key()
            _rng.push_trace_rng(rng_key)
            prev_rec = autograd.set_recording(False)
            prev_train = autograd.set_training(train_mode)
            try:
                outs = block.forward(*wrapped_args)
            finally:
                autograd.set_training(prev_train)
                autograd.set_recording(prev_rec)
                _rng.pop_trace_rng()
            new_state = {n: a._data for n, a in zip(names, arrays)
                         if n in state_names}
        flat, tree = jax.tree_util.tree_flatten(
            outs, is_leaf=lambda x: isinstance(x, NDArray))
        datas = [o._data if isinstance(o, NDArray) else o for o in flat]
        out = jax.tree_util.tree_unflatten(tree, datas)
        if train_mode and state_names:
            return out, new_state
        return out

    params = {n: a._data for n, a in zip(names, arrays)}
    return apply_fn, params


def functionalize_abstract(block):
    """``functionalize`` for compile-only flows: parameters are NEVER
    materialized. Returns ``(apply_fn, {name: jax.ShapeDtypeStruct})``.

    Every uninitialized Parameter must carry a complete static shape (the
    model must be built with explicit ``in_units``/``in_channels``) — it
    gets a 0-element placeholder slot whose only job is identity for the
    trace-time rebinding (``_ParamBinding`` swaps ``_data`` for the
    tracer, so the placeholder's shape is never read). This is what makes
    an 8B-parameter AOT memory proof possible on a laptop-sized host
    (VERDICT r3 item 5): nothing but ShapeDtypeStructs ever exists.
    """
    import jax
    from collections import OrderedDict

    import numpy as _np

    from ..device import cpu
    from ..ndarray.ndarray import NDArray

    params_od = block.collect_params()
    structs = {}
    placeholders = []
    for n, p in params_od.items():
        if getattr(p, "_abstract_placeholder", False):
            # idempotent re-functionalization (second abstract trainer on
            # the same block): lift the poison while we re-capture slots
            p._abstract_placeholder = False
            placeholders.append(p)
        elif p._data is None:
            if not _param_shape_complete(p.shape):
                raise MXNetError(
                    f"functionalize_abstract: parameter {n!r} has "
                    f"incomplete shape {p.shape}; build the model with "
                    "explicit in_units/in_channels so shapes are static")
            import jax.numpy as jnp

            slot = NDArray(jnp.zeros((0,), p.dtype or _np.float32))
            p._data = OrderedDict({cpu(): slot})
            placeholders.append(p)
        structs[n] = jax.ShapeDtypeStruct(
            tuple(p.shape), p.dtype or _np.float32)
    apply_fn, _ = functionalize(block, train_mode=True)
    # poison AFTER functionalize captured the slots: the placeholder must
    # never leak into eager use — Parameter.data()/initialize() raise on
    # it outside a trace (inside a trace the slot is rebound to a tracer)
    for p in placeholders:
        p._abstract_placeholder = True
    return apply_fn, structs


def _param_shape_complete(shape):
    return shape is not None and all(
        isinstance(s, int) and s > 0 for s in shape)


def _cost_analysis_of(compiled):
    """Normalize jax Compiled.cost_analysis() across jax versions (older
    ones return a one-element list)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca or {}


def _scalar_like(x, leaf):
    """An element of the step's float32 ``lrs`` / ``wds`` arrays as a
    scalar of ``leaf``'s dtype, for ``opt._update_raw``. A Python float in
    its place arrived as a weak float32 that took the leaf's dtype
    (float64 -> float32 -> leaf dtype); an element of a float32 array is
    strong and would promote a bf16- or f16-stored parameter and its
    moments to float32, so it takes that rounding here."""
    return x.astype(leaf.dtype)


def _collect_aux_losses(block):
    """Sum `aux_loss` values the forward just set on any sub-block (MoE
    router load-balance terms). Values are tracers from THIS trace — read
    immediately inside the loss closure, never cached."""
    total = None
    stack = [block]
    while stack:
        b = stack.pop()
        aux = getattr(b, "aux_loss", None)
        if aux is not None:
            from ..ndarray.ndarray import NDArray

            a = aux._data if isinstance(aux, NDArray) else aux
            total = a if total is None else total + a
        stack.extend(getattr(b, "_children", {}).values())
    return total


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------


class ShardingRules:
    """Ordered ``(regex, PartitionSpec)`` table mapping param names to specs.

    First match wins; no match → fsdp default (if an ``fsdp`` axis exists:
    shard the largest divisible dim) else fully replicated.
    """

    def __init__(self, rules: Sequence[Tuple[str, object]] = (),
                 default_axis: Optional[str] = "fsdp"):
        self.rules = [(re.compile(pat), spec) for pat, spec in rules]
        self.default_axis = default_axis

    def spec_for(self, name, shape, mesh):
        P = _P()
        for pat, spec in self.rules:
            if pat.search(name):
                # rank-dependent rules (pipeline-stacked leaves) are
                # callables shape -> PartitionSpec
                return spec(shape) if callable(spec) else spec
        if self.default_axis and self.default_axis in mesh.axis_names:
            n = mesh.shape[self.default_axis]
            # largest dim divisible by the fsdp axis size, else replicate
            order = sorted(range(len(shape)), key=lambda i: -shape[i])
            for i in order:
                if shape[i] % n == 0 and shape[i] >= n:
                    parts = [None] * len(shape)
                    parts[i] = self.default_axis
                    return P(*parts)
        return P()

    def shard(self, params: Dict[str, object], mesh):
        """Place a param dict onto the mesh per the rules.

        Copies rather than aliasing: device_put can reuse the source buffer
        for the matching shard, and ShardedTrainer donates these arrays —
        donation must never free a buffer the caller's Block still owns.
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        out = {}
        for name, arr in params.items():
            spec = self.spec_for(name, arr.shape, mesh)
            out[name] = jax.device_put(jnp.array(arr, copy=True),
                                       NamedSharding(mesh, spec))
        return out


# ---------------------------------------------------------------------------
# declarative parallel composition
# ---------------------------------------------------------------------------


class ParallelConfig:
    """Declarative dp×tp(×pp) composition for :class:`ShardedTrainer`.

    ``ParallelConfig(dp=2, tp=2)`` names the mesh the trainer runs over:
    ``dp`` data-parallel groups (the batch axis; ZeRO flat buckets shard
    over it), ``tp``-way tensor parallelism (explicit ``shard_map``
    collectives following the param rules' layouts), and optionally
    ``pp`` pipeline stages (the ``parallel.pipeline`` path; tp and pp do
    not compose yet). ``resilience.elastic`` rebuilds trainers from these
    three integers after chip loss: dp shrinks to the survivor groups
    while the tp/pp extents stay pinned (``parallel.mesh.rebuild_mesh``).
    """

    def __init__(self, dp, tp=1, pp=0):
        self.dp = int(dp)
        self.tp = int(tp)
        self.pp = int(pp)
        if self.dp < 1 or self.tp < 1 or self.pp < 0:
            raise MXNetError(
                f"ParallelConfig needs dp>=1, tp>=1, pp>=0; got "
                f"dp={dp}, tp={tp}, pp={pp}")

    def mesh_shape(self):
        """Axis-name -> extent dict for ``make_mesh``. ``dp`` is always
        present (the batch spec needs its axis even at extent 1); tp/pp
        appear only when actually used."""
        shape = {"dp": self.dp}
        if self.tp > 1:
            shape["tp"] = self.tp
        if self.pp > 0:
            shape["pp"] = self.pp
        return shape

    def __repr__(self):
        return f"ParallelConfig(dp={self.dp}, tp={self.tp}, pp={self.pp})"


# ---------------------------------------------------------------------------
# sharded training step
# ---------------------------------------------------------------------------


class ShardedTrainer:
    """SPMD trainer: the whole step is one compiled XLA program.

    Replaces the reference's step (forward → backward → per-param
    ``kvstore.pushpull`` → per-param optimizer kernels) with a single pjit:
    data parallelism comes from sharding the batch (``batch_spec``), tensor
    parallelism from the param rules, and gradient reduction from XLA's
    automatic collective insertion — serving the role the `Comm`/ps-lite/NCCL
    stack plays in `src/kvstore/` but riding ICI.

    Usage::

        trainer = ShardedTrainer(net, loss_fn, 'sgd',
                                 {'learning_rate': 0.1}, mesh=mesh,
                                 rules=ShardingRules([(r'dense\\d+.weight',
                                                       P('tp', None))]))
        loss = trainer.step(x, y)          # one fused SPMD step
        trainer.sync_to_block()            # write weights back to the Block
    """

    def __init__(self, block, loss_fn, optimizer, optimizer_params=None,
                 mesh=None, rules: Optional[ShardingRules] = None,
                 batch_spec=None, dtype=None, aux_loss_weight=0.01,
                 abstract=False, zero_bucket_mb=None, parallel=None):
        import jax
        from jax.sharding import NamedSharding

        from ..optimizer import optimizer as opt_mod
        from . import mesh as mesh_mod

        self.block = block
        self._abstract = bool(abstract)
        self.loss_fn = loss_fn
        if isinstance(optimizer, str):
            self.optimizer = opt_mod.create(optimizer,
                                            **(optimizer_params or {}))
        else:
            self.optimizer = optimizer
        self._parallel = parallel
        self._use_shard_map = False
        if parallel is not None:
            if parallel.tp > 1 and parallel.pp:
                raise MXNetError(
                    "ParallelConfig: composed tp×pp is not supported yet — "
                    "run tp (shard_map) or pp (pipeline) but not both")
            if mesh is None:
                mesh = mesh_mod.make_mesh(parallel.mesh_shape())
            else:
                for ax, n in parallel.mesh_shape().items():
                    if int(mesh.shape.get(ax, 0)) != n:
                        raise MXNetError(
                            f"ParallelConfig wants {ax}={n} but the given "
                            f"mesh has {ax}={mesh.shape.get(ax, 'absent')}")
            self._use_shard_map = parallel.tp > 1
        self.mesh = mesh if mesh is not None else mesh_mod.get_mesh(create=True)
        if self.mesh is None:
            raise MXNetError("ShardedTrainer needs a device mesh")
        if rules is None:
            # under a declarative ParallelConfig the ZeRO default axis is
            # dp: unruled params bucket over the dp groups while tp/pp
            # layouts come from explicit rules
            rules = ShardingRules(default_axis="dp") \
                if parallel is not None else ShardingRules()
        self.rules = rules
        # AMP policy (amp.py bf16-first): compute casts float params+inputs
        # to `dtype` inside the step; master weights, grads and the update
        # stay fp32 — the multi-precision layout of optimizer_op-inl.h
        self._dtype = dtype
        # blocks exposing `aux_loss` (MoE router balance) contribute
        # weight * sum(aux) to the objective inside the same trace
        self._aux_weight = aux_loss_weight
        P = _P()
        if batch_spec is None:
            batch_spec = P("dp") if "dp" in self.mesh.axis_names else P()
        self.batch_spec = batch_spec

        self._pp_meta = None
        pp_axis = getattr(block, "_pp_axis", None)
        if hasattr(block, "_pp_functionalize") \
                and pp_axis in self.mesh.axis_names:
            if self._use_shard_map:
                raise MXNetError(
                    "ParallelConfig(tp>1) cannot drive a pipelined block: "
                    "the shard_map tp step and the pp stage schedule do "
                    "not compose yet")
            # pipeline-parallel path (parallel/pipeline.PipelinedBlock):
            # body layers arrive stacked as `pp::<rel>` leaves sharded
            # P(pp) — one stage's params per device along the pp axis
            self._apply_fn, params, self._pp_meta = \
                block._pp_functionalize(self.mesh)
            params_od = block.collect_params()
            # trainer-local copy: the injected pp:: rule must not leak
            # into (or stack up in) the caller's ShardingRules object
            rules_copy = ShardingRules(default_axis=self.rules.default_axis)
            rules_copy.rules = [(
                re.compile(r"^pp::"),
                lambda shape, _a=pp_axis: _P()(
                    _a, *([None] * (len(shape) - 1))))] + list(self.rules.rules)
            self.rules = rules_copy
            # frozen leaves (intentionally grad_req='null' weights,
            # Constants) flow through the step as inputs but are returned
            # un-updated — see the skip in the compiled step
            self._frozen_names = set(
                self._pp_meta.pop("__frozen__", set()))
            self._train_names = list(params)
            self._state_names = []
            self.optimizer.param_dict = {
                i: params_od[n]
                for i, n in enumerate(self._train_names)
                if n in params_od}
        else:
            self._frozen_names = set()
            if self._abstract:
                # compile-only mode (VERDICT r3 item 5): params are
                # ShapeDtypeStructs, never materialized — aot_lower() is
                # the only runnable surface
                self._apply_fn, params = functionalize_abstract(block)
            else:
                self._apply_fn, params = functionalize(block,
                                                       train_mode=True)
            params_od = block.collect_params()
            self._train_names = [n for n in params
                                 if params_od[n].grad_req != "null"]
            self._state_names = [n for n in params
                                 if params_od[n].grad_req == "null"]
            # per-param lr_mult/wd_mult flow through the optimizer's
            # param_dict, same wiring as the eager gluon.Trainer
            # (trainer.py) — frozen layers (lr_mult=0) stay frozen under
            # the SPMD step too
            self.optimizer.param_dict = {
                i: params_od[n] for i, n in enumerate(self._train_names)}
        # ZeRO collective bucketing (kvstore.bucketing): opt-in via
        # MXNET_KVSTORE_BUCKET_MB or the zero_bucket_mb argument. Default
        # -rule fsdp params are stored canonically as flat P(axis)-sharded
        # fusion buffers, so the step gathers ONE buffer per bucket
        # instead of one per param (the 1829-gather lowering collapses to
        # a bucket-proportional count). Pack/unpack only ever happens on
        # the host (init, sync_to_block) or on the replicated post-gather
        # array — never on a sharded array in-trace, which would insert
        # resharding collectives.
        self._zb_specs = None
        self._zb_axis = None
        self._zb_names = set()
        self._zb_by_key = {}
        if zero_bucket_mb is None:
            from .. import config as _cfg

            zero_bucket_mb = _cfg.get("MXNET_KVSTORE_BUCKET_MB")
        if zero_bucket_mb and float(zero_bucket_mb) > 0 \
                and self._pp_meta is None:
            self._setup_zero_buckets(params, params_od,
                                     float(zero_bucket_mb))
        if self._zb_specs:
            # optimizer units follow _train_keys: a bucket takes its
            # (uniform, plan-segregated) lr/wd mults from any member
            self._train_keys = ([s.key for s in self._zb_specs]
                                + [n for n in self._train_names
                                   if n not in self._zb_names])
            self.optimizer.param_dict = {
                i: params_od[self._zb_by_key[k].names[0]
                             if k in self._zb_by_key else k]
                for i, k in enumerate(self._train_keys)
                if (self._zb_by_key[k].names[0]
                    if k in self._zb_by_key else k) in params_od}
        else:
            self._train_keys = list(self._train_names)
        # placement: params + optimizer state onto the mesh by rule
        if self._abstract:
            self.params = {
                n: jax.ShapeDtypeStruct(
                    s.shape, s.dtype,
                    sharding=NamedSharding(
                        self.mesh,
                        self.rules.spec_for(n, s.shape, self.mesh)))
                for n, s in params.items() if n not in self._zb_names}
            if self._zb_specs:
                self.params.update(self._zb_abstract_buckets())
            self._opt_states = self._init_opt_states_abstract()
        else:
            self.params = self.rules.shard(
                {n: a for n, a in params.items()
                 if n not in self._zb_names}, self.mesh)
            if self._zb_specs:
                self.params.update(self._zb_pack_buckets(params))
            self._opt_states = self._init_opt_states()
        self._step_jit = None
        self._compiled = {}   # batch-signature -> AOT executable
        self._last_compiled = None
        self._step_flops = None
        self._step_count = 0
        self._key = jax.random.PRNGKey(0)

    # -- ZeRO bucketing ---------------------------------------------------
    def _setup_zero_buckets(self, params, params_od, bucket_mb):
        """Plan flat fusion buffers over the default-rule (fsdp) float
        params. Explicitly-ruled params (tp/pp layouts) keep their
        per-param sharding — replicating them through a bucket gather
        would undo the layout the rule asked for."""
        import jax.numpy as jnp

        from ..kvstore import bucketing as _bkt

        axis = self.rules.default_axis
        if not axis or axis not in self.mesh.axis_names:
            return
        items = []
        for n in self._train_names:
            if n in self._frozen_names:
                continue
            if any(pat.search(n) for pat, _ in self.rules.rules):
                continue
            s = params[n]
            if not jnp.issubdtype(jnp.dtype(s.dtype), jnp.floating):
                continue
            p = params_od.get(n)
            group = (float(getattr(p, "lr_mult", 1.0)),
                     float(getattr(p, "wd_mult", 1.0)))
            items.append((n, tuple(s.shape), jnp.dtype(s.dtype), group))
        if not items:
            return
        opt = self.optimizer
        if not (getattr(opt, "fused_safe", True)
                and getattr(opt, "elementwise", True)):
            raise MXNetError(
                f"ZeRO bucketing needs an elementwise optimizer: "
                f"{type(opt).__name__} keeps per-tensor norms or python "
                "-side state, so updating a flat fusion buffer would "
                "change its math — unset MXNET_KVSTORE_BUCKET_MB (or "
                "zero_bucket_mb) for this optimizer")
        n_shards = int(self.mesh.shape[axis])
        self._zb_specs = _bkt.GradBucketer(
            bucket_mb, pad_multiple=n_shards).plan(items)
        self._zb_axis = axis
        self._zb_names = {n for s in self._zb_specs for n in s.names}
        self._zb_by_key = {s.key: s for s in self._zb_specs}

    def _spec_of(self, key, shape):
        """PartitionSpec for a ``self.params`` key: flat buckets shard
        P(axis) (their padded totals divide evenly by construction);
        everything else goes through the rule table."""
        if key in self._zb_by_key:
            return _P()(self._zb_axis)
        return self.rules.spec_for(key, shape, self.mesh)

    def _zb_sharding(self):
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, _P()(self._zb_axis))

    def _zb_abstract_buckets(self):
        import jax

        sh = self._zb_sharding()
        return {s.key: jax.ShapeDtypeStruct((s.total,), s.dtype,
                                            sharding=sh)
                for s in self._zb_specs}

    def _zb_pack_buckets(self, params):
        """Host-side pack of the block's materialized params into the
        sharded flat buffers (init-time only)."""
        import jax
        import numpy as onp

        sh = self._zb_sharding()
        out = {}
        for spec in self._zb_specs:
            flat = onp.zeros((spec.total,), dtype=spec.dtype)
            for n, off, size, shape in spec.items():
                flat[off:off + size] = onp.asarray(
                    jax.device_get(params[n])).reshape(-1)
            out[spec.key] = jax.device_put(flat, sh)
        return out

    # -- optimizer state --------------------------------------------------
    def _init_opt_states(self):
        import jax
        from jax.sharding import NamedSharding

        from ..gluon.trainer import _flatten_state
        from ..ndarray.ndarray import NDArray

        states = {}
        for i, n in enumerate(self._train_keys):
            if n in self._frozen_names:
                # frozen leaves are never updated: no momentum/variance
                # buffers (they'd waste 2x the frozen size in HBM)
                states[n] = ()
                continue
            w = NDArray(self.params[n])
            st = self.optimizer.create_state_multi_precision(i, w)
            flat = [s._data for s in _flatten_state(st)]
            spec = self._spec_of(n, self.params[n].shape)
            placed = []
            for s in flat:
                sh = (NamedSharding(self.mesh, spec) if s.shape == w.shape
                      else NamedSharding(self.mesh, _P()))
                placed.append(jax.device_put(s, sh))
            states[n] = tuple(placed)
        return states

    def _init_opt_states_abstract(self):
        """Optimizer-state ShapeDtypeStructs via ``jax.eval_shape`` over
        ``create_state_multi_precision`` — same shapes/dtypes the real
        path materializes, zero bytes allocated."""
        import jax
        from jax.sharding import NamedSharding

        from ..gluon.trainer import _flatten_state
        from ..ndarray.ndarray import NDArray

        P = _P()
        states = {}
        for i, n in enumerate(self._train_keys):
            w_struct = self.params[n]

            def mk(i=i, w_struct=w_struct):
                import jax.numpy as jnp

                w = NDArray(jnp.zeros(w_struct.shape, w_struct.dtype))
                st = self.optimizer.create_state_multi_precision(i, w)
                return tuple(s._data for s in _flatten_state(st))

            flat = jax.eval_shape(mk)
            spec = self._spec_of(n, w_struct.shape)
            states[n] = tuple(
                jax.ShapeDtypeStruct(
                    s.shape, s.dtype,
                    sharding=NamedSharding(
                        self.mesh,
                        spec if tuple(s.shape) == tuple(w_struct.shape)
                        else P()))
                for s in flat)
        return states

    def aot_lowered(self, batch_struct, labels_struct):
        """Lowered-but-NOT-compiled step (StableHLO) from
        ShapeDtypeStructs — pre-optimization inspection (tests check
        e.g. that ``layer_barrier`` threaded its optimization_barriers
        into the trace; backends may fold them after scheduling, so the
        compiled text cannot pin them)."""
        import jax
        import jax.numpy as jnp

        if self._step_jit is None:
            self._build_step()
        lrs, wds = self._optimizer_scalars()
        key_struct = jax.ShapeDtypeStruct(self._key.shape, self._key.dtype)
        train = {n: self.params[n] for n in self._train_keys}
        state = {n: self.params[n] for n in self._state_names}
        args = (train, state, self._opt_states, batch_struct, labels_struct,
                key_struct, lrs, wds, 1)
        return self._step_jit.lower(*args)

    def aot_lower(self, batch_struct, labels_struct):
        """AOT-compile ONE SPMD training step from ShapeDtypeStructs —
        the compile/memory-plan-only proof path for configs too big to
        materialize on the host (``abstract=True`` trainers; Llama-3-8B
        on a virtual v5e-8 mesh). Returns the jax ``Compiled`` object:
        ``.memory_analysis()`` has the per-device argument/temp bytes the
        fit assertion reads, ``.as_text()`` the HLO.
        """
        compiled = self.aot_lowered(batch_struct, labels_struct).compile()
        self._last_compiled = compiled
        self._step_flops = _cost_analysis_of(compiled).get("flops")
        return compiled

    # -- the compiled step ------------------------------------------------
    def _build_step(self):
        if self._use_shard_map:
            return self._build_step_shard_map()
        return self._build_step_pjit()

    def _build_step_shard_map(self):
        """Explicit-collective step for composed dp×tp meshes: the whole
        step runs under ``shard_map``, so every array is its per-device
        block and every cross-device exchange is written out instead of
        left to the SPMD partitioner.

        The math mirrors the pjit path exactly:

        * the local loss is ``pmean``-ed over ALL mesh axes — over dp
          that is the global batch mean; over tp it is value-identical
          (every tp peer sees the same gathered params and the same
          batch block) but it is what makes the tiled ``all_gather``
          transpose (a psum_scatter over tp) come out unscaled;
        * each param's grad is then ``psum``-ed over exactly the axes
          its PartitionSpec does NOT mention — dp for tp layouts, tp for
          dp-sharded ZeRO buckets, both for replicated params — and
          divided by ``mesh.size`` (every device seeds cotangent 1 on
          its replicated loss), after which the local grad IS the exact
          global-batch-mean grad of that slice;
        * optimizer updates run on the local slices (elementwise
          optimizers only), so sharded state never materializes whole.
        """
        import jax
        import jax.numpy as jnp
        from jax.experimental.shard_map import shard_map
        from jax.sharding import NamedSharding

        opt = self.optimizer
        if not (getattr(opt, "fused_safe", True)
                and getattr(opt, "elementwise", True)):
            raise MXNetError(
                "ParallelConfig(tp>1) runs optimizer updates on local "
                f"shards, which needs an elementwise optimizer: "
                f"{type(opt).__name__} keeps per-tensor norms or python"
                "-side state, so updating slices would change its math")
        mesh = self.mesh
        P = _P()
        all_axes = tuple(mesh.axis_names)
        mesh_n = int(mesh.size)
        apply_fn = self._apply_fn
        loss_fn = self.loss_fn
        train_names = self._train_keys
        state_names = self._state_names
        has_state = bool(state_names)
        zb_specs = self._zb_specs
        zb_keys = frozenset(self._zb_by_key)
        spec_of = {n: self._spec_of(n, self.params[n].shape)
                   for n in self.params}
        amp_dtype = self._dtype

        def cast_amp(x):
            if amp_dtype is not None and jnp.issubdtype(x.dtype,
                                                        jnp.floating):
                return x.astype(amp_dtype)
            return x

        def axes_of(spec):
            out = []
            for entry in spec:
                if entry is None:
                    continue
                out.extend(entry if isinstance(entry, (tuple, list))
                           else (entry,))
            return tuple(out)

        def gather_full(x, spec):
            # local block -> full tensor; tiled all_gather per sharded
            # dim is differentiable (its transpose is psum_scatter)
            for dim, entry in enumerate(spec):
                if entry is None:
                    continue
                for ax in (entry if isinstance(entry, (tuple, list))
                           else (entry,)):
                    x = jax.lax.all_gather(x, ax, axis=dim, tiled=True)
            return x

        def scatter_local(x, spec):
            for dim, entry in enumerate(spec):
                if entry is None:
                    continue
                for ax in (entry if isinstance(entry, (tuple, list))
                           else (entry,)):
                    size = x.shape[dim] // int(mesh.shape[ax])
                    x = jax.lax.dynamic_slice_in_dim(
                        x, jax.lax.axis_index(ax) * size, size, axis=dim)
            return x

        def local_loss(train_params, state_params, batch, labels, key):
            full = {}
            if zb_specs:
                # ZeRO per dp-group: ONE all_gather per flat bucket
                # rebuilds the replicated buffer; per-param views are
                # static slices of it — the pjit path's bucket
                # discipline with the collective written out
                for spec in zb_specs:
                    flat = gather_full(train_params[spec.key],
                                       spec_of[spec.key])
                    for pn, off, size, shape in spec.items():
                        full[pn] = jax.lax.slice_in_dim(
                            flat, off, off + size).reshape(shape)
            for pn, a in train_params.items():
                if pn not in zb_keys:
                    full[pn] = gather_full(a, spec_of[pn])
            params = dict(full)
            for sn, a in state_params.items():
                params[sn] = gather_full(a, spec_of[sn])
            if amp_dtype is not None:
                params = {n: cast_amp(a) for n, a in params.items()}
                batch = jax.tree_util.tree_map(cast_amp, batch)
            batch = batch if isinstance(batch, tuple) else (batch,)
            r = apply_fn(params, *batch, rng_key=key)
            if has_state:
                out, new_state = r
            else:
                out, new_state = r, {}
            from ..ndarray.ndarray import NDArray

            out_nd = jax.tree_util.tree_map(
                lambda x: x if isinstance(x, NDArray) else NDArray(x), out,
                is_leaf=lambda x: isinstance(x, NDArray))
            lbl_nd = jax.tree_util.tree_map(NDArray, labels)
            with device_scope("loss"):
                loss = loss_fn(out_nd, lbl_nd)
            ldata = loss._data if isinstance(loss, NDArray) else loss
            aux = _collect_aux_losses(self.block)
            if aux is not None:
                ldata = ldata + self._aux_weight * aux
            if amp_dtype is not None:
                new_state = {n: v.astype(state_params[n].dtype)
                             for n, v in new_state.items()}
            return jax.lax.pmean(
                jnp.mean(ldata.astype(jnp.float32)), all_axes), new_state

        def step(train_params, state_params, opt_states, batch, labels,
                 key, lrs, wds, t):
            with device_scope("train_step.grad"):
                (loss, new_state), grads = jax.value_and_grad(
                    local_loss, has_aux=True)(train_params, state_params,
                                              batch, labels, key)
            new_train = {}
            new_opt = {}
            frozen = self._frozen_names
            for i, n in enumerate(train_names):
                if n in frozen:
                    new_train[n] = train_params[n]
                    new_opt[n] = opt_states[n]
                    continue
                g = grads[n].astype(train_params[n].dtype)
                missing = tuple(a for a in all_axes
                                if a not in axes_of(spec_of[n]))
                if missing:
                    g = jax.lax.psum(g, missing)
                # every device seeds cotangent 1 on its own (replicated)
                # pmean'd loss, so after the psum the grad is mesh.size×
                # the global-batch-mean grad — one normalization for all
                # layouts (sharded axes already collapse in the backward,
                # missing axes in the psum above)
                g = g / float(mesh_n)
                with device_scope("train_step.optimizer"):
                    g = opt._prep_grad(g)
                    p_new, s_new = opt._update_raw(
                        train_params[n], g, opt_states[n],
                        _scalar_like(lrs[i], train_params[n]),
                        _scalar_like(wds[i], train_params[n]), t)
                new_train[n] = p_new
                new_opt[n] = tuple(s_new) \
                    if isinstance(s_new, (list, tuple)) else (s_new,)
            # mutable block state (BN running stats): average the
            # per-shard updates, keep only the local block of the result
            new_state = {n: scatter_local(jax.lax.pmean(v, all_axes),
                                          spec_of[n])
                         for n, v in new_state.items()}
            return new_train, new_state, new_opt, loss

        train_in = {n: spec_of[n] for n in train_names}
        state_in = {n: spec_of[n] for n in state_names}
        opt_in = {n: tuple(s.sharding.spec for s in self._opt_states[n])
                  for n in train_names}
        sm = shard_map(
            step, mesh=mesh,
            in_specs=(train_in, state_in, opt_in, self.batch_spec,
                      self.batch_spec, P(), P(), P(), P()),
            out_specs=(train_in, state_in, opt_in, P()),
            check_rep=False)
        train_shard = {n: NamedSharding(mesh, spec_of[n])
                       for n in train_names}
        state_shard = {n: NamedSharding(mesh, spec_of[n])
                       for n in state_names}
        opt_shard = {
            n: tuple(NamedSharding(mesh, s.sharding.spec)
                     for s in self._opt_states[n])
            for n in train_names}
        batch_shard = NamedSharding(mesh, self.batch_spec)
        repl = NamedSharding(mesh, P())
        self._step_jit = jax.jit(
            sm,
            in_shardings=(train_shard, state_shard, opt_shard, batch_shard,
                          batch_shard, repl, None, None, None),
            out_shardings=(train_shard, state_shard, opt_shard, repl),
            donate_argnums=(0, 1, 2),
        )
        stacked_spec = P(None, *self.batch_spec)
        stacked_shard = NamedSharding(mesh, stacked_spec)

        def step_n_fn(train_params, state_params, opt_states, d_all, l_all,
                      key, lrs, wds, t0):
            def body(carry, xs):
                tr, st, op, t, k = carry
                k, sub = jax.random.split(k)
                d, l = xs
                ntr, nst, nop, loss = sm(tr, st, op, d, l, sub, lrs, wds,
                                         t)
                return (ntr, nst, nop, t + 1, k), loss

            (tr, st, op, _, _), losses = jax.lax.scan(
                body, (train_params, state_params, opt_states, t0, key),
                (d_all, l_all))
            return tr, st, op, losses

        self._stepn_fn = step_n_fn
        self._stepn_jit = jax.jit(
            step_n_fn,
            in_shardings=(train_shard, state_shard, opt_shard,
                          stacked_shard, stacked_shard, repl, None, None,
                          None),
            out_shardings=(train_shard, state_shard, opt_shard, repl),
            donate_argnums=(0, 1, 2),
        )

    def _build_step_pjit(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        apply_fn = self._apply_fn
        loss_fn = self.loss_fn
        opt = self.optimizer
        train_names = self._train_keys
        state_names = self._state_names
        has_state = bool(state_names)
        zb_specs = self._zb_specs
        zb_keys = frozenset(self._zb_by_key)
        repl_shard = NamedSharding(self.mesh, _P()())

        amp_dtype = self._dtype
        # inner-AMP protocol: the block casts params at use inside its own
        # remat boundary (LlamaModel.supports_inner_amp) — the trainer
        # must NOT pre-cast the tree, or a full extra low-precision param
        # copy stays live across the step
        inner_amp = (amp_dtype is not None
                     and getattr(self.block, "supports_inner_amp", False)
                     and getattr(self.block, "_remat", False))
        inner_protocol = getattr(self.block, "supports_inner_amp", False)

        def cast_amp(x):
            if amp_dtype is not None and jnp.issubdtype(x.dtype, jnp.floating):
                return x.astype(amp_dtype)
            return x

        def loss_of(train_params, state_params, batch, labels, key):
            if zb_specs:
                # flat-bucket ZeRO: ONE all-gather per bucket (the
                # constraint to replicated), then static slices of the
                # replicated buffer rebuild the per-param views. Buckets
                # are gathered front-first (plan order); XLA's latency
                # -hiding scheduler prefetches bucket k+1 behind the
                # layers consuming bucket k. The per-param gathers the
                # partitioner would otherwise insert at every use site
                # collapse to len(zb_specs) collectives.
                full = {}
                for spec in zb_specs:
                    flat = jax.lax.with_sharding_constraint(
                        train_params[spec.key], repl_shard)
                    for pn, off, size, shape in spec.items():
                        full[pn] = jax.lax.slice_in_dim(
                            flat, off, off + size).reshape(shape)
                for pn, a in train_params.items():
                    if pn not in zb_keys:
                        full[pn] = a
                train_params = full
            params = dict(train_params)
            params.update(state_params)
            if amp_dtype is not None and not inner_amp:
                # cast-for-compute: autodiff through the cast hands back
                # fp32 grads against the fp32 master params
                params = {n: cast_amp(a) for n, a in params.items()}
                batch = jax.tree_util.tree_map(cast_amp, batch)
            elif inner_amp:
                batch = jax.tree_util.tree_map(cast_amp, batch)
            batch = batch if isinstance(batch, tuple) else (batch,)
            if inner_protocol:
                # set for THIS trace only (block.forward reads it at
                # trace time) and restore after: a persistent write
                # would leak this trainer's dtype into a sibling
                # trainer's later re-trace on the same block
                prev_amp = getattr(self.block, "_amp_dtype", None)
                self.block._amp_dtype = amp_dtype if inner_amp else None
            try:
                r = apply_fn(params, *batch, rng_key=key)
            finally:
                if inner_protocol:
                    self.block._amp_dtype = prev_amp
            if has_state:
                out, new_state = r
            else:
                out, new_state = r, {}
            from ..ndarray.ndarray import NDArray

            # outputs may be a pytree (e.g. BERT's (mlm_scores, nsp_scores));
            # hand the loss_fn NDArray leaves with the structure intact
            out_nd = jax.tree_util.tree_map(
                lambda x: x if isinstance(x, NDArray) else NDArray(x), out,
                is_leaf=lambda x: isinstance(x, NDArray))
            lbl_nd = jax.tree_util.tree_map(NDArray, labels)
            with device_scope("loss"):
                loss = loss_fn(out_nd, lbl_nd)
            ldata = loss._data if isinstance(loss, NDArray) else loss
            aux = _collect_aux_losses(self.block)
            if aux is not None:
                ldata = ldata + self._aux_weight * aux
            if amp_dtype is not None:
                # mutable state (BN running stats) flows back at the master
                # dtype so the AOT-compiled step signature stays stable
                new_state = {
                    n: v.astype(state_params[n].dtype)
                    for n, v in new_state.items()}
            return jnp.mean(ldata.astype(jnp.float32)), new_state

        mesh = self.mesh
        p_shard = {
            n: NamedSharding(mesh, self._spec_of(n, self.params[n].shape))
            for n in self.params
        }
        train_shard = {n: p_shard[n] for n in train_names}
        state_shard = {n: p_shard[n] for n in state_names}

        def step(train_params, state_params, opt_states, batch, labels, key,
                 lrs, wds, t):
            with device_scope("train_step.grad"):
                (loss, new_state), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(train_params, state_params,
                                           batch, labels, key)
            new_train = {}
            new_opt = {}
            frozen = self._frozen_names
            for i, n in enumerate(train_names):
                if n in frozen:
                    # frozen leaf: participates in forward/backward but
                    # the optimizer never moves it
                    new_train[n] = train_params[n]
                    new_opt[n] = opt_states[n]
                    continue
                g = grads[n].astype(train_params[n].dtype)
                # ZeRO discipline: pin the grad to the PARAM's sharding
                # before the update. For fsdp-sharded params this makes the
                # SPMD partitioner emit a reduce-scatter (each device gets
                # only its shard's summed grad) and run the optimizer on
                # 1/N of the state — gather-for-compute (XLA all-gathers
                # the weight at its use sites) / scatter-for-update.
                g = jax.lax.with_sharding_constraint(g, train_shard[n])
                with device_scope("train_step.optimizer"):
                    g = opt._prep_grad(g)
                    p_new, s_new = opt._update_raw(
                        train_params[n], g, opt_states[n],
                        _scalar_like(lrs[i], train_params[n]),
                        _scalar_like(wds[i], train_params[n]), t)
                new_train[n] = p_new
                new_opt[n] = tuple(s_new) if isinstance(s_new, (list, tuple)) \
                    else (s_new,)
            return new_train, new_state, new_opt, loss
        opt_shard = {
            n: tuple(
                NamedSharding(mesh, s.sharding.spec)
                for s in self._opt_states[n])
            for n in train_names
        }
        # a single NamedSharding acts as a pytree prefix: it applies to every
        # leaf of the batch/labels trees (tuple inputs shard dim 0 over dp)
        batch_shard = NamedSharding(mesh, self.batch_spec)
        repl = NamedSharding(mesh, _P()())
        self._step_jit = jax.jit(
            step,
            in_shardings=(train_shard, state_shard, opt_shard, batch_shard,
                          batch_shard, repl, None, None, None),
            out_shardings=(train_shard, state_shard, opt_shard, repl),
            donate_argnums=(0, 1, 2),
        )
        # fused multi-step (step_n): lax.scan over stacked microbatches —
        # the reference's bulk-exec segments (engine.h:311-317) done the
        # trace-once way: one dispatch runs N whole training steps
        stacked_spec = _P()(None, *self.batch_spec)
        stacked_shard = NamedSharding(mesh, stacked_spec)

        def step_n_fn(train_params, state_params, opt_states, d_all, l_all,
                      key, lrs, wds, t0):
            def body(carry, xs):
                tr, st, op, t, k = carry
                k, sub = jax.random.split(k)
                d, l = xs
                ntr, nst, nop, loss = step(tr, st, op, d, l, sub, lrs, wds,
                                           t)
                return (ntr, nst, nop, t + 1, k), loss

            (tr, st, op, _, _), losses = jax.lax.scan(
                body, (train_params, state_params, opt_states, t0, key),
                (d_all, l_all))
            return tr, st, op, losses

        self._stepn_fn = step_n_fn
        self._stepn_jit = jax.jit(
            step_n_fn,
            in_shardings=(train_shard, state_shard, opt_shard,
                          stacked_shard, stacked_shard, repl, None, None,
                          None),
            out_shardings=(train_shard, state_shard, opt_shard, repl),
            donate_argnums=(0, 1, 2),
        )

    @property
    def step_flops(self):
        """XLA cost-analysis FLOPs of one compiled step (None before the
        first step). The MFU numerator bench.py divides by chip peak."""
        return self._step_flops

    @property
    def step_hlo(self):
        """Compiled HLO text of the step (None before the first step);
        tests assert collective choice (all-gather/reduce-scatter) on it."""
        return self._last_compiled.as_text() \
            if self._last_compiled is not None else None

    @property
    def step_cost_analysis(self):
        """XLA cost analysis dict of the last executed step ({} before the
        first step): 'flops', 'bytes accessed', ... — the roofline inputs
        bench.py reads (flops/bytes = arithmetic intensity)."""
        if self._last_compiled is None:
            return {}
        return _cost_analysis_of(self._last_compiled)

    def device_memory_bytes(self):
        """Per-device bytes held by params + optimizer state (shard 0):
        the ZeRO memory claim tests assert this drops ~N× under fsdp."""
        total = 0
        for arr in list(self.params.values()) + [
                s for st in self._opt_states.values() for s in st]:
            total += arr.addressable_shards[0].data.nbytes
        return total

    # -- shared host-side step machinery ----------------------------------
    def _unwrap_batch(self, data, labels, spec=None):
        import jax
        from jax.sharding import NamedSharding

        from ..ndarray.ndarray import NDArray

        sh = NamedSharding(self.mesh,
                           spec if spec is not None else self.batch_spec)

        def raw(x):
            d = x._data if isinstance(x, NDArray) else x
            if isinstance(d, jax.Array) and getattr(d, "_committed", False):
                # eager NDArrays sit committed on their ctx device; the
                # step's in_shardings contract wants mesh-laid-out (or
                # uncommitted) inputs — re-place instead of erroring
                d = jax.device_put(d, sh)
            return d

        d = tuple(raw(x) for x in data) if isinstance(data, (list, tuple)) \
            else raw(data)
        l = jax.tree_util.tree_map(raw, labels,
                                   is_leaf=lambda x: isinstance(x, NDArray))
        return d, l

    def _optimizer_scalars(self):
        """Every train key's learning rate and weight decay, asked of the
        optimizer now (schedulers, ``set_learning_rate``, ``lr_mult`` /
        ``wd_mult``), as two float32 host arrays of shape
        ``(len(self._train_keys),)``: the compiled step takes two small
        transfers a call where a tuple of Python floats cost one a
        scalar, on every chip of the mesh."""
        import numpy as onp

        n_train = len(self._train_keys)
        opt = self.optimizer
        return (onp.fromiter((opt._get_lr(i) for i in range(n_train)),
                             onp.float32, n_train),
                onp.fromiter((opt._get_wd(i) for i in range(n_train)),
                             onp.float32, n_train))

    def _advance_optimizer(self, n):
        """Advance step/update counts by n; return (lrs, wds, t_first)."""
        t_first = self._step_count + 1
        self._step_count += n
        for i in range(len(self._train_keys)):
            self.optimizer._index_update_count[i] = self._step_count
        lrs, wds = self._optimizer_scalars()
        return lrs, wds, t_first

    def _advance_and_run(self, jit_fn, sig_head, d, l, n):
        """What ``step`` and ``step_n`` share once the batch is placed:
        the optimizer's scalars, the RNG split, the argument tuple with
        its signature, and the compiled call."""
        import jax

        with host_span("mxnet_tpu.trainer.optimizer_scalars",
                       scalars=2 * len(self._train_keys)):
            lrs, wds, t = self._advance_optimizer(n)
        with host_span("mxnet_tpu.trainer.rng_split"):
            self._key, sub = jax.random.split(self._key)
        with host_span("mxnet_tpu.trainer.gather_args") as span:
            train = {k: self.params[k] for k in self._train_keys}
            state = {k: self.params[k] for k in self._state_names}
            args = (train, state, self._opt_states, d, l, sub, lrs, wds, t)
            sig = sig_head + tuple(
                (x.shape, str(x.dtype))
                for x in jax.tree_util.tree_leaves((d, l)))
            if span.is_enabled():   # a profiler session: count for it
                leaves = jax.tree_util.tree_leaves(args)
                span.set_metadata(
                    leaves=len(leaves),
                    host_leaves=sum(not isinstance(x, jax.Array)
                                    for x in leaves))
        return self._run_compiled(sig, jit_fn, args)

    def _run_compiled(self, sig, jit_fn, args):
        """AOT-compile once per signature (a partial final batch gets its
        own executable): the compiled callable skips per-call signature
        matching and exposes XLA's cost analysis — the exact per-step
        FLOPs source for MFU reporting. Updates params/opt state from
        the executable's first three outputs; returns the fourth (the
        loss or losses) as an NDArray."""
        from ..ndarray.ndarray import NDArray

        if self._abstract:
            raise MXNetError(
                "this ShardedTrainer was built with abstract=True "
                "(compile-only): params were never materialized — use "
                "aot_lower() for the memory proof, or rebuild without "
                "abstract to train")
        hit = self._compiled.get(sig)
        if hit is None:
            with host_span("mxnet_tpu.trainer.compile"):
                compiled = jit_fn.lower(*args).compile()
                flops = _cost_analysis_of(compiled).get("flops")
                self._compiled[sig] = (compiled, flops)
        else:
            compiled, flops = hit
        # refresh per call so the property tracks the LAST executed
        # program (scan bodies are counted once by XLA, so this stays a
        # per-step figure even for step_n windows)
        self._step_flops = flops
        self._last_compiled = compiled
        with host_span("mxnet_tpu.trainer.call"):
            new_train, new_state, new_opt, out = compiled(*args)
        with host_span("mxnet_tpu.trainer.commit"):
            self.params.update(new_train)
            self.params.update(new_state)
            self._opt_states = new_opt
            return NDArray(out)

    def step(self, data, labels):
        """Run one SPMD training step; returns the scalar loss as an
        NDArray (async — reading/printing it syncs, dispatch does not).

        ``data`` may be a single array or a tuple of arrays (multi-input
        models, e.g. (tokens, segments) for BERT)."""
        from ..resilience import faults as _faults

        # chip-loss injection surface for composed-mesh elasticity: a
        # `chip_loss` rule here (optionally device-addressed) raises
        # BEFORE the compiled SPMD step dispatches, exactly where a real
        # ICI/chip failure would surface as a poisoned dispatch
        _faults.fault_point("trainer:sharded_step",
                            {"step": self._step_count})
        with host_span("mxnet_tpu.trainer.step",
                       step=self._step_count + 1, n=1):
            if self._step_jit is None:
                self._build_step()
            with host_span("mxnet_tpu.trainer.unwrap"):
                d, l = self._unwrap_batch(data, labels)
            return self._advance_and_run(self._step_jit, (), d, l, 1)

    def step_n(self, data, labels, num_steps=None):
        """Run MANY SPMD training steps in ONE compiled dispatch.

        ``data``/``labels`` leaves are stacked per-step on a leading axis:
        shape ``(num_steps, B, ...)``. Returns the per-step losses as an
        NDArray of shape (num_steps,). The learning rate and weight decay
        are held constant across the fused window (schedulers advance
        between calls); ``lax.scan`` carries params/optimizer state, so
        host dispatch cost is paid once per window instead of per step.
        """
        import jax

        with host_span("mxnet_tpu.trainer.step",
                       step=self._step_count + 1) as span:
            if self._step_jit is None:
                self._build_step()
            with host_span("mxnet_tpu.trainer.unwrap"):
                d, l = self._unwrap_batch(
                    data, labels, spec=_P()(None, *self.batch_spec))
                avail = jax.tree_util.tree_leaves(d)[0].shape[0]
                n = avail if num_steps is None else int(num_steps)
                if n < 1 or n > avail:
                    raise MXNetError(
                        f"step_n: num_steps={num_steps} but the stacked "
                        f"leading axis holds {avail} step batches")
                if avail != n:
                    # scan runs the whole leading axis: slice so
                    # bookkeeping (update counts, lr schedule, FLOPs)
                    # matches execution
                    d = jax.tree_util.tree_map(lambda x: x[:n], d)
                    l = jax.tree_util.tree_map(lambda x: x[:n], l)
            span.set_metadata(n=n)
            return self._advance_and_run(self._stepn_jit, ("step_n", n),
                                         d, l, n)

    def save_checkpoint(self, path):
        """Checkpoint the FULL training state — params, optimizer state,
        step count — for exact resume (the SPMD analog of
        ``Trainer.save_states`` + ``save_parameters``; reference
        ``gluon/trainer.py:482``). Sharded arrays are gathered to host;
        ``load_checkpoint`` re-places them with the live shardings."""
        import pickle

        import jax

        blob = {
            "params": {n: jax.device_get(a)
                       for n, a in self.params.items()},
            "opt_states": {n: tuple(jax.device_get(s) for s in st)
                           for n, st in self._opt_states.items()},
            "step_count": self._step_count,
            # the dropout/RNG stream position: without it a resumed run
            # would replay earlier steps' masks
            "rng_key": jax.device_get(self._key),
        }
        with open(path, "wb") as f:
            pickle.dump(blob, f)

    def load_checkpoint(self, path):
        """Restore a ``save_checkpoint`` blob onto the CURRENT mesh: each
        array is device_put with the trainer's live sharding, so resume
        works across process restarts (and across mesh shapes, as long as
        the rules still divide the shapes)."""
        import pickle

        import jax

        with open(path, "rb") as f:
            blob = pickle.load(f)
        if set(blob["params"]) != set(self.params):
            raise MXNetError(
                "checkpoint params do not match this trainer's params: "
                f"missing {set(self.params) - set(blob['params'])}, "
                f"unexpected {set(blob['params']) - set(self.params)}")
        # optimizer-state structure must line up with THIS trainer's
        # optimizer (same names, same per-param arity/shapes) — a
        # mismatched load (adam ckpt into sgd trainer) must fail here
        # with a clear error, not as a tracing failure steps later
        if set(blob["opt_states"]) != set(self._opt_states):
            raise MXNetError(
                "checkpoint optimizer state does not match this trainer: "
                f"missing {set(self._opt_states) - set(blob['opt_states'])}, "
                f"unexpected {set(blob['opt_states']) - set(self._opt_states)}")
        for n, st in blob["opt_states"].items():
            live = self._opt_states[n]
            if len(st) != len(live) or any(
                    tuple(h.shape) != tuple(s.shape)
                    for h, s in zip(st, live)):
                raise MXNetError(
                    f"checkpoint optimizer state for {n!r} has structure "
                    f"{[tuple(h.shape) for h in st]} but this trainer's "
                    f"optimizer ({type(self.optimizer).__name__}) expects "
                    f"{[tuple(s.shape) for s in live]}")
        for n, host in blob["params"].items():
            self.params[n] = jax.device_put(host, self.params[n].sharding)
        self._opt_states = {
            n: tuple(jax.device_put(h, live_s.sharding)
                     for h, live_s in zip(st, self._opt_states[n]))
            for n, st in blob["opt_states"].items()}
        self._step_count = int(blob["step_count"])
        if "rng_key" in blob:
            self._key = jax.device_put(blob["rng_key"])
        for i in range(len(self._train_keys)):
            self.optimizer._index_update_count[i] = self._step_count

    # -- portable state (elastic rebuild-and-reshard) ---------------------
    def checkpoint_layouts(self):
        """Tensor-split layout of every explicitly tp/pp-sharded param:
        ``{name: {"axis", "dim", "parts"}}`` — what
        ``resilience.checkpoint.save_sharded_checkpoint(layouts=...)``
        records in its manifest so a resume under ANY mesh reassembles
        the full tensor before re-laying it out. dp/fsdp sharding is
        ownership, not layout, and is not recorded."""
        out = {}
        for n in self.params:
            if n in self._zb_by_key:
                continue
            spec = self._spec_of(n, self.params[n].shape)
            for dim, entry in enumerate(spec):
                if entry is None:
                    continue
                for ax in (entry if isinstance(entry, (tuple, list))
                           else (entry,)):
                    if ax in ("dp", "fsdp"):
                        continue
                    if n in out:
                        raise MXNetError(
                            f"checkpoint_layouts: {n!r} is sharded over "
                            "more than one non-dp axis/dim — multi-axis "
                            "tensor layouts cannot be checkpointed yet")
                    out[n] = {"axis": ax, "dim": dim,
                              "parts": int(self.mesh.shape[ax])}
        return out

    def export_state(self):
        """Gather the FULL training state to host, bucket-free: whole
        numpy tensors per param (flat ZeRO buckets unpacked back into
        their member views, padding dropped), optimizer state re-keyed
        per param the same way, plus step count and RNG position. The
        result is mesh-independent: :meth:`import_state` repacks it under
        the destination trainer's own bucket plan and shardings — what
        lets an elastic resume cross dp extents."""
        import jax
        import numpy as onp

        params = {}
        opt_states = {}
        for n, a in self.params.items():
            if n not in self._zb_by_key:
                params[n] = onp.asarray(jax.device_get(a))
        for n in self._train_keys:
            st = tuple(onp.asarray(jax.device_get(s))
                       for s in self._opt_states[n])
            spec = self._zb_by_key.get(n)
            if spec is None:
                opt_states[n] = st
                continue
            flat = onp.asarray(jax.device_get(self.params[n]))
            for pn, off, size, shape in spec.items():
                params[pn] = flat[off:off + size].reshape(shape).copy()
                # per-element state (momentum) slices like the weight;
                # anything else (scalars) replicates per member
                opt_states[pn] = tuple(
                    s[off:off + size].reshape(shape).copy()
                    if s.shape == flat.shape else s.copy() for s in st)
        return {"params": params, "opt_states": opt_states,
                "step_count": self._step_count,
                "rng_key": onp.asarray(jax.device_get(self._key))}

    def _zb_repack(self, spec, values, dtype, what):
        """Zero-padded flat repack of per-member host arrays into one
        bucket buffer. Zero-filling the padding is exact for elementwise
        optimizers: a padding slot's grad is identically zero and decay
        multiplies zero, so its momentum never leaves zero."""
        import numpy as onp

        flat = onp.zeros((spec.total,), dtype=dtype)
        for pn, off, size, shape in spec.items():
            v = values.get(pn)
            if v is None:
                raise MXNetError(
                    f"{what} for bucket member {pn!r} is missing from "
                    "the imported state")
            v = onp.asarray(v)
            if int(v.size) != size:
                raise MXNetError(
                    f"{what} for bucket member {pn!r} has {v.size} "
                    f"elements, expected {size}")
            flat[off:off + size] = v.reshape(-1)
        return flat

    def import_params(self, params):
        """Place a dict of FULL host tensors (numpy or NDArray) into this
        trainer — repacking flat ZeRO buckets and resharding every array
        to the live mesh layout. Accepts ``export_state()['params']`` or
        ``resilience.checkpoint.load_checkpoint``'s reassembled output
        (extra entries are ignored; missing ones raise)."""
        import jax
        import numpy as onp

        def host(v):
            return v.asnumpy() if hasattr(v, "asnumpy") else onp.asarray(v)

        for n, live in self.params.items():
            spec = self._zb_by_key.get(n)
            if spec is not None:
                flat = self._zb_repack(
                    spec,
                    {pn: host(params[pn]) for pn, _, _, _ in spec.items()
                     if pn in params},
                    live.dtype, "parameter")
                self.params[n] = jax.device_put(flat, live.sharding)
                continue
            if n not in params:
                raise MXNetError(
                    f"import_params: parameter {n!r} missing from the "
                    "imported dict")
            h = host(params[n])
            if tuple(h.shape) != tuple(live.shape):
                raise MXNetError(
                    f"import_params: {n!r} has shape {tuple(h.shape)} "
                    f"but this trainer expects {tuple(live.shape)}")
            self.params[n] = jax.device_put(
                onp.asarray(h, dtype=live.dtype), live.sharding)

    def _import_opt_states(self, opt_states):
        import jax
        import numpy as onp

        def host(v):
            return v.asnumpy() if hasattr(v, "asnumpy") else onp.asarray(v)

        new = {}
        for n in self._train_keys:
            live = self._opt_states[n]
            spec = self._zb_by_key.get(n)
            if spec is None:
                if n not in opt_states:
                    raise MXNetError(
                        f"optimizer state for {n!r} is missing from the "
                        "imported state")
                st = tuple(host(s) for s in opt_states[n])
                if len(st) != len(live):
                    raise MXNetError(
                        f"optimizer state for {n!r} has arity {len(st)} "
                        f"but this trainer expects {len(live)}")
                new[n] = tuple(
                    jax.device_put(onp.asarray(h, dtype=l.dtype),
                                   l.sharding)
                    for h, l in zip(st, live))
                continue
            placed = []
            first = spec.names[0]
            for i, l in enumerate(live):
                if tuple(l.shape) == (spec.total,):
                    members = {}
                    for pn, off, size, shape in spec.items():
                        sts = opt_states.get(pn)
                        if sts is None or len(sts) <= i:
                            raise MXNetError(
                                f"optimizer state for bucket member "
                                f"{pn!r} is missing from the imported "
                                "state")
                        members[pn] = host(sts[i])
                    flat = self._zb_repack(spec, members, l.dtype,
                                           "optimizer state")
                    placed.append(jax.device_put(flat, l.sharding))
                else:
                    sts = opt_states.get(first)
                    if sts is None or len(sts) <= i:
                        raise MXNetError(
                            f"optimizer state for bucket member {first!r} "
                            "is missing from the imported state")
                    placed.append(jax.device_put(
                        onp.asarray(host(sts[i]), dtype=l.dtype),
                        l.sharding))
            new[n] = tuple(placed)
        self._opt_states = new

    def import_state(self, blob):
        """Inverse of :meth:`export_state` onto THIS trainer's mesh and
        bucket plan — params, optimizer state, step count, RNG
        position."""
        self.import_params(blob["params"])
        self._restore_scalars(blob)

    def _restore_scalars(self, blob):
        import jax

        self._import_opt_states(blob["opt_states"])
        self._step_count = int(blob["step_count"])
        if blob.get("rng_key") is not None:
            self._key = jax.device_put(blob["rng_key"])
        for i in range(len(self._train_keys)):
            self.optimizer._index_update_count[i] = self._step_count

    def states_to_bytes(self):
        """Trainer blob for ``resilience.checkpoint`` (the duck-typed
        ``trainer=`` hook): optimizer state + step count + RNG position,
        bucket-free — params travel separately through the checkpoint's
        own (layout-aware) params path."""
        import pickle

        st = self.export_state()
        st.pop("params")
        return pickle.dumps(st)

    def load_states_from_bytes(self, raw):
        """Restore a :meth:`states_to_bytes` blob onto THIS trainer —
        which may sit on a different mesh than the saver; the per-param
        repack is the reshard an elastic resume relies on."""
        import pickle

        self._restore_scalars(pickle.loads(raw))

    def sync_to_block(self):
        """Copy trained weights back into the Block's Parameters (a copy —
        the trainer's own arrays get donated on the next step). Pipeline
        runs unstack the ``pp::`` leaves back into the per-layer params."""
        import jax.numpy as jnp

        params_od = self.block.collect_params()
        if self._zb_specs:
            import jax
            import numpy as onp

            # bucketed params live only inside the flat buffers: gather
            # each to host once and slice the members back out
            for spec in self._zb_specs:
                host = onp.asarray(jax.device_get(self.params[spec.key]))
                for n, off, size, shape in spec.items():
                    params_od[n].data()._set_data_internal(
                        jnp.asarray(host[off:off + size].reshape(shape)))
        for n, arr in self.params.items():
            if n.startswith("__"):
                continue
            if self._pp_meta is not None and n.startswith("pp::"):
                import jax

                # device_get: the stacked leaf is sharded over pp — the
                # unstacked per-layer weights must land whole on the
                # default device for eager use
                flat = jnp.asarray(jax.device_get(arr)).reshape(
                    (-1,) + arr.shape[2:])  # (S, per_stage, ...) -> (L, ...)
                for li, pname in enumerate(self._pp_meta[n]):
                    params_od[pname].data()._set_data_internal(flat[li])
            else:
                params_od[n].data()._set_data_internal(
                    jnp.array(arr, copy=True))
