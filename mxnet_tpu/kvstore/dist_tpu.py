"""``dist_tpu_sync``: the TPU-native distributed KVStore.

This is the BASELINE.json north-star component: it replaces the reference's
ps-lite parameter-server push/pull (``src/kvstore/kvstore_dist.h`` workers ↔
``kvstore_dist_server.h`` servers over a ZMQ van) with XLA collectives over
ICI/DCN. There are no scheduler/server roles: every process is an SPMD
worker (``jax.distributed``), and ``pushpull`` is a compiled ``psum``.

Mapping (SURVEY.md §3.4):
  worker local reduce (Comm)        -> part of the same jitted psum
  ZPushPull to sharded servers      -> all-reduce over the mesh 'dp' axis
  server ApplyUpdates (sync wait)   -> collective is the barrier
  EncodeDefaultKey sharding         -> reduce_scatter option (ZeRO-style)

Two operating modes:
  * replicated arrays (one per device / per-process): ``pushpull`` jit-psums
    the stack — used by ``gluon.Trainer`` for MXNet-style per-device lists.
  * mesh-sharded ``jax.Array``s (the native path): grads computed inside a
    ``pjit`` with a sharded batch axis already arrive reduced; pushpull is
    then an identity with sharding assertions (XLA inserted the collective).
"""
from __future__ import annotations

import warnings
import weakref

from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from ..profiler import core as _prof
from ..profiler import recorder as _recorder
from ..profiler import trace as _trace
from ..resilience import counters as _res_counters
from ..resilience import retry as _retry
from .base import KVStoreBase
from .kvstore_local import KVStoreLocal, _normalize_grouped, _priority_order

# fault-injection hot-state (resilience.faults.FaultPlan slot, see
# ops/registry.py): None until a plan installs
_FAULTS = None

# straggler-monitor hot-state (resilience.elastic.StragglerMonitor slot,
# same discipline): None until a monitor installs; when set, collective
# call sites report per-replica arrival lag to it
_STRAGGLER = None

# live stores, for the process-wide collective_stats() aggregate
# (profiler.export pulls it); weak so the registry never pins a store
_stores: "weakref.WeakSet" = weakref.WeakSet()


def _tag_step(args):
    """Attach the current training-step id (profiler.trace.set_step) to a
    collective event's args so a dumped trace correlates collectives with
    the estimator's train::step spans."""
    if _trace.ENABLED:
        args["step"] = _trace.current_step()
    return args


def collective_stats():
    """Process-wide collective telemetry: per-instance ``_stats`` fields
    summed over every live store, plus the worst breaker state ('open' >
    'half_open' > 'closed') and the shared retry/watchdog counters."""
    rank = {"closed": 0, "half_open": 1, "open": 2}
    # compressed_bytes_saved is seeded so the gauge exists (at 0) even
    # after every store is collected — dashboards key on its presence
    agg = {"stores": 0, "breaker_state": "closed",
           "compressed_bytes_saved": 0}
    for kv in list(_stores):
        agg["stores"] += 1
        for k, v in kv._stats.items():
            agg[k] = agg.get(k, 0) + v
        state = kv._breaker.snapshot().get("state", "closed")
        if rank.get(state, 0) > rank[agg["breaker_state"]]:
            agg["breaker_state"] = state
    agg["retries"] = _res_counters.get("resilience.retries")
    agg["watchdog_timeouts"] = _res_counters.get(
        "resilience.watchdog_timeouts")
    agg["watchdog_orphans"] = _retry.watchdog_orphans()
    return agg


def _jax():
    import jax

    return jax


@KVStoreBase.register
class KVStoreDistTPUSync(KVStoreLocal):
    NAME = "dist_tpu_sync"

    def __init__(self, mesh=None, axis="dp"):
        super().__init__()
        from ..parallel import mesh as mesh_mod

        self._mesh = mesh if mesh is not None else mesh_mod.get_mesh(create=True)
        self._axis = axis if (self._mesh is None or axis in self._mesh.axis_names) \
            else self._mesh.axis_names[0]
        self._allreduce_jit = {}      # (shape, dtype) -> AOT-compiled psum
        self.last_path = None         # 'collective' | 'eager' (tests assert)
        self.last_hlo = None          # compiled HLO of the last collective
        self.last_error = None        # why the fast path last degraded
                                      # ("ExcType: msg" string, never the
                                      # live exception — see
                                      # _record_degradation)
        from .. import config as _config

        # resilience: after K consecutive fast-path failures stop trying
        # the collective (straight to eager) until the cooldown lets one
        # half-open probe through (resilience.retry.CircuitBreaker)
        self._breaker = _retry.CircuitBreaker(
            failure_threshold=_config.get(
                "MXNET_COLLECTIVE_BREAKER_THRESHOLD"),
            cooldown_calls=_config.get(
                "MXNET_COLLECTIVE_BREAKER_COOLDOWN"),
            name="kvstore.allreduce")
        # retry policy + watchdog timeout resolved ONCE here, like the
        # breaker thresholds above: allreduce runs per training step and
        # must not re-read the environment per call (fault plans, by
        # contrast, can be installed/cleared at any time — the _FAULTS
        # slot is re-poked, not re-read)
        self._retry_policy = _retry.collective_policy()
        self._watchdog_timeout = _retry.collective_timeout()
        # pre-collective NaN quarantine (resilience.guardrails): resolved
        # once here like the knobs above — allreduce runs per step
        self._nan_quarantine = bool(_config.get("MXNET_NAN_QUARANTINE"))
        self._nan_quarantine_mode = str(
            _config.get("MXNET_NAN_QUARANTINE_MODE"))
        if self._nan_quarantine_mode not in ("skip", "drop"):
            # a typo ('Drop') would otherwise silently behave as skip
            raise MXNetError(
                f"MXNET_NAN_QUARANTINE_MODE must be 'skip' or 'drop', "
                f"got {self._nan_quarantine_mode!r}")
        # elastic mesh-loss classification (resilience.elastic): resolved
        # once like the knobs above. Off (default): a lost chip degrades
        # to the eager fallback exactly like any fatal fast-path failure
        # (the PR-2 semantics, regression-pinned); on: it raises
        # MeshDegraded so an ElasticTrainingHandler can shrink the mesh
        # and resume from checkpoint instead of training through a
        # half-dead collective.
        self._elastic = bool(_config.get("MXNET_ELASTIC"))
        # 2-bit gradient compression (MXNET_GRADIENT_COMPRESSION=2bit, off
        # by default; Trainer's compression_params wires the same slot via
        # set_gradient_compression). Over ICI the fabric outruns the
        # quantize kernel, so this reproduces the reference's compressed
        # DCN ZPushPull *numerics* (error feedback, bounded divergence)
        # rather than saving on-chip bytes — see _maybe_compress.
        comp_type = str(_config.get("MXNET_GRADIENT_COMPRESSION") or "")
        if comp_type.strip():
            from .gradient_compression import GradientCompression

            self._compression = GradientCompression(type=comp_type.strip())
        self._stats = {"allreduce_calls": 0, "collective": 0, "eager": 0,
                       "degradations": 0, "breaker_skips": 0,
                       "quarantined": 0, "mesh_losses": 0,
                       "compressed_bytes_saved": 0}
        _stores.add(self)

    def collective_stats(self):
        """Resilience/degradation telemetry for this store (the
        ``cache_stats()`` analog): path counts, why the fast path last
        degraded, breaker state, process-wide retry counters."""
        out = dict(self._stats)
        out["breaker"] = self._breaker.snapshot()
        out["last_error"] = self.last_error
        out["retries"] = _res_counters.get("resilience.retries")
        out["watchdog_timeouts"] = _res_counters.get(
            "resilience.watchdog_timeouts")
        # abandoned watchdog bodies (still-running orphans can mutate
        # state behind the fast path — operator signal, not noise)
        out["watchdog_orphans"] = _retry.watchdog_orphans()
        return out

    def _classify_mesh_loss(self, exc, op="allreduce"):
        """Elastic classification (``MXNET_ELASTIC=1`` only): is this
        collective failure a *lost device group* rather than a transient?
        Returns a ready-to-raise :class:`~..resilience.elastic.
        MeshDegraded` (counted + traced) or ``None`` for everything
        else (which then takes the PR-2 degrade-to-eager path)."""
        from ..resilience import elastic as _elastic

        if not _elastic.is_mesh_loss(exc):
            return None
        lost = getattr(exc, "replica", None)
        lost = [int(lost)] if lost is not None else None
        # coordinate-addressed chip loss (composed dp×tp meshes): forward
        # the device address so the elastic layer can rebuild_mesh on it
        device = getattr(exc, "device", None)
        return self._mesh_degraded(
            lost, f"{type(exc).__name__}: {exc}", op,
            lost_devices=[device] if device is not None else None)

    def _mesh_degraded(self, lost, cause, op, lost_devices=None):
        """Count + trace + warn one mesh-loss event and build the
        :class:`MeshDegraded` to raise (shared by exception
        classification and the breaker-open device probe)."""
        from ..resilience import elastic as _elastic

        self._stats["mesh_losses"] += 1
        _res_counters.incr("resilience.mesh_losses")
        if _prof.ENABLED:
            _prof.record_instant(f"resilience::mesh_loss({op})",
                                 "resilience",
                                 args={"lost": lost,
                                       "error": str(cause)[:200]})
        # crash forensics: the moments before a mesh loss, on disk
        _recorder.dump("mesh_degraded",
                       args={"op": op, "lost": lost,
                             "lost_devices": lost_devices,
                             "cause": str(cause)[:500],
                             "step": _trace.current_step()})
        warnings.warn(
            f"kvstore {op}: collective failure classified as MESH LOSS "
            f"(lost replica(s) {lost if lost is not None else 'unknown'}): "
            f"{cause} — raising MeshDegraded for elastic recovery",
            RuntimeWarning, stacklevel=4)
        return _elastic.MeshDegraded(
            f"{op} lost part of the mesh: {cause}",
            lost_replicas=lost,
            mesh_size=self._mesh.size if self._mesh is not None else None,
            lost_devices=lost_devices)

    def _probe_lost_devices(self):
        """Tiny device_put + blocking read against every mesh device;
        returns the indices that FAILED. Runs only on the elastic
        breaker-open path — while the breaker skips the fast path there
        is no collective attempt to throw a classifiable error, and a
        chip that dies during the cooldown would otherwise be summed as
        a stale buffer by the eager fallback, silently, forever."""
        import jax
        import jax.numpy as jnp

        lost = []
        for i, dev in enumerate(self._mesh_devices()):
            try:
                jax.device_put(jnp.ones((1,), jnp.float32),
                               dev).block_until_ready()
            except Exception:  # noqa: BLE001 — any failure = dead
                lost.append(i)
        return lost

    def _record_degradation(self, exc, op="allreduce"):
        """Satellite fix: the fast path must not degrade silently — keep
        the cause on ``last_error``, count it, and warn (rate-limited to
        powers of ten so a degraded steady state doesn't spam one warning
        per step)."""
        # formatted, not the live exception: exc.__traceback__ would pin
        # the failed attempt's frames (and the per-device gradient
        # buffers they reference) for the life of the store
        self.last_error = f"{type(exc).__name__}: {exc}"
        self._stats["degradations"] += 1
        n = self._stats["degradations"]
        _res_counters.incr("resilience.degradations")
        if _prof.ENABLED:
            _prof.record_instant(f"resilience::degradation({op})",
                                 "resilience",
                                 args={"error": f"{type(exc).__name__}: "
                                                f"{exc}"[:200]})
        if _res_counters.should_warn(n):
            warnings.warn(
                f"kvstore {op} collective fast path degraded to the eager "
                f"fallback ({n}x so far): {type(exc).__name__}: {exc} — "
                "see collective_stats() for breaker state",
                RuntimeWarning, stacklevel=3)

    # -- cluster shape ----------------------------------------------------
    @property
    def rank(self):
        return _jax().process_index()

    @property
    def num_workers(self):
        return _jax().process_count()

    @property
    def num_devices(self):
        return self._mesh.size if self._mesh is not None else len(_jax().devices())

    @property
    def type(self):
        return self.NAME

    def barrier(self):
        """Reference: ps-lite Barrier. Here: a tiny psum over the mesh.

        Runs under the ``MXNET_COLLECTIVE_TIMEOUT`` watchdog and fires the
        ``collective:barrier`` fault site — a barrier is the one
        collective every worker blocks on unconditionally, so a hung one
        (dead peer, partitioned ring) used to be the one place the
        runtime could still wait forever un-instrumented. A timeout
        surfaces as :class:`~..resilience.retry.CollectiveTimeoutError`
        with the usual orphan accounting."""
        if self._mesh is None:
            return
        flt = _FAULTS
        if flt is not None:
            # a 'delay' rule here + MXNET_COLLECTIVE_TIMEOUT exercises the
            # hung-barrier watchdog deterministically; the sleep must be
            # INSIDE the watched body or the watchdog would never see it
            def body(mesh=self._mesh):
                flt.check("collective:barrier", {"size": mesh.size})
                return self._barrier_psum(mesh)
        else:
            def body(mesh=self._mesh):
                return self._barrier_psum(mesh)
        _retry.run_with_watchdog(body, self._watchdog_timeout,
                                 site="kvstore::barrier")

    @staticmethod
    def _barrier_psum(mesh):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        x = jax.device_put(
            jnp.ones((mesh.size,), jnp.int32),
            NamedSharding(mesh, P(mesh.axis_names)))
        total = jax.jit(
            lambda v: v.sum(), out_shardings=NamedSharding(mesh, P()))(x)
        total.block_until_ready()

    def _quarantine_check(self, arrays, datas):
        """Pre-collective NaN quarantine (``MXNET_NAN_QUARANTINE=1``): a
        non-finite gradient is caught BEFORE the collective, because after
        the allreduce every replica on the mesh carries the poison.

        Returns ``None`` when every replica is finite. On trip:
        ``mode='skip'`` raises :class:`~...resilience.guardrails.
        NonFiniteGradError` (the estimator's GuardrailHandler turns it
        into a skipped step); ``mode='drop'`` excludes the poisoned
        replicas and returns the sum of the clean ones rescaled by
        ``n_total/n_clean`` — the unbiased estimate of the full-mesh sum,
        placed back on every source device.
        """
        import jax
        import jax.numpy as jnp

        bad = [not bool(jnp.isfinite(d).all()) for d in datas]
        if not any(bad):
            return None
        from ..resilience.guardrails import NonFiniteGradError

        n, nbad = len(datas), sum(bad)
        self._stats["quarantined"] += 1
        _res_counters.incr("resilience.nan_quarantined")
        if _prof.ENABLED:
            _prof.record_instant("resilience::quarantine(allreduce)",
                                 "resilience",
                                 args={"bad_replicas": nbad, "of": n,
                                       "mode": self._nan_quarantine_mode})
        warnings.warn(
            f"NaN quarantine: {nbad}/{n} gradient replica(s) non-finite "
            f"before the allreduce (mode={self._nan_quarantine_mode})",
            RuntimeWarning, stacklevel=3)
        if self._nan_quarantine_mode == "drop" and nbad < n:
            good = [d for d, b in zip(datas, bad) if not b]
            dev0 = next(iter(good[0].devices()))
            stacked = jnp.stack([jax.device_put(d, dev0) for d in good])
            summed = jnp.sum(stacked, axis=0) * (n / len(good))
            return [NDArray(jax.device_put(
                summed, list(a._data.devices())[0])) for a in arrays]
        if nbad == n:
            raise NonFiniteGradError(
                f"allreduce quarantine: every replica ({n}/{n}) contains "
                "NaN/Inf — nothing to sum in any mode. Skip this step "
                "(GuardrailHandler does this automatically) or attach a "
                "LossScaler so overflows are absorbed pre-collective.")
        raise NonFiniteGradError(
            f"allreduce quarantine: {nbad}/{n} gradient replica(s) "
            "contain NaN/Inf — the collective would poison every replica "
            "on the mesh. Skip this step (GuardrailHandler does this "
            "automatically), or set MXNET_NAN_QUARANTINE_MODE=drop to "
            "sum the clean replicas only.")

    # -- collectives ------------------------------------------------------
    def _mesh_devices(self):
        return list(self._mesh.devices.flatten()) if self._mesh is not None \
            else []

    def _get_allreduce_jit(self, shape, dtype, sample):
        """AOT-compiled `sum over the device axis -> replicated`: one XLA
        all-reduce over ICI (the role of ZPushPull + server ApplyUpdates,
        `src/kvstore/kvstore_dist.h:578` / `kvstore_dist_server.h:346`)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        key = (tuple(shape), str(dtype))
        hit = self._allreduce_jit.get(key)
        if hit is not None:
            return hit
        mesh = self._mesh
        # stack dim 0 is one-entry-per-mesh-device: shard it over ALL mesh
        # axes (a dp×tp mesh reduces over the whole device set, matching
        # the reference's global PushPull)
        jitted = jax.jit(
            lambda s: s.sum(axis=0),
            in_shardings=NamedSharding(
                mesh, P(tuple(mesh.axis_names), *([None] * len(shape)))),
            out_shardings=NamedSharding(mesh, P()),
        )
        t0 = _prof.begin() if _prof.ENABLED else 0

        def compile_fn():
            flt = _FAULTS
            if flt is not None:
                flt.check("kvstore:allreduce_compile",
                          {"shape": tuple(shape)})
            return jitted.lower(sample).compile()

        # transient compile failures (dropped connection, concurrent-compile
        # RESOURCE_EXHAUSTED) back off and retry; real lowering errors
        # re-raise on the first attempt
        compiled = _retry.call_with_retry(
            compile_fn, site="kvstore::allreduce_compile",
            policy=_retry.compile_policy())
        if t0:
            # the AOT-compile half of the compile-vs-execute split: one
            # event per (shape, dtype), execute timing lives in allreduce
            _prof.record_duration("kvstore::allreduce_compile", "kvstore",
                                  t0, args={"shape": list(shape),
                                            "dtype": str(dtype)})
        self.last_hlo = compiled.as_text()
        self._allreduce_jit[key] = compiled
        return compiled

    def _collective_allreduce(self, datas):
        """Fast path: per-device arrays assembled zero-copy into one array
        sharded over the mesh axis, reduced by the compiled psum. Returns
        None when the list doesn't line up 1:1 with the mesh devices (then
        the eager fallback handles it)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        flt = _FAULTS
        if flt is not None:
            # per-ATTEMPT injection point: a 'transient' rule here is what
            # the retry wrapper in allreduce() recovers from; a 'delay'
            # rule simulates the stuck collective the watchdog bounds; a
            # 'chip_loss' rule raises ChipLostError (a dead device group
            # — classified as mesh loss by allreduce() when elastic is
            # on); a 'replica_delay' rule models one replica arriving
            # late at the collective — the lag is reported to the
            # straggler monitor below
            mk = flt.check("kvstore:allreduce", {"n": len(datas)})
            if isinstance(mk, dict) and mk.get("kind") == "replica_delay":
                mon = _STRAGGLER
                if mon is not None:
                    mon.observe(int(mk.get("replica", 0)),
                                float(mk.get("seconds", 0.0)),
                                site="kvstore:allreduce")
        devs = self._mesh_devices()
        if len(datas) != len(devs) or len(devs) < 2:
            return None
        by_dev = {}
        for d in datas:
            dset = d.devices()
            if len(dset) != 1:
                return None
            by_dev.setdefault(next(iter(dset)), []).append(d)
        if set(by_dev) != set(devs) or any(len(v) != 1 for v in by_dev.values()):
            return None
        shape, dtype = datas[0].shape, datas[0].dtype
        mesh = self._mesh
        sharding = NamedSharding(
            mesh, P(tuple(mesh.axis_names), *([None] * len(shape))))
        # reshape-to-(1, ...) runs on each source device; the assembled
        # array is a view — no host or cross-device copies before the psum
        shards = [by_dev[dev][0].reshape((1,) + shape) for dev in devs]
        stacked = jax.make_array_from_single_device_arrays(
            (len(devs),) + shape, sharding, shards)
        summed = self._get_allreduce_jit(shape, dtype, stacked)(stacked)
        per_dev = {s.device: s.data for s in summed.addressable_shards}
        order = [next(iter(d.devices())) for d in datas]
        return [per_dev[dev] for dev in order]

    def allreduce(self, arrays):
        """Sum a list of per-device NDArrays into identical replicas.

        Per-device lists that cover the mesh run the compiled-collective
        path (`_collective_allreduce`): one jitted XLA all-reduce over ICI
        with a replicated out-sharding. Anything else (same-device lists,
        partial meshes) takes the eager stack-and-sum fallback.

        Resilience wrapping (outside → in): circuit breaker (skip the fast
        path entirely while open), retry with backoff (transient errors),
        watchdog (MXNET_COLLECTIVE_TIMEOUT bounds a hung collective — the
        watched body blocks on the result, so the timeout covers execution,
        not just dispatch). Any failure surfacing HERE records a
        degradation and falls through to the eager fallback instead of
        crashing. Scope caveat: with the watchdog disabled (the default)
        the result is returned async, so an execution-phase device failure
        surfaces later at a wait point (engine contract (c)) rather than
        through this retry/fallback — enable the watchdog to pull
        execution errors into the recovery path at the cost of a sync per
        reduce.
        """
        import jax
        import jax.numpy as jnp

        if len(arrays) == 1:
            return arrays
        datas = [a._data for a in arrays]
        if self._nan_quarantine:
            # BEFORE breaker/retry/watchdog: a poisoned input is not a
            # fast-path failure, and the eager fallback must not sum it
            # either
            dropped = self._quarantine_check(arrays, datas)
            if dropped is not None:
                return dropped
        t0 = _prof.begin() if _prof.ENABLED else 0
        self._stats["allreduce_calls"] += 1
        fast = None
        if self._breaker.allow():
            timeout = self._watchdog_timeout

            def run_fast():
                out = self._collective_allreduce(datas)
                if timeout and out is not None:
                    # under a watchdog the result must be BLOCKED on inside
                    # the watched body — async dispatch would return long
                    # before a hung ICI ring ever fails
                    for d in out:
                        d.block_until_ready()
                return out

            try:
                fast = _retry.call_with_retry(
                    lambda: _retry.run_with_watchdog(
                        run_fast, timeout, site="kvstore::allreduce"),
                    site="kvstore::allreduce",
                    policy=self._retry_policy)
            except Exception as exc:
                # never let the fast path take down a reduce the eager
                # fallback can do (odd meshes, unexpected layouts, injected
                # or real collective failures)
                fast = None
                self._breaker.record_failure()
                if self._elastic:
                    # mesh loss is NOT degradable: the eager fallback
                    # would keep summing a dead replica's stale buffer —
                    # silent divergence. Classify and raise so the
                    # elastic handler can shrink the mesh and resume.
                    mesh_err = self._classify_mesh_loss(exc)
                    if mesh_err is not None:
                        raise mesh_err from exc
                self._record_degradation(exc)
            except BaseException:
                # KeyboardInterrupt / SimulatedWorkerDeath mid-probe: the
                # half-open probe slot must not leak (a leaked slot locks
                # the store out of the collective path forever)
                self._breaker.release_probe()
                raise
            else:
                if fast is not None:
                    self._breaker.record_success()
                else:
                    # fast None without an exception: the list simply
                    # doesn't line up with the mesh — an expected shape of
                    # input, not a fast-path failure; the breaker stays
                    # put (but a half-open probe slot is released)
                    self._breaker.release_probe()
        else:
            self._stats["breaker_skips"] += 1
            if self._elastic:
                # the breaker never attempts the collective, so a chip
                # that dies DURING the cooldown throws no classifiable
                # error — probe the devices directly before letting the
                # eager fallback sum what might be a dead replica's
                # stale buffer
                lost = self._probe_lost_devices()
                if lost:
                    raise self._mesh_degraded(
                        lost, "device probe failed while the collective "
                        "breaker was open", "allreduce")
        if fast is not None:
            self._stats["collective"] += 1
            self.last_path = "collective"
            if t0:
                _prof.record_duration(
                    "kvstore::allreduce", "kvstore", t0,
                    args=_tag_step({
                        "path": "collective",
                        "shape": list(datas[0].shape),
                        "bytes": sum(int(d.nbytes) for d in datas)}))
            return [NDArray(d) for d in fast]
        self.last_path = "eager"
        self._stats["eager"] += 1
        # gather onto one device first: a per-device list degraded here by
        # a collective failure spans devices, and jnp.stack refuses mixed
        # placements (device_put is a no-op for the same-device case)
        dev0 = next(iter(datas[0].devices()))
        stacked = jnp.stack([jax.device_put(d, dev0) for d in datas])
        summed = jnp.sum(stacked, axis=0)
        out = []
        for a in arrays:
            dev = list(a._data.devices())[0]
            out.append(NDArray(jax.device_put(summed, dev)))
        if t0:
            _prof.record_duration(
                "kvstore::allreduce", "kvstore", t0,
                args=_tag_step({
                    "path": "eager", "shape": list(datas[0].shape),
                    "bytes": sum(int(d.nbytes) for d in datas)}))
        return out

    def _cross_process_sum(self, nd):
        """Sum one (already locally-reduced) array across processes —
        the multi-host half of pushpull (reference: ps-lite ZPushPull to
        servers shared by all workers; here a gather+sum over the
        jax.distributed runtime's collectives)."""
        import jax
        from jax.experimental import multihost_utils

        if _jax().process_count() <= 1:
            return nd
        gathered = multihost_utils.process_allgather(nd._data)
        dev = list(nd._data.devices())[0]
        return NDArray(jax.device_put(gathered.sum(axis=0), dev))

    def _maybe_compress(self, k, vals):
        """Per-replica 2-bit quantize (error-feedback residual keyed by
        ``(key, replica)``) BEFORE the reduce — the numerics of the
        reference's compressed ZPushPull, simulated over ICI. The dense
        quantized array still travels on-chip (packing it would only add
        an unpack gather); ``compressed_bytes_saved`` accounts what the
        ceil(n/4)-byte wire buffer WOULD save over DCN."""
        comp = self._compression
        if comp is None or len(vals) < 2:
            return vals
        import numpy as onp

        if not all(onp.issubdtype(onp.dtype(v.dtype), onp.floating)
                   for v in vals):
            return vals
        quantized = [comp.quantize((k, j), v) for j, v in enumerate(vals)]
        saved = sum(int(v.nbytes) - (int(v.size) + 3) // 4 for v in vals)
        self._stats["compressed_bytes_saved"] += max(saved, 0)
        return quantized

    def pushpull(self, key, value, out=None, priority=0):
        """Grouped push+pull over the mesh. ``priority`` follows the
        :func:`~.kvstore_local._priority_order` contract (scalar = call
        order; per-key list must be 1:1, higher settles first) and the
        settle order lands in ``_flush_log`` so overlap tests can assert
        front-layer grads beat the tail."""
        keys, values = _normalize_grouped(key, value)
        _, outs = _normalize_grouped(key, out)
        tpp = _prof.begin() if _prof.ENABLED else 0
        multi_proc = _jax().process_count() > 1
        for idx, prio in _priority_order(keys, priority):
            k, vals, dsts = keys[idx], values[idx], outs[idx]
            if vals is None or any(v is None for v in vals):
                # a None value group used to crash below (`reduced[0]` on
                # None, the TypeError satellite); a group with ANY None
                # entry is equally unusable — summing the remaining
                # entries would silently drop one replica's contribution.
                # Skip the key loudly instead.
                warnings.warn(
                    f"pushpull: key {k!r} has no usable value group "
                    f"({'None' if vals is None else 'contains None'}) — "
                    "skipping it; pass grads for every key or drop the "
                    "key from the call", RuntimeWarning, stacklevel=2)
                continue
            flt = _FAULTS
            if flt is not None:
                flt.check("kvstore:pushpull", {"key": k})
            vals = self._maybe_compress(k, vals)
            if len(vals) > 1:
                reduced = self.allreduce(vals)
            else:
                reduced = vals
            if multi_proc and reduced is not None:
                import jax

                summed = self._cross_process_sum(reduced[0])
                # keep each destination's device placement (the single-
                # process path preserves it too)
                reduced = [
                    NDArray(jax.device_put(
                        summed._data, list(r._data.devices())[0]))
                    for r in reduced]
            if dsts is None:
                self._store[k] = reduced[0]
                self._record_flush(k, prio)
                continue
            if len(reduced) == len(dsts):
                for r, d in zip(reduced, dsts):
                    d._set_data_internal(r._data)
            else:
                for d in dsts:
                    reduced[0].copyto(d)
            self._record_flush(k, prio)
        if tpp:
            _prof.record_duration(
                "kvstore::pushpull", "kvstore", tpp,
                args=_tag_step({
                    "keys": len(keys),
                    # None-tolerant like the skip-guard above: skipped
                    # keys/entries contribute 0 bytes, not a crash
                    "bytes": sum(v.nbytes for vs in values if vs
                                 for v in vs if v is not None)}))

    def broadcast(self, key, value, out, priority=0):
        """Replicate rank-0 value to all devices (reference Broadcast)."""
        keys, values = _normalize_grouped(key, value)
        _, outs = _normalize_grouped(key, out)
        import jax

        tbc = _prof.begin() if _prof.ENABLED else 0
        for k, vals, dsts in zip(keys, values, outs):
            src = vals[0]
            self._store[k] = src
            if dsts is None:
                continue

            def replicate(src=src, dsts=dsts):
                flt = _FAULTS
                if flt is not None:
                    flt.check("kvstore:broadcast", {"key": k})
                return [jax.device_put(src._data,
                                       list(d._data.devices())[0])
                        for d in dsts]

            # transfer faults (transient device_put failures) retry with
            # backoff; destinations are written only from a fully
            # successful replication pass
            placed = _retry.call_with_retry(
                replicate, site="kvstore::broadcast",
                policy=self._retry_policy)
            for d, buf in zip(dsts, placed):
                d._set_data_internal(buf)
        if tbc:
            _prof.record_duration("kvstore::broadcast", "kvstore", tbc,
                                  args=_tag_step({"keys": len(keys)}))

    # -- sharded-native helpers -------------------------------------------
    def shard(self, array: NDArray, spec):
        """Place an NDArray onto the mesh with a PartitionSpec."""
        import jax
        from jax.sharding import NamedSharding

        return NDArray(jax.device_put(array._data,
                                      NamedSharding(self._mesh, spec)))

    def reduce_scatter(self, array: NDArray, axis=0):
        """ZeRO-style sharded reduce (reference EncodeDefaultKey slicing)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = [None] * array.ndim
        spec[axis] = self._axis
        return NDArray(jax.jit(
            lambda x: x,
            out_shardings=NamedSharding(self._mesh, P(*spec)))(array._data))

    @staticmethod
    def is_capable(capability):
        # optimizer runs on workers (update_on_kvstore=False), like Horovod
        return False


# push/pull bandwidth probe used by bench.py and tools/bandwidth parity
def measure_pushpull_bandwidth(size_mb=64, iters=10, mesh=None):
    """Measured all-reduce bandwidth in GB/s per device (the role of the
    reference's ``tools/bandwidth/measure.py``).

    On a multi-device mesh this is collective bandwidth over ICI; on a
    single chip the "all-reduce" degenerates to an HBM read+write roundtrip
    of the buffer — callers should label the 1-device figure as
    ``hbm_roundtrip`` (see bench.py), not interconnect bandwidth.

    Timing takes the median of several two-loop differences and RAISES on
    degenerate or physically implausible results (>10 TB/s or <=0) instead
    of clamping — a wrong number is worse than no number.
    """
    import time

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel import mesh as mesh_mod

    mesh = mesh or mesh_mod.get_mesh(create=True)
    n = mesh.size
    nfloat = int(size_mb * 1024 * 1024 // 4)
    x = jax.device_put(
        jnp.ones((n, nfloat), jnp.float32),
        NamedSharding(mesh, P(mesh.axis_names[0], None)))
    import numpy as onp

    sharding = NamedSharding(mesh, P(mesh.axis_names[0], None))

    def allreduce(v):
        return jnp.broadcast_to(v.sum(0), v.shape) * 0.5

    # the reduce loop runs ON DEVICE (lax.scan): a host-side loop would
    # time per-dispatch runtime overhead, not bandwidth
    import functools

    @functools.partial(jax.jit, static_argnums=1,
                       out_shardings=sharding)
    def run_n(v, m):
        def body(c, _):
            return allreduce(c), None
        out, _ = jax.lax.scan(body, v, None, length=m)
        return out

    x = run_n(x, 1)
    onp.asarray(jax.device_get(x[0, :1]))
    onp.asarray(jax.device_get(run_n(x, 1 + iters)[0, :1]))  # compile both

    # two-loop difference: an actual host fetch at the end of BOTH loop
    # lengths cancels the fetch and dispatch tails
    def run(m, x):
        t0 = time.perf_counter()
        onp.asarray(jax.device_get(run_n(x, m)[0, :1]))
        return time.perf_counter() - t0
    diffs = []
    for _ in range(3):
        # baseline loop long enough that queue-ramp effects amortize the
        # same way in both runs (a 1-iteration baseline biases the
        # difference a few % fast — enough to read above HBM peak)
        k1 = max(2, iters // 8)
        d1 = run(k1, x)
        d2 = run(k1 + iters, x)
        if d2 > d1:
            diffs.append((d2 - d1) / iters)
    if not diffs:
        raise RuntimeError(
            "degenerate bandwidth timing: the longer loop never exceeded "
            "the shorter one — queue not drained, or the runtime elided "
            "the executions")
    diffs.sort()
    dt = diffs[len(diffs) // 2]
    if n > 1:
        # ring all-reduce moves 2*(n-1)/n of the data per device over ICI
        bytes_moved = 2 * (n - 1) / n * nfloat * 4
    else:
        # single chip: the reduce is one HBM read + write of the buffer —
        # report that roundtrip so the probe stays meaningful on 1 device
        bytes_moved = 2 * nfloat * 4
    gbs = bytes_moved / dt / 1e9  # GB/s per device
    if not (0.0 < gbs < 1e4):
        raise RuntimeError(
            f"implausible bandwidth {gbs:.1f} GB/s (dt={dt:.2e}s for "
            f"{bytes_moved/1e6:.0f} MB) — refusing to report it")
    return gbs
