"""Execution-engine facade.

The reference's dependency engine (``src/engine/threaded_engine.h``,
``include/mxnet/engine.h:117-318``) provides: (a) async execution of every op
with read/write dependency tracking, (b) ``WaitForVar``/``WaitForAll`` sync
points, (c) exception capture in async closures re-thrown at wait points, and
(d) bulk-execution segments.

On TPU all four come from XLA's async dispatch model:
  (a) ``jax`` enqueues device computations asynchronously and data dependencies
      are exact (SSA values), which is strictly stronger than var-queue
      tracking — there are no false WAR/WAW hazards because arrays are
      immutable under the hood (NDArray mutation rebinds a new buffer, the
      moral equivalent of the reference's ``Var::version_`` bump,
      ``include/mxnet/engine.h:44-61``).
  (b) ``wait_to_read`` maps to ``jax.Array.block_until_ready``.
  (c) XLA surfaces async device errors at block/transfer time; we re-raise
      them as ``MXNetError`` from the same wait points the reference uses
      (tested like ``tests/python/unittest/test_exc_handling.py``).
  (d) bulk-execution segments are REAL here: inside ``bulk(N)`` (or with
      ``MXNET_ENGINE_BULK_SIZE > 0``) imperative dispatch defers into
      per-thread segments flushed as one compiled executable each — see
      the "Deferred eager dispatch" section below.

``MXNET_ENGINE_TYPE=NaiveEngine`` gives fully synchronous execution for
debugging, as in the reference (``src/engine/naive_engine.cc``): every op
result is blocked on immediately after dispatch.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
import weakref

from .base import MXNetError

_state = threading.local()

# telemetry hot-state (mxnet_tpu.profiler.core), installed by the first
# profiler.set_state('run'); None until then so unprofiled sessions pay a
# single `is None` test per site (see ops/registry.py)
_PROF = None

# fault-injection hot-state (resilience.faults.FaultPlan slot): None until
# a plan installs; wait points consult it so simulated async device errors
# surface exactly where contract (c) says real ones do
_FAULTS = None

# attribution hot-state (profiler.attribution module slot): None until the
# profiler package imports; wait points tag their stall events with the
# thread's active phase (decode/prefill/train/other) and, while the ledger
# is ENABLED, feed the stall duration into the per-phase wait accounting
_ATTR = None

# recently dispatched arrays (weakrefs): wait_all() drains these instead of
# blocking on every live array in the process (jax.live_arrays() is O(all
# arrays ever alive) — pathological when waitall() runs once per epoch).
# Tracking is per-thread (GIL-safe deque appends, no lock on the hot eager
# dispatch path); the registry of thread deques is what wait_all sweeps.
_PENDING_MAX = 4096
_pending_tls = threading.local()
_pending_registry = {}          # thread ident -> (thread weakref, deque)
_pending_orphans = collections.deque(maxlen=_PENDING_MAX)
_pending_lock = threading.Lock()  # guards registry + orphans


def _my_pending():
    dq = getattr(_pending_tls, "dq", None)
    if dq is None:
        dq = collections.deque(maxlen=_PENDING_MAX)
        _pending_tls.dq = dq
        ident = threading.get_ident()
        with _pending_lock:
            old = _pending_registry.get(ident)
            if old is not None:
                # ident reuse after a thread died: keep its undrained refs
                _pending_orphans.extend(old[1])
            _pending_registry[ident] = (
                weakref.ref(threading.current_thread()), dq)
    return dq


def track_async(arrays):
    """Record op outputs as outstanding async work for wait_all."""
    dq = _my_pending()
    for a in arrays:
        try:
            dq.append(weakref.ref(a))
        except TypeError:
            pass
    prof = _PROF
    if prof is not None and prof.ENABLED:
        # async queue depth gauge: outstanding dispatches on this thread
        prof.set_counter("engine.queue_depth", len(dq), cat="engine")


def engine_type() -> str:
    t = getattr(_state, "engine_type", None)
    if t is None:
        from . import config

        t = config.get("MXNET_ENGINE_TYPE")
        _state.engine_type = t
    return t


def set_engine_type(name: str):
    """'NaiveEngine' => synchronous op dispatch (debug aid)."""
    _state.engine_type = name


def is_naive() -> bool:
    return engine_type() == "NaiveEngine"


def maybe_sync(arrays):
    """Called by the dispatch layer after each op: tracks outputs for
    wait_all, and blocks immediately when NaiveEngine is on."""
    if is_naive():
        # already synced — nothing outstanding to track
        for a in arrays:
            try:
                a.block_until_ready()
            except AttributeError:
                pass
        return
    track_async(arrays)


def wait_for_var(data):
    """``Engine::WaitForVar`` analog: block until ``data`` is computed.
    The stall duration is recorded while the profiler runs."""
    flt = _FAULTS
    if flt is not None:
        # contract (c): injected async device errors surface at EVERY wait
        # point, not just wait_all (the reference re-throws engine
        # exceptions from WaitForVar and WaitForAll alike)
        flt.check("engine:wait")
    prof = _PROF
    attr = _ATTR
    profiling = prof is not None and prof.ENABLED
    attributing = attr is not None and attr.ENABLED
    if not profiling and not attributing:
        try:
            return data.block_until_ready()
        except AttributeError:
            return data
    t0 = time.perf_counter_ns()
    try:
        try:
            return data.block_until_ready()
        except AttributeError:
            return data
    finally:
        t1 = time.perf_counter_ns()
        phase = attr.current_phase() if attr is not None else "other"
        if attributing:
            attr.note_wait(t1 - t0, phase)
        if profiling:
            prof.record_duration("engine::wait_for_var", "engine", t0, t1,
                                 args={"phase": phase})


def _block_settled(a):
    """Block on one tracked array. Returns ``'ok'``, ``'skip'``, or the
    failure exception. Donated-away buffers (fused optimizer /
    static_alloc donate arrays that were tracked as op outputs — blocking
    on one raises 'Array has been deleted', including the race where the
    delete lands after the ``is_deleted`` check) and non-waitable strays
    are skips, not failures."""
    try:
        is_deleted = getattr(a, "is_deleted", None)
        if is_deleted is not None and is_deleted():
            return "skip"
        a.block_until_ready()
        return "ok"
    except AttributeError:
        return "skip"  # no block_until_ready: not async work
    except Exception as e:
        if "deleted" in str(e).lower():
            return "skip"
        return e


def wait_all():
    """``MXNDArrayWaitAll`` analog: drain outstanding async work.

    Blocks on the recently-dispatched set (bounded deque of weakrefs) —
    O(recent ops), not O(live arrays). ``MXNET_WAITALL_FULL=1`` restores
    the exhaustive ``jax.live_arrays()`` sweep for debugging.

    Contract (c) of the module docstring: async device errors re-raise at
    wait points. The FIRST failure encountered while draining is kept and
    re-raised as ``MXNetError`` after the drain completes — every other
    outstanding array is still waited on first, so one poisoned dispatch
    doesn't leave the rest of the queue untracked for the next wait_all.
    """
    import jax

    from . import config

    prof = _PROF
    attr = _ATTR
    profiling = prof is not None and prof.ENABLED
    attributing = attr is not None and attr.ENABLED
    t0 = time.perf_counter_ns() if profiling or attributing else 0
    drained = 0
    first_failure = None
    try:
        flush_all("wait")
    except Exception as e:
        first_failure = e  # re-raised below, after the drain completes
    flt = _FAULTS
    if flt is not None:
        flt.check("engine:wait")
    try:
        jax.effects_barrier()
    except AttributeError:
        pass  # jax version without effects_barrier
    except Exception as e:
        first_failure = e
    if config.get("MXNET_WAITALL_FULL"):
        try:
            live = jax.live_arrays()
        except Exception:
            live = []
        for a in live:
            r = _block_settled(a)
            if r == "ok":
                drained += 1
            elif r != "skip" and first_failure is None:
                first_failure = r
        if t0:
            t1 = time.perf_counter_ns()
            phase = attr.current_phase() if attr is not None else "other"
            if attributing:
                attr.note_wait(t1 - t0, phase)
            if profiling:
                prof.record_duration(
                    "engine::wait_all", "engine", t0, t1,
                    args={"mode": "full", "phase": phase,
                          "failed": first_failure is not None})
    else:
        with _pending_lock:
            deques = [dq for _, dq in _pending_registry.values()]
            deques.append(_pending_orphans)
            # prune registry entries for dead threads (their deques were
            # just captured above and get drained below) — no per-thread
            # leak
            dead = []
            for ident, (tref, _dq) in _pending_registry.items():
                t = tref()  # bind once: the second deref could race GC
                if t is None or not t.is_alive():
                    dead.append(ident)
            for ident in dead:
                del _pending_registry[ident]
        for dq in deques:
            while True:
                try:
                    ref = dq.popleft()
                except IndexError:
                    break
                a = ref()
                if a is None:
                    continue
                r = _block_settled(a)
                if r == "ok":
                    drained += 1
                elif r != "skip" and first_failure is None:
                    first_failure = r
        if t0:
            t1 = time.perf_counter_ns()
            phase = attr.current_phase() if attr is not None else "other"
            if attributing:
                attr.note_wait(t1 - t0, phase)
            if profiling:
                prof.record_duration(
                    "engine::wait_all", "engine", t0, t1,
                    args={"drained": drained, "phase": phase,
                          "failed": first_failure is not None})
                prof.set_counter("engine.queue_depth", 0, cat="engine")
    if first_failure is not None:
        raise MXNetError(
            f"async operation failed, surfaced at wait_all: "
            f"{type(first_failure).__name__}: {first_failure}"
        ) from first_failure


# ---------------------------------------------------------------------------
# Deferred eager dispatch: REAL bulk-execution segments.
#
# Inside an active ``bulk(N)`` scope (or with ``MXNET_ENGINE_BULK_SIZE > 0``
# globally), ``ops/registry.apply`` stops dispatching each op on its
# own and instead records (op, static key, input handles) into the
# thread's pending :class:`_Segment`, handing back NDArrays backed by
# :class:`_LazyRef` placeholders.  The segment flushes as ONE jitted
# executable — the reference's bulk-execution segments
# (``Engine::StartBulk``/``StopBulk``, engine.h:311-317) done the XLA way —
# when it reaches N ops, when any lazy value is materialized, at wait
# points, at autograd tape boundaries, and before any op the recorder
# can't defer.  Flushed segments compile through ``_SEG_CACHE`` keyed on
# the sequence of per-op static keys + wiring, so a steady-state eager
# training loop replays one cached executable per segment instead of ~N
# per-op executables (~N dispatches).
#
# NaiveEngine forces the effective segment size to 1 (synchronous per-op
# semantics preserved); bulk size is THREAD-LOCAL — one thread's ``bulk()``
# scope can never change another thread's flush threshold mid-step.
# ---------------------------------------------------------------------------

_bulk_tls = threading.local()
# fast gate read by ops/registry.apply per dispatch: False until the first
# bulk activation (env knob at import, or any set_bulk_size(>1)/bulk()) —
# the default-off eager path pays ONE module-attribute test per op
try:
    import os as _os

    _BULK_POSSIBLE = int(_os.environ.get("MXNET_ENGINE_BULK_SIZE",
                                         "0") or 0) > 1
except ValueError:
    _BULK_POSSIBLE = False
_env_bulk = None        # cached MXNET_ENGINE_BULK_SIZE (process default)

# segment executable caches: one compiled replay (and one compiled vjp) per
# recorded op-sequence identity.  Same clear-don't-evict runaway discipline
# as registry._EAGER_JIT_CACHE.
_SEG_CACHE = {}
_SEG_BWD_CACHE = {}
_SEG_SKIP = set()       # segment keys whose trace consumed RNG: never cache
_SEG_CACHE_MAX = 512

# every live (possibly pending) segment, any thread: wait_all's drain-all
# contract extends to segments recorded on OTHER threads — flush is
# lock-protected and owners recover via record()'s None-restart, so a
# cross-thread flush here is safe
_live_segments = weakref.WeakSet()

# executable-invocation counter: every actual device dispatch — per-op
# apply, segment flush, backward tape-node invocation — bumps this.  The
# bench's dispatches-per-step column and the bulk conformance tests read it.
_dispatch_n = 0

# cumulative segment telemetry (cheap: only touched at flush, never on the
# per-op record path); bulk_stats() exposes it, profiler counters mirror it
_BULK_STATS = {
    "flushes": 0, "ops_flushed": 0, "cache_hits": 0, "cache_misses": 0,
    "cache_clears": 0, "reasons": collections.Counter(),
}


def _count_dispatch(n=1):
    global _dispatch_n
    _dispatch_n += n


def dispatch_count() -> int:
    """Executable invocations so far (per-op dispatches + segment flushes
    + backward tape-node invocations)."""
    return _dispatch_n


def reset_dispatch_count():
    global _dispatch_n
    _dispatch_n = 0


def bulk_stats(reset=False):
    """Segment-dispatch telemetry: flush count, ops bulked, per-reason
    flush histogram, and segment-cache hit/miss counts."""
    out = {
        "flushes": _BULK_STATS["flushes"],
        "ops_flushed": _BULK_STATS["ops_flushed"],
        "cache_hits": _BULK_STATS["cache_hits"],
        "cache_misses": _BULK_STATS["cache_misses"],
        "cache_clears": _BULK_STATS["cache_clears"],
        "reasons": dict(_BULK_STATS["reasons"]),
        "ops_per_flush": (_BULK_STATS["ops_flushed"] /
                          _BULK_STATS["flushes"]
                          if _BULK_STATS["flushes"] else 0.0),
    }
    if reset:
        _BULK_STATS.update(flushes=0, ops_flushed=0, cache_hits=0,
                           cache_misses=0, cache_clears=0,
                           reasons=collections.Counter())
    return out


def _env_bulk_size() -> int:
    global _env_bulk, _BULK_POSSIBLE
    if _env_bulk is None:
        from . import config

        try:
            _env_bulk = int(config.get("MXNET_ENGINE_BULK_SIZE") or 0)
        except (ValueError, TypeError):
            _env_bulk = 0
        if _env_bulk > 1:
            _BULK_POSSIBLE = True
    return _env_bulk


def set_bulk_size(size):
    """Set this THREAD's bulk-execution size limit (reference
    ``python/mxnet/engine.py:25``); returns the previous value.  A size
    > 1 turns on deferred eager dispatch for this thread; any pending
    segment is flushed on every change so a resize can never reorder ops
    across the boundary."""
    global _BULK_POSSIBLE
    prev = getattr(_bulk_tls, "size", None)
    if prev is None:
        prev = _env_bulk_size()
    size = int(size)
    if size != prev:
        flush_current("scope")
    _bulk_tls.size = size
    if size > 1:
        _BULK_POSSIBLE = True
    return prev


@contextlib.contextmanager
def bulk(size: int = 16):
    """Bulk-execution scope (``engine.h:311-317``): ops recorded inside
    defer into segments of up to ``size`` ops, each flushed as one
    compiled executable.  The scope duration and size are recorded while
    profiling; exit flushes the pending segment."""
    prev = set_bulk_size(size)
    prof = _PROF
    t0 = prof.begin() if prof is not None and prof.ENABLED else 0
    try:
        yield
    finally:
        set_bulk_size(prev)  # flushes the pending segment on change
        flush_current("scope")  # ... and when prev == size
        if t0:
            prof.record_duration("engine::bulk", "engine", t0,
                                 args={"size": size})


def _active_bulk_size() -> int:
    """Effective segment capacity for THIS thread right now; 0 when
    deferral is off (size <= 1, or NaiveEngine's forced size-1
    synchronous semantics)."""
    size = getattr(_bulk_tls, "size", None)
    if size is None:
        size = _env_bulk_size()
        _bulk_tls.size = size
    if size <= 1 or is_naive():
        return 0
    return size


def _segment_for_record(size) -> "_Segment":
    """The thread's open segment, creating one at ``size`` capacity if the
    previous segment flushed (or none exists)."""
    seg = getattr(_bulk_tls, "seg", None)
    if seg is None or seg.done:
        seg = _Segment(size)
        _bulk_tls.seg = seg
        _live_segments.add(seg)
    return seg


def flush_current(reason="manual"):
    """Flush this thread's pending segment, if any (no-op when bulking has
    never been activated)."""
    if not _BULK_POSSIBLE:
        return
    seg = getattr(_bulk_tls, "seg", None)
    if seg is not None and not seg.done:
        seg.flush(reason)


def flush_all(reason="wait"):
    """Flush EVERY thread's pending segment (wait_all's drain-all
    contract: deferred work recorded on other threads must be submitted
    — and its errors surfaced — before wait_all returns)."""
    if not _BULK_POSSIBLE:
        return
    first_failure = None
    for seg in list(_live_segments):
        if not seg.done:
            try:
                seg.flush(reason)
            except BaseException as e:  # surface ONE, flush the rest
                if first_failure is None:
                    first_failure = e
    if first_failure is not None:
        raise first_failure


class _LazyRef:
    """Placeholder buffer for one deferred op output.

    An NDArray whose ``_buf`` is a ``_LazyRef`` owns a value that does not
    exist yet; any ``_data`` access forces the owning segment to flush
    (shape/dtype are answered from the recorded aval without flushing).
    """

    __slots__ = ("seg", "idx", "shape", "dtype", "value", "err", "tainted",
                 "owner")

    def __init__(self, seg, idx, shape, dtype):
        self.seg = seg
        self.idx = idx
        self.shape = tuple(shape)
        self.dtype = dtype
        self.value = None   # concrete jax.Array once the segment flushed
        self.err = None     # the flush failure, surfaced at materialization
        self.tainted = False  # produced by a recorded (tape-tracked) op
        self.owner = None   # weakref to the NDArray handle (tape wiring)

    @property
    def ndim(self):
        return len(self.shape)

    def force(self):
        """Materialize: flush the owning segment and return the value."""
        seg = self.seg
        if self.value is None and self.err is None and seg is not None:
            seg.flush("materialize")
        if self.err is not None:
            raise MXNetError(
                f"deferred bulk segment failed; error surfaced at "
                f"materialization: {type(self.err).__name__}: {self.err}"
            ) from self.err
        return self.value


class _SegOp:
    """One recorded call: closed callable + static key + slot wiring."""

    __slots__ = ("closed", "key", "wiring", "out_slots", "single",
                 "was_list", "recorded", "name")

    def __init__(self, closed, key, wiring, out_slots, single, was_list,
                 recorded, name):
        self.closed = closed
        self.key = key
        self.wiring = wiring      # per input: ("i", slot) | ("e", ext_idx)
        self.out_slots = out_slots
        self.single = single
        self.was_list = was_list
        self.recorded = recorded
        self.name = name


_fence_fn = None


def _fence(flat):
    """Differentiable per-op fusion fence: ``optimization_barrier`` on the
    forward values AND on the backward cotangents (the raw primitive has
    no differentiation rule), with float0 cotangents passed through."""
    global _fence_fn
    if _fence_fn is None:
        import jax

        @jax.custom_vjp
        def fence(xs):
            return jax.lax.optimization_barrier(xs)

        def fence_fwd(xs):
            return jax.lax.optimization_barrier(xs), None

        def fence_bwd(_, cts):
            def b(c):
                if c is None or getattr(c, "dtype", None) == \
                        jax.dtypes.float0:
                    return c
                return jax.lax.optimization_barrier(c)

            return (tuple(b(c) for c in cts),)

        fence.defvjp(fence_fwd, fence_bwd)
        _fence_fn = fence
    return _fence_fn(flat)


_bulk_fuse_cached = None


def _bulk_fuse() -> bool:
    """MXNET_ENGINE_BULK_FUSE: let XLA fuse ACROSS the ops of a segment.
    Off by default: bulking batches *dispatch* (one executable per
    segment), and per-op optimization barriers pin each op's numerics to
    its standalone executable so bulk-vs-unbulked results stay
    bitwise-identical. Fusing across ops can shave memory traffic at the
    cost of last-ulp drift in fused reductions."""
    global _bulk_fuse_cached
    if _bulk_fuse_cached is None:
        from . import config

        try:
            _bulk_fuse_cached = bool(config.get("MXNET_ENGINE_BULK_FUSE"))
        except Exception:
            _bulk_fuse_cached = False
    return _bulk_fuse_cached


def _build_replay(ops, n_slots):
    """The segment's forward as one traceable function of the external
    inputs. Rebuilt only on a segment-cache miss.

    Non-recorded ops get ``stop_gradient`` on their outputs: in unbulked
    eager an op outside ``autograd.record()`` (or under ``pause()``)
    produces a tape-less CONSTANT, so the segment vjp must not conduct
    gradient through it either. Identity in the forward, so sharing the
    forward executable across recorded-flag variations stays sound (the
    backward cache key pins the flags via ``rec_slots``).
    """
    barrier = not _bulk_fuse()

    def replay(*ext):
        import jax

        vals = [None] * n_slots
        for op in ops:
            ins = [vals[i] if tag == "i" else ext[i]
                   for tag, i in op.wiring]
            r = op.closed(*ins)
            if op.single:
                flat = (r,)
            else:
                flat = tuple(r)
            if not op.recorded:
                flat = jax.lax.stop_gradient(flat)
            if barrier:
                # fence each op: one executable per SEGMENT, but each op
                # keeps the exact numerics of its standalone dispatch
                flat = _fence(flat)
            for si, v in zip(op.out_slots, flat):
                vals[si] = v
        return tuple(vals)

    return replay


class _Segment:
    """A per-thread pending bulk segment: the recorded-but-not-dispatched
    op sequence plus its lazy output slots and pinned external inputs."""

    def __init__(self, size):
        self.size = size
        self.ops = []
        self.slots = []          # _LazyRef per flat output, in record order
        self.ext_vals = []       # pinned external jax.Arrays, in first-use order
        self.ext_ids = {}        # id(jax.Array) -> ext index
        self.ext_tracked = {}    # ext index -> (_slot_of(nd), nd) at record
        self.done = False
        self._lock = threading.RLock()
        self._eager_vjp = None   # exact vjp for uncacheable (RNG) segments

    # -- record (called from ops/registry on the owner thread) ------------
    def record(self, closed, key, ins, arrays, tracked_flags, avals,
               single, was_list, recorded, name):
        """Append one op; returns its lazy output refs, or ``None`` when a
        cross-thread materialization flushed this segment concurrently
        (the caller restarts on a fresh segment)."""
        with self._lock:
            if self.done:
                return None
            return self._record_locked(
                closed, key, ins, arrays, tracked_flags, avals,
                single, was_list, recorded, name)

    def _record_locked(self, closed, key, ins, arrays, tracked_flags,
                       avals, single, was_list, recorded, name):
        wiring = []
        for x, nd, tr in zip(ins, arrays, tracked_flags):
            if type(x) is _LazyRef:
                wiring.append(("i", x.idx))
            else:
                ei = self.ext_ids.get(id(x))
                if ei is None:
                    ei = len(self.ext_vals)
                    self.ext_vals.append(x)
                    self.ext_ids[id(x)] = ei
                wiring.append(("e", ei))
                if recorded and tr and ei not in self.ext_tracked:
                    from .ndarray.ndarray import _slot_of

                    self.ext_tracked[ei] = (_slot_of(nd), nd)
        base = len(self.slots)
        out_refs = []
        for k, (shape, dtype) in enumerate(avals):
            ref = _LazyRef(self, base + k, shape, dtype)
            ref.tainted = recorded
            self.slots.append(ref)
            out_refs.append(ref)
        self.ops.append(_SegOp(
            closed, key, tuple(wiring),
            tuple(range(base, base + len(avals))),
            single, was_list, recorded, name))
        return out_refs

    # -- flush ------------------------------------------------------------
    def flush(self, reason):
        with self._lock:
            if self.done:
                return
            self.done = True
            if not self.ops:
                return
            try:
                self._execute(reason)
            except BaseException as e:
                # poison every unfilled slot: the error re-surfaces at each
                # later materialization, like a real async device failure
                for s in self.slots:
                    if s.value is None and s.err is None:
                        s.err = e
                        s.seg = None
                raise

    def _execute(self, reason):
        import jax

        from . import random as _rng

        prof = _PROF
        t0 = prof.begin() if prof is not None and prof.ENABLED else 0
        flt = _FAULTS
        if flt is not None:
            # the per-op dispatch fault site still fires once per RECORDED
            # op — deferral must not make injected dispatch faults vanish;
            # they surface here, at the flush (= async) boundary
            for _op in self.ops:
                flt.check("op:dispatch")
        skey = tuple((op.key, op.wiring, len(op.out_slots))
                     for op in self.ops)
        rec_slots = tuple(si for op in self.ops if op.recorded
                          for si in op.out_slots)
        ext = tuple(self.ext_vals)
        tracked_idx = tuple(sorted(self.ext_tracked))
        _count_dispatch()
        hit = False
        if skey in _SEG_SKIP:
            if rec_slots:
                out_flat = self._run_eager_vjp(ext, tracked_idx)
            else:
                out_flat = _build_replay(self.ops, len(self.slots))(*ext)
        else:
            cached = _SEG_CACHE.get(skey)
            if cached is not None:
                hit = True
                out_flat = cached(*ext)
            else:
                replay = _build_replay(self.ops, len(self.slots))
                mark = _rng.consume_count()
                jitted = jax.jit(replay)
                out_flat = jitted(*ext)
                if _rng.consume_count() == mark:
                    if len(_SEG_CACHE) >= _seg_cache_max():
                        _SEG_CACHE.clear()
                        _SEG_BWD_CACHE.clear()
                        # attributable, like the registry cache clears:
                        # churning segment shapes re-pay compiles
                        _BULK_STATS["cache_clears"] += 1
                        from .ops.registry import _note_cache_clear

                        _note_cache_clear(
                            "bulk segment cache", "seg_cache_clears",
                            _BULK_STATS["cache_clears"],
                            limit=_seg_cache_max())
                    _SEG_CACHE[skey] = jitted
                else:
                    # the trace drew RNG keys: a cached replay would bake
                    # them forever. If the segment is on the tape, redo it
                    # under an exact residual-carrying vjp so backward
                    # replays the SAME keys this forward used.
                    _SEG_SKIP.add(skey)
                    if rec_slots:
                        _count_dispatch()
                        out_flat = self._run_eager_vjp(ext, tracked_idx)
        for s, v in zip(self.slots, out_flat):
            s.value = v
            s.seg = None
        maybe_sync(out_flat)
        if rec_slots:
            self._record_tape_node(skey, rec_slots, tracked_idx, ext)
        stats = _BULK_STATS
        stats["flushes"] += 1
        stats["ops_flushed"] += len(self.ops)
        stats["reasons"][reason] += 1
        stats["cache_hits" if hit else "cache_misses"] += 1
        if t0:
            prof.record_duration("engine::bulk_flush", "engine", t0,
                                 args={"reason": reason,
                                       "ops": len(self.ops),
                                       "cached": hit})
            prof.incr_counter("engine.bulk_flushes", cat="engine")
            prof.set_counter("engine.bulk_segment_ops", len(self.ops),
                             cat="engine")

    def _run_eager_vjp(self, ext, tracked_idx):
        """Uncacheable (RNG-consuming) recorded segment: run the forward
        under plain ``jax.vjp`` so the stored backward carries the exact
        residuals (a remat would re-draw keys and mismatch the masks)."""
        import jax

        replay = _build_replay(self.ops, len(self.slots))

        def f(*tr):
            full = list(ext)
            for i, v in zip(tracked_idx, tr):
                full[i] = v
            return replay(*full)

        out_flat, vjp = jax.vjp(f, *(ext[i] for i in tracked_idx))
        self._eager_vjp = vjp
        return out_flat

    def _record_tape_node(self, skey, rec_slots, tracked_idx, ext):
        """Transparent passthrough under tape: the flushed segment joins
        the autograd tape as ONE node (the bulk analog of a hybridized
        CachedOp node) whose backward is one compiled vjp per segment
        key — same remat discipline as ``registry._make_cached_vjp``."""
        from . import autograd as _ag

        n_ext = len(ext)
        untracked_idx = tuple(i for i in range(n_ext)
                              if i not in set(tracked_idx))
        tracked_vals = tuple(ext[i] for i in tracked_idx)
        untracked_vals = tuple(ext[i] for i in untracked_idx)
        ops = self.ops
        n_slots = len(self.slots)
        slot_avals = [(self.slots[i].shape, self.slots[i].dtype)
                      for i in rec_slots]

        if self._eager_vjp is not None:
            raw_vjp = self._eager_vjp
            all_avals = [(s.shape, s.dtype) for s in self.slots]

            def vjp_fn(cts):
                import jax
                import jax.numpy as jnp

                if not isinstance(cts, tuple):
                    cts = (cts,)
                full = [jnp.zeros(sh, dt) for sh, dt in all_avals]
                for ct, si in zip(cts, rec_slots):
                    full[si] = ct
                out = raw_vjp(tuple(full))
                return tuple(
                    None if (hasattr(c, "dtype")
                             and c.dtype == jax.dtypes.float0) else c
                    for c in out)
        else:
            bkey = (skey, tracked_idx, rec_slots)

            def vjp_fn(cts):
                import jax

                if not isinstance(cts, tuple):
                    cts = (cts,)
                bwd = _SEG_BWD_CACHE.get(bkey)
                if bwd is None:
                    replay = _build_replay(ops, n_slots)

                    def bwd_fn(cts_, tr, untr):
                        def f(*trr):
                            full = [None] * n_ext
                            for i, v in zip(tracked_idx, trr):
                                full[i] = v
                            for i, v in zip(untracked_idx, untr):
                                full[i] = v
                            vals = replay(*full)
                            return tuple(vals[i] for i in rec_slots)

                        _, vjp = jax.vjp(f, *tr)
                        out = vjp(cts_)
                        return tuple(
                            None if (hasattr(c, "dtype")
                                     and c.dtype == jax.dtypes.float0)
                            else c
                            for c in out)

                    bwd = jax.jit(bwd_fn)
                    _SEG_BWD_CACHE[bkey] = bwd
                return bwd(cts, tracked_vals, untracked_vals)

        def fwd_fn(*tr):
            # create_graph=True support: the segment's recorded outputs as
            # a function of its tracked inputs (untracked closed over —
            # they are fixed concrete values of THIS flush)
            replay = _build_replay(ops, n_slots)
            full = [None] * n_ext
            for i, v in zip(tracked_idx, tr):
                full[i] = v
            for i in untracked_idx:
                full[i] = ext[i]
            vals = replay(*full)
            return tuple(vals[i] for i in rec_slots)

        node = _ag.TapeNode(
            vjp_fn,
            [self.ext_tracked[i][0] for i in tracked_idx],
            slot_avals,
            name=f"bulk_segment[{len(ops)}]",
            fwd_fn=fwd_fn,
            in_arrays=[self.ext_tracked[i][1] for i in tracked_idx],
        )
        node.out_container = True
        for k, si in enumerate(rec_slots):
            owner = self.slots[si].owner
            nd = owner() if owner is not None else None
            if nd is not None:
                nd._tape = (node, k)


_seg_cache_max_cached = None


def _seg_cache_max() -> int:
    global _seg_cache_max_cached
    if _seg_cache_max_cached is None:
        from . import config

        try:
            _seg_cache_max_cached = int(
                config.get("MXNET_ENGINE_SEG_CACHE_MAX"))
        except Exception:
            _seg_cache_max_cached = _SEG_CACHE_MAX
    return _seg_cache_max_cached


# ---------------------------------------------------------------------------
# Raw engine push API parity (``MXEnginePushAsync/Sync``, c_api.h:3028-3110).
# External schedulers in the reference can push closures with explicit var
# deps. Here ordering is data-flow exact, so push == call.
# ---------------------------------------------------------------------------


def push_sync(fn, *args, **kwargs):
    return fn(*args, **kwargs)


def push_async(fn, *args, on_complete=None, **kwargs):
    out = fn(*args, **kwargs)
    if on_complete is not None:
        on_complete()
    return out
