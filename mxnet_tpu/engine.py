"""Execution-engine facade.

The reference's dependency engine (``src/engine/threaded_engine.h``,
``include/mxnet/engine.h:117-318``) provides: (a) async execution of every op
with read/write dependency tracking, (b) ``WaitForVar``/``WaitForAll`` sync
points, (c) exception capture in async closures re-thrown at wait points, and
(d) bulk-execution segments.

On TPU the first three come from XLA's async dispatch model, and the
fourth is not needed:
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
  (d) a bulk segment is a hint to the threaded engine to push several ops
      as one; async dispatch needs none, so ``bulk``/``set_bulk_size``
      keep their names and a per-thread integer and change nothing. The
      whole-step executable is ``hybridize()`` (``CachedOp``).

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


# executable-invocation counter: every actual device dispatch — per-op
# apply, backward tape-node invocation — bumps this.
_dispatch_n = 0


def _count_dispatch(n=1):
    global _dispatch_n
    _dispatch_n += n


def dispatch_count() -> int:
    """Executable invocations so far (per-op dispatches + backward
    tape-node invocations)."""
    return _dispatch_n


def reset_dispatch_count():
    global _dispatch_n
    _dispatch_n = 0


def set_bulk_size(size):
    """Set this THREAD's bulk-execution size limit (reference
    ``python/mxnet/engine.py:25``); returns the previous value.  A hint
    to the reference's threaded engine, which XLA's async dispatch
    replaces: the integer is kept and no op runs differently for it."""
    prev = getattr(_state, "bulk_size", 0)
    _state.bulk_size = int(size)
    return prev


@contextlib.contextmanager
def bulk(size: int = 16):
    """Bulk-execution scope (``engine.h:311-317``): sets the thread's bulk
    size for the scope and restores it; like :func:`set_bulk_size` it
    changes how no op runs.  The scope duration and size are recorded
    while profiling."""
    prev = set_bulk_size(size)
    prof = _PROF
    t0 = prof.begin() if prof is not None and prof.ENABLED else 0
    try:
        yield
    finally:
        set_bulk_size(prev)
        if t0:
            prof.record_duration("engine::bulk", "engine", t0,
                                 args={"size": size})


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
