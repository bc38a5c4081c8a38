"""Runtime lock-order sanitizer (``MXNET_LOCKDEP=1``).

The serving/training stack holds ~22 lock sites (batcher flushers, the
Router supervisor, hedge timers, the ContinuousEngine);
their ordering discipline is a convention nothing enforces at runtime.
This module is the dynamic half of the PR-13 gate (the static half is
``tools/mxlint`` rule L001): :func:`enable` replaces the
``threading.Lock`` / ``threading.RLock`` / ``threading.Condition``
factories with instrumented wrappers that

* record the per-process **acquisition-order graph** — one node per
  lock *creation site* (``file:line``), one edge A->B the first time any
  thread acquires B while holding A, with a stack sample for the edge;
* run a DFS cycle check on every new edge — an A->B edge closing a
  B->..->A path is a potential deadlock even if it never hangs in this
  run — and records a ``cycle`` violation;
* flag **blocking calls under a held lock** (``time.sleep``,
  ``Future.result`` with a non-zero timeout, ``Thread.join``,
  ``Condition.wait`` while holding *other* locks) as
  ``blocking_under_lock`` violations;
* dumps every violation through the PR-9 flight recorder
  (``flightrec-*-lockdep_*.json``) so the evidence survives the run.

Cost contract: with ``MXNET_LOCKDEP=0`` (the default) nothing is
patched — lock acquisition is untouched native code and importing this
module costs one dict. Enabled, each acquisition adds a thread-local
list append plus a dict probe per already-held lock; stack capture
happens only once per *new* edge.

Only locks **created after** :func:`enable` are instrumented: the
import-time module locks (recorder ring, counters, profiler core) stay
raw, which both keeps the sanitizer out of its own plumbing and focuses
the graph on the interesting instance locks (sessions, batchers,
routers) that are constructed at serve/train time.
"""
from __future__ import annotations

import os
import threading
import time
import traceback

__all__ = [
    "enable", "disable", "enabled", "reset", "violations", "cycles",
    "edges", "assert_no_cycles",
]

_MAX_VIOLATIONS = 256
_STACK_DEPTH = 12

_enabled = False
_orig: dict = {}            # patched name -> original object
_graph_lock = threading.Lock()   # raw on purpose: guards the structures below
_edges: dict = {}           # (a_site, b_site) -> {"count", "stack", "where"}
_adj: dict = {}             # a_site -> set(b_site)
_violations: list = []
_seen_blocking: set = set()  # (call_site, held_site) pairs already reported
_state = threading.local()   # .held: [(site, lock_id)], .depth: {}, .busy

# exact files whose frames are instrumentation plumbing, not user code
# (exact match, not a suffix: a user file named test_lockdep.py must
# still be a valid creation site)
_INTERNAL_FILES = (__file__, threading.__file__)


# -- per-thread state ---------------------------------------------------------
def _held():
    return getattr(_state, "held", None) or []


def _depths():
    d = getattr(_state, "depth", None)
    if d is None:
        d = _state.depth = {}
    return d


def _busy():
    return getattr(_state, "busy", False)


class _quiet:
    """Reentrancy guard: instrumentation internals (stack capture,
    recorder dumps) must not re-trigger instrumentation."""

    def __enter__(self):
        self._prev = getattr(_state, "busy", False)
        _state.busy = True

    def __exit__(self, *exc):
        _state.busy = self._prev


def _creation_site():
    """file:line of the frame that called the lock factory, skipping
    lockdep/threading internals — the lock's *class* identity."""
    for frame in reversed(traceback.extract_stack(limit=16)):
        fn = frame.filename
        if fn in _INTERNAL_FILES:
            continue
        return "%s:%d" % (os.path.relpath(fn) if fn.startswith("/") else fn,
                          frame.lineno)
    return "<unknown>"


def _stack_sample():
    return "".join(traceback.format_stack(limit=_STACK_DEPTH)[:-2])


# -- graph + violations -------------------------------------------------------
def _find_path(src, dst):
    """DFS path src -> dst over _adj (caller holds _graph_lock)."""
    stack, seen = [(src, [src])], {src}
    while stack:
        node, path = stack.pop()
        if node == dst:
            return path
        for nxt in _adj.get(node, ()):
            if nxt not in seen:
                seen.add(nxt)
                stack.append((nxt, path + [nxt]))
    return None


def _record_violation(kind, args):
    entry = dict(args)
    entry["kind"] = kind
    entry["thread"] = threading.current_thread().name
    entry["t"] = time.time()
    with _graph_lock:
        if len(_violations) >= _MAX_VIOLATIONS:
            return
        _violations.append(entry)
    try:
        from ..profiler import recorder as _recorder

        _recorder.note("lockdep", kind, {
            k: v for k, v in entry.items() if k != "stack"})
        _recorder.dump("lockdep_" + kind, args=entry, force=True)
    except Exception:  # noqa: BLE001 -- diagnostics must never take the run down
        pass


def _record_edges(site, lock_id):
    """Called (outside _quiet) before a first-depth acquisition of
    ``site`` while ``_held()`` locks are outstanding."""
    held = _held()
    if not held:
        return
    with _quiet():
        for held_site, _hid in held:
            if held_site == site:
                # reentrant class (two instances of one class, or an
                # RLock): no ordering information in a self-edge
                continue
            key = (held_site, site)
            with _graph_lock:
                known = key in _edges
                if known:
                    _edges[key]["count"] += 1
            if known:
                continue
            stack = _stack_sample()
            with _graph_lock:
                _edges[key] = {"count": 1, "stack": stack,
                               "where": threading.current_thread().name}
                _adj.setdefault(held_site, set()).add(site)
                path = _find_path(site, held_site)
            if path is not None:
                _record_violation("cycle", {
                    "edge": list(key),
                    "cycle": path + [site],
                    "stack": stack,
                })


def _push(site, lock_id):
    held = getattr(_state, "held", None)
    if held is None:
        held = _state.held = []
    held.append((site, lock_id))


def _pop(lock_id):
    held = getattr(_state, "held", None)
    if not held:
        return
    for i in range(len(held) - 1, -1, -1):
        if held[i][1] == lock_id:
            del held[i]
            return


def check_blocking(what, skip_id=None):
    """Record a ``blocking_under_lock`` violation when the current
    thread holds instrumented locks (other than ``skip_id``). Used by
    the patched ``time.sleep`` / ``Future.result`` / ``Thread.join``
    and by ``Condition.wait``; reported once per (call site, held
    lock-class) pair."""
    if _busy():
        return
    held = [(s, i) for (s, i) in _held() if i != skip_id]
    if not held:
        return
    with _quiet():
        for frame in reversed(traceback.extract_stack(limit=16)):
            fn = frame.filename
            if fn not in _INTERNAL_FILES:
                call_site = "%s:%d" % (fn, frame.lineno)
                break
        else:
            call_site = "<unknown>"
        new = []
        with _graph_lock:
            for held_site, _i in held:
                k = (call_site, held_site)
                if k not in _seen_blocking:
                    _seen_blocking.add(k)
                    new.append(held_site)
        if new:
            _record_violation("blocking_under_lock", {
                "call": what,
                "call_site": call_site,
                "held": new,
                "stack": _stack_sample(),
            })


# -- instrumented primitives --------------------------------------------------
class _InstrumentedLock:
    """Wrapper around a raw ``_thread.lock`` / ``_thread.RLock``;
    re-entrant inners are depth-tracked so only the outermost
    acquisition records graph edges."""

    _ld_reentrant = False

    def __init__(self, inner, site):
        self._ld_inner = inner
        self._ld_site = site

    # -- lockdep-aware acquire/release
    def acquire(self, blocking=True, timeout=-1):
        lid = id(self)
        depths = _depths()
        first = depths.get(lid, 0) == 0
        if first and not _busy():
            _record_edges(self._ld_site, lid)
        got = self._ld_inner.acquire(blocking, timeout)
        if got:
            depths[lid] = depths.get(lid, 0) + 1
            if first:
                _push(self._ld_site, lid)
        return got

    def release(self):
        self._ld_inner.release()
        lid = id(self)
        depths = _depths()
        n = depths.get(lid, 1) - 1
        if n <= 0:
            depths.pop(lid, None)
            _pop(lid)
        else:
            depths[lid] = n

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()

    def locked(self):
        return self._ld_inner.locked()

    def __repr__(self):
        return "<lockdep %s site=%s>" % (
            type(self._ld_inner).__name__, self._ld_site)


class _InstrumentedRLock(_InstrumentedLock):
    _ld_reentrant = True

    # Condition integration: threading.Condition picks these up at
    # construction time, so an instrumented RLock works as a Condition
    # lock (wait() fully releases it and restores the held stack).
    def _is_owned(self):
        return self._ld_inner._is_owned()

    def _release_save(self):
        st = self._ld_inner._release_save()
        lid = id(self)
        _depths().pop(lid, None)
        _pop(lid)
        return st

    def _acquire_restore(self, st):
        self._ld_inner._acquire_restore(st)
        lid = id(self)
        _depths()[lid] = 1
        _push(self._ld_site, lid)


def _make_lock():
    return _InstrumentedLock(_orig["Lock"](), _creation_site())


def _make_rlock():
    return _InstrumentedRLock(_orig["RLock"](), _creation_site())


class _InstrumentedCondition:
    """``threading.Condition`` over an instrumented lock, with the
    ``wait``-while-holding-other-locks check."""

    def __new__(cls, lock=None):
        if lock is None:
            lock = _make_rlock()
        cond = _orig["Condition"](lock)
        orig_wait = cond.wait

        def wait(timeout=None):
            check_blocking("Condition.wait",
                           skip_id=id(lock) if isinstance(
                               lock, _InstrumentedLock) else None)
            return orig_wait(timeout)

        cond.wait = wait
        return cond


# -- blocking-call patches ----------------------------------------------------
def _patched_sleep(secs):
    if secs and secs > 0:
        check_blocking("time.sleep(%r)" % (secs,))
    return _orig["sleep"](secs)


def _patched_result(self, timeout=None):
    if timeout != 0:
        check_blocking("Future.result(timeout=%r)" % (timeout,))
    return _orig["Future.result"](self, timeout)


def _patched_join(self, timeout=None):
    check_blocking("Thread.join(timeout=%r)" % (timeout,))
    return _orig["Thread.join"](self, timeout)


# -- public API ---------------------------------------------------------------
def enable():
    """Patch the ``threading`` factories + the blocking calls.
    Idempotent; locks created before this call stay uninstrumented."""
    global _enabled
    if _enabled:
        return
    import concurrent.futures

    _orig["Lock"] = threading.Lock
    _orig["RLock"] = threading.RLock
    _orig["Condition"] = threading.Condition
    _orig["sleep"] = time.sleep
    _orig["Future.result"] = concurrent.futures.Future.result
    _orig["Thread.join"] = threading.Thread.join
    threading.Lock = _make_lock
    threading.RLock = _make_rlock
    threading.Condition = _InstrumentedCondition
    time.sleep = _patched_sleep
    concurrent.futures.Future.result = _patched_result
    threading.Thread.join = _patched_join
    _enabled = True


def disable():
    """Undo :func:`enable` (tests). Already-created instrumented locks
    keep working — only the factories are restored."""
    global _enabled
    if not _enabled:
        return
    import concurrent.futures

    threading.Lock = _orig["Lock"]
    threading.RLock = _orig["RLock"]
    threading.Condition = _orig["Condition"]
    time.sleep = _orig["sleep"]
    concurrent.futures.Future.result = _orig["Future.result"]
    threading.Thread.join = _orig["Thread.join"]
    _enabled = False


def enabled():
    return _enabled


def reset():
    """Clear the graph and the violation log (tests)."""
    with _graph_lock:
        _edges.clear()
        _adj.clear()
        _violations.clear()
        _seen_blocking.clear()


def violations():
    """Snapshot of recorded violations (dicts with ``kind``:
    ``cycle`` | ``blocking_under_lock``)."""
    with _graph_lock:
        return list(_violations)


def cycles():
    """Just the lock-order cycles."""
    return [v for v in violations() if v["kind"] == "cycle"]


def edges():
    """Snapshot of the acquisition-order graph:
    {(a_site, b_site): count}."""
    with _graph_lock:
        return {k: v["count"] for k, v in _edges.items()}


def smoke_gate(rc):
    """Tier-1 smoke helper: print a one-line lockdep summary and
    escalate a passing exit code to failure when any lock-order cycle
    was recorded. Returns ``rc`` untouched when lockdep is off."""
    if not _enabled:
        return rc
    cyc = cycles()
    blocked = [v for v in violations()
               if v["kind"] == "blocking_under_lock"]
    print("LOCKDEP edges=%d cycles=%d blocking_under_lock=%d"
          % (len(edges()), len(cyc), len(blocked)))
    for v in cyc:
        print("LOCKDEP=CYCLE " + " -> ".join(v["cycle"]))
    for v in blocked:
        print("LOCKDEP=BLOCKING %s at %s holding %s"
              % (v["call"], v["call_site"], ",".join(v["held"])))
    if cyc and rc == 0:
        return 1
    return rc


def assert_no_cycles():
    """Raise ``RuntimeError`` naming every recorded lock-order cycle
    (the tier-1 smoke gate)."""
    cyc = cycles()
    if cyc:
        lines = [" -> ".join(v["cycle"]) for v in cyc]
        raise RuntimeError(
            "lockdep: %d lock-order cycle(s) recorded:\n  %s"
            % (len(cyc), "\n  ".join(lines)))
