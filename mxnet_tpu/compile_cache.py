"""Persistent (disk-backed) compile cache for CachedOp executables.

PR-14: a process restart — replica scale-up (``serve.fleet``), a
``swap()`` rollout, a crashed worker rejoining — used to pay the full
XLA compile storm again even though the bucket lattice it compiles is
byte-identical to the one the last process built. This module wires the
**JAX persistent compilation cache** under every ``CachedOp`` build so
lowered executables land on disk keyed by their computation fingerprint,
and ``warmup()`` in a fresh process replays the lattice from disk in
cache-read seconds.

How it composes with the in-memory signature cache:

* ``CachedOp._cache`` stays the first-level cache (exact signature key →
  live executable; zero-cost hits).
* A signature **miss** still traces and calls ``jax.jit``, but XLA's
  lowering → executable step now consults the cache directory: a disk
  hit deserializes the executable instead of compiling (``disk_hits``);
  a miss compiles once and writes through (``disk_misses``).
* Where the directory comes from: ``JAX_COMPILATION_CACHE_DIR``, when
  the environment sets it, places the cache from outside — JAX reads it
  itself and this module never re-points it (the repo's own flag and an
  explicit ``path`` then have no say). Otherwise an explicit
  ``enable(path)`` or ``MXNET_COMPILE_CACHE_DIR``.
* Disk keys are **content** keys (JAX fingerprints the lowered HLO +
  compile options + backend; since PR 37 with its metadata, the scope
  paths and source lines every instruction carries: see :func:`enable`),
  so they are process-independent exactly
  when the traced computation is — which is what
  :func:`mxnet_tpu.cachedop.stable_signature_key` pins for the
  signature-level contract (two processes, same model + bucket lattice
  → same keys).

``enable()`` is idempotent and cheap; :meth:`CachedOp._lookup_or_build`
calls it on every signature miss, so *any* process that compiles
anything participates once the flag is set — no per-callsite wiring.
Counting uses ``jax``'s monitoring events
(``/jax/compilation_cache/cache_hits`` / ``cache_misses``), observed via
a process-global listener so ``cache_stats()`` can report
``disk_hits``/``disk_misses`` without touching jax internals per call.
"""
import os
import threading

__all__ = ["enable", "disable", "enabled", "cache_dir", "disk_hits",
           "disk_misses", "stats", "reset_stats"]

_lock = threading.Lock()
_dir = None            # active cache dir (None = not enabled)
_listener_on = False   # monitoring listener registered (never unregistered)
_hits = 0
_misses = 0

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def _on_event(name, **_kw):
    global _hits, _misses
    if name == _HIT_EVENT:
        _hits += 1
    elif name == _MISS_EVENT:
        _misses += 1


def _placed_from_outside():
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or None


def enable(path=None):
    """Point the JAX persistent compilation cache at ``path`` (default:
    ``MXNET_COMPILE_CACHE_DIR``). Returns True when active. No-op
    (False) when both are empty — the knob is opt-in. Idempotent;
    re-enabling with a different explicit ``path`` re-points the cache.

    With ``JAX_COMPILATION_CACHE_DIR`` in the environment the cache
    stays where that says: the directory is recorded for :func:`stats`
    and the write-through thresholds are lowered, but
    ``jax_compilation_cache_dir`` is never written here.
    """
    global _dir, _listener_on
    from . import config

    outside = _placed_from_outside()
    if outside:
        path = outside
    elif path is None:
        path = config.get("MXNET_COMPILE_CACHE_DIR") or None
    if not path:
        return _dir is not None
    path = os.path.abspath(str(path))
    with _lock:
        if _dir == path:
            return True
        import jax
        from jax._src import monitoring

        if not outside:
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
        # serve executables are small and compile fast on CPU CI; cache
        # everything so the second process compiles literally nothing
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # an executable's names are part of what it is: the scopes the
        # program writes while tracing (profiler.core.DEVICE_SCOPES) live
        # in HLO metadata, which JAX leaves out of the key by default, so
        # a directory warmed by a program from before a scope existed, or
        # with another path to a block, would hand back an executable
        # whose device events carry the old names or none. With metadata
        # in the key such an entry is a miss: compiled once, written
        # beside the old one, a hit ever after
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        if not _listener_on:
            monitoring.register_event_listener(_on_event)
            _listener_on = True
        _dir = path
    return True


def disable():
    """Detach JAX from the persistent cache. Bench/test hygiene: a
    scoped cold-vs-warm measurement must not leave every later compile
    in the process writing through to its temp dir. The monitoring
    listener stays registered (it only counts); :func:`enable`
    re-points."""
    global _dir
    with _lock:
        if _dir is None:
            return
        if not _placed_from_outside():
            import jax

            jax.config.update("jax_compilation_cache_dir", None)
        _dir = None


def enabled():
    return _dir is not None


def cache_dir():
    return _dir


def disk_hits():
    """Executables deserialized from disk instead of compiled."""
    return _hits


def disk_misses():
    """Compiles that went to XLA and wrote through to disk."""
    return _misses


def reset_stats():
    global _hits, _misses
    with _lock:
        _hits = 0
        _misses = 0


def _disk_usage(path):
    total = entries = 0
    try:
        for f in os.listdir(path):
            if f.endswith("-cache"):
                entries += 1
                total += os.path.getsize(os.path.join(path, f))
    except OSError:
        pass
    return entries, total


def stats():
    """Telemetry dict (pulled by ``profiler.export.snapshot()`` under
    the ``compile_cache.*`` namespace and folded into
    ``cachedop.cache_stats()``)."""
    entries = nbytes = 0
    if _dir is not None:
        entries, nbytes = _disk_usage(_dir)
    return {"enabled": _dir is not None,
            "dir": _dir or "",
            "disk_hits": _hits,
            "disk_misses": _misses,
            "disk_entries": entries,
            "disk_bytes": nbytes}
