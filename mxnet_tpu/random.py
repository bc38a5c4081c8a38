"""Random number generation: stateful MXNet-style API over ``jax.random``.

Reference: per-device seeded generator pools shared through the resource
manager (``include/mxnet/random_generator.h``, ``src/resource.cc:93-138``),
seeded by ``mx.random.seed``.

TPU design: a process-global :class:`RandomState` holds a ``jax.random`` key
and splits it per draw (eager mode). Inside a traced/compiled forward
(``hybridize``), stateful splitting would bake one key into the executable,
so the CachedOp installs a *trace RNG* whose draws are ``fold_in``s of a key
that is an ordinary traced input — every compiled call gets fresh
randomness, matching the reference where dropout re-draws per call via the
engine's RNG resource (``kRandom`` in ``include/mxnet/resource.h``).
"""
from __future__ import annotations

import threading


def _jr():
    import jax.random as jr

    return jr


class RandomState:
    """Splittable stateful RNG."""

    def __init__(self, seed: int = 0):
        self._seed = seed
        self._key = None
        self._counter = 0

    def _ensure(self):
        if self._key is None:
            self._key = _jr().PRNGKey(self._seed)

    def seed(self, seed: int):
        self._seed = int(seed)
        self._key = _jr().PRNGKey(self._seed)
        self._counter = 0

    def next_key(self):
        # fold_in of a python counter rather than storing split() results:
        # if next_key is reached inside someone's jit trace, the stored state
        # (concrete key + int) must never become a tracer or it leaks out of
        # the trace and poisons later draws
        self._ensure()
        self._counter += 1
        return _jr().fold_in(self._key, self._counter)


class TraceRNG:
    """RNG used during jit tracing: folds a counter into a traced base key."""

    def __init__(self, base_key):
        self.base_key = base_key
        self.counter = 0

    def next_key(self):
        self.counter += 1
        return _jr().fold_in(self.base_key, self.counter)


class _RNGStack(threading.local):
    def __init__(self):
        super().__init__()
        self.stack = []


_global_state = RandomState(0)
_trace_stack = _RNGStack()


def seed(seed_state, ctx="all"):  # pylint: disable=unused-argument
    """Seed the global generator (``mx.random.seed``)."""
    _global_state.seed(seed_state)


# monotone count of key draws; the eager per-op jit cache (ops/registry.py)
# refuses to cache any trace that consumed a key — a cached trace would
# replay the SAME baked-in key on every call, freezing the randomness
_consume_count = 0


def consume_count() -> int:
    return _consume_count


def next_key():
    """Fresh PRNG key from the active generator (trace-aware)."""
    global _consume_count
    _consume_count += 1
    if _trace_stack.stack:
        return _trace_stack.stack[-1].next_key()
    return _global_state.next_key()


def skip_key():
    """Advance the active stream exactly as :func:`next_key` does, without
    making the key: for a caller that knows its compiled program reads
    none (a CachedOp entry whose trace drew no key). The counters move,
    so every later draw of every seeded stream is what it would have
    been; the eager ``fold_in`` (a dispatch of its own) is not paid."""
    global _consume_count
    _consume_count += 1
    if _trace_stack.stack:
        _trace_stack.stack[-1].counter += 1
    else:
        _global_state._counter += 1


def as_threefry(key):
    """Derive a threefry2x32 key from any PRNG key.

    A few jax samplers (``jax.random.poisson``) are implemented only for
    threefry; under the framework's rbg default (see ``mxnet_tpu/__init__``)
    their call sites derive a threefry key from the active key's raw bits
    — deterministic per draw, independent across draws.
    """
    import jax
    import jax.numpy as jnp

    if jax.dtypes.issubdtype(getattr(key, "dtype", None), jax.dtypes.prng_key):
        data = jax.random.key_data(key)
    else:
        data = key
    folded = jnp.asarray(data, jnp.uint32).reshape(-1)[:2]
    if folded.shape[0] < 2:
        folded = jnp.pad(folded, (0, 2 - folded.shape[0]))
    return jax.random.wrap_key_data(folded, impl="threefry2x32")


def push_trace_rng(base_key) -> TraceRNG:
    rng = TraceRNG(base_key)
    _trace_stack.stack.append(rng)
    return rng


def pop_trace_rng() -> TraceRNG:
    """Pop and return the trace RNG: its ``counter`` says how many keys
    the traced code drew."""
    return _trace_stack.stack.pop()


def in_trace() -> bool:
    return bool(_trace_stack.stack)
