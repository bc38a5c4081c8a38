"""Conformance tests for the persistent compile cache (PR-14,
``mxnet_tpu/compile_cache.py``) and the stable CachedOp signature-key
contract (``cachedop.stable_signature_key`` /
``CachedOp.signature_keys()``): key digests must be canonical,
collision-meaningful, and **byte-identical across processes** (the
regression two fresh interpreters are spawned to pin), and a second
process warming the same bucket lattice from one cache dir must
deserialize every executable from disk (``disk_hits > 0``) and compile
nothing new (``disk_misses == 0``).
"""
import json
import os
import subprocess
import sys

import pytest

from mxnet_tpu import compile_cache
from mxnet_tpu.cachedop import _TRACED, stable_signature_key

_CHILD = r"""
import json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import cachedop, compile_cache, gluon
compile_cache.enable(sys.argv[1])
mx.random.seed(0)
net = gluon.nn.HybridSequential()
net.add(gluon.nn.Dense(16, activation="relu"))
net.add(gluon.nn.Dense(4))
net.initialize()
from mxnet_tpu.serve import InferenceSession
sess = InferenceSession(net, batch_buckets=(1, 2, 4), name="cc_child")
sess.warmup(np.zeros((1, 8), np.float32))
keys = sorted({k for op in list(cachedop._instances)
               for k in op.signature_keys()})
print("CC_CHILD=" + json.dumps({
    "keys": keys,
    "disk_hits": compile_cache.disk_hits(),
    "disk_misses": compile_cache.disk_misses()}))
"""


def _spawn(cache_dir, outside=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if outside is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(outside)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    proc = subprocess.run([sys.executable, "-c", _CHILD, str(cache_dir)],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("CC_CHILD=")]
    assert proc.returncode == 0 and lines, \
        f"child failed rc={proc.returncode}: {proc.stderr[-2000:]}"
    return json.loads(lines[0].split("=", 1)[1])


class TestStableKeys:
    def test_canonicalization(self):
        # order-insensitive containers, the traced sentinel, and bytes
        # all normalize; digests are 64-hex sha256
        k = (_TRACED, ("a", 1), frozenset({2, 1}), {"b": 2.0, "a": None},
             b"\x01\xff")
        same = (_TRACED, ("a", 1), frozenset({1, 2}),
                {"a": None, "b": 2.0}, b"\x01\xff")
        d = stable_signature_key(k)
        assert d == stable_signature_key(same)
        assert len(d) == 64 and set(d) <= set("0123456789abcdef")

    def test_digest_is_collision_meaningful(self):
        base = (_TRACED, (4, 8), "float32")
        assert stable_signature_key(base) \
            != stable_signature_key((_TRACED, (4, 16), "float32"))
        # compiler options fold into the digest (a different XLA config
        # is a different executable on disk)
        assert stable_signature_key(base) \
            != stable_signature_key(base, {"xla_cpu_foo": True})

    def test_exotic_statics_never_leak_object_ids(self):
        class Weird:  # repr would embed 0x<addr> — the digest must not
            pass

        assert stable_signature_key((Weird(),)) \
            == stable_signature_key((Weird(),))

    def test_cross_process_keys_identical(self, tmp_path):
        # THE satellite regression: two fresh interpreters tracing the
        # same model over the same bucket lattice report byte-identical
        # signature_keys() — and via the shared cache dir, the second
        # warms entirely from disk
        p1 = _spawn(tmp_path)
        p2 = _spawn(tmp_path)
        assert p1["keys"] and p1["keys"] == p2["keys"]
        assert p1["disk_misses"] > 0
        assert p2["disk_hits"] > 0 and p2["disk_misses"] == 0


class TestEnableDisable:
    def test_placed_from_outside(self, tmp_path, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR places the cache: an explicit path
        (and the repo's flag) have no say, jax_compilation_cache_dir is
        never written, and the executables land in the outside dir."""
        import jax

        outside, inside = tmp_path / "outside", tmp_path / "inside"
        prev = compile_cache.cache_dir()
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(outside))
        try:
            assert compile_cache.enable(str(inside))
            assert compile_cache.stats()["dir"] == str(outside)
            assert jax.config.jax_compilation_cache_dir == before
        finally:
            compile_cache.disable()  # env still set: forgets, writes nothing
            assert jax.config.jax_compilation_cache_dir == before
            monkeypatch.undo()
            if prev is not None:
                compile_cache.enable(prev)
        p1 = _spawn(inside, outside=outside)
        p2 = _spawn(inside, outside=outside)
        assert p1["disk_misses"] > 0
        assert p2["disk_hits"] > 0 and p2["disk_misses"] == 0
        assert any(f.endswith("-cache") for f in os.listdir(outside))
        assert not inside.exists()

    def test_opt_in_and_repoint(self, tmp_path, monkeypatch):
        import jax

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        prev = compile_cache.cache_dir()
        try:
            assert compile_cache.enable(str(tmp_path / "a"))
            assert compile_cache.enabled()
            assert compile_cache.cache_dir() == str(tmp_path / "a")
            # idempotent + re-pointable
            assert compile_cache.enable(str(tmp_path / "a"))
            assert compile_cache.enable(str(tmp_path / "b"))
            assert compile_cache.cache_dir() == str(tmp_path / "b")
            st = compile_cache.stats()
            assert st["enabled"] and st["dir"] == str(tmp_path / "b")
            compile_cache.disable()
            assert not compile_cache.enabled()
            assert not compile_cache.stats()["enabled"]
            # enable() with nothing configured stays a no-op unless the
            # flag is set
            if not os.environ.get("MXNET_COMPILE_CACHE_DIR"):
                assert compile_cache.enable() is False
        finally:
            compile_cache.disable()
            jax.config.update("jax_compilation_cache_dir", before)
            monkeypatch.undo()
            if prev is not None:
                compile_cache.enable(prev)

    def test_cache_stats_carries_disk_counters(self):
        from mxnet_tpu import cachedop

        agg = cachedop.cache_stats()
        assert "disk_hits" in agg and "disk_misses" in agg

    def test_export_snapshot_carries_compile_cache(self):
        from mxnet_tpu.profiler import export

        snap = export.snapshot()
        assert "compile_cache.enabled" in snap
        assert "compile_cache.disk_hits" in snap
        assert "compile_cache.disk_bytes" in snap


_SCOPE_CHILD = r"""
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, jax.numpy as jnp
from mxnet_tpu import compile_cache
compile_cache.enable(sys.argv[1])
def work(x):
    return jnp.tanh(x * 2.0 + 1.0).sum()
def named(x):
    with jax.named_scope(sys.argv[2]):
        return work(x)
fn = jax.jit(named if sys.argv[2] else work)
x = jnp.ones((8, 8))
h0, m0 = compile_cache.disk_hits(), compile_cache.disk_misses()
fn(x).block_until_ready()
h1, m1 = compile_cache.disk_hits(), compile_cache.disk_misses()
text = fn.lower(x).compile().as_text()
print("CC_SCOPE=%d %d %d" % (h1 - h0, m1 - m0,
                             int(sys.argv[2] != "" and sys.argv[2] in text)))
"""


def _scope_child(cache_dir, scope):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    proc = subprocess.run(
        [sys.executable, "-c", _SCOPE_CHILD, str(cache_dir), scope],
        env=env, capture_output=True, text=True, timeout=300)
    line, = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("CC_SCOPE=")]
    return tuple(int(x) for x in line.split("=", 1)[1].split())


def test_an_entry_from_before_a_scope_is_a_miss(tmp_path):
    """The names a program is compiled under are part of the key
    (``enable`` puts metadata in it): a directory warmed by the same
    computation without a scope does not hand its executable to the
    program that has one, whose device events would then carry no name;
    the same program again is a hit."""
    assert _scope_child(tmp_path, "") == (0, 1, 0)
    assert _scope_child(tmp_path, "serve_step.decode") == (0, 1, 1)
    assert _scope_child(tmp_path, "serve_step.decode") == (1, 0, 1)
