"""Weights from ``--seed``, drawn on the device in the type they are
served or trained in. The benchmark makes them and hands the same values
to the program and to the plain reference; neither makes its own.

A reference module describes its leaves as ``{name: (shape, kind)}`` with
kind ``normal`` (mean 0), ``ones_normal`` (mean 1) or ``zeros``; leaves
named ``layer<i>.<rest>`` form one group a layer, so that a deep model is
drawn a layer at a time by one compiled program.
"""
import re
import zlib

_LAYER = re.compile(r"^layer(\d+)\.(.+)$")


def groups(shapes):
    """Split leaf names into ``{group id: {leaf: (shape, kind)}}``; group
    -1 holds what belongs to no layer."""
    out = {}
    for name, spec in shapes.items():
        m = _LAYER.match(name)
        gid = int(m.group(1)) if m else -1
        out.setdefault(gid, {})[name] = spec
    return out


def _salt(name):
    m = _LAYER.match(name)
    return zlib.crc32((m.group(2) if m else name).encode())


class Maker:
    """Draws groups of leaves; one jitted program for each distinct set
    of shapes (every layer of a model shares one)."""

    def __init__(self, shapes, seed, std):
        import jax

        self.groups = groups(shapes)
        self.std = float(std)
        seed = int(seed)
        key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
        self.key = jax.random.fold_in(key, seed >> 31)
        self._jitted = {}

    def _program(self, leaves):
        import jax
        import jax.numpy as jnp

        sig = tuple((_salt(n), tuple(s), k) for n, (s, k) in leaves.items())
        if sig not in self._jitted:
            std = self.std

            def draw(key, gid):
                key = jax.random.fold_in(key, gid)
                out = []
                for salt, shape, kind in sig:
                    if kind == "zeros":
                        out.append(jnp.zeros(shape, jnp.float32))
                        continue
                    k = jax.random.fold_in(key, salt)
                    x = std * jax.random.normal(k, shape, jnp.float32)
                    out.append(x + 1.0 if kind == "ones_normal" else x)
                return out

            self._jitted[sig] = jax.jit(draw)
        return self._jitted[sig]

    def group(self, gid):
        """``{leaf name: array}`` of one group."""
        import numpy as np

        leaves = self.groups[gid]
        vals = self._program(leaves)(self.key, np.int32(gid + 1))
        return dict(zip(leaves, vals))

    def all(self):
        out = {}
        for gid in sorted(self.groups):
            out.update(self.group(gid))
        return out
