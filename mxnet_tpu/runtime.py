"""Runtime feature detection (reference: ``python/mxnet/runtime.py`` over
``src/libinfo.cc`` — compile-time flags queryable at runtime)."""
from __future__ import annotations

from collections import namedtuple

Feature = namedtuple("Feature", ["name", "enabled"])


def _detect():
    import jax

    feats = {
        "TPU": any(d.platform == "tpu" for d in jax.devices()) or
               jax.default_backend() == "tpu",
        "CUDA": False,
        "CUDNN": False,
        "MKLDNN": False,
        "XLA": True,
        "PALLAS": True,
        "INT64_TENSOR_SIZE": True,
        "F16C": True,
        "BF16": True,
        "DIST_KVSTORE": True,       # dist_tpu_sync over jax.distributed
        "SIGNAL_HANDLER": False,
        "PROFILER": True,
        "FLASH_ATTENTION": True,
        "RING_ATTENTION": True,
        "OPENCV": False,
        "PIL": _has("PIL"),
    }
    return feats


def _has(mod):
    import importlib.util

    return importlib.util.find_spec(mod) is not None


class Features(dict):
    """Mapping name -> Feature (reference ``runtime.Features``)."""

    def __init__(self):
        super().__init__(
            {k: Feature(k, v) for k, v in _detect().items()})

    def is_enabled(self, name):
        feat = self.get(name.upper())
        return bool(feat and feat.enabled)

    def __repr__(self):
        on = [k for k, f in self.items() if f.enabled]
        return f"Features({', '.join(sorted(on))})"


def feature_list():
    return list(Features().values())
