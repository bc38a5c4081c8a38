"""Test harness configuration.

Reference test strategy (SURVEY.md §4): pytest with per-test seeds and
reproducibility logging. TPU adaptation: all tests run on a virtual
8-device CPU mesh (``xla_force_host_platform_device_count``) so sharding /
collective paths execute without TPU hardware — the reference's
multi-process-on-one-host trick done the JAX way.
"""
import os
import zlib

# must be set before jax initializes; append so a user-supplied XLA_FLAGS
# (e.g. --xla_dump_to) doesn't silently collapse the virtual mesh to 1 device
_FLAG = "--xla_force_host_platform_device_count=8"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()
# the tests are written for that mesh; the chip is chip_smoke.py's
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as _np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def seed_rngs(request):
    """Seed numpy + framework RNGs per test (reference conftest.py:40-91)."""
    # crc32, not hash(): str hashing is randomized per process, which would
    # defeat the reproducibility this fixture exists to provide
    seed = zlib.crc32(request.node.nodeid.encode()) % (2**31)
    marker = request.node.get_closest_marker("seed")
    if marker is not None:
        seed = marker.args[0]
    _np.random.seed(seed)
    import mxnet_tpu as mx

    mx.random.seed(seed)
    yield


def pytest_configure(config):
    config.addinivalue_line("markers", "seed(n): fix the RNG seed for a test")
    config.addinivalue_line("markers", "serial: run without xdist")
    config.addinivalue_line("markers", "integration: slower end-to-end test")
    config.addinivalue_line(
        "markers", "slow: excluded from tier-1 (-m 'not slow'); the "
        "fault-injection stress loop and other long soak tests")
