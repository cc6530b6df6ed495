"""Test harness config: run JAX on a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; sharding/collective paths are
validated on virtual CPU devices.  The chip is reached only through
`python chip_smoke.py` (see README), never from the suite.
"""

import os
import sys

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# no persistent compile cache under the suite (children inherit this):
# XLA:CPU logs an error line per cache hit about pseudo machine features,
# and six workers have no use for each other's CPU executables
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

# the CPU backend cannot compile a Mosaic kernel: the suite (and only the
# suite) runs the Pallas codec in interpreter mode
sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
from seaweedfs_tpu.ops import rs_pallas  # noqa: E402

rs_pallas.INTERPRET = True


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 run")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection chaos suite; run separately with -m chaos")


def pytest_collection_modifyitems(config, items):
    # chaos tests imply slow: tier-1 (-m 'not slow') stays fast and
    # deterministic, while `-m chaos` selects exactly the chaos suite
    for item in items:
        if "chaos" in item.keywords:
            item.add_marker(pytest.mark.slow)
