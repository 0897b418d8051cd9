"""Test configuration.

Tests run on CPU with 8 virtual devices so the multi-chip sharding paths
(mesh, shard_map, collectives) are exercised without TPU hardware — the
analog of the reference's Spark `local[N]` test harness
(ADAMFunSuite / SparkFunSuite in the reference test tree).

Env vars must be set before the first `import jax` anywhere.
"""

import os
import sys
import pathlib

os.environ["JAX_PLATFORMS"] = "cpu"
# no persistent compile cache in tests: every run compiles what it runs
os.environ.setdefault("ADAM_TPU_NO_COMPILE_CACHE", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

import pytest  # noqa: E402

# Golden files / fixtures from the reference tree (read-only differential
# test inputs; tests that need them skip when the tree is absent).
REFERENCE_RESOURCES = pathlib.Path("/root/reference/adam-core/src/test/resources")


@pytest.fixture(scope="session")
def ref_resources():
    if not REFERENCE_RESOURCES.is_dir():
        pytest.skip("reference test resources not available")
    return REFERENCE_RESOURCES
