"""Test configuration: force JAX onto a virtual 8-device CPU platform.

Tests run on the CPU; multi-chip sharding is validated on a host-platform
device mesh (tests/test_chip_compile.py compiles for a described chip).
Must run before the first `import jax` anywhere in the test process.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Pin the platform via config too, in case jax was imported before this
# conftest ran (backends are not yet initialized here, so this still
# takes effect).
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# The CLI turns the persistent compilation cache on for every test it
# drives; tests never write it (tests/test_chip_compile.py would leave
# entries no CPU run can read back).  Tests of the cache opt in.
jax.config.update("jax_enable_compilation_cache", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running (excluded from the tier-1 "
                   "'not slow' gate)")
