"""Test configuration.

Sharding/mesh tests run on a virtual 8-device CPU mesh; the chip path
is exercised separately by `python chip_smoke.py`.  All env vars must
be set before `import jax` (jax snapshots them into config defaults at
import time), hence the ordering below.
"""

import os
import sys

# Tests run on the CPU, on a virtual 8-device mesh for every sharding
# path; the chip runs `python chip_smoke.py`.
os.environ["JAX_PLATFORMS"] = "cpu"
# In-process tests compile cold (the fixture below places the cache of
# the processes they spawn).
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = \
        (xla_flags + " --xla_force_host_platform_device_count=8").strip()

# Imported here, after the environment above and before any test sets
# JAX_COMPILATION_CACHE_DIR, so this process's config has no cache.
import jax  # noqa: E402
import pytest  # noqa: E402

if jax.config.jax_compilation_cache_dir:
    raise RuntimeError("JAX was imported with a compile cache before "
                       "tests/conftest.py could turn it off")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _child_compile_cache(tmp_path, monkeypatch):
    """Entry points a test spawns (party children, tools/serve.py,
    bench.py, ...) keep their persistent compile cache in the test's
    tmp_path, never in the checkout.  The test process itself stays
    cold: JAX read the variable, unset, when conftest imported it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "jax-cache"))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy differential/adversarial/driver suites (excluded "
        "from the fast CI tier; run with -m slow or no filter)")
