"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's local-mode test strategy (docs/testing.md:42-66):
no cluster needed; multi-device behavior is tested on virtual devices.
"""
import os

# force CPU: tier-1 runs on XLA:CPU whatever the shell's JAX_PLATFORMS
# says (jax may already be imported, so set the env var AND update the
# config after import)
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

try:  # pin pyarrow pools before ANY use (see runtime.pin_arrow_threads)
    import pyarrow as _pa
    _pa.set_cpu_count(1)
    _pa.set_io_thread_count(1)
except ImportError:
    pass

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# sync dispatch: async executions on XLA's native pool racing a compile
# on an engine thread segfault this XLA build (runtime.sync_cpu_dispatch)
jax.config.update("jax_cpu_enable_async_dispatch", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running; excluded from the tier-1 run")


@pytest.fixture
def rng():
    return np.random.default_rng(42)
