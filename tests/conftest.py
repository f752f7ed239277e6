"""Test configuration.

Forces JAX onto a virtual 8-device CPU platform *before* jax is imported so
multi-chip sharding (TP/DP/SP meshes) is exercised without TPU hardware.
The chip is measured by chipbench/run.py and chip_smoke.py, not by the test suite.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import asyncio  # noqa: E402

import pytest  # noqa: E402

from dynamo_tpu.runtime import store as store_mod  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_memory_stores():
    store_mod.reset_memory_stores()
    yield
    store_mod.reset_memory_stores()


@pytest.fixture
def anyio_backend():
    return "asyncio"


def run_async(coro):
    """Run a coroutine in a fresh event loop (test helper)."""
    return asyncio.run(coro)
