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


@pytest.fixture(autouse=True)
def _packed_prefill_programs_exist_at_start(monkeypatch):
    """The suite's engines run what a worker runs: an admission wave's
    prefills go out packed where the model's limit allows it. A worker's
    packed programs come up on a thread of the runner's within its first
    minute and it sends singles until then; here ``start`` waits for them, so
    two engines a test compares run the same programs. Which requests share
    a wave is still the machine's timing, and a row's logits out of a packed
    program differ in their last bits from the same row's alone (1e-6 in
    float32 here): a test that holds streams byte for byte across engines
    fixes its wave with ``engine_waves.one_wave``."""
    from dynamo_tpu.engine.runner import LocalRunner

    start = LocalRunner._start_pack_compiles

    def start_and_wait(self):
        start(self)
        if self._pack_thread is not None:
            self._pack_thread.join()

    monkeypatch.setattr(LocalRunner, "_start_pack_compiles", start_and_wait)


@pytest.fixture
def anyio_backend():
    return "asyncio"


def run_async(coro):
    """Run a coroutine in a fresh event loop (test helper)."""
    return asyncio.run(coro)
