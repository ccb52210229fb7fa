"""Test harness config.

Forces an 8-device virtual CPU mesh so multi-chip sharding logic is exercised
without TPU hardware; what runs on the chip is ``chip_smoke.py`` and the
benchmark, through the chip tool. The suite says nothing about the chip — the
only exception is ``test_chip_compile.py``, which asks the chip's compiler
(no device attached) about the kernels of the main path. The platform is set
both ways — env var for children the tests start, ``jax.config.update`` in
case a pytest plugin imported jax before this file ran.

Compile cache: the suite stays OUT of the persistent cache on purpose
(``JAX_ENABLE_COMPILATION_CACHE=false``, inherited by every worker process a
test starts, so ``configure_compile_cache`` there places a cache nobody
reads or writes). Tests are hermetic: no stale program from another PR's
tree, no cross-xdist-worker races on one directory, and nothing written
into the checkout the driver inspects.

Async tests run under the anyio pytest plugin with the asyncio backend.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def anyio_backend():
    return "asyncio"


@pytest.fixture(scope="session")
def cpu_devices():
    devices = jax.devices("cpu")
    assert len(devices) == 8, f"expected 8 virtual CPU devices, got {devices}"
    return devices
