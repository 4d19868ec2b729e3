"""Test fixtures: virtual 8-device CPU mesh.

The reference tests multi-node behaviour with two local processes over real
gloo/MPI on localhost (reference BERT/tests/communication/README.md); the
TPU-native analogue is XLA's host-platform device-count override, which gives
real (not mocked) collectives over N virtual CPU devices (SURVEY.md §4).

This file must set the env vars before anything imports jax.
"""

import os

# The suite runs on the virtual CPU mesh wherever it is started — except the
# opt-in hardware module (tests/test_tpu_hw.py, OKTOPK_TPU_HW=1), which is
# run alone and needs jax's own platform choice (the TPU) left in place.
_flag = "--xla_force_host_platform_device_count=8"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()
if os.environ.get("OKTOPK_TPU_HW") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from oktopk_tpu.comm import get_mesh  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    return get_mesh((8,), ("data",), devices=devices[:8])


@pytest.fixture(scope="session")
def mesh4(devices):
    return get_mesh((4,), ("data",), devices=devices[:4])


@pytest.fixture
def rng():
    return np.random.RandomState(42)


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs():
    """Free each module's compiled programs when it ends. Every XLA:CPU
    executable holds memory mappings; kept for the whole session they pass
    the kernel's per-process limit (vm.max_map_count, 65530) about three
    quarters of the way through the suite, and the next compile segfaults."""
    yield
    jax.clear_caches()
