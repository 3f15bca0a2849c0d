"""Test harness: 8 virtual CPU devices in one process.

The standard JAX fake-backend trick (SURVEY.md §4 "Multi-device without a
cluster"): `--xla_force_host_platform_device_count=8` exposes 8 CPU "devices"
so mesh collectives — the DDP-equivalence property and psum'd metrics — are
testable in plain pytest with no TPU attached. Must run before jax imports.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def interpret_kernels():
    """The suite runs on the CPU, so every Pallas kernel it traces runs in
    interpret mode — requested here, once, for all tests. A test of the
    compiled kernels (or of the refusal without the request) sets
    `tpu_dp.ops._partition._interpret_requests` back to 0 itself."""
    from tpu_dp.ops import interpret_kernels

    with interpret_kernels():
        yield


@pytest.fixture(scope="session")
def mesh8():
    from tpu_dp.parallel import dist

    return dist.data_mesh()


@pytest.fixture(scope="session")
def mesh1():
    import jax

    from tpu_dp.parallel import dist

    return dist.data_mesh(devices=jax.devices()[:1])


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
