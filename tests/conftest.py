"""Test configuration: force an 8-virtual-device CPU platform.

Multi-chip TPU hardware is not available in CI; sharding/collective logic is
validated on a virtual CPU mesh (the in-process fake-fabric capability the
reference lacked — SURVEY.md §4 "gap to close").

A pytest plugin may import jax before this file runs (the JAX_PLATFORMS
env var alone is then read too late), so ``jax.config.update`` after import
is authoritative; XLA_FLAGS still applies because the CPU backend
initializes lazily at first use.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_mesh():
    assert jax.default_backend() == "cpu", jax.default_backend()
    assert len(jax.devices()) == 8, jax.devices()
    yield


@pytest.fixture
def rng():
    return np.random.default_rng(1234)
