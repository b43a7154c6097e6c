"""Test harness: emulate an 8-device TPU-like mesh on CPU.

Per SURVEY.md §4, the reference has no multi-node test affordances at all;
here every test runs against a virtual 8-device CPU backend so pipeline /
tensor / sequence parallel paths are exercised without hardware.

The suite is a CPU suite whatever the environment says: the platform is
forced through the environment AND jax.config before any backend
initializes, so a machine that has a TPU still runs the tests on 8 virtual
CPU devices.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running multi-process integration test")
    config.addinivalue_line(
        "markers", "quick: fast-lane smoke set (~2 min): one cheap, "
        "representative test per subsystem, for the edit-verify loop "
        "(`pytest -m quick`); the full suite stays the merge gate")


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs
