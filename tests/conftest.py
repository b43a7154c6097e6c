"""Test harness: emulate an 8-device TPU-like mesh on CPU.

Per SURVEY.md §4, the reference has no multi-node test affordances at all;
here every test runs against a virtual 8-device CPU backend so pipeline /
tensor / sequence parallel paths are exercised without hardware.

The suite is a CPU suite whatever the environment says: the platform is
forced through the environment AND jax.config before any backend
initializes, so a machine that has a TPU still runs the tests on 8 virtual
CPU devices.
"""

import json
import math
import os
from pathlib import Path

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


# The deal of tier-1's files over the driver's workers (docs/DESIGN.md, "How
# tier-1 is dealt"): seconds of case time a file took in a whole run, made
# by ``tools/tier1_deal.py --write``.  It tunes the order and gates nothing.
TIER1_SECONDS = json.loads(
    (Path(__file__).parent / "data" / "tier1_seconds.json").read_text())


def deal_key(file_name):
    """Longest file first, and a file the record does not know before
    them all: a stale record costs some evenness and never a tail."""
    return -TIER1_SECONDS.get(file_name, math.inf)


def pytest_configure(config):
    # ``--dist loadfile`` hands the next file to the worker that runs dry,
    # in the order the files were collected, unless xdist first ranks them
    # by how many cases they hold, which is what it does by default (and
    # this suite's longest files hold the fewest).  A single-process run
    # has no such option.
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False
    config.addinivalue_line(
        "markers", "slow: long-running multi-process integration test")
    config.addinivalue_line(
        "markers", "quick: fast-lane smoke set (~2 min): one cheap, "
        "representative test per subsystem, for the edit-verify loop "
        "(`pytest -m quick`); the full suite stays the merge gate")


def pytest_collection_modifyitems(items):
    # stable: the order inside a file stays, and every worker computes the
    # same order, which xdist requires
    items.sort(key=lambda item: deal_key(item.path.name))


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs
