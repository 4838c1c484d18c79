"""Test fixtures.

We request EIGHT host devices (not 512 — that is dry-run-only, see
launch/dryrun.py) so distributed behaviour (shard_map, ppermute chains,
GSPMD) is actually exercised in-process.  Must run before jax initializes.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tier2: slower sweeps (MS-BFS cross-product, benchmark smoke) — "
        "skipped unless RUN_TIER2=1; CI runs them in a non-blocking job",
    )


def pytest_collection_modifyitems(config, items):
    if os.environ.get("RUN_TIER2"):
        return
    skip = pytest.mark.skip(reason="tier-2 (set RUN_TIER2=1 to run)")
    for item in items:
        if "tier2" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def mesh8():
    return jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))


@pytest.fixture(scope="session")
def mesh24():
    """2x4 hierarchical mesh (pod-like axis + data axis)."""
    return jax.make_mesh(
        (2, 4), ("pod", "data"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )


@pytest.fixture(scope="session")
def mesh_dm():
    """(data=2, model=4) mesh for TP-sharded model tests."""
    return jax.make_mesh(
        (2, 4), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
