"""Test harness setup: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's strategy of emulating a whole cluster inside one
process (``TESTReconfigurationMain.startLocalServers``,
reconfiguration/testing/TESTReconfigurationMain.java:86) — here the "machines"
are virtual XLA CPU devices.

The platform and the device count are set in ``os.environ`` (before jax is
imported) rather than in jax.config, so that the processes tests spawn —
cell workers, servers — inherit them: nothing in the package forces a
platform.  ``JAX_PLATFORMS`` set from outside wins.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (multi-process, soak)"
    )
    config.addinivalue_line(
        "markers",
        "multicore: needs real parallel cores (cell scaling asserts); "
        "auto-skipped when os.cpu_count() < 4",
    )


def pytest_collection_modifyitems(config, items):
    import pytest

    if (os.cpu_count() or 1) >= 4:
        return
    skip = pytest.mark.skip(
        reason=f"multicore test needs >=4 cores, have {os.cpu_count()}")
    for item in items:
        if "multicore" in item.keywords:
            item.add_marker(skip)
