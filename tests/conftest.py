"""Test harness: force an 8-device virtual CPU mesh before jax initializes.

Mirrors the reference's one-machine multi-node test strategy
(ref: python/ray/tests/conftest.py:589-719, cluster_utils.py:135): tests run
against virtual topology, not real hardware. ``JAX_PLATFORMS=cpu`` keeps
this process off any chip; ``RT_FORCE_CPU_DEVICES`` does the same for every
process the runtime spawns (ray_tpu/utils/device.py), chip leases included.
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
# Children spawned by the runtime inherit these so worker processes also use
# the virtual CPU mesh during tests.
os.environ["RT_FORCE_CPU_DEVICES"] = "8"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual cpu devices, got {devs}"
    return devs
