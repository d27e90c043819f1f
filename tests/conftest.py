"""Test harness config.

Force the local CPU backend with 8 virtual devices so the distributed layer
(device-mesh sharding, psum merges) is exercised without TPU hardware —
mirroring the reference's strategy of testing PEM/Kelvin distribution with
fake DistributedState protos (SURVEY.md §4).

- JAX_PLATFORMS=cpu keeps every test off a chip, even on a machine that
  has one; the post-import ``jax.config.update`` also covers a JAX that a
  plugin imported before this file ran.
- XLA_FLAGS must carry the virtual-device count before backends initialize.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# CI hosts can be saturated by a concurrent benchmark; give stalled-source
# detection generous headroom so cross-process tests don't time out while
# the machine is merely slow (children inherit this through spawn).
os.environ.setdefault("PIXIE_TPU_EXEC_SOURCE_STALL_S", "180")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)
