"""Test fixtures.

Multi-chip logic is tested on a virtual 8-device CPU mesh
(``xla_force_host_platform_device_count``), the JAX analogue of the
reference's in-process multi-raylet ``Cluster`` (``cluster_utils.py:99``).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402

import jax  # noqa: E402

# Tests force the CPU, before jax starts a backend: the default
# jax.devices() must be the 8 virtual CPUs whatever accelerator the machine
# has, or the multi-device collective paths would run on one device, and a
# test process must never take a chip that another process needs. The
# environment variable above covers children; the config update covers a
# jax that something imported before this file. float32 matmuls so
# sharded-vs-dense comparisons are not dominated by bf16 default-precision
# noise.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture
def ray_start_regular():
    """Single-node runtime (reference: conftest.py:244)."""
    import ray_tpu
    ray_tpu.shutdown()
    w = ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield w
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-node in-process cluster (reference: conftest.py:325)."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=False)
    yield cluster
    cluster.shutdown()


@pytest.fixture
def eight_device_mesh():
    import jax
    devices = jax.devices("cpu")
    assert len(devices) >= 8, f"need 8 virtual devices, got {len(devices)}"
    yield devices[:8]
