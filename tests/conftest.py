"""Test fixtures.

Multi-chip logic is tested on a virtual 8-device CPU mesh
(``xla_force_host_platform_device_count``), the JAX analogue of the
reference's in-process multi-raylet ``Cluster`` (``cluster_utils.py:99``).
"""

import os
import sys

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


# -- compiles for a described chip (tests/test_chip_compile*.py) ---------------


@pytest.fixture(scope="module")
def topo():
    """A TPU v5e 2x2 host, described and not attached: the TPU compiler
    compiles for its devices, and nothing runs on them."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def on_chip(topo):
    """``on_chip(shape, dtype)``: an array's shape and type on the first
    described chip, which can hold no array."""
    from jax.sharding import SingleDeviceSharding
    first = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=first)


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip; keep these tests out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def mosaic(topo, no_compile_cache):
    """The process's backend is the CPU, where the kernels would take
    interpret mode; a module that asks for this compiles for the described
    chip (module-scoped, so that a module-scoped fixture can compile)."""
    import ray_tpu.ops  # noqa: F401  (the seven kernel modules)
    with pytest.MonkeyPatch.context() as patch:
        for module in ("flash_attention", "linear_attention",
                       "sparse_attention", "ssd", "expert_stream",
                       "decode_attention", "selective_scan"):
            patch.setattr(sys.modules[f"ray_tpu.ops.{module}"],
                          "_backend_is_cpu", lambda: False)
        yield
