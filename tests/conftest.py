"""Test harness: force JAX onto a virtual 8-device CPU mesh.

Must set env before jax is imported anywhere (SURVEY.md §4).
"""
import os

os.environ.setdefault("RAY_TPU_STORE_BYTES", str(1 << 30))

import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# force_cpu wins over whatever the ambient JAX_PLATFORMS / XLA_FLAGS say
# (must run before first jax use).
from ray_tpu.util.jaxenv import force_cpu  # noqa: E402
force_cpu(n_virtual_devices=8)

import pytest  # noqa: E402


@pytest.fixture(scope="module")
def rt():
    """A shared driver runtime per test module."""
    import ray_tpu
    handle = ray_tpu.init(num_cpus=8)
    yield handle
    ray_tpu.shutdown()
