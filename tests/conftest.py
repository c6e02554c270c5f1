"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's test strategy (SURVEY.md section 4): CPU execution is
the oracle, and distributed paths are exercised without a cluster.  Here the
"local-cluster" analog is XLA's host-platform device multiplexing — 8 virtual
CPU devices so Mesh/shard_map shuffle paths compile and run in CI without TPU
hardware.
"""

import os

# Must happen before jax initializes its backends.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
# XLA:CPU defaults to fast-math, which breaks correctly-rounded f64 division
# (7.0/3 comes out 2 digits short); the CPU oracle tests need exact IEEE.
if "xla_cpu_enable_fast_math" not in flags:
    flags += " --xla_cpu_enable_fast_math=false"
os.environ["XLA_FLAGS"] = flags.strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax  # noqa: E402

# Tests force the CPU: the virtual 8-device mesh, whatever the host has.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_pallas_gate():
    """A test that flips SPARK_RAPIDS_TPU_DISABLE_PALLAS must not poison
    later tests through the lru_cache'd use_pallas() decision."""
    from spark_rapids_tpu.ops.pallas_kernels import reset_use_pallas
    reset_use_pallas()
    yield
    reset_use_pallas()


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture
def rng():
    return np.random.default_rng(42)
