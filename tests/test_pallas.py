"""Pallas kernels: interpret-mode equivalence vs the XLA formulations.

The CPU-mesh suite runs the kernels under interpret=True — the same kernel
body the chip executes (the reference's analog: exercising cudf kernels
through the dual CPU/GPU runs, SURVEY.md section 4)."""

import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.ops import pallas_kernels as pk


@pytest.mark.parametrize("n,parts", [(100, 4), (1024, 8), (5000, 16),
                                     (1, 1), (1023, 3)])
def test_histogram_matches_xla(rng, n, parts):
    pids = jnp.asarray(rng.integers(0, parts, n).astype(np.int32))
    mask = jnp.asarray(rng.random(n) < 0.8)
    got = pk.partition_histogram(pids, mask, parts, interpret=True)
    want = pk.partition_histogram_xla(pids, mask, parts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(np.asarray(got).sum()) == int(np.asarray(mask).sum())


def test_histogram_empty_mask(rng):
    pids = jnp.asarray(rng.integers(0, 4, 500).astype(np.int32))
    mask = jnp.zeros(500, dtype=bool)
    got = pk.partition_histogram(pids, mask, 4, interpret=True)
    assert np.asarray(got).sum() == 0


def test_use_pallas_off_on_cpu():
    # conftest pins the cpu backend; dispatch must choose the XLA path
    assert not pk.use_pallas()
