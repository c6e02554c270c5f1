"""Pallas TPU kernels for the engine's hot data-parallel primitives.

- ``partition_histogram``: per-row partition-id counts.  XLA lowers
  ``segment_sum`` / one-hot scatter to a serialized scatter on TPU; here
  each grid step one-hot-expands a row block in VMEM and accumulates a
  (num_parts, 1) running sum — the TPU grid is sequential, so the
  accumulate-into-output pattern is race-free.  Feeds shuffle partition
  sizing and AQE statistics (the reference gets these numbers from cudf's
  ``contiguousSplit`` metadata, GpuPartitioning.scala:50).  Compiles for
  the chip (tests/test_chip_compile.py).

Tests run the kernel under ``interpret=True`` on the CPU mesh.
``use_pallas()`` gates dispatch: real TPU backends only (the interpreter
is for tests — the XLA formulation is faster on CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BLOCK_ROWS = 1024


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=1)
def use_pallas() -> bool:
    """True when the default backend is a real TPU."""
    import os
    if os.environ.get("SPARK_RAPIDS_TPU_DISABLE_PALLAS"):
        return False
    return _on_tpu()


def reset_use_pallas() -> None:
    """Drop the cached ``use_pallas()`` decision.

    The gate is ``lru_cache``'d over env+backend; a test (or an embedder)
    that flips ``SPARK_RAPIDS_TPU_DISABLE_PALLAS`` mid-process must call
    this or the stale decision poisons every later dispatch."""
    use_pallas.cache_clear()


def _hist_kernel(key_ref, out_ref, *, num_parts: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    keys = key_ref[...]            # (1, BLOCK) int32, masked rows are -1
    # one-hot (num_parts, BLOCK): partitions down the sublanes, rows
    # along the lanes, so the (1, BLOCK) key row broadcasts down the
    # sublanes as loaded — Mosaic refuses to reshape a vector across
    # the lane/sublane axes.  The row-axis sum is a dense reduction the
    # VPU/XLU handle natively — no scatter.
    parts = jax.lax.broadcasted_iota(
        jnp.int32, (num_parts, keys.shape[1]), 0)
    onehot = jnp.where(keys == parts, jnp.int32(1), jnp.int32(0))
    # dtype= pins the accumulator: under x64 an int32 sum promotes to
    # int64 and the store into the int32 out ref refuses
    out_ref[...] += onehot.sum(axis=1, keepdims=True, dtype=jnp.int32)


def partition_histogram(pids: jnp.ndarray, mask: jnp.ndarray,
                        num_parts: int,
                        interpret: bool | None = None) -> jnp.ndarray:
    """counts[p] = number of rows with pids[i] == p and mask[i].

    ``pids`` int32[capacity], ``mask`` bool[capacity]; capacity is padded
    up to a whole number of blocks internally.
    """
    if interpret is None:
        interpret = not _on_tpu()
    capacity = pids.shape[0]
    if capacity == 0:
        # grid would be 0: the step-0 output init never runs
        return jnp.zeros(num_parts, dtype=jnp.int32)
    # the mask folds into the key outside the kernel (XLA fuses it into
    # the producer): one int32 operand, no i1 vector inside the kernel
    keys = jnp.where(mask, pids.astype(jnp.int32), jnp.int32(-1))
    padded = ((capacity + _BLOCK_ROWS - 1) // _BLOCK_ROWS) * _BLOCK_ROWS
    if padded != capacity:
        keys = jnp.pad(keys, (0, padded - capacity), constant_values=-1)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, num_parts=num_parts),
        grid=(padded // _BLOCK_ROWS,),
        # int32 zeros: under x64 a Python 0 in an index map is an i64,
        # which Mosaic refuses
        in_specs=[pl.BlockSpec((1, _BLOCK_ROWS),
                               lambda i: (jnp.int32(0), i))],
        out_specs=pl.BlockSpec((num_parts, 1),
                               lambda i: (jnp.int32(0), jnp.int32(0))),
        out_shape=jax.ShapeDtypeStruct((num_parts, 1), jnp.int32),
        interpret=interpret,
    )(keys.reshape(1, padded))
    return out[:, 0]


def partition_histogram_xla(pids, mask, num_parts):
    """One-hot XLA formulation with identical semantics (used as the
    test oracle; O(n*num_parts), so not the production fallback)."""
    cols = jax.lax.broadcasted_iota(jnp.int32, (pids.shape[0], num_parts), 1)
    onehot = (pids.reshape(-1, 1) == cols) & mask.reshape(-1, 1)
    return onehot.astype(jnp.int32).sum(axis=0)


def histogram(pids, mask, num_parts):
    """Partition counts: pallas on TPU (scatter serializes there);
    segment_sum elsewhere (cheap O(n) scatter on CPU/GPU)."""
    if use_pallas():
        return partition_histogram(pids, mask, num_parts, interpret=False)
    key = jnp.where(mask, pids, num_parts)
    return jax.ops.segment_sum(
        jnp.ones_like(pids, dtype=jnp.int32), key,
        num_segments=num_parts + 1)[:num_parts]
