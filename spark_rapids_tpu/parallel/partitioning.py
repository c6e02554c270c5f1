"""Partitioning kernels: assign every row a destination shard.

Counterpart of ``GpuPartitioning.scala`` + Gpu{Hash,Range,RoundRobin,Single}
Partitioning (SURVEY.md section 2.4): where cudf computes partition indices
then ``Table.contiguousSplit``, the TPU path computes destination ids and
*sorts rows by destination* so each shard's outgoing rows are contiguous —
the layout the padded all-to-all collective wants.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.ops.expressions import ColVal


def _mix32(h):
    """murmur3 fmix32 — good avalanche, all 32-bit ops (TPU's X64 rewriter
    cannot lower f64<->u64 bitcast-convert, and 64-bit lane math is
    emulated; 32-bit mixing is native on the VPU)."""
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _column_words(c: ColVal):
    """Per-row (lo, hi) uint32 words encoding a column's value such that
    rows comparing equal yield equal words.  Floats canonicalize
    (-0.0 -> 0.0, NaN collapsed) then split as f32-bitcast of the value
    plus f32-bitcast of the scaled residual — no 64-bit bitcasts."""
    v = c.values
    if jnp.issubdtype(v.dtype, jnp.floating):
        v = jnp.where(v == 0.0, 0.0, v).astype(jnp.float64)
        v = jnp.where(jnp.isnan(v), jnp.float64(0.0), v)  # collapse NaN
        top = v.astype(jnp.float32)
        resid = (v - top.astype(jnp.float64)).astype(jnp.float32)
        resid = resid * jnp.float32(2.0) ** 29
        lo = jax.lax.bitcast_convert_type(top, jnp.uint32)
        hi = jax.lax.bitcast_convert_type(resid, jnp.uint32)
        return lo, hi
    if v.dtype == jnp.bool_:
        return v.astype(jnp.uint32), jnp.zeros_like(v, dtype=jnp.uint32)
    w = v.astype(jnp.int64)
    lo = jnp.bitwise_and(w, jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = jnp.right_shift(w, 32).astype(jnp.uint32)
    return lo, hi


def hash_columns(cols: Sequence[ColVal], seed: int = 42) -> jnp.ndarray:
    """uint32 hash per row over the key columns (murmur3-mix based).

    Floats are canonicalized (-0.0 -> 0.0, NaN payloads collapsed) so rows
    that compare equal hash equal, matching the reference's requirement on
    GpuHashPartitioning (murmur3 over canonical bytes)."""
    acc = None
    for c in cols:
        lo, hi = _column_words(c)
        h = _mix32(lo ^ jnp.uint32(seed))
        h = _mix32(h * jnp.uint32(31) + _mix32(hi ^ jnp.uint32(seed)))
        if c.validity is not None:
            h = jnp.where(c.validity, h, jnp.uint32(0x9E3779B9))
        acc = h if acc is None else _mix32(acc * jnp.uint32(31) + h)
    return acc


def hash_partition_ids(key_cols: Sequence[ColVal], num_parts: int
                       ) -> jnp.ndarray:
    h = hash_columns(key_cols)
    return (h % jnp.uint32(num_parts)).astype(jnp.int32)


# -- host-side parity port (numpy) ----------------------------------------
# The host-RAM staging tier (parallel/exchange_async.py) repartitions
# OFF-device, so its placement must be bit-identical to the device
# kernels above.  The numpy port lives here, next to the jnp original,
# so the two mixes cannot drift apart silently.

def _np_mix32(h):
    h = np.uint32(h)
    h = (h ^ (h >> np.uint32(16))) * np.uint32(0x85EBCA6B)
    h = (h ^ (h >> np.uint32(13))) * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def _np_column_words(values: np.ndarray):
    """numpy port of :func:`_column_words` — bit-identical (lo, hi)
    words so host-staged placement matches the device collective's."""
    v = values
    if np.issubdtype(v.dtype, np.floating):
        v = np.where(v == 0.0, 0.0, v).astype(np.float64)
        v = np.where(np.isnan(v), np.float64(0.0), v)
        top = v.astype(np.float32)
        resid = ((v - top.astype(np.float64)).astype(np.float32)
                 * np.float32(2.0) ** 29)
        return top.view(np.uint32), resid.view(np.uint32)
    if v.dtype == np.bool_:
        return v.astype(np.uint32), np.zeros_like(v, dtype=np.uint32)
    w = v.astype(np.int64)
    lo = (w & np.int64(0xFFFFFFFF)).astype(np.uint32)
    hi = (w >> 32).astype(np.uint32)
    return lo, hi


def host_hash_partition_ids(key_cols, num_parts: int,
                            seed: int = 42) -> np.ndarray:
    """Host-side murmur-mix partition ids matching
    :func:`hash_partition_ids` row for row (same mix, same null
    sentinel).  ``key_cols``: [(values ndarray, validity ndarray|None)].
    Parity is pinned by tests/test_shuffle_packed.py."""
    acc = None
    with np.errstate(over="ignore"):
        for values, validity in key_cols:
            lo, hi = _np_column_words(values)
            h = _np_mix32(lo ^ np.uint32(seed))
            h = _np_mix32(h * np.uint32(31)
                          + _np_mix32(hi ^ np.uint32(seed)))
            if validity is not None:
                h = np.where(validity, h, np.uint32(0x9E3779B9))
            acc = h if acc is None else _np_mix32(
                acc * np.uint32(31) + h)
    return (acc % np.uint32(num_parts)).astype(np.int32)


def round_robin_partition_ids(capacity: int, num_parts: int,
                              start: int = 0) -> jnp.ndarray:
    return ((jnp.arange(capacity, dtype=jnp.int32) + start) % num_parts)


def single_partition_ids(capacity: int) -> jnp.ndarray:
    return jnp.zeros(capacity, dtype=jnp.int32)


def range_partition_ids(key: ColVal, bounds: jnp.ndarray) -> jnp.ndarray:
    """Destination by sampled range bounds (ascending), like
    GpuRangePartitioning with host-sampled bounds."""
    return jnp.searchsorted(bounds, key.values, side="right").astype(jnp.int32)


def layout_by_partition(cols: Sequence[ColVal], pids: jnp.ndarray,
                        nrows, num_parts: int
                        ) -> Tuple[List[ColVal], jnp.ndarray, jnp.ndarray]:
    """Sort rows by destination; return (sorted cols, counts, starts).

    counts[d] = rows destined to shard d; starts = exclusive prefix sum.
    Padding rows sort last and are counted in no partition.
    """
    from spark_rapids_tpu.ops import selection

    from spark_rapids_tpu.ops import pallas_kernels as pk

    capacity = pids.shape[0]
    row_mask = jnp.arange(capacity, dtype=jnp.int32) < nrows
    sort_key = jnp.where(row_mask, pids, num_parts)
    perm = selection.lexsort_i32([sort_key])
    sorted_cols = selection.gather(cols, perm, nrows)
    # per-destination counts: pallas one-hot accumulation on TPU (XLA's
    # segment_sum lowers to a serialized scatter there), one-hot matmul
    # fallback elsewhere
    counts = pk.histogram(pids.astype(jnp.int32), row_mask,
                          num_parts).astype(jnp.int32)
    starts = jnp.concatenate(
        [jnp.zeros(1, dtype=jnp.int32), jnp.cumsum(counts)[:-1]])
    return sorted_cols, counts, starts
