"""Device-side batch concatenation.

The TPU analog of ``GpuCoalesceBatches``' cudf ``Table.concatenate``
(GpuCoalesceBatches.scala:195): small batches are appended into a larger
fixed-capacity buffer entirely on device — no host round trip between a
partial aggregation and its merge pass.

``append_cols`` is shape-polymorphic only over (out_capacity, in_capacity)
pairs, both power-of-two buckets, so the jit cache stays small.
"""

from __future__ import annotations

import threading
from typing import Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, bucket_capacity
from spark_rapids_tpu.ops.expressions import ColVal


class ConcatMetrics:
    """What the append kernels move, known on the host without a sync:
    every ``_append_fixed`` / ``_append_string`` call rewrites its whole
    output (``bytes_written``: the output buffers' capacity x item
    size, validity and offsets included) to place one input
    (``bytes_appended``: the input buffers').  ``bytes_written /
    bytes_appended`` is concat's wasted-work ratio.  Plain ints, bumped
    with tracing on or off."""

    def __init__(self):
        self._lock = threading.Lock()
        self.appends = self.bytes_written = self.bytes_appended = 0

    def note(self, outs, ins) -> None:
        written = sum(a.nbytes for a in outs)
        appended = sum(a.nbytes for a in ins)
        with self._lock:
            self.appends += 1
            self.bytes_written += written
            self.bytes_appended += appended

    def snapshot(self) -> dict:
        with self._lock:
            return {"appends": self.appends,
                    "bytes_written": self.bytes_written,
                    "bytes_appended": self.bytes_appended}


concat_metrics = ConcatMetrics()


@jax.jit
def _append_fixed(out_vals, out_valid, out_n, in_vals, in_valid, in_n):
    out_cap = out_vals.shape[0]
    pos = jnp.arange(out_cap, dtype=jnp.int32)
    src = jnp.clip(pos - out_n, 0, in_vals.shape[0] - 1)
    write = (pos >= out_n) & (pos < out_n + in_n)
    vals = jnp.where(write, in_vals[src], out_vals)
    valid = jnp.where(write, in_valid[src], out_valid)
    return vals, valid


@jax.jit
def _append_string(out_chars, out_offs, out_valid, out_n,
                   in_chars, in_offs, in_valid, in_n):
    out_cap = out_offs.shape[0] - 1
    pos = jnp.arange(out_cap + 1, dtype=jnp.int32)
    base = out_offs[out_n]
    src = jnp.clip(pos - out_n, 0, in_offs.shape[0] - 1)
    new_offs = jnp.where((pos >= out_n) & (pos <= out_n + in_n),
                         base + in_offs[src], out_offs)
    # rows past the appended region keep the final offset (monotone padding)
    end = base + in_offs[in_n]
    new_offs = jnp.where(pos > out_n + in_n, end, new_offs)

    cpos = jnp.arange(out_chars.shape[0], dtype=jnp.int32)
    csrc = jnp.clip(cpos - base, 0, in_chars.shape[0] - 1)
    cwrite = (cpos >= base) & (cpos < end)
    chars = jnp.where(cwrite, in_chars[csrc], out_chars)

    rpos = jnp.arange(out_cap, dtype=jnp.int32)
    rsrc = jnp.clip(rpos - out_n, 0, in_valid.shape[0] - 1)
    rwrite = (rpos >= out_n) & (rpos < out_n + in_n)
    valid = jnp.where(rwrite, in_valid[rsrc], out_valid)
    return chars, new_offs, valid


def append_fixed(out_vals, out_valid, out_n, in_vals, in_valid, in_n):
    concat_metrics.note((out_vals, out_valid), (in_vals, in_valid))
    return _append_fixed(out_vals, out_valid, out_n, in_vals, in_valid,
                         in_n)


def append_string(out_chars, out_offs, out_valid, out_n,
                  in_chars, in_offs, in_valid, in_n):
    concat_metrics.note((out_chars, out_offs, out_valid),
                        (in_chars, in_offs, in_valid))
    return _append_string(out_chars, out_offs, out_valid, out_n,
                          in_chars, in_offs, in_valid, in_n)


def _ensure_validity(col: Column):
    if col.validity is not None:
        return col.validity
    return jnp.ones(col.capacity, dtype=jnp.bool_)


def concat_batches(batches: Sequence[ColumnarBatch]) -> ColumnarBatch:
    """Concatenate same-schema batches into one device batch.

    Batches carrying deferred (device-resident) row counts concatenate
    WITHOUT forcing a host sync: appends run off the device scalars and
    the output capacity is bounded by the input capacities (offset
    columns are the exception — char-buffer sizing is a host decision,
    so string batches resolve their counts in one batched transfer).
    """
    from spark_rapids_tpu.columnar.column import RowCount
    # drop only KNOWN-empty batches; a deferred count is not worth a
    # round trip just to skip an empty input
    batches = [b for b in batches
               if not (b.row_count.is_concrete and b.nrows == 0)] \
        or list(batches[:1])
    if len(batches) == 1:
        return batches[0]
    lazy = any(not b.row_count.is_concrete for b in batches)
    if lazy and any(dt.has_offsets for _, dt in batches[0].schema):
        RowCount.materialize_all([b.row_count for b in batches])
        lazy = False
    if lazy:
        return _concat_batches_lazy(batches)
    total = sum(b.nrows for b in batches)
    cap = bucket_capacity(total)
    names = batches[0].names
    out_cols = {}
    for name in names:
        first = batches[0].column(name)
        dt = first.dtype
        any_nulls = any(b.column(name).validity is not None for b in batches)
        if dt.has_offsets:
            total_chars = sum(
                int(b.column(name).offsets[b.nrows]) for b in batches)
            ccap = bucket_capacity(max(total_chars, 1))
            chars = jnp.zeros(ccap, dtype=dt.storage)
            offs = jnp.zeros(cap + 1, dtype=jnp.int32)
            valid = jnp.zeros(cap, dtype=jnp.bool_)
            n = 0
            for b in batches:
                c = b.column(name)
                chars, offs, valid = append_string(
                    chars, offs, valid, jnp.int32(n),
                    c.data, c.offsets, _ensure_validity(c),
                    jnp.int32(c.nrows))
                n += c.nrows
            out_cols[name] = Column(dt, chars, total,
                                    validity=valid if any_nulls else None,
                                    offsets=offs)
        else:
            vals = jnp.zeros(cap, dtype=dt.storage)
            valid = jnp.zeros(cap, dtype=jnp.bool_)
            n = 0
            for b in batches:
                c = b.column(name)
                vals, valid = append_fixed(
                    vals, valid, jnp.int32(n), c.data, _ensure_validity(c),
                    jnp.int32(c.nrows))
                n += c.nrows
            out_cols[name] = Column(dt, vals, total,
                                    validity=valid if any_nulls else None)
    return ColumnarBatch(out_cols, total)


def _concat_batches_lazy(batches: Sequence[ColumnarBatch]) -> ColumnarBatch:
    """Sync-free concat for fixed-width batches with deferred counts:
    append positions come from the device-resident counts, the output
    capacity from the (host-known) input capacities — an upper bound, so
    rows beyond the true total stay padding exactly as shape-bucket
    padding always does."""
    from spark_rapids_tpu.columnar.column import RowCount
    cap = bucket_capacity(sum(b.capacity for b in batches))
    names = batches[0].names
    counts = [b.row_count.device_i32() for b in batches]
    total_dev = counts[0]
    for c in counts[1:]:
        total_dev = total_dev + c
    total_rc = RowCount(device=total_dev)
    out_cols = {}
    for name in names:
        dt = batches[0].column(name).dtype
        any_nulls = any(b.column(name).validity is not None
                        for b in batches)
        vals = jnp.zeros(cap, dtype=dt.storage)
        valid = jnp.zeros(cap, dtype=jnp.bool_)
        n_dev = None
        for b, c in zip(batches, counts):
            col = b.column(name)
            vals, valid = append_fixed(
                vals, valid, jnp.int32(0) if n_dev is None else n_dev,
                col.data, _ensure_validity(col), c)
            n_dev = c if n_dev is None else n_dev + c
        out_cols[name] = Column(dt, vals, total_rc,
                                validity=valid if any_nulls else None)
    return ColumnarBatch(out_cols, total_rc)
