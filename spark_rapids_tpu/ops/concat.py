"""Device-side batch concatenation.

The TPU analog of ``GpuCoalesceBatches``' cudf ``Table.concatenate``
(GpuCoalesceBatches.scala:195): small batches become one larger
fixed-capacity batch entirely on device — no host round trip between a
partial aggregation and its merge pass.

Placement is a block copy, never a gather: every input buffer is
written once, whole, at its row (or char) offset with
``lax.dynamic_update_slice``.  An input carries its padding with it;
the next input starts where the previous one's rows end and overwrites
that padding, and one elementwise pass finishes the tail.  The offsets
are traced scalars, so one program a column serves every mix of row
counts: programs are keyed by dtype and power-of-two capacities only
(docs/performance.md, "Concatenation is a block copy").
"""

from __future__ import annotations

import threading
from typing import Sequence

import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import (Column, RowCount,
                                              bucket_capacity)
from spark_rapids_tpu.ops.jit_cache import cached_jit
from spark_rapids_tpu.utils import hostsync


class ConcatMetrics:
    """What the concat programs move, known on the host from shapes
    without a sync: ``appends`` input columns placed; ``bytes_appended``
    the buffers those inputs carry (values or chars, offsets, validity
    where an input has one) at capacity x item size; ``bytes_written``
    what the programs write to place them: every placed block once (an
    all-True block for an input without validity, where the output
    needs one) plus each output buffer once for the finishing pass.
    ``bytes_written / bytes_appended`` is concat's wasted-work ratio.
    Plain ints, bumped with tracing on or off."""

    def __init__(self):
        self._lock = threading.Lock()
        self.appends = self.bytes_written = self.bytes_appended = 0

    def note(self, appends: int, written: int, appended: int) -> None:
        with self._lock:
            self.appends += appends
            self.bytes_written += written
            self.bytes_appended += appended

    def snapshot(self) -> dict:
        with self._lock:
            return {"appends": self.appends,
                    "bytes_written": self.bytes_written,
                    "bytes_appended": self.bytes_appended}


concat_metrics = ConcatMetrics()


def _scratch_len(out_len: int, in_lens: Sequence[int]) -> int:
    """Length of the buffer the blocks are placed into.
    ``dynamic_update_slice`` clamps its start so that the update fits,
    which would silently shift a late block whose padding reaches past
    the output; where the inputs' lengths can exceed the output's the
    scratch gets one longest input of headroom (every start is at most
    ``out_len - 1``), and the caller keeps its first ``out_len``."""
    if sum(in_lens) <= out_len:
        return out_len
    return out_len + max(in_lens)


def _place(blocks, starts, total, out_len: int, fill=0):
    """``blocks[i]`` written whole at ``starts[i]``, in order, then the
    finishing pass: ``fill`` from ``total`` on.  The scratch starts
    uninitialized (zeros off the chip): the blocks cover every element
    below ``total`` and the finishing pass masks the rest."""
    scratch = lax.empty(
        (_scratch_len(out_len, [b.shape[0] for b in blocks]),),
        blocks[0].dtype)
    for i, block in enumerate(blocks):
        scratch = lax.dynamic_update_slice(scratch, block, (starts[i],))
    pos = jnp.arange(out_len, dtype=jnp.int32)
    return jnp.where(pos < total, scratch[:out_len],
                     jnp.asarray(fill, dtype=scratch.dtype))


def _starts(counts):
    ends = jnp.cumsum(counts, dtype=jnp.int32)
    return ends - counts, ends[-1]


def _place_validity(valids, caps, starts, total, cap: int):
    """None where no input carries a validity; else the inputs' (all
    True for an input without one) placed like the values, False from
    ``total`` on."""
    if all(v is None for v in valids):
        return None
    blocks = [jnp.ones(c, dtype=jnp.bool_) if v is None else v
              for v, c in zip(valids, caps)]
    return _place(blocks, starts, total, cap, fill=False)


def _make_concat_fixed(cap: int):
    def concat_fixed(datas, valids, counts):
        starts, total = _starts(counts)
        vals = _place(datas, starts, total, cap)
        valid = _place_validity(valids, [d.shape[0] for d in datas],
                                starts, total, cap)
        return vals, valid
    return concat_fixed


def _make_concat_string(cap: int, char_cap: int):
    def concat_string(chars, offsets, valids, counts, char_counts):
        starts, total = _starts(counts)
        bases, total_chars = _starts(char_counts)
        # the last block leaves the final offset at ``total``; rows
        # past it repeat it (monotone padding)
        offs = _place([bases[i] + o for i, o in enumerate(offsets)],
                      starts, total, cap + 1, fill=total_chars)
        out_chars = _place(chars, bases, total_chars, char_cap)
        valid = _place_validity(valids, [o.shape[0] - 1 for o in offsets],
                                starts, total, cap)
        return out_chars, offs, valid
    return concat_string


def _char_totals(offsets, counts):
    """``offsets[i][counts[i]]`` for every input, as one int32 vector."""
    return jnp.stack([lax.dynamic_index_in_dim(o, counts[i], keepdims=False)
                      for i, o in enumerate(offsets)])


def _nbytes(bufs) -> int:
    return sum(b.nbytes for b in bufs if b is not None)


def _concat_column(cols: Sequence[Column], counts, total, cap: int,
                   char_counts=None, char_cap: int = 0) -> Column:
    """One program: every input of one column in, its finished buffers
    out.  ``counts`` (and ``char_counts``) are int32 vectors, device or
    host; ``total`` is what the output column carries as its row count."""
    dt = cols[0].dtype
    valids = tuple(c.validity for c in cols)
    datas = tuple(c.data for c in cols)
    if dt.has_offsets:
        offsets = tuple(c.offsets for c in cols)
        fn = cached_jit(("concat_string", cap, char_cap),
                        lambda: _make_concat_string(cap, char_cap))
        chars, offs, valid = fn(datas, offsets, valids, counts, char_counts)
        outs = (chars, offs, valid)
        ins = datas + offsets
        out = Column(dt, chars, total, validity=valid, offsets=offs)
    else:
        fn = cached_jit(("concat_fixed", cap),
                        lambda: _make_concat_fixed(cap))
        vals, valid = fn(datas, valids, counts)
        outs = (vals, valid)
        ins = datas
        out = Column(dt, vals, total, validity=valid)
    # validity blocks: an input's own, or the program's all-True one
    valid_blocks = sum(c.capacity for c in cols) if valid is not None else 0
    concat_metrics.note(
        len(cols),
        written=_nbytes(ins) + valid_blocks + _nbytes(outs),
        appended=_nbytes(ins) + _nbytes(valids))
    return out


def concat_batches(batches: Sequence[ColumnarBatch]) -> ColumnarBatch:
    """Concatenate same-schema batches into one device batch.

    Batches carrying deferred (device-resident) row counts concatenate
    WITHOUT forcing a host sync: placement runs off the device scalars
    and the output capacity is bounded by the input capacities (offset
    columns are the exception — char-buffer sizing is a host decision,
    so string batches resolve their counts in one batched transfer and
    their char totals in a second).
    """
    # drop only KNOWN-empty batches; a deferred count is not worth a
    # round trip just to skip an empty input
    batches = [b for b in batches
               if not (b.row_count.is_concrete and b.nrows == 0)] \
        or list(batches[:1])
    if len(batches) == 1:
        return batches[0]
    names = batches[0].names
    string_names = [name for name, dt in batches[0].schema
                    if dt.has_offsets]
    lazy = any(not b.row_count.is_concrete for b in batches)
    if lazy and string_names:
        RowCount.materialize_all([b.row_count for b in batches])
        lazy = False
    if lazy:
        # capacity from the (host-known) input capacities: an upper
        # bound, so rows beyond the true total stay padding exactly as
        # shape-bucket padding always does
        cap = bucket_capacity(sum(b.capacity for b in batches))
        counts = jnp.stack([b.row_count.device_i32() for b in batches])
        total = RowCount(device=jnp.sum(counts, dtype=jnp.int32))
    else:
        total = sum(b.nrows for b in batches)
        cap = bucket_capacity(total)
        counts = jnp.asarray([b.nrows for b in batches], dtype=jnp.int32)
    char_counts = {}
    if string_names:
        totals_of = cached_jit(("concat_char_totals",),
                               lambda: _char_totals)
        device = [totals_of(tuple(b.column(name).offsets for b in batches),
                            counts) for name in string_names]
        # sizing the char buffers is the host's decision: one fetch for
        # every string column of the call
        host = hostsync.fetch_all(device)
        char_counts = {
            name: (d, bucket_capacity(max(int(h.sum()), 1)))
            for name, d, h in zip(string_names, device, host)}
    out_cols = {
        name: _concat_column([b.column(name) for b in batches], counts,
                             total, cap, *char_counts.get(name, ()))
        for name in names}
    return ColumnarBatch(out_cols, total)
