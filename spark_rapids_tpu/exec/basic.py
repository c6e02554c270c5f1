"""Basic physical operators: scan, project, filter, range, union, limit.

Counterpart of ``basicPhysicalOperators.scala`` (GpuProjectExec:111,
GpuFilterExec:297, GpuRangeExec:358, GpuUnionExec:493) — with the stage-fusion
twist: project and filter own compiled StageFns, so their whole expression
forest is one XLA computation per capacity bucket.
"""

from __future__ import annotations

import threading
from typing import Iterator, List, Optional, Sequence

import numpy as np
import jax.numpy as jnp

from spark_rapids_tpu.columnar import dtypes as dts
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, bucket_capacity
from spark_rapids_tpu.exec.base import (
    NUM_INPUT_BATCHES, NUM_INPUT_ROWS, Schema, TpuExec)
from spark_rapids_tpu.ops.compiler import FilterStageFn, StageFn
from spark_rapids_tpu.ops.expressions import BoundReference, Expression


class FilterMetrics:
    """Batches through the filter stages (``TpuFilterExec`` and a fused
    stage with predicates), from what the operator has on the host
    anyway: ``batches`` and ``rows_out`` from the kept-row count it
    syncs, ``rows_in`` and ``whole_batches`` (every row kept, so the
    compaction moved nothing: ops/selection.py ``compact``) only where
    the input's row count is already concrete, never by a sync of their
    own.  Plain ints, bumped with tracing on or off."""

    def __init__(self):
        self._lock = threading.Lock()
        self.batches = self.whole_batches = 0
        self.rows_in = self.rows_out = 0

    def note(self, row_count, kept: int) -> None:
        """``row_count``: the input batch's ``RowCount``."""
        rows_in = int(row_count) if row_count.is_concrete else None
        with self._lock:
            self.batches += 1
            self.rows_out += kept
            if rows_in is not None:
                self.rows_in += rows_in
                self.whole_batches += kept == rows_in

    def snapshot(self) -> dict:
        with self._lock:
            return {"batches": self.batches,
                    "whole_batches": self.whole_batches,
                    "rows_in": self.rows_in, "rows_out": self.rows_out}


filter_metrics = FilterMetrics()


class TpuCoalesceBatchesExec(TpuExec):
    """Planner-inserted batch coalescing: accumulate undersized
    upstream batches to the goal before handing them downstream — the
    GpuCoalesceBatches.scala operator in the position
    GpuTransitionOverrides.scala:57-64 inserts it (above multi-file
    scans here, where PERFILE readers emit one small batch per
    file)."""

    def __init__(self, child: TpuExec, goal):
        super().__init__(child)
        self.goal = goal

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def describe(self):
        return f"TpuCoalesceBatchesExec[{self.goal}]"

    def do_execute(self) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.memory.coalesce import coalesce_iterator
        return coalesce_iterator(self.child.execute(), self.goal)


class TpuScanExec(TpuExec):
    """In-memory relation scan: re-chunks host/device batches to target rows."""

    def __init__(self, batches: Sequence[ColumnarBatch], schema: Schema,
                 max_rows: Optional[int] = None):
        super().__init__()
        self.batches = list(batches)
        self._schema = list(schema)
        self.max_rows = max_rows

    @property
    def schema(self) -> Schema:
        return self._schema

    def do_execute(self) -> Iterator[ColumnarBatch]:
        for b in self.batches:
            if self.max_rows is None or b.nrows <= self.max_rows:
                yield b
            else:
                table = b.to_arrow()
                for off in range(0, b.nrows, self.max_rows):
                    yield ColumnarBatch.from_arrow(
                        table.slice(off, self.max_rows))

    def describe(self):
        return f"TpuScanExec[{sum(b.nrows for b in self.batches)} rows]"


class TpuProjectExec(TpuExec):
    ephemeral_output = True

    def __init__(self, exprs: Sequence[Expression], child: TpuExec,
                 donate: bool = False):
        super().__init__(child)
        self.exprs = list(exprs)
        self._fn = StageFn(self.exprs, [dt for _, dt in child.schema],
                           donate=donate and child.ephemeral_output)

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def schema(self) -> Schema:
        return [(e.name, e.dtype) for e in self.exprs]

    def do_execute(self) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.memory.retry import with_retry
        names = [e.name for e in self.exprs]

        def compute(batch):
            cols = self._fn(batch)
            # row_count, not nrows: a deferred upstream count passes
            # through without forcing a host sync
            return ColumnarBatch(dict(zip(names, cols)),
                                 batch.row_count)

        if self._fn.donate:
            # donated inputs are consumed by the kernel, so operator-
            # level OOM retry (which re-runs over the same batch) is
            # unsafe; faults escalate to query-level recovery, which
            # re-executes from source (docs/performance.md#donation)
            for batch in self.child.execute():
                yield compute(batch)
            return
        yield from with_retry(self.child.execute(), compute)

    def describe(self):
        return f"TpuProjectExec[{', '.join(e.name for e in self.exprs)}]"


class TpuFilterExec(TpuExec):
    """Fused predicate + compaction (+ pass-through projection)."""

    ephemeral_output = True

    def __init__(self, condition: Expression, child: TpuExec,
                 donate: bool = False):
        super().__init__(child)
        self.condition = condition
        in_schema = child.schema
        passthrough = [BoundReference(i, dt, name=n)
                       for i, (n, dt) in enumerate(in_schema)]
        self._fn = FilterStageFn(condition, passthrough,
                                 [dt for _, dt in in_schema],
                                 donate=donate and child.ephemeral_output)
        self._register_metric(NUM_INPUT_ROWS)

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def do_execute(self) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.memory.retry import with_retry
        names = [n for n, _ in self.schema]

        def tallied():
            for batch in self.child.execute():
                self.metrics[NUM_INPUT_ROWS] += batch.row_count
                yield batch

        def compute(batch):
            cols, n = self._fn(batch)
            filter_metrics.note(batch.row_count, n)
            return None if n == 0 else \
                ColumnarBatch(dict(zip(names, cols)), n)

        if self._fn.donate:
            # see TpuProjectExec: donation forfeits operator-level retry
            for batch in tallied():
                out = compute(batch)
                if out is not None:
                    yield out
            return
        for out in with_retry(tallied(), compute):
            if out is not None:
                yield out

    def describe(self):
        return f"TpuFilterExec[{self.condition}]"


class TpuRangeExec(TpuExec):
    """range(start, end, step) -> bigint id column (GpuRangeExec:358)."""

    ephemeral_output = True

    def __init__(self, start: int, end: int, step: int,
                 max_rows: int = 1 << 20):
        super().__init__()
        self.start, self.end, self.step = start, end, step
        self.max_rows = max_rows
        self._schema = [("id", dts.INT64)]

    @property
    def schema(self) -> Schema:
        return self._schema

    def do_execute(self) -> Iterator[ColumnarBatch]:
        total = max(0, -(-(self.end - self.start) // self.step))
        emitted = 0
        while emitted < total:
            n = min(self.max_rows, total - emitted)
            cap = bucket_capacity(n)
            base = self.start + emitted * self.step
            vals = base + jnp.arange(cap, dtype=jnp.int64) * self.step
            yield ColumnarBatch({"id": Column(dts.INT64, vals, n)}, n)
            emitted += n


class TpuUnionExec(TpuExec):
    def __init__(self, *children: TpuExec):
        super().__init__(*children)

    @property
    def ephemeral_output(self) -> bool:
        # pass-through: output batches share every child's buffers
        return all(c.ephemeral_output for c in self.children)

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def do_execute(self) -> Iterator[ColumnarBatch]:
        names = [n for n, _ in self.schema]
        for child in self.children:
            for batch in child.execute():
                cols = dict(zip(names, batch.columns.values()))
                yield ColumnarBatch(cols, batch.row_count)


class TpuLocalLimitExec(TpuExec):
    def __init__(self, n: int, child: TpuExec):
        super().__init__(child)
        self.n = n

    @property
    def child(self):
        return self.children[0]

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def do_execute(self) -> Iterator[ColumnarBatch]:
        remaining = self.n
        for batch in self.child.execute():
            if remaining <= 0:
                return
            if batch.nrows <= remaining:
                remaining -= batch.nrows
                yield batch
            else:
                cols = {n: c.with_nrows(remaining)
                        for n, c in batch.columns.items()}
                yield ColumnarBatch(cols, remaining)
                return

    def describe(self):
        return f"TpuLocalLimitExec[{self.n}]"
