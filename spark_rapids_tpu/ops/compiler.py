"""The stage compiler: expression forests -> one jitted XLA function.

This is the architectural pivot away from the reference: where a GpuExec calls
one libcudf kernel per expression per batch over JNI
(``GpuExpression.columnarEval``, GpuExpressions.scala:113), here an operator
hands its *entire* bound expression forest to :func:`make_stage_fn` and gets a
single ``jax.jit``-compiled function.  XLA fuses the whole stage — filter
predicate, projections, partial aggregation pre-work — into a few TPU kernels,
amortizing dispatch and keeping intermediates in vector registers/VMEM instead
of HBM round-trips.

Shape discipline: the traced signature is one (capacity,) array set per input
column plus an int32 ``nrows`` scalar.  Because Column capacities are bucketed
powers of two, re-tracing is bounded by O(log max_rows) buckets per stage.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.ops.expressions import (
    ColVal, EmitContext, Expression, collect_param_slots, fold_conjuncts)
from spark_rapids_tpu.utils import tracing

# A column crosses the jit boundary as (values, validity|None, offsets|None).
FlatCol = Tuple


def donation_supported() -> bool:
    """Buffer donation is a no-op on the CPU backend (XLA:CPU ignores
    donated buffers and warns); only request it where it frees HBM."""
    import jax
    return jax.default_backend() in ("tpu", "gpu")


def _donate_kwargs(donate: bool) -> dict:
    """jit kwargs for a stage whose flat-column arg (argument 0) may be
    donated.  The effective flag — not the requested one — is folded
    into cache signatures, so a CPU process and a TPU process never
    share a signature with different donation semantics."""
    return {"donate_argnums": (0,)} if donate else {}


def effective_donate(donate: bool) -> bool:
    return bool(donate) and donation_supported()


def batch_to_flat(batch: ColumnarBatch) -> List[FlatCol]:
    # where a scanned batch's host buffers become device arrays (first
    # touch of Column.data): one ``upload.h2d`` span a batch
    with tracing.span("upload.h2d"):
        return [(c.data, c.validity, c.offsets)
                for c in batch.columns.values()]


def flat_to_colvals(flat: Sequence[FlatCol],
                    dtypes: Sequence[DataType]) -> List[ColVal]:
    return [ColVal(dt, v, validity, offsets)
            for (v, validity, offsets), dt in zip(flat, dtypes)]


def capacity_of(flat: Sequence[FlatCol]) -> int:
    for values, _, offsets in flat:
        if offsets is not None:
            return int(offsets.shape[0]) - 1
        return int(values.shape[0])
    raise ValueError("no columns")


def colvals_to_columns(outs: Sequence[ColVal], nrows: int,
                       capacity: int) -> List[Column]:
    cols = []
    for o in outs:
        values, validity, offsets = o.values, o.validity, o.offsets
        if getattr(values, "ndim", 0) == 0 and offsets is None:
            values = jnp.broadcast_to(values, (capacity,))
        if validity is not None and getattr(validity, "ndim", 1) == 0:
            validity = jnp.broadcast_to(validity, (capacity,))
        cols.append(Column(o.dtype, values, nrows, validity=validity,
                           offsets=offsets))
    return cols


# ANSI check messages per stage signature: the jit cache shares traced
# functions across StageFn instances with the same signature, so messages
# recorded at trace time must be shared the same way.  The canonical dict
# lives in ops/jit_cache.py (STAGE_CHECKS) so the persistent tier can
# serialize messages into entry headers — a warm start that never traces
# still raises the exact ANSI message.
from spark_rapids_tpu.ops.jit_cache import STAGE_CHECKS as _CHECK_MSGS


def raise_failed_checks(messages, flags) -> None:
    """Host-side surfacing of in-trace ANSI checks (Spark ANSI throws)."""
    if flags and any(bool(f) for f in flags):
        failed = [m for m, f in zip(messages, flags) if bool(f)]
        raise ArithmeticError("; ".join(failed) or "ANSI check failed")


def param_args(slots) -> Tuple:
    """Dispatch-time argument vector for a stage's ParamSlots: the
    current binding of each slot as a 0-d storage scalar.  Empty tuple
    (an empty pytree — free at the jit boundary) when the stage has no
    slots, so unparameterized stages pay nothing."""
    return tuple(s.device_value() for s in slots)


def params_dict(slots, params):
    """Traced param arguments -> the slot-index map EmitContext reads.
    Slot INDEX ordering matches :func:`collect_param_slots`, so any
    instance sharing the cached executable builds the same mapping."""
    if not slots:
        return None
    return {s.index: p for s, p in zip(slots, params)}


class StageFn:
    """A compiled per-batch function for a fixed expression forest.

    ``__call__(batch) -> list[Column]`` with the same nrows as the input.
    jax.jit's shape cache gives one XLA executable per capacity bucket.

    ``conjuncts`` (bottom-first, the FilterStageFn discipline) fold into
    a row mask that the same program returns beside the columns
    (:meth:`masked`): a filter that its consumer applies as a mask
    instead of a compaction — no column is gathered.
    """

    def __init__(self, exprs: Sequence[Expression],
                 input_dtypes: Sequence[DataType],
                 donate: bool = False,
                 conjuncts: Sequence[Expression] = ()):
        from spark_rapids_tpu.ops.jit_cache import cached_jit
        self.exprs = list(exprs)
        self.conjuncts = list(conjuncts)
        self.input_dtypes = list(input_dtypes)
        self.donate = effective_donate(donate)
        self._slots = collect_param_slots(self.exprs + self.conjuncts)
        self._sig = ("stage", tuple(e.cache_key() for e in self.exprs),
                     tuple(dt.name for dt in self.input_dtypes),
                     ("donate", self.donate))
        if self.conjuncts:
            self._sig += (("mask", tuple(c.cache_key()
                                         for c in self.conjuncts)),)
        self._jitted = cached_jit(self._sig, lambda: self._run,
                                  **_donate_kwargs(self.donate))

    def _run(self, flat_cols, nrows, params=()):
        capacity = capacity_of(flat_cols) if flat_cols else 0
        inputs = flat_to_colvals(flat_cols, self.input_dtypes)
        ctx = EmitContext(inputs, nrows, capacity,
                          params=params_dict(self._slots, params))
        # the expressions evaluate over every row; fold_conjuncts leaves
        # the check mask at the survivor set
        mask = fold_conjuncts(ctx, self.conjuncts) \
            if self.conjuncts else None
        outs = [e.emit(ctx) for e in self.exprs]
        # messages are static per expression tree: record them at trace
        # time so a failure needs no re-execution
        _CHECK_MSGS[self._sig] = [m for m, _ in ctx.checks]
        return ([(o.values, o.validity, o.offsets) for o in outs],
                tuple(flag for _, flag in ctx.checks), mask)

    def masked(self, batch: ColumnarBatch):
        """``(columns, mask)``: the mask is bool[capacity] of the rows
        the conjuncts keep, None when the stage has no conjuncts."""
        flat = batch_to_flat(batch)
        # device_i32: a deferred upstream count flows straight into the
        # stage without a host sync
        nrows = batch.row_count.device_i32()
        out_flat, check_flags, mask = self._jitted(
            flat, nrows, param_args(self._slots))
        raise_failed_checks(_CHECK_MSGS.get(self._sig, []), check_flags)
        outs = [ColVal(e.dtype, v, validity, offsets)
                for e, (v, validity, offsets) in zip(self.exprs, out_flat)]
        return (colvals_to_columns(outs, batch.row_count, batch.capacity),
                mask)

    def __call__(self, batch: ColumnarBatch) -> List[Column]:
        return self.masked(batch)[0]


class FilterStageFn:
    """Fused predicate(s) + compaction: batch -> (columns, new_nrows).

    The predicate and the gather-to-dense run in one XLA computation; only the
    selected-row count syncs back to the host (to set the logical length).

    ``predicate`` may be a LIST of conjuncts in bottom-first chain order
    (whole-stage fusion, exec/fusion.py): each conjunct evaluates with
    the mask of the conjuncts BELOW it as its ANSI check mask, so a
    fused chain's checks fire for exactly the rows the corresponding
    unfused filter stage would have evaluated.  Rows dropped by LATER
    members may skip their checks — the same latitude Spark's optimizer
    takes when collapsing projects and reordering filters; a bad value
    can never reach the output (the final keep mask gates everything).
    """

    def __init__(self, predicate, project: Sequence[Expression],
                 input_dtypes: Sequence[DataType],
                 donate: bool = False):
        from spark_rapids_tpu.ops.jit_cache import cached_jit
        conjuncts = list(predicate) if isinstance(
            predicate, (list, tuple)) else [predicate]
        self.conjuncts = conjuncts  # bottom-first evaluation order
        self.predicate = conjuncts[0]
        self.project = list(project)
        self.input_dtypes = list(input_dtypes)
        self.donate = effective_donate(donate)
        self._slots = collect_param_slots(self.conjuncts + self.project)
        self._sig = ("filter_stage",
                     tuple(p.cache_key() for p in conjuncts),
                     tuple(e.cache_key() for e in self.project),
                     tuple(dt.name for dt in self.input_dtypes),
                     ("donate", self.donate))
        self._jitted = cached_jit(self._sig, lambda: self._run,
                                  **_donate_kwargs(self.donate))

    def _run(self, flat_cols, nrows, params=()):
        from spark_rapids_tpu.ops import selection
        capacity = capacity_of(flat_cols)
        inputs = flat_to_colvals(flat_cols, self.input_dtypes)
        ctx = EmitContext(inputs, nrows, capacity,
                          params=params_dict(self._slots, params))
        # projections then evaluate over PRE-filter rows (compaction is
        # one pass at the end): fold_conjuncts leaves the check mask at
        # the survivor set, so ANSI checks only fire for survivors
        keep = fold_conjuncts(ctx, self.conjuncts)
        outs = [e.emit(ctx) for e in self.project]
        # scalar projection outputs (literals, scalar-validity
        # expressions) widen to the capacity before compaction — the
        # gather indexes every buffer, including validity (fused chains
        # project arbitrary expressions here, not just passthroughs)
        outs = [ColVal(o.dtype,
                       jnp.broadcast_to(o.values, (capacity,))
                       if getattr(o.values, "ndim", 0) == 0 and
                       o.offsets is None else o.values,
                       jnp.broadcast_to(o.validity, (capacity,))
                       if o.validity is not None and
                       getattr(o.validity, "ndim", 1) == 0
                       else o.validity, o.offsets)
                for o in outs]
        compacted, new_nrows = selection.compact(outs, keep)
        _CHECK_MSGS[self._sig] = [m for m, _ in ctx.checks]
        return ([(o.values, o.validity, o.offsets) for o in compacted],
                new_nrows, tuple(flag for _, flag in ctx.checks))

    def __call__(self, batch: ColumnarBatch) -> Tuple[List[Column], int]:
        from spark_rapids_tpu.columnar.column import RowCount
        flat = batch_to_flat(batch)
        out_flat, new_nrows, check_flags = self._jitted(
            flat, batch.row_count.device_i32(), param_args(self._slots))
        raise_failed_checks(_CHECK_MSGS.get(self._sig, []), check_flags)
        # the selected-row count is a genuine host decision (empty-batch
        # skip); RowCount makes the sync visible to the accounting
        n = int(RowCount(device=new_nrows))
        outs = [ColVal(e.dtype, v, validity, offsets)
                for e, (v, validity, offsets) in zip(self.project, out_flat)]
        return colvals_to_columns(outs, n, batch.capacity), n
