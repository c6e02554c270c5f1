"""Aggregation kernels: sort-based group-by + masked grand-total reductions.

The reference drives cudf's *hash* group-by (``aggregate.scala:209``
GpuHashAggregateIterator) with a sort-based fallback.  Hash tables scatter
serially and map poorly onto the MXU/VPU, so the TPU-first formulation is the
opposite: group-by IS sort-based — ``lexsort`` by key columns, boundary flags,
prefix-sum segment ids, then ``jax.ops.segment_*`` reductions.  Everything is
static-shaped: a batch of capacity C yields at most C groups, so outputs keep
capacity C with a traced ``num_groups``.

Aggregate functions follow the reference's update/merge split
(AggregateFunctions.scala:334-762): ``update`` reduces raw input into typed
buffer columns; ``merge`` re-reduces buffers across batches/shards; and
``finalize`` computes the result column.  That split is exactly what the
distributed exchange needs (partial agg -> shuffle by key -> final agg).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as dts
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.ops import selection
from spark_rapids_tpu.ops.expressions import ColVal, Expression, combine_validity


# ------------------------------------------------------------- sort utilities

def _row_mask(nrows, capacity: int, row_mask=None):
    """bool[capacity] of live rows: row_mask overrides the nrows prefix."""
    if row_mask is not None:
        return row_mask
    return jnp.arange(capacity, dtype=jnp.int32) < nrows


def _sortable_keys(keys: Sequence[ColVal], valid_rows, capacity: int,
                   descending: Optional[Sequence[bool]] = None,
                   nulls_first: Optional[Sequence[bool]] = None):
    """Build the lexsort key list (least-significant first) from key
    columns, and the dead-row flag that sorts last whatever the keys say
    (padding or filtered rows; ``selection.lexsort_i32``'s ``dead``).
    Floats are normalized so NaN sorts largest and -0.0 == 0.0 (Spark
    ordering)."""
    n = len(keys)
    descending = descending or [False] * n
    nulls_first = nulls_first or [not d for d in descending]
    pad = jnp.logical_not(valid_rows)
    lex: List = []
    # jnp.lexsort sorts by last key first; we append least-significant first
    for c, desc, nf in zip(reversed(list(keys)), reversed(list(descending)),
                           reversed(list(nulls_first))):
        c = widen_colval(c, capacity)
        v = c.values
        if c.validity is not None:
            # canonicalize raw values under null BEFORE building the
            # order keys: otherwise null rows scatter by their garbage
            # payload, splitting the null group whenever a
            # lower-significance key varies (the coded group-by path
            # treats all nulls as one digit, and SQL groups nulls
            # together)
            v = jnp.where(c.validity, v, jnp.zeros_like(v))
        lex.extend(_order_keys(v, desc))
        if c.validity is not None:
            null_key = jnp.logical_not(c.validity).astype(jnp.int8)
            lex.append(-null_key if nf else null_key)
    return lex, pad


def _order_keys(v, desc: bool) -> List:
    """Lexsort key pieces (least-significant first) realizing the Spark
    total order for one column.  No 64-bit bitcasts: TPU's X64 rewriter
    cannot lower f64<->u64 bitcast-convert, so floats sort as a normalized
    float key plus a more-significant NaN flag (NaN largest, -0.0 == 0.0),
    ints directly (descending via bitwise-not, monotone-decreasing for
    two's-complement)."""
    if jnp.issubdtype(v.dtype, jnp.floating):
        nan = jnp.isnan(v)
        f = jnp.where(v == 0.0, 0.0, v)
        f = jnp.where(nan, 0.0, f)
        flag = nan.astype(jnp.int8)
        if desc:
            return [-f, -flag]
        return [f, flag]
    if v.dtype == jnp.bool_:
        v = v.astype(jnp.int8)
        return [~v] if desc else [v]
    return [~v] if desc else [v]


def widen_colval(c: ColVal, capacity: int) -> ColVal:
    """Scalar-broadcast values/validity (e.g. from literal-operand
    arithmetic) widen to full columns before sort/gather — lexsort and
    row gathers require uniform shapes."""
    v, val = c.values, c.validity
    if getattr(v, "ndim", 0) == 0:
        v = jnp.broadcast_to(v, (capacity,))
    if val is not None and getattr(val, "ndim", 0) == 0:
        val = jnp.broadcast_to(val, (capacity,))
    if v is c.values and val is c.validity:
        return c
    return ColVal(c.dtype, v, val, c.offsets)


def sort_permutation(keys: Sequence[ColVal], valid_rows, capacity: int,
                     descending: Optional[Sequence[bool]] = None,
                     nulls_first: Optional[Sequence[bool]] = None):
    lex, dead = _sortable_keys(keys, valid_rows, capacity, descending,
                               nulls_first)
    return selection.lexsort_i32(lex, dead=dead)


def _keys_equal_prev(sorted_keys: Sequence[ColVal], capacity: int):
    """bool[capacity]: row i has identical keys to row i-1 (nulls equal)."""
    eq = jnp.ones(capacity, dtype=jnp.bool_)
    for c in sorted_keys:
        v = c.values
        if jnp.issubdtype(v.dtype, jnp.floating):
            v = jnp.where(v == 0.0, 0.0, v)
            same = (v == jnp.roll(v, 1)) | (jnp.isnan(v) &
                                            jnp.isnan(jnp.roll(v, 1)))
        else:
            same = v == jnp.roll(v, 1)
        if c.validity is not None:
            pv = jnp.roll(c.validity, 1)
            same = jnp.where(c.validity & pv, same,
                             jnp.logical_not(c.validity | pv))
        eq = jnp.logical_and(eq, same)
    return eq.at[0].set(False)


# --------------------------------------------------------- aggregate functions

@dataclasses.dataclass(frozen=True)
class BufferSpec:
    """One reduction buffer: how to seed it from input and re-reduce it."""
    kind: str          # 'sum' | 'min' | 'max' | 'count' | 'first' |
    #                    'last' | 'first_any' | 'last_any'
    dtype: DataType


def merge_kind(update_kind: str) -> str:
    """Reduction kind applied when re-reducing PARTIAL buffer rows
    (chunked merge and the mesh exchange).  The one mapping both the
    single-host merge (exec/aggregate.py) and the distributed merge
    (parallel/distributed.py) import — the *_any update kinds collapse
    to plain first/last because their partial validity means
    "observed >=1 live row" (presence), and first-present IS the
    ignoreNulls=false merge rule."""
    return {"sum": "sum", "count": "sum", "min": "min", "max": "max",
            "first": "first", "last": "last",
            "first_any": "first", "last_any": "last"}[update_kind]


class AggregateFunction:
    """Base: declares buffers, update transform, and finalize."""

    name = "agg"

    def __init__(self, child: Optional[Expression]):
        self.child = child

    # buffer schema produced by update (and consumed/produced by merge)
    def buffers(self) -> List[BufferSpec]:
        raise NotImplementedError

    def update_inputs(self, c: Optional[ColVal], capacity: int) -> List[ColVal]:
        """Map the evaluated child column to one ColVal per buffer."""
        raise NotImplementedError

    def finalize(self, bufs: List[ColVal]) -> ColVal:
        raise NotImplementedError

    @property
    def result_dtype(self) -> DataType:
        raise NotImplementedError

    @property
    def result_nullable(self) -> bool:
        return True

    def cache_key(self):
        return (type(self).__name__,
                self.child.cache_key() if self.child is not None else None)

    def supported_reason(self) -> Optional[str]:
        """None when the device can run this aggregate; else why not
        (the planner tags it and the query falls back)."""
        return None


def _sum_result_type(t: DataType) -> DataType:
    if t.is_floating:
        return dts.FLOAT64
    if t.is_decimal:
        # Spark: sum(decimal(p,s)) = decimal(p+10, s), capped at
        # DECIMAL_64 (device eligibility is gated separately in
        # supported_reason: p+10 > 18 falls back to CPU)
        from spark_rapids_tpu.columnar.dtypes import DecimalType
        return DecimalType(min(t.precision + 10, 18), t.scale)
    return dts.INT64


def _decimal_sum_gate(name: str, t: DataType) -> Optional[str]:
    """Why ``name`` over ``t`` cannot keep its decimal sum on the device,
    or None: the int64 accumulator could silently wrap past DECIMAL_64
    (the reference's DECIMAL_64 sum gate)."""
    if t.is_decimal and t.precision + 10 > 18:
        return (f"{name} over {t} needs decimal({t.precision + 10},"
                f"{t.scale}) > DECIMAL_64; falls back to CPU")
    return None


class Sum(AggregateFunction):
    name = "sum"

    @property
    def result_dtype(self):
        return _sum_result_type(self.child.dtype)

    def supported_reason(self):
        return _decimal_sum_gate(self.name, self.child.dtype)

    def buffers(self):
        return [BufferSpec("sum", self.result_dtype)]

    def update_inputs(self, c, capacity):
        t = self.result_dtype
        return [ColVal(t, c.values.astype(t.storage), c.validity)]

    def finalize(self, bufs):
        return bufs[0]


class Count(AggregateFunction):
    """count(expr) — count(Literal(1)) is count(*)."""

    name = "count"

    @property
    def result_dtype(self):
        return dts.INT64

    @property
    def result_nullable(self):
        return False

    def buffers(self):
        return [BufferSpec("sum", dts.INT64)]

    def update_inputs(self, c, capacity):
        if c is None or c.validity is None:
            ones = jnp.ones(capacity, dtype=jnp.int64)
            return [ColVal(dts.INT64, ones)]
        return [ColVal(dts.INT64, c.validity.astype(jnp.int64))]

    def finalize(self, bufs):
        v = bufs[0]
        # count is 0, never null, for empty groups
        if v.validity is not None:
            return ColVal(dts.INT64, jnp.where(v.validity, v.values, 0))
        return v


class Min(AggregateFunction):
    name = "min"

    @property
    def result_dtype(self):
        return self.child.dtype

    def buffers(self):
        return [BufferSpec("min", self.child.dtype)]

    def update_inputs(self, c, capacity):
        return [c]

    def finalize(self, bufs):
        return bufs[0]


class Max(AggregateFunction):
    name = "max"

    @property
    def result_dtype(self):
        return self.child.dtype

    def buffers(self):
        return [BufferSpec("max", self.child.dtype)]

    def update_inputs(self, c, capacity):
        return [c]

    def finalize(self, bufs):
        return bufs[0]


class Average(AggregateFunction):
    """avg: a sum and a count, both merge-by-sum.  Over a double or an
    integer the sum is a double and the result ``sum / count``.  Over
    ``decimal(p,s)`` the sum is the unscaled int64 of Spark's
    ``decimal(p+10,s)`` sum buffer and the result is Spark's
    ``decimal(p+4,s+4)``, rounded HALF_UP from the exact quotient in
    integers (``decimal_average``): no float comes near it."""

    name = "avg"

    @property
    def _decimal(self) -> bool:
        return self.child is not None and self.child.dtype.is_decimal

    @property
    def result_dtype(self):
        if self._decimal:
            # Spark avg(decimal(p,s)) = decimal(p+4, s+4) (capped)
            from spark_rapids_tpu.ops.decimal_ops import (
                adjust_precision_scale)
            t = self.child.dtype
            return adjust_precision_scale(t.precision + 4, t.scale + 4)
        return dts.FLOAT64

    def supported_reason(self):
        # the sum buffer's gate is sum's own (p+10 within DECIMAL_64)
        return _decimal_sum_gate(self.name, self.child.dtype) \
            if self._decimal else None

    def buffers(self):
        total = _sum_result_type(self.child.dtype) if self._decimal \
            else dts.FLOAT64
        return [BufferSpec("sum", total), BufferSpec("sum", dts.INT64)]

    def update_inputs(self, c, capacity):
        total = self.buffers()[0].dtype
        return [ColVal(total, c.values.astype(total.storage), c.validity),
                ColVal(dts.INT64,
                       c.validity.astype(jnp.int64) if c.validity is not None
                       else jnp.ones(capacity, dtype=jnp.int64))]

    def finalize(self, bufs):
        s, n = bufs
        cnt = jnp.where(n.values == 0, 1, n.values)
        validity = combine_validity(s.validity, n.values > 0)
        if self._decimal:
            out = self.result_dtype
            values, fits = decimal_average(
                s.values, cnt, out.scale - self.child.dtype.scale,
                out.precision)
            return ColVal(out, values, combine_validity(validity, fits))
        return ColVal(dts.FLOAT64, s.values / cnt, validity)


def _divmod_nonneg(n, d):
    """``(n // d, n % d)`` of int64 ``0 <= n`` and ``1 <= d < 2^62`` by
    shift and subtract, one bit a step of a 64-step loop: the chip has
    no 64-bit divider, and its compiler spends 7 s on every ``//`` it
    unrolls (22 s an average, a minute of a q7 merge program) against
    under a second for this loop; the quotients are a handful of rows
    a group, so the loop's run time is nothing."""
    zero = jnp.zeros_like(n)

    def step(_, carry):
        n, q, r = carry
        r = (r << 1) | jax.lax.shift_right_logical(n, jnp.int64(63))
        ge = r >= d
        return n << 1, (q << 1) | ge.astype(jnp.int64), \
            jnp.where(ge, r - d, r)

    _, q, r = jax.lax.fori_loop(0, 64, step, (n, zero, zero))
    return q, r


def decimal_average(total, count, digits: int, precision: int):
    """(unscaled int64 of ``total / count`` with ``digits`` more decimal
    places, rounded HALF_UP; whether it fits ``precision`` digits).

    Exact in int64: with ``q, r = divmod(|total|, count)`` the result is
    ``q * 10^digits + round_half_up(r * 10^digits / count)``, and
    ``2 * r * 10^digits < 2 * count * 10^digits`` stays inside int64 for
    any count below 4.6e14 at the usual four digits.  A ``q`` too large
    for the result type (the sum buffer overflowed) is Spark's non-ANSI
    overflow: NULL, and its product is not formed."""
    mult = 10 ** digits
    negative = total < 0
    count = count.astype(jnp.int64)
    q, r = _divmod_nonneg(jnp.abs(total), count)
    frac, _ = _divmod_nonneg(2 * r * mult + count, 2 * count)
    fits = q < 10 ** (precision - digits)
    value = jnp.where(fits, q, 0) * mult + frac
    fits = jnp.logical_and(fits, value < 10 ** precision)
    return jnp.where(negative, -value, value), fits


class _CentralMoment(AggregateFunction):
    """Base for variance/stddev: buffers are sum(x), sum(x^2), n — all
    merge-by-sum, so chunked partial merge and the mesh exchange work
    unchanged.  Spark's CPU path uses Welford updates; the sum-of-squares
    form fits the engine's single-pass variadic reduce and matches to
    ~1e-9 relative on double inputs (documented incompat class, like
    cudf's).  Reference: GpuStddevSamp/GpuVariancePop rules in
    GpuOverrides.scala (aggregate section)."""

    ddof = 0          # 0 = population, 1 = sample
    sqrt_result = False

    @property
    def result_dtype(self):
        return dts.FLOAT64

    def supported_reason(self):
        t = self.child.dtype
        if not (t.is_numeric or t.is_boolean):
            return (f"{self.name} over {t.name} values has no device "
                    "implementation")
        return None

    def buffers(self):
        return [BufferSpec("sum", dts.FLOAT64),
                BufferSpec("sum", dts.FLOAT64),
                BufferSpec("sum", dts.INT64)]

    def update_inputs(self, c, capacity):
        x = c.values.astype(jnp.float64)
        ones = (c.validity.astype(jnp.int64) if c.validity is not None
                else jnp.ones(capacity, dtype=jnp.int64))
        return [ColVal(dts.FLOAT64, x, c.validity),
                ColVal(dts.FLOAT64, x * x, c.validity),
                ColVal(dts.INT64, ones)]

    def finalize(self, bufs):
        s, s2, n = bufs
        cnt = n.values.astype(jnp.float64)
        denom = cnt - self.ddof
        safe_cnt = jnp.where(cnt == 0, 1.0, cnt)
        safe_denom = jnp.where(denom <= 0, 1.0, denom)
        m2 = s2.values - (s.values * s.values) / safe_cnt
        m2 = jnp.maximum(m2, 0.0)  # clamp catastrophic cancellation
        out = m2 / safe_denom
        if self.sqrt_result:
            out = jnp.sqrt(out)
        # var_pop defined for n>=1; *_samp needs n>=2 (Spark returns
        # NaN for n==1 sample variance, null for n==0)
        nan = jnp.where(jnp.logical_and(self.ddof == 1, cnt == 1),
                        jnp.float64(jnp.nan), out)
        validity = combine_validity(s.validity, n.values > 0)
        return ColVal(dts.FLOAT64, nan, validity)


class VariancePop(_CentralMoment):
    name = "var_pop"
    ddof = 0


class VarianceSamp(_CentralMoment):
    name = "var_samp"
    ddof = 1


class StddevPop(_CentralMoment):
    name = "stddev_pop"
    ddof = 0
    sqrt_result = True


class StddevSamp(_CentralMoment):
    name = "stddev_samp"
    ddof = 1
    sqrt_result = True


class First(AggregateFunction):
    name = "first"

    def __init__(self, child, ignore_nulls: bool = False):
        super().__init__(child)
        self.ignore_nulls = ignore_nulls

    @property
    def result_dtype(self):
        return self.child.dtype

    _any_kind = "first_any"

    def cache_key(self):
        # the buffer schema depends on _classic, so jit-cache keys must
        # distinguish ignoreNulls and child nullability
        return (type(self).__name__, self._classic,
                self.child.cache_key() if self.child is not None else None)

    def buffers(self):
        # Spark default ignoreNulls=false: the group's first ROW wins,
        # null or not.  Two buffers: the value at the first live row
        # (buffer validity = "this partial observed >=1 live row", so a
        # filtered-empty partial can never win the merge) plus the
        # selected row's validity bit as a VALUE.  Merge reduces both
        # with plain first/last over partial presence.  With
        # ignoreNulls the single classic first-valid buffer suffices.
        if self._classic:
            return [BufferSpec(self.name, self.child.dtype)]
        return [BufferSpec(self._any_kind, self.child.dtype),
                BufferSpec(self._any_kind, dts.BOOL)]

    @property
    def _classic(self) -> bool:
        """Single first-valid buffer suffices: ignoreNulls requested, or
        the child is statically non-nullable (first-valid == first-row)."""
        return self.ignore_nulls or not self.child.nullable

    def update_inputs(self, c, capacity):
        if self._classic:
            return [c]
        vbit = c.validity if c.validity is not None else \
            jnp.ones(capacity, dtype=jnp.bool_)
        return [ColVal(c.dtype, c.values, None),
                ColVal(dts.BOOL, vbit, None)]

    def finalize(self, bufs):
        if self._classic:
            return bufs[0]
        v, bit = bufs
        validity = combine_validity(v.validity, bit.values)
        return ColVal(v.dtype, v.values, validity)


class Last(First):
    name = "last"
    _any_kind = "last_any"


# ------------------------------------------------------------ reduction cores

def _sentinel(kind: str, np_dtype):
    np_dtype = np.dtype(np_dtype)
    if np_dtype.kind == "f":
        info = np.finfo(np_dtype)
        return info.max if kind == "min" else info.min
    if np_dtype.kind == "b":
        return True if kind == "min" else False
    info = np.iinfo(np_dtype)
    return info.max if kind == "min" else info.min


def _segment_reduce(kind: str, c: ColVal, seg_ids, num_segments: int,
                    valid_rows):
    """Reduce one buffer column by segment. Returns (values, nonnull_counts)."""
    contrib_valid = valid_rows if c.validity is None else \
        jnp.logical_and(valid_rows, c.validity)
    counts = jax.ops.segment_sum(contrib_valid.astype(jnp.int64), seg_ids,
                                 num_segments=num_segments)
    if kind == "sum":
        vals = jnp.where(contrib_valid, c.values,
                         jnp.zeros((), dtype=c.values.dtype))
        out = jax.ops.segment_sum(vals, seg_ids, num_segments=num_segments)
    elif kind == "min":
        vals = jnp.where(contrib_valid, c.values, _sentinel("min", c.values.dtype))
        out = jax.ops.segment_min(vals, seg_ids, num_segments=num_segments)
    elif kind == "max":
        vals = jnp.where(contrib_valid, c.values, _sentinel("max", c.values.dtype))
        out = jax.ops.segment_max(vals, seg_ids, num_segments=num_segments)
    elif kind in ("first", "last"):
        n = c.values.shape[0]
        idx = jnp.arange(n, dtype=jnp.int64)
        if kind == "first":
            pick = jnp.where(contrib_valid, idx, n)
            best = jax.ops.segment_min(pick, seg_ids, num_segments=num_segments)
        else:
            pick = jnp.where(contrib_valid, idx, -1)
            best = jax.ops.segment_max(pick, seg_ids, num_segments=num_segments)
        safe = jnp.clip(best, 0, n - 1).astype(jnp.int32)
        out = c.values[safe]
    elif kind in ("first_any", "last_any"):
        # ignoreNulls=false update: the first/last LIVE row wins
        # regardless of value validity.  counts = LIVE rows, so the
        # buffer's validity means "this partial observed any row"
        # (presence) — the merge then reduces with plain first/last
        # over presence and First.finalize re-applies the selected
        # row's validity bit from the companion buffer.
        n = c.values.shape[0]
        idx = jnp.arange(n, dtype=jnp.int64)
        if kind == "first_any":
            pick = jnp.where(valid_rows, idx, n)
            best = jax.ops.segment_min(pick, seg_ids,
                                       num_segments=num_segments)
        else:
            pick = jnp.where(valid_rows, idx, -1)
            best = jax.ops.segment_max(pick, seg_ids,
                                       num_segments=num_segments)
        safe = jnp.clip(best, 0, n - 1).astype(jnp.int32)
        out = c.values[safe]
        counts = jax.ops.segment_sum(
            valid_rows.astype(jnp.int64), seg_ids,
            num_segments=num_segments)
    else:
        raise ValueError(f"unknown reduce kind {kind}")
    return out, counts


def groupby_aggregate(keys: Sequence[ColVal],
                      buffer_inputs: Sequence[Tuple[str, ColVal]],
                      nrows, capacity: int, row_mask=None):
    """Group by ``keys``, reduce each (kind, column) buffer input.

    All arguments are traced values; runs inside jit.  ``row_mask`` (if
    given) marks live rows — a fused upstream filter — overriding the
    ``nrows`` prefix.  Returns (out_keys, out_buffers, num_groups); output
    rows beyond num_groups are padding.
    """

    keys = [widen_colval(c, capacity) for c in keys]
    buffer_inputs = [(k, widen_colval(c, capacity))
                     for k, c in buffer_inputs]
    live = _row_mask(nrows, capacity, row_mask)
    n_live = live.sum().astype(jnp.int32)
    perm = sort_permutation(keys, live, capacity)
    # after the sort all live rows form a prefix of length n_live
    valid_sorted_mask = jnp.arange(capacity, dtype=jnp.int32) < n_live
    sorted_keys = selection.gather(keys, perm, n_live)
    sorted_bufs = selection.gather([c for _, c in buffer_inputs], perm,
                                   n_live)

    same_as_prev = _keys_equal_prev(sorted_keys, capacity)
    boundary = jnp.logical_and(jnp.logical_not(same_as_prev),
                               valid_sorted_mask)
    num_groups = boundary.sum().astype(jnp.int32)
    seg_ids = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    # padding rows -> a trash segment that segment_* drops (>= num_segments)
    seg_ids = jnp.where(valid_sorted_mask, seg_ids, capacity)

    out_bufs: List[ColVal] = []
    for (kind, _), sc in zip(buffer_inputs, sorted_bufs):
        vals, counts = _segment_reduce(kind, sc, seg_ids, capacity,
                                       valid_sorted_mask)
        out_bufs.append(ColVal(sc.dtype, vals, counts > 0))

    # representative row (first) of each group for the key values
    first_idx = jax.ops.segment_min(
        jnp.arange(capacity, dtype=jnp.int64), seg_ids, num_segments=capacity)
    first_idx = jnp.clip(first_idx, 0, capacity - 1).astype(jnp.int32)
    out_keys = selection.gather(sorted_keys, first_idx, num_groups)
    return out_keys, out_bufs, num_groups


# --------------------------------------------------- coded (sort-free) path
# XLA's variadic sort is the dominant cost of the sort-based group-by
# (seconds per multi-million-row batch on CPU, and serial on TPU's VPU);
# when every key is fixed-width integral and the key-space product is
# small, groups are addressed DIRECTLY: code = radix-mix of (key - min)
# digits, one segment-reduce per buffer into the code table, then a
# cumsum-compaction of occupied slots.  No sort anywhere.  The reference
# reaches the same regime with cudf's hash aggregation
# (aggregate.scala:184-209 hash first, sort only as fallback).

MAX_CODED_GROUPS = 1 << 21


def coded_key_eligible(dtypes) -> bool:
    """Keys a radix code can address: fixed-width, non-float (floats
    have no dense integer range)."""
    return all(
        not dt.has_offsets and not dt.is_floating
        for dt in dtypes)


def key_range_probe(keys: Sequence[ColVal], live):
    """Per-key (min, max) over live valid rows as int64[nkeys] pair —
    fused into stage A so range discovery costs one pass, synced to the
    host to pick coded vs sort dispatch.  All 2*nkeys reductions ride a
    single multi-operand lax.reduce (one pass over the key columns)."""
    operands, inits = [], []
    for c in keys:
        v = c.values
        if v.dtype == jnp.bool_:
            v = v.astype(jnp.int32)
        info = jnp.iinfo(v.dtype)
        valid = live if c.validity is None else \
            jnp.logical_and(live, c.validity)
        operands.append(jnp.where(valid, v, info.max))
        inits.append(jnp.asarray(info.max, dtype=v.dtype))
        operands.append(jnp.where(valid, v, info.min))
        inits.append(jnp.asarray(info.min, dtype=v.dtype))

    def comp(acc, x):
        out = []
        for i, (a, b) in enumerate(zip(acc, x)):
            out.append(jnp.minimum(a, b) if i % 2 == 0
                       else jnp.maximum(a, b))
        return tuple(out)

    res = jax.lax.reduce(tuple(operands), tuple(inits), comp, [0])
    mins = jnp.stack([res[2 * i].astype(jnp.int64)
                      for i in range(len(keys))])
    maxs = jnp.stack([res[2 * i + 1].astype(jnp.int64)
                      for i in range(len(keys))])
    return mins, maxs


def coded_slot_ranges(mins: np.ndarray, maxs: np.ndarray):
    """Host-side: per-key slot count (digit 0 is ALWAYS the null slot,
    whether or not the key is nullable — keeps the host sizing and the
    traced validity structure trivially consistent) and the total
    key-space size; None when the space is too large for the coded
    path."""
    slots = []
    total = 1
    for mn, mx in zip(mins.tolist(), maxs.tolist()):
        rn = max(0, int(mx) - int(mn) + 1)
        slots.append(rn + 1)
        total *= rn + 1
        if total > MAX_CODED_GROUPS:
            return None
    return slots, total


def _segment_reduce_coded(kind: str, c: ColVal, code, ns: int,
                          counts_of):
    """One buffer reduction for the coded path.  Null/dead rows are
    folded into the TRASH SEGMENT of the code vector instead of masking
    the value column — an int32 pass (or none) replaces the full-width
    ``where`` pass per buffer.  ``counts_of(validity)`` returns (cached)
    per-slot live counts for a validity array."""
    capacity = code.shape[0]
    vals = c.values
    if getattr(vals, "ndim", 0) == 0:
        vals = jnp.broadcast_to(vals, (capacity,))
    if kind in ("first_any", "last_any"):
        # ignoreNulls=false update: route by the LIVE code (null-valued
        # rows stay in their group); counts = live rows (presence)
        idx = jnp.arange(capacity, dtype=jnp.int32)
        seg_op = jax.ops.segment_min if kind == "first_any" \
            else jax.ops.segment_max
        best = seg_op(idx, code, num_segments=ns)
        safe = jnp.clip(best, 0, capacity - 1)
        return vals[safe][: ns - 1], counts_of(None, code)
    if c.validity is not None:
        bcode = jnp.where(c.validity, code, ns - 1)
    else:
        bcode = code
    counts = counts_of(c.validity, bcode)
    if kind == "sum":
        out = jax.ops.segment_sum(vals, bcode, num_segments=ns)
    elif kind == "min":
        out = jax.ops.segment_min(vals, bcode, num_segments=ns)
    elif kind == "max":
        out = jax.ops.segment_max(vals, bcode, num_segments=ns)
    elif kind in ("first", "last"):
        idx = jnp.arange(capacity, dtype=jnp.int32)
        if kind == "first":
            best = jax.ops.segment_min(idx, bcode, num_segments=ns)
        else:
            best = jax.ops.segment_max(idx, bcode, num_segments=ns)
        safe = jnp.clip(best, 0, capacity - 1)
        out = vals[safe]
    else:
        raise ValueError(f"unknown reduce kind {kind}")
    return out[: ns - 1], counts


def groupby_aggregate_coded(keys: Sequence[ColVal],
                            buffer_inputs: Sequence[Tuple[str, ColVal]],
                            nrows, capacity: int, mins, slot_ranges,
                            k_bucket: int, row_mask=None):
    """Sort-free group-by: keys must be fixed-width integral with the
    key-space product <= ``k_bucket`` (static).  ``mins``/``slot_ranges``
    are traced int64[nkeys] (data-dependent, but only k_bucket shapes the
    program).  Output groups are ordered ascending with nulls first —
    identical to the sort path's order.  Output arrays are sized by the
    key space (max(k_bucket, 1024)), NOT the input capacity."""
    nkeys = len(keys)
    keys = [widen_colval(c, capacity) for c in keys]
    live = _row_mask(nrows, capacity, row_mask)

    # row codes: digit 0 = null (nulls first), 1.. = value - min + 1
    # (digit 0 is reserved even for non-nullable keys — see
    # coded_slot_ranges)
    code = jnp.zeros(capacity, dtype=jnp.int64)
    stride = jnp.int64(1)
    strides_rev = []
    for i in reversed(range(nkeys)):
        c = keys[i]
        v = c.values
        if v.dtype == jnp.bool_:
            v = v.astype(jnp.int32)
        v = v.astype(jnp.int64)
        rn = slot_ranges[i] - 1
        d = jnp.clip(v - mins[i], 0, jnp.maximum(rn - 1, 0)) + 1
        if c.validity is not None:
            d = jnp.where(c.validity, d, 0)
        code = code + d * stride
        strides_rev.append(stride)
        stride = stride * slot_ranges[i]
    strides = strides_rev[::-1]
    # clamp before the narrowing cast: the speculative path
    # (groupby_aggregate_coded_auto) runs this body even when the key
    # space overflows the bucket — codes must stay in-range garbage
    # (the trash segment), never wrap through int32
    code = jnp.clip(code, 0, k_bucket)
    code = jnp.where(live, code, k_bucket).astype(jnp.int32)
    ns = k_bucket + 1

    # ---- batched sum scatter -------------------------------------------
    # Same-dtype/same-validity "sum" buffers stack into ONE 2D
    # segment-sum: the scatter index is computed once per row for all of
    # them instead of once per buffer.  A validity-free float64/integer
    # group additionally carries a ones column, so the per-slot live
    # counts (the bincount) ride the same scatter — q1's four sums plus
    # its counts collapse from five scatters to one.  (ones ride only in
    # dtypes where the count sums exactly: f64 up to 2^53, integers.)
    sum_groups: Dict[tuple, List[int]] = {}
    for j, (kind, c) in enumerate(buffer_inputs):
        v = c.values
        if kind == "sum" and getattr(v, "ndim", 0) == 1:
            key = (v.dtype,
                   id(c.validity) if c.validity is not None else None)
            sum_groups.setdefault(key, []).append(j)
    slot_counts_all = None
    batched_sums: Dict[int, Tuple] = {}  # j -> (per-slot sums, validity)
    for (dt, vid), idxs in sum_groups.items():
        exact_ones = dt == jnp.float64 or jnp.issubdtype(dt, jnp.integer)
        fuse_counts = vid is None and exact_ones and \
            slot_counts_all is None
        if len(idxs) < 2 and not fuse_counts:
            continue
        cs = [buffer_inputs[j][1] for j in idxs]
        validity = cs[0].validity
        bcode = code if validity is None else \
            jnp.where(validity, code, ns - 1)
        cols = [c.values for c in cs]
        if fuse_counts:
            cols = cols + [jnp.ones(capacity, dtype=dt)]
        stacked = jnp.stack(cols, axis=1)
        summed = jax.ops.segment_sum(stacked, bcode, num_segments=ns)
        if fuse_counts:
            # read only as ``> 0``: left in the sums' own type (an
            # emulated f64 -> int64 convert is a chain of passes)
            slot_counts_all = summed[:, -1]
        for col_i, j in enumerate(idxs):
            batched_sums[j] = (summed[:, col_i], validity)

    # per-slot live counts, shared by every buffer whose validity is None
    if slot_counts_all is None:
        slot_counts_all = jnp.bincount(code, length=ns)
    counts_cache = {}

    slot_counts = slot_counts_all[:k_bucket]

    def counts_of(validity, bcode):
        if validity is None:
            return slot_counts
        key = id(validity)
        got = counts_cache.get(key)
        if got is None:
            got = jnp.bincount(bcode, length=ns)[:k_bucket]
            counts_cache[key] = got
        return got

    occupied = slot_counts > 0
    num_groups = occupied.sum().astype(jnp.int32)
    pos = jnp.cumsum(occupied.astype(jnp.int32)) - 1
    # compaction scatter target: occupied slot -> dense position,
    # unoccupied -> out_cap (dropped); outputs are key-space sized
    out_cap = max(k_bucket, 1024)
    out_idx = jnp.where(occupied, pos, out_cap)

    # a slot's digits, in 32 bits (a slot index fits, as the row codes
    # above do; the chip emulates 64-bit division in hundreds of passes
    # over the directory).  The last key's stride is 1 and the first
    # key's quotient is its digit already (slot < key space), so a
    # single key divides nothing.
    slots = jnp.arange(k_bucket, dtype=jnp.int32)
    out_keys: List[ColVal] = []
    for i, c in enumerate(keys):
        digit = slots
        if i < nkeys - 1:
            digit = digit // jnp.maximum(strides[i].astype(jnp.int32), 1)
        if i > 0:
            digit = digit % jnp.maximum(
                slot_ranges[i].astype(jnp.int32), 1)
        vals = mins[i] + digit - 1
        if c.validity is not None:
            vd = jnp.zeros(out_cap, dtype=jnp.bool_)
            vd = vd.at[out_idx].set(digit > 0, mode="drop")
        else:
            vd = None  # digit 0 never occupied without nulls
        out_dt = c.values.dtype
        if out_dt == jnp.bool_:
            vals = vals.astype(jnp.int64) != 0
        dst = jnp.zeros(out_cap, dtype=out_dt)
        dst = dst.at[out_idx].set(vals.astype(out_dt), mode="drop")
        out_keys.append(ColVal(c.dtype, dst, vd))

    # a buffer counted by the slots' own live rows is valid in every
    # group: the dense prefix, no scatter (the chip sorts to scatter
    # booleans)
    all_groups = jnp.arange(out_cap, dtype=jnp.int32) < num_groups

    def compact(c, vals, counts):
        dv = jnp.zeros(out_cap, dtype=vals.dtype)
        dv = dv.at[out_idx].set(vals[:k_bucket], mode="drop")
        if counts is slot_counts:
            return ColVal(c.dtype, dv, all_groups)
        dvalid = jnp.zeros(out_cap, dtype=jnp.bool_)
        dvalid = dvalid.at[out_idx].set(counts[:k_bucket] > 0, mode="drop")
        return ColVal(c.dtype, dv, dvalid)

    out_bufs: List[Optional[ColVal]] = [None] * len(buffer_inputs)
    for j, (kind, c) in enumerate(buffer_inputs):
        got = batched_sums.get(j)
        if got is not None:
            summed_col, validity = got
            bcode = code if validity is None else \
                jnp.where(validity, code, ns - 1)
            out_bufs[j] = compact(c, summed_col[: ns - 1],
                                  counts_of(validity, bcode))
            continue
        vals, counts = _segment_reduce_coded(kind, c, code, ns,
                                             counts_of)
        out_bufs[j] = compact(c, vals, counts)
    return out_keys, out_bufs, num_groups


def coded_ranges_on_device(keys: Sequence[ColVal], live, k_bucket: int):
    """On-device analog of probe + ``coded_slot_ranges``: per-key
    (min, max), clamped per-key slot counts, and a ``fits`` flag for
    ``total key space <= k_bucket``.  Everything stays device-resident,
    so the coded-vs-sort dispatch needs ONE host sync (the flag) instead
    of a probe round trip followed by a second kernel launch.

    Overflow discipline: slot counts and the running product are clamped
    (the clamps only bite when ``fits`` is already False, where the coded
    output is discarded anyway), so the arithmetic never wraps into a
    spuriously-fitting total."""
    mins, maxs = key_range_probe(keys, live)
    rn = jnp.maximum(maxs - mins + 1, 0)
    slot_ranges = rn + 1  # +1: digit 0 is always the null slot
    total = jnp.int64(1)
    total_cap = jnp.int64(1) << 40
    for i in range(len(keys)):
        s = jnp.clip(slot_ranges[i], 1, jnp.int64(1) << 20)
        total = jnp.minimum(total * s, total_cap)
    fits = total <= k_bucket
    safe_ranges = jnp.minimum(slot_ranges, jnp.int64(k_bucket) + 1)
    return mins, maxs, safe_ranges, fits


def groupby_aggregate_coded_auto(keys: Sequence[ColVal],
                                 buffer_inputs: Sequence[Tuple[str, ColVal]],
                                 nrows, capacity: int, k_bucket: int,
                                 row_mask=None):
    """Single-pass speculative coded group-by: range discovery, fit
    check and the coded reduction run in ONE computation against a
    fixed speculative ``k_bucket``.  Returns
    (out_keys, out_bufs, num_groups, fits, mins, maxs): when ``fits``
    is True the outputs are exact (identical ordering to the sort
    path); when False they are garbage to discard, and the caller
    re-dispatches from the already-computed (mins, maxs) — the old
    two-pass probe cost is only ever paid on speculation misses."""
    keys = [widen_colval(c, capacity) for c in keys]
    live = _row_mask(nrows, capacity, row_mask)
    mins, maxs, safe_ranges, fits = coded_ranges_on_device(
        keys, live, k_bucket)
    out_keys, out_bufs, num_groups = groupby_aggregate_coded(
        keys, buffer_inputs, nrows, capacity, mins, safe_ranges,
        k_bucket, row_mask=row_mask)
    return out_keys, out_bufs, num_groups, fits, mins, maxs


def reduce_aggregate(buffer_inputs: Sequence[Tuple[str, ColVal]],
                     nrows, capacity: int, row_mask=None) -> List[ColVal]:
    """Grand-total (no keys) reduction: one output row per buffer.

    Dense masked reductions, NOT segment ops: XLA lowers segment_* to
    scatter, which serializes on TPU; a masked jnp.sum/min/max is a native
    tree reduction on the VPU (orders of magnitude faster at multi-million
    row capacities)."""
    valid_rows = _row_mask(nrows, capacity, row_mask)
    # ONE multi-operand lax.reduce: every buffer's reduction plus the
    # contribution counts ride a single pass over the input — XLA fuses
    # the predicate/projection producers into the reduce loop, so a
    # filter+sum query (TPC-H q6) touches each input byte exactly once
    # (measured ~5x over one jnp-reduction per buffer on CPU)
    operands: List = []
    inits: List = []
    comb: List[str] = []

    def add_slot(op, init, how) -> int:
        operands.append(op)
        inits.append(init)
        comb.append(how)
        return len(operands) - 1

    if not buffer_inputs:
        return []
    count_slot: dict = {}
    plan = []  # per buffer: (kind, c, contrib_key, value_slot)
    for kind, c in buffer_inputs:
        contrib_valid = valid_rows if c.validity is None else \
            jnp.logical_and(valid_rows, c.validity)
        # *_any kinds count LIVE rows (presence), not valid values
        vkey = id(c.validity) if (
            c.validity is not None and
            kind not in ("first_any", "last_any")) else None
        if vkey not in count_slot:
            count_slot[vkey] = add_slot(
                (contrib_valid if vkey is not None else valid_rows
                 ).astype(jnp.int64), jnp.int64(0), "add")
        v = c.values
        if getattr(v, "ndim", 0) == 0:
            v = jnp.broadcast_to(v, (capacity,))
        if kind == "sum":
            slot = add_slot(
                jnp.where(contrib_valid, v,
                          jnp.zeros((), dtype=v.dtype)).astype(v.dtype),
                jnp.zeros((), dtype=v.dtype), "add")
        elif kind in ("min", "max"):
            s = _sentinel(kind, v.dtype)
            slot = add_slot(jnp.where(contrib_valid, v, s),
                            jnp.asarray(s, dtype=v.dtype), kind)
        elif kind in ("first", "last"):
            idx = jnp.arange(capacity, dtype=jnp.int64)
            if kind == "first":
                slot = add_slot(
                    jnp.where(contrib_valid, idx, capacity),
                    jnp.int64(capacity), "min")
            else:
                slot = add_slot(jnp.where(contrib_valid, idx, -1),
                                jnp.int64(-1), "max")
        elif kind in ("first_any", "last_any"):
            # ignoreNulls=false: pick by row liveness alone (the count
            # slot above already rides liveness via vkey=None)
            idx = jnp.arange(capacity, dtype=jnp.int64)
            if kind == "first_any":
                slot = add_slot(jnp.where(valid_rows, idx, capacity),
                                jnp.int64(capacity), "min")
            else:
                slot = add_slot(jnp.where(valid_rows, idx, -1),
                                jnp.int64(-1), "max")
        else:
            raise ValueError(f"unknown reduce kind {kind}")
        plan.append((kind, c, vkey, slot))

    def comp(acc, x):
        out = []
        for a, b, how in zip(acc, x, comb):
            if how == "add":
                out.append(a + b)
            elif how == "min":
                out.append(jnp.minimum(a, b))
            else:
                out.append(jnp.maximum(a, b))
        return tuple(out)

    res = jax.lax.reduce(tuple(operands), tuple(inits), comp, [0])

    outs: List[ColVal] = []
    for kind, c, vkey, slot in plan:
        count = res[count_slot[vkey]]
        if kind in ("first", "last", "first_any", "last_any"):
            best = jnp.clip(res[slot], 0, capacity - 1).astype(jnp.int32)
            v = c.values
            if getattr(v, "ndim", 0) == 0:
                v = jnp.broadcast_to(v, (capacity,))
            out = v[best]
        else:
            out = res[slot]
        outs.append(ColVal(c.dtype, out[None], (count > 0)[None]))
    return outs


# ----------------------------------------------------- collect aggregates

class CollectList(AggregateFunction):
    """collect_list(x): per-group array of non-null values
    (CudfCollectList, AggregateFunctions.scala:256).  Evaluated in a
    single grouped pass — after the group sort the group's values are
    already contiguous, so the array column is a compaction, not a
    per-group loop.  ``single_pass``: the exec concatenates its input
    instead of the partial/merge pipeline."""

    name = "collect_list"
    single_pass = True
    dedup = False

    @property
    def result_dtype(self):
        from spark_rapids_tpu.columnar.dtypes import ArrayType
        return ArrayType(self.child.dtype)

    @property
    def result_nullable(self):
        return False

    def buffers(self):
        raise NotImplementedError("collect runs in the single-pass path")


class CollectSet(CollectList):
    """collect_set(x): distinct non-null values per group, ascending
    (CudfCollectSet, AggregateFunctions.scala:278 — Spark leaves set
    order unspecified)."""

    name = "collect_set"
    dedup = True


def groupby_collect(keys: Sequence[ColVal], collect_inputs, nrows,
                    capacity: int,
                    buffer_inputs: Sequence[Tuple[str, ColVal]] = (),
                    row_mask=None):
    """Group by ``keys``; for each (child, dedup) in collect_inputs build
    a per-group array column, and reduce ``buffer_inputs`` as usual.

    Returns (out_keys, out_buffers, collect_arrays, num_groups) where
    each collect array is a ColVal with offsets (ARRAY layout).
    """

    live = _row_mask(nrows, capacity, row_mask)
    n_live = live.sum().astype(jnp.int32)
    perm = sort_permutation(keys, live, capacity)
    valid_sorted_mask = jnp.arange(capacity, dtype=jnp.int32) < n_live
    sorted_keys = selection.gather(keys, perm, n_live)
    same_as_prev = _keys_equal_prev(sorted_keys, capacity)
    boundary = jnp.logical_and(jnp.logical_not(same_as_prev),
                               valid_sorted_mask)
    num_groups = boundary.sum().astype(jnp.int32)
    seg_ids = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    seg_ids = jnp.where(valid_sorted_mask, seg_ids, capacity)

    out_bufs: List[ColVal] = []
    if buffer_inputs:
        sorted_bufs = selection.gather([c for _, c in buffer_inputs], perm,
                                       n_live)
        for (kind, _), sc in zip(buffer_inputs, sorted_bufs):
            vals, counts = _segment_reduce(kind, sc, seg_ids, capacity,
                                           valid_sorted_mask)
            out_bufs.append(ColVal(sc.dtype, vals, counts > 0))

    collect_outs: List[ColVal] = []
    for child, dedup in collect_inputs:
        if dedup:
            # per-group value order + dedup need values as a secondary
            # sort key: same group order (keys primary), nulls pushed to
            # the group end so they can never split a run of equal values
            null_flag = jnp.zeros(capacity, dtype=jnp.int8) \
                if child.validity is None else \
                jnp.logical_not(child.validity).astype(jnp.int8)
            lex, dead = _sortable_keys(keys, live, capacity)
            perm2 = selection.lexsort_i32(
                _order_keys(child.values, False) + [null_flag] + lex,
                dead=dead)
            sc = selection.gather([child] + list(keys), perm2, n_live)
            schild, skeys2 = sc[0], sc[1:]
            same2 = _keys_equal_prev(skeys2, capacity)
            seg2 = jnp.cumsum(jnp.logical_and(
                jnp.logical_not(same2), valid_sorted_mask)
                .astype(jnp.int32)) - 1
            seg2 = jnp.where(valid_sorted_mask, seg2, capacity)
            v = schild.values
            same_val = v == jnp.roll(v, 1)
            if jnp.issubdtype(v.dtype, jnp.floating):
                same_val = same_val | (jnp.isnan(v) &
                                       jnp.isnan(jnp.roll(v, 1)))
            if schild.validity is not None:
                # a null row's LANE value may equal a valid value; runs
                # must only merge valid-with-valid
                vv = schild.validity
                same_val = jnp.logical_and(
                    same_val, jnp.logical_and(vv, jnp.roll(vv, 1)))
            first_of_run = jnp.logical_not(
                jnp.logical_and(same2, same_val))
            keep = jnp.logical_and(valid_sorted_mask, first_of_run)
            if schild.validity is not None:
                keep = jnp.logical_and(keep, schild.validity)
            seg_for = seg2
        else:
            sc = selection.gather([child], perm, n_live)
            schild = sc[0]
            keep = valid_sorted_mask
            if schild.validity is not None:
                keep = jnp.logical_and(keep, schild.validity)
            seg_for = seg_ids
        lengths = jax.ops.segment_sum(keep.astype(jnp.int32), seg_for,
                                      num_segments=capacity)
        offsets = jnp.concatenate([jnp.zeros(1, dtype=jnp.int32),
                                   jnp.cumsum(lengths, dtype=jnp.int32)])
        compacted, _ = selection.compact(
            [ColVal(child.dtype, schild.values, None)], keep)
        from spark_rapids_tpu.columnar.dtypes import ArrayType
        collect_outs.append(ColVal(ArrayType(child.dtype),
                                   compacted[0].values, None, offsets))

    first_idx = jax.ops.segment_min(
        jnp.arange(capacity, dtype=jnp.int64), seg_ids,
        num_segments=capacity)
    first_idx = jnp.clip(first_idx, 0, capacity - 1).astype(jnp.int32)
    out_keys = selection.gather(sorted_keys, first_idx, num_groups)
    return out_keys, out_bufs, collect_outs, num_groups
