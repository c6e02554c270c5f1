"""Row selection kernels: mask compaction and permutation gather.

The TPU replacement for cudf's ``Table.filter`` / gather-map machinery
(reference ``basicPhysicalOperators.scala:297`` GpuFilterExec and
``JoinGatherer.scala``).  cudf allocates an exact-size output; XLA wants
static shapes, so these kernels keep the input capacity and return a traced
``new_nrows`` — the caller re-buckets later if occupancy gets low.

String gather is fully vectorized: new offsets by cumsum of gathered lengths,
then every output byte finds its row (``rows_of_positions``: a histogram of
the row ends and a prefix sum, O(C + N) for C chars in N rows) and from the
row's shift its source byte: two gathers of C elements in all.

``compact`` gathers only where something has to move: a ``lax.cond`` on
the mask hands the buffers out as they are when the kept rows are already
the dense prefix (a filter over a scan that applied the same predicate on
the host keeps every row; arbitrary-index gathers are what the chip does
worst: 8 ns an element, 338 ms a 2^20-row batch of q3's lineitem, PR 35).
The rule that goes with it: ``compact`` is only called under a trace,
because a ``lax.cond`` run op by op compiles on every call.  A caller on
the host runs ``compact_by_gather``, the same compaction with no branch.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.ops.expressions import ColVal


def lexsort_i32(keys: Sequence[jnp.ndarray], dead=None) -> jnp.ndarray:
    """``jnp.lexsort(keys)`` (last key primary, stable) as an int32
    permutation; with ``dead`` (bool per row), the same permutation as
    ``jnp.lexsort(keys + [dead])`` — dead rows last, the order among
    live and among dead rows untouched.

    Built so that the sorting network stays small, because on a TPU its
    compile time is the cold start (64-bit operands sort as pairs of
    32-bit ones, and the cost grows much faster than the operand
    count; asked of the chip's compiler in PR 26, sandbox CPU seconds):

    * one stable single-key sort per key, least significant first (an
      LSD radix over the keys), instead of one sort comparing every key:
      six keys (three values, three null flags) at 32,768 rows compile
      in 40 s this way, against about 300 s inside q3's group-by;
    * the row index rides as int32, not the int64 ``jnp.lexsort`` /
      ``jnp.argsort`` carry under x64 (one int64 key plus a flag at
      65,536 rows: 126 s against 75 s);
    * ``dead`` is a stable partition after the sort (a cumsum and one
      scatter), not one more key.

    Every step is stable, so the permutation is ``jnp.lexsort``'s bit
    for bit.  Capacities are far below 2^31."""
    keys = list(keys)
    n = (keys[0] if keys else dead).shape[0]
    rows = jax.lax.iota(jnp.int32, n)
    perm = rows
    for i, key in enumerate(keys):
        order = jax.lax.sort((key if i == 0 else key[perm], rows),
                             num_keys=1, is_stable=True)[1]
        perm = order if i == 0 else perm[order]
    if dead is None:
        return perm
    live = jnp.logical_not(dead[perm])
    ahead = jnp.cumsum(live, dtype=jnp.int32)      # live rows up to here
    dest = jnp.where(live, ahead - 1, ahead[n - 1] + rows - ahead)
    return jnp.zeros(n, jnp.int32).at[dest].set(
        perm, unique_indices=True)


SCAN_BLOCK = 1024


def cumsum_32(x):
    """Inclusive prefix sums of a 32-bit vector in its own type
    (wrapping), as rows of ``SCAN_BLOCK``: each row scanned, then the
    row totals, then one add.  The same numbers as ``jnp.cumsum``, whose
    one reduce-window as wide as the vector the chip's compiler takes 20
    to 100 s over at 2^19 and 2^20 rows, against 1 to 2 s for this."""
    n = x.shape[0]
    if n <= SCAN_BLOCK or n % SCAN_BLOCK:
        return jnp.cumsum(x, dtype=x.dtype)
    rows = jnp.cumsum(x.reshape(n // SCAN_BLOCK, SCAN_BLOCK), axis=1,
                      dtype=x.dtype)
    totals = rows[:, -1]
    before = cumsum_32(totals) - totals
    return (rows + before[:, None]).reshape(n)


def rows_of_positions(offsets, n: int):
    """For each position 0..n-1 of an element buffer, the row that holds
    it: how many rows end at or before it, which for offsets that start
    at 0 is ``searchsorted(offsets, pos, side="right") - 1`` to the last
    row (callers clip to their capacity).  A histogram of the row ends
    and a prefix sum: one scatter of the offsets and one scan of ``n``,
    where the binary search is log2(rows) dependent gathers of ``n``
    elements each, and an arbitrary gather is what the chip does worst:
    for the 2^23 bytes of 2^18 part names 1,145 ms against 3 (PR 37)."""
    ends = jnp.zeros(n, jnp.int32).at[offsets[1:]].add(1, mode="drop")
    return cumsum_32(ends)


def gather(cols: Sequence[ColVal], indices, out_count,
           char_capacity: int = 0) -> List[ColVal]:
    """Gather rows of every column at ``indices`` (int array, len=capacity).

    Rows at positions >= out_count are padding. ``indices`` entries for
    padding rows may be arbitrary but must be in-range.  ``char_capacity``
    (static) sizes offset-bearing outputs (string chars / array elements)
    when the gather can *expand* totals (join/explode duplication); 0
    keeps each input's capacity.
    """
    capacity = indices.shape[0]
    out_mask = jnp.arange(capacity, dtype=jnp.int32) < out_count
    outs: List[ColVal] = []
    for c in cols:
        validity = None if c.validity is None else c.validity[indices]
        if c.offsets is None:
            outs.append(ColVal(c.dtype, c.values[indices], validity))
            continue
        # string column: rebuild offsets + chars
        starts = c.offsets[indices]
        lengths = jnp.where(out_mask, c.offsets[indices + 1] - starts, 0)
        new_offsets = jnp.concatenate(
            [jnp.zeros(1, dtype=jnp.int32), jnp.cumsum(lengths,
                                                       dtype=jnp.int32)])
        in_char_cap = c.values.shape[0]
        out_char_cap = char_capacity or in_char_cap
        pos = jnp.arange(out_char_cap, dtype=jnp.int32)
        # row containing each output byte (last offset <= pos); a row's
        # bytes all move by the same distance
        row = jnp.clip(rows_of_positions(new_offsets, out_char_cap),
                       0, capacity - 1)
        shift = starts - new_offsets[:-1]
        src = jnp.clip(pos + shift[row], 0, in_char_cap - 1)
        total = new_offsets[capacity]
        # keep the element buffer's own dtype: uint8 chars for strings,
        # the element storage dtype for arrays (a hardcoded uint8 cast
        # silently truncated array elements, e.g. 300 -> 44)
        chars = jnp.where(pos < total, c.values[src],
                          jnp.zeros((), dtype=c.values.dtype))
        outs.append(ColVal(c.dtype, chars, validity, new_offsets))
    return outs


@jax.jit
def gathered_char_count(offsets, indices, out_count):
    """Total chars a gather of ``indices`` would produce (for sizing)."""
    capacity = indices.shape[0]
    mask = jnp.arange(capacity, dtype=jnp.int32) < out_count
    lengths = offsets[indices + 1] - offsets[indices]
    return jnp.where(mask, lengths, 0).sum()


def _gather_kept(cols: Sequence[ColVal], keep, new_nrows) -> List[ColVal]:
    """The kept rows gathered to the front.  Linear cost: a prefix-sum
    gives each kept row its target slot and one scatter builds the
    permutation — no sort (cudf's apply_boolean_mask does a similar
    stream compaction; an argsort here would be O(n log^2 n) on TPU's
    bitonic sorter)."""
    capacity = keep.shape[0]
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    tgt = jnp.where(keep, pos, capacity)  # dropped rows scatter out of range
    perm = jnp.zeros(capacity, dtype=jnp.int32).at[tgt].set(
        jnp.arange(capacity, dtype=jnp.int32), mode="drop")
    return gather(cols, perm, new_nrows)


def compact(cols: Sequence[ColVal], keep) -> Tuple[List[ColVal], jnp.ndarray]:
    """Move rows where ``keep`` is True to the front, preserving order.

    Returns (columns, new_nrows). ``keep`` must already exclude padding rows.

    Where the kept rows are already the dense prefix (every row kept, or
    every dropped row behind the last kept one: a predicate the scan has
    applied exactly on the host, a range over the sort key) nothing has
    to move, and nothing does: a ``lax.cond`` on the mask returns the
    buffers as they are, and only a mask that keeps scattered rows pays
    the permutation and the gathers (:func:`_gather_kept`).  Rows below
    ``new_nrows`` are bit for bit the same in both branches; the padding
    above differs and nobody may read it.  Offset-bearing columns keep
    :func:`gather`'s padding (offsets flat after the last kept row,
    elements zero from their total on) with element-wise work only.

    Call it under a trace only: a ``lax.cond`` run op by op is traced
    and compiled anew on every call.  From the host, call
    :func:`compact_by_gather`.
    """
    capacity = keep.shape[0]
    new_nrows = keep.sum().astype(jnp.int32)
    dense = jnp.all(
        keep == (jnp.arange(capacity, dtype=jnp.int32) < new_nrows))

    def in_place(cols):
        outs = []
        for c in cols:
            if c.offsets is None:
                outs.append(c)
                continue
            total = c.offsets[new_nrows]
            pos = jnp.arange(c.values.shape[0], dtype=jnp.int32)
            elements = jnp.where(pos < total, c.values,
                                 jnp.zeros((), dtype=c.values.dtype))
            outs.append(ColVal(c.dtype, elements, c.validity,
                               jnp.minimum(c.offsets, total)))
        return outs

    def moved(cols):
        return _gather_kept(cols, keep, new_nrows)

    return jax.lax.cond(dense, in_place, moved, list(cols)), new_nrows


def compact_by_gather(cols: Sequence[ColVal], keep
                      ) -> Tuple[List[ColVal], jnp.ndarray]:
    """:func:`compact` with no branch, for a caller on the host that
    runs it op by op (the join's own compactions, exec/join.py).  Not a
    program of its own, by measurement (PR 35, q18's semi join, 60 of
    1.5M orders kept, nine columns at 2^20 rows): op by op each gather
    is a program whose operand the chip's compiler stages in fast
    memory, 9–10 ms a buffer; inside one program over the nine columns
    the same gathers take 20–32 ms each (0.67 s a query against 0.30),
    and a program a column compiles its own 23 s prefix-sum."""
    new_nrows = keep.sum().astype(jnp.int32)
    return _gather_kept(cols, keep, new_nrows), new_nrows
