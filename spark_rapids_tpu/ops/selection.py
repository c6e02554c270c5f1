"""Row selection kernels: mask compaction and permutation gather.

The TPU replacement for cudf's ``Table.filter`` / gather-map machinery
(reference ``basicPhysicalOperators.scala:297`` GpuFilterExec and
``JoinGatherer.scala``).  cudf allocates an exact-size output; XLA wants
static shapes, so these kernels keep the input capacity and return a traced
``new_nrows`` — the caller re-buckets later if occupancy gets low.

String gather is fully vectorized: new offsets by cumsum of gathered lengths,
then a searchsorted over char positions maps every output byte to its source
byte (O(C log N) for C chars — bandwidth-bound, which is what TPUs like).
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
        lengths = c.offsets[indices + 1] - c.offsets[indices]
        lengths = jnp.where(out_mask, lengths, 0)
        new_offsets = jnp.concatenate(
            [jnp.zeros(1, dtype=jnp.int32), jnp.cumsum(lengths,
                                                       dtype=jnp.int32)])
        in_char_cap = c.values.shape[0]
        out_char_cap = char_capacity or in_char_cap
        pos = jnp.arange(out_char_cap, dtype=jnp.int32)
        # row containing each output byte (last offset <= pos)
        row = jnp.searchsorted(new_offsets, pos, side="right") - 1
        row = jnp.clip(row, 0, capacity - 1)
        src = c.offsets[indices[row]] + (pos - new_offsets[row])
        src = jnp.clip(src, 0, in_char_cap - 1)
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


def compact(cols: Sequence[ColVal], keep) -> Tuple[List[ColVal], jnp.ndarray]:
    """Move rows where ``keep`` is True to the front, preserving order.

    Returns (columns, new_nrows). ``keep`` must already exclude padding rows.
    Linear cost: a prefix-sum gives each kept row its target slot and one
    scatter builds the permutation — no sort (cudf's apply_boolean_mask does
    a similar stream compaction; an argsort here would be O(n log^2 n) on
    TPU's bitonic sorter).
    """
    capacity = keep.shape[0]
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    new_nrows = keep.sum().astype(jnp.int32)
    tgt = jnp.where(keep, pos, capacity)  # dropped rows scatter out of range
    perm = jnp.zeros(capacity, dtype=jnp.int32).at[tgt].set(
        jnp.arange(capacity, dtype=jnp.int32), mode="drop")
    return gather(cols, perm, new_nrows), new_nrows
