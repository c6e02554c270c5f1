"""Equi-join kernels: combined-sort run matching + two-phase materialization.

The reference drives cudf hash joins and materializes unbounded outputs
through chunked gather maps (``GpuHashJoin.scala:96``, ``JoinGatherer.scala:
36-60``).  Hash tables scatter serially; the TPU formulation is sort-merge:

* phase A (``join_match``): concatenate build+probe key columns, lexsort by
  (keys, side) so each equal-key run holds its build rows first; segment
  arithmetic yields, for every probe row, its match count and the sorted
  position of its first build match.  Null keys never match (Spark equi-join
  semantics) but outer/anti rows survive via count adjustment.
* phase B (``join_gather``): with the total match count known on the host,
  a bucketed output capacity is chosen and every output row is mapped back
  to (probe row, k-th build match): the probe row by a histogram of the
  probe rows' ends and a prefix sum, the build row by one 32-bit gather
  of the probe row's offset into the sorted build side and one of the
  build row there — the same static-shape expansion as the string gather
  (``selection.rows_of_positions``), and as there no binary search.

Semi/anti joins skip phase B entirely (a compaction of the probe side).
Full outer adds one extra batch of never-matched build rows.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.ops.expressions import ColVal
from spark_rapids_tpu.ops import selection


def _concat_col(b: ColVal, p: ColVal) -> ColVal:
    values = jnp.concatenate([b.values, p.values])
    validity = None
    if b.validity is not None or p.validity is not None:
        bv = b.validity if b.validity is not None else \
            jnp.ones(b.values.shape[0], dtype=jnp.bool_)
        pv = p.validity if p.validity is not None else \
            jnp.ones(p.values.shape[0], dtype=jnp.bool_)
        validity = jnp.concatenate([bv, pv])
    return ColVal(b.dtype, values, validity)


def _norm_key(v):
    if jnp.issubdtype(v.dtype, jnp.floating):
        v = jnp.where(v == 0.0, 0.0, v)
        bits = v.astype(jnp.float64).view(jnp.int64)
        v = jnp.where(bits < 0, jnp.int64(-1) ^ bits, bits)
    elif v.dtype == jnp.bool_:
        v = v.astype(jnp.int8)
    return v


@jax.jit
def join_match(build_keys: Sequence[ColVal], probe_keys: Sequence[ColVal],
               build_n, probe_n):
    """Phase A. Returns a dict of device arrays (see keys below)."""
    b_cap = build_keys[0].values.shape[0]
    p_cap = probe_keys[0].values.shape[0]
    cap = b_cap + p_cap
    pos = jnp.arange(cap, dtype=jnp.int32)
    is_build = pos < b_cap
    side = jnp.where(is_build, 0, 1).astype(jnp.int8)

    # live = in-range AND all keys non-null (null never matches)
    live_b = pos < build_n
    live_p = (pos >= b_cap) & (pos < b_cap + probe_n)
    live = live_b | live_p
    norm_keys = []
    for bk, pk in zip(build_keys, probe_keys):
        c = _concat_col(bk, pk)
        if c.validity is not None:
            live = live & c.validity
        norm_keys.append(_norm_key(c.values))

    # sort: dead rows last, then by keys, then build before probe —
    # the stable sort's own tie-break, build rows being the lower
    # positions, so ``side`` is no operand of the sorting network
    perm = selection.lexsort_i32(list(reversed(norm_keys)),
                                 dead=jnp.logical_not(live))
    n_live = live.sum().astype(jnp.int32)

    s_keys = [k[perm] for k in norm_keys]
    s_side = side[perm]
    s_live = jnp.arange(cap, dtype=jnp.int32) < n_live

    same = jnp.ones(cap, dtype=jnp.bool_)
    for k in s_keys:
        same = same & (k == jnp.roll(k, 1))
    boundary = jnp.logical_and(jnp.logical_not(same.at[0].set(True)) |
                               (jnp.arange(cap) == 0), s_live)
    run_id = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    run_id = jnp.where(s_live, run_id, cap)  # trash segment

    sb = jnp.logical_and(s_side == 0, s_live)
    sp = jnp.logical_and(s_side == 1, s_live)
    build_per_run = jax.ops.segment_sum(sb.astype(jnp.int32), run_id,
                                        num_segments=cap + 1)[:cap]
    probe_per_run = jax.ops.segment_sum(sp.astype(jnp.int32), run_id,
                                        num_segments=cap + 1)[:cap]
    spos = jnp.arange(cap, dtype=jnp.int32)
    first_build = jax.ops.segment_min(
        jnp.where(sb, spos, cap), run_id, num_segments=cap + 1)[:cap]

    # scatter per-sorted-probe-row info back to original probe row ids
    orig = perm - b_cap  # original probe row (valid where s_side==1)
    probe_tgt = jnp.where(sp, orig, p_cap)
    probe_count = jnp.zeros(p_cap, dtype=jnp.int32).at[probe_tgt].set(
        jnp.where(sp, build_per_run[jnp.clip(run_id, 0, cap - 1)], 0),
        mode="drop")
    probe_bstart = jnp.zeros(p_cap, dtype=jnp.int32).at[probe_tgt].set(
        jnp.where(sp, first_build[jnp.clip(run_id, 0, cap - 1)], 0),
        mode="drop")

    # sorted position -> original build row
    sorted_to_build = jnp.where(s_side == 0, perm, 0).astype(jnp.int32)

    # build rows that matched no probe row (for full outer)
    build_matched = jnp.zeros(b_cap, dtype=jnp.bool_)
    build_tgt = jnp.where(sb, perm, b_cap)
    build_matched = build_matched.at[build_tgt].set(
        jnp.where(sb, probe_per_run[jnp.clip(run_id, 0, cap - 1)] > 0,
                  False), mode="drop")
    return {
        "probe_count": probe_count,
        "probe_bstart": probe_bstart,
        "sorted_to_build": sorted_to_build,
        "build_matched": build_matched,
    }


def _cumsum_i64(count):
    """Inclusive prefix sums of non-negative int32 counts, exact in
    int64, from two 32-bit scans: the sums modulo 2^32, and how often
    they wrapped so far (a count is under 2^32, so a step wraps at most
    once, and it did iff the sum fell).  An int64 scan is emulated on
    the chip and compiles slowest of all; a cold compile died in it
    (PERF.md, PR 30)."""
    lo = selection.cumsum_32(count.astype(jnp.uint32))
    wrapped = lo < jnp.concatenate([jnp.zeros(1, jnp.uint32), lo[:-1]])
    hi = selection.cumsum_32(wrapped.astype(jnp.int32))
    return (hi.astype(jnp.int64) << 32) | lo.astype(jnp.int64)


@partial(jax.jit, static_argnames=("outer",))
def join_out_starts(probe_count, probe_n, outer: bool):
    """Adjusted counts (left outer keeps unmatched with one null row),
    exclusive starts, inclusive ends, and total."""
    p_cap = probe_count.shape[0]
    in_range = jnp.arange(p_cap, dtype=jnp.int32) < probe_n
    count = probe_count
    if outer:
        count = jnp.where(in_range & (count == 0), 1, count)
    count = jnp.where(in_range, count, 0)
    ends = _cumsum_i64(count)
    starts = (ends - count).astype(jnp.int64)
    return count, starts, ends, ends[p_cap - 1]


_SIGN = np.int32(-2 ** 31)        # ``x ^ _SIGN`` is x + 2^31 modulo 2^32


@lru_cache(maxsize=None)
def _gather_indices_kernel(out_cap: int):
    @jax.jit
    def join_gather_rows(starts, ends, probe_count, probe_bstart,
                         sorted_to_build, total):
        """Output row ``j`` of ``out_cap`` belongs to the probe row ``p``
        with ``starts[p] <= j < ends[p]``: ``p`` is how many rows end at
        or before ``j``, read off a histogram of the ends by a prefix
        sum.  (A binary search of every ``j`` in the int64 ``ends`` is
        log2(rows) dependent gathers of ``out_cap`` emulated 64-bit
        elements, and an arbitrary gather is what the chip does worst:
        320 to 394 ms a launch for q9's 2^19 rows against 9 to 12,
        PERF.md, PR 38.)  ``ends`` may be the caller's ``ends - offset``:
        an end at or before 0 is before every ``j`` and counts at 0, one
        at or past ``out_cap`` is after every ``j`` and is dropped.
        Rows that emit nothing share their end with the row before, so
        the count passes over them.  Only the clip is 64-bit."""
        p_cap = probe_count.shape[0]
        cap = sorted_to_build.shape[0]
        j = jnp.arange(out_cap, dtype=jnp.int32)
        in_range = j < jnp.minimum(total, out_cap).astype(jnp.int32)
        end_at = jnp.clip(ends, 0, out_cap).astype(jnp.int32)
        p = selection.cumsum_32(
            jnp.zeros(out_cap, jnp.int32).at[end_at].add(1, mode="drop"))
        p = jnp.minimum(p, p_cap - 1)
        # the k-th match of p sits at sorted position probe_bstart[p] + k
        # with k = j - starts[p]: one int32 a probe row, exact modulo 2^32
        # whatever the 64-bit starts of rows outside this chunk are.  A
        # row kept by an outer join alone (adjusted count 1 over a raw
        # count of 0) carries the sign bit: positions are under 2^31
        delta = probe_bstart - starts.astype(jnp.int32)
        delta = jnp.where(probe_count > 0, delta, delta ^ _SIGN)
        bpos = j + delta[p]
        matched = bpos >= 0
        brow = sorted_to_build[jnp.minimum(bpos & ~_SIGN, cap - 1)]
        return p, jnp.clip(brow, 0, None), matched & in_range, in_range
    return join_gather_rows


def join_gather_indices(starts, ends, probe_count, probe_bstart,
                        sorted_to_build, total, out_cap: int):
    """Phase B mapping: output row j -> (probe row, build row, matched?)."""
    return _gather_indices_kernel(out_cap)(
        starts, ends, probe_count, probe_bstart, sorted_to_build, total)


def gather_build_side(cols: Sequence[ColVal], brow, matched,
                      out_count, char_capacity: int = 0) -> List[ColVal]:
    """Gather build columns at brow; unmatched rows become null."""
    outs = selection.gather(cols, brow, out_count,
                            char_capacity=char_capacity)
    res = []
    for o in outs:
        validity = o.validity
        validity = matched if validity is None else \
            jnp.logical_and(validity, matched)
        res.append(ColVal(o.dtype, o.values, validity, o.offsets))
    return res
