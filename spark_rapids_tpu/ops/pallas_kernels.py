"""Pallas TPU kernels for the engine's hot data-parallel primitives.

- ``partition_histogram``: per-row partition-id counts.  XLA lowers
  ``segment_sum`` / one-hot scatter to a serialized scatter on TPU; here
  each grid step one-hot-expands a row block in VMEM and accumulates a
  (num_parts, 1) running sum — the TPU grid is sequential, so the
  accumulate-into-output pattern is race-free.  Feeds shuffle partition
  sizing and AQE statistics (the reference gets these numbers from cudf's
  ``contiguousSplit`` metadata, GpuPartitioning.scala:50).  Compiles for
  the chip (tests/test_chip_compile.py).

- ``hash_insert`` / ``hash_probe``: open-addressing hash table, behind
  the default-off ``spark.rapids.tpu.pallas.hash.enabled``.  Interpret
  mode only so far: the chip's compiler refuses the scalar stores.

Tests run the kernels under ``interpret=True`` on the CPU mesh.
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


def hash_dispatch_conf(conf=None):
    """Resolve ``(enabled, tableSlots)`` for the hash-kernel dispatch:
    explicit conf > active session > entry defaults.  Consumers read
    this per dispatch (the table size keys the jit-cache signature, so
    a conf flip can never be masked by a cached trace)."""
    from spark_rapids_tpu.config import rapids_conf as rc
    if conf is None:
        from spark_rapids_tpu.api.session import TpuSession
        s = TpuSession._active
        conf = s.conf if s is not None else None
    if conf is None:
        return (rc.PALLAS_HASH_ENABLED.default,
                rc.PALLAS_HASH_TABLE_SLOTS.default)
    return (conf.get(rc.PALLAS_HASH_ENABLED),
            conf.get(rc.PALLAS_HASH_TABLE_SLOTS))


# ---------------------------------------------------------------- histogram --

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


# ------------------------------------------------- hash table insert/probe --
# Single-pass open-addressing hash table over a 64-bit row code carried as
# two i32 lanes (TPU pallas avoids i64 lanes; the lo/hi split keeps the
# kernel i32-native and the XLA formulation bit-compatible).  Linear
# probing; a probe chain longer than ``_MAX_PROBE`` raises the overflow
# flag and the row parks in the trash slot ``T`` — callers DISCARD the
# whole output and re-run the segment-sum path (rows are never dropped,
# the shuffle slot-overflow discipline).  Table layout is impl-defined;
# only the stored code SET is contractual — callers order their output by
# stored code, so the pallas kernel and the XLA fallback are
# bit-interchangeable.
#
# VMEM bound: the table is 3 lanes x 4 bytes x num_slots resident per
# grid step — 12*T bytes, so T = 2^20 is ~12 MB and the practical ceiling
# (document in docs/performance.md).

_MAX_PROBE = 256


def _hash_index(lo, hi, num_slots: int, salt: int = 0):
    """murmur3 fmix32 over the two code lanes -> slot in [0, num_slots).

    Identical arithmetic in the pallas kernel and the XLA fallback for
    ``salt == 0``.  (Layouts can still diverge slot-for-slot — the
    sequential pallas insert and the multi-level XLA insert place
    contended keys differently — which is why only the stored-code set
    is contractual.)  ``salt`` decorrelates the XLA fallback's
    sub-table levels: without it, two keys colliding in a level would
    collide in every smaller level too (equal low hash bits imply
    equal lower ones)."""
    h = lo.astype(jnp.uint32) ^ jnp.uint32(salt & 0xFFFFFFFF) \
        ^ (hi.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return (h & jnp.uint32(num_slots - 1)).astype(jnp.int32)


# The XLA fallback's table layout: a fixed cascade of geometrically
# shrinking sub-tables (T/2, T/4, ..., the last two equal) summing to
# exactly T slots.  CPU XLA pays ~ms for every n-index scatter but ~us
# for gathers, so the insert does ONE unconditional last-writer
# scatter per level and verifies by gather — no arbitration rounds, no
# while_loop, a fixed 7 scatters total.  Keys whose level slot was
# taken by a different key cascade to the next level (salted hash per
# level keeps the cascades decorrelated); anything unresolved past the
# last level raises the overflow flag.  The pallas kernel keeps the
# sequential linear-probe layout — only the stored-code SET is
# contractual, and each impl's insert/probe pair is self-consistent.
_XLA_LEVELS = 6


def _xla_level_plan(num_slots: int):
    """[(offset, size)] of the XLA fallback's sub-table cascade."""
    assert num_slots >= 64 and num_slots & (num_slots - 1) == 0, \
        num_slots
    sizes = []
    s = num_slots // 2
    for _ in range(_XLA_LEVELS - 1):
        sizes.append(s)
        s //= 2
    sizes.append(sizes[-1])
    plan, off = [], 0
    for s in sizes:
        plan.append((off, s))
        off += s
    return plan


def hash_insert_xla(code_lo, code_hi, live, num_slots: int,
                    max_probe: int = _MAX_PROBE):
    """Vectorized XLA insert (production path off-TPU).

    Per level of the sub-table cascade: every unresolved row
    scatter-writes its packed code into its salted-hash slot
    (last-writer-wins — any winner is a correct winner, the loser key
    just cascades), then a gather checks which rows' codes were the
    ones stored; those resolve, the rest descend a level.  Duplicate
    rows of one key share every level slot, so the whole key resolves
    the moment one of its rows survives a write.  Returns
    ``(slot i32[n], table_lo i32[T], table_hi i32[T], occupied bool[T],
    overflow bool[])`` with dead/overflowed rows parked at ``slot == T``
    — overflow means a key was still homeless after the last level and
    the whole output must be DISCARDED (``max_probe`` is accepted for
    signature compatibility with the pallas kernel)."""
    del max_probe
    n = code_lo.shape[0]
    T = num_slots
    code_lo = code_lo.astype(jnp.int32)
    code_hi = code_hi.astype(jnp.int32)
    code64 = (code_hi.astype(jnp.int64) << 32) \
        | (code_lo.astype(jnp.int64) & jnp.int64(0xFFFFFFFF))
    t64 = jnp.zeros(T, jnp.int64)
    slot = jnp.where(live, jnp.int32(-1), jnp.int32(T))
    for lvl, (off, size) in enumerate(_xla_level_plan(T)):
        idx = off + _hash_index(code_lo, code_hi, size,
                                salt=lvl * 0x9E3779B9)
        unresolved = slot < 0
        t64 = t64.at[jnp.where(unresolved, idx, T)].set(
            code64, mode="drop")
        placed = unresolved & (t64[idx] == code64)
        slot = jnp.where(placed, idx, slot)
    ovf = jnp.any(slot < 0)
    slot = jnp.where(slot < 0, jnp.int32(T), slot)
    # occupancy from the resolved rows themselves (dead/overflowed rows
    # sit at T and drop): overwritten loser codes leave occ False, so
    # the probe can never false-match them, and no code value is
    # reserved as an empty sentinel (join codes may be ANY i64)
    occ = jnp.zeros(T, jnp.bool_).at[slot].set(True, mode="drop")
    tlo = t64.astype(jnp.int32)
    thi = (t64 >> 32).astype(jnp.int32)
    return slot, tlo, thi, occ, ovf


def hash_probe_xla(code_lo, code_hi, live, table_lo, table_hi, occupied,
                   max_probe: int = _MAX_PROBE):
    """Vectorized XLA lookup: slot of each live row's code, or ``T`` on
    miss.  Pure gathers — one salted-hash lookup per cascade level; a
    stored key matches at exactly the level that stored it (insert
    placement is unique), so the levels just OR together.  Only valid
    against a table built by :func:`hash_insert_xla` (the pallas pair
    owns the linear-probe layout)."""
    del max_probe
    T = occupied.shape[0]
    code_lo = code_lo.astype(jnp.int32)
    code_hi = code_hi.astype(jnp.int32)
    code64 = (code_hi.astype(jnp.int64) << 32) \
        | (code_lo.astype(jnp.int64) & jnp.int64(0xFFFFFFFF))
    t64 = (table_hi.astype(jnp.int64) << 32) \
        | (table_lo.astype(jnp.int64) & jnp.int64(0xFFFFFFFF))
    slot = jnp.full(code_lo.shape[0], T, jnp.int32)
    for lvl, (off, size) in enumerate(_xla_level_plan(T)):
        idx = off + _hash_index(code_lo, code_hi, size,
                                salt=lvl * 0x9E3779B9)
        hit = live & occupied[idx] & (t64[idx] == code64)
        slot = jnp.where(hit, idx, slot)
    return slot


def _hash_insert_kernel(lo_ref, hi_ref, live_ref, slot_ref, tlo_ref,
                        thi_ref, occ_ref, ovf_ref, *, num_slots: int,
                        max_probe: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        tlo_ref[...] = jnp.zeros_like(tlo_ref)
        thi_ref[...] = jnp.zeros_like(thi_ref)
        occ_ref[...] = jnp.zeros_like(occ_ref)
        ovf_ref[...] = jnp.zeros_like(ovf_ref)

    block = lo_ref.shape[1]

    def row_body(r, _):
        lo = lo_ref[0, r]
        hi = hi_ref[0, r]
        alive = live_ref[0, r]
        home = _hash_index(lo, hi, num_slots)
        # status: 0 probing, 1 match, 2 claim-empty, 3 overflow, 4 dead
        init = (jnp.where(alive, jnp.int32(0), jnp.int32(4)), home,
                jnp.int32(0))

        def cond(s):
            return s[0] == 0

        def probe_body(s):
            _, probe, cnt = s
            occ = occ_ref[0, probe]
            is_match = (occ != 0) & (tlo_ref[0, probe] == lo) \
                & (thi_ref[0, probe] == hi)
            status = jnp.where(is_match, jnp.int32(1),
                               jnp.where(occ == 0, jnp.int32(2),
                                         jnp.int32(0)))
            cnt = cnt + 1
            status = jnp.where((status == 0) & (cnt >= max_probe),
                               jnp.int32(3), status)
            probe = jnp.where(status == 0,
                              (probe + 1) & (num_slots - 1), probe)
            return (status, probe, cnt)

        status, pos, _ = jax.lax.while_loop(cond, probe_body, init)

        @pl.when(status == 2)
        def _claim():
            occ_ref[0, pos] = jnp.int32(1)
            tlo_ref[0, pos] = lo
            thi_ref[0, pos] = hi

        @pl.when(status == 3)
        def _overflow():
            ovf_ref[0, 0] = jnp.int32(1)

        slot_ref[0, r] = jnp.where(
            (status == 1) | (status == 2), pos, jnp.int32(num_slots))
        return 0

    jax.lax.fori_loop(0, block, row_body, 0)


def hash_insert(code_lo, code_hi, live, num_slots: int,
                max_probe: int = _MAX_PROBE,
                interpret: bool | None = None):
    """Pallas insert: the TPU grid is sequential, so the per-row probe
    loop owns the VMEM-resident table race-free.  Same contract and
    table layout as :func:`hash_insert_xla`."""
    if interpret is None:
        interpret = not _on_tpu()
    n = code_lo.shape[0]
    T = num_slots
    if n == 0:
        return (jnp.zeros(0, jnp.int32), jnp.zeros(T, jnp.int32),
                jnp.zeros(T, jnp.int32), jnp.zeros(T, jnp.bool_),
                jnp.asarray(False, jnp.bool_))
    padded = ((n + _BLOCK_ROWS - 1) // _BLOCK_ROWS) * _BLOCK_ROWS
    lo = code_lo.astype(jnp.int32)
    hi = code_hi.astype(jnp.int32)
    if padded != n:
        lo = jnp.pad(lo, (0, padded - n))
        hi = jnp.pad(hi, (0, padded - n))
        live = jnp.pad(live, (0, padded - n))
    block = pl.BlockSpec((1, _BLOCK_ROWS), lambda i: (0, i))
    table = pl.BlockSpec((1, T), lambda i: (0, 0))
    flag = pl.BlockSpec((1, 1), lambda i: (0, 0))
    slot, tlo, thi, occ, ovf = pl.pallas_call(
        functools.partial(_hash_insert_kernel, num_slots=T,
                          max_probe=max_probe),
        grid=(padded // _BLOCK_ROWS,),
        in_specs=[block, block, block],
        out_specs=[block, table, table, table, flag],
        out_shape=[jax.ShapeDtypeStruct((1, padded), jnp.int32),
                   jax.ShapeDtypeStruct((1, T), jnp.int32),
                   jax.ShapeDtypeStruct((1, T), jnp.int32),
                   jax.ShapeDtypeStruct((1, T), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret,
    )(lo.reshape(1, padded), hi.reshape(1, padded),
      live.reshape(1, padded))
    return (slot[0, :n], tlo[0], thi[0], occ[0].astype(jnp.bool_),
            ovf[0, 0] != 0)


def _hash_probe_kernel(lo_ref, hi_ref, live_ref, tlo_ref, thi_ref,
                       occ_ref, slot_ref, *, num_slots: int,
                       max_probe: int):
    block = lo_ref.shape[1]

    def row_body(r, _):
        lo = lo_ref[0, r]
        hi = hi_ref[0, r]
        alive = live_ref[0, r]
        home = _hash_index(lo, hi, num_slots)
        init = (jnp.where(alive, jnp.int32(0), jnp.int32(3)), home,
                jnp.int32(0))

        def cond(s):
            return s[0] == 0

        def probe_body(s):
            _, probe, cnt = s
            occ = occ_ref[0, probe]
            is_match = (occ != 0) & (tlo_ref[0, probe] == lo) \
                & (thi_ref[0, probe] == hi)
            status = jnp.where(is_match, jnp.int32(1),
                               jnp.where(occ == 0, jnp.int32(2),
                                         jnp.int32(0)))
            cnt = cnt + 1
            status = jnp.where((status == 0) & (cnt >= max_probe),
                               jnp.int32(2), status)
            probe = jnp.where(status == 0,
                              (probe + 1) & (num_slots - 1), probe)
            return (status, probe, cnt)

        status, pos, _ = jax.lax.while_loop(cond, probe_body, init)
        slot_ref[0, r] = jnp.where(status == 1, pos,
                                   jnp.int32(num_slots))
        return 0

    jax.lax.fori_loop(0, block, row_body, 0)


def hash_probe(code_lo, code_hi, live, table_lo, table_hi, occupied,
               max_probe: int = _MAX_PROBE,
               interpret: bool | None = None):
    """Pallas lookup matching :func:`hash_probe_xla`."""
    if interpret is None:
        interpret = not _on_tpu()
    n = code_lo.shape[0]
    T = occupied.shape[0]
    if n == 0:
        return jnp.zeros(0, jnp.int32)
    padded = ((n + _BLOCK_ROWS - 1) // _BLOCK_ROWS) * _BLOCK_ROWS
    lo = code_lo.astype(jnp.int32)
    hi = code_hi.astype(jnp.int32)
    if padded != n:
        lo = jnp.pad(lo, (0, padded - n))
        hi = jnp.pad(hi, (0, padded - n))
        live = jnp.pad(live, (0, padded - n))
    block = pl.BlockSpec((1, _BLOCK_ROWS), lambda i: (0, i))
    table = pl.BlockSpec((1, T), lambda i: (0, 0))
    slot = pl.pallas_call(
        functools.partial(_hash_probe_kernel, num_slots=T,
                          max_probe=max_probe),
        grid=(padded // _BLOCK_ROWS,),
        in_specs=[block, block, block, table, table, table],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((1, padded), jnp.int32),
        interpret=interpret,
    )(lo.reshape(1, padded), hi.reshape(1, padded),
      live.reshape(1, padded), table_lo.reshape(1, T).astype(jnp.int32),
      table_hi.reshape(1, T).astype(jnp.int32),
      occupied.reshape(1, T).astype(jnp.int32))
    return slot[0, :n]


def hash_table_insert(code_lo, code_hi, live, num_slots: int,
                      max_probe: int = _MAX_PROBE):
    """Production dispatch: pallas on a real TPU, XLA elsewhere (the
    round-based formulation vectorizes well on CPU; the sequential
    kernel only wins where VMEM residency does)."""
    if use_pallas():
        return hash_insert(code_lo, code_hi, live, num_slots,
                           max_probe=max_probe, interpret=False)
    return hash_insert_xla(code_lo, code_hi, live, num_slots,
                           max_probe=max_probe)


def hash_table_probe(code_lo, code_hi, live, table_lo, table_hi,
                     occupied, max_probe: int = _MAX_PROBE):
    """Production dispatch for the lookup side."""
    if use_pallas():
        return hash_probe(code_lo, code_hi, live, table_lo, table_hi,
                          occupied, max_probe=max_probe,
                          interpret=False)
    return hash_probe_xla(code_lo, code_hi, live, table_lo, table_hi,
                          occupied, max_probe=max_probe)
