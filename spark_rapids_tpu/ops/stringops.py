"""String expressions over the chars+offsets device layout.

Coverage target: the reference's ``stringFunctions.scala`` (1,053 LoC,
SURVEY.md Appendix A.1 "Strings").  Everything here is expressed as
bandwidth-friendly vector ops over the flat uint8 chars array plus per-row
offsets:

* per-row scalars (length, startswith, contains, ...) reduce over byte
  ranges via a byte->row segment map (``byte_to_row``: a histogram of the
  row ends and a prefix sum, ops/selection.py ``rows_of_positions``), or
  read a prefix sum over the bytes at the row's two ends (``Contains``);
* producers (substring, concat, trim, pad, upper/lower) compute output
  lengths first, then map every output byte back to its source byte — the
  same pattern the row gather uses;
* character (not byte) positions honor UTF-8 via a prefix sum over
  non-continuation bytes.

Case mapping is ASCII-only (documented incompat, like several cudf string
ops in the reference).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar import dtypes as dts
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.ops import selection
from spark_rapids_tpu.ops.expressions import (
    ColVal, EmitContext, Expression, UnaryExpression, combine_validity,
)


# ------------------------------------------------------------ layout helpers

def row_lengths(c: ColVal):
    """byte length per row."""
    return c.offsets[1:] - c.offsets[:-1]


def char_lengths(c: ColVal, ctx: EmitContext):
    """UTF-8 character count per row (non-continuation bytes)."""
    is_start = (c.values & 0xC0) != 0x80
    prefix = jnp.concatenate([jnp.zeros(1, dtype=jnp.int32),
                              jnp.cumsum(is_start.astype(jnp.int32))])
    return prefix[c.offsets[1:]] - prefix[c.offsets[:-1]]


def byte_to_row(c: ColVal, capacity: int):
    """row index of every byte position in the chars array."""
    row = selection.rows_of_positions(c.offsets, c.values.shape[0])
    return jnp.clip(row, 0, capacity - 1)


def build_strings(lengths, src_byte_fn, src_chars, out_char_cap: int,
                  capacity: int):
    """Construct (chars, offsets) given per-row output lengths and a
    function mapping (out_byte_pos, out_row, offset_in_row) -> source byte
    index into ``src_chars`` (already clipped)."""
    lengths = jnp.maximum(lengths, 0).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros(1, dtype=jnp.int32),
                               jnp.cumsum(lengths, dtype=jnp.int32)])
    pos = jnp.arange(out_char_cap, dtype=jnp.int32)
    row = jnp.clip(selection.rows_of_positions(offsets, out_char_cap),
                   0, capacity - 1)
    k = pos - offsets[row]
    src = src_byte_fn(pos, row, k)
    total = offsets[capacity]
    chars = jnp.where(pos < total,
                      src_chars[jnp.clip(src, 0, src_chars.shape[0] - 1)],
                      0).astype(jnp.uint8)
    return chars, offsets


def _literal_bytes(s: str) -> np.ndarray:
    return np.frombuffer(s.encode("utf-8"), dtype=np.uint8)


def string_select(masks, branches, capacity: int):
    """CASE over string branches: per row, the first true mask picks its
    branch's string; no true mask -> null.

    ``branches`` are string ColVals — full columns (offsets of
    capacity+1) or 1-row literals (offsets of length 2, broadcast to
    every row).  One fused pass: the chosen branch's (start, len) per
    row indexes a concatenation of all branch char buffers, and
    ``build_strings`` lays out the output — no per-branch materializing,
    no host loop."""
    nb = len(branches)
    ar = jnp.arange(capacity, dtype=jnp.int32)
    idx = jnp.full(capacity, nb, dtype=jnp.int32)
    for i in reversed(range(nb)):
        idx = jnp.where(masks[i], jnp.int32(i), idx)
    chosen = idx < nb
    safe = jnp.clip(idx, 0, nb - 1)
    starts, lens, valids, chunks = [], [], [], []
    base = 0
    out_char_cap = 0
    # literals contribute capacity * MAX literal length once (each row
    # picks at most one branch), not per-branch
    lit_max = 0
    for b in branches:
        if b.offsets is None:
            # null literal branch: zero-length slice, never valid
            chunks.append(jnp.zeros(0, dtype=jnp.uint8))
            starts.append(jnp.full(capacity, base, dtype=jnp.int32))
            lens.append(jnp.zeros(capacity, dtype=jnp.int32))
            valids.append(jnp.zeros(capacity, dtype=jnp.bool_))
            continue
        ch = b.values
        chunks.append(ch)
        if b.offsets.shape[0] == capacity + 1:
            st = b.offsets[:capacity].astype(jnp.int32)
            ln = (b.offsets[1:] - b.offsets[:-1]).astype(jnp.int32)
            out_char_cap += int(ch.shape[0])
        else:  # 1-row literal: same slice for every row
            st = jnp.zeros(capacity, dtype=jnp.int32)
            ln = jnp.broadcast_to(b.offsets[-1].astype(jnp.int32),
                                  (capacity,))
            lit_max = max(lit_max, int(ch.shape[0]))
        starts.append(st + base)
        lens.append(ln)
        if b.validity is None:
            valids.append(jnp.ones(capacity, dtype=jnp.bool_))
        elif getattr(b.validity, "ndim", 0) == 0:
            valids.append(jnp.broadcast_to(b.validity, (capacity,)))
        elif b.validity.shape[0] == capacity:
            valids.append(b.validity)
        else:
            valids.append(jnp.broadcast_to(b.validity[0], (capacity,)))
        base += int(ch.shape[0])
    out_char_cap += lit_max * capacity
    all_chars = jnp.concatenate(chunks) if chunks else \
        jnp.zeros(0, dtype=jnp.uint8)
    smat = jnp.stack(starts)
    lmat = jnp.stack(lens)
    vmat = jnp.stack(valids)
    row_start = smat[safe, ar]
    validity = jnp.logical_and(chosen, vmat[safe, ar])
    row_len = jnp.where(validity, lmat[safe, ar], 0)
    from spark_rapids_tpu.columnar.column import bucket_capacity
    chars, offsets = build_strings(
        row_len, lambda pos, row, k: row_start[row] + k, all_chars,
        bucket_capacity(out_char_cap, minimum=8), capacity)
    return ColVal(dts.STRING, chars, validity=validity, offsets=offsets)


# ------------------------------------------------------------------- scalars

class Length(UnaryExpression):
    """character length (Spark length())."""

    @property
    def dtype(self):
        return dts.INT32

    def emit(self, ctx: EmitContext) -> ColVal:
        c = self.child.emit(ctx)
        return ColVal(dts.INT32, char_lengths(c, ctx).astype(jnp.int32),
                      c.validity)


class OctetLength(UnaryExpression):
    @property
    def dtype(self):
        return dts.INT32

    def emit(self, ctx: EmitContext) -> ColVal:
        c = self.child.emit(ctx)
        return ColVal(dts.INT32, row_lengths(c).astype(jnp.int32),
                      c.validity)


class _PatternPredicate(Expression):
    """Base for startswith/endswith/contains with a literal pattern."""

    def __init__(self, child: Expression, pattern: str):
        self.children = (child,)
        self.pattern = pattern

    @property
    def child(self):
        return self.children[0]

    def with_children(self, children):
        return type(self)(children[0], self.pattern)

    @property
    def dtype(self):
        return dts.BOOL

    def cache_key(self):
        return (type(self).__name__, self.pattern, self.child.cache_key())


class StartsWith(_PatternPredicate):
    def emit(self, ctx: EmitContext) -> ColVal:
        c = self.child.emit(ctx)
        pat = _literal_bytes(self.pattern)
        lens = row_lengths(c)
        ok = lens >= len(pat)
        ccap = c.values.shape[0]
        for i, b in enumerate(pat):
            idx = jnp.clip(c.offsets[:-1] + i, 0, ccap - 1)
            ok = jnp.logical_and(ok, c.values[idx] == b)
        return ColVal(dts.BOOL, ok, c.validity)


class EndsWith(_PatternPredicate):
    def emit(self, ctx: EmitContext) -> ColVal:
        c = self.child.emit(ctx)
        pat = _literal_bytes(self.pattern)
        lens = row_lengths(c)
        ok = lens >= len(pat)
        ccap = c.values.shape[0]
        base = c.offsets[1:] - len(pat)
        for i, b in enumerate(pat):
            idx = jnp.clip(base + i, 0, ccap - 1)
            ok = jnp.logical_and(ok, c.values[idx] == b)
        return ColVal(dts.BOOL, ok, c.validity)


def _pattern_at(values, pat: np.ndarray):
    """bool per byte position: the pattern's bytes stand from here on,
    rows not regarded.  The buffer moved up a byte at a time (a slice,
    zeros behind the end; a match over the end fits no row) and
    compared: the same bytes as a gather at ``pos + i``, which costs the
    chip 83 ms a 2^23-byte buffer whatever the indices are (PR 37)."""
    m = jnp.ones(values.shape[0], dtype=jnp.bool_)
    for i, b in enumerate(pat):
        moved = values if i == 0 else jnp.concatenate(
            [values[i:], jnp.zeros(i, dtype=values.dtype)])
        m = jnp.logical_and(m, moved == b)
    return m


def _match_starts(c: ColVal, pat: np.ndarray, capacity: int):
    """bool per byte position: pattern matches starting here, within row."""
    pos = jnp.arange(c.values.shape[0], dtype=jnp.int32)
    m = _pattern_at(c.values, pat)
    row = byte_to_row(c, capacity)
    # match must fit inside the row
    fits = pos + len(pat) <= c.offsets[row + 1]
    return jnp.logical_and(m, fits), row


class Contains(_PatternPredicate):
    def emit(self, ctx: EmitContext) -> ColVal:
        c = self.child.emit(ctx)
        pat = _literal_bytes(self.pattern)
        if len(pat) == 0:
            shape = row_lengths(c).shape
            return ColVal(dts.BOOL, jnp.ones(shape, dtype=jnp.bool_),
                          c.validity)
        # a row holds the pattern iff a match starts between its first
        # byte and the last one the pattern still fits behind: matches
        # counted up to each byte, read at the row's two ends.  No byte
        # asks for its row (rows with no bytes never match)
        before = jnp.concatenate([
            jnp.zeros(1, dtype=jnp.int32),
            selection.cumsum_32(_pattern_at(c.values, pat)
                                .astype(jnp.int32))])
        lo = c.offsets[:-1]
        hi = jnp.maximum(c.offsets[1:] - (len(pat) - 1), lo)
        return ColVal(dts.BOOL, before[hi] > before[lo], c.validity)


class Like(_PatternPredicate):
    """SQL LIKE with arbitrary ``%`` wildcards (``_`` falls back via the
    planner).  Single-wildcard forms decompose into prefix/suffix/infix
    tests; multi-wildcard patterns like ``%special%requests%`` run a fully
    data-parallel ordered-infix match: per segment, find the earliest match
    position at-or-after the previous segment's end with a masked
    ``segment_min`` (the device analog of cudf's ``strings::like``)."""

    def __init__(self, child: Expression, pattern: str):
        super().__init__(child, pattern)
        self._plan = self._compile(pattern)

    @staticmethod
    def _compile(p: str):
        if "_" in p:
            return None
        parts = p.split("%")
        if "%" not in p:
            return ("exact", p)
        if set(p) == {"%"}:
            return ("any",)
        inner = [s for s in parts if s]
        if p.startswith("%") and p.endswith("%") and len(inner) == 1:
            return ("contains", inner[0])
        if p.endswith("%") and not p.startswith("%") and len(inner) == 1:
            return ("prefix", inner[0])
        if p.startswith("%") and not p.endswith("%") and len(inner) == 1:
            return ("suffix", inner[0])
        if not p.startswith("%") and not p.endswith("%") and \
                len(inner) == 2 and len(parts) == 2:
            return ("prefix_suffix", inner[0], inner[1])
        # general: ordered segments, optionally anchored at either end
        return ("general", not p.startswith("%"), not p.endswith("%"),
                tuple(inner))

    @property
    def supported(self) -> bool:
        return self._plan is not None

    def emit(self, ctx: EmitContext) -> ColVal:
        plan = self._plan
        if plan is None:
            raise NotImplementedError(f"LIKE pattern {self.pattern!r}")
        kind = plan[0]
        if kind == "any":
            c = self.child.emit(ctx)
            return ColVal(dts.BOOL,
                          jnp.ones(ctx.capacity, dtype=jnp.bool_),
                          c.validity)
        if kind == "exact":
            return EqualsLiteral(self.child, plan[1]).emit(ctx)
        if kind == "contains":
            return Contains(self.child, plan[1]).emit(ctx)
        if kind == "prefix":
            return StartsWith(self.child, plan[1]).emit(ctx)
        if kind == "suffix":
            return EndsWith(self.child, plan[1]).emit(ctx)
        if kind == "prefix_suffix":
            # both, non-overlapping
            c = self.child.emit(ctx)
            pre = StartsWith(self.child, plan[1]).emit(ctx)
            suf = EndsWith(self.child, plan[2]).emit(ctx)
            long_enough = row_lengths(c) >= (len(_literal_bytes(plan[1])) +
                                             len(_literal_bytes(plan[2])))
            return ColVal(dts.BOOL,
                          pre.values & suf.values & long_enough, c.validity)
        # general: ordered infix chain with optional anchors
        _, anchor_start, anchor_end, segments = plan
        c = self.child.emit(ctx)
        ccap = c.values.shape[0]
        starts = c.offsets[:-1]
        ends = c.offsets[1:]
        INF = jnp.int32(2**30)
        ok = jnp.ones(ctx.capacity, dtype=jnp.bool_)
        # cur[row] = earliest byte position the next segment may start at
        cur = starts.astype(jnp.int32)
        segs = list(segments)
        if anchor_start and segs:
            pre = StartsWith(self.child, segs[0]).emit(ctx)
            ok = jnp.logical_and(ok, pre.values)
            cur = cur + len(_literal_bytes(segs[0]))
            segs = segs[1:]
        last = None
        if anchor_end and segs:
            last = segs[-1]
            segs = segs[:-1]
        for seg in segs:
            pat = _literal_bytes(seg)
            m, row = _match_starts(c, pat, ctx.capacity)
            pos = jnp.arange(ccap, dtype=jnp.int32)
            eligible = jnp.logical_and(m, pos >= cur[row])
            first = jax.ops.segment_min(
                jnp.where(eligible, pos, INF), row,
                num_segments=ctx.capacity)
            ok = jnp.logical_and(ok, first < INF)
            cur = jnp.where(first < INF, first + len(pat), cur)
        if last is not None:
            pat = _literal_bytes(last)
            suf = EndsWith(self.child, last).emit(ctx)
            ok = jnp.logical_and(ok, suf.values)
            ok = jnp.logical_and(ok,
                                 ends.astype(jnp.int32) - len(pat) >= cur)
        return ColVal(dts.BOOL, ok, c.validity)


class EqualsLiteral(_PatternPredicate):
    def emit(self, ctx: EmitContext) -> ColVal:
        c = self.child.emit(ctx)
        pat = _literal_bytes(self.pattern)
        ok = row_lengths(c) == len(pat)
        ccap = c.values.shape[0]
        for i, b in enumerate(pat):
            idx = jnp.clip(c.offsets[:-1] + i, 0, ccap - 1)
            ok = jnp.logical_and(ok, c.values[idx] == b)
        return ColVal(dts.BOOL, ok, c.validity)


class StringLocate(Expression):
    """locate(substr, str[, start]) — 1-based char position, 0 if absent."""

    def __init__(self, substr: str, child: Expression, start: int = 1):
        self.children = (child,)
        self.substr = substr
        self.start = start

    @property
    def child(self):
        return self.children[0]

    def with_children(self, children):
        return StringLocate(self.substr, children[0], self.start)

    @property
    def dtype(self):
        return dts.INT32

    def emit(self, ctx: EmitContext) -> ColVal:
        c = self.child.emit(ctx)
        pat = _literal_bytes(self.substr)
        if len(pat) == 0:
            return ColVal(dts.INT32,
                          jnp.full(ctx.capacity, self.start, jnp.int32),
                          c.validity)
        m, row = _match_starts(c, pat, ctx.capacity)
        ccap = c.values.shape[0]
        pos = jnp.arange(ccap, dtype=jnp.int32)
        # char index of each byte within its row
        is_start = (c.values & 0xC0) != 0x80
        cprefix = jnp.cumsum(is_start.astype(jnp.int32))
        char_in_row = cprefix - cprefix[jnp.clip(c.offsets[row], 0,
                                                 ccap - 1)] + \
            is_start[jnp.clip(c.offsets[row], 0, ccap - 1)].astype(jnp.int32)
        eligible = jnp.logical_and(m, char_in_row >= self.start)
        first = jax.ops.segment_min(
            jnp.where(eligible, char_in_row, jnp.int32(2**31 - 1)), row,
            num_segments=ctx.capacity)
        out = jnp.where(first == 2**31 - 1, 0, first)
        return ColVal(dts.INT32, out, c.validity)

    def cache_key(self):
        return ("StringLocate", self.substr, self.start,
                self.child.cache_key())


# ----------------------------------------------------------------- producers

class _StringProducer(Expression):
    """Base for expressions producing a string column: subclasses provide
    output lengths + a source-byte mapping."""

    @property
    def dtype(self):
        return dts.STRING


class Upper(UnaryExpression):
    @property
    def dtype(self):
        return dts.STRING

    def emit(self, ctx: EmitContext) -> ColVal:
        c = self.child.emit(ctx)
        v = c.values
        out = jnp.where((v >= 97) & (v <= 122), v - 32, v)
        return ColVal(dts.STRING, out, c.validity, c.offsets)


class Lower(UnaryExpression):
    @property
    def dtype(self):
        return dts.STRING

    def emit(self, ctx: EmitContext) -> ColVal:
        c = self.child.emit(ctx)
        v = c.values
        out = jnp.where((v >= 65) & (v <= 90), v + 32, v)
        return ColVal(dts.STRING, out, c.validity, c.offsets)


class InitCap(UnaryExpression):
    """Capitalize first letter of each space-separated word (ASCII)."""

    @property
    def dtype(self):
        return dts.STRING

    def emit(self, ctx: EmitContext) -> ColVal:
        c = self.child.emit(ctx)
        v = c.values
        prev = jnp.roll(v, 1)
        row = byte_to_row(c, ctx.capacity)
        at_row_start = jnp.arange(v.shape[0], dtype=jnp.int32) == \
            c.offsets[row]
        word_start = jnp.logical_or(at_row_start, prev == 32)
        up = jnp.where((v >= 97) & (v <= 122) & word_start, v - 32, v)
        lo = jnp.where((v >= 65) & (v <= 90) & ~word_start, v + 32, up)
        out = jnp.where(word_start, up, lo)
        return ColVal(dts.STRING, out, c.validity, c.offsets)


class Substring(Expression):
    """substring(str, pos, len) — 1-based char position (Spark semantics:
    pos 0 behaves like 1, negative counts from the end)."""

    def __init__(self, child: Expression, pos: int, length: int = 2**31 - 1):
        self.children = (child,)
        self.pos = pos
        self.length = length

    @property
    def child(self):
        return self.children[0]

    def with_children(self, children):
        return Substring(children[0], self.pos, self.length)

    @property
    def dtype(self):
        return dts.STRING

    def emit(self, ctx: EmitContext) -> ColVal:
        c = self.child.emit(ctx)
        nchars = char_lengths(c, ctx)
        pos = self.pos
        if pos >= 0:
            start_char = jnp.maximum(pos - 1, 0)
        else:
            start_char = jnp.maximum(nchars + pos, 0)
        end_char = jnp.minimum(
            start_char.astype(jnp.int64) + self.length,
            nchars.astype(jnp.int64)).astype(jnp.int32)
        start_char = jnp.minimum(start_char, nchars)
        # char index -> byte offset per row: global positions of char starts
        is_start = ((c.values & 0xC0) != 0x80).astype(jnp.int32)
        cprefix = jnp.concatenate(
            [jnp.zeros(1, dtype=jnp.int32), jnp.cumsum(is_start)])
        # for row r: byte pos of its k-th char = index of (cprefix[o_r]+k)-th
        # char start; find via searchsorted over cprefix (monotone)
        base_chars = cprefix[c.offsets[:-1]]
        start_byte = jnp.searchsorted(
            cprefix[1:], base_chars + start_char + 1, side="left"
        ).astype(jnp.int32)
        end_byte = jnp.searchsorted(
            cprefix[1:], base_chars + end_char + 1, side="left"
        ).astype(jnp.int32)
        start_byte = jnp.clip(start_byte, c.offsets[:-1], c.offsets[1:])
        end_byte = jnp.clip(end_byte, start_byte, c.offsets[1:])
        lengths = end_byte - start_byte
        chars, offsets = build_strings(
            lengths, lambda p, r, k: start_byte[r] + k, c.values,
            c.values.shape[0], ctx.capacity)
        return ColVal(dts.STRING, chars, c.validity, offsets)

    def cache_key(self):
        return ("Substring", self.pos, self.length, self.child.cache_key())


class _TrimBase(UnaryExpression):
    @property
    def dtype(self):
        return dts.STRING

    trim_left = True
    trim_right = True

    def emit(self, ctx: EmitContext) -> ColVal:
        c = self.child.emit(ctx)
        ccap = c.values.shape[0]
        pos = jnp.arange(ccap, dtype=jnp.int32)
        row = byte_to_row(c, ctx.capacity)
        space = c.values == 32
        big = jnp.int32(2**31 - 1)
        if self.trim_left:
            first_ns = jax.ops.segment_min(
                jnp.where(~space, pos, big), row,
                num_segments=ctx.capacity)
            start = jnp.minimum(
                jnp.where(first_ns == big, c.offsets[1:], first_ns),
                c.offsets[1:])
            start = jnp.maximum(start, c.offsets[:-1])
        else:
            start = c.offsets[:-1]
        if self.trim_right:
            last_ns = jax.ops.segment_max(
                jnp.where(~space, pos, -1), row, num_segments=ctx.capacity)
            end = jnp.where(last_ns < c.offsets[:-1], start, last_ns + 1)
            end = jnp.clip(end, start, c.offsets[1:])
        else:
            end = c.offsets[1:]
        lengths = end - start
        chars, offsets = build_strings(
            lengths, lambda p, r, k: start[r] + k, c.values, ccap,
            ctx.capacity)
        return ColVal(dts.STRING, chars, c.validity, offsets)


class StringTrim(_TrimBase):
    pass


class StringTrimLeft(_TrimBase):
    trim_right = False


class StringTrimRight(_TrimBase):
    trim_left = False


class ConcatStrings(Expression):
    """concat(s1, s2, ...) — null if any input is null (Spark concat)."""

    def __init__(self, *children: Expression):
        self.children = tuple(children)

    def with_children(self, children):
        return ConcatStrings(*children)

    @property
    def dtype(self):
        return dts.STRING

    def emit(self, ctx: EmitContext) -> ColVal:
        cols = [_as_string_col(c.emit(ctx), ctx) for c in self.children]
        lens = [row_lengths(c) for c in cols]
        total = lens[0]
        for l in lens[1:]:
            total = total + l
        # cumulative start of each part within the output row
        part_starts = [jnp.zeros_like(total)]
        for l in lens[:-1]:
            part_starts.append(part_starts[-1] + l)
        out_cap = _next_pow2(sum(int(c.values.shape[0]) for c in cols))

        def src(p, r, k):
            # select which part byte k falls into
            src_idx = jnp.zeros_like(p)
            for part, (c, ps, l) in enumerate(zip(cols, part_starts, lens)):
                inside = jnp.logical_and(k >= ps[r], k < ps[r] + l[r])
                byte = c.offsets[r] + (k - ps[r])
                # offset into the concatenated source pool
                src_idx = jnp.where(inside, byte + self._pool_base[part],
                                    src_idx)
            return src_idx

        self._pool_base = []
        base = 0
        pool_parts = []
        for c in cols:
            self._pool_base.append(base)
            base += int(c.values.shape[0])
            pool_parts.append(c.values)
        pool = jnp.concatenate(pool_parts)
        chars, offsets = build_strings(total, src, pool, out_cap,
                                       ctx.capacity)
        validity = combine_validity(*[c.validity for c in cols])
        return ColVal(dts.STRING, chars, validity, offsets)


class StringRepeat(Expression):
    def __init__(self, child: Expression, times: int):
        self.children = (child,)
        self.times = max(int(times), 0)

    @property
    def child(self):
        return self.children[0]

    def with_children(self, children):
        return StringRepeat(children[0], self.times)

    @property
    def dtype(self):
        return dts.STRING

    def emit(self, ctx: EmitContext) -> ColVal:
        c = self.child.emit(ctx)
        lens = row_lengths(c)
        total = lens * self.times
        out_cap = _next_pow2(int(c.values.shape[0]) * max(self.times, 1))
        safe = jnp.maximum(lens, 1)

        def src(p, r, k):
            return c.offsets[r] + (k % safe[r])

        chars, offsets = build_strings(total, src, c.values, out_cap,
                                       ctx.capacity)
        return ColVal(dts.STRING, chars, c.validity, offsets)

    def cache_key(self):
        return ("StringRepeat", self.times, self.child.cache_key())


class _PadBase(Expression):
    def __init__(self, child: Expression, width: int, pad: str = " "):
        self.children = (child,)
        self.width = int(width)
        self.pad = pad or " "

    @property
    def child(self):
        return self.children[0]

    def with_children(self, children):
        return type(self)(children[0], self.width, self.pad)

    @property
    def dtype(self):
        return dts.STRING

    def cache_key(self):
        return (type(self).__name__, self.width, self.pad,
                self.child.cache_key())

    def emit(self, ctx: EmitContext) -> ColVal:
        c = self.child.emit(ctx)
        lens = row_lengths(c)  # ASCII pad assumption: chars == bytes
        width = jnp.int32(self.width)
        pad_bytes = _literal_bytes(self.pad)
        pool = jnp.concatenate([c.values, jnp.asarray(pad_bytes)])
        pad_base = int(c.values.shape[0])
        out_cap = _next_pow2(self.width * ctx.capacity)
        npad = len(pad_bytes)
        left = isinstance(self, StringLPad)

        def src(p, r, k):
            pad_n = jnp.maximum(width - lens[r], 0)
            if left:
                in_pad = k < pad_n
                data_k = k - pad_n
                pad_k = k
            else:
                in_pad = k >= lens[r]
                data_k = k
                pad_k = k - lens[r]
            return jnp.where(in_pad,
                             pad_base + (jnp.clip(pad_k, 0, None) % npad),
                             c.offsets[r] + jnp.clip(data_k, 0, None))

        # Spark pads OR truncates to exactly `width`
        out_len = jnp.broadcast_to(width, lens.shape)
        chars, offsets = build_strings(out_len, src, pool, out_cap,
                                       ctx.capacity)
        return ColVal(dts.STRING, chars, c.validity, offsets)


class StringLPad(_PadBase):
    pass


class StringRPad(_PadBase):
    pass


class SubstringIndex(Expression):
    """substring_index(str, delim, count) for single-char delim."""

    def __init__(self, child: Expression, delim: str, count: int):
        self.children = (child,)
        self.delim = delim
        self.count = int(count)

    @property
    def child(self):
        return self.children[0]

    def with_children(self, children):
        return SubstringIndex(children[0], self.delim, self.count)

    @property
    def dtype(self):
        return dts.STRING

    def cache_key(self):
        return ("SubstringIndex", self.delim, self.count,
                self.child.cache_key())

    def emit(self, ctx: EmitContext) -> ColVal:
        c = self.child.emit(ctx)
        d = _literal_bytes(self.delim)
        ccap = c.values.shape[0]
        pos = jnp.arange(ccap, dtype=jnp.int32)
        row = byte_to_row(c, ctx.capacity)
        m, _ = _match_starts(c, d, ctx.capacity)
        # delim occurrence index within row
        mcum = jnp.cumsum(m.astype(jnp.int32))
        base = mcum[jnp.clip(c.offsets[row], 0, ccap - 1)] - \
            m[jnp.clip(c.offsets[row], 0, ccap - 1)].astype(jnp.int32)
        occ = mcum - base  # count of delims at-or-before this byte, in row
        total_occ = jax.ops.segment_max(
            jnp.where(m, occ, 0), row, num_segments=ctx.capacity)
        big = jnp.int32(2**31 - 1)
        if self.count > 0:
            # bytes before the count-th delimiter
            nth = jax.ops.segment_min(
                jnp.where(m & (occ == self.count), pos, big), row,
                num_segments=ctx.capacity)
            end = jnp.where(total_occ >= self.count, nth, c.offsets[1:])
            end = jnp.minimum(end, c.offsets[1:])
            start = c.offsets[:-1]
        else:
            # occurrence index (from the left) of the split point, per byte
            want = total_occ[row] + self.count + 1
            nth = jax.ops.segment_min(
                jnp.where(m & (occ == want), pos, big), row,
                num_segments=ctx.capacity)
            start = jnp.where(total_occ >= -self.count,
                              jnp.minimum(nth + len(d), c.offsets[1:]),
                              c.offsets[:-1])
            end = c.offsets[1:]
        lengths = end - start
        chars, offsets = build_strings(
            lengths, lambda p, r, k: start[r] + k, c.values, ccap,
            ctx.capacity)
        return ColVal(dts.STRING, chars, c.validity, offsets)


def _as_string_col(c: ColVal, ctx: EmitContext) -> ColVal:
    if c.dtype.is_string:
        if c.offsets.shape[0] == 2 and ctx.capacity != 1:
            # scalar literal: broadcast to per-row
            length = c.offsets[1]
            offsets = jnp.arange(ctx.capacity + 1, dtype=jnp.int32) * 0
            # every row points at the same literal bytes
            lens = jnp.broadcast_to(length, (ctx.capacity,))
            offs = jnp.concatenate([jnp.zeros(1, dtype=jnp.int32),
                                    jnp.cumsum(lens, dtype=jnp.int32)])
            reps = int(ctx.capacity)
            chars = jnp.tile(c.values, reps)
            return ColVal(dts.STRING, chars, None, offs)
        return c
    raise TypeError(f"expected string, got {c.dtype}")


def _next_pow2(n: int) -> int:
    cap = 1024
    while cap < n:
        cap <<= 1
    return cap


def string_equal(l: ColVal, r: ColVal, ctx: EmitContext):
    """Per-row equality of two string ColVals (either may be a scalar
    literal: offsets of length 2).  Returns a bool values array."""
    l_scalar = l.offsets.shape[0] == 2 and ctx.capacity != 1
    r_scalar = r.offsets.shape[0] == 2 and ctx.capacity != 1
    if l_scalar and not r_scalar:
        return string_equal(r, l, ctx)
    if r_scalar:
        lens_l = row_lengths(l)
        rlen = r.offsets[1]
        ok = lens_l == rlen
        ccap = l.values.shape[0]
        rcap = int(r.values.shape[0])
        # compare byte-by-byte over the literal's (small) length
        for i in range(rcap):
            idx = jnp.clip(l.offsets[:-1] + i, 0, ccap - 1)
            ok = jnp.logical_and(
                ok, jnp.logical_or(i >= rlen, l.values[idx] == r.values[i]))
        return ok
    # column vs column
    lens_l = row_lengths(l)
    lens_r = row_lengths(r)
    same_len = lens_l == lens_r
    ccap = l.values.shape[0]
    pos = jnp.arange(ccap, dtype=jnp.int32)
    row = byte_to_row(l, ctx.capacity)
    k = pos - l.offsets[row]
    r_idx = jnp.clip(r.offsets[row] + k, 0, r.values.shape[0] - 1)
    byte_ok = l.values == r.values[r_idx]
    total = l.offsets[ctx.capacity]
    byte_bad = jnp.logical_and(jnp.logical_not(byte_ok), pos < total)
    any_bad = jax.ops.segment_max(byte_bad.astype(jnp.int32), row,
                                  num_segments=ctx.capacity) > 0
    return jnp.logical_and(same_len, jnp.logical_not(any_bad))


def _string_lex_compare(l: ColVal, r: ColVal, ctx: EmitContext):
    """(has_diff, l_byte_lt, len_lt, len_le): first-differing-byte verdict
    for per-row lexicographic comparison of two string ColVals.

    Single pass over l's char buffer (the byte->row map + segment_min find
    the first position where the rows differ); ties fall to length
    comparison.  UTF-8 byte-wise lex order == code-point order, so this is
    exact Spark string ordering.
    """
    l = _as_string_col(l, ctx)
    r = _as_string_col(r, ctx)
    # An empty-string literal (or all-empty column) has a zero-length char
    # buffer; every gather below would clip to bound -1 and crash.  Pad to
    # one byte — offsets are all zero so the byte is never semantically
    # read (the `within`/has_diff masks exclude it).
    if l.values.shape[0] == 0:
        l = ColVal(l.dtype, jnp.zeros(1, dtype=jnp.uint8), l.validity,
                   l.offsets)
    if r.values.shape[0] == 0:
        r = ColVal(r.dtype, jnp.zeros(1, dtype=jnp.uint8), r.validity,
                   r.offsets)
    cap = ctx.capacity
    len_l = row_lengths(l)
    len_r = row_lengths(r)
    minlen = jnp.minimum(len_l, len_r)
    ccap = l.values.shape[0]
    pos = jnp.arange(ccap, dtype=jnp.int32)
    row = byte_to_row(l, cap)
    k = pos - l.offsets[row]
    r_idx = jnp.clip(r.offsets[row] + k, 0, r.values.shape[0] - 1)
    within = jnp.logical_and(k < minlen[row], pos < l.offsets[cap])
    differ = jnp.logical_and(within, l.values != r.values[r_idx])
    big = jnp.int32(1 << 30)
    first_k = jax.ops.segment_min(jnp.where(differ, k, big), row,
                                  num_segments=cap)
    has_diff = first_k < big
    safe_k = jnp.where(has_diff, first_k, 0)
    rows = jnp.arange(cap, dtype=jnp.int32)
    lb = l.values[jnp.clip(l.offsets[rows] + safe_k, 0, ccap - 1)]
    rb = r.values[jnp.clip(r.offsets[rows] + safe_k, 0,
                           r.values.shape[0] - 1)]
    return has_diff, lb < rb, len_l < len_r, len_l <= len_r


def string_lt(l: ColVal, r: ColVal, ctx: EmitContext):
    has_diff, byte_lt, len_lt, _ = _string_lex_compare(l, r, ctx)
    return jnp.where(has_diff, byte_lt, len_lt)


def string_le(l: ColVal, r: ColVal, ctx: EmitContext):
    has_diff, byte_lt, _, len_le = _string_lex_compare(l, r, ctx)
    return jnp.where(has_diff, byte_lt, len_le)


def string_gt(l: ColVal, r: ColVal, ctx: EmitContext):
    return jnp.logical_not(string_le(l, r, ctx))


def string_ge(l: ColVal, r: ColVal, ctx: EmitContext):
    return jnp.logical_not(string_lt(l, r, ctx))


# -------------------------------------------------------------------- casts

def cast_string(c: ColVal, target: DataType, ctx: EmitContext) -> ColVal:
    if c.dtype.is_string and (target.is_integral or target.is_floating):
        return _parse_number(c, target, ctx)
    if c.dtype.is_string and target.is_date:
        return _parse_date(c, ctx)
    if c.dtype.is_string and target.is_timestamp:
        return _parse_timestamp(c, ctx)
    if c.dtype.is_string and target.is_boolean:
        return _parse_bool(c, ctx)
    if (c.dtype.is_integral or c.dtype.is_boolean) and target.is_string:
        return _format_int(c, ctx)
    if c.dtype.is_date and target.is_string:
        return _format_date(c, ctx)
    if c.dtype.is_timestamp and target.is_string:
        return _format_timestamp(c, ctx)
    raise NotImplementedError(
        f"cast {c.dtype} -> {target} not yet supported on TPU")


_MAX_NUM_BYTES = 24


def _row_window(c: ColVal, width: int, ctx: EmitContext):
    """[capacity, width] matrix of each row's first bytes (0 padded)."""
    ccap = c.values.shape[0]
    starts = c.offsets[:-1]
    lens = row_lengths(c)
    j = jnp.arange(width, dtype=jnp.int32)[None, :]
    idx = jnp.clip(starts[:, None] + j, 0, ccap - 1)
    window = c.values[idx]
    return jnp.where(j < lens[:, None], window, 0), lens


def _parse_number(c: ColVal, target: DataType, ctx: EmitContext) -> ColVal:
    win, lens = _row_window(c, _MAX_NUM_BYTES, ctx)
    j = jnp.arange(_MAX_NUM_BYTES, dtype=jnp.int32)[None, :]
    in_row = j < lens[:, None]
    neg = win[:, 0] == ord("-")
    plus = win[:, 0] == ord("+")
    signed = neg | plus
    digit = (win >= ord("0")) & (win <= ord("9"))
    dot = win == ord(".")
    start = signed.astype(jnp.int32)

    is_int_char = digit | ~in_row
    int_ok = jnp.all(is_int_char | (j < start[:, None]) |
                     (j >= lens[:, None]), axis=1)
    # integer value via Horner over the window
    val = jnp.zeros(win.shape[0], dtype=jnp.int64)
    frac = jnp.zeros(win.shape[0], dtype=jnp.float64)
    scale = jnp.zeros(win.shape[0], dtype=jnp.float64)
    seen_dot = jnp.zeros(win.shape[0], dtype=jnp.bool_)
    fdigits = jnp.zeros(win.shape[0], dtype=jnp.float64)
    has_digit = jnp.zeros(win.shape[0], dtype=jnp.bool_)
    ok = lens > 0
    for k in range(_MAX_NUM_BYTES):
        ch = win[:, k]
        active = (k >= start) & (k < lens)
        d = (ch - ord("0")).astype(jnp.int64)
        isd = digit[:, k]
        this_dot = dot[:, k] & ~seen_dot
        val = jnp.where(active & isd & ~seen_dot, val * 10 + d, val)
        has_digit = has_digit | (active & isd)
        fdigits = jnp.where(active & isd & seen_dot,
                            fdigits * 10 + d.astype(jnp.float64), fdigits)
        scale = jnp.where(active & isd & seen_dot, scale + 1, scale)
        seen_dot = seen_dot | (active & dot[:, k])
        bad = active & ~isd & ~this_dot
        ok = ok & ~bad
    ok = ok & (lens <= _MAX_NUM_BYTES) & (lens > start) & has_digit
    fval = val.astype(jnp.float64) + fdigits / jnp.power(10.0, scale)
    fval = jnp.where(neg, -fval, fval)
    ival = jnp.where(neg, -val, val)
    validity = combine_validity(c.validity, ok)
    if target.is_floating:
        return ColVal(target, fval.astype(target.storage), validity)
    int_valid = combine_validity(validity, ~seen_dot)
    return ColVal(target, ival.astype(target.storage), int_valid)


def _parse_date(c: ColVal, ctx: EmitContext) -> ColVal:
    """yyyy-MM-dd (the default Spark date cast format)."""
    from spark_rapids_tpu.ops.datetime_ops import _days_from_civil
    win, lens = _row_window(c, 10, ctx)
    digits = (win - ord("0")).astype(jnp.int32)

    def num(sl):
        out = jnp.zeros(win.shape[0], dtype=jnp.int32)
        for i in sl:
            out = out * 10 + digits[:, i]
        return out
    ok = (lens == 10) & (win[:, 4] == ord("-")) & (win[:, 7] == ord("-"))
    for i in (0, 1, 2, 3, 5, 6, 8, 9):
        ok = ok & (win[:, i] >= ord("0")) & (win[:, i] <= ord("9"))
    y = num((0, 1, 2, 3)).astype(jnp.int64)
    m_raw = num((5, 6)).astype(jnp.int64)
    d_raw = num((8, 9)).astype(jnp.int64)
    m = jnp.clip(m_raw, 1, 12)
    month_days = _days_from_civil(
        jnp.where(m == 12, y + 1, y), jnp.where(m == 12, 1, m + 1),
        jnp.ones_like(m)) - _days_from_civil(y, m, jnp.ones_like(m))
    ok = ok & (m_raw >= 1) & (m_raw <= 12) & (d_raw >= 1) & \
        (d_raw <= month_days)
    days = _days_from_civil(y, m, jnp.clip(d_raw, 1, 31)).astype(jnp.int32)
    return ColVal(dts.DATE32, days, combine_validity(c.validity, ok))


def _parse_timestamp(c: ColVal, ctx: EmitContext) -> ColVal:
    """'yyyy-MM-dd[ HH:mm:ss[.SSSSSS]]' -> micros since epoch UTC (the
    default-format quadrant of GpuCast.scala's string->timestamp rules;
    zone suffixes are not accepted — the engine is UTC-only)."""
    from spark_rapids_tpu.ops.datetime_ops import _days_from_civil
    width = 26
    win, lens = _row_window(c, width, ctx)
    digits = (win - ord("0")).astype(jnp.int64)
    isd = (win >= ord("0")) & (win <= ord("9"))

    def num(sl):
        out = jnp.zeros(win.shape[0], dtype=jnp.int64)
        for i in sl:
            out = out * 10 + digits[:, i]
        return out

    date_ok = (lens >= 10) & (win[:, 4] == ord("-")) & \
        (win[:, 7] == ord("-"))
    for i in (0, 1, 2, 3, 5, 6, 8, 9):
        date_ok = date_ok & isd[:, i]
    y, m, d = num((0, 1, 2, 3)), num((5, 6)), num((8, 9))
    mc = jnp.clip(m, 1, 12)
    # real month length: civil-day difference to the next month
    month_days = _days_from_civil(
        jnp.where(mc == 12, y + 1, y), jnp.where(mc == 12, 1, mc + 1),
        jnp.ones_like(mc)) - _days_from_civil(y, mc, jnp.ones_like(mc))
    date_ok = date_ok & (m >= 1) & (m <= 12) & (d >= 1) & (d <= month_days)
    days = _days_from_civil(y, mc, jnp.clip(d, 1, 31))

    has_time = lens >= 19
    time_ok = (win[:, 10] == ord(" ")) | (win[:, 10] == ord("T"))
    time_ok = time_ok & (win[:, 13] == ord(":")) & (win[:, 16] == ord(":"))
    for i in (11, 12, 14, 15, 17, 18):
        time_ok = time_ok & isd[:, i]
    hh, mi, ss = num((11, 12)), num((14, 15)), num((17, 18))
    secs = jnp.clip(hh, 0, 23) * 3600 + jnp.clip(mi, 0, 59) * 60 + \
        jnp.clip(ss, 0, 59)
    time_ok = time_ok & (hh <= 23) & (mi <= 59) & (ss <= 59)

    # optional .fraction (1-6 digits)
    has_frac = lens >= 21
    frac_ok = win[:, 19] == ord(".")
    frac = jnp.zeros(win.shape[0], dtype=jnp.int64)
    fdig = jnp.zeros(win.shape[0], dtype=jnp.int64)
    for i in range(20, 26):
        in_frac = (i < lens) & isd[:, i]
        frac = jnp.where(in_frac, frac * 10 + digits[:, i], frac)
        fdig = fdig + in_frac.astype(jnp.int64)
        frac_ok = frac_ok & ((i >= lens) | isd[:, i])
    # frac has fdig digits; scale to micros: frac * 10^(6-fdig)
    micros_frac = frac * (10 ** 6) // jnp.asarray(
        [1, 10, 100, 1000, 10 ** 4, 10 ** 5, 10 ** 6],
        dtype=jnp.int64)[jnp.clip(fdig, 0, 6)]

    ok = date_ok & (
        (lens == 10) |
        ((lens == 19) & time_ok) |
        ((lens >= 21) & (lens <= 26) & time_ok & frac_ok))
    micros = days * 86_400_000_000 + \
        jnp.where(has_time, secs * 1_000_000, 0) + \
        jnp.where(has_frac, micros_frac, 0)
    return ColVal(dts.TIMESTAMP_US, micros,
                  combine_validity(c.validity, ok))


_BOOL_TRUE = ("true", "t", "yes", "y", "1")
_BOOL_FALSE = ("false", "f", "no", "n", "0")


def _parse_bool(c: ColVal, ctx: EmitContext) -> ColVal:
    """Spark string->boolean: true/t/yes/y/1 and false/f/no/n/0
    (case-insensitive, whitespace-trimmed like UTF8String.trim);
    anything else is null."""
    width = 16
    win, lens = _row_window(c, width, ctx)
    ws = win <= 0x20
    in_row = jnp.arange(width, dtype=jnp.int32)[None, :] < lens[:, None]
    # leading whitespace count + trimmed length
    lead = jnp.zeros(win.shape[0], dtype=jnp.int32)
    still = jnp.ones(win.shape[0], dtype=jnp.bool_)
    for i in range(width):
        hit = still & ws[:, i] & in_row[:, i]
        lead = lead + hit.astype(jnp.int32)
        still = hit
    trail = jnp.zeros(win.shape[0], dtype=jnp.int32)
    for i in range(width):
        j = jnp.clip(lens - 1 - i, 0, width - 1)
        hit = (trail == i) & (win[jnp.arange(win.shape[0]), j] <= 0x20) & \
            (lens - i > lead)
        trail = trail + hit.astype(jnp.int32)
    tlen = jnp.maximum(lens - lead - trail, 0)
    rows = jnp.arange(win.shape[0])
    lower = jnp.where((win >= ord("A")) & (win <= ord("Z")), win + 32, win)

    def matches(word: str):
        ok = (tlen == len(word)) & (lens <= width)
        for i, ch in enumerate(word):
            ok = ok & (lower[rows, jnp.clip(lead + i, 0, width - 1)] ==
                       ord(ch))
        return ok

    is_true = jnp.zeros(win.shape[0], dtype=jnp.bool_)
    for w in _BOOL_TRUE:
        is_true = is_true | matches(w)
    is_false = jnp.zeros(win.shape[0], dtype=jnp.bool_)
    for w in _BOOL_FALSE:
        is_false = is_false | matches(w)
    ok = is_true | is_false
    return ColVal(dts.BOOL, is_true, combine_validity(c.validity, ok))


def _format_timestamp(c: ColVal, ctx: EmitContext) -> ColVal:
    """micros -> 'yyyy-MM-dd HH:mm:ss[.ffffff]' with trailing fraction
    zeros trimmed (Spark's cast timestamp->string)."""
    from spark_rapids_tpu.ops.datetime_ops import _civil_from_days
    v = c.values.astype(jnp.int64)
    days = jnp.floor_divide(v, 86_400_000_000)
    in_day = v - days * 86_400_000_000
    secs = in_day // 1_000_000
    micros = in_day - secs * 1_000_000
    y, m, d = _civil_from_days(days)
    hh = secs // 3600
    mi = (secs // 60) % 60
    ss = secs % 60

    # fraction length: 0 (none) or 1-6 digits with trailing zeros cut
    fdig = jnp.zeros(v.shape[0], dtype=jnp.int32)
    for k in range(6, 0, -1):
        # number of digits needed so micros % 10^(6-k) == 0
        fdig = jnp.where((micros % (10 ** (6 - k + 1))) != 0,
                         jnp.maximum(fdig, k), fdig)
    lens = jnp.where(micros > 0, 20 + fdig, 19).astype(jnp.int32)

    def digit_at(p, r, k):
        # returns the BYTE for output position k of row r
        yy = y[r]
        out = jnp.zeros_like(p)

        def dig(val, power):
            return (val // power) % 10 + ord("0")

        out = jnp.where(k == 0, dig(yy, 1000), out)
        out = jnp.where(k == 1, dig(yy, 100), out)
        out = jnp.where(k == 2, dig(yy, 10), out)
        out = jnp.where(k == 3, dig(yy, 1), out)
        out = jnp.where(k == 4, ord("-"), out)
        out = jnp.where(k == 5, dig(m[r], 10), out)
        out = jnp.where(k == 6, dig(m[r], 1), out)
        out = jnp.where(k == 7, ord("-"), out)
        out = jnp.where(k == 8, dig(d[r], 10), out)
        out = jnp.where(k == 9, dig(d[r], 1), out)
        out = jnp.where(k == 10, ord(" "), out)
        out = jnp.where(k == 11, dig(hh[r], 10), out)
        out = jnp.where(k == 12, dig(hh[r], 1), out)
        out = jnp.where(k == 13, ord(":"), out)
        out = jnp.where(k == 14, dig(mi[r], 10), out)
        out = jnp.where(k == 15, dig(mi[r], 1), out)
        out = jnp.where(k == 16, ord(":"), out)
        out = jnp.where(k == 17, dig(ss[r], 10), out)
        out = jnp.where(k == 18, dig(ss[r], 1), out)
        out = jnp.where(k == 19, ord("."), out)
        frac_pos = k - 20  # 0-based fraction digit index
        fr = micros[r]
        for i in range(6):
            out = jnp.where(frac_pos == i,
                            dig(fr, 10 ** (5 - i)), out)
        return out

    # build via a byte pool trick: we need computed bytes, not copied
    # bytes, so build offsets/chars directly
    offsets = jnp.concatenate([jnp.zeros(1, dtype=jnp.int32),
                               jnp.cumsum(lens, dtype=jnp.int32)])
    out_cap = _next_pow2(26 * ctx.capacity)
    pos = jnp.arange(out_cap, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(offsets, pos, side="right") - 1,
                   0, ctx.capacity - 1)
    k = pos - offsets[row]
    total = offsets[ctx.capacity]
    chars = jnp.where(pos < total, digit_at(pos, row, k),
                      0).astype(jnp.uint8)
    return ColVal(dts.STRING, chars, c.validity, offsets)


def _format_int(c: ColVal, ctx: EmitContext) -> ColVal:
    v = c.values.astype(jnp.int64)
    if c.dtype.is_boolean:
        # 'true'/'false'
        lens = jnp.where(c.values, 4, 5).astype(jnp.int32)
        pool = jnp.asarray(_literal_bytes("truefalse"))

        def src(p, r, k):
            return jnp.where(c.values[r], k, 4 + k)
        chars, offsets = build_strings(lens, src, pool,
                                       _next_pow2(5 * ctx.capacity),
                                       ctx.capacity)
        return ColVal(dts.STRING, chars, c.validity, offsets)
    neg = v < 0
    mag = jnp.where(neg, -v, v).astype(jnp.uint64)
    # digit count
    ndig = jnp.ones(v.shape[0], dtype=jnp.int32)
    p = jnp.full(v.shape[0], 10, dtype=jnp.uint64)
    for _ in range(19):
        ndig = jnp.where(mag >= p, ndig + 1, ndig)
        p = p * 10
    lens = ndig + neg.astype(jnp.int32)
    # digit matrix [cap, 20]: digit at output position k
    digmat = jnp.zeros((v.shape[0], 21), dtype=jnp.uint8)
    mags = mag
    # compute digits right-to-left into a [cap,20] then index by position
    digs = []
    for _ in range(20):
        digs.append((mags % 10).astype(jnp.uint8))
        mags = mags // 10
    digs = jnp.stack(digs, axis=1)  # [cap, 20] least-significant first

    pool_minus = ord("-")

    def src(pz, r, k):
        # k-th output byte of row r
        is_minus = neg[r] & (k == 0)
        pos_in_num = k - neg[r].astype(jnp.int32)
        digit_idx = ndig[r] - 1 - pos_in_num
        dval = digs[r, jnp.clip(digit_idx, 0, 19)]
        return jnp.where(is_minus, 10, dval).astype(jnp.int32)

    # src returns an index into pool '0123456789-'
    pool = jnp.asarray(_literal_bytes("0123456789-"))
    chars, offsets = build_strings(lens, src, pool,
                                   _next_pow2(21 * ctx.capacity),
                                   ctx.capacity)
    return ColVal(dts.STRING, chars, c.validity, offsets)


def _format_date(c: ColVal, ctx: EmitContext) -> ColVal:
    from spark_rapids_tpu.ops.datetime_ops import _civil_from_days
    y, m, d = _civil_from_days(c.values)
    digits = jnp.stack([
        (y // 1000) % 10, (y // 100) % 10, (y // 10) % 10, y % 10,
        jnp.full_like(y, 10),
        (m // 10) % 10, m % 10,
        jnp.full_like(y, 10),
        (d // 10) % 10, d % 10,
    ], axis=1).astype(jnp.int32)  # [cap, 10]; 10 = '-'
    lens = jnp.full(c.values.shape[0], 10, dtype=jnp.int32)
    pool = jnp.asarray(_literal_bytes("0123456789-"))

    def src(p, r, k):
        return digits[r, jnp.clip(k, 0, 9)]

    chars, offsets = build_strings(lens, src, pool,
                                   _next_pow2(10 * ctx.capacity),
                                   ctx.capacity)
    return ColVal(dts.STRING, chars, c.validity, offsets)


class Ascii(UnaryExpression):
    """Code point of the first character (Spark ascii(); full UTF-8
    decode of the leading character, 0 for the empty string —
    stringFunctions.scala GpuAscii role)."""

    @property
    def dtype(self):
        return dts.INT32

    def emit(self, ctx: EmitContext) -> ColVal:
        c = self.child.emit(ctx)
        cap = ctx.capacity
        nbytes = int(c.values.shape[0])
        starts = c.offsets[:cap]
        lens = c.offsets[1:cap + 1] - starts
        if nbytes == 0:
            return ColVal(dts.INT32,
                          jnp.zeros(cap, dtype=jnp.int32), c.validity)

        def byte(k):
            return c.values[jnp.clip(starts + k, 0, nbytes - 1)] \
                .astype(jnp.int32)

        b0 = byte(0)
        cp = jnp.where(
            b0 < 0x80, b0,
            jnp.where(
                b0 < 0xE0,
                ((b0 & 0x1F) << 6) | (byte(1) & 0x3F),
                jnp.where(
                    b0 < 0xF0,
                    ((b0 & 0x0F) << 12) | ((byte(1) & 0x3F) << 6)
                    | (byte(2) & 0x3F),
                    ((b0 & 0x07) << 18) | ((byte(1) & 0x3F) << 12)
                    | ((byte(2) & 0x3F) << 6) | (byte(3) & 0x3F))))
        cp = jnp.where(lens > 0, cp, 0)
        return ColVal(dts.INT32, cp, c.validity)


class Chr(UnaryExpression):
    """Character for a code point modulo 256 (Spark chr(): negative
    input yields the empty string; 128-255 encode as 2-byte UTF-8)."""

    @property
    def dtype(self):
        return dts.STRING

    def emit(self, ctx: EmitContext) -> ColVal:
        c = self.child.emit(ctx)
        cap = ctx.capacity
        n = c.values.astype(jnp.int64)
        b = jnp.mod(n, 256).astype(jnp.int32)
        lens = jnp.where(n < 0, 0, jnp.where(b < 128, 1, 2))
        lens = jnp.where(ctx.row_mask(), lens, 0).astype(jnp.int32)
        first = jnp.where(b < 128, b, 0xC0 | (b >> 6)).astype(jnp.uint8)
        second = (0x80 | (b & 0x3F)).astype(jnp.uint8)
        pool = jnp.stack([first, second], axis=1).reshape(-1)
        chars, offsets = build_strings(
            lens, lambda pos, row, k: row * 2 + k, pool,
            _next_pow2(2 * cap), cap)
        return ColVal(dts.STRING, chars, c.validity, offsets)
