"""Join physical operators.

Counterpart of the reference's join family (GpuShuffledHashJoinBase,
GpuBroadcastHashJoinExec, GpuHashJoin trait with null-key filtering +
JoinGatherer chunked materialization — SURVEY.md section 2.4 "Joins").
One exec covers the single-process path: build side collected and
concatenated on device, probe side streamed, with the combined-sort kernel
from ops/joins.py.  Join types: inner, left, right, full, semi (left semi),
anti (left anti), cross.
"""

from __future__ import annotations

import threading
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as dts
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, bucket_capacity
from spark_rapids_tpu.exec.base import JOIN_TIME, Schema, TpuExec
from spark_rapids_tpu.ops import joins as J
from spark_rapids_tpu.ops import selection
from spark_rapids_tpu.ops.compiler import StageFn
from spark_rapids_tpu.ops.concat import concat_batches
from spark_rapids_tpu.ops.expressions import ColVal, Expression


class JoinMetrics:
    """Rows through ``TpuHashJoinExec``'s probe loop, known on the host
    where the join already has them (a probe batch's row count, the
    output total it sizes its chunks from; semi and anti joins, which
    emit no pairs, count their probe rows only): ``probe_rows`` in,
    ``output_rows`` out, summed over every join of every query.  Their
    ratio is what a reordered or pre-filtered star join would save.
    ``build_rows`` and ``build_capacity``: once a join, when its build
    side has become one batch, that batch's rows and its capacity, which
    ``join_match`` sorts again with every probe batch: what building on
    the smaller side would save.  ``expand_capacity`` and
    ``expand_chunks``: the output slots the pair expansion maps (each
    emitted chunk's bucketed capacity, which sizes
    ``join_gather_indices``' work whatever the rows in it) and the chunks
    emitted; semi and anti joins emit no pairs and bump neither.  Plain
    ints, bumped with tracing on or off."""

    def __init__(self):
        self._lock = threading.Lock()
        self.probe_rows = self.output_rows = 0
        self.build_rows = self.build_capacity = 0
        self.expand_capacity = self.expand_chunks = 0

    def note(self, probe: int, output: int) -> None:
        with self._lock:
            self.probe_rows += probe
            self.output_rows += output

    def note_build(self, rows: int, capacity: int) -> None:
        with self._lock:
            self.build_rows += rows
            self.build_capacity += capacity

    def note_expand(self, capacity: int) -> None:
        with self._lock:
            self.expand_capacity += capacity
            self.expand_chunks += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"probe_rows": self.probe_rows,
                    "output_rows": self.output_rows,
                    "build_rows": self.build_rows,
                    "build_capacity": self.build_capacity,
                    "expand_capacity": self.expand_capacity,
                    "expand_chunks": self.expand_chunks}


join_metrics = JoinMetrics()


def _colvals(columns: Sequence[Column]) -> List[ColVal]:
    return [ColVal(c.dtype, c.data, c.validity, c.offsets) for c in columns]


def _to_colvals(batch: ColumnarBatch) -> List[ColVal]:
    return _colvals(batch.columns.values())


def _null_colval(dt, capacity: int) -> ColVal:
    """An all-null column nothing reads: zeros, no chars."""
    validity = jnp.zeros(capacity, dtype=jnp.bool_)
    if dt.has_offsets:
        return ColVal(dt, jnp.zeros(bucket_capacity(1), dtype=dt.storage),
                      validity, jnp.zeros(capacity + 1, dtype=jnp.int32))
    return ColVal(dt, jnp.zeros(capacity, dtype=dt.storage), validity)


def _to_columns(cols: Sequence[ColVal], nrows: int) -> List[Column]:
    return [Column(c.dtype, c.values, nrows, validity=c.validity,
                   offsets=c.offsets) for c in cols]


class _JoinKeyEncoder:
    """Shared host dictionary for string join keys (codes match across
    sides, so code equality == string equality)."""

    def __init__(self):
        self.codes: Dict[Optional[str], int] = {}
        self._values: List[Optional[str]] = []

    def encode(self, col: Column) -> Column:
        from spark_rapids_tpu.ops.dictionary import dict_encode_stable
        out = dict_encode_stable(col, self.codes, self._values,
                                 null_code=-1)
        validity = None
        hv = col.host_validity()
        if hv is not None:
            validity = hv[:col.nrows]
        return Column.from_numpy(out, dtype=dts.INT64, validity=validity,
                                 capacity=col.capacity)


class TpuHashJoinExec(TpuExec):
    ephemeral_output = True

    def __init__(self, left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression], join_type: str,
                 left: TpuExec, right: TpuExec,
                 using: Optional[List[str]] = None,
                 max_output_rows: int = 1 << 22,
                 live_columns: Optional[set] = None):
        super().__init__(left, right)
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.join_type = join_type
        self.using = using
        self.max_output_rows = max_output_rows
        # the output columns something above reads (None: all of them;
        # plan/overrides._pushdown_pass).  The others are the scans'
        # null placeholders carried along for their ordinals: the pair
        # emission gathers the live ones and makes the rest anew.  A
        # USING join's key coalescing reads both sides: all live.
        self.live_columns = None if using else live_columns
        self._register_metric(JOIN_TIME)
        self._lkey_fn = StageFn(self.left_keys,
                                [dt for _, dt in left.schema])
        self._rkey_fn = StageFn(self.right_keys,
                                [dt for _, dt in right.schema])
        self._encoders = [
            _JoinKeyEncoder() if e.dtype.is_string else None
            for e in self.left_keys]

    # ------------------------------------------------------------------ plan --
    @property
    def left(self) -> TpuExec:
        return self.children[0]

    @property
    def right(self) -> TpuExec:
        return self.children[1]

    @property
    def schema(self) -> Schema:
        lschema, rschema = self.left.schema, self.right.schema
        if self.join_type in ("semi", "anti"):
            return list(lschema)
        if self.using:
            keyset = set(self.using)
            out = [(n, dt) for n, dt in lschema if n in keyset]
            out += [(n, dt) for n, dt in lschema if n not in keyset]
            out += [(n, dt) for n, dt in rschema if n not in keyset]
            return out
        return list(lschema) + list(rschema)

    def describe(self):
        return (f"TpuHashJoinExec[{self.join_type}, "
                f"{[e.name for e in self.left_keys]}]")

    # ------------------------------------------------------------------ exec --
    def _encoded_keys(self, batch: ColumnarBatch, fn: StageFn) -> List[ColVal]:
        cols = fn(batch)
        out = []
        for enc, c in zip(self._encoders, cols):
            if enc is not None:
                c = enc.encode(c)
            out.append(ColVal(c.dtype, c.data, c.validity, c.offsets))
        return out

    def do_execute(self) -> Iterator[ColumnarBatch]:
        if self.join_type == "cross":
            yield from self._execute_cross()
            return
        # build = right side normally (the reference also builds the right,
        # GpuSortMergeJoinMeta -> shuffled hash join); a RIGHT outer join
        # swaps roles so the preserved side streams as the probe.
        self._swap = self.join_type == "right"
        probe_exec, build_exec = (self.right, self.left) if self._swap \
            else (self.left, self.right)
        probe_fn, build_fn = (self._rkey_fn, self._lkey_fn) if self._swap \
            else (self._lkey_fn, self._rkey_fn)
        from spark_rapids_tpu.memory.coalesce import (
            RequireSingleBatch, coalesce_iterator)
        from spark_rapids_tpu.memory.retry import (
            with_retry, with_retry_no_split)
        # build side is a RequireSingleBatch coalesce: pending batches
        # register spillable while accumulating (GpuCoalesceBatches with
        # the single-batch goal feeding GpuShuffledHashJoin's build)
        coalesced = coalesce_iterator(build_exec.execute(),
                                      RequireSingleBatch())
        # the join's single largest device allocation — guard it
        build = with_retry_no_split(lambda: next(coalesced, None))
        if build is None:
            from spark_rapids_tpu.columnar.batch import empty_batch
            build = empty_batch(build_exec.schema, capacity=1)
        join_metrics.note_build(build.nrows, build.capacity)
        build_keys = with_retry_no_split(
            lambda: self._encoded_keys(build, build_fn))
        build_payload = _to_colvals(build)
        b_matched_acc = None

        outer = self.join_type in ("left", "right", "full")

        # the match phase per probe batch; OOM recovery may split the
        # probe side — safe for every join type (build-matched flags
        # accumulate across splits the same way they do across batches,
        # and logical_or is idempotent under re-attempts)
        def match_one(batch):
            nonlocal b_matched_acc
            with self.timer(JOIN_TIME):
                probe_keys = self._encoded_keys(batch, probe_fn)
                m = J.join_match(build_keys, probe_keys,
                                 jnp.int32(build.nrows),
                                 jnp.int32(batch.nrows))
                if self.join_type == "full":
                    bm = m["build_matched"]
                    b_matched_acc = bm if b_matched_acc is None else \
                        jnp.logical_or(b_matched_acc, bm)
            return batch, m

        for batch, m in with_retry(probe_exec.execute(), match_one):
            with self.timer(JOIN_TIME):
                if self.join_type in ("semi", "anti"):
                    join_metrics.note(batch.nrows, 0)
                    # output <= one probe batch: spill-retry suffices
                    yield from with_retry_no_split(
                        lambda: list(self._emit_semi_anti(batch, m)))
                    continue
                count, starts, ends, total = with_retry_no_split(
                    lambda: J.join_out_starts(
                        m["probe_count"], jnp.int32(batch.nrows), outer))
                total = int(total)
                join_metrics.note(batch.nrows, total)
                # chunks stream one at a time (peak HBM stays bounded by
                # max_output_rows); each emit gets spill-retry only — its
                # size is already the configured bound, not splittable
                for off in range(0, total, self.max_output_rows):
                    n_out = min(self.max_output_rows, total - off)
                    yield with_retry_no_split(
                        lambda off=off, n_out=n_out: self._emit_chunk(
                            batch, build, build_payload, m,
                            count, starts, ends, off, n_out))
        if self.join_type == "full":
            if b_matched_acc is None:
                # probe side produced zero batches: every build row is
                # unmatched
                b_matched_acc = jnp.zeros(build.capacity, dtype=bool)
            yield from with_retry_no_split(
                lambda: list(self._emit_unmatched_build(
                    build, build_payload, b_matched_acc)))

    def _emit_chunk(self, probe_batch, build, build_payload, m, count,
                    starts, ends, offset, n_out) -> ColumnarBatch:
        out_cap = bucket_capacity(n_out)
        join_metrics.note_expand(out_cap)
        # note: starts/ends use the outer-adjusted counts (row emission),
        # while `matched` must test the RAW match count so outer rows get
        # a null build side
        p, brow, matched, _ = J.join_gather_indices(
            starts - offset if offset else starts,
            ends - offset if offset else ends,
            m["probe_count"], m["probe_bstart"], m["sorted_to_build"],
            jnp.int64(n_out), out_cap)
        probe_schema, build_schema = (self.right.schema, self.left.schema) \
            if self._swap else (self.left.schema, self.right.schema)
        probe_live = _colvals(self._live(
            list(probe_batch.columns.values()), probe_schema))
        build_live = self._live(build_payload, build_schema)
        probe_cols = selection.gather(
            probe_live, p, jnp.int32(n_out),
            char_capacity=self._char_cap_cols(probe_live, p, n_out))
        build_cols = J.gather_build_side(
            build_live, brow, matched, jnp.int32(n_out),
            char_capacity=self._char_cap_cols(build_live, brow, n_out))
        return self._assemble(
            self._with_placeholders(probe_cols, probe_schema, out_cap),
            self._with_placeholders(build_cols, build_schema, out_cap),
            n_out, probe_valid=None)

    def _live(self, cols: Sequence, schema: Schema) -> List:
        """The columns of ``cols`` (one a schema entry) read above."""
        if self.live_columns is None:
            return list(cols)
        return [c for c, (name, _) in zip(cols, schema)
                if name in self.live_columns]

    def _with_placeholders(self, live: List[ColVal], schema: Schema,
                           capacity: int) -> List[ColVal]:
        """``live`` back at the schema's positions, an all-null column
        (as ``TpuFileScanExec._finish_batch`` makes them) at the others."""
        if self.live_columns is None:
            return live
        live = iter(live)
        return [next(live) if name in self.live_columns
                else _null_colval(dt, capacity) for name, dt in schema]

    @staticmethod
    def _char_cap_cols(cols: Sequence[ColVal], indices, n_out) -> int:
        needed = 0
        for c in cols:
            if c.offsets is not None:
                needed = max(needed, int(selection.gathered_char_count(
                    c.offsets, indices, jnp.int32(n_out))))
        return bucket_capacity(needed) if needed else 0

    def _emit_semi_anti(self, batch, m) -> Iterator[ColumnarBatch]:
        count = m["probe_count"]
        in_range = jnp.arange(count.shape[0],
                              dtype=jnp.int32) < batch.nrows
        if self.join_type == "semi":
            keep = jnp.logical_and(count > 0, in_range)
        else:
            keep = jnp.logical_and(count == 0, in_range)
        cols, n = selection.compact_by_gather(_to_colvals(batch), keep)
        n = int(n)
        if n == 0:
            return
        names = [nm for nm, _ in self.schema]
        yield ColumnarBatch(dict(zip(names, _to_columns(cols, n))), n)

    def _emit_unmatched_build(self, build, build_payload, matched_acc
                              ) -> Iterator[ColumnarBatch]:
        in_range = jnp.arange(
            matched_acc.shape[0], dtype=jnp.int32) < build.nrows
        keep = jnp.logical_and(jnp.logical_not(matched_acc), in_range)
        cols, n = selection.compact_by_gather(build_payload, keep)
        n = int(n)
        if n == 0:
            return
        # left side all-null
        cap = cols[0].values.shape[0] if cols else bucket_capacity(n)
        null_left = [_null_colval(dt, cap) for _, dt in self.left.schema]
        yield self._assemble(null_left, cols, n, probe_valid=False)

    def _assemble(self, probe_cols: List[ColVal], build_cols: List[ColVal],
                  n_out: int, probe_valid) -> ColumnarBatch:
        """Stitch left+right columns into the output schema (handling
        USING-style key deduplication and full-outer key coalescing)."""
        lschema, rschema = self.left.schema, self.right.schema
        if getattr(self, "_swap", False):
            lmap = {nm: c for (nm, _), c in zip(lschema, build_cols)}
            rmap = {nm: c for (nm, _), c in zip(rschema, probe_cols)}
        else:
            lmap = {nm: c for (nm, _), c in zip(lschema, probe_cols)}
            rmap = {nm: c for (nm, _), c in zip(rschema, build_cols)}
        out_cols: Dict[str, Column] = {}
        for nm, dt in self.schema:
            if self.using and nm in self.using:
                # preserved (probe) side supplies the key
                c = rmap[nm] if getattr(self, "_swap", False) else lmap[nm]
                if self.join_type == "full":
                    rc = rmap.get(nm)
                    if rc is not None:
                        lv = c.validity if c.validity is not None else \
                            jnp.ones_like(c.values, dtype=jnp.bool_) \
                            if not dt.is_string else None
                        if dt.is_string:
                            # coalesce handled by unmatched-build batches
                            # carrying the key in the right map
                            c = rc if probe_valid is False else c
                        else:
                            c = ColVal(
                                dt,
                                jnp.where(lv, c.values, rc.values),
                                None if c.validity is None or
                                rc.validity is None else
                                jnp.logical_or(c.validity, rc.validity))
                elif probe_valid is False:
                    c = rmap.get(nm, c)
            elif nm in lmap:
                c = lmap[nm]
            else:
                c = rmap[nm]
            out_cols[nm] = Column(c.dtype, c.values, n_out,
                                  validity=c.validity, offsets=c.offsets)
        return ColumnarBatch(out_cols, n_out)

    def _execute_cross(self) -> Iterator[ColumnarBatch]:
        right_batches = list(self.right.execute())
        if not right_batches:
            return
        build = concat_batches(right_batches)
        bn = build.nrows
        build_payload = _to_colvals(build)
        for batch in self.left.execute():
            total = batch.nrows * bn
            for off in range(0, total, self.max_output_rows):
                n_out = min(self.max_output_rows, total - off)
                out_cap = bucket_capacity(n_out)
                j = jnp.arange(out_cap, dtype=jnp.int64) + off
                p = (j // bn).astype(jnp.int32)
                b = (j % bn).astype(jnp.int32)
                probe_cols = selection.gather(
                    _to_colvals(batch), jnp.clip(p, 0, batch.capacity - 1),
                    jnp.int32(n_out))
                build_cols = selection.gather(
                    build_payload, jnp.clip(b, 0, build.capacity - 1),
                    jnp.int32(n_out))
                yield self._assemble(probe_cols, build_cols, n_out, None)
