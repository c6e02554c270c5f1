"""Hash-aggregate physical operator (sort-based under the hood).

Pipeline mirrors the reference's GpuHashAggregateIterator (aggregate.scala:
184-209): per input batch run the *update* aggregation (fused with key/child
expression evaluation in one XLA computation), cache the partial result
batches, then concatenate on device and run the *merge* aggregation +
finalization.  The reference's sort-based fallback is unnecessary: the primary
algorithm here already IS sort+segment-reduce, which degrades gracefully with
cardinality instead of blowing up a hash table.

String group keys are dictionary-encoded on the host per operator instance
(codes are stable across batches) — the acknowledged round-1 compromise for
strings under XLA static shapes (SURVEY.md section 7 "hard parts").
"""

from __future__ import annotations

import functools
import threading
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as dts
from spark_rapids_tpu.columnar.batch import ColumnarBatch, empty_batch
from spark_rapids_tpu.columnar.column import Column, RowCount
from spark_rapids_tpu.utils import hostsync, tracing
from spark_rapids_tpu.exec.base import (
    AGG_TIME, CONCAT_TIME, NUM_INPUT_BATCHES, NUM_INPUT_ROWS, Schema, TpuExec)
from spark_rapids_tpu.ops import aggregates as agg
from spark_rapids_tpu.ops.compiler import (
    StageFn, batch_to_flat, capacity_of, colvals_to_columns, flat_to_colvals,
    param_args, params_dict)
from spark_rapids_tpu.ops.concat import concat_batches
from spark_rapids_tpu.ops.expressions import (
    Alias, BoundReference, ColVal, EmitContext, Expression,
    collect_param_slots)
from spark_rapids_tpu.plan.logical import AggregateExpression


class AggMetrics:
    """Which rung of the group-by's ladder each batch and each merge of
    ``TpuHashAggregateExec`` took, from what the operator already has on
    the host where it picks the rung (no sync of its own): partial
    batches on the coded directory (``coded_batches``, a speculative hit
    or a sized one; ``coded_slots`` the directory slots they swept, so
    slots over batches is the directory's size) or past it on the sort
    kernel (``sort_batches``), speculations that missed
    (``spec_misses``), partials handed to a merge (``merge_inputs``) and
    the rung each keyed merge took (``merges_coded``, ``merges_sorted``).
    A keyless reduction has no rung: it counts its merge inputs only.
    Running sums over every group-by of every query; plain ints, bumped
    with tracing on or off."""

    KEYS = ("coded_batches", "sort_batches", "spec_misses", "coded_slots",
            "merge_inputs", "merges_sorted", "merges_coded")

    def __init__(self):
        self._lock = threading.Lock()
        self._n = dict.fromkeys(self.KEYS, 0)

    def note(self, key: str, n: int = 1) -> None:
        with self._lock:
            self._n[key] += n

    def coded(self, k_bucket: int) -> None:
        with self._lock:
            self._n["coded_batches"] += 1
            self._n["coded_slots"] += k_bucket

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._n)


agg_metrics = AggMetrics()


class _StringKeyEncoder:
    """Host dictionary encoder with codes stable across batches.

    Vectorized: per batch the Python-level work is O(distinct values) via
    ``ops.dictionary`` (round 1 looped over every row, which dominated the
    runtime for string group-by keys)."""

    def __init__(self):
        self.codes: Dict[Optional[str], int] = {}
        self.values: List[Optional[str]] = []

    def encode(self, col: Column) -> Column:
        from spark_rapids_tpu.ops.dictionary import dict_encode_stable
        out = dict_encode_stable(col, self.codes, self.values).astype(
            np.int32)
        return Column.from_numpy(out, dtype=dts.INT32, capacity=col.capacity)

    def decode(self, col: Column) -> Column:
        # the distinct values once, the rows by Arrow's own take: a NULL
        # key is a NULL of the dictionary
        import pyarrow as pa
        keys = pa.DictionaryArray.from_arrays(
            col.to_numpy(), pa.array(self.values, type=pa.string()))
        return Column.from_arrow(keys, capacity=col.capacity)


from spark_rapids_tpu.ops.aggregates import merge_kind as _merge_kind  # noqa: E402


def _collect_bound_ordinals(e: Expression, out: set) -> None:
    if isinstance(e, BoundReference):
        out.add(e.ordinal)
    for c in e.children:
        _collect_bound_ordinals(c, out)


@functools.lru_cache(maxsize=None)
def _grouped_kernel(kinds: Tuple[str, ...], nkeys: int):
    """Group-by over pre-evaluated fixed-width (values, validity) columns."""

    @jax.jit
    def grouped_agg(keys_flat, bufs_flat, nrows, mask=None):
        capacity = keys_flat[0][0].shape[0]
        keys = [ColVal(None, v, val) for v, val in keys_flat]
        buf_inputs = [(k, ColVal(None, v, val))
                      for k, (v, val) in zip(kinds, bufs_flat)]
        out_keys, out_bufs, n = agg.groupby_aggregate(
            keys, buf_inputs, nrows, capacity, row_mask=mask)
        return ([(k.values, k.validity) for k in out_keys],
                [(b.values, b.validity) for b in out_bufs], n)

    return grouped_agg


@functools.lru_cache(maxsize=None)
def _keyless_kernel(kinds: Tuple[str, ...]):
    """Grand-total reduction over pre-evaluated buffer columns (the
    staged path's keyless case, e.g. SELECT min(s))."""

    @jax.jit
    def keyless_agg(bufs_flat, nrows, mask=None):
        capacity = bufs_flat[0][0].shape[0]
        buf_inputs = [(k, ColVal(None, v, val))
                      for k, (v, val) in zip(kinds, bufs_flat)]
        outs = agg.reduce_aggregate(buf_inputs, nrows, capacity,
                                    row_mask=mask)
        return [(o.values, o.validity) for o in outs]

    return keyless_agg


@functools.lru_cache(maxsize=None)
def _coded_kernel(kinds: Tuple[str, ...], k_bucket: int):
    """Sort-free radix-coded group-by (stage B when the key-space
    product fits ``k_bucket`` slots) — the hash-aggregation regime of
    the reference (aggregate.scala:184-209), realized as direct
    addressing + segment reduce."""

    @jax.jit
    def coded_agg(keys_flat, bufs_flat, mins, slot_ranges, mask):
        capacity = keys_flat[0][0].shape[0]
        keys = [ColVal(None, v, val) for v, val in keys_flat]
        buf_inputs = [(k, ColVal(None, v, val))
                      for k, (v, val) in zip(kinds, bufs_flat)]
        out_keys, out_bufs, n = agg.groupby_aggregate_coded(
            keys, buf_inputs, jnp.int32(0), capacity, mins, slot_ranges,
            k_bucket, row_mask=mask)
        return ([(k.values, k.validity) for k in out_keys],
                [(b.values, b.validity) for b in out_bufs], n)

    return coded_agg


def _pow2_bucket(n: int) -> int:
    from spark_rapids_tpu.columnar.column import bucket_capacity
    return bucket_capacity(n, minimum=64)


@functools.lru_cache(maxsize=None)
def _probe_kernel(nkeys: int):
    """Key-range probe over pre-evaluated key columns (string path and
    merge stage, where keys already exist as columns).  ``mask`` is the
    string path's folded predicate: only the rows it keeps size the key
    space."""

    @jax.jit
    def agg_key_probe(keys_flat, nrows, mask=None):
        capacity = keys_flat[0][0].shape[0]
        keys = [ColVal(None, v, val) for v, val in keys_flat]
        live = jnp.arange(capacity, dtype=jnp.int32) < nrows \
            if mask is None else mask
        return agg.key_range_probe(keys, live)

    return agg_key_probe


class TpuHashAggregateExec(TpuExec):
    ephemeral_output = True

    def __init__(self, group_exprs: Sequence[Expression],
                 agg_exprs: Sequence[Tuple[str, AggregateExpression]],
                 child: TpuExec,
                 pre_filter: Optional[Expression] = None,
                 merge_chunk_rows: int = 1 << 22,
                 defer_syncs: bool = True,
                 spec_slots: int = 4096,
                 encoded_exec: bool = False,
                 max_dict_size: int = (1 << 31) - 1):
        """``pre_filter``: a fused upstream Filter condition (whole-stage
        fusion: predicate becomes a row mask inside the aggregation kernel —
        no compaction pass at all).  Every path takes it: the two-stage
        string path evaluates it in stage A and hands the mask to the
        stage-B kernels.

        ``defer_syncs``: carry per-batch group counts as device-resident
        ``RowCount``s and dispatch the coded path speculatively
        (``spec_slots`` slots, one sync per batch instead of
        probe+count), so XLA dispatch never serializes against the host.
        ``defer_syncs=False`` restores the eager two-pass sequential
        behavior (the baseline tests/test_pipeline.py measures against).

        ``encoded_exec``: encoded execution (ISSUE 11) — string group
        keys that are bare input references dictionary-encode to stable
        i32 codes BEFORE the kernels, so the whole
        filter+project+partial-aggregate stage runs the fully fused
        (speculative coded) path and strings materialize only at the
        final key decode.  Shapes the encoder cannot prove
        equality-faithful (computed keys, a key column consumed by any
        other expression, string min/max buffers) silently keep the
        decoded host-dictionary path.  A dictionary outgrowing
        ``max_dict_size`` latches encoded execution off on the session
        and raises a retryable EncodingOverflowFault (the re-planned
        attempt runs decoded — exact results)."""
        super().__init__(child)
        self.merge_chunk_rows = merge_chunk_rows
        self.defer_syncs = defer_syncs
        self.spec_slots = spec_slots
        self._spec_misses = 0
        self.group_exprs = list(group_exprs)
        self.agg_exprs = list(agg_exprs)
        # fused upstream predicates, BOTTOM-FIRST chain order: each
        # conjunct's ANSI checks are masked by the conjuncts below it
        # (_pre_filter_mask — the FilterStageFn discipline)
        self.pre_filters = list(pre_filter) if isinstance(
            pre_filter, (list, tuple)) else (
            [pre_filter] if pre_filter is not None else [])
        self._pre_sig = tuple(c.cache_key() for c in self.pre_filters) \
            if self.pre_filters else None
        self.funcs = [ae.func for _, ae in agg_exprs]
        self._register_metric(NUM_INPUT_ROWS)
        self._register_metric(NUM_INPUT_BATCHES)
        self._register_metric(AGG_TIME)
        self._register_metric(CONCAT_TIME)

        self._in_dtypes = [dt for _, dt in child.schema]
        self._merge_dicts: Dict[int, List] = {}
        self._single_pass = any(getattr(f, "single_pass", False)
                                for f in self.funcs)
        self._string_key_idx = [i for i, e in enumerate(self.group_exprs)
                                if e.dtype.is_string]
        self._encoders = {i: _StringKeyEncoder()
                          for i in self._string_key_idx}
        # encoded execution state (set up below, after the buffer
        # layout is known): kernel-side group exprs default to the
        # logical ones; schema/decode always read self.group_exprs
        self._encoded_exec = False
        self._enc_ords: List[int] = []
        self._ord_encoders: Dict[int, _StringKeyEncoder] = {}
        self._kgroup: List[Expression] = list(self.group_exprs)
        self.max_dict_size = int(max_dict_size)
        # hoisted-literal slots across every kernel-evaluated expression
        # (keys, agg children, fused pre-filter conjuncts): the jitted
        # bodies take them as one trailing argument vector, so template
        # signatures (value-free ParamSlot cache keys) share executables
        # across literal bindings
        self._slots = collect_param_slots(
            list(self.group_exprs)
            + [f.child for f in self.funcs if f.child is not None]
            + self.pre_filters)

        if self._single_pass:
            # collect aggregates: one grouped pass over the concatenated
            # input (no partial/merge pipeline); jitted kernel below
            from spark_rapids_tpu.ops.jit_cache import cached_jit
            sig = ("agg_single_pass",
                   tuple(dt.name for dt in self._in_dtypes),
                   tuple(e.cache_key() for e in self.group_exprs),
                   tuple(f.cache_key() for f in self.funcs),
                   self._pre_sig)
            self._single_fn = cached_jit(sig, lambda: self._single_kernel)
            return
        # buffer layout: per func, a slice of the flat buffer-column list
        self._buf_specs: List[agg.BufferSpec] = []
        self._buf_slices: List[slice] = []
        for f in self.funcs:
            specs = f.buffers()
            self._buf_slices.append(
                slice(len(self._buf_specs), len(self._buf_specs) + len(specs)))
            self._buf_specs.extend(specs)
        self._update_kinds = tuple(s.kind for s in self._buf_specs)
        self._merge_kinds = tuple(_merge_kind(k) for k in self._update_kinds)
        # string-valued min/max/first/last buffers: batch-local
        # order-preserving dictionary codes on device, strings in the
        # partial batches (buffer position -> func index)
        self._string_buf_pos: Dict[int, int] = {
            sl.start: j for j, (f, sl) in
            enumerate(zip(self.funcs, self._buf_slices))
            if f.child is not None and f.child.dtype.is_string and
            f.name in ("min", "max", "first", "last")}

        if encoded_exec and self._string_key_idx and \
                not self._string_buf_pos:
            ords = self.encoded_key_ordinals(
                self.group_exprs,
                [f.child for f in self.funcs if f.child is not None]
                + self.pre_filters)
            if ords is not None:
                # rewrite: the kernels see the key columns as i32 codes
                # (stable across batches, nulls interned as a code that
                # decodes back to None) — the fused/speculative update
                # path applies; the decoded strings reappear only at
                # the final key decode in do_execute
                self._encoded_exec = True
                self._enc_ords = sorted(set(ords))
                self._ord_encoders = {o: _StringKeyEncoder()
                                      for o in self._enc_ords}
                for i, o in zip(self._string_key_idx, ords):
                    self._encoders[i] = self._ord_encoders[o]
                    e = self.group_exprs[i]
                    self._kgroup[i] = BoundReference(
                        o, dts.INT32, name=e.name, nullable=False)
                self._in_dtypes = [
                    dts.INT32 if j in self._enc_ords else dt
                    for j, dt in enumerate(self._in_dtypes)]
        from spark_rapids_tpu.ops.jit_cache import cached_jit
        base_sig = (tuple(dt.name for dt in self._in_dtypes),
                    tuple(e.cache_key() for e in self._kgroup),
                    tuple(f.cache_key() for f in self.funcs))
        if self._encoded_exec:
            base_sig += (("encexec", tuple(self._enc_ords)),)
        self._base_sig = base_sig
        # coded (sort-free) dispatch: all keys fixed-width integral after
        # string dictionary encoding, all buffers fixed-width
        key_dts = [dts.INT32 if i in self._string_key_idx else e.dtype
                   for i, e in enumerate(self.group_exprs)]
        self._coded_eligible = bool(self.group_exprs) and \
            agg.coded_key_eligible(key_dts) and \
            not any(s.dtype.has_offsets for s in self._buf_specs)
        if self._needs_string_stage:
            # stage A evaluates keys + agg children and, in the same
            # program, the fused pre-filter conjuncts as a row mask; the
            # group kernel runs in stage B after host dictionary
            # encoding of string keys / string agg children
            pre_exprs = list(self.group_exprs) + \
                [f.child for f in self.funcs if f.child is not None]
            self._pre_fn = StageFn(pre_exprs, self._in_dtypes,
                                   conjuncts=self.pre_filters)
        else:
            self._pre_fn = None
            update_sig = ("agg_update",) + base_sig + (
                self._pre_sig,)
            self._update_fn = cached_jit(update_sig,
                                         lambda: self._update_fused)
            if self._coded_eligible:
                # stage A evaluates filter mask + key-range probe only
                # (one cheap pass); stage B re-evaluates keys/buffers
                # FUSED with the coded reduction, picked on the host from
                # the probed key-space size (falls back to _update_fn's
                # sort kernel when the space is too large)
                stage_a_sig = ("agg_stage_a",) + base_sig + (
                    self._pre_sig,)
                self._stage_a_fn = cached_jit(stage_a_sig,
                                              lambda: self._stage_a)
        # merge never evaluates pre_filter: exclude it so queries differing
        # only in filter constants share the merge executable
        self._merge_fn = cached_jit(("agg_merge",) + base_sig,
                                    lambda: self._merge)
        self._merge_partial_fn = cached_jit(
            ("agg_merge_partial",) + base_sig, lambda: self._merge_partial)

    # ------------------------------------------------------------------ plan --
    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def schema(self) -> Schema:
        out = [(e.name, e.dtype) for e in self.group_exprs]
        out += [(name, ae.dtype) for name, ae in self.agg_exprs]
        return out

    def describe(self):
        enc = ", encoded" if self._encoded_exec else ""
        pre = f", pre_filter={[str(c) for c in self.pre_filters]}" \
            if self.pre_filters else ""
        return (f"TpuHashAggregateExec[keys="
                f"{[e.name for e in self.group_exprs]}, aggs="
                f"{[n for n, _ in self.agg_exprs]}{pre}{enc}]")

    @property
    def _needs_string_stage(self) -> bool:
        """True when the two-stage (pre-eval + host dictionary) string
        path must run: string keys NOT rewritten to codes, or
        string-valued min/max/first/last buffers."""
        return ((bool(self._string_key_idx) and not self._encoded_exec)
                or bool(getattr(self, "_string_buf_pos", None)))

    @staticmethod
    def encoded_key_ordinals(group_exprs, consumers
                             ) -> Optional[List[int]]:
        """Input ordinals behind the string group keys when encoded
        execution is equality-faithful, else None.  Faithful means:
        every string key is a bare input reference (optionally
        aliased), and no other kernel consumer — non-string keys, agg
        children, fused predicates (``consumers``) — reads those
        columns, so replacing them with stable dense codes changes no
        evaluated value.  It gates the rewrite only: a chain under the
        aggregate folds either way (the decoded two-stage path takes a
        fused predicate as its row mask)."""
        ords: List[int] = []
        for e in group_exprs:
            if not e.dtype.is_string:
                continue
            inner = e.children[0] if isinstance(e, Alias) else e
            if not isinstance(inner, BoundReference):
                return None  # computed key: codes are not the value
            ords.append(inner.ordinal)
        if not ords:
            return None
        refs: set = set()
        for e in list(consumers) + [g for g in group_exprs
                                    if not g.dtype.is_string]:
            if e is not None:
                _collect_bound_ordinals(e, refs)
        if refs & set(ords):
            return None  # the column's BYTES are consumed elsewhere
        return ords

    def _encode_input_batch(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Replace encoded-ordinal string columns with stable i32 code
        columns (codes stable across batches via the per-ordinal
        encoder; null rows intern as their own code and decode back to
        None, so validity is folded into the code space).  The code
        Column carries its dictionary.  Python work is O(distinct per
        batch) — ops/dictionary vectorized encode."""
        names = list(batch.columns)
        cols = dict(batch.columns)
        for o in self._enc_ords:
            name = names[o]
            enc = self._ord_encoders[o]
            ncol = enc.encode(cols[name])
            if len(enc.values) > self.max_dict_size:
                self._latch_encoding_off(len(enc.values))
            ncol.dictionary = enc.values
            cols[name] = ncol
        return ColumnarBatch(cols, batch.row_count)

    def _latch_encoding_off(self, size: int) -> None:
        """Dictionary overflow: latch encoded execution off for the
        session and raise the retryable fault — the ladder's re-planned
        attempt takes the decoded path (exact results; codes already
        issued die with this attempt)."""
        from spark_rapids_tpu.api.session import TpuSession
        from spark_rapids_tpu.robustness.driver import record_degradation
        from spark_rapids_tpu.robustness.faults import (
            EncodingOverflowFault)
        s = TpuSession._active
        if s is not None:
            s.encoding_exec_latched = True
        err = EncodingOverflowFault(self.describe(), size,
                                    self.max_dict_size)
        record_degradation(s, err.kind, "encoded-exec-latched-off",
                           str(err))
        raise err

    @property
    def _partial_schema(self) -> Schema:
        keys = []
        for i, e in enumerate(self.group_exprs):
            dt = dts.INT32 if i in self._string_key_idx else e.dtype
            keys.append((f"_k{i}", dt))
        bufs = [(f"_b{j}", spec.dtype)
                for j, spec in enumerate(self._buf_specs)]
        return keys + bufs

    # ---------------------------------------------------------- update stage --
    def _eval_update_inputs(self, ctx: EmitContext) -> List[Tuple[str, ColVal]]:
        pairs: List[Tuple[str, ColVal]] = []
        for f in self.funcs:
            c = f.child.emit(ctx) if f.child is not None else None
            if c is not None and getattr(c.values, "ndim", 0) == 0 and \
                    c.offsets is None:
                c = ColVal(c.dtype,
                           jnp.broadcast_to(c.values, (ctx.capacity,)),
                           c.validity)
            for spec, cv in zip(f.buffers(), f.update_inputs(c, ctx.capacity)):
                pairs.append((spec.kind, cv))
        return pairs

    def _pre_filter_mask(self, ctx: EmitContext):
        """Row mask from the fused pre-filter conjuncts (bottom-first,
        progressive ANSI-check masking: each conjunct — and finally the
        keys/agg children — only checks rows the conjuncts below it
        kept, exactly the rows the unfused stages would have
        evaluated).  None when there is no fused filter."""
        from spark_rapids_tpu.ops.expressions import fold_conjuncts
        if not self.pre_filters:
            return None
        return fold_conjuncts(ctx, self.pre_filters)

    def _pargs(self):
        """Dispatch-time ParamSlot argument vector (empty when the
        operator's expressions carry no hoisted literals)."""
        return param_args(self._slots)

    def _update_fused(self, flat_cols, nrows, params=()):
        """No string keys: key eval + buffer eval + group-by, one computation.

        A fused pre_filter predicate contributes a row mask — the whole
        filter+project+partial-agg stage is a single XLA program."""
        capacity = capacity_of(flat_cols)
        inputs = flat_to_colvals(flat_cols, self._in_dtypes)
        ctx = EmitContext(inputs, nrows, capacity,
                          params=params_dict(self._slots, params))
        row_mask = self._pre_filter_mask(ctx)
        keys = [e.emit(ctx) for e in self._kgroup]
        buf_inputs = self._eval_update_inputs(ctx)
        if not keys:
            outs = agg.reduce_aggregate(buf_inputs, nrows, capacity,
                                        row_mask=row_mask)
            return ([], [(o.values, o.validity, o.offsets) for o in outs],
                    jnp.int32(1))
        out_keys, out_bufs, n = agg.groupby_aggregate(
            keys, buf_inputs, nrows, capacity, row_mask=row_mask)
        return ([(k.values, k.validity, k.offsets) for k in out_keys],
                [(b.values, b.validity, b.offsets) for b in out_bufs], n)

    def _stage_a(self, flat_cols, nrows, params=()):
        """Filter mask + key-range probe: the cheap pass whose scalars
        the host needs before picking stage B (coded path)."""
        capacity = capacity_of(flat_cols)
        inputs = flat_to_colvals(flat_cols, self._in_dtypes)
        ctx = EmitContext(inputs, nrows, capacity,
                          params=params_dict(self._slots, params))
        mask = self._pre_filter_mask(ctx)
        if mask is None:
            mask = ctx.row_mask()
        keys = [agg.widen_colval(e.emit(ctx), capacity)
                for e in self._kgroup]
        mins, maxs = agg.key_range_probe(keys, mask)
        return mask, mins, maxs

    def _coded_update(self, k_bucket: int):
        """Build the coded stage-B body (cached_jit per k_bucket): key
        and buffer expressions re-evaluate HERE, fused straight into the
        segment reductions — no materialized intermediate columns."""

        def run(flat_cols, nrows, mask, mins, slot_ranges, params=()):
            capacity = capacity_of(flat_cols)
            inputs = flat_to_colvals(flat_cols, self._in_dtypes)
            ctx = EmitContext(inputs, nrows, capacity,
                              params=params_dict(self._slots, params))
            if self.pre_filters:
                ctx.extra_check_mask = mask
            keys = [agg.widen_colval(e.emit(ctx), capacity)
                    for e in self._kgroup]
            buf_inputs = self._eval_update_inputs(ctx)
            out_keys, out_bufs, n = agg.groupby_aggregate_coded(
                keys, buf_inputs, nrows, capacity, mins, slot_ranges,
                k_bucket, row_mask=mask)
            return ([(k.values, k.validity) for k in out_keys],
                    [(b.values, b.validity) for b in out_bufs], n)

        return run

    def _coded_update_auto(self, k_bucket: int):
        """Speculative stage body (cached_jit per k_bucket): filter
        mask, key-range discovery, fit check AND the coded reduction in
        ONE XLA computation — the probe pass and its host round trip
        only ever happen on a speculation miss."""

        def run(flat_cols, nrows, params=()):
            capacity = capacity_of(flat_cols)
            inputs = flat_to_colvals(flat_cols, self._in_dtypes)
            ctx = EmitContext(inputs, nrows, capacity,
                              params=params_dict(self._slots, params))
            mask = self._pre_filter_mask(ctx)
            if mask is None:
                mask = ctx.row_mask()
            keys = [agg.widen_colval(e.emit(ctx), capacity)
                    for e in self._kgroup]
            buf_inputs = self._eval_update_inputs(ctx)
            out_keys, out_bufs, n, fits, mins, maxs = \
                agg.groupby_aggregate_coded_auto(
                    keys, buf_inputs, nrows, capacity, k_bucket,
                    row_mask=mask)
            return ([(k.values, k.validity) for k in out_keys],
                    [(b.values, b.validity) for b in out_bufs],
                    n, fits, mins, maxs, mask)

        return run

    def _coded_pick_host(self, mins_h, maxs_h):
        """Size the key space from host-resident probe results; None
        when the coded path does not apply."""
        mins_h = np.asarray(mins_h)
        maxs_h = np.asarray(maxs_h)
        pick = agg.coded_slot_ranges(mins_h, maxs_h)
        if pick is None:
            return None
        slots, total = pick
        return (_pow2_bucket(total),
                jnp.asarray(np.minimum(mins_h, maxs_h)),
                jnp.asarray(np.asarray(slots, dtype=np.int64)))

    def _sync_range(self, mins, maxs):
        """Sync the probe scalars (one batched transfer when syncs are
        deferred, the legacy two when not)."""
        if self.defer_syncs:
            return hostsync.fetch(mins, maxs)
        hostsync.count_sync(2)
        return np.asarray(mins), np.asarray(maxs)

    def _coded_pick(self, mins, maxs):
        """Sync the probe scalars and size the key space."""
        return self._coded_pick_host(*self._sync_range(mins, maxs))

    def _wrap_count(self, n) -> RowCount:
        """Device group count -> RowCount; eager mode forces (and
        counts) the sync immediately, preserving the sequential
        baseline's behavior."""
        rc = RowCount(device=n)
        if not self.defer_syncs:
            int(rc)
        return rc

    def _partial_coded(self, batch, names, dtypes):
        from spark_rapids_tpu.ops.jit_cache import cached_jit
        flat = batch_to_flat(batch)
        nrows = batch.row_count.device_i32()
        # speculative single-pass dispatch: stop speculating after two
        # misses (the operator's key space clearly exceeds the bucket)
        spec_k = self.spec_slots if self.defer_syncs else 0
        if spec_k and self._spec_misses < 2:
            fn = cached_jit(
                ("agg_coded_auto", spec_k) + self._base_sig + (
                    self._pre_sig,),
                lambda: self._coded_update_auto(spec_k))
            key_out, buf_out, n, fits, mins, maxs, mask = fn(
                flat, nrows, self._pargs())
            fits_h, mins_h, maxs_h = hostsync.fetch(fits, mins, maxs)
            if bool(fits_h):
                agg_metrics.coded(spec_k)
                outs = [ColVal(dt, v, val) for dt, (v, val) in
                        zip(dtypes, list(key_out) + list(buf_out))]
                out_cap = key_out[0][0].shape[0] if key_out else \
                    buf_out[0][0].shape[0]
                n_rc = self._wrap_count(n)
                cols = colvals_to_columns(outs, n_rc, out_cap)
                return ColumnarBatch(dict(zip(names, cols)), n_rc)
            self._spec_misses += 1
            agg_metrics.note("spec_misses")
            pick = self._coded_pick_host(mins_h, maxs_h)
        else:
            mask, mins, maxs = self._stage_a_fn(flat, nrows, self._pargs())
            pick = self._coded_pick(mins, maxs)
        if pick is None:
            # key space past the coded directory: the fully fused sort
            # kernel
            agg_metrics.note("sort_batches")
            key_flat, buf_flat, n = self._update_fn(flat, nrows,
                                                    self._pargs())
            n_rc = self._wrap_count(n)
            outs = [ColVal(dt, v, val, offs)
                    for dt, (v, val, offs) in
                    zip(dtypes, list(key_flat) + list(buf_flat))]
            cols = colvals_to_columns(outs, n_rc, batch.capacity)
            return ColumnarBatch(dict(zip(names, cols)), n_rc)
        k_bucket, mins_d, slots_d = pick
        agg_metrics.coded(k_bucket)
        fn = cached_jit(
            ("agg_coded_update", k_bucket) + self._base_sig,
            lambda: self._coded_update(k_bucket))
        key_out, buf_out, n = fn(flat, nrows, mask, mins_d, slots_d,
                                 self._pargs())
        n_rc = self._wrap_count(n)
        outs = [ColVal(dt, v, val) for dt, (v, val) in
                zip(dtypes, list(key_out) + list(buf_out))]
        out_cap = key_out[0][0].shape[0] if key_out else \
            buf_out[0][0].shape[0]
        cols = colvals_to_columns(outs, n_rc, out_cap)
        return ColumnarBatch(dict(zip(names, cols)), n_rc)

    def _partial_batches(self) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.memory.retry import with_retry
        names = [n for n, _ in self._partial_schema]
        dtypes = [dt for _, dt in self._partial_schema]

        def tallied():
            for batch in self.child.execute():
                # row_count: deferred upstream counts accumulate lazily
                # in the metric and skip the per-batch empty check (not
                # worth a round trip — the kernels mask empty input)
                self.metrics[NUM_INPUT_ROWS] += batch.row_count
                self.metrics[NUM_INPUT_BATCHES] += 1
                if not batch.row_count.is_concrete or batch.nrows:
                    yield batch

        def compute(batch):
            with tracing.span("agg.partial"), self.timer(AGG_TIME):
                if self._encoded_exec:
                    batch = self._encode_input_batch(batch)
                if self._needs_string_stage:
                    return self._partial_with_string_keys(
                        batch, names, dtypes)
                if self._coded_eligible:
                    return self._partial_coded(batch, names, dtypes)
                if self.group_exprs:
                    # a key no directory can address (a float)
                    agg_metrics.note("sort_batches")
                key_flat, buf_flat, n = self._update_fn(
                    batch_to_flat(batch), batch.row_count.device_i32(),
                    self._pargs())
                # keyless reductions have statically one output row;
                # grouped counts stay device-resident (deferred) — the
                # per-batch int(n) is a device-to-host sync that stalls
                # the dispatch queue
                n = 1 if not self.group_exprs else self._wrap_count(n)
                outs = [ColVal(dt, v, val, offs)
                        for dt, (v, val, offs) in
                        zip(dtypes, list(key_flat) + list(buf_flat))]
                cols = colvals_to_columns(outs, n, batch.capacity)
                return ColumnarBatch(dict(zip(names, cols)), n)

        yield from with_retry(tallied(), compute)

    def _partial_with_string_keys(self, batch, names, dtypes):
        from spark_rapids_tpu.ops.dictionary import ordered_dict_encode
        nkeys = len(self.group_exprs)
        # mask: the rows the fused pre-filter keeps (None without one).
        # The host encoders below go on seeing every row: a value that
        # occurs only in dropped rows gets a code, and the kernels' row
        # mask keeps it from making a group or winning a min/max
        pre_cols, mask = self._pre_fn.masked(batch)
        key_cols, child_cols = pre_cols[:nkeys], pre_cols[nkeys:]
        enc_keys = [self._encoders[i].encode(c) if i in self._string_key_idx
                    else c for i, c in enumerate(key_cols)]
        child_iter = iter(child_cols)
        buf_inputs: List[Tuple[str, ColVal]] = []
        buf_dicts: Dict[int, List] = {}
        for f in self.funcs:
            cc = next(child_iter) if f.child is not None else None
            if cc is not None and len(buf_inputs) in self._string_buf_pos:
                # batch-local ORDER-PRESERVING codes: min/max over codes
                # equals min/max over strings within this batch
                codes, d = ordered_dict_encode(cc)
                buf_dicts[len(buf_inputs)] = d
                pad = np.zeros(batch.capacity, dtype=np.int64)
                pad[: len(codes)] = codes
                cv = ColVal(dts.INT64, jnp.asarray(pad), cc.validity)
            else:
                cv = None if cc is None else \
                    ColVal(cc.dtype, cc.data, cc.validity, cc.offsets)
            for spec, bi in zip(f.buffers(),
                                f.update_inputs(cv, batch.capacity)):
                buf_inputs.append((spec.kind, bi))
        key_flat_in = [(c.data, c.validity) for c in enc_keys]
        buf_flat_in = [(c.values, c.validity) for _, c in buf_inputs]
        nrows = batch.row_count.device_i32()
        if not enc_keys:
            # keyless (e.g. SELECT min(s)): one output row
            kernel = _keyless_kernel(self._update_kinds)
            buf_flat = kernel(buf_flat_in, nrows, mask)
            key_flat, n = [], 1
            out_cap = 1024
        else:
            pick = None
            if self._coded_eligible:
                mins, maxs = _probe_kernel(nkeys)(key_flat_in, nrows,
                                                  mask)
                pick = self._coded_pick(mins, maxs)
            if pick is not None:
                k_bucket, mins_d, slots_d = pick
                agg_metrics.coded(k_bucket)
                if mask is None:
                    mask = jnp.arange(batch.capacity,
                                      dtype=jnp.int32) < nrows
                key_flat, buf_flat, n = _coded_kernel(
                    self._update_kinds, k_bucket)(
                    key_flat_in, buf_flat_in, mins_d, slots_d, mask)
            else:
                agg_metrics.note("sort_batches")
                kernel = _grouped_kernel(self._update_kinds, nkeys)
                key_flat, buf_flat, n = kernel(key_flat_in, buf_flat_in,
                                               nrows, mask)
            # string buffers re-decode per batch below: a genuine host
            # decision point, so the count syncs (and is counted) here
            n = int(RowCount(device=n))
            out_cap = key_flat[0][0].shape[0]
        cols_out = {}
        for name, dt, (v, val) in zip(names, dtypes,
                                      list(key_flat) + list(buf_flat)):
            pos = len(cols_out) - nkeys
            if pos in buf_dicts:
                d = buf_dicts[pos]
                codes = np.asarray(v[:n] if getattr(v, "ndim", 0)
                                   else jnp.broadcast_to(v, (1,)))
                ok = np.ones(n, dtype=bool) if val is None else \
                    np.asarray(val[:n] if getattr(val, "ndim", 0)
                               else jnp.broadcast_to(val, (1,)))
                strs = [d[int(c)] if o and d else None
                        for c, o in zip(codes, ok)]
                cols_out[name] = Column.from_strings(strs,
                                                     capacity=out_cap)
            else:
                cv = ColVal(dt, v, val)
                cols_out[name] = colvals_to_columns([cv], n, out_cap)[0]
        return ColumnarBatch(cols_out, n)

    # ------------------------------------------------------------ merge stage --
    @property
    def _merge_dtypes(self) -> List:
        """Partial-schema dtypes as the merge kernels see them: string
        buffers arrive re-encoded as int64 codes."""
        nkeys = len(self.group_exprs)
        out = []
        for i, (_, dt) in enumerate(self._partial_schema):
            pos = i - nkeys
            out.append(dts.INT64 if pos in self._string_buf_pos else dt)
        return out

    def _merge_body(self, flat_cols, nrows):
        """Shared merge group-by/reduce over partial-schema columns."""
        dtypes = self._merge_dtypes
        nkeys = len(self.group_exprs)
        capacity = capacity_of(flat_cols)
        cols = flat_to_colvals(flat_cols, dtypes)
        keys, bufs = cols[:nkeys], cols[nkeys:]
        merge_inputs = [(k, c) for k, c in zip(self._merge_kinds, bufs)]
        if keys:
            return agg.groupby_aggregate(keys, merge_inputs, nrows,
                                         capacity)
        out_bufs = agg.reduce_aggregate(merge_inputs, nrows, capacity)
        return [], out_bufs, jnp.int32(1)

    def _merge(self, flat_cols, nrows):
        out_keys, out_bufs, n = self._merge_body(flat_cols, nrows)
        results = [f.finalize(out_bufs[sl])
                   for f, sl in zip(self.funcs, self._buf_slices)]
        return ([(k.values, k.validity, k.offsets) for k in out_keys],
                [(r.values, r.validity, r.offsets) for r in results], n)

    def _merge_partial(self, flat_cols, nrows):
        """Merge partial batches into one partial batch (no finalize) —
        the tree-reduction step bounding the final concat (the reference's
        sort-based fallback serves the same purpose, aggregate.scala:
        184-197: never require every partial in memory at once)."""
        out_keys, out_bufs, n = self._merge_body(flat_cols, nrows)
        return ([(k.values, k.validity, k.offsets) for k in out_keys],
                [(b.values, b.validity, b.offsets) for b in out_bufs], n)

    def _merge_coded(self, k_bucket: int, finalize: bool):
        """Build the coded (sort-free) merge kernel body for cached_jit."""
        dtypes = [dt for _, dt in self._partial_schema]
        nkeys = len(self.group_exprs)

        def run(flat_cols, mins, slot_ranges, nrows):
            capacity = capacity_of(flat_cols)
            cols = flat_to_colvals(flat_cols, dtypes)
            keys, bufs = cols[:nkeys], cols[nkeys:]
            merge_inputs = [(k, c)
                            for k, c in zip(self._merge_kinds, bufs)]
            out_keys, out_bufs, n = agg.groupby_aggregate_coded(
                keys, merge_inputs, nrows, capacity, mins, slot_ranges,
                k_bucket)
            if finalize:
                results = [f.finalize(out_bufs[sl])
                           for f, sl in zip(self.funcs, self._buf_slices)]
            else:
                results = out_bufs
            return ([(k.values, k.validity, k.offsets) for k in out_keys],
                    [(r.values, r.validity, r.offsets) for r in results],
                    n)

        return run

    def _merge_exec(self, merged_in: ColumnarBatch, finalize: bool):
        """Merge-stage dispatch mirroring the update stage: probe the
        partials' key ranges, run the coded kernel when the space fits.
        String buffer columns (min/max/first/last partial winners) are
        re-encoded to order-preserving codes over ALL partials first —
        comparisons across batches are then exact; outputs decode via
        ``self._merge_dicts``."""
        flat = batch_to_flat(merged_in)
        nrows = merged_in.row_count.device_i32()
        nkeys = len(self.group_exprs)
        self._merge_dicts = {}
        if self._string_buf_pos:
            from spark_rapids_tpu.ops.dictionary import ordered_dict_encode
            cols = list(merged_in.columns.values())
            for pos in self._string_buf_pos:
                ci = nkeys + pos
                col = cols[ci]
                codes, d = ordered_dict_encode(col)
                self._merge_dicts[pos] = d
                pad = np.zeros(col.capacity, dtype=np.int64)
                pad[: len(codes)] = codes
                flat[ci] = (jnp.asarray(pad), col.validity, None)
        if self._coded_eligible:
            key_flat = [(v, val) for v, val, _ in flat[:nkeys]]
            mins, maxs = _probe_kernel(nkeys)(key_flat, nrows)
            pick = self._coded_pick(mins, maxs)
            if pick is not None:
                from spark_rapids_tpu.ops.jit_cache import cached_jit
                kb, mins_d, slots_d = pick
                agg_metrics.note("merges_coded")
                fn = cached_jit(
                    ("agg_merge_coded", finalize, kb) + self._base_sig,
                    lambda: self._merge_coded(kb, finalize))
                return fn(flat, mins_d, slots_d, nrows)
        if nkeys:
            agg_metrics.note("merges_sorted")
        fn = self._merge_fn if finalize else self._merge_partial_fn
        return fn(flat, nrows)

    def _tree_merge(self, handles, catalog):
        """Reduce partial handles until their total rows fit one merge
        chunk; each step merges >=2 partials into one (still-partial)
        spillable batch, so the device never holds every partial."""
        names = [n for n, _ in self._partial_schema]
        dtypes = [dt for _, dt in self._partial_schema]
        chunk = self.merge_chunk_rows
        # merge sizing is a host decision point — but first check the
        # sync-free capacity bound: when even the upper bound fits one
        # merge chunk (the common coded-path case), no deferred count
        # ever materializes here.  Otherwise resolve every handle's
        # count in ONE batched transfer.
        if len(handles) > 1 and \
                sum(h.nrows_bound for h in handles) > chunk:
            RowCount.materialize_all([h.row_count for h in handles])
        while len(handles) > 1 and \
                sum(h.nrows_bound for h in handles) > chunk:
            group = []
            rows = 0
            while handles and (len(group) < 2 or
                               rows + handles[0].nrows <= chunk):
                h = handles.pop(0)
                group.append(h)
                rows += h.nrows
                if rows >= chunk and len(group) >= 2:
                    break
            agg_metrics.note("merge_inputs", len(group))
            with self.timer(CONCAT_TIME):
                merged_in = concat_batches([h.materialize()
                                            for h in group])
            for h in group:
                h.close()
            with self.timer(AGG_TIME):
                key_flat, buf_flat, n = self._merge_exec(
                    merged_in, finalize=False)
                # compaction below sizes the spill registration from n:
                # a genuine host decision point (counted sync)
                n = 1 if not self.group_exprs else int(RowCount(device=n))
            outs = [ColVal(dt, v, val, offs)
                    for dt, (v, val, offs) in
                    zip(dtypes, list(key_flat) + list(buf_flat))]
            # compact to the live row count before registering: n is
            # already concrete here, and keeping the concat capacity
            # would make padding, not rows, dominate the spill bytes
            # (coded-path outputs are already key-space sized)
            from spark_rapids_tpu.columnar.column import bucket_capacity
            cur_cap = int(outs[0].values.shape[0])
            out_cap = min(bucket_capacity(n), cur_cap)
            if out_cap < cur_cap:
                outs = [ColVal(c.dtype, c.values[:out_cap],
                               None if c.validity is None
                               else c.validity[:out_cap], c.offsets)
                        for c in outs]
            nkeys = len(self.group_exprs)
            cols = {}
            for name, c in zip(names, outs):
                pos = len(cols) - nkeys
                if pos in self._merge_dicts:
                    cols[name] = self._decode_codes(c, n, out_cap,
                                                    self._merge_dicts[pos])
                else:
                    cols[name] = colvals_to_columns([c], n, out_cap)[0]
            handles.append(
                catalog.register(ColumnarBatch(cols, n)))
        return handles

    @staticmethod
    def _decode_codes(c: ColVal, n: int, out_cap: int, d: List) -> Column:
        """codes ColVal -> string Column via a merge-stage dictionary."""
        codes = np.asarray(c.values[:n]) if getattr(c.values, "ndim", 0) \
            else np.broadcast_to(np.asarray(c.values), (n,))
        if c.validity is None:
            ok = np.ones(n, dtype=bool)
        else:
            ok = np.asarray(c.validity[:n]) \
                if getattr(c.validity, "ndim", 0) else \
                np.broadcast_to(np.asarray(c.validity), (n,))
        strs = [d[int(v)] if o and d else None
                for v, o in zip(codes, ok)]
        return Column.from_strings(strs, capacity=out_cap)

    def _single_kernel(self, flat_cols, nrows, params=()):
        """Grouped pass mixing collect arrays with regular reductions."""
        capacity = capacity_of(flat_cols)
        inputs = flat_to_colvals(flat_cols, self._in_dtypes)
        ctx = EmitContext(inputs, nrows, capacity,
                          params=params_dict(self._slots, params))
        row_mask = self._pre_filter_mask(ctx)
        keys = [e.emit(ctx) for e in self.group_exprs]
        keyless = not keys
        if keyless:
            # constant key -> exactly one group over the live rows; the
            # key column is dropped from the output below
            keys = [ColVal(dts.INT64,
                           jnp.zeros(capacity, dtype=jnp.int64))]
        collect_inputs = []
        buffer_inputs = []
        layout = []  # ("collect", idx) | ("buf", slice) per func
        for f in self.funcs:
            c = f.child.emit(ctx) if f.child is not None else None
            if c is not None and getattr(c.values, "ndim", 0) == 0 and                     c.offsets is None:
                c = ColVal(c.dtype,
                           jnp.broadcast_to(c.values, (capacity,)),
                           c.validity)
            if getattr(f, "single_pass", False):
                layout.append(("collect", len(collect_inputs)))
                collect_inputs.append((c, f.dedup))
            else:
                start = len(buffer_inputs)
                for spec, cv in zip(f.buffers(),
                                    f.update_inputs(c, capacity)):
                    buffer_inputs.append((spec.kind, cv))
                layout.append(("buf", slice(start, len(buffer_inputs))))
        out_keys, out_bufs, collects, n = agg.groupby_collect(
            keys, collect_inputs, nrows, capacity,
            buffer_inputs=buffer_inputs, row_mask=row_mask)
        if keyless:
            out_keys = []
        results = []
        for f, (kind, ref) in zip(self.funcs, layout):
            if kind == "collect":
                results.append(collects[ref])
            else:
                results.append(f.finalize(out_bufs[ref]))
        outs = list(out_keys) + results
        return ([(o.values, o.validity, o.offsets) for o in outs], n)

    def _single_pass_execute(self) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.memory.spill import default_catalog
        catalog = default_catalog()
        handles = []
        for b in self.child.execute():
            self.metrics[NUM_INPUT_ROWS] += b.row_count
            self.metrics[NUM_INPUT_BATCHES] += 1
            handles.append(catalog.register(b))
        if not handles:
            if self.group_exprs:
                return
            # Spark keyless aggregation of empty input is ONE row:
            # empty arrays for collects, identity for the rest
            yield self._keyless_empty_result()
            return
        batches = [h.materialize() for h in handles]
        with self.timer(CONCAT_TIME):
            merged = concat_batches(batches)
        for h in handles:
            h.close()
        with self.timer(AGG_TIME):
            out_flat, n = self._single_fn(batch_to_flat(merged),
                                          merged.row_count.device_i32(),
                                          self._pargs())
            # collect arrays re-decode on the host right below: the
            # count is needed concretely either way (counted sync)
            n = int(RowCount(device=n))
        if n == 0 and not self.group_exprs:
            yield self._keyless_empty_result()
            return
        names = [nm for nm, _ in self.schema]
        dtypes = [dt for _, dt in self.schema]
        outs = [ColVal(dt, v, val, offs)
                for dt, (v, val, offs) in zip(dtypes, out_flat)]
        cols = colvals_to_columns(outs, n, merged.capacity)
        yield ColumnarBatch(dict(zip(names, cols)), n)

    def _keyless_empty_result(self) -> ColumnarBatch:
        cols = {}
        for (name, dt), f in zip(self.schema, self.funcs):
            if getattr(f, "single_pass", False):
                cols[name] = Column.from_arrays([[]], dt.element)
            elif f.name == "count":
                cols[name] = Column.from_numpy(
                    np.zeros(1, dtype=np.int64), dtype=dts.INT64)
            else:
                cols[name] = Column.from_numpy(
                    np.zeros(1, dtype=dt.storage), dtype=dt,
                    validity=np.array([False]))
        return ColumnarBatch(cols, 1)

    def do_execute(self) -> Iterator[ColumnarBatch]:
        if self._single_pass:
            yield from self._single_pass_execute()
            return
        from spark_rapids_tpu.memory.spill import default_catalog
        catalog = default_catalog()
        # cache partials as spillable batches (the reference caches
        # SpillableColumnarBatch between update and merge, aggregate.scala)
        handles = [catalog.register(b) for b in self._partial_batches()]
        if not handles and self.group_exprs:
            return
        with tracing.span("agg.merge"):
            out = self._merge_partials(handles, catalog)
        yield out

    def _merge_partials(self, handles, catalog) -> ColumnarBatch:
        """Tree-merge the partial handles down to one merge chunk, then
        the final merge with finalization and the keys' decode."""
        nkeys = len(self.group_exprs)
        if not handles:
            partials = [empty_batch(self._partial_schema)]
        else:
            handles = self._tree_merge(handles, catalog)
            partials = [h.materialize() for h in handles]
            agg_metrics.note("merge_inputs", len(partials))
        with self.timer(CONCAT_TIME):
            merged_in = concat_batches(partials)
        for h in handles:
            h.close()
        with self.timer(AGG_TIME):
            key_flat, res_flat, n = self._merge_exec(
                merged_in, finalize=True)
            if not self.group_exprs:
                n = 1
            elif self._string_key_idx or self._merge_dicts:
                # string re-decode below walks codes on the host: a
                # genuine host decision point (counted sync)
                n = int(RowCount(device=n))
            else:
                # fully deferred: the final count rides to collect()
                n = self._wrap_count(n)
        out_names = [name for name, _ in self.schema]
        outs: List[ColVal] = []
        for i, (e, (v, val, offs)) in enumerate(zip(self.group_exprs,
                                                    key_flat)):
            dt = dts.INT32 if i in self._string_key_idx else e.dtype
            outs.append(ColVal(dt, v, val, offs))
        for (name, ae), (v, val, offs) in zip(self.agg_exprs, res_flat):
            outs.append(ColVal(ae.dtype, v, val, offs))
        out_cap = next((int(o.values.shape[0]) for o in outs
                        if getattr(o.values, "ndim", 0) >= 1),
                       merged_in.capacity)
        cols = []
        for j, c in enumerate(outs):
            fj = j - nkeys  # func index for agg outputs
            bpos = self._buf_slices[fj].start if 0 <= fj < len(
                self.funcs) else None
            if bpos is not None and bpos in self._merge_dicts:
                cols.append(self._decode_codes(c, n, out_cap,
                                               self._merge_dicts[bpos]))
            else:
                cols.append(colvals_to_columns([c], n, out_cap)[0])
        for i in self._string_key_idx:
            cols[i] = self._encoders[i].decode(cols[i])
        return ColumnarBatch(dict(zip(out_names, cols)), n)
