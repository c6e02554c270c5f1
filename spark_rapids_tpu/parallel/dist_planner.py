"""Distributed query planner: lower logical plans onto the device mesh.

This is the piece that turns ``session.sql("...")`` into an SPMD program:
when the session holds a ``jax.sharding.Mesh``, every query's logical
plan is first offered to this planner; a fully-supported plan executes
as compiled shard_map pipelines over the mesh (the reference's
planner-inserted exchange — ``GpuShuffleExchangeExec.scala:120-199``,
``RapidsShuffleInternalManagerBase.scala:114-127`` — SURVEY.md section
2.5), anything else falls back to the single-process engine with the
reason recorded on ``session.last_dist_explain``.

Design (TPU-first, whole-stage SPMD):

* A query executes as a chain of **ShardedFrame** transforms — every
  column is one leading-axis-sharded array ``[nshards * capacity]``
  plus a per-shard row-count vector.  Static shapes per stage; the only
  host syncs are the adaptive phase boundaries (histogram -> slot
  sizing) inside aggregate/join/sort.
* **Strings dictionary-encode at the scan** with ORDER-PRESERVING codes
  (``ops.dictionary.ordered_dict_encode``): group-by, sort, min/max and
  literal comparisons all run on int64 codes on device; values decode at
  collect.  Comparisons against string literals lower to code-space
  comparisons via binary search in the sorted dictionary.
* Aggregates/joins/sorts wrap the SPMD kernels in
  ``parallel/distributed.py`` / ``parallel/distsort.py``.
* The planner is an **eager executor with a dry mode**: the same
  recursion first runs with ``dry=True`` (schemas and empty
  dictionaries, no kernels, no data) as the support pre-flight, so an
  unsupported query falls back before any scan runs; the second pass
  executes for real.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from spark_rapids_tpu.columnar import dtypes as dts
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, bucket_capacity
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.ops import predicates as preds
from spark_rapids_tpu.ops.expressions import (
    Alias, BoundReference, ColVal, EmitContext, Expression, Literal)
from spark_rapids_tpu.parallel import mesh as mesh_lib
from spark_rapids_tpu.parallel.mesh import shard_map as _shard_map
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.logical import AggregateExpression


class NotDistributable(Exception):
    """Plan (or expression) cannot lower onto the mesh; single-process
    fallback with this reason."""


class _UnsplittableScan(Exception):
    """Internal: the file list cannot be sharded (no footer row counts,
    unlistable paths, or a shard overflowed its bound) — the scan falls
    back to the controller-side read+scatter path."""


def _file_row_bound(path: str, fmt: str) -> Optional[int]:
    """Exact per-file row count from footer metadata (parquet/orc) — an
    UPPER bound on post-pushdown rows, used to size shard capacity
    without reading data."""
    try:
        if fmt == "parquet":
            import pyarrow.parquet as pq
            return int(pq.ParquetFile(path).metadata.num_rows)
        if fmt == "orc":
            from pyarrow import orc
            return int(orc.ORCFile(path).nrows)
    except Exception:
        return None
    return None


@jax.jit
def _remap_codes(rank, vals):
    """Elementwise lookup of a small replicated rank table over a
    sharded codes array (stays sharded; no collectives)."""
    return rank[vals]


class ShardedFrame:
    """Columns as leading-axis sharded device arrays + per-shard counts.

    ``cols``: [(values, validity)] each ``[nshards * capacity]``;
    ``nrows``: int32 ``[nshards]``; ``enc``: ordinal -> sorted dictionary
    values for string columns travelling as int64 codes.  In dry mode
    (the support pre-flight) ``cols``/``nrows`` are None and ``enc``
    maps string ordinals to empty dictionaries."""

    def __init__(self, mesh, names: List[str], log_dtypes: List[DataType],
                 cols, nrows, enc: Dict[int, List[Optional[str]]]):
        self.mesh = mesh
        self.names = names
        self.log_dtypes = log_dtypes
        self.cols = cols
        self.nrows = nrows
        self.enc = enc

    @property
    def dry(self) -> bool:
        return self.cols is None

    @property
    def phys_dtypes(self) -> List[DataType]:
        return [_phys(dt) for dt in self.log_dtypes]

    @property
    def nshards(self) -> int:
        return self.mesh.devices.size

    @property
    def capacity(self) -> int:
        return int(self.cols[0][0].shape[0]) // self.nshards if self.cols \
            else 0

    @property
    def schema(self) -> List[Tuple[str, DataType]]:
        return list(zip(self.names, self.log_dtypes))

    def replace(self, **kw) -> "ShardedFrame":
        args = dict(mesh=self.mesh, names=self.names,
                    log_dtypes=self.log_dtypes, cols=self.cols,
                    nrows=self.nrows, enc=self.enc)
        args.update(kw)
        return ShardedFrame(**args)


def _phys(dt: DataType) -> DataType:
    return dts.INT64 if dt.is_string else dt


# --------------------------------------------------- expression lowering --

_CMP = (preds.EqualTo, preds.LessThan, preds.LessThanOrEqual,
        preds.GreaterThan, preds.GreaterThanOrEqual)


class DictLookup(Expression):
    """Gather through a per-dictionary lookup table: ``lut[codes]``.

    The distributed lowering for ANY expression over a single encoded
    string column (LIKE, regex, substring, length, ...): the original
    expression is evaluated ONCE host-side over the K dictionary values
    (K = distinct strings, tiny) and becomes an O(1)-per-row gather on
    device.  String-valued results re-encode against a fresh sorted
    dictionary (``dict_values``), so they stay sortable/groupable codes.
    """

    def __init__(self, child: Expression, lut_values, lut_valid,
                 dtype: DataType, dict_values=None, label: str = "f"):
        self.children = (child,)
        self.lut_values = np.asarray(lut_values)
        self.lut_valid = np.asarray(lut_valid, dtype=bool)
        self._dtype = dtype
        self.dict_values = dict_values  # set when result is encoded str
        self.label = label

    @property
    def dtype(self) -> DataType:
        return self._dtype

    @property
    def nullable(self) -> bool:
        return True

    @property
    def name(self) -> str:
        return self.label

    def with_children(self, children):
        return DictLookup(children[0], self.lut_values, self.lut_valid,
                          self._dtype, self.dict_values, self.label)

    def emit(self, ctx) -> ColVal:
        import jax.numpy as jnp
        from spark_rapids_tpu.ops.expressions import combine_validity
        c = self.children[0].emit(ctx)
        k = max(len(self.lut_values), 1)
        lut = jnp.asarray(self.lut_values) if len(self.lut_values) else \
            jnp.zeros(1, dtype=self._dtype.storage)
        lval = jnp.asarray(self.lut_valid) if len(self.lut_valid) else \
            jnp.zeros(1, dtype=jnp.bool_)
        idx = jnp.clip(c.values, 0, k - 1).astype(jnp.int32)
        return ColVal(self._dtype, lut[idx],
                      combine_validity(c.validity, lval[idx]))

    def cache_key(self):
        import hashlib
        h = hashlib.sha1(self.lut_values.tobytes() +
                         self.lut_valid.tobytes()).hexdigest()[:16]
        return ("DictLookup", self.children[0].cache_key(),
                self._dtype.name, h)

    def __str__(self):
        return f"DictLookup[{self.label}]"


# register with the support-tagging framework (reused by
# _check_supported); any fixed-width result type flows through
from spark_rapids_tpu.plan import typechecks as _ts  # noqa: E402
from spark_rapids_tpu.plan.overrides import expr_rule as _expr_rule  # noqa: E402

_expr_rule(DictLookup, _ts.ALL)


class ExprLowering:
    """Rewrite a bound expression for the encoded physical frame:
    references to string columns become int64 code references, and
    comparisons against string literals become code-space comparisons
    via binary search in the (sorted) dictionary.  With empty
    dictionaries (dry mode) the rewrite still type-checks — codes just
    come out as never-matching sentinels."""

    def __init__(self, enc: Dict[int, List[Optional[str]]], conf=None):
        self.enc = enc
        self.conf = conf

    def lower(self, e: Expression) -> Expression:
        if isinstance(e, Alias):
            return Alias(self.lower(e.children[0]), e.alias)
        if isinstance(e, BoundReference):
            if e.ordinal in self.enc:
                return BoundReference(e.ordinal, dts.INT64, name=e.name,
                                      nullable=e.nullable)
            if e.dtype.is_string or e.dtype.has_offsets or e.dtype.is_nested:
                raise NotDistributable(
                    f"column {e.name!r} of type {e.dtype} has no encoded "
                    "device representation on the mesh")
            return e
        if isinstance(e, _CMP) and (e.children[0].dtype.is_string or
                                    e.children[1].dtype.is_string):
            return self._lower_cmp(e)
        if isinstance(e, preds.In) and e.children[0].dtype.is_string:
            return self._lower_in(e)
        if isinstance(e, (preds.IsNull, preds.IsNotNull)) and \
                e.children[0].dtype.is_string:
            return type(e)(self.lower(e.children[0]))
        if isinstance(e, AggregateExpression):
            return self.lower_agg(e)
        if any(c.dtype.is_string for c in e.children) or e.dtype.is_string:
            # expression over / producing strings: try the dictionary
            # lowering (host-evaluate over the K distinct values, gather
            # through a LUT on device)
            d = self._try_dict_lower(e)
            if d is not None:
                return d
            raise NotDistributable(
                f"{type(e).__name__} over strings has no code-space "
                "lowering (not a function of one encoded column and "
                "literals)")
        if not e.children:
            return e
        return e.with_children([self.lower(c) for c in e.children])

    # -- dictionary lowering ---------------------------------------------
    def _dict_lower_candidate(self, e: Expression) -> Optional[int]:
        """The single encoded ordinal this subtree is a function of, or
        None when it is not dict-lowerable (multiple columns, non-
        literal leaves, aggregates/windows/UDFs inside)."""
        from spark_rapids_tpu.exec.window import WindowExpression
        ords = set()
        ok = True

        def walk(x):
            nonlocal ok
            if isinstance(x, (AggregateExpression, WindowExpression)):
                ok = False
                return
            if type(x).__name__ in ("PythonUDF", "JaxUDF"):
                ok = False
                return
            if isinstance(x, BoundReference):
                if x.ordinal in self.enc:
                    ords.add(x.ordinal)
                else:
                    ok = False  # mixed with a non-encoded column
                return
            for c in x.children:
                walk(c)

        walk(e)
        if not ok or len(ords) != 1:
            return None
        if e.dtype.has_offsets and not e.dtype.is_string:
            return None
        if e.dtype.is_nested:
            return None
        return ords.pop()

    def _try_dict_lower(self, e: Expression) -> Optional[Expression]:
        """Evaluate ``e`` host-side over the dictionary of its single
        encoded column; return a DictLookup, or None."""
        ordinal = self._dict_lower_candidate(e)
        if ordinal is None:
            return None
        # device-supported subtrees evaluate via the engine's own emit;
        # CPU-fallback-only expressions (GetJsonObject, exotic regex...)
        # evaluate via the pandas fallback evaluator instead — either
        # way the work is O(K distinct values) on host
        use_pandas = False
        if self.conf is not None:
            from spark_rapids_tpu.plan.overrides import ExprMeta
            em = ExprMeta(e, self.conf)
            em.tag()
            use_pandas = not em.can_replace
        values = [v for v in self.enc[ordinal] if v is not None]
        k = len(values)
        codes = BoundReference(ordinal, dts.INT64, name=f"_c{ordinal}")

        def replace(x):
            if isinstance(x, BoundReference) and x.ordinal == ordinal:
                return BoundReference(0, x.dtype, name=x.name,
                                      nullable=False)
            if not x.children:
                return x
            return x.with_children([replace(c) for c in x.children])

        label = f"{type(e).__name__}(dict)"
        if k == 0:
            if e.dtype.is_string:
                return DictLookup(codes, np.zeros(0, np.int64),
                                  np.zeros(0, bool), dts.INT64,
                                  dict_values=[], label=label)
            return DictLookup(codes, np.zeros(0, e.dtype.storage),
                              np.zeros(0, bool), e.dtype, label=label)
        if use_pandas:
            import pandas as pd
            from spark_rapids_tpu.exec.fallback import _eval_pandas
            try:
                res = _eval_pandas(replace(e),
                                   pd.DataFrame({"_c": values}))
            except NotImplementedError:
                return None
            if e.dtype.is_string:
                strs = [None if pd.isna(r) else r for r in res]
                return self._string_lut(codes, strs, label)
            valid = res.notna().to_numpy()
            vals = res.fillna(0).to_numpy().astype(e.dtype.storage)
            return DictLookup(codes, vals, valid, e.dtype, label=label)
        col = Column.from_strings(values)
        cv = ColVal(dts.STRING, col.data, None, col.offsets)
        ctx = EmitContext([cv], jnp.int32(k), col.capacity)
        out = replace(e).emit(ctx)
        if e.dtype.is_string:
            res = Column(dts.STRING, out.values, k, validity=out.validity,
                         offsets=out.offsets).to_pylist()
            return self._string_lut(codes, res, label)
        vo = np.asarray(out.values)
        vals = np.broadcast_to(vo, (k,)) if vo.ndim == 0 else vo[:k]
        if out.validity is None:
            valid = np.ones(k, dtype=bool)
        else:
            vv = np.asarray(out.validity)
            valid = np.broadcast_to(vv, (k,)) if vv.ndim == 0 else vv[:k]
        return DictLookup(codes, vals.astype(e.dtype.storage), valid,
                          e.dtype, label=label)

    @staticmethod
    def _string_lut(codes, res, label):
        """Re-encode K string results against a fresh sorted dict."""
        new_dict = sorted({r for r in res if r is not None})
        lut = np.array(
            [bisect.bisect_left(new_dict, r) if r is not None else 0
             for r in res], dtype=np.int64)
        lut_valid = np.array([r is not None for r in res], dtype=bool)
        return DictLookup(codes, lut, lut_valid, dts.INT64,
                          dict_values=new_dict, label=label)

    def lower_agg(self, e: AggregateExpression) -> AggregateExpression:
        import copy
        from spark_rapids_tpu.ops import aggregates as agg
        func = e.func
        if func.child is None:
            return e
        if func.child.dtype.is_string and not isinstance(
                func, (agg.Min, agg.Max, agg.First, agg.Last)):
            raise NotDistributable(
                f"aggregate {func.name} over strings not supported on "
                "the mesh (only min/max/first/last are order/identity "
                "preserving under dictionary codes)")
        f2 = copy.copy(func)
        f2.child = self.lower(func.child)
        return AggregateExpression(f2)

    def encoded_ref(self, e: Expression):
        """The encoded BoundReference behind e (through one Alias)."""
        inner = e.children[0] if isinstance(e, Alias) else e
        if isinstance(inner, BoundReference) and inner.ordinal in self.enc:
            return inner
        return None

    def out_dict(self, lowered: Expression):
        """Dictionary of a LOWERED expression's output codes, if it has
        one (bare encoded ref pass-through, or a DictLookup re-encode)."""
        inner = lowered.children[0] if isinstance(lowered, Alias) \
            else lowered
        if isinstance(inner, BoundReference) and inner.ordinal in self.enc:
            return self.enc[inner.ordinal]
        if isinstance(inner, DictLookup) and inner.dict_values is not None:
            return inner.dict_values
        return None

    def _encoded_operand(self, e: Expression):
        """(codes_expr, sorted_values) for a string subtree with a code
        representation: a bare encoded ref, or a dict-lowerable function
        of one (substring(c_phone, 1, 2), concat(s, '_x'), ...)."""
        inner = e.children[0] if isinstance(e, Alias) else e
        if isinstance(inner, BoundReference) and inner.ordinal in self.enc:
            codes = BoundReference(inner.ordinal, dts.INT64,
                                   name=inner.name,
                                   nullable=inner.nullable)
            return codes, [v for v in self.enc[inner.ordinal]
                           if v is not None]
        if inner.dtype.is_string:
            d = self._try_dict_lower(inner)
            if d is not None and d.dict_values is not None:
                return d, d.dict_values
        return None

    def _ref_and_literal(self, e):
        l, r = e.children
        if isinstance(r, Literal) and not isinstance(l, Literal):
            return l, r, False
        if isinstance(l, Literal) and not isinstance(r, Literal):
            return r, l, True
        return None

    def _lower_cmp(self, e):
        pair = self._ref_and_literal(e)
        op = self._encoded_operand(pair[0]) if pair else None
        if pair is None or op is None or \
                not isinstance(pair[1].value, str):
            d = self._try_dict_lower(e)
            if d is not None:
                return d
            raise NotDistributable(
                f"string comparison {e} is not (encoded expression vs "
                "literal); no code-space lowering")
        _, lit, flipped = pair
        codes, values = op
        cls = type(e)
        if flipped:  # lit OP ref  ->  ref OP' lit
            cls = {preds.LessThan: preds.GreaterThan,
                   preds.LessThanOrEqual: preds.GreaterThanOrEqual,
                   preds.GreaterThan: preds.LessThan,
                   preds.GreaterThanOrEqual: preds.LessThanOrEqual,
                   preds.EqualTo: preds.EqualTo}[cls]
        s = lit.value
        if cls is preds.EqualTo:
            i = bisect.bisect_left(values, s)
            code = i if i < len(values) and values[i] == s else -1
            return preds.EqualTo(codes, Literal(np.int64(code), dts.INT64))
        lo = bisect.bisect_left(values, s)
        hi = bisect.bisect_right(values, s)
        if cls is preds.LessThan:        # code < first index >= s
            return preds.LessThan(codes, Literal(np.int64(lo), dts.INT64))
        if cls is preds.LessThanOrEqual:  # code < first index > s
            return preds.LessThan(codes, Literal(np.int64(hi), dts.INT64))
        if cls is preds.GreaterThan:
            return preds.GreaterThanOrEqual(
                codes, Literal(np.int64(hi), dts.INT64))
        return preds.GreaterThanOrEqual(
            codes, Literal(np.int64(lo), dts.INT64))

    def _lower_in(self, e: preds.In):
        op = self._encoded_operand(e.children[0])
        opts = e.children[1:]
        if op is None or not all(
                isinstance(o, Literal) and isinstance(o.value, str)
                for o in opts):
            d = self._try_dict_lower(e)
            if d is not None:
                return d
            raise NotDistributable(
                "string IN is only supported as an encoded expression "
                "IN (literals...) on the mesh")
        codes, values = op
        hits = []
        for o in opts:
            i = bisect.bisect_left(values, o.value)
            if i < len(values) and values[i] == o.value:
                hits.append(Literal(np.int64(i), dts.INT64))
        if not hits:
            hits = [Literal(np.int64(-1), dts.INT64)]
        return preds.In(codes, hits)


def _check_supported(exprs: Sequence[Expression], conf) -> None:
    """Run the single-process support tagging over the LOWERED (numeric)
    expressions so per-op disables and TypeSig checks apply on the mesh
    too (RapidsMeta tagging, reused)."""
    from spark_rapids_tpu.plan.overrides import ExprMeta, _deep_reasons
    for e in exprs:
        em = ExprMeta(e, conf)
        em.tag()
        if not em.can_replace:
            raise NotDistributable(
                f"expression {type(e).__name__}: "
                + "; ".join(_deep_reasons(em)))


# ------------------------------------------------------- kernel wrappers --

def _mesh_sig(mesh):
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(str(d) for d in mesh.devices.flat))


def _ones_like_validity(c: ColVal, cap: int):
    return c.validity if c.validity is not None else \
        jnp.ones(cap, dtype=jnp.bool_)


def _run_project(f: ShardedFrame, exprs: Sequence[Expression], tag: str):
    """Compiled shard_map projection; returns the output column pairs."""
    import jax
    from spark_rapids_tpu.ops.aggregates import widen_colval
    from spark_rapids_tpu.ops.jit_cache import cached_jit
    phys = f.phys_dtypes

    def step(flat_cols, nrows_arr):
        nrows = nrows_arr[0]
        cols = [ColVal(dt, v, val)
                for (v, val), dt in zip(flat_cols, phys)]
        cap = cols[0].values.shape[0]
        ctx = EmitContext(cols, nrows, cap)
        outs = [widen_colval(e.emit(ctx), cap) for e in exprs]
        return tuple((c.values, _ones_like_validity(c, cap))
                     for c in outs)

    sig = (tag, _mesh_sig(f.mesh), tuple(dt.name for dt in phys),
           tuple(e.cache_key() for e in exprs))
    axis = f.mesh.axis_names[0]
    return cached_jit(sig, lambda: _shard_map(
        step, mesh=f.mesh, in_specs=(P(axis), P(axis)),
        out_specs=P(axis), check_vma=False))(f.cols, f.nrows)


def _run_expand(f: ShardedFrame, projections, out_phys):
    """Compiled shard_map Expand: K projection replicas per shard,
    compacted to the shard's live prefix via a replica/row gather — no
    exchange, capacity grows by K."""
    import jax
    from spark_rapids_tpu.ops.aggregates import widen_colval
    from spark_rapids_tpu.ops.jit_cache import cached_jit
    phys = f.phys_dtypes
    K = len(projections)

    def step(flat_cols, nrows_arr):
        nrows = nrows_arr[0]
        cols = [ColVal(dt, v, val)
                for (v, val), dt in zip(flat_cols, phys)]
        cap = cols[0].values.shape[0]
        ctx = EmitContext(cols, nrows, cap)
        out_cap = cap * K
        idx = jnp.arange(out_cap, dtype=jnp.int32)
        n = jnp.maximum(nrows, 1)
        rep = jnp.minimum(idx // n, K - 1)
        row = jnp.minimum(idx % n, cap - 1)
        outs = []
        for j, dt in enumerate(out_phys):
            stacked_v, stacked_m = [], []
            for proj in projections:
                c = widen_colval(proj[j].emit(ctx), cap)
                stacked_v.append(c.values.astype(dt.storage))
                stacked_m.append(_ones_like_validity(c, cap))
            sv = jnp.stack(stacked_v)   # (K, cap)
            sm = jnp.stack(stacked_m)
            outs.append((sv[rep, row], sm[rep, row]))
        return tuple(outs), (nrows * K).astype(jnp.int32)[None]

    sig = ("dplan_expand", _mesh_sig(f.mesh),
           tuple(dt.name for dt in phys),
           tuple(tuple(e.cache_key() for e in p) for p in projections))
    axis = f.mesh.axis_names[0]
    cols, nrows = cached_jit(sig, lambda: _shard_map(
        step, mesh=f.mesh, in_specs=(P(axis), P(axis)),
        out_specs=P(axis), check_vma=False))(f.cols, f.nrows)
    return cols, nrows.reshape(-1)


def _run_union(child_frames, out_phys, mesh):
    """Compiled shard_map Union: shard i concatenates its slices of
    every child's columns (live prefixes back to back) — no exchange."""
    import jax
    from spark_rapids_tpu.ops.jit_cache import cached_jit

    def step(*args):
        col_sets, nrow_arrs = args[0::2], args[1::2]
        caps = [cs[0][0].shape[0] for cs in col_sets]
        out_cap = sum(caps)
        ns = [a[0] for a in nrow_arrs]
        total = sum(ns)
        idx = jnp.arange(out_cap, dtype=jnp.int32)
        outs = []
        for j, dt in enumerate(out_phys):
            v = jnp.zeros(out_cap, dtype=dt.storage)
            m = jnp.zeros(out_cap, dtype=jnp.bool_)
            at = jnp.int32(0)
            for cs, n, cap in zip(col_sets, ns, caps):
                cv, cm = cs[j]
                src_pos = idx - at
                take = (src_pos >= 0) & (src_pos < n)
                safe = jnp.clip(src_pos, 0, cap - 1)
                v = jnp.where(take, cv.astype(dt.storage)[safe], v)
                m = jnp.where(take, cm[safe], m)
                at = at + n
            outs.append((v, m))
        return tuple(outs), total.astype(jnp.int32)[None]

    sig = ("dplan_union", _mesh_sig(mesh),
           tuple(dt.name for dt in out_phys),
           tuple(int(cf[0][0][0].shape[0]) for cf in child_frames))
    axis = mesh.axis_names[0]
    ins = []
    for cols, nrows in child_frames:
        ins.append(tuple(cols))
        ins.append(nrows)
    in_specs = tuple(P(axis) for _ in ins)
    cols, nrows = cached_jit(sig, lambda: _shard_map(
        step, mesh=mesh, in_specs=in_specs,
        out_specs=P(axis), check_vma=False))(*ins)
    return cols, nrows.reshape(-1)


def _run_slice(f: ShardedFrame, los, his):
    """Compiled shard_map row slice: each shard keeps its live rows in
    [lo, hi), compacted to the prefix (probe-side chunking for the
    chunked join emission)."""
    import jax
    from spark_rapids_tpu.ops.jit_cache import cached_jit

    def step(flat_cols, lo_arr, hi_arr):
        lo, hi = lo_arr[0], hi_arr[0]
        cap = flat_cols[0][0].shape[0]
        idx = jnp.arange(cap, dtype=jnp.int32) + lo
        safe = jnp.clip(idx, 0, cap - 1)
        outs = tuple((v[safe], m[safe]) for v, m in flat_cols)
        n = jnp.maximum(hi - lo, 0)
        return outs, n.astype(jnp.int32)[None]

    sig = ("dplan_slice", _mesh_sig(f.mesh),
           tuple(dt.name for dt in f.phys_dtypes))
    axis = f.mesh.axis_names[0]
    return cached_jit(sig, lambda: _shard_map(
        step, mesh=f.mesh, in_specs=(P(axis), P(axis), P(axis)),
        out_specs=P(axis), check_vma=False))(
        f.cols, mesh_lib.host_put(f.mesh, np.asarray(los, np.int32)),
        mesh_lib.host_put(f.mesh, np.asarray(his, np.int32)))


def _run_fused(f: ShardedFrame, exprs: Sequence[Expression],
               conds: Sequence[Expression]):
    """Compiled shard_map for a FUSED Filter/Project chain: every
    member's expressions evaluate in ONE computation — the member
    predicates (bottom-first) AND into a row mask carried inside the
    trace (each conjunct's ANSI checks masked by the conjuncts below
    it, the FilterStageFn discipline), projections stay in registers,
    and the selection compacts once at the stage boundary.  One
    dispatch per chain instead of one per member (exec/fusion.py; the
    distributed face of whole-stage fusion)."""
    import jax
    from spark_rapids_tpu.ops import selection
    from spark_rapids_tpu.ops.aggregates import widen_colval
    from spark_rapids_tpu.ops.jit_cache import cached_jit
    phys = f.phys_dtypes

    def step(flat_cols, nrows_arr):
        nrows = nrows_arr[0]
        cols = [ColVal(dt, v, val)
                for (v, val), dt in zip(flat_cols, phys)]
        cap = cols[0].values.shape[0]
        ctx = EmitContext(cols, nrows, cap)
        keep = None
        if conds:
            from spark_rapids_tpu.ops.expressions import fold_conjuncts
            # leaves the ANSI check mask at the survivor set for the
            # projections below (expressions.fold_conjuncts)
            keep = fold_conjuncts(ctx, conds)
        outs = [widen_colval(e.emit(ctx), cap) for e in exprs]
        if keep is None:
            return (tuple((c.values, _ones_like_validity(c, cap))
                          for c in outs),
                    nrows.astype(jnp.int32)[None])
        compacted, n = selection.compact(outs, keep)
        return (tuple((c.values, _ones_like_validity(c, cap))
                      for c in compacted),
                n.astype(jnp.int32)[None])

    sig = ("dplan_fused", _mesh_sig(f.mesh),
           tuple(dt.name for dt in phys),
           tuple(e.cache_key() for e in exprs),
           tuple(c.cache_key() for c in conds))
    axis = f.mesh.axis_names[0]
    cols, nrows = cached_jit(sig, lambda: _shard_map(
        step, mesh=f.mesh, in_specs=(P(axis), P(axis)),
        out_specs=P(axis), check_vma=False))(f.cols, f.nrows)
    return cols, nrows.reshape(-1)


def _run_filter(f: ShardedFrame, cond: Expression):
    import jax
    from spark_rapids_tpu.ops import selection
    from spark_rapids_tpu.ops.jit_cache import cached_jit
    phys = f.phys_dtypes

    def step(flat_cols, nrows_arr):
        nrows = nrows_arr[0]
        cols = [ColVal(dt, v, val)
                for (v, val), dt in zip(flat_cols, phys)]
        cap = cols[0].values.shape[0]
        ctx = EmitContext(cols, nrows, cap)
        pred = cond.emit(ctx)
        keep = pred.values
        if pred.validity is not None:
            keep = jnp.logical_and(keep, pred.validity)
        keep = jnp.logical_and(keep, ctx.row_mask())
        out, n = selection.compact(cols, keep)
        return (tuple((c.values, _ones_like_validity(c, cap))
                      for c in out),
                n.astype(jnp.int32)[None])

    sig = ("dplan_filter", _mesh_sig(f.mesh),
           tuple(dt.name for dt in phys), cond.cache_key())
    axis = f.mesh.axis_names[0]
    return cached_jit(sig, lambda: _shard_map(
        step, mesh=f.mesh, in_specs=(P(axis), P(axis)),
        out_specs=P(axis), check_vma=False))(f.cols, f.nrows)


def _append_key_cols(f: ShardedFrame, key_exprs) -> ShardedFrame:
    """Materialize key expressions as trailing columns (one compiled
    projection), so join kernels take plain column indices."""
    key_cols = _run_project(f, list(key_exprs), "dplan_keys")
    return ShardedFrame(
        f.mesh, f.names + [f"__k{i}" for i in range(len(key_exprs))],
        f.log_dtypes + [e.dtype for e in key_exprs],
        list(f.cols) + list(key_cols), f.nrows, f.enc)


# ---------------------------------------------------------------- planner --

class DistPlanner:
    """Eager recursive executor with a dry pre-flight mode."""

    # global cap on a distributed join's output buffer (rows across all
    # shards); beyond this the planner falls back rather than allocate
    MAX_OUT_ROWS = 1 << 27

    # exchange-consuming operators: their completed output is a stage
    # boundary the lineage log may checkpoint (robustness/checkpoint.py)
    _STAGE_OPS = None  # built lazily (L.Window import order)

    def __init__(self, session, mesh, resume: bool = False):
        self.session = session
        self.mesh = mesh
        self.conf = session.conf
        # wire-bytes watermark for this query: collect() stamps the
        # output batch with the exchange payload footprint recorded
        # between here and the final materialization (the transient-2x
        # HBM accounting, memory/spill.py SpillableHandle.wire_bytes)
        from spark_rapids_tpu.parallel.shuffle import (
            metrics_for_session, packed_enabled)
        self._wire0 = metrics_for_session(session).snapshot()
        # stage-checkpoint lineage: the per-query manager the driver
        # installed on the session (None when disabled / no catalog);
        # resume=True only on a retry-class re-attempt — the first
        # attempt never restores, it only writes.  A session-persistent
        # store (robustness/incremental.py) sets always_resume: its
        # input-fingerprinted stage ids are safe to splice across
        # queries, so continuous-ingest ticks restore on attempt one
        self._ckpt = getattr(session, "checkpoints", None)
        self._resume = self._ckpt is not None and self._ckpt.enabled \
            and (bool(resume) or
                 getattr(self._ckpt, "always_resume", False))
        # input fingerprints are folded into stage ids only for a
        # session-persistent store (cross-query splice needs input
        # identity); the per-query manager skips the stat walk — its
        # keys only need intra-query stability.  The memo caches each
        # scan node's walk for one planner run (inputs cannot change
        # mid-attempt)
        self._fp_inputs = getattr(self._ckpt, "always_resume", False)
        self._fp_memo: Dict[int, str] = {}
        self._packed = packed_enabled()
        # whole-stage fusion (exec/fusion.py, the distributed face):
        # Filter/Project chains — and the chain feeding an Aggregate —
        # collapse into one shard_map dispatch.  Never across an
        # exchange: fusion happens strictly BELOW the stage boundaries
        # the checkpoint lineage keys on, so stage_ids are untouched.
        from spark_rapids_tpu.config import rapids_conf as _rc
        self._fusion = bool(self.conf.get(_rc.FUSION_ENABLED))
        self._fusion_max = int(self.conf.get(_rc.FUSION_MAX_OPS))
        from spark_rapids_tpu.plan.costmodel import active_model
        # THIS session's model (or None), passed explicitly to every
        # consumer this planner constructs: a concurrent session
        # flipping TpuSession._active mid-query must never leak its
        # model into (or out of) this query's plan
        self._cost_model = active_model(session)
        if self._cost_model is not None:
            # self-tuning planner: one fusion-boundary decision shared
            # with the single-process planner (conf stays an override)
            self._fusion_max = self._cost_model.fusion_chain_limit()
        # async exchange/compute overlap (parallel/exchange_async.py):
        # exchange-bearing launches admit a handle into this bounded
        # window instead of blocking on their post-launch verification;
        # handles resolve at the next stage boundary (checkpoint save,
        # the next exchange under byte pressure, collect).  OFF on
        # recovery re-attempts (resume=True): a re-driven attempt runs
        # the synchronous path — AsyncExchangeOverflow's contract
        self._xwindow = None
        if self.conf.get(_rc.EXCHANGE_ASYNC_ENABLED) and not resume:
            from spark_rapids_tpu.parallel import exchange_async as _xa
            self._xwindow = _xa.ExchangeWindow(
                int(self.conf.get(_rc.EXCHANGE_INFLIGHT_WINDOW_BYTES)),
                metrics=_xa.overlap_metrics_for_session(session))
        self.fusion: Dict[str, int] = {
            "enabled": self._fusion, "fusedStages": 0,
            "fusedOperators": 0, "dispatchesSaved": 0,
            "fusibleChains": 0, "fallbacks": 0}
        # chain members already counted as fusible (when fusion is off
        # the members still convert one-by-one — the inner run must not
        # re-count as its own, shorter chain)
        self._counted_chain: set = set()

    @classmethod
    def _stage_ops(cls):
        if cls._STAGE_OPS is None:
            cls._STAGE_OPS = (L.Aggregate, L.Join, L.Sort, L.Window)
        return cls._STAGE_OPS

    def _checkpointable(self, plan: L.LogicalPlan) -> bool:
        """Stage boundaries worth checkpointing: every exchange
        consumer, plus top-N (a Limit over a Sort lowers into one
        distributed pass of its own)."""
        if isinstance(plan, self._stage_ops()):
            return True
        return isinstance(plan, L.Limit) and \
            isinstance(plan.child, L.Sort)

    def _count_stages(self, plan: L.LogicalPlan) -> int:
        """Exchange stages inside a subtree — what a resume of this
        checkpoint saves (CheckpointResume.stagesSaved)."""
        n = 1 if self._checkpointable(plan) else 0
        return n + sum(self._count_stages(c) for c in plan.children)

    def _emit_stats(self, op: str, stats, **extra) -> None:
        ev = getattr(self.session, "events", None)
        if ev is not None and ev.enabled and stats:
            clean = {k: v.tolist() if hasattr(v, "tolist") else v
                     for k, v in stats.items()}
            ev.emit("DistExchange", op=op, stats=clean, **extra)

    # -- recursion --------------------------------------------------------
    def run(self, plan: L.LogicalPlan, dry: bool) -> ShardedFrame:
        """Execute (or dry-run) one subtree, splicing in / registering
        stage checkpoints at exchange boundaries: on a resume attempt a
        completed subtree restores from the lineage log — its readers,
        stages, and collectives never run — and every freshly completed
        exchange stage registers its post-shuffle frame for the next
        attempt.  A checkpoint that fails verification or was evicted
        is dropped by the manager and the subtree re-runs here."""
        if not dry and self._checkpointable(plan):
            # fair-interleaver stage boundary: a distributed query's
            # "batches" are its exchange stages — gate here so a
            # heavy multi-stage query yields the mesh to co-tenants
            # between stages (serving/scheduler.py; no-op when the
            # interleave knob is off or no ticket is registered)
            from spark_rapids_tpu.serving.scheduler import \
                yield_current
            yield_current(self.session)
        if dry or self._ckpt is None or not self._ckpt.enabled or \
                not self._checkpointable(plan):
            return self._dispatch(plan, dry)
        from spark_rapids_tpu.robustness import checkpoint as cp
        from spark_rapids_tpu.utils import tracing
        sid = cp.stage_id(plan, self.mesh, self._packed,
                          memo=self._fp_memo, inputs=self._fp_inputs)
        if self._resume:
            frame = self._ckpt.restore(sid, self.mesh)
            if frame is not None:
                return frame
        if tracing._active:
            # per-stage span keyed by the structural stage id: nested
            # stages subtract, so the rollup's per-site exclusive time
            # is each exchange stage's own cost — and the observation
            # store gets span_ms evidence under the same id the
            # checkpoint/jit machinery uses
            with tracing.span("stage.dist", site=sid,
                              op=type(plan).__name__):
                frame = self._dispatch(plan, dry)
        else:
            frame = self._dispatch(plan, dry)
        # async-exchange barrier BEFORE the checkpoint write: a frame
        # with an unverified speculative slot must never enter the
        # lineage log (a later resume would splice truncated bytes —
        # the one wrong-results hole the deferred overflow check opens)
        if self._xwindow is not None:
            self._xwindow.resolve_all()
        # shareable hint: a sid whose fingerprint folds ONLY file
        # triples (no id()-keyed in-memory batches) is derivable by
        # any query holding the identical subtree — the epoch-aware
        # shared tier publishes exactly those at commit.  Only
        # meaningful under input-fingerprinted ids (always_resume
        # stores); the walk is cheap (node count) and saves are rare.
        self._ckpt.save(sid, frame, stages=self._count_stages(plan),
                        shareable=self._fp_inputs and
                        not self._has_mem_relation(plan))
        return frame

    @staticmethod
    def _has_mem_relation(plan: L.LogicalPlan) -> bool:
        if isinstance(plan, L.InMemoryRelation):
            return True
        return any(DistPlanner._has_mem_relation(c)
                   for c in plan.children)

    def _dispatch(self, plan: L.LogicalPlan, dry: bool) -> ShardedFrame:
        if isinstance(plan, (L.InMemoryRelation, L.FileRelation, L.Range)):
            return self._scan(plan, dry)
        if isinstance(plan, (L.Filter, L.Project)):
            fused = self._fused_chain(plan, dry)
            if fused is not None:
                return fused
        if isinstance(plan, L.Filter):
            return self._filter(plan, dry)
        if isinstance(plan, L.Project):
            return self._project(plan, dry)
        if isinstance(plan, L.Aggregate):
            return self._aggregate(plan, dry)
        if isinstance(plan, L.Join):
            return self._join(plan, dry)
        if isinstance(plan, L.Sort):
            return self._sort(plan, dry)
        if isinstance(plan, L.Limit):
            if isinstance(plan.child, L.Sort):
                return self._topn(plan, dry)
            return self._limit(plan, dry)
        if isinstance(plan, L.Window):
            return self._window(plan, dry)
        if isinstance(plan, L.Union):
            return self._union(plan, dry)
        from spark_rapids_tpu.exec.expand import Expand as _Expand
        if isinstance(plan, _Expand):
            return self._expand(plan, dry)
        if isinstance(plan, L.Generate):
            # explode/posexplode: array columns have no mesh encoding
            # yet, so the generate itself runs on the controller as a
            # materialize barrier — but its OUTPUT is flat, and the
            # post-explode pipeline (where row counts are largest) still
            # distributes.  _scan executes the subtree single-process
            # and scatters row blocks (GpuGenerateExec stays an
            # exchange producer in the reference too).
            return self._scan(plan, dry)
        raise NotDistributable(
            f"{type(plan).__name__} has no distributed lowering")

    # -- scan -------------------------------------------------------------
    def _scan(self, plan: L.LogicalPlan, dry: bool) -> ShardedFrame:
        schema = list(plan.schema)
        for name, dt in schema:
            if not dt.is_string and (dt.has_offsets or dt.is_nested):
                raise NotDistributable(
                    f"scan column {name!r} of type {dt} not supported "
                    "on the mesh")
        names = [n for n, _ in schema]
        log_dtypes = [dt for _, dt in schema]
        if dry:
            enc = {i: [] for i, dt in enumerate(log_dtypes)
                   if dt.is_string}
            return ShardedFrame(self.mesh, names, log_dtypes, None, None,
                                enc)
        if isinstance(plan, L.FileRelation) and \
                plan.file_format in ("parquet", "orc"):
            try:
                return self._scan_sharded_files(plan, schema)
            except _UnsplittableScan:
                pass
        from spark_rapids_tpu.ops.concat import concat_batches
        from spark_rapids_tpu.ops.dictionary import ordered_dict_encode
        exec_plan = self.session.plan(plan)
        batches = list(exec_plan.execute())
        nshards = self.mesh.devices.size
        merged = concat_batches(batches) if batches else None
        total = merged.nrows if merged is not None else 0
        cap = bucket_capacity(max((total + nshards - 1) // nshards, 1),
                              minimum=8)
        base, rem = divmod(total, nshards)
        counts = np.array([base + (1 if i < rem else 0)
                           for i in range(nshards)], dtype=np.int32)
        offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
        cols, enc = [], {}
        for i, (name, dt) in enumerate(schema):
            if merged is None:
                host = np.zeros(0, dtype=_phys(dt).storage)
                valid = np.ones(0, dtype=bool)
                if dt.is_string:
                    enc[i] = []
            else:
                col = merged.columns[name]
                valid = col.validity_numpy()
                if dt.is_string:
                    host, enc[i] = ordered_dict_encode(col)
                else:
                    host = col.host_values()[:total]
            vbuf = np.zeros((nshards, cap),
                            dtype=host.dtype if host.size
                            else _phys(dt).storage)
            mbuf = np.zeros((nshards, cap), dtype=bool)
            for s in range(nshards):
                sl = slice(offsets[s], offsets[s] + counts[s])
                vbuf[s, :counts[s]] = host[sl]
                mbuf[s, :counts[s]] = valid[sl]
            # host_put, not jnp.asarray: under a multi-controller mesh
            # every process executed the identical scan above, so each
            # contributes its addressable shards of the SAME global
            # buffer (single-controller this IS jnp.asarray)
            cols.append((mesh_lib.host_put(self.mesh, vbuf.reshape(-1)),
                         mesh_lib.host_put(self.mesh, mbuf.reshape(-1))))
        return ShardedFrame(self.mesh, names, log_dtypes, cols,
                            mesh_lib.host_put(self.mesh, counts), enc)

    def _scan_sharded_files(self, plan, schema) -> ShardedFrame:
        """Genuinely distributed scan: the FILE LIST is sharded across
        the mesh (greedy by per-file row counts from parquet/orc footer
        metadata) and each shard's split is read, encoded, and placed on
        its device one shard at a time — the controller never holds more
        than one shard's rows (the GpuMultiFileReader.scala:300 /
        GpuParquetScan.scala:973-1199 role: every task reads its own
        split).  Single-controller only for now: under multi-process
        JAX the per-host split read is not yet implemented, so the scan
        falls back instead of device_put-ing to a non-addressable
        device.

        String columns encode through a SHARED first-seen dictionary per
        column while reading, then remap on device to the sorted
        order-preserving codes the rest of the engine expects."""
        from spark_rapids_tpu.io.readers import _dataset
        from spark_rapids_tpu.ops.dictionary import dict_encode_stable
        from spark_rapids_tpu.utils import hostsync, tracing
        nshards = self.mesh.devices.size
        devices = self.mesh.devices.reshape(-1)
        axis = self.mesh.axis_names[0]
        if jax.process_count() > 1 or any(
                d.process_index != jax.process_index() for d in devices):
            raise _UnsplittableScan("multi-process mesh")

        dataset = _dataset(plan.paths, plan.file_format)
        files = list(getattr(dataset, "files", None) or [])
        if not files:
            raise _UnsplittableScan("no listable files")
        bounds = [_file_row_bound(f, plan.file_format) for f in files]
        if any(b is None for b in bounds):
            raise _UnsplittableScan("row bounds unavailable")

        # greedy longest-first assignment of files to shards
        order = sorted(range(len(files)), key=lambda i: -bounds[i])
        shard_files: List[List[str]] = [[] for _ in range(nshards)]
        shard_bound = np.zeros(nshards, dtype=np.int64)
        for i in order:
            s = int(np.argmin(shard_bound))
            shard_files[s].append(files[i])
            shard_bound[s] += bounds[i]
        cap = bucket_capacity(max(int(shard_bound.max()), 1), minimum=8)

        names = [n for n, _ in schema]
        log_dtypes = [dt for _, dt in schema]
        str_idx = [i for i, dt in enumerate(log_dtypes) if dt.is_string]
        dicts = {i: ({}, []) for i in str_idx}  # codes, values
        counts = np.zeros(nshards, dtype=np.int32)
        peak_host_rows = 0
        # per column, the per-shard single-device buffers
        shard_bufs: List[List] = [[] for _ in range(2 * len(schema))]

        for s in range(nshards):
            if shard_files[s]:
                sub = L.FileRelation(shard_files[s], plan.file_format,
                                     plan._schema, plan.options,
                                     plan.bucket_spec)
                sub.pushed_filters = list(plan.pushed_filters)
                sub.required_columns = plan.required_columns
                sub.file_meta = set(plan.file_meta)
                batches = list(self.session.plan(
                    sub, pushdown=False).execute())
                rows = sum(b.nrows for b in batches)
            else:
                batches, rows = [], 0
            if rows > cap:
                raise _UnsplittableScan("row bound exceeded")
            counts[s] = rows
            peak_host_rows = max(peak_host_rows, rows)
            for i, (name, dt) in enumerate(schema):
                vbuf = np.zeros(cap, dtype=_phys(dt).storage)
                mbuf = np.zeros(cap, dtype=bool)
                at = 0
                for b in batches:
                    col = b.columns[name]
                    nb = col.nrows
                    if dt.is_string:
                        codes, values = dicts[i]
                        vbuf[at:at + nb] = dict_encode_stable(
                            col, codes, values, null_code=0)
                    else:
                        vbuf[at:at + nb] = col.host_values()[:nb]
                    mbuf[at:at + nb] = col.validity_numpy()
                    at += nb
                dev = devices[s]
                with tracing.span("upload.h2d"):
                    shard_bufs[2 * i].append(hostsync.upload(vbuf, dev))
                    shard_bufs[2 * i + 1].append(
                        hostsync.upload(mbuf, dev, validity=True))
            del batches  # host copies of this shard are done

        sharding = NamedSharding(self.mesh, P(axis))
        gshape = (nshards * cap,)
        cols, enc = [], {}
        for i, (name, dt) in enumerate(schema):
            vals = jax.make_array_from_single_device_arrays(
                gshape, sharding, shard_bufs[2 * i])
            mask = jax.make_array_from_single_device_arrays(
                gshape, sharding, shard_bufs[2 * i + 1])
            if dt.is_string:
                codes_map, values = dicts[i]
                if values:
                    # remap first-seen codes -> sorted order-preserving
                    order_v = np.argsort(
                        np.array(values, dtype=object), kind="stable")
                    rank = np.empty(len(values), dtype=np.int64)
                    rank[order_v] = np.arange(len(values))
                    vals = _remap_codes(jnp.asarray(rank), vals)
                    enc[i] = [values[j] for j in order_v]
                else:
                    enc[i] = []
            cols.append((vals, mask))
        self.session.last_scan_stats = {
            "sharded_files": True, "files": len(files),
            "peak_host_rows": int(peak_host_rows),
            "total_rows": int(counts.sum())}
        return ShardedFrame(self.mesh, names, log_dtypes, cols,
                            jnp.asarray(counts), enc)

    # -- filter / project / fused chains ---------------------------------
    def _filter(self, plan: L.Filter, dry: bool) -> ShardedFrame:
        return self._filter_frame(self.run(plan.child, dry), plan, dry)

    def _filter_frame(self, f: ShardedFrame, plan: L.Filter,
                      dry: bool) -> ShardedFrame:
        low = ExprLowering(f.enc, self.conf)
        cond = low.lower(plan.condition)
        _check_supported([cond], self.conf)
        if dry:
            return f
        out_cols, nrows = _run_filter(f, cond)
        return f.replace(cols=list(out_cols), nrows=nrows)

    def _project(self, plan: L.Project, dry: bool) -> ShardedFrame:
        return self._project_frame(self.run(plan.child, dry), plan, dry)

    def _project_frame(self, f: ShardedFrame, plan: L.Project,
                       dry: bool) -> ShardedFrame:
        low = ExprLowering(f.enc, self.conf)
        exprs, enc = [], {}
        for i, e in enumerate(plan.exprs):
            le = low.lower(e)
            exprs.append(le)
            d = low.out_dict(le)
            if d is not None:
                enc[i] = d
        _check_supported(exprs, self.conf)
        names = [n for n, _ in plan.schema]
        log_dtypes = [dt for _, dt in plan.schema]
        if dry:
            return ShardedFrame(self.mesh, names, log_dtypes, None, None,
                                enc)
        out_cols = _run_project(f, exprs, "dplan_project")
        return ShardedFrame(self.mesh, names, log_dtypes, list(out_cols),
                            f.nrows, enc)

    def _chain_members(self, plan: L.LogicalPlan):
        """Maximal Filter/Project run starting at ``plan`` (top-down)
        and the tail node feeding it."""
        members: List[L.LogicalPlan] = []
        node = plan
        while isinstance(node, (L.Filter, L.Project)) and \
                len(members) < self._fusion_max:
            members.append(node)
            node = node.child
        return members, node

    def _replay_members(self, f: ShardedFrame, members,
                        dry: bool) -> ShardedFrame:
        """Unfused fallback: apply the chain member-by-member over the
        already-computed tail frame (the tail never re-runs).  The
        replay is per-shard re-execution with no collective inside, so
        it is hedge-eligible: when the mesh spans a SUSPECT host and
        gray failure is armed, an overrunning replay re-dispatches on
        the healthy ``dist.member_replay.hedge`` path and the first
        result wins (robustness/grayfailure.py)."""
        def _replay():
            from spark_rapids_tpu.robustness import grayfailure, watchdog
            from spark_rapids_tpu.robustness.inject import fire
            out = f
            point = grayfailure.hedge_point("dist.member_replay")
            with watchdog.section(point, session=self.session):
                if not dry:
                    fire(point)
                for node in reversed(members):
                    if isinstance(node, L.Filter):
                        out = self._filter_frame(out, node, dry)
                    else:
                        out = self._project_frame(out, node, dry)
            return out

        if dry:
            return _replay()
        from spark_rapids_tpu.robustness import grayfailure
        suspect = grayfailure.suspect_host_in(self.session, self.mesh)
        return grayfailure.hedged_call(
            self.session, "dist.member_replay", suspect, _replay)

    def _fused_chain(self, plan: L.LogicalPlan,
                     dry: bool) -> Optional[ShardedFrame]:
        """Collapse a Filter/Project chain into one shard_map dispatch;
        None when there is no chain (single member) — a member the
        composed lowering cannot ingest falls back to per-member
        execution over the same tail frame."""
        from spark_rapids_tpu.exec.fusion import compose_chain
        members, tail = self._chain_members(plan)
        if len(members) < 2:
            return None
        if not dry and id(plan) not in self._counted_chain:
            self.fusion["fusibleChains"] += 1
            self._counted_chain.update(id(m) for m in members)
        if not self._fusion:
            return None
        f = self.run(tail, dry)
        exprs, conds = None, []
        for node in members:
            exprs, conds = compose_chain(exprs, conds, node, node.schema)
        try:
            frame = self._fused_frame(f, exprs, conds, plan, dry)
        except NotDistributable:
            if not dry:
                self.fusion["fallbacks"] += 1
            return self._replay_members(f, members, dry)
        if not dry:
            self.fusion["fusedStages"] += 1
            self.fusion["fusedOperators"] += len(members)
            self.fusion["dispatchesSaved"] += len(members) - 1
        return frame

    def _fused_frame(self, f: ShardedFrame, exprs, conds, plan,
                     dry: bool) -> ShardedFrame:
        low = ExprLowering(f.enc, self.conf)
        lexprs, enc = [], {}
        for i, e in enumerate(exprs):
            le = low.lower(e)
            lexprs.append(le)
            d = low.out_dict(le)
            if d is not None:
                enc[i] = d
        lconds = [low.lower(c) for c in conds]
        _check_supported(lexprs + lconds, self.conf)
        names = [n for n, _ in plan.schema]
        log_dtypes = [dt for _, dt in plan.schema]
        if dry:
            return ShardedFrame(self.mesh, names, log_dtypes, None, None,
                                enc)
        out_cols, nrows = _run_fused(f, lexprs, lconds)
        return ShardedFrame(self.mesh, names, log_dtypes, list(out_cols),
                            nrows, enc)

    # -- aggregate --------------------------------------------------------
    def _aggregate(self, plan: L.Aggregate, dry: bool) -> ShardedFrame:
        """Aggregate, with the PRE-SHUFFLE fusion fold: a Filter/Project
        chain under the Aggregate composes into the aggregation kernel
        itself (projections substitute into key/agg expressions, the
        combined predicate rides as DistributedAggregate's filter_cond
        row mask) — filter, project, partial aggregate AND the
        partition-id computation all launch as ONE program per shard.
        A chain the composed lowering cannot ingest replays unfused
        over the same tail frame."""
        members, tail = self._chain_members(plan.child)
        if members and not self._fusion:
            # A/B baseline: the chain (even a single member — the
            # aggregate fold would absorb it) ran unfused; count it for
            # the health check and keep the members from re-counting as
            # their own chain during the per-op dispatch below
            if not dry and id(plan.child) not in self._counted_chain:
                self.fusion["fusibleChains"] += 1
                self._counted_chain.update(id(m) for m in members)
            members = []
        if not members:
            return self._aggregate_frame(
                plan, self.run(plan.child, dry), plan.group_exprs,
                plan.agg_exprs, None, dry)
        from spark_rapids_tpu.exec.fusion import compose_chain
        from spark_rapids_tpu.ops.expressions import substitute_bound
        if not dry:
            self.fusion["fusibleChains"] += 1
        exprs, conds = None, []
        for node in members:
            exprs, conds = compose_chain(exprs, conds, node, node.schema)
        group2 = [substitute_bound(e, exprs) for e in plan.group_exprs]
        aggs2 = [substitute_bound(e, exprs) for e in plan.agg_exprs]
        f = self.run(tail, dry)
        try:
            frame = self._aggregate_frame(plan, f, group2, aggs2,
                                          conds or None, dry)
        except NotDistributable:
            if not dry:
                self.fusion["fallbacks"] += 1
            f = self._replay_members(f, members, dry)
            return self._aggregate_frame(plan, f, plan.group_exprs,
                                         plan.agg_exprs, None, dry)
        if not dry:
            self.fusion["fusedStages"] += 1
            self.fusion["fusedOperators"] += len(members) + 1
            self.fusion["dispatchesSaved"] += len(members)
        return frame

    def _aggregate_frame(self, plan: L.Aggregate, f: ShardedFrame,
                         group_in, agg_in, pre_cond,
                         dry: bool) -> ShardedFrame:
        from spark_rapids_tpu.ops import aggregates as agg
        low = ExprLowering(f.enc, self.conf)
        group_exprs = [low.lower(e) for e in group_in]
        nkeys = len(group_exprs)

        # split agg outputs into bare aggregate calls + result exprs
        # (the _plan_aggregate split, Catalyst's resultExpressions)
        agg_list: List[AggregateExpression] = []

        group_keys = [ge.cache_key() for ge in group_exprs]

        def _has_agg(e):
            return isinstance(e, AggregateExpression) or \
                any(_has_agg(c) for c in e.children)

        def extract(e):
            if isinstance(e, AggregateExpression):
                le = low.lower_agg(e)
                idx = len(agg_list)
                agg_list.append(le)
                return BoundReference(nkeys + idx, le.dtype,
                                      name=f"_a{idx}",
                                      nullable=le.nullable)
            if not _has_agg(e):
                # group-key subtrees read the agg frame's key column,
                # not the child ordinal (Catalyst resultExpressions)
                le = low.lower(e)
                ck = le.cache_key()
                if ck in group_keys:
                    ki = group_keys.index(ck)
                    ge = group_exprs[ki]
                    return BoundReference(ki, ge.dtype, name=ge.name,
                                          nullable=ge.nullable)
                if not e.children:
                    if isinstance(le, BoundReference):
                        raise NotDistributable(
                            f"column {le.name!r} in aggregate output is "
                            "neither an aggregate nor in the GROUP BY")
                    return le
            return e.with_children([extract(c) for c in e.children])

        out_named = []
        trivial = True
        for e in agg_in:
            inner = e.children[0] if isinstance(e, Alias) else e
            rewritten = extract(inner)
            if not isinstance(inner, AggregateExpression):
                trivial = False
            out_named.append((e.name, rewritten))
        _check_supported(group_exprs, self.conf)
        _check_supported(agg_list, self.conf)
        # fused pre-shuffle chain: the upstream predicates (bottom-first
        # conjuncts) ride into the update kernel as a row mask with
        # progressive ANSI-check masking (exec/fusion.py)
        lcond = [low.lower(c) for c in pre_cond] if pre_cond else None
        if lcond:
            _check_supported(lcond, self.conf)

        # enc propagation: encoded group keys (bare or re-encoded) and
        # min/max/first/last over encoded children keep their
        # dictionaries
        agg_enc = {}
        for i, ge in enumerate(group_exprs):
            d = low.out_dict(ge)
            if d is not None:
                agg_enc[i] = d
        for idx, a in enumerate(agg_list):
            if isinstance(a.func, (agg.Min, agg.Max, agg.First, agg.Last)):
                d = low.out_dict(a.func.child) \
                    if a.func.child is not None else None
                if d is not None:
                    agg_enc[nkeys + idx] = d
        key_schema = [(e.name, e.dtype) for e in plan.group_exprs]
        agg_schema = key_schema + [(f"_a{i}", a.dtype)
                                   for i, a in enumerate(agg_list)]

        if dry:
            agg_frame = ShardedFrame(
                self.mesh, [n for n, _ in agg_schema],
                [dt for _, dt in agg_schema], None, None, agg_enc)
        else:
            from spark_rapids_tpu.parallel.distributed import (
                DistributedAggregate)
            dist = DistributedAggregate(
                self.mesh, in_dtypes=f.phys_dtypes,
                group_exprs=group_exprs,
                funcs=[a.func for a in agg_list],
                filter_cond=lcond,
                cost_model=self._cost_model,
                # compressed wire: the exchanged partial frame's code
                # columns (encoded group keys + encoded min/max/first/
                # last partials) with their dictionaries
                encoded_keys={i: d for i, d in agg_enc.items()
                              if i < nkeys},
                encoded_funcs={i - nkeys: d
                               for i, d in agg_enc.items()
                               if i >= nkeys})
            outs = dist([(v, val, None) for v, val in f.cols], f.nrows,
                        window=self._xwindow)
            self._emit_stats("aggregate", dist.last_stats)
            if not group_exprs:
                # grand totals are replicated (psum) on every shard;
                # count the single output row on shard 0 only
                nrows = np.zeros(f.nshards, dtype=np.int32)
                nrows[0] = 1
                nrows = mesh_lib.host_put(self.mesh, nrows)
            else:
                nrows = outs[0][2].reshape(-1)
            agg_frame = ShardedFrame(
                self.mesh, [n for n, _ in agg_schema],
                [dt for _, dt in agg_schema],
                [(v, val) for v, val, _ in outs], nrows, agg_enc)
        if trivial:
            # bare aggregates: rename outputs to the requested names
            return agg_frame.replace(names=[n for n, _ in plan.schema])
        # non-trivial outputs: project keys + result expressions
        proj = [BoundReference(i, dt, name=n)
                for i, (n, dt) in enumerate(agg_schema[:nkeys])]
        proj += [Alias(rewritten, name) for name, rewritten in out_named]
        # dictionaries follow bare references through the projection
        # (group keys AND encoded min/max aggregate outputs)
        agg_low = ExprLowering(agg_enc)
        penc = {}
        for i, e in enumerate(proj):
            src = agg_low.encoded_ref(e)
            if src is not None:
                penc[i] = agg_enc[src.ordinal]
        names = [n for n, _ in plan.schema]
        log_dtypes = [dt for _, dt in plan.schema]
        if dry:
            _check_supported(proj, self.conf)
            return ShardedFrame(self.mesh, names, log_dtypes, None, None,
                                penc)
        out_cols = _run_project(agg_frame, proj, "dplan_aggproj")
        return ShardedFrame(self.mesh, names, log_dtypes, list(out_cols),
                            agg_frame.nrows, penc)

    # -- join -------------------------------------------------------------
    def _join(self, plan: L.Join, dry: bool) -> ShardedFrame:
        if not plan.left_keys:
            raise NotDistributable(
                "cross / pure-residual joins have no distributed "
                "lowering")
        if plan.condition is not None and plan.join_type != "inner":
            raise NotDistributable(
                "residual conditions only distribute for inner joins")
        if plan.condition is not None and plan.using:
            raise NotDistributable(
                "residual conditions with USING joins not supported")
        str_keys = [i for i, (lk, rk) in enumerate(
            zip(plan.left_keys, plan.right_keys))
            if lk.dtype.is_string or rk.dtype.is_string]
        if str_keys and plan.using and plan.join_type == "full":
            raise NotDistributable(
                "full-outer USING join over string keys would coalesce "
                "codes from two dictionaries")
        left = self.run(plan.left, dry)
        right = self.run(plan.right, dry)
        low_l = ExprLowering(left.enc, self.conf)
        low_r = ExprLowering(right.enc, self.conf)
        lkeys = [low_l.lower(e) for e in plan.left_keys]
        rkeys = [low_r.lower(e) for e in plan.right_keys]
        _check_supported(lkeys + rkeys, self.conf)
        for i in str_keys:
            # string keys join as codes: the probe side re-codes into
            # the build side's dictionary below (GpuHashJoin.scala:96-150
            # treats string keys first-class; here the exchanged payload
            # stays int64)
            if low_l.out_dict(lkeys[i]) is None or \
                    low_r.out_dict(rkeys[i]) is None:
                raise NotDistributable(
                    "string join key has no dictionary on the mesh")

        swapped = plan.join_type == "right"
        join_type = "left" if swapped else plan.join_type
        if swapped:
            probe, build = right, left
            probe_keys, build_keys = rkeys, lkeys
        else:
            probe, build = left, right
            probe_keys, build_keys = lkeys, rkeys

        # output layout before reorder: probe cols + build cols (or probe
        # only for semi/anti); rebuild left+right then Join.schema order
        if plan.join_type in ("semi", "anti"):
            out_names = list(left.names)
            out_dtypes = list(left.log_dtypes)
            out_enc = dict(left.enc)
        else:
            out_names = list(left.names) + list(right.names)
            out_dtypes = list(left.log_dtypes) + list(right.log_dtypes)
            out_enc = dict(left.enc)
            for o, d in right.enc.items():
                out_enc[len(left.names) + o] = d

        cond = None
        if plan.condition is not None:
            cond = ExprLowering(out_enc, self.conf).lower(plan.condition)
            _check_supported([cond], self.conf)

        # USING joins dedup the key columns; the PRESERVED side supplies
        # the key value (right for right joins, coalesce for full) —
        # mirrors TpuHashJoinExec's stitch
        proj = None
        if plan.using and plan.join_type not in ("semi", "anti"):
            keyset = set(plan.using)
            nleft = len(left.names)
            proj, penc = [], {}

            def ref(i):
                return BoundReference(i, out_dtypes[i], name=out_names[i])

            for n in left.names:
                if n not in keyset:
                    continue
                li = left.names.index(n)
                ri = nleft + right.names.index(n)
                if plan.join_type == "full":
                    proj.append(Alias(preds.Coalesce(ref(li), ref(ri)), n))
                elif swapped:
                    if ri in out_enc:
                        penc[len(proj)] = out_enc[ri]
                    proj.append(Alias(ref(ri), n))
                else:
                    if li in out_enc:
                        penc[len(proj)] = out_enc[li]
                    proj.append(ref(li))
            for i, n in enumerate(left.names):
                if n not in keyset:
                    if i in out_enc:
                        penc[len(proj)] = out_enc[i]
                    proj.append(ref(i))
            for i, n in enumerate(right.names):
                if n not in keyset:
                    if nleft + i in out_enc:
                        penc[len(proj)] = out_enc[nleft + i]
                    proj.append(ref(nleft + i))
            proj_schema = [(e.name, e.dtype) for e in proj]

        if dry:
            if proj is not None:
                return ShardedFrame(self.mesh,
                                    [n for n, _ in proj_schema],
                                    [dt for _, dt in proj_schema],
                                    None, None, penc)
            return ShardedFrame(self.mesh, out_names, out_dtypes, None,
                                None, out_enc)

        probe_m = _append_key_cols(probe, probe_keys)
        build_m = _append_key_cols(build, build_keys)
        pk_idx = list(range(len(probe.names),
                            len(probe.names) + len(probe_keys)))
        bk_idx = list(range(len(build.names),
                            len(build.names) + len(build_keys)))
        # compressed wire: every code-valued exchanged column — body
        # columns from each side's frame enc, plus the appended string
        # key columns (the probe key's dictionary is the BUILD side's
        # after the remap below)
        probe_enc = dict(probe_m.enc)
        build_enc = dict(build_m.enc)
        if str_keys:
            # re-code the probe side's string key codes into the build
            # dictionary: value-equal codes become equal ints, values
            # absent from the build side map to -1 (never a build code)
            low_p, low_b = (low_r, low_l) if swapped else (low_l, low_r)
            cols = list(probe_m.cols)
            for i in str_keys:
                pd_ = low_p.out_dict(probe_keys[i])
                bd = low_b.out_dict(build_keys[i])
                pos = {v: c for c, v in enumerate(bd)}
                mapping = np.array([pos.get(v, -1) for v in pd_] or [-1],
                                   dtype=np.int64)
                vals, valid = cols[pk_idx[i]]
                cols[pk_idx[i]] = (
                    _remap_codes(jnp.asarray(mapping),
                                 jnp.clip(vals, 0, len(mapping) - 1)),
                    valid)
                probe_enc[pk_idx[i]] = bd
                build_enc[bk_idx[i]] = bd
            probe_m = probe_m.replace(cols=cols)
        flat, n_out = self._exec_join(probe_m, build_m, pk_idx, bk_idx,
                                      join_type, plan.join_type,
                                      probe_enc=probe_enc,
                                      build_enc=build_enc)
        n_out = n_out.reshape(-1)
        n_probe = len(probe.names)
        n_build = len(build.names)
        if plan.join_type in ("semi", "anti"):
            cols = list(flat[:n_probe])
        else:
            probe_cols = list(flat[:n_probe])
            build_cols = list(flat[len(probe_m.names):
                                   len(probe_m.names) + n_build])
            if swapped:
                cols = build_cols + probe_cols
            else:
                cols = probe_cols + build_cols
        frame = ShardedFrame(self.mesh, out_names, out_dtypes, cols,
                             n_out.reshape(-1), out_enc)
        if cond is not None:
            out_cols, nrows = _run_filter(frame, cond)
            frame = frame.replace(cols=list(out_cols),
                                  nrows=nrows.reshape(-1))
        if proj is not None:
            out_cols = _run_project(frame, proj, "dplan_joinproj")
            frame = ShardedFrame(self.mesh, [n for n, _ in proj_schema],
                                 [dt for _, dt in proj_schema],
                                 list(out_cols), frame.nrows, penc)
        return frame

    def _exec_join(self, probe_m, build_m, pk_idx, bk_idx, join_type,
                   plan_join_type, depth: int = 0,
                   probe_enc=None, build_enc=None):
        """Run the distributed hash join with output-size retry; when
        the needed output exceeds MAX_OUT_ROWS, degrade to CHUNKED
        emission (probe-side slices joined separately and unioned per
        shard — the JoinGatherer.scala:36-60 role) instead of falling
        off the mesh."""
        from spark_rapids_tpu.parallel.distributed import (
            DistributedHashJoin)
        probe_cap = probe_m.capacity
        nshards = self.mesh.devices.size
        out_factor = 1
        while True:
            join = DistributedHashJoin(
                self.mesh, probe_dtypes=probe_m.phys_dtypes,
                build_dtypes=build_m.phys_dtypes,
                probe_key_idx=pk_idx, build_key_idx=bk_idx,
                join_type=join_type, out_factor=out_factor,
                probe_encoded=probe_enc, build_encoded=build_enc,
                cost_model=self._cost_model)
            flat, n_out, total = join(
                probe_m.cols, probe_m.nrows, build_m.cols,
                build_m.nrows, window=self._xwindow)
            # process_count-aware fetch: the retry decision must be
            # identical on every controller (host_sync allgathers under
            # multi-process SPMD)
            from spark_rapids_tpu.parallel.distributed import host_sync
            h_total, h_nout = host_sync((total, n_out))
            if bool(np.all(h_total <= h_nout)):
                break
            # size the retry from the observed truncation; out_cap is
            # relative to the (possibly tiny) probe capacity, so the
            # factor itself may legitimately grow large
            need = int(h_total.max())
            next_factor = out_factor * 2
            while next_factor * probe_cap < need:
                next_factor *= 2  # power-of-two: bounded compile cache
            if (next_factor * probe_cap * nshards > self.MAX_OUT_ROWS):
                return self._exec_join_chunked(
                    probe_m, build_m, pk_idx, bk_idx, join_type,
                    plan_join_type, depth, probe_enc=probe_enc,
                    build_enc=build_enc)
            out_factor = next_factor
        self._emit_stats(f"join:{plan_join_type}", join.last_stats,
                         out_factor=out_factor, depth=depth)
        return flat, n_out

    def _exec_join_chunked(self, probe_m, build_m, pk_idx, bk_idx,
                           join_type, plan_join_type, depth: int,
                           probe_enc=None, build_enc=None):
        if join_type == "full":
            # probe-side chunking is linear only when each probe row's
            # output is independent; a full join also emits
            # unmatched-BUILD rows, which chunking would duplicate
            raise NotDistributable(
                "full-outer join output exceeds the distributed output "
                "cap (chunked emission covers inner/left/semi/anti)")
        if depth >= 6:
            raise NotDistributable(
                "join output exceeds the distributed output cap even "
                "with 64-way chunked emission")
        from spark_rapids_tpu.parallel.distributed import host_sync
        counts = host_sync(probe_m.nrows).reshape(-1)
        chunks = []
        for i in range(2):
            los = (counts * i) // 2
            his = (counts * (i + 1)) // 2
            cols, nr = _run_slice(probe_m, los, his)
            sliced = probe_m.replace(cols=list(cols),
                                     nrows=nr.reshape(-1))
            flat, n_out = self._exec_join(sliced, build_m, pk_idx,
                                          bk_idx, join_type,
                                          plan_join_type, depth + 1,
                                          probe_enc=probe_enc,
                                          build_enc=build_enc)
            chunks.append((list(flat), n_out.reshape(-1)))
        if len(chunks[0][0]) > len(probe_m.names):
            dtypes = probe_m.phys_dtypes + build_m.phys_dtypes
        else:
            dtypes = probe_m.phys_dtypes
        dtypes = dtypes[: len(chunks[0][0])]
        cols, nrows = _run_union(chunks, dtypes, self.mesh)
        return tuple(cols), nrows

    # -- sort / limit / topn ---------------------------------------------
    def _lower_orders(self, orders, f: ShardedFrame):
        low = ExprLowering(f.enc, self.conf)
        keys = [low.lower(e) for e, _, _ in orders]
        _check_supported(keys, self.conf)
        desc = [d for _, d, _ in orders]
        nf = [n for _, _, n in orders]
        return keys, desc, nf

    def _sort(self, plan: L.Sort, dry: bool) -> ShardedFrame:
        from spark_rapids_tpu.parallel.distsort import DistributedSort
        f = self.run(plan.child, dry)
        keys, desc, nf = self._lower_orders(plan.orders, f)
        if dry:
            return f
        dist = DistributedSort(self.mesh, f.phys_dtypes, keys, desc, nf,
                               cost_model=self._cost_model)
        out_cols, nrows = dist(f.cols, f.nrows)
        self._emit_stats("sort", dist.last_stats)
        return f.replace(cols=list(out_cols), nrows=nrows.reshape(-1))

    # -- window -----------------------------------------------------------
    def _window(self, plan: L.Window, dry: bool) -> ShardedFrame:
        """Window as an exchange consumer (GpuWindowExec role).

        Expressions are grouped by their window spec; each spec group
        runs one distributed pass — partitioned specs range-partition on
        the PARTITION BY prefix (a partition never splits a shard) then
        evaluate shard-locally; GLOBAL specs (no PARTITION BY) sort
        globally and fix up with the collective cross-shard carry
        (parallel/distwindow.DistributedGlobalWindow, the mesh analog of
        GpuWindowExec.scala:423-446's running-window optimization).
        Later groups see earlier groups' outputs as ordinary payload
        columns; the final column order is restored to plan.schema."""
        from spark_rapids_tpu.exec.window import (WindowExpression,
                                                  WindowSpec,
                                                  group_by_spec)
        from spark_rapids_tpu.parallel.distwindow import (
            DistributedGlobalWindow, DistributedWindow)
        f = self.run(plan.child, dry)
        exprs = plan.window_exprs
        nchild = len(f.names)
        groups = group_by_spec(exprs)

        names = [n for n, _ in plan.schema]
        log_dtypes = [dt for _, dt in plan.schema]
        cur_names = list(f.names)
        cur_dts = list(f.log_dtypes)
        cur_enc = dict(f.enc)
        cur_cols, cur_nrows = f.cols, f.nrows
        appended_pos: Dict[int, int] = {}
        for grp in groups:
            spec0 = grp[0][2].spec
            is_global = not spec0.partition_exprs
            low = ExprLowering(cur_enc, self.conf)
            lspec = WindowSpec(
                [low.lower(e) for e in spec0.partition_exprs],
                [(low.lower(e), d, nf) for e, d, nf in spec0.orders],
                spec0.frame)
            _check_supported(list(lspec.partition_exprs) +
                             [e for e, _, _ in lspec.orders], self.conf)
            lowered = []
            enc_new = {}
            base = len(cur_names)
            for i, (j, name, we) in enumerate(grp):
                reason = we.supported_reason()
                if reason:
                    raise NotDistributable(f"window {name}: {reason}")
                if is_global:
                    fr = we.spec.frame
                    if we.kind in ("lead", "lag"):
                        raise NotDistributable(
                            "global lead/lag needs a cross-shard halo "
                            "exchange")
                    if we.kind in ("sum", "count", "avg") and not (
                            fr.lo is None and fr.hi in (0, None)):
                        raise NotDistributable(
                            "global window frames with finite row "
                            "offsets need a cross-shard halo exchange")
                ch = None
                if we.child_expr is not None:
                    ch = low.lower(we.child_expr)
                    _check_supported([ch], self.conf)
                    d = low.out_dict(ch)
                    if d is not None:
                        if we.kind in ("min", "max", "lead", "lag"):
                            # order-preserving codes: output is codes too
                            enc_new[base + i] = d
                        elif we.kind != "count":  # count: validity only
                            raise NotDistributable(
                                f"window {we.kind} over strings not "
                                "supported on the mesh")
                dflt = low.lower(we.default) if we.default is not None \
                    else None
                lowered.append((name, WindowExpression(
                    we.kind, lspec, ch, we.offset, dflt)))
            phys_before = [_phys(dt) for dt in cur_dts]
            for i, (j, name, we) in enumerate(grp):
                appended_pos[j] = base + i
                cur_names.append(name)
                cur_dts.append(log_dtypes[nchild + j])
            cur_enc.update(enc_new)
            if not dry:
                cls = DistributedWindow if not is_global \
                    else DistributedGlobalWindow
                dist = cls(self.mesh, phys_before, lowered)
                cols, nrows2 = dist(cur_cols, cur_nrows)
                cur_cols = list(cols)
                cur_nrows = jnp.asarray(nrows2).reshape(-1)
                self._emit_stats("window", dist.last_stats,
                                 window_global=is_global)

        # restore plan.schema order: child columns stay first, window
        # columns return to their original expression order
        perm = list(range(nchild)) + \
            [appended_pos[j] for j in range(len(exprs))]
        enc = {o: d for o, d in cur_enc.items() if o < nchild}
        inv = {p: nchild + j for j, p in appended_pos.items()}
        for p, d in cur_enc.items():
            if p >= nchild and p in inv:
                enc[inv[p]] = d
        if dry:
            return ShardedFrame(self.mesh, names, log_dtypes, None, None,
                                enc)
        out_cols = [cur_cols[p] for p in perm]
        return ShardedFrame(self.mesh, names, log_dtypes, out_cols,
                            cur_nrows, enc)

    # -- expand / union ---------------------------------------------------
    def _expand(self, plan, dry: bool) -> ShardedFrame:
        """Expand is embarrassingly parallel: each shard emits its K
        projection replicas locally; no exchange (GpuExpandExec role)."""
        from spark_rapids_tpu.exec.expand import NullLiteral
        f = self.run(plan.child, dry)
        low = ExprLowering(f.enc, self.conf)
        projections = []
        enc_new = {}
        for k, proj in enumerate(plan.projections):
            lowered = []
            for j, e in enumerate(proj):
                if isinstance(e, NullLiteral):
                    le = NullLiteral(_phys(e.dtype))
                else:
                    le = low.lower(e)
                    d = low.out_dict(le)
                    if d is not None:
                        prev = enc_new.get(j)
                        if prev is not None and prev is not d:
                            raise NotDistributable(
                                "expand projections disagree on a "
                                "string column's dictionary")
                        enc_new[j] = d
                lowered.append(le)
            projections.append(lowered)
        for proj in projections:
            _check_supported(
                [e for e in proj
                 if not isinstance(e, NullLiteral)], self.conf)
        names = [n for n, _ in plan.schema]
        log_dtypes = [dt for _, dt in plan.schema]
        for j, dt in enumerate(log_dtypes):
            if dt.is_string and j not in enc_new:
                raise NotDistributable(
                    f"expand string column {names[j]!r} has no "
                    "dictionary on the mesh")
        if dry:
            return ShardedFrame(self.mesh, names, log_dtypes, None, None,
                                enc_new)
        cols, nrows = _run_expand(f, projections,
                                  [_phys(dt) for dt in log_dtypes])
        return ShardedFrame(self.mesh, names, log_dtypes, list(cols),
                            nrows, enc_new)

    def _union(self, plan: L.Union, dry: bool) -> ShardedFrame:
        """Union keeps rows where they are: shard i's output is the
        concatenation of shard i's slices of every child (no exchange)."""
        frames = [self.run(c, dry) for c in plan.children]
        names = [n for n, _ in plan.schema]
        log_dtypes = [dt for _, dt in plan.schema]
        # encoded string columns would need dictionary alignment across
        # children; only distribute when no column is a string
        if any(dt.is_string for dt in log_dtypes):
            raise NotDistributable(
                "union over string columns needs dictionary alignment "
                "(not yet distributed)")
        if dry:
            return ShardedFrame(self.mesh, names, log_dtypes, None, None,
                                {})
        cols, nrows = _run_union([(fr.cols, fr.nrows) for fr in frames],
                                 [_phys(dt) for dt in log_dtypes],
                                 self.mesh)
        return ShardedFrame(self.mesh, names, log_dtypes, list(cols),
                            nrows, {})

    def _limit(self, plan: L.Limit, dry: bool) -> ShardedFrame:
        f = self.run(plan.child, dry)
        if dry:
            return f
        counts = mesh_lib.to_host(f.nrows).copy()
        left = plan.n
        for i in range(len(counts)):
            take = min(int(counts[i]), left)
            counts[i] = take
            left -= take
        return f.replace(nrows=mesh_lib.host_put(
            self.mesh, counts.astype(np.int32)))

    def _topn(self, plan: L.Limit, dry: bool) -> ShardedFrame:
        from spark_rapids_tpu.parallel.distsort import (
            DistributedTopN, host_order)
        sort = plan.child
        f = self.run(sort.child, dry)
        keys, desc, nf = self._lower_orders(sort.orders, f)
        if dry:
            return f
        dist = DistributedTopN(self.mesh, f.phys_dtypes, keys, desc, nf,
                               plan.n)
        flat, key_flat, nrows = dist(f.cols, f.nrows)
        nshards = f.nshards
        counts = mesh_lib.to_host(nrows).reshape(-1)
        cap = int(flat[0][0].shape[0]) // nshards

        def host_rows(pair):
            v = mesh_lib.to_host(pair[0]).reshape(nshards, cap)
            m = mesh_lib.to_host(pair[1]).reshape(nshards, cap)
            vs = np.concatenate([v[i, :counts[i]] for i in range(nshards)])
            ms = np.concatenate([m[i, :counts[i]] for i in range(nshards)])
            return vs, ms

        hkeys = [host_rows(p) for p in key_flat]
        order = host_order([v for v, _ in hkeys], [m for _, m in hkeys],
                           desc, nf)[:plan.n]
        n = len(order)
        out_cap = bucket_capacity(max(n, 1), minimum=8)
        cols = []
        for pair in flat:
            vs, ms = host_rows(pair)
            vbuf = np.zeros(nshards * out_cap, dtype=vs.dtype)
            mbuf = np.zeros(nshards * out_cap, dtype=bool)
            vbuf[:n] = vs[order]
            mbuf[:n] = ms[order]
            cols.append((mesh_lib.host_put(self.mesh, vbuf),
                         mesh_lib.host_put(self.mesh, mbuf)))
        out_counts = np.zeros(nshards, dtype=np.int32)
        out_counts[0] = n
        return f.replace(cols=cols,
                         nrows=mesh_lib.host_put(self.mesh, out_counts))

    # -- collect ----------------------------------------------------------
    def collect(self, f: ShardedFrame) -> ColumnarBatch:
        # final stage boundary: every in-flight exchange must verify
        # before its bytes materialize to the host (a deferred overflow
        # raises here and the ladder re-drives — truncated frames never
        # reach a client)
        if self._xwindow is not None:
            self._xwindow.resolve_all()
        nshards = f.nshards
        cap = f.capacity
        counts = mesh_lib.to_host(f.nrows).reshape(-1)
        total = int(counts.sum())
        out = {}
        for i, ((name, dt), (v, m)) in enumerate(zip(f.schema, f.cols)):
            vals = mesh_lib.to_host(v).reshape(nshards, cap)
            mask = mesh_lib.to_host(m).reshape(nshards, cap)
            if total:
                vs = np.concatenate(
                    [vals[s, :counts[s]] for s in range(nshards)])
                ms = np.concatenate(
                    [mask[s, :counts[s]] for s in range(nshards)])
            else:
                vs = np.zeros(0, dtype=vals.dtype)
                ms = np.zeros(0, dtype=bool)
            if i in f.enc:
                values = f.enc[i]
                decoded = [values[int(c)] if ok else None
                           for c, ok in zip(vs, ms)]
                out[name] = Column.from_strings(decoded)
            else:
                storage = np.dtype(dt.storage)
                out[name] = Column.from_numpy(
                    vs.astype(storage, copy=False), dtype=dt,
                    validity=None if bool(ms.all()) else ms)
        batch = ColumnarBatch(out, total)
        # per-device share of the LAST exchange's payload bytes: the
        # ShardedFrame's device arrays (and the exchange lane buffers
        # backing them) stay alive until this result drops, so a
        # consumer that spill-registers the batch (pipeline / coalesce)
        # reserves that headroom.  Today the distributed result is
        # usually consumed straight by collect/to_arrow — the
        # reservation engages when the batch re-enters the engine (an
        # InMemoryRelation scan of a distributed result) and is the
        # wiring point for a future device-resident handoff that skips
        # the host round trip entirely.  Only when this query exchanged
        # at all (delta guard) — and only the most recent launch's
        # payload, never the query's cumulative bytes (earlier
        # exchanges' buffers are already reused; summing them would
        # overstate the reservation and trigger spurious spills).
        from spark_rapids_tpu.parallel.shuffle import (
            ShuffleWireMetrics, metrics_for_session)
        m = metrics_for_session(self.session)
        delta = ShuffleWireMetrics.delta(m.snapshot(), self._wire0)
        if delta.get("exchanges", 0):
            batch.transient_wire_bytes = \
                m.last_exchange_bytes // max(self.mesh.devices.size, 1)
        return batch


def try_distributed(session, plan: L.LogicalPlan, resume: bool = False):
    """Entry point from DataFrame execution: returns a list of
    ColumnarBatches when the plan ran on the mesh, else None (single-
    process fallback; reason on ``session.last_dist_explain``).
    ``resume=True`` on a recovery re-attempt lets the planner splice in
    stage checkpoints recorded by the failed attempt."""
    mesh = getattr(session, "mesh", None)
    if mesh is None:
        return None
    from spark_rapids_tpu.config import rapids_conf as rc
    if not session.conf.get(rc.DISTRIBUTED_ENABLED):
        session.last_dist_explain = "distributed disabled by conf"
        return None
    planner = DistPlanner(session, mesh, resume=resume)
    # this query's column pruning and filters onto its scans: the
    # FileRelation of a view is shared, and would otherwise carry
    # whatever the last single-process plan left on it
    from spark_rapids_tpu.plan.overrides import _pushdown_pass
    from spark_rapids_tpu.utils import tracing
    with tracing.span("plan.physical"):
        _pushdown_pass(plan, session.cache_manager)
    session.last_scan_stats = None  # per-query: no stale sharded stats
    session.last_fusion_stats = None  # per-query fusion attribution
    from spark_rapids_tpu.parallel import exchange_async as _xa
    _xa.set_current_window(planner._xwindow)
    try:
        with tracing.span("plan.physical"):
            planner.run(plan, dry=True)  # pre-flight: no data moves
        # data-dependent limits (e.g. join fan-out vs output capacity)
        # can only surface while executing; they fall back too
        batch = planner.collect(planner.run(plan, dry=False))
    except NotDistributable as e:
        # an unverified exchange from a partially-executed attempt is
        # moot — the single-process fallback recomputes from source
        if planner._xwindow is not None:
            planner._xwindow.discard_all()
        session.last_dist_explain = f"fallback: {e}"
        ev = getattr(session, "events", None)
        if ev is not None and ev.enabled:
            ev.emit("DistFallback", reason=str(e))
        return None
    except BaseException:
        # failed attempt: the recovery ladder re-drives the whole query
        # (on the synchronous path); pending handles just release their
        # window bytes, nothing to verify
        if planner._xwindow is not None:
            planner._xwindow.discard_all()
        raise
    finally:
        _xa.set_current_window(None)
    session.last_dist_explain = "distributed"
    session.last_fusion_stats = dict(planner.fusion)
    if planner._ckpt is not None:
        # per-execution completion signal, delivered on THIS query's
        # thread (robustness/checkpoint.py note_distributed_complete)
        planner._ckpt.note_distributed_complete()
    return [batch]
