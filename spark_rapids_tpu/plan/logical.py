"""Logical plan nodes.

The reference plugs into Spark's Catalyst and never owns a logical plan; this
framework is standalone, so it carries a small Catalyst-shaped logical algebra
that the DataFrame API builds and ``plan/overrides.py`` lowers to TpuExec
physical operators (the GpuOverrides analog, GpuOverrides.scala:3258).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.dtypes import INT32, DataType
from spark_rapids_tpu.ops.aggregates import AggregateFunction
from spark_rapids_tpu.ops.expressions import (
    Alias, ColVal, EmitContext, Expression,
)

Schema = List[Tuple[str, DataType]]


class AggregateExpression(Expression):
    """Expression wrapper around an AggregateFunction (mirrors Catalyst's)."""

    def __init__(self, func: AggregateFunction):
        self.func = func
        self.children = (func.child,) if func.child is not None else ()

    def with_children(self, children):
        import copy
        f = copy.copy(self.func)
        f.child = children[0] if children else None
        return AggregateExpression(f)

    def bind(self, schema):
        return self.with_children([c.bind(schema) for c in self.children])

    @property
    def dtype(self) -> DataType:
        return self.func.result_dtype

    @property
    def nullable(self) -> bool:
        return self.func.result_nullable

    @property
    def name(self) -> str:
        arg = self.func.child.name if self.func.child is not None else "*"
        return f"{self.func.name}({arg})"

    def emit(self, ctx: EmitContext) -> ColVal:
        raise RuntimeError(
            "AggregateExpression must be planned by TpuHashAggregateExec, "
            "not emitted directly")

    def cache_key(self):
        return ("AggregateExpression", self.func.cache_key())

    def __str__(self):
        return self.name


class LogicalPlan:
    children: Tuple["LogicalPlan", ...] = ()

    @property
    def schema(self) -> Schema:
        raise NotImplementedError

    def node_name(self) -> str:
        return type(self).__name__

    def __str__(self) -> str:
        lines: List[str] = []

        def rec(node, depth):
            lines.append("  " * depth + node.describe())
            for c in node.children:
                rec(c, depth + 1)
        rec(self, 0)
        return "\n".join(lines)

    def describe(self) -> str:
        return self.node_name()

    def tree_string(self) -> str:
        from spark_rapids_tpu.utils.trees import render_tree
        return render_tree(self)


class InMemoryRelation(LogicalPlan):
    def __init__(self, batches: Sequence[ColumnarBatch], schema: Schema):
        self.batches = list(batches)
        self._schema = list(schema)

    @property
    def schema(self) -> Schema:
        return self._schema

    def describe(self):
        rows = sum(b.nrows for b in self.batches)
        return f"InMemoryRelation[{rows} rows]"


class FileRelation(LogicalPlan):
    # the per-file metadata columns the scan can expose on request
    # (GpuFileSourceScanExec metadata-column analog): input_file_name()
    # and the _metadata struct (shredded — see columnar/nested.py)
    INPUT_FILE_COL = "__input_file_name"

    def __init__(self, paths: Sequence[str], file_format: str, schema: Schema,
                 options: Optional[dict] = None, bucket_spec=None):
        self.paths = list(paths)
        self.file_format = file_format
        self._schema = list(schema)
        self.options = dict(options or {})
        # set by the planner's pushdown pass (GpuParquetScan predicate
        # pushdown + column pruning analog)
        self.pushed_filters: List[Expression] = []
        self.required_columns = None  # None = all
        # subset of {"input_file", "metadata"}; set by the DataFrame
        # layer when a query references the metadata columns
        self.file_meta = set()
        # {"column", "num_buckets"} from the _bucket_spec.json sidecar
        self.bucket_spec = bucket_spec

    @property
    def schema(self) -> Schema:
        from spark_rapids_tpu.columnar.dtypes import (
            INT64, STRING, TIMESTAMP_US)
        out = list(self._schema)
        if "input_file" in self.file_meta:
            out.append((self.INPUT_FILE_COL, STRING))
        if "metadata" in self.file_meta:
            out += [("_metadata.file_path", STRING),
                    ("_metadata.file_name", STRING),
                    ("_metadata.file_size", INT64),
                    ("_metadata.file_modification_time", TIMESTAMP_US)]
        return out

    def describe(self):
        extra = ", bucketed" if self.bucket_spec else ""
        return (f"FileRelation[{self.file_format}, {len(self.paths)} "
                f"files{extra}]")


class Project(LogicalPlan):
    def __init__(self, exprs: Sequence[Expression], child: LogicalPlan):
        self.exprs = [e.bind(child.schema) for e in exprs]
        self.children = (child,)

    @property
    def child(self):
        return self.children[0]

    @property
    def schema(self) -> Schema:
        return [(e.name, e.dtype) for e in self.exprs]

    def describe(self):
        return f"Project[{', '.join(e.name for e in self.exprs)}]"


class Filter(LogicalPlan):
    def __init__(self, condition: Expression, child: LogicalPlan):
        self.condition = condition.bind(child.schema)
        self.children = (child,)

    @property
    def child(self):
        return self.children[0]

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def describe(self):
        return f"Filter[{self.condition}]"


class Aggregate(LogicalPlan):
    """group_exprs may be empty (grand-total reduction)."""

    def __init__(self, group_exprs: Sequence[Expression],
                 agg_exprs: Sequence[Expression], child: LogicalPlan):
        self.group_exprs = [e.bind(child.schema) for e in group_exprs]
        self.agg_exprs = [e.bind(child.schema) for e in agg_exprs]
        self.children = (child,)

    @property
    def child(self):
        return self.children[0]

    @property
    def schema(self) -> Schema:
        out = [(e.name, e.dtype) for e in self.group_exprs]
        out += [(e.name, e.dtype) for e in self.agg_exprs]
        return out

    def describe(self):
        return (f"Aggregate[keys={[e.name for e in self.group_exprs]}, "
                f"aggs={[e.name for e in self.agg_exprs]}]")


class Join(LogicalPlan):
    def __init__(self, left: LogicalPlan, right: LogicalPlan,
                 left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression], join_type: str,
                 condition: Optional[Expression] = None,
                 using: Optional[Sequence[str]] = None):
        self.left_keys = [e.bind(left.schema) for e in left_keys]
        self.right_keys = [e.bind(right.schema) for e in right_keys]
        self.join_type = join_type
        self.using = list(using) if using else None
        self.children = (left, right)
        # residual (non-equi) condition binds against left+right columns
        # (NOT Join.schema: semi/anti schemas drop the right side but a
        # residual may legitimately reference it — the planner then tags
        # the join off gracefully instead of a bind KeyError)
        self.condition = condition.bind(
            list(left.schema) + list(right.schema)) \
            if condition is not None else None
        # output columns something above reads (None = all); the pushdown
        # pass overwrites it for every query, as it does a FileRelation's
        # required_columns
        self.live_columns = None

    @property
    def left(self):
        return self.children[0]

    @property
    def right(self):
        return self.children[1]

    @property
    def schema(self) -> Schema:
        left = self.left.schema
        right = self.right.schema
        if self.join_type in ("semi", "anti"):
            return list(left)
        if self.using:
            keyset = set(self.using)
            out = [(n, dt) for n, dt in left if n in keyset]
            out += [(n, dt) for n, dt in left if n not in keyset]
            out += [(n, dt) for n, dt in right if n not in keyset]
            return out
        return list(left) + list(right)

    def describe(self):
        keys = list(zip([e.name for e in self.left_keys],
                        [e.name for e in self.right_keys]))
        return f"Join[{self.join_type}, on={keys}]"


class AggInPandas(LogicalPlan):
    """groupBy().agg(grouped-agg pandas UDFs)."""

    def __init__(self, group_names: Sequence[str], aggs: Sequence[tuple],
                 child: LogicalPlan):
        self.group_names = list(group_names)
        self.aggs = list(aggs)  # (name, fn, arg_name, dtype)
        self.children = (child,)

    @property
    def child(self):
        return self.children[0]

    @property
    def schema(self) -> Schema:
        child_schema = dict(self.child.schema)
        out = [(n, child_schema[n]) for n in self.group_names]
        out += [(name, dt) for name, _, _, dt in self.aggs]
        return out

    def describe(self):
        return f"AggInPandas[{[n for n, *_ in self.aggs]}]"


class WindowInPandas(LogicalPlan):
    """Pandas UDFs evaluated over window frames
    (GpuWindowInPandasExec analog, python/GpuWindowInPandasExec.scala).
    Output = child columns + one column per windowed UDF."""

    def __init__(self, calls: Sequence[tuple], child: LogicalPlan):
        # calls: (out_name, fn, arg_name, dtype,
        #         (partition_names, orders, frame))
        self.calls = list(calls)
        self.children = (child,)
        child_names = {n for n, _ in child.schema}
        for out_name, _, arg, _, (parts, orders, _) in self.calls:
            if out_name in child_names:
                raise ValueError(
                    f"windowed pandas UDF output {out_name!r} collides "
                    "with a child column (the select() router assigns "
                    "internal names — construct through it)")
            for c in [arg] + list(parts) + [n for n, _, _ in orders]:
                if c not in child_names:
                    raise KeyError(
                        f"windowed pandas UDF references unknown "
                        f"column {c!r}")

    @property
    def child(self):
        return self.children[0]

    @property
    def schema(self) -> Schema:
        return list(self.child.schema) + \
            [(name, dt) for name, _, _, dt, _ in self.calls]

    def describe(self):
        return f"WindowInPandas[{[n for n, *_ in self.calls]}]"


class CoGroupMapInPandas(LogicalPlan):
    """cogroup().applyInPandas."""

    def __init__(self, fn, out_schema: Schema, left_names, right_names,
                 left: LogicalPlan, right: LogicalPlan):
        self.fn = fn
        self._schema = list(out_schema)
        self.left_names = list(left_names)
        self.right_names = list(right_names)
        self.children = (left, right)

    @property
    def schema(self) -> Schema:
        return self._schema

    def describe(self):
        return "CoGroupMapInPandas"


class BatchId(LogicalPlan):
    """Appends the per-batch id columns consumed by
    monotonically_increasing_id()/spark_partition_id()."""

    def __init__(self, child: LogicalPlan):
        self.children = (child,)

    @property
    def child(self):
        return self.children[0]

    @property
    def schema(self) -> Schema:
        from spark_rapids_tpu.columnar.dtypes import INT64
        return list(self.child.schema) + [("__mid", INT64),
                                          ("__pid", INT32)]

    def describe(self):
        return "BatchId"


class Sort(LogicalPlan):
    def __init__(self, orders: Sequence[Tuple[Expression, bool, bool]],
                 child: LogicalPlan):
        """orders: (expr, descending, nulls_first)"""
        self.orders = [(e.bind(child.schema), d, nf) for e, d, nf in orders]
        self.children = (child,)

    @property
    def child(self):
        return self.children[0]

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def describe(self):
        parts = [f"{e.name} {'DESC' if d else 'ASC'}"
                 for e, d, _ in self.orders]
        return f"Sort[{', '.join(parts)}]"


class Limit(LogicalPlan):
    def __init__(self, n: int, child: LogicalPlan):
        self.n = int(n)
        self.children = (child,)

    @property
    def child(self):
        return self.children[0]

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def describe(self):
        return f"Limit[{self.n}]"


class Union(LogicalPlan):
    def __init__(self, children: Sequence[LogicalPlan]):
        self.children = tuple(children)
        first = self.children[0].schema
        for c in self.children[1:]:
            if [dt.name for _, dt in c.schema] != [dt.name for _, dt in first]:
                raise ValueError("union children schemas differ")

    @property
    def schema(self) -> Schema:
        return self.children[0].schema


class MapInPandas(LogicalPlan):
    """df.mapInPandas / groupBy().applyInPandas host-function nodes."""

    def __init__(self, fn, out_schema: Schema, child: LogicalPlan,
                 group_names: Optional[Sequence[str]] = None):
        self.fn = fn
        self._schema = list(out_schema)
        self.group_names = list(group_names) if group_names else None
        self.children = (child,)

    @property
    def child(self):
        return self.children[0]

    @property
    def schema(self) -> Schema:
        return self._schema

    def describe(self):
        kind = "FlatMapGroupsInPandas" if self.group_names else "MapInPandas"
        return f"{kind}[{getattr(self.fn, '__name__', 'fn')}]"


class Generate(LogicalPlan):
    """explode/posexplode of one array-typed generator over the child
    (GpuGenerateExec.scala analog).  ``required`` are pass-through child
    expressions repeated per output element."""

    def __init__(self, generator: Expression, required, position: bool,
                 child: LogicalPlan, col_name: str = "col",
                 pos_name: str = "pos"):
        from spark_rapids_tpu.columnar.nested import (
            MAP_KEY_SUFFIX, MAP_VALUE_SUFFIX, is_shredded_map)
        from spark_rapids_tpu.ops.expressions import UnresolvedColumn
        names = [n for n, _ in child.schema]
        # explode(map) emits key+value columns (Spark's map explode):
        # the shredded arrays share offsets, so both ride one row
        # expansion
        self.map_mode = (
            isinstance(generator, UnresolvedColumn)
            and is_shredded_map(generator.col_name, names))
        if self.map_mode:
            base = generator.col_name
            self.generator = UnresolvedColumn(
                base + MAP_KEY_SUFFIX).bind(child.schema)
            self.generator2 = UnresolvedColumn(
                base + MAP_VALUE_SUFFIX).bind(child.schema)
        else:
            self.generator = generator.bind(child.schema)
            self.generator2 = None
        self.required = [e.bind(child.schema) for e in required]
        self.position = position
        self.col_name = col_name
        self.pos_name = pos_name
        taken = {e.name for e in self.required}
        clash = {"key", "value"} if self.map_mode else {col_name}
        if position:
            clash |= {pos_name}
        if taken & clash:
            raise ValueError(
                f"explode output name(s) {sorted(taken & clash)} collide "
                "with pass-through columns; alias the explode (e.g. "
                ".alias('elem'))")
        self.children = (child,)

    @property
    def child(self):
        return self.children[0]

    @property
    def schema(self) -> Schema:
        out = [(e.name, e.dtype) for e in self.required]
        if self.position:
            out.append((self.pos_name, INT32))
        if self.map_mode:
            out.append(("key", self.generator.dtype.element))
            out.append(("value", self.generator2.dtype.element))
        else:
            out.append((self.col_name, self.generator.dtype.element))
        return out

    def describe(self):
        kind = "posexplode" if self.position else "explode"
        return f"Generate[{kind}({self.generator.name})]"


class Window(LogicalPlan):
    """Append window-function columns (WindowExec analog)."""

    def __init__(self, window_exprs: Sequence[Tuple[str, Expression]],
                 child: LogicalPlan):
        # (output name, WindowExpression) pairs, bound to child schema
        self.window_exprs = [(n, e.bind(child.schema))
                             for n, e in window_exprs]
        self.children = (child,)

    @property
    def child(self):
        return self.children[0]

    @property
    def schema(self) -> Schema:
        return list(self.child.schema) + \
            [(n, e.dtype) for n, e in self.window_exprs]

    def describe(self):
        return f"Window[{[n for n, _ in self.window_exprs]}]"


class Range(LogicalPlan):
    def __init__(self, start: int, end: int, step: int = 1):
        from spark_rapids_tpu.columnar import dtypes as dts
        self.start, self.end, self.step = start, end, step
        self._schema = [("id", dts.INT64)]

    @property
    def schema(self) -> Schema:
        return self._schema

    def describe(self):
        return f"Range[{self.start}, {self.end}, {self.step}]"
