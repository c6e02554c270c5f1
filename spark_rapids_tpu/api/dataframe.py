"""DataFrame API over logical plans."""

from __future__ import annotations

from typing import List, Optional, Sequence, Union

from spark_rapids_tpu.api.functions import Col, SortKey, _expr, _lit_expr
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.ops.expressions import (
    Alias, BoundReference, Expression, UnresolvedColumn)
from spark_rapids_tpu.plan import logical as L


class PivotedGroupedData:
    """Pivot rewrite: each aggregate over x becomes, per pivot value v,
    the same aggregate over IF(p == v, x, NULL) — the standard pivot
    lowering (nulls are ignored by every aggregate), so no new kernel is
    needed and the result matches GpuPivotFirst."""

    def __init__(self, df: DataFrame, group_exprs, pivot_expr, values):
        self.df = df
        self.group_exprs = group_exprs
        self.pivot_expr = pivot_expr
        self.values = values

    def agg(self, *aggs: "Col") -> DataFrame:
        import copy
        from spark_rapids_tpu.ops import predicates as preds
        from spark_rapids_tpu.ops.expressions import Alias, Literal
        from spark_rapids_tpu.plan.logical import AggregateExpression
        agg_exprs = [_expr(a) for a in aggs]
        out: List[Expression] = []
        for v in self.values:
            for e in agg_exprs:
                alias = e.alias if isinstance(e, Alias) else None
                inner = e.children[0] if isinstance(e, Alias) else e
                if not isinstance(inner, AggregateExpression):
                    raise ValueError("pivot aggregates must be aggregate "
                                     "expressions")
                func = copy.copy(inner.func)
                # CASE WHEN p == v THEN x END (implicit null else): every
                # aggregate ignores nulls, realizing the pivot.  count()
                # has no child: count rows where p == v via CASE -> 1.
                cond = preds.EqualTo(self.pivot_expr, Literal(v))
                if func.child is not None:
                    child_name = func.child.name
                    func.child = preds.CaseWhen([(cond, func.child)])
                else:
                    child_name = "*"
                    func.child = preds.CaseWhen([(cond, Literal(1))])
                if len(agg_exprs) == 1:
                    name = str(v)
                else:
                    name = f"{v}_{alias}" if alias else \
                        f"{v}_{func.name}({child_name})"
                out.append(Alias(AggregateExpression(func), name))
        return DataFrame(self.df.session, L.Aggregate(
            self.group_exprs, out, self.df.plan))

    def sum(self, c) -> DataFrame:
        from spark_rapids_tpu.api import functions as F
        return self.agg(F.sum(c))

    def count(self) -> DataFrame:
        from spark_rapids_tpu.api import functions as F
        return self.agg(F.count(self.pivot_expr))

    def min(self, c) -> DataFrame:
        from spark_rapids_tpu.api import functions as F
        return self.agg(F.min(c))

    def max(self, c) -> DataFrame:
        from spark_rapids_tpu.api import functions as F
        return self.agg(F.max(c))

    def avg(self, c) -> DataFrame:
        from spark_rapids_tpu.api import functions as F
        return self.agg(F.avg(c))


class CoGroupedData:
    """groupBy(a).cogroup(other.groupBy(b)).applyInPandas(fn, schema):
    fn(left_group_df, right_group_df) per key in the union of keys
    (GpuFlatMapCoGroupsInPandasExec analog)."""

    def __init__(self, left: "GroupedData", right: "GroupedData"):
        self.left = left
        self.right = right

    def applyInPandas(self, fn, schema) -> DataFrame:
        lnames = [e.name for e in self.left.group_exprs]
        rnames = [e.name for e in self.right.group_exprs]
        return DataFrame(self.left.df.session, L.CoGroupMapInPandas(
            fn, _parse_schema(schema), lnames, rnames,
            self.left.df.plan, self.right.df.plan))


def _parse_schema(schema):
    """'a int, b string' or [(name, DataType)] -> Schema."""
    from spark_rapids_tpu.columnar.dtypes import dtype_from_name
    if isinstance(schema, str):
        out = []
        for part in schema.split(","):
            name, tname = part.strip().split()
            out.append((name, dtype_from_name(tname)))
        return out
    return list(schema)


def _file_meta_needs(exprs, schema) -> set:
    """Which file-metadata column groups these expressions reference
    that the schema doesn't expose yet."""
    present = {n for n, _ in schema}
    needs = set()
    for e in exprs:
        for r in e.references():
            if r == L.FileRelation.INPUT_FILE_COL and r not in present:
                needs.add("input_file")
            elif (r == "_metadata" or r.startswith("_metadata.")) and \
                    "_metadata.file_path" not in present:
                needs.add("metadata")
    return needs


def _attach_file_meta(plan: L.LogicalPlan, needs: set):
    """Rebuild the plan with file-metadata columns enabled on its
    FileRelation leaves.  Metadata columns append to the END of the scan
    schema, so bound ordinals in intermediate Filter/Limit/Sort nodes
    stay valid; anything else between the reference and the scan is
    unsupported (as in Spark, metadata columns resolve against the
    scan)."""
    import copy
    if isinstance(plan, L.FileRelation):
        new = copy.copy(plan)
        new.pushed_filters = list(plan.pushed_filters)
        new.file_meta = set(plan.file_meta) | needs
        return new
    if isinstance(plan, (L.Filter, L.Limit, L.Sort)):
        child = _attach_file_meta(plan.children[0], needs)
        if child is None:
            return None
        new = copy.copy(plan)
        new.children = (child,)
        return new
    return None


def _persistent_delta(before: dict, after: dict) -> dict:
    """Per-query persistent jit-cache deltas for the QueryEnd fusion
    dict (ops/jit_cache.persistent_info snapshots).  Process-global
    counters, same attribution contract as the pipeline dict's
    jitCacheHits/Misses: under concurrent queries the deltas smear
    across overlapping envelopes — fine for the health checks (which
    key on zero-vs-nonzero), wrong tool for per-tenant billing."""
    return {
        "persistentEnabled": bool(after.get("enabled")),
        "persistentHits": after.get("hits", 0) - before.get("hits", 0),
        "persistentMisses":
            after.get("misses", 0) - before.get("misses", 0),
        "persistentInvalid":
            after.get("invalid", 0) - before.get("invalid", 0),
        "persistentStores":
            after.get("stores", 0) - before.get("stores", 0),
    }


def _is_window(e: Expression) -> bool:
    from spark_rapids_tpu.exec.window import WindowExpression
    inner = e.children[0] if isinstance(e, Alias) else e
    return isinstance(inner, WindowExpression)


def _contains_window(e: Expression) -> bool:
    from spark_rapids_tpu.exec.window import WindowExpression
    if isinstance(e, WindowExpression):
        return True
    return any(_contains_window(c) for c in e.children)


class DataFrame:
    def __init__(self, session, plan: L.LogicalPlan):
        self.session = session
        self.plan = plan
        # plan-template state (plan/template.py): _template is the
        # TemplateInfo active for the CURRENT execution (set fresh per
        # _execute_batches); _prepared is the owning PreparedStatement
        # handle, whose pre-hoisted template and cached physical plan
        # repeats reuse
        self._template = None
        self._prepared = None

    # ------------------------------------------------------------- transforms --
    @property
    def schema(self):
        return self.plan.schema

    @property
    def columns(self) -> List[str]:
        return [n for n, _ in self.plan.schema]

    def select(self, *cols: Union[Col, str]) -> "DataFrame":
        from spark_rapids_tpu.ops.nested_ops import \
            expand_nested_projections
        routed_pw = self._route_pandas_windows(cols)
        if routed_pw is not None:
            return routed_pw
        exprs = [_expr(c) for c in cols]
        needs = _file_meta_needs(exprs, self.plan.schema)
        if needs:
            attached = _attach_file_meta(self.plan, needs)
            if attached is None:
                raise ValueError(
                    "input_file_name()/_metadata are only available "
                    "above a file scan (optionally through "
                    "filter/limit/sort)")
            return DataFrame(self.session, attached).select(*cols)
        exprs = expand_nested_projections(exprs, self.plan.schema)
        gen = self._route_generate(exprs)
        if gen is not None:
            return gen
        routed = self._route_batch_ids(exprs)
        if routed is not None:
            return routed
        win_idx = {i for i, e in enumerate(exprs) if _contains_window(e)}
        if win_idx:
            # lift every WindowExpression (top-level OR nested inside
            # arithmetic, e.g. rev * 100 / sum(rev) over (...)) into a
            # hidden column of one Window node, then project the
            # rewritten expressions over it
            from spark_rapids_tpu.exec.window import WindowExpression
            child_names = [n for n, _ in self.plan.schema]
            prefix = "__w"
            while any(n.startswith(prefix) for n in child_names):
                prefix += "_"
            wexprs: List = []

            def extract(e):
                if isinstance(e, WindowExpression):
                    h = f"{prefix}{len(wexprs)}"
                    wexprs.append((h, e))
                    return UnresolvedColumn(h)
                if not e.children:
                    return e
                return e.with_children([extract(c) for c in e.children])

            final: List[Expression] = []
            for i, e in enumerate(exprs):
                if i not in win_idx:
                    final.append(e)
                    continue
                out_name = e.name if not isinstance(e, Alias) else None
                r = extract(e)
                # a bare window (or windowed arithmetic) keeps its
                # pretty output name; Alias.with_children keeps its own
                final.append(r if out_name is None else
                             Alias(r, out_name))
            wplan = L.Window(wexprs, self.plan)
            return DataFrame(self.session, L.Project(final, wplan))
        return DataFrame(self.session, L.Project(exprs, self.plan))

    def _route_pandas_windows(self, cols) -> Optional["DataFrame"]:
        """Route pandas-UDF-over-window markers into a WindowInPandas
        node, then select the requested columns on top.  Result columns
        get collision-proof internal names so replacing an existing
        column (withColumn semantics) never duplicates a schema entry;
        the final projection re-enters select() so nested expansion /
        explode routing still apply to the other columns."""
        from spark_rapids_tpu.api.functions import _PandasWindowCall
        if not any(isinstance(c, _PandasWindowCall) for c in cols):
            return None
        child_names = [n for n, _ in self.plan.schema]
        prefix = "_pw"
        while any(n.startswith(prefix) for n in child_names):
            prefix += "_"
        calls, final = [], []
        for c in cols:
            if isinstance(c, _PandasWindowCall):
                internal = f"{prefix}{len(calls)}"
                calls.append((internal, c.call.fn, c.call.arg_name,
                              c.call.return_type, c.spec_data()))
                final.append(Alias(UnresolvedColumn(internal),
                                   c.out_name))
            else:
                final.append(c)
        base = DataFrame(self.session,
                         L.WindowInPandas(calls, self.plan))
        return base.select(*final)

    def _route_generate(self, exprs) -> Optional["DataFrame"]:
        """Route F.explode/F.posexplode in a select into an L.Generate
        node (Spark plans Generate the same way)."""
        from spark_rapids_tpu.api.functions import _ExplodeMarker
        from spark_rapids_tpu.ops.expressions import Alias

        def marker_of(e):
            inner = e.children[0] if isinstance(e, Alias) else e
            return inner if isinstance(inner, _ExplodeMarker) else None

        marked = [(i, e, marker_of(e)) for i, e in enumerate(exprs)]
        gens = [(i, e, m) for i, e, m in marked if m is not None]
        if not gens:
            return None
        if len(gens) > 1:
            raise ValueError("only one explode per select is supported")
        i, e, m = gens[0]
        required = [x for j, x in enumerate(exprs) if j != i]
        col_name = e.alias if isinstance(e, Alias) else "col"
        return DataFrame(self.session, L.Generate(
            m.child, required, m.position, self.plan, col_name=col_name))

    def _route_batch_ids(self, exprs) -> Optional["DataFrame"]:
        """monotonically_increasing_id()/spark_partition_id() need batch
        state: insert a BatchId node and rewrite markers to its columns."""
        from spark_rapids_tpu.ops.misc_exprs import _BatchIdMarker

        def rewrite(e):
            if isinstance(e, _BatchIdMarker):
                return UnresolvedColumn(
                    "__mid" if e.kind == "mid" else "__pid")
            if not e.children:
                return e
            return e.with_children([rewrite(c) for c in e.children])

        def has_marker(e):
            if isinstance(e, _BatchIdMarker):
                return True
            return any(has_marker(c) for c in e.children)

        if not any(has_marker(e) for e in exprs):
            return None
        base = L.BatchId(self.plan)
        out = []
        for e in exprs:
            r = rewrite(e)
            if isinstance(r, UnresolvedColumn) and r.col_name in (
                    "__mid", "__pid"):
                r = Alias(r, e.name)
            out.append(r)
        return DataFrame(self.session, L.Project(out, base))

    def filter(self, condition: Col) -> "DataFrame":
        cond = _expr(condition)
        needs = _file_meta_needs([cond], self.plan.schema)
        if needs:
            attached = _attach_file_meta(self.plan, needs)
            if attached is None:
                raise ValueError(
                    "input_file_name()/_metadata are only available "
                    "above a file scan (optionally through "
                    "filter/limit/sort)")
            return DataFrame(self.session, attached).filter(condition)
        return DataFrame(self.session, L.Filter(cond, self.plan))

    where = filter

    def withColumn(self, name: str, c: Col) -> "DataFrame":
        from spark_rapids_tpu.api.functions import _PandasWindowCall
        if isinstance(c, _PandasWindowCall):
            wrapped = c.alias(name)
        else:
            wrapped = Alias(_expr(c), name)
        exprs: List = []
        replaced = False
        for n, _ in self.plan.schema:
            if n == name:
                exprs.append(wrapped)
                replaced = True
            else:
                exprs.append(UnresolvedColumn(n))
        if not replaced:
            exprs.append(wrapped)
        return self.select(*exprs)

    with_column = withColumn

    def withColumnRenamed(self, old: str, new: str) -> "DataFrame":
        exprs = [Alias(UnresolvedColumn(n), new) if n == old
                 else UnresolvedColumn(n) for n, _ in self.plan.schema]
        return DataFrame(self.session, L.Project(exprs, self.plan))

    def drop(self, *names: str) -> "DataFrame":
        exprs = [UnresolvedColumn(n) for n, _ in self.plan.schema
                 if n not in names]
        return DataFrame(self.session, L.Project(exprs, self.plan))

    def groupBy(self, *cols: Union[Col, str]) -> "GroupedData":
        from spark_rapids_tpu.ops.datetime_ops import TimeWindow
        from spark_rapids_tpu.ops.nested_ops import CreateNamedStruct
        exprs: List[Expression] = []
        for c in cols:
            e = _expr(c)
            inner = e.children[0] if isinstance(e, Alias) else e
            if isinstance(inner, CreateNamedStruct):
                # struct group keys (e.g. F.window(...)) shred into one
                # key per field; the shredded names reassemble into the
                # struct column at the output boundary
                first = inner.pairs[0][1]
                if isinstance(first, TimeWindow) and \
                        first.slide_us < first.window_us:
                    if len(cols) != 1:
                        raise ValueError(
                            "sliding window(...) must be the only "
                            "grouping column")
                    return self._group_by_sliding_window(e, first)
                name = e.name if isinstance(e, Alias) else "struct"
                exprs.extend(Alias(fe, f"{name}.{fn}")
                             for fn, fe in inner.pairs)
                continue
            exprs.append(e)
        return GroupedData(self, exprs)

    def _group_by_sliding_window(self, aliased, tw) -> "GroupedData":
        """Sliding time windows: each row belongs to up to
        ceil(window/slide) overlapping windows — expand one replica per
        overlap (Spark's TimeWindowing rule lowers through Expand the
        same way), keep replicas whose window really contains the
        timestamp, then group by (start, end)."""
        from spark_rapids_tpu.exec.expand import Expand
        from spark_rapids_tpu.ops import predicates as preds
        from spark_rapids_tpu.ops.datetime_ops import TimeWindow
        name = aliased.name if isinstance(aliased, Alias) else "window"
        s_col, e_col = f"{name}.start", f"{name}.end"
        k = -(-tw.window_us // tw.slide_us)
        base_names = [n for n, _ in self.plan.schema]
        projections = []
        for i in range(k):
            shift = i * tw.slide_us
            proj: List[Expression] = [UnresolvedColumn(n)
                                      for n in base_names]
            proj.append(Alias(TimeWindow(tw.child, tw.window_us,
                                         tw.slide_us, tw.start_us,
                                         "start", shift), s_col))
            proj.append(Alias(TimeWindow(tw.child, tw.window_us,
                                         tw.slide_us, tw.start_us,
                                         "end", shift), e_col))
            projections.append(proj)
        expand = Expand(projections, base_names + [s_col, e_col],
                        self.plan)
        cond = preds.GreaterThan(UnresolvedColumn(e_col), tw.child)
        filtered = L.Filter(cond, expand)
        return GroupedData(DataFrame(self.session, filtered),
                           [UnresolvedColumn(s_col),
                            UnresolvedColumn(e_col)])

    group_by = groupBy

    def rollup(self, *cols: Union[Col, str]) -> "GroupedData":
        """GROUP BY ROLLUP: hierarchical subtotals (a,b) -> (a) -> ()
        (lowered through Expand — GpuExpandExec analog)."""
        from spark_rapids_tpu.exec.expand import rollup_sets
        exprs = [_expr(c) for c in cols]
        return GroupedData(self, exprs, sets=rollup_sets(len(exprs)))

    def cube(self, *cols: Union[Col, str]) -> "GroupedData":
        """GROUP BY CUBE: all 2^n grouping-column subsets."""
        from spark_rapids_tpu.exec.expand import cube_sets
        exprs = [_expr(c) for c in cols]
        return GroupedData(self, exprs, sets=cube_sets(len(exprs)))

    def groupingSets(self, sets, *cols: Union[Col, str]) -> "GroupedData":
        """Explicit GROUPING SETS: ``sets`` is a list of lists of column
        names (each a subset of ``cols``)."""
        exprs = [_expr(c) for c in cols]
        names = [e.name for e in exprs]
        idx_sets = []
        for s in sets:
            idx = []
            for item in s:
                nm = item if isinstance(item, str) else _expr(item).name
                if nm not in names:
                    raise ValueError(
                        f"grouping set column {nm!r} is not in the "
                        f"grouping columns {names}")
                idx.append(names.index(nm))
            idx_sets.append(idx)
        return GroupedData(self, exprs, sets=idx_sets)

    grouping_sets = groupingSets

    def agg(self, *aggs: Col) -> "DataFrame":
        return GroupedData(self, []).agg(*aggs)

    def join(self, other: "DataFrame", on, how: str = "inner"
             ) -> "DataFrame":
        how = {"left_outer": "left", "right_outer": "right",
               "outer": "full", "full_outer": "full", "leftsemi": "semi",
               "left_semi": "semi", "leftanti": "anti",
               "left_anti": "anti"}.get(how, how)
        if isinstance(on, (str,)) or (isinstance(on, (list, tuple)) and
                                      all(isinstance(k, str) for k in on)):
            keys = [on] if isinstance(on, str) else list(on)
            lk = [UnresolvedColumn(k) for k in keys]
            rk = [UnresolvedColumn(k) for k in keys]
            return DataFrame(self.session, L.Join(
                self.plan, other.plan, lk, rk, how, using=keys))
        if isinstance(on, (list, tuple)):
            # PySpark form: a list of Column conditions, AND-ed together
            from spark_rapids_tpu.ops import predicates as preds
            exprs = [_expr(c) for c in on]
            combined = exprs[0]
            for c in exprs[1:]:
                combined = preds.And(combined, c)
            on = Col(combined)
        # expression join condition: split equi conjuncts (left-col ==
        # right-col) into hash-join keys, the rest into a residual
        # condition (GpuHashJoin equi extraction; pure-residual inner
        # joins become nested-loop = cross + filter)
        cond = _expr(on)
        lnames = {n for n, _ in self.plan.schema}
        rnames = {n for n, _ in other.plan.schema}
        dup = lnames & rnames
        if dup:
            raise ValueError(
                f"expression joins need distinct column names on the two "
                f"sides; duplicated: {sorted(dup)}")
        from spark_rapids_tpu.ops import predicates as preds

        def conjuncts(e):
            if isinstance(e, preds.And):
                return conjuncts(e.children[0]) + conjuncts(e.children[1])
            return [e]

        def side_of(e):
            refs = set(e.references())
            if refs and refs <= lnames:
                return "l"
            if refs and refs <= rnames:
                return "r"
            return None

        lk, rk, residual = [], [], []
        for c in conjuncts(cond):
            if isinstance(c, preds.EqualTo):
                a, b = c.children
                sa, sb = side_of(a), side_of(b)
                if sa == "l" and sb == "r":
                    lk.append(a)
                    rk.append(b)
                    continue
                if sa == "r" and sb == "l":
                    lk.append(b)
                    rk.append(a)
                    continue
            residual.append(c)
        condition = None
        if residual:
            condition = residual[0]
            for c in residual[1:]:
                condition = preds.And(condition, c)
        return DataFrame(self.session, L.Join(
            self.plan, other.plan, lk, rk, how, condition=condition))

    def crossJoin(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(self.session, L.Join(
            self.plan, other.plan, [], [], "cross"))

    def orderBy(self, *keys: Union[Col, str, SortKey]) -> "DataFrame":
        orders = []
        for k in keys:
            if isinstance(k, SortKey):
                orders.append((k.expr, k.descending, k.nulls_first))
            else:
                orders.append((_expr(k), False, True))
        return DataFrame(self.session, L.Sort(orders, self.plan))

    sort = orderBy

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(self.session, L.Limit(n, self.plan))

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(self.session, L.Union([self.plan, other.plan]))

    unionAll = union

    def distinct(self) -> "DataFrame":
        return DataFrame(self.session, L.Aggregate(
            [UnresolvedColumn(n) for n, _ in self.plan.schema], [],
            self.plan))

    # --------------------------------------------------------------- caching --
    def cache(self) -> "DataFrame":
        """Mark this plan for caching: the first action materializes it as
        compressed host columnar frames (ParquetCachedBatchSerializer
        analog); later queries containing this plan read the cache."""
        self.session.cache_manager.register(self.plan)
        return self

    persist = cache

    def unpersist(self) -> "DataFrame":
        self.session.cache_manager.unregister(self.plan)
        return self

    @property
    def is_cached(self) -> bool:
        return self.session.cache_manager.lookup(self.plan) is not None

    # --------------------------------------------------------------- actions --
    def _execute_batches(self) -> List[ColumnarBatch]:
        # every query action runs inside a QueryContext (serving/): the
        # per-query scope for event attribution, checkpoint lineage,
        # budgets and injection scoping — its exit purges stale
        # thread-ident adoptions so nothing of this query leaks into
        # the next one that recycles a thread.  Admission (the
        # byte-weighted fair semaphore) is acquired before any device
        # work and released on completion or fatal exit; a rejection
        # is a typed AdmissionFault for THIS query only.
        #
        # Under the context, the recovery driver re-drives classified
        # transient faults down the degradation ladder (retry -> spill
        # -> smaller batches -> single device -> CPU); fatal faults
        # re-raise untouched (robustness/driver.py).  Mesh sessions
        # additionally carry a per-query stage-checkpoint lineage log
        # so retry-class re-attempts resume from the last completed
        # exchange stage instead of re-running from source
        from spark_rapids_tpu.robustness.checkpoint import (
            CheckpointManager)
        from spark_rapids_tpu.robustness.driver import QueryRetryDriver
        from spark_rapids_tpu.serving.context import QueryContext
        with QueryContext(self.session) as ctx:
            # plan-keyed result cache (serving/reuse.py): consulted
            # BEFORE planning or admission — a verified hit (exact
            # plan text + matching input fingerprint + CRC) answers
            # with zero executions and zero queueing; the token
            # carries the PRE-execution fingerprint for the store.
            # A continuous-ingest tick's OWN executions bypass BOTH
            # reuse stores (no lookup, no store, no shared-stage
            # registration): a tick's plans over transient state
            # relations carry id()-keyed in-memory fingerprints whose
            # no-alias invariant ("the owning plan keeps its batches
            # alive") does not hold for state batches freed at the
            # next commit, and shared writes would outlive the epoch
            # store's rollback — the tick's crash-consistency
            # contract rests on the epoch store alone, and committed
            # tick work shares through the commit-published epoch
            # tier instead.  The gate is the tick-EXECUTION marker,
            # not the coarse tick-scope one: an ordinary query issued
            # from within a tick callback (an on_commit sink-side
            # lookup) caches normally
            # (robustness/incremental.in_tick_execution)
            from spark_rapids_tpu.robustness.incremental import (
                in_tick_execution)
            tick = in_tick_execution()
            # parameterized plan templates (plan/template.py): hoist
            # constant literals into typed parameter slots so the
            # jit / AOT / fused-stage tiers key on the TEMPLATE and
            # the values ride as device-scalar dispatch arguments —
            # zero retrace across literal churn.  Default-off; tick
            # executions keep the exact path (their plans are over
            # transient state relations).  A prepared handle
            # (api/prepared.py) injects its pre-hoisted template
            # instead of re-hoisting per run.
            from spark_rapids_tpu.config import rapids_conf as rc
            prep = getattr(self, "_prepared", None)
            info = None
            if prep is not None:
                info = prep.info
                self._template = info
            elif not tick and \
                    self.session.conf.get(rc.TEMPLATE_ENABLED):
                from spark_rapids_tpu.plan.template import (
                    hoist_literals)
                info = hoist_literals(self.plan)
                self._template = info if info.hoisted else None
            else:
                self._template = None
            if info is not None:
                # template facts ride the QueryEnd sharing dict: the
                # profiling health check groups repeats by fingerprint
                # and explains a template that bought nothing via the
                # refusal list (knobs-off streams stay HEAD-identical
                # — this only fires when template.enabled is on)
                ctx.sharing["template"] = {
                    "fingerprint": info.fingerprint[:16],
                    "params": info.param_count,
                    "refusals": sorted({r for r, _ in info.refusals}),
                }
            cache = getattr(self.session, "result_cache", None)
            pend = None
            use_template_cache = (
                cache is not None and not tick
                and self._template is not None
                and self.session.conf.get(
                    rc.TEMPLATE_RESULT_CACHE_ENABLED))
            if use_template_cache:
                # template tier: keyed on (template fingerprint,
                # parameter vector).  The template PLAN's exact key is
                # value-free (ParamSlot cache keys carry no binding),
                # so templated runs must never key the exact tier on
                # it — two bindings would alias.
                pend = cache.offer_template(self._template)
            elif cache is not None and not tick:
                pend = cache.offer(self.plan)
            if pend is not None and pend.hit:
                return self._answer_from_cache(pend)
            ctx.admit()
            if pend is not None and ctx.admission_wait_ms > 0.5:
                # the query actually QUEUED: an identical twin ahead
                # of it may have stored the answer while it waited
                # (the dashboard-stampede shape — N near-simultaneous
                # duplicates should cost ONE execution, not N), so
                # re-consult before paying for a redundant run.  The
                # first offer already counted this query's miss —
                # count_miss=False keeps the hit rate honest.
                pend = cache.offer_template(
                    self._template, count_miss=False) \
                    if use_template_cache else \
                    cache.offer(self.plan, count_miss=False)
                if pend.hit:
                    return self._answer_from_cache(pend)
            driver = QueryRetryDriver(self.session)
            # cross-query stage cache: when enabled, the SHARED
            # always_resume store rides as this query's checkpoint
            # manager — completed exchange stages register for every
            # tenant and input-fingerprinted subtrees splice on first
            # attempts.  The per-query manager is the fallback (its
            # lineage dies with the query).
            shared = getattr(self.session, "shared_stages", None)
            use_shared = (shared is not None and shared.enabled
                          and not tick
                          and getattr(self.session, "mesh", None)
                          is not None
                          and self.session.checkpoints is None)
            mgr = None
            if use_shared:
                self.session.checkpoints = shared
            else:
                mgr = CheckpointManager.for_query(self.session)
            try:
                batches = driver.run(self._attempt_batches)
                if pend is not None:
                    cache.store(pend, batches)
                return batches
            except Exception as exc:
                # a fatal/exhausted ladder still flushes its full
                # recovery/watchdog/checkpoint trail to the eventlog,
                # so post-mortems see what was tried —
                # QueryInfo.recovery is no longer complete only when
                # the ladder succeeds
                self._flush_fatal_trail(driver, exc)
                raise
            finally:
                if use_shared:
                    # detach only (never finish(): the shared store's
                    # entries outlive this query by design); drain any
                    # tally the QueryEnd didn't pop (events disabled)
                    # so recycled thread idents never inherit it
                    shared.take_query_stats()
                    if self.session.checkpoints is shared:
                        self.session.checkpoints = None
                elif mgr is not None:
                    mgr.finish()

    def _answer_from_cache(self, pend) -> List[ColumnarBatch]:
        """Result-cache hit: emit a complete (trivial) query envelope
        so the event stream, profiling and concurrency timeline see
        the query, then answer from the store — zero executions."""
        events = getattr(self.session, "events", None)
        note = "template-cache hit" \
            if getattr(pend, "tier", "exact") == "template" \
            else "result-cache hit"
        if events is not None and events.enabled:
            qid = next(self.session._query_ids)
            self.session._current_qid = qid
            events.emit("QueryStart", queryId=qid,
                        logicalPlan=self.plan.tree_string(),
                        physicalPlan="ResultCache",
                        explain=note)
            events.emit("QueryEnd", queryId=qid, status="success",
                        durationMs=0.0, metrics={}, spill={},
                        retry={}, sharing=self._sharing_info(),
                        explain=note)
        self.session.last_dist_explain = note
        return pend.batches

    def _flush_fatal_trail(self, driver, exc: BaseException) -> None:
        ev = getattr(self.session, "events", None)
        if ev is None or not ev.enabled:
            return
        from spark_rapids_tpu.robustness.watchdog import watchdog_metrics
        mgr = getattr(self.session, "checkpoints", None)
        try:
            ev.emit(
                "QueryFatal",
                queryId=getattr(self.session, "_current_qid", None),
                error=f"{type(exc).__name__}: {exc}",
                recovery=list(getattr(driver, "trail", [])),
                watchdog=watchdog_metrics.snapshot(),
                checkpoint=mgr.snapshot() if mgr is not None else {})
        except Exception:
            pass  # the post-mortem record must never mask the fault

    def _attempt_batches(self, mode) -> List[ColumnarBatch]:
        # every attempt runs in a watchdog query scope: stale
        # cancellation tokens from a previous attempt are cleared, and
        # the query's deadline budget (serving.deadlineBudgetMs, else
        # spark.rapids.tpu.watchdog.queryDeadlineMs) bounds this
        # attempt's wall time — an overrun is a retryable TimeoutFault
        # delivered at the next checkpoint, so a hung attempt
        # re-drives down the ladder instead of blocking forever
        from spark_rapids_tpu.robustness import watchdog
        from spark_rapids_tpu.serving import context as qc
        ctx = qc.current()
        deadline = ctx.deadline_budget_ms \
            if ctx is not None and ctx.deadline_budget_ms else None
        with watchdog.query_scope(self.session, deadline_ms=deadline):
            return self._attempt_batches_impl(mode)

    def _admission_info(self) -> dict:
        """What admission cost this query (QueryEnd payload)."""
        from spark_rapids_tpu.serving import context as qc
        ctx = qc.current()
        return ctx.admission_info() if ctx is not None else {}

    def _sharing_info(self) -> dict:
        """Cross-query reuse facts for the QueryEnd ``sharing`` dict:
        result-cache hit/miss flags (serving/reuse.py offer notes; a
        STORE lands after the envelope closed and rides the
        ResultCacheStore event instead), the shared stage store's
        write/splice tallies for this query, and the interleaver's
        wait/slice accounting.  EMPTY —
        and therefore absent from the event — when every reuse knob is
        off, so the knobs-off event stream is bit-identical to HEAD."""
        from spark_rapids_tpu.serving import context as qc
        ctx = qc.current()
        out = {}
        if ctx is not None:
            out.update(ctx.sharing)
            t = ctx.interleave_ticket
            if t is not None:
                out["interleave"] = t.info()
        shared = getattr(self.session, "shared_stages", None)
        if shared is not None and shared.enabled:
            out.update(shared.take_query_stats())
        return out

    def _attempt_batches_impl(self, mode) -> List[ColumnarBatch]:
        import time as _time
        from spark_rapids_tpu.api.session import TpuSession
        # conf resolved at call time (retry budget, semaphore) follows
        # the session EXECUTING the query, not the last-constructed one
        TpuSession._active = self.session
        # a failure before this attempt draws its qid must not inherit
        # the previous query's id on its RecoveryAction events
        self.session._current_qid = None
        mesh = getattr(self.session, "mesh", None)
        if mesh is not None and \
                (not mode.use_mesh or mode.batch_scale != 1.0):
            self.session.last_dist_explain = (
                "demoted: single-device replan (query recovery)"
                if mode.batch_scale == 1.0 else
                "demoted: single-device split-batch replan "
                "(query recovery)")
        template = getattr(self, "_template", None)
        if mesh is not None and template is not None:
            # distributed/parallel kernels build EmitContexts without
            # a parameter vector: a templated plan executes on the
            # single-process engine (whose stage + fused-aggregate
            # kernels thread params) rather than silently failing
            # every slot emit on the mesh
            self.session.last_dist_explain = (
                "template: single-process execution "
                "(parameterized kernels)")
        if mode.use_mesh and mode.batch_scale == 1.0 and \
                mesh is not None and template is None:
            # mesh session: offer the plan to the distributed planner
            # first (planner-inserted exchange analog); unsupported plans
            # fall through to the single-process engine.  The split
            # rung (batch_scale < 1) skips this branch: the distributed
            # plan has no batch knob, so re-offering it would re-run
            # the identical plan that just failed
            from spark_rapids_tpu.exec.fusion import (fusion_metrics,
                                                      wire_delta)
            from spark_rapids_tpu.ops.jit_cache import persistent_info
            from spark_rapids_tpu.parallel.dist_planner import (
                try_distributed)
            from spark_rapids_tpu.parallel.exchange_async import (
                ExchangeOverlapMetrics, overlap_metrics_for_session)
            from spark_rapids_tpu.parallel.shuffle import (
                ShuffleWireMetrics, metrics_for_session)
            from spark_rapids_tpu.utils import tracing
            events = getattr(self.session, "events", None)
            t0 = _time.perf_counter()
            wire = metrics_for_session(self.session)
            wire0 = wire.snapshot()
            fm0 = fusion_metrics.snapshot()
            overlap = overlap_metrics_for_session(self.session)
            overlap0 = overlap.snapshot()
            pjit0 = persistent_info()
            # gray-failure counter snapshot: QueryEnd pins THIS query's
            # hedge/quarantine deltas (None tracker = knob off, and the
            # event field is absent — bit-identical A/B)
            gray = getattr(self.session, "gray_health", None)
            gray0 = gray.query_counters() if gray is not None else None
            # the envelope opens BEFORE execution so everything the
            # attempt emits mid-flight — CheckpointWrite/Resume,
            # RecoveryAction, WatchdogTrip — carries this attempt's
            # qid and parses into the right QueryInfo (a failed
            # distributed attempt used to leave them unattributed);
            # QueryEnd restates the final explain once it is known
            qid = None
            if events is not None and events.enabled:
                qid = next(self.session._query_ids)
                self.session._current_qid = qid
                events.emit(
                    "QueryStart", queryId=qid,
                    logicalPlan=self.plan.tree_string(),
                    physicalPlan="DistributedPlan",
                    explain="distributed attempt")

            def _end(status, shuffle):
                # the span drain runs for EVERY envelope exit — events
                # on or off, success or failure — so trace files exist
                # for faulted attempts and buffers never pile up
                wall_ms = (_time.perf_counter() - t0) * 1e3
                spans = tracing.finish_query(self.session, qid,
                                             wall_ms, status)
                # cost-model ledger drain (every exit too: a faulted
                # attempt's envelope carries its replan decision, and
                # the ledger never leaks into the next query); absent
                # from the event when the model is off — HEAD parity
                cm = getattr(self.session, "cost_model", None)
                planner = cm.finish_query() if cm is not None else None
                self.session.last_planner_stats = planner
                if qid is not None:
                    fusion = dict(getattr(self.session,
                                          "last_fusion_stats", None)
                                  or {})
                    fusion.update(_persistent_delta(pjit0,
                                                    persistent_info()))
                    fusion.update(wire_delta(fm0))
                    sh = self._sharing_info()
                    fleet = None
                    if gray is not None:
                        delta = type(gray).counters_delta(
                            gray.query_counters(), gray0)
                        if any(delta.values()) or gray.suspect_hosts():
                            fleet = dict(delta)
                            fleet["suspectHosts"] = gray.suspect_hosts()
                    events.emit(
                        "QueryEnd", queryId=qid, status=status,
                        durationMs=round(wall_ms, 3),
                        metrics={}, spill={}, retry={},
                        distributed=True, shuffle=shuffle,
                        fusion=fusion, spans=spans,
                        admission=self._admission_info(),
                        # absent entirely when every reuse knob is
                        # off — the knobs-off event stream must stay
                        # bit-identical to HEAD
                        **({"sharing": sh} if sh else {}),
                        **({"planner": planner} if planner else {}),
                        **({"fleet": fleet} if fleet else {}),
                        explain=self.session.last_dist_explain)

            try:
                dist = try_distributed(
                    self.session, self.plan,
                    resume=getattr(mode, "resume", False))
            except Exception as exc:
                _end(f"failed: {type(exc).__name__}: {exc}", {})
                raise
            if dist is not None:
                # per-query shuffle-wire delta: collectives launched,
                # bytes moved, padding ratio, overflow retries —
                # QueryInfo.shuffle in the eventlog tools, flagged by
                # the profiling health check when padding > 4x or an
                # exchange fell back to per-column collectives
                shuffle = ShuffleWireMetrics.summarize(
                    ShuffleWireMetrics.delta(wire.snapshot(), wire0))
                # async exchange/compute overlap + host-staging deltas
                # ride the same QueryInfo.shuffle dict (the
                # exchangeOverlapMs metric the MULTICHIP tail and the
                # profiling "exchange overlap" line report)
                shuffle.update(ExchangeOverlapMetrics.delta(
                    overlap.snapshot(), overlap0))
                # session attribute contract: None when the query never
                # exchanged (a distributed scan/filter); the event log
                # still gets the (zeros) dict so every distributed
                # query's QueryInfo.shuffle is present
                self.session.last_shuffle_stats = \
                    shuffle if shuffle.get("exchanges") else None
                _end("success", shuffle)
                return dist
            # unsupported plan: close the envelope cleanly (the
            # fallback reason rides in explain — not a failure) and
            # fall through to the single-process engine, which opens
            # its own
            _end("success", {})
        overrides = None
        if mode.batch_scale != 1.0:
            # split-batch rung: re-plan with the scan/coalesce batch
            # sizes scaled down so every operator's working set
            # shrinks.  Planned through a one-off TpuOverrides — batch
            # sizes are captured into the exec nodes at plan time — so
            # the session's conf is never mutated and concurrent
            # queries on other threads keep their own sizes
            from spark_rapids_tpu.config import rapids_conf as rc
            from spark_rapids_tpu.plan.overrides import TpuOverrides
            conf = self.session.conf
            for entry in (rc.READER_BATCH_SIZE_ROWS,
                          rc.BATCH_SIZE_BYTES):
                conf = conf.set(entry.key, max(
                    1, int(conf.get(entry) * mode.batch_scale)))
            overrides = TpuOverrides(conf, self.session.cache_manager)
        return self._run_single_process(mode, overrides)

    def _drive(self, exec_plan) -> List[ColumnarBatch]:
        """Materialize the plan's batches — through the asynchronous
        pipeline driver (exec/pipeline.py) when enabled, else the
        sequential pull loop.  Pipeline stats land on
        ``session.last_pipeline_stats`` either way (None when
        sequential) so benches and the event log can attribute overlap
        wins."""
        from spark_rapids_tpu.config import rapids_conf as rc
        self.session.last_pipeline_stats = None
        conf = self.session.conf
        # fair interleaver (serving/scheduler.py): every batch pull
        # passes the weighted round-robin timeslice gate, so admitted
        # queries share the device batch-for-batch instead of FIFO
        # occupancy.  When pipelined, the wrapped iterator runs on the
        # worker thread — exactly the thread doing the dispatching.
        source = exec_plan.execute()
        sched = getattr(self.session, "interleaver", None)
        if sched is not None:
            from spark_rapids_tpu.serving import context as qc
            ctx = qc.current()
            ticket = getattr(ctx, "interleave_ticket", None) \
                if ctx is not None else None
            if ticket is not None:
                source = sched.interleaved(source, ticket)
        if not conf.get(rc.PIPELINE_ENABLED):
            return list(source)
        from spark_rapids_tpu.exec.pipeline import (
            PipelineStats, pipelined)
        stats = PipelineStats(conf.get(rc.PIPELINE_DEPTH))
        try:
            return list(pipelined(
                source, stats.depth,
                catalog=getattr(self.session, "memory_catalog", None),
                stats=stats,
                semaphore=getattr(self.session, "semaphore", None)))
        finally:
            self.session.last_pipeline_stats = stats

    def _plan_physical(self, mode, overrides=None):
        template = getattr(self, "_template", None)
        logical = template.plan if template is not None else self.plan
        prep = getattr(self, "_prepared", None)
        if mode.cpu_only:
            exec_plan = self.session.plan_cpu_only(logical)
        elif prep is not None and template is not None \
                and overrides is None:
            # prepared repeat on the baseline rung: reuse the handle's
            # cached physical plan — zero planning / override-translation
            # passes.  Ladder re-drives (cpu_only above, split-batch
            # overrides here) re-plan: their rung parameters are
            # captured into exec nodes at plan time.
            exec_plan = prep.exec_plan
            if exec_plan is None:
                exec_plan = self.session.plan(logical)
                prep.exec_plan = exec_plan
        else:
            exec_plan = self.session.plan(logical,
                                          overrides=overrides)
        return exec_plan

    def _run_single_process(self, mode,
                            overrides=None) -> List[ColumnarBatch]:
        import time as _time
        from spark_rapids_tpu.utils import tracing
        # the envelope's wall starts BEFORE planning and planning is a
        # span of its own, so the rollup's unattributedMs means what
        # its name says
        t0 = _time.perf_counter()
        with tracing.span("plan.physical"):
            exec_plan = self._plan_physical(mode, overrides)
        self._last_exec = exec_plan
        events = getattr(self.session, "events", None)
        if events is None or not events.enabled:
            from spark_rapids_tpu.exec.fusion import (
                collect_runtime_savings, fusion_metrics, wire_delta)
            from spark_rapids_tpu.ops.jit_cache import persistent_info
            self.session._current_qid = None
            p0 = persistent_info()
            fm0 = fusion_metrics.snapshot()
            status = "success"
            try:
                return self._drive(exec_plan)
            except Exception as e:
                status = f"failed: {type(e).__name__}"
                raise
            finally:
                # session attribute contract matches the distributed
                # path: last_fusion_stats is set whether or not an
                # event log is attached (bench/tests read it)
                ov = overrides or self.session.overrides
                fusion = dict(getattr(ov, "last_fusion", None) or {})
                fusion.update(collect_runtime_savings(exec_plan))
                fusion.update(_persistent_delta(p0, persistent_info()))
                fusion.update(wire_delta(fm0))
                self.session.last_fusion_stats = fusion
                # span drain runs with or without an event log: bench
                # reads session.last_span_stats, and trace files must
                # exist for logless sessions too
                tracing.finish_query(
                    self.session, None,
                    (_time.perf_counter() - t0) * 1e3, status)
                cm = getattr(self.session, "cost_model", None)
                self.session.last_planner_stats = \
                    cm.finish_query() if cm is not None else None
        qid = next(self.session._query_ids)
        # the recovery driver stamps RecoveryAction events with the qid
        # of the attempt that failed
        self.session._current_qid = qid
        events.emit("QueryStart", queryId=qid,
                    logicalPlan=self.plan.tree_string(),
                    physicalPlan=exec_plan.tree_string(),
                    explain=(overrides or
                             self.session.overrides).last_explain)
        cat = getattr(self.session, "memory_catalog", None)
        host0 = cat.spilled_to_host_total if cat else 0
        disk0 = cat.spilled_to_disk_total if cat else 0
        from spark_rapids_tpu.memory.retry import retry_metrics
        # thread-local view: concurrent queries on other threads must not
        # contaminate this query's attribution
        retry0 = retry_metrics.snapshot_local()
        from spark_rapids_tpu.exec.fusion import fusion_metrics
        from spark_rapids_tpu.ops.jit_cache import (cache_info,
                                                    persistent_info)
        jit0 = cache_info()
        pjit0 = persistent_info()
        fm0 = fusion_metrics.snapshot()
        status = "success"
        try:
            return self._drive(exec_plan)
        except Exception as e:
            status = f"failed: {type(e).__name__}: {e}"
            raise
        finally:
            # per-query deltas of the session-cumulative spill counters
            spill = {} if cat is None else {
                "spilledToHostBytes": cat.spilled_to_host_total - host0,
                "spilledToDiskBytes": cat.spilled_to_disk_total - disk0,
            }
            retry1 = retry_metrics.snapshot_local()
            ps = getattr(self.session, "last_pipeline_stats", None)
            jit1 = cache_info()
            pipeline = ps.as_dict() if ps is not None else {}
            pipeline["jitCacheHits"] = jit1["hits"] - jit0["hits"]
            pipeline["jitCacheMisses"] = \
                jit1["misses"] - jit0["misses"]
            # per-query whole-stage fusion attribution: planned chains
            # from the planner, runtime dispatch savings from the
            # executed tree, persistent-tier deltas from the jit cache
            from spark_rapids_tpu.exec.fusion import (
                collect_runtime_savings, wire_delta)
            ov = overrides or self.session.overrides
            fusion = dict(getattr(ov, "last_fusion", None) or {})
            fusion.update(collect_runtime_savings(exec_plan))
            fusion.update(_persistent_delta(pjit0, persistent_info()))
            fusion.update(wire_delta(fm0))
            self.session.last_fusion_stats = fusion
            wall_ms = (_time.perf_counter() - t0) * 1e3
            spans = tracing.finish_query(self.session, qid, wall_ms,
                                         status)
            sh = self._sharing_info()
            node_metrics = exec_plan.collect_metrics()
            cm = getattr(self.session, "cost_model", None)
            planner = None
            if cm is not None:
                # per-op observed device us/row — the evidence the
                # unified CBO reads over its calibration file (the
                # metrics were already materialized for the event)
                cm.fold_op_metrics(node_metrics)
                planner = cm.finish_query()
            self.session.last_planner_stats = planner
            events.emit(
                "QueryEnd", queryId=qid, status=status,
                durationMs=round(wall_ms, 3),
                metrics=node_metrics, spill=spill,
                retry={k: retry1[k] - retry0[k] for k in retry1},
                pipeline=pipeline, fusion=fusion, spans=spans,
                admission=self._admission_info(),
                # absent when every reuse knob is off (HEAD parity)
                **({"sharing": sh} if sh else {}),
                **({"planner": planner} if planner else {}))

    def to_arrow(self):
        import pyarrow as pa
        from spark_rapids_tpu.columnar import nested
        batches = self._execute_batches()
        if not batches:
            from spark_rapids_tpu.columnar.batch import empty_batch
            table = empty_batch(self.plan.schema).to_arrow()
        else:
            table = pa.concat_tables(b.to_arrow() for b in batches)
        # shredded struct/map columns reassemble at the output boundary
        return nested.assemble_table(table)

    def createOrReplaceTempView(self, name: str) -> None:
        self.session.register_view(name, self)

    create_or_replace_temp_view = createOrReplaceTempView

    def to_device_batches(self):
        """ML interop, streaming form (ColumnarRdd analog —
        /root/reference sql-plugin ColumnarRdd: export the device table
        per partition to ML consumers without a host round trip).
        Yields the engine's internal device-resident ColumnarBatches
        one at a time (bounded memory — batches are NOT materialized up
        front; this path skips query event logging); columns expose jax
        arrays as ``.data``/``.validity``."""
        from spark_rapids_tpu.api.session import TpuSession
        TpuSession._active = self.session
        exec_plan = self.session.plan(self.plan)
        self._last_exec = exec_plan
        yield from exec_plan.execute()

    def to_jax(self):
        """ML interop, materialized form: the full result as a dict of
        column name -> jax device array (plus ``name__mask`` boolean
        validity arrays for nullable columns), trimmed to the row
        count.  Fixed-width columns only — strings/nested types have no
        dense tensor form; project them away first."""
        import jax.numpy as jnp
        from spark_rapids_tpu.ops.concat import concat_batches
        names = [n for n, _ in self.plan.schema]
        for name, dt in self.plan.schema:
            if dt.has_offsets or dt.is_nested:
                raise ValueError(
                    f"to_jax(): column {name!r} has type {dt}; only "
                    "fixed-width columns export as dense arrays")
            if name.endswith("__mask") and \
                    name[:-len("__mask")] in names:
                raise ValueError(
                    f"to_jax(): column {name!r} collides with the "
                    "validity-mask output key for "
                    f"{name[:-len('__mask')]!r}; alias it first")
        batches = self._execute_batches()
        if not batches:
            return {name: jnp.zeros(0, dtype=dt.storage)
                    for name, dt in self.plan.schema}
        merged = concat_batches(batches)
        out = {}
        n = merged.nrows
        for name, col in merged.columns.items():
            out[name] = col.data[:n]
            if col.validity is not None:
                out[name + "__mask"] = col.validity[:n]
        return out

    def to_pandas(self):
        return self.to_arrow().to_pandas()

    toPandas = to_pandas

    def collect(self) -> List[tuple]:
        table = self.to_arrow()
        cols = [table.column(i).to_pylist()
                for i in range(table.num_columns)]
        return list(zip(*cols)) if cols else []

    def mapInPandas(self, fn, schema) -> "DataFrame":
        return DataFrame(self.session, L.MapInPandas(
            fn, _parse_schema(schema), self.plan))

    @property
    def write(self):
        from spark_rapids_tpu.io.writers import DataFrameWriter
        return DataFrameWriter(self)

    def count(self) -> int:
        from spark_rapids_tpu.api import functions as F
        rows = self.agg(F.count().alias("n")).collect()
        return int(rows[0][0])

    def show(self, n: int = 20) -> None:
        print(self.limit(n).to_pandas().to_string(index=False))

    def explain(self, mode: str = "formatted") -> None:
        exec_plan = self.session.plan(self.plan)
        print("== Logical Plan ==")
        print(str(self.plan))
        print("== Physical Plan ==")
        print(exec_plan.tree_string())
        print("== TPU Overrides ==")
        print(self.session.overrides.last_explain)


class GroupedData:
    def __init__(self, df: DataFrame, group_exprs: List[Expression],
                 sets: Optional[List[List[int]]] = None):
        self.df = df
        self.group_exprs = group_exprs
        self.sets = sets  # rollup/cube/grouping-sets index lists

    def agg(self, *aggs: Col) -> DataFrame:
        from spark_rapids_tpu.api.functions import _PandasAggCall
        if self.sets is not None:
            return self._agg_grouping_sets(aggs)
        pandas_aggs = [a for a in aggs if isinstance(a, _PandasAggCall)]
        if pandas_aggs:
            if len(pandas_aggs) != len(aggs):
                raise ValueError("cannot mix grouped-agg pandas UDFs "
                                 "with built-in aggregates")
            names = [e.name for e in self.group_exprs]
            specs = [(a.out_name, a.fn, a.arg_name, a.return_type)
                     for a in pandas_aggs]
            return DataFrame(self.df.session, L.AggInPandas(
                names, specs, self.df.plan))
        agg_exprs = [_expr(a) for a in aggs]
        return DataFrame(self.df.session, L.Aggregate(
            self.group_exprs, agg_exprs, self.df.plan))

    def _agg_grouping_sets(self, aggs) -> DataFrame:
        """Lower rollup/cube/grouping sets: Expand (one projection per
        grouping set, aggregated-away keys nulled, plus the grouping-id
        literal) -> Aggregate keyed on (keys..., grouping_id) -> final
        projection resolving grouping()/grouping_id() markers.
        Reference: GpuExpandExec rule (GpuOverrides.scala:3170)."""
        from spark_rapids_tpu.api.functions import (
            _GroupingIdMarker, _GroupingMarker)
        from spark_rapids_tpu.exec.expand import (
            Expand, GROUPING_ID_COL)
        from spark_rapids_tpu.ops import arithmetic as arith
        from spark_rapids_tpu.ops.expressions import Literal
        import numpy as np

        child = self.df.plan
        child_names = [n for n, _ in child.schema]
        n = len(self.group_exprs)

        # group columns: bare refs use the child column directly;
        # computed keys materialize as hidden columns first
        group_cols: List[str] = []
        pre_exprs: List[Expression] = []
        for i, e in enumerate(self.group_exprs):
            if isinstance(e, UnresolvedColumn) and \
                    e.col_name in child_names:
                group_cols.append(e.col_name)
            else:
                hidden = e.name if e.name not in child_names \
                    else f"__gs{i}"
                pre_exprs.append(Alias(e, hidden))
                group_cols.append(hidden)
        base = child
        if pre_exprs:
            base = L.Project(
                [UnresolvedColumn(c) for c in child_names] + pre_exprs,
                child)
        base_names = [nm for nm, _ in base.schema]

        # key slots are SEPARATE copies of the grouping columns (nulled
        # per set); the base columns pass through untouched so aggregate
        # children over a grouping column still see the real values
        # (Spark's Expand does the same duplication)
        from spark_rapids_tpu.exec.expand import grouping_set_projections
        key_exprs = [UnresolvedColumn(c).bind(base.schema)
                     for c in group_cols]
        projections = grouping_set_projections(
            key_exprs, self.sets,
            [UnresolvedColumn(nm) for nm in base_names])
        key_slots = [f"__gk{i}" for i in range(n)]
        expand = Expand(
            projections, key_slots + base_names + [GROUPING_ID_COL],
            base)

        gid_ref = UnresolvedColumn(GROUPING_ID_COL)

        def rewrite(e: Expression) -> Expression:
            if isinstance(e, _GroupingIdMarker):
                return gid_ref
            if isinstance(e, _GroupingMarker):
                target = e.children[0].name
                if target not in group_cols:
                    raise ValueError(
                        f"grouping({target}) references a non-grouping "
                        f"column; grouping columns: {group_cols}")
                bit = n - 1 - group_cols.index(target)
                from spark_rapids_tpu.ops.cast import Cast
                from spark_rapids_tpu.columnar import dtypes as _dts
                return Cast(
                    arith.BitwiseAnd(
                        arith.ShiftRight(gid_ref, Literal(bit)),
                        Literal(np.int64(1))), _dts.INT32)
            if not e.children:
                return e
            return e.with_children([rewrite(c) for c in e.children])

        agg_items: List[Expression] = []
        final_tail: List[Expression] = []  # post-agg select list tail

        def has_marker(e):
            if isinstance(e, (_GroupingIdMarker, _GroupingMarker)):
                return True
            return any(has_marker(c) for c in e.children)

        for a in aggs:
            e = _expr(a)
            if has_marker(e):
                r = rewrite(e)
                final_tail.append(r if isinstance(r, Alias)
                                  else Alias(r, e.name))
            else:
                agg_items.append(e)
                final_tail.append(UnresolvedColumn(e.name))

        agg_plan = L.Aggregate(
            [Alias(UnresolvedColumn(s), c)
             for s, c in zip(key_slots, group_cols)] + [gid_ref],
            agg_items, expand)
        final = [UnresolvedColumn(c) for c in group_cols] + final_tail
        return DataFrame(self.df.session, L.Project(final, agg_plan))

    def count(self) -> DataFrame:
        from spark_rapids_tpu.api import functions as F
        return self.agg(F.count().alias("count"))

    def pivot(self, col, values) -> "PivotedGroupedData":
        """df.groupBy(k).pivot(p, [v1, v2]).sum(x): one output column per
        pivot value (GpuPivotFirst, AggregateFunctions.scala:530).
        Values must be listed explicitly (Spark's implicit distinct-scan
        variant needs an extra query)."""
        return PivotedGroupedData(self.df, self.group_exprs, _expr(col),
                                  list(values))

    def applyInPandas(self, fn, schema) -> DataFrame:
        names = [e.name for e in self.group_exprs]
        return DataFrame(self.df.session, L.MapInPandas(
            fn, _parse_schema(schema), self.df.plan, group_names=names))

    def cogroup(self, other: "GroupedData") -> "CoGroupedData":
        return CoGroupedData(self, other)

    def _simple(self, fname, *cols) -> DataFrame:
        from spark_rapids_tpu.api import functions as F
        fn = getattr(F, fname)
        names = cols or [n for n, dt in self.df.plan.schema
                         if dt.is_numeric and
                         n not in {e.name for e in self.group_exprs}]
        return self.agg(*[fn(c).alias(f"{fname}({c})") for c in names])

    def sum(self, *cols):  # noqa: A003
        return self._simple("sum", *cols)

    def avg(self, *cols):
        return self._simple("avg", *cols)

    def min(self, *cols):  # noqa: A003
        return self._simple("min", *cols)

    def max(self, *cols):  # noqa: A003
        return self._simple("max", *cols)
