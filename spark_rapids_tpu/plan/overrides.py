"""TpuOverrides: plan-replacement rules + meta/tagging framework.

Counterpart of ``GpuOverrides.scala`` (rule registry, ``GpuOverrides.apply``)
and ``RapidsMeta.scala`` (the wrap/tag/convert lifecycle): every logical node
and expression is wrapped in a Meta carrying "will not work on TPU because…"
reasons; supported subtrees convert to TpuExec operators, unsupported ones
fall back to CPU (pandas) execs — the analog of leaving Spark ops on CPU —
and ``explain()`` renders the reasons like `spark.rapids.sql.explain=ALL`.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Type

from spark_rapids_tpu.config.rapids_conf import RapidsConf
from spark_rapids_tpu.ops import arithmetic as arith
from spark_rapids_tpu.ops import predicates as preds
from spark_rapids_tpu.ops.cast import Cast
from spark_rapids_tpu.ops.expressions import (
    Alias, BoundReference, Expression, Literal, ParamSlot, UnresolvedColumn)
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan import typechecks as ts
from spark_rapids_tpu.plan.logical import AggregateExpression


# ------------------------------------------------------- expression registry --

class ExprRule:
    def __init__(self, cls: Type[Expression], sig: ts.TypeSig,
                 note: str = "", incompat: str = ""):
        self.cls = cls
        self.sig = sig
        self.note = note
        # non-empty = documented semantics difference vs CPU Spark; runs
        # only when spark.rapids.sql.incompatibleOps.enabled
        # (RapidsMeta.scala:271 incompat tier)
        self.incompat = incompat


_EXPR_RULES: Dict[Type[Expression], ExprRule] = {}


def expr_rule(cls, sig=ts.COMMON, note="", incompat=""):
    _EXPR_RULES[cls] = ExprRule(cls, sig, note, incompat)


# leaves / structural (ParamSlot: a hoisted literal — plan/template.py)
for c in (Alias, BoundReference, Literal, ParamSlot, UnresolvedColumn, Cast):
    expr_rule(c)
# aggregates may produce arrays (collect_list/collect_set)
expr_rule(AggregateExpression, ts.ALL)

from spark_rapids_tpu.exec.window import WindowExpression  # noqa: E402

expr_rule(WindowExpression)

# strings (stringFunctions.scala analog)
from spark_rapids_tpu.ops import stringops as S  # noqa: E402

for c in (S.Length, S.OctetLength, S.StartsWith, S.EndsWith, S.Contains,
          S.Like, S.EqualsLiteral, S.StringLocate, S.Substring,
          S.StringTrim, S.StringTrimLeft, S.StringTrimRight,
          S.ConcatStrings, S.StringRepeat, S.StringLPad, S.StringRPad,
          S.SubstringIndex):
    expr_rule(c, ts.COMMON)
for c in (S.Upper, S.Lower, S.InitCap):
    expr_rule(c, ts.COMMON, incompat="ASCII-only case mapping")
expr_rule(S.Ascii, ts.COMMON)
expr_rule(S.Chr, ts.COMMON)

# date/time (datetimeExpressions.scala analog)
from spark_rapids_tpu.ops import datetime_ops as D  # noqa: E402

for c in (D.Year, D.Month, D.DayOfMonth, D.Quarter, D.DayOfWeek, D.WeekDay,
          D.DayOfYear, D.LastDay, D.Hour, D.Minute, D.Second, D.DateAdd,
          D.DateSub, D.DateDiff, D.AddMonths, D.MonthsBetween, D.TruncDate,
          D.UnixTimestamp, D.FromUnixTime, D.TimeAdd, D.DateFormatClass,
          D.TimeWindow, D.NextDay):
    expr_rule(c, ts.COMMON)
# GetJsonObject / StringSplit (ops/json_ops.py) have NO rule on purpose:
# they are host-only (CPU fallback + distributed dictionary lowering)

# arithmetic + math (numeric only)
for c in (arith.Add, arith.Subtract, arith.Multiply, arith.Divide,
          arith.IntegralDivide, arith.Remainder, arith.Pmod,
          arith.UnaryMinus, arith.UnaryPositive, arith.Abs, arith.Sqrt,
          arith.Cbrt, arith.Exp, arith.Expm1, arith.Log, arith.Log2,
          arith.Log10, arith.Log1p, arith.Sin, arith.Cos, arith.Tan,
          arith.Cot, arith.Asin, arith.Acos, arith.Atan, arith.Sinh,
          arith.Cosh, arith.Tanh, arith.Asinh, arith.Acosh, arith.Atanh,
          arith.ToDegrees, arith.ToRadians, arith.Rint, arith.Signum,
          arith.Floor, arith.Ceil, arith.Pow, arith.Logarithm, arith.Atan2,
          arith.Round, arith.BRound, arith.BitwiseAnd, arith.BitwiseOr,
          arith.BitwiseXor, arith.BitwiseNot, arith.ShiftLeft,
          arith.ShiftRight, arith.ShiftRightUnsigned, arith.Rand,
          arith.Hypot):
    expr_rule(c, ts.NUMERIC)

# decimal plumbing (GpuOverrides.scala:824-838 PromotePrecision /
# CheckOverflow pair + MakeDecimal / UnscaledValue); arithmetic fuses
# the wrappers, the named forms exist for programmatic plans
from spark_rapids_tpu.ops import decimal_ops as DEC  # noqa: E402

for c in (DEC.PromotePrecision, DEC.CheckOverflow, DEC.MakeDecimal,
          DEC.UnscaledValue):
    expr_rule(c, ts.NUMERIC)

# regex family + remaining string surface (stringFunctions.scala +
# shim RegExpReplace rules; unsupported patterns tag off like the
# reference's incompat flag)
from spark_rapids_tpu.ops import regexops as RX  # noqa: E402

for c in (RX.StringReplace, RX.ConcatWs, RX.Translate):
    expr_rule(c, ts.COMMON)
for c in (RX.RLike, RX.RegExpReplace, RX.SplitPart):
    expr_rule(c, ts.COMMON,
              incompat="byte-semantics regex ('.' matches one byte)")

# collections (collectionOperations.scala + complexType rules analog)
from spark_rapids_tpu.ops import collections_ops as C  # noqa: E402

expr_rule(C.CreateArray, ts.ARRAY)
expr_rule(C.SortArray, ts.ARRAY)
expr_rule(C.Size, ts.COMMON)
expr_rule(C.ArrayContains, ts.COMMON)
expr_rule(C.GetArrayItem, ts.COMMON)
expr_rule(C.ElementAt, ts.COMMON)
# ArrayMin/ArrayMax output the ELEMENT type (the sig check runs against
# expr.dtype) — a fixed-width scalar sig both admits the rule and
# constrains the array's element type to what the segment-reduce kernel
# handles (round-4 advisor: ts.ARRAY rejected every scalar output, so
# these silently fell back to CPU).
expr_rule(C.ArrayMin, ts.BOOLEAN + ts.NUMERIC)
expr_rule(C.ArrayMax, ts.BOOLEAN + ts.NUMERIC)
expr_rule(C.Slice, ts.ARRAY)
expr_rule(C.ArrayRepeat, ts.ARRAY,
          incompat="array_repeat(NULL, n) yields a NULL row, not an "
                   "array of nulls (null elements have no device "
                   "representation)")
expr_rule(C.Reverse, ts.COMMON + ts.ARRAY,
          incompat="string reverse is byte-wise (ASCII-only)")

# nested struct/map (complexTypeCreator/Extractors analog; most of these
# compile away at bind time — see ops/nested_ops.py)
from spark_rapids_tpu.ops import nested_ops as NO  # noqa: E402

expr_rule(NO.GetStructField, ts.COMMON)
expr_rule(NO.CreateNamedStruct, ts.COMMON)
expr_rule(NO.CreateMap, ts.COMMON)
expr_rule(NO.MapKeys, ts.COMMON)
expr_rule(NO.MapValues, ts.COMMON)
expr_rule(NO.GetMapValue, ts.COMMON)

# misc (HashFunctions.scala, GpuMonotonicallyIncreasingID analogs)
from spark_rapids_tpu.ops import misc_exprs as ME  # noqa: E402

expr_rule(ME.Murmur3Hash, ts.COMMON)
# Md5 has NO rule: it is host-only and always falls back

# UDFs: a user jax function fuses into the stage (RapidsUDF analog)
from spark_rapids_tpu.udf.python_exec import JaxUDF  # noqa: E402

expr_rule(JaxUDF, ts.ALL)

# Expand (rollup/cube/grouping sets lowering, GpuExpandExec rule analog
# — reference GpuOverrides.scala:3170): typed NULL slots for the
# aggregated-away keys
from spark_rapids_tpu.exec.expand import NullLiteral  # noqa: E402

expr_rule(NullLiteral, ts.ALL)

# predicates / conditionals (any common type flows through)
for c in (preds.EqualTo, preds.EqualNullSafe, preds.LessThan,
          preds.LessThanOrEqual, preds.GreaterThan, preds.GreaterThanOrEqual,
          preds.And, preds.Or, preds.Not, preds.IsNull, preds.IsNotNull,
          preds.IsNaN, preds.NaNvl, preds.Coalesce, preds.If, preds.CaseWhen,
          preds.In, preds.InSet, preds.Greatest, preds.Least,
          preds.AtLeastNNonNulls, preds.KnownNotNull,
          preds.KnownFloatingPointNormalized, preds.NormalizeNaNAndZero):
    expr_rule(c)


# --------------------------------------------------------------- meta classes --

class BaseMeta:
    def __init__(self, wrapped, conf: RapidsConf):
        self.wrapped = wrapped
        self.conf = conf
        self.reasons: List[str] = []
        self.child_metas: List[BaseMeta] = []

    def will_not_work(self, reason: str) -> None:
        self.reasons.append(reason)

    @property
    def can_replace(self) -> bool:
        return not self.reasons and all(
            c.can_replace for c in self.child_metas)

    def tag(self) -> None:
        raise NotImplementedError

    def explain_lines(self, depth: int = 0, all_nodes: bool = True
                      ) -> List[str]:
        status = "will run on TPU" if not self.reasons else \
            "will NOT run on TPU because " + "; ".join(self.reasons)
        name = type(self.wrapped).__name__
        lines = []
        if all_nodes or self.reasons:
            lines.append("  " * depth + f"{'*' if not self.reasons else '!'}"
                         f" {name} {status}")
        for c in self.child_metas:
            lines.extend(c.explain_lines(depth + 1, all_nodes))
        return lines


class ExprMeta(BaseMeta):
    def __init__(self, expr: Expression, conf: RapidsConf):
        super().__init__(expr, conf)
        self.child_metas = [ExprMeta(c, conf) for c in expr.children]
        if isinstance(expr, AggregateExpression) and \
                expr.func.child is not None:
            self.child_metas = [ExprMeta(expr.func.child, conf)]

    def tag(self) -> None:
        from spark_rapids_tpu.ops.cast import cast_supported
        expr = self.wrapped
        name = type(expr).__name__
        if not self.conf.op_enabled("expression", name):
            self.will_not_work(
                f"expression {name} disabled by "
                f"spark.rapids.sql.expression.{name}")
        rule = _EXPR_RULES.get(type(expr))
        if rule is not None and rule.incompat:
            from spark_rapids_tpu.config.rapids_conf import INCOMPAT_ENABLED
            if not self.conf.get(INCOMPAT_ENABLED):
                self.will_not_work(
                    f"{name} is incompatible with CPU Spark "
                    f"({rule.incompat}) and "
                    "spark.rapids.sql.incompatibleOps.enabled is false")
        if isinstance(expr, AggregateExpression):
            try:
                reason = expr.func.supported_reason()
                if reason:
                    self.will_not_work(reason)
                if expr.dtype.is_array and not getattr(
                        expr.func, "single_pass", False):
                    self.will_not_work(
                        f"aggregate {expr.func.name} over array values "
                        "not supported (only collect_list/collect_set "
                        "produce arrays)")
                child = expr.func.child
                if child is not None and child.dtype.has_offsets and \
                        expr.func.name not in ("count", "min", "max",
                                               "first", "last") and \
                        not getattr(expr.func, "single_pass", False):
                    # string min/max/first/last run via batch-local
                    # order-preserving dictionary codes
                    # (exec/aggregate.py); sum/avg over offset columns
                    # have no numeric meaning on device
                    self.will_not_work(
                        f"aggregate {expr.func.name} over "
                        f"{child.dtype.name} values falls back to CPU")
            except (RuntimeError, TypeError, ValueError) as e:
                self.will_not_work(str(e))
        if isinstance(expr, Cast):
            try:
                reason = cast_supported(expr.child.dtype, expr.target)
                if reason:
                    self.will_not_work(reason)
            except (RuntimeError, TypeError, ValueError):
                pass
        if isinstance(expr, C.CreateArray) and any(
                c.nullable for c in expr.children):
            self.will_not_work(
                "array() over nullable children not supported on TPU "
                "(null array elements have no device representation); "
                "falls back to CPU")
        if isinstance(expr, S.Like) and not expr.supported:
            self.will_not_work(
                f"LIKE pattern {expr.pattern!r} too general for TPU")
        if isinstance(expr, D.DateFormatClass) and not expr.supported:
            self.will_not_work(
                f"date_format pattern {expr.fmt!r} outside the "
                "fixed-width device subset (yyyy/MM/dd/HH/mm/ss)")
        if isinstance(expr, (RX.RLike, RX.RegExpReplace, RX.StringReplace,
                             RX.Translate, RX.SplitPart)) and \
                not expr.supported:
            self.will_not_work(
                f"{type(expr).__name__} arguments outside the TPU regex "
                "subset (falls back to CPU, like the reference's regex "
                "incompat flag)")
        if isinstance(expr, (RX.RLike, RX.RegExpReplace, RX.SplitPart)):
            from spark_rapids_tpu.config.rapids_conf import REGEXP_ENABLED
            if not self.conf.get(REGEXP_ENABLED):
                self.will_not_work(
                    f"{name} disabled by "
                    "spark.rapids.sql.regexp.enabled")
        if isinstance(expr, AggregateExpression) and \
                expr.func.name in ("sum", "avg", "average", "mean",
                                   "var_pop", "var_samp", "stddev_pop",
                                   "stddev_samp") and \
                expr.func.child is not None:
            try:
                is_float = expr.func.child.dtype.is_floating
            except (RuntimeError, TypeError, ValueError):
                is_float = False  # dtype issues already tagged above
            from spark_rapids_tpu.config.rapids_conf import \
                VARIABLE_FLOAT_AGG
            if is_float and not self.conf.get(VARIABLE_FLOAT_AGG):
                self.will_not_work(
                    f"float {expr.func.name} reorders additions across "
                    "chunks/shards and "
                    "spark.rapids.sql.variableFloatAgg.enabled is false")
        if isinstance(expr, Cast):
            from spark_rapids_tpu.config import rapids_conf as _rc
            try:
                src, dst = expr.child.dtype, expr.target
                gates = (
                    (src.is_string and dst.is_floating,
                     _rc.CAST_STRING_TO_FLOAT),
                    (src.is_floating and dst.is_string,
                     _rc.CAST_FLOAT_TO_STRING),
                    (src.is_floating and dst.is_decimal,
                     _rc.CAST_FLOAT_TO_DECIMAL),
                    (src.is_string and (dst.is_timestamp or dst.is_date),
                     _rc.CAST_STRING_TO_TIMESTAMP),
                )
                for hit, entry in gates:
                    if hit and not self.conf.get(entry):
                        self.will_not_work(
                            f"cast {src.name}->{dst.name} disabled by "
                            f"{entry.key}")
            except (RuntimeError, TypeError, ValueError):
                pass
        if isinstance(expr, WindowExpression):
            reason = expr.supported_reason()
            if reason:
                self.will_not_work(reason)
            if any(e.dtype.is_string for e, _, _ in expr.spec.orders):
                self.will_not_work("string window order keys not supported")
            for c in self.child_metas:
                c.tag()
            return
        if rule is None:
            self.will_not_work(
                f"expression {name} has no TPU implementation")
        else:
            try:
                dt = expr.dtype
                if dt.is_decimal and not self.conf[
                        "spark.rapids.sql.decimalType.enabled"]:
                    self.will_not_work(
                        "decimal is disabled by "
                        "spark.rapids.sql.decimalType.enabled")
                reason = rule.sig.reason_if_unsupported(
                    dt, f"expression {type(expr).__name__}")
                if reason and not isinstance(expr, (BoundReference, Alias,
                                                    Literal)):
                    self.will_not_work(reason)
            except (RuntimeError, TypeError, ValueError) as e:
                self.will_not_work(str(e))
        for c in self.child_metas:
            c.tag()


class PlanMeta(BaseMeta):
    """Wraps a logical node; conversion handled by the planner below."""

    def __init__(self, plan: L.LogicalPlan, conf: RapidsConf):
        super().__init__(plan, conf)
        self.child_metas = [PlanMeta(c, conf) for c in plan.children]
        self.expr_metas: List[ExprMeta] = [
            ExprMeta(e, conf) for e in _node_expressions(plan)]

    def tag(self) -> None:
        node = self.wrapped
        if not self.conf.op_enabled("exec", type(node).__name__):
            self.will_not_work(
                f"{type(node).__name__} disabled by "
                f"spark.rapids.sql.exec.{type(node).__name__}")
        if isinstance(node, L.FileRelation):
            # per-format scan switches (sql.format.<fmt>.enabled /
            # .read.enabled, RapidsConf.scala:664): a disabled format
            # runs the whole read on the pandas fallback chain
            from spark_rapids_tpu.config import rapids_conf as _rc
            gates = {"parquet": (_rc.PARQUET_ENABLED,
                                 _rc.PARQUET_READ_ENABLED),
                     "orc": (_rc.ORC_ENABLED, _rc.ORC_READ_ENABLED),
                     "csv": (_rc.CSV_ENABLED, _rc.CSV_READ_ENABLED)}
            for entry in gates.get(node.file_format, ()):
                if not self.conf.get(entry):
                    self.will_not_work(
                        f"{node.file_format} scan disabled by "
                        f"{entry.key}")
        if type(node) not in _PLAN_CONVERTERS:
            self.will_not_work(
                f"{type(node).__name__} has no TPU implementation")
        # array<string> exists only on the host surface (dictionary-coded
        # Column with a host string table no device exec preserves): any
        # node CONSUMING one must stay on the CPU fallback chain
        for c in node.children:
            for cn, cdt in c.schema:
                if cdt.is_array and cdt.element is not None and \
                        cdt.element.is_string:
                    self.will_not_work(
                        f"input column {cn!r} is array<string>, a "
                        "host-only type (no device representation)")
        if isinstance(node, L.Sort) and any(
                e.dtype.is_array for e, _, _ in node.orders):
            self.will_not_work("array sort keys not supported on TPU")
        if isinstance(node, L.Aggregate) and any(
                e.dtype.is_array for e in node.group_exprs):
            self.will_not_work("array group-by keys not supported on TPU")
        if isinstance(node, L.Aggregate):
            funcs = [x.func for e in node.agg_exprs
                     for x in _walk_aggs(e)]
            if any(getattr(f, "single_pass", False) for f in funcs) and \
                    any(f.child is not None and f.child.dtype.has_offsets
                        and not getattr(f, "single_pass", False)
                        for f in funcs):
                # the single-pass (collect) execution path has no
                # dictionary staging for string min/max siblings
                self.will_not_work(
                    "collect aggregates combined with string-valued "
                    "min/max/first/last fall back to CPU")
        if isinstance(node, L.Generate) and not \
                node.generator.dtype.is_array:
            self.will_not_work(
                f"explode needs an array column, got "
                f"{node.generator.dtype}")
        if isinstance(node, L.Join):
            if node.condition is not None and node.join_type != "inner":
                self.will_not_work(
                    "non-equi join conditions only supported for inner "
                    "joins on TPU (outer residual semantics need the "
                    "nested-loop join)")
            for lk, rk in zip(node.left_keys, node.right_keys):
                if lk.dtype.name != rk.dtype.name:
                    self.will_not_work(
                        f"join key type mismatch {lk.dtype} vs {rk.dtype}")
                if lk.dtype.is_array:
                    self.will_not_work(
                        "array join keys not supported on TPU")
        for em in self.expr_metas:
            em.tag()
            if not em.can_replace:
                deep = _deep_reasons(em)
                detail = "; ".join(deep) if deep else "unsupported"
                self.will_not_work(
                    f"expression {type(em.wrapped).__name__} cannot run on "
                    f"TPU: {detail}")
        for c in self.child_metas:
            c.tag()

    def explain_lines(self, depth: int = 0, all_nodes: bool = True):
        lines = super().explain_lines(depth, all_nodes)
        for em in self.expr_metas:
            if em.reasons:
                lines.extend(em.explain_lines(depth + 1, False))
        return lines


def _walk_aggs(e: Expression) -> List[AggregateExpression]:
    out = []
    if isinstance(e, AggregateExpression):
        out.append(e)
    for c in e.children:
        out.extend(_walk_aggs(c))
    return out


def _deep_reasons(meta: BaseMeta) -> List[str]:
    """All will-not-work reasons in an expression meta tree (the inner
    reason, e.g. a per-op disable, is what the user needs to see)."""
    out = list(meta.reasons)
    for c in meta.child_metas:
        out.extend(_deep_reasons(c))
    return out


def _node_expressions(plan: L.LogicalPlan) -> List[Expression]:
    from spark_rapids_tpu.exec.expand import Expand
    if isinstance(plan, Expand):
        return [e for p in plan.projections for e in p]
    if isinstance(plan, L.Project):
        return list(plan.exprs)
    if isinstance(plan, L.Generate):
        return [plan.generator] + list(plan.required)
    if isinstance(plan, L.Filter):
        return [plan.condition]
    if isinstance(plan, L.Aggregate):
        return list(plan.group_exprs) + list(plan.agg_exprs)
    if isinstance(plan, L.Join):
        return list(plan.left_keys) + list(plan.right_keys)
    if isinstance(plan, L.Sort):
        return [e for e, _, _ in plan.orders]
    if isinstance(plan, L.Window):
        return [e for _, e in plan.window_exprs]
    return []


# ------------------------------------------------------------------ planner --

_PLAN_CONVERTERS: Dict[type, object] = {}


def _converter(cls):
    def deco(fn):
        _PLAN_CONVERTERS[cls] = fn
        return fn
    return deco


@_converter(L.InMemoryRelation)
def _conv_inmemory(node: L.InMemoryRelation, children, conf):
    from spark_rapids_tpu.exec.basic import TpuScanExec
    return TpuScanExec(node.batches, node.schema)


@_converter(L.FileRelation)
def _conv_file(node: L.FileRelation, children, conf):
    from spark_rapids_tpu.io.readers import make_file_scan_exec
    scan = make_file_scan_exec(node, conf)
    # PERFILE readers emit one undersized batch per file: planner-
    # inserted coalesce to the batch goal (GpuTransitionOverrides.
    # scala:57-64).  Other reader types already merge to goal-sized
    # batches, and array<string> columns carry PER-BATCH dictionary
    # codes that concatenation would corrupt — leave those bare.
    if len(node.paths) > 1 and \
            getattr(scan, "reader_type", "") == "PERFILE" and \
            not any(dt.is_array and dt.element is not None
                    and dt.element.is_string for _, dt in node.schema):
        from spark_rapids_tpu.config import rapids_conf as _rc
        from spark_rapids_tpu.exec.basic import TpuCoalesceBatchesExec
        from spark_rapids_tpu.memory.coalesce import TargetSize
        from spark_rapids_tpu.plan.costmodel import model_for_conf
        goal = conf.get(_rc.BATCH_SIZE_BYTES)
        cm = model_for_conf(conf)
        if cm is not None:
            # self-tuning planner: the coalesce goal caps at a
            # fraction of the device budget unless batchSizeBytes was
            # explicitly tuned (the override discipline)
            goal = cm.coalesce_goal_bytes(goal)
        return TpuCoalesceBatchesExec(scan, TargetSize(goal))
    return scan


@_converter(L.Project)
def _conv_project(node: L.Project, children, conf):
    from spark_rapids_tpu.config import rapids_conf as _rc
    from spark_rapids_tpu.exec.basic import TpuProjectExec
    return TpuProjectExec(node.exprs, children[0],
                          donate=conf.get(_rc.PIPELINE_DONATION))


@_converter(L.Filter)
def _conv_filter(node: L.Filter, children, conf):
    from spark_rapids_tpu.config import rapids_conf as _rc
    from spark_rapids_tpu.exec.basic import TpuFilterExec
    return TpuFilterExec(node.condition, children[0],
                         donate=conf.get(_rc.PIPELINE_DONATION))


def _encoding_exec_enabled(conf) -> bool:
    """Encoded execution conf, minus the session's overflow latch (a
    dictionary that outgrew maxDictSize latched the session back onto
    the decoded path; every attempt re-plans, so the latch takes
    effect on the ladder's next rung).  With the self-tuning cost
    model active the model decides the coded-vs-decoded knob when the
    conf leaves it unset (an explicit conf stays an override)."""
    from spark_rapids_tpu.config import rapids_conf as rc
    from spark_rapids_tpu.plan.costmodel import model_for_conf
    cm = model_for_conf(conf)  # conf-gated: knobs-off conf = HEAD
    if cm is not None:
        if not cm.encoded_execution():
            return False
    elif not conf.get(rc.ENCODING_EXECUTION_ENABLED):
        return False
    from spark_rapids_tpu.api.session import TpuSession
    return not getattr(TpuSession._active, "encoding_exec_latched",
                       False)


def _plan_aggregate(group_exprs, agg_out_exprs, child_exec,
                    pre_filter=None, merge_chunk_rows=1 << 22,
                    defer_syncs=True, encoded_exec=False,
                    max_dict_size=(1 << 31) - 1):
    """Build the aggregate exec, plus a result projection when outputs
    combine aggregates in larger expressions (sum(x)*100, sum(a)/sum(b)...
    — Catalyst's resultExpressions split)."""
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.basic import TpuProjectExec

    nkeys = len(group_exprs)
    agg_list: List[AggregateExpression] = []
    group_keys = [ge.cache_key() for ge in group_exprs]

    def extract(e):
        if isinstance(e, AggregateExpression):
            idx = len(agg_list)
            agg_list.append(e)
            return BoundReference(nkeys + idx, e.dtype, name=f"_a{idx}",
                                  nullable=e.nullable)
        # non-aggregate subtrees matching a group expression read the
        # agg frame's key column, not the child's ordinal (Catalyst
        # rewrites resultExpressions the same way)
        try:
            ck = e.cache_key()
        except Exception:
            ck = None
        if ck is not None and ck in group_keys:
            ki = group_keys.index(ck)
            ge = group_exprs[ki]
            return BoundReference(ki, ge.dtype, name=ge.name,
                                  nullable=ge.nullable)
        if not e.children:
            if isinstance(e, BoundReference):
                raise ValueError(
                    f"column {e.name!r} in aggregate output is neither "
                    "an aggregate nor in the GROUP BY")
            return e
        return e.with_children([extract(c) for c in e.children])

    out_named = []
    trivial = True
    for e in agg_out_exprs:
        name = e.name
        inner = e.children[0] if isinstance(e, Alias) else e
        rewritten = extract(inner)
        if not isinstance(inner, AggregateExpression):
            trivial = False
        out_named.append((name, rewritten))

    if trivial:
        # every output is a bare aggregate: name the agg columns directly
        return TpuHashAggregateExec(
            group_exprs,
            [(name, a) for (name, _), a in zip(out_named, agg_list)],
            child_exec, pre_filter=pre_filter,
            merge_chunk_rows=merge_chunk_rows, defer_syncs=defer_syncs,
            encoded_exec=encoded_exec, max_dict_size=max_dict_size)
    agg_exec = TpuHashAggregateExec(
        group_exprs, [(f"_a{i}", a) for i, a in enumerate(agg_list)],
        child_exec, pre_filter=pre_filter,
        merge_chunk_rows=merge_chunk_rows, defer_syncs=defer_syncs,
        encoded_exec=encoded_exec, max_dict_size=max_dict_size)
    proj = [BoundReference(i, dt, name=n)
            for i, (n, dt) in enumerate(agg_exec.schema[:nkeys])]
    proj += [Alias(rewritten, name) for name, rewritten in out_named]
    return TpuProjectExec(proj, agg_exec)


@_converter(L.Aggregate)
def _conv_aggregate(node: L.Aggregate, children, conf):
    from spark_rapids_tpu.config import rapids_conf as rc
    return _plan_aggregate(node.group_exprs, node.agg_exprs, children[0],
                           merge_chunk_rows=conf.get(rc.AGG_MERGE_CHUNK_ROWS),
                           defer_syncs=conf.get(rc.PIPELINE_DEFER_SYNCS),
                           encoded_exec=_encoding_exec_enabled(conf),
                           max_dict_size=conf.get(
                               rc.ENCODING_EXECUTION_MAX_DICT))


@_converter(L.Limit)
def _conv_limit(node: L.Limit, children, conf):
    from spark_rapids_tpu.exec.basic import TpuLocalLimitExec
    return TpuLocalLimitExec(node.n, children[0])


@_converter(L.Union)
def _conv_union(node: L.Union, children, conf):
    from spark_rapids_tpu.exec.basic import TpuUnionExec
    return TpuUnionExec(*children)


@_converter(L.Range)
def _conv_range(node: L.Range, children, conf):
    from spark_rapids_tpu.exec.basic import TpuRangeExec
    return TpuRangeExec(node.start, node.end, node.step)


@_converter(L.Sort)
def _conv_sort(node: L.Sort, children, conf):
    from spark_rapids_tpu.config import rapids_conf as rc
    from spark_rapids_tpu.exec.sort import TpuSortExec
    return TpuSortExec(
        node.orders, children[0],
        ooc_threshold_bytes=conf.get(rc.SORT_OOC_THRESHOLD),
        ooc_window_rows=conf.get(rc.SORT_OOC_WINDOW_ROWS))


@_converter(L.Join)
def _conv_join(node: L.Join, children, conf):
    from spark_rapids_tpu.exec.basic import TpuFilterExec
    from spark_rapids_tpu.exec.join import TpuHashJoinExec
    join_type = node.join_type
    if node.condition is not None and not node.left_keys:
        # pure non-equi inner join: cross product + filter (the
        # GpuBroadcastNestedLoopJoinExec shape)
        join_type = "cross"
    from spark_rapids_tpu.config import rapids_conf as rc
    join = TpuHashJoinExec(node.left_keys, node.right_keys, join_type,
                           children[0], children[1], using=node.using,
                           max_output_rows=conf.get(
                               rc.JOIN_OUTPUT_BATCH_ROWS),
                           live_columns=node.live_columns)
    if node.condition is not None:
        # residual condition evaluated over the joined output
        return TpuFilterExec(node.condition, join)
    return join


@_converter(L.AggInPandas)
def _conv_agg_in_pandas(node: L.AggInPandas, children, conf):
    from spark_rapids_tpu.udf.python_exec import TpuAggregateInPandasExec
    return TpuAggregateInPandasExec(node.group_names, node.aggs,
                                    children[0])


@_converter(L.WindowInPandas)
def _conv_window_in_pandas(node: L.WindowInPandas, children, conf):
    from spark_rapids_tpu.udf.python_exec import TpuWindowInPandasExec
    return TpuWindowInPandasExec(node.calls, children[0])


@_converter(L.CoGroupMapInPandas)
def _conv_cogroup(node: L.CoGroupMapInPandas, children, conf):
    from spark_rapids_tpu.udf.python_exec import (
        TpuFlatMapCoGroupsInPandasExec)
    return TpuFlatMapCoGroupsInPandasExec(
        node.fn, node.schema, node.left_names, node.right_names,
        children[0], children[1])


@_converter(L.BatchId)
def _conv_batch_id(node: L.BatchId, children, conf):
    from spark_rapids_tpu.ops.misc_exprs import TpuBatchIdExec
    return TpuBatchIdExec(children[0])


@_converter(L.Generate)
def _conv_generate(node: L.Generate, children, conf):
    from spark_rapids_tpu.exec.generate import TpuGenerateExec
    return TpuGenerateExec(node.generator, node.required, node.position,
                           children[0], col_name=node.col_name,
                           pos_name=node.pos_name,
                           generator2=node.generator2)


def _register_expand_converter():
    from spark_rapids_tpu.exec.expand import Expand, TpuExpandExec

    @_converter(Expand)
    def _conv_expand(node, children, conf):
        return TpuExpandExec(node, children[0])


_register_expand_converter()


def _window_one_spec(window_exprs, child_exec, conf):
    from spark_rapids_tpu.config import rapids_conf as rc
    from spark_rapids_tpu.exec.sort import TpuSortExec
    from spark_rapids_tpu.exec.window import TpuWindowExec
    spec = window_exprs[0][1].spec
    if spec.partition_exprs or spec.orders:
        # Spark plans WindowExec above a SortExec on (partition, order);
        # the sort brings the engine's out-of-core machinery, and the
        # window then streams key-aligned chunks instead of
        # materializing its whole input (GpuWindowExec.scala:423-446 +
        # GpuKeyBatchingIterator analog)
        orders = [(e, False, True) for e in spec.partition_exprs] + \
            list(spec.orders)
        sort = TpuSortExec(
            orders, child_exec,
            ooc_threshold_bytes=conf.get(rc.SORT_OOC_THRESHOLD),
            ooc_window_rows=conf.get(rc.SORT_OOC_WINDOW_ROWS))
        return TpuWindowExec(window_exprs, sort, presorted=True,
                             batch_rows=conf.get(rc.WINDOW_BATCH_ROWS))
    return TpuWindowExec(window_exprs, child_exec)


@_converter(L.Window)
def _conv_window(node: L.Window, children, conf):
    from spark_rapids_tpu.exec.basic import TpuProjectExec
    from spark_rapids_tpu.exec.window import group_by_spec
    from spark_rapids_tpu.ops.expressions import Alias, BoundReference
    exprs = node.window_exprs
    nchild = len(children[0].schema)
    groups = group_by_spec(exprs)
    if len(groups) == 1:
        return _window_one_spec(exprs, children[0], conf)
    # multiple specs: chain one TpuWindowExec per spec (later specs see
    # earlier outputs as payload; bound ordinals into the child are
    # unchanged because outputs append at the end), then restore the
    # node's column order (WindowExecBase handles one spec per exec in
    # the reference too — Spark splits them the same way)
    cur = children[0]
    appended_pos: Dict[int, int] = {}
    base = nchild
    for grp in groups:
        cur = _window_one_spec([(n, we) for _, n, we in grp], cur, conf)
        for i, (j, _, _) in enumerate(grp):
            appended_pos[j] = base + i
        base += len(grp)
    cur_schema = cur.schema
    perm = list(range(nchild)) + \
        [appended_pos[j] for j in range(len(exprs))]
    projs = []
    for want_name, p in zip([n for n, _ in node.schema], perm):
        pname, pdt = cur_schema[p]
        projs.append(Alias(BoundReference(p, pdt, pname), want_name))
    return TpuProjectExec(projs, cur)


@_converter(L.MapInPandas)
def _conv_map_in_pandas(node: L.MapInPandas, children, conf):
    from spark_rapids_tpu.udf.python_exec import (
        TpuFlatMapGroupsInPandasExec, TpuMapInPandasExec)
    if node.group_names:
        return TpuFlatMapGroupsInPandasExec(node.fn, node.schema,
                                            node.group_names, children[0])
    return TpuMapInPandasExec(node.fn, node.schema, children[0])


def _pushdown_pass(plan: L.LogicalPlan, cache_manager=None) -> None:
    """Column pruning + predicate pushdown into FileRelations.

    Pruned columns are only those dropped by a Project/Aggregate above
    (the requirement passes through Filters and Joins by name), so
    BoundReference ordinals stay valid (the scan emits null placeholders
    for unread columns, which by construction nothing references).
    A Join is told the same thing (``live_columns``): the columns of its
    output that something above reads; it gathers those and re-emits the
    rest as the placeholders they already are.
    Filters push down until a Project renames the namespace.

    Cached plan nodes are pushdown BARRIERS: a query-specific filter or
    column pruning pushed below a cache boundary would materialize a
    filtered/pruned subset as the cache, silently poisoning every later
    reader.  At a cached node the pushdown restarts fresh (and, because
    assignments overwrite, clears any pushdown a previous query left on
    the shared FileRelation nodes).
    """
    barrier_entered: set = set()
    scanned: set = set()  # FileRelations this pass has already visited
    joined: set = set()   # Joins this pass has already visited

    def note_live(node, live):
        """Overwritten on the pass's first visit (None = all), widened
        on a second (a join that two parts of one query share)."""
        if id(node) in joined:
            prev = node.live_columns
            live = None if live is None or prev is None else live | prev
        joined.add(id(node))
        node.live_columns = live

    def visit(node, required, filters):
        if cache_manager is not None and id(node) not in barrier_entered \
                and cache_manager.lookup(node) is not None:
            barrier_entered.add(id(node))
            visit(node, None, [])
            return
        if isinstance(node, L.FileRelation):
            # a view's FileRelation is shared: across queries, so both
            # fields are overwritten on the pass's first visit (None =
            # "all"); and within one query that scans the view twice,
            # so a second visit reads what either scan needs and pushes
            # no filter only one of them has
            req = None if required is None else set(required)
            if id(node) in scanned:
                prev = node.required_columns
                req = None if req is None or prev is None else req | prev
                filters = []
            scanned.add(id(node))
            node.required_columns = req
            node.pushed_filters = list(filters)
            return
        if isinstance(node, L.Filter):
            req = None if required is None else \
                set(required) | set(node.condition.references())
            visit(node.child, req, filters + [node.condition])
            return
        if isinstance(node, L.Project):
            refs = set()
            for e in node.exprs:
                # a column passed through or renamed that nothing above
                # reads (the SQL resolver's join-deduplication renames)
                # asks nothing of the scan; a computed expression always
                # does — it still runs, and must see real values
                bare = e.child if isinstance(e, Alias) else e
                if required is not None and e.name not in required \
                        and isinstance(bare, BoundReference):
                    continue
                refs.update(e.references())
            visit(node.child, refs, [])
            return
        if isinstance(node, L.Aggregate):
            refs = set()
            for e in list(node.group_exprs) + list(node.agg_exprs):
                refs.update(e.references())
            visit(node.child, refs, [])
            return
        if isinstance(node, L.Sort) and required is not None:
            req = set(required)
            for e, _, _ in node.orders:
                req.update(e.references())
            visit(node.child, req, [])
            return
        if isinstance(node, L.Limit):
            visit(node.child, required, [])
            return
        if isinstance(node, L.Join) and required is not None:
            # each side reads its join keys plus whatever of it the
            # condition and the operators above reference; a name both
            # sides carry is kept on both
            above = set(required)
            if node.condition is not None:
                above.update(node.condition.references())
            note_live(node, set(above))
            for child, keys in ((node.left, node.left_keys),
                                (node.right, node.right_keys)):
                need = {n for n, _ in child.schema if n in above}
                for k in keys:
                    need.update(k.references())
                visit(child, need, [])
            return
        if isinstance(node, L.Join):
            note_live(node, None)
        for c in node.children:
            visit(c, None, [])

    visit(plan, None, [])


# process-wide planning-pass counter: every TpuOverrides.apply ticks it.
# tests/test_templates.py pins this at zero across prepared repeats — "skips
# planning entirely" is a measured claim, not a code-path assumption.
_planning_passes = 0


def planning_passes() -> int:
    return _planning_passes


class TpuOverrides:
    """The planner: logical plan -> TpuExec tree with CPU fallback."""

    def __init__(self, conf: Optional[RapidsConf] = None,
                 cache_manager=None):
        from spark_rapids_tpu.config import rapids_conf as _rc
        self.conf = conf or RapidsConf()
        self.last_explain: str = ""
        self.last_cbo: List[str] = []
        self.cache_manager = cache_manager
        self.fusion_enabled = self.conf.get(_rc.FUSION_ENABLED)
        self.fusion_max_ops = self.conf.get(_rc.FUSION_MAX_OPS)
        # per-apply fusion accounting (QueryEnd "fusion" dict): stages/
        # operators actually fused, plus chains that COULD have fused
        # (the health-check signal when fusion is disabled).  Keyed by
        # effective thread ident (the PR6 _current_qid discipline): one
        # overrides instance serves concurrent queries, and a single
        # shared dict would stamp query A's QueryEnd with query B's
        # planned chains.  Bounded: idents recycle, stale entries are
        # pruned once the map outgrows any plausible thread count.
        self._fusion_by_ident: Dict[int, Dict[str, int]] = {}
        self._chain_nodes_by_ident: Dict[int, set] = {}

    @staticmethod
    def _ident() -> int:
        from spark_rapids_tpu.serving import context as qc
        return qc.effective_ident()

    def _fresh_fusion(self) -> Dict[str, int]:
        return {"enabled": self.fusion_enabled, "fusedStages": 0,
                "fusedOperators": 0, "fusibleChains": 0}

    @property
    def last_fusion(self) -> Dict[str, int]:
        # setdefault, not get: a concurrent apply()'s oversized-map
        # prune may drop this ident's dict mid-plan — recreate so a
        # counter bump degrades the metrics, never the query
        return self._fusion_by_ident.setdefault(self._ident(),
                                                self._fresh_fusion())

    @property
    def _counted_chain_nodes(self) -> set:
        return self._chain_nodes_by_ident.setdefault(self._ident(),
                                                     set())

    def apply(self, plan: L.LogicalPlan, pushdown: bool = True):
        """``pushdown=False`` plans a scan its owner already annotated
        (the distributed planner's per-shard reads of one query's
        FileRelation); the pass would reset what it copied."""
        global _planning_passes
        _planning_passes += 1
        if pushdown:
            _pushdown_pass(plan, self.cache_manager)
        meta = PlanMeta(plan, self.conf)
        meta.tag()
        ident = self._ident()
        for m in (self._fusion_by_ident, self._chain_nodes_by_ident):
            if len(m) > 256:
                # recycled-ident flood: drop stale entries but keep the
                # concurrently-planning threads' live state (the
                # last_fusion property self-heals regardless)
                for k in list(m)[:128]:
                    if k != ident:
                        m.pop(k, None)
        self._fusion_by_ident[ident] = self._fresh_fusion()
        self._chain_nodes_by_ident[ident] = set()
        from spark_rapids_tpu.plan.costmodel import model_for_conf
        cm = model_for_conf(self.conf)  # conf-gated: see costmodel.py
        if cm is not None:
            # self-tuning planner: fusion chain boundaries come from
            # the one cost model (compile-cost evidence halves the
            # bound; an explicit maxChainOps conf stays an override) —
            # re-resolved per apply so the decision lands in the
            # CURRENT query's ledger
            self.fusion_max_ops = cm.fusion_chain_limit()
        from spark_rapids_tpu.config import rapids_conf as rc
        self.last_cbo = []
        if self.conf.get(rc.CBO_ENABLED):
            from spark_rapids_tpu.plan.cbo import CostBasedOptimizer
            cbo = CostBasedOptimizer(self.conf)
            cbo.optimize(meta)
            self.last_cbo = cbo.explain
        self.last_explain = "\n".join(meta.explain_lines())
        if self.conf.explain == "ALL":
            print(self.last_explain)
        elif self.conf.explain == "NOT_ON_TPU":
            lines = [ln for ln in meta.explain_lines(all_nodes=False)]
            if lines:
                print("\n".join(lines))
        return self._convert(meta)

    def _convert(self, meta: PlanMeta):
        node = meta.wrapped
        if self.cache_manager is not None:
            entry = self.cache_manager.lookup(node)
            if entry is not None:
                from spark_rapids_tpu.exec.cache import (
                    TpuCachedScanExec, TpuMaterializeCacheExec)
                if entry.materialized:
                    return TpuCachedScanExec(entry)
                from spark_rapids_tpu import native
                from spark_rapids_tpu.config import rapids_conf as rc
                return TpuMaterializeCacheExec(
                    entry, self._convert_uncached(meta),
                    codec_level=native.codec_level(
                        self.conf[rc.SHUFFLE_COMPRESSION_CODEC.key]))
        return self._convert_uncached(meta)

    def _convert_uncached(self, meta: PlanMeta):
        node = meta.wrapped
        if isinstance(node, L.Aggregate) and not meta.reasons:
            fused = self._try_fuse_aggregate(meta)
            if fused is not None:
                return fused
        # Limit(Sort) -> TopN (TakeOrderedAndProject analog); not across a
        # cached Sort, whose materialized result must be read/populated
        if isinstance(node, L.Limit) and meta.child_metas and \
                isinstance(meta.child_metas[0].wrapped, L.Sort) and \
                meta.child_metas[0].can_replace and \
                (self.cache_manager is None or
                 self.cache_manager.lookup(meta.child_metas[0].wrapped)
                 is None):
            from spark_rapids_tpu.exec.sort import TpuTopNExec
            sort_meta = meta.child_metas[0]
            base = self._convert(sort_meta.child_metas[0])
            return TpuTopNExec(node.n, sort_meta.wrapped.orders, base)
        if isinstance(node, (L.Project, L.Filter)) and not meta.reasons:
            fused = self._try_fuse_chain(meta)
            if fused is not None:
                return fused
        children = [self._convert(c) for c in meta.child_metas]
        own_ok = not meta.reasons
        if own_ok and type(node) in _PLAN_CONVERTERS:
            return _PLAN_CONVERTERS[type(node)](node, children, self.conf)
        if isinstance(node, L.Project) and self._udf_only_failure(meta):
            # scalar Python UDF projection: device-evaluate everything
            # except the UDF calls themselves (GpuArrowEvalPythonExec)
            from spark_rapids_tpu.udf.python_exec import (
                TpuArrowEvalPythonExec)
            return TpuArrowEvalPythonExec(node.exprs, children[0])
        if self.conf["spark.rapids.sql.test.enabled"]:
            allowed = self.conf[
                "spark.rapids.sql.test.allowedNonTpu"].split(",")
            if type(node).__name__ not in [a.strip() for a in allowed]:
                raise RuntimeError(
                    f"{type(node).__name__} fell back to CPU in strict test "
                    f"mode: {'; '.join(meta.reasons)}")
        from spark_rapids_tpu.exec.fallback import CpuFallbackExec
        return CpuFallbackExec(node, children)

    def _udf_only_failure(self, meta: PlanMeta) -> bool:
        """True when the node's only obstacles are black-box PythonUDF
        calls (everything around them is TPU-supported): re-tag each
        expression with UDF subtrees replaced by typed placeholders."""
        from spark_rapids_tpu.ops.expressions import BoundReference
        from spark_rapids_tpu.udf.python_exec import (
            _find_python_udfs, _replace_udfs)
        # (child failures need no handling here: each child converts with
        # its own fallback independently)
        node = meta.wrapped
        found = False
        for e in node.exprs:
            udfs = _find_python_udfs(e)
            if any(_find_python_udfs(a) for u in udfs
                   for a in u.children):
                return False  # nested black-box UDFs: whole-plan fallback
            if not udfs:
                em = ExprMeta(e, self.conf)
                em.tag()
                if not em.can_replace:
                    return False
                continue
            found = True
            mapping = {id(u): BoundReference(0, u.return_type,
                                             name="_udf")
                       for u in udfs}
            em = ExprMeta(_replace_udfs(e, mapping), self.conf)
            em.tag()
            if not em.can_replace:
                return False
        return found

    def _fusible_member(self, child_meta: PlanMeta) -> bool:
        """A chain member the fuser can ingest: Project/Filter, fully
        TPU-supported, and not a cache boundary (materialized batches
        must be consumed — and populated — there)."""
        if not isinstance(child_meta.wrapped, (L.Project, L.Filter)):
            return False
        if child_meta.reasons or any(
                not em.can_replace for em in child_meta.expr_metas):
            return False
        if self.cache_manager is not None and \
                self.cache_manager.lookup(child_meta.wrapped) is not None:
            return False
        return True

    def _try_fuse_chain(self, meta: PlanMeta):
        """Whole-stage chain fusion: collapse a maximal Project/Filter
        run into ONE FusedStageExec — projections substitute through,
        predicates AND into a single in-trace row mask, one compaction
        at the stage boundary, one jit dispatch per batch
        (exec/fusion.py).  Chains the fuser cannot ingest (UDF-only
        projections, CPU-fallback expressions, cached members) stop the
        walk and run unfused."""
        from spark_rapids_tpu.exec.fusion import (FusedStageExec,
                                                  compose_chain,
                                                  fusion_metrics)
        if id(meta.wrapped) in self._counted_chain_nodes:
            return None  # inner member of an already-detected chain
        exprs = None
        conds: List = []
        cur = meta
        members: List[str] = []
        node_ids: List[int] = []
        while self._fusible_member(cur) and \
                len(members) < self.fusion_max_ops:
            exprs, conds = compose_chain(exprs, conds, cur.wrapped,
                                         cur.wrapped.schema)
            members.append(type(cur.wrapped).__name__)
            node_ids.append(id(cur.wrapped))
            cur = cur.child_metas[0]
        if len(members) < 2:
            return None  # a lone operator is already one stage
        self._counted_chain_nodes.update(node_ids)
        self.last_fusion["fusibleChains"] += 1
        fusion_metrics.bump("fusibleChains")
        if not self.fusion_enabled:
            return None
        base = self._convert(cur)
        self.last_fusion["fusedStages"] += 1
        self.last_fusion["fusedOperators"] += len(members)
        fusion_metrics.bump("fusedStages")
        fusion_metrics.bump("fusedOperators", len(members))
        from spark_rapids_tpu.config import rapids_conf as rc
        return FusedStageExec(
            exprs, conds, base, members,
            donate=self.conf.get(rc.PIPELINE_DONATION))

    def _try_fuse_aggregate(self, meta: PlanMeta):
        """Whole-stage fusion: collapse Project/Filter chains under an
        Aggregate into the aggregation kernel (predicate becomes a row mask,
        projections compose into key/agg expressions).  The reference gets
        partial fusion from cudf kernel launches per op; XLA gives us the
        fully fused stage if we hand it one computation.

        The rule: a Filter/Project chain under an Aggregate always folds,
        whatever the key and buffer types, unless it records ANSI checks
        (the aggregation kernels have no check-flag channel; such a chain
        runs as a FusedStageExec).
        """
        from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
        from spark_rapids_tpu.exec.fusion import fusion_metrics
        from spark_rapids_tpu.ops.expressions import substitute_bound

        node: L.Aggregate = meta.wrapped
        group = list(node.group_exprs)
        aggs = list(node.agg_exprs)
        # bottom-first conjunct list (the aggregate's _pre_filter_mask
        # applies progressive ANSI-check masking, exec/fusion.py)
        conds: List = []
        child_meta = meta.child_metas[0]
        hops = 0
        node_ids: List[int] = []
        while self._fusible_member(child_meta) and \
                hops < self.fusion_max_ops:
            inner = child_meta.wrapped
            if isinstance(inner, L.Project):
                repl = inner.exprs
                group = [substitute_bound(e, repl) for e in group]
                aggs = [substitute_bound(e, repl) for e in aggs]
                conds = [substitute_bound(c, repl) for c in conds]
            else:
                conds = [inner.condition] + conds
            node_ids.append(id(inner))
            child_meta = child_meta.child_metas[0]
            hops += 1
        if hops == 0:
            return None  # nothing upstream to fuse
        from spark_rapids_tpu.exec.fusion import has_check_exprs
        if has_check_exprs(group + aggs + conds):
            # the aggregation kernels have no ANSI check-flag channel:
            # the chain fuses as a FusedStageExec below instead
            return None
        self.last_fusion["fusibleChains"] += 1
        fusion_metrics.bump("fusibleChains")
        if not self.fusion_enabled:
            # A/B baseline: count the lost fusion (health check) and
            # keep the chain members from re-counting as their own
            # chain during normal conversion
            self._counted_chain_nodes.update(node_ids)
            return None
        self.last_fusion["fusedStages"] += 1
        self.last_fusion["fusedOperators"] += hops + 1
        fusion_metrics.bump("fusedStages")
        fusion_metrics.bump("fusedOperators", hops + 1)
        from spark_rapids_tpu.config import rapids_conf as rc
        base = self._convert(child_meta)
        fused = _plan_aggregate(
            group, aggs, base, pre_filter=conds or None,
            merge_chunk_rows=self.conf.get(rc.AGG_MERGE_CHUNK_ROWS),
            defer_syncs=self.conf.get(rc.PIPELINE_DEFER_SYNCS),
            encoded_exec=_encoding_exec_enabled(self.conf),
            max_dict_size=self.conf.get(rc.ENCODING_EXECUTION_MAX_DICT))
        # runtime dispatch-savings attribution (QueryEnd fusion dict):
        # each folded operator would have cost one dispatch per batch
        agg_exec = fused if isinstance(fused, TpuHashAggregateExec) \
            else fused.children[0]
        if isinstance(agg_exec, TpuHashAggregateExec):
            agg_exec.fused_ops = hops
        return fused


def valid_op_names():
    """Known per-op conf suffixes: expression class names + plan node
    names (consumed by RapidsConf's unknown-key validation)."""
    exprs = {c.__name__ for c in _EXPR_RULES}
    execs = {c.__name__ for c in _PLAN_CONVERTERS}
    # logical node names double as exec keys (Sort, Join, ...)
    return exprs | execs | {"WindowExpression"}
