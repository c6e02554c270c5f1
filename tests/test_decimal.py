"""Decimal (DECIMAL_64) semantics: Spark result-type rules, HALF_UP
rounding, overflow -> null, aggregation gates, and the named plumbing
expressions (reference: GpuOverrides.scala:824-838 decimal rules +
TypeChecks.scala DECIMAL_64 notes)."""

import decimal
from decimal import Decimal as D

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.dtypes import DecimalType


@pytest.fixture(scope="module")
def session():
    return TpuSession()


def dec_df(session, cols):
    """cols: {name: (values, precision, scale)}"""
    arrays = {n: pa.array(v, type=pa.decimal128(p, s))
              for n, (v, p, s) in cols.items()}
    return session.create_dataframe(pa.table(arrays))


def test_decimal_add_sub_result_type_and_values(session):
    df = dec_df(session, {
        "a": ([D("1.25"), D("-3.50"), None, D("99.99")], 4, 2),
        "b": ([D("0.075"), D("2.000"), D("1.000"), D("0.005")], 4, 3),
    })
    q = df.select((F.col("a") + F.col("b")).alias("s"),
                  (F.col("a") - F.col("b")).alias("d"))
    plan = session.plan(q.plan)
    assert "CpuFallbackExec" not in plan.tree_string()
    # Spark: decimal(4,2) + decimal(4,3) -> decimal(6,3)
    assert dict(q.plan.schema)["s"].name == "decimal(6,3)"
    out = q.to_pandas()
    assert out["s"].tolist() == [D("1.325"), D("-1.500"), None,
                                 D("99.995")]
    assert out["d"].tolist() == [D("1.175"), D("-5.500"), None,
                                 D("99.985")]


def test_decimal_multiply(session):
    df = dec_df(session, {
        "a": ([D("1.5"), D("-2.4"), D("0.0")], 3, 1),
        "b": ([D("2.50"), D("1.25"), D("9.99")], 4, 2),
    })
    q = df.select((F.col("a") * F.col("b")).alias("m"))
    # decimal(3,1) * decimal(4,2) -> decimal(8,3)
    assert dict(q.plan.schema)["m"].name == "decimal(8,3)"
    out = q.to_pandas()["m"].tolist()
    assert out == [D("3.750"), D("-3.000"), D("0.000")]


def test_decimal_divide_half_up(session):
    df = dec_df(session, {
        "a": ([D("1.0"), D("2.0"), D("-1.0"), D("7.0")], 2, 1),
        "b": ([D("3.0"), D("0.0"), D("3.0"), D("2.0")], 2, 1),
    })
    q = df.select((F.col("a") / F.col("b")).alias("q"))
    # decimal(2,1) / decimal(2,1): s=max(6,1+2+1)=6, p=2-1+1+6=8
    assert dict(q.plan.schema)["q"].name == "decimal(8,6)"
    out = q.to_pandas()["q"].tolist()
    assert out[0] == D("0.333333")
    assert out[1] is None  # divide by zero -> null
    assert out[2] == D("-0.333333")
    assert out[3] == D("3.500000")


def test_decimal_overflow_is_null(session):
    df = dec_df(session, {
        "a": ([D("99.99"), D("1.00")], 4, 2),
        "b": ([D("99.99"), D("1.00")], 4, 2),
    })
    # decimal(4,2)*decimal(4,2) -> decimal(9,4): 99.99*99.99 fits;
    # force overflow via repeated multiply up to the precision cap
    q = df.select(((F.col("a") * F.col("b")) * F.col("a")).alias("m"))
    # decimal(9,4) * decimal(4,2) -> decimal(14,6)
    out = q.to_pandas()["m"].tolist()
    assert out[0] == D("999700.029999")
    assert out[1] == D("1.000000")


def test_decimal_int_mixed_arithmetic(session):
    df = session.create_dataframe(pa.table({
        "a": pa.array([D("1.50"), D("2.25")], type=pa.decimal128(10, 2)),
        "k": pa.array([2, 3], type=pa.int32()),
    }))
    out = df.select((F.col("a") * F.col("k")).alias("m")).to_pandas()
    assert out["m"].tolist() == [D("3.00"), D("6.75")]


def test_decimal_compare_and_filter(session):
    df = dec_df(session, {
        "a": ([D("1.25"), D("3.50"), D("2.00")], 4, 2),
    })
    out = df.filter(F.col("a") > F.lit(2)).to_pandas()
    assert out["a"].tolist() == [D("3.50")]


def test_decimal_groupby_sum(session):
    df = session.create_dataframe(pa.table({
        "k": pa.array([0, 1, 0, 1], type=pa.int32()),
        "v": pa.array([D("1.10"), D("2.20"), D("3.30"), None],
                      type=pa.decimal128(6, 2)),
    }))
    q = df.groupBy("k").agg(F.sum("v").alias("s"))
    plan = session.plan(q.plan)
    assert "CpuFallbackExec" not in plan.tree_string()
    # sum(decimal(6,2)) -> decimal(16,2)
    assert dict(q.plan.schema)["s"].name == "decimal(16,2)"
    out = q.orderBy("k").to_pandas()
    assert out["s"].tolist() == [D("4.40"), D("2.20")]


def test_decimal_sum_wide_falls_back(session):
    df = dec_df(session, {"v": ([D("1.5")], 12, 1)})
    q = df.agg(F.sum("v").alias("s"))
    plan = session.plan(q.plan)
    assert "CpuFallbackExec" in plan.tree_string()
    assert q.to_pandas()["s"].tolist() == [D("1.5")]


def test_decimal_avg_runs_on_device(session):
    df = dec_df(session, {"v": ([D("1.0"), D("2.0")], 4, 1)})
    q = df.agg(F.avg("v").alias("a"))
    plan = session.plan(q.plan)
    assert "CpuFallbackExec" not in plan.tree_string()
    # Spark: avg(decimal(4,1)) -> decimal(8,5), exact
    assert dict(q.plan.schema)["a"].name == "decimal(8,5)"
    a = q.to_pandas()["a"].tolist()[0]
    assert a == D("1.5") and a.as_tuple().exponent == -5


def test_decimal_avg_wide_falls_back(session):
    # the sum buffer decimal(19,1) is past DECIMAL_64, as for sum
    df = dec_df(session, {"v": ([D("1.0"), D("2.0")], 9, 1)})
    q = df.agg(F.avg("v").alias("a"))
    assert "CpuFallbackExec" in session.plan(q.plan).tree_string()
    assert q.to_pandas()["a"].tolist()[0] == D("1.5")


def _avg_oracle(values, scale):
    """Spark's avg over decimal(p, scale): the exact quotient at
    scale + 4 places, HALF_UP; NULL over no value."""
    vals = [v for v in values if v is not None]
    if not vals:
        return None
    with decimal.localcontext() as ctx:
        ctx.prec = 60
        return (sum(vals) / len(vals)).quantize(
            D(1).scaleb(-(scale + 4)), rounding=decimal.ROUND_HALF_UP)


def _avg_case(name):
    """(keys, values) of decimal(7,2), seeded by the case's name."""
    rng = np.random.default_rng([7, sum(name.encode())])

    def cents(lo, hi, n):
        return [D(int(x)).scaleb(-2) for x in rng.integers(lo, hi, n)]

    if name == "negative_sums":
        keys = rng.integers(0, 9, 400).tolist()
        return keys, cents(-9_999_999, 100, 400)
    if name == "mixed_signs_with_nulls":
        keys = rng.integers(0, 40, 1500).tolist()
        vals = cents(-9_999_999, 9_999_999, 1500)
        return keys, [None if rng.random() < 0.2 else v for v in vals]
    if name == "ties_at_the_seventh_place":
        # 32 values a group with an odd sum of cents: the quotient ends
        # in ...5 at the seventh place exactly, so HALF_UP decides, away
        # from zero for the negative groups
        keys, vals = [], []
        for k in range(12):
            group = rng.integers(1, 5_000_000, 32)
            group[0] += 1 - int(group.sum()) % 2
            sign = -1 if k % 2 else 1
            keys += [k] * 32
            vals += [D(sign * int(x)).scaleb(-2) for x in group]
        return keys, vals
    if name == "all_null_groups":
        keys = rng.integers(0, 6, 120).tolist()
        vals = cents(-500, 500, 120)
        return keys, [None if k % 2 else v for k, v in zip(keys, vals)]
    raise KeyError(name)


AVG_CASES = ["negative_sums", "mixed_signs_with_nulls",
             "ties_at_the_seventh_place", "all_null_groups"]


@pytest.mark.parametrize("case", AVG_CASES)
def test_decimal_avg_grouped_vs_python_decimal(session, case):
    keys, vals = _avg_case(case)
    df = session.create_dataframe(pa.table({
        "k": pa.array(keys, type=pa.int32()),
        "v": pa.array(vals, type=pa.decimal128(7, 2))}))
    q = df.groupBy("k").agg(F.avg("v").alias("a"))
    assert "CpuFallbackExec" not in session.plan(q.plan).tree_string()
    assert dict(q.plan.schema)["a"].name == "decimal(11,6)"
    out = q.orderBy("k").to_pandas()
    groups = {k: [v for kk, v in zip(keys, vals) if kk == k]
              for k in sorted(set(keys))}
    want = {k: _avg_oracle(g, 2) for k, g in groups.items()}
    assert out["k"].tolist() == list(want)
    assert out["a"].tolist() == list(want.values())
    if case == "ties_at_the_seventh_place":
        # the case is what it says: every exact quotient has seven
        # places and a 5 in the last, and the answer is the one further
        # from zero
        for g, a in zip(groups.values(), want.values()):
            exact = sum(g) / D(len(g))
            assert exact.scaleb(7) % 10 in (5, -5)
            assert abs(a) > abs(exact) and abs(a - exact) == D("5e-7")
    if case == "all_null_groups":
        assert [a is None for a in out["a"]] == [k % 2 == 1 for k in want]


@pytest.mark.parametrize("case", AVG_CASES)
def test_decimal_avg_grand_total_vs_python_decimal(session, case):
    _, vals = _avg_case(case)
    df = dec_df(session, {"v": (vals, 7, 2)})
    q = df.agg(F.avg("v").alias("a"))
    assert "CpuFallbackExec" not in session.plan(q.plan).tree_string()
    assert q.to_pandas()["a"].tolist() == [_avg_oracle(vals, 2)]


def test_decimal_avg_over_no_value_is_null(session):
    df = dec_df(session, {"v": ([None, None, None], 7, 2)})
    assert df.agg(F.avg("v").alias("a")).to_pandas()["a"].tolist() == [None]


@pytest.mark.parametrize("batch_rows", [64, 1000])
def test_decimal_avg_partials_merge_across_batches(tmp_path, batch_rows):
    """Several scan batches: every batch's (sum, count) partial is merged
    before the one exact division."""
    import pyarrow.parquet as pq
    keys, vals = _avg_case("mixed_signs_with_nulls")
    pq.write_table(pa.table({
        "k": pa.array(keys, type=pa.int32()),
        "v": pa.array(vals, type=pa.decimal128(7, 2))}),
        str(tmp_path / "t.parquet"))
    s = TpuSession({"spark.rapids.sql.reader.batchSizeRows": batch_rows})
    s.read.parquet(str(tmp_path)).createOrReplaceTempView("t")
    q = s.sql("select k, avg(v) a, count(v) n from t group by k order by k")
    assert "CpuFallbackExec" not in s.plan(q.plan).tree_string()
    out = q.to_pandas()
    groups = {k: [v for kk, v in zip(keys, vals) if kk == k]
              for k in sorted(set(keys))}
    assert out["a"].tolist() == [_avg_oracle(g, 2) for g in groups.values()]
    assert out["n"].tolist() == [sum(v is not None for v in g)
                                 for g in groups.values()]


@pytest.mark.parametrize("total,count,want", [
    # decimal(8,2): sum buffer decimal(18,2), result decimal(12,6)
    (10**18 - 1, 1, None),             # quotient past the result type
    (-(10**18 - 1), 1, None),
    (99_999_999 * 7, 7, 99_999_999 * 10**4),   # the largest that fits
    (-99_999_999 * 7, 7, -99_999_999 * 10**4),
    (1, 3, 3333), (2, 3, 6667), (-2, 3, -6667), (1, 2 * 10**4, 1),
    (1, 2 * 10**4 + 1, 0), (0, 5, 0),
])
def test_decimal_avg_finalize_overflow_is_null(total, count, want):
    """The finalize step alone, on merged buffers: a sum buffer whose
    average does not fit the result type gives NULL (Spark, non-ANSI)."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import dtypes as dts
    from spark_rapids_tpu.ops import aggregates as ag
    from spark_rapids_tpu.ops.expressions import BoundReference, ColVal
    func = ag.Average(BoundReference(0, DecimalType(8, 2)))
    assert func.result_dtype.name == "decimal(12,6)"
    assert [b.dtype.name for b in func.buffers()] == ["decimal(18,2)",
                                                      "bigint"]
    out = func.finalize([
        ColVal(DecimalType(18, 2), jnp.array([total], dtype=jnp.int64)),
        ColVal(dts.INT64, jnp.array([count], dtype=jnp.int64))])
    valid = True if out.validity is None else bool(out.validity[0])
    assert (int(out.values[0]) if valid else None) == want


def test_divmod_nonneg_is_pythons_divmod():
    """The shift-and-subtract division under ``decimal_average`` (the
    chip has no 64-bit divider; its compiler unrolls one for 7 s a
    ``//``): Python's own quotient and remainder over the whole range
    it is used on, the corners included."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.aggregates import _divmod_nonneg
    rng = np.random.default_rng(11)
    n = rng.integers(0, 2**63 - 1, size=4000, endpoint=True)
    d = rng.integers(1, 2**62 - 1, size=4000, endpoint=True)
    d[1000:] = rng.integers(1, 10**6, size=3000)   # counts of rows
    n[2000:3000] = rng.integers(0, 10**9, size=1000)
    n[:4] = [0, 1, 2**63 - 1, 2**62]
    d[:4] = [1, 2**62 - 1, 1, 2**62 - 1]
    q, r = _divmod_nonneg(jnp.asarray(n), jnp.asarray(d))
    want = [divmod(int(a), int(b)) for a, b in zip(n, d)]
    assert np.asarray(q).tolist() == [w[0] for w in want]
    assert np.asarray(r).tolist() == [w[1] for w in want]


def test_decimal_min_max_orderby(session):
    vals = [D("2.50"), D("-1.25"), None, D("9.75"), D("0.00")]
    df = dec_df(session, {"v": (vals, 5, 2)})
    out = df.agg(F.min("v").alias("lo"), F.max("v").alias("hi")) \
        .to_pandas()
    assert out["lo"][0] == D("-1.25")
    assert out["hi"][0] == D("9.75")
    got = df.orderBy("v").to_pandas()["v"].tolist()
    assert got[0] is None  # nulls first
    assert got[1:] == sorted(v for v in vals if v is not None)


def test_named_decimal_exprs_roundtrip(session):
    """MakeDecimal / UnscaledValue / PromotePrecision / CheckOverflow as
    programmatic expressions."""
    from spark_rapids_tpu.api.functions import Col
    from spark_rapids_tpu.ops.decimal_ops import (
        CheckOverflow, MakeDecimal, PromotePrecision, UnscaledValue)
    df = dec_df(session, {"v": ([D("1.23"), D("-4.56")], 6, 2)})
    uv = df.select(Col(UnscaledValue(F.col("v").expr)).alias("u"))
    assert uv.to_pandas()["u"].tolist() == [123, -456]
    md = df.select(Col(MakeDecimal(UnscaledValue(F.col("v").expr), 6, 2))
                   .alias("m"))
    assert md.to_pandas()["m"].tolist() == [D("1.23"), D("-4.56")]
    pp = df.select(Col(PromotePrecision(F.col("v").expr,
                                        DecimalType(10, 4))).alias("p"))
    assert pp.to_pandas()["p"].tolist() == [D("1.2300"), D("-4.5600")]
    co = df.select(Col(CheckOverflow(F.col("v").expr, DecimalType(3, 2)))
                   .alias("c"))
    assert co.to_pandas()["c"].tolist() == [D("1.23"), D("-4.56")]
    co2 = df.select(Col(CheckOverflow(F.col("v").expr,
                                      DecimalType(2, 2))).alias("c"))
    assert co2.to_pandas()["c"].tolist() == [None, None]  # |v| >= 1


def test_decimal_fuzz_vs_python_decimal(session):
    """Randomized add/mul against the Python decimal oracle with Spark
    result scales."""
    rng = np.random.default_rng(42)
    n = 500
    a = [D(int(x)).scaleb(-2) for x in rng.integers(-10**5, 10**5, n)]
    b = [D(int(x)).scaleb(-3) for x in rng.integers(-10**6, 10**6, n)]
    df = session.create_dataframe(pa.table({
        "a": pa.array(a, type=pa.decimal128(7, 2)),
        "b": pa.array(b, type=pa.decimal128(8, 3)),
    }))
    out = df.select((F.col("a") + F.col("b")).alias("s"),
                    (F.col("a") * F.col("b")).alias("m")).to_pandas()
    for i in range(n):
        assert out["s"][i] == a[i] + b[i], i
        assert out["m"][i] == (a[i] * b[i]), i
