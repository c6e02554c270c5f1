"""End-to-end DataFrame tests — oracle: pandas (the CPU-Spark analog).

Mirrors the reference's SparkQueryCompareTestSuite pattern: run the same
query on the TPU engine and on pandas, diff results.
"""

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession


@pytest.fixture(scope="module")
def session():
    return TpuSession()


def assert_frames_equal(got: pd.DataFrame, want: pd.DataFrame,
                        sort_by=None, approx=False):
    if sort_by:
        got = got.sort_values(sort_by).reset_index(drop=True)
        want = want.sort_values(sort_by).reset_index(drop=True)
    got = got.reset_index(drop=True)
    want = want.reset_index(drop=True)
    assert list(got.columns) == list(want.columns)
    for c in got.columns:
        g, w = got[c], want[c]
        if approx and np.issubdtype(np.asarray(w).dtype, np.floating):
            np.testing.assert_allclose(g, w, rtol=1e-12)
        else:
            pd.testing.assert_series_equal(
                g, w, check_dtype=False, check_names=False)


def test_select_filter_project(session):
    pdf = pd.DataFrame({"a": range(100), "b": np.arange(100) * 0.5})
    df = session.create_dataframe(pdf)
    out = df.filter(F.col("a") > 90).select(
        F.col("a"), (F.col("b") * 2).alias("b2")).to_pandas()
    want = pdf[pdf.a > 90].assign(b2=lambda d: d.b * 2)[["a", "b2"]]
    assert_frames_equal(out, want)


def test_with_column_and_drop(session):
    df = session.create_dataframe({"x": [1, 2, 3]})
    out = df.withColumn("y", F.col("x") + 10).drop("x").to_pandas()
    assert out["y"].tolist() == [11, 12, 13]


def test_grand_aggregate(session):
    pdf = pd.DataFrame({"v": [1.0, 2.0, None, 4.0]})
    df = session.create_dataframe(pdf)
    out = df.agg(F.sum("v").alias("s"), F.count("v").alias("c"),
                 F.avg("v").alias("a"), F.min("v").alias("mn"),
                 F.max("v").alias("mx"), F.count().alias("cnt"))
    row = out.collect()[0]
    assert row == (7.0, 3, 7.0 / 3, 1.0, 4.0, 4)


def test_groupby_aggregate(session):
    rng = np.random.default_rng(0)
    pdf = pd.DataFrame({
        "k": rng.integers(0, 10, 1000),
        "v": rng.normal(size=1000),
        "w": rng.integers(0, 100, 1000),
    })
    df = session.create_dataframe(pdf)
    out = df.groupBy("k").agg(
        F.sum("v").alias("sv"), F.count("v").alias("cv"),
        F.min("w").alias("mw"), F.max("w").alias("xw"),
        F.avg("v").alias("av")).to_pandas()
    want = pdf.groupby("k", as_index=False).agg(
        sv=("v", "sum"), cv=("v", "count"), mw=("w", "min"),
        xw=("w", "max"), av=("v", "mean"))
    assert_frames_equal(out, want, sort_by=["k"], approx=True)


def test_groupby_string_keys(session):
    pdf = pd.DataFrame({
        "name": ["apple", "banana", "apple", None, "banana", "apple"],
        "v": [1, 2, 3, 4, 5, 6]})
    df = session.create_dataframe(pdf)
    out = df.groupBy("name").agg(F.sum("v").alias("s")).to_pandas()
    out = out.sort_values("s").reset_index(drop=True)
    # apple=10, banana=7, None=4
    assert out["s"].tolist() == [4, 7, 10]
    assert pd.isna(out["name"][0])
    assert out["name"].tolist()[1:] == ["banana", "apple"]


def test_groupby_multiple_batches(session):
    # force multiple input batches through a union
    pdf1 = pd.DataFrame({"k": [1, 2, 1], "v": [1, 2, 3]})
    pdf2 = pd.DataFrame({"k": [2, 3, 1], "v": [4, 5, 6]})
    df = session.create_dataframe(pdf1).union(session.create_dataframe(pdf2))
    out = df.groupBy("k").agg(F.sum("v").alias("s")).to_pandas()
    want = pd.DataFrame({"k": [1, 2, 3], "s": [10, 6, 5]})
    assert_frames_equal(out, want, sort_by=["k"])


def test_groupby_null_keys(session):
    pdf = pd.DataFrame({"k": [1, None, 1, None, 2],
                        "v": [1, 2, 3, 4, 5]})
    df = session.create_dataframe(pdf)
    out = df.groupBy("k").agg(F.sum("v").alias("s")).to_pandas()
    s = out.sort_values("s")["s"].tolist()
    assert s == [4, 5, 6]  # k=1 -> 4, k=2 -> 5, null -> 6


def _sparse_int64_keys():
    # 2,000 keys from a 2^40 keyspace: past the coded directory's cap
    rng = np.random.default_rng(7)
    uni = np.unique(rng.integers(0, 1 << 40, 8000, dtype=np.int64))[:2000]
    return pd.DataFrame({
        "k": uni[rng.integers(0, len(uni), 20000)],
        "v": rng.integers(0, 1000, 20000).astype(np.float64)})


def _nan_float_keys():
    rng = np.random.default_rng(13)
    k = rng.normal(size=4000)
    k[::11] = np.nan
    return pd.DataFrame({"k": k, "v": rng.normal(size=4000)})


def _string_keys_500():
    rng = np.random.default_rng(17)
    words = np.array([f"k{i:05d}" for i in range(500)])
    return pd.DataFrame({"k": words[rng.integers(0, 500, 6000)],
                         "v": rng.normal(size=6000)})


@pytest.mark.parametrize("make", [_sparse_int64_keys, _nan_float_keys,
                                  _string_keys_500],
                         ids=["sparse_int64", "nan_float64", "string_500"])
def test_groupby_keys_without_a_coded_directory(session, make):
    """Group keys the coded directory cannot hold (a sparse 40-bit
    range, doubles with NaN, strings) go to the sort / segment kernel
    and answer as pandas does."""
    pdf = make()
    out = (session.create_dataframe(pdf).group_by("k")
           .agg(F.sum(F.col("v")).alias("sv"),
                F.count(F.col("v")).alias("c")).to_pandas())
    want = pdf.groupby("k", as_index=False, dropna=False).agg(
        sv=("v", "sum"), c=("v", "count"))
    assert_frames_equal(out, want, sort_by=["k"], approx=True)


def test_distinct(session):
    df = session.create_dataframe({"a": [1, 2, 1, 3, 2], "b": [1, 1, 1, 2, 1]})
    out = df.distinct().to_pandas().sort_values(["a", "b"])
    assert out.values.tolist() == [[1, 1], [2, 1], [3, 2]]


def test_count_action(session):
    df = session.create_dataframe({"a": list(range(57))})
    assert df.count() == 57
    assert df.filter(F.col("a") < 10).count() == 10


def test_case_when(session):
    df = session.create_dataframe({"x": [1, 5, 10]})
    out = df.select(
        F.when(F.col("x") < 3, "small").when(F.col("x") < 7, "medium")
        .otherwise("large").alias("size").expr and
        F.when(F.col("x") < 3, 0).when(F.col("x") < 7, 1)
        .otherwise(2).alias("bucket")).to_pandas()
    assert out["bucket"].tolist() == [0, 1, 2]


def test_range(session):
    df = session.range(5)
    assert df.collect() == [(0,), (1,), (2,), (3,), (4,)]
    assert session.range(2, 10, 3).collect() == [(2,), (5,), (8,)]


def test_limit(session):
    df = session.create_dataframe({"a": list(range(100))})
    assert df.limit(7).count() == 7


def test_sort_fallback(session):
    pdf = pd.DataFrame({"a": [3, 1, 2], "b": ["x", "y", "z"]})
    df = session.create_dataframe(pdf)
    out = df.orderBy("a").to_pandas()
    assert out["a"].tolist() == [1, 2, 3]
    assert out["b"].tolist() == ["y", "z", "x"]


def test_join_fallback(session):
    left = session.create_dataframe({"k": [1, 2, 3], "l": ["a", "b", "c"]})
    right = session.create_dataframe({"k": [2, 3, 4], "r": [20, 30, 40]})
    out = left.join(right, on="k").to_pandas().sort_values("k")
    assert out["k"].tolist() == [2, 3]
    assert out["r"].tolist() == [20, 30]


def test_explain_smoke(session, capsys):
    df = session.create_dataframe({"a": [1]}).filter(F.col("a") > 0)
    df.explain()
    text = capsys.readouterr().out
    assert "TpuFilterExec" in text
    assert "will run on TPU" in text


def test_strict_mode_raises():
    s = TpuSession({"spark.rapids.sql.test.enabled": True})
    # a LIKE pattern with the _ wildcard still falls back
    df = s.create_dataframe({"a": ["axb", "ab"]}).filter(
        F.col("a").like("a_b"))
    with pytest.raises(RuntimeError, match="fell back to CPU"):
        df.collect()


def test_tpch_q6_shape(session):
    """TPC-H q6: scan -> filter -> project -> grand sum (BASELINE config 1)."""
    rng = np.random.default_rng(7)
    n = 10_000
    lineitem = pd.DataFrame({
        "l_extendedprice": rng.uniform(1000, 100000, n),
        "l_discount": rng.uniform(0, 0.1, n).round(2),
        "l_quantity": rng.integers(1, 51, n).astype("float64"),
        "l_shipdate": rng.integers(8766, 10957, n),  # days since epoch
    })
    df = session.create_dataframe(lineitem)
    out = df.filter(
        (F.col("l_shipdate") >= 9131) & (F.col("l_shipdate") < 9496) &
        (F.col("l_discount") >= 0.05) & (F.col("l_discount") <= 0.07) &
        (F.col("l_quantity") < 24.0)
    ).select((F.col("l_extendedprice") * F.col("l_discount"))
             .alias("rev")).agg(F.sum("rev").alias("revenue"))
    got = out.collect()[0][0]
    m = lineitem[(lineitem.l_shipdate >= 9131) & (lineitem.l_shipdate < 9496)
                 & (lineitem.l_discount >= 0.05)
                 & (lineitem.l_discount <= 0.07)
                 & (lineitem.l_quantity < 24.0)]
    want = (m.l_extendedprice * m.l_discount).sum()
    np.testing.assert_allclose(got, want, rtol=1e-12)


def test_aggregate_fusion(session):
    """Filter+Project under Aggregate collapse into one fused exec."""
    pdf = pd.DataFrame({"k": [1, 2, 1, 2, 3], "v": [1., 2., 3., 4., 100.]})
    df = session.create_dataframe(pdf)
    q = df.filter(F.col("v") < 50).select("k", (F.col("v") * 2).alias("v2")) \
        .groupBy("k").agg(F.sum("v2").alias("s"))
    plan = session.plan(q.plan)
    tree = plan.tree_string()
    assert "TpuFilterExec" not in tree and "TpuProjectExec" not in tree
    out = q.to_pandas().sort_values("k")
    assert out["s"].tolist() == [8.0, 12.0]
    assert out["k"].tolist() == [1, 2]  # k=3 filtered out entirely


def test_parquet_scan_roundtrip(session, tmp_path):
    import pyarrow.parquet as pq
    import pyarrow as pa
    pdf = pd.DataFrame({"a": range(50), "s": [f"row{i}" for i in range(50)]})
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.Table.from_pandas(pdf), path)
    df = session.read.parquet(path)
    out = df.filter(F.col("a") >= 40).to_pandas()
    assert out["a"].tolist() == list(range(40, 50))
    assert out["s"].tolist() == [f"row{i}" for i in range(40, 50)]


def test_agg_result_expr_references_group_key(session):
    """Regression (round-3 advisor, medium): group-key references inside
    a combined aggregate output must read the agg frame's key column,
    not the child schema's ordinal."""
    import pandas as pd
    df = session.create_dataframe(pd.DataFrame(
        {"a": [1, 2, 3, 4], "b": [10, 20, 10, 20]}))
    out = df.groupBy("b").agg(
        (F.sum("a") + F.col("b")).alias("s")).orderBy("b").to_pandas()
    assert out["s"].tolist() == [14, 26]  # sum(a)+b: (1+3)+10, (2+4)+20
    # key expression deeper in the output tree
    out = df.groupBy("b").agg(
        (F.sum("a") + F.col("b") * 2).alias("s")).orderBy("b").to_pandas()
    assert out["s"].tolist() == [24, 46]


def test_agg_output_not_in_group_by_raises(session):
    import pandas as pd
    import pytest
    df = session.create_dataframe(pd.DataFrame(
        {"a": [1, 2], "b": [10, 20]}))
    with pytest.raises(Exception, match="GROUP BY|neither"):
        df.groupBy("b").agg((F.sum("b") + F.col("a")).alias("s")) \
            .to_pandas()
