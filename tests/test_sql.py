"""SQL frontend tests — oracle: pandas and the programmatic tpch module.

Miniature of the reference's SQL-side integration coverage: the SQL
path shares every stage below the parser with the DataFrame API, so
these tests pin the parse/resolve layer itself.
"""

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.sql import parse


@pytest.fixture(scope="module")
def session():
    s = TpuSession()
    rng = np.random.default_rng(7)
    orders = pd.DataFrame({
        "o_id": np.arange(120),
        "cust": rng.integers(0, 12, 120),
        "amount": rng.uniform(10, 500, 120).round(2),
        "note": [f"order {i} info" for i in range(120)],
    })
    cust = pd.DataFrame({
        "c_id": np.arange(12),
        "name": [f"cust{i}" for i in range(12)],
        "region": rng.integers(0, 3, 12),
    })
    s.create_dataframe(orders).createOrReplaceTempView("orders")
    s.create_dataframe(cust).createOrReplaceTempView("customers")
    s._test_orders = orders
    s._test_cust = cust
    return s


def test_simple_projection_filter(session):
    got = session.sql(
        "SELECT o_id, amount * 2 AS dbl FROM orders "
        "WHERE amount > 400 ORDER BY o_id").to_pandas()
    o = session._test_orders
    want = o[o.amount > 400].sort_values("o_id")
    assert got["o_id"].tolist() == want["o_id"].tolist()
    np.testing.assert_allclose(got["dbl"], want["amount"] * 2)


def test_star_and_limit(session):
    got = session.sql("SELECT * FROM customers LIMIT 3").to_pandas()
    assert list(got.columns) == ["c_id", "name", "region"]
    assert len(got) == 3


def test_group_by_having_order(session):
    got = session.sql(
        "SELECT cust, count(*) AS n, sum(amount) AS total FROM orders "
        "GROUP BY cust HAVING count(*) >= 5 "
        "ORDER BY total DESC").to_pandas()
    o = session._test_orders
    want = (o.groupby("cust", as_index=False)
            .agg(n=("o_id", "count"), total=("amount", "sum")))
    want = want[want.n >= 5].sort_values(
        "total", ascending=False).reset_index(drop=True)
    pd.testing.assert_frame_equal(got, want, check_dtype=False,
                                  rtol=1e-9)


def test_agg_arithmetic_composition(session):
    # sum(x) / count(*) composes through hidden agg columns
    got = session.sql(
        "SELECT cust, sum(amount) / count(*) AS mean_amt FROM orders "
        "GROUP BY cust ORDER BY cust").to_pandas()
    o = session._test_orders
    want = o.groupby("cust")["amount"].mean()
    np.testing.assert_allclose(got["mean_amt"], want.values, rtol=1e-9)


def test_join_with_qualifiers(session):
    got = session.sql(
        "SELECT c.name, o.amount FROM orders o "
        "JOIN customers c ON o.cust = c.c_id "
        "WHERE c.region = 1 ORDER BY o.amount DESC LIMIT 10"
    ).to_pandas()
    o, c = session._test_orders, session._test_cust
    want = (o.merge(c, left_on="cust", right_on="c_id")
            .query("region == 1").sort_values("amount", ascending=False)
            .head(10))
    np.testing.assert_allclose(got["amount"], want["amount"])


def test_left_join_and_semi(session):
    big = session.sql(
        "SELECT c.c_id, o.o_id FROM customers c "
        "LEFT JOIN orders o ON c.c_id = o.cust").to_pandas()
    o, c = session._test_orders, session._test_cust
    want = c.merge(o, left_on="c_id", right_on="cust", how="left")
    assert len(big) == len(want)
    semi = session.sql(
        "SELECT c_id FROM customers c LEFT SEMI JOIN orders o "
        "ON c.c_id = o.cust").to_pandas()
    assert set(semi["c_id"]) == set(o["cust"].unique())


def _filters_sit_below_joins(plan) -> bool:
    from spark_rapids_tpu.plan import logical as L
    if isinstance(plan, L.Filter) and \
            any(isinstance(c, L.Join) for c in plan.children):
        return False
    return all(_filters_sit_below_joins(c) for c in plan.children)


def test_where_filters_its_source_before_an_inner_join(session):
    # qualified, and bare names that exactly one table has; the IN
    # subquery's semi join lands on its own source too
    df = session.sql(
        "SELECT o.o_id, c.name FROM orders o "
        "JOIN customers c ON o.cust = c.c_id "
        "WHERE o.amount > 300 AND region = 1 "
        "AND o.cust IN (SELECT c_id FROM customers WHERE c_id < 9) "
        "ORDER BY o_id")
    assert _filters_sit_below_joins(df.plan)
    o, c = session._test_orders, session._test_cust
    want = o[(o.amount > 300) & (o.cust < 9)] \
        .merge(c[c.region == 1], left_on="cust", right_on="c_id") \
        .sort_values("o_id")
    assert len(want) > 0
    got = df.to_pandas()
    assert got["o_id"].tolist() == want["o_id"].tolist()
    assert got["name"].tolist() == want["name"].tolist()


def test_where_stays_above_an_outer_join(session):
    # the anti-join idiom: a filter on the null-extended side of a LEFT
    # JOIN does not commute with it
    df = session.sql(
        "SELECT c.c_id FROM customers c LEFT JOIN "
        "(SELECT cust, amount FROM orders WHERE amount > 480) o "
        "ON c.c_id = o.cust WHERE o.amount IS NULL ORDER BY c_id")
    o, c = session._test_orders, session._test_cust
    want = sorted(set(c.c_id) - set(o[o.amount > 480].cust))
    assert 0 < len(want) < len(c)
    assert df.to_pandas()["c_id"].tolist() == want
    assert not _filters_sit_below_joins(df.plan)


def test_using_join(session):
    session.sql("SELECT cust AS c_id, amount FROM orders") \
        .createOrReplaceTempView("o2")
    got = session.sql(
        "SELECT name, amount FROM o2 JOIN customers USING (c_id) "
        "ORDER BY amount LIMIT 5").to_pandas()
    assert len(got) == 5


def test_case_when_cast_between_in_like(session):
    got = session.sql("""
      SELECT o_id,
             CASE WHEN amount > 250 THEN 'big' ELSE 'small' END AS sz,
             CAST(amount AS int) AS amt_i
      FROM orders
      WHERE amount BETWEEN 100 AND 300
        AND cust IN (1, 2, 3)
        AND note LIKE 'order %'
      ORDER BY o_id""").to_pandas()
    o = session._test_orders
    want = o[(o.amount >= 100) & (o.amount <= 300)
             & o.cust.isin([1, 2, 3])]
    assert got["o_id"].tolist() == sorted(want["o_id"])
    assert set(got["sz"]) <= {"big", "small"}
    assert (got["amt_i"] == want.sort_values("o_id")
            ["amount"].astype(int).values).all()


def test_distinct_and_union_all(session):
    got = session.sql(
        "SELECT DISTINCT region FROM customers").to_pandas()
    assert sorted(got["region"]) == sorted(
        session._test_cust["region"].unique())
    u = session.sql(
        "SELECT c_id FROM customers WHERE region = 0 "
        "UNION ALL SELECT c_id FROM customers WHERE region = 0"
    ).to_pandas()
    n0 = (session._test_cust.region == 0).sum()
    assert len(u) == 2 * n0


def test_subquery_in_from(session):
    got = session.sql("""
      SELECT t.cust, t.total FROM (
        SELECT cust, sum(amount) AS total FROM orders GROUP BY cust
      ) t WHERE t.total > 1000 ORDER BY t.total DESC""").to_pandas()
    o = session._test_orders
    want = o.groupby("cust")["amount"].sum()
    want = want[want > 1000].sort_values(ascending=False)
    np.testing.assert_allclose(got["total"], want.values, rtol=1e-9)


def test_window_function(session):
    got = session.sql("""
      SELECT o_id, cust,
             row_number() OVER (PARTITION BY cust ORDER BY amount DESC)
               AS rk
      FROM orders ORDER BY cust, rk LIMIT 20""").to_pandas()
    o = session._test_orders
    want = o.copy()
    want["rk"] = want.groupby("cust")["amount"].rank(
        method="first", ascending=False).astype(int)
    merged = got.merge(want[["o_id", "rk"]], on="o_id",
                       suffixes=("", "_want"))
    assert (merged["rk"] == merged["rk_want"]).all()


def test_string_functions(session):
    got = session.sql(
        "SELECT upper(name) AS u, length(name) AS l, "
        "substring(name, 1, 4) AS pre FROM customers "
        "ORDER BY c_id LIMIT 2").to_pandas()
    assert got["u"].tolist() == ["CUST0", "CUST1"]
    assert got["pre"].tolist() == ["cust", "cust"]
    assert got["l"].tolist() == [5, 5]


def test_select_without_from(session):
    got = session.sql("SELECT 1 + 1 AS two, 'x' AS s").to_pandas()
    assert got["two"].tolist() == [2]
    assert got["s"].tolist() == ["x"]


def test_date_literal(session):
    pdf = pd.DataFrame({
        "d": pd.to_datetime(["2024-01-05", "2024-06-01",
                             "2024-09-30"]).date,
        "v": [1, 2, 3]})
    session.create_dataframe(pdf).createOrReplaceTempView("dated")
    got = session.sql(
        "SELECT v FROM dated WHERE d < DATE '2024-07-01' "
        "ORDER BY v").to_pandas()
    assert got["v"].tolist() == [1, 2]


def test_tpch_q6_in_sql(session):
    """The flagship query as SQL text vs the programmatic pipeline."""
    from spark_rapids_tpu.models import tpch
    data = tpch.gen_tables(sf=0.01)
    t = tpch.load(session, data)
    t["lineitem"].createOrReplaceTempView("lineitem")
    got = session.sql("""
      SELECT sum(l_extendedprice * l_discount) AS revenue
      FROM lineitem
      WHERE l_shipdate >= DATE '1994-01-01'
        AND l_shipdate < DATE '1995-01-01'
        AND l_discount BETWEEN 0.05 AND 0.07
        AND l_quantity < 24
    """).to_pandas()
    want = tpch.q6(t).to_pandas()
    np.testing.assert_allclose(got["revenue"].iloc[0],
                               want.iloc[0, 0], rtol=1e-9)


def test_tpch_q1_in_sql(session):
    from spark_rapids_tpu.models import tpch
    data = tpch.gen_tables(sf=0.01)
    t = tpch.load(session, data)
    t["lineitem"].createOrReplaceTempView("lineitem")
    got = session.sql("""
      SELECT l_returnflag, l_linestatus,
             sum(l_quantity) AS sum_qty,
             sum(l_extendedprice) AS sum_base_price,
             sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
             avg(l_quantity) AS avg_qty,
             count(*) AS count_order
      FROM lineitem
      WHERE l_shipdate <= DATE '1998-09-02'
      GROUP BY l_returnflag, l_linestatus
      ORDER BY l_returnflag, l_linestatus
    """).to_pandas()
    li = data["lineitem"]
    f = li[li.l_shipdate <= pd.Timestamp("1998-09-02")]
    want = (f.assign(dp=f.l_extendedprice * (1 - f.l_discount))
            .groupby(["l_returnflag", "l_linestatus"], as_index=False)
            .agg(sum_qty=("l_quantity", "sum"),
                 sum_base_price=("l_extendedprice", "sum"),
                 sum_disc_price=("dp", "sum"),
                 avg_qty=("l_quantity", "mean"),
                 count_order=("l_quantity", "count"))
            .sort_values(["l_returnflag", "l_linestatus"])
            .reset_index(drop=True))
    pd.testing.assert_frame_equal(got, want, check_dtype=False,
                                  rtol=1e-9)


def test_parse_errors_are_clear(session):
    with pytest.raises(ValueError, match="expected"):
        parse("SELECT FROM x")
    with pytest.raises(ValueError, match="unknown SQL function"):
        session.sql("SELECT nosuchfn(c_id) FROM customers")
    with pytest.raises(KeyError, match="unknown table"):
        session.sql("SELECT * FROM nope")
    with pytest.raises(ValueError, match="ambiguous"):
        session.sql("SELECT c_id FROM customers c1 "
                    "JOIN customers c2 ON c1.c_id = c2.c_id")


def test_string_case_when_programmatic(session):
    # the string_select kernel directly (CASE with string branches was
    # previously unsupported in the expression engine)
    from spark_rapids_tpu.api import functions as F
    pdf = pd.DataFrame({"x": [10.0, 300.0, 150.0, None]})
    df = session.create_dataframe(pdf)
    out = df.select(
        F.when(F.col("x") > 250, "big")
         .when(F.col("x") > 100, "mid")
         .otherwise("small").alias("sz"),
        F.when(F.col("x") > 250, "big").alias("maybe")).to_pandas()
    assert out["sz"].tolist() == ["small", "big", "mid", "small"]
    assert out["maybe"].tolist()[1] == "big"
    assert out["maybe"].isna().tolist() == [True, False, True, True]


def test_string_case_with_column_branches(session):
    from spark_rapids_tpu.api import functions as F
    pdf = pd.DataFrame({"a": ["xx", "yyy"], "b": ["zzzz", "w"],
                        "pick_a": [True, False]})
    df = session.create_dataframe(pdf)
    out = df.select(
        F.when(F.col("pick_a"), F.col("a"))
         .otherwise(F.col("b")).alias("c")).to_pandas()
    assert out["c"].tolist() == ["xx", "w"]


def test_using_join_qualified_right_column(session):
    ta = pd.DataFrame({"k": [1, 2, 3], "v": ["L1", "L2", "L3"]})
    tb = pd.DataFrame({"k": [1, 2, 3], "v": ["R1", "R2", "R3"]})
    session.create_dataframe(ta).createOrReplaceTempView("ta")
    session.create_dataframe(tb).createOrReplaceTempView("tb")
    got = session.sql(
        "SELECT tb.v FROM ta JOIN tb USING (k) ORDER BY k").to_pandas()
    assert got.iloc[:, 0].tolist() == ["R1", "R2", "R3"]


def test_qualified_star(session):
    got = session.sql(
        "SELECT c.* FROM orders o JOIN customers c "
        "ON o.cust = c.c_id LIMIT 3").to_pandas()
    assert set(got.columns) == {"c_id", "name", "region"}


def test_order_by_mixed_alias_and_input(session):
    got = session.sql(
        "SELECT amount + 1 AS b FROM orders "
        "ORDER BY cust, b DESC LIMIT 8").to_pandas()
    o = session._test_orders
    want = (o.assign(b=o.amount + 1)
            .sort_values(["cust", "b"], ascending=[True, False])
            .head(8))
    np.testing.assert_allclose(got["b"], want["b"].values)


def test_group_by_mixed_computed_and_plain_key(session):
    got = session.sql(
        "SELECT cust, count(*) AS n FROM orders "
        "GROUP BY cust / 2 * 2, cust ORDER BY cust").to_pandas()
    o = session._test_orders
    want = o.groupby("cust").size()
    assert got["n"].tolist() == want.tolist()


def test_not_in_subquery_null_aware(session):
    a = pd.DataFrame({"k": [1.0, 2.0]})
    b = pd.DataFrame({"v": [1.0, None]})
    session.create_dataframe(a).createOrReplaceTempView("na_a")
    session.create_dataframe(b).createOrReplaceTempView("na_b")
    # a NULL in the subquery makes NOT IN unknown for every row
    got = session.sql(
        "SELECT k FROM na_a WHERE k NOT IN (SELECT v FROM na_b)"
    ).to_pandas()
    assert len(got) == 0
    # without the NULL, ordinary anti-join semantics
    session.create_dataframe(pd.DataFrame({"v": [1.0]})) \
        .createOrReplaceTempView("na_c")
    got = session.sql(
        "SELECT k FROM na_a WHERE k NOT IN (SELECT v FROM na_c)"
    ).to_pandas()
    assert got["k"].tolist() == [2.0]
    # empty subquery: NOT IN is true for every row
    session.create_dataframe(pd.DataFrame({"v": [5.0]})) \
        .createOrReplaceTempView("na_d")
    got = session.sql(
        "SELECT k FROM na_a WHERE k NOT IN "
        "(SELECT v FROM na_d WHERE v > 99)").to_pandas()
    assert sorted(got["k"]) == [1.0, 2.0]


def test_scientific_notation_literal(session):
    got = session.sql("SELECT 1e5 AS big, 2.5e-2 AS small").to_pandas()
    assert got["big"].iloc[0] == pytest.approx(1e5)
    assert got["small"].iloc[0] == pytest.approx(0.025)


def test_order_by_qualified_names_input(session):
    pdf = pd.DataFrame({"cust": [1, 2, 3, 4], "amt": [4.0, 3.0, 2.0, 1.0]})
    session.create_dataframe(pdf).createOrReplaceTempView("oq")
    # qualified t.cust names the INPUT column even when an output alias
    # shadows it
    got = session.sql(
        "SELECT amt AS cust FROM oq ORDER BY oq.cust DESC").to_pandas()
    assert got["cust"].tolist() == [1.0, 2.0, 3.0, 4.0]


def test_group_expr_reprojection(session):
    got = session.sql(
        "SELECT cust / 2 AS h, count(*) AS n FROM orders "
        "GROUP BY cust / 2 ORDER BY h").to_pandas()
    o = session._test_orders
    want = o.groupby(o.cust / 2).size().sort_index()
    assert got["n"].tolist() == want.tolist()


def test_order_by_position_validation(session):
    with pytest.raises(ValueError, match="out of range"):
        session.sql("SELECT cust FROM orders ORDER BY 2")
    with pytest.raises(ValueError, match="out of range"):
        session.sql("SELECT cust FROM orders ORDER BY 0")


def test_scalar_subquery_in_having_untouched_by_group_rewrite(session):
    # group-key rewriting must not descend into scalar subqueries
    got = session.sql(
        "SELECT cust / 2 AS h, count(*) AS n FROM orders "
        "GROUP BY cust / 2 "
        "HAVING count(*) >= (SELECT min(cust / 2) FROM orders) "
        "ORDER BY h").to_pandas()
    assert len(got) > 0


def test_order_by_qualified_on_grouped_query(session):
    got = session.sql(
        "SELECT cust, count(*) AS n FROM orders o "
        "GROUP BY cust ORDER BY o.cust").to_pandas()
    assert got["cust"].tolist() == sorted(got["cust"])


def test_empty_scalar_subquery_is_null(session):
    # SQL semantics: empty scalar subquery -> NULL -> predicate false
    got = session.sql(
        "SELECT cust FROM orders WHERE amount > "
        "(SELECT amount FROM orders WHERE amount > 99999)").to_pandas()
    assert len(got) == 0


def test_two_arg_log_and_extra_math(session):
    got = session.sql(
        "SELECT log(2, 8.0) AS l2, asinh(0.0) AS ash, "
        "shiftrightunsigned(8, 2) AS sru").to_pandas()
    assert got["l2"].iloc[0] == pytest.approx(3.0)
    assert got["ash"].iloc[0] == pytest.approx(0.0)
    assert got["sru"].iloc[0] == 2


def test_distinct_with_qualified_order(session):
    got = session.sql(
        "SELECT DISTINCT cust FROM orders o ORDER BY o.cust").to_pandas()
    assert got["cust"].tolist() == sorted(got["cust"].tolist())


def test_cte_basic_and_chained(session):
    orders = session._test_orders
    got = session.sql("""
        WITH by_cust AS (
            SELECT cust, sum(amount) AS total FROM orders GROUP BY cust
        ),
        big AS (SELECT cust, total FROM by_cust WHERE total > 2000)
        SELECT b.cust, b.total FROM big b ORDER BY b.cust
    """).to_pandas()
    want = orders.groupby("cust", as_index=False).agg(
        total=("amount", "sum"))
    want = want[want.total > 2000].sort_values(
        "cust", ignore_index=True)
    pd.testing.assert_frame_equal(got, want, check_dtype=False,
                                  rtol=1e-9)


def test_cte_referenced_twice(session):
    got = session.sql("""
        WITH t AS (SELECT cust, sum(amount) AS s FROM orders
                   GROUP BY cust)
        SELECT a.cust, a.s, b.s AS s2 FROM t a JOIN t b
          ON a.cust = b.cust ORDER BY a.cust
    """).to_pandas()
    assert (got.s == got.s2).all()
    assert len(got) == 12


def test_cte_in_subquery_predicate(session):
    got = session.sql("""
        WITH rich AS (SELECT cust FROM orders GROUP BY cust
                      HAVING sum(amount) > 2500)
        SELECT count(*) AS n FROM orders WHERE cust IN (SELECT cust
                                                        FROM rich)
    """).to_pandas()
    orders = session._test_orders
    by = orders.groupby("cust").amount.sum()
    rich = set(by[by > 2500].index)
    assert int(got.n[0]) == int(orders.cust.isin(rich).sum())


def test_window_nested_in_arithmetic(session):
    """A window function inside arithmetic lifts into a hidden Window
    column (the TPC-DS q98 revenueratio shape)."""
    got = session.sql("""
        SELECT cust, amount * 100.0 / sum(amount) OVER
               (PARTITION BY cust) AS pct
        FROM orders
    """).to_pandas()
    orders = session._test_orders
    want = (orders.amount * 100.0
            / orders.groupby("cust").amount.transform("sum"))
    assert got.pct.sum() == pytest.approx(want.sum())
    # per-cust percentages total 100
    tot = got.groupby("cust").pct.sum()
    assert np.allclose(tot, 100.0)
