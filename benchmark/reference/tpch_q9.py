"""Plain pandas answer to TPC-H Q9 (clause 2.4.9, Product Type Profit
Measure).

Nothing of ``spark_rapids_tpu`` is imported and nothing the program made
is read.  ``q9`` takes the generated frames (the columns the query names,
as pandas), the float type to compute in (``float64`` is the reference,
``float32`` the control the comparison has to fail) and COLOR (``green``
is the specification's validation value, the same as
``queries/tpch_q9/q9.sql``).

What ``correct`` rests on in this query: which parts hold the colour (a
LIKE the program runs on the device: leave it out and every sum is about
eighteen times too large), which lines find their part, their supplier,
the supplier's price for that part (a two-column key) and their order,
the order's year, the supplier's nation; then ``sum_profit``, a sum of
about 1,850 products a group in the float type, which the float32 control
misses by 1e-7 and more.

A suite of its own (``tpch_q9``) only because the harness finds a
reference module by suite and ``reference/tpch.py`` may not be edited by
the PR that brought this file; a later ``benchmark`` issue folds the two.
"""

import numpy as np

from benchmark.reference.tpch import _floats, _one


def q9(t, dtype=np.float64, color="green"):
    p = t["part"]
    p = p[p.p_name.str.contains(color, regex=False)][["p_partkey"]]
    s = t["supplier"][["s_suppkey", "s_nationkey"]]
    l = _floats(t["lineitem"], dtype)[
        ["l_orderkey", "l_partkey", "l_suppkey", "l_quantity",
         "l_extendedprice", "l_discount"]]
    ps = _floats(t["partsupp"], dtype)[
        ["ps_partkey", "ps_suppkey", "ps_supplycost"]]
    o = t["orders"][["o_orderkey", "o_orderdate"]]
    n = t["nation"][["n_nationkey", "n_name"]]
    j = p.merge(l, left_on="p_partkey", right_on="l_partkey") \
        .merge(s, left_on="l_suppkey", right_on="s_suppkey") \
        .merge(ps, left_on=["l_suppkey", "l_partkey"],
               right_on=["ps_suppkey", "ps_partkey"]) \
        .merge(o, left_on="l_orderkey", right_on="o_orderkey") \
        .merge(n, left_on="s_nationkey", right_on="n_nationkey")
    j["nation"] = j.n_name
    j["o_year"] = j.o_orderdate.dt.year.astype(np.int32)
    j["amount"] = j.l_extendedprice * (_one(dtype, 1) - j.l_discount) \
        - j.ps_supplycost * j.l_quantity
    g = j.groupby(["nation", "o_year"], as_index=False) \
        .agg(sum_profit=("amount", "sum"))
    return g.sort_values(["nation", "o_year"], ascending=[True, False],
                         kind="stable").reset_index(drop=True)


ANSWERS = {"q9": q9}
