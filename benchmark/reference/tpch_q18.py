"""Plain pandas answer to TPC-H Q18 (clause 2.4.18, Large Volume Customer).

From ``chip_smoke.py``'s ``oracle_q18``; nothing of ``spark_rapids_tpu``
is imported and nothing the program made is read.  ``q18`` takes the
generated frames (the columns the query names, as pandas), the float type
to compute in (``float64`` is the reference, ``float32`` the control the
comparison has to fail) and QUANTITY (300 is the specification's
validation value, the same as ``queries/tpch_q18/q18.sql``).

What ``correct`` rests on in this query: its exact columns.  ``sum_qty``
is a sum of at most seven whole numbers up to 50, exact in any float
type, so ``o_totalprice`` is the only float that can differ; which
orders, whose names, which dates and in which order are compared cell
for cell.

A suite of its own (``tpch_q18``) only because the harness finds a
reference module by suite and ``reference/tpch.py`` may not be edited by
the PR that brought this file; a later ``benchmark`` issue folds the two.
"""

import numpy as np

from benchmark.reference.tpch import _floats


def q18(t, dtype=np.float64, quantity=300):
    c = t["customer"][["c_custkey", "c_name"]]
    o = _floats(t["orders"], dtype)[["o_orderkey", "o_custkey",
                                     "o_orderdate", "o_totalprice"]]
    l = _floats(t["lineitem"], dtype)[["l_orderkey", "l_quantity"]]
    per_order = l.groupby("l_orderkey")["l_quantity"].sum()
    big = per_order[per_order > quantity].index
    j = c.merge(o[o.o_orderkey.isin(big)], left_on="c_custkey",
                right_on="o_custkey") \
        .merge(l, left_on="o_orderkey", right_on="l_orderkey")
    g = j.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                   "o_totalprice"], as_index=False) \
        .agg(sum_qty=("l_quantity", "sum"))
    return g.sort_values(["o_totalprice", "o_orderdate"],
                         ascending=[False, True], kind="stable") \
        .head(100).reset_index(drop=True)


ANSWERS = {"q18": q18}
