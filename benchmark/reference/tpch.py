"""Plain pandas answers to the TPC-H queries the cells run.

From ``chip_smoke.py``'s oracles; nothing of ``spark_rapids_tpu`` is
imported and nothing the program made is read.  Each function takes the
generated frames (the columns the query names, as pandas) and the float
type to compute in: ``float64`` is the reference, ``float32`` is the
control that the comparison has to fail (the precision below the one the
configuration states).  Parameters are the specification's validation
values, the same as the query texts under ``queries/tpch/``.
"""

import numpy as np
import pandas as pd


def _floats(df, dtype):
    """The frame with every float column in ``dtype``."""
    if dtype == np.float64:
        return df
    cols = [c for c in df.columns if pd.api.types.is_float_dtype(df[c])]
    return df.astype({c: dtype for c in cols})


def _one(dtype, x):
    return np.asarray(x, dtype=dtype)[()]


def q6(t, dtype=np.float64):
    l = _floats(t["lineitem"], dtype)
    m = l[(l.l_shipdate >= pd.Timestamp("1994-01-01"))
          & (l.l_shipdate < pd.Timestamp("1995-01-01"))
          & (l.l_discount >= _one(dtype, 0.05))
          & (l.l_discount <= _one(dtype, 0.07))
          & (l.l_quantity < 24)]
    return pd.DataFrame(
        {"revenue": [(m.l_extendedprice * m.l_discount).sum()]})


def q1(t, dtype=np.float64):
    l = _floats(t["lineitem"], dtype)
    m = l[l.l_shipdate <= pd.Timestamp("1998-09-02")].copy()
    one = _one(dtype, 1)
    m["disc_price"] = m.l_extendedprice * (one - m.l_discount)
    m["charge"] = m.disc_price * (one + m.l_tax)
    g = m.groupby(["l_returnflag", "l_linestatus"], observed=True)
    out = g.agg(sum_qty=("l_quantity", "sum"),
                sum_base_price=("l_extendedprice", "sum"),
                sum_disc_price=("disc_price", "sum"),
                sum_charge=("charge", "sum"),
                avg_qty=("l_quantity", "mean"),
                avg_price=("l_extendedprice", "mean"),
                avg_disc=("l_discount", "mean"),
                count_order=("l_quantity", "size")).reset_index()
    return out.sort_values(["l_returnflag", "l_linestatus"],
                           ignore_index=True)


def q3(t, dtype=np.float64):
    c, o = t["customer"], t["orders"]
    l = _floats(t["lineitem"], dtype)
    cutoff = pd.Timestamp("1995-03-15")
    j = c[c.c_mktsegment == "BUILDING"][["c_custkey"]] \
        .merge(o[o.o_orderdate < cutoff], left_on="c_custkey",
               right_on="o_custkey") \
        .merge(l[l.l_shipdate > cutoff], left_on="o_orderkey",
               right_on="l_orderkey")
    j["revenue"] = j.l_extendedprice * (_one(dtype, 1) - j.l_discount)
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  as_index=False)["revenue"].sum()
    g = g.sort_values(["revenue", "o_orderdate"],
                      ascending=[False, True]).head(10)
    return g[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]] \
        .reset_index(drop=True)


ANSWERS = {"q6": q6, "q1": q1, "q3": q3}
