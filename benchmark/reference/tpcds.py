"""Plain answers to the TPC-DS queries the cells run: pandas for the
joins and filters, Python integers and ``decimal`` for the money.

Nothing of ``spark_rapids_tpu`` is imported and nothing the program made
is read.  Each function takes the generated frames (the columns the query
names, as pandas: a nullable int32 key comes as float64 with NaN, a
``decimal(7,2)`` as ``decimal.Decimal`` or None) and the float type to
compute in.  ``float64`` is the reference: an average of decimals is the
exact quotient of integers rounded HALF_UP to Spark's ``decimal(p+4,
s+4)``, NULL (None) over no value.  ``float32`` is the control that the
comparison has to fail (the precision below the one the configuration
states): the same averages worked out in float32 and quantised, handed
back as a program would hand them (a float column with NaN for NULL).
Parameters are the specification's qualification values, the same as the
query texts under ``queries/tpcds/``.
"""

import decimal

import numpy as np
import pandas as pd

# ``harness/compare.py``'s LIMITS["rel_err_max"]: the same limit, for the
# doubles it cannot take itself (see ``Double``)
REL_ERR_MAX = 1e-10


class Double:
    """One cell of a DOUBLE column that holds NULLs, in an answer of the
    reference.

    ``harness/compare.py`` takes a float column under ``rel_err_max``
    only where the program's column is finite throughout; any other
    column it compares cell by cell with ``!=``.  Q7's ``agg1`` is NULL
    wherever a group's quantities all are (about 3 of its 100 rows at
    SF1), so the column is an object column of these cells and ``!=`` is
    asked of each: a NULL wants a NULL (None, or the NaN that a float
    column shows for it), a value wants a finite float within
    ``REL_ERR_MAX`` of it, relative, the limit float columns have."""

    __slots__ = ("value",)

    def __init__(self, value):
        self.value = None if value is None or value != value \
            else float(value)

    def __eq__(self, got):
        if isinstance(got, Double):
            got = got.value
        if got is None or got != got:
            return self.value is None
        if self.value is None or not isinstance(got, (float, int)):
            return False
        return abs(got - self.value) <= REL_ERR_MAX * (abs(self.value)
                                                       or 1.0)

    def __ne__(self, got):
        return not self.__eq__(got)

    __hash__ = None

    def __repr__(self):
        return f"Double({self.value!r})"


def decimal_average(values, scale, dtype=np.float64):
    """Spark's ``avg`` over a ``decimal(p, scale)`` column: the exact
    quotient at ``scale + 4`` places, HALF_UP, None over no value.  In
    ``float32`` (the control) the mean is float32's, then quantised."""
    ints = [int(v.scaleb(scale)) for v in values if v is not None]
    if not ints:
        return None
    places = decimal.Decimal(1).scaleb(-(scale + 4))
    if dtype != np.float64:
        cents = np.asarray(ints, dtype=dtype) / dtype(10 ** scale)
        mean = cents.sum(dtype=dtype) / dtype(len(ints))
        return decimal.Decimal(repr(float(mean))).quantize(
            places, rounding=decimal.ROUND_HALF_UP)
    with decimal.localcontext() as ctx:
        ctx.prec = 60
        mean = decimal.Decimal(sum(ints)).scaleb(-scale) / len(ints)
        return mean.quantize(places, rounding=decimal.ROUND_HALF_UP)


def q7(t, dtype=np.float64):
    ss = t["store_sales"]
    cd, d, p = t["customer_demographics"], t["date_dim"], t["promotion"]
    cd = cd[(cd.cd_gender == "M") & (cd.cd_marital_status == "S")
            & (cd.cd_education_status == "College")]
    d = d[d.d_year == 2000]
    # NULL = 'N' is not true, and neither is NULL OR NULL
    p = p[(p.p_channel_email == "N") | (p.p_channel_event == "N")]
    # a NULL key (NaN here) is in no dimension
    m = ss[ss.ss_cdemo_sk.isin(cd.cd_demo_sk)
           & ss.ss_sold_date_sk.isin(d.d_date_sk)
           & ss.ss_promo_sk.isin(p.p_promo_sk)]
    m = m.merge(t["item"], left_on="ss_item_sk", right_on="i_item_sk")
    rows = []
    for item_id, g in m.groupby("i_item_id", sort=True):
        quantity = g.ss_quantity.dropna().to_numpy(dtype)
        agg1 = quantity.sum(dtype=dtype) / dtype(len(quantity)) \
            if len(quantity) else None
        rows.append((item_id, agg1,
                     decimal_average(g.ss_list_price, 2, dtype),
                     decimal_average(g.ss_coupon_amt, 2, dtype),
                     decimal_average(g.ss_sales_price, 2, dtype)))
        if len(rows) == 100:
            break
    out = pd.DataFrame(rows, columns=["i_item_id", "agg1", "agg2", "agg3",
                                      "agg4"]).astype({"agg2": object,
                                                       "agg3": object,
                                                       "agg4": object})
    if dtype == np.float64:
        out["agg1"] = pd.Series([Double(x) for x in out.agg1],
                                dtype=object)
    else:
        out["agg1"] = out.agg1.astype(np.float64)
    return out


ANSWERS = {"q7": q7}
