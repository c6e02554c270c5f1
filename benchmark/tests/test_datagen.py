"""The generator against clause 4.2.3 of the specification: the shapes
the cells' queries meet (fan-out, row order, group skew, record widths)."""

import numpy as np
import pytest

from benchmark.datagen import tpch

SF, SEED = 0.02, 2**31 + 5


@pytest.fixture(scope="module")
def t():
    tables = tpch.gen_tables(list(tpch.COLUMNS), SF, SEED)
    return {n: x.to_pandas(date_as_object=False) for n, x in tables.items()}


def test_columns_and_cardinalities(t):
    for name, frame in t.items():
        assert list(frame.columns) == tpch.COLUMNS[name]
    assert len(t["orders"]) == 1_500_000 * SF
    assert len(t["customer"]) == 150_000 * SF
    assert len(t["partsupp"]) == 4 * len(t["part"]) == 800_000 * SF
    assert len(t["nation"]) == 25 and len(t["region"]) == 5
    assert len(t["lineitem"].columns) == 16


def test_one_to_seven_lines_an_order_in_key_order(t):
    l, o = t["lineitem"], t["orders"]
    per_order = l.groupby("l_orderkey").size()
    assert per_order.min() == 1 and per_order.max() == 7
    assert abs(per_order.mean() - 4) < 0.05
    assert len(per_order) == len(o)            # no order without lines
    assert l.l_orderkey.is_monotonic_increasing
    first = l.l_orderkey != l.l_orderkey.shift()
    expect = l.groupby("l_orderkey").cumcount() + 1
    assert (l.l_linenumber == expect).all() and (l.l_linenumber[first] == 1).all()
    # sparse keys: the first 8 of every 32
    assert ((o.o_orderkey - 1) % 32 < 8).all() and o.o_orderkey.is_unique
    assert o.o_orderkey.is_monotonic_increasing
    assert (o.o_custkey % 3 != 0).all()


def test_dates_and_flags(t):
    l = t["lineitem"].merge(t["orders"], left_on="l_orderkey",
                            right_on="o_orderkey")
    day = np.timedelta64(1, "D")
    ship = (l.l_shipdate - l.o_orderdate) / day
    assert ship.min() == 1 and ship.max() == 121
    commit = (l.l_commitdate - l.o_orderdate) / day
    assert commit.min() == 30 and commit.max() == 90
    receipt = (l.l_receiptdate - l.l_shipdate) / day
    assert receipt.min() == 1 and receipt.max() == 30
    assert str(l.o_orderdate.min().date()) >= "1992-01-01"
    assert str(l.o_orderdate.max().date()) <= "1998-08-02"
    now = np.datetime64("1995-06-17")
    assert ((l.l_linestatus == "O") == (l.l_shipdate > now)).all()
    assert ((l.l_returnflag == "N") == (l.l_receiptdate > now)).all()
    groups = set(zip(l.l_returnflag, l.l_linestatus))
    assert groups == {("A", "F"), ("R", "F"), ("N", "F"), ("N", "O")}
    n_open = (l.l_linestatus == "O").groupby(l.o_orderkey).agg(["sum", "size"])
    want = np.where(n_open["sum"] == 0, "F",
                    np.where(n_open["sum"] == n_open["size"], "O", "P"))
    status = t["orders"].set_index("o_orderkey").o_orderstatus
    assert (status.loc[n_open.index] == want).all()


def test_prices_follow_the_part(t):
    l, p = t["lineitem"], t["part"].set_index("p_partkey")
    k = p.index.to_numpy()
    cents = 90000 + (k // 10) % 20001 + 100 * (k % 1000)
    assert np.allclose(p.p_retailprice, cents / 100)
    want = l.l_quantity * p.p_retailprice.loc[l.l_partkey].to_numpy()
    assert np.allclose(l.l_extendedprice, want, rtol=0, atol=1e-6)
    charge = l.l_extendedprice * (1 + l.l_tax) * (1 - l.l_discount)
    total = charge.groupby(l.l_orderkey).sum()
    o = t["orders"].set_index("o_orderkey")
    assert np.allclose(o.o_totalprice.loc[total.index], total, atol=0.006)
    assert l.l_quantity.between(1, 50).all()
    assert l.l_discount.between(0, 0.10).all() and l.l_tax.between(0, 0.08).all()


def test_a_line_s_supplier_supplies_its_part(t):
    pairs = set(zip(t["partsupp"].ps_partkey, t["partsupp"].ps_suppkey))
    assert len(pairs) == len(t["partsupp"])
    l = t["lineitem"].head(20000)
    assert all(pair in pairs for pair in zip(l.l_partkey, l.l_suppkey))


@pytest.mark.parametrize("table,column,lo,hi", [
    ("lineitem", "l_comment", 10, 43), ("orders", "o_comment", 19, 78),
    ("customer", "c_comment", 29, 116), ("partsupp", "ps_comment", 49, 198),
    ("part", "p_comment", 5, 22), ("customer", "c_address", 10, 40),
    ("supplier", "s_address", 10, 40)])
def test_record_widths(t, table, column, lo, hi):
    n = t[table][column].str.len()
    assert n.min() >= lo and n.max() <= hi
    assert abs(n.mean() - (lo + hi) / 2) < 0.05 * (lo + hi) / 2 + 2


def test_the_seed_gives_the_rows_whatever_else_is_generated():
    both = tpch.gen_tables(["orders", "lineitem"], 0.005, 7)
    alone = tpch.gen_tables(["orders"], 0.005, 7)["orders"]
    assert alone.equals(both["orders"])
    assert tpch.gen_table("lineitem", 0.005, 7).equals(both["lineitem"])
    other = tpch.gen_table("lineitem", 0.005, 8)
    assert not other.equals(both["lineitem"])
    names = tpch.gen_table("part", 0.005, 7).to_pandas().p_name
    assert (names.str.split().map(lambda w: len(set(w))) == 5).all()
