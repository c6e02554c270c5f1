"""The TPC-DS generator against the specification's shapes, the cell's
files, and the control of ``correct`` for exact decimals."""

import decimal

import numpy as np
import pyarrow as pa
import pytest

from benchmark.datagen import tpcds
from benchmark.harness import compare, spec
from benchmark.reference import tpcds as reference
from benchmark.tools import control

SF, SEED = 0.02, 2**31 + 5
FACT_KEYS = tpcds.COLUMNS["store_sales"][:9]


@pytest.fixture(scope="module")
def tables():
    return tpcds.gen_tables(list(tpcds.COLUMNS), SF, SEED)


@pytest.fixture(scope="module")
def ss(tables):
    return tables["store_sales"].to_pandas()


def test_cardinalities_by_formula():
    assert tpcds.rows("store_sales", 1.0) == 2_880_404
    assert tpcds.rows("item", 1.0) == 18_000 and tpcds.item_ids(1.0) == 9_000
    assert tpcds.rows("promotion", 1.0) == 300
    assert tpcds.rows("date_dim", 1.0) == tpcds.rows("date_dim", SF) == 73_049
    assert tpcds.rows("customer_demographics", SF) == 1_920_800 \
        == 2 * 5 * 7 * 20 * 4 * 7 * 7 * 7
    config = spec.load_json("configs", "tpcds_sf1.json")
    for table in tpcds.COLUMNS:
        assert config["scale"][table + "_rows"] == tpcds.rows(table, 1.0)
        assert config["storage"]["columns"][table] == len(
            tpcds.COLUMNS[table])
    assert config["storage"]["files"] == {"store_sales": 2}


def test_columns_and_parquet_types(tables):
    counts = {"store_sales": 23, "date_dim": 28, "item": 22,
              "customer_demographics": 9, "promotion": 19}
    for name, table in tables.items():
        assert table.column_names == tpcds.COLUMNS[name]
        assert table.num_columns == counts[name]
        assert table.num_rows == tpcds.rows(name, SF)
    schema = tables["store_sales"].schema
    for name in FACT_KEYS + ["ss_quantity"]:
        assert schema.field(name).type == pa.int32()
    assert schema.field("ss_ticket_number").type == pa.int64()
    assert len(tpcds.MONEY) == 12
    for name in tpcds.MONEY:
        assert schema.field(name).type == pa.decimal128(7, 2)
    assert tables["date_dim"].schema.field("d_date").type == pa.date32()
    assert tables["item"].schema.field("i_rec_start_date").type == pa.date32()
    assert tables["item"].schema.field("i_current_price").type \
        == pa.decimal128(7, 2)
    assert tables["promotion"].schema.field("p_cost").type \
        == pa.decimal128(15, 2)
    for name in ("d_date_sk", "i_item_sk", "cd_demo_sk", "p_promo_sk"):
        table = next(t for t in tables.values() if name in t.column_names)
        assert table.schema.field(name).type == pa.int32()
        assert table.column(name).null_count == 0
    assert tables["item"].schema.field("i_item_id").type == pa.string()


def test_null_share_of_every_nullable_fact_column(tables):
    table = tables["store_sales"]
    for name in table.column_names:
        share = table.column(name).null_count / table.num_rows
        if name in ("ss_item_sk", "ss_ticket_number"):
            assert share == 0
        else:
            assert 0.04 <= share <= 0.05, (name, share)
    # each on its own: two columns' NULLs coincide as often as chance says
    both = (table.column("ss_quantity").is_null().to_numpy(False)
            & table.column("ss_list_price").is_null().to_numpy(False))
    assert both.mean() < 0.004


def test_tickets_of_8_to_16_lines_in_date_order(ss):
    per = ss.groupby("ss_ticket_number").size()
    assert per.iloc[:-1].min() == 8 and per.max() == 16   # the last is cut
    assert abs(per.iloc[:-1].mean() - 12) < 0.15
    assert ss.ss_ticket_number.is_monotonic_increasing
    for name in ("ss_sold_date_sk", "ss_sold_time_sk", "ss_customer_sk",
                 "ss_cdemo_sk", "ss_hdemo_sk", "ss_addr_sk", "ss_store_sk"):
        assert ss.groupby("ss_ticket_number")[name].nunique().max() == 1
    # no item twice on a ticket, dates never going back
    assert not ss.duplicated(["ss_ticket_number", "ss_item_sk"]).any()
    assert ss.ss_sold_date_sk.dropna().is_monotonic_increasing


def test_sales_calendar_and_item_revisions(tables, ss):
    d = tables["date_dim"].to_pandas().set_index("d_date_sk")
    sold = d.loc[ss.ss_sold_date_sk.dropna().astype(int)]
    assert sold.d_year.min() == 1998 and sold.d_year.max() == 2002
    by_month = sold.groupby("d_moy").size() / len(sold)
    assert by_month[12] > 2.2 * by_month[3] and by_month[9] > 1.5 * by_month[3]
    assert 0.18 < (sold.d_year == 2000).mean() < 0.22
    assert str(d.d_date.iloc[0])[:10] == "1900-01-02"
    assert str(d.d_date.iloc[-1])[:10] == "2100-01-01"
    assert d.index[0] == 2415022 and d.loc[2451545].d_year == 2000
    item = tables["item"].to_pandas()
    assert item.i_item_id.nunique() == len(item) // 2
    assert item.i_item_id.iloc[:7].tolist() == [
        "AAAAAAAABAAAAAAA", "AAAAAAAACAAAAAAA", "AAAAAAAACAAAAAAA",
        "AAAAAAAAEAAAAAAA", "AAAAAAAAEAAAAAAA", "AAAAAAAAEAAAAAAA",
        "AAAAAAAAHAAAAAAA"]
    assert sorted(item.groupby("i_item_id").size().unique()) == [1, 2, 3]
    # a line's item is the revision whose period holds the sale's date
    j = ss.dropna(subset=["ss_sold_date_sk"]).merge(
        item, left_on="ss_item_sk", right_on="i_item_sk")
    day = d.d_date.loc[j.ss_sold_date_sk.astype(int)].to_numpy()
    known = j.i_rec_start_date.notna().to_numpy()
    assert (j.i_rec_start_date.to_numpy()[known].astype("datetime64[D]")
            <= day[known].astype("datetime64[D]")).all()
    ends = j.i_rec_end_date.notna().to_numpy()
    assert (day[ends].astype("datetime64[D]")
            <= j.i_rec_end_date.to_numpy()[ends].astype("datetime64[D]")).all()


def test_demographics_is_the_full_cross_product(tables):
    cd = tables["customer_demographics"].to_pandas()
    assert cd.cd_demo_sk.tolist()[:3] == [1, 2, 3]
    assert len(cd.drop(columns="cd_demo_sk").drop_duplicates()) == 1_920_800
    assert cd.cd_gender.iloc[:4].tolist() == ["M", "F", "M", "F"]
    q7 = (cd.cd_gender == "M") & (cd.cd_marital_status == "S") \
        & (cd.cd_education_status == "College")
    assert q7.sum() == 1_920_800 // 70 == 27_440
    p = tables["promotion"].to_pandas()
    assert set(p.p_channel_email.dropna()) == {"N"}
    assert set(p.p_channel_dmail.dropna()) == {"N", "Y"}


def test_money_follows_the_pricing_rules(ss):
    cents = {name: ss[name].map(
        lambda v: None if v is None else int(v.scaleb(2))).astype("float64")
        for name in tpcds.MONEY}
    q = ss.ss_quantity.astype("float64")
    ok = lambda *cols: np.logical_and.reduce([c.notna() for c in cols])
    m = ok(q, cents["ss_sales_price"], cents["ss_ext_sales_price"])
    assert (cents["ss_ext_sales_price"][m]
            == cents["ss_sales_price"][m] * q[m]).all()
    m = ok(cents["ss_net_paid"], cents["ss_ext_sales_price"],
           cents["ss_coupon_amt"])
    assert (cents["ss_net_paid"][m] == cents["ss_ext_sales_price"][m]
            - cents["ss_coupon_amt"][m]).all()
    assert (cents["ss_sales_price"].dropna()
            <= cents["ss_list_price"].max()).all()
    assert 0.75 < (cents["ss_coupon_amt"].dropna() == 0).mean() < 0.85
    assert cents["ss_net_profit"].min() < 0 < cents["ss_net_profit"].max()
    assert max(c.abs().max() for c in cents.values()) < 10**7
    assert q.min() == 1 and q.max() == 100


def test_seed_is_the_data_and_streams_are_per_table():
    a = tpcds.gen_table("promotion", SF, 7)
    assert a.equals(tpcds.gen_tables(["item", "promotion"], SF, 7)["promotion"])
    assert not a.equals(tpcds.gen_table("promotion", SF, 8))
    small = tpcds.gen_table("store_sales", 0.001, 2**31 + 9)
    assert small.equals(tpcds.gen_table("store_sales", 0.001, 2**31 + 9))


def cell():
    return spec.Cell("tpcds_sf1.q7")


def test_cell_resolves():
    c = cell()
    assert c.suite == "tpcds" and c.chips == 1 and list(c.queries) == ["q7"]
    assert c.tables == ["store_sales", "customer_demographics", "date_dim",
                        "item", "promotion"]
    assert [m["name"] for m in c.end_to_end] == ["query_s", "setup_s"]
    new = {"scan.decimal_ms", "scan.validity_bytes", "join.probe_rows",
           "join.output_rows"}
    assert new <= {m["name"] for m in c.per_layer}
    for m in spec.load_benchmark()["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == ["tpcds_sf1.q7"]
            spec.metric_reader(m["name"])
    assert c.mix["warmup_queries"] == 2 and c.mix["traced_queries"] == 2
    q1 = spec.Cell("tpch_sf1.q1")
    assert q1.mix["name"] == "q1_loop" and q1.chips == 1
    assert reference.REL_ERR_MAX == compare.LIMITS["rel_err_max"]


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_float32_control_is_not_correct(seed):
    """The reference's averages computed in float32 and quantised: the
    exact comparison of the decimal columns has to refuse them."""
    correct, numbers = control.control_run(cell(), seed, 0.05, np.float32)
    assert correct is False
    assert numbers["exact_wrong"][0] > 0 and numbers["exact_wrong"][1] == 0
    assert numbers["shape_wrong"][0] == 0


def test_reference_against_itself_is_correct_and_holds_nulls():
    c = cell()
    correct, numbers = control.control_run(c, 2**31 + 3, 0.05, np.float64)
    assert correct is True and numbers["exact_wrong"][0] == 0
    tables = c.datagen().gen_tables(c.tables, 0.05, 2**31 + 3)
    want = c.reference().ANSWERS["q7"](
        compare.reference_frames(tables, c.queries))
    assert len(want) == 100 and want.i_item_id.is_monotonic_increasing
    assert all(a is None or isinstance(a, decimal.Decimal)
               for col in ("agg2", "agg3", "agg4") for a in want[col])
    # a program's answer: doubles with NaN for NULL, decimals with None
    got = want.copy()
    got["agg1"] = [np.nan if a.value is None else a.value * (1 + 1e-13)
                   for a in want.agg1]
    got["agg1"] = got.agg1.astype(np.float64)
    assert compare.compare_answer(got, want) == (0.0, 0, 0)
    # ... and every way of being wrong in agg1 counts
    for bad in (lambda x: x * (1 + 1e-9), lambda x: np.nan):
        wrong = got.copy()
        k = int(np.flatnonzero(wrong.agg1.notna())[0])
        wrong.loc[k, "agg1"] = bad(wrong.agg1[k])
        assert compare.compare_answer(wrong, want)[1] == 1
    if got.agg1.isna().any():
        wrong = got.copy()
        wrong.loc[int(np.flatnonzero(wrong.agg1.isna())[0]), "agg1"] = 1.0
        assert compare.compare_answer(wrong, want)[1] == 1


def test_decimal_average_rounds_half_up_exactly():
    d = decimal.Decimal
    avg = reference.decimal_average
    assert avg([d("0.01"), d("0.02")], 2) == d("0.015000")
    assert avg([d("0.01")] * 31 + [d("0.00")], 2) == d("0.009688")  # ...6875
    assert avg([d("-0.01")] * 31 + [d("0.00")], 2) == d("-0.009688")
    assert avg([d("1.00"), None, d("2.00"), d("2.00")], 2) == d("1.666667")
    assert avg([None, None], 2) is None
    assert avg([d("99999.99")] * 3, 2) == d("99999.990000")
