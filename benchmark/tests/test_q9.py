"""The cell ``tpch_sf1.q9``: a rehearsal of it is ``correct`` and reads
its six metrics, its float32 control is not, an answer without the LIKE
is refused, and its suite's generator is ``tpch``'s."""

import json

import numpy as np
import pytest

from benchmark import run
from benchmark.datagen import tpch, tpch_q9
from benchmark.harness import compare, spec, window
from benchmark.reference import tpch_q9 as reference
from benchmark.tools import control

CELL = "tpch_sf1.q9"
SF, SEED = 0.02, 7
TABLES = ["part", "supplier", "lineitem", "partsupp", "orders", "nation"]


def drive(capsys, seed, trace="0", sf=SF):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.5", "--trace", trace, "--allow-cpu", "--sf", str(sf)])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def test_rehearsal_is_correct(capsys):
    line = drive(capsys, SEED)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"
    assert sorted(line["metrics"]) == ["query_s", "setup_s"]
    numbers = line["compared"]
    assert numbers["rel_err_max"][0] <= 1e-12
    assert [numbers[k][0] for k in ("exact_wrong", "shape_wrong", "missing",
                                    "off_path")] == [0, 0, 0, 0]


def test_traced_rehearsal_reads_the_join_order_the_builds_and_the_like(
        capsys):
    """The six metrics that list the cell, from the program's counters:
    no cross join and one relation out of its written place; the five
    build sides; the one filter batch, whose LIKE the device runs (the
    scan pushes no LIKE down) and whose kept rows are scattered."""
    metrics = drive(capsys, 2**31 + 29, trace="1")["metrics"]
    value = {k: v["value"] for k, v in metrics.items()}
    assert value["frontend.cross_joins"] == 0
    assert value["frontend.joins_reordered"] == 1
    parts = tpch.rows("part", SF)
    assert value["filter.rows_in"] == parts
    assert 0.03 * parts < value["filter.rows_out"] < 0.08 * parts
    assert (value["filter.batches"], value["filter.whole_batches"]) == (1, 0)
    smaller = sum(tpch.rows(t, SF) for t in
                  ("supplier", "partsupp", "orders", "nation"))
    lines = value["join.build_rows"] - smaller
    assert 3.5 * tpch.rows("orders", SF) < lines < 4.5 * tpch.rows(
        "orders", SF)
    assert value["join.build_rows"] < value["join.build_capacity"] \
        < 2 * value["join.build_rows"]
    assert metrics["join.build_rows"]["unit"] == "rows/query"
    assert "device.idle_share" not in metrics   # a rehearsal: no device


@pytest.mark.parametrize("seed", [SEED, 11, 2**31 + 3])
def test_float32_control_is_not_correct(seed):
    """``sum_profit`` sums a few hundred products a group at this scale
    (about 1,850 at the cell's): float32 misses by 1e-7 and more."""
    cell = spec.Cell(CELL)
    correct, numbers = control.control_run(cell, seed, 0.05, np.float32)
    assert correct is False
    assert (numbers["shape_wrong"][0], numbers["exact_wrong"][0]) == (0, 0)
    value, limit = numbers["rel_err_max"]
    assert value > 3 * limit


def test_an_answer_without_the_like_is_not_correct():
    """The predicate is held: a program that kept every part would
    hand sums about eighteen times too large."""
    cell = spec.Cell(CELL)
    tables = tpch_q9.gen_tables(cell.tables, SF, SEED)
    frames = compare.reference_frames(tables, cell.queries)
    want = reference.q9(frames)
    done = window.Done("q9")
    done.answer = reference.q9(frames, color="")
    correct, numbers = compare.judge([done], {"q9": want})
    assert correct is False
    assert numbers["shape_wrong"][0] == 0
    assert numbers["rel_err_max"][0] > 5
    other = reference.q9(frames, color="blue")
    assert compare.compare_answer(other, want)[0] > 1e-3


def test_the_suite_hands_tpchs_tables():
    assert tpch_q9.COLUMNS is tpch.COLUMNS
    assert tpch_q9.gen_tables is tpch.gen_tables
    ours = tpch_q9.gen_tables(TABLES, 0.01, 2**31 + 5)
    theirs = tpch.gen_tables(TABLES, 0.01, 2**31 + 5)
    for name in TABLES:
        assert ours[name].equals(theirs[name])
    cell = spec.Cell(CELL)
    assert cell.tables == TABLES
    a, b = (spec.load_json("configs", n + ".json")
            for n in ("tpch_sf1_q9", "tpch_sf1"))
    for key in ("storage", "guarantees", "assumed", "session", "deployment",
                "chips", "reduced"):
        assert a[key] == b[key], key
    assert a["scale"]["sf"] == b["scale"]["sf"] == 1.0
    assert (a["suite"], a["architecture"]) == ("tpch_q9", None)
    assert a["session"] == {"mesh_devices": None, "conf": {}}


def test_reference_takes_the_colour_and_orders_rows():
    cell = spec.Cell(CELL)
    tables = tpch_q9.gen_tables(cell.tables, SF, SEED)
    frames = compare.reference_frames(tables, cell.queries)
    want = reference.q9(frames)
    assert list(want.columns) == ["nation", "o_year", "sum_profit"]
    assert len(want) == 175
    assert want.nation.is_monotonic_increasing
    first = want[want.nation == want.nation[0]]
    assert first.o_year.tolist() == list(range(1998, 1991, -1))
    # one group by hand: the lines of green parts whose supplier is of
    # the first nation, ordered in 1995
    part, line = frames["part"], frames["lineitem"]
    green = part.p_partkey[part.p_name.str.contains("green")]
    nation = frames["nation"]
    key = nation.n_nationkey[nation.n_name == want.nation[0]].iloc[0]
    supp = frames["supplier"]
    of_nation = supp.s_suppkey[supp.s_nationkey == key]
    orders = frames["orders"]
    in_1995 = orders.o_orderkey[orders.o_orderdate.dt.year == 1995]
    rows = line[line.l_partkey.isin(green) & line.l_suppkey.isin(of_nation)
                & line.l_orderkey.isin(in_1995)]
    cost = frames["partsupp"].set_index(["ps_partkey", "ps_suppkey"]) \
        .ps_supplycost
    profit = sum(r.l_extendedprice * (1 - r.l_discount)
                 - cost[(r.l_partkey, r.l_suppkey)] * r.l_quantity
                 for r in rows.itertuples())
    got = first.sum_profit[first.o_year == 1995].iloc[0]
    assert abs(got - profit) <= 1e-9 * abs(profit)
