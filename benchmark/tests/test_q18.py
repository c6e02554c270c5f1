"""The cell ``tpch_sf1.q18``: a rehearsal of it is ``correct``, its
float32 control is not, an empty answer is refused, and its suite's
generator is ``tpch``'s."""

import json

import numpy as np
import pytest

from benchmark import run
from benchmark.datagen import tpch, tpch_q18
from benchmark.harness import compare, spec
from benchmark.reference import tpch_q18 as reference
from benchmark.tools import control

CELL = "tpch_sf1.q18"
# one order of 30,000 passes QUANTITY 300 at this scale and seed; at the
# cell's own scale about 57 of 1,500,000 do
SF, SEED = 0.02, 7


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


def test_traced_rehearsal_reads_the_group_bys_rungs(capsys):
    """The four ``agg.*`` metrics of the cell, from the program's counter
    and span; every batch and every merge of the two group-bys."""
    metrics = drive(capsys, SEED, trace="1")["metrics"]
    batches = metrics["agg.coded_batches"]["value"] + \
        metrics["agg.sort_batches"]["value"]
    assert batches >= 2
    assert metrics["agg.coded_slots"]["value"] >= 4096
    assert metrics["agg.merge_ms"]["value"] > 0
    assert metrics["agg.merge_ms"]["unit"] == "ms/query"
    assert "device.idle_share" not in metrics   # a rehearsal: no device


def test_an_empty_answer_is_shape_wrong(capsys):
    """No order passes QUANTITY 300 at this scale and seed: the
    reference's answer is empty, which the comparison refuses."""
    line = drive(capsys, 2**31 + 17, sf=0.002)
    assert line["correct"] is False
    assert line["compared"]["shape_wrong"][0] >= 1


@pytest.mark.parametrize("seed", [SEED, 11, 2**31 + 3])
def test_float32_control_is_not_correct(seed):
    """``o_totalprice`` is the only float that can differ (``sum_qty``
    sums at most seven whole numbers), so the control is read where the
    answer has rows enough: SF 0.2 here, the cell's own size on the
    chip's machine (PERF.md)."""
    cell = spec.Cell(CELL)
    correct, numbers = control.control_run(cell, seed, 0.2, np.float32)
    assert correct is False
    assert numbers["shape_wrong"][0] == 0
    value, limit = numbers["rel_err_max"]
    assert value > 3 * limit


def test_the_suite_hands_tpchs_tables():
    assert tpch_q18.COLUMNS is tpch.COLUMNS
    assert tpch_q18.gen_tables is tpch.gen_tables
    names = ["customer", "orders", "lineitem"]
    ours = tpch_q18.gen_tables(names, 0.01, 2**31 + 5)
    theirs = tpch.gen_tables(names, 0.01, 2**31 + 5)
    for name in names:
        assert ours[name].equals(theirs[name])
    a, b = (spec.load_json("configs", n + ".json")
            for n in ("tpch_sf1_q18", "tpch_sf1"))
    for key in ("storage", "guarantees", "assumed", "session", "deployment",
                "chips", "reduced"):
        assert a[key] == b[key], key
    assert a["scale"]["sf"] == b["scale"]["sf"] == 1.0
    assert (a["suite"], a["architecture"]) == ("tpch_q18", None)


def test_reference_takes_quantity_and_orders_rows():
    cell = spec.Cell(CELL)
    tables = tpch_q18.gen_tables(cell.tables, SF, SEED)
    frames = compare.reference_frames(tables, cell.queries)
    want = reference.q18(frames, quantity=250)
    assert list(want.columns) == ["c_name", "c_custkey", "o_orderkey",
                                  "o_orderdate", "o_totalprice", "sum_qty"]
    assert 1 <= len(reference.q18(frames)) < len(want) <= 100
    assert (want.sum_qty > 250).all()
    assert want.o_totalprice.is_monotonic_decreasing
    lines = frames["lineitem"]
    for key, qty in zip(want.o_orderkey[:5], want.sum_qty[:5]):
        assert lines.l_quantity[lines.l_orderkey == key].sum() == qty
