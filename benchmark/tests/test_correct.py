"""``correct`` has to come out false when it should: the float32
control, and a run with the timed path broken underneath.

Every test drives ``run.main`` itself with ``--allow-cpu`` (the look for
a chip is the only thing skipped), in this process, so that a fault can
be planted under it, at a scale a test run can hold, and reads the
result's last line like the driver does.
"""

import json

import numpy as np
import pytest

from benchmark import run
from benchmark.harness import spec
from benchmark.tools import control

SF = "0.01"


def drive(capsys, workload, seed=2**31 + 17, seconds="0.5", sf=SF):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", seconds, "--trace", "0", "--allow-cpu",
                   "--sf", sf])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def cell(workload):
    """A cell by name; q1's query, mix and reference are there for the PR
    that brings its cell, so it gets an entry here only."""
    bench = spec.load_benchmark()
    if workload not in {w["name"] for w in bench["workloads"]}:
        config, query = workload.split(".")
        bench["workloads"].append({"name": workload, "config": config,
                                   "traffic": query + "_loop", "chips": 1,
                                   "why": "not a cell yet"})
    return spec.Cell(workload, bench)


@pytest.mark.parametrize("workload", ["tpch_sf1.q6", "tpch_sf1.q3"])
def test_sound_run_is_correct(capsys, workload):
    line = drive(capsys, workload)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared"
    assert line["compared"]["rel_err_max"][0] <= 1e-12
    assert line["compared"]["rel_err_max"][1] == 1e-10
    assert set(line["metrics"]) >= {"query_s", "setup_s"}
    tail = {"tpch_sf1.q6": "query_p90_s", "tpch_sf1.q3": "query_max_s"}
    assert line["metrics"][tail[workload]]["value"] > 0
    assert len(line["metrics"]) == 3


@pytest.mark.parametrize("workload", ["tpch_sf1.q6", "tpch_sf1.q3",
                                      "tpch_sf1.q1"])
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_float32_control_is_not_correct(workload, seed):
    # one sum in float32 can land close by luck at 60,000 rows: the
    # control is read at 300,000 here and at the cell's own size on the
    # chip's machine (PERF.md)
    correct, numbers = control.control_run(cell(workload), seed, 0.05,
                                           np.float32)
    assert correct is False
    value, limit = numbers["rel_err_max"]
    assert value > 3 * limit


def test_fault_answer_altered(capsys, monkeypatch):
    from spark_rapids_tpu.api.dataframe import DataFrame
    to_pandas = DataFrame.to_pandas

    def altered(self, *a, **kw):
        frame = to_pandas(self, *a, **kw)
        if "revenue" in frame:
            frame = frame.copy()
            frame.loc[0, "revenue"] *= 1 + 1e-7
        return frame

    monkeypatch.setattr(DataFrame, "to_pandas", altered)
    line = drive(capsys, "tpch_sf1.q6")
    assert line["correct"] is False
    value, limit = line["compared"]["rel_err_max"]
    assert value > limit


def test_fault_exact_cell_altered(capsys, monkeypatch):
    from spark_rapids_tpu.api.dataframe import DataFrame
    to_pandas = DataFrame.to_pandas

    def altered(self, *a, **kw):
        frame = to_pandas(self, *a, **kw)
        if "l_orderkey" in frame:
            frame = frame.copy()
            frame.loc[3, "l_orderkey"] += 1
        return frame

    monkeypatch.setattr(DataFrame, "to_pandas", altered)
    line = drive(capsys, "tpch_sf1.q3")
    assert line["correct"] is False
    assert line["compared"]["exact_wrong"][0] >= 1


def test_fault_half_of_the_rows_left_out(capsys, monkeypatch):
    """The scan sees every second file only; the reference sees all."""
    import os
    from benchmark.harness import data
    write = data.write_parquet

    def half(tables, config, seed, sf):
        config = dict(config, storage=dict(config["storage"],
                                           files={"lineitem": 12}))
        dirs, wrote = write(tables, config, seed, sf)
        for f in sorted(os.listdir(dirs["lineitem"]))[1::2]:
            os.remove(os.path.join(dirs["lineitem"], f))
        return dirs, wrote

    monkeypatch.setattr(data, "write_parquet", half)
    line = drive(capsys, "tpch_sf1.q6", seed=991)
    assert line["correct"] is False
    assert line["compared"]["rel_err_max"][0] > 0.1


def test_fault_query_raises(capsys, monkeypatch):
    from spark_rapids_tpu.api.dataframe import DataFrame
    calls = []
    to_pandas = DataFrame.to_pandas

    def breaks_later(self, *a, **kw):
        calls.append(1)
        if len(calls) > 17:   # the mix's 15 warm-up queries went through
            raise RuntimeError("planted")
        return to_pandas(self, *a, **kw)

    monkeypatch.setattr(DataFrame, "to_pandas", breaks_later)
    line = drive(capsys, "tpch_sf1.q6")
    assert line["correct"] is False
    assert line["failed"] >= 1 and line["compared"]["missing"][0] >= 1


def test_fault_answer_off_the_timed_path(capsys, monkeypatch):
    """An answer the session's own records put on the CPU rung."""
    from spark_rapids_tpu.api.dataframe import DataFrame
    to_pandas = DataFrame.to_pandas

    def fell_back(self, *a, **kw):
        self.session.recovery_log.append({"action": "cpu", "fault": "x"})
        return to_pandas(self, *a, **kw)

    monkeypatch.setattr(DataFrame, "to_pandas", fell_back)
    line = drive(capsys, "tpch_sf1.q6")
    assert line["correct"] is False and line["failed"] >= 1
    assert line["compared"]["off_path"][0] >= 1


def test_mesh_answer_not_distributed_is_off_path():
    """On a mesh, an answer whose plan did not run distributed, or that
    the ladder demoted to one device, did not come from the timed path."""
    from types import SimpleNamespace as NS
    from benchmark.harness import window
    mesh = {"session": {"mesh_devices": 4}}
    one = {"session": {"mesh_devices": None}}
    ok = NS(recovery_log=[], last_dist_explain="distributed")
    assert window.off_path(ok, mesh, 0) is None
    assert window.off_path(NS(recovery_log=[], last_dist_explain=None),
                           one, 0) is None
    assert "not distributed" in window.off_path(
        NS(recovery_log=[], last_dist_explain="single-device"), mesh, 0)
    demoted = NS(recovery_log=[{"action": "demote"}],
                 last_dist_explain="distributed")
    assert window.off_path(demoted, mesh, 0) and \
        window.off_path(demoted, one, 0) is None
    assert window.off_path(demoted, mesh, 1) is None  # an older query's
