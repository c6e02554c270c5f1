"""The engine's spans over the device's idle gaps: the labelling rule by
hand, and a recorded ``.xplane.pb`` with engine spans in it (two traced
q6 queries on a TPU v5e, taken by this harness from the committed tree)
against the trace reduction's own idle share."""

import os
import shutil
import types

import numpy as np
import pytest

from benchmark.readers import engine_counter
from benchmark.readers import timeline as reader
from benchmark.trace import reduce as r
from benchmark.trace import timeline as t

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_q6_spans.xplane.pb")
OLD = os.path.join(HERE, "recorded_q6.xplane.pb")


def _thread(*spans):
    names, points, starts, ends = zip(*spans)
    return (list(names), list(points), np.array(starts, np.float64),
            np.array(ends, np.float64))


def _labels(mid, threads):
    label, names = t._label(np.array(mid, np.float64), threads)
    return [names[i] if i >= 0 else None for i in label]


def test_innermost_span_of_a_thread_wins():
    worker = _thread(("TpuFileScanExec", "operator.batch", 0, 100),
                     ("io.reader", "io.reader", 10, 90),
                     ("scan.decode", "scan.decode", 20, 40))
    assert _labels([5, 15, 30, 95, 150], [worker]) == [
        "TpuFileScanExec", "io.reader", "scan.decode", "TpuFileScanExec",
        None]


def test_a_waiting_span_is_named_only_where_no_thread_works():
    # the worker fetches (a wait) inside its operator; the driver is
    # parked in a short wait of its own the whole time
    worker = _thread(("pipeline.worker", "pipeline.worker", 0, 1000),
                     ("TpuHashAggregateExec", "operator.batch", 100, 400),
                     ("hostsync.fetch", "hostsync.fetch", 200, 300),
                     ("scan.decode", "scan.decode", 500, 600))
    driver = _thread(("admission.wait", "admission.wait", 240, 260),
                     ("scheduler.timeslice", "scheduler.timeslice",
                      540, 560))
    got = _labels([50, 150, 250, 550, 700], [worker, driver])
    assert got == [
        "pipeline.worker",        # nobody works: the only span there
        "TpuHashAggregateExec",   # work beats the enclosing wait
        "admission.wait",         # two waits: the shorter
        "scan.decode",            # work on one thread beats a shorter wait
        "pipeline.worker"]
    # the same whichever thread is read first
    assert _labels([50, 150, 250, 550, 700], [driver, worker]) == got


def test_a_long_gap_is_cut_into_pieces():
    p0, p1 = t._pieces(np.array([0.0, 10.0]), np.array([2.5, 10.5]), 1.0)
    assert list(p1 - p0) == pytest.approx([2.5 / 3] * 3 + [0.5])
    assert p0[0] == 0.0 and p1[2] == pytest.approx(2.5) and p0[3] == 10.0
    assert float((p1 - p0).sum()) == pytest.approx(3.0)
    assert (np.diff((p0 + p1) / 2) > 0).all()
    # one gap over two spans is shared between them, not given to one
    worker = _thread(("scan.decode", "scan.decode", 0, 1e6),
                     ("scan.convert", "scan.convert", 1e6, 4e6))
    p0, p1 = t._pieces(np.array([0.0]), np.array([4e6]))
    label, names = t._label((p0 + p1) / 2, [worker])
    by = {n: float((p1 - p0)[label == i].sum())
          for i, n in enumerate(names)}
    assert by == pytest.approx({"scan.decode": 1e6, "scan.convert": 3e6})


def test_span_names():
    ev = types.SimpleNamespace
    assert t._span_point(ev(name="io.reader", stats=[])) == "io.reader"
    assert t._span_point(ev(name="bench.query", stats=[])) is None
    assert t._span_point(ev(name="Join", stats=[("point", "stage.dist")])) \
        == "stage.dist"
    assert t._span_point(ev(name="Join", stats=[])) is None
    # a program before the point stat wrote the operator's class only
    assert t._span_point(ev(name="TpuSortExec", stats=[])) \
        == "operator.batch"
    assert t._span_point(ev(name="PjitFunction(f)", stats=[])) is None
    assert t._is_wait("hostsync.fetch") and t._is_wait("pipeline.worker")
    assert not t._is_wait("operator.batch") and not t._is_wait("io.reader")


@pytest.mark.parametrize("path", [RECORDED, OLD])
def test_labelled_shares_sum_to_the_idle_share(path):
    table, red = t.label_trace(path), r.reduce_trace(path)
    assert table["fullest_device"] == red["fullest_device"]
    assert table["window_s"] == pytest.approx(red["window_s"])
    shares = [table[k] for k in ("idle_scan_share", "idle_sync_share",
                                 "idle_unspanned_share",
                                 "idle_other_share")]
    assert sum(shares) == pytest.approx(red["idle_share"], abs=1e-6)
    assert sum(table["idle_s"].values()) == pytest.approx(
        red["window_s"] - red["busy_s_fullest"], rel=1e-9)
    assert table["launches"] > 0


def test_recorded_spans_are_read():
    table = t.label_trace(RECORDED)
    # q6 is its scan: the gaps lie under the scan's spans, by name
    assert table["idle_scan_share"] > 50.0
    assert "scan.decode" in table["idle_s"]
    assert table["idle_unspanned_share"] < 5.0
    # the trace before the bridge names operators only: nothing is scan
    old = t.label_trace(OLD)
    assert old["idle_scan_share"] == 0.0
    assert old["idle_other_share"] > 90.0


def test_reader_reads_the_run_s_trace_once(tmp_path, monkeypatch):
    cell = types.SimpleNamespace(name="tpch_sf1.q6")
    d = tmp_path / "trace" / cell.name / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(RECORDED, d / "host.xplane.pb")
    monkeypatch.setattr(reader, "cache_dir",
                        lambda *p: str(tmp_path.joinpath(*p)))
    calls = []
    label = t.label_trace
    monkeypatch.setattr(t, "label_trace",
                        lambda p: calls.append(p) or label(p))
    obs = types.SimpleNamespace(cell=cell, trace={"idle_share": 1.0},
                                traced=[1, 2])
    want = label(RECORDED)
    assert reader.read({"field": "launches", "per": "traced_query"},
                       obs, None) == want["launches"] / 2
    assert reader.read({"field": "idle_scan_share"}, obs, None) \
        == want["idle_scan_share"]
    assert len(calls) == 1
    # a rehearsal has no device trace
    obs.trace = None
    assert reader.read({"field": "launches"}, obs, None) is None


def test_engine_counter_reads_nothing_from_a_program_without_it():
    obs = types.SimpleNamespace(session=None, n_queries=2)
    gone = {"object": "spark_rapids_tpu.utils.hostsync:no_such_counter",
            "method": "snapshot", "key": "bytes", "per": "query"}
    assert engine_counter.begin(gone, obs) is None
    assert engine_counter.read(gone, obs, None) is None
    gone["object"] = "spark_rapids_tpu.no_such_module:x"
    assert engine_counter.begin(gone, obs) is None
    have = {"object": "spark_rapids_tpu.utils.hostsync:upload_metrics",
            "method": "snapshot", "key": "bytes", "per": "query"}
    from spark_rapids_tpu.utils import hostsync
    begun = engine_counter.begin(have, obs)
    hostsync.upload_metrics.note(64, 1)
    assert engine_counter.read(have, obs, begun) == 32.0
