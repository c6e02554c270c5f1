"""Span tracing runtime (utils/tracing.py, ISSUE 12): attribution,
nesting, Chrome export, the unattributed-time health check, overhead,
and the persisted per-site observation store."""

import glob
import json
import os
import threading
import time

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.utils import tracing
from spark_rapids_tpu.tools.traceview import (load_trace, summarize,
                                              validate_chrome_trace,
                                              write_trace)


def _mkrec(point, t0, dur, excl=None, site=None, op=None, owner=0,
           tid=1, is_async=False):
    return (point, site, op, t0, dur,
            dur if excl is None else excl, owner, tid, is_async)


@pytest.fixture
def traced_session(tmp_path):
    # fresh jit entries: the jit.trace span only fires on a COLD first
    # dispatch (_Entry._cold), and another suite may have warmed this
    # test's exact signature earlier in the process
    from spark_rapids_tpu.ops import jit_cache
    jit_cache.clear()
    s = TpuSession({
        "spark.rapids.tpu.trace.dir": str(tmp_path / "traces"),
        "spark.rapids.tpu.eventLog.dir": str(tmp_path / "events"),
    })
    yield s
    s.stop()
    tracing.configure(enabled=False)


def _small_df(session, rng, n=4000):
    pdf = pd.DataFrame({"k": rng.integers(0, 50, n),
                        "v": rng.normal(size=n)})
    return session.create_dataframe(pdf)


# ------------------------------------------------------------- unit layer --

def test_rollup_exclusive_and_unattributed():
    # parent 100ms containing a 60ms child: exclusive 40 + 60, wall
    # 200 -> 100ms unattributed = 50% (the blind-spot metric)
    recs = [_mkrec("operator.batch", 0, 100e6, excl=40e6, op="A"),
            _mkrec("jit.trace", 10e6, 60e6, op=None)]
    roll = tracing.rollup(recs, wall_ms=200.0)
    assert roll["exclusiveMs"] == pytest.approx(100.0)
    assert roll["unattributedMs"] == pytest.approx(100.0)
    assert roll["unattributedFrac"] == pytest.approx(0.5)
    assert roll["phases"]["compile"] == pytest.approx(60.0)
    assert roll["phases"]["compute"] == pytest.approx(40.0)
    assert roll["operators"]["A"]["exclusiveMs"] == pytest.approx(40.0)


def test_rollup_async_spans_excluded_from_attribution():
    recs = [_mkrec("operator.batch", 0, 50e6, op="A"),
            _mkrec("exchange.async.inflight", 0, 80e6, is_async=True)]
    roll = tracing.rollup(recs, wall_ms=100.0)
    # the in-flight window reports as overlap, never as attribution —
    # device-side overlap credit must not hide host blind spots
    assert roll["overlapMs"] == pytest.approx(80.0)
    assert roll["exclusiveMs"] == pytest.approx(50.0)
    assert roll["unattributedMs"] == pytest.approx(50.0)


def test_span_nesting_exclusive_time_live():
    tracing.configure(enabled=True)
    try:
        with tracing.span("operator.batch", op="outer"):
            time.sleep(0.02)
            with tracing.span("jit.trace"):
                time.sleep(0.03)
        from spark_rapids_tpu.serving import context as qc
        recs, _ = tracing._drain(qc.effective_ident())
    finally:
        tracing.configure(enabled=False)
    by_point = {r[tracing.R_POINT]: r for r in recs}
    outer = by_point["operator.batch"]
    inner = by_point["jit.trace"]
    assert inner[tracing.R_DUR] >= 25e6
    # outer's exclusive excludes the nested compile
    assert outer[tracing.R_EXCL] <= \
        outer[tracing.R_DUR] - inner[tracing.R_DUR] + 5e6


def test_chrome_export_schema_and_truncation(tmp_path):
    recs = [_mkrec("operator.batch", i * 1e6, 1e6, op=f"Op{i % 3}")
            for i in range(100)]
    path = str(tmp_path / "t.json")
    write_trace(recs, path, qid=7, max_events=64, dropped=3,
                wall_ms=123.0)
    obj = load_trace(path)
    assert validate_chrome_trace(obj) == []
    # truncation contract: bounded export announces itself both ways
    assert obj["truncated"] == 100 - 64 + 3
    markers = [e for e in obj["traceEvents"]
               if e.get("name") == "trace-truncated"]
    assert len(markers) == 1
    assert markers[0]["args"]["dropped"] == obj["truncated"]
    x = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert len(x) == 64
    assert "Op0" in summarize(obj)
    # the validator really validates: break an event
    obj["traceEvents"][0]["ph"] = "??"
    assert validate_chrome_trace(obj)
    assert validate_chrome_trace({"traceEvents": "nope"})


def test_unattributed_health_check_fires_on_synthetic_gap():
    from spark_rapids_tpu.tools.eventlog import AppInfo, QueryInfo
    from spark_rapids_tpu.tools.profiling import health_check
    # a query whose taxonomy covered 10 of 100ms: the blind-spot line
    # the ISSUE contract pins at >20%
    gap = tracing.rollup([_mkrec("operator.batch", 0, 10e6, op="A")],
                         wall_ms=100.0)
    q = QueryInfo(1, status="success", duration_ms=100.0)
    q.spans = gap
    app = AppInfo(session_id="s", path="p", queries=[q])
    problems = health_check([app])
    assert any("UNATTRIBUTED" in p for p in problems), problems
    # and a fully-attributed query does not fire
    ok = tracing.rollup([_mkrec("operator.batch", 0, 95e6, op="A")],
                        wall_ms=100.0)
    q.spans = ok
    assert not any("UNATTRIBUTED" in p
                   for p in health_check([app]))


# -------------------------------------------------------------- live layer --

def test_traced_query_spans_and_export(traced_session, rng, tmp_path):
    df = (_small_df(traced_session, rng).filter(F.col("v") > -1.0)
          .group_by("k").agg(F.sum(F.col("v")).alias("sv")))
    want = df.to_pandas().sort_values("k", ignore_index=True)
    sp = traced_session.last_span_stats
    assert sp and sp["events"] > 0
    assert "operator.batch" in sp["points"]
    assert "pipeline.worker" in sp["points"]
    assert "jit.trace" in sp["points"]
    assert sp["operators"]  # per-operator rollup present
    # attribution contract on a compile-dominated first run: the span
    # taxonomy must cover >= 80% of wall (the acceptance gate)
    assert sp["unattributedFrac"] < 0.20, sp
    files = glob.glob(str(tmp_path / "traces" / "*.json"))
    assert files
    for f in files:
        assert validate_chrome_trace(load_trace(f)) == []
    # QueryEnd -> eventlog round trip
    traced_session.events.flush()
    from spark_rapids_tpu.tools.eventlog import load_logs
    app = load_logs(str(tmp_path / "events"))[0]
    traced = [q for q in app.queries if q.spans.get("events")]
    assert traced
    assert traced[-1].spans["points"].keys() == sp["points"].keys()
    # tracing changed nothing: same bytes with it off
    tracing.configure(enabled=False)
    got_off = df.to_pandas().sort_values("k", ignore_index=True)
    pd.testing.assert_frame_equal(got_off, want)


def test_concurrent_queries_no_cross_query_smear(traced_session, rng,
                                                 tmp_path):
    df_agg = (_small_df(traced_session, rng).group_by("k")
              .agg(F.sum(F.col("v")).alias("sv")))
    df_proj = _small_df(traced_session, rng).select(
        (F.col("v") * 2.0).alias("v2"))
    # warm both plans so the concurrent run is steady-state
    df_agg.to_pandas()
    df_proj.to_pandas()
    results = {}

    def run(name, df):
        results[name] = df.to_pandas()

    ts = [threading.Thread(target=run, args=("agg", df_agg)),
          threading.Thread(target=run, args=("proj", df_proj))]
    [t.start() for t in ts]
    [t.join() for t in ts]
    traced_session.events.flush()
    from spark_rapids_tpu.tools.eventlog import load_logs
    app = load_logs(str(tmp_path / "events"))[0]
    traced = [q for q in app.queries if q.spans.get("events")]
    agg_qs = [q for q in traced
              if "TpuHashAggregateExec" in (q.spans.get("operators")
                                            or {})]
    proj_qs = [q for q in traced
               if "TpuHashAggregateExec" not in
               (q.spans.get("operators") or {})
               and (q.spans.get("operators") or {})]
    assert agg_qs and proj_qs
    # the PR6 interference discipline at span granularity: the
    # projection query's drain must never contain the aggregate
    # query's operator spans (and vice versa)
    for q in proj_qs:
        ops = q.spans["operators"]
        assert "TpuHashAggregateExec" not in ops, (q.query_id, ops)


def test_faulted_query_traces_wellformed(traced_session, rng, tmp_path):
    from spark_rapids_tpu.robustness import inject as I
    df = (_small_df(traced_session, rng).group_by("k")
          .agg(F.count(F.col("v")).alias("c")))
    want = df.to_pandas().sort_values("k", ignore_index=True)
    with I.scoped_rules():
        I.inject("memory.oom", count=1, all_threads=True)
        got = df.to_pandas().sort_values("k", ignore_index=True)
    pd.testing.assert_frame_equal(got, want)
    files = glob.glob(str(tmp_path / "traces" / "*.json"))
    assert files
    for f in files:
        assert validate_chrome_trace(load_trace(f)) == [], f


def test_tracing_off_is_single_branch_and_recordless(rng):
    s = TpuSession()  # no trace conf: disarmed
    try:
        assert not tracing.armed()
        df = _small_df(s, rng).group_by("k").agg(
            F.sum(F.col("v")).alias("sv"))
        df.to_pandas()
        assert s.last_span_stats is None
        # disarmed buffers hold nothing — the off path never records
        with tracing._reg_lock:
            assert all(not b.items for b in tracing._bufs)
        assert tracing.span("x") is tracing._NOOP
    finally:
        s.stop()


def test_tracing_overhead_bounded(rng):
    """Tracing-on must stay close to tracing-off on a warm q6-shape
    loop.  The acceptance gate is <5% measured by bench p50; this CI
    pin is deliberately generous (shared runners) — it exists to catch
    an accidental O(n) regression on the hot path, not to measure."""
    pdf = pd.DataFrame({
        "price": rng.uniform(1000.0, 100000.0, 200_000),
        "disc": rng.uniform(0.0, 0.11, 200_000),
        "qty": rng.integers(1, 51, 200_000).astype(np.float64)})

    def run(session):
        df = session.create_dataframe(pdf)
        q = (df.filter((F.col("disc") >= 0.05) &
                       (F.col("disc") <= 0.07) &
                       (F.col("qty") < 24))
             .agg(F.sum(F.col("price") * F.col("disc")).alias("rev")))
        times = []
        for _ in range(7):
            t0 = time.perf_counter()
            q.collect()
            times.append(time.perf_counter() - t0)
        return sorted(times)[len(times) // 2]

    s_off = TpuSession()
    try:
        run(s_off)  # warm compile
        p50_off = run(s_off)
    finally:
        s_off.stop()
    s_on = TpuSession({"spark.rapids.tpu.trace.enabled": True})
    try:
        run(s_on)
        p50_on = run(s_on)
    finally:
        s_on.stop()
        tracing.configure(enabled=False)
    assert p50_on < p50_off * 1.5 + 0.005, (p50_off, p50_on)


# ------------------------------------------------------ observation store --

def test_observation_store_sites_and_restart(tmp_path, rng):
    jitdir = str(tmp_path / "jit")
    from spark_rapids_tpu.ops import jit_cache
    # fresh entries so the first dispatch really traces (compile_ms
    # observations come from cold sites; earlier tests warmed these
    # signatures in-process)
    jit_cache.clear()
    s = TpuSession({"spark.rapids.tpu.trace.enabled": True,
                    "spark.rapids.tpu.jitCache.dir": jitdir})
    try:
        df = (_small_df(s, rng).filter(F.col("v") > -1.0)
              .group_by("k").agg(F.sum(F.col("v")).alias("sv")))
        df.to_pandas()
    finally:
        s.stop()
        tracing.configure(enabled=False)
    store = tracing.ObservationStore.read(jitdir)
    assert store, "observation store empty"
    assert all(len(sid) == 16 and
               all(c in "0123456789abcdef" for c in sid)
               for sid in store)
    # keyed by the SAME structural site ids the jit cache uses: at
    # least one live jit signature hashes to a persisted site
    with jit_cache._LOCK:
        sigs = list(jit_cache._CACHE)
    assert any(tracing.site_id(sig) in store for sig in sigs), \
        (list(store), len(sigs))
    compile_sites = [r for r in store.values()
                     if r.get("compile_ms", 0) > 0]
    assert compile_sites
    # "process restart": a fresh store object over the same dir reads
    # the persisted evidence back and keeps accumulating into it
    fresh = tracing.ObservationStore(jitdir)
    assert fresh.records.keys() == store.keys()
    some = next(iter(store))
    fresh.observe(some, span_ms=1.0)
    fresh.flush()
    again = tracing.ObservationStore.read(jitdir)
    assert again[some]["n"] == store[some]["n"] + 1
    # the profiling consumer renders it (the ROADMAP item 3 contract)
    from spark_rapids_tpu.tools.profiling import site_history
    text = site_history(jitdir)
    assert some in text and "compile_ms" in text


def test_observation_store_concurrent_flush_merges(tmp_path):
    """Two stores sharing one cache dir (two sessions, one AOT dir)
    must not drop each other's observations: each flush re-reads the
    on-disk file under the lock file and merges sites it did not
    itself observe.  (The pre-fix rewrite path overwrote the file
    with only its own snapshot — store B, constructed before store
    A's flush, erased A's sites on its next flush.)"""
    d = str(tmp_path / "shared")
    a = tracing.ObservationStore(d)
    b = tracing.ObservationStore(d)  # constructed BEFORE a flushed
    a.observe("site-aaaa", span_ms=1.0)
    a.flush()
    b.observe("site-bbbb", span_ms=2.0)
    b.flush()  # must preserve a's site
    got = tracing.ObservationStore.read(d)
    assert "site-aaaa" in got and "site-bbbb" in got, list(got)
    # max-semantics fields merge rather than last-writer-win
    a.observe("site-bbbb", compile_ms=50.0)
    a.flush()
    b.observe("site-bbbb", compile_ms=10.0)
    b.flush()
    got = tracing.ObservationStore.read(d)
    assert got["site-bbbb"]["compile_ms"] == 50.0, got["site-bbbb"]


def test_observation_store_two_thread_merge_race(tmp_path):
    """Regression for the load-merge-atomic-rewrite race: two threads
    hammering observe+flush on two stores over one dir must land
    EVERY site in the final file."""
    import threading as _t
    d = str(tmp_path / "race")
    stores = [tracing.ObservationStore(d),
              tracing.ObservationStore(d)]

    def worker(idx):
        for i in range(20):
            stores[idx].observe(f"s{idx}-{i:04d}", span_ms=1.0 + i)
            stores[idx].flush()

    threads = [_t.Thread(target=worker, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for st in stores:
        st.flush()  # drain any dirty re-marks from lock timeouts
    got = tracing.ObservationStore.read(d)
    missing = [f"s{i}-{j:04d}" for i in range(2) for j in range(20)
               if f"s{i}-{j:04d}" not in got]
    assert not missing, missing
    assert not (tmp_path / "race" / "observations.jsonl.lock").exists()


# ----------------------------------------------------------- satellites --

def test_eventlog_flushms_batches_but_queryend_flushes(tmp_path):
    from spark_rapids_tpu.utils.events import EventLogger
    log = EventLogger(str(tmp_path), "flushtest", flush_ms=60_000)
    # batched window: plain events write but may sit in the buffer
    for i in range(5):
        log.emit("RecoveryAction", i=i)
    log.emit("QueryEnd", queryId=1)  # always flushes through
    with open(log.path, encoding="utf-8") as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert sum(1 for r in lines if r["event"] == "RecoveryAction") == 5
    assert any(r["event"] == "QueryEnd" for r in lines)
    log.emit("RecoveryAction", i=99)
    log.flush()  # explicit flush drains the tail
    with open(log.path, encoding="utf-8") as f:
        tail = [json.loads(ln) for ln in f if ln.strip()]
    assert any(r.get("i") == 99 for r in tail)
    log.close()
    with open(log.path, encoding="utf-8") as f:
        assert "SessionEnd" in f.read()


def test_timeline_phase_stripes_and_fallback():
    from spark_rapids_tpu.tools.eventlog import AppInfo, QueryInfo
    from spark_rapids_tpu.tools.profiling import generate_timeline
    q1 = QueryInfo(1, status="success", duration_ms=100.0)
    q1.start_ts, q1.end_ts = 1000.0, 1000.1
    q1.spans = {"wallMs": 100.0, "events": 3,
                "phases": {"compile": 40.0, "exchange": 20.0,
                           "compute": 20.0}}
    q2 = QueryInfo(2, status="success", duration_ms=50.0)  # pre-span
    q2.start_ts, q2.end_ts = 1000.2, 1000.25
    app = AppInfo(session_id="s", path="p", queries=[q1, q2],
                  start_ts=1000.0)
    svg = generate_timeline([app])
    assert "compile: 40.0 ms" in svg       # striped query
    assert "#e9c46a" in svg                # compile stripe color
    assert "q2: 50.0 ms" in svg            # fallback solid bar
    assert "#cccccc" in svg                # unattributed remainder


def test_qualification_surfaces_fusion_and_encoding_counters():
    from spark_rapids_tpu.tools.eventlog import AppInfo, QueryInfo
    from spark_rapids_tpu.tools.qualification import (format_report,
                                                      qualify_app)
    q = QueryInfo(1, status="success")
    q.metrics = {"TpuFilterExec": {"opTime": 1000, "opTimeSelf": 1000}}
    q.fusion = {"fusedStages": 2, "encodedStages": 1,
                "dispatchesSaved": 128}
    q.shuffle = {"exchanges": 1, "encodedBytesSaved": 4096}
    app = AppInfo(session_id="s", path="p", queries=[q])
    s = qualify_app(app)
    assert s.fused_stages == 2
    assert s.encoded_stages == 1
    assert s.dispatches_saved == 128
    assert s.encoded_bytes_saved == 4096
    rep = format_report([s])
    assert "fusedStages=2" in rep
    assert "encodedWireBytesSaved=4096" in rep


@pytest.mark.parametrize("fused", [False, True])
def test_filter_metrics_count_whole_and_partly_kept_batches(rng, fused):
    """``filter_metrics`` (the benchmark's ``filter.batches`` and
    ``filter.whole_batches``): a batch whose every row passes counts as
    whole, a batch that loses rows does not, through ``TpuFilterExec``
    and through a fused Filter+Project stage alike."""
    from spark_rapids_tpu.exec.basic import filter_metrics
    session = TpuSession({"spark.rapids.tpu.fusion.enabled": fused})
    df = _small_df(session, rng)

    def run(cond):
        q = df.filter(cond).select((F.col("v") * 2).alias("w"), "k")
        tree = session.plan(q.plan).tree_string()
        assert ("FusedStageExec" if fused else "TpuFilterExec") in tree
        before = filter_metrics.snapshot()
        rows = len(q.to_pandas())
        after = filter_metrics.snapshot()
        return rows, {k: after[k] - before[k] for k in after}

    rows, whole = run(F.col("k") >= 0)
    assert whole == {"batches": 1, "whole_batches": 1,
                     "rows_in": 4000, "rows_out": 4000} and rows == 4000
    rows, partly = run(F.col("k") < 25)
    assert partly == {"batches": 1, "whole_batches": 0,
                      "rows_in": 4000, "rows_out": rows} and 0 < rows < 4000
