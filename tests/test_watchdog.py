"""Watchdog suite: deadlines, hang detection, cooperative cancellation,
and hang/corruption chaos through the recovery ladder.

Oracle pattern as in test_chaos.py: wedge or corrupt a named point, run
the query, and require the answer to match the clean run — detection
within the configured deadline (generous CPU tolerance), classification
through faults.py, recovery through the ladder.
"""

import os
import time

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.robustness import faults as FT
from spark_rapids_tpu.robustness import inject as I
from spark_rapids_tpu.robustness import watchdog as W
from spark_rapids_tpu.robustness.driver import recovery_metrics

pytestmark = pytest.mark.chaos

# detection must honor the deadline within this tolerance on a loaded
# CI CPU: deadline + monitor poll + checkpoint delivery + slack
TOLERANCE_S = 5.0


@pytest.fixture(autouse=True)
def _clean_registry():
    # injection rules are hard-scoped to the test (inject.scoped_rules)
    # so a leaked delay/corrupt rule can never wedge a later test
    I.clear()
    W.clear_thread()
    W.watchdog_metrics.reset()
    recovery_metrics.reset()
    with I.scoped_rules():
        yield
    W.clear_thread()


@pytest.fixture()
def lineitem_parquet(tmp_path):
    rng = np.random.default_rng(7)
    n = 5000
    pdf = pd.DataFrame({
        "k": rng.integers(0, 20, n),
        "v": rng.normal(size=n),
    })
    path = tmp_path / "t.parquet"
    pdf.to_parquet(path, index=False)
    return str(path)


def _actions(session):
    return [r["action"] for r in session.recovery_log]


def _faults(session):
    return [r["fault"] for r in session.recovery_log]


def _norm(df, keys):
    return df.sort_values(keys, ignore_index=True)


# ------------------------------------------------------------- unit layer --
def test_section_trips_and_delivers_at_checkpoint():
    t0 = time.monotonic()
    with pytest.raises(FT.TimeoutFault) as ei:
        with W.section("io.reader", deadline_ms=60):
            time.sleep(0.25)
        # the overrun is delivered at the section-exit checkpoint
    assert time.monotonic() - t0 < TOLERANCE_S
    assert ei.value.point == "io.reader"
    snap = W.watchdog_metrics.snapshot()
    assert snap["trips"].get("io.reader", 0) >= 1
    assert snap["cancels"].get("io.reader", 0) >= 1
    # classified retryable: the ladder's retry rung absorbs it
    assert FT.classify(ei.value) == FT.Fault("timeout", FT.RETRYABLE)


def test_section_within_deadline_is_silent():
    with W.section("io.reader", deadline_ms=10_000):
        time.sleep(0.01)
    W.checkpoint()  # nothing pending


def test_heartbeat_extends_deadline():
    # silence is the signal: regular beats keep a long-running section
    # alive well past its nominal deadline
    with W.section("pipeline.worker", deadline_ms=150) as s:
        for _ in range(6):
            time.sleep(0.05)
            s.beat()
    W.checkpoint()


def test_compilation_is_not_silence():
    # a section whose thread sits in XLA's compiler past the deadline
    # does not trip (on a TPU one 64-bit sort compiles for minutes);
    # the same wall time spent asleep does (the first test above)
    import jax
    import jax.numpy as jnp

    def long_chain(x):
        for i in range(1500):   # seconds of XLA:CPU compile, no loop
            x = jnp.sin(x) * 1.0001 + i
        return x

    lowered = jax.jit(long_chain).lower(jnp.ones(8))  # traced out here
    with W.section("pipeline.worker", deadline_ms=100) as s:
        t0 = time.monotonic()
        lowered.compile()
        assert time.monotonic() - t0 > 0.3, "compile too quick to prove it"
        assert not s.tripped
    W.checkpoint()  # nothing parked
    assert not W.watchdog_metrics.snapshot()["trips"]


def test_delay_rule_wedges_until_disarmed_or_deadline():
    # a tripped deadline aborts the wedge cooperatively (the delay
    # loop is itself a checkpoint)
    rule = I.inject("io.read", kind="delay", delay_s=60)
    t0 = time.monotonic()
    try:
        with pytest.raises(FT.TimeoutFault):
            with W.section("io.reader", deadline_ms=100):
                I.fire("io.read")
    finally:
        I.remove(rule)
    assert time.monotonic() - t0 < TOLERANCE_S
    assert rule.fired == 1


def test_delay_rule_finite_duration():
    # bounded delays un-wedge by themselves (the chaos-spray shape)
    with I.injected("io.read", kind="delay", delay_s=0.05) as rule:
        t0 = time.monotonic()
        I.fire("io.read")
        assert 0.04 <= time.monotonic() - t0 < TOLERANCE_S
        assert rule.fired == 1


def test_query_scope_clears_stale_tokens():
    s = TpuSession()
    with pytest.raises(FT.TimeoutFault):
        with W.section("io.reader", deadline_ms=30):
            time.sleep(0.2)
    # simulate a stale token: park one and enter a fresh attempt
    with W.query_scope(s):
        W.checkpoint()  # must not raise


def test_unknown_rule_kind_rejected():
    with pytest.raises(ValueError):
        I.inject("io.read", kind="explode")


# ----------------------------------------------------------- query layer --
def test_reader_hang_detected_and_recovered(lineitem_parquet):
    s = TpuSession({
        "spark.rapids.tpu.watchdog.deadline.io.reader": 200,
        "spark.rapids.sql.recovery.backoffMs": 5,
    })
    df = (s.read.parquet(lineitem_parquet)
          .group_by("k").agg(F.sum(F.col("v")).alias("sv")))
    want = df.to_pandas()
    s.recovery_log.clear()
    t0 = time.monotonic()
    with I.injected("io.read", kind="delay", delay_s=60, count=1):
        got = df.to_pandas()
    assert time.monotonic() - t0 < TOLERANCE_S
    pd.testing.assert_frame_equal(_norm(got, ["k"]), _norm(want, ["k"]))
    assert "timeout" in _faults(s)
    assert _actions(s)[0] == "retry"


def test_wedged_pipeline_worker_cancels_consumer():
    # a worker stuck in NON-cooperative code (plain sleep, no
    # checkpoints) stops heartbeating; the monitor cancels the driving
    # thread, which is blocked on the pipeline queue
    from spark_rapids_tpu.exec.pipeline import pipelined
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    TpuSession({
        "spark.rapids.tpu.watchdog.deadline.pipeline.worker": 200,
    })

    def source():
        yield ColumnarBatch.from_pydict({"a": np.arange(10)})
        time.sleep(30)  # wedged: no beats, no checkpoints
        yield ColumnarBatch.from_pydict({"a": np.arange(10)})

    t0 = time.monotonic()
    with pytest.raises(FT.TimeoutFault) as ei:
        list(pipelined(source(), depth=2))
    assert time.monotonic() - t0 < TOLERANCE_S
    assert ei.value.point == "pipeline.worker"


def test_shuffle_hang_recovers_distributed(lineitem_parquet):
    import jax
    if jax.device_count() < 8:
        pytest.skip("needs the virtual 8-device mesh")
    from spark_rapids_tpu.parallel.mesh import make_mesh
    s = TpuSession({
        "spark.rapids.tpu.watchdog.deadline.shuffle.exchange": 200,
        "spark.rapids.sql.recovery.backoffMs": 5,
    }, mesh=make_mesh(8))
    rng = np.random.default_rng(3)
    pdf = pd.DataFrame({"k": rng.integers(0, 40, 4096),
                        "v": rng.normal(size=4096)})
    df = (s.create_dataframe(pdf).group_by("k")
          .agg(F.sum(F.col("v")).alias("sv")))
    s.recovery_log.clear()
    with I.injected("shuffle.exchange", kind="delay", delay_s=60,
                    count=1):
        got = df.to_pandas()
    assert "timeout" in _faults(s)
    assert s.last_dist_explain == "distributed"  # recovered ON mesh
    oracle = TpuSession()
    want = (oracle.create_dataframe(pdf).group_by("k")
            .agg(F.sum(F.col("v")).alias("sv"))).to_pandas()
    pd.testing.assert_frame_equal(_norm(got, ["k"]), _norm(want, ["k"]),
                                  check_dtype=False)


def test_query_deadline_bounds_attempt(lineitem_parquet):
    # no per-point deadline at all — only the whole-query wall clock
    s = TpuSession({
        "spark.rapids.tpu.watchdog.defaultDeadlineMs": 0,
        "spark.rapids.tpu.watchdog.queryDeadlineMs": 300,
        "spark.rapids.sql.recovery.backoffMs": 5,
    })
    df = (s.read.parquet(lineitem_parquet)
          .group_by("k").agg(F.sum(F.col("v")).alias("sv")))
    want = df.to_pandas()
    s.recovery_log.clear()
    t0 = time.monotonic()
    with I.injected("io.read", kind="delay", delay_s=60, count=1):
        got = df.to_pandas()
    assert time.monotonic() - t0 < TOLERANCE_S
    pd.testing.assert_frame_equal(_norm(got, ["k"]), _norm(want, ["k"]))
    assert "timeout" in _faults(s)
    trip_points = {p for p in
                   W.watchdog_metrics.snapshot()["trips"]}
    assert "query" in trip_points


# ------------------------------------------------------ corruption layer --
def test_host_corruption_recovers_query():
    s = TpuSession({
        "spark.rapids.memory.tpu.deviceLimitBytes": 4096,
        "spark.rapids.sql.recovery.backoffMs": 5,
    })
    rng = np.random.default_rng(5)
    pdf = pd.DataFrame({"k": rng.integers(0, 1000, 3000),
                        "v": rng.normal(size=3000)})
    df = s.create_dataframe(pdf).orderBy("k")
    want = df.to_pandas()
    s.recovery_log.clear()
    with I.injected("spill.corrupt.host", kind="corrupt", count=1,
                    all_threads=True) as rule:
        got = df.to_pandas()
    assert rule.fired == 1
    pd.testing.assert_frame_equal(_norm(got, ["k", "v"]),
                                  _norm(want, ["k", "v"]))
    assert "spill_corruption" in _faults(s)
    # degradable: entered the ladder at the split rung, not retry
    assert _actions(s)[0] == "split"


def test_disk_corruption_recovers_query():
    s = TpuSession({
        "spark.rapids.memory.tpu.deviceLimitBytes": 4096,
        "spark.rapids.memory.host.spillStorageSize": 4096,
        "spark.rapids.memory.spill.diskWriteThreads": 1,
        "spark.rapids.sql.recovery.backoffMs": 5,
    })
    rng = np.random.default_rng(6)
    pdf = pd.DataFrame({"k": rng.integers(0, 1000, 3000),
                        "v": rng.normal(size=3000)})
    df = s.create_dataframe(pdf).orderBy("k")
    want = df.to_pandas()
    s.recovery_log.clear()
    with I.injected("spill.corrupt.disk", kind="corrupt", count=1,
                    all_threads=True) as rule:
        got = df.to_pandas()
    assert rule.fired == 1
    pd.testing.assert_frame_equal(_norm(got, ["k", "v"]),
                                  _norm(want, ["k", "v"]))
    assert "spill_corruption" in _faults(s)


# ------------------------------------------------------------ event trail --
def test_watchdog_and_corruption_events_land_in_log(tmp_path,
                                                    lineitem_parquet):
    from spark_rapids_tpu.tools.eventlog import load_logs
    from spark_rapids_tpu.tools.profiling import health_check
    s = TpuSession({
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
        "spark.rapids.tpu.watchdog.deadline.io.reader": 200,
        "spark.rapids.memory.tpu.deviceLimitBytes": 4096,
        "spark.rapids.sql.recovery.backoffMs": 5,
    })
    df = (s.read.parquet(lineitem_parquet)
          .group_by("k").agg(F.sum(F.col("v")).alias("sv")))
    with I.injected("io.read", kind="delay", delay_s=60, count=1):
        df.to_pandas()
    with I.injected("spill.corrupt.host", kind="corrupt", count=1,
                    all_threads=True):
        df.to_pandas()
    s.stop()
    apps = load_logs(str(tmp_path))
    assert apps
    wd = [w for a in apps
          for w in a.watchdog + [w for q in a.queries
                                 for w in q.watchdog]]
    assert any(w["kind"] == "trip" and w["point"] == "io.reader"
               for w in wd)
    assert any(w["kind"] == "cancel" for w in wd)
    cor = [c for a in apps
           for c in a.corruption + [c for q in a.queries
                                    for c in q.corruption]]
    assert any(c.get("tier") == "HOST" for c in cor)
    report = "\n".join(health_check(apps))
    assert "hang detected at io.reader" in report
    assert "failed checksum" in report


# ------------------------------------------------------- backoff satellite --
def test_backoff_jitter_capped_and_deterministic(monkeypatch):
    from spark_rapids_tpu.robustness.driver import QueryRetryDriver

    def run_once():
        s = TpuSession({
            "spark.rapids.sql.recovery.backoffMs": 40,
            "spark.rapids.sql.recovery.backoffCapMs": 60,
            "spark.rapids.sql.recovery.maxRetries": 3,
        })
        sleeps = []
        monkeypatch.setattr(time, "sleep", sleeps.append)
        calls = {"n": 0}

        def attempt(mode):
            calls["n"] += 1
            if calls["n"] <= 3:
                raise FT.TimeoutFault("io.reader", 10, 20)
            return "ok"

        assert QueryRetryDriver(s, label="t").run(attempt) == "ok"
        return sleeps

    a, b = run_once(), run_once()
    assert a == b  # seeded per-driver RNG: replayable
    assert len(a) == 3
    # jitter keeps each sleep in [0.5, 1.0] x the capped base
    for i, slept in enumerate(a):
        base = min(0.040 * (2 ** i), 0.060)
        assert 0.5 * base <= slept <= base


# ----------------------------------------------------------- chaos spray --
def test_hang_and_corruption_spray():
    """Bounded delay + corrupt rules across every registered point; the
    query must still answer with clean-run results."""
    s = TpuSession({
        "spark.rapids.tpu.watchdog.defaultDeadlineMs": 500,
        "spark.rapids.memory.tpu.deviceLimitBytes": 65536,
        "spark.rapids.sql.recovery.backoffMs": 5,
    })
    rng = np.random.default_rng(1)
    pdf = pd.DataFrame({"k": rng.integers(0, 50, 4000),
                        "v": rng.normal(size=4000)})
    df = (s.create_dataframe(pdf).group_by("k")
          .agg(F.sum(F.col("v")).alias("sv"),
               F.count(F.col("v")).alias("c")))
    want = df.to_pandas()
    rules = []
    try:
        for point in I.injection_points():
            rules.append(I.inject(point, kind="delay", delay_s=0.1,
                                  count=2, probability=0.5, seed=7,
                                  all_threads=True))
        for point in ("spill.corrupt.host", "spill.corrupt.disk"):
            rules.append(I.inject(point, kind="corrupt", count=2,
                                  probability=0.5, seed=11,
                                  all_threads=True))
        got = df.to_pandas()
    finally:
        for r in rules:
            I.remove(r)
    pd.testing.assert_frame_equal(_norm(got, ["k"]), _norm(want, ["k"]),
                                  check_dtype=False)
