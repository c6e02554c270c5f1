"""One timeline (ISSUE 28): the engine's spans in the profiler's trace,
every device program named for its site, and bytes counted where they
move (upload, concat), with planning inside the query envelope."""

import ast
import glob
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax
import jax.numpy as jnp

import spark_rapids_tpu
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.ops import concat, jit_cache
from spark_rapids_tpu.utils import hostsync, tracing

TRACE = "spark.rapids.tpu.trace.enabled"
PROFILE = "spark.rapids.tpu.profile.trace"


@pytest.fixture
def lineitem(tmp_path, rng):
    n = 5000
    d = tmp_path / "t"
    d.mkdir()
    pq.write_table(pa.table({
        "k": rng.integers(0, 20, n), "v": rng.normal(size=n),
        "s": np.array(["ab", "cde", "f"], dtype=object)[
            rng.integers(0, 3, n)]}), str(d / "part-0.parquet"))
    return str(d)


def _query(session, path):
    return (session.read.parquet(path).filter(F.col("v") > -1.0)
            .group_by("k").agg(F.sum(F.col("v")).alias("sv")))


def _session(**conf):
    jit_cache.clear()
    return TpuSession(conf or None)


def _stop(session):
    session.stop()
    tracing.configure(enabled=False)


def _host_events(trace_dir):
    """{name: [(thread line, start, end)]} of every host-plane event;
    an operator's event under ``<name>#point`` too, by its stat."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                at = (line.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                out.setdefault(ev.name, []).append(at)
                if ev.name.startswith("Tpu"):
                    point = dict(ev.stats).get("point")
                    out.setdefault(f"{ev.name}#{point}", []).append(at)
    return out


def _profiled(tmp_path, path, **conf):
    s = _session(**conf)
    try:
        df = _query(s, path)
        df.to_pandas()  # compile outside the profile
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path / "prof"),
                                 profiler_options=options)
        try:
            df.to_pandas()
        finally:
            jax.profiler.stop_trace()
    finally:
        _stop(s)
    return _host_events(str(tmp_path / "prof"))


@pytest.mark.parametrize("recording", [False, True])
def test_spans_in_profiler_trace_nest_in_operator(tmp_path, lineitem,
                                                  recording):
    """profile.trace alone, or with trace.enabled: an engine span is a
    host-plane event of the profiler's trace, inside its operator's."""
    events = _profiled(tmp_path, lineitem,
                       **{PROFILE: True, TRACE: recording})
    assert "TpuFileScanExec" in events and "io.reader" in events
    for point in ("io.reader", "scan.decode", "scan.convert",
                  "upload.h2d", "plan.physical"):
        assert point in events, sorted(events)
    # named by the operator, the span's point rides as a stat
    scans = events["TpuFileScanExec#operator.batch"]
    assert len(scans) == len(events["TpuFileScanExec"])
    for line, t0, t1 in events["io.reader"]:
        assert any(sl == line and s0 <= t0 and t1 <= s1
                   for sl, s0, s1 in scans), (line, t0, t1)
    readers = events["io.reader"]
    for line, t0, t1 in events["scan.decode"]:
        assert any(rl == line and r0 <= t0 and t1 <= r1
                   for rl, r0, r1 in readers)


def test_profile_off_writes_no_annotation(tmp_path, lineitem):
    events = _profiled(tmp_path, lineitem, **{TRACE: True})
    engine = [n for n in events
              if n.startswith(("Tpu", "io.", "scan.", "upload.",
                               "plan.", "jit.", "operator."))]
    assert engine == []


def test_profile_only_records_nothing_and_noop_when_off(lineitem):
    s = _session(**{PROFILE: True})
    try:
        assert not tracing.armed()
        assert tracing.span("x") is not tracing._NOOP
        _query(s, lineitem).to_pandas()
        assert s.last_span_stats is None
        with tracing._reg_lock:
            assert all(not b.items and not b.stack
                       for b in tracing._bufs)
    finally:
        _stop(s)
    assert tracing.span("x") is tracing._NOOP


def test_cached_jit_names_module_for_its_site():
    tracing.configure(enabled=True)
    try:
        class Stage:
            def _run(self, x, n):
                return x * 2 + n

        sig = ("filter_stage", 3, "test_timeline")
        fn = jit_cache.cached_jit(sig, lambda: Stage()._run)
        x = jnp.arange(8.0)
        fn(x, 1)
        fn(x, 1)
        records, _ = tracing._drain(tracing._owner_ident())
        sites = tracing.rollup(records, 1.0)["sites"]
        assert list(sites) == [tracing.site_id(sig)]
        module = fn._jit.lower(x, 1).as_text().split()[1]
        assert module.startswith("@jit_filter_stage_"), module
        assert module == "@jit_filter_stage_" + list(sites)[0][:8]
    finally:
        tracing.configure(enabled=False)


def _jit_targets(tree):
    """Names of functions handed to jax.jit in a module: decorated
    (``@jax.jit``, ``@partial(jax.jit, ...)``) or passed by name."""
    def is_jit(node):
        if isinstance(node, ast.Call):
            return is_jit(node.func) or any(is_jit(a) for a in node.args)
        return (isinstance(node, ast.Attribute) and node.attr == "jit"
                and getattr(node.value, "id", None) == "jax")

    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and any(
                is_jit(d) for d in node.decorator_list):
            yield node.name
        if isinstance(node, ast.Call) and is_jit(node.func):
            for a in node.args[:1]:
                if isinstance(a, ast.Lambda):
                    yield "<lambda>"
                elif isinstance(a, ast.Call):  # _named(fn, name)
                    yield getattr(a.func, "id", "")
                else:
                    yield getattr(a, "id", None) or getattr(a, "attr", "")


def test_no_jit_target_named_run():
    root = os.path.dirname(spark_rapids_tpu.__file__)
    seen = 0
    for sub in ("exec", "ops", "parallel"):
        for path in sorted(glob.glob(os.path.join(root, sub, "*.py"))):
            with open(path) as f:
                tree = ast.parse(f.read())
            for name in _jit_targets(tree):
                seen += 1
                assert name not in ("run", "_run", "<lambda>", ""), \
                    (path, name)
    assert seen >= 10  # the walk found the kernels it is about
    # and whatever a stage compiler hands cached_jit is renamed
    assert jit_cache.program_name(("stage", 1)).startswith("stage_")
    assert jit_cache.program_name(("x y", 1)).startswith("x_y_")


def test_upload_counter_counts_each_buffer_once(rng):
    n = 1000
    vals = rng.normal(size=n)
    table = pa.table({
        "a": pa.array(vals, mask=vals > 1.0),
        "s": pa.array(["xy", None, "z", "wvu"] * (n // 4))})
    batch = ColumnarBatch.from_arrow(table)
    want_bytes = want_buffers = 0
    for c in batch.columns.values():
        for buf in (c._np_data, c._np_validity, c._np_offsets):
            if buf is not None:
                want_bytes += buf.nbytes
                want_buffers += 1
    assert want_buffers >= 5
    before = hostsync.upload_metrics.snapshot()
    for _ in range(2):  # the second access uploads nothing
        for c in batch.columns.values():
            c.data, c.validity, c.offsets
    after = hostsync.upload_metrics.snapshot()
    assert after["bytes"] - before["bytes"] == want_bytes
    assert after["buffers"] - before["buffers"] == want_buffers
    assert after["ns"] > before["ns"]


def test_concat_counters_read_capacities():
    k, c = 4, 1024
    tables = [{"x": np.arange(c, dtype=np.int64) + i,
               "y": pa.array([None if j == i else j for j in range(c)],
                             type=pa.int64())} for i in range(k)]
    # y: an input with a null carries a validity; the last has none
    tables[-1]["y"] = pa.array(range(c), type=pa.int64())
    batches = [ColumnarBatch.from_arrow(pa.table(t)) for t in tables]
    assert all(b.capacity == c for b in batches)
    assert [b.column("y").validity is None for b in batches] \
        == [False] * (k - 1) + [True]
    before = concat.concat_metrics.snapshot()
    out = concat.concat_batches(batches)
    after = concat.concat_metrics.snapshot()
    K = out.capacity
    assert K == k * c
    # one program a column places each input's block once and finishes
    # each output buffer once.  x has no validity anywhere: none is
    # placed or made.  y's output needs one: every input places a
    # validity block (the program's own all-True one for the last
    # input, which appends none)
    x_written = k * c * 8 + K * 8
    y_written = k * c * (8 + 1) + K * (8 + 1)
    assert after["appends"] - before["appends"] == 2 * k
    assert after["bytes_written"] - before["bytes_written"] \
        == x_written + y_written
    assert after["bytes_appended"] - before["bytes_appended"] \
        == k * c * 8 + k * c * 8 + (k - 1) * c
    assert out.column("x").to_numpy()[-1] == c - 1 + k - 1
    assert out.column("x").validity is None
    assert out.column("y").null_count() == k - 1


def test_plan_physical_inside_the_envelope(lineitem, monkeypatch):
    import time
    s = _session(**{TRACE: True})
    try:
        plan = s.plan

        def slow_plan(*a, **kw):
            time.sleep(0.05)
            return plan(*a, **kw)

        monkeypatch.setattr(s, "plan", slow_plan)
        _query(s, lineitem).to_pandas()
        sp = s.last_span_stats
        assert sp["points"]["plan.physical"]["count"] == 1
        assert sp["points"]["plan.physical"]["ms"] >= 50.0
        assert sp["wallMs"] >= sp["exclusiveMs"] >= 50.0
    finally:
        _stop(s)


def test_jit_dispatch_carries_enclosing_operator(lineitem):
    s = _session(**{TRACE: True})
    try:
        df = _query(s, lineitem)
        df.to_pandas()
        cold = s.last_span_stats
        df.to_pandas()
        warm = s.last_span_stats
    finally:
        _stop(s)
    assert "jit.trace" in cold["points"]
    assert "jit.dispatch" in warm["points"]
    assert "jit.trace" not in warm["points"]
    ops = {v.get("op") for v in warm["sites"].values()}
    assert None not in ops
    assert "TpuHashAggregateExec" in ops
    assert ops <= set(warm["operators"])
    # every site of the warm run was launched by the same operator in
    # the cold one
    for sid, v in warm["sites"].items():
        assert cold["sites"][sid]["op"] == v["op"]


def test_results_bit_identical_with_both_confs_on(lineitem):
    s = _session()
    try:
        want = _query(s, lineitem).to_pandas().sort_values(
            "k", ignore_index=True)
    finally:
        _stop(s)
    s = _session(**{TRACE: True, PROFILE: True})
    try:
        got = _query(s, lineitem).to_pandas().sort_values(
            "k", ignore_index=True)
    finally:
        _stop(s)
    pd.testing.assert_frame_equal(got, want, check_exact=True)
