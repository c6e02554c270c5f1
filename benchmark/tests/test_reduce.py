"""The trace reduction: interval arithmetic by hand, and a recorded
``.xplane.pb`` (one traced q6 query on a TPU v5e, taken by this harness)
against an independent brute-force reading of the same file."""

import os

import numpy as np
import pytest

from benchmark.trace import reduce as r

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_q6.xplane.pb")


def test_union_of_intervals():
    # [0,10) and [5,12) overlap; [20,30) stands alone; [21,22) is inside
    assert r.union_seconds([0, 5, 20, 21], [10, 12, 30, 22]) == 22
    assert r.union_seconds([], []) == 0.0
    # order does not matter
    assert r.union_seconds([20, 0, 21, 5], [30, 10, 22, 12]) == 22
    # an interval that swallows all the later ones
    assert r.union_seconds([0, 1, 2], [100, 2, 3]) == 100


def test_gaps_are_the_complement():
    g0, g1 = r.gaps([0, 5, 20, 21], [10, 12, 30, 22], -5, 40)
    assert list(zip(g0, g1)) == [(-5, 0), (12, 20), (30, 40)]
    # busy and idle make up the window
    busy = r.union_seconds([0, 5, 20, 21], [10, 12, 30, 22])
    assert busy + float(np.sum(g1 - g0)) == 40 - (-5)
    g0, g1 = r.gaps([], [], 3, 9)
    assert list(zip(g0, g1)) == [(3, 9)]


def test_gap_labels_take_the_innermost_annotation():
    notes = [("bench.query", 0.0, 100.0), ("bench.to_pandas", 10.0, 90.0),
             ("TpuFileScanExec", 20.0, 40.0)]
    by = r._label_gaps(np.array([25.0, 50.0, 95.0, 200.0]),
                       np.array([30.0, 60.0, 99.0, 210.0]), notes)
    assert by == {"TpuFileScanExec": 5.0, "bench.to_pandas": 10.0,
                  "host.unannotated": 14.0}


def test_collective_names():
    for name in ("%all-to-all.3 = ...", "all-gather-start.1",
                 "%all-reduce.7", "collective-permute-done"):
        assert r.COLLECTIVE.search(name), name
    for name in ("%fusion.3", "%sort.12", "copy-start"):
        assert not r.COLLECTIVE.search(name), name


def _brute(path):
    """Busy nanoseconds of TPU:0 inside the bench.query marks, by
    painting every op onto a boolean timeline of nanoseconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    marks, ops, modules = [], [], {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                span = (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                if ev.name == "bench.query":
                    marks.append(span)
                elif plane.name == "/device:TPU:0":
                    if line.name == "XLA Ops":
                        ops.append(span)
                    elif line.name == "XLA Modules":
                        modules.setdefault(ev.name.split("(")[0],
                                           []).append(span)
    lo, hi = min(m[0] for m in marks), max(m[1] for m in marks)
    painted = np.zeros(hi - lo, dtype=bool)
    for s, e in ops:
        painted[max(s - lo, 0):max(min(e, hi) - lo, 0)] = True
    return lo, hi, int(painted.sum()), modules, len(marks)


@pytest.fixture(scope="module")
def reduced():
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded trace beside the test")
    return r.reduce_trace(RECORDED)


def test_recorded_trace_busy_and_idle(reduced):
    lo, hi, busy_ns, modules, marks = _brute(RECORDED)
    assert reduced["marks"] == marks >= 1
    assert reduced["window_s"] == pytest.approx((hi - lo) * 1e-9, rel=1e-12)
    # the timeline is painted in whole nanoseconds
    assert reduced["busy_s"] == pytest.approx(busy_ns * 1e-9, rel=1e-3)
    assert reduced["busy_s_fullest"] == reduced["busy_s"]  # one chip
    idle = 100 * (1 - busy_ns / (hi - lo))
    assert reduced["idle_share"] == pytest.approx(idle, abs=1e-3)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert 0 < reduced["idle_share"] < 100


def test_recorded_trace_programs_and_collectives(reduced):
    lo, hi, _, modules, _ = _brute(RECORDED)
    sums = {n: sum(min(e, hi) - max(s, lo) for s, e in spans
                   if e > lo and s < hi) * 1e-9
            for n, spans in modules.items()}
    sums = {n: v for n, v in sums.items() if v > 0}
    top = dict(reduced["device_ops"])
    assert len(top) == min(len(sums), 10)
    for name, seconds in top.items():
        assert seconds == pytest.approx(sums[name], rel=1e-9)
    assert min(top.values()) >= max(
        [v for n, v in sums.items() if n not in top] or [0])
    # one chip: no collective ran
    assert reduced["collective_s_fullest"] == 0
    assert "jit__update_fused" in sums


def test_recorded_trace_gaps_add_up(reduced):
    idle_s = reduced["window_s"] - reduced["busy_s_fullest"]
    named = sum(seconds for _, seconds in reduced["idle_gaps"])
    assert named <= idle_s * (1 + 1e-9)
    assert named >= 0.95 * idle_s   # at most 10 names are kept
    names = [n for n, _ in reduced["idle_gaps"]]
    assert all(r.HOST_NAMES.match(n) or n == "host.unannotated"
               for n in names)


def test_a_trace_without_a_device_reduces_to_none(tmp_path):
    import jax
    import jax.numpy as jnp
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.query"):
        jnp.arange(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = r.find_xplane(str(tmp_path))
    assert path is not None
    assert r.reduce_trace(path) is None
