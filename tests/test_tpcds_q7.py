"""TPC-DS Q7 at the specification's types through ``session.sql`` over
parquet: nullable int32 keys, ``decimal(7,2)`` money, a five-table star
join written with commas, exact ``avg(decimal)`` on the device.  The
tables are the benchmark generator's at ``sf`` 0.01 and the answers its
plain reference's (``benchmark/reference/tpcds.py``), judged by the
benchmark's own comparison."""

import decimal
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark.datagen import tpcds as gen
from benchmark.harness import compare, spec
from benchmark.reference import tpcds as ref
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.column import Column

SF = 0.01
Q7 = spec.load_json("queries", "tpcds", "q7.json")
with open(os.path.join(spec.BENCH, "queries", "tpcds", "q7.sql")) as f:
    Q7_TEXT = f.read()


def _write(tables, base):
    """One directory a table; store_sales in two files, as the
    configuration stores it."""
    dirs = {}
    for name, table in tables.items():
        d = base / name
        d.mkdir()
        n_files = 2 if name == "store_sales" else 1
        per_file = -(-table.num_rows // n_files)
        for i in range(n_files):
            pq.write_table(table.slice(i * per_file, per_file),
                           str(d / f"part-{i:03d}.parquet"))
        dirs[name] = str(d)
    return dirs


@pytest.fixture(scope="module")
def fixed_tables():
    """The tables that are the same for every seed (the 1,920,800-row
    cross product is made once)."""
    return gen.gen_tables(["customer_demographics", "date_dim"], SF, 0)


def _session_over(tables, base, conf=None):
    session = TpuSession(conf)
    for name, d in _write(tables, base).items():
        session.read.parquet(d).createOrReplaceTempView(name)
    return session


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_q7_matches_the_plain_reference(tmp_path, fixed_tables, seed):
    tables = dict(fixed_tables, **gen.gen_tables(
        ["store_sales", "item", "promotion"], SF, seed))
    session = _session_over(tables, tmp_path)
    frame = session.sql(Q7_TEXT)
    plan = session.plan(frame.plan).tree_string()
    assert "CpuFallbackExec" not in plan
    assert plan.count("TpuHashJoinExec") == 4
    got = frame.to_pandas()
    want = ref.q7(compare.reference_frames(tables, {"q7": Q7}))
    assert 10 <= len(want) <= 100
    err, wrong, shape = compare.compare_answer(got, want)
    assert (wrong, shape) == (0, 0) and err <= 1e-10
    # the averages are Spark's decimal(11,6), and some group of the
    # answer is NULL in one of them (each skips its own NULLs)
    assert dict(frame.plan.schema)["agg2"].name == "decimal(11,6)"
    assert all(a is None or a.as_tuple().exponent == -6
               for a in got.agg2)
    assert not session.recovery_log


def test_q7_several_scan_batches_and_the_control(tmp_path, fixed_tables):
    """The fact in batches of 16,384 rows at most (so every join and the
    aggregate merge partials), against the same reference; the float32
    control of the same answer is not correct."""
    seed = 5
    tables = dict(fixed_tables, **gen.gen_tables(
        ["store_sales", "item", "promotion"], SF, seed))
    session = _session_over(
        tables, tmp_path, {"spark.rapids.sql.reader.batchSizeRows": 16384})
    got = session.sql(Q7_TEXT).to_pandas()
    frames = compare.reference_frames(tables, {"q7": Q7})
    want = ref.q7(frames)
    assert compare.compare_answer(got, want)[1:] == (0, 0)
    control = ref.q7(frames, np.float32)
    assert compare.compare_answer(control, want)[1] > 0


def test_from_arrow_decimal128_in_bulk_equals_the_per_value_path():
    rng = np.random.default_rng(20260930)
    ints = rng.integers(-10**17, 10**17, 5000)
    ints[:4] = [0, -1, 10**17 - 1, -(10**17 - 1)]
    values = [None if rng.random() < 0.1 else
              decimal.Decimal(int(v)).scaleb(-4) for v in ints]
    arr = pa.array(values, pa.decimal128(18, 4))
    for a in (arr, arr.slice(17, 3000),
              pa.chunked_array([arr.slice(0, 10), arr.slice(10)]),
              pa.array([], pa.decimal128(7, 2))):
        col = Column.from_arrow(a)
        listed = a.to_pylist()
        # the old path, value by value
        old = [0 if v is None else int(v.scaleb(col.dtype.scale))
               for v in listed]
        assert col.to_numpy().tolist() == old
        assert col.validity_numpy().tolist() == [v is not None
                                                 for v in listed]
        assert col.to_pylist() == listed
    with pytest.raises(ValueError, match="precision 20 > 18"):
        Column.from_arrow(pa.array([decimal.Decimal(1)],
                                   pa.decimal128(20, 0)))


@pytest.fixture(scope="module")
def session():
    return TpuSession()


def _keyed(session, name, keys, payload):
    session.create_dataframe(pa.table({
        f"{name}_k": pa.array(keys, type=pa.int32()),
        f"{name}_v": pa.array(payload, type=pa.int64()),
    })).createOrReplaceTempView(name)


def test_null_int32_join_key_matches_nothing(session):
    _keyed(session, "f", [1, None, 2, None, 3, 2], [10, 20, 30, 40, 50, 60])
    _keyed(session, "d", [2, None, 3, 4], [1, 2, 3, 4])
    out = session.sql("select f_v, d_v from f, d where f_k = d_k "
                      "order by f_v").to_pandas()
    assert out.f_v.tolist() == [30, 50, 60]
    assert out.d_v.tolist() == [1, 3, 1]
    left = session.sql("select f_v, d_v from f left join d on f_k = d_k "
                       "order by f_v").to_pandas()
    assert left.f_v.tolist() == [10, 20, 30, 40, 50, 60]
    assert [None if v != v else int(v) for v in left.d_v] == \
        [None, None, 1, None, 3, 1]


def test_comma_joins_take_their_conditions_from_where(session):
    _keyed(session, "a", [1, 2, 3, 4], [1, 2, 3, 4])
    _keyed(session, "b", [2, 3, 4, 5], [20, 30, 40, 50])
    _keyed(session, "c", [3, 4, 5, 6], [300, 400, 500, 600])
    comma = session.sql(
        "select a_v, b_v, c_v from a, b, c "
        "where a_k = b_k and c_k = b_k and a_v + c_v > 303 order by a_v")
    plan = session.plan(comma.plan).tree_string()
    assert plan.count("TpuHashJoinExec[inner") == 2
    explicit = session.sql(
        "select a_v, b_v, c_v from a join b on a_k = b_k "
        "join c on c_k = b_k where a_v + c_v > 303 order by a_v")
    assert comma.to_pandas().values.tolist() == \
        explicit.to_pandas().values.tolist() == [[4, 40, 400]]
    # no condition between the two: a cross join, filtered above
    cross = session.sql("select a_v, b_v from a, b where a_v = 1 "
                        "order by b_v").to_pandas()
    assert cross.b_v.tolist() == [20, 30, 40, 50]


def test_q7_counters_and_span(tmp_path, fixed_tables):
    """What the benchmark's new per-layer metrics read: validity bytes
    uploaded, join rows in and out, the decimal conversion's span."""
    from spark_rapids_tpu.exec.join import join_metrics
    from spark_rapids_tpu.utils.hostsync import upload_metrics
    tables = dict(fixed_tables, **gen.gen_tables(
        ["store_sales", "item", "promotion"], SF, 9))
    session = _session_over(tables, tmp_path,
                            {"spark.rapids.tpu.trace.enabled": True})
    up0, join0 = upload_metrics.snapshot(), join_metrics.snapshot()
    session.sql(Q7_TEXT).to_pandas()
    up1, join1 = upload_metrics.snapshot(), join_metrics.snapshot()
    assert 0 < up1["validity_bytes"] - up0["validity_bytes"] \
        < up1["bytes"] - up0["bytes"]
    ss = tables["store_sales"]
    probe = join1["probe_rows"] - join0["probe_rows"]
    output = join1["output_rows"] - join0["output_rows"]
    # the first join is probed by every fact row, NULL keys included
    assert ss.num_rows <= probe < 2 * ss.num_rows
    assert 0 < output < ss.num_rows // 10
    points = session.last_span_stats["points"]
    assert points["scan.convert.decimal"]["count"] >= 3
    assert points["scan.convert.decimal"]["exclusiveMs"] \
        <= points["scan.convert"]["ms"]


def _joins(exec_plan):
    from spark_rapids_tpu.exec.join import TpuHashJoinExec
    found, stack = [], [exec_plan]
    while stack:
        node = stack.pop()
        if isinstance(node, TpuHashJoinExec):
            found.append(node)
        stack.extend(node.children)
    return found


def test_join_gathers_only_the_columns_read_above(session):
    """A pruned column is a null placeholder carried for its ordinal: the
    join gathers the columns something above reads and makes the others
    anew, whatever their type."""
    session.create_dataframe(pa.table({
        "f_k": pa.array([1, None, 2, 3, 2], type=pa.int32()),
        "f_v": pa.array([10, 20, 30, 50, 60], type=pa.int64()),
        "f_s": pa.array(["a", "b", None, "d", "e"]),
        "f_m": pa.array([decimal.Decimal("1.50"), None] * 2
                        + [decimal.Decimal("-2.25")], pa.decimal128(7, 2)),
    })).createOrReplaceTempView("lf")
    session.create_dataframe(pa.table({
        "d_k": pa.array([2, None, 3, 4], type=pa.int32()),
        "d_s": pa.array(["two", "none", "three", "four"]),
        "d_v": pa.array([1, 2, 3, 4], type=pa.int64()),
    })).createOrReplaceTempView("ld")
    narrow = session.sql("select f_v, d_s from lf, ld where f_k = d_k "
                         "order by f_v")
    (join,) = _joins(session.plan(narrow.plan))
    assert join.live_columns == {"f_v", "d_s"}
    assert narrow.to_pandas().values.tolist() == [
        [30, "two"], [50, "three"], [60, "two"]]
    # every column asked for: every column gathered, NULLs where they were
    wide = session.sql("select * from lf join ld on f_k = d_k "
                       "order by f_v").to_pandas()
    assert wide.f_s.isna().tolist() == [True, False, False]
    assert wide.f_s.tolist()[1:] == ["d", "e"]
    assert wide.d_v.tolist() == [1, 3, 1]
    assert wide.f_m.tolist() == [decimal.Decimal("1.50"), None,
                                 decimal.Decimal("-2.25")]
    # an outer join's unmatched rows, with the build side's columns dead
    left = session.sql("select f_v, f_s from lf left join ld on f_k = d_k "
                       "order by f_v")
    (join,) = _joins(session.plan(left.plan))
    assert join.live_columns == {"f_v", "f_s"}
    assert left.collect() == [
        (10, "a"), (20, "b"), (30, None), (50, "d"), (60, "e")]
    # a residual condition's columns are read above the join
    residual = session.sql("select f_v from lf join ld on f_k = d_k "
                           "and f_v > d_v * 20 order by f_v")
    (join,) = _joins(session.plan(residual.plan))
    assert join.live_columns == {"f_v", "d_v"}
    assert residual.to_pandas().f_v.tolist() == [30, 60]
