"""IO layer tests: reader strategies, pushdown, partition discovery,
writers (parquet_test/orc_test/csv_test miniature)."""

import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession


@pytest.fixture(scope="module")
def session():
    return TpuSession()


def _write_files(tmp_path, n_files=4, rows_per_file=100):
    paths = []
    for i in range(n_files):
        pdf = pd.DataFrame({
            "id": np.arange(i * rows_per_file, (i + 1) * rows_per_file),
            "grp": np.arange(rows_per_file) % 5,
            "name": [f"f{i}-r{j}" for j in range(rows_per_file)],
        })
        p = str(tmp_path / f"part-{i}.parquet")
        pq.write_table(pa.Table.from_pandas(pdf), p)
        paths.append(p)
    return paths


@pytest.mark.parametrize("reader_type",
                         ["PERFILE", "COALESCING", "MULTITHREADED"])
def test_multifile_strategies(session, tmp_path, reader_type):
    paths = _write_files(tmp_path)
    s = TpuSession({"spark.rapids.sql.format.parquet.reader.type":
                    reader_type})
    df = s.read.parquet(*paths)
    out = df.to_pandas().sort_values("id").reset_index(drop=True)
    assert len(out) == 400
    assert out["id"].tolist() == list(range(400))
    assert out["name"][399] == "f3-r99"


def test_predicate_pushdown_into_scan(session, tmp_path):
    paths = _write_files(tmp_path)
    df = session.read.parquet(*paths).filter(F.col("id") >= 350)
    plan = session.plan(df.plan)
    assert "pushdown" in plan.tree_string()
    out = df.to_pandas()
    assert sorted(out["id"].tolist()) == list(range(350, 400))


def test_column_pruning(session, tmp_path):
    paths = _write_files(tmp_path)
    df = session.read.parquet(*paths).select("id")
    exec_plan = session.plan(df.plan)
    scan = exec_plan
    while scan.children:
        scan = scan.children[0]
    assert scan.columns == ["id"]
    assert df.to_pandas()["id"].count() == 400


def test_parquet_write_roundtrip(session, tmp_path):
    pdf = pd.DataFrame({"a": range(100), "s": [f"x{i}" for i in range(100)]})
    df = session.create_dataframe(pdf)
    out_path = str(tmp_path / "out")
    stats = df.write.parquet(out_path)
    assert stats.num_rows == 100 and stats.num_files >= 1
    back = session.read.parquet(out_path).to_pandas() \
        .sort_values("a").reset_index(drop=True)
    pd.testing.assert_frame_equal(back, pdf, check_dtype=False)


def test_partitioned_write_and_discovery(session, tmp_path):
    pdf = pd.DataFrame({"k": [1, 2, 1, 2, 3], "v": [10., 20., 30., 40., 50.]})
    out_path = str(tmp_path / "parts")
    stats = session.create_dataframe(pdf).write.partitionBy("k") \
        .parquet(out_path)
    assert stats.num_partitions == 3
    assert any("k=1" in d for d in os.listdir(out_path))
    back = session.read.parquet(out_path).to_pandas()
    assert sorted(back.columns) == ["k", "v"]
    assert back["v"].sum() == 150.0
    # partition-column filter works (hive discovery)
    got = session.read.parquet(out_path).filter(F.col("k") == 1).to_pandas()
    assert sorted(got["v"].tolist()) == [10., 30.]


def test_write_modes(session, tmp_path):
    pdf = pd.DataFrame({"a": [1, 2, 3]})
    path = str(tmp_path / "m")
    df = session.create_dataframe(pdf)
    df.write.parquet(path)
    with pytest.raises(FileExistsError):
        df.write.parquet(path)
    df.write.mode("append").parquet(path)
    assert session.read.parquet(path).count() == 6
    df.write.mode("overwrite").parquet(path)
    assert session.read.parquet(path).count() == 3
    df.write.mode("ignore").parquet(path)
    assert session.read.parquet(path).count() == 3


def test_csv_read(session, tmp_path):
    pdf = pd.DataFrame({"x": [1, 2, 3], "y": ["a", "b", "c"]})
    p = str(tmp_path / "t.csv")
    pdf.to_csv(p, index=False)
    out = session.read.csv(p).to_pandas()
    pd.testing.assert_frame_equal(out, pdf, check_dtype=False)


def test_orc_roundtrip(session, tmp_path):
    pdf = pd.DataFrame({"a": range(10), "b": np.linspace(0, 1, 10)})
    path = str(tmp_path / "orc_out")
    session.create_dataframe(pdf).write.orc(path)
    back = session.read.orc(path).to_pandas().sort_values("a") \
        .reset_index(drop=True)
    pd.testing.assert_frame_equal(back, pdf, check_dtype=False)


def test_scan_string_columns_and_placeholders_from_buffers(tmp_path):
    """Read string columns come from Arrow's buffers and pruned ones are
    the all-NULL column a list of None would give, with no row listed."""
    from spark_rapids_tpu.columnar import dtypes as dts
    from spark_rapids_tpu.columnar.column import Column, string_metrics
    from spark_rapids_tpu.io.readers import TpuFileScanExec
    from tests.test_columnar import _assert_same_layout
    n = 3000
    table = pa.table({
        "k": pa.array(np.arange(n, dtype=np.int64)),
        "flag": pa.array([None if i % 11 == 0 else "ANR"[i % 3]
                          for i in range(n)]),
        "comment": pa.array([f"cömment {i}" * (i % 4) for i in range(n)]),
        "mode": pa.array(["AIR", "RAIL"] * (n // 2)),
    })
    path = str(tmp_path / "t.parquet")
    pq.write_table(table, path, row_group_size=1000)
    schema = [("k", dts.INT64), ("flag", dts.STRING),
              ("comment", dts.STRING), ("mode", dts.STRING)]
    scan = TpuFileScanExec([path], "parquet", schema, batch_rows=1024,
                           columns=["k", "flag", "comment"])
    before = string_metrics.snapshot()
    batches = list(scan.execute())
    moved = {k: v - before[k] for k, v in string_metrics.snapshot().items()}
    assert moved == {"string_rows_buffered": 2 * n,
                     "string_placeholder_rows": n, "string_rows_listed": 0}
    assert sum(b.nrows for b in batches) == n and len(batches) > 1
    rows = {name: [] for name, _ in schema}
    for b in batches:
        assert b.names == [name for name, _ in schema]
        want = Column.from_strings([None] * b.nrows, capacity=b.capacity)
        _assert_same_layout(b.columns["mode"], want)
        for name in rows:
            rows[name] += b.columns[name].to_pylist()
    assert rows["mode"] == [None] * n
    for name in ("k", "flag", "comment"):
        assert rows[name] == table.column(name).to_pylist()
