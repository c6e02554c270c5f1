"""Columnar core round-trip tests (Column/ColumnarBatch host<->device)."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.column import Column, bucket_capacity
from spark_rapids_tpu.columnar.batch import ColumnarBatch, empty_batch
from spark_rapids_tpu.columnar import dtypes as dts


def test_bucket_capacity():
    assert bucket_capacity(0) == 1024
    assert bucket_capacity(1) == 1024
    assert bucket_capacity(1024) == 1024
    assert bucket_capacity(1025) == 2048
    assert bucket_capacity(1 << 20) == 1 << 20


def test_int_column_roundtrip():
    vals = np.arange(10, dtype=np.int64)
    col = Column.from_numpy(vals)
    assert col.dtype is dts.INT64
    assert col.nrows == 10 and col.capacity == 1024
    assert not col.has_nulls
    np.testing.assert_array_equal(col.to_numpy(), vals)
    assert col.to_pylist() == list(range(10))


def test_nullable_column():
    vals = np.array([1.5, 2.5, 3.5])
    validity = np.array([True, False, True])
    col = Column.from_numpy(vals, validity=validity)
    assert col.has_nulls and col.null_count() == 1
    assert col.to_pylist() == [1.5, None, 3.5]


def test_string_column_roundtrip():
    strings = ["hello", "", None, "wörld", "tpu"]
    col = Column.from_strings(strings)
    assert col.dtype.is_string
    assert col.nrows == 5
    assert col.to_pylist() == strings
    arrow = col.to_arrow()
    assert arrow.to_pylist() == strings


def test_arrow_roundtrip_types():
    table = pa.table({
        "i32": pa.array([1, 2, None], type=pa.int32()),
        "f64": pa.array([1.0, None, 3.0], type=pa.float64()),
        "b": pa.array([True, False, None]),
        "s": pa.array(["a", None, "ccc"]),
        "ts": pa.array([1, 2, 3], type=pa.timestamp("us", tz="UTC")),
        "d": pa.array([10, 20, None], type=pa.date32()),
    })
    batch = ColumnarBatch.from_arrow(table)
    assert batch.nrows == 3
    out = batch.to_arrow()
    assert out.column("i32").to_pylist() == [1, 2, None]
    assert out.column("f64").to_pylist() == [1.0, None, 3.0]
    assert out.column("b").to_pylist() == [True, False, None]
    assert out.column("s").to_pylist() == ["a", None, "ccc"]
    assert out.column("d").to_pylist() == table.column("d").to_pylist()


def test_pandas_roundtrip():
    import pandas as pd
    df = pd.DataFrame({"x": [1, 2, 3], "y": ["a", "b", "c"],
                       "z": [0.1, 0.2, 0.3]})
    batch = ColumnarBatch.from_pandas(df)
    out = batch.to_pandas()
    pd.testing.assert_frame_equal(out, df, check_dtype=False)


def test_from_pydict_with_nones():
    batch = ColumnarBatch.from_pydict({
        "a": [1, None, 3],
        "s": ["x", None, "z"],
    })
    assert batch.column("a").to_pylist() == [1, None, 3]
    assert batch.column("s").to_pylist() == ["x", None, "z"]


def test_batch_select_rename_with_column():
    batch = ColumnarBatch.from_pydict({"a": [1, 2], "b": [3, 4]})
    sel = batch.select(["b"])
    assert sel.names == ["b"]
    ren = batch.rename({"a": "aa"})
    assert set(ren.names) == {"aa", "b"}
    wc = batch.with_column("c", Column.from_numpy(np.array([9, 9])))
    assert wc.column("c").to_pylist() == [9, 9]


def test_empty_batch():
    b = empty_batch([("x", dts.INT64), ("s", dts.STRING)])
    assert b.nrows == 0
    assert b.to_arrow().num_rows == 0


def test_decimal_type():
    d = dts.DecimalType(10, 2)
    assert d.precision == 10 and d.scale == 2
    with pytest.raises(ValueError):
        dts.DecimalType(19, 0)
    arr = pa.array([None, 1, 2], type=pa.decimal128(10, 2))
    col = Column.from_arrow(arr)
    out = col.to_pylist()
    assert out[0] is None and float(out[1]) == 1.0


def test_mismatched_nrows_raises():
    a = Column.from_numpy(np.arange(3))
    b = Column.from_numpy(np.arange(4))
    with pytest.raises(ValueError):
        ColumnarBatch({"a": a, "b": b})


def test_conf_registry():
    from spark_rapids_tpu.config.rapids_conf import (
        RapidsConf, SQL_ENABLED, BATCH_SIZE_BYTES, EXPLAIN)
    conf = RapidsConf()
    assert conf.sql_enabled is True
    assert conf.batch_size_bytes == 1 << 31
    conf2 = conf.set("spark.rapids.sql.enabled", "false")
    assert conf2.get(SQL_ENABLED) is False
    with pytest.raises(ValueError):
        conf.set("spark.rapids.sql.explain", "BOGUS").get(EXPLAIN)
    docs = RapidsConf.generate_docs()
    assert "spark.rapids.sql.batchSizeBytes" in docs


def _assert_same_layout(got: Column, want: Column):
    assert got.dtype == want.dtype and got.nrows == want.nrows
    for read in ("host_offsets", "host_values", "host_validity"):
        g, w = getattr(got, read)(), getattr(want, read)()
        assert (g is None) == (w is None), read
        if w is not None:
            assert g.dtype == w.dtype and g.shape == w.shape, read
            np.testing.assert_array_equal(g, w, err_msg=read)


def _null_slot_with_bytes():
    # Arrow does not promise that a NULL slot is empty: rows "ab", NULL
    # (holding "cd"), "e", NULL (empty), "fgh"
    return pa.Array.from_buffers(
        pa.string(), 5,
        [pa.py_buffer(np.packbits([1, 0, 1, 0, 1], bitorder="little")),
         pa.py_buffer(np.array([0, 2, 4, 5, 5, 8], dtype=np.int32)),
         pa.py_buffer(b"abcdefgh")], null_count=2)


_ARROW_STRING_CASES = {
    "ascii": lambda: pa.array(["hello", "tpu", "a", "columnar"]),
    "utf8": lambda: pa.array(["wörld", "日本語", "a", "ß", "👍 ok"]),
    "empty_strings": lambda: pa.array(["", "x", "", ""]),
    "nulls": lambda: pa.array(["a", None, "", None, "bcd"] * 5),
    "all_null": lambda: pa.array([None] * 9, type=pa.string()),
    "zero_rows": lambda: pa.array([], type=pa.string()),
    "slice": lambda: pa.array(
        [None if i % 7 == 3 else "r%d" % i for i in range(100)]
    ).slice(13, 61),
    "chunked": lambda: pa.chunked_array(
        [pa.array(["a", None, "bc"]), pa.array([], type=pa.string()),
         pa.array(["déf", ""]).slice(1), pa.array(["ghi"] * 40).slice(3, 9)]),
    "large_string": lambda: pa.array(["big", None, "offsets", ""],
                                     type=pa.large_string()).slice(1),
    "dictionary": lambda: pa.array(
        ["N", "R", None, "A", "N", "N"]).dictionary_encode(),
    "dictionary_null_value": lambda: pa.DictionaryArray.from_arrays(
        np.array([2, 0, 1, 1, 0], dtype=np.int32),
        pa.array(["N", None, "A"], type=pa.string())),
    "capacity": lambda: pa.array(["p", "qr", None]),
    "null_slot_with_bytes": _null_slot_with_bytes,
}


@pytest.mark.parametrize("case", sorted(_ARROW_STRING_CASES))
def test_from_arrow_strings_match_from_strings(case):
    arr = _ARROW_STRING_CASES[case]()
    capacity = 4096 if case == "capacity" else None
    got = Column.from_arrow(arr, capacity=capacity)
    want = Column.from_strings(arr.to_pylist(), capacity=capacity)
    _assert_same_layout(got, want)
    assert got.to_pylist() == arr.to_pylist()
    if case == "null_slot_with_bytes":
        assert arr.to_pylist() == ["ab", None, "e", None, "fgh"]
        assert got.host_values()[:6].tobytes() == b"abefgh"


def test_from_arrow_strings_never_list_a_row(monkeypatch):
    """2^20 rows of two string columns become host columns with no list
    of Python objects: the loop such a list went through is patched to
    raise (pyarrow's own ``to_pylist`` sits on an immutable type and
    cannot be), and the counter says which way every row went."""
    from spark_rapids_tpu.columnar.column import string_metrics
    n = 1 << 20
    flags = np.array(["A", "N", "R"])[np.arange(n) % 3]
    table = pa.table({"flag": pa.array(flags),
                      "note": pa.array(flags, mask=np.arange(n) % 5 == 0)})

    def listed(*args, **kwargs):
        raise AssertionError("a list of rows on the way to a host column")
    monkeypatch.setattr(Column, "from_strings", listed)
    before = string_metrics.snapshot()
    batch = ColumnarBatch.from_arrow(table)
    after = string_metrics.snapshot()
    assert after["string_rows_buffered"] - \
        before["string_rows_buffered"] == 2 * n
    assert after["string_rows_listed"] == before["string_rows_listed"]
    assert after["string_placeholder_rows"] == \
        before["string_placeholder_rows"]
    flag, note = batch.columns["flag"], batch.columns["note"]
    assert flag.nrows == n and flag.capacity == n and not flag.has_nulls
    np.testing.assert_array_equal(
        flag.host_offsets(), np.arange(n + 1, dtype=np.int32))
    assert flag.host_values()[:6].tobytes() == b"ANRANR"
    assert note.null_count() == -(-n // 5)
    assert int(note.host_offsets()[-1]) == n - note.null_count()


def test_from_arrow_large_string_past_int32_is_refused():
    # offsets that span 2^31 chars over a data buffer that only claims to
    # be that long: the column is refused before a char is read
    offsets = np.array([0, 5, 1 << 31, (1 << 31) + 1], dtype=np.int64)
    five = pa.py_buffer(b"hello")
    data = pa.foreign_buffer(five.address, (1 << 31) + 1, base=five)
    arr = pa.Array.from_buffers(
        pa.large_string(), 3, [None, pa.py_buffer(offsets), data])
    with pytest.raises(ValueError, match="int32"):
        Column.from_arrow(arr)
