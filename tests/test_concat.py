"""``ops/concat.concat_batches`` against a plain numpy reference.

The reference is written here, not borrowed from any kernel: rows
``[0, total)`` are the inputs' rows one after another, and the padding
contract is what every concat since PR 2 has left behind it: values 0,
validity False, offsets equal to the final offset on ``[total, cap]``.
Inputs are built with garbage in their own padding (values, validity
True, offsets that fall back to 0), so a block copy that let any of it
through would show.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar import dtypes as dts
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import (Column, RowCount,
                                              bucket_capacity)
from spark_rapids_tpu.ops import concat
from spark_rapids_tpu.utils.hostsync import host_sync_metrics

FIXED = {
    "int32": dts.INT32,
    "int64": dts.INT64,
    "float64": dts.FLOAT64,
    "bool": dts.BOOL,
    "date": dts.DATE32,
}

# (rows, capacity) of each input.  Every list has unequal capacities
# and row counts; the names say what else each is for.
LAYOUTS = {
    "two": [(700, 1024), (1500, 2048)],
    # an input with no rows whose count is known only on the device
    # cannot be dropped by the host: it is placed like any other
    "empty_in_the_middle": [(1000, 1024), (0, 1024), (24, 2048)],
    # total 2040 -> capacity 2048; the last input starts at 2039 with
    # 4096 rows of capacity, far past the output: a clamped
    # dynamic_update_slice would shift it to row 0
    "last_block_overhangs": [(1015, 1024), (1024, 1024), (1, 4096)],
    # a wide early input whose padding outlives the narrower ones after
    "wide_padding_first": [(3, 4096), (1024, 1024), (5, 1024)],
    "nine": [(n, bucket_capacity(n)) for n in
             (1, 1024, 17, 2047, 1025, 3, 4096, 500, 1)],
}


def _values(kind, n, rng):
    if kind == "bool":
        return rng.integers(0, 2, n).astype(np.bool_)
    if kind == "float64":
        return rng.standard_normal(n) * 1e6
    if kind == "int64":
        return rng.integers(-2**62, 2**62, n, dtype=np.int64)
    return rng.integers(-2**30, 2**30, n).astype(np.int32)


def _fixed_column(kind, rows, cap, nulls, rng):
    """A column with garbage in its padding, and what its rows are."""
    dt = FIXED[kind]
    data = _values(kind, cap, rng).astype(dt.storage)
    validity = None
    valid_rows = np.ones(rows, dtype=np.bool_)
    if nulls:
        validity = np.ones(cap, dtype=np.bool_)  # padding claims valid
        validity[:rows] = valid_rows = rng.random(rows) < 0.7
    return Column(dt, data, rows, validity=validity), \
        data[:rows].copy(), valid_rows


def _string_column(rows, cap, nulls, rng):
    """Offsets fall back to 0 past the rows (not monotone) and the char
    buffer is full of letters past the last string."""
    lens = rng.integers(0, 9, rows)
    ends = np.cumsum(lens)
    nchars = int(ends[-1]) if rows else 0
    ccap = bucket_capacity(max(nchars, 1))
    chars = rng.integers(97, 123, ccap).astype(np.uint8)
    offsets = np.zeros(cap + 1, dtype=np.int32)
    offsets[1:rows + 1] = ends
    offsets[rows + 1:] = rng.integers(0, max(nchars, 1), cap - rows)
    valid_rows = np.ones(rows, dtype=np.bool_)
    validity = None
    if nulls:
        validity = np.ones(cap, dtype=np.bool_)
        validity[:rows] = valid_rows = rng.random(rows) < 0.7
    strings = [bytes(chars[offsets[i]:offsets[i + 1]]) for i in range(rows)]
    return Column(dts.STRING, chars, rows, validity=validity,
                  offsets=offsets), strings, valid_rows


def _make(kind, layout, nulls, seed, lazy=False):
    rng = np.random.default_rng(seed)
    batches, rows_of, valid_of = [], [], []
    for i, (rows, cap) in enumerate(layout):
        # with nulls, every other input carries a validity: the program
        # writes True for the ones that do not
        with_validity = nulls and i % 2 == 0
        if kind == "string":
            col, vals, valid = _string_column(rows, cap, with_validity, rng)
        else:
            col, vals, valid = _fixed_column(kind, rows, cap,
                                             with_validity, rng)
        if lazy:
            count = RowCount(device=jnp.int32(rows))
            col = Column(col.dtype, jnp.asarray(col._np_data), count,
                         validity=None if col._np_validity is None
                         else jnp.asarray(col._np_validity))
            batches.append(ColumnarBatch({"c": col}, count))
        else:
            batches.append(ColumnarBatch({"c": col}, rows))
        rows_of.append(vals)
        valid_of.append(valid)
    return batches, rows_of, valid_of


def _check_fixed(out, rows_of, valid_of, nulls, total, cap):
    col = out.column("c")
    assert col.capacity == cap
    data = np.asarray(col.data)
    want = np.concatenate(rows_of)
    assert data.dtype == want.dtype
    np.testing.assert_array_equal(data[:total], want)
    np.testing.assert_array_equal(data[total:],
                                  np.zeros(cap - total, dtype=want.dtype))
    _check_validity(col, valid_of, nulls, total, cap)


def _check_validity(col, valid_of, nulls, total, cap):
    if not nulls:
        assert col.validity is None
        return
    validity = np.asarray(col.validity)
    assert validity.shape == (cap,)
    np.testing.assert_array_equal(validity[:total],
                                  np.concatenate(valid_of))
    assert not validity[total:].any()


@pytest.mark.parametrize("nulls", [False, True], ids=["no_nulls", "nulls"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("kind", sorted(FIXED))
def test_fixed_width_against_numpy(kind, layout, nulls):
    shape = LAYOUTS[layout]
    batches, rows_of, valid_of = _make(kind, shape, nulls, seed=len(layout))
    out = concat.concat_batches(batches)
    total = sum(r for r, _ in shape)
    assert out.nrows == total
    _check_fixed(out, rows_of, valid_of, nulls, total,
                 bucket_capacity(total))


@pytest.mark.parametrize("nulls", [False, True], ids=["no_nulls", "nulls"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_strings_against_numpy(layout, nulls):
    shape = LAYOUTS[layout]
    batches, rows_of, valid_of = _make("string", shape, nulls, seed=7)
    out = concat.concat_batches(batches)
    total = sum(r for r, _ in shape)
    cap = bucket_capacity(total)
    col = out.column("c")
    assert out.nrows == total and col.capacity == cap
    want = [s for strings in rows_of for s in strings]
    nchars = sum(len(s) for s in want)
    offsets = np.asarray(col.offsets)
    chars = np.asarray(col.data)
    assert offsets.shape == (cap + 1,) and offsets[0] == 0
    assert chars.shape == (bucket_capacity(max(nchars, 1)),)
    got = [bytes(chars[offsets[i]:offsets[i + 1]]) for i in range(total)]
    assert got == want
    # padding: offsets repeat the final offset, chars are 0 past it
    np.testing.assert_array_equal(offsets[total:],
                                  np.full(cap + 1 - total, nchars))
    assert not chars[nchars:].any()
    _check_validity(col, valid_of, nulls, total, cap)


@pytest.mark.parametrize("nulls", [False, True], ids=["no_nulls", "nulls"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("kind", ["int64", "float64", "bool"])
def test_device_resident_counts_concat_without_a_sync(kind, layout, nulls):
    shape = LAYOUTS[layout]
    batches, rows_of, valid_of = _make(kind, shape, nulls, seed=3, lazy=True)
    before = host_sync_metrics.snapshot()
    out = concat.concat_batches(batches)
    jax.block_until_ready(out.column("c").data)
    assert host_sync_metrics.snapshot() == before
    assert not out.row_count.is_concrete
    # the capacity is bounded by the inputs' capacities, not their rows
    cap = bucket_capacity(sum(c for _, c in shape))
    total = sum(r for r, _ in shape)
    _check_fixed(out, rows_of, valid_of, nulls, total, cap)
    assert out.nrows == total  # the one sync, the reader's


def test_string_char_totals_come_in_one_counted_fetch():
    rng = np.random.default_rng(11)
    batches = []
    for rows, cap in LAYOUTS["nine"]:
        a, _, _ = _string_column(rows, cap, False, rng)
        b, _, _ = _string_column(rows, cap, True, rng)
        batches.append(ColumnarBatch({"a": a, "b": b}, rows))
    before = host_sync_metrics.snapshot()
    concat.concat_batches(batches)
    assert host_sync_metrics.snapshot() - before == 1


def test_known_empty_inputs_are_dropped_and_one_input_passes_through():
    (full, empty), _, _ = _make("int64", [(10, 1024), (0, 1024)], False, 1)
    assert concat.concat_batches([empty, full, empty]) is full
    assert concat.concat_batches([empty, empty]) is empty


def test_mixed_schema_batch():
    rng = np.random.default_rng(5)
    batches, want = [], {"k": [], "s": [], "d": []}
    for rows, cap in LAYOUTS["last_block_overhangs"]:
        k, kv, _ = _fixed_column("int64", rows, cap, False, rng)
        s, sv, _ = _string_column(rows, cap, False, rng)
        d, dv, _ = _fixed_column("float64", rows, cap, False, rng)
        batches.append(ColumnarBatch({"k": k, "s": s, "d": d}, rows))
        want["k"].append(kv)
        want["d"].append(dv)
        want["s"].extend(sv)
    out = concat.concat_batches(batches)
    assert out.names == ["k", "s", "d"]
    np.testing.assert_array_equal(out.column("k").to_numpy(),
                                  np.concatenate(want["k"]))
    np.testing.assert_array_equal(out.column("d").to_numpy(),
                                  np.concatenate(want["d"]))
    assert [s.encode() for s in out.column("s").to_pylist()] == want["s"]


# ------------------------------------------------------------ structure --
def _lowered(kind, layout, nulls):
    """StableHLO of the program a concat of this layout runs."""
    cap = bucket_capacity(sum(r for r, _ in layout))
    counts = jax.ShapeDtypeStruct((len(layout),), jnp.int32)
    valids = tuple(
        jax.ShapeDtypeStruct((c,), jnp.bool_) if nulls and i % 2 == 0
        else None for i, (_, c) in enumerate(layout))
    if kind == "string":
        char_cap = 1 << 16
        chars = tuple(jax.ShapeDtypeStruct((char_cap // 4,), jnp.uint8)
                      for _ in layout)
        offsets = tuple(jax.ShapeDtypeStruct((c + 1,), jnp.int32)
                        for _, c in layout)
        return cap, char_cap, jax.jit(
            concat._make_concat_string(cap, char_cap)).lower(
                chars, offsets, valids, counts, counts).as_text()
    datas = tuple(jax.ShapeDtypeStruct((c,), FIXED[kind].storage)
                  for _, c in layout)
    return cap, 0, jax.jit(concat._make_concat_fixed(cap)).lower(
        datas, valids, counts).as_text()


def _index_lengths(text):
    """Element counts of the index operands of every gather and scatter
    in a StableHLO module (the second operand's tensor type)."""
    out = []
    for line in text.splitlines():
        if not re.search(r"stablehlo\.(dynamic_)?(gather|scatter)", line):
            continue
        types = re.findall(r"tensor<([0-9x]*)x?[a-z0-9]+>",
                           line.split(" : ", 1)[1])
        dims = [int(d) for d in types[1].split("x") if d]
        out.append(int(np.prod(dims)) if dims else 1)
    return out


@pytest.mark.parametrize("nulls", [False, True], ids=["no_nulls", "nulls"])
@pytest.mark.parametrize("kind", ["int64", "float64", "string"])
def test_no_gather_as_long_as_the_output(kind, nulls):
    """Placement is a block copy: the lowered program holds no gather
    (or scatter) whose index operand grows with the output.  The old
    appends indexed ``in_vals[src]`` with ``src`` as long as the output
    capacity, 10 ns an element on the chip."""
    layout = LAYOUTS["nine"]
    cap, char_cap, text = _lowered(kind, layout, nulls)
    assert "dynamic_update_slice" in text
    smallest_buffer = min(c for _, c in layout)
    for n in _index_lengths(text):
        assert n < smallest_buffer, (n, cap, char_cap)


def test_the_gather_detector_sees_the_old_append():
    def old_append(out_vals, out_n, in_vals, in_n):
        pos = jnp.arange(out_vals.shape[0], dtype=jnp.int32)
        src = jnp.clip(pos - out_n, 0, in_vals.shape[0] - 1)
        write = (pos >= out_n) & (pos < out_n + in_n)
        return jnp.where(write, in_vals[src], out_vals)
    text = jax.jit(old_append).lower(
        jax.ShapeDtypeStruct((8192,), jnp.int64), jnp.int32(0),
        jax.ShapeDtypeStruct((1024,), jnp.int64), jnp.int32(0)).as_text()
    assert max(_index_lengths(text)) == 8192


def test_programs_are_keyed_by_capacities_not_row_counts():
    """Two concats of the same capacities and different row counts run
    the same compiled program."""
    first, _, _ = _make("int64", [(100, 1024), (900, 1024)], False, 1)
    second, _, _ = _make("int64", [(1000, 1024), (3, 1024)], False, 2)
    concat.concat_batches(first)
    fn = concat.cached_jit(("concat_fixed", 1024), None)
    compiled = fn._jit._cache_size()
    concat.concat_batches(second)
    assert fn._jit._cache_size() == compiled
