"""What the chip's compiler says about the main path, asked without a chip.

The TPU compiler is installed in the sandbox and compiles for a chip that
is described, not attached (``v5e:2x2``).  Nothing runs here: these tests
only prove the programs COMPILE for the chip — a kernel Mosaic refuses or
an op the X64 rewriter cannot lower fails here, at no chip time.  The
topology is described inside the module-scoped fixture only (never at
import): one process at a time may load the TPU library, and under
pytest-xdist every worker imports this file.

No 64-bit sort program is compiled at a real capacity here: each takes
the chip's compiler one to two minutes.  The join, filter-stage and
group-by programs that lead the ledger's ``device_ops`` are compiled at
``SMALL`` rows, a few seconds each: what the compiler refuses (an op the
X64 rewriter cannot lower, a layout Mosaic will not take) does not
depend on the row count.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from spark_rapids_tpu.columnar import dtypes as dts
from spark_rapids_tpu.ops import pallas_kernels as pk
from spark_rapids_tpu.ops.expressions import ColVal
from spark_rapids_tpu.parallel import shuffle
from spark_rapids_tpu.parallel.mesh import shard_map

ROWS = 1 << 22
SMALL = 1 << 12
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.parametrize("parts", [4, 8, 16])
def test_partition_histogram_compiles(one_chip, parts):
    compiled = jax.jit(
        lambda pids, mask: pk.partition_histogram(
            pids, mask, parts, interpret=False)
    ).lower(_spec((ROWS,), jnp.int32, one_chip),
            _spec((ROWS,), jnp.bool_, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# an int64 key, a double, a flag and a date, the first two nullable
WIRE_DTYPES = [dts.INT64, dts.FLOAT64, dts.BOOL, dts.INT32]
WIRE_NULLABLE = [True, True, False, False]


def _wire_cols(values, masks):
    return [ColVal(dt, v, m if nullable else None)
            for dt, v, m, nullable
            in zip(WIRE_DTYPES, values, masks, WIRE_NULLABLE)]


def _wire_specs(rows, sharding):
    values = tuple(_spec((rows,), dt.storage, sharding)
                   for dt in WIRE_DTYPES)
    masks = tuple(_spec((rows,), jnp.bool_, sharding)
                  for _ in WIRE_DTYPES)
    return values, masks


def test_shuffle_pack_and_unpack_compile(one_chip):
    """The packed wire's send and receive sides, each its own program so
    the compiler cannot cancel a cast against its inverse.  A double
    rides the f64 lane group as itself: the X64 rewriter refuses every
    ``bitcast-convert`` of an f64 operand."""
    values, masks = _wire_specs(ROWS, one_chip)
    plan = shuffle._plan_pack(_wire_cols(values, masks))
    assert plan.lanes == {"u32": 3, "u8": 2, "f64": 1}

    def pack(values, masks):
        return shuffle._pack_payloads(_wire_cols(values, masks), plan)

    packed = jax.jit(pack).lower(values, masks).compile()
    payloads = {g: _spec(s.shape, s.dtype, one_chip) for g, s
                in jax.eval_shape(pack, values, masks).items()}

    def unpack(flat, in_range):
        cols = shuffle._unpack_payloads(
            _wire_cols(values, masks), plan, flat, in_range)
        return [(c.values, c.validity) for c in cols]

    unpacked = jax.jit(unpack).lower(
        payloads, _spec((ROWS,), jnp.bool_, one_chip)).compile()
    for compiled in (packed, unpacked):
        assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("kind", ["int64", "float64", "date", "string"])
def test_concat_programs_compile_without_a_gather(one_chip, kind):
    """q3's shape: six inputs of 2^19 rows into 2^21, every other input
    with a validity.  The scratch is ``lax.empty`` (an ``AllocateBuffer``
    the X64 rewriter has to split for 64-bit lanes), the blocks are
    ``dynamic-update-slice`` ops, and no gather is left in what the
    chip's compiler makes of it."""
    from spark_rapids_tpu.ops import concat
    k, in_cap, cap = 6, 1 << 19, 1 << 21
    counts = _spec((k,), jnp.int32, one_chip)
    valids = tuple(_spec((in_cap,), jnp.bool_, one_chip) if i % 2 else None
                   for i in range(k))
    if kind == "string":
        chars = tuple(_spec((1 << 22,), jnp.uint8, one_chip)
                      for _ in range(k))
        offsets = tuple(_spec((in_cap + 1,), jnp.int32, one_chip)
                        for _ in range(k))
        compiled = jax.jit(concat._make_concat_string(cap, 1 << 24)).lower(
            chars, offsets, valids, counts, counts).compile()
    else:
        storage = {"int64": jnp.int64, "float64": jnp.float64,
                   "date": jnp.int32}[kind]
        datas = tuple(_spec((in_cap,), storage, one_chip) for _ in range(k))
        compiled = jax.jit(concat._make_concat_fixed(cap)).lower(
            datas, valids, counts).compile()
    text = compiled.as_text()
    assert "dynamic-update-slice" in text
    assert " gather(" not in text and " scatter(" not in text
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("capacity", [1 << 19, 1 << 20])
def test_join_out_starts_compiles_without_a_wide_scan(one_chip, capacity):
    """q7's and q3's probe capacities.  As one int64 ``cumsum`` this
    program took the chip's compiler 70 to 100 s a capacity, and one
    cold compile of q7 died in it (PERF.md, PR 30); as 32-bit rows of
    1,024 it takes under 2 s.  No reduce-window is wider than a row."""
    import re
    from spark_rapids_tpu.ops import joins as J
    from spark_rapids_tpu.ops import selection
    lowered = J.join_out_starts.lower(
        _spec((capacity,), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip), outer=False)
    windows = [max(int(d) for d in m.split(","))
               for m in re.findall(r"window_dimensions = array<i64: ([\d, ]+)>",
                                   lowered.as_text())]
    assert windows and max(windows) <= selection.SCAN_BLOCK
    assert _device_bytes(lowered.compile()) < HBM_BYTES


@pytest.mark.parametrize("p_cap,b_cap", [(1 << 19, 1 << 21),
                                         (1 << 18, 1 << 23)])
def test_join_gather_rows_compiles_without_a_search(one_chip, p_cap, b_cap):
    """q9's pair expansions: 2^19 output rows from a probe batch of 2^19
    (orders built at 2^21) and of 2^18 (the parts, lineitem built at
    2^23).  The probe row of an output row was a binary search in the
    int64 ends, a ``while`` of 19 dependent gathers and 321 to 453 ms a
    launch on the chip (PERF.md, PR 37); it is a histogram and a prefix
    sum in rows of 1,024: no loop, no reduce-window wider than a row."""
    from spark_rapids_tpu.ops import joins as J
    from spark_rapids_tpu.ops import selection
    out_cap = 1 << 19
    i64 = _spec((p_cap,), jnp.int64, one_chip)
    i32 = _spec((p_cap,), jnp.int32, one_chip)
    lowered = J._gather_indices_kernel(out_cap).lower(
        i64, i64, i32, i32, _spec((b_cap + p_cap,), jnp.int32, one_chip),
        _spec((), jnp.int64, one_chip))
    text = lowered.as_text()
    assert "while" not in text
    windows = [max(int(d) for d in m.split(","))
               for m in re.findall(r"window_dimensions = array<i64: ([\d, ]+)>",
                                   text)]
    assert windows and max(windows) <= selection.SCAN_BLOCK
    compiled = lowered.compile()
    assert "while" not in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("storage,nullable",
                         [(jnp.int64, False), (jnp.int32, True)],
                         ids=["int64", "nullable_int32"])
def test_join_match_compiles(one_chip, storage, nullable):
    """``jit_join_match`` on q3's key (one int64) and on q7's (one
    nullable int32): the join's only match phase."""
    from spark_rapids_tpu.ops import joins as J

    def key():
        return [ColVal(None, _spec((SMALL,), storage, one_chip),
                       _spec((SMALL,), jnp.bool_, one_chip)
                       if nullable else None)]

    n = _spec((), jnp.int32, one_chip)
    compiled = J.join_match.lower(key(), key(), n, n).compile()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("with_string", [False, True],
                         ids=["int64_float64_date", "with_string"])
def test_filter_stage_with_compaction_compiles(one_chip, with_string):
    """``jit_filter_stage_*``: q1's row (a nullable int64, double and
    date, kept by a date predicate and compacted) and q3's, which also
    carries a string column through its char buffer.  The compaction's
    gathers stay inside a conditional (ops/selection.py ``compact``): a
    compiler that flattened it into a select would run them on every
    batch, the dense-prefix ones included."""
    from spark_rapids_tpu.ops import predicates as P
    from spark_rapids_tpu.ops.compiler import FilterStageFn
    from spark_rapids_tpu.ops.expressions import BoundReference, Literal
    row = [dts.INT64, dts.FLOAT64, dts.DATE32]
    flat = [(_spec((SMALL,), dt.storage, one_chip),
             _spec((SMALL,), jnp.bool_, one_chip), None) for dt in row]
    if with_string:
        row.append(dts.STRING)
        flat.append((_spec((16 * SMALL,), jnp.uint8, one_chip), None,
                     _spec((SMALL + 1,), jnp.int32, one_chip)))
    refs = [BoundReference(i, dt) for i, dt in enumerate(row)]
    stage = FilterStageFn(
        P.LessThanOrEqual(refs[2], Literal("1998-09-02", dts.DATE32)),
        refs, row)
    compiled = jax.jit(stage._run).lower(
        flat, _spec((), jnp.int32, one_chip)).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    assert len(re.findall(r" conditional\(", compiled.as_text())) == 1


def _agg_buffers(one_chip):
    # sum(double), avg(double) as its sum and count, count(*)
    return [(_spec((SMALL,), dt, one_chip), None)
            for dt in (jnp.float64, jnp.float64, jnp.int64, jnp.int64)]


def test_coded_groupby_compiles(one_chip):
    """``jit_coded_agg``, q1's group-by: two dictionary-coded keys
    addressed directly, sum / avg / count buffers, no sort."""
    from spark_rapids_tpu.exec.aggregate import _coded_kernel
    keys = [(_spec((SMALL,), jnp.int64, one_chip), None)] * 2
    ranges = _spec((2,), jnp.int64, one_chip)
    compiled = _coded_kernel(("sum",) * 4, 64).lower(
        keys, _agg_buffers(one_chip), ranges, ranges,
        _spec((SMALL,), jnp.bool_, one_chip)).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_string_groupby_stage_a_with_folded_filter_compiles(one_chip):
    """``jit_stage_*`` of q1 since its filter folds into the group-by:
    the two string keys and the aggregates' children evaluated, and the
    date predicate returned as the row mask ``jit_coded_agg`` takes —
    one program, no compaction."""
    from spark_rapids_tpu.ops import arithmetic as A
    from spark_rapids_tpu.ops import predicates as P
    from spark_rapids_tpu.ops.compiler import StageFn
    from spark_rapids_tpu.ops.expressions import BoundReference, Literal
    row = [dts.STRING, dts.STRING, dts.FLOAT64, dts.FLOAT64, dts.DATE32]
    flat = [(_spec((16 * SMALL,), jnp.uint8, one_chip), None,
             _spec((SMALL + 1,), jnp.int32, one_chip))] * 2
    flat += [(_spec((SMALL,), dt.storage, one_chip),
              _spec((SMALL,), jnp.bool_, one_chip), None)
             for dt in row[2:]]
    flag, status, price, discount, shipdate = [
        BoundReference(i, dt) for i, dt in enumerate(row)]
    stage = StageFn(
        [flag, status, price,
         A.Multiply(price, A.Subtract(Literal(1.0, dts.FLOAT64),
                                      discount))],
        row, conjuncts=[P.LessThanOrEqual(
            shipdate, Literal("1998-09-02", dts.DATE32))])
    lowered = jax.jit(stage._run).lower(
        flat, _spec((), jnp.int32, one_chip))
    cols, _, mask = lowered.out_info
    assert len(cols) == 4 and mask.shape == (SMALL,) \
        and mask.dtype == jnp.bool_
    assert _device_bytes(lowered.compile()) < HBM_BYTES


def test_sort_segment_groupby_compiles(one_chip):
    """The rung under the coded directory: one sparse int64 key sorted
    (``lexsort_i32``) and its runs reduced by segment."""
    from spark_rapids_tpu.exec.aggregate import _grouped_kernel
    keys = [(_spec((SMALL,), jnp.int64, one_chip), None)]
    compiled = _grouped_kernel(("sum",) * 4, 1).lower(
        keys, _agg_buffers(one_chip),
        _spec((), jnp.int32, one_chip)).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_decimal_average_compiles_without_a_divide(one_chip):
    """The finalize of ``avg(decimal)`` at q7's group capacity: the
    64-bit quotients come from a 64-step loop, not from ``//`` (7 s of
    compile each, six of them in q7's merge program)."""
    from spark_rapids_tpu.ops.aggregates import decimal_average
    lowered = jax.jit(lambda s, n: decimal_average(s, n, 4, 11)).lower(
        _spec((8192,), jnp.int64, one_chip),
        _spec((8192,), jnp.int64, one_chip))
    text = lowered.as_text()
    assert "stablehlo.divide" not in text and "stablehlo.while" in text
    assert _device_bytes(lowered.compile()) < HBM_BYTES


def test_fused_q6_stage_compiles(one_chip, monkeypatch):
    """The flagship fused filter+project+reduce stage at 2^23 rows."""
    import __graft_entry__ as g
    from spark_rapids_tpu.utils import compile_cache
    # tests keep no compile cache: a program compiled for a described
    # chip is written to it but cannot be read back without one
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    fn, example = g.entry()
    rows = 1 << 23
    args = [_spec((rows,), a.dtype, one_chip) for a in example[:-1]]
    args.append(_spec((), example[-1].dtype, one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_four_chip_exchange_compiles(topo, monkeypatch):
    """The shard_map exchange around ``lax.all_to_all`` on a 4-device
    mesh, as the chip runs it: packed wire, Pallas histogram."""
    # use_pallas() asks jax.default_backend(), which is the CPU here:
    # steer it from the test so the program is the chip's
    on_chip = lambda: True  # noqa: E731
    on_chip.cache_clear = lambda: None
    monkeypatch.setattr(pk, "use_pallas", on_chip)

    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    sharded = NamedSharding(mesh, P("data"))
    cap, slot = 1 << 21, 1 << 20     # SF1 lineitem is 1.5M rows a shard
    values, masks = _wire_specs(4 * cap, sharded)

    def step(values, masks, pids, nrows):
        out, total, overflow = shuffle.exchange(
            _wire_cols(values, masks), pids, nrows[0], "data", 4,
            slot=slot, packed=True, with_overflow=True)
        return ([(c.values, c.validity) for c in out],
                total.reshape(1), overflow.reshape(1))

    fn = shard_map(step, mesh=mesh,
                   in_specs=(P("data"),) * 4, out_specs=P("data"))
    compiled = jax.jit(fn).lower(
        values, masks, _spec((4 * cap,), jnp.int32, sharded),
        _spec((4,), jnp.int32, sharded)).compile()
    text = compiled.as_text()
    assert "all-to-all" in text
    assert "tpu_custom_call" in text
    assert _device_bytes(compiled) < HBM_BYTES


def test_two_key_join_match_compiles_at_q9s_capacities(one_chip):
    """``jit_join_match`` on Q9's ``ps_suppkey = l_suppkey and
    ps_partkey = l_partkey``: two int64 keys, ``partsupp`` at SF1 as the
    build side (2^20 slots) and the lines that found a part and a
    supplier as the probe (2^19): two single-key 64-bit sorts over
    2^20 + 2^19 rows (ops/selection.py ``lexsort_i32``), at the cell's
    own capacities."""
    from spark_rapids_tpu.ops import joins as J

    def keys(capacity):
        return [ColVal(None, _spec((capacity,), jnp.int64, one_chip), None)
                for _ in range(2)]

    n = _spec((), jnp.int32, one_chip)
    compiled = J.join_match.lower(keys(1 << 20), keys(1 << 19), n, n).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_like_filter_stage_compiles_at_q9s_capacities(one_chip):
    """``jit_filter_stage_*`` of Q9's ``p_name like '%green%'``: ``part``
    at SF1 is 200,000 rows (2^18 slots) of ``p_partkey`` and ``p_name``,
    whose five colour words fill a 2^23-byte buffer; about one row in
    nineteen is kept, scattered, so the compaction's gathers run."""
    from spark_rapids_tpu.ops.compiler import FilterStageFn
    from spark_rapids_tpu.ops.expressions import BoundReference
    from spark_rapids_tpu.ops.stringops import Like
    rows = 1 << 18
    row = [dts.INT64, dts.STRING]
    flat = [(_spec((rows,), jnp.int64, one_chip), None, None),
            (_spec((1 << 23,), jnp.uint8, one_chip), None,
             _spec((rows + 1,), jnp.int32, one_chip))]
    refs = [BoundReference(i, dt) for i, dt in enumerate(row)]
    stage = FilterStageFn(Like(refs[1], "%green%"), refs, row)
    compiled = jax.jit(stage._run).lower(
        flat, _spec((), jnp.int32, one_chip)).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    assert len(re.findall(r" conditional\(", compiled.as_text())) == 1
