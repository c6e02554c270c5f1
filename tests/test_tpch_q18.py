"""TPC-H Q18 (clause 2.4.18) through ``session.sql`` over parquet: the
benchmark's own text over the benchmark generator's tables against its
plain reference (``benchmark/reference/tpch_q18.py``), judged by the
benchmark's own comparison; and the counters that say which rung of the
group-by's ladder a batch and a merge took (``exec/aggregate.py
agg_metrics``)."""

import os

import numpy as np
import pandas as pd
import pyarrow.parquet as pq
import pytest

from benchmark.datagen import tpch_q18 as gen
from benchmark.harness import compare, spec
from benchmark.reference import tpch_q18 as ref
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.dataframe import DataFrame
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.aggregate import agg_metrics
from spark_rapids_tpu.ops import aggregates
from spark_rapids_tpu.plan import logical as L

# the (scale, seed) of the issue: one order of 30,000 passes QUANTITY 300
SF, SEED = 0.02, 7
Q18 = spec.load_json("queries", "tpch_q18", "q18.json")
with open(os.path.join(spec.BENCH, "queries", "tpch_q18", "q18.sql")) as f:
    Q18_TEXT = f.read()


@pytest.fixture(scope="module")
def tables():
    return gen.gen_tables(list(Q18["tables"]), SF, SEED)


@pytest.fixture(scope="module")
def frames(tables):
    return compare.reference_frames(tables, {"q18": Q18})


def _session_over(tables, base, conf=None):
    """One directory a table; lineitem in four files, as the
    configuration stores it."""
    session = TpuSession(conf)
    for name, table in tables.items():
        d = base / name
        d.mkdir()
        n_files = 4 if name == "lineitem" else 1
        per_file = -(-table.num_rows // n_files)
        for i in range(n_files):
            pq.write_table(table.slice(i * per_file, per_file),
                           str(d / f"part-{i:03d}.parquet"))
        session.read.parquet(str(d)).createOrReplaceTempView(name)
    return session


def _delta(before):
    now = agg_metrics.snapshot()
    return {k: now[k] - before[k] for k in now}


def test_q18_at_quantity_300_matches_the_plain_reference(tmp_path, tables,
                                                         frames):
    session = _session_over(tables, tmp_path)
    before = agg_metrics.snapshot()
    got = session.sql(Q18_TEXT).to_pandas()
    want = ref.q18(frames)
    assert len(want) >= 1
    assert list(got.columns) == ["c_name", "c_custkey", "o_orderkey",
                                 "o_orderdate", "o_totalprice", "sum_qty"]
    err, wrong, shape = compare.compare_answer(got, want)
    assert (wrong, shape) == (0, 0) and err <= 1e-10
    assert (got.sum_qty > 300).all()
    assert not session.recovery_log
    # every partial batch of the two group-bys took one rung or the other
    d = _delta(before)
    assert d["coded_batches"] + d["sort_batches"] >= 2
    assert d["merges_sorted"] + d["merges_coded"] == 2
    assert d["merge_inputs"] >= 2


@pytest.mark.parametrize("quantity,rows", [(270, "dozens"), (200, "limit")])
def test_q18_at_a_lower_quantity(tmp_path, tables, frames, quantity, rows):
    """300 replaced in text and reference alike: dozens of rows, then
    more than LIMIT 100 lets through; the scan in several batches so
    that both group-bys merge partials."""
    assert Q18_TEXT.count("300") == 1
    session = _session_over(
        tables, tmp_path, {"spark.rapids.sql.reader.batchSizeRows": 16384})
    got = session.sql(Q18_TEXT.replace("300", str(quantity))).to_pandas()
    want = ref.q18(frames, quantity=quantity)
    assert 24 <= len(want) < 100 if rows == "dozens" else len(want) == 100
    err, wrong, shape = compare.compare_answer(got, want)
    assert (wrong, shape) == (0, 0) and err <= 1e-10
    assert not session.recovery_log


def test_the_in_subquery_plans_as_a_semi_join_on_the_device(tmp_path,
                                                             tables):
    session = _session_over(tables, tmp_path)
    plan = session.plan(session.sql(Q18_TEXT).plan).tree_string()
    assert "CpuFallbackExec" not in plan
    assert plan.count("TpuHashJoinExec") == 3
    assert plan.count("semi") == 1
    assert plan.count("TpuHashAggregateExec") == 2
    assert "TpuTopNExec" in plan


def test_the_float32_control_differs_in_o_totalprice_only(frames):
    want = ref.q18(frames, quantity=200)
    control = ref.q18(frames, np.float32, quantity=200)
    # sum_qty is a sum of at most seven whole numbers: exact in float32
    assert (control.sum_qty.to_numpy(np.float64)
            == want.sum_qty.to_numpy()).all()
    err, wrong, shape = compare.compare_answer(control, want)
    assert shape == 0 and err > 1e-10


def _grouped(keys, batch_rows, conf=None):
    """sum(v) by k over in-memory batches of ``batch_rows`` rows."""
    pdf = pd.DataFrame({"k": np.asarray(keys, dtype=np.int64),
                        "v": np.arange(len(keys), dtype=np.float64)})
    batches = [ColumnarBatch.from_pandas(pdf.iloc[i:i + batch_rows])
               for i in range(0, len(pdf), batch_rows)]
    s = TpuSession(conf)
    df = DataFrame(s, L.InMemoryRelation(batches, batches[0].schema)) \
        .groupBy("k").agg(F.sum("v").alias("v"))
    before = agg_metrics.snapshot()
    got = df.to_pandas().sort_values("k", ignore_index=True)
    want = pdf.groupby("k", as_index=False).v.sum()
    assert got.k.tolist() == want.k.tolist()
    assert np.allclose(got.v.to_numpy(), want.v.to_numpy(), rtol=1e-12)
    return _delta(before), len(batches)


def test_agg_metrics_a_dense_key_range_is_a_coded_batch():
    # 8 batches of 512 rows, 100 key values: the speculative directory
    d, n = _grouped(np.arange(4096) % 100, 512)
    assert (d["coded_batches"], d["sort_batches"]) == (n, 0)
    assert d["spec_misses"] == 0
    assert d["coded_slots"] == n * 4096
    assert (d["merges_coded"], d["merges_sorted"]) == (1, 0)
    assert d["merge_inputs"] == n


def test_agg_metrics_a_sized_directory_after_two_misses():
    # 10,000 consecutive keys a batch: past the speculative 4,096 slots,
    # under the directory's limit, so each batch is sized from its range
    d, n = _grouped(np.arange(40000), 10000)
    assert (d["coded_batches"], d["sort_batches"]) == (n, 0)
    assert d["spec_misses"] == 2
    assert d["coded_slots"] == n * 16384
    # 40,000 groups over a range of 40,000: the merge is coded too
    assert (d["merges_coded"], d["merges_sorted"]) == (1, 0)


def test_agg_metrics_past_the_directory_is_a_sort_batch(monkeypatch):
    monkeypatch.setattr(aggregates, "MAX_CODED_GROUPS", 1 << 10)
    d, n = _grouped(np.arange(40000), 10000)
    assert (d["coded_batches"], d["sort_batches"]) == (0, n)
    assert d["coded_slots"] == 0
    assert (d["merges_coded"], d["merges_sorted"]) == (0, 1)
    assert d["merge_inputs"] == n


def test_agg_metrics_coded_partials_under_a_sorted_merge(monkeypatch):
    """q18's inner group-by in small: every batch's key range fits the
    directory, all of them together do not."""
    monkeypatch.setattr(aggregates, "MAX_CODED_GROUPS", 1 << 14)
    d, n = _grouped(np.arange(40000) * 4, 2500)
    assert (d["coded_batches"], d["sort_batches"]) == (n, 0)
    assert (d["merges_coded"], d["merges_sorted"]) == (0, 1)


def test_agg_metrics_string_and_float_keys():
    pdf = pd.DataFrame({"s": ["a", "b", "c", "a"] * 64,
                        "x": np.arange(256) % 3 + 0.5,
                        "v": np.ones(256)})
    s = TpuSession()
    before = agg_metrics.snapshot()
    s.create_dataframe(pdf).groupBy("s").agg(F.sum("v")).to_pandas()
    d = _delta(before)
    assert (d["coded_batches"], d["sort_batches"]) == (1, 0)
    before = agg_metrics.snapshot()
    out = s.create_dataframe(pdf).groupBy("s", "x").agg(F.sum("v")) \
        .to_pandas()
    assert len(out) == 9
    d = _delta(before)
    # a float key: no directory can address it, partial and merge sort
    assert (d["coded_batches"], d["sort_batches"]) == (0, 1)
    assert (d["merges_coded"], d["merges_sorted"]) == (0, 1)
    before = agg_metrics.snapshot()
    s.create_dataframe(pdf).agg(F.sum("v")).to_pandas()
    d = _delta(before)
    assert d["coded_batches"] + d["sort_batches"] == 0
    assert d["merges_coded"] + d["merges_sorted"] == 0


def test_agg_spans_are_in_the_rollup_inside_the_operator(tmp_path, tables):
    session = _session_over(
        tables, tmp_path, {"spark.rapids.tpu.trace.enabled": True,
                           "spark.rapids.sql.reader.batchSizeRows": 16384})
    try:
        before = agg_metrics.snapshot()
        session.sql(Q18_TEXT).to_pandas()
        d = _delta(before)
        points = session.last_span_stats["points"]
        assert points["agg.partial"]["count"] == \
            d["coded_batches"] + d["sort_batches"]
        assert points["agg.merge"]["count"] == 2
        assert points["agg.merge"]["exclusiveMs"] <= \
            points["agg.merge"]["ms"]
    finally:
        session.stop()


def _coded_jaxpr(nkeys, k_bucket=1 << 12, cap=1 << 10):
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import dtypes as dts
    from spark_rapids_tpu.ops.expressions import ColVal

    def body(ks, v, mins, slots):
        keys = [ColVal(dts.INT64, k, None) for k in ks]
        out = aggregates.groupby_aggregate_coded(
            keys, [("sum", ColVal(dts.FLOAT64, v, None))], jnp.int32(cap),
            cap, mins, slots, k_bucket)
        return out[0][0].values, out[1][0].values, out[1][0].validity

    return jax.make_jaxpr(body)(
        [jnp.zeros(cap, jnp.int64)] * nkeys, jnp.zeros(cap, jnp.float64),
        jnp.zeros(nkeys, jnp.int64), jnp.ones(nkeys, jnp.int64))


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in eqn.params.values():
            if hasattr(sub, "jaxpr"):
                yield from _eqns(sub.jaxpr)


@pytest.mark.parametrize("nkeys", [1, 3])
def test_the_directorys_digits_divide_in_32_bits_or_not_at_all(nkeys):
    """The chip emulates a 64-bit division in hundreds of passes over the
    directory (0.14 s a million slots in q18): one key divides nothing,
    several divide slot indexes in 32 bits; and a buffer without NULLs
    scatters no validity (one scatter a key and one a buffer)."""
    eqns = list(_eqns(_coded_jaxpr(nkeys).jaxpr))
    divides = [e for e in eqns if e.primitive.name in ("div", "rem")]
    assert all(str(v.aval.dtype) == "int32"
               for e in divides for v in e.invars)
    assert (len(divides) == 0) == (nkeys == 1)
    scatters = [e for e in eqns if e.primitive.name == "scatter"]
    assert len(scatters) == nkeys + 1
