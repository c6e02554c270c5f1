"""TPC-H Q9 in the specification's own text through ``session.sql`` over
parquet: six tables in one FROM clause that the resolver orders by the
join graph, ``extract(year from ...)``, a two-column join key, a LIKE on
the device.  The tables are the benchmark generator's and the answers
its plain reference's (``benchmark/reference/tpch_q9.py``), judged by
the benchmark's own comparison."""

import os
import re

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark.datagen import tpcds, tpch
from benchmark.harness import compare, spec
from benchmark.reference import tpch_q9 as ref
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.column import bucket_capacity
from spark_rapids_tpu.exec.join import TpuHashJoinExec, join_metrics
from spark_rapids_tpu.sql import parser
from spark_rapids_tpu.sql.resolver import resolver_metrics

Q9 = spec.load_json("queries", "tpch_q9", "q9.json")
FROM_AS_WRITTEN = ["part", "supplier", "lineitem", "partsupp", "orders",
                   "nation"]


def _text(suite, query):
    with open(os.path.join(spec.BENCH, "queries", suite,
                           query + ".sql")) as f:
        return f.read()


Q9_TEXT = _text("tpch_q9", "q9")


def _session_over(tables, base):
    """One directory a table, lineitem in four files as the
    configuration stores it."""
    session = TpuSession()
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


def _join_chain(exec_plan):
    """The joins of the plan's left-deep chain, first made first."""
    node = exec_plan
    while not isinstance(node, TpuHashJoinExec):
        node = node.children[0]
    chain = []
    while isinstance(node, TpuHashJoinExec):
        chain.append(node)
        node = node.left
    return chain[::-1]


def _keys(exec_plan):
    return [[e.name for e in j.left_keys] for j in _join_chain(exec_plan)]


def _counted(metrics, run):
    before = metrics.snapshot()
    out = run()
    after = metrics.snapshot()
    return out, {k: after[k] - before[k] for k in after}


# ------------------------------------------------- (a) the text, the answer


# a row a nation and order year: 25 x 7 at the cell's scale; of the 100
# suppliers of sf 0.01 none is of one nation under seed 3
@pytest.mark.parametrize("sf,seed,rows", [(0.01, 3, 168),
                                          (0.02, 2**31 + 11, 175)])
def test_q9_in_the_specifications_text_matches_the_reference(
        tmp_path, sf, seed, rows):
    tables = tpch.gen_tables(FROM_AS_WRITTEN, sf, seed)
    session = _session_over(tables, tmp_path)
    assert "extract(year from o_orderdate)" in Q9_TEXT
    assert re.search(r"from\s+part,\s+supplier,\s+lineitem,\s+partsupp,"
                     r"\s+orders,\s+nation\s+where", Q9_TEXT)
    frame = session.sql(Q9_TEXT)
    plan = session.plan(frame.plan)
    tree = plan.tree_string()
    assert "CpuFallbackExec" not in tree and "cross" not in tree
    assert tree.count("TpuHashJoinExec[inner") == 5
    assert ["l_suppkey", "l_partkey"] in _keys(plan)
    got = frame.to_pandas()
    want = ref.q9(compare.reference_frames(tables, {"q9": Q9}))
    assert len(want) == rows and list(want.columns) == [
        "nation", "o_year", "sum_profit"]
    err, wrong, shape = compare.compare_answer(got, want)
    assert (wrong, shape) == (0, 0) and err <= 1e-10
    assert not session.recovery_log


# ---------------------------------------------------- (b) the order rule


@pytest.fixture(scope="module")
def q9_small(tmp_path_factory):
    tables = tpch.gen_tables(FROM_AS_WRITTEN, 0.01, 5)
    session = _session_over(tables, tmp_path_factory.mktemp("q9"))
    want = ref.q9(compare.reference_frames(tables, {"q9": Q9}))
    return session, want


@pytest.mark.parametrize("from_list,reordered", [
    # supplier waits for lineitem
    (FROM_AS_WRITTEN, 1),
    # nation reaches only supplier, supplier only lineitem
    (["nation", "part", "orders", "lineitem", "supplier", "partsupp"], 2),
    # the fact table first: nation waits for supplier
    (["lineitem", "nation", "supplier", "part", "partsupp", "orders"], 1),
], ids=["as_written", "nation_first", "lineitem_first"])
def test_a_from_list_is_joined_in_an_order_the_join_graph_allows(
        q9_small, from_list, reordered):
    session, want = q9_small
    text = Q9_TEXT.replace(
        ",\n".join(" " * 12 + t for t in FROM_AS_WRITTEN),
        ",\n".join(" " * 12 + t for t in from_list))
    assert (text == Q9_TEXT) == (from_list == FROM_AS_WRITTEN)
    frame, counted = _counted(resolver_metrics, lambda: session.sql(text))
    assert counted == {"comma_joins": 5, "reordered": reordered,
                       "cross_joins": 0}
    tree = session.plan(frame.plan).tree_string()
    assert tree.count("TpuHashJoinExec[inner") == 5 and "cross" not in tree
    err, wrong, shape = compare.compare_answer(frame.to_pandas(), want)
    assert (wrong, shape) == (0, 0) and err <= 1e-10


def _views_of(session, gen, names):
    # the schemas are all the resolver reads
    for name, table in gen.gen_tables(names, 0.001, 1).items():
        session.create_dataframe(table.slice(0, 8)) \
            .createOrReplaceTempView(name)


@pytest.mark.parametrize("suite,query,commas,keys", [
    ("tpch", "q3", 0, [["c_custkey"], ["o_orderkey"]]),
    ("tpch_q18", "q18", 2, [["c_custkey"], ["o_orderkey"]]),
    ("tpcds", "q7", 4, [["ss_cdemo_sk"], ["ss_sold_date_sk"],
                        ["ss_item_sk"], ["ss_promo_sk"]]),
])
def test_a_list_connected_as_written_keeps_its_order(suite, query, commas,
                                                     keys):
    """The cells that were there: every relation of their FROM lists is
    connected to the ones before it, so each is joined where it is
    written, on the keys it was joined on before."""
    session = TpuSession()
    meta = spec.load_json("queries", suite, query + ".json")
    _views_of(session, tpcds if suite == "tpcds" else tpch,
              list(meta["tables"]))
    frame, counted = _counted(resolver_metrics,
                              lambda: session.sql(_text(suite, query)))
    assert counted == {"comma_joins": commas, "reordered": 0,
                       "cross_joins": 0}
    assert _keys(session.plan(frame.plan)) == keys


@pytest.fixture(scope="module")
def session():
    return TpuSession()


def _keyed(session, name, keys, payload):
    session.create_dataframe(pa.table({
        f"{name}_k": pa.array(keys, type=pa.int64()),
        f"{name}_v": pa.array(payload, type=pa.int64()),
    })).createOrReplaceTempView(name)


def test_a_relation_nothing_connects_is_cross_joined_last(session):
    _keyed(session, "a", [1, 2, 3, 4], [1, 2, 3, 4])
    _keyed(session, "x", [7, 8], [70, 80])
    _keyed(session, "b", [2, 3, 4, 5], [20, 30, 40, 50])
    frame, counted = _counted(resolver_metrics, lambda: session.sql(
        "select a_v, b_v, x_v from a, x, b where a_k = b_k "
        "order by a_v, x_v"))
    assert counted == {"comma_joins": 2, "reordered": 1, "cross_joins": 1}
    tree = session.plan(frame.plan).tree_string()
    # b first, on its key; then x, which no conjunct names
    assert tree.index("[cross") < tree.index("TpuHashJoinExec[inner")
    assert frame.to_pandas().values.tolist() == [
        [a, 10 * a, x] for a in (2, 3, 4) for x in (70, 80)]
    # a theta condition alone orders nothing: x stays where it is written
    frame, counted = _counted(resolver_metrics, lambda: session.sql(
        "select a_v, x_v from a, x where a_v * 20 > x_v order by a_v"))
    assert counted == {"comma_joins": 1, "reordered": 0, "cross_joins": 0}
    assert frame.to_pandas().values.tolist() == [[4, 70]]


# ------------------------------------------------------------ (c) extract


@pytest.mark.parametrize("field,function", [
    ("year", "year"), ("MONTH", "month"), ("day", "day"),
    ("quarter", "quarter"), ("doy", "dayofyear")])
def test_extract_is_the_date_part_function(session, field, function):
    days = pa.array(np.array(["1992-01-01", "1995-06-17", "1996-02-29",
                              "1998-12-31"], "datetime64[D]"))
    session.create_dataframe(pa.table({"d": days})) \
        .createOrReplaceTempView("days")
    out = session.sql(
        f"select extract({field} from d) as e, {function}(d) as f, "
        f"extract({field} from d) + 1 as g from days").to_pandas()
    assert out.e.tolist() == out.f.tolist() == [
        getattr(pd.Timestamp(str(v)), function) for v in days.to_pylist()]
    assert out.g.tolist() == [v + 1 for v in out.e.tolist()]


@pytest.mark.parametrize("text", [
    "select extract(year, d) from days",
    "select extract(year d) from days",
    "select extract(epoch from d) from days",
    "select extract(second from d) from days",
    "select extract(from d) from days",
    "select extract(year from d from days",
    "select extract(",
])
def test_a_malformed_extract_raises(text):
    with pytest.raises(ValueError, match="EXTRACT|expected"):
        parser.parse(text)


# ------------------------------------------------- (d) the two-column key


def test_two_key_join_against_pandas_with_nulls_in_either_key(session):
    rng = np.random.default_rng(20261004)

    def side(name, n):
        k1 = rng.integers(0, 12, n).astype(object)
        k2 = rng.integers(0, 5, n).astype(object)
        k1[rng.random(n) < 0.15] = None
        k2[rng.random(n) < 0.15] = None
        frame = pd.DataFrame({f"{name}_k1": k1, f"{name}_k2": k2,
                              f"{name}_v": np.arange(n) + 1000 * (
                                  name == "r")})
        session.create_dataframe(pa.table({
            f"{name}_k1": pa.array(k1.tolist(), pa.int64()),
            f"{name}_k2": pa.array(k2.tolist(), pa.int64()),
            f"{name}_v": pa.array(frame[f"{name}_v"]),
        })).createOrReplaceTempView(name)
        return frame

    left, right = side("l", 300), side("r", 200)
    frame = session.sql(
        "select l_v, r_v from l, r where r_k1 = l_k1 and r_k2 = l_k2 "
        "order by l_v, r_v")
    (join,) = _join_chain(session.plan(frame.plan))
    assert [e.name for e in join.left_keys] == ["l_k1", "l_k2"]
    # a NULL in either column matches nothing (pandas would pair NaNs)
    want = left.dropna().merge(
        right.dropna(), left_on=["l_k1", "l_k2"],
        right_on=["r_k1", "r_k2"]).sort_values(["l_v", "r_v"])
    got = frame.to_pandas()
    assert len(got) > 100
    assert got.values.tolist() == want[["l_v", "r_v"]].values.tolist()
    # pairs equal in one column only are there, and do not match
    one_only = left.dropna().merge(right.dropna(), left_on="l_k1",
                                   right_on="r_k1")
    assert len(one_only) > 3 * len(got)


# ------------------------------------------------------------ (e) counters


def test_resolver_and_build_side_counters_on_known_plans(session):
    _keyed(session, "a", [1, 2, 3, 4], [1, 2, 3, 4])
    _keyed(session, "b", [2, 3, 4, 5, 6], [20, 30, 40, 50, 60])
    _keyed(session, "c", list(range(3, 103)), list(range(300, 400)))
    # c names only b: it waits for b, which is written after it
    frame, counted = _counted(resolver_metrics, lambda: session.sql(
        "select a_v, b_v, c_v from a, c, b where a_k = b_k and c_k = b_k "
        "order by a_v"))
    assert counted == {"comma_joins": 2, "reordered": 1, "cross_joins": 0}
    assert _keys(session.plan(frame.plan)) == [["a_k"], ["b_k"]]
    got, joined = _counted(join_metrics, frame.to_pandas)
    assert got.values.tolist() == [[3, 30, 300], [4, 40, 301]]
    # the build sides: b (5 rows), then c (100 rows), each one batch
    assert joined["build_rows"] == 5 + 100
    assert joined["build_capacity"] == \
        bucket_capacity(5) + bucket_capacity(100)
    assert (joined["probe_rows"], joined["output_rows"]) == (4 + 3, 3 + 2)
    # explicit joins are not the resolver's to order, and count nothing
    _, counted = _counted(resolver_metrics, lambda: session.sql(
        "select a_v from a join c on a_k = c_k join b on c_k = b_k"))
    assert counted == {"comma_joins": 0, "reordered": 0, "cross_joins": 0}


# ------------------------------- (f) what the chip forced: LIKE, string rows


def _strings(values):
    from spark_rapids_tpu.columnar.column import Column
    from spark_rapids_tpu.ops.expressions import ColVal
    col = Column.from_arrow(pa.array(values, pa.string()))
    return col, ColVal(col.dtype, col.data, col.validity, col.offsets)


@pytest.mark.parametrize("rows,seed", [(1, 0), (700, 1), (5000, 2)])
def test_rows_of_positions_is_the_binary_search(rows, seed):
    """Empty rows, padding rows whose offsets stay at the total, element
    buffers longer than the rows fill and of a length that is no
    multiple of the scan's block."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import selection
    rng = np.random.default_rng(seed)
    lengths = rng.integers(0, 9, rows) * (rng.random(rows) < 0.7)
    offsets = np.zeros(bucket_capacity(rows) + 1, np.int32)
    offsets[1:rows + 1] = np.cumsum(lengths)
    offsets[rows + 1:] = offsets[rows]
    for n in (bucket_capacity(int(offsets[-1]) + 1), 3 * 1024 + 5):
        want = np.searchsorted(offsets, np.arange(n), side="right") - 1
        got = np.asarray(selection.rows_of_positions(jnp.asarray(offsets), n))
        assert (got == want).all()
    x = rng.integers(-5, 5, 8 * 1024).astype(np.int32)
    assert (np.asarray(selection.cumsum_32(jnp.asarray(x)))
            == np.cumsum(x, dtype=np.int32)).all()


@pytest.mark.parametrize("pattern", ["green", "n", "green g", "é", ""])
def test_contains_counts_matches_inside_a_row_only(pattern):
    """The prefix-sum ``Contains`` against Python's ``in``: a pattern at
    a row's first and last bytes, one that only the end of a row and the
    start of the next spell together, rows shorter than it, empty rows,
    NULLs, two bytes a character."""
    from spark_rapids_tpu.ops.expressions import BoundReference, EmitContext
    from spark_rapids_tpu.ops.stringops import Contains, Like
    words = ["green", "gree", "n", "", "forest green", "green lace",
             "dark gre", "en snow", "evergreen g", "reen", None, "ngreeng",
             "é", "vert é green", "greengreen", "g r e e n"]
    rng = np.random.default_rng(len(pattern))
    values = [words[i] for i in rng.integers(0, len(words), 500)]
    col, c = _strings(values)
    for expr in (Contains(BoundReference(0, col.dtype), pattern),
                 Like(BoundReference(0, col.dtype), f"%{pattern}%")):
        out = expr.emit(EmitContext([c], len(values), col.capacity))
        got = np.asarray(out.values)[:len(values)]
        want = [v is not None and pattern in v for v in values]
        live = [v is not None for v in values]
        assert (got[live] == np.array(want)[live]).all()
        if pattern:
            assert (np.asarray(out.validity)[:len(values)]
                    == np.array(live)).all()
    assert 0 < sum(want) < len(values) or pattern == ""


def test_like_keeps_scattered_rows_and_their_strings(session):
    """A filter stage that moves rows: the device's own LIKE over
    in-memory batches (a parquet scan would push nothing down here, but
    an in-memory relation pushes nothing ever), strings of other
    columns carried through the compaction's gather."""
    from spark_rapids_tpu.api.dataframe import DataFrame
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.exec.basic import filter_metrics
    from spark_rapids_tpu.plan import logical as L
    part = tpch.gen_table("part", 0.01, 17).to_pandas()[
        ["p_partkey", "p_name", "p_type"]]
    part.loc[::97, "p_type"] = None
    batches = [ColumnarBatch.from_pandas(part.iloc[i:i + 600])
               for i in range(0, len(part), 600)]
    DataFrame(session, L.InMemoryRelation(batches, batches[0].schema)) \
        .createOrReplaceTempView("part_batches")
    frame = session.sql("select p_partkey, p_type, p_name from part_batches "
                        "where p_name like '%green%'")
    assert "Filter" in session.plan(frame.plan).tree_string()
    got, counted = _counted(filter_metrics, frame.to_pandas)
    want = part[part.p_name.str.contains("green")]
    assert 50 < len(want) < len(part) // 10
    assert counted["batches"] == len(batches)
    assert counted["whole_batches"] == 0
    assert (counted["rows_in"], counted["rows_out"]) == (len(part),
                                                         len(want))
    assert got.p_partkey.tolist() == want.p_partkey.tolist()
    assert got.p_name.tolist() == want.p_name.tolist()
    assert [None if v != v else v for v in got.p_type] == \
        [None if v != v else v for v in want.p_type]
