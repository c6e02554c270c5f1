"""Device join / sort / TopN tests — oracle: pandas merge/sort.

Miniature of the reference's join + sort integration suites
(integration_tests join_test.py 681 LoC, sort_test.py).
"""

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession


@pytest.fixture(scope="module")
def session():
    return TpuSession()


def _join_frames(session, how, rng=None, n_left=300, n_right=200, kmax=50):
    rng = rng or np.random.default_rng(3)
    left = pd.DataFrame({
        "k": rng.integers(0, kmax, n_left),
        "lv": rng.normal(size=n_left).round(3),
    })
    right = pd.DataFrame({
        "k": rng.integers(0, kmax, n_right),
        "rv": rng.integers(0, 1000, n_right),
    })
    got = (session.create_dataframe(left)
           .join(session.create_dataframe(right), on="k", how=how))
    return left, right, got


def _check_native(df):
    tree = df.session.plan(df.plan).tree_string()
    assert "TpuHashJoinExec" in tree or "TpuSortExec" in tree or \
        "TpuTopNExec" in tree, tree
    assert "CpuFallbackExec" not in tree, tree


def _compare_join(got_df, want: pd.DataFrame):
    got = got_df.to_pandas()
    assert sorted(got.columns) == sorted(want.columns)
    want = want[got.columns.tolist()]
    key = got.columns.tolist()
    g = got.sort_values(key).reset_index(drop=True)
    w = want.sort_values(key).reset_index(drop=True)
    assert len(g) == len(w), (len(g), len(w))
    for c in g.columns:
        gv, wv = g[c], w[c]
        if np.issubdtype(np.asarray(wv.dropna()).dtype, np.floating):
            np.testing.assert_allclose(
                gv.fillna(-9e99), wv.fillna(-9e99), rtol=1e-9)
        else:
            pd.testing.assert_series_equal(gv, wv, check_dtype=False,
                                           check_names=False)


def test_inner_join(session):
    left, right, got = _join_frames(session, "inner")
    _check_native(got)
    _compare_join(got, left.merge(right, on="k", how="inner"))


def test_left_join(session):
    left, right, got = _join_frames(session, "left")
    _check_native(got)
    _compare_join(got, left.merge(right, on="k", how="left"))


def test_right_join(session):
    left, right, got = _join_frames(session, "right")
    _compare_join(got, left.merge(right, on="k", how="right"))


def test_full_outer_join(session):
    left, right, got = _join_frames(session, "full", kmax=80)
    _compare_join(got, left.merge(right, on="k", how="outer"))


def test_semi_anti_join(session):
    rng = np.random.default_rng(5)
    left = pd.DataFrame({"k": rng.integers(0, 30, 100),
                         "lv": np.arange(100)})
    right = pd.DataFrame({"k": rng.integers(0, 15, 40),
                          "rv": np.arange(40)})
    semi = (session.create_dataframe(left)
            .join(session.create_dataframe(right), on="k", how="semi"))
    anti = (session.create_dataframe(left)
            .join(session.create_dataframe(right), on="k", how="anti"))
    in_right = left.k.isin(right.k.unique())
    _compare_join(semi, left[in_right])
    _compare_join(anti, left[~in_right])


@pytest.mark.parametrize("how", ["semi", "anti", "full"])
def test_join_compaction_compiles_nothing_on_a_second_run(session, how):
    """The join's own compactions (semi and anti survivors, a full
    join's unmatched build rows) run op by op on the host, so they take
    ``selection.compact_by_gather``: ``compact`` itself branches with a
    ``lax.cond``, which outside a trace compiles on every call."""
    import jax.monitoring
    compiles = []

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    def run():
        left, right, got = _join_frames(session, how, kmax=80)
        want = left.merge(right, on="k", how="outer") if how == "full" \
            else left[left.k.isin(right.k) == (how == "semi")]
        assert len(got.to_pandas()) == len(want)

    run()
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        run()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert compiles == []


def test_join_with_nulls(session):
    left = pd.DataFrame({"k": [1, None, 2, 3], "lv": [10, 20, 30, 40]})
    right = pd.DataFrame({"k": [1, None, 3], "rv": [100, 200, 300]})
    got = (session.create_dataframe(left)
           .join(session.create_dataframe(right), on="k", how="inner"))
    out = got.to_pandas().sort_values("k").reset_index(drop=True)
    # null keys never match (Spark equi-join semantics)
    assert out["k"].tolist() == [1, 3]
    assert out["rv"].tolist() == [100, 300]
    left_g = (session.create_dataframe(left)
              .join(session.create_dataframe(right), on="k", how="left"))
    lout = left_g.to_pandas()
    assert len(lout) == 4  # null-key row kept, unmatched


def test_join_string_keys(session):
    left = pd.DataFrame({"name": ["a", "b", "c", "a"],
                         "lv": [1, 2, 3, 4]})
    right = pd.DataFrame({"name": ["a", "c", "d"], "rv": [10, 30, 40]})
    got = (session.create_dataframe(left)
           .join(session.create_dataframe(right), on="name", how="inner"))
    _compare_join(got, left.merge(right, on="name", how="inner"))


def test_join_multi_key(session):
    rng = np.random.default_rng(9)
    left = pd.DataFrame({"a": rng.integers(0, 5, 60),
                         "b": rng.integers(0, 5, 60),
                         "lv": np.arange(60)})
    right = pd.DataFrame({"a": rng.integers(0, 5, 40),
                          "b": rng.integers(0, 5, 40),
                          "rv": np.arange(40)})
    got = (session.create_dataframe(left)
           .join(session.create_dataframe(right), on=["a", "b"],
                 how="inner"))
    _compare_join(got, left.merge(right, on=["a", "b"], how="inner"))


def test_join_duplicate_build_keys(session):
    left = pd.DataFrame({"k": [1, 1, 2], "lv": [10, 11, 20]})
    right = pd.DataFrame({"k": [1, 1, 1, 2], "rv": [5, 6, 7, 8]})
    got = (session.create_dataframe(left)
           .join(session.create_dataframe(right), on="k", how="inner"))
    _compare_join(got, left.merge(right, on="k"))  # 2*3 + 1 = 7 rows


def test_join_sparse_40bit_keys(session):
    """One int64 key from a 2^40 keyspace, half of the keys on the
    build side, the joined rows summed by key."""
    rng = np.random.default_rng(11)
    uni = np.unique(rng.integers(0, 1 << 40, 4000,
                                 dtype=np.int64))[:1000]
    probe = pd.DataFrame({"k": uni[rng.integers(0, len(uni), 8000)],
                          "v": rng.normal(size=8000)})
    build = pd.DataFrame({"k": uni[::2],
                          "w": rng.normal(size=len(uni[::2]))})
    got = (session.create_dataframe(probe)
           .join(session.create_dataframe(build), on="k")
           .group_by("k").agg(F.sum(F.col("v")).alias("sv"),
                              F.sum(F.col("w")).alias("sw")))
    want = probe.merge(build, on="k").groupby("k", as_index=False).agg(
        sv=("v", "sum"), sw=("w", "sum"))
    _compare_join(got, want)


def test_cross_join(session):
    left = pd.DataFrame({"a": [1, 2, 3]})
    right = pd.DataFrame({"b": ["x", "y"]})
    got = (session.create_dataframe(left)
           .crossJoin(session.create_dataframe(right)))
    assert got.count() == 6
    _compare_join(got, left.merge(right, how="cross"))


def test_sort_native(session):
    rng = np.random.default_rng(11)
    pdf = pd.DataFrame({
        "a": rng.integers(0, 100, 500),
        "b": rng.normal(size=500),
    })
    df = session.create_dataframe(pdf)
    out = df.orderBy(F.col("a").asc(), F.col("b").desc())
    _check_native(out)
    want = pdf.sort_values(["a", "b"], ascending=[True, False],
                           kind="stable").reset_index(drop=True)
    got = out.to_pandas()
    np.testing.assert_array_equal(got["a"], want["a"])
    np.testing.assert_allclose(got["b"], want["b"])


def test_sort_nulls_and_nan(session):
    # note: via pydict, not pandas — pandas folds NaN into null on ingest
    df = session.create_dataframe(
        {"x": [3.0, None, float("nan"), 1.0, -0.0]})
    got = df.orderBy("x").to_pandas()["x"]
    # nulls first (asc default), then 1.0 < -0.0==0.0... -0.0 < 1.0 < 3.0 < NaN
    assert pd.isna(got[0])
    assert got[1:4].tolist() == [-0.0, 1.0, 3.0]
    assert np.isnan(got[4])


def test_sort_desc_nulls(session):
    pdf = pd.DataFrame({"x": [2, None, 1]})
    got = session.create_dataframe(pdf).orderBy(
        F.col("x").desc()).to_pandas()["x"]
    assert got[0] == 2 and got[1] == 1 and pd.isna(got[2])


def test_topn(session):
    rng = np.random.default_rng(13)
    pdf = pd.DataFrame({"v": rng.integers(0, 10**6, 5000)})
    df = session.create_dataframe(pdf)
    q = df.orderBy(F.col("v").desc()).limit(10)
    tree = session.plan(q.plan).tree_string()
    assert "TpuTopNExec" in tree
    got = q.to_pandas()["v"].tolist()
    want = sorted(pdf.v.tolist(), reverse=True)[:10]
    assert got == want


def test_sort_strings_runs_native(session):
    """String sort keys run on device since round 2 (rank-encoded keys);
    previously this fell back to CPU."""
    pdf = pd.DataFrame({"s": ["b", "a", "c"]})
    q = session.create_dataframe(pdf).orderBy("s")
    tree = session.plan(q.plan).tree_string()
    assert "CpuFallbackExec" not in tree
    assert "TpuSortExec" in tree
    assert q.to_pandas()["s"].tolist() == ["a", "b", "c"]


def test_lexsort_i32_is_jnp_lexsort_with_an_int32_index(rng):
    """The engine's sort permutation: same order as ``jnp.lexsort``
    (last key primary, stable), with the row index carried as int32 —
    on a TPU the int64 index jnp carries under x64 is pure compile
    time."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.selection import lexsort_i32
    n = 5000
    keys = [jnp.asarray(rng.integers(0, 7, n)),                  # int64
            jnp.asarray(rng.normal(size=n).round(1)),            # f64, ties
            jnp.asarray(rng.integers(0, 2, n).astype(np.int8))]  # flag
    got = lexsort_i32(keys)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.lexsort(keys)))
    np.testing.assert_array_equal(
        np.asarray(lexsort_i32(keys[:1])),
        np.asarray(jnp.argsort(keys[0], stable=True)))
    # dead rows last, by a stable partition after the sort: the same
    # permutation as one more (most significant) sort key
    dead = jnp.asarray(rng.random(n) < 0.3)
    for ks in (keys, []):
        np.testing.assert_array_equal(
            np.asarray(lexsort_i32(ks, dead=dead)),
            np.asarray(jnp.lexsort(ks + [dead.astype(jnp.int8)])))


@pytest.mark.parametrize("capacity,top", [
    (1000, 2**31 - 1),       # not a multiple of the scan's block: one scan
    (1024, 7),               # one block
    (3 * 1024, 2**31 - 1),   # rows of a block, sums far past 2^32
    (1 << 16, 2**31 - 1),    # 64 rows
    (1 << 21, 2**31 - 1),    # two levels of row totals
    (1 << 20, 3),            # a million small counts: the usual join
])
@pytest.mark.parametrize("outer", [False, True])
def test_join_out_starts_is_an_exact_int64_scan(capacity, top, outer):
    """``join_out_starts`` scans its counts in 32-bit rows and counts
    the wraps (an int64 scan is the chip's slowest compile): the ends
    are numpy's int64 cumsum of the same counts to the last unit, also
    where one count alone is 2^31 - 1 and the total passes 2^50."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import joins as J
    rng = np.random.default_rng(capacity + top)
    counts = rng.integers(0, top, size=capacity, endpoint=True) \
        .astype(np.int32)
    counts[rng.random(capacity) < 0.3] = 0
    counts[capacity // 2] = top
    probe_n = capacity - 17
    count, starts, ends, total = J.join_out_starts(
        jnp.asarray(counts), jnp.int32(probe_n), outer)
    live = np.arange(capacity) < probe_n
    want = counts.astype(np.int64)
    if outer:
        want = np.where(live & (want == 0), 1, want)
    want = np.where(live, want, 0)
    want_ends = np.cumsum(want)
    assert ends.dtype == starts.dtype == total.dtype == jnp.int64
    np.testing.assert_array_equal(np.asarray(count), want)
    np.testing.assert_array_equal(np.asarray(ends), want_ends)
    np.testing.assert_array_equal(np.asarray(starts), want_ends - want)
    assert int(total) == int(want_ends[-1])


def _expansion_case(name):
    """(raw counts, probe_n, outer, offset, n_out, out_cap) of one case
    of the pair expansion; build matches are laid out by the test."""
    rng = np.random.default_rng(38)
    if name == "inner_zero_counts_in_the_middle_and_at_both_ends":
        return [0, 0, 3, 0, 0, 1, 2, 0, 4, 0, 0, 0], 12, False, 0, 10, 16
    if name == "left_outer_unmatched_rows_emit_one_null_row":
        return [0, 2, 0, 0, 1, 3, 0], 7, True, 0, 10, 16
    if name == "every_count_zero":
        return [0] * 8, 8, False, 0, 0, 16
    if name == "total_is_the_capacity":
        return [4, 0, 4, 1, 0, 7], 6, False, 0, 16, 16
    if name == "padding_probe_rows":
        # rows past probe_n: 9 of 16, whatever their raw counts say
        return [2, 0, 1, 3, 0, 1, 2, 5, 5, 0, 7, 1, 1, 0, 0, 9], \
            7, False, 0, 9, 16
    if name == "left_outer_padding_probe_rows":
        return [0, 2, 0, 1, 0, 0, 0, 0], 5, True, 0, 6, 8
    if name == "chunk_with_an_offset":
        # rows 0..2 end before the chunk (ends - offset <= 0), row 3
        # straddles its start, the last rows run past out_cap
        return [5, 0, 6, 9, 0, 0, 3, 8, 0, 20, 4], 11, False, 14, 16, 16
    if name == "left_outer_chunk_with_an_offset":
        return [0, 5, 0, 0, 6, 0, 9, 0, 3, 0, 0, 8], 12, True, 9, 16, 16
    if name == "one_row_wider_than_the_chunk":
        return [3, 100, 2], 3, False, 20, 32, 32
    if name == "last_chunk_shorter_than_its_capacity":
        return [7, 0, 30, 1, 12], 5, False, 32, 18, 32
    if name == "int64_ends_above_2_31_chunk_from_the_middle":
        # 2^16 probe rows that each match 2^16 build rows: ends to 2^32
        counts = np.full(1 << 16, 1 << 16)
        counts[rng.random(1 << 16) < 0.2] = 0
        return counts, 1 << 16, False, (1 << 31) + 12_345, 4096, 4096
    if name == "total_past_the_capacity":
        # the mesh's call: no offset, the whole total, the output cut
        return [3, 0, 9, 2, 0, 30, 4, 1], 8, False, 0, 49, 16
    if name == "blocked_scan_random_counts":
        counts = rng.integers(0, 4, size=4096)
        counts[rng.random(4096) < 0.5] = 0
        return counts, 4000, True, 0, None, 8192
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "inner_zero_counts_in_the_middle_and_at_both_ends",
    "left_outer_unmatched_rows_emit_one_null_row",
    "every_count_zero",
    "total_is_the_capacity",
    "padding_probe_rows",
    "left_outer_padding_probe_rows",
    "chunk_with_an_offset",
    "left_outer_chunk_with_an_offset",
    "one_row_wider_than_the_chunk",
    "last_chunk_shorter_than_its_capacity",
    "int64_ends_above_2_31_chunk_from_the_middle",
    "total_past_the_capacity",
    "blocked_scan_random_counts",
])
def test_join_gather_indices_is_the_plain_expansion(name):
    """``join_gather_indices`` finds an output row's probe row with a
    histogram of the rows' ends and a prefix sum: the same pairs in the
    same order as ``np.repeat`` of the probe rows by their adjusted
    counts with the k-th build match beside each, row for row below
    ``total``; padding rows only have to be in range."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import joins as J
    raw, probe_n, outer, offset, n_out, out_cap = _expansion_case(name)
    raw = np.asarray(raw, dtype=np.int32)
    p_cap = raw.shape[0]
    live = np.arange(p_cap) < probe_n
    rng = np.random.default_rng(p_cap + out_cap)
    # a sorted build side with room for every probe row's run; a probe
    # row that matched nothing points one past it, as join_match leaves it
    b_cap = int(raw.max()) + 5
    cap = b_cap + p_cap
    sorted_to_build = rng.integers(0, b_cap, size=cap).astype(np.int32)
    probe_bstart = np.where(
        raw > 0, rng.integers(0, 5, size=p_cap), cap).astype(np.int32)

    count, starts, ends, total = J.join_out_starts(
        jnp.asarray(raw), jnp.int32(probe_n), outer)
    adj = np.where(live, np.where(outer & (raw == 0), 1, raw), 0) \
        .astype(np.int64)
    np.testing.assert_array_equal(np.asarray(count), adj)
    if n_out is None:
        n_out = int(total)
    assert offset + n_out <= int(total)

    p, brow, matched, in_range = J.join_gather_indices(
        starts - offset if offset else starts,
        ends - offset if offset else ends,
        jnp.asarray(raw), jnp.asarray(probe_bstart),
        jnp.asarray(sorted_to_build), jnp.int64(n_out), out_cap)
    n_out = min(n_out, out_cap)
    p, brow, matched, in_range = (np.asarray(a) for a in
                                  (p, brow, matched, in_range))
    assert p.dtype == brow.dtype == np.int32
    assert p.shape == brow.shape == matched.shape == in_range.shape \
        == (out_cap,)

    # the plain expansion, cut to the chunk's window of output rows
    np_ends = np.cumsum(adj)
    np_starts = np_ends - adj
    lo, hi = offset, offset + n_out
    in_chunk = np.clip(np_ends, lo, hi) - np.clip(np_starts, lo, hi)
    want_p = np.repeat(np.arange(p_cap), in_chunk)
    k = lo + np.arange(n_out) - np_starts[want_p]
    want_matched = raw[want_p] > 0
    assert (k >= 0).all() and (k < adj[want_p]).all()
    want_brow = sorted_to_build[
        np.clip(probe_bstart[want_p] + k, 0, cap - 1)]

    np.testing.assert_array_equal(in_range, np.arange(out_cap) < n_out)
    np.testing.assert_array_equal(p[:n_out], want_p)
    np.testing.assert_array_equal(matched[:n_out], want_matched)
    np.testing.assert_array_equal(brow[:n_out], want_brow)
    assert not matched[n_out:].any()
    assert p.min(initial=0) >= 0 and p.max(initial=0) < p_cap
    assert brow.min(initial=0) >= 0 and brow.max(initial=0) < b_cap
    if outer:
        assert (~want_matched).any()


@pytest.mark.parametrize("how,chunks", [("inner", 1), ("left", 1),
                                        ("semi", 0), ("anti", 0)])
def test_join_metrics_count_the_expansions_slots(session, how, chunks):
    """``expand_capacity`` is the sum of the emitted chunks' bucketed
    capacities (what ``join_gather_indices`` maps, whatever the rows in
    them) and ``expand_chunks`` their number; semi and anti joins emit
    no pairs and bump neither."""
    from spark_rapids_tpu.columnar.column import bucket_capacity
    from spark_rapids_tpu.exec.join import join_metrics
    left, right, got = _join_frames(session, how)
    before = join_metrics.snapshot()
    rows = len(got.to_pandas())
    after = join_metrics.snapshot()
    assert {"expand_capacity", "expand_chunks"} <= set(after)
    moved = {k: after[k] - before[k] for k in after}
    assert moved["expand_chunks"] == chunks
    assert moved["expand_capacity"] == \
        (bucket_capacity(rows) if chunks else 0)
    assert moved["probe_rows"] > 0
    assert moved["output_rows"] == (rows if chunks else 0)


@pytest.mark.parametrize("how", ["inner", "left"])
def test_chunked_join_output_is_the_whole_expansion(how):
    """A probe batch whose pairs leave in chunks of ``outputBatchRows``:
    every chunk after the first maps its rows from ``ends - offset``
    (ends at or below zero before it, ends past its capacity after it),
    and the chunks together are the join; ``expand_capacity`` is the sum
    of the chunks' bucketed capacities."""
    from spark_rapids_tpu.columnar.column import bucket_capacity
    from spark_rapids_tpu.exec.join import join_metrics
    chunk = 96                      # no power of two: capacity 128 a chunk
    s = TpuSession({"spark.rapids.sql.join.outputBatchRows": str(chunk)})
    left, right, got = _join_frames(s, how, kmax=80)
    want = left.merge(right, on="k", how=how)
    before = join_metrics.snapshot()
    _compare_join(got, want)
    after = join_metrics.snapshot()
    sizes = [min(chunk, len(want) - off)
             for off in range(0, len(want), chunk)]
    assert len(sizes) > 5
    assert after["expand_chunks"] - before["expand_chunks"] == len(sizes)
    assert after["expand_capacity"] - before["expand_capacity"] == \
        sum(bucket_capacity(n) for n in sizes)
