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
