"""Whole-stage fusion compiler + persistent AOT executable cache suite.

Four layers, mirroring ISSUE 8's acceptance criteria:

* oracle parity — fused execution is BIT-identical (batchwise arrow
  equality, nulls/NaN included) to unfused execution across TPC-H
  q1/q3/q6 and TPC-DS q3/q55/q96, and matches the pandas oracle;
* dispatch budget (counter-pinned, no timing) — a q6-shape
  scan→filter→project→aggregate pipeline executes ONE fused jit call
  per batch where the unfused plan pays >= 3;
* lineage stability — fusion never crosses an exchange, so a fused
  plan's checkpoint ``stage_id`` is unchanged and PR5 stage checkpoints
  written before the fuser still splice (counter-pinned resume);
* persistent cache — with ``jitCache.dir`` set, a fresh process
  re-running the same query records ZERO persistent misses (pinned);
  corruption, truncation, and version mismatch degrade to a fresh
  compile with a ``JitCacheInvalid`` event — never a wrong result.
"""

import glob
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.models import tpch, tpcds
from spark_rapids_tpu.ops import jit_cache
from spark_rapids_tpu.robustness import inject as I

FUSE_ON = {"spark.rapids.tpu.fusion.enabled": True}
FUSE_OFF = {"spark.rapids.tpu.fusion.enabled": False}


@pytest.fixture(autouse=True)
def _clean_registry():
    I.clear()
    yield
    I.clear()
    jit_cache.configure_persistent(None)


@pytest.fixture(scope="module")
def data():
    return tpch.gen_tables(sf=0.002)


@pytest.fixture(scope="module")
def ds_data():
    return tpcds.gen_tables(sf=0.003)


def _norm(df: pd.DataFrame) -> pd.DataFrame:
    return df.sort_values(list(df.columns), ignore_index=True,
                          na_position="last")


def _batches_of(conf, build):
    s = TpuSession(dict(conf))
    return s, build(s)._execute_batches()


def _assert_fused_identical(build, extra=()):
    """The strong A/B form: fusion on vs off — same batch count, same
    per-batch row counts, bit-identical arrow contents (nulls/NaN
    included)."""
    extra = dict(extra)
    s_on, got = _batches_of({**FUSE_ON, **extra}, build)
    s_off, want = _batches_of({**FUSE_OFF, **extra}, build)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.nrows == w.nrows
        ga, wa = g.to_arrow(), w.to_arrow()
        assert ga.equals(wa), f"batch diverged: {ga} vs {wa}"
    return s_on, s_off


# --------------------------------------------------------- oracle parity --
@pytest.mark.parametrize("q", ["q1", "q3", "q6"])
def test_fused_tpch_bit_identical(data, q):
    def build(s):
        return getattr(tpch, q)(tpch.load(s, data))

    s_on, s_off = _assert_fused_identical(build)
    # q1 too: its filter folds into the string-keyed group-by as the
    # stage-B kernels' row mask
    fu = s_on.overrides.last_fusion
    assert fu["fusedStages"] >= 1, fu
    assert s_off.overrides.last_fusion["fusedStages"] == 0


def test_fused_q6_matches_pandas(data):
    s = TpuSession(dict(FUSE_ON))
    got = tpch.q6(tpch.load(s, data)).to_pandas()
    l = data["lineitem"]
    m = l[(l.l_shipdate >= pd.Timestamp("1994-01-01")) &
          (l.l_shipdate < pd.Timestamp("1995-01-01")) &
          (l.l_discount >= 0.05) & (l.l_discount <= 0.07) &
          (l.l_quantity < 24)]
    want = (m.l_extendedprice * m.l_discount).sum()
    np.testing.assert_allclose(got.iloc[0, 0], want, rtol=1e-9)


@pytest.mark.parametrize("q", ["q3", "q55", "q96"])
def test_fused_tpcds_bit_identical(ds_data, q):
    on = TpuSession(dict(FUSE_ON))
    tpcds.load(on, ds_data)
    off = TpuSession(dict(FUSE_OFF))
    tpcds.load(off, ds_data)
    got = on.sql(tpcds.QUERIES[q]).to_arrow()
    want = off.sql(tpcds.QUERIES[q]).to_arrow()
    assert got.equals(want)


def test_fused_nulls_and_nan_bit_identical():
    rng = np.random.default_rng(11)
    pdf = pd.DataFrame({
        "a": rng.normal(size=2000),
        "b": rng.integers(0, 9, 2000).astype(np.float64),
        "s": rng.choice(["x", "yy", None], 2000),
    })
    pdf.loc[::5, "a"] = np.nan
    pdf.loc[::7, "b"] = None

    def build(s):
        return (s.create_dataframe(pdf)
                .filter(F.col("b") > 1.0)
                .select((F.col("a") / F.col("b")).alias("q"),
                        F.col("b"), F.col("s"))
                .filter(~F.col("q").isNull() | F.col("s").isNotNull())
                .select(F.col("q"), (F.col("b") * 0.5).alias("h"),
                        F.col("s")))

    _assert_fused_identical(build)


def test_fused_ansi_checks_only_fire_for_survivors():
    """A fused chain evaluates projections over PRE-filter rows; an
    ANSI cast must not raise for a row the upstream filter drops (the
    unfused plan compacts it away first) — but must still raise when
    the offending row SURVIVES."""
    pdf = pd.DataFrame({"v": [1.0, 2.0, 1e20],
                        "w": [1.0, 2.0, 100.0]})

    def build(s, cutoff):
        return (s.create_dataframe(pdf)
                .filter(F.col("w") < cutoff)
                .select(F.col("v").cast("int", ansi=True).alias("i"))
                .filter(F.col("i") >= 0))

    s_on = TpuSession(dict(FUSE_ON))
    s_off = TpuSession(dict(FUSE_OFF))
    # overflow row filtered out: both modes succeed identically
    got = build(s_on, 50).to_pandas()
    want = build(s_off, 50).to_pandas()
    pd.testing.assert_frame_equal(got, want)
    assert got["i"].tolist() == [1, 2]
    # overflow row survives the filter: both modes raise
    for s in (s_on, s_off):
        with pytest.raises(ArithmeticError):
            build(s, 1000).to_pandas()


def test_agg_fold_ansi_checks_only_fire_for_survivors():
    """Same contract through the AGGREGATE fold: a chain of two filters
    (ANSI cast in the upper one) feeding a group-by — the fused update
    kernel's progressive conjunct masking must not raise for the row
    the bottom filter drops."""
    pdf = pd.DataFrame({"k": [1, 1, 2],
                        "v": [1.0, 2.0, 1e20],
                        "w": [1.0, 2.0, 100.0]})

    def build(s, cutoff):
        return (s.create_dataframe(pdf)
                .filter(F.col("w") < cutoff)
                .filter(F.col("v").cast("int", ansi=True) >= 0)
                .groupBy("k").agg(F.sum("v").alias("sv")))

    s_on = TpuSession(dict(FUSE_ON))
    s_off = TpuSession(dict(FUSE_OFF))
    got = _norm(build(s_on, 50).to_pandas())
    assert s_on.overrides.last_fusion["fusedStages"] >= 1
    want = _norm(build(s_off, 50).to_pandas())
    pd.testing.assert_frame_equal(got, want)
    for s in (s_on, s_off):
        with pytest.raises(ArithmeticError):
            build(s, 1000).to_pandas()


# A Filter/Project chain under an Aggregate folds whatever the key and
# buffer types (ISSUE 33): the two-stage string path takes the predicate
# as the group-by's row mask.  Every case runs over five in-memory
# batches of 64 rows (a parquet scan would apply a pushed filter on the
# host, and the mask would have nothing to drop); ``x`` is 1..320 in row
# order, so ``x`` ranges pick batches.
def _fold_frame():
    rng = np.random.default_rng(33)
    n = 320
    flag = rng.choice(np.array(["A", "N", "R", None], dtype=object), n)
    status = rng.choice(np.array(["F", "O"], dtype=object), n)
    x = np.arange(1, n + 1, dtype=np.int64)
    # "ZZ" and "!only" occur in dropped rows alone (x <= 20): the former
    # as a key, the latter as what min(s) would pick; "~last" likewise
    # for max(s)
    flag[:20] = "ZZ"
    s = rng.choice(np.array(["kiwi", "apple", "mango", "fig", None],
                            dtype=object), n)
    s[:10] = "!only"
    s[10:20] = "~last"
    v = rng.normal(size=n).round(3)
    w = np.where(np.arange(n) % 7 == 3, np.nan, rng.integers(0, 100, n))
    return pd.DataFrame({"flag": flag, "status": status, "x": x, "s": s,
                         "v": v, "w": w,
                         "phone": [f"{10 + i % 4}-{i:04d}"
                                   for i in range(n)]})


def _fold_q1_shape(df):
    return (df.filter(F.col("x") > 20).groupBy("flag", "status")
            .agg(F.sum("v").alias("sv"), F.avg("v").alias("av"),
                 F.count("*").alias("c")))


def _fold_q1_oracle(p):
    m = p[p.x > 20]
    return (m.groupby(["flag", "status"], dropna=False)
            .agg(sv=("v", "sum"), av=("v", "mean"), c=("v", "size"))
            .reset_index())


_FOLD_CASES = {
    # q1's shape: two string keys, NULLs among them, partials of five
    # batches merged
    "q1_shape_null_keys": (_fold_q1_shape, _fold_q1_oracle),
    # "ZZ" is encoded on the host (the encoder sees every row) and must
    # make no group
    "key_only_in_dropped_rows": (
        lambda df: df.filter(F.col("x") > 20).groupBy("flag")
        .agg(F.count("*").alias("c")),
        lambda p: p[p.x > 20].groupby("flag", dropna=False)
        .agg(c=("v", "size")).reset_index()),
    # the second batch (x 65..128) loses every row
    "batch_with_every_row_dropped": (
        lambda df: df.filter((F.col("x") <= 64) | (F.col("x") > 128))
        .groupBy("status").agg(F.sum("v").alias("sv"),
                               F.count("*").alias("c")),
        lambda p: p[(p.x <= 64) | (p.x > 128)].groupby("status")
        .agg(sv=("v", "sum"), c=("v", "size")).reset_index()),
    # w is NULL every seventh row: a NULL predicate keeps no row
    "predicate_null_for_some_rows": (
        lambda df: df.filter(F.col("w") < 50).groupBy("flag")
        .agg(F.sum("v").alias("sv"), F.count("*").alias("c")),
        lambda p: p[p.w < 50].groupby("flag", dropna=False)
        .agg(sv=("v", "sum"), c=("v", "size")).reset_index()),
    # TPC-H Q22's cntrycode: the key is computed, not a column
    "computed_string_key": (
        lambda df: df.filter(F.col("x") > 20)
        .groupBy(F.substring(F.col("phone"), 1, 2).alias("cc"))
        .agg(F.sum("v").alias("sv"), F.count("*").alias("c")),
        lambda p: p[p.x > 20].assign(cc=p.phone.str[:2]).groupby("cc")
        .agg(sv=("v", "sum"), c=("v", "size")).reset_index()),
    # the dropped rows hold the would-be extrema "!only" and "~last"
    "string_minmax_grouped": (
        lambda df: df.filter(F.col("x") > 20).groupBy("status")
        .agg(F.min("s").alias("lo"), F.max("s").alias("hi")),
        lambda p: p[p.x > 20].groupby("status")
        .agg(lo=("s", "min"), hi=("s", "max")).reset_index()),
    "string_minmax_keyless": (
        lambda df: df.filter(F.col("x") > 20)
        .agg(F.min("s").alias("lo"), F.max("s").alias("hi")),
        lambda p: pd.DataFrame({"lo": [p[p.x > 20].s.dropna().min()],
                                "hi": [p[p.x > 20].s.dropna().max()]})),
    # Filter <- Project <- Aggregate: the projection substitutes into
    # the key and the aggregate's child, the predicate stays a mask
    "project_between_filter_and_aggregate": (
        lambda df: df.filter(F.col("x") > 20)
        .select(F.col("flag").alias("f"),
                (F.col("v") * 2.0).alias("v2"))
        .groupBy("f").agg(F.sum("v2").alias("sv")),
        lambda p: p[p.x > 20].assign(v2=p.v * 2.0)
        .groupby("flag", dropna=False).agg(sv=("v2", "sum"))
        .reset_index().rename(columns={"flag": "f"})),
}


@pytest.mark.parametrize("case", list(_FOLD_CASES))
def test_agg_fold_string_keys_and_buffers(case):
    from spark_rapids_tpu.api.dataframe import DataFrame
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.plan import logical as L
    build, oracle = _FOLD_CASES[case]
    pdf = _fold_frame()
    batches = [ColumnarBatch.from_pandas(pdf.iloc[i:i + 64])
               for i in range(0, len(pdf), 64)]

    def norm(df):
        df = df.copy()
        for c in df.columns:
            if df[c].dtype == object:
                df[c] = df[c].where(df[c].notna(), "<null>")
        return _norm(df)

    res = {}
    for fuse in (True, False):
        s = TpuSession({"spark.rapids.tpu.fusion.enabled": fuse})
        df = build(DataFrame(s, L.InMemoryRelation(batches,
                                                   batches[0].schema)))
        tree = s.plan(df.plan).tree_string()
        res[fuse] = norm(df.to_pandas())
        fu = s.last_fusion_stats
        if fuse:
            # the chain is gone from under the aggregate, which names it
            assert "TpuFilterExec" not in tree, tree
            assert "FusedStageExec" not in tree, tree
            assert "pre_filter=" in tree, tree
            assert fu["fusedStages"] >= 1, fu
        else:
            assert "TpuFilterExec" in tree, tree
            assert fu["fusedStages"] == 0, fu
    pd.testing.assert_frame_equal(res[True], res[False])
    want = norm(oracle(pdf))
    pd.testing.assert_frame_equal(res[True], want[list(res[True].columns)],
                                  check_dtype=False)


# ------------------------------------------------------------ plan shape --
def _chain_df(s, pdf):
    return (s.create_dataframe(pdf)
            .filter(F.col("w") > 10)
            .select(F.col("k"), (F.col("v") * F.col("w")).alias("vw"))
            .filter(F.col("vw") < 50.0))


def test_fused_stage_exec_in_plan():
    from spark_rapids_tpu.exec.fusion import FusedStageExec
    rng = np.random.default_rng(0)
    pdf = pd.DataFrame({"k": rng.integers(0, 20, 500),
                        "v": rng.normal(size=500),
                        "w": rng.integers(0, 100, 500).astype(float)})
    s = TpuSession(dict(FUSE_ON))
    plan = s.plan(_chain_df(s, pdf).plan)
    assert isinstance(plan, FusedStageExec)
    assert len(plan.members) == 3  # Filter + Project + Filter
    assert "FusedStageExec" in plan.tree_string()
    off = TpuSession(dict(FUSE_OFF))
    plan_off = off.plan(_chain_df(off, pdf).plan)
    assert "FusedStageExec" not in plan_off.tree_string()
    fu = off.overrides.last_fusion
    assert fu["fusibleChains"] == 1 and fu["fusedStages"] == 0


def test_fusion_stops_at_udf_member():
    """A black-box Python UDF projection is not fusible: the chain
    splits around it (auto-fallback), and the answer still matches."""
    rng = np.random.default_rng(1)
    pdf = pd.DataFrame({"v": rng.normal(size=400),
                        "w": rng.integers(1, 50, 400).astype(float)})
    scale = {0: 3.0}

    @F.udf(returnType="double")
    def triple(x):
        # dict .get() is outside the udf-compiler subset: a genuine
        # host black box
        return x * scale.get(0, 3.0)

    def build(s):
        return (s.create_dataframe(pdf)
                .filter(F.col("w") > 5)
                .select(triple(F.col("v")).alias("u"), F.col("w"))
                .filter(F.col("u") > 0)
                .select((F.col("u") + F.col("w")).alias("z")))

    s_on, _ = _assert_fused_identical(build)
    # the chain ABOVE the UDF fuses; the UDF member itself runs on the
    # host ArrowEval exec, never inside a fused stage
    tree = s_on.plan(build(s_on).plan).tree_string()
    assert "FusedStageExec" in tree
    assert "TpuArrowEvalPythonExec" in tree


def test_fusion_max_chain_ops_splits():
    rng = np.random.default_rng(2)
    pdf = pd.DataFrame({"v": rng.normal(size=100)})

    def build(s):
        df = s.create_dataframe(pdf)
        for i in range(6):
            df = df.select((F.col("v") + i).alias("v"))
        return df

    s = TpuSession({**FUSE_ON, "spark.rapids.tpu.fusion.maxChainOps": 2})
    plan = s.plan(build(s).plan)
    from spark_rapids_tpu.exec.fusion import FusedStageExec

    def count(n):
        return (1 if isinstance(n, FusedStageExec) else 0) + \
            sum(count(c) for c in n.children)

    assert count(plan) == 3  # 6 projects in chains of <= 2
    got = build(s).to_pandas()
    want = build(TpuSession(dict(FUSE_OFF))).to_pandas()
    pd.testing.assert_frame_equal(got, want)


# -------------------------------------------------------- dispatch budget --
def _q6_shape_batches(k=4, n=2048):
    from spark_rapids_tpu.plan import logical as L
    from spark_rapids_tpu.api.dataframe import DataFrame
    rng = np.random.default_rng(42)
    batches = []
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    for _ in range(k):
        batches.append(ColumnarBatch.from_pydict({
            "price": rng.uniform(1000.0, 100000.0, n),
            "disc": rng.uniform(0.0, 0.11, n).round(2),
            "qty": rng.integers(1, 51, n).astype(np.float64),
            "ship": rng.integers(8766, 10957, n).astype(np.int32),
        }))
    return batches


def _q6_shape_df(s, batches):
    from spark_rapids_tpu.api.dataframe import DataFrame
    from spark_rapids_tpu.plan import logical as L
    df = DataFrame(s, L.InMemoryRelation(batches, batches[0].schema))
    return (df.filter((F.col("ship") >= 9131) & (F.col("ship") < 9496) &
                      (F.col("disc") >= 0.05) & (F.col("qty") < 24.0))
            .select((F.col("price") * F.col("disc")).alias("rev"))
            .agg(F.sum("rev").alias("revenue")))


@pytest.mark.perf
def test_q6_shape_dispatch_budget_counter_pinned():
    """The tentpole's measurable core: the fused
    scan→filter→project→partial-aggregate pipeline dispatches ONE
    jitted call per batch; the unfused plan pays one per operator
    (>= 3 per batch).  Counts only — deterministic on any backend."""
    k = 4
    batches = _q6_shape_batches(k=k)

    def measure(conf):
        s = TpuSession(dict(conf))
        df = _q6_shape_df(s, batches)
        want = df.to_pandas()      # warm the in-memory jit cache
        d0 = jit_cache.dispatch_count()
        got = df.to_pandas()
        d = jit_cache.dispatch_count() - d0
        pd.testing.assert_frame_equal(got, want)
        return got, d

    got_on, fused = measure(FUSE_ON)
    got_off, unfused = measure(FUSE_OFF)
    pd.testing.assert_frame_equal(got_on, got_off)
    # fused: one update call per batch + the final merge (small const)
    assert fused <= k + 3, \
        f"fused pipeline dispatched {fused} calls for {k} batches"
    # unfused: filter + project + agg-update per batch at minimum
    assert unfused >= 3 * k, \
        f"unfused pipeline dispatched only {unfused} calls " \
        f"for {k} batches"
    assert fused < unfused


# ------------------------------------------------- lineage / checkpoints --
NSHARDS = 8


@pytest.fixture(scope="module")
def mesh():
    import jax
    if jax.device_count() < NSHARDS:
        pytest.skip("needs the virtual 8-device mesh")
    from spark_rapids_tpu.parallel.mesh import make_mesh
    return make_mesh(NSHARDS)


def test_stage_id_independent_of_fusion_conf(mesh):
    """The lineage contract: fusion happens strictly BELOW exchange
    boundaries, so the checkpoint stage id of the exchange a fused
    chain feeds is byte-identical with fusion on or off — PR5
    checkpoints and PR7 incremental state written before the fuser
    still splice."""
    from spark_rapids_tpu.robustness import checkpoint as cp
    rng = np.random.default_rng(3)
    pdf = pd.DataFrame({"k": rng.integers(0, 40, 2048),
                        "v": rng.normal(size=2048),
                        "w": rng.integers(0, 99, 2048).astype(float)})

    def build(s):
        return (s.create_dataframe(pdf)
                .filter(F.col("w") > 10)
                .select(F.col("k"), (F.col("v") * 2).alias("v2"))
                .groupBy("k").agg(F.sum("v2").alias("sv"))
                .orderBy("k"))

    s_on = TpuSession(dict(FUSE_ON), mesh=mesh)
    s_off = TpuSession(dict(FUSE_OFF), mesh=mesh)
    # inputs=False: the per-query manager's key form (input identity is
    # session-local; the structural half is what fusion must not move)
    sid_on = cp.stage_id(build(s_on).plan, mesh, packed=True,
                         inputs=False)
    sid_off = cp.stage_id(build(s_off).plan, mesh, packed=True,
                          inputs=False)
    assert sid_on == sid_off
    # and the sort stage above it agrees too
    assert cp.stage_id(build(s_on).plan.child, mesh, packed=True,
                       inputs=False) == \
        cp.stage_id(build(s_off).plan.child, mesh, packed=True,
                    inputs=False)


@pytest.mark.chaos
def test_fused_plan_resumes_unfused_checkpoints(mesh):
    """Checkpoints written by an (unfused-era) attempt splice into the
    fused planner's resume: fault the second exchange, pin exactly one
    extra launch, identical results — with fusion ON."""
    from spark_rapids_tpu.robustness.checkpoint import checkpoint_metrics
    rng = np.random.default_rng(3)
    pdf = pd.DataFrame({"k": rng.integers(0, 40, 4096),
                        "v": rng.normal(size=4096),
                        "w": rng.integers(0, 99, 4096).astype(float)})
    s = TpuSession({**FUSE_ON, "spark.rapids.sql.recovery.backoffMs": 1},
                   mesh=mesh)
    df = (s.create_dataframe(pdf)
          .filter(F.col("w") > 10)
          .select(F.col("k"), (F.col("v") * 2).alias("v2"))
          .groupBy("k").agg(F.sum("v2").alias("sv"))
          .orderBy("k"))

    def count_rule():
        return I.inject("shuffle.exchange", count=1, skip=1_000_000,
                        all_threads=True)

    with I.scoped_rules():
        launches = count_rule()
        want = df.to_pandas()
        clean = 1_000_000 - launches.skip
        I.remove(launches)
        assert clean >= 2
        assert s.last_dist_explain == "distributed"
        assert s.last_fusion_stats["fusedStages"] >= 1

        checkpoint_metrics.reset()
        s.recovery_log.clear()
        launches = count_rule()
        with I.injected("shuffle.exchange", count=1, skip=1):
            got = df.to_pandas()
        faulted = 1_000_000 - launches.skip
        I.remove(launches)
    pd.testing.assert_frame_equal(got, want)
    m = checkpoint_metrics.snapshot()
    assert m["resumes"] >= 1 and m["stagesSkipped"] >= 1
    # the fused aggregate stage's checkpoint spliced: ONE extra launch
    assert faulted == clean + 1


def test_distributed_fused_ab_bit_identical(mesh):
    rng = np.random.default_rng(7)
    pdf = pd.DataFrame({"k": rng.integers(0, 30, 4096),
                        "v": rng.normal(size=4096),
                        "w": rng.integers(0, 99, 4096).astype(float)})

    def build(s):
        return (s.create_dataframe(pdf)
                .filter(F.col("w") > 5)
                .select(F.col("k"), (F.col("v") + F.col("w")).alias("x"))
                .filter(F.col("x") > 0)
                .groupBy("k").agg(F.sum("x").alias("sx"),
                                  F.count("x").alias("c"))
                .orderBy("k"))

    s_on = TpuSession(dict(FUSE_ON), mesh=mesh)
    got = build(s_on).to_arrow()
    assert s_on.last_dist_explain == "distributed"
    fu = s_on.last_fusion_stats
    assert fu["fusedStages"] >= 1 and fu["dispatchesSaved"] >= 1, fu
    s_off = TpuSession(dict(FUSE_OFF), mesh=mesh)
    want = build(s_off).to_arrow()
    assert s_off.last_dist_explain == "distributed"
    assert s_off.last_fusion_stats["fusedStages"] == 0
    assert got.equals(want)


# ------------------------------------------------------ persistent cache --
def _simple_df(s, pdf):
    return (s.create_dataframe(pdf)
            .filter(F.col("v") > -1.0)
            .select((F.col("v") * 2.0).alias("v2"), F.col("k"))
            .groupBy("k").agg(F.sum("v2").alias("sv")))


def _fresh_against(d):
    """Simulate a fresh process: drop every in-memory executable, keep
    (re-point at) the on-disk store."""
    jit_cache.clear()
    jit_cache.configure_persistent(None)
    jit_cache.configure_persistent(d)


@pytest.fixture()
def cache_pdf():
    rng = np.random.default_rng(5)
    return pd.DataFrame({"k": rng.integers(0, 50, 2000),
                         "v": rng.normal(size=2000)})


def test_persistent_cache_warm_start_miss_pinned(tmp_path, cache_pdf):
    d = str(tmp_path / "jitcache")
    s = TpuSession({"spark.rapids.tpu.jitCache.dir": d})
    jit_cache.clear()
    df = _simple_df(s, cache_pdf)
    want = df.to_pandas()
    cold = jit_cache.persistent_info()
    assert cold["stores"] >= 1 and cold["misses"] >= 1
    assert glob.glob(os.path.join(d, "*.jit"))

    _fresh_against(d)
    got = _simple_df(s, cache_pdf).to_pandas()
    warm = jit_cache.persistent_info()
    # the warm-start acceptance pin: ZERO new compiles
    assert warm["misses"] == 0, warm
    assert warm["hits"] >= 1
    pd.testing.assert_frame_equal(_norm(got), _norm(want))


def test_persistent_cache_fresh_process_zero_misses(tmp_path, cache_pdf):
    """The real thing: a SECOND PYTHON PROCESS re-running the same
    query against the same jitCache.dir records zero persistent misses
    and an identical answer."""
    d = str(tmp_path / "jitcache")
    csv = str(tmp_path / "data.csv")
    cache_pdf.to_csv(csv, index=False)
    out = str(tmp_path / "out%d.json")
    script = r"""
import json, sys
import pandas as pd
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.ops import jit_cache
pdf = pd.read_csv(sys.argv[1])
s = TpuSession({"spark.rapids.tpu.jitCache.dir": sys.argv[2]})
df = (s.create_dataframe(pdf)
      .filter(F.col("v") > -1.0)
      .select((F.col("v") * 2.0).alias("v2"), F.col("k"))
      .groupBy("k").agg(F.sum("v2").alias("sv")))
res = df.to_pandas().sort_values("k", ignore_index=True)
info = jit_cache.persistent_info()
with open(sys.argv[3], "w") as f:
    json.dump({"misses": info["misses"], "hits": info["hits"],
               "stores": info["stores"],
               "sum": res["sv"].sum()}, f)
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    runs = []
    for i in (1, 2):
        p = subprocess.run(
            [sys.executable, "-c", script, csv, d, out % i],
            env=env, capture_output=True, text=True, timeout=240)
        assert p.returncode == 0, p.stderr[-2000:]
        with open(out % i) as f:
            runs.append(json.load(f))
    assert runs[0]["misses"] >= 1 and runs[0]["stores"] >= 1
    # acceptance pin: the second process compiled NOTHING
    assert runs[1]["misses"] == 0, runs[1]
    assert runs[1]["hits"] >= 1
    assert runs[0]["sum"] == runs[1]["sum"]


def test_persistent_cache_corruption_recovers(tmp_path, cache_pdf):
    d = str(tmp_path / "jitcache")
    logdir = str(tmp_path / "events")
    s = TpuSession({"spark.rapids.tpu.jitCache.dir": d,
                    "spark.rapids.tpu.eventLog.dir": logdir})
    jit_cache.clear()
    df = _simple_df(s, cache_pdf)
    want = df.to_pandas()
    entries = sorted(glob.glob(os.path.join(d, "*.jit")))
    assert entries
    # flip a byte deep in the first entry's payload
    with open(entries[0], "r+b") as f:
        raw = f.read()
        f.seek(len(raw) - 16)
        f.write(bytes([raw[-16] ^ 0x40]))

    _fresh_against(d)
    got = _simple_df(s, cache_pdf).to_pandas()
    pd.testing.assert_frame_equal(_norm(got), _norm(want))
    info = jit_cache.persistent_info()
    assert info["invalid"] >= 1, info
    assert info["stores"] >= 1  # the dropped entry was re-persisted
    s.stop()
    from spark_rapids_tpu.tools.eventlog import load_logs
    app = load_logs(logdir)[0]
    events = [j for q in app.queries for j in q.jitcache] + app.jitcache
    assert any("crc" in j.get("reason", "") for j in events), events


def test_persistent_cache_version_mismatch_recovers(tmp_path, cache_pdf):
    d = str(tmp_path / "jitcache")
    s = TpuSession({"spark.rapids.tpu.jitCache.dir": d})
    jit_cache.clear()
    df = _simple_df(s, cache_pdf)
    want = df.to_pandas()
    for path in glob.glob(os.path.join(d, "*.jit")):
        raw = open(path, "rb").read()
        head, _, payload = raw.partition(b"\n")
        hdr = json.loads(head)
        hdr["env"]["jaxlib"] = "0.0.0-elsewhere"
        with open(path, "wb") as f:
            f.write(json.dumps(hdr).encode() + b"\n" + payload)

    _fresh_against(d)
    got = _simple_df(s, cache_pdf).to_pandas()
    pd.testing.assert_frame_equal(_norm(got), _norm(want))
    info = jit_cache.persistent_info()
    assert info["invalid"] >= 1 and info["hits"] == 0, info


@pytest.mark.chaos
def test_persistent_cache_load_chaos_bit_flip(tmp_path, cache_pdf):
    """The jitcache.load fire_mutate hook: an armed corrupt rule rots
    the payload in flight; the CRC gate drops the entry and the query
    recompiles to the exact answer."""
    d = str(tmp_path / "jitcache")
    s = TpuSession({"spark.rapids.tpu.jitCache.dir": d})
    jit_cache.clear()
    df = _simple_df(s, cache_pdf)
    want = df.to_pandas()

    _fresh_against(d)
    with I.scoped_rules():
        I.inject("jitcache.load", kind="corrupt", count=2,
                 all_threads=True)
        got = _simple_df(s, cache_pdf).to_pandas()
    pd.testing.assert_frame_equal(_norm(got), _norm(want))
    info = jit_cache.persistent_info()
    assert info["invalid"] >= 1, info


def test_persistent_cache_max_bytes_prunes(tmp_path, cache_pdf):
    d = str(tmp_path / "jitcache")
    s = TpuSession({"spark.rapids.tpu.jitCache.dir": d,
                    "spark.rapids.tpu.jitCache.maxBytes": 1})
    jit_cache.clear()
    _simple_df(s, cache_pdf).to_pandas()
    # every store immediately prunes back under the 1-byte budget
    assert len(glob.glob(os.path.join(d, "*.jit"))) <= 1


# ------------------------------------------------------- build-race dedup --
def test_cached_jit_build_race_single_build():
    """N threads racing into one new signature share ONE build: make()
    runs exactly once (the per-signature build lock), so concurrent
    queries share one compile."""
    jit_cache.clear()
    sig = ("test_fusion", "race")
    calls = []
    got = []
    barrier = threading.Barrier(8)

    def make():
        calls.append(threading.get_ident())
        return lambda x: x + 1

    def hit():
        barrier.wait()
        got.append(jit_cache.cached_jit(sig, make))

    threads = [threading.Thread(target=hit) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(calls) == 1, f"{len(calls)} duplicate builds"
    assert len({id(f) for f in got}) == 1
    info = jit_cache.cache_info()
    assert info["misses"] == 1 and info["hits"] == 7
    import jax.numpy as jnp
    assert int(got[0](jnp.int32(2))) == 3
    jit_cache.clear()


# ---------------------------------------------------------- observability --
def test_fusion_eventlog_and_health(tmp_path, cache_pdf):
    from spark_rapids_tpu.tools.eventlog import load_logs
    from spark_rapids_tpu.tools.profiling import (fusion_stats,
                                                  health_check)
    logdir = str(tmp_path / "ev-on")
    s = TpuSession({**FUSE_ON, "spark.rapids.tpu.eventLog.dir": logdir})
    _simple_df(s, cache_pdf).to_pandas()
    s.stop()
    apps = load_logs(logdir)
    q = apps[0].queries[-1]
    assert q.fusion["fusedStages"] >= 1
    assert q.fusion["fusibleChains"] >= 1
    assert q.fusion["dispatchesSaved"] >= 1
    assert "persistentHits" in q.fusion
    agg = fusion_stats(apps)
    assert agg["fused_stages"] >= 1 and agg["dispatches_saved"] >= 1
    assert not any("ran UNFUSED" in p for p in health_check(apps))

    logdir_off = str(tmp_path / "ev-off")
    s2 = TpuSession({**FUSE_OFF,
                     "spark.rapids.tpu.eventLog.dir": logdir_off})
    _simple_df(s2, cache_pdf).to_pandas()
    s2.stop()
    apps2 = load_logs(logdir_off)
    q2 = apps2[0].queries[-1]
    assert q2.fusion["fusedStages"] == 0 and \
        q2.fusion["fusibleChains"] >= 1
    assert any("ran UNFUSED" in p for p in health_check(apps2))


def test_persistent_thrash_health_check(tmp_path, cache_pdf):
    """Repeat of the same plan with zero warm hits but fresh misses —
    the 'persistent cache bought nothing' health check fires."""
    from spark_rapids_tpu.tools.eventlog import load_logs
    from spark_rapids_tpu.tools.profiling import health_check
    d = str(tmp_path / "jitcache")
    logdir = str(tmp_path / "events")
    s = TpuSession({"spark.rapids.tpu.jitCache.dir": d,
                    "spark.rapids.tpu.eventLog.dir": logdir})
    jit_cache.clear()
    _simple_df(s, cache_pdf).to_pandas()
    # wipe the store so the repeat re-misses with zero hits (a broken
    # or version-churned dir in production)
    for p in glob.glob(os.path.join(d, "*.jit")):
        os.unlink(p)
    _fresh_against(d)
    _simple_df(s, cache_pdf).to_pandas()
    s.stop()
    problems = health_check(load_logs(logdir))
    assert any("0% hit on a REPEAT" in p for p in problems), problems
