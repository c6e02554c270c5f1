"""Config completeness: unknown-key rejection, per-op enable keys,
incompat tier (RapidsConf.scala + RapidsMeta.scala:271 analogs)."""

import pathlib
import re

import pytest

import spark_rapids_tpu
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.config.rapids_conf import RapidsConf


def test_unknown_rapids_key_rejected():
    with pytest.raises(ValueError, match="unknown configuration key"):
        RapidsConf({"spark.rapids.sql.batchSizeByts": "1024"})  # typo
    # non-rapids keys pass through untouched
    RapidsConf({"spark.sql.shuffle.partitions": "8"})


def test_per_expression_disable():
    s = TpuSession({"spark.rapids.sql.expression.Upper": "false"})
    df = s.create_dataframe({"x": ["ab"]})
    q = df.select(F.upper("x").alias("u"))
    tree = s.plan(q.plan).tree_string()
    assert "CpuFallbackExec" in tree
    assert "disabled by spark.rapids.sql.expression.Upper" in \
        s.overrides.last_explain
    # still enabled by default
    s2 = TpuSession()
    assert "CpuFallbackExec" not in s2.plan(q.plan).tree_string()


def test_per_exec_disable():
    s = TpuSession({"spark.rapids.sql.exec.Sort": "false"})
    df = s.create_dataframe({"x": [3, 1, 2]})
    tree = s.plan(df.orderBy("x").plan).tree_string()
    assert "CpuFallbackExec" in tree
    assert df.orderBy("x").to_pandas()["x"].tolist() == [1, 2, 3]


def test_incompat_tier():
    s = TpuSession({"spark.rapids.sql.incompatibleOps.enabled": "false"})
    df = s.create_dataframe({"x": ["ab1"]})
    # regex ops are incompat-flagged (byte-semantics)
    q = df.select(F.rlike("x", r"\d").alias("m"))
    tree = s.plan(q.plan).tree_string()
    assert "CpuFallbackExec" in tree
    assert "incompatible" in s.overrides.last_explain
    assert bool(q.to_pandas()["m"][0])  # fallback still correct
    # default: runs on device
    s2 = TpuSession()
    assert "CpuFallbackExec" not in s2.plan(q.plan).tree_string()


def test_conf_docs_generate():
    from spark_rapids_tpu.config.rapids_conf import RapidsConf
    reg = RapidsConf.registry()
    assert len(reg) >= 25
    assert "spark.rapids.sql.incompatibleOps.enabled" in reg


def test_per_op_key_typo_rejected():
    with pytest.raises(ValueError, match="unknown configuration key"):
        RapidsConf({"spark.rapids.sql.expression.Uppr": "false"})


def test_window_expression_disable_honored():
    s = TpuSession(
        {"spark.rapids.sql.expression.WindowExpression": "false"})
    df = s.create_dataframe({"g": [1, 1, 2], "x": [3.0, 1.0, 2.0]})
    q = df.select("g", F.row_number().over(
        F.Window.partitionBy("g").orderBy("x")).alias("rn"))
    tree = s.plan(q.plan).tree_string()
    assert "TpuWindowExec" not in tree


def test_incompat_fallback_uses_unicode_semantics():
    s = TpuSession({"spark.rapids.sql.incompatibleOps.enabled": "false"})
    df = s.create_dataframe({"x": ["straße", "café"]})
    out = df.select(F.upper("x").alias("u")).to_pandas()["u"]
    assert out.tolist() == ["STRASSE", "CAFÉ"]


def test_new_knobs_wired(tmp_path):
    """The round's new conf entries actually reach their consumers."""
    import numpy as np
    import pandas as pd
    import pyarrow.parquet as pq
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.config import rapids_conf as rc
    from spark_rapids_tpu.memory import retry as R

    p = str(tmp_path / "t.parquet")
    pq.write_table(
        __import__("pyarrow").table({"a": list(range(100))}), p)
    s = TpuSession({
        "spark.rapids.sql.reader.batchSizeRows": "16",
        "spark.rapids.sql.join.outputBatchRows": "32",
        "spark.rapids.memory.oomRetry.maxRetries": "5",
    })
    # retry budget resolves from the ACTIVE session's conf at call time
    assert R._resolve_max_retries() == 5
    scan = s.read.parquet(p)
    plan = s.plan(scan.plan)
    from tests.test_io_meta import _walk
    scans = [n for n in _walk(plan)
             if type(n).__name__ == "TpuFileScanExec"]
    assert scans[0].batch_rows == 16
    df = s.create_dataframe(pd.DataFrame({"k": [1, 2], "v": [1.0, 2.0]}))
    j = df.join(s.create_dataframe(pd.DataFrame({"k": [1], "w": [9]})),
                on="k")
    joins = [n for n in _walk(s.plan(j.plan))
             if type(n).__name__ == "TpuHashJoinExec"]
    assert joins[0].max_output_rows == 32


def test_per_format_reader_type_keys():
    from spark_rapids_tpu.config.rapids_conf import RapidsConf
    c = RapidsConf({"spark.rapids.sql.format.orc.reader.type": "PERFILE"})
    assert c["spark.rapids.sql.format.orc.reader.type"] == "PERFILE"
    assert c["spark.rapids.sql.format.csv.reader.type"] == "AUTO"


def test_memory_sizing_family():
    """reserve/min/max alloc fractions shape the derived pool
    (GpuDeviceManager.scala:170-245 sizing contract)."""
    # squeeze the pool below minAllocFraction -> fail fast
    with pytest.raises(ValueError, match="minAllocFraction"):
        TpuSession({
            "spark.rapids.memory.tpu.reserve": str(15 << 30),
            "spark.rapids.memory.tpu.minAllocFraction": "0.5"})
    # maxAllocFraction caps the pool
    s = TpuSession({
        "spark.rapids.memory.tpu.reserve": "0",
        "spark.rapids.memory.tpu.allocFraction": "0.9",
        "spark.rapids.memory.tpu.maxAllocFraction": "0.5",
        "spark.rapids.memory.tpu.minAllocFraction": "0.1"})
    s2 = TpuSession({
        "spark.rapids.memory.tpu.reserve": "0",
        "spark.rapids.memory.tpu.allocFraction": "0.9",
        "spark.rapids.memory.tpu.minAllocFraction": "0.1"})
    assert s.memory_catalog.device_budget < s2.memory_catalog.device_budget


def test_format_enable_gate(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq
    p = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"a": list(range(10))}), p)
    s = TpuSession({"spark.rapids.sql.format.parquet.enabled": "false"})
    df = s.read.parquet(p)
    tree = s.plan(df.plan).tree_string()
    assert "CpuFallbackExec" in tree
    assert sorted(df.to_pandas()["a"].tolist()) == list(range(10))
    s2 = TpuSession()
    assert "CpuFallbackExec" not in s2.plan(s2.read.parquet(p).plan
                                            ).tree_string()


def test_regexp_enable_gate():
    s = TpuSession({"spark.rapids.sql.regexp.enabled": "false"})
    df = s.create_dataframe({"x": ["a1", "bb"]})
    q = df.select(F.rlike("x", r"\d").alias("m"))
    assert "CpuFallbackExec" in s.plan(q.plan).tree_string()
    assert q.to_pandas()["m"].tolist() == [True, False]


def test_variable_float_agg_gate():
    s = TpuSession(
        {"spark.rapids.sql.variableFloatAgg.enabled": "false"})
    df = s.create_dataframe({"g": [1, 1, 2], "v": [0.5, 0.25, 1.0]})
    q = df.groupBy("g").agg(F.sum("v").alias("s"))
    assert "CpuFallbackExec" in s.plan(q.plan).tree_string()
    got = q.to_pandas().sort_values("g", ignore_index=True)
    assert got["s"].tolist() == [0.75, 1.0]
    # integer sums unaffected
    q2 = df.groupBy("g").agg(F.count("v").alias("c"))
    assert "CpuFallbackExec" not in s.plan(q2.plan).tree_string()


def test_cast_config_gates():
    s = TpuSession(
        {"spark.rapids.sql.castStringToFloat.enabled": "false"})
    df = s.create_dataframe({"x": ["1.5", "2.5"]})
    q = df.select(F.col("x").cast("double").alias("d"))
    assert "CpuFallbackExec" in s.plan(q.plan).tree_string()
    assert q.to_pandas()["d"].tolist() == [1.5, 2.5]
    s2 = TpuSession()
    assert "CpuFallbackExec" not in s2.plan(q.plan).tree_string()


def test_suppress_planning_failure():
    s = TpuSession({"spark.rapids.sql.suppressPlanningFailure": "true"})
    df = s.create_dataframe({"x": [2, 1]})
    plan = df.orderBy("x").plan

    class Boom:
        def apply(self, logical, pushdown=True):
            raise RuntimeError("planner bug")
    real = s.overrides
    s.overrides = Boom()
    try:
        exec_plan = s.plan(plan)
        assert "CpuFallbackExec" in exec_plan.tree_string()
        import pyarrow as pa
        out = pa.concat_tables(
            [b.to_arrow() for b in exec_plan.execute()]).to_pandas()
        assert out["x"].tolist() == [1, 2]
    finally:
        s.overrides = real
    # default: the failure surfaces
    s2 = TpuSession()
    s2.overrides = Boom()
    try:
        with pytest.raises(RuntimeError, match="planner bug"):
            s2.plan(plan)
    finally:
        pass


def test_spill_disk_write_threads(tmp_path):
    import numpy as np
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.memory.spill import (
        DISK, SpillableBatchCatalog)
    cat = SpillableBatchCatalog(
        device_budget=1, host_budget=1, spill_dir=str(tmp_path),
        disk_write_threads=3)
    hs = [cat.register(ColumnarBatch.from_pydict(
        {"a": np.arange(2048) + i})) for i in range(4)]
    assert all(h.tier == DISK for h in hs)
    for h in hs:
        got = h.materialize()
        assert got.to_pydict()["a"][0] == hs.index(h)


PACKAGE = pathlib.Path(spark_rapids_tpu.__file__).parent


# named only by an accessor of ``RapidsConf`` that nothing calls
# (``sql_enabled``, ``max_batch_rows``, ``shuffle_partitions``): debts of
# ROADMAP C3.  Wire or delete one and it leaves this set.
ACCESSOR_ONLY = {"spark.rapids.sql.enabled",
                 "spark.rapids.sql.tpu.maxBatchRows",
                 "spark.rapids.sql.shuffle.partitions"}


def test_every_conf_entry_is_read():
    """A registered key that nothing reads promises a user behaviour the
    engine does not have: every entry is named, by its constant or by
    its key, somewhere in the package outside the registry itself (the
    per-format reader keys by the ``{fmt}`` pattern io/readers.py
    builds them from)."""
    from spark_rapids_tpu.config import rapids_conf as rc
    registry_file = pathlib.Path(rc.__file__)
    text = "\n".join(f.read_text() for f in sorted(PACKAGE.rglob("*.py"))
                     if f != registry_file)
    text = re.sub(r'"\s*\n\s*f?"', "", text)  # implicit concatenation
    words = set(re.findall(r"\w+", text))
    constants = {id(v): k for k, v in vars(rc).items()
                 if isinstance(v, rc.ConfEntry)}

    def named(key, entry):
        by_format = re.sub(r"\.format\.\w+\.", ".format.{fmt}.", key)
        return (constants.get(id(entry)) in words or key in text
                or by_format in text)

    unread = {key for key, entry in rc.RapidsConf.registry().items()
              if not named(key, entry)}
    assert unread == ACCESSOR_ONLY, sorted(unread ^ ACCESSOR_ONLY)


@pytest.mark.parametrize("layer", ["ops", "columnar"])
def test_kernels_do_not_import_the_session(layer):
    """The kernel and column layers sit below the session: what they
    need of it (a conf value, a metrics sink) arrives as an argument."""
    reaching = [str(f.relative_to(PACKAGE))
                for f in sorted((PACKAGE / layer).rglob("*.py"))
                if re.search(r"spark_rapids_tpu\.api\b|\bTpuSession\b",
                             f.read_text())]
    assert not reaching, reaching
