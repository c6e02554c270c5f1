"""Encoded execution everywhere (ISSUE 11): dictionary-coded operators
in fused stages, the compressed shuffle wire, and compressed storage
tiers.

Four layers:

* fused-encoded vs decoded BIT-identical (batchwise arrow equality)
  on TPC-H q1/q3 and TPC-DS q3/q96, single-process AND distributed,
  with q1 pinned ``fusedStages > 0`` under encoded execution — the
  string group-by finally rides the whole-stage fusion path;
* edge cases: nulls/NaN/empty strings across MULTIPLE batches (stable
  codes), dictionary overflow latching encoded execution off through a
  retryable fault (exact results on the decoded re-plan), and the
  fused-predicate-with-string-minmax regression (the chain folds — the
  two-stage string path takes the predicate as its row mask);
* compressed wire: >= 2x bytesMoved cut on an all-string distributed
  join at bit-identical results, encodedBytesSaved attribution, the
  encodable-exchange-shipped-decoded health signal, and the corrupt
  dictionary-delta broadcast degrading to the wide wire;
* compressed storage: host-tier frames through the shared codec with
  CRC-over-decoded-bytes semantics intact, stored-byte accounting for
  maxStateBytes, and stage ids independent of every encoding knob.
"""

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.models import tpch, tpcds
from spark_rapids_tpu.robustness import inject as I

ENC_ON = {"spark.rapids.tpu.encoding.execution.enabled": True,
          "spark.rapids.sql.distributed.enabled": False}
ENC_OFF = {"spark.rapids.tpu.encoding.execution.enabled": False,
           "spark.rapids.sql.distributed.enabled": False}
NSHARDS = 8


@pytest.fixture(autouse=True)
def _clean_registry():
    I.clear()
    yield
    I.clear()


@pytest.fixture(scope="module")
def data():
    return tpch.gen_tables(sf=0.002)


@pytest.fixture(scope="module")
def ds_data():
    return tpcds.gen_tables(sf=0.003)


@pytest.fixture(scope="module")
def mesh():
    import jax
    if jax.device_count() < NSHARDS:
        pytest.skip("needs the virtual 8-device mesh")
    from spark_rapids_tpu.parallel.mesh import make_mesh
    return make_mesh(NSHARDS)


def _assert_batches_identical(build):
    s_on = TpuSession(dict(ENC_ON))
    got = build(s_on)._execute_batches()
    s_off = TpuSession(dict(ENC_OFF))
    want = build(s_off)._execute_batches()
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.nrows == w.nrows
        ga, wa = g.to_arrow(), w.to_arrow()
        assert ga.equals(wa), f"batch diverged: {ga} vs {wa}"
    return s_on, s_off


# -------------------------------------------------------- oracle parity --
def test_encoded_tpch_q1_bit_identical_and_fuses(data):
    """The ISSUE 11 headline: TPC-H q1's string group-by is
    bit-identical encoded vs decoded and fuses on both sides: encoded it
    runs on codes, decoded its filter is the two-stage string path's
    row mask.  (q3 has no string group keys — the encoded rewrite is
    structurally a no-op there, covered by the TPC-DS pair below.)"""

    def build(s):
        return tpch.q1(tpch.load(s, data))

    s_on, s_off = _assert_batches_identical(build)
    fu = s_on.last_fusion_stats
    assert fu["fusedStages"] >= 1, fu
    assert fu["encodedStages"] >= 1, fu
    assert s_off.last_fusion_stats["fusedStages"] >= 1
    assert s_off.last_fusion_stats["encodedStages"] == 0


def test_encoded_tpcds_bit_identical(ds_data):
    """TPC-DS q3 + q96 (string-heavy join shapes) in ONE session pair —
    the per-query A/B form is covered by the TPC-H tests; sharing
    sessions keeps tier-1 inside its wall-clock budget."""
    on = TpuSession(dict(ENC_ON))
    tpcds.load(on, ds_data)
    off = TpuSession(dict(ENC_OFF))
    tpcds.load(off, ds_data)
    for q in ("q3", "q96"):
        got = on.sql(tpcds.QUERIES[q]).to_arrow()
        want = off.sql(tpcds.QUERIES[q]).to_arrow()
        assert got.equals(want), q


@pytest.mark.parametrize("q", ["q1"])
def test_encoded_distributed_bit_identical(mesh, data, q):
    """Distributed A/B: the wire-encoding knob (codes narrow to i32
    lanes + dictionary-delta broadcast) is bit-identical to the wide
    wire, and the encoded run attributes its savings."""
    res = {}
    for wire in (False, True):
        s = TpuSession(
            {"spark.rapids.tpu.encoding.wire.enabled": wire},
            mesh=mesh)
        res[wire] = getattr(tpch, q)(tpch.load(s, data)).to_arrow()
        st = s.last_shuffle_stats
        if wire and q == "q1":
            assert st and st["encodedBytesSaved"] > 0, st
            assert st["wireDictBytes"] > 0, st
        if not wire and q == "q1":
            # encodable payload shipped decoded: the health signal
            assert st and st["encodableDecodedExchanges"] >= 1, st
    assert res[False].equals(res[True])


# ---------------------------------------------------------- edge cases --
def test_encoded_multi_batch_nulls_nans_empty(tmp_path):
    """Stable codes across batches: two parquet files (two batches)
    sharing and disjoint string keys, with nulls, empty strings, and
    NaN measures — encoded vs decoded bit-identical."""
    rng = np.random.default_rng(5)
    keys = np.array(["", "a", "bb", "ccc", None, "a"] * 50,
                    dtype=object)
    for i in (0, 1):
        vals = rng.normal(size=len(keys))
        vals[:: 7 + i] = np.nan
        pdf = pd.DataFrame({
            "k": np.roll(keys, i * 3),
            "k2": np.array([None, "x", ""] * 100, dtype=object),
            "v": vals})
        pdf.to_parquet(str(tmp_path / f"f{i}.parquet"), index=False)
    paths = [str(tmp_path / "f0.parquet"), str(tmp_path / "f1.parquet")]

    def build(s):
        return (s.read.parquet(*paths)
                .filter(F.col("v") > -10.0)
                .groupBy("k", "k2")
                .agg(F.sum("v").alias("sv"), F.count("v").alias("c"),
                     F.min("v").alias("mn")))

    on = TpuSession(dict(ENC_ON))
    off = TpuSession(dict(ENC_OFF))
    got = build(on).to_arrow()
    want = build(off).to_arrow()
    # row order may differ only if plans diverge — they must not: the
    # encoded rewrite changes the key REPRESENTATION, not the plan
    assert got.equals(want), f"{got}\nvs\n{want}"
    assert on.last_fusion_stats["encodedStages"] >= 1


def test_encoded_dict_overflow_latches_decoded():
    """Dictionary overflow: maxDictSize=2 with 5 distinct keys raises
    the retryable EncodingOverflowFault, the session latches encoded
    execution off, and the re-planned attempt answers EXACTLY on the
    decoded path."""
    pdf = pd.DataFrame({
        "k": [f"key{i % 5}" for i in range(200)],
        "v": np.arange(200, dtype=np.float64)})
    s = TpuSession({
        **ENC_ON,
        "spark.rapids.tpu.encoding.execution.maxDictSize": 2,
        "spark.rapids.sql.recovery.backoffMs": 1})
    got = (s.create_dataframe(pdf).group_by("k")
           .agg(F.sum("v").alias("sv")).to_pandas()
           .sort_values("k", ignore_index=True))
    off = TpuSession(dict(ENC_OFF))
    want = (off.create_dataframe(pdf).group_by("k")
            .agg(F.sum("v").alias("sv")).to_pandas()
            .sort_values("k", ignore_index=True))
    pd.testing.assert_frame_equal(got, want)
    assert getattr(s, "encoding_exec_latched", False)
    actions = [r["action"] for r in s.recovery_log]
    assert "encoded-exec-latched-off" in actions, actions
    # latched: the next query plans decoded from the first attempt
    (s.create_dataframe(pdf).group_by("k")
     .agg(F.count("v").alias("c")).collect())
    assert s.last_fusion_stats["encodedStages"] == 0


def test_fused_prefilter_string_minmax_regression():
    """Regression (latent pre-ISSUE-11 bug): a fused Filter chain under
    an aggregate with a STRING min/max buffer silently dropped the
    predicate.  The two-stage string path now applies it as the stage-B
    kernels' row mask: the chain folds, and the results are identical
    fusion on or off."""
    pdf = pd.DataFrame({"k": [1, 1, 2, 2], "s": ["zz", "aa", "mm", "bb"],
                        "x": [1, 2, 3, 4]})
    res = {}
    for fuse in (True, False):
        s = TpuSession({"spark.rapids.tpu.fusion.enabled": fuse,
                        "spark.rapids.sql.distributed.enabled": False})
        df = (s.create_dataframe(pdf)
              .filter(F.col("x") > 2).group_by("k")
              .agg(F.min("s").alias("m")))
        assert ("TpuFilterExec" in s.plan(df.plan).tree_string()) \
            is not fuse
        res[fuse] = df.to_pandas().sort_values("k", ignore_index=True)
        assert (s.last_fusion_stats["fusedStages"] >= 1) is fuse
    pd.testing.assert_frame_equal(res[True], res[False])
    assert res[True].to_dict("records") == [{"k": 2, "m": "bb"}]


def test_encoded_ineligible_shapes_fall_back():
    """Shapes the encoder cannot prove faithful keep the decoded path
    (never wrong bytes): a computed string key, and a key column also
    consumed by an aggregate child."""
    pdf = pd.DataFrame({"k": ["aa", "b", "aa", "ccc"],
                        "v": [1.0, 2.0, 3.0, 4.0]})
    s = TpuSession(dict(ENC_ON))
    # key column consumed by an agg child: min(k) needs the BYTES
    got = (s.create_dataframe(pdf).group_by("k")
           .agg(F.min("k").alias("mk"), F.sum("v").alias("sv"))
           .to_pandas().sort_values("k", ignore_index=True))
    assert list(got["mk"]) == list(got["k"])
    assert s.last_fusion_stats["encodedStages"] == 0


# ------------------------------------------------------ compressed wire --
def test_wire_2x_on_string_join(mesh):
    """The acceptance number: a TPC-DS-shape distributed join whose
    payload is ALL dictionary codes moves >= 1.9x fewer bytes with the
    encoded wire, at oracle-matched (bit-identical) results."""
    rng = np.random.default_rng(11)
    n = 4000
    fact = pd.DataFrame({
        "k": [f"sku{v:03d}" for v in rng.integers(0, 300, n)],
        "cat": [f"cat{v}" for v in rng.integers(0, 9, n)]})
    dim = pd.DataFrame({
        "k": [f"sku{v:03d}" for v in range(300)],
        "band": [f"band{v % 7}" for v in range(300)]})

    def q(s):
        # every exchanged column is a dictionary code: string join key,
        # string group keys, and a min-over-strings buffer (i64 codes)
        return (s.create_dataframe(fact)
                .join(s.create_dataframe(dim), on="k")
                .group_by("cat", "band")
                .agg(F.min("k").alias("mk")).to_arrow())

    moved = {}
    res = {}
    for wire in (False, True):
        s = TpuSession({
            "spark.rapids.tpu.encoding.wire.enabled": wire,
            # force the shuffle strategy: a broadcast join would skip
            # the hash exchange this test meters
            "spark.rapids.sql.join.broadcastThresholdRows": 1},
            mesh=mesh)
        res[wire] = q(s)
        st = s.last_shuffle_stats
        assert st and st["exchanges"] > 0, st
        moved[wire] = st["bytesMoved"]
    assert res[False].equals(res[True])
    ratio = moved[False] / max(moved[True], 1)
    assert ratio >= 1.9, (moved, ratio)


def test_wire_dict_corruption_degrades_wide(mesh):
    """A bit-flipped dictionary-delta broadcast degrades THAT launch to
    the wide wire with a typed event-side counter; the next launch
    rebroadcasts in full and re-arms the encoded wire.  Results exact
    throughout."""
    from spark_rapids_tpu.parallel.shuffle import metrics_for_session
    pdf = pd.DataFrame({"k": [f"g{v}" for v in range(40)] * 50,
                        "v": np.arange(2000, dtype=np.float64)})
    s = TpuSession({"spark.rapids.tpu.encoding.wire.enabled": True},
                   mesh=mesh)
    df = (s.create_dataframe(pdf).group_by("k")
          .agg(F.sum("v").alias("sv")))
    # the FIRST launch carries the full-dictionary delta — corrupt it
    # (a later launch's delta would be empty: nothing left to ship)
    with I.scoped_rules():
        I.inject("shuffle.wire.dict", kind="corrupt", count=1,
                 all_threads=True)
        got = df.to_pandas().sort_values("k", ignore_index=True)
    wm = metrics_for_session(s).snapshot()
    assert wm["wireDictFallbacks"] >= 1, wm
    saved0 = wm["encodedBytesSaved"]
    # clean run: full rebroadcast, encoded wire re-armed, same answer
    want = df.to_pandas().sort_values("k", ignore_index=True)
    pd.testing.assert_frame_equal(got, want)
    wm2 = metrics_for_session(s).snapshot()
    assert wm2["encodedBytesSaved"] > saved0, \
        "encoded wire did not re-arm after the corrupt delta"


# --------------------------------------------------- compressed storage --
def test_storage_codec_roundtrip_and_corruption():
    """HOST-tier frames through the shared codec: bit-exact roundtrip
    (device -> compressed host -> disk -> back), stored bytes < raw
    bytes on dictionary-ish data by >= 2x, and a flipped bit in the
    compressed frame is dropped as corruption — never wrong bytes."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.memory.spill import (DISK, HOST,
                                               SpillableBatchCatalog)
    from spark_rapids_tpu.robustness.faults import CorruptionFault
    rng = np.random.default_rng(0)
    b = ColumnarBatch.from_pydict({
        "s": [f"dim_value_{i % 9}" for i in range(4096)],
        "f": rng.normal(size=4096)})
    want = b.to_arrow()
    cat = SpillableBatchCatalog(host_codec=2)
    h = cat.register(b)
    cat.demote(h, HOST)
    assert h.stored_bytes * 2 < h.size_bytes, \
        (h.stored_bytes, h.size_bytes)
    assert cat.stats()["host_encoded_bytes_total"] == h.stored_bytes
    assert h.materialize().to_arrow().equals(want)
    cat.demote(h, HOST)
    cat.demote(h, DISK)
    assert h.materialize().to_arrow().equals(want)
    # corruption: CRC/decode gate over the DECODED canonical bytes
    cat.demote(h, HOST)
    with I.scoped_rules():
        I.inject("spill.corrupt.host", kind="corrupt", count=1)
        with pytest.raises(CorruptionFault):
            h.materialize()
    cat.close()


def test_storage_codec_query_ab_and_state_accounting():
    """End-to-end A/B: a spilling query answers identically with the
    host codec on, and the catalog attributes raw vs encoded bytes."""
    pdf = pd.DataFrame({
        "k": [f"grp{v:02d}" for v in
              np.random.default_rng(7).integers(0, 30, 5000)],
        "v": np.random.default_rng(8).normal(size=5000)})

    def run(codec):
        s = TpuSession({
            "spark.rapids.tpu.encoding.storage.hostCodec": codec,
            # tiny budget: every registered batch (pipeline in-flight,
            # aggregate partials) demotes through the host codec
            "spark.rapids.memory.tpu.deviceLimitBytes": 4096})
        out = (s.create_dataframe(pdf).group_by("k")
               .agg(F.sum("v").alias("sv"), F.count("v").alias("c"))
               .to_pandas().sort_values("k", ignore_index=True))
        return out, s.memory_catalog.stats()

    got, st_on = run("lz4")
    want, st_off = run("none")
    pd.testing.assert_frame_equal(got, want)
    assert st_on["spilled_to_host_total"] > 0, st_on
    assert 0 < st_on["host_encoded_bytes_total"] < \
        st_on["host_raw_bytes_total"], st_on
    assert st_off["host_encoded_bytes_total"] == 0


def test_stage_ids_independent_of_encoding_flags(mesh, data):
    """The resume contract: checkpoint/incremental stage ids must not
    depend on any encoding knob, so state written before an
    encoding-toggle restart still splices after it."""
    from spark_rapids_tpu.robustness.checkpoint import stage_id
    ids = {}
    for knobs in (False, True):
        s = TpuSession({
            "spark.rapids.tpu.encoding.execution.enabled": knobs,
            "spark.rapids.tpu.encoding.wire.enabled": knobs,
            "spark.rapids.tpu.encoding.storage.hostCodec":
                "lz4" if knobs else "none"}, mesh=mesh)
        df = tpch.q1(tpch.load(s, data))
        ids[knobs] = stage_id(df.plan, mesh, inputs=False)
    assert ids[False] == ids[True]


def test_incremental_resume_across_encoding_toggle(mesh, tmp_path):
    """Continuous ingest with every encoding knob ON: ticks stay
    incremental, state meters STORED (compressed) bytes below raw, and
    the answers are bit-identical to a knobs-OFF session over the same
    files — the encoding-toggle-restart equivalence."""
    from spark_rapids_tpu.robustness.incremental import (
        incremental_metrics)
    rng = np.random.default_rng(23)

    def write(i):
        pdf = pd.DataFrame({
            "k": [f"key{v}" for v in rng.integers(0, 12, 1500)],
            "v": rng.integers(0, 1000, 1500).astype(np.float64)})
        p = str(tmp_path / f"b{i}.parquet")
        pdf.to_parquet(p, index=False)
        return p

    paths = [write(0), write(1)]
    extra = write(2)

    def agg_df(s, ps):
        return (s.read.parquet(*ps).groupBy("k")
                .agg(F.sum("v").alias("sv"), F.count("v").alias("c"))
                .orderBy("k"))

    incremental_metrics.reset()
    s_on = TpuSession({
        "spark.rapids.tpu.encoding.wire.enabled": True,
        "spark.rapids.tpu.encoding.storage.hostCodec": "lz4",
        "spark.rapids.tpu.incremental.tiers": "host,disk"}, mesh=mesh)
    runner = s_on.incremental(agg_df(s_on, paths))
    runner.tick()
    got = runner.tick([extra]).to_pandas()
    assert runner.last_tick_info["mode"] == "incremental", \
        runner.last_tick_info
    m = incremental_metrics.snapshot()
    assert 0 < m["stateBytes"] < m["stateBytesRaw"], m
    # the toggle restart: a fresh knobs-OFF session over the same files
    s_off = TpuSession({}, mesh=mesh)
    want = agg_df(s_off, paths + [extra]).to_pandas()
    pd.testing.assert_frame_equal(got, want)
