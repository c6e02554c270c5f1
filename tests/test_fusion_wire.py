"""Wire-fused distributed stages.

A warm wire-fused distributed stage runs ONE program per shard — pinned
by the jit dispatch counter, not eyeballed — and recovers across
checkpoint resume like any other exchange stage; the knob's default-off
state fuses nothing.
"""

import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.exec.fusion import fusion_metrics


def test_wire_knob_defaults_off():
    s = TpuSession()
    try:
        from spark_rapids_tpu.parallel.shuffle import \
            wire_fusion_enabled
        assert wire_fusion_enabled() is False
        rng = np.random.default_rng(7)
        pdf = pd.DataFrame({
            "k": rng.integers(0, 1 << 40, 4000, dtype=np.int64),
            "v": rng.integers(0, 1000, 4000).astype(np.float64)})
        fusion_metrics.reset()
        (s.create_dataframe(pdf).group_by("k")
         .agg(F.sum(F.col("v")).alias("sv"))).to_pandas()
        m = fusion_metrics.snapshot()
        assert m["fusedWireStages"] == 0, m
    finally:
        s.stop()


@pytest.fixture(scope="module")
def tpch_data():
    from spark_rapids_tpu.models import tpch
    return tpch.gen_tables(sf=0.002)


NSHARDS = 8


@pytest.fixture(scope="module")
def mesh():
    import jax
    from spark_rapids_tpu.parallel.mesh import make_mesh
    if jax.device_count() < NSHARDS:
        pytest.skip("needs the virtual 8-device mesh")
    return make_mesh(NSHARDS)


def test_fused_wire_one_dispatch_per_shard(mesh):
    """Warm wire-fused launches run ONE program per shard: pinned by
    the jit dispatch counter (a warm fused launch = exactly 1
    dispatch, strictly fewer than the warm two-dispatch path), with
    results bit-identical to the unfused stage at every launch."""
    from spark_rapids_tpu.columnar import dtypes as dts
    from spark_rapids_tpu.ops import aggregates as agg
    from spark_rapids_tpu.ops import jit_cache
    from spark_rapids_tpu.ops.expressions import BoundReference
    from spark_rapids_tpu.parallel.distributed import \
        DistributedAggregate

    CAP = 256
    rng = np.random.default_rng(11)
    keys = rng.integers(0, 20, NSHARDS * CAP).astype(np.int64)
    vals = rng.normal(size=NSHARDS * CAP)
    nrows = jnp.asarray(
        rng.integers(50, CAP, NSHARDS).astype(np.int32))
    flat = [(jnp.asarray(keys), None, None),
            (jnp.asarray(vals), None, None)]

    def run(fused):
        s = TpuSession(
            {"spark.rapids.tpu.fusion.wire.enabled": fused})
        try:
            dist = DistributedAggregate(
                mesh, in_dtypes=[dts.INT64, dts.FLOAT64],
                group_exprs=[BoundReference(0, dts.INT64, name="k",
                                            nullable=False)],
                funcs=[agg.Sum(BoundReference(1, dts.FLOAT64,
                                              name="v")),
                       agg.Count(BoundReference(1, dts.FLOAT64,
                                                name="v"))])
            results, dispatches = [], []
            for _ in range(4):
                d0 = jit_cache.dispatch_count()
                outs = dist(flat, nrows)
                dispatches.append(jit_cache.dispatch_count() - d0)
                results.append([np.asarray(o[0]) for o in outs])
            return results, dispatches
        finally:
            s.stop()

    fusion_metrics.reset()
    r_off, d_off = run(False)
    fusion_metrics.reset()
    r_on, d_on = run(True)
    m = fusion_metrics.snapshot()
    assert m["fusedWireStages"] >= 1, m
    assert d_on[-1] == 1, d_on  # one program per shard, warm
    assert d_on[-1] < d_off[-1], (d_on, d_off)
    for a, b in zip(r_off, r_on):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("qname", ["q3", "q18"])
def test_fused_wire_drops_dispatches_on_tpch_shapes(mesh, tpch_data,
                                                    qname):
    """The acceptance pin: warm distributed q3/q18 runs dispatch
    strictly fewer programs with wire fusion on (the aggregate
    exchange stage folds its packer), bit-identically."""
    from spark_rapids_tpu.models import tpch
    from spark_rapids_tpu.ops import jit_cache

    def run(fused):
        s = TpuSession(
            {"spark.rapids.tpu.fusion.wire.enabled": fused},
            mesh=mesh)
        try:
            df = getattr(tpch, qname)(tpch.load(s, tpch_data))
            df.to_pandas()  # cold
            df.to_pandas()  # warm-up (arms the speculative site)
            d0 = jit_cache.dispatch_count()
            got = df.to_pandas()  # measured warm launch
            return got, jit_cache.dispatch_count() - d0, \
                s.last_dist_explain
        finally:
            s.stop()

    g_off, d_off, e_off = run(False)
    assert e_off == "distributed", e_off
    fusion_metrics.reset()
    g_on, d_on, e_on = run(True)
    assert e_on == "distributed", e_on
    assert fusion_metrics.snapshot()["fusedWireStages"] >= 1
    assert d_on < d_off, (d_on, d_off)
    pd.testing.assert_frame_equal(g_off, g_on)


@pytest.mark.chaos
def test_checkpoint_resume_across_fused_wire_stage(mesh):
    """A fault on the exchange after the warm (fused) launch: the
    recovery ladder resumes and the answer stays bit-identical — the
    fused program is as recoverable as the two-dispatch path."""
    from spark_rapids_tpu.robustness import inject as I
    rng = np.random.default_rng(3)
    pdf = pd.DataFrame({"k": rng.integers(0, 40, 4096),
                        "v": rng.normal(size=4096)})
    s = TpuSession({"spark.rapids.tpu.fusion.wire.enabled": True,
                    "spark.rapids.sql.recovery.backoffMs": 1},
                   mesh=mesh)
    try:
        df = (s.create_dataframe(pdf).group_by("k")
              .agg(F.sum(F.col("v")).alias("sv")).orderBy("k"))
        want = df.to_pandas()
        fusion_metrics.reset()
        pd.testing.assert_frame_equal(df.to_pandas(), want)  # warm
        assert fusion_metrics.snapshot()["fusedWireStages"] >= 1
        s.recovery_log.clear()
        with I.scoped_rules():
            with I.injected("shuffle.exchange", count=1, skip=1):
                got = df.to_pandas()
        pd.testing.assert_frame_equal(got, want)
        assert s.recovery_log, "fault never fired"
    finally:
        s.stop()
