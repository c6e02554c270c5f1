"""Fused packed shuffle wire format (parallel/shuffle.py).

Pins the exchange data-path rebuild: ONE all_to_all per width group
(jaxpr-level collective budgets), bit-identical results vs the
per-column path for mixed/nullable columns across the virtual 8-device
CPU mesh, adaptive slot planning (speculative launches, hostsync
budget, slot-overflow -> degradable recovery -> correct result), the
transient wire-bytes HBM accounting, and the QueryInfo.shuffle
observability trail.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest
from jax.sharding import PartitionSpec as P

from spark_rapids_tpu.columnar import dtypes as dts
from spark_rapids_tpu.ops import aggregates as agg
from spark_rapids_tpu.ops.expressions import BoundReference, ColVal
from spark_rapids_tpu.parallel.mesh import make_mesh, shard_map
from spark_rapids_tpu.parallel.shuffle import (
    SlotPlanner, all_gather_cols, exchange, metrics_for_session,
    planner_for_session)

NSHARDS = 8
CAP = 64

# the q3-shape exchange: join keys + aggregation payloads, all nullable
# (two i64 keys, two f64 measures, an i32 date, an f32 discount)
Q3_DTYPES = [dts.INT64, dts.INT64, dts.FLOAT64, dts.FLOAT64,
             dts.INT32, dts.FLOAT32]


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(NSHARDS)


def _exchange_fn(mesh, dtypes, packed, slot=None):
    axis = mesh.axis_names[0]

    def step(flat, pids, nrows_arr):
        cols = [ColVal(dt, v, val) for (v, val), dt in zip(flat, dtypes)]
        out, total = exchange(cols, pids, nrows_arr[0], axis, NSHARDS,
                              slot=slot, packed=packed)
        res = tuple(
            (c.values, c.validity if c.validity is not None
             else jnp.ones_like(c.values, dtype=jnp.bool_))
            for c in out)
        return res + (jnp.reshape(total.astype(jnp.int32), (1,)),)

    return shard_map(step, mesh=mesh,
                     in_specs=(P(axis), P(axis), P(axis)),
                     out_specs=P(axis), check_vma=False)


def _q3_data(rng, nullable=True):
    flat = []
    for dt in Q3_DTYPES:
        storage = np.dtype(dt.storage)
        if np.issubdtype(storage, np.floating):
            v = rng.normal(size=NSHARDS * CAP).astype(storage)
        else:
            v = rng.integers(-1000, 1000,
                             NSHARDS * CAP).astype(storage)
        m = jnp.asarray(rng.random(NSHARDS * CAP) < 0.85) \
            if nullable else None
        flat.append((jnp.asarray(v), m))
    pids = jnp.asarray(
        rng.integers(0, NSHARDS, NSHARDS * CAP).astype(np.int32))
    nrows = jnp.asarray(
        rng.integers(0, CAP + 1, NSHARDS).astype(np.int32))
    return tuple(flat), pids, nrows


def _count_collectives(fn, args, prim="all_to_all"):
    # match the primitive INVOCATION (`= all_gather[`), not its params
    # (`all_gather_dimension=...` would double-count)
    return len(re.findall(rf"= {prim}\[",
                          str(jax.make_jaxpr(fn)(*args))))


@pytest.mark.perf
def test_packed_collective_budget_q3_shape(mesh, rng):
    """The premerge collective-count budget: a packed q3-shape
    (6-column nullable) exchange compiles to <= 4 all_to_all ops —
    counts vector + u32 payload + u8 validity payload + the f64 payload
    (a double is never bit-cast: the chip's compiler refuses it) — where
    the per-column path launches >= 8 (here 13: counts + 6 columns + 6
    masks)."""
    args = _q3_data(rng)
    n_packed = _count_collectives(
        _exchange_fn(mesh, Q3_DTYPES, packed=True), args)
    n_percol = _count_collectives(
        _exchange_fn(mesh, Q3_DTYPES, packed=False), args)
    assert n_packed <= 4, n_packed
    assert n_percol >= 8, n_percol
    # acceptance: >= 7 per-column collectives collapse to <= 4
    assert n_percol >= 7 > n_packed


def _bits(a):
    """Bit view for exact (NaN-payload-preserving) comparison."""
    if a.dtype == np.bool_:
        return a.view(np.uint8)
    kind = a.dtype.str.replace("f", "u").replace("i", "u")
    return a.view(kind)


def _assert_identical(rp, ru, ncols):
    tot_p = np.asarray(rp[ncols]).reshape(NSHARDS, -1)[:, 0]
    tot_u = np.asarray(ru[ncols]).reshape(NSHARDS, -1)[:, 0]
    np.testing.assert_array_equal(tot_p, tot_u)
    for i in range(ncols):
        vp, mp = np.asarray(rp[i][0]), np.asarray(rp[i][1])
        vu, mu = np.asarray(ru[i][0]), np.asarray(ru[i][1])
        rcap = vp.shape[0] // NSHARDS
        for s in range(NSHARDS):
            n = tot_p[s]
            a = vp.reshape(NSHARDS, rcap)[s, :n]
            b = vu.reshape(NSHARDS, rcap)[s, :n]
            np.testing.assert_array_equal(_bits(a), _bits(b),
                                          err_msg=f"col {i} shard {s}")
            np.testing.assert_array_equal(
                mp.reshape(NSHARDS, rcap)[s, :n],
                mu.reshape(NSHARDS, rcap)[s, :n],
                err_msg=f"validity {i} shard {s}")


def test_packed_roundtrip_bit_identical(mesh, rng):
    """Mixed i32/i64/f32/f64 + bool + nullable columns, ragged row
    counts including an empty shard: the packed wire format is
    bit-identical to the per-column path (NaN payloads included)."""
    dtypes = [dts.INT32, dts.INT64, dts.FLOAT32, dts.FLOAT64,
              dts.BOOL, dts.INT64]
    flat = []
    for k, dt in enumerate(dtypes):
        storage = np.dtype(dt.storage)
        if storage == np.bool_:
            v = rng.random(NSHARDS * CAP) < 0.5
        elif np.issubdtype(storage, np.floating):
            v = np.where(rng.random(NSHARDS * CAP) < 0.1, np.nan,
                         rng.normal(size=NSHARDS * CAP)).astype(storage)
        else:
            v = rng.integers(-10**6, 10**6,
                             NSHARDS * CAP).astype(storage)
        m = jnp.asarray(rng.random(NSHARDS * CAP) < 0.8) \
            if k % 2 == 0 else None  # mix nullable / non-nullable
        flat.append((jnp.asarray(v), m))
    pids = jnp.asarray(
        rng.integers(0, NSHARDS, NSHARDS * CAP).astype(np.int32))
    nrows = np.array([CAP, 50, 0, 33, CAP, 1, 17, 60], dtype=np.int32)
    args = (tuple(flat), pids, jnp.asarray(nrows))
    rp = _exchange_fn(mesh, dtypes, packed=True)(*args)
    ru = _exchange_fn(mesh, dtypes, packed=False)(*args)
    _assert_identical(rp, ru, len(dtypes))


def test_packed_skewed_one_hot_shard(mesh, rng):
    """Every row bound for ONE destination (the worst skew): totals are
    exact, the hot shard receives every live row, cold shards receive
    zero, and packed == per-column."""
    dtypes = [dts.INT64, dts.FLOAT64]
    vals = rng.normal(size=NSHARDS * CAP)
    keys = rng.integers(0, 100, NSHARDS * CAP).astype(np.int64)
    flat = ((jnp.asarray(keys), None),
            (jnp.asarray(vals), jnp.asarray(
                rng.random(NSHARDS * CAP) < 0.9)))
    pids = jnp.asarray(np.full(NSHARDS * CAP, 3, dtype=np.int32))
    nrows = np.array([CAP, 0, CAP, 10, 0, CAP, 7, CAP], dtype=np.int32)
    args = (flat, pids, jnp.asarray(nrows))
    # full-capacity slot: a single destination takes every live row
    rp = _exchange_fn(mesh, dtypes, packed=True, slot=CAP)(*args)
    ru = _exchange_fn(mesh, dtypes, packed=False, slot=CAP)(*args)
    _assert_identical(rp, ru, 2)
    totals = np.asarray(rp[2]).reshape(NSHARDS, -1)[:, 0]
    assert totals[3] == nrows.sum()
    assert all(totals[s] == 0 for s in range(NSHARDS) if s != 3)


def test_all_gather_cols_packed(mesh, rng):
    """The broadcast collective rides the same lane packing: one
    all_gather per width group (+ the counts gather) instead of one per
    column + mask, results identical."""
    dtypes = [dts.INT64, dts.FLOAT64, dts.INT32, dts.BOOL]
    axis = mesh.axis_names[0]

    def make(packed):
        def step(flat, nrows_arr):
            cols = [ColVal(dt, v, val)
                    for (v, val), dt in zip(flat, dtypes)]
            out, total = all_gather_cols(cols, nrows_arr[0], axis,
                                         NSHARDS, packed=packed)
            res = tuple(
                (c.values, c.validity if c.validity is not None
                 else jnp.ones_like(c.values, dtype=jnp.bool_))
                for c in out)
            return res + (jnp.reshape(total.astype(jnp.int32), (1,)),)
        return shard_map(step, mesh=mesh, in_specs=(P(axis), P(axis)),
                         out_specs=P(axis), check_vma=False)

    flat = []
    for dt in dtypes:
        storage = np.dtype(dt.storage)
        if storage == np.bool_:
            v = rng.random(NSHARDS * CAP) < 0.5
        elif np.issubdtype(storage, np.floating):
            v = rng.normal(size=NSHARDS * CAP).astype(storage)
        else:
            v = rng.integers(-99, 99, NSHARDS * CAP).astype(storage)
        flat.append((jnp.asarray(v),
                     jnp.asarray(rng.random(NSHARDS * CAP) < 0.8)))
    nrows = jnp.asarray(
        np.array([10, 0, CAP, 5, 9, 0, 31, 2], dtype=np.int32))
    args = (tuple(flat), nrows)
    n_packed = _count_collectives(make(True), args, prim="all_gather")
    n_percol = _count_collectives(make(False), args, prim="all_gather")
    assert n_packed <= 4, n_packed       # counts + u32 + u8 + f64 payloads
    assert n_percol >= 1 + 2 * len(dtypes), n_percol
    rp, ru = make(True)(*args), make(False)(*args)
    _assert_identical(rp, ru, len(dtypes))


# ------------------------------------------------------- slot planner --

def test_slot_planner_modes():
    cap = 1024
    p = SlotPlanner(mode="capacity")
    assert p.plan("s", 10, cap) == cap
    p = SlotPlanner(mode="fixed")
    assert p.plan("s", 100, cap) == 128
    p = SlotPlanner(mode="adaptive", growth=2.0)
    assert p.plan("s", 100, cap) == 128
    p.observe("s", 100, 128, cap, lut=np.zeros(4, np.int32), rows=500)
    # EMA keeps the bucket sticky for nearby maxima
    assert p.plan("s", 70, cap) == 128
    spec = p.speculative("s", cap)
    assert spec is not None and spec["slot"] == 128
    # capacity change invalidates the cached prediction
    assert p.speculative("s", cap * 2) is None
    # an overflow latches the site off the speculative path and grows
    # the EMA by the configured factor
    p.observe_overflow("s")
    assert p.speculative("s", cap) is None
    assert p.plan("s", 100, cap) >= 256
    # the next observed (stats-sized) launch re-arms speculation
    p.observe("s", 300, 512, cap, lut=np.zeros(4, np.int32))
    assert p.speculative("s", cap)["slot"] == 512


from spark_rapids_tpu.parallel.distributed import DistributedAggregate  # noqa: E402


def _agg_for(mesh, key_name):
    return DistributedAggregate(
        mesh, in_dtypes=[dts.INT64, dts.FLOAT64],
        group_exprs=[BoundReference(0, dts.INT64, name=key_name,
                                    nullable=False)],
        funcs=[agg.Sum(BoundReference(1, dts.FLOAT64, name="v"))])


def _run_agg(dist, keys, vals, nrows):
    flat = [(jnp.asarray(keys.reshape(-1)), None, None),
            (jnp.asarray(vals.reshape(-1)), None, None)]
    outs = dist(flat, jnp.asarray(nrows))
    (kv, _, kn), (sv, _, _) = outs
    recv_cap = np.asarray(kv).shape[0] // NSHARDS
    ngroups = np.asarray(kn).reshape(NSHARDS, -1)[:, 0]
    got = {}
    kvs = np.asarray(kv).reshape(NSHARDS, recv_cap)
    svs = np.asarray(sv).reshape(NSHARDS, recv_cap)
    for s in range(NSHARDS):
        for i in range(ngroups[s]):
            got[int(kvs[s, i])] = svs[s, i]
    return got


def _check_agg(got, keys, vals, nrows):
    dfs = [pd.DataFrame({"k": keys[s, :nrows[s]],
                         "v": vals[s, :nrows[s]]})
           for s in range(NSHARDS)]
    want = pd.concat(dfs).groupby("k")["v"].sum()
    assert set(got) == set(want.index)
    for k, v in want.items():
        np.testing.assert_allclose(got[k], v, rtol=1e-9)


def test_adaptive_speculative_launch_and_overflow(mesh, rng):
    """The steady-state path: launch #1 sizes from the histogram
    hostsync and warms the site; launch #2 (same shape) goes
    speculative — NO stats sync, exactly one budgeted hostsync (the
    overflow-flag fetch); launch #3 shifts to heavy skew, the
    speculative slot overflows, the site re-runs at full capacity
    (results stay exact — rows are never dropped) and the event lands
    on the recovery trail as a degradable local action."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.utils.hostsync import host_sync_metrics
    session = TpuSession()
    try:
        dist = _agg_for(mesh, "spec_ovf_key")
        planner = planner_for_session(session)
        planner.sites.pop(dist._sig, None)
        nrows = np.full(NSHARDS, CAP, dtype=np.int32)

        # launch 1: cold -> stats-sized (observes the site)
        keys = rng.integers(0, 40, (NSHARDS, CAP)).astype(np.int64)
        vals = rng.normal(size=(NSHARDS, CAP))
        _check_agg(_run_agg(dist, keys, vals, nrows), keys, vals, nrows)
        assert dist.last_stats.get("speculative") is None
        warm_slot = dist.last_stats["slot"]

        # launch 2: warm -> speculative, hostsync budget == 1
        keys2 = rng.integers(0, 40, (NSHARDS, CAP)).astype(np.int64)
        vals2 = rng.normal(size=(NSHARDS, CAP))
        s0 = host_sync_metrics.snapshot_local()
        got = _run_agg(dist, keys2, vals2, nrows)
        syncs = host_sync_metrics.snapshot_local() - s0
        _check_agg(got, keys2, vals2, nrows)
        assert dist.last_stats.get("speculative") is True
        assert "overflow" not in dist.last_stats
        assert syncs <= 1, \
            f"speculative launch made {syncs} counted hostsyncs"

        # launch 3: CAP *distinct* keys per shard, ALL hashing into one
        # bucket — the stale LUT funnels every group through a single
        # (src, dst) slice of CAP rows, far past the warm slot -> the
        # speculative launch overflows -> full-capacity re-run, exact
        # results, and a degradable action on the recovery trail
        from spark_rapids_tpu.parallel.partitioning import (
            hash_partition_ids)
        assert warm_slot < CAP
        cand = np.arange(100_000, 400_000, dtype=np.int64)
        bids = np.asarray(hash_partition_ids(
            [ColVal(dts.INT64, jnp.asarray(cand))], dist.buckets))
        hot = cand[bids == bids[0]][:NSHARDS * CAP]
        assert hot.size == NSHARDS * CAP, "need one full hot bucket"
        keys3 = hot.reshape(NSHARDS, CAP)
        vals3 = rng.normal(size=(NSHARDS, CAP))
        n_recovery = len(session.recovery_log)
        got3 = _run_agg(dist, keys3, vals3, nrows)
        assert dist.last_stats.get("overflow") is True, dist.last_stats
        _check_agg(got3, keys3, vals3, nrows)  # no dropped rows, ever
        trail = session.recovery_log[n_recovery:]
        assert any(r["action"] == "shuffle-slot-capacity-rerun"
                   and r["fault"] == "shuffle_slot"
                   for r in trail), trail
        assert metrics_for_session(session).snapshot()[
            "slotOverflowRetries"] >= 1
        # the planner latched the site off speculation; the next launch
        # re-sizes from its histogram
        assert planner.speculative(dist._sig, CAP) is None
        keys4 = rng.integers(0, 40, (NSHARDS, CAP)).astype(np.int64)
        vals4 = rng.normal(size=(NSHARDS, CAP))
        _check_agg(_run_agg(dist, keys4, vals4, nrows), keys4, vals4,
                   nrows)
        assert dist.last_stats.get("speculative") is None
    finally:
        session.stop()


def test_packed_toggle_results_equal(mesh, rng):
    """A/B knob: the same aggregation with packed.enabled=false matches
    the packed default bit-for-bit (per-column collectives are kept as
    a first-class fallback, with their own jit-cache signature)."""
    from spark_rapids_tpu.api.session import TpuSession
    keys = rng.integers(0, 30, (NSHARDS, CAP)).astype(np.int64)
    vals = rng.normal(size=(NSHARDS, CAP))
    nrows = np.full(NSHARDS, CAP, dtype=np.int32)
    results = {}
    for enabled in (True, False):
        session = TpuSession({
            "spark.rapids.tpu.shuffle.packed.enabled": enabled})
        try:
            dist = _agg_for(mesh, "toggle_key")
            assert dist.packed is enabled
            results[enabled] = _run_agg(dist, keys, vals, nrows)
        finally:
            session.stop()
    assert results[True] == results[False]
    _check_agg(results[True], keys, vals, nrows)


# ------------------------------------------- wire accounting + events --

def test_transient_wire_accounting():
    """Spill registration reserves a shuffle-received batch's transient
    payload bytes against the DEVICE budget; the reservation is
    consumed once, never follows the batch to the host tier, and is
    released when the batch leaves the device."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.memory.spill import SpillableBatchCatalog
    cat = SpillableBatchCatalog(device_budget=1 << 30,
                                host_budget=1 << 30)
    batch = ColumnarBatch.from_pydict(
        {"a": np.arange(1000, dtype=np.int64)})
    base = batch.device_size_bytes()
    batch.transient_wire_bytes = 4096
    h = cat.register(batch, priority=0)
    assert cat.device_bytes == base + 4096
    assert batch.transient_wire_bytes == 0  # consumed by registration
    # demotion releases the wire reservation; only the batch payload
    # lands on the host tier
    freed = h.spill_to_host()
    cat.device_bytes -= freed
    cat.host_bytes += h.size_bytes
    assert freed == base + 4096
    assert h.wire_bytes == 0
    assert cat.device_bytes == 0
    h.close()
    assert cat.host_bytes == 0
    cat.close()


def test_coalesce_counts_wire_bytes():
    """The coalesce goal accounting sees the transient footprint: a
    wire-stamped batch fills the byte target sooner, so accumulation
    right after an exchange cannot pin ~2x the goal in HBM."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.memory.coalesce import (
        TargetSize, coalesce_iterator)
    from spark_rapids_tpu.memory.spill import SpillableBatchCatalog
    cat = SpillableBatchCatalog(device_budget=1 << 30,
                                host_budget=1 << 30)

    def batches():
        for _ in range(4):
            b = ColumnarBatch.from_pydict(
                {"a": np.arange(256, dtype=np.int64)})
            b.transient_wire_bytes = b.device_size_bytes() * 8
            yield b

    plain = ColumnarBatch.from_pydict(
        {"a": np.arange(256, dtype=np.int64)})
    target = plain.device_size_bytes() * 4
    out = list(coalesce_iterator(batches(), TargetSize(target),
                                 catalog=cat))
    # wire-stamped batches are ~9x their payload, so each flush holds
    # ONE batch instead of coalescing all four under the byte target
    assert len(out) == 4
    assert sum(b.nrows for b in out) == 4 * 256
    cat.close()


def test_distributed_query_stamps_wire_bytes(mesh):
    """End to end: a distributed query's collected batch carries the
    exchange payload reservation for downstream spill registration."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.session import TpuSession
    session = TpuSession(mesh=mesh)
    try:
        rng = np.random.default_rng(3)
        pdf = pd.DataFrame({"k": rng.integers(0, 50, 4000),
                            "v": rng.normal(size=4000)})
        df = (session.create_dataframe(pdf).group_by("k")
              .agg(F.sum(F.col("v")).alias("sv")))
        batches = df._execute_batches()
        assert session.last_dist_explain == "distributed"
        assert len(batches) == 1
        # consumed-once reservation stamped by DistPlanner.collect
        assert batches[0].transient_wire_bytes > 0
        assert session.last_shuffle_stats["bytesMoved"] > 0
    finally:
        session.stop()


def test_eventlog_queryinfo_shuffle_tpch_dryrun(mesh, tmp_path):
    """Every distributed TPC-H dryrun query's QueryEnd carries the
    shuffle wire summary (padding ratio + bytes moved), parsed into
    QueryInfo.shuffle and aggregated by the profiling report."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.models import tpch, tpch_sql
    from spark_rapids_tpu.tools.eventlog import load_logs
    from spark_rapids_tpu.tools.profiling import shuffle_wire_stats
    session = TpuSession(
        {"spark.rapids.tpu.eventLog.dir": str(tmp_path)}, mesh=mesh)
    try:
        data = tpch.gen_tables(sf=0.002)
        tpch_sql.register(session, tpch.load(session, data))
        for q in ("q1", "q3"):
            session.sql(tpch_sql.QUERIES[q]).to_pandas()
            assert session.last_dist_explain == "distributed", q
    finally:
        session.stop()
    apps = load_logs(str(tmp_path))
    assert len(apps) == 1
    dist_queries = [q for a in apps for q in a.queries
                    if q.explain == "distributed"]
    assert len(dist_queries) >= 2
    for q in dist_queries:
        assert q.shuffle, f"query {q.query_id} missing shuffle summary"
        assert q.shuffle["bytesMoved"] > 0
        assert q.shuffle["paddingRatio"] >= 1.0
        assert q.shuffle["collectives"] >= 1
    agg_stats = shuffle_wire_stats(apps)
    assert agg_stats["queries"] >= 2
    assert agg_stats["bytes_moved"] > 0


# --------------------------------------------------------------- chaos --

@pytest.mark.chaos
@pytest.mark.parametrize("packed", [True, False])
def test_chaos_packed_exchange_injection_once_per_launch(mesh, packed):
    """The "shuffle.exchange" checkpoint fires exactly once per packed
    (or per-column) launch: an armed count=1 rule kills the first
    exchange-bearing launch, the recovery ladder re-drives, and the
    answer matches the clean run."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.robustness import inject as I
    session = TpuSession({
        "spark.rapids.tpu.shuffle.packed.enabled": packed,
        "spark.rapids.sql.recovery.backoffMs": 1}, mesh=mesh)
    try:
        rng = np.random.default_rng(11)
        pdf = pd.DataFrame({"k": rng.integers(0, 40, 3000),
                            "v": rng.normal(size=3000)})
        df = (session.create_dataframe(pdf).group_by("k")
              .agg(F.sum(F.col("v")).alias("sv")))
        want = df.to_pandas().sort_values("k", ignore_index=True)
        with I.injected("shuffle.exchange", count=1) as rule:
            got = df.to_pandas().sort_values("k", ignore_index=True)
            assert rule.fired == 1
        pd.testing.assert_frame_equal(got, want)
        faults = [r["fault"] for r in session.recovery_log]
        assert "shuffle" in faults, faults
    finally:
        session.stop()


# ------------------------------------------------- ragged / topology --

def _skewed_args(rng, dtypes, hot=3, hot_frac=0.8):
    """Sharded columns + pids with ~hot_frac of live rows bound for ONE
    destination, plus the true [src, dst] histogram."""
    flat = []
    for k, dt in enumerate(dtypes):
        storage = np.dtype(dt.storage)
        if np.issubdtype(storage, np.floating):
            v = rng.normal(size=NSHARDS * CAP).astype(storage)
        else:
            v = rng.integers(-1000, 1000, NSHARDS * CAP).astype(storage)
        m = jnp.asarray(rng.random(NSHARDS * CAP) < 0.85) \
            if k % 2 == 0 else None
        flat.append((jnp.asarray(v), m))
    pids_h = np.where(rng.random(NSHARDS * CAP) < hot_frac, hot,
                      rng.integers(0, NSHARDS, NSHARDS * CAP)
                      ).astype(np.int32)
    nrows = np.full(NSHARDS, CAP, dtype=np.int32)
    counts = np.zeros((NSHARDS, NSHARDS), dtype=np.int64)
    for s in range(NSHARDS):
        row = pids_h.reshape(NSHARDS, CAP)[s, :nrows[s]]
        counts[s] = np.bincount(row, minlength=NSHARDS)
    return tuple(flat), jnp.asarray(pids_h), jnp.asarray(nrows), counts


def _ragged_fn(mesh, dtypes, rp, site=None):
    axis = mesh.axis_names[0]

    def step(flat, pids, nrows_arr):
        cols = [ColVal(dt, v, val) for (v, val), dt in zip(flat, dtypes)]
        out, total = exchange(cols, pids, nrows_arr[0], axis, NSHARDS,
                              slot=rp.base_slot + rp.surplus_slot,
                              packed=True, ragged=rp, report_site=site)
        res = tuple(
            (c.values, c.validity if c.validity is not None
             else jnp.ones_like(c.values, dtype=jnp.bool_))
            for c in out)
        return res + (jnp.reshape(total.astype(jnp.int32), (1,)),)

    return shard_map(step, mesh=mesh,
                     in_specs=(P(axis), P(axis), P(axis)),
                     out_specs=P(axis), check_vma=False)


def test_ragged_exchange_bit_identical(mesh, rng):
    """One hot destination (~80% of rows): the ragged wire (cold base
    all_to_all + hot-pair collective-permutes) delivers bit-identical
    rows to the per-column uniform-slot path, while moving strictly —
    and at this skew >= 2x — fewer wire rows.  The same traced program
    then pins the wire accounting as EXACT (one compile serves both)."""
    from spark_rapids_tpu.parallel.shuffle import pick_slot, plan_ragged
    # one 8-byte + one 4-byte column, first nullable: covers both width
    # groups (u32 lanes + bit-packed masks in u8) at a fraction of the
    # compile cost of a wide column set — the surplus-round ppermutes
    # replicate per lane, so program size scales with the lane count
    dtypes = [dts.INT64, dts.FLOAT32]
    flat, pids, nrows, counts = _skewed_args(rng, dtypes)
    rp = plan_ragged(counts, CAP)
    assert rp is not None, f"no ragged plan for skew {counts.max(axis=0)}"
    args = (flat, pids, nrows)
    site = ("ragged_bytes_site",)
    r_ragged = _ragged_fn(mesh, dtypes, rp, site=site)(*args)
    u_slot = pick_slot(int(counts.max()), CAP)
    # packed uniform baseline: bit-identity of packed-vs-per-column is
    # already pinned by test_packed_roundtrip_bit_identical, and the
    # packed program compiles in a fraction of the per-column one
    r_uniform = _exchange_fn(mesh, dtypes, packed=True,
                             slot=u_slot)(*args)
    # receive capacities legitimately differ (ragged: base slices +
    # worst destination's surplus buffers); compare live prefixes
    tot_r = np.asarray(r_ragged[len(dtypes)]).reshape(NSHARDS, -1)[:, 0]
    tot_u = np.asarray(r_uniform[len(dtypes)]).reshape(NSHARDS, -1)[:, 0]
    np.testing.assert_array_equal(tot_r, tot_u)
    for i in range(len(dtypes)):
        vr = np.asarray(r_ragged[i][0]).reshape(NSHARDS, -1)
        vu = np.asarray(r_uniform[i][0]).reshape(NSHARDS, -1)
        mr = np.asarray(r_ragged[i][1]).reshape(NSHARDS, -1)
        mu = np.asarray(r_uniform[i][1]).reshape(NSHARDS, -1)
        for s in range(NSHARDS):
            n = tot_r[s]
            np.testing.assert_array_equal(
                _bits(vr[s, :n]), _bits(vu[s, :n]),
                err_msg=f"col {i} shard {s}")
            np.testing.assert_array_equal(mr[s, :n], mu[s, :n],
                                          err_msg=f"validity {i} "
                                                  f"shard {s}")
    uniform_rows = NSHARDS * NSHARDS * u_slot
    assert rp.wire_rows(NSHARDS) * 2 <= uniform_rows, \
        (rp.wire_rows(NSHARDS), uniform_rows)

    # -- exact wire accounting (satellite gate: reported bytesMoved ==
    # the payload bytes the traced ragged program actually transmits,
    # derived here from first principles: base all_to_all moves every
    # (src, dst) slice at the cold slot; each hot pair's surplus buffer
    # crosses its one link once) --
    from spark_rapids_tpu.parallel.shuffle import (
        ShuffleWireMetrics, _ragged_site, record_exchange_metrics,
        wire_report)
    # hand-derived packed row bytes for [i64, f32]: u32 lanes
    # = 2+1 = 3 -> 12B; u8 lanes = ceil(1 nullable / 8) = 1 -> 1B
    row_bytes = 4 * 3 + 1
    # the ragged variant records under its OWN report key — a uniform
    # trace at the same site must not clobber it (and vice versa)
    assert wire_report(site) is None
    rep = wire_report(_ragged_site(site, rp))
    assert rep["row_bytes"] == row_bytes, rep
    assert rep["collectives"] == 1 + 2 * (1 + len(rp.rounds)), rep
    # wire rows from the plan geometry: every shard sends the full base
    # payload; each hot pair's surplus crosses its one link once
    wire_rows = NSHARDS * NSHARDS * rp.base_slot \
        + len(rp.pairs) * rp.surplus_slot
    assert rp.wire_rows(NSHARDS) == wire_rows
    metrics = ShuffleWireMetrics()
    record_exchange_metrics(
        metrics, dtypes=dtypes, slot=0, num_parts=NSHARDS,
        nshards=NSHARDS, rows_useful=int(counts.sum()), packed=True,
        site=site, ragged=rp, counts=counts)
    snap = metrics.snapshot()
    assert snap["bytesMoved"] == wire_rows * row_bytes, snap
    assert snap["rowsMoved"] == wire_rows
    assert snap["rowsUseful"] == int(counts.sum())
    assert snap["raggedExchanges"] == 1
    # per-destination wire rows must sum to the aggregate (no
    # destination hides behind the mean)
    pd_rows = sum(v["rowsMoved"]
                  for v in snap["perDestination"].values())
    assert pd_rows == wire_rows, snap["perDestination"]
    assert sum(v["rowsUseful"]
               for v in snap["perDestination"].values()) \
        == int(counts.sum())
    # width-group bytes partition the total exactly
    assert sum(v["bytesMoved"] for v in snap["perGroup"].values()) \
        == snap["bytesMoved"]


def test_ragged_fallback_accounting():
    """A ragged-requested exchange whose columns the lane packer
    refuses runs the uniform per-column wire at the base+surplus slot.
    The exchange body marks the RAGGED report key ``fallback`` at trace
    time; the consumer must then account the uniform program — not the
    ragged plan geometry — and keep the fallback report's exact
    per-column collectives/row bytes (the plain-site report may belong
    to a different variant compiled at the same signature)."""
    from spark_rapids_tpu.parallel.shuffle import (
        ShuffleWireMetrics, _ragged_site, _record_wire_report,
        plan_ragged, record_exchange_metrics, wire_report)
    counts = np.full((NSHARDS, NSHARDS), 4, dtype=np.int64)
    counts[:, 0] = CAP - 4 * (NSHARDS - 1)  # hot destination 0
    rp = plan_ragged(counts, CAP)
    assert rp is not None
    site = ("ragged_fallback_site",)
    # what exchange() records when _plan_pack refuses the columns
    cols = [ColVal(dts.INT64, jnp.arange(8, dtype=jnp.int64), None)]
    _record_wire_report(_ragged_site(site, rp), cols, None,
                        fallback=True)
    assert wire_report(_ragged_site(site, rp))["fallback"]
    metrics = ShuffleWireMetrics()
    record_exchange_metrics(
        metrics, dtypes=[dts.INT64], slot=0, num_parts=NSHARDS,
        nshards=NSHARDS, rows_useful=int(counts.sum()), packed=True,
        site=site, ragged=rp, counts=counts)
    snap = metrics.snapshot()
    # uniform wire at the plan's upper-bound slot, NOT ragged geometry
    slot = rp.base_slot + rp.surplus_slot
    rows = NSHARDS * NSHARDS * slot
    assert snap["raggedExchanges"] == 0, snap
    assert snap["rowsMoved"] == rows, snap
    assert snap["bytesMoved"] == rows * 8, snap  # one i64, no mask
    assert snap["collectives"] == 2, snap  # counts vector + 1 column
    # per-destination wire reflects the uniform slot for every dest
    assert all(v["rowsMoved"] == rows // NSHARDS
               for v in snap["perDestination"].values()), snap


def test_padding_ratio_per_destination(mesh, rng):
    """Per-destination padding under a UNIFORM slot: the hot
    destination is nearly dense while cold destinations pad toward
    num_parts x — the aggregate ratio alone would hide both."""
    from spark_rapids_tpu.parallel.shuffle import (
        ShuffleWireMetrics, pick_slot, record_exchange_metrics)
    dtypes = [dts.INT64, dts.FLOAT64]
    _, _, _, counts = _skewed_args(rng, dtypes)
    slot = pick_slot(int(counts.max()), CAP)
    metrics = ShuffleWireMetrics()
    record_exchange_metrics(
        metrics, dtypes=dtypes, slot=slot, num_parts=NSHARDS,
        nshards=NSHARDS, rows_useful=int(counts.sum()), packed=True,
        counts=counts)
    summary = ShuffleWireMetrics.summarize(metrics.snapshot())
    per_dest = summary["paddingRatioPerDestination"]
    assert set(per_dest) == {str(d) for d in range(NSHARDS)}
    hot = per_dest["3"]
    cold = [v for d, v in per_dest.items() if d != "3"]
    assert hot < min(cold), per_dest
    assert all(v >= 1.0 for v in per_dest.values())
    # the aggregate ratio is the wire-rows-weighted blend, so it sits
    # between the dense hot destination and the padded cold ones
    assert hot <= summary["paddingRatio"] <= max(cold)


def test_exchange_via_gather_matches_all_to_all(mesh, rng):
    """Topology strategy 'gather' (gather-then-redistribute, the
    DCN-friendly shape): identical delivered rows to the uniform
    all_to_all path, zero all_to_all primitives in the compiled
    program."""
    from spark_rapids_tpu.parallel.shuffle import exchange_via_gather
    # both width groups at minimal lane count (compile cost, see
    # test_ragged_exchange_bit_identical)
    dtypes = [dts.INT64, dts.FLOAT32]
    flat, pids, nrows, counts = _skewed_args(rng, dtypes)
    axis = mesh.axis_names[0]

    def gather_step(flat, pids, nrows_arr):
        cols = [ColVal(dt, v, val) for (v, val), dt in zip(flat, dtypes)]
        out, total = exchange_via_gather(cols, pids, nrows_arr[0], axis,
                                         NSHARDS, packed=True)
        res = tuple(
            (c.values, c.validity if c.validity is not None
             else jnp.ones_like(c.values, dtype=jnp.bool_))
            for c in out)
        return res + (jnp.reshape(total.astype(jnp.int32), (1,)),)

    gfn = shard_map(gather_step, mesh=mesh,
                    in_specs=(P(axis), P(axis), P(axis)),
                    out_specs=P(axis), check_vma=False)
    args = (flat, pids, nrows)
    assert _count_collectives(gfn, args, prim="all_to_all") == 0
    assert _count_collectives(gfn, args, prim="all_gather") >= 1
    rg = gfn(*args)
    # packed uniform baseline (see test_ragged_exchange_bit_identical)
    ru = _exchange_fn(mesh, dtypes, packed=True, slot=CAP)(*args)
    _assert_identical(rg, ru, len(dtypes))


def test_topology_strategy_resolution(mesh):
    """'auto' resolves by mesh axis link kind: the virtual CPU mesh is
    single-process single-slice (ici) -> all_to_all; explicit conf
    overrides win; mesh.topology() reports the axis map."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.parallel.mesh import axis_link_kind, topology
    from spark_rapids_tpu.parallel.shuffle import topology_strategy
    assert axis_link_kind(mesh) == "ici"
    topo = topology(mesh)
    assert topo["devices"] == NSHARDS
    assert topo["axes"] == {mesh.axis_names[0]: "ici"}
    assert topology_strategy(mesh, conf=None) == "all_to_all"
    for want in ("gather", "all_to_all"):
        s = TpuSession({"spark.rapids.tpu.shuffle.topology.strategy":
                        want})
        try:
            assert topology_strategy(mesh, conf=s.conf) == want
        finally:
            s.stop()


# ---------------------------------------------------- host staging --

def test_host_hash_partition_parity(mesh, rng):
    """The host-side murmur mix must place every row exactly where the
    device kernels would — the invariant host-RAM staging correctness
    rests on.  Mixed dtypes, NaN/-0.0 canonicalization, null
    sentinels."""
    from spark_rapids_tpu.parallel.exchange_async import (
        host_hash_partition_ids)
    from spark_rapids_tpu.parallel.partitioning import hash_partition_ids
    n = 512
    vals_i = rng.integers(-10**9, 10**9, n).astype(np.int64)
    vals_f = rng.normal(size=n)
    vals_f[rng.choice(n, 30, replace=False)] = np.nan
    vals_f[rng.choice(n, 30, replace=False)] = -0.0
    vals_b = rng.random(n) < 0.5
    valid = rng.random(n) < 0.9
    cols_dev = [ColVal(dts.INT64, jnp.asarray(vals_i),
                       jnp.asarray(valid)),
                ColVal(dts.FLOAT64, jnp.asarray(vals_f), None),
                ColVal(dts.BOOL, jnp.asarray(vals_b), None)]
    dev = np.asarray(hash_partition_ids(cols_dev, NSHARDS))
    host = host_hash_partition_ids(
        [(vals_i, valid), (vals_f, None), (vals_b, None)], NSHARDS)
    np.testing.assert_array_equal(dev, host)


def test_host_staged_partition_layout(rng):
    """host_staged_partition delivers the post-exchange layout: every
    live row lands on its destination shard (stable source order),
    dead padding stays dead, and the staged bytes are the compressed
    frame size (> 0, <= raw)."""
    from spark_rapids_tpu.parallel.exchange_async import (
        host_staged_partition)
    cap = 32
    vals = rng.normal(size=NSHARDS * cap)
    mask = rng.random(NSHARDS * cap) < 0.9
    counts = rng.integers(0, cap + 1, NSHARDS).astype(np.int32)
    pids = rng.integers(0, NSHARDS, NSHARDS * cap).astype(np.int32)
    out_cols, dest_counts, staged_bytes = host_staged_partition(
        [(vals, mask)], counts, pids, NSHARDS)
    live = np.zeros(NSHARDS * cap, dtype=bool)
    for s in range(NSHARDS):
        live[s * cap: s * cap + counts[s]] = True
    assert int(dest_counts.sum()) == int(live.sum())
    (ov, om), = out_cols
    out_cap = ov.shape[0] // NSHARDS
    for d in range(NSHARDS):
        want = vals[live & (pids == d)]  # stable source order
        got = ov.reshape(NSHARDS, out_cap)[d, :dest_counts[d]]
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(
            om.reshape(NSHARDS, out_cap)[d, :dest_counts[d]],
            mask[live & (pids == d)])
    assert 0 < staged_bytes
    raw = vals.nbytes + mask.nbytes
    assert staged_bytes <= raw + 256  # frame header overhead bound


def test_oversized_exchange_host_stages_not_split(mesh):
    """E2E acceptance: a payload past the staging threshold routes
    through host RAM — the query stays distributed, answers exactly,
    records hostStagedExchanges, and the recovery ladder's split rung
    NEVER fires."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.session import TpuSession
    session = TpuSession({
        "spark.rapids.tpu.exchange.hostStaging.thresholdBytes": 1,
        "spark.rapids.sql.join.broadcastThresholdRows": 1,
    }, mesh=mesh)
    oracle = TpuSession()
    try:
        rng = np.random.default_rng(5)
        pdf = pd.DataFrame({"k": rng.integers(0, 300, 4000),
                            "v": rng.normal(size=4000)})
        dim = pd.DataFrame({"k": np.arange(300),
                            "w": rng.normal(size=300)})

        def q(s):
            return (s.create_dataframe(pdf)
                    .join(s.create_dataframe(dim), on="k")
                    .group_by("k")
                    .agg(F.sum(F.col("v")).alias("sv"),
                         F.sum(F.col("w")).alias("sw"))
                    .to_pandas().sort_values("k", ignore_index=True))

        got = q(session)
        assert session.last_dist_explain == "distributed"
        pd.testing.assert_frame_equal(got, q(oracle))
        ov = session.exchange_overlap_metrics.snapshot()
        assert ov["hostStagedExchanges"] >= 2, ov  # join + aggregate
        assert 0 < ov["hostStagedBytes"]
        assert not session.recovery_log, session.recovery_log
    finally:
        session.stop()
        oracle.stop()


# ------------------------------------------------- compressed wire (ISSUE 11) --
def test_wire_encoded_exchange_first_principles(mesh, rng):
    """Compressed wire: an int64 dictionary-code column marked
    ``wire_encode`` ships as ONE i32 lane (half its decoded bytes) and
    widens back bit-identically.  The satellite gate: reported
    ``bytesMoved`` partitions the ENCODED payload exactly — derived
    here from first principles off the hand-computed lane layout — and
    ``encodedBytesSaved`` attributes precisely the narrowed delta,
    with the per-destination breakdown still summing to the totals."""
    from spark_rapids_tpu.parallel.shuffle import (
        ShuffleWireMetrics, record_exchange_metrics, wire_report)
    dtypes = [dts.INT64, dts.FLOAT64]
    axis = mesh.axis_names[0]
    codes = rng.integers(0, 900, NSHARDS * CAP).astype(np.int64)
    meas = rng.normal(size=NSHARDS * CAP)
    mask = rng.random(NSHARDS * CAP) < 0.85
    flat = ((jnp.asarray(codes), jnp.asarray(mask)),
            (jnp.asarray(meas), None))
    pids_h = rng.integers(0, NSHARDS, NSHARDS * CAP).astype(np.int32)
    nrows = np.full(NSHARDS, CAP, dtype=np.int32)
    counts = np.zeros((NSHARDS, NSHARDS), dtype=np.int64)
    for s in range(NSHARDS):
        counts[s] = np.bincount(pids_h.reshape(NSHARDS, CAP)[s],
                                minlength=NSHARDS)
    args = (flat, jnp.asarray(pids_h), jnp.asarray(nrows))
    site = ("wenc_site",)

    def fn(wire_encode, report_site=None):
        def step(flat, pids, nrows_arr):
            cols = [ColVal(dt, v, val)
                    for (v, val), dt in zip(flat, dtypes)]
            out, total = exchange(cols, pids, nrows_arr[0], axis,
                                  NSHARDS, slot=CAP, packed=True,
                                  wire_encode=wire_encode,
                                  report_site=report_site)
            res = tuple(
                (c.values, c.validity if c.validity is not None
                 else jnp.ones_like(c.values, dtype=jnp.bool_))
                for c in out)
            return res + (jnp.reshape(total.astype(jnp.int32), (1,)),)

        return shard_map(step, mesh=mesh,
                         in_specs=(P(axis), P(axis), P(axis)),
                         out_specs=P(axis), check_vma=False)

    r_enc = fn((0,), report_site=site)(*args)
    r_wide = fn(())(*args)
    _assert_identical(r_enc, r_wide, len(dtypes))
    # received dtype must be the ORIGINAL int64, not the wire i32
    assert np.asarray(r_enc[0][0]).dtype == np.int64

    # hand-derived encoded lane layout for [i64-as-i32, f64]:
    # u32 lanes = 1 + 2 = 12B/row; u8 = 1 bit-packed mask lane = 1B
    rep = wire_report(site)
    assert rep["row_bytes"] == 13, rep
    assert rep["row_bytes_saved"] == 4, rep
    metrics = ShuffleWireMetrics()
    record_exchange_metrics(
        metrics, dtypes=dtypes, slot=CAP, num_parts=NSHARDS,
        nshards=NSHARDS, rows_useful=int(counts.sum()), packed=True,
        site=site, counts=counts, wire_encode_cols=1)
    snap = metrics.snapshot()
    rows_moved = NSHARDS * NSHARDS * CAP
    assert snap["rowsMoved"] == rows_moved
    assert snap["bytesMoved"] == rows_moved * 13, snap
    assert snap["encodedBytesSaved"] == rows_moved * 4, snap
    # per-destination wire/useful rows still partition the aggregates
    assert sum(v["rowsMoved"]
               for v in snap["perDestination"].values()) == rows_moved
    assert sum(v["rowsUseful"]
               for v in snap["perDestination"].values()) \
        == int(counts.sum())
    assert sum(v["bytesMoved"] for v in snap["perGroup"].values()) \
        == snap["bytesMoved"]
    # the summarize() headline: decoded/encoded wire ratio
    summary = ShuffleWireMetrics.summarize(snap)
    assert summary["wireCompressionRatio"] == round(17 / 13, 3), summary
