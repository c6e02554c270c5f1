"""Benchmark driver: TPC-H q6 + q1-shaped group-by (BASELINE.md configs 1-2).

The default main measures in THIS process — one process owns the chip, so
there is no probe and no child — and prints ONE JSON line:
``{"metric": "tpch_q6_rows_per_sec", "value": rows/s, "unit": "rows/s",
"vs_baseline": x, "platform": ..., "device_kind": ..., "device_count": n,
...extra diagnostics...}``.  A platform other than ``tpu`` is a non-zero
exit before any work unless ``--allow-cpu`` is given (a CPU number is
never a device number: the line names the platform it ran on).  A phase
that fails raises, and the exit code says so.

The other mains (``--repeat``, ``--concurrency``, ...) run on whatever
platform JAX resolves; set ``JAX_PLATFORMS=cpu`` for a CPU number.
"""

import json
import os
import subprocess
import sys
import time

WALL_BUDGET = float(os.environ.get("BENCH_WALL_BUDGET", "480"))
_T0 = time.monotonic()


def remaining() -> float:
    return WALL_BUDGET - (time.monotonic() - _T0)


def log(msg: str) -> None:
    print(f"bench[{WALL_BUDGET - remaining():6.0f}s]: {msg}",
          file=sys.stderr, flush=True)


def main() -> int:
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and "--allow-cpu" not in sys.argv:
        print(f"bench: platform is {platform!r}, not 'tpu' "
              "(--allow-cpu measures the CPU backend instead)",
              file=sys.stderr)
        return 1
    from spark_rapids_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    best = child_main()
    best.update(platform=platform, device_kind=devs[0].device_kind,
                device_count=len(devs))
    print(json.dumps(best), flush=True)
    return 0


# ------------------------------------------------------------ default main --
def trace_conf(extra=None):
    """Session conf for a bench main: BENCH_TRACE=1 arms span tracing
    so emissions carry the phase-fraction breakdown."""
    conf = dict(extra or {})
    if os.environ.get("BENCH_TRACE"):
        conf["spark.rapids.tpu.trace.enabled"] = True
    return conf or None


def span_frac_fields(session) -> dict:
    """Span-derived phase fractions (utils/tracing.py, ISSUE 12) for a
    bench emission: compile / exchange / spill / unattributed wall
    fractions of the session's LAST traced query.  Empty when tracing
    is off — a zero fraction must mean "measured zero", never "not
    measured"."""
    from spark_rapids_tpu.utils import tracing
    sp = getattr(session, "last_span_stats", None)
    if not tracing.armed() or not sp:
        return {}
    wall = sp.get("wallMs") or 0.0

    def frac(ms):
        return round(ms / wall, 4) if wall else 0.0

    ph = sp.get("phases") or {}
    return {
        "compile_ms_frac": frac(ph.get("compile", 0.0)),
        "exchange_ms_frac": frac(ph.get("exchange", 0.0)),
        "spill_ms_frac": frac(ph.get("spill", 0.0)),
        "unattributed_ms_frac": frac(sp.get("unattributedMs", 0.0)),
    }


def fused_wire_fields(session=None) -> dict:
    """Wire-fusion launch accounting (parallel/shuffle.py, ISSUE 19)
    for a bench emission: warm distributed stages that shipped the
    packed wire payload out of ONE program vs stages that still ran
    the two-dispatch sequence.  Structural zeros on single-device runs
    and with `spark.rapids.tpu.fusion.wire.enabled` off — same
    convention as shuffle_bytes_moved."""
    from spark_rapids_tpu.parallel.shuffle import metrics_for_session
    w = metrics_for_session(session).snapshot()
    return {
        "fused_wire_dispatches": w.get("fusedWireDispatches", 0),
        "unfused_wire_dispatches": w.get("unfusedWireDispatches", 0),
    }


def gen_host(n: int, seed: int = 42):
    import numpy as np
    rng = np.random.default_rng(seed)
    return {
        "l_extendedprice": rng.uniform(1000.0, 100000.0, n),
        "l_discount": rng.uniform(0.0, 0.11, n).round(2),
        "l_quantity": rng.integers(1, 51, n).astype(np.float64),
        "l_shipdate": rng.integers(8766, 10957, n).astype(np.int32),
        "l_tax": rng.uniform(0.0, 0.08, n).round(2),
        "l_returnflag_code": rng.integers(0, 3, n).astype(np.int64),
        "l_linestatus_code": rng.integers(0, 2, n).astype(np.int64),
    }


def gen_device_batch(n: int, seed: int = 42):
    """Generate lineitem columns on device; only PRNG keys cross host."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import dtypes as dts
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import Column

    @jax.jit
    def gen(key):
        ks = jax.random.split(key, 7)
        price = jax.random.uniform(ks[0], (n,), dtype=jnp.float64,
                                   minval=1000.0, maxval=100000.0)
        disc = jnp.round(
            jax.random.uniform(ks[1], (n,), dtype=jnp.float64,
                               maxval=0.11), 2)
        qty = jax.random.randint(ks[2], (n,), 1, 51).astype(jnp.float64)
        ship = jax.random.randint(ks[3], (n,), 8766, 10957).astype(jnp.int32)
        tax = jnp.round(
            jax.random.uniform(ks[4], (n,), dtype=jnp.float64,
                               maxval=0.08), 2)
        rf = jax.random.randint(ks[5], (n,), 0, 3).astype(jnp.int64)
        ls = jax.random.randint(ks[6], (n,), 0, 2).astype(jnp.int64)
        return price, disc, qty, ship, tax, rf, ls

    price, disc, qty, ship, tax, rf, ls = gen(jax.random.PRNGKey(seed))
    price.block_until_ready()
    return ColumnarBatch({
        "l_extendedprice": Column(dts.FLOAT64, price, n),
        "l_discount": Column(dts.FLOAT64, disc, n),
        "l_quantity": Column(dts.FLOAT64, qty, n),
        "l_shipdate": Column(dts.INT32, ship, n),
        "l_tax": Column(dts.FLOAT64, tax, n),
        "l_returnflag_code": Column(dts.INT64, rf, n),
        "l_linestatus_code": Column(dts.INT64, ls, n),
    })


def make_q6(session, df):
    from spark_rapids_tpu.api import functions as F

    def query():
        q = df.filter(
            (F.col("l_shipdate") >= 9131) & (F.col("l_shipdate") < 9496) &
            (F.col("l_discount") >= 0.05) & (F.col("l_discount") <= 0.07) &
            (F.col("l_quantity") < 24.0)
        ).select((F.col("l_extendedprice") * F.col("l_discount"))
                 .alias("rev")).agg(F.sum("rev").alias("revenue"))
        return q.collect()[0][0]

    return query


def make_q1(session, df):
    """q1-shaped group-by: BASELINE.md config 2's first step (grouped
    sums/averages with a derived product expression, 6 groups)."""
    from spark_rapids_tpu.api import functions as F

    def query():
        q = (df.filter(F.col("l_shipdate") <= 10471)
             .groupBy("l_returnflag_code", "l_linestatus_code")
             .agg(F.sum("l_quantity").alias("sum_qty"),
                  F.sum("l_extendedprice").alias("sum_base"),
                  F.sum((F.col("l_extendedprice") *
                         (F.lit(1.0) - F.col("l_discount")))
                        .alias("d")).alias("sum_disc"),
                  F.avg("l_discount").alias("avg_disc"),
                  F.count("l_quantity").alias("n")))
        return q.collect()

    return query


def time_query(query, budget: float, max_iters: int = 5):
    result = query()  # warmup / compile
    times = []
    t_stop = time.monotonic() + budget
    for _ in range(max_iters):
        t0 = time.perf_counter()
        result = query()
        times.append(time.perf_counter() - t0)
        if time.monotonic() > t_stop:
            break
    return result, min(times)


def pandas_q6(data, max_iters: int = 3):
    import pandas as pd
    df = pd.DataFrame(data)

    def query():
        m = df[(df.l_shipdate >= 9131) & (df.l_shipdate < 9496) &
               (df.l_discount >= 0.05) & (df.l_discount <= 0.07) &
               (df.l_quantity < 24.0)]
        return (m.l_extendedprice * m.l_discount).sum()

    return time_query(query, budget=30.0, max_iters=max_iters)


def pandas_q1(data, max_iters: int = 3):
    import pandas as pd
    df = pd.DataFrame(data)

    def query():
        m = df[df.l_shipdate <= 10471].copy()
        m["disc_price"] = m.l_extendedprice * (1.0 - m.l_discount)
        return (m.groupby(["l_returnflag_code", "l_linestatus_code"])
                .agg(sum_qty=("l_quantity", "sum"),
                     sum_base=("l_extendedprice", "sum"),
                     sum_disc=("disc_price", "sum"),
                     avg_disc=("l_discount", "mean"),
                     n=("l_quantity", "count")))

    return time_query(query, budget=30.0, max_iters=max_iters)


def child_main() -> dict:
    """The default main's measurement; returns the emission dict."""
    import numpy as np
    left = remaining
    best = {"metric": "tpch_q6_rows_per_sec", "value": 0, "unit": "rows/s",
            "vs_baseline": 0.0,
            # shuffle-wire attribution (parallel/shuffle.py): stays 0
            # for single-device runs; on a mesh the padding ratio is
            # the fused packed exchange's headline diagnostic
            "shuffle_bytes_moved": 0, "shuffle_padding_ratio": 0.0,
            # stage-checkpoint recovery attribution
            # (robustness/checkpoint.py): resumes stay 0 on clean runs;
            # bytes written show what the lineage log cost
            "checkpoint_resume_count": 0, "checkpoint_bytes_written": 0,
            # persistent AOT executable cache (ops/jit_cache.py): the
            # warm-start counters ride EVERY bench emission (not just
            # --repeat) so BENCH_* artifacts show whether this process
            # compiled anything a previous session had already exported
            "jit_cache_persistent_hits": 0,
            "jit_cache_persistent_misses": 0,
            "jit_cache_persistent_stores": 0,
            # async exchange/compute overlap (parallel/exchange_async.py)
            "exchange_overlap_ms": 0.0, "exchange_overlap_fraction": 0.0,
            # wire-fused distributed stages (ISSUE 19): one program
            # per shard emitting the packed wire payload
            "fused_wire_dispatches": 0, "unfused_wire_dispatches": 0}

    def wire_fields(session):
        from spark_rapids_tpu.ops.jit_cache import persistent_info
        from spark_rapids_tpu.parallel.exchange_async import \
            overlap_metrics_for_session
        from spark_rapids_tpu.parallel.shuffle import metrics_for_session
        from spark_rapids_tpu.robustness.checkpoint import \
            checkpoint_metrics
        w = metrics_for_session(session).snapshot()
        best["shuffle_bytes_moved"] = w["bytesMoved"]
        best["fused_wire_dispatches"] = w.get("fusedWireDispatches", 0)
        best["unfused_wire_dispatches"] = \
            w.get("unfusedWireDispatches", 0)
        best["shuffle_padding_ratio"] = round(
            w["rowsMoved"] / max(w["rowsUseful"], 1), 3)
        c = checkpoint_metrics.snapshot()
        best["checkpoint_resume_count"] = c["resumes"]
        best["checkpoint_bytes_written"] = c["bytesWritten"]
        p = persistent_info()
        best["jit_cache_persistent_hits"] = p["hits"]
        best["jit_cache_persistent_misses"] = p["misses"]
        best["jit_cache_persistent_stores"] = p["stores"]
        ov = overlap_metrics_for_session(session).snapshot()
        best["exchange_overlap_ms"] = ov["exchangeOverlapMs"]
        best["exchange_overlap_fraction"] = round(
            ov["exchangeOverlapMs"] / ov["exchangeWallMs"], 3) \
            if ov["exchangeWallMs"] else 0.0
        # encoded execution / compressed wire / compressed storage
        # attribution (ISSUE 11): the decoded-vs-encoded wire ratio,
        # stages that ran on dictionary codes, and the raw->stored
        # byte totals of compressed host-tier frames.  Wire fields are
        # structural zeros on single-device runs (no exchanges — the
        # shuffle_bytes_moved precedent); the MULTICHIP artifacts and
        # the storage probe below carry the real ratios
        best["encoded_bytes_saved"] = w.get("encodedBytesSaved", 0)
        best["wire_compression_ratio"] = round(
            (w["bytesMoved"] + w.get("encodedBytesSaved", 0))
            / max(w["bytesMoved"], 1), 3)
        if "encoded_stage_count" not in best:
            # the string-q1 A/B (encoded session) may already have
            # recorded the real number; this session runs decoded
            fu = getattr(session, "last_fusion_stats", None) or {}
            best["encoded_stage_count"] = fu.get("encodedStages", 0)
        cat = getattr(session, "memory_catalog", None)
        if cat is not None and "state_bytes_raw" not in best:
            # the storage probe (string-q1 A/B block) may already have
            # measured a REAL compressed-spill ratio; this session
            # runs codec-off and would report structural zeros
            st = cat.stats()
            best["state_bytes_raw"] = st["host_raw_bytes_total"]
            best["state_bytes_compressed"] = \
                st["host_encoded_bytes_total"]
        best.update(span_frac_fields(session))

    from spark_rapids_tpu.api.session import TpuSession
    # BENCH_TRACE=1 arms span tracing on the measured session: every
    # emission then carries compile/exchange/spill/unattributed phase
    # fractions (span_frac_fields).  Off by default — the tracing-off
    # p50 is the number the overhead pin compares against.
    session = TpuSession(trace_conf())
    # correctness gate at 64K rows (cheap)
    n_small = 1 << 16
    small = gen_host(n_small)
    engine_res, _ = time_query(
        make_q6(session, session.create_dataframe(small)), budget=5.0,
        max_iters=1)
    pd_res, _ = pandas_q6(small, max_iters=1)
    rel = abs(engine_res - pd_res) / max(abs(pd_res), 1e-9)
    assert rel < 1e-9, f"q6 wrong answer: {engine_res} vs {pd_res}"
    g_engine = make_q1(session, session.create_dataframe(small))()
    g_pandas = pandas_q1(small, max_iters=1)[0]
    assert len(g_engine) == len(g_pandas), "q1 group count mismatch"
    eng = {(int(r[0]), int(r[1])): r[2:] for r in g_engine}
    for key, row in g_pandas.iterrows():
        got = eng[(int(key[0]), int(key[1]))]
        for a, b in zip(got, row):
            assert abs(a - b) / max(abs(b), 1e-9) < 1e-9, (key, got, row)
    best["correctness"] = "ok"
    log(f"correctness ok at {n_small} rows ({left():.0f}s left)")

    # pandas CPU baselines, sampled then scaled (both queries are O(n));
    # shrink the sample under a tight budget so baselines can't eat it
    pd_n = 1 << (23 if left() > 120 else 21)
    data = gen_host(pd_n)
    _, t_q6 = pandas_q6(data)
    _, t_q1 = pandas_q1(data)
    q6_base = pd_n / t_q6
    q1_base = pd_n / t_q1
    del data
    log(f"pandas q6 {q6_base / 1e6:.1f}M rows/s, "
        f"q1 {q1_base / 1e6:.1f}M rows/s ({left():.0f}s left)")

    # engine perf at growing device-resident sizes
    for shift in (22, 24, 26):
        if left() < 20:
            log(f"skipping n=2^{shift} ({left():.0f}s left)")
            break
        n = 1 << shift
        batch = gen_device_batch(n)
        df = session.create_dataframe(batch)
        r6, t6 = time_query(make_q6(session, df),
                            budget=min(15.0, left() / 4))
        assert np.isfinite(r6) and r6 > 0, r6
        best.update(value=round(n / t6),
                    vs_baseline=round(n / t6 / q6_base, 3))
        log(f"q6 n=2^{shift} t={t6 * 1e3:.1f}ms "
            f"{n / t6 / 1e6:.1f}M rows/s "
            f"vs_pandas={best['vs_baseline']}x")
        if left() < 30:
            continue
        # sync accounting rides the timed runs (the per-run count
        # is deterministic, so delta/runs is exact): no extra
        # query execution outside the wall-clock budget.  The
        # BENCH_r* trajectory tracks this alongside rows/s so wins
        # are attributable to the deferred-sync/pipeline work.
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.utils.hostsync import \
            host_sync_metrics
        q1 = make_q1(session, df)
        runs = [0]

        def q1_counted():
            runs[0] += 1
            return q1()

        s0 = host_sync_metrics.snapshot()
        r1, t1 = time_query(q1_counted,
                            budget=min(15.0, left() / 4))
        assert len(r1) == 6, f"q1 expected 6 groups, got {len(r1)}"
        best["groupby_rows_per_sec"] = round(n / t1)
        best["groupby_vs_baseline"] = round(n / t1 / q1_base, 3)
        best["host_sync_count"] = round(
            (host_sync_metrics.snapshot() - s0) / runs[0])
        best["pipeline_depth"] = (
            session.conf.get(rc.PIPELINE_DEPTH)
            if session.conf.get(rc.PIPELINE_ENABLED) else 0)
        log(f"q1 n=2^{shift} t={t1 * 1e3:.1f}ms "
            f"{n / t1 / 1e6:.1f}M rows/s "
            f"vs_pandas={best['groupby_vs_baseline']}x")
    # string-heavy q1-shape A/B (ISSUE 11 headline): REAL string group
    # keys, encoded execution off vs on.  Decoded runs the two-stage
    # host-dictionary path; encoded runs the whole stage fused on i32
    # codes.  Results must match exactly; the p50 pair is the
    # trajectory's encoded-execution number.
    if left() > 25:
        n_str = 1 << 21
        d = gen_host(n_str)
        flags = np.array(["A", "N", "R"])
        status = np.array(["F", "O"])
        d["l_returnflag"] = flags[d.pop("l_returnflag_code") % 3]
        d["l_linestatus"] = status[d.pop("l_linestatus_code") % 2]
        results = {}
        ab_sessions = []
        try:
            for enc in (False, True):
                s2 = TpuSession({
                    "spark.rapids.tpu.encoding.execution.enabled":
                        enc,
                    "spark.rapids.sql.distributed.enabled": False})
                ab_sessions.append(s2)
                df2 = s2.create_dataframe(d)
                from spark_rapids_tpu.api import functions as F

                def q():
                    return (df2.filter(F.col("l_shipdate") <= 10471)
                            .groupBy("l_returnflag", "l_linestatus")
                            .agg(F.sum("l_quantity").alias("sq"),
                                 F.sum("l_extendedprice").alias(
                                     "sb"),
                                 F.avg("l_discount").alias("ad"),
                                 F.count("l_quantity").alias("n"))
                            .collect())

                r, t = time_query(q, budget=min(10.0, left() / 3))
                results[enc] = (sorted(map(tuple, r)), t)
                key = "encoded" if enc else "decoded"
                best[f"{key}_string_q1_ms"] = round(t * 1e3, 3)
                if enc:
                    fu = getattr(s2, "last_fusion_stats",
                                 None) or {}
                    best["encoded_stage_count"] = \
                        fu.get("encodedStages", 0)
        finally:
            for s2 in ab_sessions:
                s2.stop()
        assert results[False][0] == results[True][0], \
            "encoded A/B diverged"
        best["encoded_string_q1_speedup"] = round(
            results[False][1] / max(results[True][1], 1e-9), 3)
        log(f"string q1 decoded "
            f"{results[False][1] * 1e3:.1f}ms -> encoded "
            f"{results[True][1] * 1e3:.1f}ms "
            f"({best['encoded_string_q1_speedup']}x)")
        # storage-codec attribution probe (untimed): a tiny-budget
        # session with the host codec ON actually spills through
        # compressed frames, so state_bytes_raw/compressed carry a
        # real ratio (the main session never spills at default
        # budgets — its catalog would report structural zeros)
        from spark_rapids_tpu.api import functions as F
        s3 = TpuSession({
            "spark.rapids.tpu.encoding.storage.hostCodec": "lz4",
            "spark.rapids.memory.tpu.deviceLimitBytes": 4096,
            "spark.rapids.sql.distributed.enabled": False})
        try:
            (s3.create_dataframe(d).groupBy("l_returnflag")
             .agg(F.sum("l_quantity").alias("s")).collect())
            st3 = s3.memory_catalog.stats()
            best["state_bytes_raw"] = st3["host_raw_bytes_total"]
            best["state_bytes_compressed"] = \
                st3["host_encoded_bytes_total"]
        finally:
            s3.stop()
        log(f"storage codec {best['state_bytes_raw']}B raw"
            f" -> {best['state_bytes_compressed']}B stored")
    wire_fields(session)
    return best


# ------------------------------------------------------------------ ingest --
def ingest_main(n_ticks: int) -> None:
    """Continuous-ingest bench: THREE standing query shapes — plain
    aggregation, join-enrich-then-aggregate, and windowed aggregation
    with watermark eviction — each ingesting one appended file per
    tick (robustness/incremental.py).  Emits ONE JSON line with
    per-shape cold-query latency vs steady-state tick p50/p95, the
    per-shape reuse ratio, and the state-size / watermark-eviction
    diagnostics — the ISSUE 14 acceptance metric (join+agg steady
    tick < 1/2 the cold-query wall at 10+ tick history) lands in
    BENCH_*.json here.  Runs in-process on whatever platform jax
    resolves (set JAX_PLATFORMS=cpu for a CPU number)."""
    import shutil
    import tempfile

    import numpy as np
    import pandas as pd

    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.robustness.incremental import \
        incremental_metrics
    from spark_rapids_tpu.tools.profiling import nearest_rank

    rows_per_file = 1 << 17
    d = tempfile.mkdtemp(prefix="tpu-ingest-bench-")
    rng = np.random.default_rng(7)

    def write(i: int) -> str:
        pdf = pd.DataFrame({
            "k": rng.integers(0, 64, rows_per_file),
            "v": rng.integers(0, 10_000,
                              rows_per_file).astype(np.float64)})
        p = os.path.join(d, f"batch-{i:04d}.parquet")
        pdf.to_parquet(p, index=False)
        return p

    def write_win(i: int, tick: int) -> str:
        pdf = pd.DataFrame({
            "k": rng.integers(0, 64, rows_per_file),
            "v": rng.integers(0, 10_000,
                              rows_per_file).astype(np.float64),
            "ts": pd.to_datetime("2024-01-01") + pd.to_timedelta(
                tick * 600 + rng.integers(0, 600, rows_per_file),
                unit="s")})
        p = os.path.join(d, f"win-{i:04d}.parquet")
        pdf.to_parquet(p, index=False)
        return p

    def drive(name: str, make_df, writer, out: dict) -> None:
        """One shape: first tick, n_ticks steady ticks, then the
        COLD wall — the one-shot recompute over everything ingested
        (the runner keeps its standing scan in step), jit-warm second
        run.  That is the acceptance comparison: a steady tick at
        10+ tick history vs re-answering the same standing query from
        scratch over the same data.  Per-shape reuse ratio comes from
        the metric deltas around this shape's loop alone."""
        runner = session.incremental(make_df())
        t0 = time.perf_counter()
        runner.tick()
        first_tick_ms = (time.perf_counter() - t0) * 1e3
        m0 = incremental_metrics.snapshot()
        ticks_ms = []
        for i in range(n_ticks):
            p = writer(2 + i)
            t0 = time.perf_counter()
            runner.tick([p])
            ticks_ms.append((time.perf_counter() - t0) * 1e3)
        m1 = incremental_metrics.snapshot()
        # cold = the standing df one-shot over the FULL ingested
        # history (runner._finish keeps its scan's paths in step)
        cold_df = runner.df
        cold_df.to_pandas()
        t0 = time.perf_counter()
        cold_df.to_pandas()
        cold_ms = (time.perf_counter() - t0) * 1e3
        runner.close()
        ticks_ms.sort()
        steady = nearest_rank(ticks_ms, 0.50)
        out[f"{name}_cold_query_ms"] = round(cold_ms, 3)
        out[f"{name}_first_tick_ms"] = round(first_tick_ms, 3)
        out[f"{name}_steady_tick_ms"] = round(steady, 3)
        out[f"{name}_p95_tick_ms"] = round(
            nearest_rank(ticks_ms, 0.95), 3)
        out[f"{name}_cold_vs_steady"] = round(
            cold_ms / max(steady, 1e-9), 3)
        out[f"{name}_reuse_ratio"] = round(
            (m1["incrementalTicks"] - m0["incrementalTicks"])
            / max(m1["ticks"] - m0["ticks"], 1), 3)

    try:
        conf = dict(trace_conf() or {})
        # windowed shape: evict buckets two windows behind the newest
        # event time so steady state stays bounded
        conf["spark.rapids.tpu.incremental.watermarkDelayMs"] = \
            1_200_000
        session = TpuSession(conf)
        incremental_metrics.reset()
        first = [write(0), write(1)]
        firstw = [write_win(0, 0), write_win(1, 1)]
        dim = pd.DataFrame({
            "k": np.arange(64),
            "w": (np.arange(64) % 9 + 1).astype(np.float64)})
        dim_agg = (session.create_dataframe(dim).groupBy("k")
                   .agg(F.max("w").alias("w")))

        def agg_df():
            return (session.read.parquet(*first)
                    .groupBy("k")
                    .agg(F.sum("v").alias("sv"),
                         F.count("v").alias("n"),
                         F.avg("v").alias("av"))
                    .orderBy("k"))

        def join_df():
            return (session.read.parquet(*first)
                    .join(dim_agg, "k").groupBy("k")
                    .agg(F.sum((F.col("v") * F.col("w")).alias("vw"))
                         .alias("s"),
                         F.count("v").alias("n"))
                    .orderBy("k"))

        def win_df():
            return (session.read.parquet(*firstw)
                    .groupBy(F.window("ts", "10 minutes"), "k")
                    .agg(F.sum("v").alias("sv"),
                         F.count("v").alias("n"))
                    .orderBy("window.start", "k"))

        shapes: dict = {}
        drive("agg", agg_df, write, shapes)
        drive("join", join_df, write, shapes)
        drive("window", win_df,
              lambda i: write_win(i, i), shapes)
        m = incremental_metrics.snapshot()
        ingested = rows_per_file * (2 + n_ticks)
        print(json.dumps({
            "metric": "ingest_steady_tick_ms",
            "value": shapes["agg_steady_tick_ms"],
            "unit": "ms",
            "ticks": n_ticks,
            "rows_ingested": ingested,
            # legacy top-level fields keep BENCH continuity (they ARE
            # the agg shape's numbers)
            "cold_query_ms": shapes["agg_cold_query_ms"],
            "first_tick_ms": shapes["agg_first_tick_ms"],
            "p95_tick_ms": shapes["agg_p95_tick_ms"],
            "cold_vs_steady": shapes["agg_cold_vs_steady"],
            "incremental_state_bytes": m["stateBytes"],
            "incremental_state_bytes_raw": m.get("stateBytesRaw",
                                                 m["stateBytes"]),
            "incremental_reuse_ratio": round(
                m["incrementalTicks"] / max(m["ticks"], 1), 3),
            "rollbacks": m["rollbacks"],
            **shapes,
            "watermark_evicted_buckets":
                m["watermarkEvictedBuckets"],
            "watermark_evicted_bytes": m["watermarkEvictedBytes"],
            **span_frac_fields(session),
            **fused_wire_fields(session),
        }))
        sys.stdout.flush()
        session.stop()
    finally:
        shutil.rmtree(d, ignore_errors=True)


# ------------------------------------------------------------------- fleet --
def fleet_main(n_subs: int) -> None:
    """Standing-query fleet bench (serving/fleet.py): N join-enrich
    standing queries over ONE append-only fact stream, ticked in
    shared-ingest rounds, vs the same query ticked alone.  Emits ONE
    JSON line whose headline is the aggregate-round wall over N x the
    lone steady tick — the ISSUE 16 acceptance metric (well under N)
    — plus the counters proving WHY: source reads per round (1 per
    new file, not N) and cross-subscriber epoch-tier splices."""
    import shutil
    import tempfile

    import numpy as np
    import pandas as pd

    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.robustness import inject as I
    from spark_rapids_tpu.tools.profiling import nearest_rank

    n_ticks = int(os.environ.get("BENCH_FLEET_TICKS", "6"))
    rows_per_file = 1 << 17
    d = tempfile.mkdtemp(prefix="tpu-fleet-bench-")
    rng = np.random.default_rng(11)

    def write(tag: str, i: int) -> str:
        pdf = pd.DataFrame({
            "k": rng.integers(0, 64, rows_per_file),
            "v": rng.integers(0, 10_000,
                              rows_per_file).astype(np.float64)})
        p = os.path.join(d, f"{tag}-{i:04d}.parquet")
        pdf.to_parquet(p, index=False)
        return p

    try:
        import jax
        conf = dict(trace_conf() or {})
        # cross-subscriber splices ride the session shared-stage
        # cache's epoch tier; the bench measures them, so opt in.
        # Stage checkpoints (and therefore splices) need the
        # distributed planner: run on a mesh when devices allow
        conf["spark.rapids.tpu.serving.sharedStage.enabled"] = True
        mesh = None
        if jax.device_count() >= 2:
            from spark_rapids_tpu.parallel.mesh import make_mesh
            mesh = make_mesh(jax.device_count())
        session = TpuSession(conf, mesh=mesh)
        dim = pd.DataFrame({
            "k": np.arange(64),
            "w": (np.arange(64) % 9 + 1).astype(np.float64)})
        pdim = os.path.join(d, "dim.parquet")
        dim.to_parquet(pdim, index=False)

        def join_df(paths):
            dim_agg = (session.read.parquet(pdim).groupBy("k")
                       .agg(F.max("w").alias("w")))
            return (session.read.parquet(*paths)
                    .join(dim_agg, "k").groupBy("k")
                    .agg(F.sum((F.col("v") * F.col("w")).alias("vw"))
                         .alias("s"),
                         F.count("v").alias("n"))
                    .orderBy("k"))

        # lone baseline: ONE standing query ticking its own stream
        lone0 = write("lone", 0)
        runner = session.incremental(join_df([lone0]), fact=lone0)
        runner.tick()
        lone_ms = []
        for i in range(n_ticks):
            p = write("lone", 1 + i)
            t0 = time.perf_counter()
            runner.tick([p])
            lone_ms.append((time.perf_counter() - t0) * 1e3)
        runner.close()  # retracts its epoch tier: the fleet phase
        lone_ms.sort()  # measures fleet-internal sharing only

        # fleet: N near-duplicate subscribers over one shared stream
        f0 = write("fact", 0)
        fleet = session.fleet()
        for i in range(n_subs):
            fleet.subscribe(join_df([f0]), name=f"q{i}", fact=f0)
        fleet.tick()
        round_ms, pulls, splices = [], 0, 0
        reads = I.inject("io.read", count=1, skip=1_000_000,
                         all_threads=True)
        for i in range(n_ticks):
            p = write("fact", 1 + i)
            t0 = time.perf_counter()
            fleet.tick([p])
            round_ms.append((time.perf_counter() - t0) * 1e3)
            pulls += int(fleet.last_round_info["sourcePulls"])
            splices += int(fleet.last_round_info["splices"])
        round_reads = 1_000_000 - reads.skip
        I.remove(reads)
        fleet.close()
        round_ms.sort()

        lone_p50 = nearest_rank(lone_ms, 0.50)
        round_p50 = nearest_rank(round_ms, 0.50)
        print(json.dumps({
            "metric": "fleet_round_vs_n_lone_ratio",
            "value": round(round_p50 / max(n_subs * lone_p50, 1e-9),
                           4),
            "unit": "ratio",
            "subscribers": n_subs,
            "ticks": n_ticks,
            "lone_steady_tick_ms": round(lone_p50, 3),
            "lone_p95_tick_ms": round(nearest_rank(lone_ms, 0.95), 3),
            "fleet_round_ms": round(round_p50, 3),
            "fleet_round_p95_ms": round(
                nearest_rank(round_ms, 0.95), 3),
            "fleet_round_per_sub_ms": round(round_p50 / n_subs, 3),
            # the WHY counters: 1 pull per new file for the whole
            # fleet, and committed tick work spliced across subs
            "source_pulls": pulls,
            "source_reads_steady_rounds": round_reads,
            "delta_files": n_ticks,
            "splices": splices,
            "distributed": mesh is not None,
            **span_frac_fields(session),
            **fused_wire_fields(session),
        }))
        sys.stdout.flush()
        session.stop()
    finally:
        shutil.rmtree(d, ignore_errors=True)


# ------------------------------------------------------------- fleet-hosts --
_FLEET_CHILD_SRC = """
import json, sys
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.api import functions as F

path, cache_dir = sys.argv[1], sys.argv[2]
s = TpuSession(conf={
    "spark.rapids.tpu.serving.resultCache.enabled": True,
    "spark.rapids.tpu.fleet.cache.dir": cache_dir,
})
df = (s.read.parquet(path).filter(F.col("v") >= 0.0)
      .group_by("k").agg(F.sum(F.col("v")).alias("sv"),
                         F.count(F.col("v")).alias("c")))
df.to_pandas()
print("CHILD " + json.dumps({
    "fleet_hits": s.result_cache.fleet_hits,
    "cross_hits": s.fleet_cache.stats()["cross_hits"]}), flush=True)
s.stop()
"""


def fleet_hosts_main(n_hosts: int) -> None:
    """--fleet-hosts N: multi-host fleet bench (ISSUE 18) on a
    logical-host partition of the local device mesh — the data axis
    classifies DCN, so host-staged exchange, the DCN deadline scale,
    and the membership layer all run exactly as they would across
    processes.  Emits ONE JSON line: per-host rows/s, the cross-host
    exchange wall (shuffle.exchange spans) and bytes moved vs the same
    query on the undivided ICI mesh, plus the fleet-scoped cache's
    cross-PROCESS hit counters (a real child process answering from
    this process's published result)."""
    import shutil
    import tempfile

    import numpy as np
    import pandas as pd

    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.parallel.shuffle import metrics_for_session
    from spark_rapids_tpu.tools.profiling import nearest_rank
    from spark_rapids_tpu.utils import tracing

    import jax
    ndev = jax.device_count()
    reps = int(os.environ.get("BENCH_FLEET_HOSTS_REPS", "5"))
    rows = 1 << 17
    d = tempfile.mkdtemp(prefix="tpu-fleet-hosts-bench-")
    rng = np.random.default_rng(29)
    path = os.path.join(d, "fact.parquet")
    pd.DataFrame({"k": rng.integers(0, 64, rows),
                  "v": rng.integers(0, 10_000, rows)
                  .astype(np.float64)}).to_parquet(path, index=False)

    def query(s):
        return (s.read.parquet(path).filter(F.col("v") >= 0.0)
                .group_by("k").agg(F.sum(F.col("v")).alias("sv"),
                                   F.count(F.col("v")).alias("c")))

    def drive(s):
        """Warm once, then reps timed runs: wall p50, the exchange
        span wall, and the exchange bytes actually moved."""
        q = query(s)
        q.to_pandas()
        m0 = metrics_for_session(s).snapshot()
        walls, ex_ms = [], 0.0
        for _ in range(reps):
            t0 = time.perf_counter()
            q.to_pandas()
            walls.append((time.perf_counter() - t0) * 1e3)
            sp = getattr(s, "last_span_stats", None) or {}
            ex_ms += (sp.get("phases") or {}).get("exchange", 0.0)
        m1 = metrics_for_session(s).snapshot()
        walls.sort()
        return {
            "wall_ms_p50": round(nearest_rank(walls, 0.50), 3),
            "exchange_wall_ms": round(ex_ms, 3),
            "bytes_moved": int(m1["bytesMoved"] - m0["bytesMoved"]),
            "exchanges": int(m1["exchanges"] - m0["exchanges"]),
        }

    try:
        base_conf = dict(trace_conf() or {})
        base_conf["spark.rapids.tpu.trace.enabled"] = True
        base_conf["spark.rapids.sql.distributed.numShards"] = str(ndev)

        # undivided mesh: every link ICI, the A/B baseline
        s_ici = TpuSession(dict(base_conf))
        ici = drive(s_ici)
        s_ici.stop()

        # logical-host fleet: data axis spans hosts -> DCN semantics
        cache_dir = os.path.join(d, "fcache")
        s_dcn = TpuSession(dict(base_conf, **{
            "spark.rapids.tpu.fleet.logicalHosts": str(n_hosts),
            "spark.rapids.tpu.fleet.membershipDir":
                os.path.join(d, "members"),
        }))
        fleet_live = s_dcn.fleet_membership is not None
        dcn = drive(s_dcn)
        s_dcn.stop()

        # fleet-scoped cache, cross-PROCESS: publish here, then a real
        # child process answers from the shared directory
        s_pub = TpuSession({
            "spark.rapids.tpu.serving.resultCache.enabled": True,
            "spark.rapids.tpu.fleet.cache.dir": cache_dir,
        })
        query(s_pub).to_pandas()
        stores = s_pub.result_cache.fleet_stores
        s_pub.stop()
        child = subprocess.run(
            [sys.executable, "-c", _FLEET_CHILD_SRC, path, cache_dir],
            capture_output=True, text=True, timeout=300)
        child_stats = {"fleet_hits": 0, "cross_hits": 0}
        for line in child.stdout.splitlines():
            if line.startswith("CHILD "):
                child_stats = json.loads(line[len("CHILD "):])
        tracing.configure(enabled=False)

        wall_s = sum([dcn["wall_ms_p50"]]) / 1e3
        rows_per_s = rows / max(wall_s, 1e-9)
        print(json.dumps({
            "metric": "fleet_hosts_rows_per_s_per_host",
            "value": round(rows_per_s / max(n_hosts, 1), 1),
            "unit": "rows/s/host",
            "hosts": n_hosts,
            "devices": ndev,
            "rows": rows,
            "reps": reps,
            "fleet_membership_live": fleet_live,
            "rows_per_s": round(rows_per_s, 1),
            "dcn": dcn,
            "ici": ici,
            "dcn_vs_ici_bytes": round(
                dcn["bytes_moved"] / max(ici["bytes_moved"], 1), 3),
            "dcn_vs_ici_exchange_wall": round(
                dcn["exchange_wall_ms"] /
                max(ici["exchange_wall_ms"], 1e-9), 3),
            "fleet_cache": {
                "stores": stores,
                "child_fleet_hits": child_stats["fleet_hits"],
                "cross_process_hits": child_stats["cross_hits"],
            },
        }))
        sys.stdout.flush()
    finally:
        shutil.rmtree(d, ignore_errors=True)


# --------------------------------------------------------------- fail-slow --
def fail_slow_main() -> None:
    """--fail-slow: gray-failure A/B (ISSUE 20) — one logical host
    turns fail-slow (sub-deadline delay rules wedge its host-staging
    shards; its gossiped walls stretch 10x) and the SAME workload runs
    with ``fleet.grayFailure.enabled`` off then on.  Off, every wedge
    rides the query wall; on, the SUSPECT host's shards hedge onto the
    healthy path.  Emits ONE JSON line: slowed-vs-healthy wall ratios
    for both arms, the hedge/duplicate counters, and the bit-identical
    gate (both arms must answer exactly the healthy run's result)."""
    import shutil
    import tempfile

    import numpy as np
    import pandas as pd

    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.robustness import inject as I
    from spark_rapids_tpu.tools.profiling import nearest_rank

    import jax
    ndev = jax.device_count()
    reps = int(os.environ.get("BENCH_FAIL_SLOW_REPS", "5"))
    delay_s = float(os.environ.get("BENCH_FAIL_SLOW_DELAY_S", "0.15"))
    rows = 1 << 15
    d = tempfile.mkdtemp(prefix="tpu-fail-slow-bench-")
    rng = np.random.default_rng(31)
    fact = pd.DataFrame({"k": rng.integers(0, 300, rows),
                         "v": rng.normal(size=rows)})
    dim = pd.DataFrame({"k": np.arange(300),
                        "w": rng.normal(size=300)})

    def session(gray: bool) -> TpuSession:
        return TpuSession({
            "spark.rapids.sql.distributed.numShards": str(ndev),
            "spark.rapids.tpu.fleet.logicalHosts": "2",
            "spark.rapids.tpu.fleet.membershipDir":
                os.path.join(d, "members-on" if gray else "members-off"),
            "spark.rapids.tpu.fleet.grayFailure.enabled": gray,
            "spark.rapids.tpu.fleet.suspectWindow": 8,
            "spark.rapids.tpu.fleet.hedgeFloorMs": 25,
            "spark.rapids.tpu.exchange.hostStaging.thresholdBytes": 1,
            "spark.rapids.sql.join.broadcastThresholdRows": 1,
            # the logical-host sim auto-picks the DCN gather strategy,
            # which never host-stages; pin the ICI collective so the
            # staging tier (the hedgeable path) carries the exchange
            "spark.rapids.tpu.shuffle.topology.strategy": "all_to_all",
            "spark.rapids.sql.recovery.backoffMs": 1,
        })

    def query(s):
        return (s.create_dataframe(fact)
                .join(s.create_dataframe(dim), on="k")
                .group_by("k")
                .agg(F.sum(F.col("v")).alias("sv"),
                     F.sum(F.col("w")).alias("sw")))

    def drive(s, slow: bool):
        """Warm once, then reps timed runs; ``slow`` arms ONE
        sub-deadline staging wedge per rep (the sick host's shard)."""
        q = query(s)
        q.to_pandas()
        walls = []
        for _ in range(reps):
            rule = I.inject("exchange.host_staging", kind="delay",
                            delay_s=delay_s, count=1) if slow else None
            t0 = time.perf_counter()
            out = q.to_pandas().sort_values("k", ignore_index=True)
            walls.append((time.perf_counter() - t0) * 1e3)
            if rule is not None:
                I.remove(rule)
        walls.sort()
        return round(nearest_rank(walls, 0.50), 3), out

    try:
        results = {}
        frames = {}
        for gray in (False, True):
            s = session(gray)
            t = s.gray_health
            if t is not None:
                # host 1's gossiped beat walls stretch 10x -> SUSPECT
                for _ in range(8):
                    t.observe_wall(0, "exchange.host_staging", 10.0)
                    t.observe_peer_walls(
                        1, {"exchange.host_staging": 100.0})
                t.poll()
            healthy_ms, frames["healthy"] = drive(s, slow=False)
            slowed_ms, frames["gray_on" if gray else "gray_off"] = \
                drive(s, slow=True)
            arm = {
                "healthy_wall_ms_p50": healthy_ms,
                "slowed_wall_ms_p50": slowed_ms,
                "slowdown": round(slowed_ms / max(healthy_ms, 1e-9), 3),
            }
            if t is not None:
                arm["counters"] = {
                    k: v for k, v in t.query_counters().items()
                    if k in ("hedgesFired", "hedgesWon",
                             "duplicatesSuppressed", "suspects")}
            results["gray_on" if gray else "gray_off"] = arm
            s.stop()
        bit_identical = all(
            frames[k].equals(frames["healthy"])
            for k in ("gray_off", "gray_on"))
        on, off = results["gray_on"], results["gray_off"]
        print(json.dumps({
            "metric": "fail_slow_hedge_wall_ratio",
            # hedged slowed-wall over unhedged slowed-wall: < 1.0 means
            # hedging bought the wedge back
            "value": round(on["slowed_wall_ms_p50"]
                           / max(off["slowed_wall_ms_p50"], 1e-9), 3),
            "unit": "x",
            "devices": ndev,
            "rows": rows,
            "reps": reps,
            "injected_delay_ms": round(delay_s * 1e3, 1),
            "bit_identical": bit_identical,
            "gray_off": off,
            "gray_on": on,
        }))
        sys.stdout.flush()
    finally:
        shutil.rmtree(d, ignore_errors=True)


# ------------------------------------------------------------------ repeat --
def repeat_main(n_repeats: int) -> None:
    """Warm-start bench (whole-stage fusion + persistent jit cache):
    TPC-H q6 + the q1 group-by shape through a session with
    ``spark.rapids.tpu.jitCache.dir`` set.  Phase 1 runs COLD (empty
    store: trace + compile + persist).  Phase 2 simulates a fresh
    process — the in-memory jit cache is cleared so every stage re-binds
    — and repeats the queries N times against the on-disk executables.
    Emits ONE JSON line: cold_compile_ms (cold minus warm — the
    trace/compile cost the persistent tier deletes on repeat runs), warm
    p50/p95, persistent hit/miss counters (misses in phase 2 mean the
    warm start bought nothing) and fused_stage_count.  Runs in-process
    on whatever platform jax resolves (set JAX_PLATFORMS=cpu for a
    CPU number)."""
    import shutil
    import tempfile

    from spark_rapids_tpu.config import rapids_conf as rc
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.exec.fusion import fusion_metrics
    from spark_rapids_tpu.ops import jit_cache
    from spark_rapids_tpu.tools.profiling import nearest_rank

    cache_dir = os.environ.get("BENCH_JITCACHE_DIR") or \
        tempfile.mkdtemp(prefix="tpu-jitcache-bench-")
    n_rows = 1 << 20
    try:
        session = TpuSession(trace_conf(
            {"spark.rapids.tpu.jitCache.dir": cache_dir}))
        df = session.create_dataframe(gen_host(n_rows))
        q6 = make_q6(session, df)
        q1 = make_q1(session, df)
        fm0 = fusion_metrics.snapshot()

        jit_cache.clear()
        t0 = time.perf_counter()
        q6()
        q1()
        cold_ms = (time.perf_counter() - t0) * 1e3
        p_cold = jit_cache.persistent_info()

        # "fresh process": drop every in-memory executable; phase 2 may
        # only reuse what phase 1 persisted to disk
        jit_cache.clear()
        jit_cache.configure_persistent(None)
        jit_cache.configure_persistent(
            cache_dir, session.conf.get(rc.JIT_CACHE_MAX_BYTES))
        warm = []
        for _ in range(max(n_repeats, 1)):
            t0 = time.perf_counter()
            q6()
            q1()
            warm.append((time.perf_counter() - t0) * 1e3)
        warm.sort()
        p_warm = jit_cache.persistent_info()
        fm1 = fusion_metrics.snapshot()
        warm_p50 = nearest_rank(warm, 0.50)
        print(json.dumps({
            "metric": "warm_repeat_ms",
            "value": round(warm_p50, 3),
            "unit": "ms",
            "repeats": len(warm),
            "rows": n_rows,
            "cold_ms": round(cold_ms, 3),
            "cold_compile_ms": round(max(cold_ms - warm_p50, 0.0), 3),
            "warm_p50_ms": round(warm_p50, 3),
            "warm_p95_ms": round(nearest_rank(warm, 0.95), 3),
            "jit_cache_persistent_hits": p_warm["hits"],
            "jit_cache_persistent_misses": p_warm["misses"],
            "jit_cache_persistent_stores": p_cold["stores"],
            "jit_cache_persistent_invalid": p_warm["invalid"],
            "fused_stage_count":
                fm1["fusedStages"] - fm0["fusedStages"],
            "fused_operator_count":
                fm1["fusedOperators"] - fm0["fusedOperators"],
            **span_frac_fields(session),
            **fused_wire_fields(session),
        }))
        sys.stdout.flush()
        session.stop()
    finally:
        if not os.environ.get("BENCH_JITCACHE_DIR"):
            shutil.rmtree(cache_dir, ignore_errors=True)


# ------------------------------------------------------------- concurrency --
def concurrency_main(n_clients: int, seconds: float = 10.0) -> None:
    """Serving-mode bench: N client threads hammer TPC-H q6 through one
    session's admission layer.  Emits ONE JSON line with aggregate
    rows/s, p50/p95 per-query latency, and admission wait — the
    metrics the multi-tenant ROADMAP item is judged on.  Runs
    in-process on whatever platform jax resolves (set JAX_PLATFORMS=cpu
    for a CPU number)."""
    import threading

    from spark_rapids_tpu.api.session import TpuSession
    session = TpuSession(trace_conf())
    n_rows = 1 << 20
    df = session.create_dataframe(gen_host(n_rows))
    query = make_q6(session, df)
    query()  # warm the jit cache outside the measured window
    latencies = []
    lock = threading.Lock()
    stop_at = time.monotonic() + seconds

    def client():
        local = []
        while time.monotonic() < stop_at:
            t0 = time.perf_counter()
            query()
            local.append(time.perf_counter() - t0)
        with lock:
            latencies.extend(local)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client)
               for _ in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    latencies.sort()
    from spark_rapids_tpu.tools.profiling import nearest_rank

    def pct(p):
        return nearest_rank(latencies, p) * 1e3

    adm = session.admission.snapshot() if session.admission else {}
    print(json.dumps({
        "metric": "concurrent_q6_rows_per_sec",
        "value": round(len(latencies) * n_rows / max(wall, 1e-9)),
        "unit": "rows/s",
        "concurrency": n_clients,
        "queries": len(latencies),
        "p50_latency_ms": round(pct(0.50), 3),
        "p95_latency_ms": round(pct(0.95), 3),
        "admission_wait_ms": adm.get("totalWaitMs", 0.0),
        "admission_peak_concurrent": adm.get("peakConcurrent", 0),
        "admission_rejected": adm.get("totalRejected", 0),
        **span_frac_fields(session),
        **fused_wire_fields(session),
    }))
    sys.stdout.flush()


# ----------------------------------------------------- template serving --
def template_qps_main(target_qps: int, seconds: float = 4.0) -> None:
    """Prepared-statement serving bench (plan templates): a q6-family
    stream whose filter literals are randomized per query, driven
    through prepared handles on N client threads.  Phase 1 holds the
    literals FIXED (the no-churn baseline); phase 2 randomizes them
    from a small pool every run.  Emits ONE JSON line with aggregate
    queries/s, p50/p95 per-phase latency (p95 flat across phases is
    the headline), and the pinned counters — retraces (in-memory jit
    misses), persistent-tier misses, and planning passes on repeats
    must all be ZERO after warmup, or the template tier bought
    nothing.  Template-tier hit ratio reflects pool reuse.  Runs
    in-process on whatever platform jax resolves (set JAX_PLATFORMS=cpu
    for a CPU number)."""
    import random
    import threading

    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.ops import jit_cache
    from spark_rapids_tpu.plan import overrides as _ov
    from spark_rapids_tpu.tools.profiling import nearest_rank

    n_threads = int(os.environ.get("BENCH_TEMPLATE_THREADS", "4"))
    n_rows = 1 << 16
    session = TpuSession(trace_conf({
        "spark.rapids.tpu.template.enabled": "true",
        "spark.rapids.tpu.serving.resultCache.enabled": "true",
        "spark.rapids.tpu.template.resultCache.enabled": "true",
    }))
    df = session.create_dataframe(gen_host(n_rows))
    base = (df.filter(
        (F.col("l_shipdate") >= F.lit(9131)) &
        (F.col("l_shipdate") < F.lit(9496)) &
        (F.col("l_discount") >= F.lit(0.05)) &
        (F.col("l_discount") <= F.lit(0.07)) &
        (F.col("l_quantity") < F.lit(24.0)))
        .select((F.col("l_extendedprice") * F.col("l_discount"))
                .alias("rev"))
        .agg(F.sum(F.col("rev")).alias("revenue")))
    # one handle per thread: ParamSlot bindings are per-handle mutable
    # state, and handles with identical plans share every jit entry
    handles = [session.prepare(base) for _ in range(n_threads)]
    # literal pool: ~32 distinct vectors => churn with some repeats,
    # so the template-tier hit ratio is meaningful
    rng = random.Random(42)
    pool = [(9131 + rng.randrange(0, 300), 9496 + rng.randrange(0, 300),
             round(0.02 + 0.01 * rng.randrange(0, 6), 2),
             float(rng.randrange(20, 40)))
            for _ in range(32)]
    for h in handles:  # warmup: trace + plan, outside every counter
        h.run_batches()
    jit0 = jit_cache.cache_info()
    pjit0 = jit_cache.persistent_info()
    plan0 = _ov.planning_passes()
    rc_cache = session.result_cache
    th0, tm0 = rc_cache.template_hits, rc_cache.template_misses

    def phase(churn: bool):
        lat, lock = [], threading.Lock()
        stop_at = time.monotonic() + seconds / 2.0

        def client(h):
            local = []
            while time.monotonic() < stop_at:
                if churn:
                    lo, hi, d, q = pool[rng.randrange(len(pool))]
                else:
                    lo, hi, d, q = pool[0]
                t0 = time.perf_counter()
                h.run_batches(lo, hi, d - 0.01, d + 0.01, q)
                local.append(time.perf_counter() - t0)
            with lock:
                lat.extend(local)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(h,))
                   for h in handles]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        lat.sort()
        return lat, wall

    fixed_lat, fixed_wall = phase(churn=False)
    churn_lat, churn_wall = phase(churn=True)
    jit1 = jit_cache.cache_info()
    pjit1 = jit_cache.persistent_info()
    plan1 = _ov.planning_passes()
    th1, tm1 = rc_cache.template_hits, rc_cache.template_misses
    queries = len(fixed_lat) + len(churn_lat)
    qps = queries / max(fixed_wall + churn_wall, 1e-9)
    hits, misses = th1 - th0, tm1 - tm0
    print(json.dumps({
        "metric": "template_qps",
        "value": round(qps, 1),
        "unit": "queries/s",
        "target_qps": target_qps,
        "threads": n_threads,
        "rows": n_rows,
        "queries": queries,
        "fixed_p50_ms": round(
            nearest_rank(fixed_lat, 0.50) * 1e3, 3),
        "fixed_p95_ms": round(
            nearest_rank(fixed_lat, 0.95) * 1e3, 3),
        "churn_p50_ms": round(
            nearest_rank(churn_lat, 0.50) * 1e3, 3),
        "churn_p95_ms": round(
            nearest_rank(churn_lat, 0.95) * 1e3, 3),
        "retraces": jit1["misses"] - jit0["misses"],
        "persistent_misses": pjit1["misses"] - pjit0["misses"],
        "planning_passes": plan1 - plan0,
        "template_hits": hits,
        "template_misses": misses,
        "template_hit_ratio": round(
            hits / max(hits + misses, 1), 4),
        "param_count": handles[0].param_count,
        "refusals": [r for r, _ in handles[0].refusals],
        **span_frac_fields(session),
        **fused_wire_fields(session),
    }))
    sys.stdout.flush()
    session.stop()


# ------------------------------------------------------- overlap workload --
def overlap_main(n_clients: int, seconds: float = 8.0) -> None:
    """Overlapping-workload serving bench (the ISSUE 13 acceptance
    gate): N client threads draw round-robin from a TPC-H q3/q6-family
    pool over SHARED parquet scans — the near-duplicate dashboard
    traffic shape.  Phase 1 measures the N-independent baseline (all
    reuse knobs off, FIFO occupancy); phase 2 re-runs the identical
    workload with the fair interleaver + result cache + shared stage
    cache on.  Emits ONE JSON line with aggregate queries/s + rows/s
    for both phases, the speedup, and the reuse counters
    (``result_cache_hits``, ``stage_splice_count``).  Both phases warm
    every pool entry once before their measured window so jit compile
    cost (process-global cache) cancels out.  Run with
    ``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``
    for a distributed CPU number (the stage cache needs a
    mesh; without one only the result cache engages)."""
    import shutil
    import tempfile
    import threading

    import numpy as np
    import pandas as pd

    import jax
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.session import TpuSession

    d = tempfile.mkdtemp(prefix="tpu-bench-overlap-")
    n_rows = 1 << 17
    nfiles = 4
    try:
        rng = np.random.default_rng(7)
        per = n_rows // nfiles
        files = []
        for i in range(nfiles):
            p = os.path.join(d, f"lineitem-{i}.parquet")
            pd.DataFrame({
                "l_extendedprice":
                    rng.uniform(1000.0, 100000.0, per),
                "l_discount": rng.uniform(0.0, 0.11, per).round(2),
                "l_quantity":
                    rng.integers(1, 51, per).astype(np.float64),
                "l_shipdate":
                    rng.integers(8766, 10957, per).astype(np.int32),
                "l_orderkey":
                    rng.integers(0, 512, per).astype(np.int64),
            }).to_parquet(p)
            files.append(p)

        def make_pool(session):
            lineitem = session.read.parquet(*files)

            def q6(lo, hi):  # q6 family: filter + grand aggregate
                return (lineitem
                        .filter((F.col("l_shipdate") >= lo) &
                                (F.col("l_shipdate") < hi) &
                                (F.col("l_discount") >= 0.05) &
                                (F.col("l_quantity") < 24))
                        .agg(F.sum((F.col("l_extendedprice") *
                                    F.col("l_discount"))
                                   .alias("r")).alias("revenue")))

            def q3_agg():  # q3 family: filter + grouped revenue
                return (lineitem
                        .filter(F.col("l_shipdate") > 9500)
                        .group_by("l_orderkey")
                        .agg(F.sum((F.col("l_extendedprice") *
                                    (F.lit(1.0) -
                                     F.col("l_discount")))
                                   .alias("r")).alias("revenue")))

            def q3_top():  # shares q3_agg's aggregate subtree
                return q3_agg().orderBy(
                    F.col("revenue").desc()).limit(10)

            return [lambda: q6(9000, 9500), lambda: q6(9500, 10000),
                    q3_agg, q3_top, lambda: q6(9000, 10000)]

        wire_acc: dict = {}

        def run_phase(conf_extra):
            mesh = None
            if jax.device_count() >= 2:
                from spark_rapids_tpu.parallel.mesh import make_mesh
                mesh = make_mesh(jax.device_count())
            session = TpuSession(trace_conf(conf_extra), mesh=mesh)
            pool = make_pool(session)
            for q in pool:  # warm compile outside the window
                q().collect()
            counts = []
            lock = threading.Lock()
            stop_at = time.monotonic() + seconds

            def client(ci):
                i, n = ci, 0
                while time.monotonic() < stop_at:
                    pool[i % len(pool)]().collect()
                    i += 1
                    n += 1
                with lock:
                    counts.append(n)

            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(n_clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            rc = session.result_cache.snapshot() \
                if session.result_cache else {}
            ss = session.shared_stages.snapshot() \
                if session.shared_stages else {}
            il = session.interleaver.snapshot() \
                if session.interleaver else {}
            for k, v in fused_wire_fields(session).items():
                wire_acc[k] = wire_acc.get(k, 0) + v
            session.stop()
            return sum(counts) / max(wall, 1e-9), rc, ss, il

        base_qps, _, _, _ = run_phase({})
        shared_qps, rc, ss, il = run_phase({
            "spark.rapids.tpu.serving.interleave.enabled": True,
            "spark.rapids.tpu.serving.resultCache.enabled": True,
            "spark.rapids.tpu.serving.sharedStage.enabled": True,
        })
        print(json.dumps({
            "metric": "overlap_concurrent_rows_per_sec",
            "value": round(shared_qps * n_rows),
            "unit": "rows/s",
            "concurrency": n_clients,
            "shared_queries_per_sec": round(shared_qps, 3),
            "baseline_queries_per_sec": round(base_qps, 3),
            "speedup_vs_independent": round(
                shared_qps / max(base_qps, 1e-9), 3),
            "result_cache_hits": rc.get("hits", 0),
            "result_cache_invalidations": rc.get("invalidations", 0),
            "stage_splice_count": ss.get("resumes", 0),
            "stage_cache_writes": ss.get("writes", 0),
            "interleave_timeslices": il.get("totalSlices", 0),
            "interleave_wait_ms": il.get("totalWaitMs", 0.0),
            **wire_acc,
            "distributed": bool(jax.device_count() >= 2),
        }))
        sys.stdout.flush()
    finally:
        shutil.rmtree(d, ignore_errors=True)


def zero_conf_main() -> None:
    """Zero-conf A/B (the ISSUE 15 acceptance gate): the distributed
    TPC-H sweep with EVERY tuned conf unset + the self-tuning cost
    model on, against the current hand-tuned settings.  Phase 1 runs
    the hand-tuned confs (async exchange, ragged slots, encoded
    execution/wire — the MULTICHIP dryrun set); phase 2 unsets them
    all and arms ``spark.rapids.tpu.costModel.enabled`` so the model
    decides per-site from evidence.  Both phases warm each query once
    (the model's evidence-fed second execution IS the converged plan)
    then measure; every zero-conf answer must match the hand-tuned
    one.  Emits ONE JSON line: per-query wall delta, aggregate walls,
    the zero-conf/hand-tuned ratio, and the decision/replan counts
    read from the decision ledger.  Env knobs:
    ``BENCH_ZERO_CONF_QUERIES`` (comma list, default the full sweep),
    ``BENCH_ZERO_CONF_SF`` (default 0.002)."""
    import pandas as pd

    import jax
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.models import tpch, tpch_sql
    from spark_rapids_tpu.parallel.mesh import make_mesh

    sf = float(os.environ.get("BENCH_ZERO_CONF_SF", "0.002"))
    sel_env = os.environ.get("BENCH_ZERO_CONF_QUERIES", "")
    sel = [q.strip() for q in sel_env.split(",") if q.strip()] or \
        sorted(tpch_sql.QUERIES, key=lambda s: int(s.lstrip("q")))
    mesh = make_mesh(jax.device_count()) \
        if jax.device_count() >= 2 else None
    data = tpch.gen_tables(sf=sf)
    wire_acc: dict = {}

    def run_phase(conf):
        session = TpuSession(trace_conf(conf), mesh=mesh)
        tpch_sql.register(session, tpch.load(session, data))
        walls, results = {}, {}
        decisions = replans = mispredicts = 0
        for q in sel:
            df = session.sql(tpch_sql.QUERIES[q])
            df.to_pandas()  # warm: compile + (phase 2) evidence
            t0 = time.perf_counter()
            results[q] = df.to_pandas()
            walls[q] = (time.perf_counter() - t0) * 1e3
            if mesh is not None:
                assert session.last_dist_explain == "distributed", \
                    (q, session.last_dist_explain)
            p = getattr(session, "last_planner_stats", None)
            if p:
                decisions += len(p.get("decisions", []))
                replans += p.get("replans", 0)
                mispredicts += p.get("mispredicts", 0)
        for k, v in fused_wire_fields(session).items():
            wire_acc[k] = wire_acc.get(k, 0) + v
        session.stop()
        return walls, results, decisions, replans, mispredicts

    tuned_conf = {
        "spark.rapids.tpu.exchange.async.enabled": True,
        "spark.rapids.tpu.shuffle.slot.ragged.enabled": True,
        "spark.rapids.tpu.encoding.execution.enabled": True,
        "spark.rapids.tpu.encoding.wire.enabled": True,
    }
    t_walls, t_res, _, _, _ = run_phase(tuned_conf)
    z_walls, z_res, dec, rep, mis = run_phase(
        {"spark.rapids.tpu.costModel.enabled": True})
    matched = 0
    for q in sel:
        pd.testing.assert_frame_equal(
            z_res[q].reset_index(drop=True),
            t_res[q].reset_index(drop=True), rtol=1e-9)
        matched += 1
    t_total = sum(t_walls.values())
    z_total = sum(z_walls.values())
    print(json.dumps({
        "metric": "zero_conf_vs_hand_tuned_wall_ratio",
        "value": round(z_total / max(t_total, 1e-9), 4),
        "unit": "ratio",
        "queries_matched": matched,
        "queries_total": len(sel),
        "hand_tuned_wall_ms": round(t_total, 1),
        "zero_conf_wall_ms": round(z_total, 1),
        "per_query_delta_ms": {
            q: round(z_walls[q] - t_walls[q], 2) for q in sel},
        "planner_decisions": dec,
        "planner_replans": rep,
        "planner_mispredicts": mis,
        **wire_acc,
        "distributed": mesh is not None,
    }))
    sys.stdout.flush()


def hash_agg_main(cards) -> None:
    """--hash-agg-cardinality N1,N2,...: hash-table group-by vs the
    current dispatch per key cardinality (ISSUE 19 acceptance axis).

    Keys are sampled SPARSELY from a 2^40 space so the coded
    directory refuses every cardinality (keyspace over the 2^21 cap)
    and the baseline is the sort/segment-sum kernel — exactly the
    path the hash table is meant to beat.  Per cardinality the table
    is sized to the next power of two >= 4*C (recorded in the
    emission) so the sweep measures the hash kernel, not its
    overflow fallback; the forced-overflow story lives in ci/chaos.sh.
    Every cardinality asserts bit-identical answers before timing
    counts.  Emits ONE JSON line with rows/s for both paths, the
    speedup per cardinality, and the measured crossover (largest
    swept cardinality where the hash path still wins; past it the
    sort/segment-sum baseline is faster on this backend).  Env knobs:
    ``BENCH_HASH_AGG_ROWS`` (default 262144), ``BENCH_HASH_AGG_REPS``
    (default 3)."""
    import numpy as np

    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.exec.fusion import fusion_metrics

    n_rows = int(os.environ.get("BENCH_HASH_AGG_ROWS", str(1 << 18)))
    reps = int(os.environ.get("BENCH_HASH_AGG_REPS", "3"))
    rng = np.random.default_rng(42)
    rows = []
    for c in cards:
        uni = np.unique(rng.integers(0, 1 << 40, 4 * c,
                                     dtype=np.int64))[:c]
        keys = uni[rng.integers(0, len(uni), n_rows)]
        # integer-valued floats: group sums are exact in float64, so
        # bit-identity never hinges on accumulation order
        vals = rng.integers(0, 1000, n_rows).astype(np.float64)
        slots = 1 << max(6, int(np.ceil(np.log2(2 * len(uni)))))

        def run(enabled):
            s = TpuSession({
                "spark.rapids.tpu.pallas.hash.enabled": enabled,
                "spark.rapids.tpu.pallas.hash.tableSlots": str(slots),
            })
            try:
                q = (s.create_dataframe({"k": keys, "v": vals})
                     .groupBy("k")
                     .agg(F.sum("v").alias("s"),
                          F.count("v").alias("n")))
                res = q.to_pandas()  # warm: compile + dispatch pick
                fm0 = fusion_metrics.snapshot()
                t0 = time.perf_counter()
                for _ in range(reps):
                    q.to_pandas()
                wall = time.perf_counter() - t0
                fm1 = fusion_metrics.snapshot()
            finally:
                s.stop()
            launches = fm1["hashKernelLaunches"] \
                - fm0["hashKernelLaunches"]
            res = res.sort_values("k").reset_index(drop=True)
            return res, reps * n_rows / max(wall, 1e-9), launches

        base_res, base_rps, base_hl = run("false")
        hash_res, hash_rps, hash_hl = run("true")
        assert base_hl == 0, ("hash launches with conf off", base_hl)
        assert hash_hl >= reps, \
            ("hash path never engaged", c, hash_hl)
        assert base_res.equals(hash_res), \
            ("hash vs baseline answers diverged", c)
        rows.append({"cardinality": c, "table_slots": slots,
                     "baseline_rows_per_sec": round(base_rps),
                     "hash_rows_per_sec": round(hash_rps),
                     "speedup": round(hash_rps / max(base_rps, 1e-9),
                                      3)})
        log(f"hash-agg: C={c} base={base_rps:,.0f} r/s "
            f"hash={hash_rps:,.0f} r/s "
            f"({rows[-1]['speedup']}x)")
    wins = [r["cardinality"] for r in rows if r["speedup"] > 1.0]
    print(json.dumps({
        "metric": "hash_agg_rows_per_sec",
        "value": max(r["hash_rows_per_sec"] for r in rows),
        "unit": "rows/s",
        "rows": n_rows,
        "reps": reps,
        "sweep": rows,
        "crossover_cardinality": max(wins) if wins else None,
        "bit_identical": True,
    }))
    sys.stdout.flush()


if __name__ == "__main__":
    if "--zero-conf" in sys.argv:
        zero_conf_main()
    elif "--concurrency" in sys.argv:
        idx = sys.argv.index("--concurrency")
        n = int(sys.argv[idx + 1]) if len(sys.argv) > idx + 1 else 4
        secs = float(os.environ.get("BENCH_CONCURRENCY_SECONDS", "10"))
        if "--overlap" in sys.argv:
            overlap_main(n, float(os.environ.get(
                "BENCH_OVERLAP_SECONDS", str(min(secs, 8.0)))))
        else:
            concurrency_main(n, secs)
    elif "--ingest-ticks" in sys.argv:
        idx = sys.argv.index("--ingest-ticks")
        n = int(sys.argv[idx + 1]) if len(sys.argv) > idx + 1 else 8
        ingest_main(n)
    elif "--fleet-hosts" in sys.argv:
        idx = sys.argv.index("--fleet-hosts")
        n = int(sys.argv[idx + 1]) if len(sys.argv) > idx + 1 else 2
        fleet_hosts_main(n)
    elif "--fail-slow" in sys.argv:
        fail_slow_main()
    elif "--fleet" in sys.argv:
        idx = sys.argv.index("--fleet")
        n = int(sys.argv[idx + 1]) if len(sys.argv) > idx + 1 else 8
        fleet_main(n)
    elif "--repeat" in sys.argv:
        idx = sys.argv.index("--repeat")
        n = int(sys.argv[idx + 1]) if len(sys.argv) > idx + 1 else 5
        repeat_main(n)
    elif "--template-qps" in sys.argv:
        idx = sys.argv.index("--template-qps")
        n = int(sys.argv[idx + 1]) if len(sys.argv) > idx + 1 else 1000
        template_qps_main(n, float(os.environ.get(
            "BENCH_TEMPLATE_SECONDS", "4")))
    elif "--hash-agg-cardinality" in sys.argv:
        idx = sys.argv.index("--hash-agg-cardinality")
        spec = sys.argv[idx + 1] if len(sys.argv) > idx + 1 \
            else "512,8192,65536"
        hash_agg_main([int(x) for x in spec.split(",") if x])
    else:
        sys.exit(main())
