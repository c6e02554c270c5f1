"""Chip smoke: TPC-H SF1 over parquet through ``session.sql`` on one v5e.

The quickest proof that the engine still starts on the chip.  One
process, JAX touched once, no children:

    python chip_smoke.py             # one chip: q6, q1, q3, q18 in one session
    python chip_smoke.py --chips 4   # four chips: q3 on a 4-device mesh, only

Data comes from ``models/tpch.py gen_tables(sf, seed)``, is written as
parquet (dates as ``date32``) and read back through the README's front
door: ``TpuSession()`` with default conf, ``read.parquet``,
``createOrReplaceTempView``, ``sql(...).to_pandas()``.  Every answer is
compared with plain pandas over the same frames and must be non-empty.
Any mismatch, any exception, any platform other than ``tpu`` (unless
``--allow-cpu``, the CPU rehearsal) is a non-zero exit.

Earlier output lines are notes (one JSON object each: set-up seconds,
cold and warm seconds per query, dispatches and host syncs, peak device
bytes).  They are smoke timings, not benchmark numbers.  The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

import argparse
import json
import os
import sys
import time

import numpy as np
import pandas as pd

HERE = os.path.dirname(os.path.abspath(__file__))
ONE_CHIP_QUERIES = ("q6", "q1", "q3", "q18")
# tests/test_tpch.py compares at the same bound.  The chip emulates f64 at
# about 48 bits; the largest relative error this script has seen there is
# printed per query as "max_rel_err" (see CHANGES.md, PR 26).
RTOL = 1e-9
# a parquet file holds at most this many rows, so SF1 lineitem is 4 files
ROWS_PER_FILE = 1_500_000


NOTES = []  # every printed line, for the chip_smoke.json report


def check(ok, what):
    """A smoke failure: raised, never an ``assert`` (``-O`` drops those)."""
    if not ok:
        raise RuntimeError(f"chip_smoke failed: {what}")


def note(**kv):
    NOTES.append(kv)
    print(json.dumps(kv), flush=True)


# ------------------------------------------------------------ pandas oracle --
# Plain pandas over the generated frames; nothing of spark_rapids_tpu.

def oracle_q6(t):
    l = t["lineitem"]
    m = l[(l.l_shipdate >= pd.Timestamp("1994-01-01"))
          & (l.l_shipdate < pd.Timestamp("1995-01-01"))
          & (l.l_discount >= 0.05) & (l.l_discount <= 0.07)
          & (l.l_quantity < 24)]
    return pd.DataFrame(
        {"revenue": [(m.l_extendedprice * m.l_discount).sum()]})


def oracle_q1(t):
    l = t["lineitem"]
    m = l[l.l_shipdate <= pd.Timestamp("1998-09-02")].copy()
    m["disc_price"] = m.l_extendedprice * (1 - m.l_discount)
    m["charge"] = m.disc_price * (1 + m.l_tax)
    g = m.groupby(["l_returnflag", "l_linestatus"])
    out = g.agg(sum_qty=("l_quantity", "sum"),
                sum_base_price=("l_extendedprice", "sum"),
                sum_disc_price=("disc_price", "sum"),
                sum_charge=("charge", "sum"),
                avg_qty=("l_quantity", "mean"),
                avg_price=("l_extendedprice", "mean"),
                avg_disc=("l_discount", "mean"),
                count_order=("l_quantity", "size")).reset_index()
    return out.sort_values(["l_returnflag", "l_linestatus"],
                           ignore_index=True)


def oracle_q3(t):
    c, o, l = t["customer"], t["orders"], t["lineitem"]
    cutoff = pd.Timestamp("1995-03-15")
    j = c[c.c_mktsegment == "BUILDING"][["c_custkey"]] \
        .merge(o[o.o_orderdate < cutoff], left_on="c_custkey",
               right_on="o_custkey") \
        .merge(l[l.l_shipdate > cutoff], left_on="o_orderkey",
               right_on="l_orderkey")
    j["revenue"] = j.l_extendedprice * (1 - j.l_discount)
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  as_index=False)["revenue"].sum()
    g = g.sort_values(["revenue", "o_orderdate"],
                      ascending=[False, True]).head(10)
    return g[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]] \
        .reset_index(drop=True)


def oracle_q18(t):
    c, o, l = t["customer"], t["orders"], t["lineitem"]
    per_order = l.groupby("l_orderkey")["l_quantity"].sum()
    big = per_order[per_order > 300].index
    j = c[["c_custkey", "c_name"]] \
        .merge(o[o.o_orderkey.isin(big)], left_on="c_custkey",
               right_on="o_custkey") \
        .merge(l[["l_orderkey", "l_quantity"]], left_on="o_orderkey",
               right_on="l_orderkey")
    g = j.groupby(["c_name", "o_custkey", "o_orderkey", "o_orderdate",
                   "o_totalprice"], as_index=False) \
        .agg(sum_qty=("l_quantity", "sum"))
    return g.sort_values(["o_totalprice", "o_orderdate"],
                         ascending=[False, True]).head(100) \
        .reset_index(drop=True)


ORACLES = {"q6": oracle_q6, "q1": oracle_q1, "q3": oracle_q3,
           "q18": oracle_q18}


def compare(q, got, want):
    """``got`` equals ``want`` row for row (every query here is ordered
    or has one row); floats within RTOL.  Returns the largest relative
    float error seen."""
    check(len(want) > 0, f"{q}: the oracle's answer is empty")
    check(len(got) > 0, f"{q}: the engine's answer is empty")
    check(list(got.columns) == list(want.columns),
          (q, list(got.columns), list(want.columns)))
    check(len(got) == len(want), (q, len(got), len(want)))
    worst = 0.0
    for name in want.columns:
        w, g = want[name], got[name]
        if pd.api.types.is_float_dtype(w.dtype):
            w = w.to_numpy(np.float64)
            g = g.to_numpy(np.float64)
            check(np.isfinite(g).all(), (q, name, "not finite"))
            err = float(np.max(np.abs(g - w) / np.abs(w)))
            worst = max(worst, err)
            check(err <= RTOL,
                  f"{q}.{name}: relative error {err!r} over rtol {RTOL}")
        elif pd.api.types.is_datetime64_any_dtype(w.dtype):
            # the engine hands DATE back as python dates
            g = pd.to_datetime(g).dt.tz_localize(None)
            check(g.tolist() == w.tolist(), (q, name))
        else:
            check(g.tolist() == w.tolist(), (q, name))
    return worst


# ------------------------------------------------------------------ set-up --

def write_parquet(tables, out_dir):
    """One directory per table; TPC-H dates are DATE, so the arrow
    table's timestamp columns (pandas datetime64) are cast to date32."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    dirs = {}
    for name, df in tables.items():
        tbl = pa.Table.from_pandas(df, preserve_index=False)
        tbl = tbl.cast(pa.schema(
            [pa.field(f.name, pa.date32())
             if pa.types.is_timestamp(f.type) else f
             for f in tbl.schema]))
        d = os.path.join(out_dir, name)
        os.makedirs(d, exist_ok=True)
        for stale in os.listdir(d):
            os.remove(os.path.join(d, stale))
        for i, at in enumerate(range(0, len(df), ROWS_PER_FILE)):
            pq.write_table(tbl.slice(at, ROWS_PER_FILE),
                           os.path.join(d, f"part-{i:03d}.parquet"))
        dirs[name] = d
    return dirs


def open_views(session, dirs):
    for name, d in dirs.items():
        session.read.parquet(d).createOrReplaceTempView(name)


def check_scan_on_device(session, dirs, platform):
    """A scanned batch must live on the accelerator, not on the host."""
    import jax
    batch = next(iter(
        session.read.parquet(dirs["lineitem"]).to_device_batches()))
    for name, col in batch.columns.items():
        for arr in (col.data, col.validity):
            if arr is None:
                continue
            check(isinstance(arr, jax.Array), (name, type(arr)))
            where = {d.platform for d in arr.devices()}
            check(where == {platform},
                  f"scanned column {name} is on {where}, not {platform}")
    note(phase="scan", rows=int(batch.nrows), columns=len(batch.columns),
         on=platform)


def check_histogram_kernel():
    """The exchange's partition histogram (the Pallas kernel on a TPU)
    against the one-hot XLA formulation, on the device."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import pallas_kernels as pk
    rng = np.random.default_rng(0)
    n, parts = 1 << 20, 8
    pids = jnp.asarray(rng.integers(0, parts, n).astype(np.int32))
    mask = jnp.asarray(rng.random(n) < 0.8)
    got = np.asarray(pk.histogram(pids, mask, parts))
    want = np.asarray(pk.partition_histogram_xla(pids, mask, parts))
    check((got == want).all(), (got, want))
    note(phase="histogram", pallas=bool(pk.use_pallas()),
         rows=int(got.sum()))


def run_query(session, q, tables, runs):
    """Cold run, then warm runs; each compared with the oracle."""
    from spark_rapids_tpu.models import tpch_sql
    from spark_rapids_tpu.ops import jit_cache
    from spark_rapids_tpu.utils.hostsync import host_sync_metrics
    want = ORACLES[q](tables)
    line = {"phase": "query", "query": q, "rows_out": len(want)}
    for run in range(runs):
        d0 = jit_cache.dispatch_count()
        s0 = host_sync_metrics.snapshot()
        t0 = time.monotonic()
        got = session.sql(tpch_sql.QUERIES[q]).to_pandas()
        secs = time.monotonic() - t0
        err = compare(q, got, want)
        tag = "cold" if run == 0 else "warm"
        line[f"{tag}_seconds"] = secs
        line[f"{tag}_dispatches"] = jit_cache.dispatch_count() - d0
        line[f"{tag}_host_syncs"] = host_sync_metrics.snapshot() - s0
        line["max_rel_err"] = max(err, line.get("max_rel_err", 0.0))
    return line


def peak_bytes(devices):
    stats = [d.memory_stats() or {} for d in devices]
    return [s.get("peak_bytes_in_use") for s in stats]


# -------------------------------------------------------------------- main --

def one_chip(tables, dirs, platform):
    import jax
    from spark_rapids_tpu.api.session import TpuSession
    session = TpuSession()
    open_views(session, dirs)
    check_scan_on_device(session, dirs, platform)
    check_histogram_kernel()
    for q in ONE_CHIP_QUERIES:
        line = run_query(session, q, tables, runs=2)
        line["peak_device_bytes"] = peak_bytes(jax.devices()[:1])[0]
        note(**line)


def four_chips(chips, tables, dirs):
    """q3 through the distributed planner on a 4-device mesh, and only
    that: the one-chip phases are the default run's."""
    import jax
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.parallel.mesh import make_mesh
    from spark_rapids_tpu.parallel.shuffle import metrics_for_session
    session = TpuSession(mesh=make_mesh(chips))
    open_views(session, dirs)
    # rows each device holds after the first exchange, from that
    # exchange's own [src, dst] histogram
    metrics = metrics_for_session(session)
    first_exchange = []
    record = metrics.record_exchange

    def spy(*a, **kw):
        if not first_exchange and kw.get("per_dest") is not None:
            first_exchange.append(
                {d: int(useful) for d, (_, useful)
                 in kw["per_dest"].items()})
        return record(*a, **kw)

    metrics.record_exchange = spy
    line = run_query(session, "q3", tables, runs=2)
    check(session.last_dist_explain == "distributed",
          f"q3 did not run distributed: {session.last_dist_explain}")
    check(first_exchange, "no exchange reported its histogram")
    held = first_exchange[0]
    line["rows_after_first_exchange"] = held
    line["exchanges"] = metrics.snapshot()["exchanges"]
    line["peak_device_bytes"] = peak_bytes(jax.devices()[:chips])
    note(**line)
    check(len(held) == chips and all(held.values()),
          f"a device holds no rows after the first exchange: {held}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"))
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on the CPU backend (never a chip result)")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.allow_cpu:
        print(f"chip_smoke: platform is {platform!r}, not 'tpu' "
              "(--allow-cpu rehearses on the CPU)", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX reports "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from spark_rapids_tpu import native
    from spark_rapids_tpu.models import tpch
    from spark_rapids_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    t0 = time.monotonic()
    tables = tpch.gen_tables(sf=args.sf, seed=args.seed)
    t1 = time.monotonic()
    dirs = write_parquet(tables, args.out)
    t2 = time.monotonic()
    note(phase="setup", sf=args.sf, seed=args.seed,
         generate_seconds=t1 - t0, write_seconds=t2 - t1,
         rows_in={n: len(df) for n, df in tables.items()},
         native=bool(native.available()), compile_cache=cache_dir)

    if args.chips == 1:
        one_chip(tables, dirs, platform)
    else:
        four_chips(args.chips, tables, dirs)

    note(ok=True, device={"platform": platform,
                          "kind": devices[0].device_kind,
                          "count": len(devices)})
    with open(os.path.join(args.out, "chip_smoke.json"), "w") as f:
        json.dump(NOTES, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
