"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, JAX touched once.  Set-up (all of it ``setup_s``): the
configuration's tables from ``--seed``, parquet in the checkout, the
session the configuration's file describes, the views, each query of the
mix once.  Then a closed loop over ``TpuSession.sql(text).to_pandas()``
for ``--seconds``; then every answer of the window against the plain
pandas reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (end-to-end with
``--trace 0``, per-layer with ``--trace 1``), ``device``, ``breakdown``
(traced run) and ``compared`` (each number compared beside its limit).
Any platform but ``tpu``, or fewer chips than the cell asks for, is a
non-zero exit before any work (``--allow-cpu`` rehearses; a rehearsal
prints no device metric).  See ``benchmark/README.md``.
"""

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def note(**kv):
    """A line of notes on standard error; the result is the only thing
    this program writes to standard output."""
    print(json.dumps(kv, default=str), file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse off the chip (never a device metric)")
    ap.add_argument("--sf", type=float, default=None,
                    help="rehearsal only: a scale other than the "
                         "configuration's")
    return ap.parse_args(argv)


def percentile(values, q):
    """The ``q``-th percentile by the nearest-rank rule (no value is
    made up between two samples)."""
    ordered = sorted(values)
    rank = max(int(-(-q * len(ordered) // 100)), 1)
    return ordered[rank - 1]


def open_session(config, trace):
    from spark_rapids_tpu.api.session import TpuSession
    conf = dict(config["session"].get("conf") or {})
    if trace:
        # the only conf the benchmark ever sets, and only in a traced run
        conf["spark.rapids.tpu.trace.enabled"] = True
        conf["spark.rapids.tpu.profile.trace"] = True
    mesh = None
    if config["session"].get("mesh_devices"):
        from spark_rapids_tpu.parallel.mesh import make_mesh
        mesh = make_mesh(int(config["session"]["mesh_devices"]))
    return TpuSession(conf or None, mesh=mesh)


def main(argv=None):
    args = parse(sys.argv[1:] if argv is None else list(argv))
    if not os.path.isdir(os.path.join(ROOT, "spark_rapids_tpu")):
        print("benchmark: no spark_rapids_tpu beside benchmark/: nothing "
              "to measure", file=sys.stderr)
        return 2
    from benchmark.harness import compare, data, observe, spec, window
    cell = spec.Cell(args.workload)
    if args.sf is not None and not args.allow_cpu:
        print("benchmark: --sf is for rehearsals (--allow-cpu) only",
              file=sys.stderr)
        return 2
    if args.allow_cpu and cell.chips > 1:
        flag = f"--xla_force_host_platform_device_count={cell.chips}"
        if flag not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " " + flag).strip()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.allow_cpu:
        print(f"benchmark: platform is {platform!r}, not 'tpu'",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"benchmark: cell {cell.name} needs {cell.chips} chip(s), "
              f"JAX reports {len(devices)}", file=sys.stderr)
        return 1
    on_chip = platform == "tpu"
    used = devices[:cell.chips]
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = data.cache_dir("jax")
        jax.config.update("jax_compilation_cache_dir", cache)
    # every program of the cell goes to the cache, the sub-second ones
    # too: a run after the checkout's first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cached0 = len(os.listdir(cache)) if os.path.isdir(cache) else 0

    # ------------------------------------------------------------ set-up --
    sf = float(args.sf if args.sf is not None else cell.config["scale"]["sf"])
    gen = cell.datagen()
    t0 = time.perf_counter()
    tables = gen.gen_tables(cell.tables, sf, args.seed)
    t1 = time.perf_counter()
    dirs, wrote = data.write_parquet(tables, cell.config, args.seed, sf)
    data.prune(cell.config["name"] + "-")
    t2 = time.perf_counter()
    session = open_session(cell.config, args.trace)
    for name, d in dirs.items():
        session.read.parquet(d).createOrReplaceTempView(name)
    obs = observe.Observations(cell, session)
    # the process's first query of each of the mix, then the mix's loop
    # until ``warmup_queries`` have run: the window starts steady
    first, order = {}, window.schedule(cell.mix)
    warmup = max(int(cell.mix.get("warmup_queries", 0)),
                 len(cell.mix["queries"]))
    t_warm = time.perf_counter()
    for _ in range(warmup):
        d = window.one_query(session, cell, next(order),
                             window.NoAnnotation)
        if d.error is not None:
            print(d.error, file=sys.stderr)
            print(f"benchmark: warm-up of {d.query} failed", file=sys.stderr)
            return 3
        first.setdefault(d.query, d.seconds)
    warmup_s = time.perf_counter() - t_warm
    obs.clock["first_query_s"] = sum(first.values())
    readers = observe.Readers(cell) if args.trace else None
    tracer = None
    if args.trace:
        tracer = observe.Tracer(cell.name, cell.mix.get("traced_queries", 1))
        readers.begin(obs)
    from spark_rapids_tpu.ops import jit_cache
    misses0 = jit_cache.cache_info()["misses"]
    setup_s = time.perf_counter() - T_START
    rows = {n: t.num_rows for n, t in tables.items()}
    note(phase="setup", setup_s=setup_s, generate_s=t1 - t0,
         parquet_s=t2 - t1, tables_written=wrote, first_query_s=first,
         warmup_queries=warmup, warmup_s=warmup_s, sf=sf, rows=rows,
         compile_cache=cache, platform=platform,
         programs_cached=[cached0, len(os.listdir(cache))])

    # ------------------------------------------------------------ window --
    done, w0, w1 = window.run_window(session, cell, args.seconds, tracer)
    window_s = w1 - w0
    obs.done, obs.n_queries = done, len(done)
    obs.memory_stats = [d.memory_stats() or {} for d in used]
    peaks = [s.get("peak_bytes_in_use") for s in obs.memory_stats]
    note(phase="window", window_s=window_s, queries=len(done),
         jit_cache_misses_in_window=jit_cache.cache_info()["misses"] - misses0,
         peak_bytes=peaks,
         query_seconds=[round(d.seconds, 6) for d in done])
    if not done:
        print("benchmark: the window completed no query", file=sys.stderr)
        return 3

    # ----------------------------------------------- per-layer (traced) --
    metrics, breakdown = {}, None
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": max([p for p in peaks if p] or [0])}
    if args.trace:
        from benchmark.trace import reduce as trace_reduce
        obs.clock["sql_s"] = sum(d.t_sql - d.t0 for d in done
                                 if d.error is None)
        if on_chip:
            obs.traced = done[:tracer.covered]
            path = trace_reduce.find_xplane(tracer.dir)
            obs.trace = trace_reduce.reduce_trace(path) if path else None
            if obs.trace is None:
                print("benchmark: the traced run left no device trace",
                      file=sys.stderr)
                return 3
            t = obs.trace
            device["busy_s"], device["window_s"] = t["busy_s"], t["window_s"]
            breakdown = {"device_ops": t["device_ops"],
                         "idle_gaps": t["idle_gaps"]}
            note(phase="trace", file=path, bytes=os.path.getsize(path),
                 traced_queries=len(obs.traced), marks=t["marks"],
                 per_device=t["per_device"], top_ops=t["top_ops"])
        # a rehearsal has no trace and no memory statistics, so the
        # readers of device metrics find nothing and leave them out
        metrics = readers.read(obs)
        note(phase="per_layer", notes=obs.notes)
    else:
        times = [d.seconds for d in done]
        values = {"query_s": window_s / len(done),
                  "query_p90_s": percentile(times, 90),
                  "query_p80_s": percentile(times, 80),
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    # -------------------------------------- correct: after the window --
    t0 = time.perf_counter()
    frames = compare.reference_frames(tables, cell.queries)
    ref = cell.reference()
    wants = {q: ref.ANSWERS[meta["reference"]](frames)
             for q, meta in cell.queries.items()}
    correct, numbers = compare.judge(done, wants)
    note(phase="reference", seconds=time.perf_counter() - t0)
    for d in done:
        if d.error is not None:
            print(d.error, file=sys.stderr)
            break
    failed = sum(1 for d in done if d.error is not None or d.off_path)
    session.stop()
    result = {"correct": bool(correct), "attempted": len(done),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = numbers
    for name, (number, limit) in numbers.items():
        print(f"compared {name}: {number!r} limit {limit!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # the checkout's root in place of this directory: ``benchmark.*`` and
    # ``spark_rapids_tpu`` import from there, and nothing here shadows a
    # module of the standard library
    sys.path[0] = ROOT
    sys.exit(main())
