"""Run cells several times and read the spreads: how a bound is set.

    python benchmark/tools/runs.py --out chiprun_out/q6 \
        --workload tpch_sf1.q6 --seconds 51 --seeds 11,12,13 --sets 2 [--trace 0]

Each run is a child process of its own (this parent never touches JAX,
so the chip is the child's); runs of a set get the seeds in order, and
every set uses the same seeds.  Result lines go to ``<out>.jsonl``, each
run's standard error to ``<out>.err``; the last lines printed are, per
metric, each set's median and spread (the distance between the first
and third quartile of ``statistics.quantiles(values, n=4)`` as a share
of the median), the way the builder's contract reads them.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("extra", nargs="*", help="passed on to run.py")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    sets = []
    with open(args.out + ".jsonl", "a") as out, \
            open(args.out + ".err", "a") as err:
        for k in range(args.sets):
            lines = []
            for seed in seeds:
                cmd = [sys.executable, os.path.join(BENCH, "run.py"),
                       "--workload", args.workload, "--seed", str(seed),
                       "--seconds", str(args.seconds),
                       "--trace", str(args.trace)] + args.extra
                t0 = time.time()
                p = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                   text=True)
                took = time.time() - t0
                err.write(f"==== set {k} seed {seed} rc {p.returncode} "
                          f"{took:.1f}s\n{p.stderr[-6000:]}\n")
                err.flush()
                last = (p.stdout.strip().splitlines() or [""])[-1]
                try:
                    line = json.loads(last)
                except ValueError:
                    line = {"correct": False, "malformed": last[-300:]}
                line.update(set=k, seed=seed, rc=p.returncode,
                            process_s=took, workload=args.workload,
                            trace=args.trace)
                out.write(json.dumps(line) + "\n")
                out.flush()
                lines.append(line)
                print(json.dumps({
                    "set": k, "seed": seed, "rc": p.returncode,
                    "process_s": round(took, 1),
                    "correct": line.get("correct"),
                    "attempted": line.get("attempted"),
                    "metrics": {n: m["value"] for n, m in
                                line.get("metrics", {}).items()},
                    "compared": line.get("compared")}), flush=True)
            sets.append(lines)
    names = sorted({n for lines in sets for l in lines
                    for n in l.get("metrics", {})})
    for n in names:
        per_set = [[l["metrics"][n]["value"] for l in lines
                    if n in l.get("metrics", {})] for lines in sets]
        print(json.dumps({
            "metric": n,
            "medians": [statistics.median(v) if v else None
                        for v in per_set],
            "spreads": [spread(v) for v in per_set]}), flush=True)
    bad = [l for lines in sets for l in lines
           if l.get("rc") != 0 or not l.get("correct")]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
