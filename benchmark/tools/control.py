"""The control of ``correct``: the reference in float32 put in the
program's place, judged like a run.  It has to come out not correct.

    python benchmark/tools/control.py --workload tpch_sf1.q6 --seeds 1,2,3 [--sf 0.01]

Plain pandas on the host (no JAX); at the cell's own size it is run in a
chip call so that the readings come from the machine the cells run on.
Prints one line per seed: the numbers compared beside their limits.
"""

import argparse
import json
import os
import sys

import numpy as np


def control_run(cell, seed, sf, dtype=np.float32):
    """(correct, numbers) of one answer per query of the mix, computed
    by the reference in ``dtype``, against the float64 reference."""
    from benchmark.harness import compare, window
    tables = cell.datagen().gen_tables(cell.tables, sf, seed)
    frames = compare.reference_frames(tables, cell.queries)
    ref = cell.reference()
    done, wants = [], {}
    for q, meta in cell.queries.items():
        wants[q] = ref.ANSWERS[meta["reference"]](frames)
        d = window.Done(q)
        d.answer = ref.ANSWERS[meta["reference"]](frames, dtype)
        done.append(d)
    return compare.judge(done, wants)


def main():
    from benchmark.harness import spec
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sf", type=float, default=None)
    args = ap.parse_args()
    cell = spec.Cell(args.workload)
    sf = args.sf if args.sf is not None else cell.config["scale"]["sf"]
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        correct, numbers = control_run(cell, seed, float(sf))
        passed += bool(correct)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control_correct": correct,
                          "compared": numbers}), flush=True)
    return 1 if passed else 0  # a control that passes is the failure


if __name__ == "__main__":
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
