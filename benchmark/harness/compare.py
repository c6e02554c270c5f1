"""What decides ``correct``: every answer of the window against the
plain reference, each number beside its limit.

Numbers compared (limits in ``LIMITS``; how each was set is in PERF.md):

* ``rel_err_max``  the largest relative error of any float cell of any
  answer against the float64 pandas reference.  The float32 control
  reads far above the limit, the program far below.
* ``exact_wrong``  cells of the other columns (keys, dates, counts,
  strings) that differ, row for row in the query's order: limit 0.
* ``shape_wrong``  answers whose columns or row count differ, or that
  are empty: limit 0.
* ``missing``      queries of the window that raised: limit 0.
* ``off_path``     answers that the session's own records say did not
  come from the timed path: limit 0.
"""

import numpy as np
import pandas as pd

LIMITS = {"rel_err_max": 1e-10, "exact_wrong": 0, "shape_wrong": 0,
          "missing": 0, "off_path": 0}


def reference_frames(tables, queries):
    """The columns the queries name, as pandas (dates as datetime64)."""
    need = {}
    for meta in queries.values():
        for t, cols in meta["tables"].items():
            need.setdefault(t, [])
            need[t] += [c for c in cols if c not in need[t]]
    return {t: tables[t].select(cols).to_pandas(date_as_object=False)
            for t, cols in need.items()}


def compare_answer(got, want):
    """(largest relative float error, exact cells wrong, shape wrong)."""
    if (got is None or len(want) == 0 or len(got) != len(want)
            or list(got.columns) != list(want.columns)):
        return 0.0, 0, 1
    worst, wrong = 0.0, 0
    for name in want.columns:
        w, g = want[name], got[name]
        if pd.api.types.is_float_dtype(w.dtype):
            w = w.to_numpy(np.float64)
            g = g.to_numpy(np.float64)
            if not np.isfinite(g).all():
                return 0.0, 0, 1
            scale = np.where(w == 0, 1.0, np.abs(w))
            worst = max(worst, float(np.max(np.abs(g - w) / scale)))
        elif pd.api.types.is_datetime64_any_dtype(w.dtype):
            # the engine hands DATE back as python dates
            g = pd.to_datetime(g).dt.tz_localize(None)
            w = pd.to_datetime(w).dt.tz_localize(None)
            wrong += int((g.to_numpy("datetime64[D]")
                          != w.to_numpy("datetime64[D]")).sum())
        else:
            wrong += sum(a != b for a, b in zip(g.tolist(), w.tolist()))
    return worst, wrong, 0


def judge(done, wants):
    """``done``: the window's queries; ``wants``: {query: reference
    answer}.  Returns (correct, {name: [number, limit]})."""
    read = {k: 0 for k in LIMITS}
    read["rel_err_max"] = 0.0
    compared = 0
    for d in done:
        if d.error is not None:
            read["missing"] += 1
            continue
        if d.off_path:
            read["off_path"] += 1
        err, wrong, shape = compare_answer(d.answer, wants[d.query])
        read["rel_err_max"] = max(read["rel_err_max"], err)
        read["exact_wrong"] += wrong
        read["shape_wrong"] += shape
        compared += 1
    numbers = {k: [read[k], LIMITS[k]] for k in LIMITS}
    numbers["answers_compared"] = [compared, len(done)]
    correct = compared > 0 and compared == len(done) and all(
        read[k] <= LIMITS[k] for k in LIMITS)
    return correct, numbers
