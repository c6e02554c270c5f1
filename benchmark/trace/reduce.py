"""From a profiler trace (``.xplane.pb``) to device numbers.

``reduce_trace(path)`` reads the file with ``jax.profiler.ProfileData``
and nothing else, and returns, for the window between the first and the
last ``bench.query`` annotation the harness wrote:

* per device: busy seconds (the union of the intervals in which an
  operation ran, from the ``XLA Ops`` line, or ``XLA Modules`` where a
  plane has no ops line), summed program seconds by module name,
  collective seconds (the union of the collective ops' intervals, the
  ``Async XLA Ops`` line's start-to-done spans with them);
* ``busy_s`` (mean over devices), ``busy_s_fullest``, ``idle_share``
  (1 - busy over the window, fullest device), ``window_s``;
* ``device_ops``: the programs that took most device time;
* ``idle_gaps``: the fullest device's idle seconds by what the host was
  doing (the innermost host annotation over each gap's midpoint).

A trace with no device plane (a CPU rehearsal) reduces to None.
"""

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"   # a collective may run as start ... done
MODULES_LINE = "XLA Modules"
WINDOW_MARK = "bench.query"
COLLECTIVE = re.compile(
    r"all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute"
    r"|collective-broadcast|ragged-all-to-all", re.I)
# host annotations worth naming a gap after: the harness's own, and what
# the program's profile.trace writes (an operator's class name, Tpu...Exec)
HOST_NAMES = re.compile(r"^(bench\.[\w.]+|Tpu\w*Exec)$")
TOP = 10


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _by_start(starts, ends):
    """The intervals sorted by start, each end raised to the latest end
    of any interval before it."""
    order = np.argsort(starts, kind="stable")
    s = np.asarray(starts, dtype=np.float64)[order]
    e = np.maximum.accumulate(np.asarray(ends, dtype=np.float64)[order])
    return s, e


def union_seconds(starts, ends):
    """Total length of the union of [start, end) intervals, in the
    intervals' unit."""
    if len(starts) == 0:
        return 0.0
    s, e = _by_start(starts, ends)
    # an interval opens a new run where it starts after all before ended
    opens = np.concatenate(([True], s[1:] > e[:-1]))
    run_start = s[opens]
    run_end = np.concatenate((e[:-1][opens[1:]], e[-1:]))
    return float(np.sum(run_end - run_start))


def gaps(starts, ends, lo, hi):
    """The complement of the union of intervals within [lo, hi], as two
    arrays (gap starts, gap ends)."""
    if len(starts) == 0:
        return np.array([lo], dtype=np.float64), np.array([hi],
                                                          dtype=np.float64)
    s, e = _by_start(starts, ends)
    g0 = np.concatenate(([lo], e))
    g1 = np.concatenate((s, [hi]))
    keep = g1 > g0
    return g0[keep], g1[keep]


def _clip(starts, ends, lo, hi):
    s = np.clip(np.asarray(starts, dtype=np.float64), lo, hi)
    e = np.clip(np.asarray(ends, dtype=np.float64), lo, hi)
    keep = e > s
    return s[keep], e[keep]


def _events(line):
    names, starts, ends = [], [], []
    for ev in line.events:
        names.append(ev.name)
        starts.append(ev.start_ns)
        ends.append(ev.start_ns + ev.duration_ns)
    return names, np.asarray(starts, np.float64), np.asarray(ends, np.float64)


def _short(name):
    """A module event is named ``jit_join_match(1234567890)``: the
    program's name without the run's fingerprint."""
    return re.sub(r"\(\d+\)$", "", name)


def _host_annotations(data):
    """(name, start, end) of every host event the harness or the program
    annotated; the profiler's own python and runtime events are left."""
    out = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if HOST_NAMES.match(name):
                    out.append((name, float(ev.start_ns),
                                float(ev.start_ns + ev.duration_ns)))
    return out


def _sum_by_name(names, starts, ends):
    out = {}
    for n, s, e in zip(names, starts, ends):
        out[n] = out.get(n, 0.0) + (e - s)
    return out


def _top(by_name, scale=1e-9):
    return [[n, v * scale] for n, v in
            sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]]


def _label_gaps(g0, g1, annotations):
    """Idle nanoseconds by the innermost annotation over each gap's
    midpoint; gaps under no annotation fall to ``host.unannotated``."""
    mid = (g0 + g1) / 2
    length = g1 - g0
    label = np.full(len(mid), -1, dtype=np.int64)
    best = np.full(len(mid), np.inf)
    index = {}
    for name, s, e in annotations:
        if name == WINDOW_MARK:
            continue
        hit = (mid >= s) & (mid < e) & ((e - s) < best)
        label[hit] = index.setdefault(name, len(index))
        best[hit] = e - s
    out = {}
    for name, i in index.items():
        total = float(length[label == i].sum())
        if total > 0:
            out[name] = total
    rest = float(length[label == -1].sum())
    if rest > 0:
        out["host.unannotated"] = rest
    return out


def reduce_trace(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    annotations = _host_annotations(data)
    marks = [(s, e) for n, s, e in annotations if n == WINDOW_MARK]
    devices = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {line.name: line for line in plane.lines}
        devices[int(m.group(1))] = lines
    if not devices:
        return None
    if marks:
        lo = min(s for s, _ in marks)
        hi = max(e for _, e in marks)
    else:  # no mark: the span of everything the devices ran
        every = [_events(l)[1:] for lines in devices.values()
                 for l in lines.values() if l.name in (OPS_LINE, MODULES_LINE)]
        lo = min(s.min() for s, _ in every if len(s))
        hi = max(e.max() for _, e in every if len(e))
    per_device = {}
    for idx, lines in sorted(devices.items()):
        busy_line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        if busy_line is None:
            continue
        names, s, e = _events(busy_line)
        keep = (e > lo) & (s < hi)
        cs, ce = _clip(s, e, lo, hi)
        coll = np.array([bool(COLLECTIVE.search(n)) for n in names],
                        dtype=bool) & keep
        ks, ke = _clip(s[coll], e[coll], lo, hi)
        if ASYNC_LINE in lines:
            an, as_, ae = _events(lines[ASYNC_LINE])
            acoll = np.array([bool(COLLECTIVE.search(n)) for n in an],
                             dtype=bool)
            a0, a1 = _clip(as_[acoll], ae[acoll], lo, hi)
            ks, ke = np.concatenate((ks, a0)), np.concatenate((ke, a1))
        programs = {}
        if MODULES_LINE in lines:
            mn, ms, me = _events(lines[MODULES_LINE])
            mkeep = (me > lo) & (ms < hi)
            ps, pe = np.clip(ms[mkeep], lo, hi), np.clip(me[mkeep], lo, hi)
            programs = _sum_by_name(
                [_short(n) for n, k in zip(mn, mkeep) if k], ps, pe)
        per_device[idx] = {
            "busy_ns": union_seconds(cs, ce),
            "collective_ns": union_seconds(ks, ke),
            "ops": int(keep.sum()),
            "busy_line": busy_line.name,
            "programs": programs,
            "op_seconds": _sum_by_name(
                [n for n, k in zip(names, keep) if k], cs, ce)
            if busy_line.name == OPS_LINE else {},
            "intervals": (cs, ce),
        }
    if not per_device:
        return None
    window_ns = hi - lo
    fullest = max(per_device, key=lambda i: per_device[i]["busy_ns"])
    full = per_device[fullest]
    g0, g1 = gaps(*full["intervals"], lo, hi)
    busy = [d["busy_ns"] for d in per_device.values()]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": float(np.mean(busy)) * 1e-9,
        "busy_s_fullest": full["busy_ns"] * 1e-9,
        "idle_share": 100.0 * (1.0 - full["busy_ns"] / window_ns),
        "collective_s_fullest": max(
            d["collective_ns"] for d in per_device.values()) * 1e-9,
        "fullest_device": fullest,
        "marks": len(marks),
        "per_device": {
            i: {"busy_s": d["busy_ns"] * 1e-9,
                "collective_s": d["collective_ns"] * 1e-9,
                "ops": d["ops"], "busy_line": d["busy_line"]}
            for i, d in per_device.items()},
        "device_ops": _top(full["programs"] or full["op_seconds"]),
        "top_ops": _top(full["op_seconds"]),
        "idle_gaps": _top(_label_gaps(g0, g1, annotations)),
    }
