"""One timeline: the engine's spans laid over the device's idle gaps.

Under ``spark.rapids.tpu.profile.trace`` every span of the program's
``utils/tracing`` is an annotation on the profiler's host planes, named
by its operator where it has one (``TpuFileScanExec``) and by its point
otherwise (``io.reader``, ``scan.decode``, ``hostsync.fetch``, ...).
``label_trace(path)`` reads the ``.xplane.pb`` and returns, for the same
window (first to last ``bench.query`` mark) and the same fullest device
as ``reduce.reduce_trace``:

* ``launches``: the programs (``XLA Modules`` events) the device ran;
* ``idle_s``: the device's idle seconds by the engine span the host was
  in.  A gap is cut into pieces of at most ``PIECE_NS`` (one long gap
  spans many spans) and each piece takes the span over its midpoint.
  Per host thread the innermost span wins;
  across threads a working span beats a waiting one (``phase_of`` says
  ``wait``: a thread parked in ``pipeline.worker`` or blocked in
  ``hostsync.fetch`` is named only where no thread works), the shorter
  span first.  A gap under no engine span at all is ``unspanned``;
* the shares of the window: ``idle_scan_share`` (``scan.*``,
  ``upload.h2d``, ``io.reader``), ``idle_sync_share``
  (``hostsync.fetch``, ``dist.host_sync``), ``idle_unspanned_share`` and
  ``idle_other_share`` (any other engine span); the four sum to
  ``idle_share``, the reduction's.

A trace with no device plane (a CPU rehearsal) gives None.
"""

import re

import numpy as np

from benchmark.trace.reduce import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,
                                    WINDOW_MARK, _clip, _events, gaps,
                                    union_seconds)

# what the program's spans are called on the host planes: a dotted point
# of the span taxonomy (the harness's own bench.* marks are not the
# engine's), or an operator's name, which carries its point as a stat
# (a program before that stat wrote operators' class names only)
POINT = re.compile(r"^(?!bench\.)[a-z][a-z_]*(\.[A-Za-z_]\w*)+$")
OPERATOR = re.compile(r"^[A-Za-z_]\w*$")
OLD_OPERATOR = re.compile(r"^Tpu\w*Exec$")
SCAN = re.compile(r"^(scan\.\w+|upload\.h2d|io\.reader)$")
SYNC = re.compile(r"^(hostsync\.fetch|dist\.host_sync)$")
UNSPANNED = "unspanned"
PIECE_NS = 1e5  # 0.1 ms: shorter than the spans worth telling apart


def _is_wait(point):
    """The program's own taxonomy says which spans wait."""
    from spark_rapids_tpu.utils.tracing import phase_of
    return phase_of(point) == "wait"


def _span_point(ev):
    """The point of the engine span this host event is, or None."""
    name = ev.name
    if POINT.match(name):
        return name
    if OPERATOR.match(name):
        for key, value in ev.stats:
            if key == "point":
                return str(value)
        if OLD_OPERATOR.match(name):
            return "operator.batch"
    return None


def _engine_spans(data):
    """Per host thread: (names, points, starts, ends) of the engine's
    spans."""
    threads = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            names, points, starts, ends = [], [], [], []
            for ev in line.events:
                point = _span_point(ev)
                if point is not None:
                    names.append(ev.name)
                    points.append(point)
                    starts.append(ev.start_ns)
                    ends.append(ev.start_ns + ev.duration_ns)
            if names:
                threads.append((names, points,
                                np.asarray(starts, np.float64),
                                np.asarray(ends, np.float64)))
    return threads


def _pieces(g0, g1, step=PIECE_NS):
    """The gaps cut into equal pieces no longer than ``step``, in
    order: (piece starts, piece ends).  Their lengths sum to the gaps'."""
    n = np.maximum(np.ceil((g1 - g0) / step), 1).astype(np.int64)
    gap = np.repeat(np.arange(len(g0)), n)
    k = np.arange(int(n.sum())) - np.repeat(np.cumsum(n) - n, n)
    width = ((g1 - g0) / n)[gap]
    p0 = g0[gap] + k * width
    return p0, p0 + width


def _label(mid, threads):
    """(label of each midpoint as an index into names or -1, names).
    ``mid`` ascends, so a span looks only at the midpoints inside it."""
    names, index, is_wait = [], {}, []
    n = len(mid)
    label = np.full(n, -1, dtype=np.int64)
    best = np.full(n, np.inf)           # length of the span that holds it
    waiting = np.ones(n, dtype=bool)    # held by a waiting span, or none
    for tnames, tpoints, starts, ends in threads:
        # this thread's innermost span over each midpoint
        t_label = np.full(n, -1, dtype=np.int64)
        t_best = np.full(n, np.inf)
        first = np.searchsorted(mid, starts, side="left")
        last = np.searchsorted(mid, ends, side="left")
        for name, point, s, e, i0, i1 in zip(tnames, tpoints, starts, ends,
                                             first, last):
            if i1 <= i0:
                continue
            if name not in index:
                index[name] = len(names)
                names.append(name)
                is_wait.append(_is_wait(point))
            hit = (e - s) < t_best[i0:i1]
            t_label[i0:i1][hit] = index[name]
            t_best[i0:i1][hit] = e - s
        held = t_label >= 0
        # -1 (no span of this thread) reads the trailing True: waiting
        t_wait = np.array(is_wait + [True], dtype=bool)[t_label]
        # a working span beats a waiting one; like against like, the
        # shorter wins
        take = held & ((waiting & ~t_wait)
                       | ((waiting == t_wait) & (t_best < best)))
        label[take] = t_label[take]
        best[take] = t_best[take]
        waiting[take] = t_wait[take]
    return label, names


def label_trace(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, marks = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = {l.name: l for l in plane.lines}
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_MARK:
                    marks.append((float(ev.start_ns),
                                  float(ev.start_ns + ev.duration_ns)))
    if not devices or not marks:
        return None
    lo = min(s for s, _ in marks)
    hi = max(e for _, e in marks)
    busy = {}
    for idx, lines in sorted(devices.items()):
        line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        if line is not None:
            _, s, e = _events(line)
            busy[idx] = _clip(s, e, lo, hi)
    if not busy:
        return None
    fullest = max(busy, key=lambda i: union_seconds(*busy[i]))
    launches = 0
    if MODULES_LINE in devices[fullest]:
        _, ms, me = _events(devices[fullest][MODULES_LINE])
        launches = int(((me > lo) & (ms < hi)).sum())
    g0, g1 = _pieces(*gaps(*busy[fullest], lo, hi))
    label, names = _label((g0 + g1) / 2, _engine_spans(data))
    length = g1 - g0
    idle_ns = {n: float(length[label == i].sum())
               for i, n in enumerate(names)}
    idle_ns[UNSPANNED] = float(length[label == -1].sum())
    window_ns = hi - lo

    def share(names_):
        return 100.0 * sum(idle_ns[n] for n in names_) / window_ns

    scan = [n for n in idle_ns if SCAN.match(n)]
    sync = [n for n in idle_ns if SYNC.match(n)]
    other = [n for n in idle_ns
             if n != UNSPANNED and n not in scan and n not in sync]
    return {
        "idle_share": share(idle_ns),
        "fullest_device": fullest,
        "window_s": window_ns * 1e-9,
        "launches": launches,
        "idle_s": {n: v * 1e-9 for n, v in
                   sorted(idle_ns.items(), key=lambda kv: -kv[1]) if v > 0},
        "idle_scan_share": share(scan),
        "idle_sync_share": share(sync),
        "idle_unspanned_share": share([UNSPANNED]),
        "idle_other_share": share(other),
    }
