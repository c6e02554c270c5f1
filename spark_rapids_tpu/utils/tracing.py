"""Span tracing: wall-clock attribution for every engine phase.

The engine's counters (PR2 host syncs, PR4 wire bytes, PR9 overlap,
PR10 encoded savings) say *what happened*; nothing until now said
*where the time went*.  This module is the structured span runtime (the
NVTX-range analog, NvtxWithMetrics.scala, carried host-side so it works
on any backend):

* ``span(point, site=..., op=...)`` wraps a region.  Spans are
  **thread-aware** — each thread appends to its own buffer (list
  appends under the GIL; no lock on the hot path) — and
  **query-attributed**: each record is stamped with the *effective*
  owner ident (adopted worker threads resolve to their driving query
  via the PR6 ident-adoption discipline, serving/context.py), so two
  concurrent queries' spans never smear.
* Nesting is tracked per thread: a span's **exclusive** time is its
  duration minus its direct children's durations, so rollups never
  double count (the ``opTimeSelf`` discipline, at span granularity).
* Tracing is DEFAULT-OFF and, when off, every span site costs a single
  branch (``span`` returns a shared no-op; hot loops read ``_active``
  directly and skip even the call).  Tracing changes no data path —
  chaos proves results bit-identical with it on.
* **One timeline**: under ``spark.rapids.tpu.profile.trace`` every span
  also enters a ``jax.profiler.TraceAnnotation`` for its lifetime —
  named by its ``op`` where it has one (``operator.batch`` shows as
  ``TpuFileScanExec``, its point in the event's ``point`` stat), by
  its ``point`` otherwise (``io.reader``, ``hostsync.fetch``, ...) —
  so the engine's spans sit on the
  profiler's clock beside the device's programs.  This is the only
  place the engine writes profiler annotations.  ``emit_span`` records
  (already timed, async) stay host-only.
* At QueryEnd ``finish_query`` drains the owner's closed records into
  (a) a Chrome-trace-event JSON file per query under
  ``spark.rapids.tpu.trace.dir`` (tools/traceview.py — load it in
  Perfetto), (b) an exclusive-time rollup per point / operator /
  structural site id that rides the QueryEnd ``spans`` dict, and (c)
  the persisted per-site :class:`ObservationStore` below.

**Observation store** (ROADMAP item 3's producer half): per-site
evidence — ``site_id -> {rows, bytes, skew, compile_ms, overlap_ms,
span_ms}`` — keyed by the SAME structural site ids the jit cache uses
(``site_id(sig)`` over the jit signature / exchange-site object),
persisted as JSONL beside the AOT cache dir, so a warm start has warm
evidence before any cost model exists.  Values are exponentially
smoothed (alpha 0.5) except ``compile_ms`` which keeps the max.

The runtime is process-global (the persistent-jit-tier discipline): the
last-constructed session's ``spark.rapids.tpu.trace.*`` conf wins.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

# --------------------------------------------------------------- state --

_armed = False     # spans are recorded (trace.enabled / trace.dir)
_profile = False   # spans enter a profiler annotation (profile.trace)
_active = False    # _armed or _profile: THE flag hot loops read
_annotation = None  # jax.profiler.TraceAnnotation, bound when profiling
_trace_dir: Optional[str] = None
_max_events = 100_000
_obs: Optional["ObservationStore"] = None
_reg_lock = threading.Lock()
_bufs: List["_Buf"] = []
_tls = threading.local()
# per-process trace file sequence for drains without a query id
_seq_lock = threading.Lock()
_seq = 0

# record tuple indices (tuples, not objects: the hot path allocates one
# per span and the drain touches thousands)
R_POINT, R_SITE, R_OP, R_T0, R_DUR, R_EXCL, R_OWNER, R_TID, R_ASYNC = \
    range(9)

# span points that measure DEVICE-side in-flight time overlapping host
# work (the async exchange window): exported and summed as overlapMs,
# but excluded from the exclusive-attribution sums — counting them
# toward "attributed wall" would let real blind spots hide under
# overlap credit
ASYNC_POINTS = frozenset({"exchange.async.inflight"})

# phase classification for timeline stripes / bench fractions: every
# span point maps to one of compile | exchange | spill | wait | compute
# (docs/observability.md "span taxonomy")
_PHASE_PREFIXES: Tuple[Tuple[str, str], ...] = (
    ("jit.", "compile"),
    ("shuffle.exchange", "exchange"),
    ("exchange.", "exchange"),
    ("spill.", "spill"),
    ("checkpoint.", "spill"),
    ("incremental.commit", "spill"),
    # state maintenance, not answer compute: watermark eviction is the
    # windowed tick's state-bounding pass (incremental.join.delta /
    # .topn.merge stay "compute" — they ARE the steady-tick work)
    ("incremental.window.evict", "spill"),
    ("admission.wait", "wait"),
    ("scheduler.", "wait"),
    ("udf.worker", "wait"),
    ("pipeline.worker", "wait"),
    ("hostsync.", "wait"),
    ("dist.host_sync", "wait"),
)


def phase_of(point: str) -> str:
    for prefix, phase in _PHASE_PREFIXES:
        if point.startswith(prefix):
            return phase
    return "compute"


def site_id(site: Any) -> str:
    """Stable short id for a structural site object — the jit-cache
    signature (or exchange-site / checkpoint stage id) hashed the same
    way everywhere, so the observation store, the spans rollup, and
    any future cost model key on identical strings."""
    return hashlib.sha256(repr(site).encode()).hexdigest()[:16]


class _Buf:
    """One thread's append-only span storage.

    ``items`` holds closed records; appends are plain ``list.append``
    (GIL-atomic).  The drain compacts with one slice assignment —
    also a single atomic list op — so no lock is ever taken on the
    recording path."""

    __slots__ = ("items", "stack", "dropped", "tid", "name", "thread")

    def __init__(self):
        t = threading.current_thread()
        self.items: List[tuple] = []
        # open spans: [point, site, op, t0_ns, child_ns, owner]
        self.stack: List[list] = []
        self.dropped = 0
        self.tid = t.ident or 0
        self.name = t.name
        # held so the drain can prune buffers of finished threads (the
        # pipeline spawns one worker per drive — without pruning the
        # registry grows one buffer per query for the process life)
        self.thread = t


def _buf() -> _Buf:
    b = getattr(_tls, "buf", None)
    if b is None:
        b = _Buf()
        _tls.buf = b
        with _reg_lock:
            _bufs.append(b)
    return b


def _owner_ident() -> int:
    from spark_rapids_tpu.serving import context as qc
    return qc.effective_ident()


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _SpanCtx:
    __slots__ = ("point", "site", "op", "observe", "_ann", "_open")

    def __init__(self, point: str, site, op, observe):
        self.point = point
        self.site = site
        self.op = op
        self.observe = observe

    def __enter__(self):
        # the flags are read once per span: a (re)configure mid-span
        # unwinds exactly what this enter did
        self._ann = None
        if _profile:
            # named by the operator where there is one (its point rides
            # along as the event's ``point`` stat), else by the point
            self._ann = _annotation(self.op, point=self.point) \
                if self.op else _annotation(self.point)
            self._ann.__enter__()
        self._open = _armed
        if _armed:
            _buf().stack.append([self.point, self.site, self.op,
                                 time.perf_counter_ns(), 0,
                                 _owner_ident()])
        return self

    def __exit__(self, *exc):
        if self._open:
            self._record()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False

    def _record(self):
        b = _buf()
        end = time.perf_counter_ns()
        point, site, op, t0, child_ns, owner = b.stack.pop()
        dur = end - t0
        excl = dur - child_ns
        if b.stack:
            b.stack[-1][4] += dur
        if not _armed:
            return  # disarmed mid-span: unwind, record nothing
        if len(b.items) < _max_events:
            b.items.append((point, site, op, t0, dur, excl, owner,
                            b.tid, False))
        else:
            b.dropped += 1
        if self.observe is not None and site is not None and \
                _obs is not None:
            _obs.observe(site_id(site), **{self.observe: dur / 1e6})


def span(point: str, site=None, op=None, observe: Optional[str] = None):
    """Trace the enclosed region.  One branch when tracing (recording
    and the profiler bridge both) is off.

    ``site``: structural site object (jit signature / stage id) —
    hashed into the rollup's per-site breakdown and, with ``observe``
    set to an observation-store field name (e.g. ``"compile_ms"``),
    the span's duration is recorded as that site observation."""
    if not _active:
        return _NOOP
    return _SpanCtx(point, site, op, observe)


def current_op() -> Optional[str]:
    """``op`` of the innermost open span on this thread that has one
    (the enclosing ``operator.batch``), None outside any or when spans
    are not recorded.  ``jit.dispatch`` stamps it so the rollup maps a
    program's site to the operator that launched it."""
    b = getattr(_tls, "buf", None)
    if b is not None:
        for frame in reversed(b.stack):
            if frame[2]:
                return frame[2]
    return None


def emit_span(point: str, t0_ns: int, dur_ns: int, site=None, op=None,
              is_async: bool = True) -> None:
    """Append an already-timed record (no stack interaction): the async
    exchange in-flight window, incremental tick phases — regions whose
    endpoints the caller times itself."""
    if not _armed:
        return
    b = _buf()
    if len(b.items) < _max_events:
        b.items.append((point, site, op, int(t0_ns), int(dur_ns),
                        int(dur_ns), _owner_ident(), b.tid, is_async))
    else:
        b.dropped += 1


def observe_site(site, **fields) -> None:
    """Record per-site evidence (rows/bytes/skew/...) into the
    observation store.  ``site`` is the raw structural object; no-op
    when tracing is off or no store is configured."""
    if not _armed or _obs is None:
        return
    _obs.observe(site_id(site), **fields)


def observe_host(host: int, point: str, **fields) -> None:
    """Record per-HOST evidence into the observation store — the
    gray-failure per-host axis beside the structural per-site axis.
    Sites are sha-hashed structural ids; host records use the stable
    human-readable ``host<h>@<point>`` form so the profiling per-host
    history and a fresh process's HostHealthTracker can read them
    back without a reverse mapping.  No-op when tracing is off or no
    store is configured."""
    if not _armed or _obs is None:
        return
    _obs.observe(f"host{int(host)}@{point}", **fields)


# ------------------------------------------------------------ configure --

def configure(enabled: bool, trace_dir: Optional[str] = None,
              max_events: int = 100_000,
              obs_dir: Optional[str] = None,
              profile: bool = False) -> None:
    """(Re)arm the process-global runtime from a session's conf.
    ``enabled=False`` disarms (buffers drop their backlog so a
    disarmed process holds no span memory).  ``profile``
    (``spark.rapids.tpu.profile.trace``) bridges every span into the
    profiler's trace, with or without recording."""
    global _armed, _profile, _active, _annotation
    global _trace_dir, _max_events, _obs
    if profile and _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    _trace_dir = trace_dir or None
    _max_events = max(int(max_events), 1)
    if enabled and obs_dir:
        if _obs is None or _obs.dir != obs_dir:
            _obs = ObservationStore(obs_dir)
    else:
        # enabled without a store dir must DISABLE the store, not
        # silently keep writing beside a previous session's cache dir
        _obs = None
    _armed = bool(enabled)
    _profile = bool(profile)
    _active = _armed or _profile
    if not _armed:
        with _reg_lock:
            for b in _bufs:
                del b.items[:]
                b.dropped = 0


def armed() -> bool:
    return _armed


# ---------------------------------------------------------------- drain --

def _drain(owner: int) -> Tuple[List[tuple], int]:
    """Collect (and remove) every CLOSED record attributed to
    ``owner`` across all thread buffers.  Open spans stay on their
    stacks and surface in a later drain."""
    out: List[tuple] = []
    dropped = 0
    # the whole drain holds _reg_lock: recording stays lock-free
    # (appends land at >= n and the slice assignment preserves them),
    # but two concurrent QueryEnd drains must not interleave their
    # snapshot/compact sequences on a shared buffer — a stale
    # compaction would resurrect the other query's already-drained
    # records into its next trace (cross-query duplication)
    with _reg_lock:
        # prune finished threads' drained buffers (one pipeline worker
        # is born per drive; its buffer must die with it once emptied)
        _bufs[:] = [b for b in _bufs
                    if b.thread.is_alive() or b.items or b.stack]
        for b in _bufs:
            n = len(b.items)
            mine = ()
            if n:
                snapshot = b.items[:n]
                mine = [r for r in snapshot if r[R_OWNER] == owner]
                if mine:
                    keep = [r for r in snapshot
                            if r[R_OWNER] != owner]
                    # single slice assignment: atomic under the GIL,
                    # racing appends land at >= n and are preserved
                    b.items[:n] = keep
                    out.extend(mine)
            # drop accounting is per-buffer, so attribution is
            # best-effort: charge a buffer's drops to the drain that
            # owns the buffer's thread or harvested records from it —
            # an unrelated query's drain must not zero the counter and
            # make the owner's truncated trace read as complete
            if b.dropped and (mine or b.tid == owner):
                dropped += b.dropped
                b.dropped = 0
    out.sort(key=lambda r: r[R_T0])
    return out, dropped


def rollup(records: List[tuple], wall_ms: float,
           dropped: int = 0) -> Dict[str, Any]:
    """Exclusive-time rollup: per point, per operator, per structural
    site, plus the phase stripes and the unattributed-time health
    metric (wall - sum(exclusive); > 20% of wall = an instrumentation
    blind spot)."""
    points: Dict[str, Dict[str, float]] = {}
    operators: Dict[str, Dict[str, float]] = {}
    sites: Dict[str, Dict[str, float]] = {}
    phases: Dict[str, float] = {}
    total_excl = 0.0
    overlap_ms = 0.0
    for r in records:
        dur_ms = r[R_DUR] / 1e6
        excl_ms = max(r[R_EXCL], 0) / 1e6
        point = r[R_POINT]
        if r[R_ASYNC] or point in ASYNC_POINTS:
            overlap_ms += dur_ms
            p = points.setdefault(point, {"count": 0, "ms": 0.0,
                                          "exclusiveMs": 0.0})
            p["count"] += 1
            p["ms"] += dur_ms
            continue
        p = points.setdefault(point, {"count": 0, "ms": 0.0,
                                      "exclusiveMs": 0.0})
        p["count"] += 1
        p["ms"] += dur_ms
        p["exclusiveMs"] += excl_ms
        total_excl += excl_ms
        ph = phase_of(point)
        phases[ph] = phases.get(ph, 0.0) + excl_ms
        if r[R_OP]:
            o = operators.setdefault(r[R_OP], {"count": 0, "ms": 0.0,
                                               "exclusiveMs": 0.0})
            o["count"] += 1
            o["ms"] += dur_ms
            o["exclusiveMs"] += excl_ms
        if r[R_SITE] is not None:
            # one key derivation everywhere (jit sigs, exchange sites,
            # stage ids): the observation store and the rollup must
            # agree on the string a site hashes to
            sid = site_id(r[R_SITE])
            s = sites.setdefault(sid, {"count": 0, "ms": 0.0})
            s["count"] += 1
            s["ms"] += excl_ms
            if r[R_OP]:
                # site -> operator: a jit.dispatch span carries the
                # operator that launched the site's program
                s["op"] = r[R_OP]
    unattributed = max(wall_ms - total_excl, 0.0)
    out = {
        "wallMs": round(wall_ms, 3),
        "exclusiveMs": round(total_excl, 3),
        "unattributedMs": round(unattributed, 3),
        "unattributedFrac": round(unattributed / wall_ms, 4)
        if wall_ms > 0 else 0.0,
        "overlapMs": round(overlap_ms, 3),
        "events": len(records),
        "dropped": dropped,
        "phases": {k: round(v, 3) for k, v in sorted(phases.items())},
        "points": {k: {"count": v["count"], "ms": round(v["ms"], 3),
                       "exclusiveMs": round(v["exclusiveMs"], 3)}
                   for k, v in sorted(points.items())},
    }
    if operators:
        out["operators"] = {
            k: {"count": v["count"], "ms": round(v["ms"], 3),
                "exclusiveMs": round(v["exclusiveMs"], 3)}
            for k, v in sorted(operators.items())}
    if sites:
        out["sites"] = {k: dict(v, ms=round(v["ms"], 3))
                        for k, v in sorted(sites.items())}
    return out


def finish_query(session, qid: Optional[int], wall_ms: float,
                 status: str = "success",
                 label: Optional[str] = None) -> Dict[str, Any]:
    """The QueryEnd drain: collect this thread's query's spans, export
    the per-query Chrome trace file (trace.dir), fold per-site span
    time into the observation store, and return the rollup dict for the
    QueryEnd ``spans`` field.  Cheap no-op ({}) when tracing is off —
    faulted and fatal envelopes call it too, so their trace files are
    still well-formed."""
    if not _armed:
        return {}
    records, dropped = _drain(_owner_ident())
    roll = rollup(records, wall_ms, dropped)
    roll["status"] = status
    if _obs is not None:
        for sid, s in (roll.get("sites") or {}).items():
            _obs.observe(sid, span_ms=s["ms"])
        _obs.flush()
    if _trace_dir and (records or qid is not None):
        global _seq
        with _seq_lock:
            _seq += 1
            seq = _seq
        sid = getattr(session, "session_id", "nosession")
        name = label or (f"q{qid}" if qid is not None else f"s{seq}")
        path = os.path.join(_trace_dir,
                            f"trace-{sid}-{name}-{seq}.json")
        try:
            from spark_rapids_tpu.tools.traceview import write_trace
            write_trace(records, path, qid=qid, max_events=_max_events,
                        dropped=dropped, wall_ms=wall_ms, status=status)
            roll["traceFile"] = path
        except Exception:
            pass  # trace export must never fail the query
    try:
        session.last_span_stats = roll
    except Exception:
        pass
    return roll


def finish_scope(session, label: str, wall_ms: float) -> Dict[str, Any]:
    """Drain a non-query scope (an incremental tick's phase spans,
    emitted between query envelopes) into its own trace file."""
    return finish_query(session, None, wall_ms, status="scope",
                        label=label)


# ---------------------------------------------------- observation store --

# observation fields that keep the MAX across observations (compile
# cost per site is the worst-case trace+compile); everything else
# exponentially smooths
_OBS_MAX_FIELDS = frozenset({"compile_ms"})
_OBS_ALPHA = 0.5
OBS_FILE = "observations.jsonl"


class ObservationStore:
    """Persisted per-site observations: one JSONL file beside the AOT
    jit-cache dir.  Load-merge-rewrite on flush (atomic replace), so a
    fresh process reads the prior process's evidence — the ROADMAP
    item 3 producer contract.

    Flushes are serialized across PROCESSES by a lock file
    (O_CREAT|O_EXCL beside the store) and each flush RE-READS the
    on-disk file under the lock, merging records it did not itself
    observe — two concurrent sessions sharing one AOT cache dir can no
    longer drop each other's observations in the read-rewrite window
    (each used to overwrite the file with only its own snapshot).
    Only sites this store OBSERVED since its last flush are written —
    a site merely loaded at construction is a stale copy and must not
    clobber another session's fresher on-disk record.  For a site both
    observed, the flushing store's smoothed values win (freshest
    evidence) except ``compile_ms`` (max — worst-case cost) and
    ``n``/``ts`` (max — monotone counters).  A lock that cannot be
    acquired within the timeout re-marks the snapshot dirty and
    retries at the next flush; a lock file older than ``LOCK_STALE_S``
    is broken by an atomic rename (exactly one breaker wins — two
    sessions both unlinking could otherwise delete each other's FRESH
    locks and run the merge concurrently)."""

    LOCK_TIMEOUT_S = 2.0
    # generous: the stale break exists for CRASHED holders only.  A
    # live-but-slow holder whose merge outruns this window could have
    # its lock stolen (two concurrent merges, lost updates) — the
    # holder stamps the lock's mtime at acquire so the window measures
    # from the start of ITS flush, and a flush that takes longer than
    # this on an optimization-only store is an acceptable residual
    # risk (the store degrades, it never corrupts queries)
    LOCK_STALE_S = 30.0

    def __init__(self, dirpath: str):
        self.dir = dirpath
        self.path = os.path.join(dirpath, OBS_FILE)
        self._lock = threading.Lock()
        self._file_lock = None  # lazy InterProcessLock (pid-stamped)
        self.records: Dict[str, Dict[str, float]] = {}
        self._dirty = False
        # sites THIS store observed since its last successful flush —
        # only these may overwrite the on-disk record: a site merely
        # LOADED at construction is a stale copy, and flushing it
        # ours-win would revert a concurrent session's fresher values
        self._dirty_sids: set = set()
        try:
            os.makedirs(dirpath, exist_ok=True)
            self.records = self.read(dirpath)
        except Exception:
            self.records = {}

    def observe(self, sid: str, **fields) -> None:
        with self._lock:
            rec = self.records.setdefault(sid, {"n": 0})
            rec["n"] = int(rec.get("n", 0)) + 1
            for k, v in fields.items():
                v = float(v)
                prev = rec.get(k)
                if prev is None:
                    rec[k] = round(v, 3)
                elif k in _OBS_MAX_FIELDS:
                    rec[k] = round(max(float(prev), v), 3)
                else:
                    rec[k] = round(_OBS_ALPHA * v +
                                   (1 - _OBS_ALPHA) * float(prev), 3)
            rec["ts"] = round(time.time(), 3)
            self._dirty = True
            self._dirty_sids.add(sid)

    def _acquire_file_lock(self) -> bool:
        """Best-effort cross-process lock beside the store.  False when
        another holder kept it past the timeout — the caller retries at
        the next flush.  Delegates to the shared pid-stamped
        InterProcessLock: a kill-9'd merger's lock is reaped as soon as
        any waiter observes the dead pid, instead of wedging every
        writer for the full LOCK_STALE_S window."""
        from spark_rapids_tpu.utils.locking import InterProcessLock
        if self._file_lock is None:
            self._file_lock = InterProcessLock(self.path + ".lock",
                                               stale_s=self.LOCK_STALE_S)
        return self._file_lock.acquire(timeout_s=self.LOCK_TIMEOUT_S)

    def _release_file_lock(self) -> None:
        if self._file_lock is not None:
            self._file_lock.release()

    @classmethod
    def _merge_record(cls, disk: Dict[str, float],
                      ours: Dict[str, float]) -> Dict[str, float]:
        """Field-wise merge for a site both stores observed: our
        smoothed values win (freshest evidence), except max-semantics
        fields (compile_ms worst case; n/ts monotone)."""
        out = dict(disk)
        out.update(ours)
        for k in list(_OBS_MAX_FIELDS) + ["n", "ts"]:
            if k in disk and k in ours:
                out[k] = max(disk[k], ours[k])
        return out

    def flush(self) -> None:
        with self._lock:
            if not self._dirty:
                return
            # only sites observed since load/last flush: a record this
            # store merely loaded must never clobber a concurrent
            # session's fresher on-disk copy of the same site
            snapshot = {k: dict(self.records[k])
                        for k in self._dirty_sids
                        if k in self.records}
            taken = set(self._dirty_sids)
            self._dirty_sids.clear()
            self._dirty = False
        if not self._acquire_file_lock():
            with self._lock:
                self._dirty = True  # nothing lost: retry next flush
                self._dirty_sids |= taken
            return
        try:
            # merge under the lock: a concurrent session's flush since
            # our load must survive ours (sites only it observed keep
            # its record; shared sites merge field-wise)
            merged = self.read(self.dir)
            for sid, rec in snapshot.items():
                prev = merged.get(sid)
                merged[sid] = self._merge_record(prev, rec) \
                    if prev else rec
            tmp = self.path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                for sid in sorted(merged):
                    rec = {"site": sid}
                    rec.update(merged[sid])
                    f.write(json.dumps(rec) + "\n")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
        except OSError:
            pass  # persistence is an optimization, never a failure
        finally:
            self._release_file_lock()

    @staticmethod
    def read(dirpath: str) -> Dict[str, Dict[str, float]]:
        """Parse a store directory's observations (empty dict when
        absent) — the consumer half used by tools/profiling.py's
        per-site history section and any future cost model."""
        path = os.path.join(dirpath, OBS_FILE)
        out: Dict[str, Dict[str, float]] = {}
        try:
            with open(path, encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        continue  # torn tail of a live store
                    sid = rec.pop("site", None)
                    if sid:
                        out[sid] = rec
        except OSError:
            pass
        return out


def observation_store() -> Optional[ObservationStore]:
    return _obs
