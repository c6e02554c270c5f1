"""Stage-boundary lineage checkpoints for partial query recovery.

The PR1 recovery ladder (driver.py) re-executes every failed query
*from source*: a fault in the last exchange of a five-stage plan throws
away every completed stage, and under chaos spray the retry ladder
multiplies end-to-end latency by the number of completed stages.
Theseus-style resilience (PAPERS.md) treats materialized exchange
outputs as durable, restartable units; this module is that unit for the
mesh engine.

Every time the distributed planner (parallel/dist_planner.py) completes
an exchange-consuming operator — aggregate, join, sort, window, top-N —
the post-shuffle, compacted ShardedFrame is registered here as a named
**StageCheckpoint** in a per-query lineage log:

- the **stage id** is a stable hash of the plan subtree plus the shard
  layout (mesh axes/devices and the packed-wire flag), so the same
  subtree re-planned on the next attempt resolves to the same entry;
- the **payload** lives in the session's spill catalog
  (memory/spill.py) and therefore inherits CRC32 integrity stamps,
  DEVICE→HOST→DISK tier demotion under HBM pressure, and atomic disk
  frames; the manager additionally stamps its own canonical checksum at
  write time so a checkpoint that never left the DEVICE tier is still
  verified on restore;
- on a **resume** attempt (QueryRetryDriver arms ``mode.resume`` for
  retry/spill rungs) the planner consults the log before recursing into
  a subtree and splices the checkpoint in place of the completed work —
  skipping its readers, stages, and collectives entirely;
- a checkpoint that fails verification, no longer materializes, or was
  evicted is **dropped from the log and the subtree re-runs** — never
  wrong bytes, never a stuck query;
- rungs that change the shard layout (split scales batches, demote/cpu
  leave the mesh) **clear the log**: lineage keyed to a layout that no
  longer exists must not resurface.

Governed by ``spark.rapids.sql.recovery.checkpoint.enabled`` /
``.maxBytes`` / ``.tiers``; observable end to end — ``CheckpointWrite``
/ ``CheckpointResume`` / ``CheckpointEvict`` / ``CheckpointInvalid``
events → eventlog ``QueryInfo.checkpoint`` → profiling report + health
checks — with watchdog sections around write/restore so a wedged disk
write classifies as a ``TimeoutFault`` instead of hanging the query.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

import numpy as np

from spark_rapids_tpu.robustness import watchdog
from spark_rapids_tpu.robustness.inject import (fire, fire_mutate,
                                                register_point)

# checkpoints are insurance, colder than shuffle outputs: under HBM
# pressure they demote before any live batch (SpillPriorities analog)
CHECKPOINT_PRIORITY = -1500

# injection surface: a raise/delay rule on the write covers a wedged
# checkpoint store; a corrupt rule on the restore flips payload bits so
# the CRC gate has real rot to catch (the fire_mutate chaos hook)
register_point("checkpoint.write")
register_point("checkpoint.restore")


class CheckpointMetrics:
    """Process-wide checkpoint counters, surfaced by tools/profiling
    alongside the recovery/watchdog counters."""

    FIELDS = ("writes", "bytesWritten", "resumes", "stagesSkipped",
              "evictions", "invalid")

    def __init__(self):
        self._lock = threading.Lock()
        self.counters: Dict[str, int] = {k: 0 for k in self.FIELDS}

    def bump(self, field: str, by: int = 1) -> None:
        with self._lock:
            self.counters[field] += int(by)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.counters)

    def reset(self) -> None:
        with self._lock:
            for k in self.counters:
                self.counters[k] = 0


checkpoint_metrics = CheckpointMetrics()


def input_fingerprint(plan, memo: Optional[dict] = None) -> str:
    """Identity of everything a subtree READS: for every FileRelation
    leaf the sorted (path, size, mtime_ns) triples of its input files
    (appending a file — or mutating one: new size, or a SAME-SIZE
    in-place rewrite, which only the mtime catches — changes the
    fingerprint), and for every InMemoryRelation the identity of its
    live batch objects (two relations alive at once can never share an
    id; the owning plan keeps its batches alive, so a recycled id
    cannot alias).  Folded into the stage lineage key of the
    session-persistent store (robustness/incremental.py) so a
    cross-query splice can only ever use a frame computed from
    byte-identical inputs; the per-query log skips the fold — its ids
    only need intra-query stability, and inputs cannot change
    mid-query.

    ``memo`` (a per-planner-run dict) caches each scan node's stat
    walk: a deep plan stats every file once per EXECUTION ATTEMPT, not
    once per enclosing checkpointable subtree — safe because inputs
    may not change mid-attempt (the existing lineage contract), and
    the memo dies with the planner, so a later attempt (or tick)
    re-observes the filesystem."""
    from spark_rapids_tpu.plan import logical as L
    parts = []

    def scan_part(node):
        if memo is not None and id(node) in memo:
            return memo[id(node)]
        from spark_rapids_tpu.io.readers import (input_signature,
                                                 scan_input_meta)
        part = "files:" + input_signature(scan_input_meta(node.paths))
        if memo is not None:
            memo[id(node)] = part
        return part

    def walk(node):
        if isinstance(node, L.FileRelation):
            parts.append(scan_part(node))
        elif isinstance(node, L.InMemoryRelation):
            parts.append("mem:" + ";".join(
                f"{id(b)}={b.nrows}" for b in node.batches))
        for c in node.children:
            walk(c)

    walk(plan)
    return "\x1e".join(parts)


def stage_id(plan, mesh, packed: bool = True,
             memo: Optional[dict] = None, inputs: bool = True) -> str:
    """Stable lineage key for one plan subtree on one shard layout.
    Structural, not object identity: every re-planned attempt of the
    same query resolves the same subtree to the same id, and two
    occurrences of an identical subtree (a self-join) legitimately
    share one checkpoint — same plan, same layout, same bytes.  With
    ``inputs`` (the default, and what the session-persistent store
    needs) the key also folds in the subtree's INPUT fingerprint
    (file list + sizes + mtimes; see input_fingerprint) so a lineage store
    resuming ACROSS queries can never splice a frame computed from
    different bytes: appending files moves exactly the scan-adjacent
    subtrees' ids and leaves static subtrees resumable.  The
    per-query manager passes ``inputs=False`` — its keys only need
    intra-query stability (inputs cannot change mid-query), and the
    fingerprint's stat walk is pure planning-path overhead there.
    A full-width sha256 digest, not a 32-bit crc: a lineage-key
    collision between two different subtrees would splice the WRONG
    stage's (individually valid) bytes into a resumed plan, the one
    failure the payload checksum cannot catch."""
    import hashlib
    sig = "\x1f".join([
        plan.tree_string(),
        input_fingerprint(plan, memo) if inputs else "",
        ",".join(mesh.axis_names),
        "x".join(str(d) for d in mesh.devices.shape),
        ",".join(str(d) for d in mesh.devices.flat),
        f"packed={bool(packed)}",
    ])
    return hashlib.sha256(sig.encode()).hexdigest()


class StageCheckpoint:
    """One lineage entry: the spill-catalog handle holding the frame
    payload plus the host-side frame metadata (schema, dictionaries,
    shard layout) needed to splice it back into a plan."""

    __slots__ = ("stage_id", "handle", "names", "log_dtypes", "enc",
                 "nshards", "capacity", "crc", "size_bytes", "stages",
                 "seq")

    def __init__(self, sid: str, handle, names, log_dtypes, enc,
                 nshards: int, capacity: int, crc: int,
                 size_bytes: int, stages: int, seq: int):
        self.stage_id = sid
        self.handle = handle
        self.names = list(names)
        self.log_dtypes = list(log_dtypes)
        self.enc = {k: list(v) for k, v in enc.items()}
        self.nshards = nshards
        self.capacity = capacity
        self.crc = crc
        self.size_bytes = size_bytes
        self.stages = stages  # exchange stages the subtree contains
        self.seq = seq


def _frame_payload(frame) -> dict:
    """Canonical host payload of a ShardedFrame: per-column value and
    mask buffers plus the per-shard counts vector, keyed so the spill
    module's canonical checksum covers every byte.  The whole frame
    comes down in ONE budgeted transfer (utils/hostsync.fetch_all) —
    syncs are a counted resource, and per-buffer ``np.asarray`` would
    pay a device-to-host sync per column."""
    from spark_rapids_tpu.utils.hostsync import fetch_all
    bufs = [frame.nrows]
    for v, m in frame.cols:
        bufs.append(v)
        bufs.append(m)
    host = fetch_all(bufs)
    payload = {"__counts.data": np.ascontiguousarray(
        np.asarray(host[0], dtype=np.int32))}
    for i in range(len(frame.cols)):
        payload[f"c{i}.data"] = np.ascontiguousarray(host[1 + 2 * i])
        payload[f"c{i}.validity"] = np.ascontiguousarray(
            np.asarray(host[2 + 2 * i], dtype=bool))
    return payload


class CheckpointManager:
    """Per-query lineage log of StageCheckpoints.

    Lives on ``session.checkpoints`` for the duration of one
    ``DataFrame._execute_batches`` call (all attempts of one query);
    the driver arms ``resume`` on retry-class rungs and clears the log
    on layout-changing rungs; the planner saves after every completed
    exchange stage and restores on resume attempts."""

    # the session-persistent subclass (robustness/incremental.py
    # IncrementalStateStore) sets this True: the planner then consults
    # the log on FIRST attempts too, not only recovery re-attempts —
    # input-fingerprinted stage ids make the cross-query splice safe
    always_resume = False
    # spill priority stage payloads register at (the persistent store
    # registers colder still — standing state never competes with a
    # live query's checkpoints for HBM)
    priority = CHECKPOINT_PRIORITY

    def __init__(self, session):
        from spark_rapids_tpu.config import rapids_conf as rc
        self.session = session
        conf = session.conf
        self.enabled = bool(conf.get(rc.RECOVERY_CHECKPOINT_ENABLED))
        self.max_bytes = int(conf.get(rc.RECOVERY_CHECKPOINT_MAX_BYTES))
        self.tiers = tuple(
            t.strip().upper()
            for t in conf.get(rc.RECOVERY_CHECKPOINT_TIERS).split(",")
            if t.strip())
        self.catalog = getattr(session, "memory_catalog", None)
        self._entries: Dict[str, StageCheckpoint] = {}
        self._seq = 0
        self.local = {k: 0 for k in CheckpointMetrics.FIELDS}

    # --------------------------------------------------------------- plumbing --
    @classmethod
    def for_query(cls, session) -> Optional["CheckpointManager"]:
        """Install a manager on the session for one query execution.
        None (and no session mutation) when checkpointing cannot apply:
        no mesh, conf disabled, no spill catalog, or a manager already
        active (a nested query must not clobber the outer lineage)."""
        if getattr(session, "mesh", None) is None:
            return None
        if getattr(session, "checkpoints", None) is not None:
            return None
        mgr = cls(session)
        if not mgr.enabled or mgr.catalog is None:
            return None
        session.checkpoints = mgr
        return mgr

    def finish(self) -> None:
        """Query over (success or not): release every payload and
        detach from the session.  Lineage never outlives its query —
        the stage ids are only meaningful against this query's plan."""
        for e in list(self._entries.values()):
            try:
                e.handle.close()
            except Exception:
                pass
        self._entries.clear()
        if getattr(self.session, "checkpoints", None) is self:
            self.session.checkpoints = None

    def _bump(self, field: str, by: int = 1) -> None:
        checkpoint_metrics.bump(field, by)
        self.local[field] += int(by)

    def _emit(self, event: str, **fields) -> None:
        from spark_rapids_tpu.utils.events import emit_on_session
        emit_on_session(event, session=self.session, **fields)

    def snapshot(self) -> Dict[str, int]:
        out = dict(self.local)
        out["live"] = len(self._entries)
        out["liveBytes"] = self.live_bytes
        out["liveBytesRaw"] = self.live_bytes_raw
        return out

    def note_distributed_complete(self) -> None:
        """Hook called by ``try_distributed`` on the executing thread
        when a query ANSWERS distributed (the final successful
        attempt, by construction).  No-op here; the session-persistent
        store uses it as the thread-safe signal that stale-entry
        pruning is sound — a shared session attribute like
        ``last_dist_explain`` would race under concurrent queries."""

    @staticmethod
    def _entry_bytes(entry) -> int:
        """Bytes an entry occupies at its CURRENT tier: compressed
        host/disk frames (encoding.storage.hostCodec) meter their
        encoded size, so maxBytes buys proportionally more retained
        lineage when the codec is on."""
        h = getattr(entry, "handle", None)
        if h is not None and not h.closed:
            return h.stored_bytes
        return entry.size_bytes

    @property
    def live_bytes(self) -> int:
        return sum(self._entry_bytes(e)
                   for e in self._entries.values())

    @property
    def live_bytes_raw(self) -> int:
        """Decoded (device-canonical) size of the same entries — the
        raw side of the storage-compression ratio."""
        return sum(e.size_bytes for e in self._entries.values())

    # ------------------------------------------------------------------ write --
    def save(self, sid: str, frame, stages: int = 1,
             shareable: bool = False) -> None:
        """Register a completed stage's ShardedFrame under ``sid``.
        Best-effort: an I/O failure while persisting drops the
        checkpoint (the query continues without it); a watchdog
        deadline on a wedged write still classifies as TimeoutFault.
        ``shareable`` is the planner's hint that the sid's input
        fingerprint is purely file-backed (no in-memory batch
        identities), i.e. derivable by OTHER queries holding the same
        subtree — ignored here; the session-persistent store uses it
        to scope cross-query epoch publication."""
        if not self.enabled or sid in self._entries:
            return
        with watchdog.section("checkpoint.write"):
            fire("checkpoint.write")
            try:
                self._save_body(sid, frame, stages)
            except OSError:
                # a checkpoint is an optimization; losing one must
                # never fail the query that just computed the data
                self.drop(sid, reason="write-failed")

    def _save_body(self, sid: str, frame, stages: int) -> None:
        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        from spark_rapids_tpu.columnar.column import Column
        from spark_rapids_tpu.memory.spill import (DEVICE,
                                                   _payload_checksum)
        if not frame.cols:
            return
        payload = _frame_payload(frame)
        total = int(payload["c0.data"].shape[0])
        crc = _payload_checksum(payload, total)
        # every column carries the batch's logical nrows (the flat
        # nshards*capacity buffer length) so the spill codec keeps the
        # full padded buffers; __counts is just a short int32 buffer
        # riding along (nothing iterates it by nrows)
        cols = {"__counts": Column(
            _int32_dtype(), payload["__counts.data"], total)}
        for i, dt in enumerate(frame.phys_dtypes):
            cols[f"c{i}"] = Column(dt, payload[f"c{i}.data"], total,
                                   validity=payload[f"c{i}.validity"])
        batch = ColumnarBatch(cols, nrows=total)
        handle = self.catalog.register(batch, priority=self.priority)
        entry = StageCheckpoint(
            sid, handle, frame.names, frame.log_dtypes, frame.enc,
            frame.nshards, frame.capacity, crc, handle.size_bytes,
            stages, self._seq)
        self._seq += 1
        self._entries[sid] = entry
        if DEVICE not in self.tiers:
            # tier policy excludes HBM residency: demote the payload
            # now so checkpoints never compete with live batches
            self.catalog.demote(handle,
                                self.tiers[0] if self.tiers else "HOST")
        self._bump("writes")
        self._bump("bytesWritten", entry.size_bytes)
        self._emit("CheckpointWrite", stageId=sid,
                   bytes=entry.size_bytes, stages=stages,
                   tier=handle.tier)
        self._evict_over_budget()

    def _evict_over_budget(self) -> None:
        """Oldest-first eviction keeps the lineage log inside
        ``maxBytes`` — the same HBM-goal accounting the coalesce/spill
        path applies to transient wire bytes (PR4 precedent): the spill
        catalog already counts the payloads against the device budget,
        and this cap bounds what the log may pin across ALL tiers."""
        while self.live_bytes > self.max_bytes and self._entries:
            victim = min(self._entries.values(), key=lambda e: e.seq)
            self.drop(victim.stage_id, reason="max-bytes", evict=True)

    # ---------------------------------------------------------------- restore --
    def restore(self, sid: str, mesh):
        """Materialize the checkpoint for ``sid`` back into a
        ShardedFrame, or None when the subtree must re-run (no entry,
        eviction, CRC mismatch, undecodable payload).  Wrong bytes are
        never returned: verification failure drops the entry and lands
        a CheckpointInvalid event on the trail."""
        entry = self._entries.get(sid)
        if entry is None:
            return None
        from spark_rapids_tpu.robustness.faults import CorruptionFault
        with watchdog.section("checkpoint.restore"):
            try:
                batch = entry.handle.materialize()
            except (CorruptionFault, OSError, ValueError) as e:
                # the spill tiers' own CRC gate (or a vanished disk
                # frame / closed handle) already dropped the batch;
                # treat it as an invalid checkpoint, not a query fault
                self.drop(sid, reason=f"{type(e).__name__}: {e}")
                return None
            return self._restore_body(sid, entry, batch, mesh)

    def _restore_body(self, sid, entry, batch, mesh):
        from spark_rapids_tpu.memory.spill import _payload_checksum
        from spark_rapids_tpu.parallel.dist_planner import ShardedFrame
        payload = {"__counts.data":
                   batch.columns["__counts"].host_values()
                   [:entry.nshards].astype(np.int32)}
        for i in range(len(entry.names)):
            col = batch.columns[f"c{i}"]
            payload[f"c{i}.data"] = col.host_values()
            v = col.host_validity()
            payload[f"c{i}.validity"] = v if v is not None else \
                np.ones(col.capacity, dtype=bool)
        # chaos hook: offer the first data buffer to an armed corrupt
        # rule so the verification gate has real rot to catch
        mutated = fire_mutate("checkpoint.restore", payload["c0.data"]) \
            if entry.names else payload.get("c0.data")
        if mutated is not None:
            payload["c0.data"] = mutated
        total = int(payload["c0.data"].shape[0]) if entry.names else 0
        got = _payload_checksum(payload, total)
        if got != entry.crc:
            self.drop(sid, reason=f"crc {got:#010x} != stored "
                                  f"{entry.crc:#010x}")
            return None
        # host_put, not jnp.asarray: every fleet controller restores the
        # identical host payload, so each contributes its shards of the
        # global frame (single-controller this IS jnp.asarray)
        from spark_rapids_tpu.parallel.mesh import host_put
        cols = [(host_put(mesh, payload[f"c{i}.data"]),
                 host_put(mesh, payload[f"c{i}.validity"]))
                for i in range(len(entry.names))]
        nrows = host_put(mesh, payload["__counts.data"])
        self._bump("resumes")
        self._bump("stagesSkipped", entry.stages)
        self._emit("CheckpointResume", stageId=sid,
                   bytes=entry.size_bytes, stagesSaved=entry.stages)
        return ShardedFrame(mesh, entry.names, entry.log_dtypes, cols,
                            nrows, entry.enc)

    # ------------------------------------------------------------ invalidation --
    def drop(self, sid: str, reason: str, evict: bool = False) -> None:
        """Remove one entry (verification failure, eviction, write
        failure); its subtree simply re-runs on the next attempt."""
        entry = self._entries.pop(sid, None)
        if entry is not None:
            try:
                entry.handle.close()
            except Exception:
                pass
        if evict:
            self._bump("evictions")
            self._emit("CheckpointEvict", stageId=sid, reason=reason,
                       bytes=entry.size_bytes if entry else 0)
        else:
            self._bump("invalid")
            self._emit("CheckpointInvalid", stageId=sid, reason=reason)

    def clear(self, reason: str) -> None:
        """Invalidate the whole log — a ladder rung changed the shard
        layout (split/demote/cpu), so every lineage key is stale."""
        if not self._entries:
            return
        for sid in list(self._entries):
            entry = self._entries.pop(sid)
            try:
                entry.handle.close()
            except Exception:
                pass
        self._bump("invalid")
        self._emit("CheckpointInvalid", stageId="*", reason=reason)


def _int32_dtype():
    from spark_rapids_tpu.columnar import dtypes as dts
    return dts.INT32
