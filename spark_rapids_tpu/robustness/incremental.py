"""Continuous micro-batch ingest on checkpoint lineage: crash-consistent
incremental state with epoch semantics.

PR5 made completed exchange stages durable *within* one query (the
per-query :class:`~spark_rapids_tpu.robustness.checkpoint.CheckpointManager`);
this module promotes that log into a **session-persistent
IncrementalStateStore** and turns the checkpoint subsystem from a
failure feature into a latency feature (ROADMAP item 5): a standing
query over an append-only input re-executes only what the appended
files can change, and resumes everything else from state.

The unit of standing work is a :class:`MicroBatchRunner`
(``session.incremental(df)``); each ``runner.tick(new_paths)`` is one
micro-batch with **epoch semantics**:

- the tick executes against the last *committed* epoch; everything it
  writes — the new partial-aggregate state, fresh stage checkpoints —
  lands in a *provisional* epoch;
- the provisional epoch **commits atomically only when the tick
  completes**; any fault mid-tick (chaos-injected or real: reader
  fault, shuffle wedge, spill corruption, watchdog timeout, admission
  reject) **rolls back** to the committed epoch and the tick degrades
  to a full recompute — standing state is never half-updated, a
  degraded tick answers with recomputed (correct) bytes, never wrong
  ones;
- the full robustness stack is live the whole time: every execution
  inside a tick runs through ``DataFrame._execute_batches`` — admission
  control, per-query budgets, the recovery ladder, watchdog deadlines,
  spill integrity and per-query stage checkpoints all apply unchanged.

Two reuse mechanisms compose:

1. **Delta decomposition** (the streaming workload classes): plans of
   shape ``[Sort|Limit|Filter]* <- Aggregate <- [Filter|Project]* <-
   source`` decompose into mergeable partials (sum→sum, count→sum,
   min→min, max→max, avg→(sum,count)).  The tick aggregates ONLY the
   appended files and merges (old-state ⊕ delta) through the engine's
   own aggregate merge discipline — zero re-pulls of already-ingested
   source files.  ``source`` may be the appended fact scan itself, or
   a **delta-join**: ``Join(fact chain, dim subtree)`` where the join
   type preserves per-fact-row locality (inner always; left/semi/anti
   with the fact on the left; right with the fact on the right) — the
   tick joins only the NEW fact batches against the unchanged
   dimension state, whose completed subtrees splice from the lineage
   store, and a dim-side input-fingerprint drift drops the state and
   degrades the tick to full recompute.  Two refinements bound state:
   **windowed aggregation** (group keys from ``functions.window``)
   under ``incremental.watermarkDelayMs`` advances an event-time
   watermark at every commit and evicts expired window buckets
   atomically with it (rollback restores data AND watermark — no
   resurrection of evicted windows, no premature eviction from a
   rolled-back tick); **mergeable top-N**
   (``orderBy(group keys).limit(n)``) trims state and delta partials
   to the top-n rows whenever the sort key set provably makes the
   merge reproduce the one-shot answer bit-for-bit (bare group-key
   sort columns covering every key; value sorts refuse).
2. **Lineage splice** for everything else: the store subclasses the
   PR5 CheckpointManager with ``always_resume`` — stage ids now fold in
   an **input fingerprint** (file list + sizes + mtimes,
   ``checkpoint.input_fingerprint``), so appending files invalidates
   exactly the scan-adjacent subtrees and a full-recompute tick still
   splices unchanged subtrees (a static dimension side of a join, a
   pre-aggregated reference table) via the existing
   ``try_distributed(resume=True)`` machinery.

State lives in the spill catalog at ``INCREMENTAL_STATE_PRIORITY``
(colder than per-query checkpoints — standing state never competes
with live queries for HBM) under its own budget/tier confs
(``spark.rapids.tpu.incremental.enabled`` / ``.maxStateBytes`` /
``.tiers``); eviction or CRC failure of a state entry degrades the
next tick to recompute — it never fails a tick and never returns wrong
bytes.  Observable end to end: ``StateCommit`` / ``StateRollback`` /
``StateEvict`` / ``IncrementalResume`` events → eventlog
``QueryInfo.incremental`` → profiling "Continuous ingest" section and
health checks.
"""

from __future__ import annotations

import copy
import hashlib
import itertools
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from spark_rapids_tpu.robustness.checkpoint import (CheckpointManager,
                                                    CheckpointMetrics)
from spark_rapids_tpu.robustness.inject import (fire, fire_mutate,
                                                register_point)

# chaos surface: a raise/delay rule on the write covers a wedged state
# commit; a corrupt rule on the restore flips state bytes so the CRC
# gate has real rot to catch (fire_mutate site); the sink point sits
# in the emission hand-off between compute and commit — a kill there
# is the crash window exactly-once emission must survive, a corrupt
# rule rots the staged sink payload so the promote-time CRC gate has
# real rot to catch
register_point("incremental.state.write")
register_point("incremental.state.restore")
register_point("incremental.sink.commit")

# Tick markers (thread-local: ticks serialize per runner and every
# execution inside a tick starts on the tick thread).  TWO distinct
# facts live here, split deliberately:
#
# - ``depth``  — "inside MicroBatchRunner.tick()" (in_tick): scope
#   bookkeeping, spans, and user code a tick invokes (an on_commit
#   sink callback) all run under it;
# - ``exec_depth`` — "running one of the RUNNER'S OWN executions"
#   (in_tick_execution): delta partial, merge, watermark evict,
#   finalize, degraded recompute, and the fleet shared-ingest read.
#
# Only the second gates the serving reuse stores
# (DataFrame._execute_batches): the runner's plans over transient
# state relations must bypass the result cache and shared-stage
# registration — their crash-consistency contract rests on the epoch
# store alone, and their id()-keyed in-memory fingerprints die with
# the epoch.  An ORDINARY query issued from within a tick callback
# (e.g. a sink-side lookup) carries depth but not exec_depth and
# caches normally — one coarse marker for both facts silently
# uncached every such query.
_TICK_TLS = threading.local()


def in_tick() -> bool:
    """True while the calling thread is inside MicroBatchRunner.tick()
    (any runner, incremental.enabled on or off) — including user code
    the tick invokes, e.g. an on_commit sink callback."""
    return getattr(_TICK_TLS, "depth", 0) > 0


def in_tick_execution() -> bool:
    """True only while the calling thread is running one of a tick's
    OWN plan executions (or a fleet shared-ingest read) — the marker
    the serving reuse stores gate on; see the module comment above."""
    return getattr(_TICK_TLS, "exec_depth", 0) > 0


class tick_execution_scope:
    """Mark the calling thread as running a tick-owned execution for
    the duration of the ``with`` block (see in_tick_execution)."""

    def __enter__(self) -> "tick_execution_scope":
        _TICK_TLS.exec_depth = getattr(_TICK_TLS, "exec_depth", 0) + 1
        return self

    def __exit__(self, *exc) -> bool:
        _TICK_TLS.exec_depth -= 1
        return False


class IncrementalMetrics(CheckpointMetrics):
    """Process-wide continuous-ingest counters (the profiling tool reads
    these alongside the checkpoint/recovery counters).  Same
    lock/bump/snapshot discipline as the checkpoint counters, wider
    field set; ``stateBytes`` is a gauge (last committed epoch's size),
    everything else is a counter."""

    FIELDS = ("ticks", "incrementalTicks", "fullRecomputes", "commits",
              "rollbacks", "writes", "bytesWritten", "resumes",
              "stagesSkipped", "evictions", "invalid", "stateBytes",
              "stateBytesRaw", "joinTicks", "windowTicks", "topnTicks",
              "watermarkEvictedBuckets", "watermarkEvictedBytes",
              "sinkCommits", "sinkReplays")

    def set(self, field: str, value: int) -> None:
        with self._lock:
            self.counters[field] = int(value)


incremental_metrics = IncrementalMetrics()


def _batch_payload(batch) -> dict:
    """Canonical host payload of a ColumnarBatch (the spill module's
    key layout) for the store's own checksum — a DEVICE-resident state
    batch is verified on restore even though the catalog's CRC only
    stamps at tier crossings.  Host-backed buffers are used bit-exact;
    every still-on-device buffer comes down in ONE budgeted transfer
    (utils/hostsync.fetch_all — syncs are a counted resource, and a
    per-buffer ``np.asarray`` would pay a device-to-host sync per
    column, the checkpoint._frame_payload discipline)."""
    payload = {}
    pending = []  # (payload key, device buffer)
    for name, col in batch.columns.items():
        for suffix, np_buf, jax_buf in (
                ("data", col._np_data, col._jax_data),
                ("validity", col._np_validity, col._jax_validity),
                ("offsets", col._np_offsets, col._jax_offsets)):
            if np_buf is not None:
                payload[f"{name}.{suffix}"] = \
                    np.ascontiguousarray(np_buf)
            elif jax_buf is not None:
                pending.append((f"{name}.{suffix}", jax_buf))
    if pending:
        from spark_rapids_tpu.utils.hostsync import fetch_all
        fetched = fetch_all([b for _, b in pending])
        for (key, _), host in zip(pending, fetched):
            payload[key] = np.ascontiguousarray(np.asarray(host))
    return payload


class AggState:
    """One epoch's partial-aggregate state: the spill-catalog handle
    holding the merged partial batch plus the input fingerprint it was
    computed from.  ``watermark`` is the epoch's event-time watermark
    (microseconds; None for non-windowed shapes) — it lives WITH the
    state so commit promotes and rollback discards them together:
    a rolled-back tick can neither advance the watermark nor
    resurrect a bucket the committed epoch already evicted."""

    __slots__ = ("handle", "nrows", "crc", "size_bytes", "fingerprint",
                 "epoch", "watermark")

    def __init__(self, handle, nrows: int, crc: int, size_bytes: int,
                 fingerprint: str, epoch: int,
                 watermark: Optional[int] = None):
        self.handle = handle
        self.nrows = nrows
        self.crc = crc
        self.size_bytes = size_bytes
        self.fingerprint = fingerprint
        self.epoch = epoch
        self.watermark = watermark


class _SinkRecord:
    """One COMMITTED (or staged-provisional) emission's identity:
    epoch + payload CRC + row/byte counts.  Metadata only — the
    payload itself is the tick's result (bit-identical on recompute by
    the epoch contract), so idempotent re-emission needs the identity,
    not a copy of the bytes."""

    __slots__ = ("epoch", "crc", "rows", "size_bytes")

    def __init__(self, epoch: int, crc: int, rows: int,
                 size_bytes: int):
        self.epoch = epoch
        self.crc = crc
        self.rows = rows
        self.size_bytes = size_bytes


class SinkCommit:
    """What ``runner.tick()`` hands a downstream sink: exactly-once
    emission metadata that rode the atomic epoch commit.  ``epoch`` is
    the emission's COMMITTED epoch — a replayed tick (no new data, or
    a retried delivery) re-surfaces the SAME epoch with
    ``replayed=True`` and an identical ``crc``, so a sink that
    dedupes on (store, epoch) gets every answer exactly once across
    crash/rollback/replay.  ``df`` is the tick's result DataFrame
    (attached by the runner after commit)."""

    __slots__ = ("store", "epoch", "crc", "rows", "size_bytes",
                 "replayed", "df")

    def __init__(self, store: int, epoch: int, crc: int, rows: int,
                 size_bytes: int, replayed: bool):
        self.store = store
        self.epoch = epoch
        self.crc = crc
        self.rows = rows
        self.size_bytes = size_bytes
        self.replayed = replayed
        self.df = None

    def __repr__(self) -> str:
        return (f"SinkCommit(store={self.store}, epoch={self.epoch}, "
                f"crc={self.crc:#010x}, rows={self.rows}, "
                f"replayed={self.replayed})")


class SharedIngest:
    """One fleet round's single source pull, fanned out to every
    subscriber: the delta file list, its PRE-READ ``scan_input_meta``
    stat triples (the stat-before-read rule — a file mutating after
    the stat leaves the committed fingerprint describing pre-mutation
    bytes, so the next staleness check catches it), the materialized
    batches, and the full scan schema the batches carry (subscribers
    whose fact scan reads a different shape fall back to their own
    pull — correct, just unshared)."""

    __slots__ = ("paths", "meta", "batches", "schema_names")

    def __init__(self, paths, meta, batches, schema_names):
        self.paths = list(paths)
        self.meta = list(meta)
        self.batches = list(batches)
        self.schema_names = list(schema_names)  # [(name, dtype.name)]


class IncrementalStateStore(CheckpointManager):
    """Session-persistent lineage + aggregate state with epochs.

    The PR5 CheckpointManager, promoted: entries outlive a query, stage
    ids are input-fingerprinted (safe to splice across queries —
    ``always_resume``), and every mutation lands provisionally until
    :meth:`commit` — :meth:`rollback` restores the committed epoch
    exactly.  Committed entries are only ever *dropped* outside the
    epoch discipline (CRC failure, eviction) — a drop degrades a future
    tick to recompute, which is always correct."""

    always_resume = True

    # per-process store sequence: stamps StateWatermark with a stable
    # per-standing-query discriminator so app-level consumers (the
    # watermark-stall health check) can group one runner's trail —
    # without it, one advancing windowed query masks a stalled one
    _STORE_SEQ = itertools.count(1)

    def __init__(self, session):
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.memory.spill import (
            INCREMENTAL_STATE_PRIORITY)
        # base wiring (session/catalog/entry log/counters) is the
        # manager's; only the governing confs and the priority differ
        super().__init__(session)
        self.store_id = next(IncrementalStateStore._STORE_SEQ)
        conf = session.conf
        self.enabled = bool(conf.get(rc.INCREMENTAL_ENABLED))
        self.max_bytes = int(conf.get(rc.INCREMENTAL_MAX_STATE_BYTES))
        self.tiers = tuple(
            t.strip().upper()
            for t in conf.get(rc.INCREMENTAL_TIERS).split(",")
            if t.strip())
        self.priority = INCREMENTAL_STATE_PRIORITY
        self.epoch = 0
        self._agg: Optional[AggState] = None
        self._agg_prov: Optional[AggState] = None
        self._provisional: set = set()
        self._touched: set = set()
        # exactly-once sink log: committed emission records (epoch →
        # identity, insertion-ordered, trimmed to sink_max) plus the
        # one staged-provisional record that rides the next commit
        self.sink_max = int(conf.get(rc.FLEET_SINK_MAX_RECORDS))
        self._sink: Dict[int, _SinkRecord] = {}
        self._sink_prov: Optional[_SinkRecord] = None
        self.last_sink: Optional[SinkCommit] = None
        # epoch-aware sharing: sids whose input fingerprint is purely
        # file-backed (no in-memory batch identities — the planner's
        # shareable hint) are safe to splice ACROSS standing queries;
        # commit publishes the committed subset to the session
        # SharedStageCache's epoch tier
        self.share_epoch = bool(
            conf.get(rc.FLEET_EPOCH_SHARED_STAGE_ENABLED))
        self._shareable: set = set()
        self._splice_active = False
        # True only when a splice execution ran DISTRIBUTED end to end
        # — the precondition for stale-entry pruning at commit: an
        # attempt that fell off the mesh (ladder demotion, fallback)
        # touched nothing, and "untouched" must not read as "stale"
        self._splice_complete = False

    # ------------------------------------------------------- metric/event taps --
    # the base class's save/restore/drop machinery is reused verbatim;
    # only where its counters and events land changes
    _EVENT_MAP = {"CheckpointWrite": None,  # commit carries the bytes
                  "CheckpointResume": "IncrementalResume",
                  "CheckpointEvict": "StateEvict",
                  "CheckpointInvalid": "StateEvict"}

    def _bump(self, field: str, by: int = 1) -> None:
        incremental_metrics.bump(field, by)
        if field in self.local:
            self.local[field] += int(by)

    def _emit(self, event: str, **fields) -> None:
        mapped = self._EVENT_MAP.get(event, event)
        if mapped is None:
            return
        from spark_rapids_tpu.utils.events import emit_on_session
        emit_on_session(mapped, session=self.session, **fields)

    # ------------------------------------------------------------ stage lineage --
    def save(self, sid: str, frame, stages: int = 1,
             shareable: bool = False) -> None:
        known = sid in self._entries
        super().save(sid, frame, stages)
        if not known and sid in self._entries:
            self._provisional.add(sid)
            if shareable:
                # the planner vouched: this sid's fingerprint is
                # purely file-backed, so another standing query whose
                # plan contains the identical subtree derives the
                # identical sid — publishable at commit
                self._shareable.add(sid)
        self._touched.add(sid)

    def restore(self, sid: str, mesh):
        frame = super().restore(sid, mesh)
        if frame is not None:
            self._touched.add(sid)
            return frame
        # local miss: try a co-subscriber's COMMITTED epoch via the
        # session shared-stage cache's epoch tier.  The hit is not
        # adopted (not _touched, not ours): the owner store's epoch
        # discipline governs its lifetime, and this store's pruning
        # must not treat a borrowed entry as its own lineage.
        if self.share_epoch:
            shared = getattr(self.session, "shared_stages", None)
            if shared is not None and getattr(shared, "enabled", False):
                er = getattr(shared, "epoch_restore", None)
                if er is not None:
                    return er(sid, mesh, exclude=self)
        return None

    def drop(self, sid: str, reason: str, evict: bool = False) -> None:
        self._provisional.discard(sid)
        self._shareable.discard(sid)
        super().drop(sid, reason, evict=evict)

    def note_distributed_complete(self) -> None:
        """The planner's on-thread completion signal: the final
        attempt of a splice execution really ran distributed, so
        untouched entries are provably stale at commit.  clear() (a
        layout rung) can only be followed by off-mesh attempts, which
        never reach this hook — the veto sticks."""
        if self._splice_active:
            self._splice_complete = True

    def clear(self, reason: str) -> None:
        """A layout-changing ladder rung inside one tick invalidates
        only that tick's PROVISIONAL work: committed entries are keyed
        to (subtree, mesh layout, input fingerprint), all of which
        survive the rung — the next tick runs on the mesh again and
        they splice correctly.  (The per-query manager clears its whole
        log here; a persistent store that did the same would throw away
        every standing epoch on one transient demotion.)"""
        self._splice_complete = False  # a layout rung ran: this tick
        # can no longer vouch for which committed entries are stale
        self._sink_prov = None  # metadata only; nothing to release
        for sid in list(self._provisional):
            entry = self._entries.pop(sid, None)
            self._provisional.discard(sid)
            self._shareable.discard(sid)
            if entry is not None:
                try:
                    entry.handle.close()
                except Exception:
                    pass
        if self._agg_prov is not None:
            try:
                self._agg_prov.handle.close()
            except Exception:
                pass
            self._agg_prov = None

    # ------------------------------------------------------------ agg state I/O --
    def put_state(self, batch, fingerprint: str,
                  watermark: Optional[int] = None) -> None:
        """Register the tick's merged partial-aggregate batch as the
        PROVISIONAL epoch's state (replacing any earlier provisional
        from the same tick — a degraded tick overwrites its own
        half-built state, never the committed epoch).  For windowed
        shapes the batch arrives already watermark-evicted and
        ``watermark`` is the epoch it was evicted against — the two
        are one provisional unit, promoted or discarded together."""
        from spark_rapids_tpu.memory.spill import _payload_checksum
        fire("incremental.state.write")
        if self._agg_prov is not None:
            try:
                self._agg_prov.handle.close()
            except Exception:
                pass
            self._agg_prov = None
        payload = _batch_payload(batch)
        crc = _payload_checksum(payload, batch.nrows)
        # put_state runs BETWEEN a tick's query executions (no
        # QueryContext to auto-tag from), but the standing state must
        # still bill its tenant: the tick thread's ident is the same
        # owner ident every QueryContext of this tick registers its
        # budgets under, so per-owner accounting and the eviction
        # floor see the state as the standing query's, not nobody's
        handle = self.catalog.register(batch, priority=self.priority,
                                       owner=threading.get_ident())
        if "DEVICE" not in self.tiers:
            self.catalog.demote(
                handle, self.tiers[0] if self.tiers else "HOST")
        self._agg_prov = AggState(handle, batch.nrows, crc,
                                  handle.size_bytes, fingerprint,
                                  self.epoch + 1, watermark=watermark)
        self._bump("writes")
        self._bump("bytesWritten", handle.size_bytes)
        self._evict_over_budget()

    def get_state(self):
        """The COMMITTED epoch's state batch, or None when the next
        tick must full-recompute (no state, evicted, CRC mismatch,
        undecodable spill frame).  Wrong bytes are never returned: any
        verification failure drops the state and lands a StateEvict on
        the trail."""
        from spark_rapids_tpu.memory.spill import _payload_checksum
        from spark_rapids_tpu.robustness.faults import CorruptionFault
        st = self._agg
        if st is None:
            return None
        try:
            batch = st.handle.materialize()
        except (CorruptionFault, OSError, ValueError) as e:
            self.drop_state(f"{type(e).__name__}: {e}")
            return None
        payload = _batch_payload(batch)
        key = next((k for k in sorted(payload)
                    if payload[k].size > 0), None)
        if key is not None:
            mutated = fire_mutate("incremental.state.restore",
                                  payload[key])
            if mutated is not payload[key]:
                payload = dict(payload)
                payload[key] = mutated
        got = _payload_checksum(payload, st.nrows)
        if got != st.crc:
            self.drop_state(f"crc {got:#010x} != stored {st.crc:#010x}")
            return None
        return batch

    def drop_state(self, reason: str, evict: bool = False,
                   provisional: bool = False) -> None:
        """Release one aggregate-state slot (committed by default, the
        in-flight provisional one under budget pressure) with the
        shared eviction accounting — both paths must emit the same
        StateEvict shape."""
        if provisional:
            st, self._agg_prov = self._agg_prov, None
        else:
            st, self._agg = self._agg, None
        if st is None:
            return
        try:
            st.handle.close()
        except Exception:
            pass
        self._bump("evictions" if evict else "invalid")
        self._emit("StateEvict", kind="aggState", reason=reason,
                   bytes=st.size_bytes, epoch=st.epoch)

    # ---------------------------------------------------------------- sink log --
    def sink_prepare(self, batches) -> None:
        """Stage this tick's emission as the PROVISIONAL sink record
        (CRC + rows + bytes over the result batches).  This is the
        hand-off between compute and commit — the chaos point here IS
        the crash window exactly-once emission must survive: a kill
        raises before anything is staged (rollback discards, the
        degraded recompute stages afresh, one commit → one emission),
        and a corrupt rule rots the staged payload so the CRC gate
        below catches real bit rot before it can ride a commit."""
        from spark_rapids_tpu.memory.spill import _payload_checksum
        from spark_rapids_tpu.robustness.faults import CorruptionFault
        fire("incremental.sink.commit")
        crc, rows, size = 0, 0, 0
        probed = False
        for b in batches:
            payload = _batch_payload(b)
            c = _payload_checksum(payload, b.nrows)
            if not probed:
                key = next((k for k in sorted(payload)
                            if payload[k].size > 0), None)
                if key is not None:
                    probed = True
                    mut = fire_mutate("incremental.sink.commit",
                                      payload[key])
                    if mut is not payload[key]:
                        staged = dict(payload)
                        staged[key] = mut
                        got = _payload_checksum(staged, b.nrows)
                        if got != c:
                            raise CorruptionFault(
                                "sink payload rot between compute and"
                                f" commit: crc {got:#010x} != "
                                f"computed {c:#010x}")
            crc = (crc * 1000003 + c) & 0xFFFFFFFF
            rows += int(b.nrows)
            size += sum(a.nbytes for a in payload.values())
        self._sink_prov = _SinkRecord(self.epoch + 1, crc, rows, size)

    @property
    def state_fingerprint(self) -> Optional[str]:
        return self._agg.fingerprint if self._agg is not None else None

    @property
    def state_watermark(self) -> Optional[int]:
        """The COMMITTED epoch's event-time watermark (us) — the floor
        every later advance builds on; None for non-windowed state or
        after a state drop (a recompute then re-derives an equal-or-
        later watermark from the data, monotone by construction)."""
        return self._agg.watermark if self._agg is not None else None

    @property
    def state_bytes(self) -> int:
        """STORED bytes of all standing state — compressed host/disk
        frames meter their encoded size, so maxStateBytes holds
        proportionally more state when the storage codec is on."""
        n = self.live_bytes
        for st in (self._agg, self._agg_prov):
            if st is not None:
                n += self._entry_bytes(st)
        return n

    @property
    def state_bytes_raw(self) -> int:
        n = self.live_bytes_raw
        for st in (self._agg, self._agg_prov):
            if st is not None:
                n += st.size_bytes
        return n

    # -------------------------------------------------------------------- epochs --
    def commit(self, mode: str, delta_files: int, reused: bool,
               evicted_buckets: int = 0, evicted_rows: int = 0,
               evicted_bytes: int = 0) -> int:
        """Atomically promote the provisional epoch: the new aggregate
        state replaces the old (whose payload is released), provisional
        stage entries become committed, and — when this tick spliced —
        committed entries the tick never touched are pruned (their
        input fingerprints have moved on; they can never match again).
        The commit is the LAST step of a tick: everything before it is
        invisible to the next tick until this returns.  For windowed
        shapes the commit doubles as the watermark advance — the
        provisional state was built already-evicted against its
        watermark, so promoting it IS the atomic
        eviction+advance (``evicted_buckets``/``evicted_bytes`` are
        the counts that eviction removed, stamped on the
        ``StateWatermark`` event this emits)."""
        self.epoch += 1
        if self._agg_prov is not None:
            old, self._agg = self._agg, self._agg_prov
            self._agg_prov = None
            if old is not None:
                try:
                    old.handle.close()
                except Exception:
                    pass
        if self._splice_active and self._splice_complete:
            # lifecycle GC, not pressure: a DISTRIBUTED splice tick
            # that completed on the mesh and never touched an entry
            # proves its input fingerprint moved on — the key can
            # never match again.  Removed silently (no StateEvict, no
            # eviction counter): routine pruning on a healthy standing
            # query must not trip the eviction-thrash health check.
            # Guarded by _splice_complete: a tick whose final attempt
            # left the mesh (layout rung, planner fallback) touched
            # nothing, and pruning then would wipe still-valid lineage
            for sid in [s for s in self._entries
                        if s not in self._touched]:
                entry = self._entries.pop(sid)
                self._provisional.discard(sid)
                self._shareable.discard(sid)
                try:
                    entry.handle.close()
                except Exception:
                    pass
        self._provisional.clear()
        self._touched.clear()
        self._splice_active = False
        self._splice_complete = False
        self._evict_over_budget()
        # promote the staged sink record — the emission rides THIS
        # commit.  An identical payload to the latest committed record
        # is a REPLAY: the same committed epoch re-emits idempotently
        # (retried tick, zero-delta round) and no new record lands —
        # a (store, epoch)-deduping sink sees every answer exactly once
        sink = None
        if self._sink_prov is not None:
            prov, self._sink_prov = self._sink_prov, None
            last = (self._sink[next(reversed(self._sink))]
                    if self._sink else None)
            if last is not None and (last.crc, last.rows) == \
                    (prov.crc, prov.rows):
                self._bump("sinkReplays")
                sink = SinkCommit(self.store_id, last.epoch, last.crc,
                                  last.rows, last.size_bytes, True)
            else:
                self._sink[self.epoch] = _SinkRecord(
                    self.epoch, prov.crc, prov.rows, prov.size_bytes)
                while len(self._sink) > self.sink_max:
                    self._sink.pop(next(iter(self._sink)))
                self._bump("sinkCommits")
                sink = SinkCommit(self.store_id, self.epoch, prov.crc,
                                  prov.rows, prov.size_bytes, False)
            self._emit("SinkCommit", epoch=sink.epoch, crc=sink.crc,
                       rows=sink.rows, bytes=sink.size_bytes,
                       replayed=bool(sink.replayed),
                       store=self.store_id)
        self.last_sink = sink
        # publish the committed epoch's shareable sids to the session
        # shared-stage cache — ONLY here, never from provisional state
        # (rollback publishes nothing, so the snapshot other standing
        # queries splice from is always a committed epoch's); an empty
        # set still publishes, replacing a stale snapshot
        self._publish_epoch()
        incremental_metrics.bump("commits")
        incremental_metrics.set("stateBytes", self.state_bytes)
        incremental_metrics.set("stateBytesRaw", self.state_bytes_raw)
        self._emit("StateCommit", epoch=self.epoch,
                   stateBytes=self.state_bytes,
                   entries=len(self._entries), mode=mode,
                   deltaFiles=delta_files, reusedState=bool(reused))
        if self._agg is not None and self._agg.watermark is not None:
            # the windowed shape's commit fact: where the watermark
            # landed and what its eviction removed — the profiling
            # "Continuous ingest" watermark line and the
            # watermark-stalled-growth health check read these
            incremental_metrics.bump("watermarkEvictedBuckets",
                                     evicted_buckets)
            incremental_metrics.bump("watermarkEvictedBytes",
                                     evicted_bytes)
            self._emit("StateWatermark", epoch=self.epoch,
                       store=self.store_id,
                       watermark=int(self._agg.watermark),
                       evictedBuckets=int(evicted_buckets),
                       evictedRows=int(evicted_rows),
                       evictedBytes=int(evicted_bytes),
                       stateRows=int(self._agg.nrows),
                       stateBytes=self.state_bytes)
        return self.epoch

    def _publish_epoch(self) -> None:
        """Hand the session SharedStageCache a by-reference snapshot of
        this store's committed, cross-query-safe stage entries (called
        from commit ONLY)."""
        if not self.share_epoch:
            return
        shared = getattr(self.session, "shared_stages", None)
        if shared is None or not getattr(shared, "enabled", False):
            return
        pub = getattr(shared, "publish_epoch", None)
        if pub is not None:
            pub(self, frozenset(s for s in self._entries
                                if s in self._shareable))

    def rollback(self, reason: str) -> None:
        """Discard every provisional write; the committed epoch is
        untouched — a chaos-killed tick leaves the standing state
        exactly as the last commit left it (including the sink log and
        the published shared-epoch snapshot: neither is touched here,
        both only ever move at commit)."""
        self.clear(reason)
        self._touched.clear()
        self._splice_active = False
        self._splice_complete = False
        incremental_metrics.bump("rollbacks")
        self._emit("StateRollback", epoch=self.epoch, reason=reason)

    def _evict_over_budget(self) -> None:
        """maxStateBytes over ALL state: oldest stage entries evict
        first (stale lineage is the cheapest loss), then the committed
        aggregate state (superseded at the next commit anyway), and
        only then the provisional one — each eviction degrades a
        future tick to recompute, never fails one."""
        while self.state_bytes > self.max_bytes and self._entries:
            victim = min(self._entries.values(), key=lambda e: e.seq)
            self.drop(victim.stage_id, reason="max-state-bytes",
                      evict=True)
        if self.state_bytes > self.max_bytes and self._agg is not None:
            self.drop_state("max-state-bytes", evict=True)
        if self.state_bytes > self.max_bytes and \
                self._agg_prov is not None:
            self.drop_state("max-state-bytes", evict=True,
                            provisional=True)

    def close(self) -> None:
        """Release every payload (runner teardown / session stop)."""
        shared = getattr(self.session, "shared_stages", None)
        if shared is not None and \
                hasattr(shared, "retract_epoch"):
            try:
                shared.retract_epoch(self)
            except Exception:
                pass
        self._sink_prov = None
        self._sink.clear()
        self.last_sink = None
        self.clear("store-closed")
        for sid in list(self._entries):
            entry = self._entries.pop(sid)
            try:
                entry.handle.close()
            except Exception:
                pass
        if self._agg is not None:
            try:
                self._agg.handle.close()
            except Exception:
                pass
            self._agg = None


# ------------------------------------------------------------- plan analysis --

def _file_scans(plan) -> list:
    """Every FileRelation leaf of a plan, in pre-order."""
    from spark_rapids_tpu.plan import logical as L
    scans = []

    def walk(node):
        if isinstance(node, L.FileRelation):
            scans.append(node)
        for c in node.children:
            walk(c)

    walk(plan)
    return scans


def _find_fact_scan(plan, fact=None):
    """The FileRelation leaf tick() appends to: the plan's unique scan,
    or — for multi-scan plans like a fact⋈dim join over two parquet
    tables — the one designated by ``fact`` (a path already in its
    file list).  None when no unambiguous choice exists (the runner
    then has no append target; plans without one still tick as full
    re-executions with lineage splice)."""
    scans = _file_scans(plan)
    if fact is not None:
        hits = [s for s in scans if fact in s.paths]
        return hits[0] if len(hits) == 1 else None
    return scans[0] if len(scans) == 1 else None


def _replace_scan(plan, scan, paths, replacement=None):
    """Clone ``plan`` with ``scan``'s path list swapped for ``paths``
    — or, with ``replacement``, with the scan node swapped for that
    relation outright (the fleet shared-ingest form: an
    InMemoryRelation holding the already-pulled delta batches).
    Expressions stay shared (they are bound by ordinal and the delta
    scan exposes the identical schema), and subtrees that do not
    contain ``scan`` are shared UNTOUCHED — the dimension side of a
    delta-join keeps node identity across ticks, so its
    InMemoryRelation batch ids (and therefore its input fingerprints
    and spliceable stage ids) stay stable; only the spine from the
    root down to the scan is copied."""
    if plan is scan:
        if replacement is not None:
            return replacement
        new = copy.copy(plan)
        new.paths = list(paths)
        new.pushed_filters = list(plan.pushed_filters)
        new.file_meta = set(plan.file_meta)
        return new
    if not plan.children:
        return plan
    new_children = tuple(_replace_scan(c, scan, paths, replacement)
                         for c in plan.children)
    if all(nc is c for nc, c in zip(new_children, plan.children)):
        return plan
    new = copy.copy(plan)
    new.children = new_children
    return new


class _AggSpec:
    """Decomposition prover: certify a standing plan's delta form, or
    refuse it (``None`` — ticks then full-recompute with lineage
    splice, which is always correct).

    ``[Sort|Limit|Filter]* <- Aggregate <- [Filter|Project]* <-
    source`` splits into: a partial-aggregate plan template (run over
    the delta files only), a merge aggregate (re-reduce (old-state ⊕
    delta) partial rows — the same update/merge split the engine's
    chunked and distributed aggregates use, ops/aggregates.merge_kind),
    a finalize projection (avg = sum/count), and the post-aggregate
    operator chain re-applied on top.  Three admitted source/refinement
    shapes beyond the plain scan:

    - **delta-join** — ``source`` is ``Join(fact chain, dim subtree)``
      where the fact scan sits under its own [Filter|Project]* chain
      and the join type makes output rows a per-fact-row function
      (inner always; left/semi/anti only with the fact on the left;
      right only with the fact on the right — every other type scopes
      output to DIM rows, where a new fact batch can flip matched-ness
      and no per-delta decomposition is sound).  Dim subtrees routing
      through arbitrary Python (UDF/pandas) refuse: the delta merge
      re-executes the dim side and non-determinism would diverge from
      the one-shot oracle.
    - **windowed aggregation** — a group key pair built by
      ``functions.window`` (tumbling only; sliding lowers through
      Expand and never reaches this prover).  With
      ``incremental.watermarkDelayMs`` set, ``window_end`` names the
      bucket-end key the watermark advances on and eviction filters.
    - **mergeable top-N** — post chain exactly ``Limit <- Sort`` whose
      sort keys are bare group-key references covering EVERY group key
      (the ordering over output rows is then total, and for append-only
      ingest a group trimmed from the top-n can never re-enter: the n
      better-keyed groups that displaced it persist — so merging
      trimmed partials provably reproduces the one-shot answer
      bit-for-bit).  Sort keys touching aggregated values refuse the
      trim (a value can move a group back into the top-n after its
      partial was discarded); limits above
      ``incremental.topn.maxStateRows`` keep full-group state.
    """

    def __init__(self, agg, pre_chain_root, post_ops, partial_aggs,
                 merge_keys, merge_aggs, final_exprs, partial_schema,
                 join_type=None, dim_plan=None, window_end=None,
                 delay_us=None, trim_n=None, trim_sort=None):
        self.agg = agg
        self.pre_root = pre_chain_root  # plan node directly above scan
        self.post_ops = post_ops        # outermost-first [Sort|Limit|Filter]
        self.partial_aggs = partial_aggs
        self.merge_keys = merge_keys
        self.merge_aggs = merge_aggs
        self.final_exprs = final_exprs
        self.partial_schema = partial_schema
        self.join_type = join_type      # admitted delta-join type
        self.dim_plan = dim_plan        # static dimension subtree
        self.window_end = window_end    # bucket-end key (eviction on)
        self.delay_us = delay_us        # watermark delay (us)
        self.trim_n = trim_n            # proven top-N state bound
        self.trim_sort = trim_sort      # the Sort node the trim applies

    @property
    def shape(self) -> str:
        """Primary shape label (spans, last_tick_info, bench)."""
        if self.join_type is not None:
            return "join"
        if self.window_end is not None:
            return "window"
        if self.trim_n is not None:
            return "topn"
        return "agg"

    @staticmethod
    def _fact_side(join, scan):
        """Which join child reaches ``scan`` through a pure
        [Filter|Project]* chain (0=left, 1=right), or None.  Chain
        purity is what lets ``_replace_scan`` build the delta fact
        side; the other child is the dimension subtree and must not
        contain the fact scan anywhere (a self-join over the appended
        table has no per-delta form — delta×delta pairs would be
        lost)."""
        from spark_rapids_tpu.plan import logical as L
        side = None
        for i, child in enumerate(join.children):
            c = child
            while isinstance(c, (L.Filter, L.Project)):
                c = c.children[0]
            if c is scan:
                side = i if side is None else None
        if side is None:
            return None

        def contains(node):
            if node is scan:
                return True
            return any(contains(ch) for ch in node.children)

        return None if contains(join.children[1 - side]) else side

    @classmethod
    def analyze(cls, plan, scan, watermark_delay_us=None, topn_cap=0):
        from spark_rapids_tpu.columnar import dtypes as dts
        from spark_rapids_tpu.ops import aggregates as ag
        from spark_rapids_tpu.ops.arithmetic import Divide
        from spark_rapids_tpu.ops.cast import Cast
        from spark_rapids_tpu.ops.expressions import (Alias,
                                                      UnresolvedColumn)
        from spark_rapids_tpu.plan import logical as L
        from spark_rapids_tpu.plan.logical import AggregateExpression
        if scan is None:
            return None
        post, node = [], plan
        while isinstance(node, (L.Sort, L.Limit, L.Filter)):
            post.append(node)
            node = node.children[0]
        if not isinstance(node, L.Aggregate):
            return None
        agg = node
        pre = agg.child
        c = pre
        while isinstance(c, (L.Filter, L.Project)):
            c = c.children[0]
        join_type = dim_plan = None
        if isinstance(c, L.Join):
            side = cls._fact_side(c, scan)
            if side is None:
                return None
            jt = c.join_type
            if not (jt == "inner"
                    or (side == 0 and jt in ("left", "semi", "anti"))
                    or (side == 1 and jt == "right")):
                return None  # output scoped to dim rows: a new fact
                #               batch can flip a dim row's matched-ness,
                #               so no per-delta decomposition is sound
            dim_plan = c.children[1 - side]
            dtext = dim_plan.tree_string()
            if "UDF" in dtext or "InPandas" in dtext or \
                    "ArrowEval" in dtext:
                return None  # dim re-executes per delta; arbitrary
                #               Python is not provably deterministic
            join_type = jt
        elif c is not scan:
            return None

        keys = [(ge.name, ge.dtype) for ge in agg.group_exprs]
        if len({n for n, _ in keys}) != len(keys):
            return None  # duplicate key names would mis-merge
        if any(n.startswith("__p") or n == "__wm" for n, _ in keys):
            return None  # reserved partial/watermark column names
        partial_aggs: List = []   # Alias(AggregateExpression, pname)
        merge_aggs: List = []
        final_tail: List = []
        partial_cols: List[Tuple[str, object]] = []

        def add(pname, update_func, merge_cls):
            ae = AggregateExpression(update_func)
            partial_aggs.append(Alias(ae, pname))
            partial_cols.append((pname, ae.dtype))
            merge_aggs.append(Alias(AggregateExpression(
                merge_cls(UnresolvedColumn(pname))), pname))

        for i, e in enumerate(agg.agg_exprs):
            name = e.name
            inner = e.children[0] if isinstance(e, Alias) else e
            if not isinstance(inner, AggregateExpression):
                return None
            func = inner.func
            child = func.child
            if child is not None and child.dtype.is_decimal:
                return None  # sum(decimal) widens per level; no merge form
            if isinstance(func, ag.Average):
                sname, cname = f"__p{i}s", f"__p{i}c"
                add(sname, ag.Sum(Cast(child, dts.FLOAT64)), ag.Sum)
                add(cname, ag.Count(child), ag.Sum)
                final_tail.append(Alias(
                    Divide(UnresolvedColumn(sname),
                           UnresolvedColumn(cname)), name))
            elif isinstance(func, ag.Sum):
                add(f"__p{i}", ag.Sum(child), ag.Sum)
                final_tail.append(Alias(UnresolvedColumn(f"__p{i}"),
                                        name))
            elif isinstance(func, ag.Count):
                add(f"__p{i}", ag.Count(child), ag.Sum)
                final_tail.append(Alias(UnresolvedColumn(f"__p{i}"),
                                        name))
            elif isinstance(func, ag.Min):
                add(f"__p{i}", ag.Min(child), ag.Min)
                final_tail.append(Alias(UnresolvedColumn(f"__p{i}"),
                                        name))
            elif isinstance(func, ag.Max):
                add(f"__p{i}", ag.Max(child), ag.Max)
                final_tail.append(Alias(UnresolvedColumn(f"__p{i}"),
                                        name))
            else:
                return None  # first/last/collect/moments: order- or
                #               shape-dependent; no safe delta merge yet

        partial_schema = keys + partial_cols
        merge_keys = [Alias(UnresolvedColumn(n), n) for n, _ in keys]
        final_exprs = [UnresolvedColumn(n) for n, _ in keys] + final_tail

        # windowed shape: a tumbling functions.window bucket pair among
        # the group keys — eviction arms only when the watermark delay
        # conf is set and exactly ONE end edge exists (two different
        # windows in one key set have no single watermark)
        window_end = delay_us = None
        if watermark_delay_us is not None and watermark_delay_us >= 0:
            from spark_rapids_tpu.ops.datetime_ops import TimeWindow
            ends = []
            for ge in agg.group_exprs:
                inner = ge.children[0] if isinstance(ge, Alias) else ge
                if isinstance(inner, TimeWindow) and \
                        inner.field == "end" and \
                        inner.slide_us >= inner.window_us:
                    ends.append(ge.name)
            if len(ends) == 1:
                window_end = ends[0]
                delay_us = int(watermark_delay_us)

        # mergeable top-N: post chain exactly Limit <- Sort, sort keys
        # bare group-key references covering every key (total order
        # over output rows -> trimmed merges provably reproduce the
        # one-shot answer; see class docstring).  Never combined with
        # watermark eviction: trimming to n keys BEFORE eviction could
        # under-fill the limit the one-shot answer fills after its
        # filter — eviction already bounds windowed state anyway.
        trim_n = trim_sort = None
        if window_end is None and len(post) == 2 and keys and \
                isinstance(post[0], L.Limit) and \
                isinstance(post[1], L.Sort) and \
                0 < post[0].n <= int(topn_cap):
            from spark_rapids_tpu.ops.expressions import BoundReference
            n_keys = len(keys)
            ords = []
            for oe, _, _ in post[1].orders:
                if isinstance(oe, BoundReference) and \
                        oe.ordinal < n_keys:
                    ords.append(oe.ordinal)
                else:
                    ords = None
                    break
            if ords is not None and set(ords) == set(range(n_keys)):
                trim_n = post[0].n
                trim_sort = post[1]

        spec = cls(agg, pre, post, partial_aggs, merge_keys, merge_aggs,
                   final_exprs, partial_schema, join_type=join_type,
                   dim_plan=dim_plan, window_end=window_end,
                   delay_us=delay_us, trim_n=trim_n,
                   trim_sort=trim_sort)
        # the decomposition must reproduce the original output schema
        # exactly — name or dtype drift means the merge form is not the
        # same query, so refuse it rather than answer differently
        try:
            probe = spec.result_plan([])
        except Exception:
            return None
        if [(n, dt.name) for n, dt in probe.schema] != \
                [(n, dt.name) for n, dt in plan.schema]:
            return None
        return spec

    # -- plan builders ----------------------------------------------------
    def _trimmed(self, node):
        """The proven top-N state bound applied to a partial plan: the
        group keys lead the partial schema at the same ordinals as the
        aggregate output, so the post chain's bound sort keys transfer
        verbatim.  Identity when the trim was refused."""
        from spark_rapids_tpu.plan import logical as L
        if self.trim_n is None:
            return node
        return L.Limit(self.trim_n,
                       L.Sort(list(self.trim_sort.orders), node))

    def partial_plan(self, scan, paths, batches=None):
        """Partial aggregate over ONLY ``paths`` (the delta).  For a
        delta-join the cloned spine keeps the dimension subtree SHARED
        (node identity — see ``_replace_scan``), so its stage ids stay
        spliceable and its in-memory batch ids stay fingerprintable.
        With ``batches`` (a fleet round's shared-ingest pull of those
        same paths) the scan is replaced by an InMemoryRelation over
        them — same schema, zero additional source pulls."""
        from spark_rapids_tpu.plan import logical as L
        rel = None
        if batches is not None:
            rel = L.InMemoryRelation(list(batches), list(scan.schema))
        child = _replace_scan(self.pre_root, scan, paths,
                              replacement=rel)
        return self._trimmed(L.Aggregate(list(self.agg.group_exprs),
                                         list(self.partial_aggs),
                                         child))

    def merge_plan(self, batches):
        """Re-aggregate (old-state ⊕ delta) partial rows into the next
        epoch's state — the aggregate merge discipline over an
        in-memory union of partial batches."""
        from spark_rapids_tpu.plan import logical as L
        rel = L.InMemoryRelation(batches, self.partial_schema)
        return self._trimmed(
            L.Aggregate(list(self.merge_keys), list(self.merge_aggs),
                        rel))

    def evict_plan(self, state_batches, watermark: int):
        """Watermark eviction as an engine plan: keep only buckets
        whose window end is strictly AFTER the watermark.  Runs
        through the full exec path (string keys, validity, the mesh
        when one is up) instead of a hand-rolled host row filter.
        The watermark rides as a DATA column (``__wm``), not a
        literal: a literal would bake each tick's watermark into the
        jit signature and recompile the evict stage every tick —
        column-vs-column keeps one stable compiled program for the
        life of the standing query."""
        from spark_rapids_tpu.columnar import dtypes as dts
        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        from spark_rapids_tpu.columnar.column import Column
        from spark_rapids_tpu.ops.expressions import UnresolvedColumn
        from spark_rapids_tpu.ops.predicates import (GreaterThan,
                                                     IsNull, Or)
        from spark_rapids_tpu.plan import logical as L
        aug = []
        for b in state_batches:
            cols = dict(b.columns)
            cap = next(iter(cols.values())).capacity if cols else 1
            cols["__wm"] = Column.from_numpy(
                np.full(b.nrows, int(watermark), dtype=np.int64),
                dtype=dts.TIMESTAMP_US, capacity=cap)
            aug.append(ColumnarBatch(cols, b.nrows))
        rel = L.InMemoryRelation(
            aug, self.partial_schema + [("__wm", dts.TIMESTAMP_US)])
        # Kleene OR keeps NULL-end buckets: a null event time interns
        # as its own group (the engine's null-key semantics) and has
        # no position on the event-time axis — it can never expire.
        # A bare `end > wm` would evaluate null for those rows and
        # the filter's keep-mask discipline would silently evict a
        # real data bucket (answers would then diverge from one-shot).
        cond = Or(IsNull(UnresolvedColumn(self.window_end)),
                  GreaterThan(UnresolvedColumn(self.window_end),
                              UnresolvedColumn("__wm")))
        return L.Project(
            [UnresolvedColumn(n) for n, _ in self.partial_schema],
            L.Filter(cond, rel))

    def result_plan(self, state_batches):
        """Finalize projection over the merged state (avg = sum/count)
        with the post-aggregate operator chain re-applied."""
        from spark_rapids_tpu.plan import logical as L
        rel = L.InMemoryRelation(state_batches, self.partial_schema)
        node = L.Project(list(self.final_exprs), rel)
        for op in reversed(self.post_ops):
            if isinstance(op, L.Sort):
                node = L.Sort(list(op.orders), node)
            elif isinstance(op, L.Limit):
                node = L.Limit(op.n, node)
            else:
                node = L.Filter(op.condition, node)
        return node


# ---------------------------------------------------------------- the runner --

class _TickDegraded(Exception):
    """Internal: the incremental path cannot proceed (no state, state
    dropped, fingerprint moved) — fall through to full recompute
    WITHOUT counting a rollback (nothing provisional was written)."""


class MicroBatchRunner:
    """One standing query over an append-only input.

    ``session.incremental(df)`` → runner; ``runner.tick(new_paths)``
    ingests the appended files and returns the query's result over
    everything ingested so far, as a DataFrame over the materialized
    result (cheap to ``collect()``/``to_pandas()``).  Ticks serialize
    per runner; each execution inside a tick is an ordinary query to
    the rest of the engine (admission, budgets, ladder, watchdog)."""

    def __init__(self, session, df, fact=None,
                 watermark_delay_ms=None):
        from spark_rapids_tpu.config import rapids_conf as rc
        self.session = session
        self.df = df
        conf = session.conf
        self.enabled = bool(conf.get(rc.INCREMENTAL_ENABLED)) and \
            getattr(session, "memory_catalog", None) is not None
        self.store: Optional[IncrementalStateStore] = \
            IncrementalStateStore(session) if self.enabled else None
        # the append target: the plan's unique file scan, or the one a
        # multi-scan plan (fact⋈dim over two tables) designates via
        # ``fact`` (any path already in the fact table's file list)
        self._scan = _find_fact_scan(df.plan, fact)
        if fact is not None and self._scan is None:
            # fail fast with the candidates: swallowing this would
            # surface ticks later with an error telling the user to
            # pass the fact= they already passed
            cands = [s.paths for s in _file_scans(df.plan)]
            raise ValueError(
                f"fact={fact!r} resolves to no unique file scan of "
                "this plan (typo, relative-vs-absolute path, or the "
                "path appears in several tables); scans present: "
                + (str(cands) if cands else "none"))
        # the per-runner override lets fleet subscribers over ONE
        # shared ingest evict on their own schedules (watermark
        # independence); the session conf stays the default
        delay_ms = int(conf.get(rc.INCREMENTAL_WATERMARK_DELAY_MS)) \
            if watermark_delay_ms is None else int(watermark_delay_ms)
        self._spec = _AggSpec.analyze(
            df.plan, self._scan,
            watermark_delay_us=(delay_ms * 1000 if delay_ms >= 0
                                else None),
            topn_cap=int(conf.get(rc.INCREMENTAL_TOPN_MAX_STATE_ROWS))
        ) if self.enabled else None
        self._initial = list(self._scan.paths) if self._scan is not None \
            else []
        self._paths: List[str] = []   # committed (ingested) input set
        self._ticked = False
        self._lock = threading.Lock()
        self._phase_log: list = []  # (name, t0_ns, dur_ns) per tick
        self.last_tick_info: Dict[str, object] = {}
        # exactly-once emission surface: the committed SinkCommit of
        # the latest tick (result df attached), and an optional
        # user callback invoked after every commit — the callback runs
        # in tick SCOPE but not tick EXECUTION, so ordinary queries it
        # issues (a sink-side lookup) hit the serving caches normally
        self.last_sink_commit: Optional[SinkCommit] = None
        self.on_commit = None
        self._ingest: Optional[SharedIngest] = None  # per-tick loan

    # ------------------------------------------------------------- helpers --
    def _fingerprint(self, paths) -> str:
        from spark_rapids_tpu.io.readers import scan_input_meta
        return self._state_fingerprint(scan_input_meta(paths))

    def _dim_fingerprint(self) -> str:
        """The delta-join dimension subtree's input fingerprint
        (file triples statted now + in-memory batch identities); ""
        for non-join shapes."""
        if self._spec is None or self._spec.dim_plan is None:
            return ""
        from spark_rapids_tpu.robustness.checkpoint import (
            input_fingerprint)
        return input_fingerprint(self._spec.dim_plan)

    def _state_fingerprint(self, meta, dim_fp: Optional[str] = None
                           ) -> str:
        """Identity of everything the standing state was computed
        from: the fact scan's already-statted ``scan_input_meta``
        triples (one walk serves both the staleness check and the new
        epoch's fingerprint within a tick) plus — for delta-joins —
        the dimension subtree's input fingerprint.  ``dim_fp`` must be
        the PRE-READ stat (the tick captures it once before its first
        execution and reuses it for both the staleness check and the
        new epoch's stamp): statting the dim side after the read would
        stamp post-mutation identity onto state computed from
        pre-mutation bytes and hide the mutation forever — the same
        stat-before-read rule the fact side follows."""
        from spark_rapids_tpu.io.readers import input_signature
        sig = input_signature(sorted(meta))
        if self._spec is not None and self._spec.dim_plan is not None:
            sig += "\x1f" + (dim_fp if dim_fp is not None
                             else self._dim_fingerprint())
        return hashlib.sha256(sig.encode()).hexdigest()

    def _run(self, plan, splice: bool = False) -> list:
        """Execute one logical plan through the full robustness stack.
        With ``splice`` the persistent store rides as the query's
        checkpoint manager, so unchanged (input-fingerprinted) subtrees
        restore instead of re-running."""
        from spark_rapids_tpu.api.dataframe import DataFrame
        df = DataFrame(self.session, plan)
        with tick_execution_scope():
            if splice and self.store is not None and \
                    getattr(self.session, "mesh", None) is not None:
                self.store._splice_active = True
                self.session.checkpoints = self.store
                try:
                    # stale-entry pruning at commit is only sound when
                    # the FINAL attempt really ran on the mesh; the
                    # planner signals that via
                    # note_distributed_complete on THIS thread (a
                    # shared session attribute would race with
                    # concurrent queries), and clear() (layout rung)
                    # vetoes it for the rest of the tick
                    return df._execute_batches()
                finally:
                    self.session.checkpoints = None
            return df._execute_batches()

    @staticmethod
    def _concat(batches):
        from spark_rapids_tpu.ops.concat import concat_batches
        live = [b for b in batches if b.nrows]
        if not live:
            return None
        return concat_batches(live) if len(live) > 1 else live[0]

    def _result_df(self, batches, schema):
        from spark_rapids_tpu.api.dataframe import DataFrame
        from spark_rapids_tpu.plan import logical as L
        return DataFrame(self.session,
                         L.InMemoryRelation(batches, list(schema)))

    def _ingest_for(self, paths) -> Optional[SharedIngest]:
        """This tick's shared-ingest loan, iff it is usable for
        ``paths``: same file set, and a fact scan whose read shape the
        pulled batches reproduce exactly (full schema, no metadata
        columns, no pushdown pruning).  None falls back to the
        runner's own pull — correct, just unshared."""
        ing = self._ingest
        scan = self._scan
        if ing is None or scan is None or \
                set(ing.paths) != set(paths):
            return None
        required = getattr(scan, "required_columns", None)
        if scan.file_meta or scan.pushed_filters or (
                required is not None and
                any(n not in required for n, _ in scan.schema)):
            return None
        if [(n, d.name) for n, d in scan.schema] != ing.schema_names:
            return None
        return ing

    # ---------------------------------------------------------------- ticks --
    def tick(self, new_paths=(), _ingest=None):
        """Ingest ``new_paths`` (appended files) and return the result
        over everything ingested so far.  Every execution the RUNNER
        issues inside the tick runs under the tick-execution marker:
        the session ResultCache and direct SharedStageCache
        registration are bypassed (no lookup, no store) — a tick must
        never answer from a pre-tick entry, and its crash-consistency
        contract rests on the epoch store alone.  (Cross-query sharing
        of tick work happens instead through the epoch tier: committed
        entries published at commit, borrowed via epoch_restore.)
        ``_ingest`` is the fleet's shared-ingest loan for this round
        (internal)."""
        with self._lock:
            _TICK_TLS.depth = getattr(_TICK_TLS, "depth", 0) + 1
            self._ingest = _ingest
            try:
                return self._tick(
                    [new_paths] if isinstance(new_paths, str)
                    else list(new_paths))
            finally:
                self._ingest = None
                _TICK_TLS.depth -= 1

    def _phased(self, name: str, fn, *args, **kwargs):
        """Run one tick phase, timing it for the span runtime.  Phase
        records are EMITTED only at tick end (_tick): a phase contains
        whole query envelopes whose own spans drain mid-tick, so an
        open phase span would smear into an inner query's trace —
        deferred emission keeps tick phases in the tick's own scope."""
        from spark_rapids_tpu.utils import tracing
        if not tracing._armed:
            return fn(*args, **kwargs)
        import time as _t
        t0 = _t.perf_counter_ns()
        try:
            return fn(*args, **kwargs)
        finally:
            self._phase_log.append(
                (name, t0, _t.perf_counter_ns() - t0))

    def _tick(self, new_paths):
        from spark_rapids_tpu.utils import tracing
        if not tracing._armed:
            return self._tick_impl(new_paths)
        import time as _t
        self._phase_log = []
        t0 = _t.perf_counter()
        try:
            return self._tick_impl(new_paths)
        finally:
            for name, t0_ns, dur_ns in self._phase_log:
                tracing.emit_span(f"incremental.{name}", t0_ns,
                                  dur_ns, is_async=False)
            self._phase_log = []
            ep = self.store.epoch if self.store is not None else 0
            tracing.finish_scope(self.session, f"tick-e{ep}",
                                 (_t.perf_counter() - t0) * 1e3)

    def _tick_impl(self, new_paths):
        from spark_rapids_tpu.plan import logical as L
        if new_paths and self._scan is None:
            raise ValueError(
                "tick(new_paths) needs an append-target file scan; "
                "this plan has none, or several — designate one with "
                "session.incremental(df, fact=<path in the fact "
                "table's file list>)")
        base = list(self._paths) if self._ticked else list(self._initial)
        seen = set(base)
        delta = []
        for p in new_paths:
            if p not in seen:  # dedupe within the call too: a watcher
                seen.add(p)    # emitting [p, p] must not ingest twice
                delta.append(p)
        target = base + delta
        if not self._ticked:
            delta = list(target)  # the first tick ingests everything
        incremental_metrics.bump("ticks")
        info: Dict[str, object] = {"deltaFiles": len(delta),
                                   "mode": "full", "reused": False}

        if self.store is None:
            # incremental.enabled=false parity: every tick is a plain
            # full execution, no standing state (and no sink log — the
            # exactly-once contract needs the epoch store)
            out = self._run(self._full_plan(target))
            self._finish(target, info)
            self.last_sink_commit = None
            return self._result_df(out, self.df.plan.schema)

        try:
            out = self._tick_body(target, delta, info)
        except _TickDegraded:
            out = self._full_or_rollback(target, info)
        except Exception as exc:  # noqa: BLE001 - every escape degrades
            # mid-tick fault (exhausted ladder, fatal, admission
            # reject): roll back to the committed epoch, then answer
            # with a full recompute — never partial state, never wrong
            # bytes.  A full recompute that ALSO fails re-raises with
            # the epoch still intact.
            self.store.rollback(f"{type(exc).__name__}: {exc}")
            info["rollbackFrom"] = f"{type(exc).__name__}: {exc}"
            out = self._full_or_rollback(target, info)
        self._phased("commit", self.store.commit, info["mode"],
                     info["deltaFiles"], info["reused"],
                     info.get("evictedBuckets", 0),
                     info.get("evictedRows", 0),
                     info.get("evictedBytes", 0))
        res = self._result_df(out, self.df.plan.schema)
        sc = self.store.last_sink
        if sc is not None:
            sc.df = res
            info["sinkEpoch"] = sc.epoch
            info["sinkReplayed"] = bool(sc.replayed)
        self.last_sink_commit = sc
        self._finish(target, info)
        if sc is not None and self.on_commit is not None:
            # user code: runs in tick SCOPE (depth) but not tick
            # EXECUTION, so ordinary queries it issues cache normally;
            # a callback fault must not un-commit the epoch — it
            # already committed — so it propagates to the caller as-is
            self.on_commit(sc)
        return res

    def _finish(self, target, info) -> None:
        self._paths = list(target)
        if self._scan is not None:
            # keep the standing plan's own scan in step, so a direct
            # df.to_pandas() (the oracle form) sees the ingested set
            self._scan.paths = list(target)
        self._ticked = True
        info["epoch"] = self.store.epoch if self.store is not None else 0
        self.last_tick_info = dict(info)

    def _full_plan(self, paths):
        if self._scan is None:
            return self.df.plan
        return _replace_scan(self.df.plan, self._scan, paths)

    def _tick_body(self, target, delta, info) -> list:
        """The incremental path; raises _TickDegraded when the
        committed epoch cannot carry this tick."""
        if self._spec is None or not self._ticked:
            raise _TickDegraded
        spec = self._spec
        state = self.store.get_state()
        if state is None:
            raise _TickDegraded
        from spark_rapids_tpu.io.readers import scan_input_meta
        # one stat walk per file per tick: the committed-set walk
        # serves the staleness check, and the target fingerprint
        # derives from it plus the (small) delta walk
        meta_committed = scan_input_meta(self._paths)
        # dim side statted ONCE, before any execution: the same
        # pre-read snapshot serves the staleness check AND the new
        # epoch's stamp below — a post-execution re-stat could stamp a
        # mid-tick dim mutation's identity onto state computed from
        # the old bytes, hiding the mutation from every later check
        dim_fp = self._dim_fingerprint()
        if self.store.state_fingerprint != \
                self._state_fingerprint(meta_committed, dim_fp):
            # an already-ingested file (or the dimension side of a
            # delta-join) changed out-of-band (rewritten, truncated,
            # even same-size — mtime catches it): the state no longer
            # describes the input
            self.store.drop_state("input-fingerprint-moved")
            raise _TickDegraded
        watermark = self.store.state_watermark
        if delta:
            # stat BEFORE read: if a delta file mutates between the
            # stat and the scan, the committed fingerprint describes
            # the PRE-mutation bytes and the next tick's staleness
            # check drops the state — the safe failure mode.  Statting
            # after the read would stamp post-mutation identity onto
            # pre-mutation state and hide the mutation forever.  A
            # fleet shared-ingest loan carries its own PRE-READ stat
            # (the fleet statted before its one pull) — zero source
            # pulls and zero stats on this runner's account.
            ing = self._ingest_for(delta)
            meta_delta = list(ing.meta) if ing is not None \
                else scan_input_meta(delta)
            # delta-join: only the NEW fact batches join the unchanged
            # dimension state — the delta runs with the store riding
            # as checkpoint manager, so completed dim subtrees splice
            # from committed lineage instead of re-running
            partial = self._phased(
                "join.delta" if spec.join_type is not None else "delta",
                self._run, spec.partial_plan(
                    self._scan, delta,
                    batches=ing.batches if ing is not None else None),
                splice=spec.join_type is not None)
            merged = self._phased(
                "topn.merge" if spec.trim_n is not None else "merge",
                self._run, spec.merge_plan(
                    [state] + [b for b in partial if b.nrows]))
            state = self._concat(merged)
            if state is None:
                from spark_rapids_tpu.columnar.batch import empty_batch
                state = empty_batch(spec.partial_schema)
            state, watermark = self._advance_watermark(state, watermark,
                                                       info)
            self.store.put_state(
                state,
                self._state_fingerprint(meta_committed + meta_delta,
                                        dim_fp),
                watermark=watermark)
        out = self._phased("finalize", self._run,
                           spec.result_plan([state]))
        # stage the emission — a fault here (kill/rot in the
        # compute→commit window) degrades the tick exactly like any
        # other mid-tick fault: rollback, recompute, ONE commit
        self._phased("sink", self.store.sink_prepare, out)
        # counted only once the WHOLE incremental path answered: a
        # finalize-run fault degrades this tick to full recompute and
        # must not leave it double-counted in the reuse ratio
        info["mode"] = "incremental"
        info["reused"] = True
        info["shape"] = spec.shape
        if watermark is not None:
            info["watermark"] = int(watermark)
        incremental_metrics.bump("incrementalTicks")
        self._bump_shape_ticks(spec)
        return out

    @staticmethod
    def _bump_shape_ticks(spec) -> None:
        for field, on in (("joinTicks", spec.join_type is not None),
                          ("windowTicks", spec.window_end is not None),
                          ("topnTicks", spec.trim_n is not None)):
            if on:
                incremental_metrics.bump(field)

    def _advance_watermark(self, state, committed, info):
        """Windowed shapes with eviction armed: advance the watermark
        to max(window end seen) − delay (never regressing below the
        committed floor — monotone by construction) and evict expired
        buckets from the merged state via an engine Filter execution.
        The evicted batch is what put_state registers, so eviction and
        advance are one provisional unit that commits — or rolls
        back — atomically with the epoch.  Identity for non-windowed
        shapes."""
        spec = self._spec
        if spec.window_end is None or state.nrows == 0:
            return state, committed
        col = state.columns[spec.window_end]
        ends = np.asarray(col.host_values())[:state.nrows]
        valid = col.host_validity()
        if valid is not None:
            ends = ends[np.asarray(valid)[:state.nrows]]
        if ends.size == 0:
            return state, committed  # all-null buckets never expire
        cand = int(ends.max()) - int(spec.delay_us)
        wm = cand if committed is None else max(int(committed), cand)
        expired = ends[ends <= wm]
        info["watermark"] = wm
        if expired.size == 0:
            return state, wm
        rows_before = int(state.nrows)
        # payload buffers are capacity-padded, so attribute bytes
        # row-proportionally instead of diffing padded buffer sizes
        bytes_before = sum(a.nbytes for a in
                           _batch_payload(state).values())
        kept = self._phased("window.evict", self._run,
                            spec.evict_plan([state], wm))
        state = self._concat(kept)
        if state is None:
            from spark_rapids_tpu.columnar.batch import empty_batch
            state = empty_batch(spec.partial_schema)
        rows_evicted = max(0, rows_before - int(state.nrows))
        # units: a BUCKET is one expired time window (distinct end
        # edge); each bucket spans one state ROW per group-key tuple,
        # and bytes are attributed per row — evictedRows is the
        # denominator that makes evictedBytes ratios meaningful
        info["evictedBuckets"] = info.get("evictedBuckets", 0) + \
            int(np.unique(expired).size)
        info["evictedRows"] = info.get("evictedRows", 0) + rows_evicted
        info["evictedBytes"] = info.get("evictedBytes", 0) + \
            bytes_before * rows_evicted // max(rows_before, 1)
        return state, wm

    def _full_or_rollback(self, target, info) -> list:
        """Degraded recompute with the leak guard: a full recompute
        that dies mid-flight must not leave ITS provisional writes
        (the rebuilt state it put before the finalize run failed)
        pinned in the catalog — roll them back before re-raising, so
        the tick fails with the committed epoch exactly intact."""
        try:
            return self._tick_full(target, info)
        except Exception as exc:  # noqa: BLE001 - re-raised below
            self.store.rollback(
                f"degraded-recompute-failed: {type(exc).__name__}: "
                f"{exc}")
            raise

    def _tick_full(self, target, info) -> list:
        """Full recompute: correct under every degradation.  With a
        delta-capable plan the state rebuilds from one partial pass
        over ALL inputs (result derives from it); otherwise the
        original plan re-runs with the lineage splice restoring
        unchanged subtrees.  Windowed shapes advance+evict against the
        SAME committed watermark floor the incremental path would
        have used, so a degraded tick's answer is identical to the
        incremental tick it replaced (expired buckets rebuilt from
        history evict right back out — no resurrection)."""
        incremental_metrics.bump("fullRecomputes")
        info["mode"] = "full"
        # a rolled-back incremental attempt may have advanced/evicted
        # into this SAME info dict before it died; those provisional
        # facts were discarded with the rollback, and the recompute
        # recounts its own from scratch — without the reset the one
        # commit would stamp roughly double onto StateWatermark and
        # the watermarkEvicted* counters
        for k in ("watermark", "evictedBuckets", "evictedRows",
                  "evictedBytes"):
            info.pop(k, None)
        if self._spec is not None:
            spec = self._spec
            info["shape"] = spec.shape
            # a fleet loan covers this recompute only when it spans
            # the WHOLE target (the first tick: delta == everything);
            # a degraded later tick must re-read history it owns
            ing = self._ingest_for(target)
            # stat before read (see _tick_body): a mid-scan mutation
            # must leave the state stamped with PRE-mutation identity
            fp = self._state_fingerprint(list(ing.meta)) \
                if ing is not None else self._fingerprint(target)
            partial = self._phased(
                "recompute", self._run,
                spec.partial_plan(
                    self._scan, target,
                    batches=ing.batches if ing is not None else None),
                splice=spec.join_type is not None)
            state = self._concat(partial)
            if state is None:
                from spark_rapids_tpu.columnar.batch import empty_batch
                state = empty_batch(spec.partial_schema)
            state, watermark = self._advance_watermark(
                state, self.store.state_watermark, info)
            self.store.put_state(state, fp, watermark=watermark)
            out = self._phased("finalize", self._run,
                               spec.result_plan([state]))
            self._phased("sink", self.store.sink_prepare, out)
            return out
        # reuse detection reads the STORE-LOCAL resume counter, not the
        # process-global one: concurrent runners must not contaminate
        # each other's reusedState flag
        info["shape"] = "splice"
        r0 = self.store.local["resumes"]
        out = self._phased("recompute", self._run,
                           self._full_plan(target), splice=True)
        info["reused"] = self.store.local["resumes"] > r0
        self._phased("sink", self.store.sink_prepare, out)
        return out

    def close(self) -> None:
        """Release the standing state (the runner's epochs die here;
        the session's catalog sweep would collect them at stop()
        anyway)."""
        if self.store is not None:
            self.store.close()
