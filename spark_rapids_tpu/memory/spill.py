"""Tiered spill framework: HBM -> host RAM -> disk.

Counterpart of the reference's RapidsBufferCatalog / RapidsBufferStore chain
(RapidsBufferCatalog.scala:40, RapidsBufferStore.scala:41, Device/Host/Disk
stores) and SpillableColumnarBatch (SpillableColumnarBatch.scala:29), with
one structural difference dictated by the platform: XLA owns HBM and there
is no RMM-style allocation-failure callback, so spilling is *watermark-
driven* — the catalog tracks bytes held by spillable batches and proactively
moves the lowest-priority ones to host (numpy) and then disk (npz files)
when the budget is exceeded.  The analog of the reference's
``DeviceMemoryEventHandler.onAllocFailure`` retry loop is
``ensure_budget()``, which callers invoke before large allocations.

Spill priorities mirror SpillPriorities.scala: shuffle outputs coldest,
actively-iterated batches hottest.
"""

from __future__ import annotations

import heapq
import itertools
import os
import tempfile
import threading
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column

# storage tiers (RapidsBuffer.scala:53 StorageTier)
DEVICE = "DEVICE"
HOST = "HOST"
DISK = "DISK"

# spill priorities (SpillPriorities.scala:26-61)
OUTPUT_FOR_SHUFFLE_INITIAL_PRIORITY = -1000
AGGREGATE_INTERMEDIATE_PRIORITY = 0
ACTIVE_ON_DECK_PRIORITY = 1000
# stage checkpoints register at or below this priority
# (robustness/checkpoint.py CHECKPOINT_PRIORITY); the cross-query
# eviction floor applies to handles in this class
CHECKPOINT_TIER_MAX = -1500
# session-persistent incremental-ingest state (robustness/incremental.py)
# is the coldest class of all: standing state outlives any one query, so
# under HBM pressure it leaves the device before even per-query
# checkpoints — restores pay a host round trip, live queries never wait
INCREMENTAL_STATE_PRIORITY = -2000


class IntegrityMetrics:
    """Process-wide spill-integrity counters (checksum verification
    failures per tier), surfaced by tools/profiling."""

    def __init__(self):
        self._lock = threading.Lock()
        self.corruption_counts: Dict[str, int] = {}

    def bump(self, tier: str) -> None:
        with self._lock:
            self.corruption_counts[tier] = \
                self.corruption_counts.get(tier, 0) + 1

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.corruption_counts)

    def reset(self) -> None:
        with self._lock:
            self.corruption_counts.clear()


integrity_metrics = IntegrityMetrics()


def _payload_checksum(payload: dict, nrows: int) -> int:
    """crc32 over the host payload in canonical form: buffer keys in
    sorted order, every buffer's raw bytes, plus the row count — so
    any single flipped bit anywhere fails verification.  Canonical
    means identical across representations of the same batch: non-
    array entries and zero-length buffers are skipped (the disk frame
    codec stores empty buffers as absent, and ``__nrows`` rides the
    handle, not the restored dict)."""
    crc = zlib.crc32(str(int(nrows)).encode())
    for key in sorted(payload):
        v = payload[key]
        if not isinstance(v, np.ndarray) or v.size == 0:
            continue
        crc = zlib.crc32(key.encode(), crc)
        a = np.ascontiguousarray(v)
        crc = zlib.crc32(a.view(np.uint8).reshape(-1), crc)
    return crc & 0xFFFFFFFF


def _emit_corruption(tier: str, buf_id: int, detail: str) -> None:
    """Count + event-log a checksum failure (SpillCorruption events
    feed the profiling health check with per-query attribution)."""
    integrity_metrics.bump(tier)
    from spark_rapids_tpu.utils.events import emit_on_session
    emit_on_session("SpillCorruption", tier=tier, bufId=buf_id,
                    detail=detail)


class SpillableHandle:
    """One registered batch, resident at exactly one tier."""

    _ids = itertools.count()

    def __init__(self, catalog: "SpillableBatchCatalog",
                 batch: ColumnarBatch, priority: int,
                 owner: Optional[int] = None):
        self.id = next(SpillableHandle._ids)
        self.catalog = catalog
        self.priority = priority
        # owning query (the QueryContext owner ident that registered
        # this batch; None outside any query scope).  Drives per-owner
        # budgets and cross-query eviction-floor isolation.
        self.owner = owner
        self.tier = DEVICE
        self.size_bytes = batch.device_size_bytes()
        # transient shuffle-wire reservation (ColumnarBatch
        # .transient_wire_bytes): a just-received exchange batch still
        # pins its packed lane payloads in HBM, so backpressure must
        # see the larger footprint while the batch sits at DEVICE.  The
        # payload is never spilled — it dies with the exchange program
        # — so leaving DEVICE releases the reservation for good.
        self.wire_bytes = int(
            getattr(batch, "transient_wire_bytes", 0) or 0)
        self.last_access = 0
        self._device: Optional[ColumnarBatch] = batch
        self._host: Optional[dict] = None
        # HOST tier, compressed form: when the catalog's host codec is
        # on, the payload lives as ONE frame-codec blob (the same
        # self-describing frame format the DISK tier writes) instead of
        # raw numpy buffers — checkpoints and incremental state demote
        # through this catalog, so they inherit the codec for free
        self._host_frame: Optional[bytes] = None
        self._host_stored = 0
        self._disk_path: Optional[str] = None
        # crc32 of the host payload, stamped when the batch leaves
        # DEVICE and verified on every HOST->DEVICE / DISK->HOST
        # restore (None until first spill, or with integrity off)
        self._integrity_crc: Optional[int] = None
        self._schema = batch.schema
        self._capacity = batch.capacity
        # deferred (device-resident) counts stay deferred while the
        # batch sits at the DEVICE tier; spilling materializes (the
        # host payload needs the concrete count anyway)
        self._row_count = batch.row_count
        self.closed = False

    @property
    def nrows(self) -> int:
        return int(self._row_count)

    @property
    def row_count(self):
        return self._row_count

    @property
    def nrows_bound(self) -> int:
        """Sync-free upper bound on nrows (capacity when deferred)."""
        if self._row_count.is_concrete:
            return int(self._row_count)
        return self._capacity

    # -------------------------------------------------------------- movement --
    def _to_host_payload(self) -> dict:
        b = self._device
        payload = {"__nrows": self.nrows}
        for name, col in b.columns.items():
            # host_* readers keep still-host columns bit-exact and skip
            # the device fetch entirely
            payload[f"{name}.data"] = col.host_values()
            v = col.host_validity()
            if v is not None:
                payload[f"{name}.validity"] = v
            o = col.host_offsets()
            if o is not None:
                payload[f"{name}.offsets"] = o
        return payload

    def _rebuild(self, get) -> ColumnarBatch:
        cols = {}
        for name, dt in self._schema:
            data = get(f"{name}.data")
            if data is None:
                # the frame codec stores zero-length buffers as absent
                # (lens=0); a legitimately empty buffer (e.g. the chars of
                # an all-empty string column) must round-trip as empty, not
                # as None -> asarray(None) crash
                data = np.zeros(
                    0, dtype=np.uint8 if dt.is_string else dt.storage)
            # hand the host buffers straight to Column: it materializes
            # the device copy lazily on first device use
            cols[name] = Column(
                dt, np.ascontiguousarray(data), self.nrows,
                validity=get(f"{name}.validity"),
                offsets=get(f"{name}.offsets"))
        return ColumnarBatch(cols, self.nrows)

    def _frame_columns(self, payload: dict):
        """(dtype_code, data, validity, offsets) per schema column —
        the native frame codec's input layout."""
        from spark_rapids_tpu import native
        return [(native.dtype_code(dt),
                 payload.get(f"{name}.data"),
                 payload.get(f"{name}.validity"),
                 payload.get(f"{name}.offsets"))
                for name, dt in self._schema]

    def _payload_from_frame(self, blob: bytes) -> dict:
        """Decode a self-describing frame blob back into the canonical
        payload dict (raises on a frame that no longer decodes — the
        caller converts that into CorruptionFault)."""
        from spark_rapids_tpu import native
        _, cols = native.deserialize_batch(blob)
        payload = {}
        for (name, dt), (_, d, v, o) in zip(self._schema, cols):
            if d is not None:
                payload[f"{name}.data"] = d if dt.is_string else \
                    d.view(dt.storage)
            if v is not None:
                payload[f"{name}.validity"] = v.view(np.bool_)
            if o is not None:
                payload[f"{name}.offsets"] = o.view(np.int32)
        return payload

    @property
    def stored_bytes(self) -> int:
        """Bytes this handle actually occupies at its current tier —
        the encoded frame size at HOST (codec on) / DISK, the device
        size otherwise.  Budget consumers that meter STANDING state
        (checkpoint.maxBytes, incremental.maxStateBytes) read this so
        compression buys proportionally more retained state."""
        if self.tier == HOST and self._host_frame is not None:
            return self._host_stored
        if self.tier == DISK and self._host_stored:
            return self._host_stored
        return self.size_bytes

    def spill_to_host(self) -> int:
        """Demote to HOST; returns the DEVICE bytes released (the batch
        plus any transient wire reservation — the wire headroom never
        follows the batch to the host tier).  With the catalog's host
        codec on, the payload is kept as ONE compressed frame blob; the
        integrity crc is stamped over the DECODED canonical bytes
        BEFORE encoding, so verification semantics are unchanged."""
        assert self.tier == DEVICE
        payload = self._to_host_payload()
        if self.catalog.integrity_check:
            # stamped exactly once, when the bytes leave the device:
            # every later restore (host or disk) verifies against this
            self._integrity_crc = _payload_checksum(payload, self.nrows)
        if self.catalog.host_codec:
            from spark_rapids_tpu import native
            blob = native.serialize_batch(
                self.nrows, self._frame_columns(payload),
                compress=self.catalog.host_codec)
            self._host_frame = blob
            self._host_stored = len(blob)
            self.catalog.note_host_encoding(self.size_bytes, len(blob))
        else:
            self._host = payload
        self._device = None
        self.tier = HOST
        released = self.size_bytes + self.wire_bytes
        self.wire_bytes = 0
        return released

    def spill_to_disk(self) -> int:
        assert self.tier == HOST
        from spark_rapids_tpu import native
        from spark_rapids_tpu.robustness.faults import SpillIOError
        from spark_rapids_tpu.robustness.inject import fire
        # "spill.disk" fires before any state moves: on failure the
        # batch is still intact at the HOST tier, nothing is lost, and
        # the query driver can retry the whole query
        fire("spill.disk")
        path = os.path.join(self.catalog.spill_dir, f"buf-{self.id}.tcf")
        if self._host_frame is not None:
            # already a self-describing frame (compressed host tier):
            # the disk write is a straight page-out, no re-encode
            blob = self._host_frame
        else:
            blob = native.serialize_batch(
                self.nrows, self._frame_columns(self._host),
                compress=self.catalog.frame_codec)
            self._host_stored = len(blob)
        # torn-write-proof: stage to a temp file, fsync, then rename
        # into place.  A crash anywhere before the rename leaves no
        # file at ``path``, so a partial frame is never restorable.
        tmp = path + ".tmp"
        try:
            os.makedirs(self.catalog.spill_dir, exist_ok=True)
            native.write_spill_file(tmp, blob)
            fd = os.open(tmp, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
            os.replace(tmp, path)
        except OSError as e:
            try:
                if os.path.exists(tmp):
                    os.unlink(tmp)
            except OSError:
                pass
            # disk full / unreachable: re-type for the fault taxonomy
            # (retryable — the host copy is untouched)
            raise SpillIOError(
                f"disk spill of buf-{self.id} failed: {e}") from e
        self._disk_path = path
        self._host = None
        self._host_frame = None
        self.tier = DISK
        return self.size_bytes

    def _verify_payload(self, payload: dict, tier: str) -> None:
        """Checksum gate on every restore: a mismatch DROPS the batch
        (close unlinks any disk file and deregisters) and raises a
        degradable CorruptionFault — the ladder re-runs from source;
        wrong bytes are never returned."""
        if not self.catalog.integrity_check or \
                self._integrity_crc is None:
            return
        got = _payload_checksum(payload, self.nrows)
        if got == self._integrity_crc:
            return
        detail = (f"buf-{self.id}: crc {got:#010x} != stored "
                  f"{self._integrity_crc:#010x}")
        self.close()
        _emit_corruption(tier, self.id, detail)
        from spark_rapids_tpu.robustness.faults import CorruptionFault
        raise CorruptionFault(tier, detail)

    def materialize(self) -> ColumnarBatch:
        """Get the batch back on device (unspilling if needed)."""
        if self.closed:
            raise ValueError("spillable batch already closed")
        self.last_access = self.catalog.next_access_stamp()
        if self.tier == DEVICE:
            return self._device
        from spark_rapids_tpu.utils import tracing
        if tracing._active:
            with tracing.span(f"spill.restore.{self.tier.lower()}"):
                return self._materialize_cold()
        return self._materialize_cold()

    def _materialize_cold(self) -> ColumnarBatch:
        from spark_rapids_tpu.robustness.faults import CorruptionFault
        from spark_rapids_tpu.robustness.inject import fire_mutate
        if self.tier == HOST:
            if self._host_frame is not None:
                # compressed host tier: the chaos hook mutates the
                # frame bytes (as on disk); a frame that no longer
                # decodes is corruption — drop, never guess at bytes
                blob = fire_mutate("spill.corrupt.host",
                                   self._host_frame)
                try:
                    payload = self._payload_from_frame(blob)
                except Exception as e:
                    detail = (f"buf-{self.id}: host frame decode "
                              f"failed: {e}")
                    self.close()
                    _emit_corruption(HOST, self.id, detail)
                    raise CorruptionFault(HOST, detail) from e
            else:
                payload = self._corrupt_point(self._host,
                                              "spill.corrupt.host")
            self._verify_payload(payload, HOST)
            batch = self._rebuild(lambda k: payload.get(k))
        else:
            from spark_rapids_tpu import native
            from spark_rapids_tpu.robustness.faults import SpillIOError
            try:
                blob = native.read_spill_file(self._disk_path)
            except OSError as e:
                raise SpillIOError(
                    f"disk unspill of buf-{self.id} failed: {e}") from e
            blob = fire_mutate("spill.corrupt.disk", blob)
            try:
                payload = self._payload_from_frame(blob)
            except OSError:
                raise
            except Exception as e:
                # a frame that no longer decodes IS corruption (a
                # flipped bit in the compressed stream): drop the
                # batch, never guess at bytes
                detail = f"buf-{self.id}: frame decode failed: {e}"
                self.close()
                _emit_corruption(DISK, self.id, detail)
                raise CorruptionFault(DISK, detail) from e
            self._verify_payload(payload, DISK)
            batch = self._rebuild(lambda k: payload.get(k))
        self.catalog.unspill(self, batch)
        return batch

    @staticmethod
    def _corrupt_point(payload: dict, point: str) -> dict:
        """Chaos hook: offer ONE payload buffer (the first data buffer
        in canonical order) to an armed corrupt rule.  The mutated copy
        replaces the buffer in a shallow-copied dict — the restore sees
        rot, the stored payload object itself is untouched."""
        from spark_rapids_tpu.robustness.inject import fire_mutate
        key = next((k for k in sorted(payload)
                    if isinstance(payload[k], np.ndarray)
                    and payload[k].size > 0), None)
        if key is None:
            return payload
        mutated = fire_mutate(point, payload[key])
        if mutated is payload[key]:
            return payload
        payload = dict(payload)
        payload[key] = mutated
        return payload

    def close(self) -> None:
        if self.closed:
            return
        self.closed = True
        self._device = None
        self._host = None
        self._host_frame = None
        try:
            if self._disk_path and os.path.exists(self._disk_path):
                os.unlink(self._disk_path)
        except OSError:
            # the catalog's session-close sweep collects stragglers a
            # racing unlink left behind
            pass
        finally:
            # deregistration must survive an unlink failure, else the
            # dead handle pins catalog counters for the session's life
            self._disk_path = None
            self.catalog.remove(self)


class SpillableBatchCatalog:
    """Singleton-ish registry with watermark-driven tier demotion.

    ``device_budget``: bytes of HBM this engine lets spillable batches pin
    before demoting the coldest to host; ``host_budget``: same for host RAM
    before demoting to disk (reference `memory.host.spillStorageSize`).
    """

    def __init__(self, device_budget: int = 1 << 34,
                 host_budget: int = 1 << 30,
                 spill_dir: Optional[str] = None,
                 frame_codec: int = 2,
                 disk_write_threads: int = 2,
                 integrity_check: bool = True,
                 checkpoint_floor: int = 0,
                 host_codec: int = 0):
        self.device_budget = device_budget
        self.host_budget = host_budget
        # cross-query isolation floor: device pressure originating
        # from one owner may not demote ANOTHER owner's checkpoint-
        # priority handles below this many device-resident bytes
        # (spark.rapids.tpu.serving.checkpointEvictionFloorBytes)
        self.checkpoint_floor = int(checkpoint_floor)
        # per-owner DEVICE-tier byte budgets (QueryContext installs
        # one for the duration of its query when
        # serving.queryMemoryBudgetBytes is set)
        self._owner_budgets: Dict[int, int] = {}
        # incremental per-owner DEVICE-tier byte counters, maintained
        # alongside device_bytes at every tier transition so the
        # per-register budget check is O(1), not a catalog scan
        self._owner_device: Dict[int, int] = {}
        # spark.rapids.memory.spill.integrityCheck.enabled: checksum
        # every payload leaving DEVICE, verify on every restore
        self.integrity_check = bool(integrity_check)
        # only a directory this catalog created gets rmdir'd at close
        self._owns_spill_dir = spill_dir is None
        # host->disk demotions overlap in a small writer pool: the
        # native pager releases the GIL for serialize+write
        # (spark.rapids.memory.spill.diskWriteThreads)
        self.disk_write_threads = max(int(disk_write_threads), 1)
        # per-session frame codec level for spilled/cached frames
        # (0 raw / 1 zrle / 2 zrle+lzb); sessions set this from
        # spark.rapids.shuffle.compression.codec
        self.frame_codec = frame_codec
        # HOST-tier codec level (spark.rapids.tpu.encoding.storage.
        # hostCodec): 0 keeps raw numpy payloads; >0 stores host-tier
        # payloads as compressed frame blobs (checkpoints and
        # incremental state inherit this — the one shared codec layer)
        self.host_codec = int(host_codec)
        # raw vs encoded host-frame byte totals (bench
        # state_bytes_raw/compressed and the profiling storage line).
        # Own lock: note_host_encoding is called from spill_to_host,
        # which may run UNDER the catalog lock (demote/_spill_tier) —
        # re-taking the non-reentrant catalog lock would deadlock
        self._enc_lock = threading.Lock()
        self.host_raw_bytes_total = 0
        self.host_encoded_bytes_total = 0
        self.spill_dir = spill_dir or tempfile.mkdtemp(prefix="tpu-spill-")
        # warm the native library now: its first load may shell out to g++
        # (up to ~2min); doing it lazily inside spill_to_disk would stall
        # every thread behind the catalog lock
        from spark_rapids_tpu import native
        native.available()
        self._lock = threading.Lock()
        self._handles: Dict[int, SpillableHandle] = {}
        # every handle id THIS catalog ever issued: close()'s orphan
        # sweep is scoped to these, so two catalogs sharing a spill
        # dir can never unlink each other's live frames
        self._issued_ids: set = set()
        self.device_bytes = 0
        self.host_bytes = 0
        self.disk_bytes = 0
        self.spilled_to_host_total = 0
        self.spilled_to_disk_total = 0
        self._access_counter = itertools.count(1)

    def next_access_stamp(self) -> int:
        return next(self._access_counter)

    def note_host_encoding(self, raw: int, encoded: int) -> None:
        """Cumulative raw->encoded attribution for host-tier frames
        (called by the handle on each compressed demotion, possibly
        under the catalog lock — see _enc_lock)."""
        with self._enc_lock:
            self.host_raw_bytes_total += int(raw)
            self.host_encoded_bytes_total += int(encoded)

    # ------------------------------------------------------------- interface --
    def register(self, batch: ColumnarBatch,
                 priority: int = AGGREGATE_INTERMEDIATE_PRIORITY,
                 owner: Optional[int] = None) -> SpillableHandle:
        if owner is None:
            # auto-tag with the registering query's scope (covers the
            # pipeline worker, checkpoint saves, coalesce — any site
            # running inside, or adopted into, a QueryContext)
            from spark_rapids_tpu.serving import context as _qc
            ctx = _qc.current()
            owner = ctx.owner_ident if ctx is not None else None
        from spark_rapids_tpu.utils import tracing
        if tracing._active:
            with tracing.span("spill.register"):
                return self._register_impl(batch, priority, owner)
        return self._register_impl(batch, priority, owner)

    def _register_impl(self, batch: ColumnarBatch, priority: int,
                       owner: Optional[int]) -> SpillableHandle:
        h = SpillableHandle(self, batch, priority, owner=owner)
        with self._lock:
            self._handles[h.id] = h
            self._issued_ids.add(h.id)
            self.device_bytes += h.size_bytes + h.wire_bytes
            self._owner_device_adjust(h.owner,
                                      h.size_bytes + h.wire_bytes)
        # the wire reservation is consumed by registration: a later
        # re-registration of the same batch (coalesce after pipeline)
        # must not re-reserve the exchange payload headroom
        if h.wire_bytes:
            batch.transient_wire_bytes = 0
        self.ensure_budget(for_owner=owner)
        self._enforce_owner_budget(h)
        return h

    # ------------------------------------------------------- per-owner budgets --
    def set_owner_budget(self, owner: int, budget: int) -> None:
        with self._lock:
            self._owner_budgets[owner] = int(budget)

    def clear_owner_budget(self, owner: int) -> None:
        with self._lock:
            self._owner_budgets.pop(owner, None)

    def owner_device_bytes(self, owner: int) -> int:
        with self._lock:
            return self._owner_device_bytes(owner)

    def _owner_device_bytes(self, owner: int) -> int:
        return self._owner_device.get(owner, 0)

    def _owner_device_adjust(self, owner: Optional[int],
                             delta: int) -> None:
        """Mirror every DEVICE-tier byte movement into the per-owner
        counter (called wherever ``device_bytes`` changes)."""
        if owner is None:
            return
        new = self._owner_device.get(owner, 0) + delta
        if new:
            self._owner_device[owner] = new
        else:
            self._owner_device.pop(owner, None)

    def _demote_to_host_locked(self, h: SpillableHandle) -> int:
        """One DEVICE->HOST transition with all its accounting (caller
        holds the lock).  Returns the device bytes freed — the batch
        plus any transient wire reservation; only the batch payload
        itself lands on the host tier."""
        from spark_rapids_tpu.utils import tracing
        with tracing.span("spill.demote.host"):
            freed = h.spill_to_host()
        self.device_bytes -= freed
        self._owner_device_adjust(h.owner, -freed)
        self.host_bytes += h.size_bytes
        self.spilled_to_host_total += h.size_bytes
        return freed

    def _enforce_owner_budget(self, h: SpillableHandle) -> None:
        """The per-query memory-budget ladder, run after each of the
        owner's registrations: over budget, the owner's OWN coldest
        device handles demote to host (degrade — other queries'
        batches are untouched); when self-spilling everything else
        still leaves the owner over (the new batch alone busts the
        budget), the owning query is rejected with a typed
        BudgetExhaustedFault.  Other queries never pay."""
        owner = h.owner
        if owner is None:
            return
        with self._lock:
            budget = self._owner_budgets.get(owner)
            if budget is None or self._owner_device_bytes(owner) <= budget:
                return
            victims = sorted(
                (x for x in self._handles.values()
                 if x.owner == owner and x.tier == DEVICE
                 and x.id != h.id),
                key=lambda x: (x.priority, x.last_access, x.id))
            used = self._owner_device_bytes(owner)
            for v in victims:
                if used <= budget:
                    break
                used -= self._demote_to_host_locked(v)
            spilled = bool(victims)
            over = used > budget
        if spilled:
            # the self-spill may push the HOST tier over ITS watermark
            self.ensure_budget(for_owner=owner)
        from spark_rapids_tpu.serving import context as _qc
        ctx = _qc.current()
        if ctx is None:
            return
        if spilled:
            ctx.note_memory_pressure(used, spilled=True)
        if over:
            # the rejection propagates out of register(): the caller
            # never receives the handle, so it must not stay in the
            # catalog — a leaked registration would pin its bytes for
            # the session's life and bill spurious pressure to the
            # NEXT query on a recycled thread ident
            self.remove(h)
            ctx.note_memory_pressure(used, spilled=False)  # raises

    def unspill(self, h: SpillableHandle, batch: ColumnarBatch) -> None:
        """Promote back to DEVICE after materialize (shouldUnspill=true
        behavior, RapidsBufferCatalog.scala)."""
        with self._lock:
            if h.tier == HOST:
                self.host_bytes -= h.size_bytes
            elif h.tier == DISK:
                self.disk_bytes -= h.size_bytes
                if h._disk_path and os.path.exists(h._disk_path):
                    os.unlink(h._disk_path)
                    h._disk_path = None
            h.tier = DEVICE
            h._device = batch
            h._host = None
            h._host_frame = None
            self.device_bytes += h.size_bytes
            self._owner_device_adjust(h.owner, h.size_bytes)
        self.ensure_budget(for_owner=h.owner)

    def remove(self, h: SpillableHandle) -> None:
        with self._lock:
            if h.id not in self._handles:
                return
            del self._handles[h.id]
            if h.tier == DEVICE:
                self.device_bytes -= h.size_bytes + h.wire_bytes
                self._owner_device_adjust(
                    h.owner, -(h.size_bytes + h.wire_bytes))
            elif h.tier == HOST:
                self.host_bytes -= h.size_bytes
            else:
                self.disk_bytes -= h.size_bytes

    def demote(self, h: SpillableHandle, target: str) -> None:
        """Push one handle down to ``target`` tier immediately,
        independent of the watermark loop (the checkpoint tier policy:
        payloads whose conf excludes DEVICE residency leave HBM at
        registration instead of waiting for pressure).  No-op for a
        closed/foreign handle or a tier at/below the current one."""
        if target not in (HOST, DISK):
            return
        with self._lock:
            if h.closed or h.id not in self._handles:
                return
            if h.tier == DEVICE:
                self._demote_to_host_locked(h)
            if h.tier == HOST and target == DISK:
                freed = h.spill_to_disk()
                self.host_bytes -= freed
                self.disk_bytes += freed
                self.spilled_to_disk_total += freed

    def ensure_budget(self, extra_needed: int = 0,
                      for_owner: Optional[int] = None) -> None:
        """Demote coldest handles until budgets hold (the synchronousSpill
        loop, RapidsBufferStore.scala:146).  ``for_owner`` attributes
        the pressure to the query that caused it: that owner's own
        handles demote first, and other owners' checkpoint-priority
        payloads are protected by the eviction floor."""
        with self._lock:
            self._spill_tier(DEVICE, self.device_budget - extra_needed,
                             for_owner)
            self._spill_tier(HOST, self.host_budget)

    def _floor_protected(self, h: SpillableHandle,
                         for_owner: Optional[int],
                         device_left: Dict[int, int]) -> bool:
        """Cross-query checkpoint floor: pressure from ``for_owner``
        may not demote ANOTHER owner's checkpoint-priority handle once
        that owner's device-resident checkpoint bytes would drop below
        the floor.  An owner's own handles are never protected from
        its own pressure."""
        if not self.checkpoint_floor or h.owner is None or \
                h.owner == for_owner or h.priority > CHECKPOINT_TIER_MAX:
            return False
        left = device_left.get(h.owner)
        if left is None:
            left = sum(x.size_bytes for x in self._handles.values()
                       if x.owner == h.owner and x.tier == DEVICE
                       and x.priority <= CHECKPOINT_TIER_MAX)
            device_left[h.owner] = left
        if left - h.size_bytes < self.checkpoint_floor:
            return True
        device_left[h.owner] = left - h.size_bytes
        return False

    def _spill_tier(self, tier: str, budget: int,
                    for_owner: Optional[int] = None) -> None:
        used = self.device_bytes if tier == DEVICE else self.host_bytes
        if used <= budget:
            return
        # coldest first: lowest priority, then — under attributed
        # pressure — the CAUSING owner's handles before a co-tenant's
        # within the same priority class, then least-recently
        # accessed.  Priority stays dominant: a neighbor's cold
        # shuffle output must still demote before the causing query's
        # own pinned on-deck batch, else every registration under
        # pressure would thrash its own working set device<->host
        def key(h: SpillableHandle):
            foreign = 1 if (for_owner is not None and
                            h.owner != for_owner) else 0
            return (h.priority, foreign, h.last_access, h.id)

        candidates = sorted(
            (h for h in self._handles.values() if h.tier == tier),
            key=key)
        if tier == DEVICE:
            device_left: Dict[int, int] = {}
            deferred = []
            for h in candidates:
                if used <= budget:
                    break
                if self._floor_protected(h, for_owner, device_left):
                    deferred.append(h)
                    continue
                used -= self._demote_to_host_locked(h)
            # the floor is isolation, not a leak: if the budget cannot
            # be met any other way, protected handles demote after all
            for h in deferred:
                if used <= budget:
                    break
                used -= self._demote_to_host_locked(h)
            if self.host_bytes > self.host_budget:
                self._spill_tier(HOST, self.host_budget)
            return
        # host -> disk: pick the victims first, then overlap the
        # serialize+write calls in the writer pool (handles are
        # disjoint; catalog counters update on this thread)
        to_spill = []
        for h in candidates:
            if used <= budget:
                break
            to_spill.append(h)
            used -= h.size_bytes
        if not to_spill:
            return
        def account(freed):
            self.host_bytes -= freed
            self.disk_bytes += freed
            self.spilled_to_disk_total += freed

        if self.disk_write_threads > 1 and len(to_spill) > 1:
            # account every COMPLETED demotion even when one writer
            # fails mid-batch, else host/disk counters drift for the
            # rest of the session.  The wait is watchdog-cooperative:
            # a wedged writer (stalled NFS, an unbounded delay rule on
            # "spill.disk") trips the section deadline and the fault
            # delivers HERE — a bare fut.result() under the catalog
            # lock would deadlock the whole process unrecoverably.
            import concurrent.futures as cf
            from spark_rapids_tpu.robustness import watchdog
            pool = cf.ThreadPoolExecutor(
                max_workers=self.disk_write_threads)
            first_err = None
            try:
                pending = [pool.submit(h.spill_to_disk)
                           for h in to_spill]
                with watchdog.section("spill.disk") as sect:
                    while pending:
                        watchdog.checkpoint()
                        done = [f for f in pending if f.done()]
                        if not done:
                            cf.wait(pending, timeout=0.05,
                                    return_when=cf.FIRST_COMPLETED)
                            continue
                        if sect is not None:
                            sect.beat()  # progress, not a hang
                        for fut in done:
                            pending.remove(fut)
                            try:
                                account(fut.result())
                            except BaseException as e:  # noqa: BLE001
                                first_err = first_err or e
            finally:
                # never wait=True: joining a wedged writer re-creates
                # the hang the cooperative wait just escaped
                pool.shutdown(wait=False, cancel_futures=True)
            if first_err is not None:
                raise first_err
        else:
            for h in to_spill:
                account(h.spill_to_disk())

    def close(self) -> None:
        """Session-teardown sweep: close every live handle (unlinking
        their disk files), then collect any orphaned spill artifacts —
        ``buf-*.tcf`` left by a crashed restore, ``*.tmp`` staging
        files from a torn write — and remove the temp dir if this
        catalog created it.  Idempotent; the catalog stays usable
        afterwards (spill_to_disk re-creates the directory)."""
        with self._lock:
            handles = list(self._handles.values())
        for h in handles:
            h.close()

        def _mine(name: str) -> bool:
            # only artifacts THIS catalog issued (buf-<id>.tcf[.tmp]):
            # a shared spill_dir may hold another live catalog's frames
            if not name.startswith("buf-") or not (
                    name.endswith(".tcf") or name.endswith(".tcf.tmp")):
                return False
            try:
                return int(name[4:].split(".", 1)[0]) in self._issued_ids
            except ValueError:
                return False

        try:
            for name in os.listdir(self.spill_dir):
                if _mine(name):
                    try:
                        os.unlink(os.path.join(self.spill_dir, name))
                    except OSError:
                        pass
            if self._owns_spill_dir:
                os.rmdir(self.spill_dir)
        except OSError:
            pass

    def stats(self) -> Dict[str, int]:
        return {
            "device_bytes": self.device_bytes,
            "host_bytes": self.host_bytes,
            "disk_bytes": self.disk_bytes,
            "spilled_to_host_total": self.spilled_to_host_total,
            "spilled_to_disk_total": self.spilled_to_disk_total,
            "host_raw_bytes_total": self.host_raw_bytes_total,
            "host_encoded_bytes_total": self.host_encoded_bytes_total,
            "num_handles": len(self._handles),
        }


_default_catalog: Optional[SpillableBatchCatalog] = None


def default_catalog() -> SpillableBatchCatalog:
    global _default_catalog
    if _default_catalog is None:
        _default_catalog = SpillableBatchCatalog()
    return _default_catalog


def set_default_catalog(cat: Optional[SpillableBatchCatalog]) -> None:
    global _default_catalog
    _default_catalog = cat


class TpuSemaphore:
    """Admission control: bounds tasks concurrently issuing TPU work
    (GpuSemaphore.scala:28, `spark.rapids.sql.concurrentGpuTasks`)."""

    def __init__(self, permits: int = 1):
        self._sem = threading.BoundedSemaphore(permits)
        self._held = threading.local()
        self.wait_time_ns = 0

    def acquire_if_necessary(self) -> None:
        if getattr(self._held, "count", 0) == 0:
            import time
            t0 = time.perf_counter_ns()
            self._sem.acquire()
            self.wait_time_ns += time.perf_counter_ns() - t0
        self._held.count = getattr(self._held, "count", 0) + 1

    def release_if_held(self) -> None:
        count = getattr(self._held, "count", 0)
        if count > 0:
            self._held.count = count - 1
            if self._held.count == 0:
                self._sem.release()

    def release_all_held(self) -> None:
        """Drop this thread's whole admission count (end-of-task hook:
        the pipeline worker calls this before exiting, else a permit
        acquired by a UDF exec's re-admission would die with the thread
        and deadlock the next query's worker)."""
        if getattr(self._held, "count", 0) > 0:
            self._held.count = 0
            self._sem.release()

    def __enter__(self):
        self.acquire_if_necessary()
        return self

    def __exit__(self, *exc):
        self.release_if_held()
        return False
