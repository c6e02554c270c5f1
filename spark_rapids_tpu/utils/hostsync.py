"""Host-sync accounting: every device->host scalar/buffer fetch counts.

A device->host synchronization stalls the dispatch queue: the host
blocks until the device has drained everything ahead of the read, so
the engine treats syncs as a budgeted resource: every site that
materializes device data on the host goes through :func:`fetch` /
:func:`count_sync`, and the counters surface in QueryEnd events
(``pipeline.hostSyncCount``), ``benchmark/run.py``'s ``exec.host_syncs``
and ``tests/test_pipeline.py``'s regression assertions.

The discipline for when a sync is allowed lives in
``docs/performance.md`` ("when is ``int(x)`` on a device value
allowed"); the short form: only at true host decision points —
coded-vs-sort dispatch, spill/merge sizing, string re-decode, and the
final collect.

Process-wide totals plus a thread-local mirror (the RetryMetrics
pattern): a query runs its operator pipeline on one thread, so
per-query deltas read the thread-local view and concurrent sessions
don't contaminate each other's attribution.
"""

from __future__ import annotations

import threading
import time
from typing import Sequence


class HostSyncMetrics:
    def __init__(self):
        self._lock = threading.Lock()
        self.sync_count = 0
        self._per_thread = {}  # effective thread ident -> count
        self._owner = {}       # worker ident -> owning (driving) ident

    def _effective_ident(self) -> int:
        ident = threading.get_ident()
        return self._owner.get(ident, ident)

    def bump(self, n: int = 1) -> None:
        with self._lock:
            self.sync_count += n
            ident = self._effective_ident()
            self._per_thread[ident] = self._per_thread.get(ident, 0) + n

    def snapshot(self) -> int:
        with self._lock:
            return self.sync_count

    def snapshot_local(self) -> int:
        with self._lock:
            return self._per_thread.get(self._effective_ident(), 0)

    def adopt(self, owner_ident: int) -> None:
        """Attribute this thread's syncs to ``owner_ident``'s view.
        The pipeline worker (exec/pipeline.py) adopts its driving
        thread so per-query deltas keep working when the operator
        iterator runs on the worker."""
        with self._lock:
            self._owner[threading.get_ident()] = owner_ident

    def release(self) -> None:
        with self._lock:
            self._owner.pop(threading.get_ident(), None)

    def disown(self, ident: int) -> None:
        """Sever ``ident``'s adoption from the outside (a driver
        abandoning a wedged worker thread)."""
        with self._lock:
            self._owner.pop(ident, None)

    def purge_owner(self, owner_ident: int) -> None:
        """Drop every adoption mapping TO ``owner_ident`` — the
        query-exit counterpart of disown(): the OS reuses idents, so a
        stale entry would attribute a NEW query's syncs to this dead
        query's view (serving/context.QueryContext.__exit__).  The
        per-thread counters themselves survive: callers take deltas
        across queries on long-lived client threads."""
        from spark_rapids_tpu.robustness.inject import purge_adoptions
        with self._lock:
            purge_adoptions(self._owner, owner_ident)

    def reset(self) -> None:
        with self._lock:
            self.sync_count = 0
            self._per_thread.clear()


host_sync_metrics = HostSyncMetrics()


def _charge_budget(n: int) -> None:
    """Serving-layer sync budget: the owning QueryContext counts every
    sync against spark.rapids.tpu.serving.syncBudget and rejects THIS
    query (typed BudgetExhaustedFault) past the limit — a runaway sync
    loop in one tenant must not serialize the shared device.  Free
    (one dict probe) when no context is active."""
    from spark_rapids_tpu.serving import context as qc
    ctx = qc.current()
    if ctx is not None:
        ctx.charge_syncs(n)


def count_sync(n: int = 1) -> None:
    """Record ``n`` device->host synchronizations.  Every counted sync
    is also a watchdog cancellation checkpoint — host syncs are the
    places the driving thread provably touches the host, so a tripped
    deadline surfaces here rather than after minutes of dead pipeline.
    """
    from spark_rapids_tpu.robustness import watchdog
    watchdog.checkpoint()
    host_sync_metrics.bump(n)
    _charge_budget(n)


# ------------------------------------------------------ upload accounting --
class UploadMetrics:
    """Host->device uploads, counted where a host buffer becomes a
    device array (:func:`upload`: columnar/column.py ``Column._upload``
    and the sharded scan's per-shard placement):
    ``bytes`` and ``buffers`` moved, ``validity_bytes`` the share of
    ``bytes`` that is validity buffers (a column without NULLs uploads
    none), and ``ns`` of host-side dispatch+staging time (the transfer
    itself is async).  Plain ints,
    bumped with tracing on or off — what an operator would scrape.
    ``thread_ns`` is the calling thread's own share of ``ns``: the
    pipeline worker's delta of it is ``uploadOverlapMs``, the upload
    work the sequential loop would have serialized against
    consumption."""

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self.bytes = self.buffers = self.ns = self.validity_bytes = 0

    def note(self, nbytes: int, ns: int, validity: bool = False) -> None:
        with self._lock:
            self.bytes += nbytes
            self.buffers += 1
            self.ns += ns
            if validity:
                self.validity_bytes += nbytes
        self._local.ns = getattr(self._local, "ns", 0) + ns

    def snapshot(self) -> dict:
        with self._lock:
            return {"bytes": self.bytes, "buffers": self.buffers,
                    "ns": self.ns, "validity_bytes": self.validity_bytes}

    def thread_ns(self) -> int:
        return getattr(self._local, "ns", 0)


upload_metrics = UploadMetrics()


def upload(np_buf, device=None, validity: bool = False):
    """Host buffer -> device array, counted (``upload_metrics``; a
    validity buffer says so): the default device through
    ``jnp.asarray``, a named one (a mesh shard's) through
    ``jax.device_put``.  The ``upload.h2d`` span is the
    caller's, one a batch (``ops/compiler.batch_to_flat``, the sharded
    scan's placement): one a buffer made a profiled q6 query 8% slower
    on the chip (PERF.md, PR 28)."""
    import jax
    t0 = time.perf_counter_ns()
    out = jax.numpy.asarray(np_buf) if device is None \
        else jax.device_put(np_buf, device)
    upload_metrics.note(np_buf.nbytes, time.perf_counter_ns() - t0,
                        validity)
    return out


def _globalize(buffers):
    """Replicate non-fully-addressable buffers before the device_get:
    in a multi-controller fleet each process holds only its shards of
    a global array, and ``jax.device_get`` on one raises instead of
    fetching — route those through ``mesh.to_host`` (a cross-fleet
    replicate, every controller gets the identical full copy the SPMD
    contract needs).  Single-controller arrays pass through untouched,
    so this is one attribute probe per buffer on the common path."""
    import jax
    out = list(buffers)
    for i, b in enumerate(out):
        if isinstance(b, jax.Array) and not b.is_fully_addressable:
            from spark_rapids_tpu.parallel.mesh import to_host
            out[i] = to_host(b)
    return out


def fetch(*buffers):
    """Fetch device buffers to host in ONE transfer (one counted sync).

    Per-buffer ``np.asarray`` pays a device-to-host sync each;
    batching through ``jax.device_get`` amortizes them into a single
    sync.  Returns numpy arrays in input
    order (a single buffer returns the bare array).
    """
    import jax
    from spark_rapids_tpu.robustness import watchdog
    from spark_rapids_tpu.utils import tracing
    watchdog.checkpoint()
    host_sync_metrics.bump(1)
    _charge_budget(1)
    with tracing.span("hostsync.fetch"):
        got = jax.device_get(_globalize(buffers))
    return got[0] if len(buffers) == 1 else got


def fetch_all(buffers: Sequence):
    """List form of :func:`fetch` (always returns a list)."""
    import jax
    from spark_rapids_tpu.robustness import watchdog
    from spark_rapids_tpu.utils import tracing
    if not buffers:
        return []
    watchdog.checkpoint()
    host_sync_metrics.bump(1)
    _charge_budget(1)
    with tracing.span("hostsync.fetch"):
        return jax.device_get(_globalize(buffers))
