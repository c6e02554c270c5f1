"""Bounded asynchronous pipeline driver.

The sequential pull loop (``list(exec_plan.execute())``) serializes
every stage of a query against the host: the reader decodes a file,
uploads it, dispatches the XLA stage, then ``int(n)``-style syncs block
until the device answers before the next batch even starts decoding.
Each of those device-to-host syncs stalls the dispatch queue: dead
pipeline while the device drains and the host waits.

``pipelined(iterator, depth)`` re-drives the same operator iterator
from a worker thread with a bounded in-flight queue:

* the worker pulls batches — running reader host decode
  (io/multifile.py's MULTITHREADED pool), host->device upload
  (columnar ``jnp.asarray``) and XLA dispatch (async by construction)
  — while the consuming thread drains already-produced batches;
* every in-flight batch is registered in the spill catalog before it
  enters the queue, so backpressure is HBM-aware: a stalled consumer
  never pins more than ``depth`` batches and the catalog may demote
  them to host under memory pressure;
* ``depth`` bounds the queue (``spark.rapids.tpu.pipeline.depth``,
  default 2): the worker blocks on a full queue, the consumer on an
  empty one;
* exceptions on the worker re-raise on the driving thread with their
  original traceback and injection context intact — the recovery
  ladder (robustness/driver.py) classifies them exactly as it would
  sequential faults.  The worker adopts the driving thread's identity
  for fault-injection rules (robustness/inject.py) and for the
  host-sync / retry attribution views, so per-query accounting and
  thread-scoped chaos rules keep working.

Batch identity is preserved: the pipelined iterator yields the same
batches in the same order as the sequential loop — it is a pure
overlap optimization (tier-1 runs it on CPU with identical results).
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import Iterator, Optional

from spark_rapids_tpu.columnar.batch import ColumnarBatch

_DONE = object()


@contextlib.contextmanager
def worker_attribution(owner_ident: int, stats=None):
    """Make the calling thread act as ``owner_ident`` for every
    thread-attributed registry at once: fault-injection rules
    (robustness/inject.py), host-sync accounting and upload timing
    (utils/hostsync.py), and OOM-retry counters (memory/retry.py).

    The single place that knows the full adoption set — any future
    worker thread (another pipeline stage, a reader pool that runs
    engine code) should use this rather than hand-rolling the adopt/
    release pairs, where forgetting one silently mis-attributes
    metrics or stops thread-scoped chaos rules from firing."""
    from spark_rapids_tpu.memory.retry import retry_metrics
    from spark_rapids_tpu.robustness import inject, watchdog
    from spark_rapids_tpu.serving import context as qcontext
    from spark_rapids_tpu.utils import hostsync
    inject.adopt_thread(owner_ident)
    watchdog.adopt_thread(owner_ident)
    qcontext.adopt_thread(owner_ident)
    hostsync.host_sync_metrics.adopt(owner_ident)
    retry_metrics.adopt(owner_ident)
    upload0 = hostsync.upload_metrics.thread_ns()
    try:
        yield
    finally:
        if stats is not None:
            # this thread's share of the one upload accounting
            stats.upload_overlap_ns += \
                hostsync.upload_metrics.thread_ns() - upload0
        retry_metrics.release()
        hostsync.host_sync_metrics.release()
        qcontext.release_thread()
        watchdog.release_thread()
        inject.release_thread()


def disown_worker(ident: int) -> None:
    """Sever a worker thread's adopted identity in EVERY registry
    worker_attribution enrolled it in — the counterpart operation,
    invoked by a driver abandoning a wedged worker.  The zombie must
    not consume the driver's next attempt's cancellation token or
    rule budgets, nor mis-attribute its dying syncs/retries into the
    next query's thread-local deltas."""
    from spark_rapids_tpu.memory.retry import retry_metrics
    from spark_rapids_tpu.robustness import inject, watchdog
    from spark_rapids_tpu.serving import context as qcontext
    from spark_rapids_tpu.utils import hostsync
    watchdog.disown(ident)
    inject.disown(ident)
    qcontext.disown(ident)
    hostsync.host_sync_metrics.disown(ident)
    retry_metrics.disown(ident)


class PipelineStats:
    """One pipelined drive's observability counters.

    ``fill_ratio``: mean queue occupancy (0..1) sampled at each consumer
    get — 1.0 means the worker always had a batch ready (compute-bound
    consumer), ~0 means the consumer starved (producer-bound query).
    ``host_sync_count``: device->host syncs attributed to the query
    while the pipeline ran (utils/hostsync.py).  ``upload_overlap_ms``:
    host->device transfer time spent on the worker thread — time the
    sequential loop would have serialized against consumption.
    """

    def __init__(self, depth: int):
        self.depth = depth
        self.batches = 0
        self.gets = 0
        self.fill_sum = 0.0
        self.upload_overlap_ns = 0
        self.host_sync_count = 0
        self.wait_ns = 0  # consumer time blocked on an empty queue

    @property
    def fill_ratio(self) -> float:
        return (self.fill_sum / self.gets) if self.gets else 0.0

    def as_dict(self) -> dict:
        from spark_rapids_tpu.exec.base import (
            HOST_SYNC_COUNT, PIPELINE_FILL_RATIO, UPLOAD_OVERLAP_MS)
        return {
            "depth": self.depth,
            "batches": self.batches,
            PIPELINE_FILL_RATIO: round(self.fill_ratio, 4),
            HOST_SYNC_COUNT: self.host_sync_count,
            UPLOAD_OVERLAP_MS: round(self.upload_overlap_ns / 1e6, 3),
            "consumerWaitMs": round(self.wait_ns / 1e6, 3),
        }


def _put_final(q: "queue.Queue", stop: threading.Event, item) -> None:
    """Deliver the worker's terminal item (sentinel or exception)
    without deadlocking against a departed consumer: on a full queue,
    keep trying until space frees or the consumer signals stop (its
    shutdown drain then makes room or makes delivery moot)."""
    while True:
        try:
            q.put(item, timeout=0.1)
            return
        except queue.Full:
            if stop.is_set():
                return


def pipelined(source: Iterator[ColumnarBatch], depth: int,
              catalog=None,
              stats: Optional[PipelineStats] = None,
              semaphore=None) -> Iterator[ColumnarBatch]:
    """Drive ``source`` from a worker thread with ``depth`` batches of
    lookahead.  Yields the identical batch sequence.

    The returned generator owns the worker: closing it early (LIMIT
    queries, an exception in the consumer) stops the worker at its next
    queue put, closes every still-queued spill registration, and joins
    the thread — no leaked registrations, no orphan threads."""
    from spark_rapids_tpu.memory.spill import (
        ACTIVE_ON_DECK_PRIORITY, default_catalog)
    from spark_rapids_tpu.utils.hostsync import host_sync_metrics

    depth = max(int(depth), 1)
    catalog = catalog or default_catalog()
    stats = stats or PipelineStats(depth)
    q: "queue.Queue" = queue.Queue(maxsize=depth)
    stop = threading.Event()
    owner_ident = threading.get_ident()
    sync0 = host_sync_metrics.snapshot_local()

    def worker() -> None:
        # act as the driving thread for injection rules and metric
        # attribution (worker_attribution); host->device uploads
        # anywhere in the operator chain (columnar/column.py
        # materialization) time themselves into stats while this
        # thread runs the iterator — that is work the sequential loop
        # would have serialized against consumption.
        from spark_rapids_tpu.robustness import watchdog
        try:
            with worker_attribution(owner_ident, stats):
                try:
                    # heartbeat section: the deadline measures SILENCE
                    # (time since the last produced batch / queue
                    # wait), so a worker wedged inside the operator
                    # iterator trips while a merely busy one never
                    # does.  The trip cancels the DRIVING thread's
                    # token (this thread adopted its identity), which
                    # the consumer's queue-wait checkpoint delivers as
                    # a retryable TimeoutFault.
                    with watchdog.section("pipeline.worker") as beat:
                        for batch in source:
                            if beat is not None:
                                beat.beat()
                            if stop.is_set():
                                break
                            # registration charges the DEVICE budget
                            # with the batch PLUS any transient wire
                            # reservation (a shuffle-received batch's
                            # packed exchange payload,
                            # memory/spill.py SpillableHandle), so
                            # depth x footprint backpressure can't
                            # undercount mid-exchange; the handle
                            # consumes the reservation, releasing it
                            # when the batch leaves DEVICE
                            handle = catalog.register(
                                batch, ACTIVE_ON_DECK_PRIORITY)
                            while not stop.is_set():
                                if beat is not None:
                                    # backpressure (full queue) is a
                                    # slow consumer, not a hang
                                    beat.beat()
                                try:
                                    q.put(handle, timeout=0.1)
                                    break
                                except queue.Full:
                                    continue
                            else:
                                handle.close()
                                break
                    _put_final(q, stop, _DONE)
                except BaseException as exc:  # noqa: BLE001 — re-raised
                    _put_final(q, stop, exc)
        finally:
            if semaphore is not None:
                # the worker is the "task thread": any admission it
                # holds (UDF execs re-admit per batch, TpuSemaphore)
                # must not die with it
                semaphore.release_all_held()

    from spark_rapids_tpu.robustness import watchdog

    t = threading.Thread(target=worker, name="tpu-pipeline", daemon=True)
    t.start()
    try:
        while True:
            stats.fill_sum += min(q.qsize() / depth, 1.0)
            stats.gets += 1
            t0 = time.perf_counter_ns()
            # the queue wait is the driving thread's cancellation
            # checkpoint: when the watchdog trips (wedged worker, query
            # deadline) the TimeoutFault is raised HERE instead of
            # blocking forever on a queue no one will ever fill.  It is
            # also a stage boundary: any async exchange this thread
            # still has in flight (a distributed sub-execution feeding
            # this pipeline) verifies here, after downstream work was
            # dispatched — the exchange/compute-overlap contract
            # (parallel/exchange_async.py)
            from spark_rapids_tpu.parallel.exchange_async import (
                resolve_pending)
            while True:
                watchdog.checkpoint()
                resolve_pending()
                try:
                    item = q.get(timeout=0.05)
                    break
                except queue.Empty:
                    continue
            stats.wait_ns += time.perf_counter_ns() - t0
            if item is _DONE:
                break
            if isinstance(item, BaseException):
                # original traceback (and injection point/note for
                # InjectedFaults) intact: the recovery ladder classifies
                # the re-raise exactly like a sequential fault
                raise item
            try:
                batch = item.materialize()
            finally:
                # close even when materialize raises (disk unspill
                # failure): a dequeued handle is no longer in the
                # queue, so the shutdown drain cannot reach it —
                # without this the dead registration and its spill
                # file would leak for the session lifetime
                item.close()
            stats.batches += 1
            yield batch
    finally:
        stop.set()
        # drain whatever the worker had queued so spill registrations
        # never leak on early close; keep draining until the worker is
        # gone (it may slip one more item in between drain and join)
        def drain() -> None:
            while True:
                try:
                    leftover = q.get_nowait()
                except queue.Empty:
                    return
                if leftover is not _DONE and \
                        not isinstance(leftover, BaseException):
                    leftover.close()

        # bound the join: waiting forever on a WEDGED worker would
        # re-create the very hang the watchdog just converted into a
        # fault.  A healthy worker exits within the grace period; an
        # abandoned one is a daemon that self-cleans when it unwedges
        # (sees ``stop`` set, closes its in-flight registration, drops
        # its terminal put — the drain above already made delivery
        # moot).
        grace_until = time.monotonic() + 1.0
        while t.is_alive() and time.monotonic() < grace_until:
            drain()
            t.join(timeout=0.05)
        drain()
        if t.is_alive() and t.ident is not None:
            # sever the zombie's adopted identity everywhere: when it
            # unwedges it must not consume the driver's NEXT attempt's
            # one-shot cancellation token, its armed rule budgets, or
            # its per-thread metric attribution
            disown_worker(t.ident)
        stats.host_sync_count = \
            host_sync_metrics.snapshot_local() - sync0
