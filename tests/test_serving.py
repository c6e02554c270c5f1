"""Multi-tenant serving layer: admission control + query isolation.

Covers the serving/ subsystem end to end: FIFO fairness and
byte-weighted admission of the query semaphore, typed AdmissionFault
rejection (timeout / queue bound), per-query budget ladders (memory
self-spill, sync reject), thread-ident-reuse purging at QueryContext
exit, per-owner spill isolation (pressure-owner-first ordering and the
checkpoint eviction floor), thread-keyed query-id event attribution,
and the concurrent chaos interference gate: N client threads with
faults sprayed into half of them through keyed injection scopes —
every clean query must return bit-identical results with ZERO recovery
events attributed to it.
"""

import threading
import time

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.memory.retry import retry_metrics
from spark_rapids_tpu.memory.spill import (
    ACTIVE_ON_DECK_PRIORITY, DEVICE, HOST, SpillableBatchCatalog)
from spark_rapids_tpu.robustness import inject as I
from spark_rapids_tpu.robustness import watchdog
from spark_rapids_tpu.robustness.driver import recovery_metrics
from spark_rapids_tpu.robustness.faults import (
    FATAL, AdmissionFault, BudgetExhaustedFault, classify)
from spark_rapids_tpu.serving import context as qc
from spark_rapids_tpu.serving.admission import AdmissionController
from spark_rapids_tpu.serving.context import QueryContext
from spark_rapids_tpu.utils import hostsync


@pytest.fixture(autouse=True)
def _clean_registry():
    I.clear()
    recovery_metrics.reset()
    with I.scoped_rules():
        yield
    I.clear()


def _pdf(n=2000, seed=0):
    rng = np.random.default_rng(seed)
    return pd.DataFrame({"k": rng.integers(0, 20, n),
                         "v": rng.normal(size=n)})


def _groupby(session, pdf):
    return (session.create_dataframe(pdf).group_by("k")
            .agg(F.sum(F.col("v")).alias("sv"),
                 F.count(F.col("v")).alias("c")))


def _norm(df):
    return df.sort_values("k", ignore_index=True)


# ------------------------------------------------------------- admission --
def test_admission_fifo_fairness_no_starvation():
    """Strict FIFO: with one slot, waiters admit in arrival order —
    a queue position is a guarantee, so no query can starve behind
    later arrivals."""
    ctrl = AdmissionController(max_queries=1, hbm_bytes=1 << 20)
    order = []
    first = ctrl.acquire()
    started = []
    lock = threading.Lock()

    def waiter(i):
        with lock:
            started.append(i)
        t = ctrl.acquire()
        order.append(i)
        time.sleep(0.005)
        ctrl.release(t)

    threads = []
    for i in range(6):
        t = threading.Thread(target=waiter, args=(i,))
        t.start()
        # stagger arrivals so queue order is deterministic
        while len(started) != i + 1:
            time.sleep(0.001)
        time.sleep(0.01)
        threads.append(t)
    ctrl.release(first)
    for t in threads:
        t.join()
    assert order == [0, 1, 2, 3, 4, 5]
    snap = ctrl.snapshot()
    assert snap["totalAdmitted"] == 7
    assert snap["peakConcurrent"] == 1
    assert snap["totalRejected"] == 0


def test_admission_byte_weighted():
    """Admission is bounded by summed memory weights, not just count."""
    ctrl = AdmissionController(max_queries=8, hbm_bytes=100)
    a = ctrl.acquire(weight_bytes=40)
    b = ctrl.acquire(weight_bytes=40)
    got = []

    def third():
        got.append(ctrl.acquire(weight_bytes=40))

    t = threading.Thread(target=third)
    t.start()
    time.sleep(0.05)
    assert not got, "40+40+40 > 100 must queue the third query"
    ctrl.release(a)
    t.join(timeout=5)
    assert len(got) == 1
    ctrl.release(b)
    ctrl.release(got[0])


def test_admission_heavier_than_pool_admits_alone():
    ctrl = AdmissionController(max_queries=4, hbm_bytes=100)
    t = ctrl.acquire(weight_bytes=10_000)  # must not deadlock
    assert t.admitted
    ctrl.release(t)


def test_admission_timeout_and_queue_bound_reject_typed():
    ctrl = AdmissionController(max_queries=1, hbm_bytes=1 << 20,
                               timeout_ms=50, max_queue=1)
    held = ctrl.acquire()
    # one waiter fills the bounded queue, then times out
    errs = []

    def waiter():
        try:
            ctrl.acquire()
        except AdmissionFault as e:
            errs.append(e)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.01)
    # queue full: rejected immediately with the typed fault
    with pytest.raises(AdmissionFault) as exc:
        ctrl.acquire()
    assert exc.value.reason == "queue-full"
    t.join(timeout=5)
    assert len(errs) == 1 and errs[0].reason == "timeout"
    # both rejections are FATAL for that query — the ladder hands them
    # back instead of re-driving into a saturated session
    assert classify(errs[0]).severity == FATAL
    assert ctrl.snapshot()["totalRejected"] == 2
    ctrl.release(held)


def test_admission_wired_into_query_and_eventlog(tmp_path):
    """End to end: two clients through a 1-slot session — both answer,
    QueryEnd carries the admission dict, and the second query's wait
    reflects serialization."""
    s = TpuSession({"spark.rapids.tpu.eventLog.dir": str(tmp_path),
                    "spark.rapids.tpu.serving.concurrentQueries": 1})
    pdf = _pdf()
    df = _groupby(s, pdf)
    want = _norm(df.to_pandas())
    results = {}

    def client(i):
        results[i] = _norm(df.to_pandas())

    ts = [threading.Thread(target=client, args=(i,)) for i in range(2)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    for r in results.values():
        pd.testing.assert_frame_equal(r, want)
    assert s.admission.snapshot()["totalAdmitted"] == 3
    s.stop()
    from spark_rapids_tpu.tools.eventlog import load_logs
    app = load_logs(str(tmp_path))[0]
    done = [q for q in app.queries if q.succeeded]
    assert len(done) == 3
    assert all("waitMs" in q.admission for q in done)
    assert len(app.admission) == 3  # one grant event per query


# ---------------------------------------------------------------- budgets --
def test_sync_budget_rejects_typed(tmp_path):
    s = TpuSession({"spark.rapids.tpu.eventLog.dir": str(tmp_path),
                    "spark.rapids.tpu.serving.syncBudget": 1})
    df = _groupby(s, _pdf())
    with pytest.raises(BudgetExhaustedFault) as exc:
        df.to_pandas()
    assert exc.value.budget == "syncs"
    s.stop()
    from spark_rapids_tpu.tools.eventlog import load_logs
    app = load_logs(str(tmp_path))[0]
    budget = [b for q in app.queries for b in q.budget] + app.budget
    assert any(b.get("budget") == "syncs" and
               b.get("action") == "reject" for b in budget)


def test_sync_budget_contained_to_its_session():
    """The rejecting budget is per-session conf, and another session's
    concurrent query is untouched by the rejection."""
    s_tight = TpuSession({"spark.rapids.tpu.serving.syncBudget": 1})
    df = _groupby(s_tight, _pdf())
    with pytest.raises(BudgetExhaustedFault):
        df.to_pandas()
    s_free = TpuSession()
    out = _norm(_groupby(s_free, _pdf()).to_pandas())
    assert len(out) == 20


def test_memory_budget_self_spills_own_handles_only():
    """Per-owner memory budget: the over-budget owner's own coldest
    handles demote to host; a co-tenant's handles stay on device."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    cat = SpillableBatchCatalog(device_budget=1 << 30)
    mk = lambda: ColumnarBatch.from_pydict(  # noqa: E731
        {"v": np.arange(1024, dtype=np.int64)})
    other = cat.register(mk(), ACTIVE_ON_DECK_PRIORITY, owner=2)
    sz = other.size_bytes
    cat.set_owner_budget(1, int(2.5 * sz))
    mine = [cat.register(mk(), ACTIVE_ON_DECK_PRIORITY, owner=1)
            for _ in range(3)]
    # owner 1 is over budget (3 batches > 2.5x): its coldest demoted
    assert cat.owner_device_bytes(1) <= int(2.5 * sz)
    assert sum(1 for h in mine if h.tier == HOST) >= 1
    assert other.tier == DEVICE, "co-tenant must not pay owner 1's bill"


def test_memory_budget_rejects_when_self_spill_cannot_cure():
    """A single batch larger than the owner's budget cannot be cured
    by self-spilling — the owning query is rejected, inside its
    QueryContext, with the typed fault."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    s = TpuSession({
        "spark.rapids.tpu.serving.queryMemoryBudgetBytes": 128})
    cat = s.memory_catalog
    big = ColumnarBatch.from_pydict(
        {"v": np.arange(1 << 14, dtype=np.int64)})
    with QueryContext(s) as ctx:
        n0 = cat.stats()["num_handles"]
        dev0 = cat.device_bytes
        with pytest.raises(BudgetExhaustedFault) as exc:
            cat.register(big, ACTIVE_ON_DECK_PRIORITY)
        assert exc.value.budget == "memory"
        assert any(b["action"] == "reject" for b in ctx.budget_events)
        # the caller never received a handle, so the catalog must not
        # keep one: a leaked registration would pin its bytes forever
        # and bill spurious pressure to the next tenant
        assert cat.stats()["num_handles"] == n0
        assert cat.device_bytes == dev0
        assert cat.owner_device_bytes(ctx.owner_ident) == 0


def test_checkpoint_eviction_floor_protects_co_tenant():
    """Device pressure from query A demotes A's own handles first and
    may not demote B's checkpoint-priority payloads below B's floor."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.robustness.checkpoint import (
        CHECKPOINT_PRIORITY)
    mk = lambda: ColumnarBatch.from_pydict(  # noqa: E731
        {"v": np.arange(1024, dtype=np.int64)})
    probe = SpillableBatchCatalog(device_budget=1 << 30)
    sz = probe.register(mk()).size_bytes
    # floor covers one checkpoint; budget fits 3 batches
    cat = SpillableBatchCatalog(device_budget=3 * sz + sz // 2,
                                checkpoint_floor=sz)
    b_ckpt = cat.register(mk(), CHECKPOINT_PRIORITY, owner=2)
    a1 = cat.register(mk(), ACTIVE_ON_DECK_PRIORITY, owner=1)
    a2 = cat.register(mk(), ACTIVE_ON_DECK_PRIORITY, owner=1)
    a3 = cat.register(mk(), ACTIVE_ON_DECK_PRIORITY, owner=1)
    # over budget by ~one batch: WITHOUT the floor the checkpoint
    # (coldest priority) would demote first; with it, A pays
    assert b_ckpt.tier == DEVICE
    assert sum(1 for h in (a1, a2, a3) if h.tier == HOST) >= 1
    # sanity: without owner attribution (no pressure owner, no floor)
    # pure priority order demotes the coldest — the checkpoint — first
    cat2 = SpillableBatchCatalog(device_budget=3 * sz + sz // 2)
    b2 = cat2.register(mk(), CHECKPOINT_PRIORITY)
    for _ in range(3):
        cat2.register(mk(), ACTIVE_ON_DECK_PRIORITY)
    assert b2.tier == HOST


# ------------------------------------------------- ident reuse / scoping --
def test_query_context_purges_stale_adoptions():
    """Thread-ident reuse regression: a worker that adopted the query
    and died without releasing leaves entries in every adoption
    registry; QueryContext exit must purge them ALL, else a future
    thread with the recycled ident consumes this dead query's rules,
    token, and attribution."""
    s = TpuSession()
    with QueryContext(s) as ctx:
        owner = ctx.owner_ident

        def rogue_worker():
            # adopt everywhere, then die WITHOUT releasing (the
            # killed-worker / abandoned-zombie shape)
            I.adopt_thread(owner)
            watchdog.adopt_thread(owner)
            qc.adopt_thread(owner)
            hostsync.host_sync_metrics.adopt(owner)
            retry_metrics.adopt(owner)

        t = threading.Thread(target=rogue_worker)
        t.start()
        t.join()
        wid = t.ident
        assert I._adopted.get(wid) == owner
        assert watchdog._adopted.get(wid) == owner
    # context exited: every registry purged
    assert wid not in I._adopted
    assert wid not in watchdog._adopted
    assert wid not in qc._adopted
    assert wid not in hostsync.host_sync_metrics._owner
    assert wid not in retry_metrics._owner
    # and no cancellation token is left parked for the dead owner
    assert owner not in watchdog._pending


def test_stale_adoption_would_misattribute_without_purge():
    """The failure mode the purge prevents, demonstrated end to end:
    a recycled ident carrying a stale adoption attributes its syncs to
    the dead query's view; after a purged context exit it does not."""
    s = TpuSession()
    with QueryContext(s) as ctx:
        owner = ctx.owner_ident
    # post-exit: simulate the OS recycling the worker ident for a
    # brand-new thread that never asked to be adopted
    before = hostsync.host_sync_metrics._per_thread.get(owner, 0)

    def reused():
        hostsync.host_sync_metrics.bump(3)

    t = threading.Thread(target=reused)
    t.start()
    t.join()
    after = hostsync.host_sync_metrics._per_thread.get(owner, 0)
    assert after == before, "dead query's view must not absorb syncs"


def test_context_exit_clears_thread_qid():
    """A finished query's qid must not survive on the thread: the next
    query's pre-attempt events (e.g. an AdmissionReject before it
    draws a qid) would be stamped with the dead query's id."""
    s = TpuSession()
    with QueryContext(s):
        s._current_qid = 41
        assert s._current_qid == 41
    assert s._current_qid is None


def test_thread_keyed_qid_and_checkpoints_views():
    s = TpuSession()
    seen = {}

    def worker(i):
        s._current_qid = 100 + i
        s.checkpoints = f"mgr{i}"
        time.sleep(0.05)
        seen[i] = (s._current_qid, s.checkpoints)
        s._current_qid = None
        s.checkpoints = None

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert seen == {i: (100 + i, f"mgr{i}") for i in range(4)}
    assert s._current_qid is None


def test_keyed_scope_contains_all_threads_rules():
    """A rule armed in a keyed scope — even with all_threads=True —
    fires only on threads working for that scope."""
    fired_elsewhere = []

    def other_thread():
        try:
            I.fire("memory.oom")
        except Exception as e:  # noqa: BLE001
            fired_elsewhere.append(e)

    with I.scoped_rules(key="tenantA"):
        I.inject("memory.oom", count=10, all_threads=True)
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
        assert not fired_elsewhere, \
            "keyed rule fired outside its scope"
        with pytest.raises(Exception):
            I.fire("memory.oom")  # in-scope thread: fires


def test_concurrent_scopes_do_not_clobber_each_other():
    """A scope exiting on one thread must not disarm a rule another
    thread's still-open scope armed mid-block (one client finishing
    must not un-wedge a concurrent client's injected hang)."""
    armed = {}
    entered = threading.Barrier(2)
    both_armed = threading.Barrier(2)
    release = threading.Event()

    def tenant(i):
        with I.scoped_rules(key=f"s{i}"):
            entered.wait()
            armed[i] = I.inject("memory.oom", count=5,
                                all_threads=True)
            # tenant 0 must not leave before tenant 1 has armed (on a
            # loaded host it did, and the assert below read armed[1]
            # before it was there)
            both_armed.wait()
            if i == 0:
                return  # exits first — removes only ITS rule
            release.wait(timeout=10)

    t0 = threading.Thread(target=tenant, args=(0,))
    t1 = threading.Thread(target=tenant, args=(1,))
    t0.start(), t1.start()
    t0.join()
    # tenant 0's scope exited; tenant 1's rule must still be armed
    with I._lock:
        assert armed[1] in I._rules
        assert armed[0] not in I._rules
    release.set()
    t1.join()
    with I._lock:
        assert armed[1] not in I._rules


def test_scope_still_contains_non_adopted_thread_rules():
    """The fixture guarantee survives the concurrency fix: a rule
    armed by a plain helper thread (no adoption, no scope of its own)
    inside the block is an orphan the enclosing scope removes on
    exit — it must not leak into later tests."""
    leaked = {}
    with I.scoped_rules():
        def helper():
            leaked["r"] = I.inject("memory.oom", count=5,
                                   all_threads=True)

        t = threading.Thread(target=helper)
        t.start()
        t.join()
        with I._lock:
            assert leaked["r"] in I._rules
    with I._lock:
        assert leaked["r"] not in I._rules


def test_query_context_rejects_nesting():
    s = TpuSession()
    with QueryContext(s):
        with pytest.raises(RuntimeError):
            QueryContext(s).__enter__()


# ------------------------------------------------- eventlog concurrency --
def test_eventlog_parses_interleaved_envelopes(tmp_path):
    """Satellite regression: two queries' envelopes interleaved in one
    log parse into the right QueryInfo, including mid-flight recovery
    and watchdog events keyed by query id."""
    import json
    p = tmp_path / "tpu-events-interleave.jsonl"
    recs = [
        {"event": "SessionStart", "ts": 1.0, "sessionId": "x",
         "conf": {}},
        {"event": "QueryStart", "ts": 2.0, "queryId": 1,
         "logicalPlan": "A"},
        {"event": "QueryStart", "ts": 2.5, "queryId": 2,
         "logicalPlan": "B"},
        {"event": "RecoveryAction", "ts": 3.0, "queryId": 2,
         "action": "retry", "fault": "io_read", "severity": "RETRYABLE",
         "error": "x"},
        {"event": "WatchdogTrip", "ts": 3.1, "queryId": 1,
         "point": "io.reader", "deadlineMs": 10, "elapsedMs": 20,
         "overrunMs": 10},
        {"event": "BudgetExhausted", "ts": 3.2, "queryId": 2,
         "budget": "memory", "used": 10, "limit": 5,
         "action": "spill"},
        {"event": "QueryEnd", "ts": 4.0, "queryId": 2,
         "status": "success", "durationMs": 1500.0,
         "admission": {"waitMs": 7.0, "weightBytes": 42}},
        {"event": "QueryEnd", "ts": 5.0, "queryId": 1,
         "status": "success", "durationMs": 3000.0},
    ]
    p.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    from spark_rapids_tpu.tools.eventlog import parse_event_log
    app = parse_event_log(str(p))
    q1 = next(q for q in app.queries if q.query_id == 1)
    q2 = next(q for q in app.queries if q.query_id == 2)
    assert q1.logical_plan == "A" and q2.logical_plan == "B"
    assert not q1.recovery and len(q2.recovery) == 1
    assert len(q1.watchdog) == 1 and not q2.watchdog
    assert q2.budget[0]["budget"] == "memory"
    assert q2.admission == {"waitMs": 7.0, "weightBytes": 42}
    assert not app.recovery and not app.watchdog
    assert app.max_concurrent() == 2


def test_concurrent_queries_attribute_their_own_events(tmp_path):
    """Live version of the parser test: two concurrent clients, one
    faulted through a keyed scope — the recovery events land on the
    faulted client's query ids only."""
    s = TpuSession({"spark.rapids.tpu.eventLog.dir": str(tmp_path),
                    "spark.rapids.sql.recovery.backoffMs": 1})
    df = _groupby(s, _pdf())
    want = _norm(df.to_pandas())
    qids = {}
    barrier = threading.Barrier(2)

    def client(i, faulty):
        barrier.wait()
        if faulty:
            with I.scoped_rules(key=f"t{i}"):
                # io_read never fires here (in-memory source); use an
                # oom burst big enough to escape operator retry
                I.inject("memory.oom", count=8, all_threads=True)
                got = df.to_pandas()
        else:
            got = df.to_pandas()
        pd.testing.assert_frame_equal(_norm(got), want)
        qids[i] = True

    ts = [threading.Thread(target=client, args=(i, i == 0))
          for i in range(2)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    s.stop()
    from spark_rapids_tpu.tools.eventlog import load_logs
    app = load_logs(str(tmp_path))[0]
    dirty = [q for q in app.queries if q.recovery]
    clean = [q for q in app.queries if not q.recovery and q.succeeded]
    assert app.recovery == [], "no unattributed recovery events"
    # the faulted client recovered (or its fault was absorbed below
    # the query ladder); every OTHER query shows a clean trail
    assert len(clean) >= 2
    for q in dirty:
        assert all(r.get("fault") in ("device_oom",)
                   for r in q.recovery)


# ------------------------------------------------------ interference gate --
@pytest.mark.chaos
def test_concurrent_chaos_interference_gate(tmp_path):
    """The acceptance gate: N concurrent clients on one session, faults
    sprayed into half of them via per-query keyed scopes ({oom burst,
    delay+deadline -> timeout, spill corruption}); every faulted query
    recovers or fails with a typed fault, and every clean query
    returns bit-identical results with ZERO recovery / watchdog /
    corruption / budget events attributed to its query ids."""
    s = TpuSession({
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
        "spark.rapids.sql.recovery.backoffMs": 1,
        # contention-proof: 8 threads cold-compiling XLA programs can
        # legitimately go seconds without a heartbeat on a loaded CI
        # box — the deadline must only catch the injected wedge class,
        # never honest slowness (that would be self-inflicted noise in
        # the isolation gate, not interference)
        "spark.rapids.tpu.watchdog.defaultDeadlineMs": 15_000,
        # tight device budget: spills happen, so corrupt rules have a
        # restore path to bite
        "spark.rapids.memory.tpu.deviceLimitBytes": 1 << 16,
    })
    pdf = _pdf(4000, seed=1)
    df = _groupby(s, pdf)
    want = _norm(df.to_pandas())
    n, results, failures = 8, {}, {}
    flavors = {1: ("memory.oom", dict(count=8, all_threads=True)),
               3: ("memory.oom",
                   dict(count=2, kind="delay", delay_s=1.0,
                        all_threads=True)),
               5: ("spill.corrupt.host",
                   dict(count=2, kind="corrupt", all_threads=True)),
               7: ("io.read", dict(count=2, all_threads=True))}

    def client(i):
        try:
            if i in flavors:
                point, kw = flavors[i]
                with I.scoped_rules(key=f"client{i}"):
                    I.inject(point, **kw)
                    got = df.to_pandas()
            else:
                got = df.to_pandas()
            results[i] = _norm(got)
        except Exception as e:  # noqa: BLE001 - gate checks below
            failures[i] = e

    ts = [threading.Thread(target=client, args=(i,)) for i in range(n)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    # every clean client answered, bit-identical to solo execution
    for i in range(n):
        if i not in flavors:
            assert i in results, f"clean client {i}: {failures.get(i)}"
            pd.testing.assert_frame_equal(results[i], want)
    # faulted clients: recovered exactly, or failed with a typed fault
    for i in flavors:
        if i in results:
            pd.testing.assert_frame_equal(results[i], want)
        else:
            fault = classify(failures[i])
            assert fault.kind != "unknown", failures[i]
    s.stop()
    from spark_rapids_tpu.tools.eventlog import load_logs
    app = load_logs(str(tmp_path))[0]
    # zero robustness events may float unattributed under concurrency
    assert app.recovery == []
    assert app.corruption == []
    assert app.budget == []
    # interference gate: every dirty trail must be explainable by an
    # injected fault class (qids are per-ATTEMPT, so one faulted
    # client's ladder can own several dirty queries — but a clean
    # client's query carrying any of these events would still show up
    # here, and a fault kind outside the injected set would prove
    # contamination from elsewhere)
    injected_kinds = {"device_oom", "io_read", "spill_corruption",
                      "timeout"}
    dirty = [q for q in app.queries
             if q.recovery or q.corruption or q.budget]
    for q in dirty:
        kinds = {r.get("fault") for r in q.recovery}
        assert kinds <= injected_kinds, (q.query_id, q.recovery)
    # at least every clean client's query (plus the baseline) has a
    # completely clean trail
    clean_ok = [q for q in app.queries
                if q.succeeded and not q.recovery and not q.corruption
                and not q.watchdog and not q.budget]
    assert len(clean_ok) >= n - len(flavors) + 1


@pytest.mark.chaos
def test_concurrent_throughput_scales(tmp_path):
    """Sanity floor for the serving claim: 4 concurrent clients finish
    in comfortably less wall time than 4x one client (admission
    overlap works); generous 3x bound keeps CI noise-proof."""
    s = TpuSession()
    df = _groupby(s, _pdf(4000))
    df.to_pandas()  # warm the jit cache
    t0 = time.perf_counter()
    df.to_pandas()
    serial = time.perf_counter() - t0

    ts = [threading.Thread(target=df.to_pandas) for _ in range(4)]
    t0 = time.perf_counter()
    [t.start() for t in ts]
    [t.join() for t in ts]
    wall = time.perf_counter() - t0
    assert wall < max(4 * serial * 0.75, serial + 5.0), \
        f"4 clients took {wall:.3f}s vs serial {serial:.3f}s"


def test_exchange_inflight_bytes_charged_to_query_budget():
    """In-flight async-exchange payload bytes are real HBM the serving
    memory budget must see (parallel/exchange_async.ExchangeWindow):
    the query context tracks the high-water mark, an overrun past the
    memory budget records ONE budget fact with action='stage' (staging/
    eviction engage — never a rejection), and the peak rides the
    QueryEnd admission payload."""
    from spark_rapids_tpu.parallel.exchange_async import (
        ExchangeOverlapMetrics, ExchangeWindow)
    s = TpuSession({
        "spark.rapids.tpu.serving.queryMemoryBudgetBytes": 1000})
    with QueryContext(s) as ctx:
        win = ExchangeWindow(max_bytes=1 << 20,
                             metrics=ExchangeOverlapMetrics())
        win.admit("site_a", 600)
        assert ctx.exchange_inflight == 600
        assert not ctx.budget_events
        win.admit("site_b", 600)  # 1200 > the 1000-byte budget
        assert ctx.exchange_inflight == 1200
        facts = [b for b in ctx.budget_events
                 if b["budget"] == "exchangeInflight"]
        assert len(facts) == 1 and facts[0]["action"] == "stage", \
            ctx.budget_events
        win.admit("site_c", 600)  # overrun noted once, not per admit
        assert len([b for b in ctx.budget_events
                    if b["budget"] == "exchangeInflight"]) == 1
        win.resolve_all()
        assert ctx.exchange_inflight == 0
        assert ctx.exchange_inflight_peak == 1800
        assert ctx.admission_info()["exchangeInflightPeak"] == 1800
