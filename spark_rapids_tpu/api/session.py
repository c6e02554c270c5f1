"""TpuSession: entry point (the SparkSession + SQLPlugin bootstrap analog).

Where the reference's SQLPlugin hooks into an existing SparkSession
(Plugin.scala:57-70 injecting ColumnarOverrideRules), this standalone engine
owns the session: it holds the RapidsConf, the planner (TpuOverrides), and
the device runtime handles (memory manager + semaphore come with the memory
task).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Union

from spark_rapids_tpu.api.dataframe import DataFrame
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.config.rapids_conf import RapidsConf
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.overrides import TpuOverrides


class DataFrameReader:
    def __init__(self, session: "TpuSession"):
        self.session = session
        self._options: Dict[str, str] = {}

    def option(self, key: str, value) -> "DataFrameReader":
        self._options[key] = value
        return self

    def _make(self, paths, file_format) -> DataFrame:
        from spark_rapids_tpu.io.bucketing import read_spec
        from spark_rapids_tpu.io.readers import infer_file_schema
        if isinstance(paths, str):
            paths = [paths]
        schema = infer_file_schema(paths, file_format)
        # a _bucket_spec.json sidecar marks a bucketed table (enables
        # equality-filter bucket pruning, io/bucketing.py)
        bucket_spec = read_spec(paths[0]) if len(paths) == 1 else None
        rel = L.FileRelation(paths, file_format, schema, self._options,
                             bucket_spec=bucket_spec)
        return DataFrame(self.session, rel)

    def parquet(self, *paths: str) -> DataFrame:
        return self._make(list(paths), "parquet")

    def orc(self, *paths: str) -> DataFrame:
        return self._make(list(paths), "orc")

    def csv(self, *paths: str) -> DataFrame:
        return self._make(list(paths), "csv")


class TpuSession:
    _active: Optional["TpuSession"] = None

    def __init__(self, conf: Optional[Union[RapidsConf, Dict]] = None,
                 mesh=None):
        """``mesh``: a ``jax.sharding.Mesh`` — supported queries then run
        distributed over it (parallel/dist_planner.py); alternatively set
        spark.rapids.sql.distributed.numShards to build one here."""
        if isinstance(conf, dict):
            conf = RapidsConf(conf)
        self.conf = conf or RapidsConf()
        from spark_rapids_tpu.exec.cache import CacheManager
        self.cache_manager = CacheManager()
        self.overrides = TpuOverrides(self.conf, self.cache_manager)
        self.last_dist_explain = ""
        self.last_scan_stats = None  # set by the sharded distributed scan
        self.last_pipeline_stats = None  # exec/pipeline.py PipelineStats
        # per-query shuffle-wire summary (parallel/shuffle.py
        # ShuffleWireMetrics.summarize): collectives, bytes moved,
        # padding ratio, slot-overflow retries of the last distributed
        # query; None when the query never exchanged
        self.last_shuffle_stats = None
        # per-query whole-stage fusion summary (exec/fusion.py):
        # fusedStages/fusedOperators/dispatchesSaved + persistent
        # jit-cache hit/miss deltas; None before the first query
        self.last_fusion_stats = None
        self.last_planning_error = None  # set by suppressPlanningFailure
        # persistent jit-cache tier (ops/jit_cache.py): process-global,
        # (re)configured from this session's conf — AOT-serialized
        # executables survive the process under jitCache.dir
        from spark_rapids_tpu.config import rapids_conf as _rc
        from spark_rapids_tpu.ops import jit_cache as _jc
        _jc.configure_persistent(
            self.conf.get(_rc.JIT_CACHE_DIR) or None,
            self.conf.get(_rc.JIT_CACHE_MAX_BYTES))
        # multi-controller bring-up MUST precede the first jax.devices()
        # call (mesh construction below): jax.distributed.initialize is
        # what makes the fleet's global devices visible
        self._init_fleet_runtime()
        self.mesh = mesh
        if self.mesh is None:
            from spark_rapids_tpu.config import rapids_conf as rc
            n = self.conf.get(rc.DISTRIBUTED_NUM_SHARDS)
            if n:
                from spark_rapids_tpu.parallel.mesh import make_mesh
                self.mesh = make_mesh(n)
        self._init_fleet_membership()
        self._init_memory()
        self._init_observability()
        if self.fleet_membership is not None:
            # the JOIN beat waits for the event logger so HostJoin
            # lands in the log (membership itself must exist earlier:
            # the serving caches read fleet_cache at construction)
            self.fleet_membership.beat(force=True)
        TpuSession._active = self

    def _init_fleet_runtime(self) -> None:
        """Join the multi-controller fleet when
        spark.rapids.tpu.fleet.coordinator/.processId/.numProcesses are
        configured (parallel/mesh.py init_fleet); single-controller
        configs no-op."""
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.parallel import mesh as mesh_lib
        self._fleet_multi = mesh_lib.init_fleet(
            self.conf.get(rc.FLEET_COORDINATOR),
            self.conf.get(rc.FLEET_PROCESS_ID),
            self.conf.get(rc.FLEET_NUM_PROCESSES))

    def _init_fleet_membership(self) -> None:
        """Stand up host membership + the fleet-scoped cache store.
        Three shapes: a real multi-controller fleet (hosts = jax
        processes), a logical-host fleet (fleet.logicalHosts partitions
        of a single-process mesh — the tier-1-testable simulation), or
        no fleet at all (every attribute None, zero overhead)."""
        import threading

        import jax
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.parallel import mesh as mesh_lib
        self.fleet_membership = None
        self.fleet_cache = None
        self.fleet_epoch = 0
        self._logical_hosts_assigned = False
        n_hosts, host = 1, 0
        if self._fleet_multi:
            n_hosts, host = jax.process_count(), jax.process_index()
        elif self.mesh is not None:
            logical = self.conf.get(rc.FLEET_LOGICAL_HOSTS)
            if logical >= 2:
                mesh_lib.assign_logical_hosts(self.mesh, logical)
                self._logical_hosts_assigned = True
                n_hosts = len(mesh_lib.mesh_hosts(self.mesh))
        if n_hosts > 1:
            self.fleet_membership = mesh_lib.HostMembership(
                mesh_lib.membership_dir(
                    self.conf.get(rc.FLEET_MEMBERSHIP_DIR),
                    self.conf.get(rc.FLEET_COORDINATOR)),
                host_id=host, n_hosts=n_hosts,
                heartbeat_ms=self.conf.get(rc.FLEET_HEARTBEAT_MS),
                missed_fatal=self.conf.get(rc.FLEET_MISSED_BEATS_FATAL),
                session=self)
        cache_dir = self.conf.get(rc.FLEET_CACHE_DIR)
        if cache_dir:
            from spark_rapids_tpu.serving.fleetcache import FleetStore
            self.fleet_cache = FleetStore(cache_dir, session=self)
            self.fleet_epoch = self.fleet_cache.fence_epoch()
        # gray-failure (fail-slow) runtime: default-off — None keeps
        # every consumption site a single getattr and the hot path
        # bit-identical to the knob-off run
        self.gray_health = None
        self.gray_deadlines = None
        self._full_mesh = None  # pre-quarantine mesh, for rejoin
        self._quarantined = set()  # hosts soft-shrunk but NOT lost
        self._gray_inflight = 0  # queries in flight (safe-boundary gate)
        self._gray_lock = threading.Lock()
        if self.conf.get(rc.GRAY_FAILURE_ENABLED):
            from spark_rapids_tpu.robustness.grayfailure import (
                DeadlineCalibrator, HostHealthTracker)
            if self.fleet_membership is not None:
                self.gray_health = HostHealthTracker(
                    session=self, host_id=host, n_hosts=n_hosts,
                    suspect_factor=self.conf.get(rc.FLEET_SUSPECT_FACTOR),
                    window=self.conf.get(rc.FLEET_SUSPECT_WINDOW),
                    min_samples=self.conf.get(rc.FLEET_SUSPECT_MIN_SAMPLES),
                    quarantine_after_ms=self.conf.get(
                        rc.FLEET_QUARANTINE_AFTER_MS),
                    rejoin_after_ms=self.conf.get(rc.FLEET_REJOIN_AFTER_MS),
                    hedge_percentile=self.conf.get(rc.FLEET_HEDGE_PERCENTILE),
                    hedge_margin=self.conf.get(rc.FLEET_HEDGE_MARGIN),
                    hedge_floor_ms=self.conf.get(rc.FLEET_HEDGE_FLOOR_MS))
            self.gray_deadlines = DeadlineCalibrator(
                floor_ms=self.conf.get(rc.WATCHDOG_CALIBRATION_FLOOR_MS),
                ceiling_ms=self.conf.get(rc.WATCHDOG_CALIBRATION_CEILING_MS),
                margin=self.conf.get(rc.WATCHDOG_CALIBRATION_MARGIN),
                min_samples=self.conf.get(
                    rc.WATCHDOG_CALIBRATION_MIN_SAMPLES))

    def shrink_fleet_mesh(self, lost_host: int = -1) -> bool:
        """The shrink rung's side effect (robustness/driver.py): swap
        ``session.mesh`` for one rebuilt over the surviving hosts, so
        the re-driven attempt plans distributed on what's left.  The
        fleet cache's fence epoch bumps atomically with the swap — a
        publish in flight from the lost host carries the OLD epoch and
        is rejected (it could hold bytes computed on the dead layout).
        ``lost_host`` names the casualty when known (-1: take the
        membership registry's lost set, else drop the highest-indexed
        remote host — the injected-loss-with-no-named-host case).
        Returns False when there is nothing to shrink."""
        from spark_rapids_tpu.parallel import mesh as mesh_lib
        membership = self.fleet_membership
        if membership is None or self.mesh is None:
            return False
        hosts_before = mesh_lib.mesh_hosts(self.mesh)
        lost = set(membership.lost)
        if lost_host >= 0:
            lost.add(lost_host)
        lost.discard(membership.host)
        if not (lost & set(hosts_before)):
            remote = [h for h in hosts_before if h != membership.host]
            if not remote:
                return False
            lost = {max(remote)}
        new_mesh = mesh_lib.surviving_mesh(self.mesh, lost)
        membership.lost |= lost
        from_devices = int(self.mesh.devices.size)
        if self._full_mesh is None:
            self._full_mesh = self.mesh  # rejoin's restore point
        self.mesh = new_mesh
        if self.fleet_cache is not None:
            self.fleet_epoch = self.fleet_cache.bump_fence(
                reason="shrink")
        from spark_rapids_tpu.utils.events import emit_on_session
        emit_on_session(
            "MeshShrink", self,
            fromHosts=len(hosts_before),
            toHosts=len(mesh_lib.mesh_hosts(new_mesh)),
            fromDevices=from_devices,
            toDevices=int(new_mesh.devices.size),
            lostHosts=sorted(lost), reason="host_loss")
        return True

    def quarantine_host(self, host: int) -> bool:
        """Gray-failure soft-shrink: drain a SUSPECT host out of the
        mesh through the SAME machinery the hard shrink rung uses
        (mesh swap + fence-epoch bump) — but the host is NOT judged
        lost: its beats keep flowing through the membership registry so
        the health tracker can watch it recover and rejoin it later."""
        from spark_rapids_tpu.parallel import mesh as mesh_lib
        if self.mesh is None or host < 0:
            return False
        hosts_before = mesh_lib.mesh_hosts(self.mesh)
        if host not in hosts_before or len(hosts_before) < 2:
            return False
        membership = self.fleet_membership
        if membership is not None and host == membership.host:
            return False  # never quarantine ourselves
        if self._full_mesh is None:
            self._full_mesh = self.mesh
        self._quarantined.add(host)
        drop = set(self._quarantined)
        if membership is not None:
            drop |= set(membership.lost)
        new_mesh = mesh_lib.surviving_mesh(self._full_mesh, drop)
        from_devices = int(self.mesh.devices.size)
        self.mesh = new_mesh
        if self.fleet_cache is not None:
            self.fleet_epoch = self.fleet_cache.bump_fence(
                reason="quarantine")
        tracker = self.gray_health
        if tracker is not None:
            tracker.mark_quarantined(host)
        from spark_rapids_tpu.utils.events import emit_on_session
        emit_on_session(
            "HostQuarantine", self, host=host,
            fromHosts=len(hosts_before),
            toHosts=len(mesh_lib.mesh_hosts(new_mesh)),
            fromDevices=from_devices,
            toDevices=int(new_mesh.devices.size))
        emit_on_session(
            "MeshShrink", self,
            fromHosts=len(hosts_before),
            toHosts=len(mesh_lib.mesh_hosts(new_mesh)),
            fromDevices=from_devices,
            toDevices=int(new_mesh.devices.size),
            lostHosts=sorted({host}), reason="quarantine")
        return True

    def rejoin_fleet_mesh(self, host: int) -> bool:
        """The shrink rung's inverse (new with gray failure): fold a
        recovered quarantined host back into the mesh at a safe
        boundary — caller guarantees no query in flight.  The fence
        epoch bumps AGAIN (advanced twice across quarantine→rejoin), so
        entries published against the shrunken layout are fenced from
        the restored one."""
        from spark_rapids_tpu.parallel import mesh as mesh_lib
        if self._full_mesh is None or host not in self._quarantined:
            return False
        self._quarantined.discard(host)
        membership = self.fleet_membership
        if membership is not None:
            membership.rejoin(host)
        drop = set(self._quarantined)
        if membership is not None:
            drop |= set(membership.lost)
        hosts_before = mesh_lib.mesh_hosts(self.mesh)
        from_devices = int(self.mesh.devices.size)
        new_mesh = (self._full_mesh if not drop
                    else mesh_lib.surviving_mesh(self._full_mesh, drop))
        self.mesh = new_mesh
        if not drop:
            self._full_mesh = None  # fully restored
        if self.fleet_cache is not None:
            self.fleet_epoch = self.fleet_cache.bump_fence(
                reason="rejoin")
        tracker = self.gray_health
        if tracker is not None:
            tracker.mark_rejoined(host)
        from spark_rapids_tpu.utils.events import emit_on_session
        emit_on_session(
            "HostRejoin", self, host=host,
            fromHosts=len(hosts_before),
            toHosts=len(mesh_lib.mesh_hosts(new_mesh)),
            fromDevices=from_devices,
            toDevices=int(new_mesh.devices.size))
        return True

    def maybe_apply_gray_actions(self) -> None:
        """Apply due quarantine/rejoin transitions — called from the
        recovery driver at a safe boundary (before a query's first
        attempt, when this is the only query in flight): mesh swaps
        never touch a plan mid-execution."""
        tracker = self.gray_health
        if tracker is None:
            return
        tracker.poll()
        with self._gray_lock:
            if self._gray_inflight > 1:
                return  # another query mid-flight: not a safe boundary
            for h in tracker.quarantine_due():
                self.quarantine_host(h)
            for h in tracker.rejoin_due():
                self.rejoin_fleet_mesh(h)

    def _init_observability(self) -> None:
        import itertools
        import uuid
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.utils.events import EventLogger
        self._query_ids = itertools.count(1)
        self.session_id = uuid.uuid4().hex[:12]
        # recovery actions (robustness/driver.py) in arrival order —
        # the in-memory mirror of the RecoveryAction event stream, so
        # tests and tools can read the trail without an event-log dir
        self.recovery_log = []
        # thread-keyed backing stores for the _current_qid /
        # checkpoints properties: one session serves concurrent
        # queries, each on its own driving thread, and a single
        # session-global "the qid in flight" would stamp query A's
        # recovery/watchdog/checkpoint events with query B's id
        self._qid_by_ident = {}
        self._checkpoints_by_ident = {}
        self._current_qid = None  # qid of the attempt in flight
        self.events = EventLogger(
            self.conf.get(rc.EVENT_LOG_DIR) or None, self.session_id,
            conf_snapshot=dict(self.conf.settings),
            flush_ms=self.conf.get(rc.EVENT_LOG_FLUSH_MS))
        # span-tracing runtime (utils/tracing.py): process-global, the
        # jitCache-tier discipline — this session's trace conf wins.
        # The observation store persists beside the AOT cache dir when
        # one is configured (warm starts get warm evidence), else
        # beside the trace exports.
        from spark_rapids_tpu.utils import tracing
        trace_dir = self.conf.get(rc.TRACE_DIR) or None
        self.last_span_stats = None  # QueryEnd spans rollup mirror
        tracing.configure(
            enabled=bool(self.conf.get(rc.TRACE_ENABLED) or trace_dir),
            trace_dir=trace_dir,
            max_events=self.conf.get(rc.TRACE_MAX_EVENTS),
            obs_dir=(self.conf.get(rc.JIT_CACHE_DIR) or trace_dir
                     or None),
            profile=bool(self.conf.get(rc.PROFILE_TRACE)))
        # self-tuning cost-based planner (plan/costmodel.py): one
        # evidence-fed decision authority over every tuning knob,
        # default-off — None keeps every consumption site a single
        # getattr and plans bit-identical to HEAD
        self.cost_model = None
        self.last_planner_stats = None  # QueryEnd planner dict mirror
        if self.conf.get(rc.COSTMODEL_ENABLED):
            from spark_rapids_tpu.plan.costmodel import CostModel
            self.cost_model = CostModel(self, self.conf)

    # per-query state views: call sites keep reading/writing
    # ``session._current_qid`` / ``session.checkpoints`` and get the
    # CALLING query's value — resolution is by effective thread ident
    # (worker threads adopted via exec/pipeline.worker_attribution
    # resolve to their driving query)
    @property
    def _current_qid(self):
        from spark_rapids_tpu.serving import context as qc
        return getattr(self, "_qid_by_ident", {}).get(
            qc.effective_ident())

    @_current_qid.setter
    def _current_qid(self, qid) -> None:
        from spark_rapids_tpu.serving import context as qc
        ident = qc.effective_ident()
        if qid is None:
            self._qid_by_ident.pop(ident, None)
        else:
            self._qid_by_ident[ident] = qid
        ctx = qc.current()
        if ctx is not None:
            ctx.set_qid(qid)

    @property
    def checkpoints(self):
        from spark_rapids_tpu.serving import context as qc
        return getattr(self, "_checkpoints_by_ident", {}).get(
            qc.effective_ident())

    @checkpoints.setter
    def checkpoints(self, mgr) -> None:
        from spark_rapids_tpu.serving import context as qc
        ident = qc.effective_ident()
        if mgr is None:
            self._checkpoints_by_ident.pop(ident, None)
        else:
            self._checkpoints_by_ident[ident] = mgr
        ctx = qc.current()
        if ctx is not None:
            ctx.checkpoints = mgr

    def stop(self) -> None:
        """Close the session's observability resources (SessionEnd)
        and sweep its spill tier — live handles close, orphaned
        ``buf-*`` spill/temp files are deleted, and the catalog's own
        temp dir is removed (the RapidsDiskStore shutdown analog)."""
        self.events.close()
        from spark_rapids_tpu.utils import tracing
        obs = tracing.observation_store()
        if obs is not None:
            obs.flush()
        cm = getattr(self, "cost_model", None)
        if cm is not None:
            try:
                cm.store.flush()
            except Exception:
                pass  # evidence persistence must not block teardown
        for store_attr in ("result_cache", "shared_stages"):
            store = getattr(self, store_attr, None)
            if store is not None:
                try:
                    store.close()
                except Exception:
                    pass  # teardown must reach the catalog sweep
        membership = getattr(self, "fleet_membership", None)
        if membership is not None:
            membership.leave()
        if getattr(self, "_logical_hosts_assigned", False):
            # module-level simulation state must not leak into the
            # next session's link classification
            from spark_rapids_tpu.parallel.mesh import \
                clear_logical_hosts
            clear_logical_hosts()
        cat = getattr(self, "memory_catalog", None)
        if cat is not None:
            cat.close()
        if TpuSession._active is self:
            TpuSession._active = None

    def _init_memory(self) -> None:
        """GpuDeviceManager.initializeGpuAndMemory analog: size the spill
        catalog from HBM and install the admission semaphore."""
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.memory.spill import (
            SpillableBatchCatalog, TpuSemaphore, set_default_catalog)
        device_budget = self.conf.get(rc.DEVICE_MEMORY_LIMIT)
        if not device_budget:
            import jax
            # this process's own device: in a multi-controller fleet
            # devices()[0] belongs to process 0 and answers only there
            dev = jax.local_devices()[0]
            if dev.platform == "tpu":
                # the chip reports its HBM; a missing figure is a broken
                # backend, not something to guess around
                hbm = dev.memory_stats()["bytes_limit"]
            else:
                # the CPU backend reports no limit: size the pool as if
                # for 16 GiB so tests spill where a chip would
                cpu_assumed_bytes = 16 << 30
                hbm = (dev.memory_stats() or {}).get(
                    "bytes_limit", cpu_assumed_bytes)
            # GpuDeviceManager.scala:170-245 sizing contract: subtract
            # the runtime reserve, apply alloc fraction, clamp to the
            # max fraction, and fail fast below the min fraction
            reserve = self.conf.get(rc.MEM_RESERVE)
            usable = max(hbm - reserve, 0)
            device_budget = int(usable * self.conf.get(rc.MEM_POOL_FRACTION))
            max_budget = int(usable * self.conf.get(rc.MEM_MAX_ALLOC_FRACTION))
            device_budget = min(device_budget, max_budget)
            min_budget = int(hbm * self.conf.get(rc.MEM_MIN_ALLOC_FRACTION))
            if device_budget < min_budget:
                raise ValueError(
                    f"device pool {device_budget} bytes is below "
                    f"minAllocFraction*HBM ({min_budget}); lower "
                    "spark.rapids.memory.tpu.reserve / raise "
                    "allocFraction, or lower minAllocFraction")
        from spark_rapids_tpu import native
        self.memory_catalog = SpillableBatchCatalog(
            device_budget=device_budget,
            host_budget=self.conf.get(rc.HOST_SPILL_STORAGE_SIZE),
            frame_codec=native.codec_level(
                self.conf.get(rc.SHUFFLE_COMPRESSION_CODEC)),
            disk_write_threads=self.conf.get(rc.SPILL_DISK_WRITE_THREADS),
            integrity_check=self.conf.get(rc.SPILL_INTEGRITY_ENABLED),
            checkpoint_floor=self.conf.get(
                rc.SERVING_CHECKPOINT_FLOOR_BYTES),
            host_codec=native.codec_level(
                self.conf.get(rc.ENCODING_STORAGE_HOST_CODEC)))
        set_default_catalog(self.memory_catalog)
        self.semaphore = TpuSemaphore(
            self.conf.get(rc.CONCURRENT_TPU_TASKS))
        # session-level admission control (serving/admission.py): the
        # query-granularity GpuSemaphore — at most concurrentQueries
        # in flight, their memory weights fitting in
        # hbmAdmissionFraction of the device budget; 0 disables
        n_adm = self.conf.get(rc.SERVING_CONCURRENT_QUERIES)
        if n_adm > 0:
            from spark_rapids_tpu.serving.admission import (
                AdmissionController)
            self.admission = AdmissionController(
                max_queries=n_adm,
                hbm_bytes=int(device_budget * self.conf.get(
                    rc.SERVING_HBM_ADMISSION_FRACTION)),
                default_weight=self.conf.get(
                    rc.SERVING_QUERY_MEMORY_BUDGET),
                timeout_ms=self.conf.get(
                    rc.SERVING_ADMISSION_TIMEOUT_MS),
                max_queue=self.conf.get(rc.SERVING_MAX_QUEUED_QUERIES))
        else:
            self.admission = None
        # fair interleaving + cross-query reuse (serving/scheduler.py,
        # serving/reuse.py) — all default-off; None attributes keep the
        # knobs-off hot path to a single getattr
        self.interleaver = None
        if self.conf.get(rc.SERVING_INTERLEAVE_ENABLED):
            from spark_rapids_tpu.serving.scheduler import (
                FairInterleaver)
            self.interleaver = FairInterleaver(
                self.conf.get(rc.SERVING_INTERLEAVE_QUANTUM))
        self.result_cache = None
        if self.conf.get(rc.SERVING_RESULT_CACHE_ENABLED):
            from spark_rapids_tpu.serving.reuse import ResultCache
            self.result_cache = ResultCache(self)
        self.shared_stages = None
        if self.conf.get(rc.SERVING_SHARED_STAGE_ENABLED):
            from spark_rapids_tpu.serving.reuse import SharedStageCache
            self.shared_stages = SharedStageCache(self)

    # --------------------------------------------------------------- builders --
    @classmethod
    def builder(cls) -> "SessionBuilder":
        return SessionBuilder()

    @classmethod
    def active(cls) -> "TpuSession":
        if cls._active is None:
            cls._active = TpuSession()
        return cls._active

    def set_conf(self, key: str, value) -> None:
        from spark_rapids_tpu.config import rapids_conf as rc
        old_log_dir = self.conf.get(rc.EVENT_LOG_DIR)
        self.conf = self.conf.set(key, value)
        self.overrides = TpuOverrides(self.conf, self.cache_manager)
        if self.conf.get(rc.EVENT_LOG_DIR) != old_log_dir:
            # rebuild the logger so a post-construction eventLog.dir
            # change takes effect instead of being silently ignored
            self.events.close()
            self._init_observability()

    # ------------------------------------------------------------ data inputs --
    def create_dataframe(self, data, schema: Optional[Sequence[str]] = None
                         ) -> DataFrame:
        import pandas as pd
        import pyarrow as pa
        from spark_rapids_tpu.columnar.nested import check_reserved_names
        if isinstance(data, pd.DataFrame):
            check_reserved_names(data.columns)
            batch = ColumnarBatch.from_pandas(data)
        elif isinstance(data, pa.Table):
            check_reserved_names(data.column_names)
            batch = ColumnarBatch.from_arrow(data)
        elif isinstance(data, dict):
            check_reserved_names(data.keys())
            batch = ColumnarBatch.from_pydict(data)
        elif isinstance(data, ColumnarBatch):
            batch = data
        elif isinstance(data, list) and schema is not None:
            batch = ColumnarBatch.from_pydict(
                {name: [row[i] for row in data]
                 for i, name in enumerate(schema)})
        else:
            raise TypeError(f"cannot create DataFrame from {type(data)}")
        rel = L.InMemoryRelation([batch], batch.schema)
        return DataFrame(self, rel)

    createDataFrame = create_dataframe

    def create_dataframe_from_jax(self, arrays: dict,
                                  masks: Optional[dict] = None
                                  ) -> DataFrame:
        """ML-interop ingest: build a DataFrame directly from jax device
        arrays (zero host round trip — the inverse of
        ``DataFrame.to_jax``: ``name__mask`` keys route automatically
        into validity).  ``masks``: optional {name: bool array}
        validity, merged with any inline ``__mask`` keys."""
        from spark_rapids_tpu.columnar.column import (
            Column, bucket_capacity)
        from spark_rapids_tpu.columnar.dtypes import from_numpy_dtype
        from spark_rapids_tpu.columnar.nested import check_reserved_names
        import jax.numpy as jnp
        import numpy as np
        masks = dict(masks or {})
        # round-trip support: to_jax() emits validity as '<name>__mask'
        inline = {n: a for n, a in arrays.items()
                  if n.endswith("__mask")}
        if inline:
            arrays = {n: a for n, a in arrays.items() if n not in inline}
            for n, a in inline.items():
                base = n[:-len("__mask")]
                if base not in arrays:
                    raise ValueError(
                        f"mask key {n!r} has no matching column "
                        f"{base!r}")
                masks.setdefault(base, a)
        check_reserved_names(arrays.keys())
        unknown = set(masks) - set(arrays)
        if unknown:
            raise ValueError(f"masks for unknown column(s) {unknown}")
        cols = {}
        nrows = None
        for name, arr in arrays.items():
            arr = jnp.asarray(arr)
            if arr.ndim != 1:
                raise ValueError(
                    f"column {name!r}: expected 1-D array, got "
                    f"shape {arr.shape}")
            if nrows is None:
                nrows = arr.shape[0]
            elif arr.shape[0] != nrows:
                raise ValueError(
                    f"column {name!r}: length {arr.shape[0]} != {nrows}")
            dt = from_numpy_dtype(np.dtype(arr.dtype))
            cap = bucket_capacity(nrows)
            if arr.shape[0] < cap:
                arr = jnp.concatenate(
                    [arr, jnp.zeros(cap - arr.shape[0], dtype=arr.dtype)])
            validity = masks.get(name)
            if validity is not None:
                validity = jnp.asarray(validity).astype(bool)
                if validity.shape[0] != nrows:
                    raise ValueError(
                        f"mask for {name!r}: length "
                        f"{validity.shape[0]} != {nrows}")
                validity = jnp.concatenate(
                    [validity,
                     jnp.zeros(cap - validity.shape[0], dtype=bool)])
            cols[name] = Column(dt, arr, nrows, validity=validity)
        batch = ColumnarBatch(cols, nrows or 0)
        rel = L.InMemoryRelation([batch], batch.schema)
        return DataFrame(self, rel)

    def range(self, start: int, end: Optional[int] = None,
              step: int = 1) -> DataFrame:
        if end is None:
            start, end = 0, start
        return DataFrame(self, L.Range(start, end, step))

    @property
    def read(self) -> DataFrameReader:
        return DataFrameReader(self)

    # ------------------------------------------------------------- SQL --
    def register_view(self, name: str, df: DataFrame) -> None:
        """Temp-view registry backing ``session.sql`` FROM clauses
        (df.createOrReplaceTempView forwards here)."""
        if not hasattr(self, "_views"):
            self._views = {}
        self._views[name.lower()] = df

    def table(self, name: str) -> DataFrame:
        views = getattr(self, "_views", {})
        key = name.lower()
        if key not in views:
            raise KeyError(
                f"unknown table or view {name!r}; register with "
                "df.createOrReplaceTempView(name)")
        return views[key]

    def sql(self, query: str) -> DataFrame:
        """Run a SQL SELECT over registered temp views (the SQL string
        entry point; parsing/lowering in spark_rapids_tpu/sql/)."""
        from spark_rapids_tpu.sql import parse, resolve
        return resolve(self, parse(query))

    def prepare(self, df: DataFrame):
        """Prepare ``df`` as a parameterized plan template
        (api/prepared.py): the literal-hoisting pass runs ONCE, and
        each ``handle.run(p0=..., ...)`` binds a fresh parameter
        vector and executes — zero re-planning, zero retracing and
        zero recompilation across literal churn, while admission,
        budgets, the recovery ladder and span tracing all still
        apply.  Requires ``spark.rapids.tpu.template.enabled``."""
        from spark_rapids_tpu.api.prepared import PreparedStatement
        return PreparedStatement(self, df)

    # --------------------------------------------------- continuous ingest --
    def incremental(self, df: DataFrame, fact: Optional[str] = None,
                    watermark_delay_ms: Optional[int] = None):
        """Stand ``df`` up as a continuous-ingest micro-batch query
        (robustness/incremental.py): the returned
        :class:`MicroBatchRunner`'s ``tick(new_paths)`` ingests
        appended files and answers over everything ingested so far,
        re-executing only the delta and merging with crash-consistent
        committed state — any mid-tick fault rolls back to the last
        committed epoch and the tick degrades to a full recompute.
        Aggregates, delta-joins (new fact batches × unchanged
        dimension state), windowed aggregation with watermark
        eviction, and provably-mergeable top-N all tick
        incrementally; anything else ticks as a full re-execution
        with lineage splice.  Every commit also yields an
        exactly-once :class:`SinkCommit` (``runner.last_sink_commit``,
        or the ``runner.on_commit`` callback).  ``fact`` designates
        the append-target scan for multi-scan plans (a fact⋈dim join
        over two file tables): pass any path already in the fact
        table's file list.  ``watermark_delay_ms`` overrides the
        session watermark conf for THIS runner.  Governed by
        ``spark.rapids.tpu.incremental.*``."""
        from spark_rapids_tpu.robustness.incremental import (
            MicroBatchRunner)
        return MicroBatchRunner(self, df, fact=fact,
                                watermark_delay_ms=watermark_delay_ms)

    def fleet(self):
        """A standing-query fleet over one append-only stream
        (serving/fleet.py): ``fleet().subscribe(df, ...)`` registers
        standing queries; each ``tick(new_paths)`` round pulls the
        delta ONCE and fans the batches out to every subscriber,
        whose epochs commit/roll back independently and whose
        committed stage work cross-splices through the epoch-aware
        shared stage cache.  Every subscriber tick returns an
        exactly-once :class:`SinkCommit`.  Governed by
        ``spark.rapids.tpu.fleet.*``."""
        from spark_rapids_tpu.serving.fleet import FleetRunner
        return FleetRunner(self)

    # --------------------------------------------------------------- planning --
    def plan(self, logical: L.LogicalPlan, overrides=None,
             pushdown: bool = True):
        from spark_rapids_tpu.config import rapids_conf as rc
        # a caller may plan through a one-off TpuOverrides (the recovery
        # driver's split-batch rung scales batch sizes this way) without
        # mutating session state under concurrent queries
        ov = overrides if overrides is not None else self.overrides
        if self.conf.get(rc.SUPPRESS_PLANNING_FAILURE):
            # sql.suppressPlanningFailure: a bug in TPU planning demotes
            # the whole query to the CPU fallback chain instead of
            # failing it (RapidsConf.scala suppressPlanningFailure)
            try:
                exec_plan = ov.apply(logical, pushdown=pushdown)
            except Exception as exc:
                import warnings
                # surface the root cause: the CPU chain may itself lack
                # a branch for some node, and that later error must not
                # eat the actual planner bug
                warnings.warn(
                    f"TPU planning failed ({type(exc).__name__}: {exc}); "
                    "demoting the whole query to the CPU fallback chain "
                    "(spark.rapids.sql.suppressPlanningFailure)",
                    RuntimeWarning, stacklevel=2)
                self.last_planning_error = exc
                exec_plan = self.plan_cpu_only(logical)
        else:
            exec_plan = ov.apply(logical, pushdown=pushdown)
        return exec_plan

    def plan_cpu_only(self, logical: L.LogicalPlan):
        """Plan the whole query onto the CPU fallback chain — the
        terminal rung of the recovery ladder (robustness/driver.py)
        and the suppressPlanningFailure demotion target."""
        from spark_rapids_tpu.exec.fallback import CpuFallbackExec

        def whole_cpu(n):
            return CpuFallbackExec(n, [whole_cpu(c) for c in n.children])
        return whole_cpu(logical)


class SessionBuilder:
    def __init__(self):
        self._conf: Dict[str, str] = {}

    def config(self, key: str, value) -> "SessionBuilder":
        self._conf[key] = value
        return self

    def getOrCreate(self) -> TpuSession:
        return TpuSession(RapidsConf(self._conf))
