"""Typed, self-documenting configuration registry.

Counterpart of ``sql-plugin/.../RapidsConf.scala`` (1,745 LoC, 119 entries):
typed entries with defaults, docs and validators, a global registry, and a
``generate_docs()`` that renders the configs reference markdown the same way
``RapidsConf.main`` writes ``docs/configs.md``.

Key names keep the reference's ``spark.rapids.*`` prefix so that users of the
reference find the same knobs; GPU-specific words become TPU ones
(``concurrentGpuTasks`` -> ``concurrentTpuTasks``).
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, List, Optional


class ConfEntry:
    """One typed config entry (RapidsConf.scala:116 `ConfEntry`)."""

    def __init__(self, key: str, default: Any, doc: str, conv: Callable,
                 validator: Optional[Callable[[Any], Optional[str]]] = None,
                 internal: bool = False):
        self.key = key
        self.default = default
        self.doc = doc
        self.conv = conv
        self.validator = validator
        self.internal = internal

    def env_key(self) -> str:
        """The entry's environment-variable form — the ONE derivation
        shared by value resolution (``get``) and the explicitly-set
        test (``RapidsConf.is_set``), so the cost model's
        override-vs-decide discipline can never diverge from what
        ``get`` actually reads."""
        return self.key.upper().replace(".", "_")

    def get(self, settings: Dict[str, str]) -> Any:
        raw = settings.get(self.key)
        if raw is None:
            raw = os.environ.get(self.env_key())
        if raw is None:
            return self.default
        value = self.conv(raw) if isinstance(raw, str) else raw
        if self.validator is not None:
            err = self.validator(value)
            if err:
                raise ValueError(f"{self.key}={value!r}: {err}")
        return value


def _to_bool(s: str) -> bool:
    return s.strip().lower() in ("1", "true", "yes", "on")


def _to_int(s: str) -> int:
    return int(s)


def _to_float(s: str) -> float:
    return float(s)


_REGISTRY: Dict[str, ConfEntry] = {}


def _register(entry: ConfEntry) -> ConfEntry:
    assert entry.key not in _REGISTRY, f"duplicate conf {entry.key}"
    _REGISTRY[entry.key] = entry
    return entry


def conf(key, default, doc, conv=str, validator=None, internal=False):
    return _register(ConfEntry(key, default, doc, conv, validator, internal))


def _positive(v):
    return None if v > 0 else "must be positive"


def _fraction(v):
    return None if 0.0 <= v <= 1.0 else "must be in [0, 1]"


# --------------------------------------------------------------------- entries --
SQL_ENABLED = conf(
    "spark.rapids.sql.enabled", True,
    "Enable or disable TPU acceleration of SQL operators entirely. "
    "(reference RapidsConf.scala:514)", _to_bool)

EXPLAIN = conf(
    "spark.rapids.sql.explain", "NONE",
    "Explain why parts of a query did or did not run on TPU: NONE, "
    "NOT_ON_TPU, ALL. (reference `sql.explain` RapidsConf.scala:1142)", str,
    lambda v: None if v in ("NONE", "NOT_ON_TPU", "ALL") else
    "must be NONE, NOT_ON_TPU or ALL")

EVENT_LOG_DIR = conf(
    "spark.rapids.tpu.eventLog.dir", "",
    "Directory for the session's JSON-lines query event log (plans, per-op "
    "metrics, spill stats). Empty disables logging. Consumed by the "
    "qualification/profiling tools (reference analog: Spark event logs + "
    "GpuMetric -> SQLMetrics).", str)

EVENT_LOG_FLUSH_MS = conf(
    "spark.rapids.tpu.eventLog.flushMs", 0,
    "Batched event-log flushing: lines are written immediately but "
    "fsync-class flush()es are coalesced to at most one per this many "
    "milliseconds, so hot-path emitters (the watchdog monitor, spill "
    "integrity) stop paying a flush per line. 0 (default) keeps "
    "flush-per-line (today's behavior). QueryEnd/QueryFatal/SessionEnd "
    "always flush explicitly, so crash post-mortems still see the "
    "tail.", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

TRACE_ENABLED = conf(
    "spark.rapids.tpu.trace.enabled", False,
    "Arm the span-tracing runtime (utils/tracing.py): thread-aware, "
    "query-attributed wall-clock spans over operator batch loops, "
    "fused-stage dispatch, jit trace/AOT-cache loads, host syncs, "
    "exchange launch/resolve, spill tier transitions, checkpoint "
    "write/resume, incremental tick phases, admission and UDF-pool "
    "waits. Spans drain at QueryEnd into the QueryEnd 'spans' rollup "
    "(eventlog QueryInfo.spans -> profiling \"Where the time went\"), "
    "the per-site observation store, and — with trace.dir set — a "
    "Perfetto-loadable Chrome trace file per query. Default off; when "
    "off every span site costs a single branch and results are "
    "bit-identical either way. Setting trace.dir also arms tracing. "
    "Process-global (the jitCache.dir discipline): the last-"
    "constructed session's setting wins.", _to_bool)

TRACE_DIR = conf(
    "spark.rapids.tpu.trace.dir", "",
    "Directory for per-query Chrome-trace-event JSON exports "
    "(tools/traceview.py; open at ui.perfetto.dev). One file per "
    "query envelope, written at QueryEnd — including failed and fatal "
    "envelopes, so post-mortems get a timeline. Empty disables export "
    "(the spans rollup and observation store still work when "
    "trace.enabled is set). Setting this implies trace.enabled.", str)

TRACE_MAX_EVENTS = conf(
    "spark.rapids.tpu.trace.maxEvents", 100_000,
    "Bound on span records per query: per-thread buffers stop "
    "recording past this many events and the exported trace carries "
    "an explicit trace-truncated marker with the dropped count — a "
    "bounded trace never silently reads as complete.", _to_int,
    _positive)

PROFILE_TRACE = conf(
    "spark.rapids.tpu.profile.trace", False,
    "Enter a jax.profiler annotation for the lifetime of every "
    "engine span (utils/tracing.py), named by the span's operator where "
    "it has one (TpuFileScanExec) and by its point otherwise "
    "(io.reader, hostsync.fetch, ...), so the engine's spans lie on the "
    "profiler's clock beside the device's programs in XPlane/perfetto "
    "captures (the NVTX-range analog, NvtxWithMetrics.scala).  Works "
    "with or without spark.rapids.tpu.trace.enabled.", _to_bool)

BATCH_SIZE_BYTES = conf(
    "spark.rapids.sql.batchSizeBytes", 1 << 31,
    "Target size in bytes for columnar batches; hard-capped at 2 GiB "
    "mirroring the reference's per-column row-count limit "
    "(RapidsConf.scala:436-444).", _to_int,
    lambda v: None if 0 < v <= (1 << 31) else "must be in (0, 2GiB]")

BATCH_ROW_CAPACITY = conf(
    "spark.rapids.sql.tpu.maxBatchRows", 1 << 22,
    "Maximum rows per device batch (shape-bucket ceiling). TPU-specific: "
    "bounds the set of XLA-compiled shapes.", _to_int, _positive)

SORT_OOC_THRESHOLD = conf(
    "spark.rapids.sql.sort.outOfCoreThresholdBytes", 256 << 20,
    "Total input bytes above which multi-batch sorts use the windowed "
    "out-of-core merge (sorted spillable runs, bounded merge windows) "
    "instead of one concatenated device sort (reference "
    "GpuSortExec.scala:225 GpuOutOfCoreSortIterator).", _to_int, _positive)

SORT_OOC_WINDOW_ROWS = conf(
    "spark.rapids.sql.sort.outOfCoreWindowRows", 1 << 16,
    "Rows pulled from each sorted run per merge step of the out-of-core "
    "sort; bounds the merge working set to ~2*runs*window rows.",
    _to_int, _positive)

AGG_MERGE_CHUNK_ROWS = conf(
    "spark.rapids.sql.agg.mergeChunkRows", 1 << 22,
    "Partial-aggregate batches are merged in chunks of at most this many "
    "rows (tree reduction) instead of one concatenation of every partial, "
    "so the merge working set stays bounded (reference sort-based "
    "fallback, aggregate.scala:184-197).", _to_int, _positive)

CONCURRENT_TPU_TASKS = conf(
    "spark.rapids.sql.concurrentTpuTasks", 1,
    "Number of tasks that may issue work to the TPU concurrently "
    "(reference `concurrentGpuTasks` RapidsConf.scala:423).", _to_int,
    _positive)

DECIMAL_ENABLED = conf(
    "spark.rapids.sql.decimalType.enabled", True,
    "Enable decimal (DECIMAL_64) processing: device arithmetic with "
    "Spark result-type rules and overflow->null, sum and avg over up to "
    "decimal(8,s) children (avg is exact: decimal(p+4,s+4), HALF_UP); "
    "wider sum buffers fall back to CPU "
    "(reference RapidsConf.scala:564).", _to_bool)

OPTIMIZER_TRANSITION_COST = conf(
    "spark.rapids.sql.optimizer.transitionRowCost", 0.1,
    "Microseconds per row charged for a host<->device transition by the "
    "cost-based optimizer; operator costs come calibrated from "
    "plan/cbo_weights.json (regenerate with "
    "spark-rapids-tpu-cbo-calibrate).", _to_float)

INCOMPAT_ENABLED = conf(
    "spark.rapids.sql.incompatibleOps.enabled", True,
    "Run operators whose semantics differ from CPU Spark in documented "
    "corner cases (ASCII-only case mapping, byte-semantics regex). The "
    "reference defaults this OFF (RapidsMeta.scala:271); this engine "
    "defaults ON because each incompat is individually documented and "
    "per-op keys (spark.rapids.sql.expression.<Name>) can disable any "
    "single one.", _to_bool)

REGEXP_ENABLED = conf(
    "spark.rapids.sql.regexp.enabled", True,
    "Evaluate regular-expression expressions (rlike, regexp_replace, "
    "split_part) on device; when false every regex expression tags off "
    "to the CPU fallback (reference `sql.regexp.enabled`, "
    "RapidsConf.scala).", _to_bool)

VARIABLE_FLOAT_AGG = conf(
    "spark.rapids.sql.variableFloatAgg.enabled", True,
    "Allow sum/avg over floating-point values even though chunked and "
    "distributed evaluation reorders the additions, so results can "
    "differ from CPU Spark in the last ulps (reference "
    "`sql.variableFloatAgg.enabled`; defaults ON here because the "
    "engine is chunk-parallel by construction).", _to_bool)

CAST_STRING_TO_FLOAT = conf(
    "spark.rapids.sql.castStringToFloat.enabled", True,
    "Allow string->float casts on device (reference "
    "`sql.castStringToFloat.enabled`; tiny-ulp differences possible "
    "for values near the subnormal range).", _to_bool)

CAST_FLOAT_TO_STRING = conf(
    "spark.rapids.sql.castFloatToString.enabled", True,
    "Allow float->string casts on device (reference "
    "`sql.castFloatToString.enabled`; formatting of some exponents "
    "differs from Java).", _to_bool)

CAST_FLOAT_TO_DECIMAL = conf(
    "spark.rapids.sql.castFloatToDecimal.enabled", True,
    "Allow float->decimal casts on device (reference "
    "`sql.castFloatToDecimal.enabled`).", _to_bool)

CAST_STRING_TO_TIMESTAMP = conf(
    "spark.rapids.sql.castStringToTimestamp.enabled", True,
    "Allow string->timestamp/date casts on device (reference "
    "`sql.castStringToTimestamp.enabled`; only the fixed-width ISO "
    "subset parses on device).", _to_bool)

SUPPRESS_PLANNING_FAILURE = conf(
    "spark.rapids.sql.suppressPlanningFailure", False,
    "When TPU planning itself raises, retry the whole query on the "
    "CPU fallback chain instead of failing (reference "
    "`sql.suppressPlanningFailure`, RapidsConf.scala).", _to_bool)

MEM_POOL_FRACTION = conf(
    "spark.rapids.memory.tpu.allocFraction", 0.9,
    "Fraction of HBM this engine may retain in its batch pool before "
    "spilling (reference `memory.gpu.allocFraction`).", _to_float, _fraction)

MEM_MIN_ALLOC_FRACTION = conf(
    "spark.rapids.memory.tpu.minAllocFraction", 0.25,
    "Minimum fraction of HBM the batch pool must be able to claim; "
    "session init fails fast when reserve/limit squeeze the pool below "
    "this (reference `memory.gpu.minAllocFraction`, "
    "GpuDeviceManager.scala:170-245).", _to_float, _fraction)

MEM_MAX_ALLOC_FRACTION = conf(
    "spark.rapids.memory.tpu.maxAllocFraction", 1.0,
    "Hard ceiling on the HBM fraction the batch pool may claim, "
    "applied after the reserve is subtracted (reference "
    "`memory.gpu.maxAllocFraction`).", _to_float, _fraction)

MEM_RESERVE = conf(
    "spark.rapids.memory.tpu.reserve", 640 << 20,
    "Bytes of HBM held back from the pool for the XLA runtime and "
    "compiled-program scratch (the CUDA-context reserve analog, "
    "`memory.gpu.reserve`).", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

HOST_SPILL_STORAGE_SIZE = conf(
    "spark.rapids.memory.host.spillStorageSize", 1 << 30,
    "Bytes of host memory used as the first spill tier before disk "
    "(reference RapidsConf.scala:357).", _to_int, _positive)

SPILL_DISK_WRITE_THREADS = conf(
    "spark.rapids.memory.spill.diskWriteThreads", 2,
    "Concurrent writer threads used when demoting host-tier batches "
    "to disk; the native pager releases the GIL so writes overlap "
    "(reference spill-thread sizing, RapidsConf.scala:393).",
    _to_int, _positive)

DEVICE_MEMORY_LIMIT = conf(
    "spark.rapids.memory.tpu.deviceLimitBytes", 0,
    "Device-pool budget in bytes for spillable batches; 0 = derive from HBM "
    "size * allocFraction.", _to_int)

SHUFFLE_PARTITIONS = conf(
    "spark.rapids.sql.shuffle.partitions", 8,
    "Default number of shuffle partitions (spark.sql.shuffle.partitions "
    "analog).", _to_int, _positive)

SHUFFLE_COMPRESSION_CODEC = conf(
    "spark.rapids.shuffle.compression.codec", "lz4",
    "Codec for host-path frame payloads (spill, cache, host-staged "
    "shuffle): none, zrle (zero-RLE only), lz4 (zrle + LZ4-class lzb, "
    "smaller wins per buffer; zstd accepted as an alias) — reference "
    "TableCompressionCodec.scala:107.", str,
    lambda v: None if v in ("none", "zrle", "lz4", "zstd")
    else "unknown codec")

WINDOW_BATCH_ROWS = conf(
    "spark.rapids.sql.window.batchRows", 1 << 20,
    "Target rows per window-operator chunk when the input arrives "
    "sorted (the planner inserts a sort under every partitioned "
    "window). Chunks flush at partition boundaries (the "
    "GpuKeyBatchingIterator analog); a single partition larger than "
    "this streams with running-state carry when every window function "
    "in the operator has a running frame, and otherwise grows the "
    "chunk.", _to_int, _positive)

DISTRIBUTED_ENABLED = conf(
    "spark.rapids.sql.distributed.enabled", True,
    "When the session holds a device mesh, offer every query plan to the "
    "distributed planner (parallel/dist_planner.py) before the single-"
    "process engine; unsupported plans fall back with the reason on "
    "session.last_dist_explain (the planner-inserted exchange analog, "
    "reference GpuShuffleExchangeExec.scala:120).", _to_bool)

DISTRIBUTED_NUM_SHARDS = conf(
    "spark.rapids.sql.distributed.numShards", 0,
    "Build an N-device mesh at session start and run supported queries "
    "distributed (0 = only when a Mesh is passed to TpuSession "
    "directly). N devices must already be visible to jax — real chips, "
    "or virtual CPU devices which require XLA_FLAGS="
    "--xla_force_host_platform_device_count=N to be set BEFORE jax "
    "initializes; session construction raises otherwise.", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

SHUFFLE_PACKED_ENABLED = conf(
    "spark.rapids.tpu.shuffle.packed.enabled", True,
    "Fused packed shuffle wire format: gather all fixed-width columns of "
    "an exchange into width-homogeneous lane payloads (uint32 lanes for "
    "4-byte columns and int64, uint8 lanes for bool/small ints, validity "
    "masks bit-packed eight to a lane, float64 columns as themselves in "
    "an f64 group — the TPU compiler refuses to bit-cast a double) and "
    "move each payload with ONE all_to_all — O(distinct widths) <= 3 "
    "collectives per exchange instead of O(columns + masks). False restores per-column "
    "collectives (the A/B baseline, and the automatic fallback for "
    "exchanges carrying unpackable columns). See docs/performance.md "
    "\"Shuffle wire format\".", _to_bool)

SHUFFLE_SLOT_MODE = conf(
    "spark.rapids.tpu.shuffle.slot.mode", "adaptive",
    "All-to-all slot (padding) sizing per exchange site: 'adaptive' "
    "smooths the power-of-two slot with a per-site EMA of observed max "
    "slices (stable slots keep jit-cache keys stable) and lets warm "
    "sites launch speculatively without the stats hostsync — a slot "
    "overflow re-runs the launch at full capacity and records a "
    "degradable recovery action instead of dropping rows; 'fixed' sizes "
    "every launch from its own histogram only; 'capacity' restores "
    "full-capacity padding (always correct, numShards x the useful "
    "bytes on ICI).", str,
    lambda v: None if v in ("adaptive", "fixed", "capacity") else
    "must be adaptive, fixed or capacity")

SHUFFLE_SLOT_OVERFLOW_GROWTH = conf(
    "spark.rapids.tpu.shuffle.slot.overflowGrowth", 2.0,
    "Multiplier applied to an exchange site's slot EMA after a "
    "speculative-slot overflow, so the next stats-sized launch carries "
    "headroom above the slice that overflowed.", _to_float,
    lambda v: None if v >= 1.0 else "must be >= 1.0")

SHUFFLE_SLOT_RAGGED_ENABLED = conf(
    "spark.rapids.tpu.shuffle.slot.ragged.enabled", False,
    "Skew-adaptive RAGGED slot plans for stats-sized exchanges: when "
    "the per-destination histogram shows a few hot (src, dst) slices, "
    "the base all_to_all is sized from the COLD slices and the hot "
    "surplus rides per-pair collective-permutes that transmit only on "
    "their own link — padded wire bytes stop scaling with the hottest "
    "destination times every slice (parallel/shuffle.py RaggedPlan). "
    "False (default) keeps one uniform slot per exchange (current "
    "behavior). The overflow-retry rung stays the safety net: a slice "
    "exceeding its ragged limit re-runs at full capacity, rows are "
    "never dropped.", _to_bool)

SHUFFLE_SLOT_RAGGED_FACTOR = conf(
    "spark.rapids.tpu.shuffle.slot.ragged.minSavings", 1.5,
    "Minimum wire-rows reduction (uniform / ragged) a ragged plan must "
    "buy before it is used; below this the uniform slot wins (fewer "
    "collectives, stable jit keys).", _to_float,
    lambda v: None if v >= 1.0 else "must be >= 1.0")

EXCHANGE_ASYNC_ENABLED = conf(
    "spark.rapids.tpu.exchange.async.enabled", False,
    "Asynchronous exchange/compute overlap (parallel/exchange_async.py): "
    "exchange-bearing launches are dispatched, not blocked on — the "
    "post-launch verification (speculative slot-overflow flag) defers "
    "into an AsyncExchangeHandle resolved at the next stage boundary, "
    "so downstream fused compute dispatches while the collective is "
    "still in flight.  Bounded by the in-flight window below; a "
    "deferred overflow (or an injected fault at resolve time) degrades "
    "to the synchronous path through the recovery ladder — results are "
    "never wrong, only re-driven.  False (default) keeps every "
    "exchange synchronous (current behavior).", _to_bool)

EXCHANGE_INFLIGHT_WINDOW_BYTES = conf(
    "spark.rapids.tpu.exchange.async.inflightWindowBytes", 1 << 28,
    "Budget on unresolved exchange payload bytes in flight at once "
    "(the async window's backpressure): admitting a handle past the "
    "budget resolves the oldest pending handles first, so a deep plan "
    "cannot pin unbounded HBM in unverified exchange buffers.  "
    "In-flight bytes are also charged to the query's serving memory "
    "budget (serving/context.py).", _to_int, _positive)

EXCHANGE_HOST_STAGING_THRESHOLD = conf(
    "spark.rapids.tpu.exchange.hostStaging.thresholdBytes", 0,
    "When a single exchange's estimated payload exceeds this many "
    "bytes, stage it through host RAM instead of the device collective: "
    "rows round-trip through the spill tier's frame codec (compressed, "
    "pinned-host analog) and come back already co-located, so an "
    "oversized shuffle lands in host memory instead of failing over to "
    "the recovery ladder's split rung.  0 (default) disables staging "
    "(current behavior).", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

SHUFFLE_TOPOLOGY_STRATEGY = conf(
    "spark.rapids.tpu.shuffle.topology.strategy", "auto",
    "Collective strategy per mesh axis: 'all_to_all' always uses the "
    "ICI-style padded all-to-all; 'gather' uses gather-then-"
    "redistribute (ONE all-gather per width group, each shard compacts "
    "its own rows locally — fewer, larger transfers, the DCN-friendly "
    "shape); 'auto' (default) picks all_to_all on single-slice (ICI) "
    "axes and gather on axes that span hosts/slices "
    "(parallel/mesh.py axis_link_kind) — i.e. current behavior on a "
    "single-slice mesh.", str,
    lambda v: None if v in ("auto", "all_to_all", "gather") else
    "must be auto, all_to_all or gather")

_READER_TYPES = ("PERFILE", "COALESCING", "MULTITHREADED", "AUTO")


def _reader_type_ok(v):
    return None if v in _READER_TYPES else \
        "must be PERFILE, COALESCING, MULTITHREADED or AUTO"


MULTITHREADED_READ_NUM_THREADS = conf(
    "spark.rapids.sql.format.parquet.multiThreadedRead.numThreads", 8,
    "Thread-pool size for the multithreaded file reader "
    "(reference RapidsConf.scala:734).", _to_int, _positive)

MAX_NUM_FILES_PARALLEL = conf(
    "spark.rapids.sql.format.parquet.multiThreadedRead.maxNumFilesParallel", 4,
    "Max files buffered in flight per task by the multithreaded reader "
    "(reference RapidsConf.scala:740).", _to_int, _positive)

PARQUET_ENABLED = conf(
    "spark.rapids.sql.format.parquet.enabled", True,
    "Use the engine's columnar parquet scan; when false parquet scans "
    "tag off and the whole read runs on the pandas fallback chain "
    "(reference `sql.format.parquet.enabled`, RapidsConf.scala:664).",
    _to_bool)

PARQUET_READ_ENABLED = conf(
    "spark.rapids.sql.format.parquet.read.enabled", True,
    "Read side of the parquet format switch (reference "
    "`sql.format.parquet.read.enabled`).", _to_bool)

ORC_ENABLED = conf(
    "spark.rapids.sql.format.orc.enabled", True,
    "Use the engine's columnar ORC scan (reference "
    "`sql.format.orc.enabled`).", _to_bool)

ORC_READ_ENABLED = conf(
    "spark.rapids.sql.format.orc.read.enabled", True,
    "Read side of the ORC format switch.", _to_bool)

CSV_ENABLED = conf(
    "spark.rapids.sql.format.csv.enabled", True,
    "Use the engine's columnar CSV scan (reference "
    "`sql.format.csv.enabled`).", _to_bool)

CSV_READ_ENABLED = conf(
    "spark.rapids.sql.format.csv.read.enabled", True,
    "Read side of the CSV format switch.", _to_bool)

PARQUET_READER_TYPE = conf(
    "spark.rapids.sql.format.parquet.reader.type", "AUTO",
    "Parquet reader strategy: PERFILE, COALESCING, MULTITHREADED, AUTO "
    "(reference RapidsConf.scala:693-722).", str, _reader_type_ok)

ORC_READER_TYPE = conf(
    "spark.rapids.sql.format.orc.reader.type", "AUTO",
    "ORC reader strategy (reference RapidsConf.scala per-format reader "
    "knobs).", str, _reader_type_ok)

CSV_READER_TYPE = conf(
    "spark.rapids.sql.format.csv.reader.type", "AUTO",
    "CSV reader strategy.", str, _reader_type_ok)

ORC_READ_NUM_THREADS = conf(
    "spark.rapids.sql.format.orc.multiThreadedRead.numThreads", 8,
    "Thread-pool size for the multithreaded ORC reader.",
    _to_int, _positive)

CSV_READ_NUM_THREADS = conf(
    "spark.rapids.sql.format.csv.multiThreadedRead.numThreads", 8,
    "Thread-pool size for the multithreaded CSV reader.",
    _to_int, _positive)

ORC_MAX_NUM_FILES_PARALLEL = conf(
    "spark.rapids.sql.format.orc.multiThreadedRead.maxNumFilesParallel",
    4, "Max ORC files buffered in flight per task.", _to_int, _positive)

CSV_MAX_NUM_FILES_PARALLEL = conf(
    "spark.rapids.sql.format.csv.multiThreadedRead.maxNumFilesParallel",
    4, "Max CSV files buffered in flight per task.", _to_int, _positive)

READER_BATCH_SIZE_ROWS = conf(
    "spark.rapids.sql.reader.batchSizeRows", 1 << 20,
    "Soft cap on rows per batch produced by file scans (reference "
    "`spark.rapids.sql.reader.batchSizeRows`).", _to_int, _positive)

WRITER_MAX_ROWS_PER_FILE = conf(
    "spark.rapids.sql.writer.maxRowsPerFile", 1 << 22,
    "Max rows per output file for dataset writes.", _to_int, _positive)

JOIN_OUTPUT_BATCH_ROWS = conf(
    "spark.rapids.sql.join.outputBatchRows", 1 << 22,
    "Join output chunk size in rows — bounds peak HBM per emitted "
    "batch (the JoinGatherer output-splitting analog, "
    "GpuHashJoin output batching).", _to_int, _positive)

OOM_RETRY_MAX = conf(
    "spark.rapids.memory.oomRetry.maxRetries", 2,
    "Spill-and-retry attempts per device OOM before splitting or "
    "failing (memory/retry.py split-and-retry framework).",
    _to_int, lambda v: None if v >= 0 else "must be >= 0")

QUERY_RECOVERY_ENABLED = conf(
    "spark.rapids.sql.recovery.enabled", True,
    "Enable the query-level recovery/degradation driver: classified "
    "transient faults (device OOM, reader/transport hiccups, "
    "preemption) re-drive the query down a bounded ladder — retry, "
    "spill-and-retry, smaller batches, single-device replan, CPU "
    "fallback — instead of failing it (robustness/driver.py).",
    _to_bool)

QUERY_RECOVERY_MAX_RETRIES = conf(
    "spark.rapids.sql.recovery.maxRetries", 2,
    "Plain same-plan retries (with backoff) before the recovery "
    "ladder escalates to degradation.", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

QUERY_RECOVERY_BACKOFF_MS = conf(
    "spark.rapids.sql.recovery.backoffMs", 25,
    "Base backoff between same-plan query retries, doubled per retry, "
    "jittered (deterministically, seeded per driver), and capped at "
    "spark.rapids.sql.recovery.backoffCapMs.", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

QUERY_RECOVERY_BACKOFF_CAP_MS = conf(
    "spark.rapids.sql.recovery.backoffCapMs", 2000,
    "Ceiling on the exponential retry backoff (before jitter). Chaos "
    "tests lower it so ladders stay fast; long-haul batch jobs may "
    "raise it to ride out minutes-long maintenance events.", _to_int,
    _positive)

RECOVERY_CHECKPOINT_ENABLED = conf(
    "spark.rapids.sql.recovery.checkpoint.enabled", True,
    "Register the post-shuffle output of every completed distributed "
    "exchange stage (aggregate/join/sort/window) as a stage checkpoint "
    "in a per-query lineage log (robustness/checkpoint.py). On a "
    "retryable fault the recovery ladder's re-attempt resumes from the "
    "last good checkpoint — completed subtrees splice in from the "
    "spill catalog instead of re-reading sources and re-running "
    "collectives; recovery cost becomes proportional to the FAILED "
    "stage, not the whole query. Checkpoints are CRC-verified on "
    "restore; a corrupt or evicted one is dropped and its subtree "
    "re-runs.", _to_bool)

RECOVERY_CHECKPOINT_MAX_BYTES = conf(
    "spark.rapids.sql.recovery.checkpoint.maxBytes", 1 << 30,
    "Ceiling on the bytes one query's stage-checkpoint lineage log may "
    "pin across all spill tiers; oldest checkpoints evict first "
    "(CheckpointEvict events) and their subtrees simply re-run on "
    "resume. Payloads are additionally counted against the spill "
    "catalog's device budget while HBM-resident, so checkpoints "
    "demote under the same watermark pressure as live batches.",
    _to_int, _positive)

RECOVERY_CHECKPOINT_TIERS = conf(
    "spark.rapids.sql.recovery.checkpoint.tiers", "device,host,disk",
    "Spill tiers a stage-checkpoint payload may occupy. "
    "'device,host,disk' (default) registers at DEVICE and lets "
    "watermark pressure demote; 'host,disk' demotes to host "
    "immediately at write (checkpoints never compete for HBM); 'disk' "
    "pushes straight to the atomic disk frames.", str,
    lambda v: None if v in ("device,host,disk", "host,disk", "disk")
    else "must be 'device,host,disk', 'host,disk' or 'disk'")

WATCHDOG_ENABLED = conf(
    "spark.rapids.tpu.watchdog.enabled", True,
    "Enable the hang watchdog (robustness/watchdog.py): monitored "
    "sections around reader decode, shuffle program launch, host "
    "syncs, UDF worker calls and the pipeline worker heartbeat "
    "convert deadline overruns into classified retryable TimeoutFault"
    "s delivered at the next cooperative cancellation checkpoint, so "
    "the recovery ladder absorbs hangs the same way it absorbs "
    "exceptions (the UCX transport heartbeat/timeout analog).",
    _to_bool)

WATCHDOG_DEFAULT_DEADLINE_MS = conf(
    "spark.rapids.tpu.watchdog.defaultDeadlineMs", 300_000,
    "Deadline applied to every monitored section without a per-point "
    "override (spark.rapids.tpu.watchdog.deadline.<point>). 0 "
    "disables monitoring for sections without an override.", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

WATCHDOG_QUERY_DEADLINE_MS = conf(
    "spark.rapids.tpu.watchdog.queryDeadlineMs", 0,
    "Wall-time deadline for one query execution attempt; an overrun "
    "is a retryable TimeoutFault, so the recovery ladder re-drives "
    "(and ultimately degrades) rather than hanging forever. 0 "
    "disables the whole-query deadline.", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

WATCHDOG_POLL_MS = conf(
    "spark.rapids.tpu.watchdog.pollMs", 25,
    "Target poll interval of the watchdog monitor thread; the "
    "effective cadence also adapts to the shortest active deadline "
    "so short test deadlines detect promptly.", _to_int, _positive)

SPILL_INTEGRITY_ENABLED = conf(
    "spark.rapids.memory.spill.integrityCheck.enabled", True,
    "Verify a crc32 checksum (computed when a batch leaves the "
    "device) on every HOST and DISK tier spill restore; a mismatch "
    "drops the batch and raises a degradable CorruptionFault so the "
    "recovery ladder re-runs from source — wrong bytes are never "
    "returned. Disk spill files are always written atomically "
    "(temp file + fsync + rename) regardless of this flag.",
    _to_bool)

SKEW_JOIN_ENABLED = conf(
    "spark.rapids.sql.join.skew.enabled", True,
    "Enable skew-join mitigation in the distributed exchange "
    "(OptimizeSkewedJoin analog; parallel/distributed.py).", _to_bool)

SKEW_JOIN_FACTOR = conf(
    "spark.rapids.sql.join.skew.factor", 4.0,
    "A shuffle destination receiving more than factor x median rows "
    "is treated as skewed.", float,
    lambda v: None if v > 1.0 else "must be > 1.0")

SKEW_JOIN_MIN_ROWS = conf(
    "spark.rapids.sql.join.skew.minRows", 1 << 12,
    "Minimum destination row count before skew mitigation triggers.",
    _to_int, _positive)

BROADCAST_JOIN_THRESHOLD_ROWS = conf(
    "spark.rapids.sql.join.broadcastThresholdRows", 1 << 16,
    "Build sides at or below this many rows broadcast instead of "
    "shuffling (autoBroadcastJoinThreshold analog, in rows).",
    _to_int, _positive)

PYTHON_NUM_WORKERS = conf(
    "spark.rapids.sql.python.numWorkers", 0,
    "Worker processes for black-box Python UDF evaluation (0 = inline "
    "on the driver thread; the concurrentPythonWorkers analog). "
    "Spawn-started and reused across batches; unpicklable functions "
    "fall back to inline.", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

PIPELINE_ENABLED = conf(
    "spark.rapids.tpu.pipeline.enabled", True,
    "Drive query execution through the asynchronous pipeline "
    "(exec/pipeline.py): a worker thread pulls operator batches — "
    "overlapping reader decode, host->device upload and XLA dispatch — "
    "while the driving thread consumes results.  Pure overlap "
    "optimization: batch contents and order are identical to the "
    "sequential pull loop.", _to_bool)

PIPELINE_DEPTH = conf(
    "spark.rapids.tpu.pipeline.depth", 2,
    "Maximum batches in flight between the pipeline worker and the "
    "consuming thread.  In-flight batches stay registered in the spill "
    "catalog, so depth bounds pinned HBM, not just queue length; depth "
    "1 still overlaps one producer step with the consumer.",
    _to_int, _positive)

PIPELINE_DONATION = conf(
    "spark.rapids.tpu.pipeline.donation.enabled", True,
    "Donate input HBM to XLA on fused filter/project stages whose "
    "input batches are pipeline-ephemeral (produced by the upstream "
    "operator and dropped after the stage), letting outputs reuse the "
    "input buffers.  No-op on the CPU backend (XLA:CPU ignores "
    "donation); donated stages skip operator-level OOM retry and "
    "escalate straight to query-level recovery, which re-runs from "
    "source (docs/performance.md#donation).", _to_bool)

FUSION_ENABLED = conf(
    "spark.rapids.tpu.fusion.enabled", True,
    "Whole-stage fusion (exec/fusion.py): the planner collapses maximal "
    "Filter/Project chains — and the chain feeding a (pre-shuffle) "
    "aggregate — into ONE compiled XLA computation per pipeline stage, so "
    "intermediates stay in registers/VMEM and each batch costs one jit "
    "dispatch instead of one per operator (selection travels as a mask "
    "inside the trace, compacted once at the stage boundary). Fusion "
    "never crosses an exchange, a cached plan node, or an operator the "
    "fuser cannot ingest (black-box UDFs, CPU-fallback expressions) — "
    "those chains auto-fall-back to unfused execution. False restores "
    "one-dispatch-per-operator execution (the A/B baseline; results are "
    "bit-identical either way).", _to_bool)

FUSION_WIRE_ENABLED = conf(
    "spark.rapids.tpu.fusion.wire.enabled", False,
    "Fuse the wire across the exchange boundary (parallel/"
    "distributed.py): a warm distributed aggregate launches ONE program "
    "per shard that runs scan-mask -> filter -> partial-agg -> lane "
    "packing/validity bit-packing -> all_to_all -> merge/finalize, "
    "instead of the separate local-partials and exchange+merge "
    "dispatches.  Applies only on the speculative (warm-slot) path; "
    "stats-planned, ragged, staged, and keyless launches keep the "
    "two-dispatch shape and record a fused-wire fallback breadcrumb.  "
    "Slot overflow inside a fused launch degrades to the current "
    "two-phase path exactly like speculative overflow does today.  "
    "stage_ids are unchanged fused or not (checkpoint/resume splice "
    "unaffected).  False (default) is a full A/B: results are "
    "bit-identical either way.", _to_bool)

FUSION_MAX_OPS = conf(
    "spark.rapids.tpu.fusion.maxChainOps", 16,
    "Ceiling on the operators one fused stage may collapse. Bounds the "
    "size of the traced computation (compile time grows with the fused "
    "expression forest); chains longer than this split into multiple "
    "fused stages.", _to_int, _positive)

JIT_CACHE_DIR = conf(
    "spark.rapids.tpu.jitCache.dir", "",
    "Directory for the PERSISTENT jit-cache tier (ops/jit_cache.py): "
    "compiled stages are AOT-serialized via jax.export, keyed by "
    "sha256(structural signature, input shapes, backend, jax/jaxlib "
    "versions), and loaded before tracing on a miss — a second process "
    "running the same query compiles nothing. Entries are CRC-verified "
    "and environment-checked on load; truncation, bit rot, or a store "
    "written by a different jax/jaxlib falls back to a fresh compile "
    "(JitCacheInvalid event), never a failed or wrong query. Cold runs "
    "pay one extra Python trace per stage to produce the export — the "
    "price of the zero-trace warm start. Empty disables the tier (the "
    "in-memory cache still applies).", str)

JIT_CACHE_MAX_BYTES = conf(
    "spark.rapids.tpu.jitCache.maxBytes", 1 << 30,
    "Ceiling on the persistent jit-cache directory's total size; "
    "oldest entries evict first (their signatures simply recompile "
    "next cold run).", _to_int, _positive)

PIPELINE_DEFER_SYNCS = conf(
    "spark.rapids.tpu.pipeline.deferSyncs", True,
    "Carry per-batch row/group counts as device-resident scalars "
    "(columnar RowCount) and only materialize them at true host "
    "decision points, collapsing the per-batch int(n) device->host "
    "round trips in the aggregation path.  False restores the eager "
    "per-batch syncs (the sequential baseline tests/test_pipeline.py "
    "measures against).", _to_bool)

SERVING_CONCURRENT_QUERIES = conf(
    "spark.rapids.tpu.serving.concurrentQueries", 4,
    "Maximum queries admitted onto the device concurrently by the "
    "session-level admission controller (serving/admission.py — the "
    "query-granularity face of the reference's GpuSemaphore). Queries "
    "past the limit wait in a fair FIFO queue; 0 disables admission "
    "control entirely (every query runs immediately, the pre-serving "
    "behavior).", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

SERVING_HBM_ADMISSION_FRACTION = conf(
    "spark.rapids.tpu.serving.hbmAdmissionFraction", 0.8,
    "Fraction of the spill catalog's device budget that admitted "
    "queries' declared memory weights may claim together — the "
    "byte-weighted half of the admission semaphore. A query whose "
    "weight does not fit waits (FIFO) until admitted queries release; "
    "a single query heavier than the whole budget still admits alone "
    "rather than deadlocking.", _to_float, _fraction)

SERVING_ADMISSION_TIMEOUT_MS = conf(
    "spark.rapids.tpu.serving.admissionTimeoutMs", 0,
    "Longest one query may wait in the admission queue before it is "
    "rejected with a typed AdmissionFault (the queue->reject rung of "
    "the budget ladder). 0 waits indefinitely.", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

SERVING_MAX_QUEUED_QUERIES = conf(
    "spark.rapids.tpu.serving.maxQueuedQueries", 0,
    "Bound on the admission queue depth; a query arriving at a full "
    "queue is rejected immediately with AdmissionFault('queue-full') "
    "instead of piling onto a session that is already saturated. 0 "
    "leaves the queue unbounded.", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

SERVING_QUERY_MEMORY_BUDGET = conf(
    "spark.rapids.tpu.serving.queryMemoryBudgetBytes", 0,
    "Per-query ceiling on spill-catalog bytes the query's own batches "
    "may pin at the DEVICE tier. Exhaustion degrades THAT query: its "
    "own coldest handles spill to host first (BudgetExhausted event, "
    "action=spill); a query whose device-resident set still exceeds "
    "the budget after self-spilling is rejected with a typed "
    "BudgetExhaustedFault. 0 disables enforcement (the admission "
    "weight then derives from hbmAdmissionFraction / "
    "concurrentQueries).", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

SERVING_SYNC_BUDGET = conf(
    "spark.rapids.tpu.serving.syncBudget", 0,
    "Per-query ceiling on counted device->host synchronizations "
    "(utils/hostsync.py). A query that exceeds it is rejected with a "
    "typed BudgetExhaustedFault at the offending sync — a runaway "
    "sync loop in one query must not serialize the whole session's "
    "dispatch queue. 0 disables.", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

SERVING_DEADLINE_BUDGET_MS = conf(
    "spark.rapids.tpu.serving.deadlineBudgetMs", 0,
    "Wall-time deadline applied to EACH execution attempt of a query "
    "admitted through the serving layer (overrides "
    "spark.rapids.tpu.watchdog.queryDeadlineMs when set). An overrun "
    "is a retryable TimeoutFault for that query only; a query that "
    "overruns on every rung can therefore hold its admission slot "
    "for up to ladder-length x this budget before exhausting. 0 "
    "defers to the watchdog conf.", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

SERVING_CHECKPOINT_FLOOR_BYTES = conf(
    "spark.rapids.tpu.serving.checkpointEvictionFloorBytes", 0,
    "Cross-query isolation floor for stage checkpoints: device-tier "
    "pressure originating from one query demotes that query's own "
    "handles first, and may not demote ANOTHER query's "
    "checkpoint-priority payloads below this many device-resident "
    "bytes (unless the budget cannot be met any other way). 0 "
    "disables the floor (pure priority order).", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

SERVING_INTERLEAVE_ENABLED = conf(
    "spark.rapids.tpu.serving.interleave.enabled", False,
    "Fair batch-for-batch interleaving of admitted queries "
    "(serving/scheduler.py): instead of each admitted query's batch "
    "loop occupying the device FIFO until it finishes, queries take "
    "weighted round-robin timeslices at every batch (and distributed "
    "stage) boundary — a 10ms dashboard query no longer queues behind "
    "a long scan, and every runnable query advances within one round "
    "(starvation-proof by construction). Weights derive from the "
    "serving budgets: lighter byte weights and deadline-budgeted "
    "queries get more batch slices per round. Cooperative only — it "
    "reorders when batches dispatch, never what they compute, so "
    "results are bit-identical with it off.", _to_bool)

SERVING_INTERLEAVE_QUANTUM = conf(
    "spark.rapids.tpu.serving.interleave.quantumBatches", 1,
    "Base number of batch slices one query may advance per "
    "round-robin turn of the fair interleaver. The effective quantum "
    "scales up for queries declaring a byte weight lighter than the "
    "pool default (bounded 8x) and doubles for deadline-budgeted "
    "queries; every registered query always advances at least one "
    "batch per round.", _to_int, _positive)

SERVING_RESULT_CACHE_ENABLED = conf(
    "spark.rapids.tpu.serving.resultCache.enabled", False,
    "Plan-keyed query RESULT cache (serving/reuse.py): before "
    "planning, a query's exact logical-plan text plus the input "
    "fingerprint of everything it reads (file path/size/mtime_ns "
    "triples, in-memory batch identities) is looked up in a "
    "session-scoped host/disk-tier store; a hit answers with ZERO "
    "executions. Any fingerprint drift invalidates the entry (a "
    "mutated input can never serve stale bytes), results are "
    "CRC-verified on every hit (a failed check degrades to "
    "recompute), and plans containing UDFs or pandas stages are "
    "never cached. Most production dashboard traffic is "
    "near-duplicate — this is the 'Accelerating Presto with GPUs' "
    "result-reuse leg.", _to_bool)

SERVING_RESULT_CACHE_MAX_BYTES = conf(
    "spark.rapids.tpu.serving.resultCache.maxBytes", 256 << 20,
    "Ceiling on the bytes the result cache may pin across the "
    "host/disk spill tiers (stored size — the storage codec "
    "stretches it). Least-recently-used entries evict first; a "
    "result larger than the whole budget is simply not stored.",
    _to_int, _positive)

SERVING_SHARED_STAGE_ENABLED = conf(
    "spark.rapids.tpu.serving.sharedStage.enabled", False,
    "CROSS-QUERY stage cache (serving/reuse.py): mesh queries "
    "register every completed exchange stage in a shared, "
    "session-scoped store keyed by the structural stage id WITH the "
    "input fingerprint folded in (the always_resume lineage "
    "machinery, robustness/incremental.py precedent), so two "
    "different queries sharing a subtree — same scan + filter + "
    "partial aggregate — splice each other's checkpoints through "
    "try_distributed(resume=True) on FIRST attempts. Entries carry "
    "owner attribution for per-query budget billing; CRC failure, "
    "eviction and fingerprint drift all degrade to recompute — "
    "never wrong bytes. Payloads demote to host at write so the "
    "shared store never competes with live batches for HBM.",
    _to_bool)

SERVING_SHARED_STAGE_MAX_BYTES = conf(
    "spark.rapids.tpu.serving.sharedStage.maxBytes", 1 << 30,
    "Ceiling on the bytes the shared cross-query stage cache may pin "
    "across the host/disk spill tiers (stored size). Oldest entries "
    "evict first (SharedStageEvict events); an evicted entry just "
    "re-runs its subtree on the next query that wanted it.",
    _to_int, _positive)

TEMPLATE_ENABLED = conf(
    "spark.rapids.tpu.template.enabled", False,
    "Parameterized plan templates (plan/template.py): before "
    "planning, constant literals are hoisted out of the logical plan "
    "into typed parameter slots with VALUE-FREE cache keys, so the "
    "stage-compiler signatures, fused-aggregate programs and "
    "persistent AOT entries all key on the normalized template and "
    "the literal values travel as device-scalar arguments at "
    "dispatch — a dashboard plan re-issued with shifting literals "
    "retraces and recompiles ZERO times after warmup. Hoisting "
    "refuses literals that change plan shape (nulls, strings, "
    "decimals, ANSI-check constants, LIMIT/slot constants, unaliased "
    "projection names) — refused shapes fall back to exact keying "
    "and produce byte-identical results. Default off; with it off "
    "every plan takes the exact-key path bit-identically.", _to_bool)

TEMPLATE_RESULT_CACHE_ENABLED = conf(
    "spark.rapids.tpu.template.resultCache.enabled", False,
    "TEMPLATE tier of the serving result cache (serving/reuse.py): "
    "answered queries also store under (normalized template "
    "fingerprint, parameter vector), so the SAME dashboard re-issued "
    "with the SAME literals hits even when the exact plan text was "
    "never seen in this form (prepared statements, re-hoisted "
    "ad-hoc plans). Same verification discipline as the exact tier "
    "— input fingerprints statted fresh at lookup, CRC re-verified "
    "on every hit, failures degrade to recompute. Requires BOTH "
    "template.enabled and serving.resultCache.enabled; shares the "
    "exact tier's byte budget.", _to_bool)

INCREMENTAL_ENABLED = conf(
    "spark.rapids.tpu.incremental.enabled", True,
    "Enable incremental state for continuous micro-batch ingest "
    "(robustness/incremental.py, session.incremental(df).tick(paths)): "
    "a tick executes against the last COMMITTED state epoch — "
    "aggregation plans re-aggregate only the appended files and merge "
    "with the standing partial-aggregate state, other plans splice "
    "unchanged (input-fingerprinted) stage checkpoints from the "
    "session-persistent lineage store — and commits the new epoch "
    "atomically only when the tick completes. Any fault mid-tick rolls "
    "back to the committed epoch and the tick degrades to a full "
    "recompute; state is never half-updated. False makes every tick a "
    "plain full re-execution with no standing state.", _to_bool)

INCREMENTAL_MAX_STATE_BYTES = conf(
    "spark.rapids.tpu.incremental.maxStateBytes", 1 << 30,
    "Ceiling on the bytes one standing query's incremental state "
    "(partial-aggregate epochs plus persistent stage checkpoints) may "
    "pin across all spill tiers. Oldest stage entries evict first, "
    "then the aggregate state itself (StateEvict events); an evicted "
    "entry degrades the next tick to full recompute — never a wrong "
    "or failed tick. Per-owner spill accounting (serving layer) keeps "
    "one standing query's state from starving co-tenants regardless.",
    _to_int, _positive)

INCREMENTAL_TIERS = conf(
    "spark.rapids.tpu.incremental.tiers", "device,host,disk",
    "Spill tiers incremental state may occupy (same semantics as "
    "spark.rapids.sql.recovery.checkpoint.tiers): 'device,host,disk' "
    "registers at DEVICE and lets watermark pressure demote; "
    "'host,disk' demotes to host immediately at commit so standing "
    "state never competes with live batches for HBM; 'disk' pushes "
    "straight to the atomic disk frames.", str,
    lambda v: None if v in ("device,host,disk", "host,disk", "disk")
    else "must be 'device,host,disk', 'host,disk' or 'disk'")

INCREMENTAL_WATERMARK_DELAY_MS = conf(
    "spark.rapids.tpu.incremental.watermarkDelayMs", -1,
    "Event-time watermark delay for windowed continuous-ingest "
    "queries (group keys built from functions.window): each committed "
    "epoch advances the watermark to max(window end seen) minus this "
    "delay, the tick's answer excludes windows whose end is at or "
    "before the watermark, and their partial-state buckets evict "
    "atomically with the commit — state stays bounded under infinite "
    "ingest and late rows for expired windows are dropped (they can "
    "never change the answer). A rolled-back tick advances nothing: "
    "watermark and state restore to the committed epoch together. "
    "-1 (default) disables eviction — windowed aggregations then keep "
    "every bucket, like any other group key.", _to_int,
    lambda v: None if v >= -1 else "must be >= -1 (-1 = off)")

INCREMENTAL_TOPN_MAX_STATE_ROWS = conf(
    "spark.rapids.tpu.incremental.topn.maxStateRows", 65536,
    "State cap for mergeable top-N continuous-ingest queries "
    "(orderBy(group keys).limit(n) over a decomposable aggregate): "
    "when the sort key set covers the group keys with bare column "
    "references — the condition under which merging per-epoch top-K "
    "partials provably reproduces the one-shot answer bit-for-bit — "
    "the standing state and every delta partial are trimmed to the "
    "limit's n rows, so state is bounded by n instead of by the "
    "number of groups ever seen. Limits larger than this cap keep "
    "the untrimmed full-group state (still correct, just bigger); "
    "sort keys touching aggregated values always refuse the trim.",
    _to_int, _positive)

FLEET_SHARED_INGEST_ENABLED = conf(
    "spark.rapids.tpu.fleet.sharedIngest.enabled", True,
    "Shared-ingest fan-out for standing-query fleets "
    "(serving/fleet.py, session.fleet()): each fleet tick-round stats "
    "and READS the appended fact files exactly once and fans the "
    "ingested batches out to every delta-capable subscriber — N "
    "dashboards over one stream cost one source pull per new file "
    "instead of N. Per-subscriber epochs still commit and roll back "
    "independently (a faulted subscriber re-reads its own history on "
    "the degraded path; co-subscribers are untouched). False makes "
    "every subscriber pull its own delta, the lone-runner behavior.",
    _to_bool)

FLEET_EPOCH_SHARED_STAGE_ENABLED = conf(
    "spark.rapids.tpu.fleet.sharedStage.epoch.enabled", True,
    "Epoch-aware tier of the cross-query shared stage cache "
    "(serving/reuse.py): at every standing-query COMMIT the epoch "
    "store publishes a snapshot of its committed, file-fingerprinted "
    "stage entries (stage id + input fingerprint + committed epoch) "
    "into the session SharedStageCache, so two standing queries "
    "sharing a delta-join subtree splice each other's committed tick "
    "work. Entries register only at commit — never from provisional "
    "state — so a rolled-back tick can never leak a pre-commit entry "
    "to a co-tenant; an entry evicted from its owner after publication "
    "simply misses and the subtree re-runs. Requires "
    "spark.rapids.tpu.serving.sharedStage.enabled and a mesh.",
    _to_bool)

FLEET_SINK_MAX_RECORDS = conf(
    "spark.rapids.tpu.fleet.sink.maxRecords", 16,
    "Committed sink records one standing query retains for idempotent "
    "re-emission (robustness/incremental.py SinkCommit): each record "
    "is one committed epoch's emission (payload CRC + epoch + query "
    "id, plus the result batches) riding the atomic epoch commit — a "
    "replayed tick whose payload matches the latest committed record "
    "re-emits THAT epoch instead of minting a duplicate. Oldest "
    "records age out past this cap (they can no longer be replayed "
    "against, which only matters for consumers lagging more than this "
    "many data-bearing ticks).", _to_int, _positive)

FLEET_COORDINATOR = conf(
    "spark.rapids.tpu.fleet.coordinator", "",
    "Coordinator address (host:port) for multi-controller fleet "
    "bring-up. When set together with fleet.processId and "
    "fleet.numProcesses, session construction calls "
    "jax.distributed.initialize so every host's process contributes "
    "its local devices to one global mesh spanning DCN. Empty "
    "(default) keeps the single-controller mode — one process, one "
    "host, the behavior of every prior release.", str)

FLEET_PROCESS_ID = conf(
    "spark.rapids.tpu.fleet.processId", -1,
    "This host's process index in the multi-controller fleet "
    "(0..numProcesses-1; process 0 also serves as the coordinator). "
    "-1 (default) with an empty fleet.coordinator means "
    "single-controller mode.", _to_int,
    lambda v: None if v >= -1 else "must be >= -1")

FLEET_NUM_PROCESSES = conf(
    "spark.rapids.tpu.fleet.numProcesses", 0,
    "Total process count in the multi-controller fleet. 0 (default) "
    "means single-controller mode; values >= 2 require "
    "fleet.coordinator and fleet.processId.", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

FLEET_HEARTBEAT_MS = conf(
    "spark.rapids.tpu.fleet.heartbeatMs", 500,
    "Heartbeat period for the per-host membership registry "
    "(parallel/mesh.py HostMembership): each host writes a beat "
    "record at most this often, and peers are judged against it. A "
    "peer silent for heartbeatMs * missedBeatsFatal is declared lost "
    "— a HostLoss event is emitted and the next membership check "
    "raises a RETRYABLE HostLossFault, entering the recovery "
    "ladder's shrink rung.", _to_int, _positive)

FLEET_MISSED_BEATS_FATAL = conf(
    "spark.rapids.tpu.fleet.missedBeatsFatal", 3,
    "How many consecutive missed heartbeats declare a peer host lost "
    "(see fleet.heartbeatMs). Higher values tolerate longer GC/compile "
    "pauses at the cost of slower failure detection.", _to_int,
    _positive)

FLEET_MEMBERSHIP_DIR = conf(
    "spark.rapids.tpu.fleet.membershipDir", "",
    "Directory backing the HostMembership registry (one beat file per "
    "host, written atomically). On CPU test meshes and "
    "logical-host fleets this is a local tmp dir; on a real fleet it "
    "is shared storage every host can reach. Empty (default) places "
    "it under the system temp dir keyed by coordinator address, or "
    "disables membership entirely when the session has no fleet.",
    str)

FLEET_CACHE_DIR = conf(
    "spark.rapids.tpu.fleet.cache.dir", "",
    "Shared-storage directory for FLEET-scoped stage/result/template "
    "cache entries (serving/fleetcache.py): session caches publish "
    "CRC-stamped, fingerprint-verified payloads here so a repeated "
    "plan on ANY host answers from a peer's work. Writers are "
    "epoch-fenced — a publish carrying a fence token older than the "
    "registry's current epoch (a partitioned or restarted 'zombie' "
    "host) is rejected and health-checked, never read. Empty "
    "(default) keeps every cache session-scoped.", str)

FLEET_DCN_DEADLINE_SCALE = conf(
    "spark.rapids.tpu.fleet.dcnDeadlineScale", 4.0,
    "Watchdog deadline multiplier for exchange launches whose "
    "collective crosses DCN (the data axis spans processes or "
    "logical hosts): cross-host hops are orders of magnitude slower "
    "than ICI, so the shuffle.exchange deadline scales by this factor "
    "before a TimeoutFault is parked. 1.0 disables the scaling.",
    _to_float, _positive)

FLEET_LOGICAL_HOSTS = conf(
    "spark.rapids.tpu.fleet.logicalHosts", 0,
    "Partition a SINGLE-process mesh's devices into this many "
    "simulated hosts for testing the fleet machinery without real "
    "multi-controller bring-up: axis link classification reads 'dcn' "
    "across simulated host boundaries (DCN collective selection, "
    "deadline scaling, and byte accounting all engage), membership "
    "tracks one logical host per partition, and the shrink rung can "
    "rebuild the mesh over survivors. 0 (default) disables; ignored "
    "in real multi-controller mode (process boundaries define "
    "hosts).", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

GRAY_FAILURE_ENABLED = conf(
    "spark.rapids.tpu.fleet.grayFailure.enabled", False,
    "Master switch for the gray-failure subsystem "
    "(robustness/grayfailure.py): per-host health scoring from "
    "heartbeat jitter and exchange/host-staging wall observations, "
    "hedged re-dispatch of a SUSPECT host's host-side shard work, "
    "proactive quarantine of a persistently-degraded host through the "
    "soft-shrink path (and its rejoin once recovered), and "
    "self-calibrated watchdog deadlines derived from observed p99 "
    "walls. A fail-slow host — thermal throttle, degraded DCN link — "
    "never trips the heartbeat-loss judgment, so without this the "
    "whole fleet stalls at its pace. False (default) keeps every "
    "decision path bit-identical to the pre-gray-failure engine.",
    _to_bool)

FLEET_SUSPECT_FACTOR = conf(
    "spark.rapids.tpu.fleet.suspectFactor", 3.0,
    "A host whose median observed wall (per evidence point: heartbeat "
    "interval, dist.host_sync, exchange.host_staging) is persistently "
    "this many times the fleet median over the rolling suspect window "
    "becomes SUSPECT — a typed HostSuspect event, never a hard fault "
    "on its own. SUSPECT gates hedged execution and starts the "
    "quarantine clock.", _to_float,
    lambda v: None if v > 1.0 else "must be > 1.0")

FLEET_SUSPECT_WINDOW = conf(
    "spark.rapids.tpu.fleet.suspectWindow", 32,
    "Rolling window (observations per host per evidence point) the "
    "gray-failure health score is computed over. Smaller windows "
    "detect faster but flap on one slow GC pause; larger windows "
    "smooth transients at the cost of detection latency.", _to_int,
    _positive)

FLEET_SUSPECT_MIN_SAMPLES = conf(
    "spark.rapids.tpu.fleet.suspectMinSamples", 3,
    "Minimum observations a host must have at an evidence point "
    "before that point contributes to its health score — bring-up "
    "and cold caches must not read as sickness.", _to_int, _positive)

FLEET_QUARANTINE_AFTER_MS = conf(
    "spark.rapids.tpu.fleet.quarantineAfterMs", 60_000,
    "A host continuously SUSPECT for this long is proactively "
    "quarantined: drained out of the mesh through the soft-shrink "
    "path (fence-epoch bump, survivors-only mesh) at the next safe "
    "query boundary, before anything wedges. Unlike a heartbeat "
    "loss, the host keeps beating and its recovery is tracked for "
    "rejoin. 0 disables proactive quarantine (detection and hedging "
    "still run).", _to_int,
    lambda v: None if v >= 0 else "must be >= 0")

FLEET_REJOIN_AFTER_MS = conf(
    "spark.rapids.tpu.fleet.rejoinAfterMs", 30_000,
    "A quarantined host whose health score stays below the suspect "
    "threshold for this long rejoins the mesh at the next safe query "
    "boundary: devices restored, fleet caches re-fenced (the fence "
    "epoch advances again), no in-flight query touched.", _to_int,
    _positive)

FLEET_HEDGE_PERCENTILE = conf(
    "spark.rapids.tpu.fleet.hedgePercentile", 0.95,
    "Adaptive hedge deadline: a SUSPECT host's host-side shard work "
    "(host staging, member replay) that runs past this percentile of "
    "the fleet's recent healthy walls (times fleet.hedgeMarginFactor) "
    "is re-dispatched on a healthy survivor; first result wins, the "
    "loser is discarded with hedgesFired/hedgesWon/"
    "duplicatesSuppressed pinned.", _to_float,
    lambda v: None if 0.5 <= v <= 1.0 else "must be in [0.5, 1.0]")

FLEET_HEDGE_MARGIN = conf(
    "spark.rapids.tpu.fleet.hedgeMarginFactor", 2.0,
    "Multiplier applied to the hedge percentile wall before a hedge "
    "fires — hedging costs duplicate work, so the deadline leaves "
    "honest headroom above the observed healthy tail.", _to_float,
    lambda v: None if v >= 1.0 else "must be >= 1.0")

FLEET_HEDGE_FLOOR_MS = conf(
    "spark.rapids.tpu.fleet.hedgeFloorMs", 25,
    "Floor on the adaptive hedge deadline: never hedge work that has "
    "run for less than this, whatever the observed walls say — "
    "sub-floor work is cheaper to wait out than to duplicate.",
    _to_int, _positive)

WATCHDOG_CALIBRATION_FLOOR_MS = conf(
    "spark.rapids.tpu.watchdog.calibration.floorMs", 1000,
    "Floor for self-calibrated watchdog deadlines (gray-failure mode "
    "only): a calibrated per-point deadline never drops below this, "
    "whatever the observed p99 says — operator-controlled headroom "
    "against a burst of fast observations tightening a deadline onto "
    "normal jitter.", _to_int, _positive)

WATCHDOG_CALIBRATION_CEILING_MS = conf(
    "spark.rapids.tpu.watchdog.calibration.ceilingMs", 600_000,
    "Ceiling for self-calibrated watchdog deadlines (gray-failure "
    "mode only): the calibrated value never exceeds this, so a run "
    "of pathologically slow observations cannot disable hang "
    "detection by inflating the deadline without bound.", _to_int,
    _positive)

WATCHDOG_CALIBRATION_MARGIN = conf(
    "spark.rapids.tpu.watchdog.calibration.marginFactor", 4.0,
    "Multiplier applied to the observed per-point p99 wall to form "
    "the self-calibrated deadline — the deadline is a hang detector, "
    "not a latency SLO, so it sits well above the healthy tail.",
    _to_float, lambda v: None if v >= 1.0 else "must be >= 1.0")

WATCHDOG_CALIBRATION_MIN_SAMPLES = conf(
    "spark.rapids.tpu.watchdog.calibration.minSamples", 8,
    "Observations a point needs before its watchdog deadline "
    "self-calibrates; below this the static conf deadline "
    "(deadline.<point> / defaultDeadlineMs, DCN-scaled) applies "
    "unchanged.", _to_int, _positive)

ENCODING_EXECUTION_ENABLED = conf(
    "spark.rapids.tpu.encoding.execution.enabled", False,
    "Encoded execution: string GROUP BY keys that are bare column "
    "references dictionary-encode ONCE per batch (stable codes across "
    "batches) and the whole filter+project+partial-aggregate stage "
    "evaluates on i32 codes inside the fused kernels "
    "(exec/aggregate.py), with the strings materialized only at the "
    "stage boundary that needs them (the final key decode). This is "
    "what lets string-heavy group-bys (TPC-H q1 shape) ride the "
    "whole-stage fusion path. Any shape the encoder cannot prove "
    "equality-faithful (computed string keys, a key column consumed "
    "by another expression, string-valued min/max buffers) falls back "
    "to the decoded host-dictionary path — never wrong bytes. False "
    "(default) keeps the decoded path everywhere (bit-identical A/B).",
    _to_bool)

ENCODING_EXECUTION_MAX_DICT = conf(
    "spark.rapids.tpu.encoding.execution.maxDictSize", (1 << 31) - 1,
    "Ceiling on distinct values one encoded-execution dictionary may "
    "hold. Exceeding it mid-query raises a RETRYABLE "
    "EncodingOverflowFault after latching encoded execution OFF for "
    "the session, so the recovery ladder's re-planned attempt runs "
    "the decoded path — exact results, never wrong bytes. The hard "
    "bound is i32 code space; lower values bound host dictionary "
    "memory.", _to_int, _positive)

ENCODING_WIRE_ENABLED = conf(
    "spark.rapids.tpu.encoding.wire.enabled", False,
    "Compressed device wire for dictionary-coded columns: exchange "
    "payload columns that carry int64 dictionary codes (string group "
    "keys, encoded min/max partials, string join keys) narrow to ONE "
    "i32 lane on the packed wire (half the bytes per code column) and "
    "widen back after the collective, and each exchange site "
    "broadcasts only its dictionary DELTA (frame-codec compressed, "
    "crc-verified) instead of materialized rows. A corrupt delta "
    "broadcast degrades that launch to the wide (unnarrowed) wire "
    "with a typed EncodedWireInvalid event — exact results either "
    "way. Savings are attributed as encodedBytesSaved in the QueryEnd "
    "shuffle dict. False (default) ships codes at their storage width "
    "(bit-identical A/B).", _to_bool)

ENCODING_STORAGE_HOST_CODEC = conf(
    "spark.rapids.tpu.encoding.storage.hostCodec", "none",
    "Frame codec for HOST-tier spill payloads (and therefore "
    "checkpoint and incremental-state frames, which demote through "
    "the same catalog): none keeps raw numpy buffers (current "
    "behavior); zrle / lz4 / zstd compress the payload through the "
    "shared native frame codec the DISK tier already uses — the "
    "integrity crc32 is still stamped and verified over the DECODED "
    "canonical bytes, so PR3 corruption semantics are unchanged and a "
    "frame that no longer decodes is dropped as corruption. "
    "Compressed host frames also mean checkpoint.maxBytes and "
    "incremental.maxStateBytes meter STORED bytes, buying several "
    "times more standing state per byte.", str,
    lambda v: None if v in ("none", "zrle", "lz4", "zstd")
    else "unknown codec")

COSTMODEL_ENABLED = conf(
    "spark.rapids.tpu.costModel.enabled", False,
    "Self-tuning cost-based planner (plan/costmodel.py): ONE "
    "evidence-fed cost model decides every tuning knob the engine "
    "otherwise takes from hand-set confs — exchange strategy (uniform "
    "vs ragged vs gather vs host-staged), the host-staging threshold, "
    "fusion chain boundaries, coded-vs-decoded execution, shuffle slot "
    "priors, and the coalesce goal — reading per-site evidence from "
    "the PR11 ObservationStore (rows/bytes/skew/compile_ms per "
    "structural site id, persisted beside the AOT cache dir so WARM "
    "STARTS GET WARM PLANS) and falling back to built-in tables when "
    "a site has no history.  Explicitly-set conf keys stay as "
    "OVERRIDES — the model only decides knobs the user left unset.  "
    "Every decision is recorded in a per-query ledger (QueryEnd "
    "'planner' dict -> eventlog -> profiling \"Planner decisions\") "
    "and observed costs fold back into the store so the model "
    "converges.  False (default) changes nothing: plans, events and "
    "results are bit-identical to the model never existing.", _to_bool)

COSTMODEL_DIR = conf(
    "spark.rapids.tpu.costModel.dir", "",
    "Directory holding the cost model's persisted per-site evidence "
    "(the observations.jsonl the span-tracing ObservationStore "
    "writes).  Empty (default) falls back to "
    "spark.rapids.tpu.jitCache.dir, then spark.rapids.tpu.trace.dir; "
    "with no directory at all the model runs on in-memory evidence "
    "only (decisions still work, they just start cold every "
    "process).  A corrupt or truncated store degrades the model to "
    "its built-in defaults with a CostModelInvalid event — never a "
    "failed or wrong query (the costmodel.load injection point).", str)

COSTMODEL_REPLAN_ENABLED = conf(
    "spark.rapids.tpu.costModel.replan.enabled", True,
    "Mid-query adaptive re-planning (requires costModel.enabled and "
    "the recovery ladder): when an exchange launch's measured "
    "statistics contradict the model's plan-time decision past the "
    "hysteresis band (measured skew says ragged, the plan chose "
    "uniform), the launch raises a RETRYABLE ReplanRequested after "
    "folding the fresh evidence into the store — the ladder's retry "
    "rung keeps the mesh layout, completed stages splice from the "
    "checkpoint lineage, and only the contradicted subtree re-plans "
    "with the measured-optimal strategy.  At most ONE replan per "
    "query; False records the contradiction in the decision ledger "
    "without re-driving.", _to_bool)

COSTMODEL_REPLAN_HYSTERESIS = conf(
    "spark.rapids.tpu.costModel.replan.hysteresis", 2.0,
    "How decisively the measured statistics must beat the plan-time "
    "decision before a mid-query replan fires: the contradicting "
    "alternative's predicted win (e.g. uniform wire rows / ragged "
    "wire rows) must be at least this factor.  Higher values replan "
    "less (the band a borderline workload oscillates in without "
    "re-driving).", _to_float,
    lambda v: None if v >= 1.0 else "must be >= 1.0")

CBO_ENABLED = conf(
    "spark.rapids.sql.optimizer.enabled", False,
    "Enable the cost-based optimizer: device regions whose estimated "
    "speedup cannot pay for the host<->device transition costs are "
    "reverted to CPU (reference CostBasedOptimizer.scala:35, default "
    "off).", _to_bool)


TEST_ENABLED = conf(
    "spark.rapids.sql.test.enabled", False,
    "Strict test mode: fail if an op silently falls back to CPU "
    "(reference RapidsConf.scala:928).", _to_bool, internal=True)

TEST_ALLOWED_NON_TPU = conf(
    "spark.rapids.sql.test.allowedNonTpu", "",
    "Comma-separated op names tolerated on CPU in strict test mode "
    "(reference `test.allowedNonGpu`).", str, internal=True)


# dynamic per-op enable keys (confKey wiring, GpuOverrides.scala:204-296):
# spark.rapids.sql.expression.<Name> / spark.rapids.sql.exec.<Name>
_DYNAMIC_PREFIXES = ("spark.rapids.sql.expression.",
                     "spark.rapids.sql.exec.")
# per-op cost-model overrides (any logical-plan op name): the CBO loads
# calibrated defaults from plan/cbo_weights.json and these keys override
_COST_PREFIXES = ("spark.rapids.sql.optimizer.tpuOpCost.",
                  "spark.rapids.sql.optimizer.cpuOpCost.")
# per-point watchdog deadline overrides (any monitored section name,
# e.g. io.reader / shuffle.exchange / pipeline.worker); values in ms,
# 0 disables that point
_WATCHDOG_DEADLINE_PREFIX = "spark.rapids.tpu.watchdog.deadline."


def _known_key(key: str) -> bool:
    if key in _REGISTRY:
        return True
    if key.startswith(_WATCHDOG_DEADLINE_PREFIX):
        return True
    for p in _COST_PREFIXES:
        if key.startswith(p):
            return True
    for p in _DYNAMIC_PREFIXES:
        if key.startswith(p):
            suffix = key[len(p):]
            try:  # lazy: the planner imports this module
                from spark_rapids_tpu.plan.overrides import valid_op_names
                return suffix in valid_op_names()
            except ImportError:
                return True
    return False


class RapidsConf:
    """Immutable snapshot view over a settings dict (RapidsConf.scala:1281).

    Unknown ``spark.rapids.*`` keys are rejected at construction — a typo
    in a tuning knob must fail loudly, not silently no-op.  Non-rapids
    keys (e.g. ``spark.sql.*`` passthroughs) are kept untouched."""

    def __init__(self, settings: Optional[Dict[str, str]] = None):
        self.settings = dict(settings or {})
        for k in self.settings:
            if k.startswith("spark.rapids.") and not _known_key(k):
                raise ValueError(
                    f"unknown configuration key {k!r}; see "
                    "RapidsConf.registry() for available keys")

    def op_cost(self, side: str, name: str):
        """Per-op cost override (us/row):
        spark.rapids.sql.optimizer.<side>OpCost.<Op>; None = use the
        calibrated default from plan/cbo_weights.json."""
        raw = self.settings.get(
            f"spark.rapids.sql.optimizer.{side}OpCost.{name}")
        return None if raw is None else float(raw)

    def watchdog_deadline_ms(self, point: str) -> int:
        """Per-point watchdog deadline:
        spark.rapids.tpu.watchdog.deadline.<point>, falling back to
        the defaultDeadlineMs entry.  0 disables the point."""
        raw = self.settings.get(_WATCHDOG_DEADLINE_PREFIX + point)
        if raw is None:
            return self.get(WATCHDOG_DEFAULT_DEADLINE_MS)
        return int(raw)

    def op_enabled(self, kind: str, name: str) -> bool:
        """Per-op enable key: spark.rapids.sql.<kind>.<Name>, default
        True (the reference derives one such key per replacement rule)."""
        raw = self.settings.get(f"spark.rapids.sql.{kind}.{name}")
        if raw is None:
            return True
        return raw if isinstance(raw, bool) else _to_bool(str(raw))

    def get(self, entry: ConfEntry) -> Any:
        return entry.get(self.settings)

    def is_set(self, entry: ConfEntry) -> bool:
        """True when the user EXPLICITLY configured this entry (the
        settings dict or its env-var form).  The cost model treats
        explicit confs as overrides and only decides unset knobs."""
        if entry.key in self.settings:
            return True
        return os.environ.get(entry.env_key()) is not None

    def __getitem__(self, key: str) -> Any:
        return _REGISTRY[key].get(self.settings)

    def set(self, key: str, value) -> "RapidsConf":
        s = dict(self.settings)
        s[key] = value
        return RapidsConf(s)

    # convenience accessors used on hot paths
    @property
    def sql_enabled(self) -> bool:
        return self.get(SQL_ENABLED)

    @property
    def explain(self) -> str:
        return self.get(EXPLAIN)

    @property
    def batch_size_bytes(self) -> int:
        return self.get(BATCH_SIZE_BYTES)

    @property
    def max_batch_rows(self) -> int:
        return self.get(BATCH_ROW_CAPACITY)

    @property
    def shuffle_partitions(self) -> int:
        return self.get(SHUFFLE_PARTITIONS)

    @staticmethod
    def registry() -> Dict[str, ConfEntry]:
        return dict(_REGISTRY)

    @staticmethod
    def generate_docs() -> str:
        """Render docs/configs.md (reference RapidsConf.main)."""
        lines = ["# spark-rapids-tpu Configuration", "",
                 "Name | Description | Default", "---|---|---"]
        for key in sorted(_REGISTRY):
            e = _REGISTRY[key]
            if e.internal:
                continue
            lines.append(f"{e.key} | {e.doc} | {e.default}")
        return "\n".join(lines) + "\n"
