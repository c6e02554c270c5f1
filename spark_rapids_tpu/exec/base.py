"""TpuExec: base class for columnar physical operators + metrics.

Counterpart of ``GpuExec.scala`` (metric registry with ESSENTIAL/MODERATE/
DEBUG levels, standard names like opTime/numOutputRows/numOutputBatches).
Operators produce an iterator of device-resident ColumnarBatches; crossing to
the host happens only in collect/transition nodes.
"""

from __future__ import annotations

import time
from typing import Dict, Iterator, List, Tuple

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.dtypes import DataType

Schema = List[Tuple[str, DataType]]

ESSENTIAL = 0
MODERATE = 1
DEBUG = 2

# standard metric names (GpuExec.scala:43-160)
# pipeline-level names (exec/pipeline.py PipelineStats -> QueryEnd
# "pipeline" dict -> tools/eventlog.QueryInfo.pipeline)
PIPELINE_FILL_RATIO = "pipelineFillRatio"
HOST_SYNC_COUNT = "hostSyncCount"
UPLOAD_OVERLAP_MS = "uploadOverlapMs"
NUM_OUTPUT_ROWS = "numOutputRows"
NUM_OUTPUT_BATCHES = "numOutputBatches"
OP_TIME = "opTime"
NUM_INPUT_ROWS = "numInputRows"
NUM_INPUT_BATCHES = "numInputBatches"
SORT_TIME = "sortTime"
AGG_TIME = "computeAggTime"
CONCAT_TIME = "concatTime"
JOIN_TIME = "joinTime"
SPILL_AMOUNT = "spillData"


class TpuMetric:
    """One counter.  Accepts lazy ``RowCount`` additions: deferred
    device-resident counts accumulate unmaterialized and resolve in a
    single batched device fetch when ``value`` is first read (at
    QueryEnd metric collection), so per-batch row tallies never force
    a per-batch host sync."""

    __slots__ = ("name", "level", "_value", "_pending")

    def __init__(self, name: str, level: int = MODERATE):
        self.name = name
        self.level = level
        self._value = 0
        self._pending = None  # deferred RowCounts, resolved on read

    @property
    def value(self):
        if self._pending:
            from spark_rapids_tpu.columnar.column import RowCount
            RowCount.materialize_all(self._pending)
            self._value += sum(int(rc) for rc in self._pending)
            self._pending = None
        return self._value

    @value.setter
    def value(self, v) -> None:
        self._value = v
        self._pending = None

    def add(self, v) -> None:
        from spark_rapids_tpu.columnar.column import RowCount
        if isinstance(v, RowCount):
            if v.is_concrete:
                self._value += int(v)
            else:
                if self._pending is None:
                    self._pending = []
                self._pending.append(v)
            return
        self._value += v

    def __iadd__(self, v):
        self.add(v)
        return self


class MetricTimer:
    def __init__(self, metric: TpuMetric):
        self.metric = metric

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.metric.add(time.perf_counter_ns() - self._t0)
        return False


class TpuExec:
    """Base physical operator."""

    # True when every batch this operator yields is freshly allocated
    # per pull and never retained by the operator (or anyone upstream) —
    # the safety precondition for a consumer stage to DONATE the batch's
    # buffers to XLA (ops/compiler.py).  Retaining scans (in-memory,
    # cache) and pass-through operators keep the default False.
    ephemeral_output = False

    def __init__(self, *children: "TpuExec"):
        self.children: Tuple[TpuExec, ...] = tuple(children)
        self.metrics: Dict[str, TpuMetric] = {}
        self._register_metric(NUM_OUTPUT_ROWS, ESSENTIAL)
        self._register_metric(NUM_OUTPUT_BATCHES, MODERATE)
        self._register_metric(OP_TIME, MODERATE)

    def _register_metric(self, name: str, level: int = MODERATE) -> TpuMetric:
        m = self.metrics.setdefault(name, TpuMetric(name, level))
        return m

    def metric(self, name: str) -> TpuMetric:
        return self.metrics[name]

    def timer(self, name: str) -> MetricTimer:
        return MetricTimer(self.metrics[name])

    # ---- interface -----------------------------------------------------------
    @property
    def schema(self) -> Schema:
        raise NotImplementedError

    def execute(self) -> Iterator[ColumnarBatch]:
        """Produce device batches, updating numOutputRows/Batches.

        opTime covers the operator's own iteration steps (the pull of each
        batch), not just generator construction — generators return
        instantly, the work happens in ``next()``."""
        from spark_rapids_tpu.utils import tracing
        it = self.do_execute()
        timer = self.metrics[OP_TIME]
        name = self.node_name()
        while True:
            t0 = time.perf_counter_ns()
            try:
                # single branch per pull when tracing is off; spans
                # nest through the child iterator pulls, so the
                # rollup's exclusive time per operator matches the
                # opTimeSelf discipline at span granularity.  Under
                # profile.trace the span is also the operator's
                # annotation in the profiler's trace (NVTX-range
                # analog), named by ``op``.
                if tracing._active:
                    with tracing.span("operator.batch", op=name):
                        batch = next(it)
                else:
                    batch = next(it)
            except StopIteration:
                timer.add(time.perf_counter_ns() - t0)
                return
            timer.add(time.perf_counter_ns() - t0)
            # row_count, not nrows: a deferred device-resident count
            # accumulates lazily instead of forcing a per-batch sync
            self.metrics[NUM_OUTPUT_ROWS] += batch.row_count
            self.metrics[NUM_OUTPUT_BATCHES] += 1
            yield batch

    def do_execute(self) -> Iterator[ColumnarBatch]:
        raise NotImplementedError

    # ---- plan display --------------------------------------------------------
    def node_name(self) -> str:
        return type(self).__name__

    def describe(self) -> str:
        return self.node_name()

    def tree_string(self) -> str:
        from spark_rapids_tpu.utils.trees import render_tree
        return render_tree(self)

    def collect_metrics(self) -> Dict[str, Dict[str, int]]:
        """Per-node metric dicts keyed by tree path.  ``opTime`` is
        inclusive of the child subtree (iterator pulls); the derived
        ``opTimeSelf`` subtracts direct children so consumers can
        aggregate without double counting."""
        out = {}

        def rec(node, path):
            key = f"{path}{node.node_name()}"
            m = {metric.name: metric.value
                 for metric in node.metrics.values()}
            child_time = sum(c.metrics[OP_TIME].value
                             for c in node.children)
            m["opTimeSelf"] = max(m.get(OP_TIME, 0) - child_time, 0)
            out[key] = m
            for i, c in enumerate(node.children):
                rec(c, f"{key}.{i}.")
        rec(self, "")
        return out
