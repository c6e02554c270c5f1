"""Whole-stage fusion: collapse Filter/Project chains into ONE compiled
stage (the cross-operator half of the stage compiler).

``ops/compiler.py`` already fuses WITHIN one operator — a project's whole
expression forest, or a filter's predicate + compaction, is one XLA
computation.  This module fuses ACROSS operators: a
``Filter <- Project <- Filter`` chain that today dispatches three jitted
callables (with a full device materialization between each) composes into
a single :class:`FusedStageExec` whose compiled function evaluates every
member's expressions in one trace — projections substitute through
(``substitute_bound``), predicates AND into one row mask carried inside
the trace, and the selection compacts ONCE at the stage boundary instead
of once per filter.  Intermediates never leave registers/VMEM; each batch
costs one jit dispatch per pipeline stage ("Data Path Fusion in GPU for
Analytical Query Processing", PAPERS.md).  A Filter/Project chain under an
Aggregate always folds into the aggregate unless it records ANSI checks
(``TpuOverrides._try_fuse_aggregate``): the predicate is the group-by's
row mask and nothing compacts.

Composition is the logical-plan walk in ``plan/overrides.py``
(``TpuOverrides._try_fuse_chain``) and
``parallel/dist_planner.py`` (``DistPlanner._fused_chain``); this module
holds the shared chain composer and the single-process operator.  Fusion
never crosses an exchange, a cached plan node, or a member the fuser
cannot ingest (black-box UDFs, CPU-fallback expressions) — those chains
run unfused, counted as ``fusibleChains`` so the profiling health check
can flag the lost fusion.  ``spark.rapids.tpu.fusion.enabled=false`` is
the A/B switch: results are bit-identical either way (masked evaluation
and per-operator compaction select the same rows in the same order).
"""

from __future__ import annotations

import threading
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.base import (NUM_INPUT_BATCHES, NUM_INPUT_ROWS,
                                        Schema, TpuExec)
from spark_rapids_tpu.exec.basic import filter_metrics
from spark_rapids_tpu.ops.compiler import FilterStageFn, StageFn
from spark_rapids_tpu.ops.expressions import (BoundReference, Expression,
                                              substitute_bound)

# QueryEnd "fusion" dict metric names (tools/eventlog.QueryInfo.fusion)
FUSED_OPERATORS = "fusedOperators"
DISPATCHES_SAVED = "dispatchesSaved"


class FusionMetrics:
    """Process-wide fusion counters (the checkpoint_metrics discipline)."""

    FIELDS = ("fusedStages", "fusedOperators", "fusibleChains",
              "fallbacks",
              # Wire-fused distributed stages: stages that emitted the
              # packed wire payload inside the compute program, and warm
              # stages that COULD have fused but ran the two-dispatch
              # path (the "fusible chain ran unfused" health-check family).
              "fusedWireStages", "wireUnfusedLaunches")

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


fusion_metrics = FusionMetrics()

# Wire-fusion counters folded into each QueryEnd fusion dict as
# per-query deltas of the process-wide counters above.  Only non-zero
# deltas are merged: a query with no wire-fusion activity emits a
# fusion dict without them.
QUERY_DELTA_FIELDS = ("fusedWireStages", "wireUnfusedLaunches")


def wire_delta(before: Dict[str, int]) -> Dict[str, int]:
    """Non-zero per-query deltas of the wire-fusion counters since
    ``before`` (a ``fusion_metrics.snapshot()`` taken at query start)."""
    now = fusion_metrics.snapshot()
    return {k: now.get(k, 0) - before.get(k, 0)
            for k in QUERY_DELTA_FIELDS
            if now.get(k, 0) - before.get(k, 0)}


def compose_chain(exprs: Optional[List[Expression]],
                  conds: List[Expression], node,
                  schema: Schema) -> Tuple[List[Expression],
                                           List[Expression]]:
    """Fold one chain member into the running (exprs, conds) pair.

    Invariant: after folding member ``node``, ``exprs`` and every
    conjunct in ``conds`` are expressed over ``node``'s INPUT (child)
    namespace — a Project substitutes its expressions through all of
    them, a Filter (pass-through namespace) prepends its predicate, so
    ``conds`` stays in BOTTOM-FIRST chain order (the evaluation order
    FilterStageFn's progressive ANSI-check masking needs).  Masked
    evaluation selects the same rows as per-operator compaction:
    compaction preserves row order and every expression is pure, so
    evaluating a projection before (rather than after) a downstream
    filter's compaction gathers identical values for the surviving
    rows."""
    from spark_rapids_tpu.plan import logical as L
    if isinstance(node, L.Project):
        repl = list(node.exprs)
        if exprs is None:
            exprs = repl
        else:
            exprs = [substitute_bound(e, repl) for e in exprs]
        conds = [substitute_bound(c, repl) for c in conds]
    else:  # Filter: namespace unchanged
        if exprs is None:
            exprs = [BoundReference(i, dt, name=n)
                     for i, (n, dt) in enumerate(schema)]
        conds = [node.condition] + conds
    return exprs, conds


def has_check_exprs(exprs) -> bool:
    """True when any expression tree records trace-time ANSI checks
    (today: ``Cast(ansi=True)``, the only ``EmitContext.add_check``
    producer).  The AGGREGATE fold must refuse such chains: the
    aggregation kernels return (keys, buffers, count) with no check-
    flag channel, so a check recorded inside them would be silently
    dropped — the chain fuses as a FusedStageExec (whose stage wrappers
    surface checks) feeding an unfused aggregate instead."""
    from spark_rapids_tpu.ops.cast import Cast

    def walk(e) -> bool:
        if isinstance(e, Cast) and e.ansi:
            return True
        return any(walk(c) for c in e.children)

    return any(walk(e) for e in exprs)


def collect_runtime_savings(exec_root: TpuExec) -> Dict[str, int]:
    """Walk an executed physical tree for fusion attribution: stages and
    member operators actually fused, plus the jit dispatches banked this
    run (one per collapsed operator per batch) — the runtime half of the
    QueryEnd ``fusion`` dict."""
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    out = {"fusedStages": 0, "fusedOperators": 0, "dispatchesSaved": 0,
           "encodedStages": 0}

    def rec(n):
        if isinstance(n, FusedStageExec):
            out["fusedStages"] += 1
            out["fusedOperators"] += len(n.members)
            out["dispatchesSaved"] += n.metrics[DISPATCHES_SAVED].value
        elif isinstance(n, TpuHashAggregateExec):
            if getattr(n, "fused_ops", 0):
                out["fusedStages"] += 1
                out["fusedOperators"] += n.fused_ops + 1
                out["dispatchesSaved"] += \
                    n.fused_ops * n.metrics[NUM_INPUT_BATCHES].value
            if getattr(n, "_encoded_exec", False):
                # encoded execution: the stage ran on dictionary codes
                # (QueryEnd fusion dict)
                out["encodedStages"] += 1
        for c in n.children:
            rec(c)

    rec(exec_root)
    return out


class FusedStageExec(TpuExec):
    """One compiled stage for a collapsed Filter/Project chain.

    ``exprs`` are the stage's output expressions and ``conds`` the
    member predicates (bottom-first), all over the child's schema.
    With predicates the stage runs a :class:`FilterStageFn` (one
    progressively-masked predicate pass + projections + a single
    compaction in one XLA computation); without, a plain
    :class:`StageFn`.  ``members`` names the collapsed logical
    operators (display + observability)."""

    ephemeral_output = True

    def __init__(self, exprs: Sequence[Expression],
                 conds: Sequence[Expression], child: TpuExec,
                 members: Sequence[str], donate: bool = False):
        super().__init__(child)
        self.exprs = list(exprs)
        self.conds = list(conds or [])
        self.condition = self.conds[0] if self.conds else None
        self.members = list(members)
        in_dtypes = [dt for _, dt in child.schema]
        donate = donate and child.ephemeral_output
        if self.conds:
            self._fn = FilterStageFn(self.conds, self.exprs, in_dtypes,
                                     donate=donate)
        else:
            self._fn = StageFn(self.exprs, in_dtypes, donate=donate)
        self._register_metric(NUM_INPUT_ROWS)
        self._register_metric(NUM_INPUT_BATCHES)
        m = self._register_metric(FUSED_OPERATORS)
        m.value = len(self.members)
        self._register_metric(DISPATCHES_SAVED)

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def schema(self) -> Schema:
        return [(e.name, e.dtype) for e in self.exprs]

    def describe(self) -> str:
        return (f"FusedStageExec[{'+'.join(self.members)}; "
                f"{len(self.exprs)} cols"
                + (", filtered" if self.condition is not None else "")
                + "]")

    def _compute_batch(self, batch, names):
        """One fused dispatch (span point ``stage.fused``)."""
        if self.condition is None:
            cols = self._fn(batch)
            return ColumnarBatch(dict(zip(names, cols)),
                                 batch.row_count)
        cols, n = self._fn(batch)
        filter_metrics.note(batch.row_count, n)
        return None if n == 0 else \
            ColumnarBatch(dict(zip(names, cols)), n)

    def do_execute(self) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.memory.retry import with_retry
        names = [e.name for e in self.exprs]
        saved_per_batch = max(len(self.members) - 1, 0)

        def tallied():
            from spark_rapids_tpu.parallel.exchange_async import (
                resolve_pending)
            for batch in self.child.execute():
                self.metrics[NUM_INPUT_ROWS] += batch.row_count
                self.metrics[NUM_INPUT_BATCHES] += 1
                yield batch
                # fused-stage batch boundary = async-exchange resolution
                # point: the stage's compute for this batch has been
                # dispatched, so any in-flight exchange on this thread
                # (a distributed sub-execution feeding the stage)
                # verifies NOW, behind that dispatch — no-op when the
                # thread holds no window (parallel/exchange_async.py)
                resolve_pending()

        from spark_rapids_tpu.utils import tracing
        stage_op = "+".join(self.members)

        def compute(batch):
            # one jit dispatch where the unfused chain pays one per
            # member — the saving the QueryEnd fusion dict reports.
            # Counted per ATTEMPT (an OOM retry re-dispatches here, and
            # would have re-dispatched every member unfused), so the
            # metric can legitimately exceed members-1 x inputBatches
            # on retried queries
            self.metrics[DISPATCHES_SAVED] += saved_per_batch
            if tracing._active:
                with tracing.span("stage.fused", op=stage_op):
                    return self._compute_batch(batch, names)
            return self._compute_batch(batch, names)

        if self._fn.donate:
            # donated inputs are consumed by the kernel: operator-level
            # OOM retry is unsafe, faults escalate to query-level
            # recovery (docs/performance.md#donation)
            for batch in tallied():
                out = compute(batch)
                if out is not None:
                    yield out
            return
        for out in with_retry(tallied(), compute):
            if out is not None:
                yield out
