"""Prepared statements: hoist once, bind per run, execute at QPS.

``session.prepare(df)`` runs the literal-hoisting pass
(plan/template.py) ONCE and returns a :class:`PreparedStatement`.
Each ``handle.run(p0=..., p1=...)`` binds a new parameter vector and
executes — skipping parsing, planning and override translation on
repeats (the baseline-rung physical plan is cached on the handle) while
still passing through admission, deadline budgets, the recovery ladder
and span tracing like any ad-hoc query.  Because the ParamSlot cache
keys are value-free, repeats share one traced program per stage across
literal churn: zero retraces, zero persistent-tier recompiles, zero
planning passes after warmup.

The handle's ParamSlots are mutable shared state: ``run`` serializes
executions with a per-handle lock, so one handle is safe to call from
many threads (runs queue) but concurrent throughput wants one handle
per thread — ``prepare`` is cheap and handles with identical plans
share every jit/AOT entry anyway.

Requires ``spark.rapids.tpu.template.enabled`` (default off): with the
conf off, plans execute on the exact-key path and ``prepare`` refuses
rather than silently returning a handle that re-plans every run.
"""

from __future__ import annotations

import threading
from typing import List, Tuple

from spark_rapids_tpu.api.dataframe import DataFrame


class PreparedStatement:
    """A hoisted plan template plus a cached physical plan.

    Construct via :meth:`TpuSession.prepare`.  ``info`` is the
    :class:`~spark_rapids_tpu.plan.template.TemplateInfo`; ``refusals``
    lists the (reason, expr) pairs the hoister left inline — a handle
    with refusals still works, it just shares less (the profiling
    health check surfaces templates whose refusals cost them reuse).
    """

    def __init__(self, session, df: DataFrame):
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.plan.template import hoist_literals
        if not session.conf.get(rc.TEMPLATE_ENABLED):
            raise RuntimeError(
                "session.prepare requires "
                f"{rc.TEMPLATE_ENABLED.key}=true (plan templates are "
                "default-off; ad-hoc execution is unaffected)")
        self.session = session
        self.dataframe = df
        self.info = hoist_literals(df.plan)
        # baseline-rung physical plan, planned ONCE here (classic
        # prepared-statement semantics: prepare pays for planning so
        # no run ever does — a run whose first miss planned lazily
        # would smuggle a planning pass into the serving window) and
        # reused on every repeat (physical plans are stateless —
        # execute() returns a fresh iterator).  Recovery-ladder rungs
        # (cpu_only / split-batch) re-plan per attempt and never touch
        # this slot.
        self.exec_plan = session.plan(self.info.plan)
        self.run_count = 0
        self._lock = threading.Lock()
        # the frame that executes: the ORIGINAL plan for event/explain
        # text, with the back-pointer _execute_batches reads to adopt
        # this handle's pre-hoisted template and cached physical plan
        self._frame = DataFrame(session, df.plan)
        self._frame._prepared = self

    # ------------------------------------------------------------ facts --
    @property
    def param_count(self) -> int:
        return self.info.param_count

    @property
    def fingerprint(self) -> str:
        return self.info.fingerprint

    @property
    def refusals(self) -> List[Tuple[str, str]]:
        return list(self.info.refusals)

    def describe(self) -> str:
        """Human-readable slot table + refusal list (docs/debugging)."""
        lines = [f"template {self.info.fingerprint[:16]} "
                 f"({self.param_count} parameter(s))"]
        for s in self.info.slots:
            lines.append(f"  $p{s.index}: {s.dtype.name} "
                         f"= {s.value!r}")
        for reason, expr in self.info.refusals:
            lines.append(f"  inline [{reason}]: {expr}")
        return "\n".join(lines)

    # ------------------------------------------------------------- runs --
    def _resolve(self, args, kwargs) -> Tuple:
        """Positional args (full vector) or ``pN=...`` keywords
        (partial: unnamed slots keep their previous binding)."""
        n = self.info.param_count
        if args and kwargs:
            raise TypeError(
                "pass parameters positionally or by name, not both")
        if args:
            return tuple(args)
        vals = list(self.info.values())
        for k, v in kwargs.items():
            if not (len(k) > 1 and k[0] == "p" and k[1:].isdigit()):
                raise TypeError(
                    f"unknown parameter {k!r}; slots are named "
                    f"p0..p{n - 1}")
            i = int(k[1:])
            if i >= n:
                raise TypeError(
                    f"parameter p{i} out of range; template has "
                    f"{n} slot(s)")
            vals[i] = v
        return tuple(vals)

    def run_batches(self, *args, **params):
        """Bind and execute, returning raw columnar batches (no
        conversion to pandas)."""
        values = self._resolve(args, params)
        with self._lock:
            self.info.bind(values)
            self.run_count += 1
            return self._frame._execute_batches()

    def run(self, *args, **params) -> List[tuple]:
        """Bind and execute, returning rows like ``df.collect()``."""
        values = self._resolve(args, params)
        with self._lock:
            self.info.bind(values)
            self.run_count += 1
            return self._frame.collect()

    def run_pandas(self, *args, **params):
        values = self._resolve(args, params)
        with self._lock:
            self.info.bind(values)
            self.run_count += 1
            return self._frame.to_pandas()
