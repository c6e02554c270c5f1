"""What a run observed, for the metric readers; and the traced run's
profiler window."""

import os
import shutil

from benchmark.harness import spec
from benchmark.harness.data import cache_dir


class Observations:
    """Everything a reader may read.  Filled by ``run.py`` as the run
    goes; readers never write here except ``notes``."""

    def __init__(self, cell, session):
        self.cell = cell
        self.session = session
        self.done = []          # the window's queries (window.Done)
        self.traced = []        # those the profiler covered
        self.n_queries = 0
        self.clock = {}         # seconds the harness took itself
        self.trace = None       # trace/reduce.py's reduction
        self.memory_stats = []  # per chip used, after the window
        self.notes = {}


class Readers:
    """The cell's per-layer metrics, each with its reader."""

    def __init__(self, cell):
        self.metrics = [(m, *spec.metric_reader(m["name"]))
                        for m in cell.per_layer]
        self.begun = {}

    def begin(self, obs):
        for m, reader, arg in self.metrics:
            if hasattr(reader, "begin"):
                self.begun[m["name"]] = reader.begin(arg, obs)

    def read(self, obs):
        out = {}
        for m, reader, arg in self.metrics:
            value = reader.read(arg, obs, self.begun.get(m["name"]))
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out


class Tracer:
    """``jax.profiler`` over the first ``n`` queries of the window (what
    comes back from a trace is capped, and tracing slows the host)."""

    def __init__(self, cell_name, n):
        import jax.profiler
        self.profiler = jax.profiler
        self.annotation = jax.profiler.TraceAnnotation
        self.n = max(int(n), 1)
        self.dir = os.path.join(cache_dir("trace"), cell_name)
        shutil.rmtree(self.dir, ignore_errors=True)
        self.running = False
        self.covered = 0

    def before_query(self, sent):
        """The client has completed ``sent`` queries and is about to
        send the next; None once its loop ended."""
        if sent == 0:
            options = self.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            self.profiler.start_trace(self.dir, profiler_options=options)
            self.running = True
        elif self.running and (sent is None or sent >= self.n):
            self.profiler.stop_trace()
            self.running = False
        if sent is not None and self.running:
            self.covered = sent + 1
