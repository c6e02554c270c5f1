"""The timed window: one client's closed loop over ``session.sql``.

The one general traffic generator.  A mix file gives the queries and how
many of them warm the session up; the client sends them round robin, the
next when the last one's rows are in hand.  No query starts after
``seconds``; the one in flight finishes and the window ends at its
completion.  Every seed sends the same queries in the same order: the
seed changes the data, never the work.  (Several clients, each with an
order of its own, come with the cell that needs them.)
"""

import itertools
import time
import traceback


class Done:
    """One query of the window: what was sent, when, and what came."""

    __slots__ = ("query", "t0", "t_sql", "t1", "answer", "error",
                 "off_path", "spans")

    def __init__(self, query):
        self.query = query
        self.t0 = self.t_sql = self.t1 = 0.0
        self.answer = self.error = self.off_path = self.spans = None

    @property
    def seconds(self):
        return self.t1 - self.t0


def schedule(mix):
    """The endless order in which the mix's queries are sent."""
    if (int(mix.get("clients", 1)) != 1
            or mix.get("loop", "closed") != "closed"):
        raise ValueError("this generator drives one client in a closed "
                         f"loop; the mix asks for {mix!r}")
    return itertools.cycle(mix["queries"])


def off_path(session, config, log_from):
    """Why this answer did not come from the timed path, or None: the
    session's own records (a recovery trail that reached the CPU rung or,
    on a mesh, the single-device rung; on a mesh, a plan that did not run
    distributed)."""
    mesh = bool(config["session"].get("mesh_devices"))
    for rec in session.recovery_log[log_from:]:
        if rec.get("action") == "cpu" or (mesh and
                                          rec.get("action") == "demote"):
            return f"recovery ladder left the timed path: {rec}"
    if mesh:
        if session.last_dist_explain != "distributed":
            return f"not distributed: {session.last_dist_explain!r}"
    return None


def one_query(session, cell, name, annotate):
    """Send one query through the front door and wait for its rows."""
    done = Done(name)
    text = cell.queries[name]["text"]
    log_from = len(session.recovery_log)
    done.t0 = time.perf_counter()
    try:
        with annotate("bench.query"):
            with annotate("bench.sql"):
                frame = session.sql(text)
            done.t_sql = time.perf_counter()
            with annotate("bench.to_pandas"):
                done.answer = frame.to_pandas()
    except Exception:  # the loop must go on; the query counts as failed
        done.error = traceback.format_exc()
    done.t1 = time.perf_counter()
    if done.error is None:
        done.off_path = off_path(session, cell.config, log_from)
        done.spans = session.last_span_stats
    return done


class NoAnnotation:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def run_window(session, cell, seconds, tracer=None):
    """Drive the loop for ``seconds``; returns (list of Done in order of
    completion, window start, window end).  ``tracer`` (a traced run) is
    told before each query and after the last."""
    annotate = tracer.annotation if tracer else NoAnnotation
    order, done = schedule(cell.mix), []
    start = time.perf_counter()
    deadline = start + seconds
    while time.perf_counter() < deadline:
        if tracer:
            tracer.before_query(len(done))
        done.append(one_query(session, cell, next(order), annotate))
    if tracer:
        tracer.before_query(None)
    end = done[-1].t1 if done else start
    return done, start, end
