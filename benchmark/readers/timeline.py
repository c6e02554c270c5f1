"""The engine's spans over the device's idle gaps, and the programs the
device ran (``trace/timeline.py`` over the traced run's ``.xplane.pb``,
read once a run; the whole table goes to standard error).

arg: ``field`` of ``timeline.label_trace``'s result (``launches``,
``idle_scan_share``, ``idle_sync_share``, ``idle_unspanned_share``);
``per`` "traced_query" divides by the queries the trace covered.  A
rehearsal has no device trace: None.
"""

import json
import os
import sys

from benchmark.harness.data import cache_dir
from benchmark.trace import reduce as trace_reduce
from benchmark.trace import timeline

_TABLES = {}  # .xplane.pb path -> its table: four metrics read one file


def _table(obs):
    path = trace_reduce.find_xplane(
        os.path.join(cache_dir("trace"), obs.cell.name))
    if path is None:
        return None
    if path not in _TABLES:
        table = _TABLES[path] = timeline.label_trace(path)
        if table is not None:
            print(json.dumps({"phase": "timeline", **table}),
                  file=sys.stderr, flush=True)
    return _TABLES[path]


def read(arg, obs, begun):
    if not obs.trace or not obs.traced:
        return None
    table = _table(obs)
    if table is None:
        return None
    value = float(table[arg["field"]])
    if arg.get("per") == "traced_query":
        value /= len(obs.traced)
    return value
