"""Find a cell's files by name: ``BENCHMARK.json`` names, files hold.

    workloads[].config  -> configs/<config>.json   (and BENCHMARK.json's
                           configs[].file, which has to be that path)
    workloads[].traffic -> mixes/<traffic>.json
    mix.queries[]       -> queries/<suite>/<q>.sql and <q>.json
    config.suite        -> datagen/<suite>.py, reference/<suite>.py
    metric name         -> metrics/<name>.json -> readers/<reader>.py
"""

import importlib
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with every file it names loaded."""

    def __init__(self, name, bench=None):
        bench = bench or load_benchmark()
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(has {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config = load_json("configs", self.entry["config"] + ".json")
        self.mix = load_json("mixes", self.entry["traffic"] + ".json")
        self.suite = self.config["suite"]
        self.queries = {}
        for q in self.mix["queries"]:
            meta = load_json("queries", self.suite, q + ".json")
            with open(os.path.join(BENCH, "queries", self.suite,
                                   q + ".sql")) as f:
                meta["text"] = f.read()
            self.queries[q] = meta
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    @property
    def tables(self):
        """Every table a query of the mix names, in a fixed order."""
        seen = []
        for meta in self.queries.values():
            for t in meta["tables"]:
                if t not in seen:
                    seen.append(t)
        return seen

    def datagen(self):
        return importlib.import_module(f"benchmark.datagen.{self.suite}")

    def reference(self):
        return importlib.import_module(f"benchmark.reference.{self.suite}")


def metric_reader(name):
    """(reader module, its argument) for a per-layer metric."""
    doc = load_json("metrics", name + ".json")
    mod = importlib.import_module(f"benchmark.readers.{doc['reader']}")
    return mod, doc.get("arg", {})
