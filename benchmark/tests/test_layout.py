"""``BENCHMARK.json`` against the files it names: every cell resolves,
every name and unit holds only the allowed characters."""

import json
import os
import re

import pytest

from benchmark.harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert sorted(BENCH) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads",
         "end_to_end", "per_layer"])
    assert BENCH["paths"] == ["benchmark"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 65536


def test_names_and_units():
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[kind]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((kind in ("end_to_end", "per_layer"),
                          kind, entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry
                assert entry["better"] in ("lower", "higher")
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200
                    assert "\n" not in entry[key] and "\t" not in entry[key]
    metric_names = [n for is_metric, _, n in names if is_metric]
    assert len(metric_names) == len(set(metric_names))
    for w in BENCH["workloads"]:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
        assert all(NAME.match(k) for k in c["reduced"])
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(len(BENCH["workloads"]) // 2, 1)


def test_files_under_paths_have_plain_names():
    for base, dirs, files in os.walk(spec.BENCH):
        dirs[:] = [d for d in dirs if d not in (".cache", "__pycache__",
                                                ".pytest_cache")]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), spec.ROOT)
            assert PATH.match(rel), rel


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = spec.Cell(name, BENCH)
    configs = {c["name"]: c for c in BENCH["configs"]}
    entry = configs[cell.entry["config"]]
    assert entry["file"] == f"benchmark/configs/{entry['name']}.json"
    assert cell.config["name"] == entry["name"]
    assert cell.config["source"] == entry["source"]
    assert cell.config["reduced"] == entry["reduced"]
    assert cell.config["chips"] == cell.chips
    gen, ref = cell.datagen(), cell.reference()
    assert cell.queries
    for q, meta in cell.queries.items():
        assert meta["text"].strip()
        assert meta["reference"] in ref.ANSWERS
        for table, columns in meta["tables"].items():
            for c in columns:
                assert c in gen.COLUMNS[table], (q, table, c)
                assert c in meta["text"], (q, c)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in reported, (name, m["name"])
        reader, arg = spec.metric_reader(m["name"])
        assert callable(reader.read) and isinstance(arg, dict)


def test_every_config_is_used_and_differs():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    sources = [c["source"] for c in BENCH["configs"]]
    assert len(sources) == len(set(sources))
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))


def test_layers_spelled_alike():
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers
