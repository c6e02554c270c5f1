"""The harness behind ``benchmark/run.py``: everything here is general;
what belongs to one configuration, mix, query or metric sits in a data
file of its own, found by the name ``BENCHMARK.json`` gives."""
