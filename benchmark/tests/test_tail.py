"""The tail each cell reports: a percentile by nearest rank with ten
samples beyond it, not the single slowest query of the window."""

import numpy as np
import pytest

from benchmark import run
from benchmark.harness import spec

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
TAILS = {"tpch_sf1.q6": "query_p90_s", "tpch_sf1.q3": "query_p80_s"}


def test_percentile_is_the_nearest_rank():
    values = list(range(1, 51))
    assert run.percentile(values, 80) == 40
    assert run.percentile(values, 90) == 45
    assert run.percentile(values[::-1], 80) == 40
    assert run.percentile([7.0], 80) == 7.0


@pytest.mark.parametrize("name", CELLS)
def test_cell_reads_its_tail(name):
    reported = {m["name"] for m in spec.Cell(name, BENCH).end_to_end}
    assert "query_max_s" not in reported
    want = {"query_s", "setup_s"} | ({TAILS[name]} if name in TAILS else set())
    assert reported == want


def test_one_outlier_moves_the_max_not_p80():
    times = list(np.random.default_rng(2**31 + 42).uniform(0.68, 0.76, 50))
    slow = list(times)
    slow[int(np.argmax(times))] *= 10
    assert max(slow) > 5 * max(times)
    assert run.percentile(slow, 80) == run.percentile(times, 80)
    # wherever it lands, one outlier moves p80 by one rank at the most
    ordered = sorted(times)
    for i in range(len(times)):
        slow = list(times)
        slow[i] *= 10
        assert ordered[39] <= run.percentile(slow, 80) <= ordered[40]
