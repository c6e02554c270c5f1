"""``join.expand_capacity`` in the cell ``tpch_sf1.q9``: a traced
rehearsal reads it from the program's counter, and it is the sum of the
bucketed capacities of the chunks the five joins emit."""

import json

from benchmark import run

CELL = "tpch_sf1.q9"


def test_traced_rehearsal_reads_the_expansions_slots(capsys, monkeypatch):
    from spark_rapids_tpu.columnar.column import bucket_capacity
    from spark_rapids_tpu.exec import join

    chunks = []
    emit = join.TpuHashJoinExec._emit_chunk

    def noting(self, *args):
        chunks.append(bucket_capacity(args[-1]))       # n_out
        return emit(self, *args)

    monkeypatch.setattr(join.TpuHashJoinExec, "_emit_chunk", noting)
    before = join.join_metrics.snapshot()
    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 38),
                   "--seconds", "0.5", "--trace", "1", "--allow-cpu",
                   "--sf", "0.02"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line = json.loads(out[-1])
    assert line["correct"] is True and line["failed"] == 0
    after = join.join_metrics.snapshot()
    assert after["expand_chunks"] - before["expand_chunks"] == len(chunks)
    assert after["expand_capacity"] - before["expand_capacity"] == sum(chunks)
    metric = line["metrics"]["join.expand_capacity"]
    assert metric["unit"] == "slots/query"
    # every query of the rehearsal runs the same plan over the same
    # tables: five joins, a chunk each at this scale
    queries = len(chunks) // 5
    assert queries >= 1 and len(chunks) == 5 * queries
    assert metric["value"] == sum(chunks) / queries
