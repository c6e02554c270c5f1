"""The generator of suite ``tpch_q9``: ``benchmark/datagen/tpch.py``'s,
imported and not copied.  The suite exists only because the harness finds
a reference by suite (``configs/tpch_sf1_q9.json``); its tables are
``tpch``'s, row for row, for the same (names, sf, seed)."""

from benchmark.datagen.tpch import COLUMNS, gen_tables, rows

__all__ = ["COLUMNS", "gen_tables", "rows"]
