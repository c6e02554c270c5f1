"""ColumnarBatch: a set of equal-length device columns.

Counterpart of Spark's ``ColumnarBatch`` of GpuColumnVectors flowing between
GpuExecs (SURVEY.md section 1 "data-plane containment").  All columns share one
logical ``nrows`` and one row capacity; batches flow device-resident between
TPU operators, and crossing back to the host happens only at explicit
collect/transition points (exec/collect.py).
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu.columnar import dtypes as dts
from spark_rapids_tpu.columnar.column import (
    Column, RowCount, bucket_capacity)
from spark_rapids_tpu.columnar.dtypes import DataType

Schema = Sequence[Tuple[str, DataType]]


class ColumnarBatch:
    # __weakref__: the serving result cache (serving/reuse.py) tracks
    # in-memory input batches weakly — id()-based fingerprints are only
    # sound while the referent lives, and the cache must never pin a
    # client's batches
    __slots__ = ("columns", "_row_count", "transient_wire_bytes",
                 "__weakref__")

    def __init__(self, columns: Dict[str, Column], nrows=None):
        self.columns: Dict[str, Column] = dict(columns)
        # transient headroom a shuffle-received batch still pins in HBM
        # beyond its own columns: the packed exchange's lane payloads
        # live until the next program launch reuses their buffers, so
        # spill registration (memory/spill.py) counts this against the
        # DEVICE budget while the batch is device-resident.  Consumed
        # once — the first downstream materialization (pipeline /
        # coalesce) zeroes it.
        self.transient_wire_bytes: int = 0
        if nrows is None:
            if not columns:
                raise ValueError("empty batch needs explicit nrows")
            nrows = next(iter(columns.values())).row_count
        self._row_count = RowCount.wrap(nrows)
        if self._row_count.is_concrete:
            # deferred counts skip the cross-column check: forcing each
            # column's device scalar here would defeat the deferral (the
            # count is shared from one kernel output anyway)
            n = int(self._row_count)
            for name, col in self.columns.items():
                if col.row_count.is_concrete and col.nrows != n:
                    raise ValueError(
                        f"column {name} nrows {col.nrows} != batch {n}")

    @property
    def nrows(self) -> int:
        """Concrete row count (syncs once if carried lazily on device)."""
        return int(self._row_count)

    @property
    def row_count(self) -> RowCount:
        """The possibly-lazy count; device paths use
        ``row_count.device_i32()`` instead of ``nrows`` so a deferred
        aggregate count never forces a host sync."""
        return self._row_count

    # ------------------------------------------------------------------ basics --
    @property
    def names(self) -> List[str]:
        return list(self.columns)

    @property
    def schema(self) -> List[Tuple[str, DataType]]:
        return [(n, c.dtype) for n, c in self.columns.items()]

    @property
    def capacity(self) -> int:
        if not self.columns:
            return bucket_capacity(self.nrows)
        return next(iter(self.columns.values())).capacity

    def column(self, name: str) -> Column:
        return self.columns[name]

    def device_size_bytes(self) -> int:
        return sum(c.device_size_bytes() for c in self.columns.values())

    def __len__(self) -> int:
        return self.nrows

    def __repr__(self) -> str:
        cols = ", ".join(f"{n}:{c.dtype}" for n, c in self.columns.items())
        return f"ColumnarBatch[{self.nrows} rows]({cols})"

    # ------------------------------------------------------------ host interop --
    @classmethod
    def from_pydict(cls, data: Dict[str, Sequence],
                    capacity: Optional[int] = None) -> "ColumnarBatch":
        nrows = len(next(iter(data.values()))) if data else 0
        cap = capacity or bucket_capacity(nrows)
        cols = {}
        for name, values in data.items():
            if isinstance(values, Column):
                cols[name] = values
                continue
            arr = np.asarray(values) if not isinstance(values, (list, tuple)) \
                else values
            if isinstance(arr, (list, tuple)):
                if any(isinstance(v, str) or v is None for v in arr) and \
                        any(isinstance(v, str) for v in arr):
                    cols[name] = Column.from_strings(arr, capacity=cap)
                    continue
                if any(isinstance(v, (list, tuple, np.ndarray))
                       for v in arr):
                    flat = [e for v in arr if v is not None for e in v]
                    edt = dts.from_numpy_dtype(np.asarray(
                        flat if flat else [0]).dtype)
                    cols[name] = Column.from_arrays(arr, edt, capacity=cap)
                    continue
                validity = np.array([v is not None for v in arr])
                filled = [0 if v is None else v for v in arr]
                present = [v for v in arr if v is not None]
                if present and all(isinstance(v, bool) for v in present):
                    # bools + None otherwise infer as int64
                    filled = np.array([bool(v) for v in filled],
                                      dtype=np.bool_)
                cols[name] = Column.from_numpy(
                    np.asarray(filled), capacity=cap,
                    validity=None if validity.all() else validity)
            else:
                cols[name] = Column.from_numpy(arr, capacity=cap)
        return cls(cols, nrows)

    @classmethod
    def from_arrow(cls, table, capacity: Optional[int] = None) -> "ColumnarBatch":
        from spark_rapids_tpu.columnar import nested
        if nested.has_nested(table):
            table = nested.shred_table(table)
        nrows = table.num_rows
        cap = capacity or bucket_capacity(nrows)
        cols = {name: Column.from_arrow(table.column(name), capacity=cap)
                for name in table.column_names}
        return cls(cols, nrows)

    @classmethod
    def from_pandas(cls, df, capacity: Optional[int] = None) -> "ColumnarBatch":
        import pyarrow as pa
        return cls.from_arrow(pa.Table.from_pandas(df, preserve_index=False),
                              capacity=capacity)

    def to_arrow(self):
        import jax
        import pyarrow as pa
        # Host-built columns export their EXACT numpy buffers and never
        # touch the device (the .data property would materialize a
        # device copy — on emulated-f64 TPUs the round trip perturbs
        # doubles, see Column's docstring).  For genuinely
        # device-resident buffers, gather everything in ONE device_get:
        # per-buffer np.asarray would pay a device-to-host sync each.
        def devbuf(c, kind):
            if getattr(c, f"_np_{kind}") is not None:
                return None
            return getattr(c, f"_jax_{kind}")

        device_bufs = []
        seen = set()
        for c in self.columns.values():
            for kind in ("data", "validity", "offsets"):
                buf = devbuf(c, kind)
                if buf is not None and id(buf) not in seen:
                    seen.add(id(buf))
                    device_bufs.append(buf)
        if device_bufs:
            from spark_rapids_tpu.utils import hostsync
            fetched = hostsync.fetch_all(device_bufs)
            cache = {id(d): h for d, h in zip(device_bufs, fetched)}

            def pick(c, kind):
                np_buf = getattr(c, f"_np_{kind}")
                if np_buf is not None:
                    return np_buf
                jb = getattr(c, f"_jax_{kind}")
                return cache.get(id(jb), jb) if jb is not None else None

            cols = {}
            for n, c in self.columns.items():
                cols[n] = Column(
                    c.dtype, pick(c, "data"), c.nrows,
                    validity=pick(c, "validity"),
                    offsets=pick(c, "offsets"),
                    dictionary=c.dictionary)
            return pa.table({n: c.to_arrow() for n, c in cols.items()})
        return pa.table({n: c.to_arrow() for n, c in self.columns.items()})

    def to_pandas(self):
        return self.to_arrow().to_pandas()

    def to_pydict(self):
        return {n: c.to_pylist() for n, c in self.columns.items()}

    # --------------------------------------------------------------- reshaping --
    def select(self, names: Iterable[str]) -> "ColumnarBatch":
        return ColumnarBatch({n: self.columns[n] for n in names},
                             self._row_count)

    def rename(self, mapping: Dict[str, str]) -> "ColumnarBatch":
        return ColumnarBatch({mapping.get(n, n): c
                              for n, c in self.columns.items()},
                             self._row_count)

    def with_column(self, name: str, col: Column) -> "ColumnarBatch":
        cols = dict(self.columns)
        cols[name] = col
        return ColumnarBatch(cols, self._row_count)


def empty_batch(schema: Schema, capacity: int = 0) -> ColumnarBatch:
    cap = bucket_capacity(max(capacity, 1))
    cols = {}
    for name, dt in schema:
        if dt.is_array:
            cols[name] = Column.from_arrays([], dt.element, capacity=cap)
        elif dt.is_string:
            cols[name] = Column.from_strings([], capacity=cap)
        else:
            cols[name] = Column.from_numpy(
                np.zeros(0, dtype=dt.storage), dtype=dt, capacity=cap)
    return ColumnarBatch(cols, 0)
