"""Device-resident column: the TPU counterpart of GpuColumnVector.

Reference: ``sql-plugin/src/main/java/com/nvidia/spark/rapids/GpuColumnVector.java:46``
wraps a device cudf ColumnVector with dynamic length.  XLA wants static shapes,
so a TPU Column is a *fixed-capacity* device array plus a host-side logical row
count:

* capacity is bucketed to powers of two (min 1024) so the universe of traced
  shapes — and therefore XLA recompiles — stays bounded;
* rows in ``[nrows, capacity)`` are padding with unspecified contents; any
  row-sensitive kernel (aggregate, sort, compaction, collect) masks them with
  ``iota < nrows``;
* null tracking is a separate bool validity array (True = valid), ``None``
  meaning "no nulls" — the dense equivalent of cudf's validity bitmask.

Strings are a pair of fixed-capacity arrays (int32 offsets[capacity+1] +
uint8 chars[char_capacity]) mirroring Arrow/cudf layout but padded.
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar import dtypes as dts
from spark_rapids_tpu.columnar.dtypes import DataType

MIN_CAPACITY = 1024


def bucket_capacity(n: int, minimum: int = MIN_CAPACITY) -> int:
    """Round up to the shape bucket: next power of two, floor ``minimum``."""
    n = max(int(n), 1)
    cap = minimum
    while cap < n:
        cap <<= 1
    return cap


class RowCount:
    """Lazy, possibly device-resident row count.

    The per-batch ``int(n)`` on an aggregation's group count is a
    device->host sync that stalls the dispatch queue — the dominant
    serialization in the r05 group-by bench.  A RowCount carries the
    count as a device scalar through the batch pipeline and only
    materializes (``int(rc)``) at true host decision points; the
    materialized value is cached, so one RowCount never syncs twice.

    ``materialize_all`` resolves many RowCounts in ONE device transfer
    (one counted sync) — the end-of-query metric resolution path.
    """

    __slots__ = ("_value", "_device", "_device_i32")

    def __init__(self, value=None, device=None):
        if value is None and device is None:
            raise ValueError("RowCount needs a value or a device scalar")
        self._value = None if value is None else int(value)
        self._device = device
        self._device_i32 = None

    @property
    def is_concrete(self) -> bool:
        return self._value is not None

    def __int__(self) -> int:
        if self._value is None:
            from spark_rapids_tpu.utils import hostsync
            hostsync.count_sync()
            self._value = int(np.asarray(self._device))
        return self._value

    __index__ = __int__

    def device_i32(self):
        """The count as an int32 device scalar (no sync)."""
        if self._device_i32 is None:
            import jax.numpy as jnp
            if self._device is not None:
                d = self._device
                self._device_i32 = d if d.dtype == jnp.int32 \
                    else d.astype(jnp.int32)
            else:
                self._device_i32 = jnp.int32(self._value)
        return self._device_i32

    @staticmethod
    def wrap(n) -> "RowCount":
        if isinstance(n, RowCount):
            return n
        return RowCount(value=int(n))

    @staticmethod
    def materialize_all(counts) -> None:
        """Resolve every unmaterialized RowCount in ``counts`` with one
        batched device fetch (one counted sync)."""
        from spark_rapids_tpu.utils import hostsync
        lazy = [rc for rc in counts
                if isinstance(rc, RowCount) and rc._value is None]
        if not lazy:
            return
        values = hostsync.fetch_all([rc._device for rc in lazy])
        for rc, v in zip(lazy, values):
            rc._value = int(v)

    def __repr__(self) -> str:
        if self._value is not None:
            return f"RowCount({self._value})"
        return "RowCount(<device>)"


class StringLayoutMetrics:
    """Rows of host string columns by how they were laid out:
    ``string_rows_buffered`` from Arrow's own buffers
    (``Column.from_arrow``), ``string_placeholder_rows`` the all-NULL
    columns the file scan puts where it pruned one
    (io/readers.py ``_finish_batch``), ``string_rows_listed`` through
    ``Column.from_strings``' loop over Python objects, counted there
    whoever calls it.  Plain ints, bumped with tracing on or off."""

    def __init__(self):
        self._lock = threading.Lock()
        self.string_rows_buffered = self.string_placeholder_rows = \
            self.string_rows_listed = 0

    def note(self, buffered: int = 0, placeholder: int = 0,
             listed: int = 0) -> None:
        with self._lock:
            self.string_rows_buffered += buffered
            self.string_placeholder_rows += placeholder
            self.string_rows_listed += listed

    def snapshot(self) -> dict:
        with self._lock:
            return {"string_rows_buffered": self.string_rows_buffered,
                    "string_placeholder_rows": self.string_placeholder_rows,
                    "string_rows_listed": self.string_rows_listed}


string_metrics = StringLayoutMetrics()


def _decimal_unscaled(arr, validity: Optional[np.ndarray]) -> np.ndarray:
    """The unscaled int64 of every value of an Arrow decimal array, in
    bulk: a decimal128 is 16 little-endian bytes and a precision within
    DECIMAL_64 leaves the high word as the low word's sign, so the values
    are a strided view of the low words (NULL slots, whose bytes Arrow
    leaves undefined, read 0)."""
    import pyarrow as pa
    if not len(arr):
        return np.zeros(0, dtype=np.int64)
    if not pa.types.is_decimal128(arr.type):
        arr = arr.cast(pa.decimal128(arr.type.precision, arr.type.scale))
    words = np.frombuffer(arr.buffers()[1], dtype=np.int64)
    low = words[2 * arr.offset: 2 * (arr.offset + len(arr)): 2]
    if validity is not None:
        return np.where(validity, low, 0)
    return low


class Column:
    """One device column with logical length ``nrows`` and static capacity.

    Buffers are HOST-LAZY: a column built from host data keeps the exact
    numpy arrays and materializes the device (jax) copy only when a
    device consumer touches ``.data``/``.validity``/``.offsets``.  On
    real TPU hardware f64 is emulated (~48-bit mantissa), so an eager
    host->device->host round trip silently perturbs doubles by ~1e-16 —
    enough to flip boundary comparisons (0.05 >= 0.05) on any host-side
    consumer (CPU fallback, writers, to_pandas).  Host-side export paths
    therefore read ``host_values()`` and never touch the device."""

    __slots__ = ("dtype", "_np_data", "_jax_data", "_np_validity",
                 "_jax_validity", "_np_offsets", "_jax_offsets",
                 "_row_count", "dictionary")

    def __init__(self, dtype: DataType, data, nrows,
                 validity=None, offsets=None, dictionary=None):
        self.dtype = dtype
        # fixed-width values, or uint8 chars for string
        self._np_data = data if isinstance(data, np.ndarray) else None
        self._jax_data = None if self._np_data is not None else data
        # bool[capacity] or None (all valid)
        self._np_validity = validity if isinstance(validity, np.ndarray) \
            else None
        self._jax_validity = None if self._np_validity is not None \
            else validity
        # int32[capacity+1] for strings else None
        self._np_offsets = offsets if isinstance(offsets, np.ndarray) \
            else None
        self._jax_offsets = None if self._np_offsets is not None \
            else offsets
        self.dictionary = dictionary  # host list[str] when elements are
        #                               dictionary codes (array<string>)
        self._row_count = RowCount.wrap(nrows)
        if dtype.has_offsets and self._np_offsets is None and \
                self._jax_offsets is None:
            raise ValueError(f"{dtype} column requires offsets")

    @property
    def nrows(self) -> int:
        """Concrete row count (syncs once if carried lazily on device)."""
        return int(self._row_count)

    @nrows.setter
    def nrows(self, n) -> None:
        self._row_count = RowCount.wrap(n)

    @property
    def row_count(self) -> RowCount:
        """The possibly-lazy count; use ``row_count.device_i32()`` on
        device paths to avoid forcing a host sync."""
        return self._row_count

    # -------------------------------------------------------- buffer access --
    def _upload(self, np_buf, validity: bool = False):
        """Host->device materialization (once per buffer), counted
        where it happens (utils/hostsync.upload)."""
        from spark_rapids_tpu.utils import hostsync
        return hostsync.upload(np_buf, validity=validity)

    @property
    def data(self):
        """Device view of the value buffer (materialized on demand)."""
        if self._jax_data is None:
            self._jax_data = self._upload(self._np_data)
        return self._jax_data

    @property
    def validity(self):
        if self._jax_validity is None:
            if self._np_validity is None:
                return None
            self._jax_validity = self._upload(self._np_validity,
                                              validity=True)
        return self._jax_validity

    @property
    def offsets(self):
        if self._jax_offsets is None:
            if self._np_offsets is None:
                return None
            self._jax_offsets = self._upload(self._np_offsets)
        return self._jax_offsets

    def host_values(self) -> np.ndarray:
        """Exact host view of the full value buffer: the original numpy
        when the column was built from host data (bit-exact), else a
        device fetch."""
        if self._np_data is not None:
            return self._np_data
        from spark_rapids_tpu.utils import hostsync
        hostsync.count_sync()
        return np.asarray(self._jax_data)

    def host_validity(self) -> Optional[np.ndarray]:
        if self._np_validity is not None:
            return self._np_validity
        if self._jax_validity is None:
            return None
        from spark_rapids_tpu.utils import hostsync
        hostsync.count_sync()
        return np.asarray(self._jax_validity)

    def host_offsets(self) -> Optional[np.ndarray]:
        if self._np_offsets is not None:
            return self._np_offsets
        if self._jax_offsets is None:
            return None
        from spark_rapids_tpu.utils import hostsync
        hostsync.count_sync()
        return np.asarray(self._jax_offsets)

    # ------------------------------------------------------------------ shape --
    @property
    def capacity(self) -> int:
        if self.dtype.has_offsets:
            off = self._np_offsets if self._np_offsets is not None \
                else self._jax_offsets
            return int(off.shape[0]) - 1
        d = self._np_data if self._np_data is not None else self._jax_data
        return int(d.shape[0])

    @property
    def char_capacity(self) -> int:
        """Element-buffer capacity (chars for strings, elements for
        arrays)."""
        assert self.dtype.has_offsets
        d = self._np_data if self._np_data is not None else self._jax_data
        return int(d.shape[0])

    @property
    def has_nulls(self) -> bool:
        return self._np_validity is not None or \
            self._jax_validity is not None

    def null_count(self) -> int:
        if not self.has_nulls:
            return 0
        v = self.host_validity()[: self.nrows]
        return int((~v).sum())

    def device_size_bytes(self) -> int:
        d = self._np_data if self._np_data is not None else self._jax_data
        n = d.size * d.dtype.itemsize
        if self.has_nulls:
            v = self._np_validity if self._np_validity is not None \
                else self._jax_validity
            n += v.size
        off = self._np_offsets if self._np_offsets is not None \
            else self._jax_offsets
        if off is not None:
            n += off.size * 4
        return int(n)

    # ----------------------------------------------------------- construction --
    @classmethod
    def from_numpy(cls, values: np.ndarray, dtype: Optional[DataType] = None,
                   validity: Optional[np.ndarray] = None,
                   capacity: Optional[int] = None) -> "Column":
        """Build a device column from host values (non-string)."""
        values = np.asarray(values)
        if values.dtype.kind == "O":
            import datetime as _dt
            sample = next((v for v in values if v is not None), None)
            if isinstance(sample, _dt.datetime):
                validity = np.array([v is not None for v in values]) \
                    if validity is None else validity
                filled = [sample if v is None else v for v in values]
                values = np.array(filled, dtype="datetime64[us]")
                dtype = dtype or dts.TIMESTAMP_US
            elif isinstance(sample, _dt.date):
                validity = np.array([v is not None for v in values]) \
                    if validity is None else validity
                filled = [sample if v is None else v for v in values]
                values = np.array(filled, dtype="datetime64[D]").astype(
                    np.int32)
                dtype = dtype or dts.DATE32
        if values.dtype.kind in ("U", "S", "O"):
            return cls.from_strings(values.tolist(), validity=validity,
                                    capacity=capacity)
        if validity is not None and np.asarray(validity).all():
            validity = None
        if values.dtype.kind == "M":
            values = values.astype("datetime64[us]").astype(np.int64)
            dtype = dtype or dts.TIMESTAMP_US
        dtype = dtype or dts.from_numpy_dtype(values.dtype)
        nrows = len(values)
        cap = capacity or bucket_capacity(nrows)
        buf = np.zeros(cap, dtype=dtype.storage)
        buf[:nrows] = values.astype(dtype.storage, copy=False)
        dev_validity = None
        if validity is not None:
            v = np.zeros(cap, dtype=np.bool_)
            v[:nrows] = validity
            if not v[:nrows].all():
                dev_validity = v
        return cls(dtype, buf, nrows, validity=dev_validity)

    @classmethod
    def from_strings(cls, values: Sequence[Optional[str]],
                     validity: Optional[np.ndarray] = None,
                     capacity: Optional[int] = None,
                     char_capacity: Optional[int] = None) -> "Column":
        nrows = len(values)
        valid = np.ones(nrows, dtype=np.bool_)
        if validity is not None:
            valid &= np.asarray(validity, dtype=np.bool_)
        encoded = []
        for i, s in enumerate(values):
            if s is None:
                valid[i] = False
                encoded.append(b"")
            else:
                encoded.append(str(s).encode("utf-8"))
        offsets = np.zeros(nrows + 1, dtype=np.int32)
        np.cumsum([len(b) for b in encoded], out=offsets[1:] if nrows else None)
        chars = np.frombuffer(b"".join(encoded), dtype=np.uint8)
        string_metrics.note(listed=nrows)
        return cls.from_string_buffers(offsets, chars, nrows, validity=valid,
                                       capacity=capacity,
                                       char_capacity=char_capacity)

    @classmethod
    def from_string_buffers(cls, offsets: np.ndarray, chars: np.ndarray,
                            nrows: int,
                            validity: Optional[np.ndarray] = None,
                            capacity: Optional[int] = None,
                            char_capacity: Optional[int] = None) -> "Column":
        """The one place that lays a host string column out: ``offsets``
        are int32[nrows + 1] starting at zero, ``chars`` the uint8 bytes
        they index, ``validity`` bool[nrows] or None.  Offsets are padded
        to ``capacity + 1`` with the tail repeating the total, chars with
        zeros to ``char_capacity``; validity is kept (padded with False)
        only where some row is NULL."""
        total = int(offsets[nrows])
        cap = capacity or bucket_capacity(nrows)
        ccap = char_capacity or bucket_capacity(max(total, 1))
        off_buf = np.empty(cap + 1, dtype=np.int32)
        off_buf[: nrows + 1] = offsets
        off_buf[nrows + 1:] = total
        char_buf = np.zeros(ccap, dtype=np.uint8)
        char_buf[:total] = chars
        dev_validity = None
        if validity is not None and not validity.all():
            dev_validity = np.zeros(cap, dtype=np.bool_)
            dev_validity[:nrows] = validity
        return cls(dts.STRING, char_buf, nrows,
                   validity=dev_validity, offsets=off_buf)

    @classmethod
    def from_arrays(cls, values, element: DataType,
                    validity: Optional[np.ndarray] = None,
                    capacity: Optional[int] = None,
                    elem_capacity: Optional[int] = None) -> "Column":
        """Array column from a list of (list | None): flat element buffer +
        int32 offsets, the string chars layout generalized to any
        fixed-width element type.  Null ELEMENTS inside arrays are not
        supported (the planner tags them off)."""
        nrows = len(values)
        valid = np.ones(nrows, dtype=np.bool_)
        if validity is not None:
            valid &= np.asarray(validity, dtype=np.bool_)
        rows = []
        for i, v in enumerate(values):
            if v is None:
                valid[i] = False
                rows.append([])
            elif any(e is None for e in v):
                raise ValueError("null array elements not supported")
            else:
                rows.append(list(v))
        lens = np.array([len(r) for r in rows], dtype=np.int32)
        offsets = np.zeros(nrows + 1, dtype=np.int32)
        np.cumsum(lens, out=offsets[1:] if nrows else None)
        total = int(offsets[-1]) if nrows else 0
        dictionary = None
        if element.is_string:
            # variable-width elements: store int32 dictionary codes with a
            # host-side string table (array<string> is a host-surface type)
            flat_strs = [e for r in rows for e in r]
            dictionary = sorted(set(flat_strs))
            code = {s: i for i, s in enumerate(dictionary)}
            flat = np.array([code[s] for s in flat_strs], dtype=np.int32) \
                if total else np.zeros(0, dtype=np.int32)
            storage = np.dtype(np.int32)
        else:
            flat = np.array([e for r in rows for e in r],
                            dtype=element.storage) if total else \
                np.zeros(0, dtype=element.storage)
            storage = element.storage
        cap = capacity or bucket_capacity(nrows)
        ecap = elem_capacity or bucket_capacity(max(total, 1))
        off_buf = np.zeros(cap + 1, dtype=np.int32)
        off_buf[: nrows + 1] = offsets
        off_buf[nrows + 1:] = offsets[-1] if nrows else 0
        elem_buf = np.zeros(ecap, dtype=storage)
        elem_buf[:total] = flat
        dev_validity = None
        if not valid.all():
            v = np.zeros(cap, dtype=np.bool_)
            v[:nrows] = valid
            dev_validity = v
        if element.is_string:
            from spark_rapids_tpu.ops.json_ops import ARRAY_STRING
            adt = ARRAY_STRING
        else:
            from spark_rapids_tpu.columnar.dtypes import ArrayType
            adt = ArrayType(element)
        return cls(adt, elem_buf, nrows,
                   validity=dev_validity, offsets=off_buf,
                   dictionary=dictionary)

    @classmethod
    def from_arrow(cls, arr, capacity: Optional[int] = None) -> "Column":
        import pyarrow as pa
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        if pa.types.is_dictionary(arr.type):
            arr = arr.dictionary_decode()
        dtype = dts.from_arrow_type(arr.type)
        if dtype.is_string:
            return cls._from_arrow_strings(arr, capacity)
        if dtype.is_array:
            return cls.from_arrays(arr.to_pylist(), dtype.element,
                                   capacity=capacity)
        validity = None
        if arr.null_count:
            validity = ~np.asarray(arr.is_null())
        if dtype.is_decimal:
            from spark_rapids_tpu.utils import tracing
            with tracing.span("scan.convert.decimal"):
                values = _decimal_unscaled(arr, validity)
        elif dtype.is_timestamp:
            ints = arr.cast(pa.timestamp("us")).cast(pa.int64())
            values = np.asarray(ints.fill_null(0))
        elif dtype.is_date:
            values = np.asarray(arr.cast(pa.int32()).fill_null(0))
        else:
            np_arr = arr.to_numpy(zero_copy_only=False)
            if arr.null_count:
                # to_numpy promotes ints-with-nulls to float NaN; zero the
                # null slots before casting back to the storage dtype.
                np_arr = np.where(validity, np_arr, 0)
            values = np_arr.astype(dtype.storage, copy=False)
        return cls.from_numpy(values, dtype=dtype, validity=validity,
                              capacity=capacity)

    @classmethod
    def _from_arrow_strings(cls, arr, capacity: Optional[int]) -> "Column":
        """A string / large_string array's own buffers as a host column:
        what ``from_strings(arr.to_pylist())`` lays out, with no Python
        object a row."""
        import pyarrow as pa
        nrows = len(arr)
        string_metrics.note(buffered=nrows)
        if not nrows:
            return cls.from_string_buffers(
                np.zeros(1, dtype=np.int32), np.zeros(0, dtype=np.uint8), 0,
                capacity=capacity)
        bitmap, offs_buf, data = arr.buffers()
        wide = np.dtype(np.int64 if pa.types.is_large_string(arr.type)
                        else np.int32)
        offsets = np.frombuffer(offs_buf, dtype=wide, count=nrows + 1,
                                offset=arr.offset * wide.itemsize)
        first, last = int(offsets[0]), int(offsets[-1])
        if last - first > np.iinfo(np.int32).max:
            raise ValueError(
                f"string column of {last - first} chars: a column's int32 "
                f"offsets hold at most 2^31 - 1")
        if first:
            offsets = offsets - first
        offsets = offsets.astype(np.int32, copy=False)
        chars = np.frombuffer(data, dtype=np.uint8)[first:last] \
            if last > first else np.zeros(0, dtype=np.uint8)
        validity = None
        if arr.null_count:
            start = arr.offset % 8
            bits = np.frombuffer(bitmap, dtype=np.uint8)[
                arr.offset // 8: (arr.offset + nrows + 7) // 8]
            validity = np.unpackbits(bits, bitorder="little")[
                start: start + nrows].view(np.bool_)
            # Arrow lets a NULL slot keep bytes; a column's NULL is empty
            lens = np.diff(offsets)
            if lens[~validity].any():
                chars = chars[np.repeat(validity, lens)]
                offsets = np.zeros(nrows + 1, dtype=np.int32)
                np.cumsum(np.where(validity, lens, 0), out=offsets[1:])
        return cls.from_string_buffers(offsets, chars, nrows,
                                       validity=validity, capacity=capacity)

    # ------------------------------------------------------------- host export --
    def to_numpy(self) -> np.ndarray:
        """Valid-length values as numpy; nulls hold unspecified data.
        Reads the exact host buffer when one exists (never a device
        round trip — see class docstring)."""
        if self.dtype.is_string:
            raise TypeError("use to_pylist for string columns")
        return self.host_values()[: self.nrows]

    def validity_numpy(self) -> np.ndarray:
        v = self.host_validity()
        if v is None:
            return np.ones(self.nrows, dtype=np.bool_)
        return v[: self.nrows]

    def to_pylist(self):
        valid = self.validity_numpy()
        if self.dtype.is_array:
            offs = self.host_offsets()[: self.nrows + 1]
            elems = self.host_values()
            edt = self.dtype.element
            if self.dictionary is not None:
                table = self.dictionary
                return [[table[int(v)] for v in elems[offs[i]:offs[i + 1]]]
                        if valid[i] else None for i in range(self.nrows)]
            def conv(x):
                if edt.is_boolean:
                    return bool(x)
                if edt.is_floating:
                    return float(x)
                return int(x)
            return [[conv(v) for v in elems[offs[i]:offs[i + 1]]]
                    if valid[i] else None for i in range(self.nrows)]
        if self.dtype.is_string:
            offs = self.host_offsets()[: self.nrows + 1]
            chars = self.host_values()
            blob = chars.tobytes()
            return [blob[offs[i]:offs[i + 1]].decode("utf-8")
                    if valid[i] else None for i in range(self.nrows)]
        vals = self.to_numpy()
        out = []
        for i in range(self.nrows):
            if not valid[i]:
                out.append(None)
            elif self.dtype.is_decimal:
                import decimal
                out.append(decimal.Decimal(int(vals[i])).scaleb(-self.dtype.scale))
            elif self.dtype.is_boolean:
                out.append(bool(vals[i]))
            elif self.dtype.is_floating:
                out.append(float(vals[i]))
            else:
                out.append(int(vals[i]))
        return out

    def to_arrow(self):
        import pyarrow as pa
        at = dts.to_arrow_type(self.dtype)
        if self.dtype.is_string or self.dtype.is_array:
            return pa.array(self.to_pylist(), type=at)
        vals = self.to_numpy()
        valid = self.validity_numpy()
        if self.dtype.is_timestamp:
            vals = vals.astype("datetime64[us]")
        elif self.dtype.is_date:
            vals = vals.astype("datetime64[D]")
        elif self.dtype.is_decimal:
            return pa.array(self.to_pylist(), type=at)
        mask = None if valid.all() else ~valid
        return pa.array(vals, type=at, mask=mask)

    # ------------------------------------------------------------------- misc --
    def with_nrows(self, nrows: int) -> "Column":
        # slot copy so the clone keeps BOTH the exact host buffer and
        # any already-materialized device copy (re-upload-free slicing)
        c = Column.__new__(Column)
        c.dtype = self.dtype
        c._np_data = self._np_data
        c._jax_data = self._jax_data
        c._np_validity = self._np_validity
        c._jax_validity = self._jax_validity
        c._np_offsets = self._np_offsets
        c._jax_offsets = self._jax_offsets
        c.dictionary = self.dictionary
        c._row_count = RowCount.wrap(nrows)
        return c

    def __repr__(self) -> str:
        return (f"Column({self.dtype}, nrows={self.nrows}, "
                f"capacity={self.capacity}, nulls={self.has_nulls})")
