"""File scan execs (parquet / orc / csv) with pushdown.

Counterpart of the reference's L6 I/O layer (GpuParquetScan.scala 1,900 LoC,
GpuOrcScan.scala, GpuBatchScanExec.scala, GpuFileSourceScanExec.scala): the
host side parses footers, prunes row groups by predicate, discovers hive
partition values, and assembles host buffers (here: pyarrow, the parquet-mr
analog); the device side receives columnar uploads.  The three multi-file
strategies live in ``multifile.py``.

Predicate pushdown: supported filter subtrees are translated to pyarrow
dataset expressions (``to_arrow_filter``) — this subsumes the reference's
row-group statistics filtering AND applies exact filtering host-side; the
engine's own TpuFilterExec still runs above for semantics parity.
"""

from __future__ import annotations

from typing import Iterator, List, Optional

import numpy as np

from spark_rapids_tpu.columnar import dtypes as dts
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.config import rapids_conf as rc
from spark_rapids_tpu.exec.base import NUM_INPUT_BATCHES, Schema, TpuExec
from spark_rapids_tpu.ops import predicates as P
from spark_rapids_tpu.ops import stringops as S
from spark_rapids_tpu.ops.expressions import (
    Alias, BoundReference, Expression, Literal, UnresolvedColumn)
from spark_rapids_tpu.plan.logical import FileRelation
from spark_rapids_tpu.utils import tracing

_END = object()


def _decoded(it) -> Iterator:
    """Pull a pyarrow iterator with each pull under a ``scan.decode``
    span: the read and decode happen inside ``next``."""
    it = iter(it)
    while True:
        with tracing.span("scan.decode"):
            item = next(it, _END)
        if item is _END:
            return
        yield item


def _dataset(paths, file_format):
    import pyarrow.dataset as ds
    fmt = file_format
    if file_format == "csv":
        fmt = ds.CsvFileFormat()
    # a single path may be a directory (hive-partitioned dataset root);
    # pyarrow only accepts directories as a bare string
    src = paths[0] if len(paths) == 1 else paths
    return ds.dataset(src, format=fmt, partitioning="hive")


def infer_file_schema(paths: List[str], file_format: str) -> Schema:
    dataset = _dataset(paths, file_format)
    return [(f.name, dts.from_arrow_type(f.type)) for f in dataset.schema]


def scan_input_meta(paths: List[str]) -> List[tuple]:
    """Sorted ``(path, size_bytes, mtime_ns)`` triples for a scan's
    input file set — the identity of what a FileRelation will actually
    read, without opening a single footer.  Folded into
    stage-checkpoint lineage keys (robustness/checkpoint.py) so
    appending a file — or mutating one: new size, or a SAME-SIZE
    in-place rewrite, which only the mtime catches — invalidates
    exactly the scan-adjacent subtrees, and used by the
    incremental-ingest runner to detect out-of-band input mutation.
    (A touch without a content change forces a spurious recompute;
    degradation is always allowed, wrong bytes never are.)
    Unstattable paths fingerprint as (-1, -1) — a vanished file still
    changes the key."""
    import os

    def stat(p):
        try:
            st = os.stat(p)
            return (p, st.st_size, st.st_mtime_ns)
        except OSError:
            return (p, -1, -1)

    out = []
    for p in paths:
        if os.path.isdir(p):
            # hive-partitioned dataset root: the file set IS the input
            for root, _dirs, names in sorted(os.walk(p)):
                out.extend(stat(os.path.join(root, name))
                           for name in sorted(names))
            continue
        out.append(stat(p))
    return sorted(out)


def input_signature(meta: List[tuple]) -> str:
    """Canonical string form of a ``scan_input_meta`` result — THE one
    encoding of input identity, shared by the stage-lineage keys
    (checkpoint.input_fingerprint) and the incremental runner's
    state-staleness check so their invalidation rules can never
    silently diverge."""
    return ";".join(f"{p}={s}@{m}" for p, s, m in meta)


def to_arrow_filter(expr: Expression):
    """Translate a supported predicate subtree to a pyarrow expression;
    returns None when any part is untranslatable (the caller keeps the full
    engine-side filter either way)."""
    import pyarrow.dataset as ds
    import pyarrow.compute as pc

    def field(e):
        if isinstance(e, BoundReference):
            return ds.field(e.name)
        if isinstance(e, UnresolvedColumn):
            return ds.field(e.col_name)
        return None

    def lit(e):
        if isinstance(e, Literal) and not (
                e.dtype.is_string and e.value is None):
            return e.value
        return None

    def rec(e):
        if isinstance(e, P.And):
            l, r = rec(e.left), rec(e.right)
            return l & r if l is not None and r is not None else None
        if isinstance(e, P.Or):
            l, r = rec(e.left), rec(e.right)
            return (l | r) if l is not None and r is not None else None
        ops = {P.EqualTo: "__eq__", P.LessThan: "__lt__",
               P.LessThanOrEqual: "__le__", P.GreaterThan: "__gt__",
               P.GreaterThanOrEqual: "__ge__"}
        for cls, method in ops.items():
            if isinstance(e, cls):
                f, v = field(e.left), lit(e.right)
                if f is not None and v is not None:
                    return getattr(f, method)(v)
                f, v = field(e.right), lit(e.left)
                if f is not None and v is not None:
                    flipped = {"__lt__": "__gt__", "__le__": "__ge__",
                               "__gt__": "__lt__", "__ge__": "__le__",
                               "__eq__": "__eq__"}[method]
                    return getattr(f, flipped)(v)
                return None
        if isinstance(e, P.IsNull):
            f = field(e.child)
            return f.is_null() if f is not None else None
        if isinstance(e, P.IsNotNull):
            f = field(e.child)
            return f.is_valid() if f is not None else None
        if isinstance(e, P.In):
            f = field(e.children[0])
            vals = [lit(o) for o in e.children[1:]]
            if f is not None and all(v is not None for v in vals):
                return f.isin(vals)
            return None
        return None

    return rec(expr)


META_COLUMN_NAMES = frozenset({
    "__input_file_name", "_metadata.file_path", "_metadata.file_name",
    "_metadata.file_size", "_metadata.file_modification_time"})


class TpuFileScanExec(TpuExec):
    # each pull decodes + uploads a fresh batch; nothing is retained,
    # so downstream stages may donate these buffers
    ephemeral_output = True

    def __init__(self, paths: List[str], file_format: str, schema: Schema,
                 batch_rows: int = 1 << 20,
                 columns: Optional[List[str]] = None,
                 arrow_filter=None, reader_type: str = "AUTO",
                 num_threads: int = 8, max_files_parallel: int = 4,
                 file_meta=()):
        super().__init__()
        self.paths = paths
        self.file_format = file_format
        self._schema = list(schema)
        # per-file metadata columns requested (input_file_name /
        # _metadata struct); these never read from the files themselves
        self.file_meta = set(file_meta)
        # columns actually read; the rest are emitted as null placeholders
        # (pruning preserves the schema so bound ordinals stay valid)
        self.columns = [n for n, _ in schema
                        if (columns is None or n in columns)
                        and n not in META_COLUMN_NAMES]
        self.batch_rows = batch_rows
        self.arrow_filter = arrow_filter
        self.reader_type = reader_type
        self.num_threads = num_threads
        self.max_files_parallel = max_files_parallel
        self._register_metric(NUM_INPUT_BATCHES)

    @property
    def schema(self) -> Schema:
        return self._schema

    def describe(self):
        extra = ", pushdown" if self.arrow_filter is not None else ""
        return (f"TpuFileScanExec[{self.file_format}, {len(self.paths)} "
                f"files, {self.reader_type}{extra}]")

    def _finish_batch(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Re-add pruned columns as all-null placeholders so the output
        matches the relation schema position-for-position."""
        if len(batch.names) == len(self._schema):
            return batch.select([n for n, _ in self._schema]) \
                if batch.names != [n for n, _ in self._schema] else batch
        import jax.numpy as jnp
        from spark_rapids_tpu.columnar.column import Column, string_metrics
        cols = {}
        cap = batch.capacity
        n = batch.nrows
        no_offsets = np.zeros(n + 1, dtype=np.int32)
        no_chars = np.zeros(0, dtype=np.uint8)
        all_null = np.zeros(n, dtype=np.bool_)
        for name, dt in self._schema:
            if name in batch.columns:
                cols[name] = batch.columns[name]
            elif dt.is_string:
                cols[name] = Column.from_string_buffers(
                    no_offsets, no_chars, n, validity=all_null, capacity=cap)
                string_metrics.note(placeholder=n)
            else:
                cols[name] = Column(
                    dt, jnp.zeros(cap, dtype=dt.storage), n,
                    validity=jnp.zeros(cap, dtype=jnp.bool_))
        return ColumnarBatch(cols, n)

    def _attach_meta(self, batch: ColumnarBatch, path: str
                     ) -> ColumnarBatch:
        import os
        from spark_rapids_tpu.columnar.column import Column
        cols = dict(batch.columns)
        n, cap = batch.nrows, batch.capacity
        if "input_file" in self.file_meta:
            cols["__input_file_name"] = Column.from_strings(
                [path] * n, capacity=cap)
        if "metadata" in self.file_meta:
            import jax.numpy as jnp
            st = os.stat(path)
            cols["_metadata.file_path"] = Column.from_strings(
                [os.path.abspath(path)] * n, capacity=cap)
            cols["_metadata.file_name"] = Column.from_strings(
                [os.path.basename(path)] * n, capacity=cap)
            cols["_metadata.file_size"] = Column(
                dts.INT64, jnp.full(cap, st.st_size, dtype=jnp.int64), n)
            cols["_metadata.file_modification_time"] = Column(
                dts.TIMESTAMP_US,
                jnp.full(cap, int(st.st_mtime * 1e6), dtype=jnp.int64), n)
        return ColumnarBatch(cols, n)

    def _per_file_scan(self) -> Iterator[ColumnarBatch]:
        """Metadata columns need per-file batch attribution: each
        dataset fragment reads and chunks independently (fragment reads
        keep hive partition columns), its constant meta columns ride
        every chunk."""
        with tracing.span("scan.decode"):
            dataset = _dataset(self.paths, self.file_format)
            fragments = dataset.get_fragments(filter=self.arrow_filter)
        for frag in _decoded(fragments):
            with tracing.span("scan.decode"):
                table = frag.to_table(schema=dataset.schema,
                                      columns=self.columns,
                                      filter=self.arrow_filter)
            for off in range(0, table.num_rows, self.batch_rows):
                chunk = table.slice(off, self.batch_rows)
                if not chunk.num_rows:
                    continue
                self.metrics[NUM_INPUT_BATCHES] += 1
                with tracing.span("scan.convert"):
                    batch = self._finish_batch(self._attach_meta(
                        ColumnarBatch.from_arrow(chunk), frag.path))
                yield batch

    def do_execute(self) -> Iterator[ColumnarBatch]:
        # "io.read" fires once per produced batch, so chaos tests can
        # kill a scan mid-stream; recovery is query-level (the
        # QueryRetryDriver re-drives the whole plan — scans re-read).
        # Each pull runs under an "io.reader" watchdog section: a
        # stalled decode (slow object store, wedged reader pool
        # thread) overruns its deadline and the monitor converts the
        # hang into a retryable TimeoutFault at the next checkpoint.
        # Inside the section's span the pull splits where the work
        # happens: ``scan.decode`` (pyarrow's read), ``scan.convert``
        # (arrow -> host columns) and ``upload.h2d`` (Column._upload);
        # ``io.reader``'s exclusive time is what is left
        from spark_rapids_tpu.robustness import watchdog
        from spark_rapids_tpu.robustness.inject import fire
        it = self._scan_batches()
        while True:
            with watchdog.section("io.reader"):
                batch = next(it, None)
                if batch is not None:
                    fire("io.read")
            if batch is None:
                return
            yield batch

    def _scan_batches(self) -> Iterator[ColumnarBatch]:
        if not self.paths:
            # bucket pruning eliminated every file
            return
        if self.file_meta:
            yield from self._per_file_scan()
            return
        if self.file_format == "csv" or len(self.paths) == 1:
            yield from self._simple_scan()
            return
        from spark_rapids_tpu.io.multifile import iter_file_tables
        for table in _decoded(iter_file_tables(
                self.paths, self.file_format, self.columns,
                self.arrow_filter, self.reader_type, self.batch_rows,
                self.num_threads, self.max_files_parallel)):
            self.metrics[NUM_INPUT_BATCHES] += 1
            for off in range(0, table.num_rows, self.batch_rows):
                chunk = table.slice(off, self.batch_rows)
                if chunk.num_rows:
                    with tracing.span("scan.convert"):
                        batch = self._finish_batch(
                            ColumnarBatch.from_arrow(chunk))
                    yield batch

    def _simple_scan(self) -> Iterator[ColumnarBatch]:
        import pyarrow as pa
        kwargs = {"columns": self.columns, "batch_size": self.batch_rows}
        if self.arrow_filter is not None:
            kwargs["filter"] = self.arrow_filter
        with tracing.span("scan.decode"):
            dataset = _dataset(self.paths, self.file_format)
            record_batches = dataset.to_batches(**kwargs)
        for record_batch in _decoded(record_batches):
            if record_batch.num_rows == 0:
                continue
            self.metrics[NUM_INPUT_BATCHES] += 1
            with tracing.span("scan.convert"):
                batch = self._finish_batch(ColumnarBatch.from_arrow(
                    pa.Table.from_batches([record_batch])))
            yield batch


def _bucket_pruned_paths(node: FileRelation) -> List[str]:
    """Bucket pruning: an equality filter on the bucket column narrows
    the scan to that bucket's file (GpuFileSourceScanExec bucket-pruning
    analog, spec from the _bucket_spec.json sidecar)."""
    from spark_rapids_tpu.io import bucketing as B
    spec = node.bucket_spec
    if not spec:
        return node.paths
    col = spec["column"]

    def name_of(e):
        if isinstance(e, BoundReference):
            return e.name
        if isinstance(e, UnresolvedColumn):
            return e.col_name
        return None

    for f in node.pushed_filters:
        if not isinstance(f, P.EqualTo):
            continue
        for a, b in ((f.left, f.right), (f.right, f.left)):
            if name_of(a) == col and isinstance(b, Literal) \
                    and b.value is not None:
                pruned, _ = B.prune_paths(node.paths, spec,
                                          node.file_format, b.value)
                return pruned
    return node.paths


def make_file_scan_exec(node: FileRelation, conf) -> TpuFileScanExec:
    arrow_filter = None
    for f in node.pushed_filters:
        af = to_arrow_filter(f)
        if af is not None:
            arrow_filter = af if arrow_filter is None else \
                (arrow_filter & af)
    fmt = node.file_format
    return TpuFileScanExec(
        _bucket_pruned_paths(node), node.file_format, node.schema,
        columns=sorted(node.required_columns)
        if getattr(node, "required_columns", None) else None,
        arrow_filter=arrow_filter,
        file_meta=node.file_meta,
        batch_rows=conf["spark.rapids.sql.reader.batchSizeRows"],
        reader_type=conf[
            f"spark.rapids.sql.format.{fmt}.reader.type"],
        num_threads=conf[
            f"spark.rapids.sql.format.{fmt}.multiThreadedRead."
            "numThreads"],
        max_files_parallel=conf[
            f"spark.rapids.sql.format.{fmt}.multiThreadedRead."
            "maxNumFilesParallel"])
