"""Parquet scan.

Reference: GpuParquetScan.scala:65-671 — the CPU reads/prunes footers,
clips the schema to requested columns, chunks row groups by row/byte limits
(:490-540), and the device decodes.  Here: pyarrow reads footers, prunes
row groups by min/max statistics against pushed-down predicates (the
footer-surgery analog), reads only requested columns, and uploads per-chunk
to the device.
"""

from __future__ import annotations

import datetime as _dt
import functools
import glob as _glob
import os
import threading
from typing import Dict, Iterator, List, Optional, Sequence

import pyarrow as pa
import pyarrow.parquet as pq

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.dtypes import Schema
from spark_rapids_tpu.exec.base import CpuExec, ExecContext, TpuExec
from spark_rapids_tpu.io.hostio import (
    coalesce_host_batches, make_uploader, pipelined_scan,
)
from spark_rapids_tpu.exprs.base import Expression, Literal, BoundReference
from spark_rapids_tpu.exprs import predicates as pr


_EPOCH = _dt.date(1970, 1, 1)


def expand_paths(path) -> List[str]:
    if isinstance(path, (list, tuple)):
        out: List[str] = []
        for p in path:
            out.extend(expand_paths(p))
        return out
    if os.path.isdir(path):
        return sorted(
            _glob.glob(os.path.join(path, "**", "*.parquet"),
                       recursive=True))
    if any(ch in path for ch in "*?["):
        return sorted(_glob.glob(path))
    return [path]


def tail_marker(path: str) -> str:
    """Cheap content marker for the snapshot fingerprint: the 8 tail
    bytes of a parquet file (4-byte LE footer length + ``PAR1``), hex.
    An append rewrites the footer and almost always changes its length,
    so a rewrite that lands within mtime granularity at an unchanged
    byte size — invisible to ``(path, mtime_ns, size)`` — still changes
    the token and can never serve a stale cache entry.  Unreadable or
    too-short files raise OSError (the caller degrades the snapshot to
    "not fingerprintable", exactly like a failed stat)."""
    with open(path, "rb") as f:
        f.seek(0, os.SEEK_END)
        if f.tell() < 8:
            raise OSError(f"{path}: too short for a parquet footer")
        f.seek(-8, os.SEEK_END)
        return f.read(8).hex()


def _comparable(v):
    """A footer statistic in the units a ``Literal`` carries: a date as
    days since the epoch (exprs/base.py ``Literal.__init__``).  A
    timestamp stays a ``datetime``, which no literal's integer compares
    with, so timestamps never prune (their unit and zone would have to
    be settled first); every other value as it is."""
    if isinstance(v, _dt.date) and not isinstance(v, _dt.datetime):
        return (v - _EPOCH).days
    return v


def _group_stats(md) -> tuple:
    """Per row group of a footer: ``{column path: (min, max)}`` for the
    columns that carry min/max statistics."""
    groups = []
    for ridx in range(md.num_row_groups):
        rg = md.row_group(ridx)
        col_stats = {}
        for ci in range(rg.num_columns):
            col = rg.column(ci)
            st = col.statistics
            if st is not None and st.has_min_max:
                col_stats[col.path_in_schema] = (
                    _comparable(st.min), _comparable(st.max))
        groups.append(col_stats)
    return tuple(groups)


@functools.lru_cache(maxsize=512)
def _footer_stats(path: str, mtime: float, size: int) -> tuple:
    """``_group_stats`` of the file at ``path``, remembered under the
    file's identity: the scan cache's key reads it for every query, and
    a rewritten file is another identity."""
    return _group_stats(pq.read_metadata(path))


def _may_match(col_stats: dict, checks) -> bool:
    """True if a row group with these statistics may contain matching
    rows.  Conservative min/max pruning for simple `col <op> literal`
    predicates (reference: predicate pushdown through the clipped footer,
    GpuParquetScan.scala:316)."""
    for (name, op, value) in checks:
        if name not in col_stats:
            continue
        mn, mx = col_stats[name]
        try:
            if op == "eq" and (value < mn or value > mx):
                return False
            if op == "lt" and mn >= value:
                return False
            if op == "le" and mn > value:
                return False
            if op == "gt" and mx <= value:
                return False
            if op == "ge" and mx < value:
                return False
        except TypeError:
            continue
    return True


def kept_row_groups(groups: tuple, checks, rg_shard=None) -> List[int]:
    """The row groups a scan reads of a file whose footer says
    ``groups`` (``_group_stats``): those ``checks`` cannot rule out, and
    of them the ``rg_shard`` = (r, k) share (post-prune position r mod
    k).  The ONE place pruning is decided: the reader reads these, and
    the scan cache's key names them."""
    keep = [i for i, col_stats in enumerate(groups)
            if _may_match(col_stats, checks)]
    if rg_shard is not None:
        r, k = rg_shard
        keep = [g for j, g in enumerate(keep) if j % k == r]
    return keep


_SIMPLE_OPS = {
    pr.EqualTo: "eq", pr.LessThan: "lt", pr.LessThanOrEqual: "le",
    pr.GreaterThan: "gt", pr.GreaterThanOrEqual: "ge",
}


def _literal_value(e: Expression):
    """Python value of a Literal, seeing through value-preserving coercion
    Casts the binder inserts (e.g. int32 literal -> int64 column type).
    Returns None when the expression is not a safely-foldable literal —
    a value-changing cast (float->int truncation) must not drive pruning."""
    from spark_rapids_tpu.exprs.cast import Cast
    if isinstance(e, Cast):
        inner = _literal_value(e.children[0])
        if inner is None:
            return None
        if isinstance(inner, bool) or not isinstance(inner, (int, float)):
            return None
        # Fold the cast to the value the runtime comparison will actually
        # use: an int->float cast can round (16777217 -> 16777216.0f), so
        # pruning with the pre-cast int would discard groups that match at
        # runtime.  int->int only when in range (overflow wraps at runtime
        # in ways we don't model); float->int truncation: bail.
        import numpy as np
        if isinstance(inner, int) and e.to.is_integral:
            info = np.iinfo(e.to.numpy_dtype)
            return inner if info.min <= inner <= info.max else None
        if isinstance(inner, (int, float)) and e.to.is_floating:
            return float(np.dtype(e.to.numpy_dtype).type(inner))
        return None
    if isinstance(e, Literal):
        return e.value
    return None


def _collect_simple_predicates(pred: Optional[Expression]):
    """AND-tree of (bound_col <op> literal) -> [(col_name, op, value)];
    nothing for no predicate."""
    out = []

    def walk(e):
        if isinstance(e, pr.And):
            walk(e.children[0])
            walk(e.children[1])
            return
        op = _SIMPLE_OPS.get(type(e))
        if op is None:
            return
        l, r = e.children
        lv, rv = _literal_value(l), _literal_value(r)
        if isinstance(l, BoundReference) and rv is not None:
            out.append((l.col_name, op, rv))
        elif isinstance(r, BoundReference) and lv is not None:
            flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le",
                    "eq": "eq"}
            out.append((r.col_name, flip[op], lv))
    walk(pred)
    return out


class ParquetPartitionReader:
    """Per-file reader: footer prune -> column-clipped row-group reads
    (reference ParquetPartitionReader GpuParquetScan.scala:266)."""

    def __init__(self, path: str, schema: Schema,
                 columns: Optional[List[str]] = None,
                 pred: Optional[Expression] = None,
                 batch_rows: int = 1 << 19,
                 read_dictionary: Optional[List[str]] = None,
                 rg_shard=None):
        self.path = path
        self.schema = schema
        self.columns = columns or schema.names
        self.pred = pred
        self.batch_rows = batch_rows
        # encoded-plane ingest (docs/compressed.md): surface the
        # dictionary encoding parquet already stores for these columns
        # instead of pyarrow-decoding to dense strings — the scan hands
        # DictionaryArrays straight to the ingest encoder
        self.read_dictionary = read_dictionary
        # sharded scan ingest (docs/sharded_scan.md): (r, k) reads only
        # the surviving row groups whose post-prune position is r mod k,
        # so k mesh shards partition one file's row groups exactly
        self.rg_shard = rg_shard

    def read_host(self) -> Iterator[pa.RecordBatch]:
        """Eagerly reads the footer and prunes (so ``total_row_groups`` /
        ``read_row_groups`` are set on return even if the caller never
        iterates, e.g. under a Limit), then streams batches lazily."""
        f = pq.ParquetFile(self.path,
                           read_dictionary=self.read_dictionary or None)
        md = f.metadata
        checks = _collect_simple_predicates(self.pred)
        keep = kept_row_groups(_group_stats(md) if checks
                               else ({},) * md.num_row_groups,
                               checks, self.rg_shard)
        self.total_row_groups = md.num_row_groups
        # k shard clones share the planner scan node's metrics and each
        # re-reads this footer: attribute the file's total to shard 0
        # only, so the summed numRowGroupsTotal stays the file's real
        # count instead of k x it (read counts are disjoint per shard
        # and sum correctly on their own)
        if self.rg_shard is not None and self.rg_shard[0] != 0:
            self.total_row_groups = 0
        self.read_row_groups = len(keep)
        return self._iter_batches(f, keep)

    def _iter_batches(self, f, keep) -> Iterator[pa.RecordBatch]:
        if not keep:
            return
        for batch in f.iter_batches(batch_size=self.batch_rows,
                                    row_groups=keep,
                                    columns=self.columns):
            if batch.num_rows:
                yield batch


def scan_cache_key(kind: str, paths: List[str], schema: Schema,
                   pred_key, batch_rows: int, max_w) -> Optional[tuple]:
    """Cache key for a device-resident scan: file identities (path,
    mtime, size) + the scan shape.  None when any file is unstatable.
    ``pred_key`` is whatever else decides WHICH rows the scan uploads:
    a callable is given the file identities and returns it (parquet:
    the row groups pruning keeps, ``pruning_outcome``).
    The compressed-ingest switch is part of the key: the cache is
    process-wide, and a compressed-off session must never be served
    another session's encoded batches (off = byte-identical planes)."""
    try:
        ids = tuple((p, os.path.getmtime(p), os.path.getsize(p))
                    for p in paths)
        if callable(pred_key):
            pred_key = pred_key(ids)
    except OSError:
        return None
    from spark_rapids_tpu.columnar import encoding
    return (kind, ids, tuple((f.name, f.dtype.name) for f in schema),
            pred_key, batch_rows, max_w, encoding.ingest_enabled())


def pruning_outcome(pred: Optional[Expression], rg_shard):
    """What a parquet scan's pushed-down predicate contributes to the
    scan cache's key: its text with every prepared-statement parameter
    masked (``plan.fingerprint.mask_params``: slot and dtype, no value),
    and in place of the values what they DO, which is prune row groups:
    per file the row groups kept (``kept_row_groups``, the reader's own
    decision).  Two bindings of a prepared statement that keep the same
    row groups share one device-resident entry; a binding that prunes
    differently gets its own; a predicate with nothing to prune by (no
    `col <op> literal` conjunct) keeps everything under every binding.
    An inline literal stays in the text, as it does in the plan
    fingerprint: it is the query, not a binding of it.  For
    ``scan_cache_key``'s ``pred_key``."""
    from spark_rapids_tpu.plan.fingerprint import mask_params
    text = mask_params(pred).key() if pred is not None else None
    checks = _collect_simple_predicates(pred)

    def outcome(ids):
        kept = None
        if checks:
            kept = tuple(tuple(kept_row_groups(
                _footer_stats(*ident), checks, rg_shard)) for ident in ids)
        return (text, kept, rg_shard)
    return outcome


# Always-on counters of the device scan cache (the ``scan`` group of
# ``engine_stats()``, docs/observability.md): a served request leaves no
# plan to read ``scanCacheHits`` from, so the cache counts for itself.
# Bumped once per scan, never per batch.  The last three are what a
# miss cost on the host, from the scan node's own clocks
# (io/hostio.py pipelined_scan): microseconds decoding, microseconds
# dispatching uploads, host bytes uploaded; a hit moves none of them.
# ``columns_read`` / ``columns_total``: the file columns a parquet scan
# asked its reader for, and its table's before the planner pruned them
# (planner.py prune_scan_columns); bumped once a parquet scan, hit or miss.
_SCAN_LOCK = threading.Lock()
_SCAN = {"cache_lookups": 0, "cache_hits": 0, "decoded_bytes": 0,
         "decode_us": 0, "upload_us": 0, "upload_bytes": 0,
         "columns_read": 0, "columns_total": 0}
# counter -> (the node metric it sums, what divides that metric)
_MISS_COSTS = {"decode_us": ("decodeTime", 1000),
               "upload_us": ("uploadTime", 1000),
               "upload_bytes": ("uploadBytes", 1)}


def _scan_add(counter: str, amount: int = 1) -> None:
    with _SCAN_LOCK:
        _SCAN[counter] += amount


def scan_stats() -> Dict[str, int]:
    with _SCAN_LOCK:
        return dict(_SCAN)


def reset_scan_stats() -> None:
    with _SCAN_LOCK:
        for k in _SCAN:
            _SCAN[k] = 0


def cached_device_scan(ctx: ExecContext, key, gen, metrics=None,
                       metric_names: Sequence[str] = ()):
    """Serve device scan batches through the runtime scan cache
    (``spark.rapids.sql.scan.deviceCacheEnabled``).  ``gen`` is a
    zero-arg callable producing the fresh batch iterator; the named
    scan metrics are snapshotted with the entry and replayed on a hit so
    observability (row-group pruning counters etc.) survives caching.
    Counts every lookup, every hit, the device bytes a miss decoded
    and uploaded, and what the miss cost on the host (``scan_stats``)."""
    from spark_rapids_tpu.memory.spill import SpillableBatch
    cache = ctx.runtime.scan_cache
    if key is None or not ctx.conf.scan_device_cache_enabled:
        yield from gen()
        return
    hit = cache.get(key)
    _scan_add("cache_lookups")
    if hit is not None:
        _scan_add("cache_hits")
        handles, _, snap = hit
        if metrics is not None:
            for name, v in snap.items():
                metrics[name].add(v)
            metrics["scanCacheHits"].add(1)
        for h in handles:
            yield h.get(device=ctx.runtime.device)
        return
    from spark_rapids_tpu.memory.spill import PRIORITY_RECREATABLE
    handles = []
    schema = None
    watched = (*metric_names, *(m for m, _ in _MISS_COSTS.values())) \
        if metrics is not None else ()
    before = {n: metrics[n].value for n in watched}
    batches = gen()
    try:
        for b in batches:
            schema = b.schema
            # re-creatable from the file: first in line to spill
            h = SpillableBatch(b, ctx.runtime.catalog,
                               priority=PRIORITY_RECREATABLE)
            h.suppress_leak_warning = True
            handles.append(h)
            yield b
    finally:
        # a scan cut short (LIMIT) paid for what it decoded: stop its
        # decode thread first, so the clocks have stopped
        if hasattr(batches, "close"):
            batches.close()
        if metrics is not None:
            for counter, (m, per) in _MISS_COSTS.items():
                _scan_add(counter, (metrics[m].value - before[m]) // per)
    snap = {n: metrics[n].value - before[n] for n in metric_names} \
        if metrics is not None else {}
    _scan_add("decoded_bytes", sum(h.size for h in handles))
    cache.put(key, handles, schema, snap)


class _ParquetScan:
    """What the TPU and CPU parquet scans share: the files, the hive
    partition columns the schema reads, and the file columns the reader
    is asked for (``_file_schema``) out of the table's (``columns_total``,
    before the planner's column pruning: ``full_schema``)."""

    def _init_scan(self, paths, schema: Schema,
                   full_schema: Optional[Schema]) -> None:
        from spark_rapids_tpu.io import hivepart
        self.roots = list(paths) if isinstance(paths, (list, tuple)) \
            else [paths]
        self.paths = expand_paths(paths)
        part_schema, part_values = hivepart.discover(self.roots, self.paths)
        part_names = set(part_schema.names) if part_schema else set()
        self.columns_total = sum(f.name not in part_names
                                 for f in (full_schema or schema))
        self.part_schema, self.part_values = hivepart.narrow(
            part_schema, part_values, schema.names)
        self._schema = schema
        self._file_schema = Schema(
            [f for f in schema if f.name not in part_names])

    def _count_columns(self) -> None:
        _scan_add("columns_read", len(self._file_schema.fields))
        _scan_add("columns_total", self.columns_total)

    def _columns_text(self) -> str:
        return f"{len(self._file_schema.fields)}/{self.columns_total} columns"


class TpuParquetScanExec(_ParquetScan, TpuExec):
    """Parquet -> device batches (reference GpuParquetScan.scala:65).
    Hive-partitioned layouts (col=value/ dirs) contribute partition-value
    columns per file and prune files on partition predicates
    (reference ColumnarPartitionReaderWithPartitionValues.scala:32)."""

    def __init__(self, paths, schema: Schema,
                 pred: Optional[Expression] = None,
                 batch_rows: Optional[int] = None,
                 full_schema: Optional[Schema] = None):
        super().__init__()
        self._init_scan(paths, schema, full_schema)
        self.pred = pred
        self.batch_rows = batch_rows
        self.children = []
        # (r, k) row-group shard of a sharded scan ingest clone
        # (parallel/shardscan.py); None on planner-built scans
        self.rg_shard = None

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        extra = f", pushdown={self.pred.name}" if self.pred else ""
        if self.part_schema:
            extra += f", partitioned by {self.part_schema.names}"
        return (f"TpuParquetScan [{len(self.paths)} files, "
                f"{self._columns_text()}{extra}]")

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.io import hivepart
        rows = self.batch_rows or ctx.conf.reader_batch_size_rows
        max_w = ctx.conf.max_string_width
        files, fvals = hivepart.prune_files(
            self.part_schema, self.part_values, self.paths, self.pred)
        if self.part_schema:
            self.metrics["numFilesTotal"].add(len(self.paths))
            self.metrics["numFilesRead"].add(len(files))
        self._count_columns()

        dump_prefix = ctx.conf.get_raw(
            "spark.rapids.sql.parquet.debug.dumpPrefix", "") or ""
        from spark_rapids_tpu.columnar.dtypes import STRING as _STR
        read_dict = None
        if ctx.conf.compressed_enabled and ctx.conf.compressed_ingest:
            read_dict = [f.name for f in self._file_schema
                         if f.dtype == _STR] or None

        def host_gen():
            """Host-side decode stream: runs on the prefetch thread when
            ``spark.rapids.sql.io.prefetch.enabled`` (io/prefetch.py)."""
            for fi, path in enumerate(files):
                if dump_prefix:
                    # debug dump: copy each parquet file the scan opens
                    # next to the prefix (reference dumpBuffer,
                    # GpuParquetScan.scala debug path) for offline
                    # inspection of problem inputs
                    import shutil
                    dst = (f"{dump_prefix}-{fi}-"
                           f"{os.path.basename(path)}")
                    os.makedirs(os.path.dirname(dst) or ".",
                                exist_ok=True)
                    if not os.path.exists(dst):
                        shutil.copyfile(path, dst)
                reader = ParquetPartitionReader(
                    path, self._file_schema,
                    columns=self._file_schema.names,
                    pred=self.pred, batch_rows=rows,
                    read_dictionary=read_dict,
                    rg_shard=self.rg_shard)
                it = reader.read_host()  # footer pruned eagerly
                self.metrics["numRowGroupsTotal"].add(reader.total_row_groups)
                self.metrics["numRowGroupsRead"].add(reader.read_row_groups)
                for rb in coalesce_host_batches(it, rows):
                    yield fi, rb

        # the upload's span, its clock and staging admission are
        # pipelined_scan's
        upload = make_uploader(ctx, self._file_schema, self.part_schema,
                               fvals, metrics=self.metrics)

        def gen():
            return pipelined_scan(ctx, self.metrics, host_gen(), upload,
                                  "parquet-decode")

        key = scan_cache_key(
            "parquet", files, self._schema,
            pruning_outcome(self.pred, self.rg_shard), rows, max_w)
        return self._count_output(cached_device_scan(
            ctx, key, gen, metrics=self.metrics,
            metric_names=("numRowGroupsTotal", "numRowGroupsRead")))


class CpuParquetScanExec(_ParquetScan, CpuExec):
    def __init__(self, paths, schema: Schema,
                 pred: Optional[Expression] = None,
                 batch_rows: Optional[int] = None,
                 full_schema: Optional[Schema] = None):
        super().__init__()
        self._init_scan(paths, schema, full_schema)
        self.pred = pred
        self.batch_rows = batch_rows
        self.children = []

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        return f"CpuParquetScan [{len(self.paths)} files, " \
            f"{self._columns_text()}]"

    def execute_host(self, ctx: ExecContext) -> Iterator[pa.RecordBatch]:
        # _count_output: placement-calibration hook, a passthrough
        # unless cost calibration is active (plan/cost.py)
        return self._count_output(self._execute_gen(ctx))

    def _execute_gen(self, ctx: ExecContext) -> Iterator[pa.RecordBatch]:
        from spark_rapids_tpu.io import hivepart
        rows = self.batch_rows or ctx.conf.reader_batch_size_rows
        files, fvals = hivepart.prune_files(
            self.part_schema, self.part_values, self.paths, self.pred)
        self._count_columns()
        for fi, path in enumerate(files):
            reader = ParquetPartitionReader(
                path, self._file_schema, columns=self._file_schema.names,
                pred=self.pred, batch_rows=rows)
            for rb in reader.read_host():
                if self.part_schema:
                    rb = hivepart.append_partition_arrow(
                        rb, self.part_schema, fvals[fi])
                yield rb


def read_schema(paths) -> Schema:
    from spark_rapids_tpu.io import hivepart
    files = expand_paths(paths)
    if not files:
        raise FileNotFoundError(f"no parquet files at {paths!r}")
    schema = Schema.from_arrow(pq.read_schema(files[0]))
    roots = list(paths) if isinstance(paths, (list, tuple)) else [paths]
    part_schema, _ = hivepart.discover(roots, files)
    if part_schema:
        schema = Schema(
            [f for f in schema if f.name not in part_schema.names]
            + list(part_schema.fields))
    return schema
