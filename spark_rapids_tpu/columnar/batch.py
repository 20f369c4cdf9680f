"""Columnar batches and host<->device conversion.

Reference: the ColumnarBatch flowing between Gpu execs (GpuExec.scala:43-60
``doExecuteColumnar(): RDD[ColumnarBatch]``), built by
``GpuColumnarBatchBuilder`` (GpuColumnVector.java:43-132) and converted
to/from host data by GpuRowToColumnarExec.scala / GpuColumnarToRowExec.scala.

Here the host format is Arrow (pyarrow) — the CPU engine operates on Arrow
RecordBatches, and ``host_batch_to_device`` / ``device_batch_to_host`` are
the R2C / C2R transitions' workhorses. Arrow string (offsets+bytes) is
converted to the device padded-matrix layout with vectorized numpy.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np
import pyarrow as pa

import jax

from spark_rapids_tpu.compile.service import engine_jit
from spark_rapids_tpu.columnar.dtypes import (
    DataType, Field, Schema, STRING, TIMESTAMP, DATE, BOOLEAN,
    device_dtype,
    from_arrow_type, to_arrow_type,
)
from spark_rapids_tpu.columnar.column import (
    DeviceColumn, LazyRows, bucket_capacity,
    rows_bound, rows_get, rows_known, rows_traced,
)


class ColumnarBatch:
    """A batch of device columns sharing one logical row count.

    ``num_rows`` may be host-resident (int) or device-resident
    (``LazyRows``): kernels consume ``rows_traced`` without a sync, and
    host code that truly needs the number pays the link round trip once
    via the ``num_rows`` property (see LazyRows in columnar/column.py)."""

    __slots__ = ("columns", "_rows", "schema", "_size")

    def __init__(self, columns: List[DeviceColumn], num_rows,
                 schema: Optional[Schema] = None):
        self.columns = columns
        self._size = None  # size_bytes(), once asked
        self._rows = num_rows if isinstance(num_rows, LazyRows) \
            else int(num_rows)
        self.schema = schema

    @property
    def num_rows(self) -> int:
        return rows_get(self._rows)

    @property
    def rows_raw(self):
        """int or LazyRows, no sync."""
        return self._rows

    @property
    def rows_known(self) -> bool:
        return rows_known(self._rows)

    @property
    def rows_bound(self) -> int:
        """Host-known upper bound on num_rows, no sync."""
        return min(rows_bound(self._rows), self.capacity)

    @property
    def rows_traced(self):
        """Traceable row-count scalar, no sync."""
        return rows_traced(self._rows)

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    @property
    def capacity(self) -> int:
        return self.columns[0].capacity if self.columns else bucket_capacity(
            self.num_rows)

    def column(self, i: int) -> DeviceColumn:
        return self.columns[i]

    def size_bytes(self) -> int:
        """Device bytes of the planes: a walk over every plane's shape,
        made once (the planes of a batch do not change) — the coalesce
        and the spill catalog each ask, for every batch of every
        query."""
        if self._size is None:
            self._size = sum(c.size_bytes() for c in self.columns)
        return self._size

    def gather(self, indices, num_rows) -> "ColumnarBatch":
        """All-column row gather as ONE compiled kernel — eager per-column
        takes cost a device round trip each, which dominates when dispatch
        latency is high (remote-attached chips).  Encoded columns
        (columnar/encoding.py) gather their CODES plane and stay
        encoded — a partition slice or join gather never touches a
        dense char matrix."""
        from spark_rapids_tpu.columnar import encoding
        flats, sig = encoding.flat_and_sig(self)
        fn = _compile_batch_gather(sig, indices.shape[0])
        outs = fn(flats, indices, self.rows_traced, rows_traced(num_rows))
        return encoding.wrap_gathered(self.columns, outs, num_rows,
                                      self.schema)

    def slice_rows(self, start: int, length: int) -> "ColumnarBatch":
        return ColumnarBatch([c.slice_rows(start, length) for c in self.columns],
                             length, self.schema)

    def select(self, indices: List[int],
               schema: Optional[Schema] = None) -> "ColumnarBatch":
        return ColumnarBatch([self.columns[i] for i in indices],
                             self.num_rows, schema)

    def __repr__(self):
        return f"ColumnarBatch(rows={self.num_rows}, cols={self.num_columns})"


from spark_rapids_tpu.utils.kernel_cache import KernelCache

_BATCH_GATHER_CACHE = KernelCache("batch.gather", 256)


def _compile_batch_gather(sig: tuple, out_len: int):
    import jax.numpy as jnp
    key = (sig, out_len)
    fn = _BATCH_GATHER_CACHE.get(key)
    if fn is not None:
        return fn

    def run(flat, indices, src_rows, out_rows):
        from spark_rapids_tpu.columnar.gatherfab import gather_planes
        pos = jnp.arange(out_len)
        ok = (indices >= 0) & (indices < src_rows) & (pos < out_rows)
        # ONE fused row-gather for every plane of every column (int32
        # lane fabric — element-granular takes run >20x slower on TPU)
        planes = [p for d, v, ch in flat for p in (d, v, ch)]
        g = gather_planes(planes, jnp.clip(indices, 0, None))
        outs = []
        for ci in range(len(flat)):
            data, valid, chars = g[3 * ci], g[3 * ci + 1], g[3 * ci + 2]
            outs.append((data, jnp.where(ok, valid, False), chars))
        return tuple(outs)

    fn = engine_jit(run, family="concat", name="gather")
    _BATCH_GATHER_CACHE[key] = fn
    return fn


def estimate_batch_size_bytes(schema: Schema, num_rows: int,
                              avg_string_len: int = 32) -> int:
    """Estimate device bytes for planning (reference GpuBatchUtils.scala:25)."""
    total = 0
    for f in schema:
        if f.dtype == STRING:
            total += num_rows * (avg_string_len + 4 + 1)
        else:
            total += num_rows * (f.dtype.byte_width + 1)
    return total


# ---------------------------------------------------------------------------
# Arrow -> device
# ---------------------------------------------------------------------------

def _arrow_string_to_matrix(arr: pa.Array, max_width: Optional[int] = None):
    """Vectorized arrow-string -> (chars (n,W) uint8, lengths int32)."""
    arr = arr.cast(pa.large_string()) if pa.types.is_string(arr.type) else arr
    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    n = len(arr)
    if n == 0:
        return np.zeros((0, 8), np.uint8), np.zeros(0, np.int32)
    buffers = arr.buffers()
    offsets = np.frombuffer(buffers[1], dtype=np.int64,
                            count=n + 1, offset=arr.offset * 8)
    databuf = np.frombuffer(buffers[2], dtype=np.uint8) if buffers[2] is not None \
        else np.zeros(0, np.uint8)
    starts = offsets[:-1]
    lengths = (offsets[1:] - starts).astype(np.int32)
    width = int(lengths.max()) if n else 1
    width = bucket_capacity(max(1, width))
    if max_width is not None and width > max_width:
        raise ValueError(
            f"string width {width} exceeds device limit {max_width} "
            "(spark.rapids.sql.maxDeviceStringWidth)")
    chars = np.zeros((n, width), dtype=np.uint8)
    col_idx = np.arange(width)[None, :]
    mask = col_idx < lengths[:, None]
    flat_idx = (starts[:, None] + col_idx)[mask]
    chars[mask] = databuf[flat_idx]
    return chars, lengths


def _arrow_fixed_to_numpy(arr: pa.Array, dtype: DataType):
    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    if pa.types.is_date32(arr.type):
        arr = arr.cast(pa.int32())
    elif pa.types.is_timestamp(arr.type):
        arr = arr.cast(pa.timestamp("us")).cast(pa.int64())
    if arr.null_count:
        import pyarrow.compute as pc
        filled = pc.fill_null(arr, 0 if dtype != BOOLEAN else False)
    else:
        filled = arr
    values = filled.to_numpy(zero_copy_only=False).astype(
        device_dtype(dtype))
    return values


def arrow_array_validity(arr: pa.Array) -> np.ndarray:
    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    if arr.null_count == 0:
        return np.ones(len(arr), dtype=np.bool_)
    return np.asarray(arr.is_valid())


def arrow_array_to_device(arr, dtype: DataType,
                          capacity: Optional[int] = None,
                          string_width: Optional[int] = None,
                          max_string_width: Optional[int] = None,
                          device=None) -> DeviceColumn:
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if pa.types.is_dictionary(arr.type):
        # a read_dictionary scan column the ingest encoder declined
        # (or compressed off mid-path): densify to the logical type
        arr = arr.cast(arr.type.value_type)
    n = len(arr)
    cap = capacity or bucket_capacity(n)
    validity = arrow_array_validity(arr)
    if dtype == STRING:
        chars, lengths = _arrow_string_to_matrix(arr, max_string_width)
        if string_width and chars.shape[1] < string_width:
            chars = np.pad(chars, ((0, 0), (0, string_width - chars.shape[1])))
        return DeviceColumn.from_numpy(STRING, chars, validity, capacity=cap,
                                       lengths=lengths, device=device)
    values = _arrow_fixed_to_numpy(arr, dtype)
    return DeviceColumn.from_numpy(dtype, values, validity, capacity=cap,
                                   device=device)


def host_batch_to_device(rb, schema: Optional[Schema] = None,
                         capacity: Optional[int] = None,
                         max_string_width: Optional[int] = None,
                         device=None, encoder=None) -> ColumnarBatch:
    """Arrow RecordBatch/Table -> device ColumnarBatch (the HostColumnarToTpu
    transition; reference HostColumnarToGpu.scala:31-130).

    ``encoder`` (columnar/encoding.py IngestEncoder, built by the scans
    when ``spark.rapids.sql.compressed.ingest`` is on) may claim string
    columns: those upload dictionary CODES + a small shared dictionary
    instead of dense char matrices — the encoded-plane ingest path
    (docs/compressed.md).  A declined or fault-degraded column falls
    through to the plain plane upload below, byte-identical to the
    encoder-less path."""
    if schema is None:
        schema = Schema.from_arrow(rb.schema)
    n = rb.num_rows
    cap = capacity or bucket_capacity(n)
    cols = []
    for i, f in enumerate(schema):
        if encoder is not None:
            enc = encoder.upload_column(rb.column(i), f.dtype, cap,
                                        max_string_width=max_string_width)
            if enc is not None:
                cols.append(enc)
                continue
        cols.append(arrow_array_to_device(
            rb.column(i), f.dtype, capacity=cap,
            max_string_width=max_string_width, device=device))
    return ColumnarBatch(cols, n, schema)


# ---------------------------------------------------------------------------
# Device -> arrow
# ---------------------------------------------------------------------------

def device_column_to_arrow(col: DeviceColumn) -> pa.Array:
    """Single-column device->arrow (one-off paths); batch downloads go
    through device_batch_to_host, which fetches EVERY plane of the batch
    in one pull — on remote-attached chips each separate pull pays
    a full round trip, which dominated D2H wall time."""
    from spark_rapids_tpu.columnar.transfer import device_pull
    data_h, valid_h, chars_h = device_pull(
        (col.data, col.validity, col.chars))
    return _column_to_arrow_host(
        col, np.asarray(data_h), np.asarray(valid_h),
        None if chars_h is None else np.asarray(chars_h))


def _column_to_arrow_host(col: DeviceColumn, data_h: np.ndarray,
                          valid_h: np.ndarray,
                          chars_h) -> pa.Array:
    n = col.num_rows
    valid = np.ascontiguousarray(valid_h[:n])
    mask = ~valid  # pyarrow wants null mask
    if col.dtype == STRING:
        chars = chars_h[:n]
        lengths = data_h[:n].astype(np.int64)
        lengths = np.clip(lengths, 0, chars.shape[1] if chars.ndim == 2 else 0)
        offsets = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(lengths, out=offsets[1:])
        width = chars.shape[1] if chars.ndim == 2 else 0
        if width:
            col_idx = np.arange(width)[None, :]
            sel = col_idx < lengths[:, None]
            databuf = chars[sel]
        else:
            databuf = np.zeros(0, np.uint8)
        arr = pa.LargeStringArray.from_buffers(
            n, pa.py_buffer(offsets.tobytes()),
            pa.py_buffer(databuf.tobytes()))
        arr = arr.cast(pa.string())
        if mask.any():
            import pyarrow.compute as pc
            arr = pc.if_else(pa.array(valid), arr, pa.nulls(n, pa.string()))
        return arr
    data = np.ascontiguousarray(data_h[:n])
    if np.dtype(col.dtype.numpy_dtype) != data.dtype and \
            col.dtype not in (DATE, TIMESTAMP, BOOLEAN):
        # device float policy: DOUBLE computes as f32 on chip; widen at
        # the host boundary so the arrow schema stays float64
        data = data.astype(col.dtype.numpy_dtype)
    if col.dtype == DATE:
        return pa.array(data, type=pa.date32(),
                        mask=mask if mask.any() else None)
    if col.dtype == TIMESTAMP:
        return pa.array(data, type=pa.timestamp("us", tz="UTC"),
                        mask=mask if mask.any() else None)
    return pa.array(data, mask=mask if mask.any() else None)


def device_batch_to_host(batch: ColumnarBatch,
                         schema: Optional[Schema] = None,
                         metrics=None) -> pa.RecordBatch:
    """Device ColumnarBatch -> Arrow RecordBatch (the TpuColumnarToRow /
    BringBackToHost side; reference GpuColumnarToRowExec.scala:35).

    All planes of all columns come back in ONE pull through
    ``columnar/transfer.py:device_pull`` (counted, fault-injectable) —
    the fixed per-pull latency would otherwise multiply by 2-3 pulls
    per column.  An ``egress.finish`` section (``egress_us``), the pull
    inside it its own."""
    from spark_rapids_tpu.utils import tracing
    with tracing.section(tracing.SPAN_EGRESS_FINISH, own="egress_us"):
        return _batch_to_host(batch, schema, metrics)


def _batch_to_host(batch: ColumnarBatch, schema: Optional[Schema],
                   metrics) -> pa.RecordBatch:
    from spark_rapids_tpu.columnar.transfer import device_pull
    schema = schema or batch.schema
    pulls = []
    for c in batch.columns:
        pulls.append(c.data)
        pulls.append(c.validity)
        if c.chars is not None:
            pulls.append(c.chars)
    host = device_pull(pulls, metrics=metrics)
    arrays = []
    i = 0
    for c in batch.columns:
        data_h = np.asarray(host[i]); i += 1
        valid_h = np.asarray(host[i]); i += 1
        chars_h = None
        if c.chars is not None:
            chars_h = np.asarray(host[i]); i += 1
        arrays.append(_column_to_arrow_host(c, data_h, valid_h, chars_h))
    if schema is not None:
        target = schema.to_arrow()
        arrays = [a.cast(target.field(i).type) for i, a in enumerate(arrays)]
        return pa.RecordBatch.from_arrays(arrays, schema=target)
    names = [f"c{i}" for i in range(len(arrays))]
    return pa.RecordBatch.from_arrays(arrays, names=names)


def arrow_table_to_batches(table: pa.Table, batch_rows: int,
                           max_string_width: Optional[int] = None,
                           device=None) -> List[ColumnarBatch]:
    schema = Schema.from_arrow(table.schema)
    out = []
    for rb in table.to_batches(max_chunksize=batch_rows):
        out.append(host_batch_to_device(rb, schema,
                                        max_string_width=max_string_width,
                                        device=device))
    return out


def batches_to_arrow_table(batches: List[ColumnarBatch],
                           schema: Optional[Schema] = None) -> pa.Table:
    if not batches:
        if schema is None:
            raise ValueError("empty batch list needs an explicit schema")
        return pa.Table.from_batches([], schema=schema.to_arrow())
    rbs = [device_batch_to_host(b, schema or b.schema) for b in batches]
    return pa.Table.from_batches(rbs)
