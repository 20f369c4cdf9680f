"""Format-agnostic host-side reader helpers shared by the file scans."""

from __future__ import annotations

import contextlib
from typing import Callable, Iterator, List

import pyarrow as pa


def make_uploader(ctx, file_schema, part_schema=None, part_values=None,
                  metrics=None) -> Callable:
    """Build the one-item host->device conversion shared by every scan
    and the HostToDevice transition: upload the record batch at the
    session's max string width, append hive partition columns when the
    layout has them.  The span and the clock around it, and staging
    admission, deliberately happen OUTSIDE this closure (pipelined_scan):
    on the prefetch path the bytes are already admitted by the queue
    grant, and re-admitting here could exceed the cap with neither side
    able to release."""
    from spark_rapids_tpu.columnar import encoding
    max_w = ctx.conf.max_string_width
    # encoded-plane ingest (docs/compressed.md): the 45 MB/s link
    # carries dictionary codes, not values; gated per session, shared
    # by every format scan and the HostToDevice transition
    encoder = None
    if ctx.conf.compressed_enabled and ctx.conf.compressed_ingest:
        encoder = encoding.IngestEncoder(
            device=ctx.runtime.device, metrics=metrics,
            max_dict_fraction=ctx.conf.compressed_max_dict_fraction)

    def upload(item):
        from spark_rapids_tpu.columnar.batch import host_batch_to_device
        from spark_rapids_tpu.io import hivepart
        fi, rb = item
        b = host_batch_to_device(rb, file_schema,
                                 max_string_width=max_w,
                                 device=ctx.runtime.device,
                                 encoder=encoder)
        if part_schema:
            b = hivepart.append_partition_columns(
                b, part_schema, part_values[fi])
        return b
    return upload


def _timed_decode(host_batches: Iterator, metric) -> Iterator:
    """``host_batches`` with each item's production under the
    ``scan.decode`` span and its nanoseconds in ``metric``, on whichever
    thread drives the iterator (the prefetch thread where there is
    one); closing it closes ``host_batches`` on that thread too."""
    from spark_rapids_tpu.utils import tracing
    it = iter(host_batches)
    done = object()
    try:
        while True:
            with tracing.trace_range(tracing.SPAN_SCAN_DECODE, metric):
                item = next(it, done)
            if item is done:
                return
            yield item
    finally:
        close = getattr(it, "close", None)
        if close is not None:
            close()


def pipelined_scan(ctx, metrics, host_batches: Iterator,
                   upload: Callable, name: str):
    """The shared scan tail: background-prefetch the host decode stream
    (bounded, staging-admitted — io/prefetch.py) and double-buffer the
    uploads (columnar/transfer.py:pipelined_h2d) so decode, H2D copy,
    and consumer compute overlap.  ``host_batches`` yields
    ``(file_index, RecordBatch)``; ``upload`` turns one such item into a
    device batch.  With ``spark.rapids.sql.io.prefetch.enabled=false``
    both layers collapse to the serial decode->upload->yield loop.

    Staging admission lives here, once, in path-appropriate form: on
    the prefetch path each item's bytes are already admitted by the
    queue grant (held until the consumer pulls the NEXT item, i.e.
    across this upload), so the upload runs grant-covered; on the
    serial path the upload takes the classic ``staging.limit`` scope
    (the pinned-pool admission role, GpuDeviceManager.scala:200-206).

    The two halves are timed here, once for every caller: a host batch's
    decode under the ``scan.decode`` span into ``decodeTime``, its upload
    dispatch (the analog of the reference's buffer-copy NVTX span,
    GpuParquetScan.scala:317; not the consumer's time) under
    ``scan.upload`` into ``uploadTime``, its host bytes into
    ``uploadBytes``.  ``cached_device_scan`` sums the three into the
    ``scan`` group of ``engine_stats()``, once a scan that missed."""
    from spark_rapids_tpu.columnar.transfer import pipelined_h2d
    from spark_rapids_tpu.io.prefetch import maybe_prefetch
    from spark_rapids_tpu.utils import tracing
    host_batches = _timed_decode(host_batches, metrics["decodeTime"])
    src = maybe_prefetch(host_batches, ctx, metrics,
                         nbytes=lambda t: t[1].nbytes, name=name)
    if src is host_batches:  # serial path: admit per upload
        admit = ctx.runtime.catalog.staging.limit
    else:  # the queue's grant covers the upload
        def admit(nbytes):
            return contextlib.nullcontext()

    def do_upload(item):
        nbytes = item[1].nbytes
        with admit(nbytes), tracing.trace_range(
                tracing.SPAN_SCAN_UPLOAD, metrics["uploadTime"]):
            b = upload(item)
        metrics["uploadBytes"].add(nbytes)
        return b
    try:
        yield from pipelined_h2d(
            src, do_upload, ctx.runtime, metrics=metrics,
            enabled=ctx.conf.io_prefetch_enabled)
    finally:
        if hasattr(src, "close"):
            src.close()


def coalesce_host_batches(it: Iterator[pa.RecordBatch],
                          target_rows: int) -> Iterator[pa.RecordBatch]:
    """Combine reader record batches host-side up to ``target_rows``
    before upload: pyarrow yields per-row-group batches, and each upload
    plus its downstream kernel launches costs device round trips, so
    fewer/larger device batches win whenever dispatch latency matters
    (reference: the multi-threaded reader coalesces buffers pre-transfer,
    GpuParquetScan.scala:490-540).  The target is a cap, not a goal: a
    batch that would cross it flushes the buffer first."""
    buf: List[pa.RecordBatch] = []
    n = 0
    for rb in it:
        if buf and n + rb.num_rows > target_rows:
            yield _combine_host(buf)
            buf, n = [], 0
        buf.append(rb)
        n += rb.num_rows
        if n >= target_rows:
            yield _combine_host(buf)
            buf, n = [], 0
    if buf:
        yield _combine_host(buf)


def _combine_host(rbs: List[pa.RecordBatch]) -> pa.RecordBatch:
    if len(rbs) == 1:
        return rbs[0]
    t = pa.Table.from_batches(rbs).combine_chunks()
    batches = t.to_batches()
    return batches[0] if batches else rbs[0]
