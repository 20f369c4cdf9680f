"""Basic physical operators: project / filter / union / limit / local scan
/ range, plus the host<->device transition execs.

Reference: basicPhysicalOperators.scala:65 (GpuProjectExec), :96-126
(GpuFilter + GpuFilterExec), :179 (GpuUnionExec), limit.scala:40-105
(GpuBaseLimitExec), GpuRowToColumnarExec.scala / GpuColumnarToRowExec.scala
(transitions), GpuRangeExec (basicPhysicalOperators.scala:~240).

TPU filter design: XLA needs static shapes, so one fused jitted kernel
computes the keep-mask, its population count, the padded compaction index
vector via ``jnp.nonzero(size=capacity)``, AND the compaction gather of
every column — the output keeps the input capacity (rows beyond the count
are validity-masked padding), so the host only syncs the count scalar and
the whole filter costs a single kernel dispatch.
"""

from __future__ import annotations

from typing import Iterator, List, Optional

import pyarrow as pa

import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch, host_batch_to_device, device_batch_to_host,
)
from spark_rapids_tpu.columnar.column import DeviceColumn, bucket_capacity
from spark_rapids_tpu.columnar.dtypes import Field, Schema, INT64
from spark_rapids_tpu.exec.base import CpuExec, ExecContext, TpuExec
from spark_rapids_tpu.exprs.base import Expression
from spark_rapids_tpu.utils.metrics import METRIC_TOTAL_TIME


def output_schema_of(exprs: List[Expression]) -> Schema:
    return Schema([Field(e.name, e.dtype, e.nullable) for e in exprs])


class TpuProjectExec(TpuExec):
    """reference GpuProjectExec basicPhysicalOperators.scala:65."""

    def __init__(self, exprs: List[Expression], child):
        super().__init__()
        self.exprs = list(exprs)
        self.children = [child]
        self._schema = output_schema_of(self.exprs)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        return "TpuProject [" + ", ".join(e.name for e in self.exprs) + "]"

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.exec.stage import run_project

        def gen():
            for pid, batch in enumerate(
                    self.children[0].execute_columnar(ctx)):
                with self.metrics.timed(METRIC_TOTAL_TIME):
                    cols = run_project(self.exprs, batch,
                                       partition_id=pid,
                                       metrics=self.metrics)
                    yield ColumnarBatch(cols, batch.rows_raw, self._schema)
        return self._count_output(gen())


# --------------------------------------------------------------------------
# Filter
# --------------------------------------------------------------------------

def filter_batch(pred: Expression, batch: ColumnarBatch,
                 metrics=None) -> ColumnarBatch:
    """Fused static-shape filter (reference GpuFilter
    basicPhysicalOperators.scala:96 uses cuDF Table.filter): keep-mask,
    population count, padded compaction index vector, and the compaction
    gather of every column are ONE kernel launch, routed through the
    shared stage compiler (exec/stage.py) as a single-step stage.  The
    output row count stays device-resident (LazyRows) — no host sync
    here."""
    from spark_rapids_tpu.exec.stage import run_filter
    return run_filter(pred, batch, metrics=metrics)


class TpuFilterExec(TpuExec):
    """reference GpuFilterExec basicPhysicalOperators.scala:126."""

    def __init__(self, pred: Expression, child):
        super().__init__()
        self.pred = pred
        self.children = [child]

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def describe(self) -> str:
        return f"TpuFilter [{self.pred.name}]"

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        def gen():
            for batch in self.children[0].execute_columnar(ctx):
                with self.metrics.timed(METRIC_TOTAL_TIME):
                    out = filter_batch(self.pred, batch,
                                       metrics=self.metrics)
                out.schema = batch.schema
                yield out
        return self._count_output(gen())


class TpuUnionExec(TpuExec):
    """reference GpuUnionExec basicPhysicalOperators.scala:179 — streams
    children back to back (no concat; coalesce handles batch sizing)."""

    def __init__(self, children):
        super().__init__()
        self.children = list(children)

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        def gen():
            for child in self.children:
                yield from child.execute_columnar(ctx)
        return self._count_output(gen())


class TpuLocalLimitExec(TpuExec):
    """reference GpuBaseLimitExec limit.scala:40 — slices batches until the
    limit is reached."""

    def __init__(self, limit: int, child):
        super().__init__()
        self.limit = int(limit)
        self.children = [child]

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def describe(self) -> str:
        return f"TpuLocalLimit [{self.limit}]"

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        def gen():
            remaining = self.limit
            for batch in self.children[0].execute_columnar(ctx):
                if remaining <= 0:
                    break
                if batch.num_rows <= remaining:
                    remaining -= batch.num_rows
                    yield batch
                else:
                    yield batch.slice_rows(0, remaining)
                    remaining = 0
        return self._count_output(gen())


class TpuLocalScanExec(TpuExec):
    """Scan over an in-memory arrow table (the LocalTableScan analog; used
    by create_dataframe and tests)."""

    def __init__(self, table: pa.Table, batch_rows: int = 1 << 20):
        super().__init__()
        self.table = table
        self.batch_rows = batch_rows
        self.children = []
        self._schema = Schema.from_arrow(table.schema)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        return f"TpuLocalScan [rows={self.table.num_rows}]"

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        def gen():
            max_w = ctx.conf.max_string_width
            for rb in self.table.to_batches(max_chunksize=self.batch_rows):
                if rb.num_rows == 0:
                    continue
                yield host_batch_to_device(rb, self._schema,
                                           max_string_width=max_w,
                                           device=ctx.runtime.device)
        return self._count_output(gen())


class TpuRangeExec(TpuExec):
    """reference GpuRangeExec — generates [start, end) step on device."""

    def __init__(self, start: int, end: int, step: int = 1,
                 batch_rows: int = 1 << 20, name: str = "id"):
        super().__init__()
        self.start, self.end, self.step = int(start), int(end), int(step)
        self.batch_rows = batch_rows
        self.children = []
        self._schema = Schema([Field(name, INT64, nullable=False)])

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        return f"TpuRange [{self.start}, {self.end}, {self.step}]"

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        def gen():
            total = max(0, -(-(self.end - self.start) // self.step))
            pos = 0
            while pos < total:
                n = min(self.batch_rows, total - pos)
                cap = bucket_capacity(n)
                base = self.start + pos * self.step
                data = base + jnp.arange(cap, dtype=jnp.int64) * self.step
                valid = jnp.arange(cap) < n
                col = DeviceColumn(INT64, data, valid, n)
                yield ColumnarBatch([col], n, self._schema)
                pos += n
        return self._count_output(gen())


# --------------------------------------------------------------------------
# Transitions (reference GpuTransitionOverrides inserts these;
# HostColumnarToGpu.scala:222, GpuColumnarToRowExec.scala:35)
# --------------------------------------------------------------------------

class HostToDeviceExec(TpuExec):
    """CPU child -> device batches (R2C / HostColumnarToGpu analog).
    Acquires the task semaphore before touching the device.

    Runs the same overlap pipeline as the file scans
    (docs/io_overlap.md): the CPU child's batch production is
    background-prefetched (bounded, staging-admitted) and uploads are
    double-buffered, so a CPU-fallback stage below this transition
    overlaps with device compute above it."""

    def __init__(self, child: CpuExec):
        super().__init__()
        self.children = [child]

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def describe(self) -> str:
        return "HostToDevice"

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        def gen():
            from spark_rapids_tpu.io.hostio import (
                make_uploader, pipelined_scan,
            )

            def host_gen():
                for rb in self.children[0].execute_host(ctx):
                    if rb.num_rows == 0:
                        continue
                    yield 0, rb

            upload = make_uploader(ctx, self.output_schema,
                                   metrics=self.metrics)
            yield from pipelined_scan(ctx, self.metrics, host_gen(),
                                      upload, "host-to-device")
        return self._count_output(gen())


class DeviceToHostExec(CpuExec):
    """Device child -> host record batches (C2R / GpuBringBackToHost
    analog; releases device pressure as soon as the copy lands)."""

    def __init__(self, child: TpuExec):
        super().__init__()
        self.children = [child]

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def describe(self) -> str:
        return "DeviceToHost"

    def execute_host(self, ctx: ExecContext) -> Iterator[pa.RecordBatch]:
        from spark_rapids_tpu.utils import tracing
        it = self._egress(ctx)
        # under the trace switch the pack programs launched here are
        # this node's (deviceTime / deviceDispatches), as an operator's
        # are in TpuExec._count_output
        return tracing.running(self, it) if tracing.is_enabled() else it

    def _egress(self, ctx: ExecContext) -> Iterator[pa.RecordBatch]:
        """Result egress runs through the pipelined download loop
        (columnar/transfer.py:pipelined_d2h, docs/d2h_egress.md): group
        k+1's pack kernel and device->host copy are dispatched —
        asynchronously, on THIS thread — before group k's blocking pull,
        so k+1's bytes cross the link while the consumer (collect /
        writer encode) works on k.  With egress disabled the loop
        degenerates to the serial pull-then-yield path byte-for-byte."""
        from spark_rapids_tpu.columnar.transfer import (
            pack_dispatch, pack_finish, pipelined_d2h, start_host_copies,
        )
        schema = self.output_schema
        if not ctx.conf.transfer_pack_enabled:
            def disp(b):
                start_host_copies([(c.data, c.validity, c.chars)
                                   for c in b.columns])
                return b
            yield from pipelined_d2h(
                self.children[0].execute_columnar(ctx), disp,
                lambda b: device_batch_to_host(b, schema,
                                               metrics=self.metrics),
                ctx, metrics=self.metrics,
                nbytes=lambda b: b.size_bytes())
            return

        # Pack-and-pull: group result batches and cross the link in as
        # few round trips as possible (columnar/transfer.py).  Groups cap
        # at ~256MB of bound bytes so enormous results still stream.
        thresh = ctx.conf.transfer_stats_threshold

        def groups():
            group: List[ColumnarBatch] = []
            group_bytes = 0
            limit = 256 * 1024 * 1024
            for batch in self.children[0].execute_columnar(ctx):
                group.append(batch)
                group_bytes += batch.size_bytes()
                if group_bytes >= limit:
                    yield group
                    group, group_bytes = [], 0
            if group:
                yield group

        yield from pipelined_d2h(
            groups(),
            lambda g: pack_dispatch(g, schema, thresh,
                                    metrics=self.metrics),
            lambda p: pack_finish(p, metrics=self.metrics),
            ctx, metrics=self.metrics,
            nbytes=lambda p: p.wire_bytes())
