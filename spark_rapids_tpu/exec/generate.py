"""Generate exec: explode/posexplode of literal arrays.

Reference: GpuGenerateExec.scala:33-190 — input rows repeated once per
array element, the element column appended (plus a position column for
posexplode); ``outer`` null-extends rows for empty arrays.

TPU design: one gather kernel replicates the batch (output row j reads
input row j // N), the element column is a tiny N-row device batch built
once and gathered with j % N, and the position column is the same modulo
iota — all static shapes, one XLA program per (signature, N).
"""

from __future__ import annotations

from typing import Iterator, List

import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch, host_batch_to_device,
)
from spark_rapids_tpu.columnar.column import DeviceColumn, bucket_capacity
from spark_rapids_tpu.columnar.dtypes import Field, INT32, Schema
from spark_rapids_tpu.exec.base import CpuExec, ExecContext, TpuExec
from spark_rapids_tpu.exprs.generators import Explode
from spark_rapids_tpu.utils.metrics import METRIC_TOTAL_TIME


def generate_schema(gen: Explode, child_schema: Schema,
                    names: List[str]) -> Schema:
    fields = list(child_schema)
    if gen.with_pos:
        fields.append(Field(names[0], INT32, gen.outer))
    fields.append(Field(names[-1], gen.dtype, gen.nullable))
    return Schema(fields)


def _element_values_arrow(gen: Explode) -> pa.Array:
    from spark_rapids_tpu.columnar.dtypes import to_arrow_type
    return pa.array(gen.array.values, to_arrow_type(gen.dtype))


class TpuGenerateExec(TpuExec):
    """reference GpuGenerateExec.scala:66 (doExecuteColumnar)."""

    def __init__(self, gen: Explode, names: List[str], child):
        super().__init__()
        self.gen = gen
        self.names = names
        self.children = [child]
        self._schema = generate_schema(gen, child.output_schema, names)
        self._elem_batch = None

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        k = "posexplode" if self.gen.with_pos else "explode"
        return (f"TpuGenerate [{k}{'_outer' if self.gen.outer else ''}, "
                f"{len(self.gen.array.values)} elements]")

    def _elements(self, ctx: ExecContext) -> ColumnarBatch:
        if self._elem_batch is None:
            vals = _element_values_arrow(self.gen)
            if len(vals) == 0:
                # one dummy row so gathers have a source; index -1 makes
                # every output read invalid (outer's null extension)
                vals = pa.array([None], vals.type)
            rb = pa.RecordBatch.from_arrays([vals], names=["col"])
            schema = Schema([Field("col", self.gen.dtype, True)])
            self._elem_batch = host_batch_to_device(
                rb, schema, max_string_width=ctx.conf.max_string_width,
                device=ctx.runtime.device)
        return self._elem_batch

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        gen_expr = self.gen
        n_elem = len(gen_expr.array.values)

        def gen():
            if n_elem == 0 and not gen_expr.outer:
                return  # every row explodes to nothing
            rep = max(1, n_elem)
            elem_col = self._elements(ctx).column(0)
            for batch in self.children[0].execute_columnar(ctx):
                with self.metrics.timed(METRIC_TOTAL_TIME):
                    n_out = batch.num_rows * rep
                    cap = bucket_capacity(n_out)
                    j = jnp.arange(cap)
                    out = batch.gather(j // rep, n_out)
                    if n_elem == 0:
                        eidx = jnp.full(cap, -1)  # all-null extension
                    else:
                        eidx = j % rep
                    cols = list(out.columns)
                    live = j < n_out
                    if gen_expr.with_pos:
                        cols.append(DeviceColumn(
                            INT32, eidx.astype(jnp.int32),
                            live & (eidx >= 0), n_out))
                    cols.append(elem_col.gather(eidx, n_out))
                yield ColumnarBatch(cols, n_out, self._schema)
        return self._count_output(gen())


class CpuGenerateExec(CpuExec):
    def __init__(self, gen: Explode, names: List[str], child):
        super().__init__()
        self.gen = gen
        self.names = names
        self.children = [child]
        self._schema = generate_schema(gen, child.output_schema, names)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        k = "posexplode" if self.gen.with_pos else "explode"
        return f"CpuGenerate [{k}{'_outer' if self.gen.outer else ''}]"

    def execute_host(self, ctx: ExecContext) -> Iterator[pa.RecordBatch]:
        gen = self.gen
        n_elem = len(gen.array.values)
        target = self._schema.to_arrow()
        vals = _element_values_arrow(gen)
        for rb in self.children[0].execute_host(ctx):
            n = rb.num_rows
            if n_elem == 0:
                if not gen.outer:
                    continue
                arrays = list(rb.columns)
                if gen.with_pos:
                    arrays.append(pa.nulls(n, pa.int32()))
                arrays.append(pa.nulls(n, vals.type))
                yield pa.RecordBatch.from_arrays(arrays, schema=target)
                continue
            idx = pa.array(np.repeat(np.arange(n), n_elem))
            arrays = [c.take(idx) for c in rb.columns]
            if gen.with_pos:
                arrays.append(pa.array(
                    np.tile(np.arange(n_elem, dtype=np.int32), n)))
            arrays.append(pa.concat_arrays([vals] * n) if n
                          else vals.slice(0, 0))
            yield pa.RecordBatch.from_arrays(arrays, schema=target)
