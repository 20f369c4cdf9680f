"""Expand exec: one output batch per (input batch, projection list).

Reference: GpuExpandExec.scala:66-160 — each input batch is projected once
per grouping-set projection; rows replicate with masked key columns and a
grouping id.  TPU: every projection compiles through the shared fused
projection kernel (exprs/base), so an N-set expand is N cached XLA
programs over the same resident batch — no data movement between them.
"""

from __future__ import annotations

from typing import Iterator, List

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.dtypes import Field, Schema
from spark_rapids_tpu.exec.base import CpuExec, ExecContext, TpuExec
from spark_rapids_tpu.exprs.base import evaluate_projection
from spark_rapids_tpu.exprs.base import Expression
from spark_rapids_tpu.utils.metrics import METRIC_TOTAL_TIME

import pyarrow as pa


def expand_schema(projections: List[List[Expression]],
                   names: List[str]) -> Schema:
    fields = []
    for i, name in enumerate(names):
        dtype = projections[0][i].dtype
        nullable = any(p[i].nullable for p in projections)
        fields.append(Field(name, dtype, nullable))
    return Schema(fields)


class TpuExpandExec(TpuExec):
    """reference GpuExpandExec.scala:66."""

    def __init__(self, projections: List[List[Expression]],
                 names: List[str], child):
        super().__init__()
        self.projections = projections
        self.names = names
        self.children = [child]
        self._schema = expand_schema(projections, names)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        return f"TpuExpand [{len(self.projections)} projections]"

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        def gen():
            for pid, batch in enumerate(
                    self.children[0].execute_columnar(ctx)):
                for proj in self.projections:
                    with self.metrics.timed(METRIC_TOTAL_TIME):
                        cols = evaluate_projection(proj, batch,
                                                   partition_id=pid)
                    yield ColumnarBatch(cols, batch.rows_raw, self._schema)
        return self._count_output(gen())


class CpuExpandExec(CpuExec):
    def __init__(self, projections: List[List[Expression]],
                 names: List[str], child):
        super().__init__()
        self.projections = projections
        self.names = names
        self.children = [child]
        self._schema = expand_schema(projections, names)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        return f"CpuExpand [{len(self.projections)} projections]"

    def execute_host(self, ctx: ExecContext) -> Iterator[pa.RecordBatch]:
        from spark_rapids_tpu.cpu.expr_eval import (
            _from_arrow, eval_expr, rows_to_arrow,
        )
        child_schema = self.children[0].output_schema
        target = self._schema.to_arrow()
        for rb in self.children[0].execute_host(ctx):
            cols = [_from_arrow(rb.column(i), f.dtype)
                    for i, f in enumerate(child_schema)]
            for proj in self.projections:
                arrays = []
                for i, e in enumerate(proj):
                    r = eval_expr(e, cols, rb.num_rows)
                    arrays.append(rows_to_arrow(r, e.dtype)
                                  .cast(target.field(i).type))
                yield pa.RecordBatch.from_arrays(arrays, schema=target)
