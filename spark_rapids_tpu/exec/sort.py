"""Sort exec.

Reference: GpuSortExec.scala:52-270 — per-batch cuDF ``Table.orderBy``
with ``RequireSingleBatch`` when global.  TPU: one variadic ``lax.sort``
over sortable int keys + iota payload, then a fused gather of every column
by the permutation (one compiled kernel per (orders, signature))."""

from __future__ import annotations

from typing import Iterator, List, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.compile.service import engine_jit
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.columnar.dtypes import Schema
from spark_rapids_tpu.exec.base import ExecContext, TpuExec
from spark_rapids_tpu.exec.coalesce import concat_batches
from spark_rapids_tpu.exec.sortkeys import colval_sort_keys, sort_permutation
from spark_rapids_tpu.exprs.base import (
    ColVal, EvalContext, Expression, _batch_signature, _flatten_batch,
)
from spark_rapids_tpu.utils.metrics import METRIC_TOTAL_TIME

from spark_rapids_tpu.utils.kernel_cache import KernelCache

_SORT_CACHE = KernelCache("sort", 256)


def _compile_sort(orders_key: tuple, orders, input_sig, capacity: int):
    key = (orders_key, input_sig, capacity)
    fn = _SORT_CACHE.get(key)
    if fn is not None:
        return fn

    def run(flat_cols, num_rows):
        cols = [ColVal(*t) for t in flat_cols]
        ctx = EvalContext(cols, num_rows, capacity)
        live = jnp.arange(capacity) < num_rows
        all_keys = []
        for expr, asc, nulls_first in orders:
            cv = expr.emit(ctx)
            all_keys.extend(
                colval_sort_keys(cv, expr.dtype, asc, nulls_first))
        perm = sort_permutation(all_keys, capacity, live_first=live)
        # ONE fused row-gather for every column plane (element takes are
        # >20x slower on TPU; see columnar/gatherfab.py)
        from spark_rapids_tpu.columnar.gatherfab import gather_planes
        g = gather_planes(
            [p for cv in cols for p in (cv.data, cv.validity, cv.chars)],
            perm)
        outs = []
        for ci in range(len(cols)):
            outs.append(ColVal(g[3 * ci], g[3 * ci + 1] & live,
                               g[3 * ci + 2]))
        return tuple(outs)

    fn = engine_jit(run, family="sort", name="full")
    _SORT_CACHE[key] = fn
    return fn


def sort_batch(orders: List[Tuple[Expression, bool, bool]],
               batch: ColumnarBatch) -> ColumnarBatch:
    orders_key = tuple((e.key(), asc, nf) for e, asc, nf in orders)
    fn = _compile_sort(orders_key, orders, _batch_signature(batch),
                       batch.capacity)
    outs = fn(_flatten_batch(batch), batch.rows_traced)
    cols = [DeviceColumn(c.dtype, o.data, o.validity, batch.rows_raw,
                         chars=o.chars)
            for c, o in zip(batch.columns, outs)]
    return ColumnarBatch(cols, batch.rows_raw, batch.schema)


class TpuSortExec(TpuExec):
    """Global sort: coalesces input to a single batch (reference
    RequireSingleBatch goal for global sort, GpuSortExec.scala:52-101) then
    one fused sort+gather kernel."""

    def __init__(self, orders: List[Tuple[Expression, bool, bool]], child,
                 global_sort: bool = True):
        super().__init__()
        self.orders = orders
        self.children = [child]
        self.global_sort = global_sort

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def describe(self) -> str:
        parts = [f"{e.name} {'ASC' if a else 'DESC'}"
                 for e, a, _ in self.orders]
        return "TpuSort [" + ", ".join(parts) + "]"

    @property
    def output_batching(self):
        from spark_rapids_tpu.exec.coalesce import SINGLE_BATCH
        return SINGLE_BATCH if self.global_sort else None

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        def gen():
            from spark_rapids_tpu.memory.spill import (
                collect_spillable, materialize_all,
            )
            from spark_rapids_tpu.utils.retry import with_retry
            if self.global_sort:
                # accumulate the whole input through the spill catalog so
                # collection stays within the device budget
                handles = collect_spillable(
                    self.children[0].execute_columnar(ctx), ctx)
                if not handles:
                    return
                with self.metrics.timed(METRIC_TOTAL_TIME):
                    batch = concat_batches(materialize_all(handles, ctx))
                    # spill-retry only (withRetryNoSplit): a global sort
                    # needs its whole input in one kernel
                    yield from with_retry(
                        lambda b: sort_batch(self.orders, b), batch, ctx)
            else:
                for b in self.children[0].execute_columnar(ctx):
                    with self.metrics.timed(METRIC_TOTAL_TIME):
                        yield from with_retry(
                            lambda bb: sort_batch(self.orders, bb), b,
                            ctx)
        return self._count_output(gen())


_HEAD_CACHE = KernelCache("sort.head", 256)


def _compile_head_take(sig, out_cap: int, limit: int):
    """Fused head-take: first min(limit, rows) sorted rows of every
    column in ONE kernel (eager glue would compile per-op)."""
    key = (sig, out_cap, limit)
    fn = _HEAD_CACHE.get(key)
    if fn is not None:
        return fn

    def run(flat, src_rows):
        keep_n = jnp.minimum(jnp.int32(limit),
                             jnp.asarray(src_rows, jnp.int32))
        pos = jnp.arange(out_cap, dtype=jnp.int32)
        ok = pos < keep_n
        outs = []
        for (d, v, ch) in flat:
            cap_in = d.shape[0]
            idx = jnp.minimum(pos, cap_in - 1)
            data = jnp.take(d, idx, axis=0)
            valid = jnp.where(ok, jnp.take(v, idx), False)
            chars = None if ch is None else jnp.take(ch, idx, axis=0)
            outs.append((data, valid, chars))
        return tuple(outs), keep_n

    fn = engine_jit(run, family="sort", name="head")
    _HEAD_CACHE[key] = fn
    return fn


class TpuTopNExec(TpuExec):
    """Fused Limit-over-global-Sort (Spark's TakeOrderedAndProjectExec
    shape; the reference runs it as RequireSingleBatch sort + limit,
    GpuSortExec.scala:52-101 + limit.scala:40 — fusing avoids ever
    materializing more than limit + one batch of rows, so a top-N over an
    arbitrarily large stream stays in budget)."""

    def __init__(self, orders: List[Tuple[Expression, bool, bool]],
                 limit: int, child):
        super().__init__()
        self.orders = orders
        self.limit = int(limit)
        self.children = [child]

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def describe(self) -> str:
        parts = [f"{e.name} {'ASC' if a else 'DESC'}"
                 for e, a, _ in self.orders]
        return f"TpuTopN [{self.limit}, " + ", ".join(parts) + "]"

    @property
    def output_batching(self):
        from spark_rapids_tpu.exec.coalesce import SINGLE_BATCH
        return SINGLE_BATCH

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        def gen():
            from spark_rapids_tpu.columnar.column import (
                LazyRows, bucket_capacity,
            )
            top = None
            out_cap = bucket_capacity(max(1, self.limit))
            for b in self.children[0].execute_columnar(ctx):
                with self.metrics.timed(METRIC_TOTAL_TIME):
                    cand = b if top is None else concat_batches([top, b])
                    s = sort_batch(self.orders, cand)
                    fn = _compile_head_take(_batch_signature(s),
                                            out_cap, self.limit)
                    outs, keep_n = fn(_flatten_batch(s), s.rows_traced)
                    keep = LazyRows(keep_n, min(self.limit, s.rows_bound))
                    cols = [DeviceColumn(c.dtype, d, v, keep, chars=ch)
                            for c, (d, v, ch) in zip(s.columns, outs)]
                    top = ColumnarBatch(cols, keep, s.schema)
            if top is not None and (not top.rows_known
                                    or top.num_rows > 0):
                yield top
        return self._count_output(gen())
