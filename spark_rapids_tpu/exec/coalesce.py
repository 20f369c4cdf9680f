"""Batch coalescing: goals + concat.

Reference: GpuCoalesceBatches.scala — the ``CoalesceGoal`` lattice
(``RequireSingleBatch`` / ``TargetSize`` :90-112), the accumulate loop
honoring row/byte limits (:147-362), and device concatenation via
``Table.concatenate`` (:364-415).

TPU concat: columns are padded to a shared power-of-two capacity and row
blocks land via ``lax.dynamic_update_slice`` at host-known offsets — a pure
device operation, no host round trip.
"""

from __future__ import annotations

from typing import Iterator, List, Optional

import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn, bucket_capacity
from spark_rapids_tpu.columnar.dtypes import STRING, Schema
from spark_rapids_tpu.exec.base import ExecContext, TpuExec
from spark_rapids_tpu.utils.metrics import METRIC_TOTAL_TIME


class CoalesceGoal:
    """Lattice of batch-size requirements (GpuCoalesceBatches.scala:90)."""

    def satisfied_by(self, other: "CoalesceGoal") -> bool:
        raise NotImplementedError


class RequireSingleBatch(CoalesceGoal):
    """All input rows in one batch (sort-global / join build side)."""

    def satisfied_by(self, other):
        return isinstance(other, RequireSingleBatch)

    def __repr__(self):
        return "RequireSingleBatch"


class TargetSize(CoalesceGoal):
    def __init__(self, target_bytes: int):
        self.target_bytes = int(target_bytes)

    def satisfied_by(self, other):
        return (isinstance(other, RequireSingleBatch)
                or (isinstance(other, TargetSize)
                    and other.target_bytes >= self.target_bytes))

    def __repr__(self):
        return f"TargetSize({self.target_bytes})"


SINGLE_BATCH = RequireSingleBatch()


from spark_rapids_tpu.utils.kernel_cache import KernelCache

_CONCAT_CACHE = KernelCache("coalesce.concat", 256)


def _compile_concat(sigs: tuple, out_cap: int):
    """One fused kernel concatenating every column of every batch: row
    counts arrive as a traced offsets vector, so ONE compile covers any
    fill levels at these capacities (eager per-column dynamic_update_slice
    costs batches x columns device round trips otherwise)."""
    key = (sigs, out_cap)
    fn = _CONCAT_CACHE.get(key)
    if fn is not None:
        return fn
    ncols = len(sigs[0])
    widths = [max(s[i][2] for s in sigs) for i in range(ncols)]

    def run(all_flat, count_scalars):
        # offsets/counts derived INSIDE the kernel from the per-batch
        # count scalars — eager host-side stack/cumsum would each compile
        # their own executable per shape
        counts = jnp.stack([jnp.asarray(c, jnp.int32)
                            for c in count_scalars])
        csum = jnp.cumsum(counts)
        offsets = jnp.concatenate([jnp.zeros(1, counts.dtype), csum[:-1]])
        outs = []
        for ci in range(ncols):
            head = all_flat[0][ci]
            is_str = head[2] is not None
            data = jnp.zeros(out_cap, head[0].dtype)
            valid = jnp.zeros(out_cap, jnp.bool_)
            chars = jnp.zeros((out_cap, widths[ci]), jnp.uint8) \
                if is_str else None
            for bi, flat in enumerate(all_flat):
                d, v, ch = flat[ci]
                cap_b = d.shape[0]
                rowpos = jnp.arange(cap_b)
                write = rowpos < counts[bi]
                # out-of-range targets drop (mode='drop'), so padding rows
                # never land
                tgt = jnp.where(write, offsets[bi] + rowpos, out_cap)
                data = data.at[tgt].set(d, mode="drop")
                valid = valid.at[tgt].set(v & write, mode="drop")
                if is_str:
                    blk = ch
                    if blk.shape[1] < widths[ci]:
                        blk = jnp.pad(
                            blk, ((0, 0), (0, widths[ci] - blk.shape[1])))
                    chars = chars.at[tgt].set(blk, mode="drop")
            outs.append((data, valid, chars))
        return tuple(outs), csum[-1]

    from spark_rapids_tpu.compile.service import engine_jit
    fn = engine_jit(run, family="concat", name="concat")
    _CONCAT_CACHE[key] = fn
    return fn


def concat_batches(batches: List[ColumnarBatch],
                   schema: Optional[Schema] = None) -> ColumnarBatch:
    """Concatenate device batches (ConcatAndConsumeAll analog,
    GpuCoalesceBatches.scala:74) in a single fused kernel.

    When any input row count is device-resident the offsets/counts are
    computed on device too (no host sync): the output capacity is then
    bucketed from the host-known BOUNDS — at most one bucket larger than
    the true total; the final transfer pack trims the padding before any
    bytes cross the link.

    An ordinal that is ENCODED in every input (columnar/encoding.py)
    concatenates its CODES plane — batches on different dictionaries
    re-key onto the sorted union first (a tiny device gather each) — so
    coalescing never densifies a dictionary column; a mixed
    encoded/dense ordinal densifies through the counted late decode."""
    import numpy as np
    from spark_rapids_tpu.columnar import encoding
    from spark_rapids_tpu.columnar.column import LazyRows
    if not batches:
        raise ValueError("concat_batches of empty list needs a batch")
    if len(batches) == 1:
        return batches[0]
    col_lists = [list(b.columns) for b in batches]
    enc_cols = {}
    if any(encoding.has_encoded(b) for b in batches):
        enc_cols = encoding.unify_ordinals(col_lists)
    sigs = tuple(
        tuple(encoding.col_planes(c, ci in enc_cols)[1]
              for ci, c in enumerate(cols))
        for cols in col_lists)
    if all(b.rows_known for b in batches):
        cap = bucket_capacity(max(1, sum(b.num_rows for b in batches)))
        out_rows = sum(b.num_rows for b in batches)
    else:
        bound = sum(b.rows_bound for b in batches)
        cap = bucket_capacity(max(1, bound))
        out_rows = None  # filled from the kernel's total below
    fn = _compile_concat(sigs, cap)
    outs, total_dev = fn(
        tuple(tuple(encoding.col_planes(c, ci in enc_cols)[0]
                    for ci, c in enumerate(cols))
              for cols in col_lists),
        tuple(b.rows_traced for b in batches))
    if out_rows is None:
        out_rows = LazyRows(total_dev,
                            sum(b.rows_bound for b in batches))
    head = batches[0]
    cols = []
    for ci, (hc, (d, v, ch)) in enumerate(zip(head.columns, outs)):
        if ci in enc_cols:
            from spark_rapids_tpu.columnar.encoding import EncodedColumn
            cols.append(EncodedColumn(d, v, out_rows, enc_cols[ci]))
        else:
            cols.append(DeviceColumn(hc.dtype, d, v, out_rows,
                                     chars=ch))
    return ColumnarBatch(cols, out_rows, schema or head.schema)


class TpuCoalesceBatchesExec(TpuExec):
    """Accumulate input batches up to the goal (reference
    AbstractGpuCoalesceIterator GpuCoalesceBatches.scala:147-362)."""

    def __init__(self, goal: CoalesceGoal, child):
        super().__init__()
        self.goal = goal
        self.children = [child]

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def describe(self) -> str:
        return f"TpuCoalesceBatches [{self.goal!r}]"

    @property
    def output_batching(self):
        return self.goal

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        def gen():
            from spark_rapids_tpu.io.prefetch import device_lookahead
            from spark_rapids_tpu.memory.spill import (
                SpillableBatch, close_all, materialize_all,
            )
            cat = ctx.runtime.catalog
            target = (self.goal.target_bytes
                      if isinstance(self.goal, TargetSize) else None)
            # with the capacity ladder configured, accumulate toward a
            # LADDER rung instead of the raw conf value: an exact-size
            # row target flushes batches at arbitrary row counts,
            # manufacturing a novel padded capacity per flush boundary
            # and defeating the capacity bucketing every kernel cache
            # downstream keys on (docs/compile_cache.md).  Gated on the
            # ladder being explicitly configured so compile.*-unset
            # runs coalesce exactly as before — snapping a
            # non-power-of-two batchSizeRows would otherwise silently
            # change flush targets
            from spark_rapids_tpu.compile import buckets as _buckets
            max_rows = (_buckets.snap_rows(ctx.conf.batch_size_rows)
                        if _buckets.configured()
                        else ctx.conf.batch_size_rows)
            # accumulated batches are spillable while waiting for the goal
            # (reference: the coalesce iterator's pending batches are
            # spill-tracked, GpuCoalesceBatches.scala:147)
            pending: List = []
            pending_bytes = 0
            pending_rows = 0
            # pull the child through a depth-1 background lookahead: the
            # accumulate/concat work below overlaps the child's next
            # decode+upload instead of stalling on it (io/prefetch.py;
            # conf-gated with the rest of the overlap pipeline)
            src = device_lookahead(
                self.children[0].execute_columnar(ctx), ctx, self.metrics)
            try:
                for b in src:
                    # skip-empty only when the count is already host-known;
                    # checking a device-resident count would force a sync
                    if b.rows_known and b.num_rows == 0:
                        continue
                    if target is not None and pending and (
                            pending_bytes + b.size_bytes() > target
                            or pending_rows + b.rows_bound > max_rows):
                        # Ordering rule: staging BEFORE permit — never
                        # wait on the spill-staging limiter while
                        # holding a chip permit.  materialize_all can
                        # block on that limiter (spill promotion), and a
                        # permit held across such a wait would starve
                        # every other stage needing admission (prefetch
                        # queue grants live on a separate limiter, so
                        # there is no deadlock cycle — this is the
                        # liveness discipline that keeps it that way).
                        # Only the concat dispatch takes chip admission
                        # (stage-scoped model, transfer.pipelined_h2d);
                        # the yield and the acquisition sit outside
                        # concatTime so the metric stays pure concat
                        # work.
                        flushed = materialize_all(pending, ctx)
                        pending = []
                        with ctx.runtime.acquire_device():
                            with self.metrics.timed("concatTime"):
                                out = concat_batches(flushed)
                        yield out
                        pending_bytes, pending_rows = 0, 0
                    pending_bytes += b.size_bytes()
                    pending_rows += b.rows_bound
                    pending.append(SpillableBatch(b, cat))
                if pending:
                    flushed = materialize_all(pending, ctx)
                    pending = []
                    with ctx.runtime.acquire_device():
                        with self.metrics.timed("concatTime"):
                            out = concat_batches(flushed)
                    yield out
            except BaseException:
                close_all(pending)
                raise
            finally:
                if hasattr(src, "close"):
                    src.close()
        return self._count_output(gen())
