"""Hash aggregate exec.

Reference: aggregate.scala:227-825 — GpuHashAggregateExec drives cuDF
``Table.groupBy().aggregate()`` per batch (update mode), then iteratively
concat+merge-aggregates the partials (:366-391); empty-input global
aggregation emits initial values (:406-419); aggregate functions declare
update/merge op pairs (AggregateFunctions.scala:157-530).

TPU design — sort-based segmented reduction in ONE fused kernel per batch:
  1. emit group-key ColVals and aggregate-input projections,
  2. build sortable int keys (sortkeys.py), variadic ``lax.sort`` with an
     iota payload,
  3. segment boundaries = any key differs from the previous sorted row;
     group ids = prefix-sum of boundaries,
  4. every buffer slot reduces with ``jax.ops.segment_{sum,min,max}`` (or
     first/last via boundary gathers) at static num_segments = capacity,
  5. group representatives gather the key columns back.
The merge phase runs the same kernel shape over concatenated partials with
the merge ops.  All shapes static; only the final group count syncs to host.

An update whose key domain the host already knows (every key a dictionary
code, no keys at all, or one integer key of small probed range) skips the
sort: ``_try_dense_update`` reduces it by direct address over K slots
(exec/pallas_agg.py) and its partial is K slots long, so the concat, the
merge and everything downstream run at the domain's capacity, not the
input's.

A filter / project chain whose only consumer is this update is FOLDED
into it by the planner (plan/fusion.py, docs/fusion.md): the steps run
masked inside the update's own program (``exec.stage.emit_steps(...,
compact=False)``) and the keep-mask is the update's liveness, so the
filter costs one elementwise predicate and no compaction gather.
"""

from __future__ import annotations

import math
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_tpu.compile.service import engine_jit
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn, bucket_capacity
from spark_rapids_tpu.columnar.dtypes import (
    DataType, Field, Schema, STRING, INT32, INT64, FLOAT32, FLOAT64,
)
from spark_rapids_tpu.exec.base import ExecContext, TpuExec
from spark_rapids_tpu.exec.coalesce import concat_batches
from spark_rapids_tpu.exec.sortkeys import colval_sort_keys, sort_permutation
from spark_rapids_tpu.exprs.aggregates import AggregateFunction
from spark_rapids_tpu.exprs.base import (
    Alias, BoundReference, ColVal, EvalContext, Expression,
    _batch_signature, _flatten_batch, hoisted_args,
)
from spark_rapids_tpu.utils.metrics import (
    METRIC_MASKED_FILTER_BATCHES, METRIC_PALLAS_AGG_BATCHES,
    METRIC_TOTAL_TIME,
)


def unwrap_aggregate(e: Expression) -> Tuple[str, AggregateFunction]:
    """Aggregate output expr -> (output name, function).  Bare functions
    and Alias-wrapped functions are supported (general post-expressions
    over aggregate results are planned via a follow-up projection)."""
    if isinstance(e, Alias):
        inner = e.children[0]
        if isinstance(inner, AggregateFunction):
            return e.out_name, inner
    if isinstance(e, AggregateFunction):
        return e.name, e
    raise TypeError(f"not an aggregate expression: {e!r}")


def _segment_reduce(op: str, vals: jnp.ndarray, valid: jnp.ndarray,
                    gid: jnp.ndarray, num_segments: int,
                    boundary: jnp.ndarray, live: jnp.ndarray):
    """Masked segment reduction over sorted rows."""
    if op == "count":
        contrib = (valid & live).astype(jnp.int64)
        return jax.ops.segment_sum(contrib, gid, num_segments=num_segments)
    if op == "sum":
        contrib = jnp.where(valid & live, vals, jnp.zeros_like(vals))
        return jax.ops.segment_sum(contrib, gid, num_segments=num_segments)
    if op in ("min", "max"):
        if jnp.issubdtype(vals.dtype, jnp.floating):
            # Spark ordering: NaN is greatest.  min ignores NaN unless the
            # group is all-NaN; max returns NaN when any NaN is present.
            nanmask = jnp.isnan(vals)
            sentinel = jnp.asarray(
                jnp.inf if op == "min" else -jnp.inf, vals.dtype)
            contrib = jnp.where(valid & live & ~nanmask, vals, sentinel)
            red = jax.ops.segment_min if op == "min" else \
                jax.ops.segment_max
            base = red(contrib, gid, num_segments=num_segments)
            has_nan = jax.ops.segment_max(
                (valid & live & nanmask).astype(jnp.int32), gid,
                num_segments=num_segments) > 0
            has_non_nan = jax.ops.segment_max(
                (valid & live & ~nanmask).astype(jnp.int32), gid,
                num_segments=num_segments) > 0
            nan_v = jnp.asarray(jnp.nan, vals.dtype)
            if op == "min":
                return jnp.where(has_nan & ~has_non_nan, nan_v, base)
            return jnp.where(has_nan, nan_v, base)
        if vals.dtype == jnp.bool_:
            vals = vals.astype(jnp.int32)
            sentinel = jnp.asarray(1 if op == "min" else 0, jnp.int32)
        else:
            info = jnp.iinfo(vals.dtype)
            sentinel = jnp.asarray(
                info.max if op == "min" else info.min, vals.dtype)
        contrib = jnp.where(valid & live, vals, sentinel)
        red = jax.ops.segment_min if op == "min" else jax.ops.segment_max
        return red(contrib, gid, num_segments=num_segments)
    if op in ("first", "last"):
        # position of first/last VALID row per segment, then gather
        cap = vals.shape[0]
        pos = jnp.arange(cap, dtype=jnp.int32)
        mask = valid & live
        sent = jnp.asarray(cap, jnp.int32)
        if op == "first":
            p = jnp.where(mask, pos, sent)
            best = jax.ops.segment_min(p, gid, num_segments=num_segments)
        else:
            p = jnp.where(mask, pos, -1)
            best = jax.ops.segment_max(p, gid, num_segments=num_segments)
        best_c = jnp.clip(best, 0, cap - 1)
        return jnp.take(vals, best_c, axis=0)
    raise ValueError(f"unknown segment op {op}")


class _AggSpec:
    """Static description of one aggregation (shared by update & merge)."""

    def __init__(self, groupings: Sequence[Expression],
                 aggs: Sequence[Tuple[str, AggregateFunction]]):
        self.groupings = list(groupings)
        self.aggs = list(aggs)

    def key(self) -> tuple:
        return (tuple(g.key() for g in self.groupings),
                tuple((n, f.key()) for n, f in self.aggs))


from spark_rapids_tpu.utils.kernel_cache import KernelCache

_AGG_CACHE = KernelCache("aggregate", 256)

# agg-spec -> consecutive pallas range-probe memo misses (see
# _probe_key_range: probing costs a host sync, so specs whose inputs
# are fresh every run stop probing after 2 misses)
_PALLAS_FRESH_MISSES: dict = {}


def make_agg_body(spec: _AggSpec, phase: str, capacity: int):
    """Build the traceable aggregation body (used directly inside
    ``shard_map`` by the distributed layer, or jitted by ``_compile_agg``).

    phase: 'update' (inputs = raw child cols) or 'merge' (inputs =
    key cols + buffer cols of partials).  ``live_mask`` (optional)
    overrides the default contiguous row-liveness ``arange < num_rows`` —
    the distributed exchange produces non-contiguous live rows."""
    n_groups_cols = len(spec.groupings)

    def run(flat_cols, num_rows, live_mask=None):
        cols = [ColVal(*t) for t in flat_cols]
        ctx = EvalContext(cols, num_rows, capacity)
        live = live_mask if live_mask is not None \
            else jnp.arange(capacity) < num_rows
        if phase == "update":
            key_cvs = [g.emit(ctx) for g in spec.groupings]
            inputs: List[Tuple[ColVal, DataType, str]] = []
            for _, f in spec.aggs:
                projs = f.input_projection()
                ops = f.update_ops()
                # every buffer slot reduces over the (single) projected input
                cv = projs[0].emit(ctx)
                for op in ops:
                    inputs.append((cv, projs[0].dtype, op))
        else:
            key_cvs = cols[:n_groups_cols]
            inputs = []
            i = n_groups_cols
            for _, f in spec.aggs:
                for op, bt in zip(f.merge_ops(), f.buffer_dtypes()):
                    inputs.append((cols[i], bt, op))
                    i += 1

        # sort rows by group keys
        all_keys = []
        per_key_counts = []
        for g, cv in zip(spec.groupings, key_cvs):
            dt = g.dtype if phase == "update" else g.dtype
            ks = colval_sort_keys(cv, dt, True, True)
            per_key_counts.append(len(ks))
            all_keys.extend(ks)
        if all_keys:
            perm = sort_permutation(all_keys, capacity, live_first=live)
        else:
            perm = jnp.arange(capacity, dtype=jnp.int32)

        # ONE fused row-gather applies the sort permutation to every
        # plane this kernel touches (keys, liveness, every aggregate
        # input) — element-granular takes are >20x slower on TPU.  The
        # global-agg case (no keys) has an identity perm: skip the move.
        from spark_rapids_tpu.columnar.gatherfab import gather_planes
        in_planes = []
        for cv, _, _ in inputs:
            in_planes.extend((cv.data, cv.validity, cv.chars))
        if all_keys:
            permuted = gather_planes([live] + all_keys + in_planes, perm)
        else:
            permuted = [live] + list(all_keys) + in_planes
        live_s = permuted[0]
        keys_s = permuted[1:1 + len(all_keys)]
        inputs_s = []
        base = 1 + len(all_keys)
        for ii, (cv, dt, op) in enumerate(inputs):
            inputs_s.append((ColVal(permuted[base + 3 * ii],
                                    permuted[base + 3 * ii + 1],
                                    permuted[base + 3 * ii + 2]), dt, op))
        # the raw permuted liveness: the global-agg branch below may
        # force live_s[0] True so an EMPTY input still emits one segment
        # of initial values, but reductions must keep masking dead rows
        real_live = live_s

        # boundaries over sorted key values
        if all_keys:
            neq_prev = jnp.zeros(capacity, jnp.bool_)
            for ks in keys_s:
                prev = jnp.concatenate([ks[:1], ks[:-1]])
                neq_prev = neq_prev | (ks != prev)
            boundary = neq_prev.at[0].set(True) & live_s
            boundary = boundary.at[0].set(live_s[0])
        else:
            # global aggregation: single segment (even when empty —
            # reference emits initial values, aggregate.scala:406)
            boundary = jnp.zeros(capacity, jnp.bool_).at[0].set(True)
            if live_mask is not None:
                live_s = live_s.at[0].set(True)
            else:
                live_s = jnp.arange(capacity) < jnp.maximum(num_rows, 1)
        from spark_rapids_tpu.utils.pscan import prefix_sum
        gid_raw = prefix_sum(boundary.astype(jnp.int32)) - 1
        gid = jnp.clip(gid_raw, 0, capacity - 1)
        n_groups = jnp.sum(boundary.astype(jnp.int32))
        if not all_keys:
            n_groups = jnp.int32(1)

        # reduce every buffer slot (inputs already permuted by the fused
        # gather above)
        buf_outs = []
        for cv_s, dt, op in inputs_s:
            vals = cv_s.data
            valid = cv_s.validity
            if dt == STRING:
                if op not in ("min", "max", "first", "last", "count"):
                    raise ValueError(f"op {op} unsupported for strings")
                if op == "count":
                    red = _segment_reduce("count", vals, valid, gid,
                                          capacity, boundary, real_live)
                    buf_outs.append(ColVal(red, None, None))
                    continue
                chars = cv_s.chars
                if op in ("first", "last"):
                    mask = valid & real_live
                    pos = jnp.arange(capacity, dtype=jnp.int32)
                    if op == "first":
                        p = jnp.where(mask, pos, capacity)
                        best = jax.ops.segment_min(
                            p, gid, num_segments=capacity)
                    else:
                        p = jnp.where(mask, pos, -1)
                        best = jax.ops.segment_max(
                            p, gid, num_segments=capacity)
                    bc = jnp.clip(best, 0, capacity - 1)
                    buf_outs.append(ColVal(jnp.take(vals, bc),
                                           None, jnp.take(chars, bc,
                                                          axis=0)))
                else:
                    # min/max over strings via packed-key argmin trick:
                    # reduce over first sorted occurrence is NOT correct in
                    # general, so reduce positions by packed-key order —
                    # strings sort by the same packed keys used above, so
                    # within a segment the rows are NOT sorted by this
                    # column unless it is a group key.  Use a two-level
                    # reduce: order rows by (gid, string keys) and take
                    # segment first/last.
                    sks = colval_sort_keys(
                        ColVal(vals, valid, chars), STRING, True,
                        # nulls must lose: for min, nulls last; for max,
                        # nulls first
                        nulls_first=(op == "max"))
                    perm2 = sort_permutation(
                        [gid] + sks, capacity,
                        live_first=valid & real_live)
                    gid2 = jnp.take(gid, perm2)
                    pos = jnp.arange(capacity, dtype=jnp.int32)
                    mask2 = jnp.take(valid & real_live, perm2)
                    if op == "min":
                        p = jnp.where(mask2, pos, capacity)
                        best2 = jax.ops.segment_min(
                            p, gid2, num_segments=capacity)
                    else:
                        p = jnp.where(mask2, pos, -1)
                        best2 = jax.ops.segment_max(
                            p, gid2, num_segments=capacity)
                    b2 = jnp.clip(best2, 0, capacity - 1)
                    orig = jnp.take(perm2, b2)
                    buf_outs.append(ColVal(
                        jnp.take(vals, orig), None,
                        jnp.take(chars, orig, axis=0)))
            else:
                red = _segment_reduce(op, vals, valid, gid, capacity,
                                      boundary, real_live)
                buf_outs.append(ColVal(red, None, None))

        # representative row per group for key output (one fused gather
        # for every key plane)
        pos = jnp.arange(capacity, dtype=jnp.int32)
        rep_sorted = jax.ops.segment_min(
            jnp.where(boundary, pos, capacity), gid, num_segments=capacity)
        rep = jnp.take(perm, jnp.clip(rep_sorted, 0, capacity - 1))
        group_valid = pos < n_groups
        key_planes = []
        for cv in key_cvs:
            key_planes.extend((cv.data, cv.validity, cv.chars))
        kg = gather_planes(key_planes, rep)
        key_outs = []
        for ki in range(len(key_cvs)):
            key_outs.append(ColVal(kg[3 * ki],
                                   kg[3 * ki + 1] & group_valid,
                                   kg[3 * ki + 2]))
        buf_final = [ColVal(b.data, group_valid, b.chars) for b in buf_outs]
        return n_groups, tuple(key_outs), tuple(buf_final)

    return run


def _compile_agg(spec: _AggSpec, phase: str, input_sig, capacity: int,
                 decoder=None):
    """``decoder`` (encoding.plane_view) maps compressed flat triples to
    dense ones inside the jitted body; the marker-bearing ``input_sig``
    keys those variants separately from the dense layout."""
    cache_key = (spec.key(), phase, input_sig, capacity)
    fn = _AGG_CACHE.get(cache_key)
    if fn is not None:
        return fn
    body = make_agg_body(spec, phase, capacity)
    if decoder is not None:
        inner = body

        def body(flat_cols, num_rows, _inner=inner, _dec=decoder):
            return _inner(_dec(flat_cols), num_rows)
    fn = engine_jit(body, family="aggregate", name=phase)
    _AGG_CACHE[cache_key] = fn
    return fn


def _compile_folded_update(h_steps, input_sig, aux_sig, capacity: int,
                           spec: _AggSpec, radices=None):
    """ONE update program for a batch whose filter / project chain the
    planner folded into the aggregate: ``h_steps`` (hoisted, code-viewed;
    the last one projects ``spec``'s keys and inputs) run MASKED, then
    the update body reduces under the steps' liveness — the dense body
    over ``radices`` (exec/pallas_agg.py) or, with ``radices`` None, the
    sorted-segment body.  Literals ride in as traced scalars and
    dictionary tables as aux inputs, so the key is literal-free: a new
    binding of a prepared query reuses the program."""
    from spark_rapids_tpu.exec.stage import emit_steps, stage_fingerprint
    dense = radices is not None
    cache_key = ("folded", stage_fingerprint(h_steps), input_sig, aux_sig,
                 capacity, spec.key(),
                 tuple(int(r) for r in radices) if dense else None)
    fn = _AGG_CACHE.get(cache_key)
    if fn is not None:
        return fn
    if dense:
        from spark_rapids_tpu.exec import pallas_agg as pag
        body = pag.make_update_body(spec, capacity, radices)
    else:
        body = make_agg_body(spec, "update", capacity)

    def run(flat_cols, aux, num_rows, hoisted, bases):
        cols = [ColVal(*t) for t in flat_cols]
        # folded steps are deterministic: the partition id is unread
        cols, live = emit_steps(h_steps, cols, num_rows, capacity,
                                jnp.int64(0), hoisted, aux=aux,
                                compact=False)
        flat = tuple((c.data, c.validity, c.chars) for c in cols)
        if dense:
            return body(flat, num_rows, bases, live)
        return body(flat, num_rows, live)

    fn = engine_jit(run, family="aggregate",
                    name="masked_pallas_update" if dense
                    else "masked_update")
    _AGG_CACHE[cache_key] = fn
    return fn


_EVAL_CACHE = KernelCache("aggregate.eval", 256)


def _compile_evaluate(spec: _AggSpec, input_sig, capacity: int):
    """Finalize: merged buffers -> output columns (keys + evaluated)."""
    cache_key = (spec.key(), "eval", input_sig, capacity)
    fn = _EVAL_CACHE.get(cache_key)
    if fn is not None:
        return fn

    nk = len(spec.groupings)

    def run(flat_cols, num_rows):
        cols = [ColVal(*t) for t in flat_cols]
        live = jnp.arange(capacity) < num_rows
        outs = list(cols[:nk])
        i = nk
        for _, f in spec.aggs:
            nbuf = len(f.buffer_dtypes())
            bufs = cols[i:i + nbuf]
            i += nbuf
            ev = f.evaluate(bufs)
            outs.append(ColVal(ev.data, ev.validity & live, ev.chars))
        return tuple(outs)

    fn = engine_jit(run, family="aggregate", name="evaluate")
    _EVAL_CACHE[cache_key] = fn
    return fn


def _fused_decode_planes(vbatch: ColumnarBatch, count: bool = True):
    """``(flat, sig, decoder)`` of a batch for a compiled whole-batch
    consumer: plane-compressed inputs (rle/delta/packed bool) feed the
    kernel their compressed planes and decode INSIDE it — one dispatch,
    no decode_plane_late on the update path (``encoding.plane_view``;
    ``count=False`` for a probe that may not dispatch the view)."""
    from spark_rapids_tpu.columnar import encoding
    pv = encoding.plane_view(vbatch, count=count)
    if pv is not None:
        return pv
    return _flatten_batch(vbatch), _batch_signature(vbatch), None


def _colvals_to_batch(cvs, dtypes, n_rows: int,
                      schema: Optional[Schema] = None,
                      wrap=None) -> ColumnarBatch:
    """``wrap`` maps column position -> DictPlanes for group keys that
    ran in the code domain (columnar/encoding.py): those positions'
    data planes are dictionary CODES and re-wrap as EncodedColumns —
    the aggregate's key output never materializes dense strings."""
    from spark_rapids_tpu.columnar.encoding import EncodedColumn
    cols = []
    for i, (cv, dt) in enumerate(zip(cvs, dtypes)):
        d = wrap.get(i) if wrap else None
        if d is not None:
            cols.append(EncodedColumn(cv.data, cv.validity, n_rows, d))
        else:
            cols.append(DeviceColumn(dt, cv.data, cv.validity, n_rows,
                                     chars=cv.chars))
    return ColumnarBatch(cols, n_rows, schema)


class TpuHashAggregateExec(TpuExec):
    """reference GpuHashAggregateExec aggregate.scala:227."""

    def __init__(self, groupings: List[Expression],
                 aggregates: List[Expression], child):
        super().__init__()
        # filter / project steps the fusion pass folded in from below
        # (``fold_steps``); the groupings and aggregates are bound to
        # the LAST step's output, ``children[0]`` feeds the first
        self.pre_steps: tuple = ()
        self.groupings = list(groupings)
        # the original bound aggregate expressions, kept so the AQE
        # placement re-score can rebuild the CPU analog of this node
        # (plan/placement.py:_demote_physical) — agg_pairs below is the
        # unwrapped device form and cannot round-trip
        self.aggregates = list(aggregates)
        self.agg_pairs = [unwrap_aggregate(e) for e in aggregates]
        for _, f in self.agg_pairs:
            if getattr(f, "ignore_nulls", True) is False:
                raise ValueError(
                    f"{type(f).__name__}(ignore_nulls=False) is "
                    "unsupported: the segment kernels always skip nulls")
        self.children = [child]
        self.spec = _AggSpec(self.groupings, self.agg_pairs)
        fields = [Field(g.name, g.dtype, g.nullable) for g in self.groupings]
        fields += [Field(n, f.dtype, f.nullable) for n, f in self.agg_pairs]
        self._schema = Schema(fields)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        gs = ", ".join(g.name for g in self.groupings)
        asx = ", ".join(n for n, _ in self.agg_pairs)
        pre = ""
        if self.pre_steps:
            from spark_rapids_tpu.exec.stage import describe_steps
            pre = f", masked=[{describe_steps(self.pre_steps)}]"
        return f"TpuHashAggregate [keys=[{gs}], aggs=[{asx}]{pre}]"

    def fold_steps(self, steps, child) -> None:
        """Take over a filter / project chain (plan/fusion.py): its steps
        run masked inside this node's update program, over ``child``'s
        batches."""
        self.pre_steps = tuple((k, tuple(es)) for k, es in steps) \
            + self.pre_steps
        self.children = [child]

    def child_coalesce_goals(self, conf):
        from spark_rapids_tpu.exec.coalesce import TargetSize
        return [TargetSize(conf.batch_size_bytes)]

    @property
    def output_batching(self):
        from spark_rapids_tpu.exec.coalesce import SINGLE_BATCH
        return SINGLE_BATCH

    # buffer schema between update and merge phases
    def _buffer_dtypes(self) -> List[DataType]:
        out = [g.dtype for g in self.groupings]
        for _, f in self.agg_pairs:
            out.extend(f.buffer_dtypes())
        return out

    def _agg_view(self, phase: str, batch: ColumnarBatch):
        """The compressed code view of one aggregate phase
        (columnar/encoding.py): group keys over encoded columns group
        by CODES — ranks, so boundaries and output order are
        byte-identical to grouping the strings — and the key output
        stays encoded.  Returns ``(spec, batch, wrap)``; the identity
        triple when nothing is encoded."""
        from spark_rapids_tpu.columnar import encoding
        if phase == "update":
            value_exprs = [p for _, f in self.agg_pairs
                           for p in f.input_projection()]
            view = encoding.agg_code_view(batch, self.groupings,
                                          value_exprs)
            if view is None:
                return self.spec, batch, None
            batch2, groupings2, wrap = view
            return _AggSpec(groupings2, self.agg_pairs), batch2, wrap
        view = encoding.key_columns_code_view(batch,
                                              len(self.groupings))
        if view is None:
            return self.spec, batch, None
        batch2, overrides, wrap = view
        from spark_rapids_tpu.exprs.base import BoundReference
        groupings2 = [
            BoundReference(ki, overrides[ki], g.nullable, g.name)
            if ki in overrides else g
            for ki, g in enumerate(self.groupings)]
        return _AggSpec(groupings2, self.agg_pairs), batch2, wrap

    def _run_phase(self, phase: str, batch: ColumnarBatch,
                   conf=None):
        from spark_rapids_tpu.columnar.column import LazyRows
        with self.metrics.timed("computeAggTime"):
            if phase == "update" and self.pre_steps:
                return self._run_folded_update(batch, conf)
            spec, vbatch, wrap = self._agg_view(phase, batch)
            flat, sig, decoder = _fused_decode_planes(vbatch)
            dense = None
            if phase == "update":
                dense = self._try_dense_update(spec, vbatch, wrap, conf,
                                               flat, sig, decoder)
            if dense is not None:
                # the partial has the shape of its key domain: every
                # possible key combination owns a slot, so the bound
                # cannot cut a group off
                (n_groups, key_outs, buf_outs), bound = dense
            else:
                fn = _compile_agg(spec, phase, sig, vbatch.capacity,
                                  decoder)
                n_groups, key_outs, buf_outs = fn(flat,
                                                  vbatch.rows_traced)
                # n_groups <= num_rows, except empty-input global agg
                bound = max(1, min(batch.rows_bound, batch.capacity))
            return _colvals_to_batch(
                list(key_outs) + list(buf_outs), self._buffer_dtypes(),
                LazyRows(n_groups, bound), wrap=wrap)

    def _folded_spec(self, coded) -> _AggSpec:
        """This aggregation over the output of the folded steps' last
        projection (keys first, then one input per function); ``coded``
        holds the key positions that arrive as dictionary codes."""
        nk = len(self.groupings)
        groupings = [
            BoundReference(i, INT32 if i in coded else g.dtype,
                           g.nullable, g.name)
            for i, g in enumerate(self.groupings)]
        aggs = [
            (n, f.with_children([BoundReference(
                nk + j, f.child.dtype, f.child.nullable, f.child.name)]))
            for j, (n, f) in enumerate(self.agg_pairs)]
        return _AggSpec(groupings, aggs)

    def _run_folded_update(self, batch: ColumnarBatch, conf):
        """One update over an UNFILTERED input batch: the folded steps
        and a last projection of this node's keys and inputs go through
        one code view (``encoding.stage_view``: predicates over
        dictionary columns become code-set membership, bare dictionary
        keys stay codes, as ``_agg_view`` would have them), literals
        hoist out of the key, and the program reduces under the steps'
        keep-mask (``_compile_folded_update``)."""
        from spark_rapids_tpu.columnar import encoding
        from spark_rapids_tpu.columnar.column import LazyRows
        from spark_rapids_tpu.exec.stage import hoist_steps, norm_rows
        nk = len(self.groupings)
        inputs = tuple(f.child for _, f in self.agg_pairs)
        tail = ("project", tuple(self.groupings) + inputs)
        view = encoding.stage_view(self.pre_steps + (tail,), batch,
                                   dense_tail=len(inputs))
        wrap = {i: d for i, d in view.wrap.items() if i < nk}
        spec = self._folded_spec(frozenset(wrap))
        h_steps, values = hoist_steps(view.steps)
        domain = self._dense_domain(
            spec, batch, wrap, conf, lambda: self._probe_unfolded(batch))
        if domain is not None:
            radices, bases, bound = domain
            self.metrics[METRIC_PALLAS_AGG_BATCHES].add(1)
        else:
            radices, bases = None, ()
            bound = max(1, min(batch.rows_bound, batch.capacity))
        fn = _compile_folded_update(h_steps, view.sig, view.aux_sig,
                                    batch.capacity, spec, radices)
        n_groups, key_outs, buf_outs = fn(
            view.flat, view.aux, norm_rows(batch), hoisted_args(values),
            np.asarray(bases, np.int64))
        self.metrics[METRIC_MASKED_FILTER_BATCHES].add(1)
        return _colvals_to_batch(
            list(key_outs) + list(buf_outs), self._buffer_dtypes(),
            LazyRows(n_groups, bound), wrap=wrap)

    def _probe_unfolded(self, batch: ColumnarBatch):
        """The one-integer-key range probe for a folded update.  Filters
        leave the column space alone, so the key's range over the
        unfiltered batch bounds the kept rows'; past a projection the
        key has no expression over the input, and the sorted body runs."""
        if any(kind != "filter" for kind, _ in self.pre_steps):
            return None
        spec, vbatch, _wrap = self._agg_view("update", batch)
        return self._probe_key_range(
            spec, vbatch, *_fused_decode_planes(vbatch, count=False))

    def _try_dense_update(self, spec: _AggSpec, vbatch: ColumnarBatch,
                          wrap, conf, flat, sig, decoder):
        """Sort-free update over a key domain the host knows (see
        exec/pallas_agg.py): ``((n_groups, keys, buffers), bound)`` with
        ``bound`` the exact domain size, or None -> take the
        sorted-segment kernel.  ``spec``/``vbatch``/``wrap`` are the code
        view of the batch (``_agg_view``)."""
        from spark_rapids_tpu.exec import pallas_agg as pag
        domain = self._dense_domain(
            spec, vbatch, wrap, conf,
            lambda: self._probe_key_range(spec, vbatch, flat, sig,
                                          decoder))
        if domain is None:
            return None
        radices, bases, bound = domain
        fn = pag.make_update(spec, sig, vbatch.capacity, radices,
                             decoder=decoder)
        out = fn(flat, vbatch.rows_traced, np.asarray(bases, np.int64))
        self.metrics[METRIC_PALLAS_AGG_BATCHES].add(1)
        return out, bound

    def _dense_domain(self, spec: _AggSpec, vbatch: ColumnarBatch, wrap,
                      conf, probe):
        """``(radices, bases, bound)`` of the dense update's key domain,
        or None when the host does not know one the kernel takes.

        The domain is known without a pull when every key is a
        dictionary-code view (radix ``dict.size + 1``, digit 0 the null
        key) or there are no keys; one bare integer key learns its range
        from ``probe()`` (memoized) instead, and the first batch whose
        range does not fit disables that probe for this exec so
        high-cardinality aggs don't pay a blocking range check (kernel +
        host sync) per batch."""
        from spark_rapids_tpu.exec import pallas_agg as pag
        if conf is None or not (pag.enabled(conf) and pag.supports(spec)):
            return None
        if vbatch.capacity > pag.max_capacity(spec):
            # per-spec exactness bound (int64-sum limb decomposition)
            return None
        coded = wrap or {}
        nk = len(spec.groupings)
        if len(coded) == nk:
            radices = [coded[i].size + 1 for i in range(nk)]
            bases = [0] * nk
            bound = math.prod(radices)
            if bound > pag.MAX_K:
                return None
        elif nk == 1 and not coded and vbatch.rows_bound > 0:
            rng = probe()
            if rng is None:
                return None
            lo, hi = rng
            radices, bases = [pag.range_radix(lo, hi)], [lo]
            bound = min(vbatch.rows_bound, hi - lo + 2)
        else:
            return None
        return radices, bases, bound

    def _probe_key_range(self, spec: _AggSpec, vbatch: ColumnarBatch,
                         flat, sig, decoder):
        """(lo, hi) of the one integer key when it fits the dense
        kernel, else None."""
        from spark_rapids_tpu.exec import pallas_agg as pag
        if getattr(self, "_pallas_off", False):
            return None
        # The range probe is a host sync (~100ms+ over a remote link).
        # Re-runs over device-cached scans hit the buffer memo for free,
        # but inputs that are fresh every run (e.g. join outputs) would
        # pay the sync each time — after 2 fresh-buffer misses for this
        # agg spec, the probe becomes memo-only (a later memo hit still
        # uses Pallas and resets the counter; only the PULL is gated).
        spec_key = spec.key()
        # at large capacities the sorted-segment fallback costs seconds
        # (bitonic at 2^22+), so the ~100ms probe sync is always worth
        # paying; the miss gate only governs small fast batches
        allow_pull = _PALLAS_FRESH_MISSES.get(spec_key, 0) < 2 or \
            vbatch.capacity >= (1 << 21)
        info: dict = {}
        rng = pag.key_range(spec.groupings[0], vbatch, info=info,
                            allow_pull=allow_pull, flat=flat, sig=sig,
                            decoder=decoder)
        if info.get("hit"):
            _PALLAS_FRESH_MISSES[spec_key] = 0
        elif info.get("pulled"):
            _PALLAS_FRESH_MISSES[spec_key] = \
                _PALLAS_FRESH_MISSES.get(spec_key, 0) + 1
        if rng is not None and not pag.fits(*rng):
            self._pallas_off = True
            return None
        return rng

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        def gen():
            from spark_rapids_tpu.memory.spill import (
                SpillableBatch, close_all, materialize_all,
            )
            cat = ctx.runtime.catalog
            # per-batch update partials accumulate through the spill
            # catalog (reference: partials are spillable between update
            # and merge, aggregate.scala:366-391)
            partials = []
            try:
                from spark_rapids_tpu.utils.retry import (
                    split_batch_half, with_retry,
                )
                for batch in self.children[0].execute_columnar(ctx):
                    # OOM -> spill-retry, then split rows and retry
                    # (reference RmmRapidsRetryIterator withRetry +
                    # SplitAndRetryOOM, aggregate.scala update path)
                    for part in with_retry(
                            lambda b: self._run_phase("update", b,
                                                      ctx.conf),
                            batch, ctx, split=split_batch_half):
                        partials.append(SpillableBatch(part, cat))
                if not partials:
                    if self.groupings:
                        return  # grouped agg of empty input -> no rows
                    # global agg of empty input emits initial values
                    # (reference aggregate.scala:406-419)
                    empty = _empty_input_batch(
                        self.children[0].output_schema)
                    partials.append(SpillableBatch(
                        self._run_phase("update", empty), cat))  # global agg: sorted path
            except BaseException:
                close_all(partials)
                raise
            many = len(partials) > 1
            materialized = materialize_all(partials, ctx)
            merged = materialized[0]
            if many:
                with self.metrics.timed("concatTime"):
                    merged = concat_batches(materialized)
                merged = self._run_phase("merge", merged)
            elif self.groupings:
                # single partial is already segment-reduced; merge is
                # idempotent, skip it
                pass
            # the finalize kernel passes key columns through untouched:
            # encoded keys flatten as codes and re-wrap on the way out
            # (the grouped result leaves this operator still encoded —
            # egress carries codes, docs/compressed.md)
            from spark_rapids_tpu.columnar import encoding as _enc
            ev_view = _enc.key_columns_code_view(merged,
                                                 len(self.groupings))
            ev_wrap = None
            ev_batch = merged
            if ev_view is not None:
                ev_batch, _overrides, ev_wrap = ev_view
            fn = _compile_evaluate(self.spec, _batch_signature(ev_batch),
                                   ev_batch.capacity)
            outs = fn(_flatten_batch(ev_batch), ev_batch.rows_traced)
            out_dtypes = [f.dtype for f in self._schema]
            yield _colvals_to_batch(outs, out_dtypes, merged.rows_raw,
                                    self._schema, wrap=ev_wrap)
        return self._count_output(gen())


def _empty_input_batch(schema: Schema) -> ColumnarBatch:
    cols = [DeviceColumn.full_null(f.dtype, 0) for f in schema]
    return ColumnarBatch(cols, 0, schema)
