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

Every update takes ONE route (``TpuHashAggregateExec._stage`` ->
``_update_group``): the filter / project chain the planner folded into
the node (plan/fusion.py, docs/fusion.md; empty when nothing was
folded) plus a last projection of the keys and inputs go through one
code view (``encoding.stage_view``), run MASKED inside the update's own
program (``exec.stage.emit_steps(..., compact=False)``) and the
keep-mask is the update's liveness, so a folded filter costs one
elementwise predicate and no compaction gather.
``_compile_folded_update`` is the one builder.  Input batches whose
dense updates share a program are updated as a GROUP, up to
``GROUP_MEMBERS`` in one launch that returns one partial a batch: what a
launch costs the host is paid once for all of them.

The body under that mask is chosen from what the host can see of the
batch (``_dense_domain``): a key domain the host already knows (every
key a dictionary code, no keys at all, or one integer key of small
probed range) skips the sort and reduces by direct address over K slots
(exec/pallas_agg.py); its partial is K slots long, so the concat, the
merge and everything downstream run at the domain's capacity, not the
input's.  Anything else takes the sorted body above.
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
    METRIC_GROUPED_UPDATE_BATCHES, METRIC_MASKED_FILTER_BATCHES,
    METRIC_PALLAS_AGG_BATCHES, METRIC_TOTAL_TIME,
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


def _compile_agg(spec: _AggSpec, phase: str, input_sig, capacity: int):
    cache_key = (spec.key(), phase, input_sig, capacity)
    fn = _AGG_CACHE.get(cache_key)
    if fn is not None:
        return fn
    fn = engine_jit(make_agg_body(spec, phase, capacity),
                    family="aggregate", name=phase)
    _AGG_CACHE[cache_key] = fn
    return fn


# most input batches one update launch takes (``execute_columnar``): a
# launch's host price is paid once for all of them, and a program is
# compiled per member count it was asked for
GROUP_MEMBERS = 8


def _compile_folded_update(h_steps, input_sig, aux_sig, capacity: int,
                           spec: _AggSpec, radices=None, members: int = 1):
    """The update program of a group of ``members`` input batches that
    share everything but their planes, and the only builder of one:
    ``h_steps`` (hoisted, code-viewed: the chain the planner folded in,
    possibly empty, then a projection of ``spec``'s keys and inputs) run
    MASKED over each member, then the update body reduces it under the
    steps' liveness — the dense body over ``radices``
    (exec/pallas_agg.py) or, with ``radices`` None, the sorted-segment
    body.  It returns one ``(n_groups, keys, buffers, valid)`` a member
    (a buffer whose validity is ``None`` is valid where ``valid`` says):
    what that many launches of the one-member program return.  Literals ride
    in as traced scalars and dictionary tables as aux inputs, so the key
    is literal-free: a new binding of a prepared query reuses the
    program.

    ``run(members_flat, members_aux, rows, hoisted, bases)``: ``rows``
    is ``int32[members]`` (or, where a count lives on the device, a
    tuple of scalars), ``bases`` ``int64[members, keys]``, and a hoisted
    slot is one scalar for every member or a vector with one value a
    member — the host's arguments do not grow with the group.  One
    member is traced alone; more are joined end to end inside the
    program and mapped (``lax.map``), so the body compiles once whatever
    the count
    (CHANGES.md, PR 32, has what that and an unrolled trace compiled
    in)."""
    from spark_rapids_tpu.exec.stage import emit_steps, stage_fingerprint
    dense = radices is not None
    cache_key = ("folded", stage_fingerprint(h_steps), input_sig, aux_sig,
                 capacity, spec.key(),
                 tuple(int(r) for r in radices) if dense else None,
                 members)
    fn = _AGG_CACHE.get(cache_key)
    if fn is not None:
        return fn
    if dense:
        from spark_rapids_tpu.exec import pallas_agg as pag
        body = pag.make_update_body(spec, capacity, radices)
    else:
        body = make_agg_body(spec, "update", capacity)

    def one(flat_cols, aux, num_rows, hoisted, bases):
        cols = [ColVal(*t) for t in flat_cols]
        # folded steps are deterministic: the partition id is unread
        cols, live = emit_steps(h_steps, cols, num_rows, capacity,
                                jnp.int64(0), hoisted, aux=aux,
                                compact=False)
        flat = tuple((c.data, c.validity, c.chars) for c in cols)
        n_groups, key_outs, buf_outs = body(flat, num_rows, bases, live) \
            if dense else body(flat, num_rows, live)
        # a partial's buffers are all valid where its groups are: that
        # plane leaves once, not once a buffer (``None`` stands for it).
        # An output buffer is the dearest thing in a launch, 46 us of
        # host time each on the chip (PERF.md, PR 32)
        valid = buf_outs[0].validity if buf_outs else None
        return n_groups, key_outs, tuple(
            ColVal(b.data, None if b.validity is valid else b.validity,
                   b.chars) for b in buf_outs), valid

    def run(members_flat, members_aux, rows, hoisted, bases):
        if members == 1:  # every slot is a scalar: nothing to bind by
            return (one(members_flat[0], members_aux[0], rows[0], hoisted,
                        bases[0]),)
        # joined and mapped, not unrolled: the body compiles once
        # whatever the count.  Members are joined end to end along the
        # rows, so that each is a run of whole tiles: stacked under a
        # new leading axis that axis is tiled too, and a member is read
        # in strides through it (PERF.md, PR 32)
        def join(*planes):
            return jnp.concatenate(planes)

        def part(m):
            def of(joined):
                n = joined.shape[0] // members
                return jax.lax.dynamic_slice_in_dim(joined, m * n, n)
            return of

        flat = jax.tree_util.tree_map(join, *members_flat)
        aux = jax.tree_util.tree_map(join, *members_aux)
        rows = rows if hasattr(rows, "ndim") else jnp.stack(rows)

        def member(m):
            return one(jax.tree_util.tree_map(part(m), flat),
                       jax.tree_util.tree_map(part(m), aux), rows[m],
                       tuple(h if h.ndim == 0 else h[m] for h in hoisted),
                       bases[m])

        outs = jax.lax.map(member, jnp.arange(members, dtype=jnp.int32))
        return tuple(jax.tree_util.tree_map(lambda a: a[m], outs)
                     for m in range(members))

    fn = engine_jit(run, family="aggregate",
                    name="masked_pallas_update" if dense
                    else "masked_update")
    _AGG_CACHE[cache_key] = fn
    return fn


def _hoisted_by_member(values: Sequence[tuple]) -> tuple:
    """``hoisted_args`` for a group whose members bind other literals
    to the same slots (``values``: one ``hoist_steps`` tuple a member):
    a slot every member binds alike goes once, as ``hoisted_args`` sends
    it; any other as one vector, a value a member."""
    from spark_rapids_tpu.columnar.dtypes import device_dtype
    out = []
    for slot in zip(*values):
        vals = [v for v, _ in slot]
        same = all(v == vals[0] for v in vals[1:])
        out.append(np.asarray(vals[0] if same else vals,
                              device_dtype(slot[0][1])))
    return tuple(out)


class _Member:
    """One input batch's update as the host staged it
    (``TpuHashAggregateExec._stage``), without the batch's planes: the
    batch may wait for its group as a spillable handle, and
    ``encoding.stage_planes`` reads the planes again at the launch."""

    __slots__ = ("steps", "steps_key", "sig", "aux", "aux_sig", "capacity",
                 "wrap", "spec", "radices", "bases", "bound", "_hoisted")

    def __init__(self, view, capacity: int, wrap, spec: _AggSpec, radices,
                 bases, bound: int):
        from spark_rapids_tpu.exec.stage import stage_fingerprint
        self.steps = view.steps
        # literals and all: equal keys bind equal values
        self.steps_key = stage_fingerprint(view.steps)
        self.sig, self.aux, self.aux_sig = view.sig, view.aux, view.aux_sig
        self.capacity = capacity
        self.wrap = wrap          # {key position -> DictPlanes}
        self.spec = spec
        self.radices = None if radices is None \
            else tuple(int(r) for r in radices)
        self.bases = tuple(bases)
        self.bound = bound
        self._hoisted = None

    def hoisted(self):
        """``(h_steps, values)``: the steps with their literals hoisted
        into slots, and the values this member binds to them."""
        if self._hoisted is None:
            from spark_rapids_tpu.exec.stage import hoist_steps
            self._hoisted = hoist_steps(self.steps)
        return self._hoisted

    def shares_program(self, other: "_Member") -> bool:
        """Whether ``other``'s update can ride this member's launch:
        both dense, and everything ``_compile_folded_update`` keys on
        equal — signatures, capacity, spec (the coded key positions
        decide it), radices and the hoisted steps.  Steps that differ
        in a literal only still share: the slot binds by member."""
        from spark_rapids_tpu.exec.stage import stage_fingerprint
        if self.radices is None or other.radices != self.radices \
                or (other.sig, other.aux_sig, other.capacity) \
                != (self.sig, self.aux_sig, self.capacity) \
                or sorted(other.wrap) != sorted(self.wrap):
            return False
        return other.steps_key == self.steps_key or \
            stage_fingerprint(other.hoisted()[0]) \
            == stage_fingerprint(self.hoisted()[0])


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
        # set by the first batch whose one-integer-key range does not
        # fit the dense kernel: this node stops probing
        self._pallas_off = False
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

    def _update_spec(self, coded) -> _AggSpec:
        """This aggregation over the output of the update's last
        projection (keys first, then one input per function); ``coded``
        is keyed by the key positions that arrive as dictionary codes."""
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

    def _stage(self, batch: ColumnarBatch, conf=None) -> "_Member":
        """What the host decides about one input batch's update, and
        the only route to one: the folded steps (none, when the planner
        folded nothing) and a last projection of this node's keys and
        inputs go through one code view (``encoding.stage_view``:
        predicates over dictionary columns become code-set membership,
        bare dictionary keys stay codes and re-wrap on the way out,
        plane-compressed columns decode in-kernel), and the body is
        dense over the key domain the host knows, else sorted."""
        from spark_rapids_tpu.columnar import encoding
        with self.metrics.timed("computeAggTime"):
            nk = len(self.groupings)
            inputs = tuple(f.child for _, f in self.agg_pairs)
            tail = ("project", tuple(self.groupings) + inputs)
            view = encoding.stage_view(self.pre_steps + (tail,), batch,
                                       dense_tail=len(inputs))
            wrap = {i: d for i, d in view.wrap.items() if i < nk}
            spec = self._update_spec(wrap)
            domain = self._dense_domain(spec, batch, wrap, conf, view)
            if domain is not None:
                # the partial has the shape of its key domain: every
                # possible key combination owns a slot, so the bound
                # cannot cut a group off
                radices, bases, bound = domain
            else:
                radices, bases = None, ()
                # n_groups <= num_rows, except empty-input global agg
                bound = max(1, min(batch.rows_bound, batch.capacity))
            return _Member(view, batch.capacity, wrap, spec, radices,
                           bases, bound)

    def _update_group(self, members: Sequence["_Member"],
                      batches: Sequence[ColumnarBatch]
                      ) -> List[ColumnarBatch]:
        """One launch over ``batches`` as ``members`` staged them (they
        share a program: ``_Member.shares_program``), one partial a
        member: literals hoist out of the key once for the group, and
        the program reduces each member under its steps' keep-mask
        (``_compile_folded_update``)."""
        from spark_rapids_tpu.columnar.column import LazyRows
        from spark_rapids_tpu.columnar.encoding import stage_planes
        from spark_rapids_tpu.exec.stage import norm_rows
        with self.metrics.timed("computeAggTime"):
            head, n = members[0], len(members)
            h_steps, values = head.hoisted()
            fn = _compile_folded_update(h_steps, head.sig, head.aux_sig,
                                        head.capacity, head.spec,
                                        head.radices, n)
            if all(m.steps_key == head.steps_key for m in members[1:]):
                hoisted = hoisted_args(values)
            else:
                hoisted = _hoisted_by_member(
                    [m.hoisted()[1] for m in members])
            rows = [norm_rows(b) for b in batches]
            # one host array, unless a count lives on the device (an
            # eager stack would be a device op of its own)
            rows = np.asarray(rows, np.int32) \
                if all(isinstance(r, np.integer) for r in rows) \
                else tuple(rows)
            outs = fn(tuple(stage_planes(b)[0] for b in batches),
                      tuple(m.aux for m in members), rows, hoisted,
                      np.asarray([m.bases for m in members], np.int64))
            if head.radices is not None:
                self.metrics[METRIC_PALLAS_AGG_BATCHES].add(n)
            if self.pre_steps:
                self.metrics[METRIC_MASKED_FILTER_BATCHES].add(n)
            if n > 1:
                self.metrics[METRIC_GROUPED_UPDATE_BATCHES].add(n)
            return [
                _colvals_to_batch(
                    list(key_outs) + [
                        b if b.validity is not None
                        else ColVal(b.data, valid, b.chars)
                        for b in buf_outs],
                    self._buffer_dtypes(), LazyRows(n_groups, m.bound),
                    wrap=m.wrap)
                for m, (n_groups, key_outs, buf_outs, valid)
                in zip(members, outs)]

    def _run_update(self, batch: ColumnarBatch, conf=None):
        """One update over one input batch: a group of one."""
        return self._update_group([self._stage(batch, conf)], [batch])[0]

    def _update_with_retry(self, members, batches, ctx):
        """OOM -> spill-retry, then split rows and retry (reference
        RmmRapidsRetryIterator withRetry + SplitAndRetryOOM,
        aggregate.scala update path).  A group retries whole after the
        spill; one that still does not fit is given up, and its members
        go one a launch, where the rows split to the full depth."""
        from spark_rapids_tpu.utils.retry import (
            is_device_oom, split_batch_half, with_retry,
        )
        if len(members) > 1:
            try:
                (parts,) = with_retry(
                    lambda bs: self._update_group(members, bs), batches,
                    ctx)
                return parts
            except Exception as e:
                if not is_device_oom(e):
                    raise
        parts = []
        for m, b in zip(members, batches):
            # a half is another batch: it is staged anew
            parts.extend(with_retry(
                lambda x: self._update_group(
                    [m if x is b else self._stage(x, ctx.conf)], [x])[0],
                b, ctx, split=split_batch_half))
        return parts

    def _run_merge(self, batch: ColumnarBatch):
        """Merge concatenated partials: the sorted body with the merge
        ops.  Encoded key columns group by their CODES (ranks, so
        boundaries and output order are byte-identical to grouping the
        strings) and the key output stays encoded."""
        from spark_rapids_tpu.columnar import encoding
        from spark_rapids_tpu.columnar.column import LazyRows
        with self.metrics.timed("computeAggTime"):
            spec, wrap = self.spec, None
            view = encoding.key_columns_code_view(batch,
                                                  len(self.groupings))
            if view is not None:
                batch, overrides, wrap = view
                spec = _AggSpec([
                    BoundReference(ki, overrides[ki], g.nullable, g.name)
                    if ki in overrides else g
                    for ki, g in enumerate(self.groupings)],
                    self.agg_pairs)
            fn = _compile_agg(spec, "merge", _batch_signature(batch),
                              batch.capacity)
            n_groups, key_outs, buf_outs = fn(_flatten_batch(batch),
                                              batch.rows_traced)
            return _colvals_to_batch(
                list(key_outs) + list(buf_outs), self._buffer_dtypes(),
                LazyRows(n_groups,
                         max(1, min(batch.rows_bound, batch.capacity))),
                wrap=wrap)

    def _dense_domain(self, spec: _AggSpec, batch: ColumnarBatch, wrap,
                      conf, view):
        """``(radices, bases, bound)`` of the dense update's key domain,
        or None when the host does not know one the kernel takes.

        The domain is known without a pull when every key is a
        dictionary-code view (radix ``dict.size + 1``, digit 0 the null
        key) or there are no keys; one bare integer key learns its range
        from ``_probe_key_range`` (memoized) instead."""
        from spark_rapids_tpu.exec import pallas_agg as pag
        if conf is None or not (pag.enabled(conf) and pag.supports(spec)):
            return None
        if batch.capacity > pag.max_capacity(spec):
            # per-spec exactness bound (int64-sum limb decomposition)
            return None
        nk = len(spec.groupings)
        if len(wrap) == nk:
            radices = [wrap[i].size + 1 for i in range(nk)]
            bases = [0] * nk
            bound = math.prod(radices)
            if bound > pag.MAX_K:
                return None
        elif nk == 1 and batch.rows_bound > 0:
            rng = self._probe_key_range(view, batch)
            if rng is None:
                return None
            lo, hi = rng
            radices, bases = [pag.range_radix(lo, hi)], [lo]
            bound = min(batch.rows_bound, hi - lo + 2)
        else:
            return None
        return radices, bases, bound

    def _probe_key_range(self, view, batch: ColumnarBatch):
        """(lo, hi) of the one integer key over the input batch when it
        fits the dense kernel, else None.  Filters leave the column
        space alone, so the key's range over the unfiltered batch bounds
        the kept rows'; past a projection the key has no expression over
        the input, and the sorted body runs.  A re-run over the scan
        cache hits the buffer memo and pulls nothing; a fresh batch pays
        one launch and one pull, which the first batch whose range does
        not fit stops for this exec, so a high-cardinality aggregate
        does not pay a blocking range check per batch."""
        from spark_rapids_tpu.exec import pallas_agg as pag
        if self._pallas_off or \
                any(kind != "filter" for kind, _ in self.pre_steps):
            return None
        rng = pag.key_range(view, batch)
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
            # the open group: batches whose updates share a program wait
            # here for one launch, spillable while they wait as the
            # coalesce's pending batches are (exec/coalesce.py), their
            # staging beside them
            members, waiting = [], []

            def update(ms, batches):
                for part in self._update_with_retry(ms, batches, ctx):
                    partials.append(SpillableBatch(part, cat))

            def close_group():
                if members:
                    ms, handles = members[:], waiting[:]
                    del members[:], waiting[:]
                    update(ms, materialize_all(handles, ctx))

            try:
                for batch in self.children[0].execute_columnar(ctx):
                    m = self._stage(batch, ctx.conf)
                    if members and not members[0].shares_program(m):
                        close_group()
                    if m.radices is None:
                        # the sorted body: a launch is device work, not
                        # host price, and nothing waits for a group
                        update([m], [batch])
                        continue
                    members.append(m)
                    waiting.append(SpillableBatch(batch, cat))
                    if len(members) >= GROUP_MEMBERS:
                        close_group()
                close_group()
                if not partials:
                    if self.groupings:
                        return  # grouped agg of empty input -> no rows
                    # global agg of empty input emits initial values
                    # (reference aggregate.scala:406-419)
                    empty = _empty_input_batch(
                        self.children[0].output_schema)
                    partials.append(SpillableBatch(
                        self._run_update(empty), cat))  # sorted body
            except BaseException:
                close_all(waiting)
                close_all(partials)
                raise
            many = len(partials) > 1
            materialized = materialize_all(partials, ctx)
            merged = materialized[0]
            if many:
                with self.metrics.timed("concatTime"):
                    merged = concat_batches(materialized)
                merged = self._run_merge(merged)
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
