"""Window exec: one fused kernel per (spec, functions, signature).

Reference: GpuWindowExec.scala:92-210 + GpuWindowExpression.scala:110-232 —
the reference lowers each window function to cuDF rolling/scan aggregations
over sorted partition groups.

TPU design: sort once by (partition keys, order keys) with the sortable-int
machinery, derive all frame geometry as vectors (segment start/end, peer
group start/end via ``jax.ops.segment_max`` broadcasts), then evaluate
every window function with three shape-static primitives XLA fuses freely:

  * global inclusive prefix sums for count/sum/avg over any frame (frame
    bounds are clamped inside the segment, so cross-segment terms cancel);
  * segmented arg-select scans (``lax.associative_scan`` forward/reverse
    over (select-key, row-index) pairs) for min/max/first/last and running
    frames — floats select on order-preserving int bitcasts so Spark's
    NaN-greatest ordering holds;
  * a sparse-table range-min query (log2(cap) doubling levels, two
    gathers per row) for doubly-bounded min/max rows and offset
    RANGE frames.

Results scatter back to the original row order through the sort
permutation, so the exec appends window columns without reordering input.
"""

from __future__ import annotations

from typing import Iterator, List, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.compile.service import engine_jit
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.columnar.dtypes import (
    DataType, Field, Schema, BOOLEAN, FLOAT32, FLOAT64, INT32, INT64,
    device_dtype,
)
from spark_rapids_tpu.exec.base import ExecContext, TpuExec
from spark_rapids_tpu.exec.coalesce import concat_batches
from spark_rapids_tpu.exec.sortkeys import (
    colval_sort_keys, sort_permutation,
)
from spark_rapids_tpu.exprs.base import (
    ColVal, EvalContext, _batch_signature, _flatten_batch,
)
from spark_rapids_tpu.exprs.aggregates import (
    Count, Sum, Min, Max, Average, First, Last,
)
from spark_rapids_tpu.exprs.windows import (
    WindowExpression, RowNumber, Rank, DenseRank, Lag, Lead,
)
from spark_rapids_tpu.utils.metrics import METRIC_TOTAL_TIME
from spark_rapids_tpu.utils.pscan import prefix_sum



def _select_keys(vals: jnp.ndarray, dtype: DataType, for_max: bool):
    """Value column -> (rank int32, key) pair selected by lexicographic
    MIN.  Floats stay floats (the TPU x64 rewriter cannot lower 64-bit
    bitcast_convert, so no int bit tricks): the rank key settles NaN —
    for min NaN loses (rank 1), for max NaN wins (rank 0) — matching
    Spark's NaN-greatest ordering; ints/dates/bools select on the value
    itself (bitwise NOT for max, which is order-inverting and safe at
    INT64_MIN where negation is not)."""
    cap = vals.shape[0]
    if dtype in (FLOAT32, FLOAT64):
        isnan = jnp.isnan(vals)
        canon = jnp.where(isnan, jnp.zeros_like(vals), vals)
        canon = jnp.where(canon == 0, jnp.zeros_like(canon), canon)
        if for_max:
            return jnp.where(isnan, 0, 1).astype(jnp.int32), -canon
        return isnan.astype(jnp.int32), canon
    k = vals.astype(jnp.int64)
    if for_max:
        k = ~k
    return jnp.zeros(cap, jnp.int32), k


def _seg_argmin_scan(flags: jnp.ndarray, valid: jnp.ndarray,
                     k1: jnp.ndarray, k2: jnp.ndarray, idx: jnp.ndarray,
                     reverse: bool = False):
    """Segmented inclusive arg-min scan over VALID elements, selecting by
    the lexicographic (k1, k2) pair.

    forward: out[i] = (any_valid, min pair's row index) over
    [segment_start, i]; reverse: same over [i, segment_end].
    ``flags`` marks segment STARTS (forward orientation) in both cases.
    Validity is an explicit carried flag, so no sentinel key is needed."""
    if reverse:
        end_flags = jnp.concatenate(
            [flags[1:], jnp.ones(1, dtype=jnp.bool_)])
        v, i = _seg_argmin_scan(end_flags[::-1], valid[::-1],
                                k1[::-1], k2[::-1], idx[::-1])
        return v[::-1], i[::-1]

    def combine(a, b):
        fa, va, ka1, ka2, ia = a
        fb, vb, kb1, kb2, ib = b
        # within a segment prefer the valid operand, then the smaller
        # (k1, k2); a reset (fb) discards the accumulated left operand
        smaller = (kb1 < ka1) | ((kb1 == ka1) & (kb2 <= ka2))
        better_b = (vb & ~va) | (vb & va & smaller)
        take_b = fb | better_b
        return (fa | fb,
                jnp.where(fb, vb, va | vb),
                jnp.where(take_b, kb1, ka1),
                jnp.where(take_b, kb2, ka2),
                jnp.where(take_b, ib, ia))

    _, v, _, _, i = jax.lax.associative_scan(
        combine, (flags, valid, k1, k2, idx))
    return v, i


class _Geometry:
    """Per-sorted-row frame geometry vectors."""

    __slots__ = ("pos", "live", "seg_start", "seg_end", "peer_start",
                 "peer_end", "peer_gid", "boundary", "gid", "order_cv",
                 "order_asc")


def _build_geometry(part_keys, order_keys, live_s, cap: int) -> _Geometry:
    pos = jnp.arange(cap, dtype=jnp.int64)
    neq_part = jnp.zeros(cap, jnp.bool_)
    for k in part_keys:
        prev = jnp.concatenate([k[:1], k[:-1]])
        neq_part = neq_part | (k != prev)
    boundary = (neq_part | (pos == 0)) & live_s
    gid = jnp.clip(prefix_sum(boundary.astype(jnp.int32)) - 1, 0, cap - 1)

    neq_order = neq_part
    for k in order_keys:
        prev = jnp.concatenate([k[:1], k[:-1]])
        neq_order = neq_order | (k != prev)
    oboundary = (neq_order | (pos == 0)) & live_s
    pgid = jnp.clip(prefix_sum(oboundary.astype(jnp.int32)) - 1, 0, cap - 1)

    def broadcast(flag_pos, seg_ids):
        per_seg = jax.ops.segment_max(flag_pos, seg_ids,
                                      num_segments=cap)
        return jnp.take(per_seg, seg_ids)

    g = _Geometry()
    g.pos = pos
    g.live = live_s
    g.boundary = boundary
    g.gid = gid
    g.seg_start = broadcast(jnp.where(boundary, pos, -1), gid)
    g.seg_end = broadcast(jnp.where(live_s, pos, -1), gid)
    g.peer_start = broadcast(jnp.where(oboundary, pos, -1), pgid)
    g.peer_end = broadcast(jnp.where(live_s, pos, -1), pgid)
    g.peer_gid = pgid.astype(jnp.int64)
    return g


def _bounded_search(vals: jnp.ndarray, targets: jnp.ndarray,
                    lo_b: jnp.ndarray, hi_b: jnp.ndarray,
                    side_left: bool, cap: int):
    """Per-row binary search with per-row bounds: smallest j in
    [lo_b, hi_b] with vals[j] >= target (side_left) or > target (right);
    returns hi_b + 1 when no such j.  vals must be ascending within each
    [lo_b, hi_b] window (they are: sorted order-column values inside one
    segment's non-null run)."""
    steps = max(1, cap.bit_length()) + 1
    # statically unrolled: a fori_loop's big carries land in HOST memory
    # space on the remote-attached TPU runtime and round-trip the link
    # every iteration (see exec/joins.py _left_search)
    lo, hi = lo_b, hi_b + 1
    for _ in range(steps):
        searching = lo < hi
        mid = (lo + hi) // 2
        mv = jnp.take(vals, jnp.clip(mid, 0, cap - 1))
        go_right = (mv < targets) if side_left else (mv <= targets)
        lo = jnp.where(searching & go_right, mid + 1, lo)
        hi = jnp.where(searching & ~go_right, mid, hi)
    return lo


def _range_offset_bounds(fr, g: _Geometry, cap: int):
    """Value-based frame bounds for RANGE BETWEEN x PRECEDING AND y
    FOLLOWING over the (single) order column, composed per side to match
    Spark: an UNBOUNDED side is POSITIONAL (the partition edge, null/NaN
    rows included); a bounded side binary-searches the sorted non-special
    values for normal rows and snaps to the peer-group edge for null/NaN
    rows (NaN +- x = NaN, so such rows see exactly their peers)."""
    cv = g.order_cv
    v = cv.data
    if jnp.issubdtype(v.dtype, jnp.floating):
        special = ~cv.validity | jnp.isnan(v)
        vv = jnp.where(special, jnp.zeros_like(v), v)
    else:
        special = ~cv.validity
        vv = v
    if not g.order_asc:
        vv = -vv
    pos = g.pos
    # [first, last] non-special position per segment: the searchable run
    # (a normal row is itself in the run, so it is never empty for rows
    # that search)
    ok = (~special) & g.live
    first_ok = _per_segment_broadcast(jnp.where(ok, pos, cap), g, True)
    last_ok = _per_segment_broadcast(jnp.where(ok, pos, -1), g, False)
    lo_b = jnp.clip(first_ok, 0, cap - 1)
    hi_b = jnp.clip(last_ok, 0, cap - 1)

    if fr.lower is None:
        lo_c = g.seg_start
    else:
        lo_c = _bounded_search(vv, vv + fr.lower, lo_b, hi_b, True, cap)
        lo_c = jnp.where(special, g.peer_start, lo_c)
    if fr.upper is None:
        hi_c = g.seg_end
    else:
        hi_c = _bounded_search(vv, vv + fr.upper, lo_b, hi_b, False,
                               cap) - 1
        hi_c = jnp.where(special, g.peer_end, hi_c)
    nonempty = (lo_c <= hi_c) & g.live
    return lo_c, hi_c, nonempty


def _per_segment_broadcast(masked_pos: jnp.ndarray, g: _Geometry,
                           take_min: bool):
    """Reduce masked positions per segment and broadcast back per row."""
    cap = masked_pos.shape[0]
    red = jax.ops.segment_min if take_min else jax.ops.segment_max
    per = red(masked_pos, g.gid, num_segments=cap)
    return jnp.take(per, g.gid)


def _frame_bounds(wexpr: WindowExpression, g: _Geometry, cap: int):
    fr = wexpr.frame
    if fr.is_whole_partition:
        lo, hi = g.seg_start, g.seg_end
    elif fr.is_default_range:
        lo, hi = g.seg_start, g.peer_end
    elif fr.kind == "range":
        return _range_offset_bounds(fr, g, cap)
    else:  # rows frame with literal offsets
        lo = g.seg_start if fr.lower is None else g.pos + fr.lower
        hi = g.seg_end if fr.upper is None else g.pos + fr.upper
    lo_c = jnp.maximum(lo, g.seg_start)
    hi_c = jnp.minimum(hi, g.seg_end)
    nonempty = (lo_c <= hi_c) & g.live
    return lo_c, hi_c, nonempty


def _prefix_frame_sum(contrib: jnp.ndarray, lo_c, hi_c, cap: int):
    """sum(contrib[lo_c..hi_c]) via one global inclusive prefix sum (frame
    bounds never cross segment borders, so no segmentation is needed)."""
    p = prefix_sum(contrib)
    hi_v = jnp.take(p, jnp.clip(hi_c, 0, cap - 1))
    lo_v = jnp.where(lo_c > 0,
                     jnp.take(p, jnp.clip(lo_c - 1, 0, cap - 1)),
                     jnp.zeros_like(hi_v))
    return hi_v - lo_v


def _select_in_frame(valid_s, k1, k2, vals_s, g: _Geometry, lo_c, hi_c,
                     lower, upper, cap: int, static_width: int = 0):
    """Arg-select (lexicographic min (k1, k2) among valid rows) over the
    frame; returns (value, found).

    Strategy by frame shape:
      lower unbounded -> forward scan gathered at hi;
      upper unbounded -> reverse scan gathered at lo;
      both bounded    -> sparse-table range-min query at [lo_c, hi_c]
      (``static_width`` caps the table depth for static ROWS frames)."""
    pos = jnp.arange(cap, dtype=jnp.int64)
    if lower is None:
        v, i = _seg_argmin_scan(g.boundary, valid_s, k1, k2, pos)
        at = jnp.clip(hi_c, 0, cap - 1)
    elif upper is None:
        v, i = _seg_argmin_scan(g.boundary, valid_s, k1, k2, pos,
                                reverse=True)
        at = jnp.clip(lo_c, 0, cap - 1)
    else:
        # doubly-bounded frame (rows offsets or value-searched RANGE
        # bounds): sparse-table range-min query at the clamped bounds
        found, ii = _rmq_argmin(valid_s, k1, k2, lo_c, hi_c, cap,
                                max_width=static_width)
        value = jnp.take(vals_s, jnp.clip(ii, 0, cap - 1), axis=0)
        return value, found
    found = jnp.take(v, at)
    ii = jnp.take(i, at)
    value = jnp.take(vals_s, jnp.clip(ii, 0, cap - 1), axis=0)
    return value, found


def _rmq_argmin(valid_s, k1, k2, lo_c, hi_c, cap: int,
                max_width: int = 0):
    """Arg-select (lexicographic min over (valid-rank, k1, k2)) for
    ARBITRARY per-row frames [lo_c, hi_c] via a sparse table (range-min
    query): log2(cap) doubling levels built once (each a shift + select),
    then every row answers with two gathers from the level floor(log2 L).
    This is the TPU answer to cuDF's sliding-window min/max for offset
    RANGE and wide bounded ROWS frames (reference
    GpuWindowExpression.scala bounded frames): O(n log n) build shared by
    all rows instead of a per-row O(width) loop, every shape static.

    Queries must not cross segment borders (frame bounds are clamped to
    the partition by construction), so the table ignores segmentation.
    Returns (found, winning row index).

    ``max_width`` > 0 (a static ROWS frame's width) caps the table depth
    at ceil(log2(width)) levels — a 3-row frame builds 2 levels, not
    log2(cap) — while 0 (dynamic value-searched RANGE bounds) builds the
    full table."""
    levels = max(1, cap.bit_length() - 1)
    if max_width > 0:
        levels = min(levels, max(1, (max_width - 1).bit_length()))
    f0 = jnp.where(valid_s, 0, 1).astype(jnp.int32)
    i0 = jnp.arange(cap, dtype=jnp.int32)
    fs, k1s, k2s, idxs = [f0], [k1], [k2], [i0]
    f, a, b, i = f0, k1, k2, i0
    for lev in range(1, levels + 1):
        sh = 1 << (lev - 1)
        fp = jnp.concatenate([f[sh:], jnp.full((sh,), 2, f.dtype)])
        ap = jnp.concatenate([a[sh:], a[:sh]])  # flag 2 never wins
        bp = jnp.concatenate([b[sh:], b[:sh]])
        ip = jnp.concatenate([i[sh:], i[:sh]])
        better = (fp < f) | ((fp == f) &
                             ((ap < a) | ((ap == a) & (bp < b))))
        f = jnp.where(better, fp, f)
        a = jnp.where(better, ap, a)
        b = jnp.where(better, bp, b)
        i = jnp.where(better, ip, i)
        fs.append(f)
        k1s.append(a)
        k2s.append(b)
        idxs.append(i)
    F, K1, K2, I = (jnp.stack(x) for x in (fs, k1s, k2s, idxs))
    L = (hi_c - lo_c + 1).astype(jnp.int32)
    k = 31 - jax.lax.clz(jnp.maximum(L, 1))
    base = k * cap
    p1 = base + jnp.clip(lo_c, 0, cap - 1).astype(jnp.int32)
    p2 = base + jnp.clip(
        hi_c + 1 - jnp.left_shift(jnp.int64(1), k.astype(jnp.int64)),
        0, cap - 1).astype(jnp.int32)

    def gat(m, p):
        return jnp.take(m.reshape(-1), p)

    f1, a1, b1, i1 = gat(F, p1), gat(K1, p1), gat(K2, p1), gat(I, p1)
    f2, a2, b2, i2 = gat(F, p2), gat(K1, p2), gat(K2, p2), gat(I, p2)
    two = (f2 < f1) | ((f2 == f1) &
                       ((a2 < a1) | ((a2 == a1) & (b2 < b1))))
    fw = jnp.where(two, f2, f1)
    iw = jnp.where(two, i2, i1)
    return (fw == 0) & (L > 0), iw


def _eval_one(wexpr: WindowExpression, g: _Geometry, ctx: EvalContext,
              perm, cap: int):
    """-> (data_sorted, valid_sorted) for one window function."""
    f = wexpr.func
    live = g.live

    if isinstance(f, RowNumber):
        return (g.pos - g.seg_start + 1).astype(jnp.int32), live
    if isinstance(f, Rank):
        return (g.peer_start - g.seg_start + 1).astype(jnp.int32), live
    if isinstance(f, DenseRank):
        first_pg = jnp.take(g.peer_gid,
                            jnp.clip(g.seg_start, 0, cap - 1))
        return (g.peer_gid - first_pg + 1).astype(jnp.int32), live

    if isinstance(f, (Lag, Lead)):
        cv = f.child.emit(ctx)
        from spark_rapids_tpu.columnar.gatherfab import gather_planes
        _lg = gather_planes([cv.data, cv.validity], perm)
        vals_s, valid_s = _lg[0], _lg[1]
        # NB: Lead subclasses Lag, so test the subclass first
        off = f.offset if isinstance(f, Lead) else -f.offset
        src = g.pos + off
        inb = (src >= g.seg_start) & (src <= g.seg_end) & live
        srcc = jnp.clip(src, 0, cap - 1)
        data = jnp.take(vals_s, srcc, axis=0)
        valid = inb & jnp.take(valid_s, srcc)
        if f.has_default:
            dflt = f.default.emit(ctx)
            data = jnp.where(inb, data,
                             dflt.data.astype(data.dtype))
            valid = jnp.where(inb, valid, dflt.validity & live)
        return data.astype(device_dtype(wexpr.dtype)), valid

    # aggregates over a frame
    proj = f.input_projection()[0]
    cv = proj.emit(ctx)
    from spark_rapids_tpu.columnar.gatherfab import gather_planes
    _vg = gather_planes([cv.data, cv.validity], perm)
    vals_s = _vg[0]
    valid_s = _vg[1] & live
    lo_c, hi_c, nonempty = _frame_bounds(wexpr, g, cap)
    fr = wexpr.frame
    if fr.kind == "range" and not (fr.is_whole_partition
                                   or fr.is_default_range):
        # value-based bounds: sums/counts use prefix sums, first/last
        # position-checked scans, min/max the sparse-table RMQ — all
        # exact at arbitrary [lo_c, hi_c]
        lower, upper = -1, 1  # any bounded pair: strategies below only
        # use lo_c/hi_c for these functions
    elif fr.is_whole_partition or fr.is_default_range:
        # lo is the segment start, so the forward-scan strategy (gather at
        # hi_c, which _frame_bounds set to seg_end / peer_end) is exact;
        # upper only needs to be non-None to select that strategy
        lower, upper = None, 0
    else:
        lower, upper = fr.lower, fr.upper

    if isinstance(f, Count):
        contrib = valid_s.astype(jnp.int64)
        cnt = _prefix_frame_sum(contrib, lo_c, hi_c, cap)
        cnt = jnp.where(nonempty, cnt, jnp.zeros_like(cnt))
        return cnt, live

    if isinstance(f, (Sum, Average)):
        acc_dt = device_dtype(FLOAT64) if isinstance(f, Average) or \
            f.dtype.is_floating else jnp.int64
        contrib = jnp.where(valid_s, vals_s.astype(acc_dt),
                            jnp.zeros(cap, acc_dt))
        s = _prefix_frame_sum(contrib, lo_c, hi_c, cap)
        cnt = _prefix_frame_sum(valid_s.astype(jnp.int64), lo_c, hi_c, cap)
        ok = nonempty & (cnt > 0)
        if isinstance(f, Average):
            denom = jnp.where(ok, cnt, 1).astype(device_dtype(FLOAT64))
            return s / denom, ok
        return s.astype(device_dtype(wexpr.dtype)), ok

    if isinstance(f, (Min, Max)):
        k1, k2 = _select_keys(vals_s, proj.dtype, isinstance(f, Max))
        # static ROWS frames cap the RMQ table depth at their width;
        # value-searched RANGE bounds (dynamic) build the full table
        sw = 0
        if fr.kind == "rows" and fr.lower is not None and \
                fr.upper is not None:
            sw = max(1, int(fr.upper) - int(fr.lower) + 1)
        value, found = _select_in_frame(
            valid_s, k1, k2, vals_s, g, lo_c, hi_c, lower, upper, cap,
            static_width=sw)
        return value.astype(device_dtype(wexpr.dtype)), nonempty & found

    if isinstance(f, (First, Last)):
        pos = jnp.arange(cap, dtype=jnp.int64)
        zero_rank = jnp.zeros(cap, jnp.int32)
        if isinstance(f, First):
            # earliest valid row >= lo: reverse scan of pos, gathered at
            # lo, then checked against hi (exact for every frame shape);
            # the selected row index IS the winning position
            v, i = _seg_argmin_scan(g.boundary, valid_s, zero_rank,
                                    g.pos, pos, reverse=True)
            at = jnp.clip(lo_c, 0, cap - 1)
            found = jnp.take(v, at)
            sel = jnp.take(i, at)
            ok = nonempty & found & (sel <= hi_c)
        else:
            # latest valid row <= hi: forward scan of -pos, gathered at hi
            v, i = _seg_argmin_scan(g.boundary, valid_s, zero_rank,
                                    -g.pos, pos)
            at = jnp.clip(hi_c, 0, cap - 1)
            found = jnp.take(v, at)
            sel = jnp.take(i, at)
            ok = nonempty & found & (sel >= lo_c)
        data = jnp.take(vals_s, jnp.clip(sel, 0, cap - 1), axis=0)
        return data.astype(device_dtype(wexpr.dtype)), ok

    raise NotImplementedError(
        f"window function {type(f).__name__} on device")


from spark_rapids_tpu.utils.kernel_cache import KernelCache

_WINDOW_CACHE = KernelCache("window", 256)


def _compile_window(window_cols, input_sig, cap: int):
    cache_key = (tuple((n, w.key()) for n, w in window_cols),
                 input_sig, cap)
    fn = _WINDOW_CACHE.get(cache_key)
    if fn is not None:
        return fn

    spec = window_cols[0][1]

    def run(flat_cols, num_rows):
        cols = [ColVal(*t) for t in flat_cols]
        ctx = EvalContext(cols, num_rows, cap)
        live = jnp.arange(cap) < num_rows

        part_keys: List[jnp.ndarray] = []
        for e in spec.partition_exprs:
            cv = e.emit(ctx)
            part_keys.extend(colval_sort_keys(cv, e.dtype, True, True))
        order_keys: List[jnp.ndarray] = []
        for e, asc, nf in spec.orders:
            cv = e.emit(ctx)
            order_keys.extend(colval_sort_keys(cv, e.dtype, asc, nf))

        perm = sort_permutation(part_keys + order_keys, cap,
                                live_first=live)
        from spark_rapids_tpu.columnar.gatherfab import gather_planes
        _g = gather_planes(part_keys + order_keys + [live], perm)
        part_keys_s = _g[:len(part_keys)]
        order_keys_s = _g[len(part_keys):len(part_keys) + len(order_keys)]
        live_s = _g[-1]
        g = _build_geometry(part_keys_s, order_keys_s, live_s, cap)
        g.order_cv = None
        g.order_asc = True
        if spec.orders:
            # the first order column's VALUES (sorted), for value-based
            # RANGE offset frames
            e0, asc0, _ = spec.orders[0]
            ocv = e0.emit(ctx)
            _og = gather_planes([ocv.data, ocv.validity], perm)
            g.order_cv = ColVal(_og[0], _og[1] & live_s, None)
            g.order_asc = asc0

        outs = []
        for name, wexpr in window_cols:
            data_s, valid_s = _eval_one(wexpr, g, ctx, perm, cap)
            data = jnp.zeros(data_s.shape, data_s.dtype).at[perm].set(
                data_s)
            valid = jnp.zeros(cap, jnp.bool_).at[perm].set(
                valid_s & live_s)
            outs.append((data, valid))
        return tuple(outs)

    fn = engine_jit(run, family="window", name="evaluate")
    _WINDOW_CACHE[cache_key] = fn
    return fn


class TpuWindowExec(TpuExec):
    """reference GpuWindowExec.scala:92.  All window expressions in one
    exec share a (partition, order) spec; frames differ per function."""

    def __init__(self, window_cols: List[Tuple[str, WindowExpression]],
                 child):
        super().__init__()
        assert window_cols, "window exec needs at least one function"
        sk = window_cols[0][1].spec_key()
        assert all(w.spec_key() == sk for _, w in window_cols), \
            "window exprs in one exec must share the partition/order spec"
        self.window_cols = window_cols
        self.children = [child]
        fields = list(child.output_schema.fields)
        fields += [Field(n, w.dtype, w.nullable) for n, w in window_cols]
        self._schema = Schema(fields)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        fs = ", ".join(f"{w.func.name} as {n}" for n, w in self.window_cols)
        w0 = self.window_cols[0][1]
        parts = ", ".join(e.name for e in w0.partition_exprs)
        return f"TpuWindow [{fs}] partition by [{parts}]"

    @property
    def output_batching(self):
        from spark_rapids_tpu.exec.coalesce import SINGLE_BATCH
        return SINGLE_BATCH

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        def gen():
            from spark_rapids_tpu.memory.spill import (
                collect_spillable, materialize_all,
            )
            handles = collect_spillable(
                self.children[0].execute_columnar(ctx), ctx)
            if not handles:
                return
            with self.metrics.timed(METRIC_TOTAL_TIME):
                from spark_rapids_tpu.utils.retry import with_retry
                batch = concat_batches(materialize_all(handles, ctx))

                def run_window(b):
                    # spill-retry only (withRetryNoSplit): partitions
                    # must stay whole, and they cross any row split
                    fn = _compile_window(self.window_cols,
                                         _batch_signature(b),
                                         b.capacity)
                    outs = fn(_flatten_batch(b), b.rows_traced)
                    cols = list(b.columns)
                    for (data, valid), (name, w) in zip(
                            outs, self.window_cols):
                        cols.append(DeviceColumn(w.dtype, data, valid,
                                                 b.rows_raw))
                    return ColumnarBatch(cols, b.rows_raw, self._schema)

                yield from with_retry(run_window, batch, ctx)
        return self._count_output(gen())
