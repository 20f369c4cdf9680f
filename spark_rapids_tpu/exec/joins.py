"""Hash joins.

Reference: GpuHashJoin.scala:40-139 (shared core driving cuDF
``Table.onColumns(keys).{innerJoin,leftJoin,leftSemiJoin,leftAntiJoin}``),
GpuShuffledHashJoinExec.scala:58 (build side coalesced to a single batch,
kept for the task lifetime), GpuBroadcastHashJoinExec.scala:83.

TPU design (SURVEY §7 "hard parts": two-pass count-then-gather under
static shapes):
  0. SIDES: the build side is the right child, except that an inner
     equi-join with no condition (``TpuHashJoinExec`` itself, not the
     broadcast join) builds on its left child when the left side ends
     holding fewer rows than the right by the host-known row bounds
     (``_peek_smaller_left``) and the uniqueness probe (step 1) finds
     its keys unique; the right batches then stream past it on an fk
     route, each output is cut to its pulled count (``_trim_to_count``),
     and the output keeps its [left..., right...] column order.  A
     smaller left side with repeated keys is streamed past the right
     build as before: the generic probe's search over every row of the
     larger side costs more than the larger build.
  1. BUILD (once): hash the build-side keys (splitmix64 over column
     values; packed-chunk folds for strings), sort build rows by hash.
  2. PROBE-COUNT (per stream batch, jitted): hash stream keys, binary
     search the sorted hash array for [lo, hi) candidate ranges, prefix-sum
     the counts.  One host sync reads the candidate total.
  3. EXPAND+VERIFY (jitted, static output capacity): candidate k maps back
     to (stream row i, build row j) with searchsorted over the offsets;
     actual key equality is re-checked (hash collisions) and a compaction
     gather produces the final pairs.
  4. Outer variants derive matched/unmatched masks with segment sums over
     the verified candidates; right/full accumulate a matched-build-row
     mask across stream batches and emit the null-extended remainder last.
"""

from __future__ import annotations

import threading
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_tpu.compile.service import engine_jit
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn, bucket_capacity
from spark_rapids_tpu.columnar.dtypes import (
    DataType, Field, Schema, STRING, BOOLEAN, FLOAT32, FLOAT64,
)
from spark_rapids_tpu.exec.base import ExecContext, TpuExec
from spark_rapids_tpu.exec.coalesce import concat_batches
from spark_rapids_tpu.exec.basic import filter_batch
from spark_rapids_tpu.exprs.base import (
    BoundReference, ColVal, EvalContext, Expression, Literal,
    _batch_signature, _flatten_batch,
)
from spark_rapids_tpu.exprs.predicates import string_compare
from spark_rapids_tpu.utils import tracing
from spark_rapids_tpu.utils.metrics import METRIC_TOTAL_TIME

# Always-on counters of the one-chip hash join (the ``join`` group of
# ``engine_stats()``, docs/observability.md), bumped once a join, never
# a batch or a row: ``joins`` executed, each under the ONE route that
# produced its rows (``generic``: probe, one count pulled, expand;
# ``band``: the same over a band-narrowed probe; ``fk``: the one
# sync-free program over unique build keys; ``fk_dense``: its
# direct-address form), ``broadcast`` where the build side came from a
# ``TpuBroadcastExchangeExec`` whatever the route, ``build_left`` where
# the join built on its smaller left side (unique keys, an fk route),
# and the rows a join saw as the host knows them without a device read:
# an exact count where one was pulled already, else a ``LazyRows``
# bound.
# ``out_slots`` is the capacity of the batches handed on: work
# downstream is done at capacity, not at rows (ROADMAP D14).
# ``build_us`` / ``probe_us`` sum the node metrics ``buildTime`` /
# ``joinTime``.
JOIN_ROUTES = ("generic", "band", "fk", "fk_dense")
_JOIN_LOCK = threading.Lock()
_JOIN = dict.fromkeys(
    ("joins", *JOIN_ROUTES, "broadcast", "build_left", "build_rows",
     "stream_rows", "out_rows", "out_slots", "build_us", "probe_us"), 0)


def join_stats() -> Dict[str, int]:
    with _JOIN_LOCK:
        return dict(_JOIN)


def reset_join_stats() -> None:
    with _JOIN_LOCK:
        for k in _JOIN:
            _JOIN[k] = 0


# ---------------------------------------------------------------------------
# hashing
# ---------------------------------------------------------------------------

def _splitmix64(x: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.uint64)
    x = (x + jnp.uint64(0x9E3779B97F4A7C15))
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


def _hash_colval(cv: ColVal, dtype: DataType) -> jnp.ndarray:
    """Per-row 64-bit hash of one key column (nulls hash to 0; the join
    validity mask excludes them anyway)."""
    if dtype == STRING:
        chars = cv.chars
        w = chars.shape[1]
        pad = (-w) % 8
        if pad:
            chars = jnp.pad(chars, ((0, 0), (0, pad)))
            w += pad
        blocks = chars.reshape(chars.shape[0], w // 8, 8).astype(jnp.uint64)
        lens = cv.data.astype(jnp.int64)
        h = _splitmix64(lens)  # seed with length
        # WIDTH-INDEPENDENT fold: only blocks the string's length
        # reaches mix into the hash — all-zero tail blocks past the
        # length leave it unchanged, so the same value hashes equal
        # at ANY char-matrix width.  Without the gate, two batches
        # whose widths bucket differently (different files, a
        # dictionary vs its batch, a width-changing expression) would
        # route equal keys to different hash partitions and miss join
        # matches across differently-padded sides.
        for i in range(w // 8):
            chunk = jnp.zeros(chars.shape[0], jnp.uint64)
            for b in range(8):
                chunk = (chunk << jnp.uint64(8)) | blocks[:, i, b]
            mixed = _splitmix64(h ^ chunk)
            h = jnp.where(lens > jnp.int64(i * 8), mixed, h)
        return h.astype(jnp.int64)
    if dtype in (FLOAT32, FLOAT64):
        # Equal values must hash equal: canonicalize NaN (one group) and
        # -0.0 == 0.0, then take bits through f32 bitcasts only — the TPU
        # x64 rewriter cannot lower 64-bit bitcast_convert, so f64 is
        # Dekker-split into (f32 head, f32 tail).  Distinct doubles that
        # collide in the split (beyond f32+f32 precision) merely share a
        # hash bucket; the probe re-verifies true key equality.
        x = cv.data
        isnan = jnp.isnan(x)
        x = jnp.where(isnan, jnp.zeros_like(x), x)
        x = jnp.where(x == 0, jnp.zeros_like(x), x)  # -0.0 == 0.0
        if dtype == FLOAT32:
            bits = jax.lax.bitcast_convert_type(x, jnp.int32) \
                .astype(jnp.int64)
        else:
            hi = x.astype(jnp.float32)
            hi64 = hi.astype(jnp.float64)
            lo = jnp.where(jnp.isfinite(x) & jnp.isfinite(hi64),
                           x - hi64, jnp.zeros_like(x)) \
                .astype(jnp.float32)
            hb = jax.lax.bitcast_convert_type(hi, jnp.int32)
            lb = jax.lax.bitcast_convert_type(lo, jnp.int32)
            bits = hb.astype(jnp.int64) ^ (lb.astype(jnp.int64) << 32)
        bits = jnp.where(isnan, jnp.int64(-0x7FF8000000000001), bits)
        return _splitmix64(bits).astype(jnp.int64)
    if dtype == BOOLEAN:
        return _splitmix64(cv.data.astype(jnp.int64)).astype(jnp.int64)
    return _splitmix64(cv.data.astype(jnp.int64)).astype(jnp.int64)


def _hash_keys(key_exprs: List[Expression], ctx: EvalContext
               ) -> Tuple[jnp.ndarray, jnp.ndarray, List[ColVal]]:
    """-> (combined hash, all-keys-valid, key colvals).

    A key whose expression carries ``is_precomputed_hash`` (the
    compressed code view's per-code hash gather,
    columnar/encoding.py) already EMITS `_hash_colval` values — its
    data enters the combine directly, so a hash over dictionary codes
    is bit-identical to the dense hash over the strings."""
    cvs = [e.emit(ctx) for e in key_exprs]
    acc = jnp.zeros(ctx.capacity, jnp.uint64)
    valid = jnp.ones(ctx.capacity, jnp.bool_)
    for e, cv in zip(key_exprs, cvs):
        if getattr(e, "is_precomputed_hash", False):
            h = cv.data.astype(jnp.uint64)
        else:
            h = _hash_colval(cv, e.dtype).astype(jnp.uint64)
        acc = _splitmix64(acc ^ h)
        valid = valid & cv.validity
    return acc.astype(jnp.int64), valid, cvs


def _keys_equal(a: ColVal, b: ColVal, dtype: DataType) -> jnp.ndarray:
    if dtype == STRING:
        return string_compare(a, b) == 0
    if dtype in (FLOAT32, FLOAT64):
        an, bn = jnp.isnan(a.data), jnp.isnan(b.data)
        return (an & bn) | (~an & ~bn & (a.data == b.data))
    return a.data == b.data


# ---------------------------------------------------------------------------
# compiled stages
# ---------------------------------------------------------------------------

from spark_rapids_tpu.utils.kernel_cache import KernelCache

_BUILD_CACHE = KernelCache("join.build", 256)
_PROBE_CACHE = KernelCache("join.probe", 256)
_EXPAND_CACHE = KernelCache("join.expand", 256)
_GATHER_CACHE = KernelCache("join.gather", 256)


def _compile_build(keys_key, key_exprs, input_sig, capacity):
    k = (keys_key, input_sig, capacity)
    fn = _BUILD_CACHE.get(k)
    if fn is not None:
        return fn

    def run(flat_cols, num_rows):
        cols = [ColVal(*t) for t in flat_cols]
        ctx = EvalContext(cols, jnp.int32(num_rows), capacity)
        h, valid, key_cvs = _hash_keys(key_exprs, ctx)
        live = jnp.arange(capacity) < num_rows
        usable = valid & live
        # unusable rows hash to INT64_MAX so they sort to the end and can
        # never be produced by a stream range (verify rejects them anyway)
        h = jnp.where(usable, h, jnp.iinfo(jnp.int64).max)
        from spark_rapids_tpu.exec.sortkeys import bitonic_lex_sort
        sorted_h, perm = bitonic_lex_sort([h])
        run_len = _run_lengths(sorted_h)
        # max run among VALID hashes: the FK-fast-path uniqueness probe
        # (computed here so the check costs no extra executable)
        max_run = jnp.max(jnp.where(
            sorted_h == jnp.iinfo(jnp.int64).max, 0, run_len))
        # single integer-like key: observed [lo, hi] drives the dense
        # direct-address join (LUT instead of hash + sort + search)
        if len(key_exprs) == 1 and key_exprs[0].dtype.name in (
                "byte", "short", "int", "long", "date"):
            kd = key_cvs[0].data.astype(jnp.int64)
            klo = jnp.min(jnp.where(usable, kd,
                                    jnp.iinfo(jnp.int64).max))
            khi = jnp.max(jnp.where(usable, kd,
                                    jnp.iinfo(jnp.int64).min))
        else:
            klo = jnp.int64(0)
            khi = jnp.int64(-1)
        return sorted_h, perm, run_len, max_run, klo, khi

    fn = engine_jit(run, family="join", name="build")
    _BUILD_CACHE[k] = fn
    return fn


def _derive_build_sort(bkey_exprs, b_ctx, b_cap: int, b_rows):
    """Hash-sorted build index derived IN-KERNEL (hash keys, sentinel
    unusable rows to INT64_MAX, bitonic sort) — shared by the probe,
    expand, and FK kernels so the sentinel/liveness semantics cannot
    diverge, and so no cross-kernel build buffers exist (the remote
    runtime places those in host memory space and pays a link round trip
    per execution).  Returns (sorted_h, perm_b)."""
    h_b0, valid_b0, _ = _hash_keys(bkey_exprs, b_ctx)
    live_b = jnp.arange(b_cap) < jnp.asarray(b_rows, jnp.int32)
    hb = jnp.where(valid_b0 & live_b, h_b0, jnp.iinfo(jnp.int64).max)
    from spark_rapids_tpu.exec.sortkeys import bitonic_lex_sort
    return bitonic_lex_sort([hb])


def _left_search(sorted_h: jnp.ndarray, h: jnp.ndarray):
    """Left insertion points of ``h`` in ``sorted_h`` as a STATICALLY
    UNROLLED binary search (log2(n)+1 vector steps XLA fuses into the
    surrounding kernel).  A ``fori_loop`` here is a measured disaster on
    the remote-attached TPU runtime: the while-op's 1M-row carries get
    assigned to HOST memory space (S(1)) and every iteration round-trips
    them over the device link (~450ms of a join kernel); the unrolled
    form keeps everything in HBM and vanishes into the fusion.
    (``jnp.searchsorted`` was worse still: two searches per probe.)"""
    n = sorted_h.shape[0]
    # derive the init from h so its varying-manual-axes (vma) match
    # inside shard_map (a fresh zeros() is replicated and mixing would
    # fail the aval check)
    z = (h * 0).astype(jnp.int32)
    return _unrolled_search(sorted_h, h, z, z + n, False, n)


def _unrolled_search(vals, targets, lo_b, hi_b, strict: bool, cap: int):
    """Shared unrolled binary-search core: first j in [lo_b, hi_b)
    with vals[j] > target (strict) or >= target (non-strict); hi_b when
    none.  log2(cap)+1 static vector steps — see _left_search's note on
    why a fori_loop is forbidden here."""
    steps = max(1, cap.bit_length()) + 1
    lo, hi = lo_b, hi_b
    for _ in range(steps):
        searching = lo < hi
        mid = (lo + hi) // 2
        mv = jnp.take(vals, jnp.clip(mid, 0, cap - 1))
        go = (mv <= targets) if strict else (mv < targets)
        lo = jnp.where(searching & go, mid + 1, lo)
        hi = jnp.where(searching & ~go, mid, hi)
    return lo


def _run_lengths(sorted_h: jnp.ndarray):
    """run_len[p] = length of the equal-value run of sorted_h starting at
    p (meaningful at run starts, which is all a left-search can land on).
    Computed once at build time so the probe gets its right bound with a
    single gather instead of a second binary-search chain."""
    from spark_rapids_tpu.utils.pscan import prefix_sum
    n = sorted_h.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    prev = jnp.concatenate([sorted_h[:1], sorted_h[:-1]])
    start = (sorted_h != prev) | (pos == 0)
    rid = prefix_sum(start.astype(jnp.int32)) - 1
    run_count = jax.ops.segment_sum(jnp.ones(n, jnp.int32), rid,
                                    num_segments=n)
    return jnp.take(run_count, rid)


class _BandSpec:
    """A band condition over ONE integer-like build column:
    ``lower_expr(stream) (<|<=) build_col (<|<=)-ish upper_expr(stream)``.
    Drives the band-aware probe: the build side sorts by (key hash, band
    column), so each stream row's candidate range is the DATE-WINDOW
    SUB-RANGE of its equi run instead of the whole run — a many-to-many
    band join (TPCx-BB q3/q8's clicks-before-purchase shape) stops
    materializing every equi pair.  The narrowed range is conservative
    (hash-collision rows of other keys may ride along); the existing key
    verify + condition post-filter keep exactness."""

    __slots__ = ("build_ord", "lower", "lower_strict", "lower_shift",
                 "upper", "upper_strict", "upper_shift")

    def __init__(self, build_ord, lower, lower_strict, upper,
                 upper_strict, lower_shift=0, upper_shift=0):
        self.build_ord = build_ord
        self.lower = lower                # stream-side expr or None
        self.lower_strict = lower_strict  # True: build > lower
        self.lower_shift = lower_shift    # build+c OP bound: subtract c
        self.upper = upper
        self.upper_strict = upper_strict  # True: build < upper
        self.upper_shift = upper_shift

    def key(self):
        return (self.build_ord,
                self.lower.key() if self.lower else None,
                self.lower_strict, self.lower_shift,
                self.upper.key() if self.upper else None,
                self.upper_strict, self.upper_shift)


def _int_like_dtype(dt) -> bool:
    return dt.is_integral or dt.name in ("date", "timestamp")


def _extract_band(condition, n_stream: int, build_schema):
    """Parse an inner-join condition into a _BandSpec when it is an
    AND-tree over comparisons of ONE build column against stream-only
    expressions; None when no band is extractable.  The spec only
    NARROWS candidates — the caller's condition post-filter still runs,
    so residual terms need no special handling."""
    from spark_rapids_tpu.exprs import predicates as pr

    terms = []

    def flatten(e):
        if isinstance(e, pr.And):
            flatten(e.children[0])
            flatten(e.children[1])
        else:
            terms.append(e)
    flatten(condition)

    def side(e):
        """'build' if every ref is build-side, 'stream' if every ref is
        stream-side, else None."""
        refs = []

        def walk(x):
            if isinstance(x, BoundReference):
                refs.append(x.ordinal)
            for c in x.children:
                walk(c)
        walk(e)
        if not refs:
            return "stream"  # constants fold to the stream side
        if all(r >= n_stream for r in refs):
            return "build"
        if all(r < n_stream for r in refs):
            return "stream"
        return None

    def normalize_build(e):
        """build-side expr -> (build_ref, shift) for the forms
        ``ref``, ``ref + lit``, ``lit + ref``, ``ref - lit`` — the
        constant moves to the stream bound (build + c OP bound ==
        build OP bound - c), so date-window conditions like
        ``s.date <= w.date + 10`` still drive the band probe."""
        from spark_rapids_tpu.exprs.arithmetic import Add, Subtract
        from spark_rapids_tpu.exprs.cast import Cast

        def unwrap(x):
            # only strip value-PRESERVING casts (pure integral widening,
            # e.g. the int32->int64 coercions the binder inserts): a
            # value-changing cast (timestamp->seconds, narrowing wrap)
            # must keep the band extractor away — the probe PRUNES
            # candidates, so a wrong window silently drops matches
            if isinstance(x, Cast):
                frm = x.children[0].dtype
                if frm.is_integral and x.to.is_integral and \
                        x.to.byte_width >= frm.byte_width:
                    return unwrap(x.children[0])
            return x

        e = unwrap(e)
        if isinstance(e, BoundReference):
            return e, 0
        if isinstance(e, (Add, Subtract)):
            a, b = (unwrap(c) for c in e.children)
            sign = 1 if isinstance(e, Add) else -1
            if isinstance(a, BoundReference) and isinstance(b, Literal) \
                    and isinstance(b.value, int):
                return a, sign * b.value
            if isinstance(e, Add) and isinstance(b, BoundReference) \
                    and isinstance(a, Literal) \
                    and isinstance(a.value, int):
                return b, a.value
        return None, 0

    build_ord = None
    lower = upper = None
    lower_strict = upper_strict = True
    lower_shift = upper_shift = 0
    ops = {pr.GreaterThan: (">",), pr.GreaterThanOrEqual: (">=",),
           pr.LessThan: ("<",), pr.LessThanOrEqual: ("<=",)}
    for t in terms:
        if type(t) not in ops:
            continue
        a, b = t.children
        sa, sb = side(a), side(b)
        op = ops[type(t)][0]
        if sa == "build" and sb == "stream":
            ref, shift = normalize_build(a)
            if ref is None:
                continue
            bo = ref.ordinal - n_stream
            bound, bshift = b, shift
            is_lower = op in (">", ">=")
            strict = op in (">", "<")
        elif sb == "build" and sa == "stream":
            # stream < build  ==  build > stream
            ref, shift = normalize_build(b)
            if ref is None:
                continue
            bo = ref.ordinal - n_stream
            bound, bshift = a, shift
            is_lower = op in ("<", "<=")
            strict = op in (">", "<")
        else:
            continue
        if not _int_like_dtype(build_schema[bo].dtype) or \
                not _int_like_dtype(bound.dtype):
            continue
        if build_ord is None:
            build_ord = bo
        elif build_ord != bo:
            continue  # bands over two build columns: use the first
        if is_lower and lower is None:
            lower, lower_strict, lower_shift = bound, strict, bshift
        elif not is_lower and upper is None:
            upper, upper_strict, upper_shift = bound, strict, bshift
    if build_ord is None or (lower is None and upper is None):
        return None
    return _BandSpec(build_ord, lower, lower_strict, upper, upper_strict,
                     lower_shift if lower is not None else 0,
                     upper_shift if upper is not None else 0)


def _derive_build_sort_band(bkey_exprs, band_ord: int, b_ctx, b_cap: int,
                            b_rows):
    """Build sort by (key hash, band column): returns
    (sorted_h, sorted_band int64, perm_b).  Unusable rows sentinel both
    planes to +max so they sort last and no band window reaches them."""
    h_b0, valid_b0, _ = _hash_keys(bkey_exprs, b_ctx)
    live_b = jnp.arange(b_cap) < jnp.asarray(b_rows, jnp.int32)
    bcv = b_ctx.cols[band_ord]
    bv = bcv.data.astype(jnp.int64)
    usable = valid_b0 & live_b & bcv.validity
    hb = jnp.where(usable, h_b0, jnp.iinfo(jnp.int64).max)
    bv = jnp.where(usable, bv, jnp.iinfo(jnp.int64).max)
    from spark_rapids_tpu.exec.sortkeys import bitonic_lex_sort
    sorted_h, sorted_band, perm_b = bitonic_lex_sort([hb, bv])
    return sorted_h, sorted_band, perm_b


def _bounded_left_search(vals, targets, lo_b, hi_b, strict: bool,
                         cap: int):
    """Per-row bounded binary search over the shared unrolled core
    (_unrolled_search): first j in [lo_b, hi_b) past the band bound."""
    return _unrolled_search(vals, targets, lo_b, hi_b, strict, cap)


def _compile_probe(keys_key, key_exprs, bkey_exprs, input_sig, capacity,
                   build_cap, cross_count=None, band=None):
    k = (keys_key, input_sig, capacity, build_cap, cross_count,
         band.key() if band is not None else None)
    fn = _PROBE_CACHE.get(k)
    if fn is not None:
        return fn

    def run(flat_cols, num_rows, b_flat, n_build):
        b_cols = [ColVal(*t) for t in b_flat]
        b_ctx = EvalContext(b_cols, jnp.int32(n_build), build_cap)
        if band is None:
            sorted_h, _perm_b = _derive_build_sort(bkey_exprs, b_ctx,
                                                   build_cap, n_build)
            sorted_band = None
        else:
            sorted_h, sorted_band, _perm_b = _derive_build_sort_band(
                bkey_exprs, band.build_ord, b_ctx, build_cap, n_build)
        run_len = _run_lengths(sorted_h)
        cols = [ColVal(*t) for t in flat_cols]
        ctx = EvalContext(cols, jnp.int32(num_rows), capacity)
        live = jnp.arange(capacity) < num_rows
        if cross_count is not None:
            counts = jnp.where(live, n_build, 0).astype(jnp.int64)
            lo = jnp.zeros(capacity, jnp.int32)
        else:
            h, valid, _ = _hash_keys(key_exprs, ctx)
            usable = valid & live
            lo = _left_search(sorted_h, h)
            loc = jnp.clip(lo, 0, build_cap - 1)
            present = (lo < build_cap) & (jnp.take(sorted_h, loc) == h)
            runs = jnp.where(present, jnp.take(run_len, loc), 0)
            if band is None:
                counts = jnp.where(usable, runs, 0).astype(jnp.int64)
            else:
                # narrow each equi run to the band sub-range: the build
                # is sorted by (hash, band col), so two bounded binary
                # searches find the window (many-to-many band joins stop
                # materializing every equi pair)
                lo_b = jnp.where(present & usable, loc, 0)
                hi_b = jnp.where(present & usable, loc + runs, 0)
                bound_ok = usable & present
                start = lo_b
                if band.lower is not None:
                    lcv = band.lower.emit(ctx)
                    bound_ok = bound_ok & lcv.validity
                    start = _bounded_left_search(
                        sorted_band,
                        lcv.data.astype(jnp.int64) - band.lower_shift,
                        lo_b, hi_b, band.lower_strict, build_cap)
                end = hi_b
                if band.upper is not None:
                    ucv = band.upper.emit(ctx)
                    bound_ok = bound_ok & ucv.validity
                    end = _bounded_left_search(
                        sorted_band,
                        ucv.data.astype(jnp.int64) - band.upper_shift,
                        lo_b, hi_b, not band.upper_strict, build_cap)
                counts = jnp.where(
                    bound_ok, jnp.maximum(end - start, 0), 0) \
                    .astype(jnp.int64)
                lo = jnp.where(bound_ok, start, 0).astype(lo.dtype)
        from spark_rapids_tpu.utils.pscan import prefix_sum
        inclusive = prefix_sum(counts)
        total = inclusive[-1] if capacity else jnp.int64(0)
        exclusive = inclusive - counts
        return total, lo, inclusive, exclusive

    fn = engine_jit(run, family="join", name="probe")
    _PROBE_CACHE[k] = fn
    return fn


def _compile_expand(keys_key, skey_exprs, bkey_exprs, s_sig, b_sig,
                    s_cap, b_cap, out_cap, is_cross, band=None):
    k = (keys_key, s_sig, b_sig, s_cap, b_cap, out_cap, is_cross,
         band.key() if band is not None else None)
    fn = _EXPAND_CACHE.get(k)
    if fn is not None:
        return fn

    def run(s_cols_flat, s_rows, b_cols_flat, b_rows, lo, inclusive,
            exclusive, total):
        s_cols = [ColVal(*t) for t in s_cols_flat]
        b_cols = [ColVal(*t) for t in b_cols_flat]
        s_ctx = EvalContext(s_cols, jnp.int32(s_rows), s_cap)
        b_ctx = EvalContext(b_cols, jnp.int32(b_rows), b_cap)
        if not is_cross:
            if band is None:
                _sorted_h, perm_b = _derive_build_sort(
                    bkey_exprs, b_ctx, b_cap, b_rows)
            else:
                # MUST match the probe's coordinate system: same
                # (hash, band col) sort
                _sh, _sb, perm_b = _derive_build_sort_band(
                    bkey_exprs, band.build_ord, b_ctx, b_cap, b_rows)
        kk = jnp.arange(out_cap, dtype=jnp.int64)
        # candidate -> stream row: equivalent to
        # searchsorted(inclusive, kk, 'right') but built with one
        # delta-scatter + prefix sum — a 1M/1M binary search costs ~20
        # full gather chains on device, dominating the expand kernel
        from spark_rapids_tpu.utils.pscan import masked_positions, \
            prefix_sum
        counts_r = (inclusive - exclusive).astype(jnp.int32)
        nonempty = counts_r > 0
        comp = masked_positions(nonempty, s_cap, s_cap)
        comp_prev = jnp.concatenate(
            [jnp.zeros(1, comp.dtype), comp[:-1]])
        delta_vals = jnp.where(comp < s_cap, comp - comp_prev, 0)
        starts = jnp.take(exclusive, jnp.clip(comp, 0, s_cap - 1))
        pos_t = jnp.where(comp < s_cap, starts, out_cap).astype(jnp.int32)
        delta = jnp.zeros(out_cap, jnp.int32).at[pos_t].add(
            delta_vals, mode="drop")
        i = prefix_sum(delta)
        i = jnp.clip(i, 0, s_cap - 1)
        j_off = kk - jnp.take(exclusive, i)
        j = jnp.take(lo, i).astype(jnp.int64) + j_off
        j = jnp.clip(j, 0, b_cap - 1).astype(jnp.int32)
        if is_cross:
            brow = j
        else:
            brow = jnp.take(perm_b, j)
        keep = kk < total
        if not is_cross:
            from spark_rapids_tpu.columnar.gatherfab import gather_planes
            _, _, s_cvs = _hash_keys(skey_exprs, s_ctx)
            _, _, b_cvs = _hash_keys(bkey_exprs, b_ctx)
            sg_all = gather_planes(
                [p for cv in s_cvs
                 for p in (cv.data, cv.validity, cv.chars)], i)
            bg_all = gather_planes(
                [p for cv in b_cvs
                 for p in (cv.data, cv.validity, cv.chars)], brow)
            for ki, e in enumerate(skey_exprs):
                sg = ColVal(sg_all[3 * ki], sg_all[3 * ki + 1],
                            sg_all[3 * ki + 2])
                bg = ColVal(bg_all[3 * ki], bg_all[3 * ki + 1],
                            bg_all[3 * ki + 2])
                keep = keep & sg.validity & bg.validity & \
                    _keys_equal(sg, bg, e.dtype)
        kept = jnp.sum(keep.astype(jnp.int64))
        # per-stream-row verified match count (for outer/semi/anti)
        m_stream = jax.ops.segment_sum(keep.astype(jnp.int32), i,
                                       num_segments=s_cap)
        # matched build rows (for right/full)
        m_build = jax.ops.segment_sum(keep.astype(jnp.int32), brow,
                                      num_segments=b_cap)
        # outer-variant masks computed HERE so the host layer never runs
        # eager jnp glue (each eager op is its own compiled executable)
        live_s = jnp.arange(s_cap) < jnp.asarray(s_rows, jnp.int32)
        unmatched = live_s & (m_stream == 0)
        n_unmatched = jnp.sum(unmatched.astype(jnp.int32))
        matched_sel = live_s & (m_stream > 0)
        n_matched = jnp.sum(matched_sel.astype(jnp.int32))
        return (keep, i, brow, kept, m_stream, m_build,
                unmatched, n_unmatched, matched_sel, n_matched)

    fn = engine_jit(run, family="join", name="expand")
    _EXPAND_CACHE[k] = fn
    return fn


_FK_CACHE = KernelCache("join.fk", 256)


def _compile_fk_join(keys_key, skey_exprs, bkey_exprs, s_sig, b_sig,
                     s_cap: int, b_cap: int):
    """Fused FK (unique-build-key) inner join: probe + verify + compact
    + gather of BOTH sides in ONE kernel with a STATIC output capacity
    (= the stream capacity, since each stream row matches at most one
    build row).  No host sync at all — the two-pass count/expand path
    exists only for joins that can expand."""
    k = (keys_key, s_sig, b_sig, s_cap, b_cap)
    fn = _FK_CACHE.get(k)
    if fn is not None:
        return fn

    def run(s_flat, s_rows, b_flat, b_rows):
        s_cols = [ColVal(*t) for t in s_flat]
        b_cols = [ColVal(*t) for t in b_flat]
        s_ctx = EvalContext(s_cols, jnp.int32(s_rows), s_cap)
        b_ctx = EvalContext(b_cols, jnp.int32(b_rows), b_cap)
        h, valid, s_cvs = _hash_keys(skey_exprs, s_ctx)
        live = jnp.arange(s_cap) < jnp.asarray(s_rows, jnp.int32)
        sorted_h, perm_b = _derive_build_sort(bkey_exprs, b_ctx,
                                              b_cap, b_rows)
        lo = _left_search(sorted_h, h)
        loc = jnp.clip(lo, 0, b_cap - 1)
        present = (lo < b_cap) & (jnp.take(sorted_h, loc) == h)
        brow = jnp.take(perm_b, loc)
        keep = present & valid & live
        _, _, b_cvs = _hash_keys(bkey_exprs, b_ctx)
        from spark_rapids_tpu.columnar.gatherfab import gather_planes
        bplanes = [p for bcv in b_cvs
                   for p in (bcv.data, bcv.validity, bcv.chars)]
        bg_all = gather_planes(bplanes, brow)
        for ki, (e, scv) in enumerate(zip(skey_exprs, s_cvs)):
            bg = ColVal(bg_all[3 * ki], bg_all[3 * ki + 1],
                        bg_all[3 * ki + 2])
            keep = keep & scv.validity & bg.validity & \
                _keys_equal(scv, bg, e.dtype)
        kept = jnp.sum(keep.astype(jnp.int32))
        i = jnp.arange(s_cap, dtype=jnp.int32)
        outs = _gather_pair_tail(s_flat, b_flat, keep, i, brow, kept,
                                 s_cap)
        return outs, kept

    fn = engine_jit(run, family="join", name="fk")
    _FK_CACHE[k] = fn
    return fn


_FK_DENSE_CACHE = KernelCache("join.fk_dense", 256)


def _compile_fk_dense_join(keys_key, skey_exprs, bkey_exprs, s_sig,
                           b_sig, s_cap: int, b_cap: int,
                           dense_cap: int):
    """Dense direct-address FK inner join: the single integer build key's
    observed range [lo, hi] fits a lookup table, so probe = ONE scatter
    (key offset -> build row) + ONE gather — no hashing, no bitonic
    sort, no binary search, and no collision verify (the LUT is keyed by
    the exact key value).  ``lo`` rides in as a traced scalar so every
    range with the same bucketed span shares the compiled kernel.
    Reference shape: GpuHashJoin's build map specialized the way cuDF
    would for a perfect-hash dimension key."""
    k = (keys_key, s_sig, b_sig, s_cap, b_cap, dense_cap)
    fn = _FK_DENSE_CACHE.get(k)
    if fn is not None:
        return fn

    def run(s_flat, s_rows, b_flat, b_rows, lo_t):
        s_cols = [ColVal(*t) for t in s_flat]
        b_cols = [ColVal(*t) for t in b_flat]
        s_ctx = EvalContext(s_cols, jnp.int32(s_rows), s_cap)
        b_ctx = EvalContext(b_cols, jnp.int32(b_rows), b_cap)
        skey = skey_exprs[0].emit(s_ctx)
        bkey = bkey_exprs[0].emit(b_ctx)
        live_s = jnp.arange(s_cap) < jnp.asarray(s_rows, jnp.int32)
        live_b = jnp.arange(b_cap) < jnp.asarray(b_rows, jnp.int32)
        boff = bkey.data.astype(jnp.int64) - lo_t
        b_ok = bkey.validity & live_b & (boff >= 0) & (boff < dense_cap)
        slot = jnp.where(b_ok, boff, dense_cap).astype(jnp.int32)
        lut = jnp.full(dense_cap, -1, jnp.int32).at[slot].set(
            jnp.arange(b_cap, dtype=jnp.int32), mode="drop")
        soff = skey.data.astype(jnp.int64) - lo_t
        s_ok = skey.validity & live_s & (soff >= 0) & (soff < dense_cap)
        brow_raw = jnp.take(lut, jnp.clip(soff, 0, dense_cap - 1)
                            .astype(jnp.int32))
        keep = s_ok & (brow_raw >= 0)
        brow = jnp.clip(brow_raw, 0, b_cap - 1)
        kept = jnp.sum(keep.astype(jnp.int32))
        i = jnp.arange(s_cap, dtype=jnp.int32)
        outs = _gather_pair_tail(s_flat, b_flat, keep, i, brow, kept,
                                 s_cap)
        return outs, kept

    fn = engine_jit(run, family="join", name="fk_dense")
    _FK_DENSE_CACHE[k] = fn
    return fn


_UNIQ_CACHE_KEY = "join_build_unique"


def _build_probe(keys_key, b_flat, b_rows, probe_thunk,
                 b_cap: int) -> tuple:
    """Memoized build-side probe -> (max_run, key_lo, key_hi).

    max_run <= 1 iff every valid build hash occurs once (unique hashes
    imply unique keys; collisions conservatively read as non-unique — a
    valid key hashing to the int64-max sentinel could in principle slip
    through, at 2^-64 odds per key).  (key_lo, key_hi) is the observed
    single-integer-key range (lo > hi = not applicable), driving the
    dense direct-address join.  The scalar pull memoizes on build buffer
    identity, so re-runs over the device scan cache answer from host
    memory."""
    from spark_rapids_tpu.columnar.column import rows_traced
    from spark_rapids_tpu.utils.memo import memoized_pull

    arrays = [a for t in b_flat for a in t if a is not None]
    logical = [_UNIQ_CACHE_KEY, keys_key, b_cap]
    r = rows_traced(b_rows)
    if isinstance(r, int):
        logical.append(r)
    else:
        arrays.append(r)

    return memoized_pull(tuple(logical), arrays, probe_thunk)


def _gather_pair_tail(s_flat, b_flat, keep, i, brow, kept_t,
                      out_cap: int, in_cap: int = None):
    """Shared traced tail: compact verified candidates and gather both
    sides' columns (used inside both the FK and general join kernels so
    the gather semantics cannot diverge)."""
    from spark_rapids_tpu.columnar.gatherfab import gather_planes
    from spark_rapids_tpu.utils.pscan import masked_positions
    if in_cap is None:
        in_cap = keep.shape[0]
    idx = masked_positions(keep, out_cap, in_cap - 1)
    # the compaction indices themselves ride the fused gather too
    si, bi = gather_planes([i, brow], idx)
    pos_live = jnp.arange(out_cap) < kept_t
    outs = []
    for flat, sel in ((s_flat, si), (b_flat, bi)):
        planes = [p for (d, v, ch) in flat for p in (d, v, ch)]
        g = gather_planes(planes, sel)
        for ci in range(len(flat)):
            outs.append((g[3 * ci], g[3 * ci + 1] & pos_live,
                         g[3 * ci + 2]))
    return tuple(outs)


_PAIRS_CACHE = KernelCache("join.pairs", 256)


def _compile_gather_pairs(s_sig, b_sig, in_cap: int, out_cap: int):
    """ONE jitted kernel for the pair compaction+gather — eager jnp ops
    here each cost a separate XLA executable (one TPU compile per op
    per shape), which dominated join cold time."""
    key = (s_sig, b_sig, in_cap, out_cap)
    fn = _PAIRS_CACHE.get(key)
    if fn is not None:
        return fn

    def run(s_flat, b_flat, keep, i, brow, kept_t):
        return _gather_pair_tail(s_flat, b_flat, keep, i, brow, kept_t,
                                 out_cap, in_cap=in_cap)

    fn = engine_jit(run, family="join", name="gather_pairs")
    _PAIRS_CACHE[key] = fn
    return fn


def _wrap_joined(sv, outs, rows, schema: Schema,
                 build_left: bool) -> ColumnarBatch:
    """The joined batch from a pair gather's planes, which come stream
    side first.  Encoded columns re-wrap: the code view's wrap maps
    override the dictionary per position (its re-keyed stream key
    decodes through the build dictionary).  A join built on its left
    side turns the columns round here, on the host, so that its output
    is [left..., right...] like any other join's."""
    from spark_rapids_tpu.columnar import encoding
    cols = list(sv.s_batch.columns) + list(sv.b_batch.columns)
    ns = len(sv.s_batch.columns)
    wrap = dict(sv.s_wrap)
    wrap.update({ns + i: d for i, d in sv.b_wrap.items()})
    if build_left:
        order = list(range(ns, len(cols))) + list(range(ns))
        cols = [cols[i] for i in order]
        outs = [outs[i] for i in order]
        at = {src: dst for dst, src in enumerate(order)}
        wrap = {at[i]: d for i, d in wrap.items()}
    return encoding.wrap_gathered(cols, outs, rows, schema,
                                  extra_wrap=wrap)


def _gather_pairs(sv, keep, i, brow, kept, out_cap: int,
                  schema: Schema) -> ColumnarBatch:
    """Compact verified candidates and gather both sides of the stream
    view ``sv``.  ``kept`` may be a device scalar (LazyRows) — the
    output capacity is sized by the host-known candidate total instead,
    avoiding a second link sync.  Encoded columns gather their codes
    planes (``_wrap_joined``)."""
    from spark_rapids_tpu.columnar import encoding
    from spark_rapids_tpu.columnar.column import rows_traced
    s_flat, s_sig = encoding.flat_and_sig(sv.s_batch)
    b_flat, b_sig = encoding.flat_and_sig(sv.b_batch)
    fn = _compile_gather_pairs(s_sig, b_sig, keep.shape[0], out_cap)
    outs = fn(s_flat, b_flat, keep, i, brow, rows_traced(kept))
    return _wrap_joined(sv, outs, kept, schema, False)


_TRIM_CACHE = KernelCache("join.trim", 256)


def _trim_to_count(outs, kept, in_cap: int, metrics):
    """-> (planes, rows): an fk program's output, whose matches lie
    packed at its front, cut to the bucket of their pulled count.  A
    join built on its left side streams the larger side past the
    smaller, so most of a stream batch's slots find no partner, and the
    operators downstream work at the capacity they are handed (D14):
    one count a batch, as the generic route pulls, buys them the rows'
    own capacity."""
    from spark_rapids_tpu.columnar.transfer import device_pull
    n = int(device_pull(kept, metrics=metrics))
    cap = bucket_capacity(max(1, n))
    if cap >= in_cap:
        return outs, n
    fn = _TRIM_CACHE.get(cap)
    if fn is None:
        def run(planes):
            return jax.tree_util.tree_map(lambda p: p[:cap], planes)
        fn = engine_jit(run, family="join", name="trim")
        _TRIM_CACHE[cap] = fn
    return fn(outs), n


def _peek_smaller_left(right: List[ColumnarBatch],
                       left: Iterator[ColumnarBatch]):
    """-> (left batches pulled, the left side, whether it is smaller).

    ``right`` is the drained right side; ``left`` is pulled only until
    its batches' host-known row bounds reach the right's sum.  The left
    side is the smaller where it ends first holding fewer rows; a tie,
    or a left side with no batch, is not.  No device value is read."""
    r = sum(b.rows_bound for b in right)
    pulled: List[ColumnarBatch] = []
    rows = 0
    for b in left:
        pulled.append(b)
        rows += b.rows_bound
        if rows >= r:
            return pulled, left, False
    return pulled, left, bool(pulled)


def _handing_on(batches: List[ColumnarBatch],
                rest=()) -> Iterator[ColumnarBatch]:
    """``batches`` in their order, each let go of as it is handed on,
    then ``rest``: a side held whole is freed batch by batch as it
    streams."""
    while batches:
        yield batches.pop(0)
    yield from rest


_UNMATCHED_CACHE = KernelCache("join.unmatched", 256)


def _compile_unmatched(cap: int):
    fn = _UNMATCHED_CACHE.get(cap)
    if fn is None:
        def run(m_total, rows):
            live = jnp.arange(cap) < jnp.asarray(rows, jnp.int32)
            um = live & (m_total == 0)
            return um, jnp.sum(um.astype(jnp.int32))
        fn = engine_jit(run, family="join", name="unmatched")
        _UNMATCHED_CACHE[cap] = fn
    return fn


_SIDE_NULLS_CACHE = KernelCache("join.side_nulls", 256)


def _compile_side_gather(sig, in_cap: int, out_cap: int,
                         null_fields_key: tuple):
    """ONE jitted kernel for selected-side gather + null extension —
    eager jnp glue here costs a separate XLA executable (one TPU
    compile) per op per shape."""
    key = (sig, in_cap, out_cap, null_fields_key)
    fn = _SIDE_NULLS_CACHE.get(key)
    if fn is not None:
        return fn

    def run(flat, mask, count_t):
        from spark_rapids_tpu.utils.pscan import masked_positions
        idx = masked_positions(mask, out_cap, in_cap - 1)
        pos_live = jnp.arange(out_cap) < count_t
        outs = []
        for (d, v, ch) in flat:
            data = jnp.take(d, idx, axis=0)
            valid = jnp.take(v, idx, axis=0) & pos_live
            chars = None if ch is None else jnp.take(ch, idx, axis=0)
            outs.append((data, valid, chars))
        nulls = []
        nvalid = jnp.zeros(out_cap, jnp.bool_)
        for (np_dt, width) in null_fields_key:
            if width:
                nulls.append((jnp.zeros(out_cap, jnp.int32), nvalid,
                              jnp.zeros((out_cap, width), jnp.uint8)))
            else:
                nulls.append((jnp.zeros(out_cap, np_dt), nvalid, None))
        return tuple(outs), tuple(nulls)

    fn = engine_jit(run, family="join", name="side_gather")
    _SIDE_NULLS_CACHE[key] = fn
    return fn


def _gather_side_with_nulls(batch: ColumnarBatch, mask, count,
                            other_schema_fields, schema: Schema,
                            side_first: bool) -> ColumnarBatch:
    """Rows of one side selected by mask, other side all-null, as ONE
    compiled kernel.  ``count`` may be device-resident (LazyRows): the
    output keeps the side batch's capacity so no host sync sizes it."""
    from spark_rapids_tpu.columnar.column import rows_bound, rows_traced
    out_cap = bucket_capacity(max(1, rows_bound(count)))
    nf_key = tuple(
        ("i4" if f.dtype == STRING else
         str(np.dtype(f.dtype.numpy_dtype)),
         8 if f.dtype == STRING else 0)
        for f in other_schema_fields)
    from spark_rapids_tpu.columnar import encoding
    flat, sig = encoding.flat_and_sig(batch)
    fn = _compile_side_gather(sig, mask.shape[0], out_cap, nf_key)
    outs, nulls = fn(flat, mask, rows_traced(count))
    side_cols = list(encoding.wrap_gathered(
        batch.columns, outs, count, None).columns)
    null_cols = [DeviceColumn(f.dtype, d, v, count, chars=ch)
                 for f, (d, v, ch) in zip(other_schema_fields, nulls)]
    cols = side_cols + null_cols if side_first else null_cols + side_cols
    return ColumnarBatch(cols, count, schema)


class TpuHashJoinExec(TpuExec):
    """Shared hash-join core (reference GpuHashJoin.scala:40).  The build
    side is the right child, as GpuShuffledHashJoinExec's, except that an
    inner equi-join with no condition builds on its left child where the
    left side holds fewer rows by the host-known bounds
    (``_peek_smaller_left``) and its keys are unique: the side a plan
    cannot size statically, such as a join's output, is sized here, and
    the larger right side streams past it on a sync-free fk route.  A
    class whose plan fixes the build side (the broadcast join) sets
    ``_sides_by_size`` False and always builds right."""

    _sides_by_size = True

    def __init__(self, left, right, left_keys: List[Expression],
                 right_keys: List[Expression], join_type: str = "inner",
                 condition: Optional[Expression] = None):
        super().__init__()
        if condition is not None and join_type not in ("inner", "cross"):
            raise ValueError(
                f"join condition on {join_type} join is unsupported: the "
                "post-filter implementation would drop rows that must be "
                "null-extended (planner should have rejected this)")
        self.children = [left, right]
        self.left_keys = left_keys
        self.right_keys = right_keys
        self.join_type = join_type
        self.condition = condition

    @property
    def output_schema(self) -> Schema:
        lt = self.join_type
        ls = self.children[0].output_schema
        rs = self.children[1].output_schema
        if lt in ("semi", "anti"):
            return ls
        lf = list(ls.fields)
        rf = list(rs.fields)
        if lt in ("right", "full"):
            lf = [Field(f.name, f.dtype, True) for f in lf]
        if lt in ("left", "full"):
            rf = [Field(f.name, f.dtype, True) for f in rf]
        return Schema(lf + rf)

    def describe(self) -> str:
        ks = ", ".join(f"{l.name}={r.name}"
                       for l, r in zip(self.left_keys, self.right_keys))
        return f"TpuHashJoin [{self.join_type}, {ks}]"

    def child_coalesce_goals(self, conf):
        from spark_rapids_tpu.exec.coalesce import TargetSize
        return [TargetSize(conf.batch_size_bytes), None]

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        return self._count_output(self._run(ctx))

    def _run(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        """The join (``_join``), counted once into the ``join`` group
        when it ends, however it ends."""
        from spark_rapids_tpu.exec.broadcast import TpuBroadcastExchangeExec
        seen = {"joins": 1, "route": "generic", "build_left": 0,
                "build_rows": 0, "stream_rows": 0, "out_rows": 0,
                "out_slots": 0,
                "broadcast": int(isinstance(self.children[1],
                                            TpuBroadcastExchangeExec))}
        clocks = {"build_us": self.metrics["buildTime"],
                  "probe_us": self.metrics["joinTime"]}
        before = {k: m.value for k, m in clocks.items()}
        try:
            for out in self._join(ctx, seen):
                seen["out_rows"] += out.rows_bound
                seen["out_slots"] += out.capacity
                yield out
        finally:
            seen[seen.pop("route")] = 1
            for k, m in clocks.items():
                seen[k] = (m.value - before[k]) // 1000
            with _JOIN_LOCK:
                for k, v in seen.items():
                    _JOIN[k] += v

    def _build(self, batches: List[ColumnarBatch], build_left: bool):
        """-> (join code view, keys key): ``batches`` made the one build
        batch, on the side ``build_left`` names, and its code view.  The
        join's own work on its build side (the child's is the child's)."""
        from spark_rapids_tpu.columnar import encoding as _enc
        s_child, b_child = self.children[::-1] if build_left \
            else self.children
        s_keys, b_keys = (self.right_keys, self.left_keys) if build_left \
            else (self.left_keys, self.right_keys)
        with tracing.trace_range(tracing.SPAN_JOIN_BUILD,
                                 self.metrics["buildTime"]):
            if batches:
                b_batch = concat_batches(batches)
            else:
                b_batch = _empty_batch(b_child.output_schema)
            # equi-join keys compare as CODES where both sides reference
            # encoded columns (docs/compressed.md): the view keeps the
            # build side's codes, re-keys each stream batch into the
            # build code space, and rewrites the key expressions to
            # INT32 refs — a stream batch arriving dense drops to the
            # dense-keys variant
            jv = _enc.JoinCodeView(
                b_batch, s_keys, b_keys,
                len(s_child.output_schema.fields),
                condition=self.condition)
        keys_key = (tuple(e.key() for e in s_keys),
                    tuple(e.key() for e in jv.rkeys_code),
                    self.join_type)
        return jv, keys_key

    def _probe(self, jv, keys_key) -> tuple:
        """-> (max_run, key_lo, key_hi) of the build batch (``_build_probe``,
        memoized)."""
        from spark_rapids_tpu.columnar import encoding as _enc
        b_batch = jv.build_batch
        b_flat, b_sig = _enc.flat_and_sig(b_batch)

        def build_probe_thunk():
            # the separate build executable exists ONLY for this probe;
            # the join kernels re-derive the build sort internally (its
            # cross-kernel outputs land in host memory space on the
            # remote runtime and cost a link round trip per execution).
            # One pull answers uniqueness AND the single-int-key range
            # (the dense direct-address fast path's precondition).
            with tracing.trace_range(tracing.SPAN_JOIN_BUILD,
                                     self.metrics["buildTime"]):
                build_fn = _compile_build(keys_key, jv.rkeys_code,
                                          b_sig, b_batch.capacity)
                _sh, _pb, _rl, max_run, klo, khi = build_fn(
                    b_flat, b_batch.rows_traced)
            from spark_rapids_tpu.columnar.transfer import device_pull
            return tuple(int(x) for x in
                         device_pull((max_run, klo, khi),
                                     metrics=self.metrics))

        return _build_probe(keys_key, b_flat, b_batch.rows_raw,
                            build_probe_thunk, b_batch.capacity)

    def _join(self, ctx: ExecContext, seen: dict
              ) -> Iterator[ColumnarBatch]:
        """``seen`` is ``_run``'s tally: the route taken, the side built
        on and the rows of each side, written here as they become known
        on the host."""
        from spark_rapids_tpu.columnar import encoding as _enc
        schema = self.output_schema
        is_cross = self.join_type == "cross"
        equi = self.join_type == "inner" and self.condition is None
        # BUILD: coalesce the build side to one batch
        # (RequireSingleBatch goal, GpuShuffledHashJoinExec.scala:83);
        # the right side is drained first, and an inner equi-join then
        # builds on the left instead where the left side is the smaller
        # and its keys are unique
        right = list(self.children[1].execute_columnar(ctx))
        stream = self.children[0].execute_columnar(ctx)
        built = probe = None
        if equi and self._sides_by_size:
            pulled, rest, smaller = _peek_smaller_left(right, stream)
            if smaller:
                built = self._build(pulled, True)
                probe = self._probe(*built)
                if probe[0] > 1:
                    # repeated keys would take the generic route, whose
                    # search runs over every row of the larger side
                    built = probe = None
            if built is None:
                stream = _handing_on(pulled, rest)
            else:
                pulled.clear()
                stream = _handing_on(right)
        build_left = built is not None
        if built is None:
            built = self._build(right, False)
            right.clear()
        jv, keys_key = built
        b_batch = jv.build_batch
        b_flat, b_sig = _enc.flat_and_sig(b_batch)
        seen["build_left"] = int(build_left)
        seen["build_rows"] = b_batch.rows_bound

        from spark_rapids_tpu.columnar.column import LazyRows
        # FK fast path: inner equi-join against UNIQUE build keys (the
        # dimension-table shape) fuses probe+verify+compact+gather into
        # one kernel with a static output capacity — no host sync per
        # batch (the general path needs one to size its expansion)
        if equi:
            max_run, klo, khi = probe or self._probe(jv, keys_key)
            fk = max_run <= 1
        else:
            fk, klo, khi = False, 0, -1
        # dense direct-address variant: a single integer key whose
        # observed range fits a lookup table replaces hash + bitonic
        # sort + log(n) binary-search gathers with ONE scatter + ONE
        # gather (every TPC dimension join is this shape)
        dense_cap = 0
        if fk and khi >= klo and khi - klo + 1 <= (1 << 24):
            dense_cap = bucket_capacity(max(8, khi - klo + 1))
        if fk:
            seen["route"] = "fk_dense" if dense_cap else "fk"
        from spark_rapids_tpu.utils.retry import (
            split_batch_half, with_retry,
        )
        if fk:
            def process_fk(sb):
                # one stream batch -> one joined batch; OOM here retries
                # after a catalog-wide spill, then on row-split halves
                # (reference RmmRapidsRetryIterator withRetry around the
                # probe, GpuHashJoin doJoin)
                with tracing.trace_range(tracing.SPAN_JOIN_PROBE,
                                         self.metrics["joinTime"]):
                    sv = jv.for_stream(sb)
                    vb_flat, vb_sig = _enc.flat_and_sig(sv.b_batch)
                    s_flat, s_sig = _enc.flat_and_sig(sv.s_batch)
                    kk = (tuple(e.key() for e in sv.lkeys),
                          tuple(e.key() for e in sv.rkeys),
                          self.join_type)
                    # the dense direct-address LUT is keyed in the
                    # code space when pairs ride codes — a dense-
                    # fallback stream batch takes the general FK kernel
                    if dense_cap and (sv.keys_tag == "code"
                                      or not jv.pairs):
                        fk_fn = _compile_fk_dense_join(
                            kk, sv.lkeys, sv.rkeys,
                            s_sig, vb_sig, sb.capacity,
                            b_batch.capacity, dense_cap)
                        outs, kept = fk_fn(
                            s_flat, sb.rows_traced, vb_flat,
                            b_batch.rows_traced, jnp.int64(klo))
                    else:
                        seen["route"] = "fk"
                        fk_fn = _compile_fk_join(
                            kk, sv.lkeys, sv.rkeys,
                            s_sig, vb_sig, sb.capacity,
                            b_batch.capacity)
                        outs, kept = fk_fn(
                            s_flat, sb.rows_traced,
                            vb_flat, b_batch.rows_traced)
                    self.metrics["fkFastPathBatches"].add(1)
                    rows = LazyRows(kept, sb.rows_bound)
                    if build_left:
                        outs, rows = _trim_to_count(outs, kept,
                                                    sb.capacity,
                                                    self.metrics)
                    return _wrap_joined(sv, outs, rows, schema,
                                        build_left)

            for s_batch in stream:
                seen["stream_rows"] += s_batch.rows_bound
                yield from with_retry(process_fk, s_batch, ctx,
                                      split=split_batch_half)
            return

        # band condition -> narrowed candidate ranges (the condition
        # post-filter below still runs: the probe only prunes)
        band = None
        if self.join_type == "inner" and self.condition is not None:
            band = _extract_band(
                self.condition,
                len(self.children[0].output_schema.fields),
                list(self.children[1].output_schema.fields))
            if band is not None:
                self.metrics["bandJoinProbes"].add(1)
                seen["route"] = "band"

        m_build_total = jnp.zeros(b_batch.capacity, jnp.int32)

        def process_stream(sb):
            # one stream batch -> (output batches, build-mask delta); the
            # build-mask delta is returned (not accumulated in place) so a
            # failed attempt that gets retried/split cannot double-count
            # matched build rows
            outs = []
            mb = None
            with tracing.trace_range(tracing.SPAN_JOIN_PROBE,
                                     self.metrics["joinTime"]):
                sv = jv.for_stream(sb)
                s_flat, s_sig = _enc.flat_and_sig(sv.s_batch)
                vb_flat, vb_sig = _enc.flat_and_sig(sv.b_batch)
                kk = (tuple(e.key() for e in sv.lkeys),
                      tuple(e.key() for e in sv.rkeys),
                      self.join_type)
                probe_fn = _compile_probe(
                    kk, sv.lkeys, sv.rkeys, s_sig,
                    sb.capacity, b_batch.capacity,
                    cross_count=True if is_cross else None, band=band)
                total, lo, inclusive, exclusive = probe_fn(
                    s_flat, sb.rows_traced, vb_flat,
                    b_batch.rows_traced)
                # the ONE host sync of the join: the candidate total sizes
                # the expand capacity (two-pass count/gather needs it);
                # every later count stays device-resident.  Memoized on
                # input buffer identity so re-running over the device scan
                # cache skips the link round trip entirely.
                from spark_rapids_tpu.utils.memo import memoized_pull
                memo_arrays = [a for t in (s_flat + vb_flat) for a in t
                               if a is not None]
                logical = ["join_total", kk, s_sig]
                for r in (sb.rows_traced, b_batch.rows_traced):
                    if isinstance(r, int):
                        logical.append(r)
                    else:
                        memo_arrays.append(r)
                n_candidates = memoized_pull(
                    tuple(logical), memo_arrays, lambda: int(total))
                out_cap = bucket_capacity(max(1, n_candidates))
                expand_fn = _compile_expand(
                    kk, sv.lkeys, sv.rkeys, s_sig,
                    vb_sig, sb.capacity, b_batch.capacity, out_cap,
                    is_cross, band=band)
                (keep, i, brow, kept, m_stream, m_build, unmatched,
                 n_unmatched, matched_sel, n_matched) = expand_fn(
                    s_flat, sb.rows_traced, vb_flat,
                    b_batch.rows_traced, lo, inclusive,
                    exclusive, total)
                jt = self.join_type
                if jt in ("right", "full"):
                    mb = m_build
                if jt in ("inner", "cross", "left", "right", "full"):
                    if n_candidates:
                        out = _gather_pairs(
                            sv, keep, i, brow,
                            LazyRows(kept, n_candidates), out_cap,
                            schema)
                        if self.condition is not None:
                            out = filter_batch(self.condition, out)
                            out.schema = schema
                        if not out.rows_known or out.num_rows:
                            outs.append(out)
                if jt in ("left", "full"):
                    outs.append(_gather_side_with_nulls(
                        sb, unmatched,
                        LazyRows(n_unmatched, sb.rows_bound),
                        self.children[1].output_schema.fields,
                        schema, side_first=True))
                if jt == "semi":
                    outs.append(_select_rows(
                        sb, matched_sel,
                        LazyRows(n_matched, sb.rows_bound), schema))
                if jt == "anti":
                    outs.append(_select_rows(
                        sb, unmatched,
                        LazyRows(n_unmatched, sb.rows_bound), schema))
            return outs, mb

        for s_batch in stream:
            seen["stream_rows"] += s_batch.rows_bound
            for outs, mb in with_retry(process_stream, s_batch, ctx,
                                       split=split_batch_half):
                if mb is not None:
                    m_build_total = m_build_total + mb
                yield from outs

        if self.join_type in ("right", "full"):
            unmatched_b, n_un_b = _compile_unmatched(b_batch.capacity)(
                m_build_total, b_batch.rows_traced)
            yield _gather_side_with_nulls(
                b_batch, unmatched_b,
                LazyRows(n_un_b, b_batch.rows_bound),
                self.children[0].output_schema.fields,
                schema, side_first=False)


def _select_rows(batch: ColumnarBatch, mask, count,
                 schema: Schema) -> ColumnarBatch:
    """Mask-compacted row select as ONE compiled kernel (shares the
    side-gather kernel with an empty null-extension)."""
    from spark_rapids_tpu.columnar import encoding
    from spark_rapids_tpu.columnar.column import rows_bound, rows_traced
    out_cap = bucket_capacity(max(1, rows_bound(count)))
    flat, sig = encoding.flat_and_sig(batch)
    fn = _compile_side_gather(sig, mask.shape[0], out_cap, ())
    outs, _ = fn(flat, mask, rows_traced(count))
    return encoding.wrap_gathered(batch.columns, outs, count, schema)


def _empty_batch(schema: Schema) -> ColumnarBatch:
    cols = [DeviceColumn.full_null(f.dtype, 0) for f in schema]
    return ColumnarBatch(cols, 0, schema)
