"""Out-of-core device execution (docs/out_of_core.md).

Grace-style partitioned operators: working sets far larger than HBM
execute on-TPU instead of degrading the whole fragment to the host
path.  The reference treats out-of-core as the production common case
(Theseus, PAPERS.md), and the data-movement discipline here is what
makes it viable:

* **hash join / hash aggregate** — phase 1 hash-partitions every input
  batch into K spill-resident partitions IN THE ENCODED DOMAIN
  (``partition_batch`` gathers dict codes / RLE / delta planes as-is;
  ``SpillableBatch`` spills the compressed planes through the existing
  three-tier path — values never densify on the way down); phase 2
  streams partition (pairs) back through HBM under the existing
  ``BufferCatalog`` budgets, with partition *i+1*'s tier promotions
  dispatched before partition *i* is handed to compute (the
  ``pipelined_h2d`` dispatch/finish split — thread-free, double
  buffered).  Each promoted partition runs the operator's own
  single-chip exec (``node.ici_fallback``) over a replayed
  ``_DrainedSource`` — co-partitioning by key hash makes that correct
  per partition for grouped aggregation and for all six equi-join
  types (null keys hash deterministically, so both sides of a pair
  agree).
* **sort** — phase 1 generates sorted runs on device (each HBM-sized
  chunk through the existing fused sort kernel, spilled as fixed-
  capacity blocks); phase 2 is a device K-way merge kernel over
  promoted run prefixes: one compiled step sorts the window of every
  run's next rows with a per-run LAST-LOADED flag appended as the
  least-significant ascending key, so every row ahead of the first
  flag is safely emittable and ONE ``device_pull`` per step returns
  the emit count plus per-run consumption.  Runs beyond
  ``spark.rapids.sql.ooc.sort.mergeWidth`` fold through intermediate
  passes.

K comes from the AQE byte statistics (total collected bytes vs the
stage budget, widened on a skew hint); a partition (pair) that still
exceeds budget recursively re-partitions with a RE-SALTED hash
(``partition_batch(salt=depth)``), bounded by
``spark.rapids.sql.ooc.maxRecursionDepth`` before a counted host
fallback.  The ``ooc.partition`` fault site degrades the whole
operator to the host path over its recovered input (``oocFallbacks``
counted, query correct).

Gated by ``spark.rapids.sql.ooc.enabled`` (default false =
byte-identical plans, results, and metric structure — the established
kill-switch contract).  tests/lint_robustness.py bans whole-input
materialization in this module: all data motion goes through the
counted spill/promote seams (``SpillableBatch`` registration and
``_promote_group``), never a full drain.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Iterator, List, Optional, Tuple

import jax.numpy as jnp

from spark_rapids_tpu import faults
from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch, estimate_batch_size_bytes,
)
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.compile.service import engine_jit
from spark_rapids_tpu.exec.base import ExecContext, TpuExec
from spark_rapids_tpu.exec.sortkeys import colval_sort_keys, sort_permutation
from spark_rapids_tpu.exprs.base import (
    ColVal, EvalContext, _batch_signature, _flatten_batch,
)
from spark_rapids_tpu.faults import InjectedFault
from spark_rapids_tpu.utils.kernel_cache import KernelCache
from spark_rapids_tpu.utils.metrics import (
    METRIC_OOC_FALLBACKS, METRIC_OOC_PARTITIONS, METRIC_OOC_RECURSIONS,
    METRIC_OOC_SPILL_BYTES,
)

log = logging.getLogger("spark_rapids_tpu.ooc")

FAULT_SITE_PARTITION = "ooc.partition"

# ---------------------------------------------------------------------------
# Process-wide OOC statistics (the `ooc` object in bench.py's summary,
# mirroring the ici/prefetch/d2h global stats convention)
# ---------------------------------------------------------------------------

_OOC_LOCK = threading.Lock()
_OOC_STATS = {
    # spill-resident partitions (and sort runs) the grace phase created
    "partitions": 0,
    # bytes written through the partition-spill seam (encoded planes
    # spill as-is, so this is the COMPRESSED footprint)
    "spill_bytes": 0,
    # re-salted recursive re-partitions of over-budget partitions, plus
    # intermediate sort merge passes beyond ooc.sort.mergeWidth
    "recursions": 0,
    # operators (or single partitions) degraded to the host path — an
    # injected ooc.partition fault or the recursion bound
    "fallbacks": 0,
    # wall ms of partition-i+1 promote dispatch overlapped with
    # partition-i compute (the pipelined_h2d overlap convention)
    "promote_overlap_ms": 0,
    # device K-way merge kernel steps (one device_pull each)
    "merge_steps": 0,
}


def _bump(key: str, v) -> None:
    with _OOC_LOCK:
        _OOC_STATS[key] += v


def ooc_stats() -> dict:
    with _OOC_LOCK:
        return dict(_OOC_STATS)


def reset_ooc_stats() -> None:
    with _OOC_LOCK:
        for k in _OOC_STATS:
            _OOC_STATS[k] = 0


# ---------------------------------------------------------------------------
# Qualification + shared plumbing
# ---------------------------------------------------------------------------

def qualifies(node: TpuExec, ctx: ExecContext, handle_sets) -> bool:
    """Fragment qualification (replaces the blanket over-budget degrade
    for collected inputs): OOC engages only when enabled, the fragment
    has a host path to re-parent per partition, and the COLLECTED input
    actually exceeds ``spark.rapids.shuffle.ici.maxStageBytes`` — an
    in-budget stage keeps the one-shot collective, byte-identical."""
    if node.ici_fallback is None or not ctx.conf.ooc_enabled:
        return False
    total = sum(sb.size for hs in handle_sets for sb in hs)
    return total > ctx.conf.ici_max_stage_bytes


def _budget(ctx: ExecContext) -> int:
    return max(1, ctx.conf.ici_max_stage_bytes)


def _pick_k(ctx: ExecContext, total: int, budget: int) -> int:
    """Partition count: the conf override when set, else sized so each
    partition lands near HALF the stage budget (phase 2 double-buffers
    two partitions), widened 2x when the AQE exchange statistics carry
    a skew hint (max/median partition bytes > 4) — a skewed key space
    needs more buckets for the heavy key's neighbors to fit."""
    k = ctx.conf.ooc_partitions
    if k > 0:
        return k
    k = max(2, -(-2 * total // budget))
    from spark_rapids_tpu.exec.aqe import global_stats
    g = global_stats()
    med = g.get("median_partition_bytes") or 0
    mx = g.get("max_partition_bytes") or 0
    if med and mx / med > 4:
        k *= 2
    return int(min(64, k))


def _promote_group(handles, ctx: ExecContext) -> List[ColumnarBatch]:
    """The ONE promote seam: pin every handle BEFORE reserving (so
    making room cannot demote the partition being promoted), reserve
    once for the whole group, materialize, release the handles.  All
    promote traffic is counted by the catalog (unspill_count / the
    spill.promote fault site inside ``SpillableBatch.get``)."""
    from spark_rapids_tpu.memory.spill import TIER_DEVICE, close_all
    if not handles:
        return []
    dev = ctx.runtime.device
    cat = ctx.runtime.catalog
    with cat._lock:
        for sb in handles:
            sb.pinned = True
    try:
        cat.reserve(sum(sb.size for sb in handles
                        if sb.tier != TIER_DEVICE))
        out = [sb.get(dev) for sb in handles]
    finally:
        close_all(handles)
    return out


def _run_host_path(node: TpuExec, ctx: ExecContext,
                   inputs: List[List[ColumnarBatch]]):
    """Run the operator's original single-chip exec over replayed
    batches — phase 2's per-partition compute AND the counted fallback
    path share this, so the two can never diverge in how the host path
    is re-parented (mirrors meshexec._host_fallback, multi-batch)."""
    from spark_rapids_tpu.exec.meshexec import _DrainedSource
    fb = node.ici_fallback
    fb.children = [
        _DrainedSource(batches, c.output_schema)
        for batches, c in zip(inputs, node.children)]
    return fb.execute_columnar(ctx)


def _note_fallback(node: TpuExec, reason: str) -> None:
    _bump("fallbacks", 1)
    node.metrics[METRIC_OOC_FALLBACKS].add(1)
    log.warning("ooc operator degraded to host path (%s): %s",
                node.node_name, reason)


def _note_recursion(node: TpuExec) -> None:
    _bump("recursions", 1)
    node.metrics[METRIC_OOC_RECURSIONS].add(1)


def _note_partition_phase(node: TpuExec, k: int, spilled: int,
                          salt: int, depth: int) -> None:
    _bump("partitions", k)
    _bump("spill_bytes", spilled)
    node.metrics[METRIC_OOC_PARTITIONS].add(k)
    node.metrics[METRIC_OOC_SPILL_BYTES].add(spilled)
    from spark_rapids_tpu.obs import journal
    if journal.enabled():
        journal.emit(journal.EVENT_OOC_PARTITION, node=node.node_name,
                     parts=k, bytes=spilled, salt=salt, depth=depth)


# ---------------------------------------------------------------------------
# Phase 1: grace partitioning (encoded domain, one batch in HBM at a time)
# ---------------------------------------------------------------------------

def _partition_handles(node: TpuExec, ctx: ExecContext, handles,
                       keys, k: int, salt: int, depth: int):
    """Hash-partition collected handles into ``k`` spill-resident
    partitions.  One input batch is promoted at a time; its partition
    slices re-register as spillable handles (encoded planes spill
    as-is) so at no point does more than one source batch plus its
    slices sit in HBM.  Returns ``(parts, None)`` on success, or
    ``(None, recovered)`` when the injected ``ooc.partition`` fault
    fired — ``recovered`` is the FULL input as plain batches for the
    host path (partition spill reclaimed; nothing lost).  Consumes
    every handle either way."""
    from spark_rapids_tpu.exec.exchange import partition_batch
    from spark_rapids_tpu.memory.spill import SpillableBatch, close_all
    cat = ctx.runtime.catalog
    parts: List[List] = [[] for _ in range(k)]
    spilled = 0
    remaining = list(handles)
    try:
        while remaining:
            b = _promote_group([remaining.pop(0)], ctx)[0]
            try:
                faults.maybe_fail(
                    FAULT_SITE_PARTITION,
                    f"injected ooc partition-write failure "
                    f"(k={k}, salt={salt}, depth={depth})")
            except InjectedFault as e:
                if e.site != FAULT_SITE_PARTITION:
                    raise
                # degrade: reclaim the partial partition spill plus the
                # un-partitioned tail into host-path input batches
                recovered: List[ColumnarBatch] = []
                for lst in parts:
                    recovered.extend(_promote_group(lst, ctx))
                recovered.append(b)
                while remaining:
                    recovered.extend(
                        _promote_group([remaining.pop(0)], ctx))
                _note_fallback(node, str(e))
                return None, recovered
            pieces = partition_batch(b, k, keys, salt=salt)
            del b
            for pi, piece in enumerate(pieces):
                if piece is None:
                    continue
                h = SpillableBatch(piece, cat)
                parts[pi].append(h)
                spilled += h.size
    except BaseException:
        for lst in parts:
            close_all(lst)
        close_all(remaining)
        raise
    _note_partition_phase(node, k, spilled, salt, depth)
    return parts, None


def _stream_groups(groups, ctx: ExecContext):
    """Yield ``(key, [batches])`` per partition group with the NEXT
    group's tier promotions dispatched before the current group is
    handed to compute — ``jax.device_put`` is asynchronous, so
    partition i+1's host->device copies proceed while the consumer
    computes on partition i (the pipelined_h2d dispatch/finish split,
    thread-free).  The dispatch wall time is the overlapped leg
    (``promote_overlap_ms``)."""
    nxt: Optional[List[ColumnarBatch]] = None
    for pos, (gkey, hs) in enumerate(groups):
        cur = nxt if nxt is not None else _promote_group(hs, ctx)
        nxt = None
        if pos + 1 < len(groups):
            t0 = time.perf_counter_ns()
            nxt = _promote_group(groups[pos + 1][1], ctx)
            _bump("promote_overlap_ms",
                  (time.perf_counter_ns() - t0) // 1_000_000)
        yield gkey, cur


# ---------------------------------------------------------------------------
# Grace hash aggregate
# ---------------------------------------------------------------------------

def run_aggregate(node: TpuExec, ctx: ExecContext, handles,
                  depth: int = 0) -> Iterator[ColumnarBatch]:
    """Two-phase grouped aggregation: partition by the grouping keys
    (group key sets are disjoint across partitions, so per-partition
    aggregation is exact), stream each partition through the original
    single-chip exec."""
    budget = _budget(ctx)
    total = sum(sb.size for sb in handles)
    k = _pick_k(ctx, total, budget)
    parts, recovered = _partition_handles(
        node, ctx, handles, node.groupings, k, salt=depth, depth=depth)
    if recovered is not None:
        yield from _run_host_path(node, ctx, [recovered])
        return
    small, big = [], []
    for i, hs in enumerate(parts):
        if not hs:
            continue
        tgt = big if sum(sb.size for sb in hs) > budget else small
        tgt.append((i, hs))
    for _i, batches in _stream_groups(small, ctx):
        yield from _run_host_path(node, ctx, [batches])
    for i, hs in big:
        if depth < ctx.conf.ooc_max_recursion_depth:
            _note_recursion(node)
            yield from run_aggregate(node, ctx, hs, depth + 1)
        else:
            _note_fallback(
                node, f"partition {i} still over budget at "
                f"ooc.maxRecursionDepth={depth}")
            yield from _run_host_path(node, ctx,
                                      [_promote_group(hs, ctx)])


# ---------------------------------------------------------------------------
# Grace hash join
# ---------------------------------------------------------------------------

def run_join(node: TpuExec, ctx: ExecContext, lh, rh,
             depth: int = 0) -> Iterator[ColumnarBatch]:
    """Two-phase repartition join: co-partition BOTH sides by the join
    key hash with the same k and salt — every left row's potential
    matches land in the same partition pair, which makes per-pair
    execution of the original join exec exact for all six equi-join
    types (outer/semi/anti included: a side's unmatched rows are
    unmatched within their pair)."""
    from spark_rapids_tpu.memory.spill import close_all
    budget = _budget(ctx)
    total = sum(sb.size for sb in lh) + sum(sb.size for sb in rh)
    k = _pick_k(ctx, total, budget)
    try:
        lparts, lrec = _partition_handles(
            node, ctx, lh, node.left_keys, k, salt=depth, depth=depth)
    except BaseException:
        close_all(rh)
        raise
    if lrec is not None:
        yield from _run_host_path(node, ctx,
                                  [lrec, _promote_group(rh, ctx)])
        return
    try:
        rparts, rrec = _partition_handles(
            node, ctx, rh, node.right_keys, k, salt=depth, depth=depth)
    except BaseException:
        for lst in lparts:
            close_all(lst)
        raise
    if rrec is not None:
        lbatches: List[ColumnarBatch] = []
        for lst in lparts:
            lbatches.extend(_promote_group(lst, ctx))
        yield from _run_host_path(node, ctx, [lbatches, rrec])
        return
    small, big = [], []
    for i in range(k):
        ls, rs = lparts[i], rparts[i]
        if not ls and not rs:
            continue
        sz = sum(sb.size for sb in ls) + sum(sb.size for sb in rs)
        (big if sz > budget else small).append((i, ls, rs))
    groups = [((i, len(ls)), ls + rs) for i, ls, rs in small]
    for (_i, nl), batches in _stream_groups(groups, ctx):
        yield from _run_host_path(node, ctx,
                                  [batches[:nl], batches[nl:]])
    for i, ls, rs in big:
        if depth < ctx.conf.ooc_max_recursion_depth:
            _note_recursion(node)
            yield from run_join(node, ctx, ls, rs, depth + 1)
        else:
            _note_fallback(
                node, f"partition pair {i} still over budget at "
                f"ooc.maxRecursionDepth={depth}")
            yield from _run_host_path(
                node, ctx,
                [_promote_group(ls, ctx), _promote_group(rs, ctx)])


# ---------------------------------------------------------------------------
# Out-of-core sort: run generation + device K-way merge
# ---------------------------------------------------------------------------

_MERGE_CACHE = KernelCache("ooc.merge", 64)


def _compile_merge_step(orders_key: tuple, orders, sig, block_cap: int,
                        k: int):
    """One K-way merge step as ONE fused kernel: window = 2 blocks per
    run; each run's LAST-LOADED row carries a flag that sorts as the
    least-significant ASCENDING key — after the windowed sort, every
    row ahead of the first flag is ≤ every unloaded row of every run
    (ties are fine: any order among equal keys is a valid sort), so
    the emit count and per-run consumption come back in one pull."""
    key = (orders_key, sig, block_cap, k)
    fn = _MERGE_CACHE.get(key)
    if fn is not None:
        return fn
    w = 2 * block_cap * k

    def run(flats, starts, lens_a, lens_b, flags):
        from spark_rapids_tpu.columnar.gatherfab import gather_planes
        ncols = len(flats[0])
        cols = []
        for ci in range(ncols):
            datas = [rb[ci][0] for rb in flats]
            valids = [rb[ci][1] for rb in flats]
            chars = [rb[ci][2] for rb in flats]
            data = jnp.concatenate(datas, axis=0)
            valid = jnp.concatenate(valids, axis=0)
            ch = None if chars[0] is None \
                else jnp.concatenate(chars, axis=0)
            cols.append(ColVal(data, valid, ch))
        run_of = jnp.repeat(jnp.arange(k, dtype=jnp.int32),
                            2 * block_cap)
        posin = jnp.tile(jnp.arange(2 * block_cap, dtype=jnp.int32), k)
        st = starts[run_of]
        la = lens_a[run_of]
        lb = lens_b[run_of]
        # live rows: slot a carries [start, lens_a), slot b [0, lens_b)
        live = jnp.where(posin < block_cap,
                         (posin >= st) & (posin < la),
                         (posin - block_cap) < lb)
        loaded_last = jnp.where(lb > 0, block_cap + lb - 1, la - 1)
        flag = flags[run_of] & (posin == loaded_last) & live
        # the bitonic sort needs a power-of-two window: pad with dead
        # rows (live False sorts last, run id k never matches a count)
        w2 = 1 << (w - 1).bit_length()
        pad = w2 - w
        if pad:
            def padp(a):
                if a is None:
                    return None
                return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
            cols = [ColVal(padp(cv.data), padp(cv.validity),
                           padp(cv.chars)) for cv in cols]
            run_of = jnp.pad(run_of, (0, pad), constant_values=k)
            live = jnp.pad(live, (0, pad), constant_values=False)
            flag = jnp.pad(flag, (0, pad), constant_values=False)
        ectx = EvalContext(cols, jnp.sum(live.astype(jnp.int32)), w2)
        all_keys = []
        for expr, asc, nf in orders:
            cv = expr.emit(ectx)
            all_keys.extend(
                colval_sort_keys(cv, expr.dtype, asc, nf))
        all_keys.append(flag.astype(jnp.int32))
        perm = sort_permutation(all_keys, w2, live_first=live)
        planes = [p for cv in cols
                  for p in (cv.data, cv.validity, cv.chars)]
        planes += [run_of, flag, live]
        g = gather_planes(planes, perm)
        s_run, s_flag, s_live = g[-3], g[-2], g[-1]
        total_live = jnp.sum(s_live.astype(jnp.int32))
        posw = jnp.arange(w2, dtype=jnp.int32)
        flag_pos = jnp.where(s_flag & s_live, posw, w2)
        emit_n = jnp.minimum(jnp.min(flag_pos), total_live)
        emitted = posw < emit_n
        counts = jnp.sum(
            (s_run[None, :] == jnp.arange(k, dtype=jnp.int32)[:, None])
            & emitted[None, :] & s_live[None, :], axis=1).astype(
                jnp.int32)
        outs = []
        for ci in range(ncols):
            outs.append((g[3 * ci], g[3 * ci + 1] & emitted,
                         g[3 * ci + 2]))
        return tuple(outs), emit_n, counts

    fn = engine_jit(run, family="exchange", name="ooc_merge")
    _MERGE_CACHE[key] = fn
    return fn


def _block_rows(budget: int, width: int, row_bytes: int) -> int:
    """Power-of-two merge block rows sized so the whole window (2
    blocks x mergeWidth runs) stays near the stage budget."""
    b = budget // max(1, 2 * width * row_bytes)
    b = 1 << max(4, (int(b) or 1).bit_length() - 1)
    return min(b, 1 << 15)


def _spill_run(batch: ColumnarBatch, block_rows: int,
               ctx: ExecContext):
    """Split one sorted chunk into fixed-capacity spill blocks (the
    padded gather keeps every block's kernel signature identical, so
    the merge step compiles once)."""
    from spark_rapids_tpu.memory.spill import SpillableBatch
    cat = ctx.runtime.catalog
    n = batch.num_rows
    blocks: List[Tuple] = []
    nbytes = 0
    for start_row in range(0, max(n, 1), block_rows):
        rows = min(block_rows, n - start_row)
        if rows <= 0:
            break
        idx = jnp.arange(block_rows, dtype=jnp.int32) \
            + jnp.int32(start_row)
        h = SpillableBatch(batch.gather(idx, rows), cat)
        blocks.append((h, rows))
        nbytes += h.size
    return blocks, nbytes


def _widen(batch: ColumnarBatch, widths) -> ColumnarBatch:
    """Pad string char matrices to the merge-wide width (runs sorted
    from different chunks may have bucketed different max lengths;
    zero padding preserves the padded-matrix compare semantics)."""
    cols = []
    changed = False
    for c, wd in zip(batch.columns, widths):
        if wd and c.chars is not None and c.chars.shape[1] < wd:
            ch = jnp.pad(c.chars,
                         ((0, 0), (0, wd - c.chars.shape[1])))
            cols.append(DeviceColumn(c.dtype, c.data, c.validity,
                                     batch.rows_raw, chars=ch))
            changed = True
        else:
            cols.append(c)
    if not changed:
        return batch
    return ColumnarBatch(cols, batch.rows_raw, batch.schema)


class _RunCursor:
    """Host-side cursor over one spilled run: the current 2-block
    window, the consumed offset within block a, and lazy promotion of
    the next block as the cursor advances (counted as promote
    overlap: the dispatch lands while the consumer computes on the
    previous step's emit)."""

    __slots__ = ("blocks", "j", "start", "a", "rows_a", "b", "rows_b",
                 "widths")

    def __init__(self, blocks, ctx: ExecContext, widths):
        self.blocks = blocks
        self.widths = widths
        self.j = 0
        self.start = 0
        self.a, self.rows_a = self._take(0, ctx, initial=True)
        self.b, self.rows_b = self._take(1, ctx, initial=True)

    def _take(self, j: int, ctx: ExecContext, initial: bool = False):
        if j >= len(self.blocks):
            return None, 0
        sb, rows = self.blocks[j]
        t0 = time.perf_counter_ns()
        b = _widen(_promote_group([sb], ctx)[0], self.widths)
        if not initial:
            _bump("promote_overlap_ms",
                  (time.perf_counter_ns() - t0) // 1_000_000)
        return b, rows

    @property
    def exhausted(self) -> bool:
        return self.start >= self.rows_a and self.b is None

    @property
    def has_more(self) -> bool:
        # blocks beyond the window: the last loaded row must carry the
        # merge flag, or rows behind it could be emitted too early
        return self.j + 2 < len(self.blocks)

    def consume(self, n: int, ctx: ExecContext) -> None:
        self.start += n
        while self.rows_a and self.start >= self.rows_a \
                and self.b is not None:
            self.start -= self.rows_a
            self.j += 1
            self.a, self.rows_a = self.b, self.rows_b
            self.b, self.rows_b = self._take(self.j + 1, ctx)


def _merge_stream(node: TpuExec, ctx: ExecContext, runs,
                  block_rows: int) -> Iterator[ColumnarBatch]:
    """Device K-way merge over promoted run prefixes: one compiled
    step per iteration, ONE device_pull per step (emit count + per-run
    consumption), refills promoted as cursors advance."""
    from spark_rapids_tpu.columnar.dtypes import STRING
    from spark_rapids_tpu.columnar.transfer import device_pull
    k = len(runs)
    schema = node.output_schema
    # merge-wide char widths: runs sorted from different chunks can
    # bucket different max string lengths, but one compiled step needs
    # one signature — probe every run's first block and widen the rest
    widths = [0] * len(schema.fields)
    if any(f.dtype == STRING for f in schema.fields):
        from spark_rapids_tpu.memory.spill import SpillableBatch
        cat = ctx.runtime.catalog
        for blocks in runs:
            b = _promote_group([blocks[0][0]], ctx)[0]
            for ci, c in enumerate(b.columns):
                if c.chars is not None:
                    widths[ci] = max(widths[ci],
                                     int(c.chars.shape[1]))
            # re-register so the cursor promotes it like any block
            blocks[0] = (SpillableBatch(b, cat), blocks[0][1])
    cursors = [_RunCursor(blocks, ctx, widths) for blocks in runs]
    orders_key = tuple((e.key(), asc, nf)
                       for e, asc, nf in node.orders)
    fn = None
    while not all(c.exhausted for c in cursors):
        flats = []
        starts, lens_a, lens_b, flags = [], [], [], []
        for c in cursors:
            fa = _flatten_batch(c.a)
            fb = _flatten_batch(c.b) if c.b is not None else fa
            flats.append(fa)
            flats.append(fb)
            starts.append(min(c.start, c.rows_a))
            lens_a.append(c.rows_a)
            lens_b.append(c.rows_b if c.b is not None else 0)
            flags.append(c.has_more)
        if fn is None:
            fn = _compile_merge_step(
                orders_key, node.orders,
                _batch_signature(cursors[0].a), block_rows, k)
        outs, emit_n, counts = fn(
            tuple(flats),
            jnp.asarray(starts, jnp.int32),
            jnp.asarray(lens_a, jnp.int32),
            jnp.asarray(lens_b, jnp.int32),
            jnp.asarray(flags, jnp.bool_))
        e_h, cnts_h = device_pull((emit_n, counts))
        e = int(e_h)
        _bump("merge_steps", 1)
        if e <= 0:
            raise RuntimeError(
                "ooc merge made no progress (window invariant broken)")
        # advance cursors FIRST: the refill promotes dispatch while the
        # consumer computes on the emitted batch below
        for c, n in zip(cursors, [int(x) for x in cnts_h]):
            c.consume(n, ctx)
        cols = [DeviceColumn(f.dtype, d, v, e, chars=ch)
                for f, (d, v, ch) in zip(schema, outs)]
        yield ColumnarBatch(cols, e, schema)


def run_sort(node: TpuExec, ctx: ExecContext,
             handles) -> Iterator[ColumnarBatch]:
    """Out-of-core global sort: sorted-run generation through the
    existing fused sort kernel (one HBM-sized chunk at a time), then
    the device K-way merge.  Emits a STREAM of sorted batches in
    global order — the out-of-core shape never materializes the whole
    output in one batch."""
    from spark_rapids_tpu.exec.coalesce import concat_batches
    from spark_rapids_tpu.exec.sort import sort_batch
    budget = _budget(ctx)
    width = max(2, ctx.conf.ooc_sort_merge_width)
    row_bytes = max(1, estimate_batch_size_bytes(node.output_schema, 1))
    block_rows = _block_rows(budget, width, row_bytes)
    runs = []
    spilled = 0
    group: List = []
    gbytes = 0
    remaining = list(handles)
    try:
        while remaining:
            sb = remaining.pop(0)
            group.append(sb)
            gbytes += sb.size
            if gbytes < max(1, budget // 2) and remaining:
                continue
            try:
                faults.maybe_fail(
                    FAULT_SITE_PARTITION,
                    f"injected ooc run-spill failure "
                    f"({len(runs)} runs written)")
            except InjectedFault as e:
                if e.site != FAULT_SITE_PARTITION:
                    raise
                recovered: List[ColumnarBatch] = []
                for blocks in runs:
                    recovered.extend(_promote_group(
                        [blk for blk, _ in blocks], ctx))
                recovered.extend(_promote_group(group, ctx))
                while remaining:
                    recovered.extend(
                        _promote_group([remaining.pop(0)], ctx))
                _note_fallback(node, str(e))
                yield from _run_host_path(node, ctx, [recovered])
                return
            batches = _promote_group(group, ctx)
            group, gbytes = [], 0
            chunk = batches[0] if len(batches) == 1 \
                else concat_batches(batches)
            del batches
            # a single upstream batch can exceed the chunk target (the
            # giant-batch ingest case): slice it into HBM-sized chunks
            # so every run's sort stays within budget
            max_rows = max(1, (budget // 2) // row_bytes)
            cap = 1 << max(3, max_rows.bit_length() - 1)
            n_chunk = chunk.num_rows
            starts = range(0, max(n_chunk, 1), cap) if n_chunk > cap \
                else (0,)
            for c0 in starts:
                rows = min(cap, n_chunk - c0)
                if n_chunk > cap:
                    idx = jnp.arange(cap, dtype=jnp.int32) \
                        + jnp.int32(c0)
                    piece = chunk.gather(idx, rows)
                else:
                    piece = chunk
                sorted_chunk = sort_batch(node.orders, piece)
                del piece
                blocks, nbytes = _spill_run(sorted_chunk, block_rows,
                                            ctx)
                del sorted_chunk
                if blocks:
                    runs.append(blocks)
                    spilled += nbytes
            del chunk
    except BaseException:
        from spark_rapids_tpu.memory.spill import close_all
        for blocks in runs:
            close_all([blk for blk, _ in blocks])
        close_all(group)
        close_all(remaining)
        raise
    if not runs:
        return
    _note_partition_phase(node, len(runs), spilled, salt=0, depth=0)
    if len(runs) == 1:
        # a single run is already globally sorted: stream its blocks
        for blk, _rows in runs[0]:
            yield _promote_group([blk], ctx)[0]
        return
    while len(runs) > width:
        # intermediate pass: fold the first `width` runs into one
        _note_recursion(node)
        merged: List[Tuple] = []
        mbytes = 0
        head, runs = runs[:width], runs[width:]
        for out in _merge_stream(node, ctx, head, block_rows):
            blocks, nbytes = _spill_run(out, block_rows, ctx)
            merged.extend(blocks)
            mbytes += nbytes
        runs.append(merged)
        _bump("spill_bytes", mbytes)
        node.metrics[METRIC_OOC_SPILL_BYTES].add(mbytes)
    yield from _merge_stream(node, ctx, runs, block_rows)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

def run_single(node: TpuExec, ctx: ExecContext,
               handles) -> Iterator[ColumnarBatch]:
    """Single-child entry (meshexec._single_child_collective): grouped
    aggregate or global sort, by node shape."""
    if getattr(node, "groupings", None) is not None:
        return run_aggregate(node, ctx, handles)
    return run_sort(node, ctx, handles)
