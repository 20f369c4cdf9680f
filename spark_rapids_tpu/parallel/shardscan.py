"""Sharded scan ingest: data-parallel scan→mesh pipelines with
per-chip H2D streams (docs/sharded_scan.md).

PR 6's ICI lowering delivered the *exchange* half of the mesh promise —
``all_to_all`` collectives move shuffle bytes over the interconnect —
but its ingest still ran the whole scan on the single-chip host path
(``exec/meshexec.py:_drain_single_batch``), fully drained it, then
re-split host-side via ``parallel/mesh.py:shard_table``: one H2D
stream, one chip's upload bandwidth, and a host round trip per
fragment, on a link measured at ~45 MB/s (BENCH_r05).  The reference
plugin's accelerated shuffle keeps data device-resident end to end
(PAPER.md §7) and Theseus (PAPERS.md) shows data movement — not
compute — dominates distributed accelerator SQL; eight chips have
eight independent H2D streams and the drained ingest used one.

This module is the missing ingest half.  For a guarded mesh fragment
whose input subtree bottoms out in a file scan (optionally under
project/filter/fused-stage/coalesce ops — qualified by
``mark_sharded_scans`` at plan time), the ingest:

1. **partitions the input** across the mesh — files greedily by size
   (LPT, so skewed file sizes still balance), and for parquet inputs
   with fewer files than chips, ROW GROUPS round-robin within each
   file (``ParquetPartitionReader.rg_shard``);
2. **runs one scan pipeline per shard** — the per-shard operator chain
   is a clone of the fragment's own subtree over the shard's file
   subset, executing under a shard ``ExecContext`` whose runtime
   device is that shard's chip, so the existing machinery is reused
   whole: bounded background prefetch/decode (io/prefetch.py, one
   ``srt-`` producer per shard, leak-audited), staging-admitted
   dispatch-overlapped uploads (``columnar/transfer.py:pipelined_h2d``
   — ``jax.device_put`` to a COMMITTED per-shard device is the
   dedicated per-chip H2D stream), scan caches, and the fused stage /
   encoded-plane kernels of PR 3/12, which execute per-shard ON that
   shard's chip before any collective;
3. **stacks device-resident** — each shard's batches concatenate in
   one per-chip kernel to a common capacity, and the per-shard planes
   assemble into global mesh-sharded arrays
   (``jax.make_array_from_single_device_arrays`` — zero copies, zero
   host round trips) that feed the shard_map exchange program
   directly (``run_stacked`` on the dist pipelines): no full host
   drain, no ``shard_table`` re-split.

The egress direction is mirrored by ``mesh.gather_stacked``'s
``parallel_pull`` mode: one concurrent ``device_pull`` per chip
instead of one serial pull carrying every chip's bytes.

Fallback matrix (docs/sharded_scan.md): an injected
``shuffle.ici.ingest`` fault or a RESOURCE_EXHAUSTED during ingest
abandons the shard pipelines and the fragment degrades to the host
path over a freshly drained input (reason ``ingest`` in
``iciFallbacks``); a failure at the collective itself keeps the
standard ``_guarded_collective`` matrix, with the drained-input host
fallback materialized from the stacked planes (``ShardedInput.drain``
— per-chip parallel pulls).  With
``spark.rapids.shuffle.ici.shardedScan.enabled`` false (default)
nothing here runs and plans/results/metrics are byte-identical.
"""

from __future__ import annotations

import copy
import logging
import os
import threading
import time
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import (
    DeviceColumn, LazyRows, bucket_capacity,
)
from spark_rapids_tpu.compile.service import engine_jit
from spark_rapids_tpu.exec.base import ExecContext
from spark_rapids_tpu.parallel.mesh import DATA_AXIS
from spark_rapids_tpu.utils.kernel_cache import KernelCache

log = logging.getLogger("spark_rapids_tpu.shardscan")

FAULT_SITE_INGEST = "shuffle.ici.ingest"

# sentinel: the sharded scan ran and found NO input batches anywhere —
# the fragment short-circuits exactly like an empty drained input
EMPTY = object()

# ---------------------------------------------------------------------------
# Process-wide ingest statistics (the `sharded_ingest` object in
# bench.py's summary line, beside the prefetch/d2h/ici stats)
# ---------------------------------------------------------------------------

_STATS_LOCK = threading.Lock()
_STATS = {
    # fragments whose input arrived device-resident through per-chip
    # shard pipelines
    "fragments": 0,
    # shard pipelines those fragments ran (shards with assigned input)
    "shards": 0,
    # input files partitioned across the mesh
    "files": 0,
    # device batches the shard pipelines produced
    "batches": 0,
    # device-layout bytes the per-chip H2D streams landed (static
    # plane arithmetic, no sync) — aggregate_h2d_mbps = bytes/wall
    "bytes": 0,
    # wall time of the ingest phase (decode + per-chip uploads +
    # per-shard chain + stacking), accumulated in NANOSECONDS so
    # sub-millisecond fragments are not floored away (global_stats
    # exposes the ingest_ms the bench throughput number divides by)
    "ingest_ns": 0,
}


def _bump(key: str, v: int) -> None:
    if v:
        with _STATS_LOCK:
            _STATS[key] += int(v)


def global_stats() -> dict:
    with _STATS_LOCK:
        out = dict(_STATS)
    out["ingest_ms"] = out.pop("ingest_ns") // 1_000_000
    return out


def reset_stats() -> None:
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0


# ---------------------------------------------------------------------------
# Qualification (plan time): which fragment inputs can shard
# ---------------------------------------------------------------------------

class ShardSpec:
    """One qualifying fragment input: the unary operator chain (top to
    bottom, scan excluded) and the multi-file scan it bottoms out in.
    Attached to guarded mesh execs as ``node.sharded_scan`` by
    ``mark_sharded_scans``; consumed at execution by
    ``ingest_child``."""

    __slots__ = ("chain", "scan")

    def __init__(self, chain: List, scan):
        self.chain = list(chain)
        self.scan = scan

    @property
    def schema(self):
        return self.chain[0].output_schema if self.chain \
            else self.scan.output_schema


def _scan_types() -> tuple:
    from spark_rapids_tpu.io.csv import TpuCsvScanExec
    from spark_rapids_tpu.io.orc import TpuOrcScanExec
    from spark_rapids_tpu.io.parquet import TpuParquetScanExec
    return (TpuParquetScanExec, TpuOrcScanExec, TpuCsvScanExec)


def _chain_ok(node) -> bool:
    """True when ``node`` is a shard-safe unary wrapper: deterministic
    (a re-run on the host fallback path must reproduce it) and
    row-stream-local (per-shard execution sees a subset of batches,
    which must not change per-row results)."""
    from spark_rapids_tpu.exec.basic import TpuFilterExec, TpuProjectExec
    from spark_rapids_tpu.exec.coalesce import TpuCoalesceBatchesExec
    from spark_rapids_tpu.exec.stage import TpuStageExec
    from spark_rapids_tpu.exprs.nondeterministic import (
        contains_nondeterministic,
    )
    if len(getattr(node, "children", ())) != 1:
        return False
    if isinstance(node, TpuCoalesceBatchesExec):
        return True
    if isinstance(node, TpuStageExec):
        return not node.nondeterministic
    if isinstance(node, TpuProjectExec):
        return not any(contains_nondeterministic(e) for e in node.exprs)
    if isinstance(node, TpuFilterExec):
        return not contains_nondeterministic(node.pred)
    return False


def qualify_child(child) -> Optional[ShardSpec]:
    """Walk one fragment input subtree; a ShardSpec when it is a
    shard-safe unary chain over a multi-file-capable scan, else None
    (the fragment keeps the drained ingest)."""
    chain: List = []
    node = child
    while True:
        if isinstance(node, _scan_types()):
            if not getattr(node, "paths", None):
                return None
            return ShardSpec(chain, node)
        if not _chain_ok(node):
            return None
        chain.append(node)
        node = node.children[0]


def mark_sharded_scans(physical, conf):
    """Planner pass (plan/planner.py:plan_query, after coalesce
    insertion so the chain it qualifies is the tree that will
    execute): stamp every guarded ICI mesh exec with the per-child
    ShardSpecs.  Gated on
    ``spark.rapids.shuffle.ici.shardedScan.enabled`` — off never
    touches a node, so plans stay byte-identical."""
    if not conf.ici_sharded_scan:
        return physical
    from spark_rapids_tpu.exec.meshexec import (
        TpuMeshAggregateExec, TpuMeshHashJoinExec, TpuMeshSortExec,
    )
    mesh_types = (TpuMeshAggregateExec, TpuMeshSortExec,
                  TpuMeshHashJoinExec)

    def walk(node):
        for c in node.children:
            walk(c)
        if isinstance(node, mesh_types) and node.ici_fallback is not None:
            specs = [qualify_child(c) for c in node.children]
            if any(s is not None for s in specs):
                node.sharded_scan = specs

    walk(physical)
    return physical


# ---------------------------------------------------------------------------
# Shard assignment: files by size (LPT), parquet row groups by modulo
# ---------------------------------------------------------------------------

def assign_files(sizes: List[int], n_shards: int) -> List[List[int]]:
    """Greedy LPT: files in descending size order each land on the
    least-loaded shard, so a skewed file-size distribution still
    balances (the classic 4/3-approximation).  Deterministic: ties
    break on file index.  Returns per-shard sorted file-index lists."""
    order = sorted(range(len(sizes)), key=lambda i: (-sizes[i], i))
    loads = [0] * n_shards
    out: List[List[int]] = [[] for _ in range(n_shards)]
    for i in order:
        d = min(range(n_shards), key=lambda s: (loads[s], s))
        out[d].append(i)
        loads[d] += max(1, int(sizes[i]))
    for shard in out:
        shard.sort()
    return out


def scan_file_bytes(scan) -> int:
    """Total on-disk bytes of a spec's input files — the pre-ingest
    over-HBM heuristic (exec/meshexec.py:_attempt_sharded): when even
    the RAW file bytes exceed ``spark.rapids.shuffle.ici.maxStageBytes``
    the fragment keeps the drained ingest, whose gate degrades BEFORE
    any device upload, instead of committing an over-budget stage to
    HBM and pulling it all back for the fallback."""
    total = 0
    for p in scan.paths:
        try:
            total += os.path.getsize(p)
        except OSError:
            pass
    return total


def plan_shards(scan, n_dev: int) -> List[tuple]:
    """Per-shard ``(file_indices, rg_shard)`` assignment.  File-level
    LPT by on-disk size when there are at least as many files as
    shards; parquet inputs with FEWER files than shards fall back to
    row-group sharding — every shard reads every file, taking the
    row groups whose post-prune position is ``shard mod n_dev``, so a
    single large file still feeds the whole mesh."""
    from spark_rapids_tpu.io.parquet import TpuParquetScanExec
    files = list(scan.paths)
    if len(files) < n_dev and isinstance(scan, TpuParquetScanExec):
        idx = list(range(len(files)))
        return [(idx, (d, n_dev)) for d in range(n_dev)]
    sizes = []
    for p in files:
        try:
            sizes.append(os.path.getsize(p))
        except OSError:
            sizes.append(0)
    return [(s, None) for s in assign_files(sizes, n_dev)]


# ---------------------------------------------------------------------------
# Per-shard pipeline construction (clones of the fragment's own subtree)
# ---------------------------------------------------------------------------

class _ShardCatalog:
    """Catalog facade giving one shard pipeline its OWN prefetch
    staging limiter (an equal slice of the shared budget).  N shard
    producers sharing the single ``prefetch_staging`` instance could
    CIRCULAR-WAIT against the fixed-order round-robin consumer: queue
    grants are held until that shard's next pull, so the budget can be
    entirely held by shards the consumer is not currently blocked on.
    Per-shard limiters restore the invariant the limiter's design
    proves deadlock-free — one producer, one consumer, no cross-shard
    admission edge (each limiter clamps an oversized ask to its own
    cap, so a single large batch always fits).  Everything else
    delegates to the real catalog."""

    __slots__ = ("_cat", "prefetch_staging")

    def __init__(self, cat, limiter):
        self._cat = cat
        self.prefetch_staging = limiter

    def __getattr__(self, name):
        return getattr(self._cat, name)


class _ShardRuntime:
    """Runtime facade pinning ``device`` to one mesh chip and the
    catalog to the shard's own prefetch limiter; everything else
    (semaphore, scan cache) delegates to the real runtime, so shard
    pipelines share chip admission and memory accounting with the rest
    of the engine."""

    __slots__ = ("_rt", "device", "catalog")

    def __init__(self, rt, device, catalog):
        self._rt = rt
        self.device = device
        self.catalog = catalog

    def __getattr__(self, name):
        return getattr(self._rt, name)


def _shard_ctx(ctx: ExecContext, device, n_dev: int) -> ExecContext:
    """A per-shard ExecContext clone: same conf, device-pinned runtime,
    per-shard prefetch staging (``_ShardCatalog``).  ``__new__`` bypass
    — the real ctx already applied the process-global switches
    ExecContext.__init__ sets."""
    from spark_rapids_tpu.memory.spill import HostStagingLimiter
    cat = ctx.runtime.catalog
    cap = cat.prefetch_staging.cap
    limiter = HostStagingLimiter(
        max(1, cap // max(1, n_dev)) if cap else 0, name="prefetch")
    sc = object.__new__(ExecContext)
    sc.conf = ctx.conf
    sc.runtime = _ShardRuntime(ctx.runtime, device,
                               _ShardCatalog(cat, limiter))
    return sc


def _clone_scan(scan, file_idx: List[int], rg_shard):
    """Shallow scan clone over a file subset (hive partition values
    subset in lockstep); parquet row-group shards set ``rg_shard``.
    Metrics are shared with the planner's scan node, so the profile
    aggregates all shards' row-group/file counters in one place."""
    s = copy.copy(scan)
    s.paths = [scan.paths[i] for i in file_idx]
    pv = getattr(scan, "part_values", None)
    if pv:
        s.part_values = [pv[i] for i in file_idx]
    if rg_shard is not None:
        s.rg_shard = rg_shard
    return s


def _clone_chain(spec: ShardSpec, source):
    """Rebuild the fragment's unary chain over a per-shard source:
    shallow clones sharing expressions, kernels caches, and metrics —
    only the child edges are fresh."""
    node = source
    for op in reversed(spec.chain):
        c = copy.copy(op)
        c.children = [node]
        node = c
    return node


def _close_all(iters) -> None:
    for it in iters:
        close = getattr(it, "close", None)
        if close is not None:
            try:
                close()
            except Exception as e:
                log.warning("shard pipeline close failed: %s", e)


def _drain_round_robin(iters) -> List[List[ColumnarBatch]]:
    """Drive every shard pipeline from THIS thread, round-robin: each
    ``next`` dispatches one shard's decode-pull + upload + chain
    kernels asynchronously on ITS chip, so all chips' H2D streams and
    stage kernels are in flight concurrently while the host loop moves
    on — per-chip overlap without driving XLA from background threads
    (the pipelined_d2h lesson: thread-free asynchrony, not threads).
    The only package threads involved are each shard's own bounded
    ``srt-`` prefetch producer (io/prefetch.py, lifecycle-registered,
    leak-audited)."""
    out: List[List[ColumnarBatch]] = [[] for _ in iters]
    alive = list(range(len(iters)))
    try:
        while alive:
            for d in list(alive):
                try:
                    out[d].append(next(iters[d]))
                except StopIteration:
                    alive.remove(d)
    except BaseException:
        _close_all(iters)
        raise
    return out


# ---------------------------------------------------------------------------
# Device-resident stacking: per-shard planes -> global mesh-sharded arrays
# ---------------------------------------------------------------------------

_STACK_CACHE = KernelCache("shardscan.stack", 128)


def _compile_stack(sigs: tuple, cap: int, widths: tuple):
    """One per-shard kernel: concatenate the shard's batches at the
    COMMON capacity (chars padded to the mesh-wide width so every
    shard's planes stack), returning the planes plus the live count —
    dispatched on the shard's own chip (all inputs are committed
    there), so the n_dev stack kernels run concurrently."""
    key = (sigs, cap, widths)
    fn = _STACK_CACHE.get(key)
    if fn is not None:
        return fn
    ncols = len(sigs[0])

    def run(all_flat, count_scalars):
        counts = jnp.stack([jnp.asarray(c, jnp.int32)
                            for c in count_scalars])
        csum = jnp.cumsum(counts)
        offsets = jnp.concatenate([jnp.zeros(1, counts.dtype),
                                   csum[:-1]])
        outs = []
        for ci in range(ncols):
            head = all_flat[0][ci]
            is_str = widths[ci] > 0
            data = jnp.zeros(cap, head[0].dtype)
            valid = jnp.zeros(cap, jnp.bool_)
            chars = jnp.zeros((cap, widths[ci]), jnp.uint8) \
                if is_str else None
            for bi, flat in enumerate(all_flat):
                d, v, ch = flat[ci]
                cap_b = d.shape[0]
                rowpos = jnp.arange(cap_b)
                write = rowpos < counts[bi]
                tgt = jnp.where(write, offsets[bi] + rowpos, cap)
                data = data.at[tgt].set(d, mode="drop")
                valid = valid.at[tgt].set(v & write, mode="drop")
                if is_str:
                    blk = ch
                    if blk.shape[1] < widths[ci]:
                        blk = jnp.pad(
                            blk,
                            ((0, 0), (0, widths[ci] - blk.shape[1])))
                    chars = chars.at[tgt].set(blk, mode="drop")
            outs.append((data, valid, chars))
        return tuple(outs), csum[-1].astype(jnp.int32)

    fn = engine_jit(run, family="exchange", name="stack")
    _STACK_CACHE[key] = fn
    return fn


class ShardedInput:
    """A mesh fragment's device-resident input: global mesh-sharded
    planes + per-device live counts, ready for the dist pipelines'
    ``run_stacked``.  ``views`` are per-shard single-chip batch views
    over the SAME buffers (zero-copy) — the sort bounds sampler reads
    them without touching the global arrays."""

    __slots__ = ("planes", "counts", "cap", "n_dev", "schema", "views")

    def __init__(self, planes, counts, cap: int, n_dev: int, schema,
                 views):
        self.planes = planes
        self.counts = counts
        self.cap = cap
        self.n_dev = n_dev
        self.schema = schema
        self.views = views

    def est_bytes(self) -> int:
        """Static device-layout byte estimate for the over-HBM gate
        (``spark.rapids.shuffle.ici.maxStageBytes``) — padded capacity,
        so conservative vs the drained-input estimate; no sync."""
        total = 0
        for (d, v, c) in self.planes:
            total += d.nbytes + v.nbytes
            if c is not None:
                total += c.nbytes
        return total

    def drain(self) -> ColumnarBatch:
        """Materialize ONE host-path batch from the stacked planes (the
        drained input the ``_guarded_collective`` fallback matrix
        re-parents the single-chip exec onto) — per-chip parallel
        pulls, one counts pull."""
        from spark_rapids_tpu.columnar.transfer import device_pull
        from spark_rapids_tpu.parallel.mesh import gather_stacked
        counts_h = np.asarray(device_pull(self.counts))
        return gather_stacked(self.planes, counts_h,
                              [f.dtype for f in self.schema],
                              self.schema, parallel_pull=True)


def _zero_planes(template, cap: int, widths: tuple, device):
    """Empty-shard planes matching a populated shard's layout
    (dtypes/shapes come from the template), committed to the empty
    shard's chip through the sanctioned transfer upload seam."""
    from spark_rapids_tpu.columnar.transfer import place_on_device
    outs = []
    for ci, (data, valid, chars) in enumerate(template):
        z = place_on_device(np.zeros((cap,) + tuple(data.shape[1:]),
                                     np.dtype(data.dtype)), device)
        zv = place_on_device(np.zeros(cap, np.bool_), device)
        zc = None
        if chars is not None:
            zc = place_on_device(
                np.zeros((cap, widths[ci]), np.uint8), device)
        outs.append((z, zv, zc))
    return tuple(outs)


def _stack(shard_batches: List[List[ColumnarBatch]], schema, mesh,
           devices):
    """Concatenate each shard's batches on its own chip and assemble
    the per-shard planes into global mesh-sharded arrays — the
    zero-copy, zero-host-round-trip handoff into the shard_map
    exchange program."""
    from spark_rapids_tpu.columnar import encoding
    n_dev = len(devices)
    dtypes = [f.dtype for f in schema]
    ncols = len(dtypes)
    flats: List[list] = []
    sigs: List[tuple] = []
    bounds: List[int] = []
    for bs in shard_batches:
        fl, sg, bd = [], [], 0
        for b in bs:
            planes = [encoding.col_planes(c, False) for c in b.columns]
            fl.append(tuple(p[0] for p in planes))
            sg.append(tuple(p[1] for p in planes))
            bd += b.rows_bound
        flats.append(fl)
        sigs.append(tuple(sg))
        bounds.append(bd)
    cap = bucket_capacity(max(1, max(bounds)))
    widths = tuple(
        max((sg[ci][2] for shard_sg in sigs for sg in shard_sg),
            default=0)
        for ci in range(ncols))

    per_dev_planes: List[Optional[tuple]] = [None] * n_dev
    counts_dev: List = [None] * n_dev
    views: List[Optional[ColumnarBatch]] = [None] * n_dev
    template = None
    for d in range(n_dev):
        if not flats[d]:
            continue
        fn = _compile_stack(sigs[d], cap, widths)
        outs, count = fn(tuple(flats[d]),
                         tuple(b.rows_traced for b in shard_batches[d]))
        per_dev_planes[d] = outs
        counts_dev[d] = count
        if template is None:
            template = outs
        rows = LazyRows(count, bounds[d])
        views[d] = ColumnarBatch(
            [DeviceColumn(dtypes[ci], outs[ci][0], outs[ci][1], rows,
                          chars=outs[ci][2]) for ci in range(ncols)],
            rows, schema)
    if template is None:
        return EMPTY
    from spark_rapids_tpu.columnar.transfer import place_on_device
    for d in range(n_dev):
        if per_dev_planes[d] is None:
            outs = _zero_planes(template, cap, widths, devices[d])
            per_dev_planes[d] = outs
            counts_dev[d] = place_on_device(np.int32(0), devices[d])
            views[d] = ColumnarBatch(
                [DeviceColumn(dtypes[ci], outs[ci][0], outs[ci][1], 0,
                              chars=outs[ci][2])
                 for ci in range(ncols)],
                0, schema)

    sharding = NamedSharding(mesh, P(DATA_AXIS))

    def assemble(per_dev):
        shaped = [a[None] for a in per_dev]
        gshape = (n_dev,) + tuple(shaped[0].shape[1:])
        return jax.make_array_from_single_device_arrays(
            gshape, sharding, shaped)

    planes = []
    for ci in range(ncols):
        gdata = assemble([per_dev_planes[d][ci][0]
                          for d in range(n_dev)])
        gvalid = assemble([per_dev_planes[d][ci][1]
                           for d in range(n_dev)])
        gchars = None
        if widths[ci] > 0:
            gchars = assemble([per_dev_planes[d][ci][2]
                               for d in range(n_dev)])
        planes.append((gdata, gvalid, gchars))
    counts = jax.make_array_from_single_device_arrays(
        (n_dev,), sharding,
        [counts_dev[d][None] for d in range(n_dev)])
    return ShardedInput(planes, counts, cap, n_dev, schema, views)


# ---------------------------------------------------------------------------
# Ingest driver
# ---------------------------------------------------------------------------

def ingest_child(spec: ShardSpec, ctx: ExecContext, mesh,
                 metrics=None):
    """Run one fragment input's sharded ingest over ``mesh``'s devices
    (the SAME device set the fragment's collective will run over — the
    caller builds both from one healthy-pool snapshot).  Returns a
    ``ShardedInput``, or ``EMPTY`` when the scan produced no batches.
    Raises on failure — exec/meshexec.py owns the degrade-to-host-path
    policy (fault site ``shuffle.ici.ingest`` fires here, once per
    fragment ingest)."""
    from spark_rapids_tpu import faults
    from spark_rapids_tpu.utils.metrics import (
        METRIC_ICI_SHARDED_SCANS, METRIC_ICI_SHARDED_SHARDS,
    )
    t0 = time.perf_counter_ns()
    devices = list(mesh.devices.flat)
    n_dev = len(devices)
    faults.maybe_fail(FAULT_SITE_INGEST,
                      "injected sharded scan ingest failure")
    shards = plan_shards(spec.scan, n_dev)
    iters = []
    used = 0
    for d in range(n_dev):
        file_idx, rg = shards[d]
        if not file_idx:
            iters.append(iter(()))
            continue
        used += 1
        root = _clone_chain(spec, _clone_scan(spec.scan, file_idx, rg))
        iters.append(root.execute_columnar(
            _shard_ctx(ctx, devices[d], n_dev)))
    shard_batches = _drain_round_robin(iters)
    result = _stack(shard_batches, spec.schema, mesh, devices)
    n_batches = sum(len(bs) for bs in shard_batches)
    nbytes = sum(b.size_bytes() for bs in shard_batches for b in bs)
    _bump("fragments", 1)
    _bump("shards", used)
    _bump("files", len(spec.scan.paths))
    _bump("batches", n_batches)
    _bump("bytes", nbytes)
    _bump("ingest_ns", time.perf_counter_ns() - t0)
    if metrics is not None:
        metrics[METRIC_ICI_SHARDED_SCANS].add(1)
        metrics[METRIC_ICI_SHARDED_SHARDS].add(used)
    return result
