"""Mesh-parallel physical operators: the planner's lowering of
aggregate / sort / join onto a multi-chip ``jax.sharding.Mesh``.

Reference: the reference distributes queries by inserting
GpuShuffleExchangeExec boundaries and letting executors move batches
over UCX (GpuShuffleExchangeExec.scala:60-244,
RapidsShuffleInternalManager.scala:178-336).  The TPU-native design has
no executor processes to shuffle between: one SPMD ``shard_map`` program
per operator partitions rows by key hash and moves them with
``jax.lax.all_to_all`` over ICI, so partition + exchange + merge compile
into a single XLA program (parallel/distagg.py, distjoin.py,
distsort.py).  These exec nodes are the planner-visible wrappers that
feed those pipelines from the ordinary single-host batch stream.

Two lowerings share the rewrite (``_lower_fragments``):

* ``spark.rapids.sql.mesh.devices`` = N > 1 (``mesh_lower``): the
  explicit, STATIC mesh configuration — unguarded, no fallback, the
  shape the dryruns exercise;
* ``spark.rapids.shuffle.mode=ici`` (``ici_lower``,
  docs/ici_shuffle.md): the production path.  Every lowered fragment
  keeps its original single-chip exec as ``ici_fallback`` and runs the
  collective through ``_guarded_collective`` — the
  ``shuffle.ici.collective`` fault site, the per-stage over-HBM
  qualification (``spark.rapids.shuffle.ici.maxStageBytes``), and a
  runtime RESOURCE_EXHAUSTED all degrade to the host path over the
  already-drained input (query correct, ``iciFallbacks`` counted).
  Per-destination byte counts from the already-synced device counts
  feed ``shufflePartitionBytes`` and the AQE stats stream, so the
  adaptive rules keep seeing ICI exchanges (docs/adaptive.md).
"""

from __future__ import annotations

import logging
import threading
from typing import Iterator, List, Optional, Tuple

import numpy as np

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.dtypes import Field, Schema
from spark_rapids_tpu.exec.base import ExecContext, TpuExec
from spark_rapids_tpu.exec.coalesce import SINGLE_BATCH, concat_batches
from spark_rapids_tpu.exprs.base import Expression
from spark_rapids_tpu.faults import InjectedFault
from spark_rapids_tpu.utils.metrics import (
    METRIC_ICI_BYTES, METRIC_ICI_EXCHANGES, METRIC_ICI_FALLBACKS,
    METRIC_TOTAL_TIME,
)

log = logging.getLogger("spark_rapids_tpu.ici")


def _mesh_for(n_devices: int):
    from spark_rapids_tpu.parallel.mesh import data_mesh
    return data_mesh(n_devices)


def _mesh_key_and_builder(node, ctx: "ExecContext"):
    """(cache key, lazy mesh builder) for one guarded fragment's
    ``_dist`` pipeline.  With ``spark.rapids.health.enabled`` the mesh
    re-forms over the first ``width`` HEALTHY devices at the
    power-of-two floor of the surviving pool (the degraded-mesh
    re-lowering, docs/fault_tolerance.md) and the key is the CHIP SET
    itself — a membership change at the same width (a second chip
    quarantined, a probation restore) must rebuild, or a cached
    pipeline would keep running collectives on a dead chip.  The mesh
    is only constructed when the caller actually rebuilds; the static
    mesh.devices lowering and the health-off path keep the planned
    width byte-for-byte.  A pool that shrank below 2 chips between the
    gate's width check and this read (a concurrent query's quarantine)
    degrades TYPED — never a bare empty-mesh construction error."""
    from spark_rapids_tpu import health
    n = node.n_devices
    if node.ici_fallback is not None and health.conf_enabled(ctx.conf):
        # the gate stashed ITS snapshot on the node right before
        # invoking the mesh thunk: the chip set it consulted (and will
        # credit or blame) IS the set the collective runs over — a
        # concurrent quarantine between gate and build cannot make the
        # scores describe a mesh that never ran.  A direct _run_mesh
        # call outside the gate (tests) falls back to a fresh read.
        chips = getattr(node, "_health_chips", None)
        if chips is None:
            chips = health.mesh_snapshot(n)
        if len(chips) < 2:
            raise IciDegradedWidthError(
                "healthy chip pool degraded below a 2-wide mesh "
                f"(surviving chips {chips}) while the fragment was in "
                "flight; fragment keeps the host path")
        return chips, lambda: health.mesh_for_chips(chips)
    return n, lambda: _mesh_for(n)


# ---------------------------------------------------------------------------
# Process-wide ICI statistics (the `ici` object in bench.py's summary
# line, mirroring the prefetch/d2h/fusion/aqe global stats)
# ---------------------------------------------------------------------------

_ICI_LOCK = threading.Lock()
_ICI_STATS = {
    # exchange fragments executed as on-device collectives
    "exchanges": 0,
    # estimated bytes those collectives moved over the interconnect
    "bytes": 0,
    # fragments that degraded to the host path (total across reasons)
    "fallbacks": 0,
    # reason-tagged degrade counters (docs/ici_shuffle.md fallback
    # matrix; the health layer attributes chip blame from these):
    # the per-stage over-HBM qualification...
    "fallbacks_over_budget": 0,
    # ...a mesh degraded below 2 healthy chips (chip failure domain)...
    "fallbacks_width": 0,
    # ...an injected shuffle.ici.collective fault...
    "fallbacks_injected": 0,
    # ...a runtime RESOURCE_EXHAUSTED / out-of-memory escape...
    "fallbacks_oom": 0,
    # ...and a watchdog trip on a wedged mesh program
    "fallbacks_hang": 0,
    # ...and a failed sharded scan ingest (docs/sharded_scan.md) —
    # pre-declared like every reason code so the snapshot schema never
    # depends on whether a degrade happened
    "fallbacks_ingest": 0,
    # device_pulls observed ACROSS the exchange programs themselves —
    # the MULTICHIP acceptance number (0 for hash exchanges: the
    # collective never crosses the host link; range exchanges pay their
    # one bounds-sample pull here)
    "exchange_pulls": 0,
}


def _bump_ici(key: str, v: int) -> None:
    with _ICI_LOCK:
        _ICI_STATS[key] += v


def _bump_fallback(code: str) -> None:
    with _ICI_LOCK:
        _ICI_STATS["fallbacks"] += 1
        _ICI_STATS["fallbacks_" + code] = \
            _ICI_STATS.get("fallbacks_" + code, 0) + 1


def ici_stats() -> dict:
    """Process-wide ICI snapshot, merged with the gather-egress
    counters (parallel/mesh.py: per-chip parallel result pulls and the
    link wall time the fan-out reclaimed, and ``ingest_us`` /
    ``collective_us`` / ``gather_us``: the microseconds mesh fragments
    spent in each of their three phases, the ``ici.*`` spans, and
    ``program_lookups`` / ``program_hits``: mesh programs asked for and
    found compiled, ``mesh.mesh_program``) and the
    sharded-scan ingest counters (parallel/shardscan.py) so bench.py
    and the acceptance tests read ONE dict."""
    from spark_rapids_tpu.parallel import mesh as _mesh
    from spark_rapids_tpu.parallel import shardscan as _shardscan
    with _ICI_LOCK:
        out = dict(_ICI_STATS)
    out.update(_mesh.gather_stats())
    out["sharded"] = _shardscan.global_stats()
    return out


def reset_ici_stats() -> None:
    from spark_rapids_tpu.parallel import mesh as _mesh
    from spark_rapids_tpu.parallel import shardscan as _shardscan
    with _ICI_LOCK:
        for k in _ICI_STATS:
            _ICI_STATS[k] = 0
    _mesh.reset_gather_stats()
    _shardscan.reset_stats()


class IciUnqualifiedError(RuntimeError):
    """A stage failed ICI qualification at execution time (input over
    ``spark.rapids.shuffle.ici.maxStageBytes``): the fragment keeps the
    host path.  Never escapes ``_guarded_collective``."""

    code = "over_budget"  # reason tag for the fallback counters


class IciDegradedWidthError(IciUnqualifiedError):
    """The healthy chip pool degraded below a 2-wide mesh
    (docs/fault_tolerance.md, "Chip failure domain"): the fragment
    keeps the host path.  Never escapes ``_guarded_collective``."""

    code = "width"


def _plane_row_bytes(cols) -> int:
    """Per-row device-layout byte width of one stacked column set
    ``[(data (n_dev, cap, ...), valid, chars|None), ...]`` — static
    shape arithmetic only, no device sync."""
    w = 0
    for t in cols:
        data = t[0]
        chars = t[2] if len(t) > 2 else None
        per = 1
        for d in data.shape[2:]:
            per *= int(d)
        w += data.dtype.itemsize * per + 1  # +1: validity plane
        if chars is not None:
            w += int(chars.shape[2]) * chars.dtype.itemsize
    return w


def _record_ici_exchange(node: TpuExec, counts, planes, pulls: int,
                         n_collectives: int = 1) -> None:
    """Record one on-device exchange's statistics: per-destination
    bytes = already-synced per-device counts x static per-row plane
    width (host arithmetic only, like PR 5's exchange stats — never an
    extra link round trip).  Feeds the ``ici*`` metrics, the AQE stats
    stream (``shufflePartitionBytes`` + ``record_exchange_stats``), and
    the process-wide ici stats bench.py surfaces."""
    from spark_rapids_tpu.exec.exchange import record_partition_sizes
    roww = _plane_row_bytes(planes)
    sizes = [int(c) * roww for c in np.asarray(counts).tolist()]
    total = sum(sizes)
    node.metrics[METRIC_ICI_EXCHANGES].add(n_collectives)
    node.metrics[METRIC_ICI_BYTES].add(total)
    record_partition_sizes(node.metrics, sizes)
    with _ICI_LOCK:
        _ICI_STATS["exchanges"] += n_collectives
        _ICI_STATS["bytes"] += total
        _ICI_STATS["exchange_pulls"] += int(pulls)


def _exchange_pulls_since(before: int) -> int:
    from spark_rapids_tpu.columnar import transfer
    return transfer.d2h_stats()["pulls"] - before


def _d2h_pulls() -> int:
    from spark_rapids_tpu.columnar import transfer
    return transfer.d2h_stats()["pulls"]


class _DrainedSource(TpuExec):
    """Replays already-drained batches into the host-path fallback plan
    (the input was collected once through the spill catalog; a fallback
    must never re-run the child subtree — a nondeterministic scan or an
    exhausted upstream iterator cannot be replayed)."""

    def __init__(self, batches: List[ColumnarBatch], schema: Schema):
        super().__init__()
        self.children = []
        self._batches = list(batches)
        self._schema = schema

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        return f"IciDrainedSource [{len(self._batches)} batches]"

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        return iter(self._batches)


def _host_fallback(node: TpuExec, ctx: ExecContext,
                   inputs: List[Optional[ColumnarBatch]]):
    """Degrade one lowered fragment to its original single-chip exec,
    re-parented onto the already-drained input batches (the host path
    the ICI mode's fallback matrix names, docs/ici_shuffle.md)."""
    fb = node.ici_fallback
    fb.children = [
        _DrainedSource([] if b is None else [b], c.output_schema)
        for b, c in zip(inputs, node.children)]
    return fb.execute_columnar(ctx)


def _guarded_collective(node: TpuExec, ctx: ExecContext,
                        inputs: List[Optional[ColumnarBatch]],
                        mesh, fallback):
    """The ONE gate every ICI lowering site passes through
    (tests/lint_robustness.py enforces that mesh exec bodies route
    their collectives here — no bare ``all_to_all`` without the
    host-path degrade).  Fires the ``shuffle.ici.collective`` fault
    site, applies the per-stage over-HBM qualification, and runs the
    collective under the hang watchdog (``shuffle.ici.hang`` +
    ``spark.rapids.sql.watchdog.hangTimeoutMs``, lifecycle.supervise);
    an injected fault, a failed qualification, a watchdog trip on a
    wedged mesh program, or a runtime RESOURCE_EXHAUSTED degrades to
    ``fallback`` over the drained input with ``iciFallbacks`` counted
    (reason-tagged in ``ici_stats()``).  With
    ``spark.rapids.health.enabled`` the gate is also the chip failure
    domain's sensor (docs/fault_tolerance.md): the ``chip.fail`` /
    ``chip.slow`` sites are consulted per mesh chip, every outcome
    feeds the per-chip EWMA health score (mesh-wide failures spread
    blame at alpha/width), a pool degraded below 2 healthy chips keeps
    the host path, and a chip-attributed failure raises a typed
    ``ChipFailedError`` — the query dies for the serving path's
    bounded replay instead of degrading fragments to the host path
    forever.  Explicitly mesh-configured plans
    (``spark.rapids.sql.mesh.devices`` > 1; no ``ici_fallback``) are
    the static lowering and never degrade."""
    from spark_rapids_tpu import lifecycle
    if node.ici_fallback is None:
        return mesh()
    from spark_rapids_tpu import faults, health
    health_on = health.conf_enabled(ctx.conf)
    chips = slow = None
    try:
        cap = ctx.conf.ici_max_stage_bytes
        total = sum(_est_input_bytes(b) for b in inputs
                    if b is not None)
        if total > cap:
            raise IciUnqualifiedError(
                f"stage input ~{total} bytes over "
                f"spark.rapids.shuffle.ici.maxStageBytes={cap}")
        if health_on:
            # a sharded ingest already snapshotted the pool (and built
            # the mesh over it) before this gate ran: consult THAT set,
            # never a second read a concurrent quarantine could tear
            # from the mesh the shards uploaded to.  Cleared at each
            # execute entry, so it is never a previous run's snapshot.
            chips = getattr(node, "_health_chips", None)
            if chips is None:
                chips = health.mesh_snapshot(node.n_devices)
            if len(chips) < 2:
                raise IciDegradedWidthError(
                    "healthy chip pool degraded below a 2-wide mesh "
                    f"(surviving chips {list(chips)}); fragment keeps "
                    "the host path")
            # hand THIS snapshot to the mesh builder (_mesh_key_and_
            # builder): the consulted/credited set and the mesh device
            # set are one read, never two
            node._health_chips = chips
            # chip fault sites: a chip.fail fire records the failure
            # (quarantining past the threshold) and raises the typed
            # ChipFailedError PAST this gate — the chip domain fails
            # fast for bounded replay, never host-path-forever
            slow = health.consult_collective(chips)
        faults.maybe_fail("shuffle.ici.collective")
        # _run_mesh returns eagerly-built batches, so failures (and the
        # watchdog bound on a wedged collective sync) surface inside
        # this try, not at a downstream consumer
        result = lifecycle.supervise(mesh, lifecycle.FAULT_SITE_ICI_HANG)
        if health_on and chips:
            health.record_collective_success(chips, exclude=slow)
        return result
    except IciUnqualifiedError as e:
        reason, code = str(e), e.code
    except lifecycle.QueryHangError as e:
        # the mesh program wedged past the watchdog bound: the query
        # must not hang — degrade this fragment to the host path
        reason, code = str(e), "hang"
        if health_on and chips:
            health.record_mesh_failure(chips)
    except InjectedFault as e:
        if e.site != "shuffle.ici.collective":
            raise  # another site's fault keeps its own recovery path
        reason, code = str(e), "injected"
        if health_on and chips:
            health.record_mesh_failure(chips)
    except (RuntimeError, MemoryError) as e:
        # the over-HBM runtime escape hatch: a collective program that
        # exhausted device memory degrades like a failed qualification;
        # anything else is a real bug and must surface
        msg = str(e).lower()
        if "resource_exhausted" not in msg and "out of memory" not in msg:
            raise
        reason, code = f"{type(e).__name__}: {e}", "oom"
        if health_on and chips:
            health.record_mesh_failure(chips)
    log.warning("ICI exchange degraded to host path (%s, %s): %s",
                node.node_name, code, reason)
    node.metrics[METRIC_ICI_FALLBACKS].add(1)
    _bump_fallback(code)
    from spark_rapids_tpu.obs import journal
    if journal.enabled():
        journal.emit(journal.EVENT_ICI_FALLBACK, node=node.node_name,
                     reason=reason, code=code)
    return fallback()


def _collect_handles(child, ctx: ExecContext):
    """Drain a child's stream into spill-catalog handles: the collected
    input participates in the device budget (demotable to host/disk)
    instead of pinning every batch in HBM while the rest arrives."""
    from spark_rapids_tpu.memory.spill import collect_spillable
    return collect_spillable(child.execute_columnar(ctx), ctx)


def _concat_from_handles(handles, ctx: ExecContext):
    """Materialize handles (budget-aware, pinned against demotion during
    the copy) and fuse into the ONE batch the SPMD pipelines consume;
    None when the stream was empty."""
    from spark_rapids_tpu.memory.spill import materialize_all
    from spark_rapids_tpu.parallel.mesh import phase
    if not handles:
        return None
    with phase("ingest_us"):
        batches = materialize_all(handles, ctx)
        return batches[0] if len(batches) == 1 \
            else concat_batches(batches)


def _drain_single_batch(child, ctx: ExecContext):
    return _concat_from_handles(_collect_handles(child, ctx), ctx)


# ---------------------------------------------------------------------------
# Sharded scan ingest (docs/sharded_scan.md): the device-resident
# alternative to the drained ingest above, gated by
# spark.rapids.shuffle.ici.shardedScan.enabled
# ---------------------------------------------------------------------------

def _parallel_gather(ctx: ExecContext) -> bool:
    """Per-chip parallel result pulls ride the same conf gate as the
    sharded ingest (off = the single stacked pull, byte-identical)."""
    return ctx.conf.ici_sharded_scan


def _est_input_bytes(b) -> int:
    """Byte estimate for the over-HBM gate: a drained batch estimates
    via AQE's batch model; a device-resident ShardedInput reports its
    static stacked-plane footprint (padded, so conservative)."""
    est = getattr(b, "est_bytes", None)
    if est is not None:
        return int(est())
    from spark_rapids_tpu.exec.aqe import est_batch_bytes
    return est_batch_bytes(b)


def _drained_input(x):
    """Host-path form of one gate input: ShardedInputs materialize ONE
    host-side batch from their stacked planes (per-chip parallel
    pulls); drained batches pass through."""
    if x is None or isinstance(x, ColumnarBatch):
        return x
    return x.drain()


def _note_ingest_degrade(node: TpuExec, reason: str) -> None:
    """Account one fragment's ingest-failure degrade to the host path:
    ``iciFallbacks`` with reason tag ``ingest`` (the fallback matrix
    row the ``shuffle.ici.ingest`` fault site proves)."""
    log.warning("sharded scan ingest degraded to host path (%s): %s",
                node.node_name, reason)
    node.metrics[METRIC_ICI_FALLBACKS].add(1)
    _bump_fallback("ingest")
    from spark_rapids_tpu.obs import journal
    if journal.enabled():
        journal.emit(journal.EVENT_ICI_FALLBACK, node=node.node_name,
                     reason=reason, code="ingest")


def _single_child_collective(node: TpuExec, ctx: ExecContext):
    """The ONE execute body of the single-child mesh execs (aggregate,
    sort): resolve the child input (sharded ingest, drained, empty, or
    ingest-failure degrade) and route the collective through
    ``_guarded_collective`` — shared so the resolution ladder cannot
    silently diverge between the two execs (the join keeps its own
    two-child body).  tests/lint_robustness.py accepts this helper as
    the sanctioned gate routing and checks IT calls the gate."""
    from spark_rapids_tpu.parallel import shardscan
    node._health_chips = None
    inp, degrade = _attempt_sharded(node, ctx, 0)
    if degrade is not None:
        # ingest failure: the fragment keeps the host path over a
        # freshly drained input (reason 'ingest')
        _note_ingest_degrade(node, degrade)
        batch = _drain_single_batch(node.children[0], ctx)
        if batch is None:
            return
        yield from node.metrics.timed_iter(
            METRIC_TOTAL_TIME, _host_fallback(node, ctx, [batch]))
        return
    if inp is shardscan.EMPTY:
        return
    if inp is None:
        from spark_rapids_tpu.exec import ooc
        handles = _collect_handles(node.children[0], ctx)
        if not handles:
            return
        if ooc.qualifies(node, ctx, [handles]):
            # fragment qualification (docs/out_of_core.md): an
            # over-budget collected input runs the grace-partitioned
            # out-of-core path instead of consulting the over-budget
            # gate — the operator stays on device, partition by
            # partition, under the same stage budget
            yield from node.metrics.timed_iter(
                METRIC_TOTAL_TIME, ooc.run_single(node, ctx, handles))
            return
        inp = _concat_from_handles(handles, ctx)
        if inp is None:
            return
    yield from node.metrics.timed_iter(METRIC_TOTAL_TIME, _guarded_collective(
        node, ctx, [inp],
        lambda: node._run_mesh(ctx, inp),
        lambda: _host_fallback(node, ctx, [_drained_input(inp)])))


def _attempt_sharded(node: TpuExec, ctx: ExecContext, idx: int):
    """Try the sharded scan ingest for child ``idx``.  Returns
    ``(input, degrade_reason)``:

    * ``(ShardedInput, None)`` — device-resident input, feed
      ``run_stacked``;
    * ``(EMPTY, None)`` — the sharded scan found no rows (the
      fragment short-circuits exactly like an empty drained input);
    * ``(None, None)`` — not sharded (no spec / conf off / pool
      degraded): keep the drained ingest;
    * ``(None, reason)`` — the ingest FAILED (injected
      ``shuffle.ici.ingest`` fault or RESOURCE_EXHAUSTED): the whole
      fragment must degrade to the host path over a freshly drained
      input (``_note_ingest_degrade``).

    The dist pipeline (and its mesh) is built here, BEFORE the gate,
    from the same healthy-pool snapshot the gate will consult
    (``node._health_chips``) — the chips the shards upload to ARE the
    chips the collective runs over."""
    specs = getattr(node, "sharded_scan", None)
    if not specs or node.ici_fallback is None \
            or not ctx.conf.ici_sharded_scan:
        return None, None
    spec = specs[idx]
    if spec is None:
        return None, None
    from spark_rapids_tpu.parallel import shardscan
    if shardscan.scan_file_bytes(spec.scan) > ctx.conf.ici_max_stage_bytes:
        # even the RAW file bytes exceed the over-HBM budget: keep the
        # drained ingest, whose gate degrades BEFORE any device upload
        # — sharding would commit the whole over-budget stage to HBM
        # only to pull it all back for the fallback
        return None, None
    try:
        dist = node._ensure_dist(ctx)
    except IciUnqualifiedError:
        # pool degraded below a 2-wide mesh between planning and now:
        # the drained path's gate degrades typed with the width reason
        return None, None
    if isinstance(node._dist_n, tuple):
        # health-on: the chip set the pipeline was built over is the
        # set the gate must consult/credit
        node._health_chips = node._dist_n
    from spark_rapids_tpu.parallel.mesh import phase
    try:
        with phase("ingest_us"):
            return shardscan.ingest_child(spec, ctx, dist.mesh,
                                          metrics=node.metrics), None
    except InjectedFault as e:
        if e.site != shardscan.FAULT_SITE_INGEST:
            raise  # another site's fault keeps its own recovery path
        return None, str(e)
    except (RuntimeError, MemoryError) as e:
        msg = str(e).lower()
        if "resource_exhausted" not in msg and "out of memory" not in msg:
            raise
        return None, f"{type(e).__name__}: {e}"


class TpuMeshAggregateExec(TpuExec):
    """Grouped aggregation over the mesh: per-device partial aggregate ->
    all_to_all hash exchange -> per-device merge, one shard_map program
    (parallel/distagg.py; reference pipeline aggregate.scala:259-460 +
    GpuShuffleExchangeExec)."""

    def __init__(self, groupings: List[Expression],
                 aggregates: List[Expression], child, n_devices: int):
        super().__init__()
        self.groupings = list(groupings)
        self.aggregates = list(aggregates)
        self.n_devices = int(n_devices)
        self.children = [child]
        self.ici_fallback = None
        self.sharded_scan = None
        from spark_rapids_tpu.exec.aggregate import unwrap_aggregate
        pairs = [unwrap_aggregate(e) for e in aggregates]
        fields = [Field(g.name, g.dtype, g.nullable)
                  for g in self.groupings]
        fields += [Field(n, f.dtype, f.nullable) for n, f in pairs]
        self._schema = Schema(fields)
        self._dist = None
        self._dist_n = None

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        gs = ", ".join(g.name for g in self.groupings)
        return (f"TpuMeshAggregate [mesh={self.n_devices}, "
                f"keys=[{gs}]]")

    @property
    def output_batching(self):
        return SINGLE_BATCH

    def _ensure_dist(self, ctx: ExecContext):
        from spark_rapids_tpu.parallel.distagg import DistributedAggregate
        key, build_mesh = _mesh_key_and_builder(self, ctx)
        if self._dist is None or self._dist_n != key:
            self._dist = DistributedAggregate(
                self.groupings, self.aggregates, mesh=build_mesh())
            self._dist_n = key
        return self._dist

    def _run_mesh(self, ctx: ExecContext, inp):
        from spark_rapids_tpu.parallel.shardscan import ShardedInput
        dist = self._ensure_dist(ctx)
        pulls0 = _d2h_pulls()
        if isinstance(inp, ShardedInput):
            # device-resident sharded ingest: the stacked global planes
            # feed the shard_map program directly — no shard_table
            n_groups, out_cols = dist.run_stacked(
                inp.planes, inp.counts, inp.cap)
        else:
            n_groups, out_cols = dist.run_sharded(inp)
        exch_pulls = _exchange_pulls_since(pulls0)
        out = dist.gather(n_groups, out_cols,
                          parallel_pull=_parallel_gather(ctx))
        out.schema = self._schema
        # record only after the gather succeeded: a RESOURCE_EXHAUSTED
        # mid-gather degrades this fragment to the host path, and a
        # degraded fragment must not ALSO count as a completed exchange
        # (the stats consumers read exchanges+fallbacks as disjoint)
        _record_ici_exchange(self, n_groups, out_cols, exch_pulls)
        return [out]

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        return self._count_output(_single_child_collective(self, ctx))


class TpuMeshSortExec(TpuExec):
    """Global sort over the mesh: sampled range bounds -> all_to_all
    range exchange -> per-device local sort (parallel/distsort.py;
    reference GpuRangePartitioning + GpuSortExec)."""

    def __init__(self, orders: List[Tuple[Expression, bool, bool]],
                 child, n_devices: int):
        super().__init__()
        self.orders = list(orders)
        self.n_devices = int(n_devices)
        self.children = [child]
        self.ici_fallback = None
        self.sharded_scan = None
        self._dist = None
        self._dist_n = None

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def describe(self) -> str:
        parts = [f"{e.name} {'ASC' if a else 'DESC'}"
                 for e, a, _ in self.orders]
        return (f"TpuMeshSort [mesh={self.n_devices}, "
                + ", ".join(parts) + "]")

    @property
    def output_batching(self):
        return SINGLE_BATCH

    def _ensure_dist(self, ctx: ExecContext):
        from spark_rapids_tpu.parallel.distsort import DistributedSort
        key, build_mesh = _mesh_key_and_builder(self, ctx)
        if self._dist is None or self._dist_n != key:
            self._dist = DistributedSort(
                self.orders, self.output_schema, mesh=build_mesh(),
                pad_width=ctx.conf.max_string_width)
            self._dist_n = key
        return self._dist

    def _run_mesh(self, ctx: ExecContext, inp):
        from spark_rapids_tpu.parallel.shardscan import ShardedInput
        dist = self._ensure_dist(ctx)
        pulls0 = _d2h_pulls()
        if isinstance(inp, ShardedInput):
            # per-shard device-resident bound sampling: each shard's
            # keys compute on its own chip, one pooled sample pull
            bounds, pad = dist.sample_bounds_sharded(inp.views)
            if bounds is None:  # degenerate: empty / unboundable
                out = inp.drain()
                out.schema = self.output_schema
                return [out]
            n_local, out_cols = dist.run_stacked(
                inp.planes, inp.counts, inp.cap, bounds, pad)
        else:
            n_local, out_cols = dist.run_sharded(inp)
            if n_local is None:  # degenerate input: empty / unboundable
                inp.schema = self.output_schema
                return [inp]
        # the range exchange's one bounds-sample pull is attributed to
        # the exchange (exchange_pulls); hash exchanges record 0 here.
        # Recorded only after the gather succeeds (see _run_mesh in
        # TpuMeshAggregateExec): degraded fragments must not also
        # count as completed exchanges.
        exch_pulls = _exchange_pulls_since(pulls0)
        out = dist.gather(n_local, out_cols,
                          parallel_pull=_parallel_gather(ctx))
        out.schema = self.output_schema
        _record_ici_exchange(self, n_local, out_cols, exch_pulls)
        return [out]

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        return self._count_output(_single_child_collective(self, ctx))


class TpuMeshHashJoinExec(TpuExec):
    """Repartition (shuffled) hash join over the mesh: BOTH sides
    hash-partition by join key and move over ICI with all_to_all, then
    each device joins its key range locally (parallel/distjoin.py
    DistributedHashJoin; reference GpuShuffledHashJoinExec.scala:58-137,
    the fact-fact q16/q24 shape)."""

    def __init__(self, left, right, left_keys: List[Expression],
                 right_keys: List[Expression], join_type: str,
                 n_devices: int):
        super().__init__()
        self.children = [left, right]
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.join_type = join_type
        self.n_devices = int(n_devices)
        self.ici_fallback = None
        self.sharded_scan = None
        self._dist = None
        self._dist_n = None

    @property
    def output_schema(self) -> Schema:
        ls = self.children[0].output_schema
        if self.join_type in ("semi", "anti"):
            return ls
        rs = self.children[1].output_schema
        lf = list(ls.fields)
        rf = list(rs.fields)
        if self.join_type in ("right", "full"):
            lf = [Field(f.name, f.dtype, True) for f in lf]
        if self.join_type in ("left", "full"):
            rf = [Field(f.name, f.dtype, True) for f in rf]
        return Schema(lf + rf)

    def describe(self) -> str:
        ks = ", ".join(f"{l.name}={r.name}"
                       for l, r in zip(self.left_keys, self.right_keys))
        return (f"TpuMeshHashJoin [mesh={self.n_devices}, "
                f"{self.join_type}, {ks}]")

    def _ensure_dist(self, ctx: ExecContext):
        from spark_rapids_tpu.parallel.distjoin import DistributedHashJoin
        key, build_mesh = _mesh_key_and_builder(self, ctx)
        if self._dist is None or self._dist_n != key:
            self._dist = DistributedHashJoin(
                self.left_keys, self.right_keys,
                self.children[0].output_schema,
                self.children[1].output_schema,
                join_type=self.join_type, mesh=build_mesh())
            self._dist_n = key
        return self._dist

    def _run_mesh(self, ctx: ExecContext, lb, rb):
        from spark_rapids_tpu.parallel.shardscan import ShardedInput
        from spark_rapids_tpu.exec.joins import _empty_batch
        dist = self._ensure_dist(ctx)
        if lb is None:
            lb = _empty_batch(self.children[0].output_schema)
        if rb is None:
            rb = _empty_batch(self.children[1].output_schema)
        pulls0 = _d2h_pulls()
        if isinstance(lb, ShardedInput) or isinstance(rb, ShardedInput):
            # either side (or both) arrived device-resident: feed the
            # stacked planes straight into the count+join programs; a
            # drained side host-splits inside run_mixed
            def side(x):
                return (x.planes, x.counts, x.cap) \
                    if isinstance(x, ShardedInput) else x
            ns, blocks = dist.run_mixed(side(lb), side(rb))
        else:
            ns, blocks = dist.run_sharded(lb, rb)
        exch_pulls = _exchange_pulls_since(pulls0)
        out = dist.gather(ns, blocks,
                          parallel_pull=_parallel_gather(ctx))
        out.schema = self.output_schema
        # both sides crossed the interconnect: 2 collectives; the first
        # block's planes carry the joined row layout for byte estimates.
        # Recorded only after the gather succeeds: a degraded fragment
        # must not also count as a completed exchange.
        _record_ici_exchange(self, ns.sum(axis=1), blocks[0],
                             exch_pulls, n_collectives=2)
        return [out]

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        def gen():
            from spark_rapids_tpu.parallel import shardscan
            self._health_chips = None
            sharded = [None, None]
            degrade = None
            for i in (0, 1):
                sharded[i], degrade = _attempt_sharded(self, ctx, i)
                if degrade is not None:
                    break
            if degrade is not None:
                # ingest failure on either side degrades the WHOLE
                # fragment to the host path: an already-ingested side
                # drains from its stacked planes, the other side drains
                # its original subtree
                _note_ingest_degrade(self, degrade)
                inputs = []
                for i in (0, 1):
                    x = sharded[i]
                    if x is shardscan.EMPTY:
                        inputs.append(None)
                    elif x is not None:
                        inputs.append(_drained_input(x))
                    else:
                        inputs.append(
                            _drain_single_batch(self.children[i], ctx))
                yield from self.metrics.timed_iter(
                    METRIC_TOTAL_TIME, _host_fallback(self, ctx, inputs))
                return
            if sharded[0] is not None or sharded[1] is not None:
                # at least one sharded side: the other side (if any)
                # drains through the simple single-batch path
                def resolve(i):
                    x = sharded[i]
                    if x is shardscan.EMPTY:
                        return None
                    if x is not None:
                        return x
                    return _drain_single_batch(self.children[i], ctx)
                lb, rb = resolve(0), resolve(1)
            else:
                # no sharded side: the original memory-aware drain —
                # one side at a time through spill handles: while the
                # right side streams in, the left side's batches may
                # demote to host under memory pressure instead of
                # pinning both whole inputs + concat copies in HBM
                # (reference: build side through RequireSingleBatch +
                # the spillable store, GpuShuffledHashJoinExec.scala:83)
                from spark_rapids_tpu.exec import ooc
                from spark_rapids_tpu.memory.spill import close_all
                lh = _collect_handles(self.children[0], ctx)
                try:
                    rh = _collect_handles(self.children[1], ctx)
                except BaseException:
                    close_all(lh)
                    raise
                if ooc.qualifies(self, ctx, [lh, rh]):
                    # over-budget collected inputs take the grace-
                    # partitioned join (docs/out_of_core.md) instead of
                    # the giant concat + over-budget gate
                    yield from self.metrics.timed_iter(
                        METRIC_TOTAL_TIME, ooc.run_join(self, ctx, lh, rh))
                    return
                try:
                    # materialize_all closes lh itself (even on error);
                    # only rh needs cleanup if the left-side promotion
                    # fails
                    lb = _concat_from_handles(lh, ctx)
                except BaseException:
                    close_all(rh)
                    raise
                rb = _concat_from_handles(rh, ctx)
            yield from self.metrics.timed_iter(
                METRIC_TOTAL_TIME, _guarded_collective(
                    self, ctx, [lb, rb],
                    lambda: self._run_mesh(ctx, lb, rb),
                    lambda: _host_fallback(
                        self, ctx, [_drained_input(lb),
                                    _drained_input(rb)])))
        return self._count_output(gen())


# ---------------------------------------------------------------------------
# Planner lowering passes
# ---------------------------------------------------------------------------

_MESH_JOIN_TYPES = ("inner", "left", "right", "full", "semi", "anti")


def _realias(name, func):
    from spark_rapids_tpu.exprs.base import Alias
    return Alias(func, name)


def _over_read_columns(node):
    """``node`` (a grouped ``TpuHashAggregateExec``) rebuilt over a
    projection of the child columns its groupings and aggregates read,
    or ``node`` itself where it reads them all.  A scan hands over every
    column of its table and the single-chip aggregate just leaves the
    others alone, but a mesh fragment drains, splits and uploads its
    WHOLE input: an aggregate straight over lineitem would move sixteen
    columns to keep two, and at SF1 that is over
    ``spark.rapids.shuffle.ici.maxStageBytes`` where the two are a
    tenth of it."""
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.basic import TpuProjectExec
    from spark_rapids_tpu.exprs.base import BoundReference
    child = node.children[0]
    fields = child.output_schema.fields
    exprs = list(node.groupings) + list(node.aggregates)
    read = set()

    def collect(e):
        if isinstance(e, BoundReference):
            read.add(e.ordinal)
        for c in e.children:
            collect(c)

    for e in exprs:
        collect(e)
    if node.pre_steps or not read or len(read) == len(fields):
        return node
    kept = sorted(read)
    moved = {o: i for i, o in enumerate(kept)}

    def rebind(e):
        if isinstance(e, BoundReference):
            return BoundReference(moved[e.ordinal], e.dtype, e.nullable,
                                  e.col_name)
        if not e.children:
            return e
        return e.with_children([rebind(c) for c in e.children])

    project = TpuProjectExec(
        [BoundReference(o, fields[o].dtype, fields[o].nullable,
                        fields[o].name) for o in kept], child)
    n_keys = len(node.groupings)
    rebound = [rebind(e) for e in exprs]
    return TpuHashAggregateExec(rebound[:n_keys], rebound[n_keys:],
                                project)


def _lower_fragments(plan, n: int, guarded: bool):
    """Rewrite single-chip aggregate/sort/join execs to the
    mesh-parallel forms.  ``guarded`` = the ICI production mode: the
    original exec rides along as ``ici_fallback`` (the host path an
    injected fault / failed qualification degrades to) and
    AQE-inserted hash exchanges under a lowered join are unwrapped —
    the mesh join's shard_map program IS the exchange, so the planted
    host exchange would re-bucket rows the collective is about to move
    again.  The insertion point mirrors the reference's exchange
    placement (GpuShuffleExchangeExec insertion in GpuOverrides; here
    the exchange is inside the SPMD operator)."""
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.joins import TpuHashJoinExec
    from spark_rapids_tpu.exec.sort import TpuSortExec

    def rewrite(node):
        node.children = [rewrite(c) for c in node.children]
        if isinstance(node, TpuHashAggregateExec) and node.groupings:
            # grouping-set flavors route through Expand and still match
            node = _over_read_columns(node)
            new = TpuMeshAggregateExec(
                node.groupings,
                [_realias(n_, f_) for n_, f_ in node.agg_pairs],
                node.children[0], n)
            if guarded:
                new.ici_fallback = node
            return new
        if isinstance(node, TpuSortExec) and node.global_sort:
            new = TpuMeshSortExec(node.orders, node.children[0], n)
            if guarded:
                new.ici_fallback = node
            return new
        if isinstance(node, TpuHashJoinExec) and \
                node.join_type in _MESH_JOIN_TYPES and \
                node.condition is None:
            left, right = node.children
            if guarded:
                from spark_rapids_tpu.plan.adaptive import (
                    unwrap_aqe_exchange,
                )
                left, _lex = unwrap_aqe_exchange(left)
                right, _rex = unwrap_aqe_exchange(right)
            new = TpuMeshHashJoinExec(
                left, right, node.left_keys, node.right_keys,
                node.join_type, n)
            if guarded:
                new.ici_fallback = node
            return new
        return node

    return rewrite(plan)


def mesh_lower(plan, conf) -> "object":
    """Planner pass: rewrite single-chip aggregate/sort/join execs to the
    mesh-parallel forms when ``spark.rapids.sql.mesh.devices`` > 1 and
    the device pool is large enough — the explicit, static mesh
    configuration (no fallback; the dryrun shape)."""
    import jax

    n = conf.mesh_devices
    if n <= 1:
        return plan
    if len(jax.devices()) < n:
        return plan  # not enough chips; stay single-device
    return _lower_fragments(plan, n, guarded=False)


def ici_lower(plan, conf) -> "object":
    """Planner pass for ``spark.rapids.shuffle.mode=ici``
    (docs/ici_shuffle.md): the PRODUCTION mesh lowering.  Promotes the
    ``parallel/`` pipelines into real lowerings of agg-under-exchange,
    sort-under-exchange, and shuffled-join fragments across every
    visible chip (``spark.rapids.shuffle.ici.devices`` caps the
    width), with the original single-chip exec carried as the
    per-fragment host-path fallback.  Session-level qualification
    (mode conf, workers, device count) already ran in
    ``shuffle/manager.py:select_shuffle_mode``; per-stage
    qualification runs inside ``_guarded_collective`` at execution."""
    from spark_rapids_tpu.shuffle.manager import ici_mesh_width
    n = ici_mesh_width(conf)
    if n <= 1:
        return plan
    return _lower_fragments(plan, n, guarded=True)
