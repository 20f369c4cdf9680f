"""Engine-integrated host shuffle: planner-produced plans route an
exchange through ``TpuShuffleManager`` across OS worker processes.

Reference: RapidsShuffleInternalManager.scala:90-138 (map output written
through the shuffle into the tiered store), RapidsCachingReader.scala:
60-170 (reduce fetches from peers), GpuShuffleExchangeExec.scala:60-244
(the exchange operator driving partition writes).

TPU-shaped split of roles: the MAP side — file scan/decode, expression
work below the exchange, hash partitioning — is CPU work the reference
spreads across executors, so it runs in N spawned worker processes,
each executing a pickled fragment of the planner's physical plan over
its stripe of the scan's files on the jax-CPU backend and pushing
partition blocks (Arrow IPC + zstd) through its own TpuShuffleManager.
The REDUCE side runs in the parent where the one real chip lives:
partition blocks are fetched from every peer through the transport,
staged under the spill catalog's host-staging budget (the
ShuffleBufferCatalog role: in-flight shuffle bytes are visible to the
memory accounting), uploaded, and streamed to the downstream operators
as ordinary device batches.
"""

from __future__ import annotations

import logging
import multiprocessing as mp
import pickle
from typing import Iterator, List, Optional

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.dtypes import Schema
from spark_rapids_tpu.exec.base import ExecContext, TpuExec
from spark_rapids_tpu.exprs.base import Expression
from spark_rapids_tpu.utils.metrics import METRIC_TOTAL_TIME
from spark_rapids_tpu.utils.queues import bounded_q_get as _bounded_q_get

_SHUFFLE_ID = 11  # one shuffle per exchange execution; ids scoped per run

log = logging.getLogger("spark_rapids_tpu.shuffle")


class _MapStageFailed(RuntimeError):
    """A map worker process died (hard kill / OOM) or never started —
    the recoverable class of map-stage failure: the exchange falls back
    to re-running the map work in-process (the Spark map-stage-recompute
    contract) when spark.rapids.shuffle.recompute.enabled is on."""


def _scan_nodes(plan) -> List:
    """All file-scan execs (nodes with a ``paths`` file list) in a
    fragment."""
    out = []

    def walk(n):
        if hasattr(n, "paths") and isinstance(getattr(n, "paths"), list):
            out.append(n)
        for c in n.children:
            walk(c)
    walk(plan)
    return out


_ROW_PRESERVING = None  # lazily-resolved set of fragment-safe exec types


def _splittable_types():
    global _ROW_PRESERVING
    if _ROW_PRESERVING is None:
        from spark_rapids_tpu.exec.basic import (
            TpuFilterExec, TpuProjectExec,
        )
        from spark_rapids_tpu.exec.coalesce import TpuCoalesceBatchesExec
        from spark_rapids_tpu.exec.stage import TpuStageExec
        _ROW_PRESERVING = (TpuFilterExec, TpuProjectExec,
                           TpuCoalesceBatchesExec, TpuStageExec)
    return _ROW_PRESERVING


def splittable(plan) -> bool:
    """A fragment is map-splittable when it is a LINEAR pipeline of
    per-row operators (scan / filter / project / coalesce) over ONE
    multi-file scan — striping files through a join or aggregate would
    change its semantics (each worker would see only part of the other
    side / other groups), so such fragments are never split (the
    exchange-consistency discipline, RapidsMeta.scala:413-478)."""
    node = plan
    safe = _splittable_types()
    while True:
        if hasattr(node, "paths") and isinstance(node.paths, list):
            return len(node.paths) > 1 and not node.children
        if not isinstance(node, safe) or len(node.children) != 1:
            return False
        node = node.children[0]


def _restrict_to_split(plan, idx: int, n: int):
    """Deep-copy a fragment with every scan restricted to its idx-th
    file stripe (files assigned round-robin, the reference's split
    assignment)."""
    import copy
    plan = copy.deepcopy(plan)

    for s in _scan_nodes(plan):
        stripe = s.paths[idx::n]
        s.paths = stripe
        # partition-value maps stay aligned because hive discovery keys
        # per file; re-discover over the stripe (roots fall back to the
        # stripe itself for scan types that don't retain them)
        if getattr(s, "part_schema", None):
            from spark_rapids_tpu.io import hivepart
            s.part_schema, s.part_values = hivepart.narrow(
                *hivepart.discover(getattr(s, "roots", stripe), stripe),
                s.output_schema.names)
    return plan


def _worker_main(idx: int, n_workers: int, plan_blob: bytes,
                 keys_blob: bytes, num_parts: int, conf_dict: dict,
                 port_q, ports_q, done_q) -> None:
    # pin the worker to the CPU backend BEFORE the engine imports —
    # worker processes must never grab the parent's chip
    import jax
    jax.config.update("jax_platforms", "cpu")
    from spark_rapids_tpu import faults
    from spark_rapids_tpu.columnar.batch import device_batch_to_host
    from spark_rapids_tpu.conf import TpuConf

    faults.set_worker_index(idx)
    from spark_rapids_tpu.exec.base import ExecContext
    from spark_rapids_tpu.exec.exchange import (
        partition_batch, partition_batch_to_host_dispatch,
    )
    from spark_rapids_tpu.runtime import TpuRuntime
    from spark_rapids_tpu.shuffle.manager import (
        TRANSPORT_ERRORS, TpuShuffleManager,
    )

    conf = TpuConf(dict(conf_dict or {}))
    # worker fragments journal into their own events-<pid>.jsonl when
    # the shipped conf carries the obs keys (docs/observability.md)
    from spark_rapids_tpu.obs import journal
    journal.configure_from_conf(conf)
    # persistent compilation service (docs/compile_cache.md): the
    # shipped conf carries the compile.* keys and the spawn environment
    # carries JAX_COMPILATION_CACHE_DIR, so this worker's first batch
    # deserializes the driver's kernels instead of recompiling them.
    # No warm pool: a map worker lives for one stage and has no
    # startup latency to hide
    from spark_rapids_tpu import compile as _compile
    _compile.configure_from_conf(conf, platform="cpu",
                                 start_warm=False)
    mgr = TpuShuffleManager.from_conf(conf, port=0)
    port_q.put((idx, mgr.server.port))
    # bounded receive (lint_robustness: no blocking queue get without a
    # timeout): a driver that died before broadcasting the port list
    # must not park this worker process forever
    ports = _bounded_q_get(ports_q, 120.0,
                           "peer port list from the driver")
    mgr.register_peers(ports)
    from spark_rapids_tpu import lifecycle
    try:
        plan = pickle.loads(plan_blob)
        keys = pickle.loads(keys_blob)
        frag = _restrict_to_split(plan, idx, n_workers)
        wrote = [0] * num_parts
        # per-partition byte counts for the map-output index: the
        # runtime statistics the driver's AQE reduce grouping and the
        # shufflePartitionBytes metric are built from — free, the
        # payload size is in hand at every write
        wrote_bytes = [0] * num_parts
        egress_on = conf.io_egress_enabled

        def dispatch_parts(item):
            """Map egress dispatch for one batch (docs/d2h_egress.md):
            partition kernel + whole-batch gather + pack, all
            asynchronous XLA dispatches, with the device->host copies
            started — ONE pull covers every partition where the old
            loop paid one gather + one pull per non-empty partition.
            The conf-off path keeps the per-partition pulls
            byte-for-byte (finish is then the identity)."""
            bno, batch = item
            if faults.should_fire("worker.kill"):
                import os
                import signal
                os.kill(os.getpid(), signal.SIGKILL)
            mode = "hash" if keys else "roundrobin"
            if egress_on:
                return bno, partition_batch_to_host_dispatch(
                    batch, num_parts, keys if keys else None, mode)
            pieces = partition_batch(
                batch, num_parts, keys if keys else None, mode)
            return bno, [None if p is None else device_batch_to_host(p)
                         for p in pieces]

        def finish_parts(staged):
            bno, pend = staged
            if egress_on:
                from spark_rapids_tpu.columnar.transfer import (
                    pack_partitions_finish,
                )
                return bno, pack_partitions_finish(pend)
            return bno, pend

        # pipelined egress: batch k+1's pack + D2H copy are in flight
        # while this loop serializes/compresses/sends batch k's
        # partition blocks through the shuffle manager.  The fragment
        # is a query execution in THIS process — its own lifecycle
        # scope, so the scan-prefetch threads and staging permits it
        # spawns tear down deterministically on any exit
        from spark_rapids_tpu.columnar.transfer import pipelined_d2h
        with lifecycle.query_scope(conf):
            ctx = ExecContext(conf, TpuRuntime.get_or_create(conf))
            batches = frag.execute_columnar(ctx)

            def numbered():
                # enumerate() has no close(): pipelined_d2h's teardown
                # close must reach the underlying batch generator, or a
                # mid-stream write failure would leave the scan pipeline
                # (and its prefetch threads) to GC
                try:
                    yield from enumerate(batches)
                finally:
                    close = getattr(batches, "close", None)
                    if close is not None:
                        close()

            for bno, slices in pipelined_d2h(
                    numbered(), dispatch_parts, finish_parts, ctx,
                    nbytes=lambda t: t[1].wire_bytes()):
                # map ids stripe by worker AND batch ordinal: the block
                # store keys blocks by (shuffle, part, map_id), so a
                # second batch under the same map id would replace the
                # first
                map_id = idx + n_workers * bno
                for p, rb in enumerate(slices):
                    if rb is None:
                        continue
                    if rb.num_rows:
                        mgr.write_partition(_SHUFFLE_ID, map_id=map_id,
                                            part=p, rb=rb)
                        wrote[p] += rb.num_rows
                        wrote_bytes[p] += rb.nbytes
        done_q.put((idx, sum(wrote), wrote_bytes, None))
        # hold the server open until the parent finished reducing —
        # bounded by the stage timeout so an orphaned worker (driver
        # killed between done and release) exits on its own
        try:
            from spark_rapids_tpu.conf import SHUFFLE_STAGE_TIMEOUT
            _bounded_q_get(ports_q, conf.get(SHUFFLE_STAGE_TIMEOUT),
                           "reduce-complete release from the driver")
        except TimeoutError as te:
            log.warning("map worker %d: %s; shutting down the block "
                        "server anyway", idx, te)
    except Exception as e:  # surface the failure to the parent
        # transport-class failures (peer died under our writes) are the
        # recoverable kind: tag them so the driver reroutes to the
        # map-recompute path.  Deliberately NOT every OSError (see
        # TRANSPORT_ERRORS): a scan hitting FileNotFoundError would
        # recompute the same plan into the same error
        kind = "transport" if isinstance(e, TRANSPORT_ERRORS) else "error"
        done_q.put((idx, -1, None, f"{kind}:{type(e).__name__}: {e}"))
    finally:
        mgr.stop()


# one arrow RecordBatch caps a utf8 column's offsets at 2^31 bytes;
# groups near that bound skip concatenation rather than risk an offset
# overflow in combine_chunks (the off path never concatenates at all)
_CONCAT_BYTE_CAP = (1 << 31) - (1 << 20)


def _concat_record_batches(rbs: List) -> List:
    """Concatenate same-schema record batches (zero-copy column chunks
    combined once) into as FEW batches as arrow can represent — one in
    practice; oversized groups pass through unconcatenated."""
    if len(rbs) == 1:
        return list(rbs)
    if sum(rb.nbytes for rb in rbs) >= _CONCAT_BYTE_CAP:
        return list(rbs)
    import pyarrow as pa
    # to_batches(), not [0]: if a column cannot combine into one chunk
    # every batch must still reach the consumer
    return pa.Table.from_batches(rbs).combine_chunks().to_batches()


def _reduce_upload_groups(fetched, parts, conf,
                          all_part_bytes: Optional[List[int]]):
    """Group one fetch window's reduce blocks into device-upload
    batches from the map-output statistics (docs/adaptive.md), via the
    SAME greedy policy as the in-process stage spec
    (``plan/adaptive.py:greedy_partition_groups``), here at map-block
    granularity: adjacent undersized partitions share one upload, a
    skewed partition's blocks split into ~target-byte sub-groups — the
    sub-partition fetch-range realization.  The skew median prefers
    the WHOLE exchange's reported partition sizes over the
    window-local view.  Returns ``(groups_of_record_batches,
    coalesced_partitions, skew_splits)``."""
    from spark_rapids_tpu.plan.adaptive import greedy_partition_groups
    blocks = {p: [rb for rb in fetched.get(p, []) if rb.num_rows]
              for p in parts}
    part_list = [(p, sum(rb.nbytes for rb in blocks[p]),
                  [rb.nbytes for rb in blocks[p]])
                 for p in parts if blocks[p]]
    groups, ncoal, nsplit = greedy_partition_groups(
        part_list, conf, allow_skew=True,
        stat_sizes=all_part_bytes)
    rb_groups = [[rb for p, lo, hi in g for rb in blocks[p][lo:hi]]
                 for g in groups]
    return rb_groups, ncoal, nsplit


class TpuHostShuffleExchangeExec(TpuExec):
    """Partition the child's rows across OS worker processes through the
    shuffle transport, then stream the fetched partitions back as device
    batches (reference GpuShuffleExchangeExec.scala:60-244 +
    RapidsShuffleInternalManager write/read).  Inserted by the planner
    when ``spark.rapids.shuffle.workers.count`` > 1 and the fragment is
    map-splittable."""

    def __init__(self, keys: List[Expression], child, workers: int,
                 num_partitions: Optional[int] = None):
        super().__init__()
        self.keys = list(keys)
        self.children = [child]
        self.workers = max(2, int(workers))
        # explicit count (the planner resolves
        # spark.rapids.shuffle.defaultNumPartitions) or the derived
        # workers*2 default
        self.num_partitions = int(num_partitions or self.workers * 2)
        # per-partition byte sizes from the last map stage's worker
        # reports (the map-output index statistics)
        self.last_partition_bytes: Optional[List[int]] = None

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def describe(self) -> str:
        k = ", ".join(e.name for e in self.keys)
        return (f"TpuHostShuffleExchange [workers={self.workers}, "
                f"parts={self.num_partitions}"
                + (f", keys={k}" if k else "") + "]")

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        return self._count_output(self._run(ctx))

    def _run(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.columnar.batch import host_batch_to_device
        from spark_rapids_tpu.shuffle.manager import TpuShuffleManager

        child = self.children[0]
        n = self.workers
        plan_blob = pickle.dumps(child)
        keys_blob = pickle.dumps(self.keys)
        conf_dict = dict(ctx.conf._settings)
        # workers are map-side only: never recurse into another host
        # shuffle, never grab a chip
        conf_dict["spark.rapids.shuffle.workers.count"] = 0

        from spark_rapids_tpu.conf import (
            SHUFFLE_RECOMPUTE_ENABLED, SHUFFLE_STAGE_TIMEOUT,
        )
        from spark_rapids_tpu.shuffle.manager import (
            TRANSPORT_ERRORS, FetchFailedError,
        )

        recompute_enabled = ctx.conf.get(SHUFFLE_RECOMPUTE_ENABLED)
        mgr = TpuShuffleManager.from_conf(ctx.conf, port=0)
        mp_ctx = mp.get_context("spawn")
        port_q = mp_ctx.Queue()
        ports_qs = [mp_ctx.Queue() for _ in range(n)]
        done_q = mp_ctx.Queue()
        procs = []

        def _reclaim_workers():
            # lifecycle-registered closer: a cancelled/timed-out query
            # (or session stop) reclaims the spawned map workers and the
            # driver-side manager even if this generator was abandoned
            # mid-stream and its finally never ran
            for q in ports_qs:
                try:
                    q.put(None)
                except (OSError, ValueError):
                    pass  # queue already torn down with the process
            for p in procs:
                if p.is_alive():
                    p.terminate()
                p.join(timeout=5)
            mgr.stop()

        from spark_rapids_tpu import lifecycle
        reg = lifecycle.register_resource(
            _reclaim_workers, kind="workers", name="host-shuffle-map")
        if reg.rejected:
            # query teardown raced exchange startup: _reclaim_workers
            # already ran on arrival (manager stopped, nothing spawned
            # yet) — surface the typed abort instead of driving a map
            # stage against a stopped manager
            from spark_rapids_tpu.errors import QueryCancelledError
            raise QueryCancelledError(
                "host shuffle exchange construction raced query teardown")
        try:
            map_failed: Optional[_MapStageFailed] = None
            try:
                with self.metrics.timed(METRIC_TOTAL_TIME):
                    for i in range(n):
                        p = mp_ctx.Process(
                            target=_worker_main,
                            args=(i, n, plan_blob, keys_blob,
                                  self.num_partitions, conf_dict, port_q,
                                  ports_qs[i], done_q))
                        p.start()
                        lifecycle.track_process(p)
                        procs.append(p)
                    import queue as _queue
                    import time as _time
                    map_timeout = ctx.conf.get(SHUFFLE_STAGE_TIMEOUT)
                    deadline = _time.monotonic() + map_timeout
                    start_deadline = _time.monotonic() + 120
                    ports = {}
                    while len(ports) < n:
                        lifecycle.check_cancel()
                        try:
                            i, port = port_q.get(timeout=0.5)
                            ports[i] = port
                            continue
                        except _queue.Empty:
                            pass
                        dead = [p.pid for p in procs
                                if not p.is_alive() and p.exitcode]
                        if dead:
                            raise _MapStageFailed(
                                "host shuffle map worker process(es) "
                                f"died during startup (pids {dead})")
                        if _time.monotonic() > start_deadline:
                            raise RuntimeError(
                                "host shuffle worker startup timed out "
                                f"(120s) — {n - len(ports)} of {n} "
                                "workers never reported a transport "
                                "port")
                    # the parent is peer 0 so reduce fetches of
                    # self-owned partitions stay local; workers follow
                    port_list = [mgr.server.port] + \
                        [ports[i] for i in range(n)]
                    try:
                        mgr.register_peers(port_list)
                    except TRANSPORT_ERRORS as e:
                        # a worker can die in the window between
                        # reporting its port and our connect — the same
                        # recoverable death as one second earlier or
                        # later, so it must reach the recompute path,
                        # not abort the exchange
                        raise _MapStageFailed(
                            "cannot connect to host shuffle worker(s) "
                            f"({type(e).__name__}: {e})") from e
                    for q in ports_qs:
                        q.put(port_list)
                    rows_written = 0
                    part_bytes = [0] * self.num_partitions
                    done = 0
                    while done < n:
                        lifecycle.check_cancel()
                        try:
                            i, wrote, wbytes, err = done_q.get(timeout=1)
                        except _queue.Empty:
                            # fail FAST on hard-killed workers (OOM
                            # kill, segfault) instead of burning the
                            # full timeout
                            dead = [p.pid for p in procs
                                    if not p.is_alive() and p.exitcode]
                            if dead:
                                raise _MapStageFailed(
                                    "host shuffle map worker "
                                    f"process(es) died (pids {dead}) "
                                    "before reporting results")
                            if _time.monotonic() > deadline:
                                raise RuntimeError(
                                    "host shuffle map stage timed out "
                                    f"after {map_timeout}s waiting for "
                                    f"{n - done} of {n} workers (spark."
                                    "rapids.shuffle.stage.timeout)"
                                ) from None
                            continue
                        if err is not None:
                            if err.startswith("transport:"):
                                # collateral damage of a dead peer: a
                                # survivor's writes failed.  Recoverable
                                # — do NOT let this race ahead of the
                                # dead-process check and abort the query
                                raise _MapStageFailed(
                                    f"host shuffle map worker {i} hit a "
                                    "transport failure "
                                    f"({err[len('transport:'):]})")
                            raise RuntimeError(
                                f"host shuffle map worker {i} failed: "
                                f"{err}")
                        rows_written += wrote
                        if wbytes is not None:
                            for p, b in enumerate(wbytes):
                                part_bytes[p] += b
                        done += 1
                    self.metrics["shuffleRowsWritten"].add(rows_written)
                    # map-output index statistics: per-partition bytes
                    # aggregated across workers (the data source for
                    # AQE reduce grouping and bench's aqe object)
                    from spark_rapids_tpu.exec.aqe import (
                        record_exchange_stats,
                    )
                    from spark_rapids_tpu.utils.metrics import (
                        METRIC_SHUFFLE_PARTITION_BYTES,
                    )
                    self.last_partition_bytes = part_bytes
                    self.metrics[METRIC_SHUFFLE_PARTITION_BYTES].add(
                        sum(part_bytes))
                    record_exchange_stats(part_bytes)
            except _MapStageFailed as e:
                if not recompute_enabled:
                    raise RuntimeError(str(e)) from None
                map_failed = e

            if map_failed is not None:
                # The map stage is incomplete AND possibly partially
                # visible (a dying worker may have pushed some blocks),
                # so no per-partition repair is sound.  Re-run the map
                # work in-process from its source input — the exchange's
                # output contract is the multiset of child rows, which a
                # local execution reproduces exactly.
                log.warning(
                    "%s; recomputing the map stage in-process "
                    "(spark.rapids.shuffle.recompute.enabled)",
                    map_failed)
                self.metrics["shuffleMapRecomputes"].add(1)
                for b in child.execute_columnar(ctx):
                    yield b
                return

            # REDUCE: fetch partitions through the manager's THREADED
            # fetch pool (maxBytesInFlight window), in bounded chunks so
            # host memory stays bounded; fetched bytes reserve the
            # catalog's host-staging budget ONLY across the device
            # upload (the yield sits outside the limiter, matching the
            # scan-upload pattern — holding it across the yield could
            # deadlock a same-thread spill).  Reference
            # ShuffleBufferCatalog.scala:50 (shuffle blocks visible to
            # the memory accounting) + RapidsCachingReader fetch.
            chunk = max(1, mgr.threads)
            if ctx.conf.adaptive_enabled and \
                    self.last_partition_bytes is None:
                # no inline worker reports (shouldn't happen on the
                # normal path): fall back to the map-output index —
                # one metadata stat per partition
                self.last_partition_bytes = mgr.partition_sizes(
                    _SHUFFLE_ID, list(range(self.num_partitions)))
            lost_parts: List[int] = []
            yielded_any = False
            for start in range(0, self.num_partitions, chunk):
                parts = list(range(start, min(start + chunk,
                                              self.num_partitions)))
                try:
                    fetched = mgr.read_partitions(_SHUFFLE_ID, parts)
                except FetchFailedError as e:
                    # a peer died/blacklisted after its maps completed:
                    # reroute this chunk to the map-recompute path (the
                    # chunk's partitions are recomputed wholesale — a
                    # partially-fetched chunk is discarded, never mixed)
                    if not recompute_enabled:
                        raise
                    log.warning(
                        "reduce fetch failed (%s); partitions %s will "
                        "be recomputed from the map input", e, parts)
                    lost_parts.extend(parts)
                    continue
                if ctx.conf.adaptive_enabled:
                    # stats-driven upload grouping (docs/adaptive.md):
                    # adjacent undersized partitions share one device
                    # upload, a skewed partition's blocks upload in
                    # sub-groups — batch boundaries move, the row
                    # sequence is the off-path's exactly
                    groups, ncoal, nsplit = _reduce_upload_groups(
                        fetched, parts, ctx.conf,
                        self.last_partition_bytes)
                    if ncoal or nsplit:
                        from spark_rapids_tpu.exec.aqe import (
                            _bump_global,
                        )
                        from spark_rapids_tpu.utils.metrics import (
                            METRIC_COALESCED_PARTITIONS,
                            METRIC_SKEW_SPLITS,
                        )
                        self.metrics[METRIC_COALESCED_PARTITIONS].add(
                            ncoal)
                        self.metrics[METRIC_SKEW_SPLITS].add(nsplit)
                        _bump_global("coalesced_partitions", ncoal)
                        _bump_global("skew_splits", nsplit)
                    rb_groups = [rb for g in groups
                                 for rb in _concat_record_batches(g)]
                else:
                    rb_groups = [rb for part in parts
                                 for rb in fetched.get(part, [])
                                 if rb.num_rows]
                for rb in rb_groups:
                    with ctx.runtime.catalog.staging.limit(
                            rb.nbytes):
                        b = host_batch_to_device(
                            rb, self.output_schema,
                            max_string_width=(
                                ctx.conf.max_string_width),
                            device=ctx.runtime.device)
                    yielded_any = True
                    yield b
            if lost_parts:
                self.metrics["shufflePartitionsRecomputed"].add(
                    len(lost_parts))
                for b in self._recompute_partitions(
                        ctx, lost_parts, yielded_any):
                    yield b
        finally:
            reg.release()  # teardown runs inline below; deregister the closer
            if lifecycle.cancel_requested():
                # cancelled/timed-out query: the typed error is already
                # propagating through this finally — reclaim promptly
                # (terminate, short join) instead of granting each
                # possibly-wedged worker a 30s graceful join that would
                # hold the error past the deadline
                _reclaim_workers()
            else:
                for q in ports_qs:
                    try:
                        q.put(None)  # release workers holding servers open
                    except (OSError, ValueError) as e:
                        log.debug("worker release message failed: %s", e)
                for p in procs:
                    p.join(timeout=30)
                    if p.is_alive():
                        p.terminate()
                mgr.stop()

    def _recompute_partitions(self, ctx: ExecContext,
                              lost_parts: List[int],
                              yielded_any: bool
                              ) -> Iterator[ColumnarBatch]:
        """Re-run the owning map work for ``lost_parts`` from the source
        input: execute the child in-process and keep only the lost
        partitions' rows.  Sound for hash partitioning (per-row
        deterministic: a row's partition never depends on which process
        mapped it).  Round-robin assignment is placement-dependent, so
        it can only be recovered by a FULL re-run — possible only while
        nothing was yielded downstream yet."""
        from spark_rapids_tpu.exec.exchange import partition_batch
        from spark_rapids_tpu.utils.retry import (
            split_batch_half, with_retry,
        )
        child = self.children[0]
        if not self.keys:
            if yielded_any:
                raise RuntimeError(
                    "cannot recompute round-robin-partitioned shuffle "
                    "output after partial results were consumed; "
                    "rerun the query")
            log.warning("recomputing the whole round-robin exchange "
                        "in-process")
            for b in child.execute_columnar(ctx):
                yield b
            return
        lost = set(lost_parts)
        for batch in child.execute_columnar(ctx):
            for pieces in with_retry(
                    lambda b: partition_batch(
                        b, self.num_partitions, self.keys, "hash"),
                    batch, ctx, split=split_batch_half):
                for p in lost:
                    piece = pieces[p]
                    if piece is not None and piece.num_rows:
                        yield piece
