"""Device->host transfer packing — the D2H "wire codec".

Reference analog: the reference compresses/stages GPU tables before they
cross the PCIe/IB link (TableCompressionCodec.scala, the shuffle bounce
buffers RapidsShuffleTransport.scala:376-497).  Every pull is a blocking
device sync plus a fixed per-pull latency, and D2H bandwidth is the
narrowest link a result crosses (docs/performance.md carries the
constants measured on the directly attached v5e), so result batches are
packed ON DEVICE before any byte crosses:

  * every result batch of a query concatenates into ONE pull — each
    separate ``device_get`` pays the full link round trip;
  * rows trim to a quarter-power-of-two bucket of the true total instead
    of the compute capacity (a filter keeps its input's capacity, so a
    45%-selective filter would otherwise pull 2.2x the live bytes);
  * validity masks and BOOLEAN data bitpack 8 rows/byte;
  * integer / date / timestamp columns delta-narrow losslessly against
    their device-computed minimum (int64 -> uint8/16/32 when the
    observed range allows — group keys, dates, and timestamps in a
    window almost always do);
  * string char matrices trim to the observed max-length bucket.

Host-side unpack restores exact values and dtypes: the codec is
lossless.  Small results (below ``statsThresholdBytes``) skip the stats
round trip and pull counts together with the data in a single round
trip; large results spend one extra tiny pull on (count, min, max,
maxlen) stats to shrink the big pull.
"""

from __future__ import annotations

import contextlib
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

import jax
import jax.numpy as jnp

from spark_rapids_tpu.compile.service import engine_jit
from spark_rapids_tpu import faults
from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch, _column_to_arrow_host,
)
from spark_rapids_tpu.columnar.column import rows_traced
from spark_rapids_tpu.columnar.dtypes import (
    BOOLEAN, DataType, Schema, STRING,
)
from spark_rapids_tpu.utils import tracing
from spark_rapids_tpu.utils.metrics import (
    METRIC_D2H_BYTES, METRIC_D2H_OVERLAP_MS, METRIC_D2H_PULLS,
)


# ---------------------------------------------------------------------------
# The device->host pull primitive (docs/d2h_egress.md)
# ---------------------------------------------------------------------------

FAULT_SITE_D2H = "transfer.d2h"

# process-global egress counters, surfaced by bench.py's summary line so
# the link trajectory (pulls issued x fixed latency, bytes moved,
# overlapped host time) is visible across BENCH rounds
_D2H_LOCK = threading.Lock()
_D2H_GLOBAL = {"pulls": 0, "bytes": 0, "overlap_ms": 0,
               # raw-vs-wire mirror of the ingest encoding counters
               # (docs/compressed.md): what the pack pull stages vs
               # what it would stage with encoded columns dense
               "raw_bytes": 0, "wire_bytes": 0}


def _bump_d2h(key: str, v: int) -> None:
    if v:
        with _D2H_LOCK:
            _D2H_GLOBAL[key] += int(v)


def d2h_stats() -> dict:
    """Snapshot of process-wide egress counters (bench.py)."""
    with _D2H_LOCK:
        return dict(_D2H_GLOBAL)


def reset_d2h_stats() -> None:
    with _D2H_LOCK:
        for k in _D2H_GLOBAL:
            _D2H_GLOBAL[k] = 0


def device_pull(tree, metrics=None):
    """The ONE device->host pull primitive: every egress ``device_get``
    in exec/, shuffle/, and io/ routes through here (enforced by
    tests/lint_robustness.py), so admission, the ``d2hPulls``/
    ``d2hBytes`` metrics, the ``transfer.d2h`` fault site, and the hang
    watchdog (``io.pipeline.hang`` + ``spark.rapids.sql.watchdog.
    hangTimeoutMs``, lifecycle.supervise) cannot be bypassed.  ``tree``
    is any pytree of device arrays; returns the matching host tree.
    One call = one link round trip — the unit the single-pull egress
    paths minimize."""
    import time
    from spark_rapids_tpu import lifecycle
    from spark_rapids_tpu.obs import registry as obs
    faults.maybe_fail(FAULT_SITE_D2H,
                      "injected device->host pull failure")
    # the blocking link wait is the one spot in the egress path
    # cooperative cancellation cannot reach: a wedged pull is bounded
    # by the watchdog and surfaces as a typed QueryHangError
    t0 = time.perf_counter_ns()
    with tracing.trace_range(tracing.SPAN_D2H_PULL):
        host = lifecycle.supervise(lambda: jax.device_get(tree),
                                   lifecycle.FAULT_SITE_PIPELINE_HANG)
    pull_us = (time.perf_counter_ns() - t0) // 1000
    tracing.phase_add("pull_wait_us", pull_us)
    nbytes = sum(getattr(x, "nbytes", 8)
                 for x in jax.tree_util.tree_leaves(host))
    _bump_d2h("pulls", 1)
    _bump_d2h("bytes", nbytes)
    # per-pull latency/size distribution (docs/observability.md): the
    # fixed link latency is THE egress cost model, so its p50/p99 are
    # recorded beside the additive counters above
    obs.record(obs.HIST_D2H_PULL_US, pull_us)
    obs.record(obs.HIST_D2H_PULL_BYTES, nbytes)
    if metrics is not None:
        metrics[METRIC_D2H_PULLS].add(1)
        metrics[METRIC_D2H_BYTES].add(nbytes)
    return host


@contextlib.contextmanager
def _blocked(what: str):
    """A synchronous wait on the device that is not an egress pull: it
    carries a span (``d2h.sync:<what>``) and lands in the ``phases``
    counters (``blocking_reads``, ``pull_wait_us``), so an idle gap under
    it is named for what it is.  Not a counted link pull: ``d2hPulls`` /
    ``d2hBytes`` keep meaning what egress moved."""
    import time
    t0 = time.perf_counter_ns()
    try:
        with tracing.trace_range(f"{tracing.SPAN_D2H_SYNC}:{what}"):
            yield
    finally:
        tracing.phase_add("pull_wait_us",
                          (time.perf_counter_ns() - t0) // 1000)
        tracing.phase_add("blocking_reads", 1)


def blocking_read(tree, what: str):
    """The sibling of ``device_pull`` for a small synchronous read that
    is not egress (a lazy row count, a debug ``to_numpy``)."""
    with _blocked(what):
        return jax.device_get(tree)


def blocking_wait(arrays, what: str) -> None:
    """Wait, copying nothing, until ``arrays`` are computed (``with_retry``
    does, so a launch failure raises inside its scope)."""
    with _blocked(what):
        jax.block_until_ready(arrays)


def place_on_device(host_array, device):
    """Committed single-device upload — the sharded scan ingest's
    per-chip placement primitive (parallel/shardscan.py: empty-shard
    zero planes and count scalars land on THEIR shard's chip).  Kept
    here so the ICI exchange code carries no raw ``jax.device_put``
    (tests/lint_robustness.py confines host-staged uploads to this
    module)."""
    return jax.device_put(host_array, device)


def parallel_device_pull(trees, metrics=None):
    """One ``device_pull`` per entry of ``trees``, issued CONCURRENTLY
    on short-lived daemon threads — the egress mirror of the sharded
    scan ingest's per-chip upload streams (docs/sharded_scan.md): on a
    remote-attached mesh each pull pays the same ~fixed link latency,
    so N per-device pulls issued together overlap it N ways instead of
    paying it serially.  Every pull routes through ``device_pull``
    (counted, ``transfer.d2h`` fault-covered, watchdog-supervised in
    its own worker).  Returns ``(results, overlap_ms)`` where
    ``overlap_ms`` is the per-pull wall time the concurrency reclaimed
    (sum of individual pull times minus the fan-out's wall time).  A
    worker's failure (injected or real) re-raises in the caller with
    its original type; the calling thread polls its query's cancel
    token while waiting, so a cancelled query surfaces typed instead
    of parking on a wedged link."""
    import time
    from spark_rapids_tpu import lifecycle
    n = len(trees)
    if n == 0:
        return [], 0
    if n == 1:
        return [device_pull(trees[0], metrics=metrics)], 0
    results: list = [None] * n
    errors: list = [None] * n
    durs_ns = [0] * n

    def _work(i):
        t0 = time.perf_counter_ns()
        try:
            results[i] = device_pull(trees[i], metrics=metrics)
        except BaseException as e:  # re-raised typed in the caller
            errors[i] = e
        finally:
            durs_ns[i] = time.perf_counter_ns() - t0

    threads = [threading.Thread(target=_work, args=(i,),
                                name=f"srt-d2h-fanout-{i}", daemon=True)
               for i in range(n)]

    def _close():
        for th in threads:
            th.join(timeout=1.0)

    reg = lifecycle.register_resource(_close, kind="d2h-fanout",
                                      name="srt-d2h-fanout")
    if reg.rejected:
        from spark_rapids_tpu.errors import QueryCancelledError
        raise QueryCancelledError(
            "parallel device pull raced query teardown")
    t0 = time.perf_counter_ns()
    try:
        for th in threads:
            th.start()
        for th in threads:
            while th.is_alive():
                th.join(timeout=lifecycle.poll_interval_s())
                if th.is_alive():
                    lifecycle.check_cancel()
    finally:
        reg.release()
    wall_ns = time.perf_counter_ns() - t0
    for e in errors:
        if e is not None:
            raise e
    # NOT bumped into the d2h overlap_ms counter: that key has meant
    # pipelined-D2H egress overlap since PR 4, and the gather fan-out's
    # reclaimed wall is recorded by the caller (mesh.gather_stats) —
    # one quantity, one counter
    overlap_ms = max(0, (sum(durs_ns) - wall_ns) // 1_000_000)
    return results, overlap_ms


# ---------------------------------------------------------------------------
# H2D double buffering (the upload half of the scan overlap pipeline)
# ---------------------------------------------------------------------------

def pipelined_h2d(items, upload, runtime, metrics=None, enabled=True):
    """Double-buffered host->device upload loop shared by the file scans
    and the HostToDevice transition (docs/io_overlap.md).

    ``upload(item)`` dispatches one host item's device upload —
    ``jax.device_put`` is asynchronous, so dispatch returns before the
    bytes land — and the loop keeps a ping-pong pair of device batches:
    the upload of batch k+1 is dispatched BEFORE batch k is yielded, so
    the consumer's compute on k overlaps k+1's copy in flight.  At most
    two upload results are live here (pending + yielded), bounding the
    staging footprint to a buffer pair; the host-side copy count is
    bounded upstream by the prefetch queue depth.

    Admission scoping differs by path.  The serial path
    (``enabled=False``) keeps the pre-pipeline model byte-for-byte: the
    semaphore is held across dispatch AND yield, so downstream work on
    the yielded batch runs under admission (the per-task GpuSemaphore
    reading).  The overlap path holds the semaphore ONLY while
    dispatching: this generator may be driven by a background lookahead
    thread (exec/coalesce.py) that parks on a bounded queue between
    pulls, and a permit held across that park would cap the chip on
    idle threads while the actual compute runs elsewhere unadmitted.
    Stage-scoped permits keep admission honest in a pipelined world;
    together with the staging-before-permit ordering rule (no
    staging-limiter wait ever happens under a held permit — see
    exec/coalesce.py, and prefetch-path uploads are queue-grant covered
    so they take no staging here), the semaphore cannot deadlock even
    at concurrentTasks=1.  Today only upload dispatch (here) and
    coalesce concat take stage permits: downstream operators (join/agg/
    sort kernels on yielded batches) run unadmitted on the overlap
    path, a deliberate narrowing of the old held-across-yield coverage
    — extending stage permits to those operators' kernel dispatches is
    the follow-up that completes the model (docs/io_overlap.md).

    ``h2dOverlapMs`` accumulates the consumer time spent inside the
    yield while an upload was dispatched but not yet synchronized — the
    wall-clock the pipeline reclaimed from the old serial loop.
    """
    import time
    from spark_rapids_tpu.obs import registry as obs

    def _timed_upload(item):
        # upload dispatch latency + size distribution: jax.device_put
        # returns at dispatch, so this is the host-side cost of getting
        # an upload IN FLIGHT (the link itself overlaps downstream)
        t0 = time.perf_counter_ns()
        b = upload(item)
        obs.record(obs.HIST_H2D_UPLOAD_US,
                   (time.perf_counter_ns() - t0) // 1000)
        size = getattr(b, "size_bytes", None)
        if callable(size):
            obs.record(obs.HIST_H2D_UPLOAD_BYTES, size())
        return b

    if not enabled:
        for item in items:
            with runtime.acquire_device():
                yield _timed_upload(item)
        return
    pending = None
    overlap_ns = 0
    try:
        for item in items:
            with runtime.acquire_device():
                b = _timed_upload(item)
            if pending is not None:
                t0 = time.perf_counter_ns()
                with tracing.trace_range(tracing.SPAN_H2D_OVERLAP):
                    yield pending
                overlap_ns += time.perf_counter_ns() - t0
            pending = b
        if pending is not None:
            yield pending
            pending = None
    finally:
        overlap_ms = overlap_ns // 1_000_000
        if metrics is not None:
            metrics["h2dOverlapMs"].add(overlap_ms)
        from spark_rapids_tpu.io import prefetch as _prefetch
        _prefetch._bump_global("overlap_ms", overlap_ms)


# ---------------------------------------------------------------------------
# D2H double buffering (the download half of the egress overlap pipeline)
# ---------------------------------------------------------------------------

def start_host_copies(tree) -> None:
    """Begin the device->host transfer of every array in ``tree``
    WITHOUT blocking (``jax.Array.copy_to_host_async``): a later
    ``device_pull`` of the same arrays finds the bytes already on (or
    en route to) the host and returns without paying the full link
    round trip again.  No-op for leaves that don't support it (numpy
    arrays, CPU-backend fast paths)."""
    for a in jax.tree_util.tree_leaves(tree):
        start = getattr(a, "copy_to_host_async", None)
        if start is not None:
            start()


def pipelined_d2h(items, dispatch, finish, ctx=None, metrics=None,
                  enabled=None, limiter=None, nbytes=None):
    """Double-buffered device->host download loop shared by the result
    collect path and the shuffle map-worker egress
    (docs/d2h_egress.md) — the exact mirror of ``pipelined_h2d``, and
    like it deliberately THREAD-FREE: a background download thread
    would drive the whole upstream device pipeline from a non-main
    thread, which measurably degrades XLA:CPU execution (~2x on the
    window suite) and entangles the semaphore's thread-local admission.
    The split is asynchrony, not threads:

      * ``dispatch(item)`` runs the item's DEVICE side — pack/partition
        kernels are asynchronous XLA dispatches — and starts its
        device->host copies (``start_host_copies``), returning a staged
        handle without blocking;
      * ``finish(staged)`` blocks for the bytes (``device_pull``) and
        builds the host result.

    The loop dispatches item k+1 BEFORE finishing item k, so k+1's
    copy is in flight across k's finish AND across the consumer's work
    on k (serialize/compress/send for the shuffle; parquet/ORC/CSV
    encode for the writers, which consume this through
    ``DeviceToHostExec.execute_host``).  At most two items' host bytes
    are live (pending + yielded) — the same structural buffer-pair
    bound ``pipelined_h2d`` relies on; additionally each blocking
    finish is admitted through the catalog's dedicated egress
    ``HostStagingLimiter`` for the duration of the pull ONLY (scoped,
    never held across a yield — so it cannot deadlock against prefetch
    queue grants or spill staging waits, each of which has its own
    limiter instance).

    ``enabled=False`` is the strictly serial pre-pipeline loop:
    dispatch, finish, yield, repeat — no lookahead, no admission,
    byte-for-byte the old path.  ``d2hOverlapMs`` accumulates consumer
    time spent inside the yield while a dispatched item's copy was in
    flight — the wall-clock the pipeline reclaimed."""
    if enabled is None:
        enabled = ctx is not None and ctx.conf.io_egress_enabled
    if not enabled:
        try:
            for item in items:
                yield finish(dispatch(item))
        finally:
            # same guaranteed upstream close as the pipelined path: a
            # consumer failure must unwind the device pipeline on BOTH
            # conf settings, not leave it to traceback-deferred GC
            close = getattr(items, "close", None)
            if close is not None:
                close()
        return
    import time
    if limiter is None and ctx is not None:
        limiter = ctx.runtime.catalog.egress_staging

    def _finish(staged):
        with tracing.trace_range(tracing.SPAN_D2H_WAIT):
            if limiter is not None and nbytes is not None:
                with limiter.limit(nbytes(staged)):
                    return finish(staged)
            return finish(staged)

    pending = None
    overlap_ns = 0
    try:
        for item in items:
            staged = dispatch(item)
            if pending is not None:
                out = _finish(pending)
                pending = staged
                t0 = time.perf_counter_ns()
                with tracing.trace_range(tracing.SPAN_D2H_OVERLAP):
                    yield out
                overlap_ns += time.perf_counter_ns() - t0
            else:
                pending = staged
        if pending is not None:
            yield _finish(pending)
            pending = None
    finally:
        # close the upstream iterator explicitly: on an abandoned or
        # failed run, generator frames pinned by the traceback would
        # otherwise keep the device pipeline (and its scan-prefetch
        # threads) alive until GC
        close = getattr(items, "close", None)
        if close is not None:
            close()
        ms = overlap_ns // 1_000_000
        if metrics is not None:
            metrics[METRIC_D2H_OVERLAP_MS].add(ms)
        _bump_d2h("overlap_ms", ms)


def transfer_bucket(n: int) -> int:
    """Smallest quarter-power-of-two >= n that is a multiple of 8.

    Compute capacities are full powers of two (one compile per bucket);
    the transfer shape can afford 4x the shape variants for <=25% padding
    waste because pack kernels are tiny to compile."""
    n = max(8, int(n))
    if n <= 32:
        p = 8
        while p < n:
            p <<= 1
        return p
    p = 32
    while p < n:
        p <<= 1
    if p == n:
        return p
    # quarters of the next power of two: 1.25/1.5/1.75/2 * p/2
    half = p >> 1
    q = half >> 2
    for m in (half + q, half + 2 * q, half + 3 * q, p):
        if m >= n:
            return m
    return p


class _ColPlan:
    """Per-column packing decision (host-side, from pulled stats).

    ``enc`` marks a dictionary-encoded column (docs/compressed.md): the
    wire carries its CODES plane — narrowed to the smallest unsigned
    type the dictionary size allows — and ``values`` holds the
    host-resident dictionary the unpack side rebuilds exact strings
    from (the values never touch the link: they arrived at ingest)."""

    __slots__ = ("dtype", "base", "store", "width", "enc", "values")

    def __init__(self, dtype: DataType, base: int = 0,
                 store: Optional[str] = None, width: int = 0,
                 enc: bool = False, values=None):
        self.dtype = dtype
        self.base = base      # delta base for integer narrowing
        self.store = store    # numpy dtype name for the wire, or None=raw
        self.width = width    # chars width for strings
        self.enc = enc
        self.values = values  # host dictionary values (enc only)

    def key(self) -> tuple:
        return (self.dtype.name, self.base != 0, self.store, self.width,
                self.enc)


def _int_like(dtype: DataType) -> bool:
    return dtype.name in ("int8", "int16", "int32", "int64", "date",
                          "timestamp")


def _np_dtype(dtype: DataType):
    return np.dtype(dtype.numpy_dtype)


# ---------------------------------------------------------------------------
# stats kernel (one per batch signature)
# ---------------------------------------------------------------------------

from spark_rapids_tpu.utils.kernel_cache import KernelCache

_STATS_CACHE = KernelCache("transfer.stats", 128)


def _compile_stats(sig: tuple, dtypes_key: tuple, capacity: int,
                   dtypes: Sequence[DataType]):
    key = (sig, dtypes_key, capacity)
    fn = _STATS_CACHE.get(key)
    if fn is not None:
        return fn

    def run(flat, num_rows):
        live = jnp.arange(capacity) < num_rows
        outs = [jnp.asarray(num_rows, jnp.int64)]
        for (d, v, ch), dt in zip(flat, dtypes):
            m = v & live
            if dt == STRING and ch is None:
                # encoded column: codes need no stats (the dictionary
                # size bounds them host-side)
                continue
            if dt == STRING:
                # d holds lengths
                outs.append(jnp.max(jnp.where(m, d, 0)).astype(jnp.int64))
            elif _int_like(dt):
                x = d.astype(jnp.int64)
                lo = jnp.min(jnp.where(m, x, jnp.iinfo(jnp.int64).max))
                hi = jnp.max(jnp.where(m, x, jnp.iinfo(jnp.int64).min))
                outs.append(lo)
                outs.append(hi)
        return tuple(outs)

    fn = engine_jit(run, family="egress", name="stats")
    _STATS_CACHE[key] = fn
    return fn


# ---------------------------------------------------------------------------
# pack kernel (one per (sigs, out_cap, plan))
# ---------------------------------------------------------------------------

_PACK_CACHE = KernelCache("transfer.pack", 128)


def _bitpack(bits, out_cap: int):
    """(out_cap,) bool -> (out_cap//8,) uint8, little-endian bit order
    (numpy.unpackbits(bitorder='little') inverts it)."""
    b = bits.astype(jnp.uint8).reshape(out_cap // 8, 8)
    w = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))[None, :]
    return jnp.sum(b * w, axis=1).astype(jnp.uint8)


# -- the shared plane pack primitives (spill + egress both route here) ------

_BITPACK_CACHE = KernelCache("transfer.bitpack", 64)


def bitpack_plane(arr):
    """Device bool plane (cap,) -> (cap//8,) uint8 — the standalone
    form of the wire codec's validity/boolean bitpack, shared with
    spill demotion (memory/spill.py) so boolean planes cross the link
    (and sit in the host/disk tiers) at 8 rows/byte everywhere, not
    just on the egress path."""
    cap = int(arr.shape[0])

    def build():
        return engine_jit(lambda a: _bitpack(a, cap),
                          family="egress", name="bitpack")
    return _BITPACK_CACHE.get_or_build(("pack", cap), build)(arr)


def bitunpack_host(packed: np.ndarray, cap: int) -> np.ndarray:
    """Host inverse of ``bitpack_plane``: (cap//8,) uint8 -> (cap,)
    bool, exact."""
    return np.unpackbits(np.asarray(packed),
                         bitorder="little")[:cap].astype(np.bool_)


def _compile_pack(sigs: tuple, plan_key: tuple, out_cap: int,
                  dtypes: Sequence[DataType], plans: Sequence[_ColPlan],
                  with_counts: bool):
    key = (sigs, plan_key, out_cap, with_counts)
    fn = _PACK_CACHE.get(key)
    if fn is not None:
        return fn
    ncols = len(dtypes)

    def run(all_flat, count_scalars):
        # concat every batch's columns at the transfer capacity; counts
        # stacked INSIDE the kernel (eager stack/cumsum each cost their
        # own compiled executable per shape)
        counts = jnp.stack([jnp.asarray(c, jnp.int32)
                            for c in count_scalars])
        offsets = jnp.concatenate(
            [jnp.zeros(1, jnp.int32),
             jnp.cumsum(counts.astype(jnp.int32))[:-1]])
        total = jnp.sum(counts.astype(jnp.int32))
        merged = []
        for ci in range(ncols):
            dt = dtypes[ci]
            pl = plans[ci]
            head = all_flat[0][ci]
            data = jnp.zeros(out_cap, head[0].dtype)
            valid = jnp.zeros(out_cap, jnp.bool_)
            chars = None
            if dt == STRING and not pl.enc:
                chars = jnp.zeros((out_cap, pl.width), jnp.uint8)
            for bi, flat in enumerate(all_flat):
                d, v, ch = flat[ci]
                cap_b = d.shape[0]
                rowpos = jnp.arange(cap_b)
                write = rowpos < counts[bi]
                tgt = jnp.where(write, offsets[bi] + rowpos, out_cap)
                data = data.at[tgt].set(d, mode="drop")
                valid = valid.at[tgt].set(v & write, mode="drop")
                if chars is not None:
                    blk = ch[:, :pl.width]
                    if blk.shape[1] < pl.width:
                        blk = jnp.pad(
                            blk, ((0, 0), (0, pl.width - blk.shape[1])))
                    chars = chars.at[tgt].set(blk, mode="drop")
            merged.append((data, valid, chars))

        outs = []
        for ci in range(ncols):
            dt = dtypes[ci]
            pl = plans[ci]
            data, valid, chars = merged[ci]
            vbytes = _bitpack(valid, out_cap)
            if pl.enc:
                # dictionary codes on the wire, narrowed to the dict
                # size; the host dictionary rebuilds exact values
                codes = jnp.where(valid, data, 0)
                if pl.store is not None:
                    codes = codes.astype(pl.store)
                outs.append((codes, vbytes, None))
            elif dt == STRING:
                lens = jnp.where(valid, data, 0).astype(jnp.int32)
                if pl.store is not None:
                    lens = lens.astype(pl.store)
                outs.append((lens, vbytes, chars))
            elif dt == BOOLEAN:
                dbits = _bitpack(valid & data.astype(jnp.bool_), out_cap)
                outs.append((dbits, vbytes, None))
            elif pl.store is not None:
                x = data.astype(jnp.int64)
                x = jnp.where(valid, x - jnp.int64(pl.base), 0)
                outs.append((x.astype(pl.store), vbytes, None))
            else:
                outs.append((data, vbytes, None))
        if with_counts:
            return tuple(outs), total
        return tuple(outs)

    fn = engine_jit(run, family="egress", name="pack")
    _PACK_CACHE[key] = fn
    return fn


# ---------------------------------------------------------------------------
# host-side unpack
# ---------------------------------------------------------------------------

class _ColShim:
    __slots__ = ("dtype", "num_rows")

    def __init__(self, dtype, num_rows):
        self.dtype = dtype
        self.num_rows = num_rows


def _unpack_column(dt: DataType, pl: _ColPlan, planes, n: int,
                   out_cap: int) -> pa.Array:
    data_w, vbytes, chars = planes
    valid = np.unpackbits(np.asarray(vbytes),
                          bitorder="little")[:n].astype(np.bool_)
    shim = _ColShim(dt, n)
    if pl.enc:
        # codes -> values through the HOST dictionary (the values never
        # crossed the link); exact strings, nulls from the bitmask
        codes = np.asarray(data_w)[:n].astype(np.int64)
        codes = np.clip(codes, 0, max(0, len(pl.values) - 1))
        if len(pl.values):
            vals = pl.values[codes]
        else:
            vals = np.full(n, "", dtype=object)
        out = np.where(valid, vals, None)
        return pa.array(out.tolist(), type=pa.string())
    if dt == STRING:
        lens = np.asarray(data_w)
        if pl.store is not None:
            lens = lens.astype(np.int64)
        return _column_to_arrow_host(shim, lens, valid,
                                     np.asarray(chars))
    if dt == BOOLEAN:
        dbits = np.unpackbits(np.asarray(data_w),
                              bitorder="little")[:n].astype(np.bool_)
        return _column_to_arrow_host(shim, dbits, valid, None)
    data = np.asarray(data_w)
    if pl.store is not None:
        data = data.astype(np.int64) + pl.base
        data = data.astype(_np_dtype(dt))
    return _column_to_arrow_host(shim, data, valid, None)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def _narrow_store(rng: int):
    """Smallest unsigned wire dtype holding [0, rng]."""
    if rng < (1 << 8):
        return "uint8"
    if rng < (1 << 16):
        return "uint16"
    if rng < (1 << 32):
        return "uint32"
    return None


def _bound_bytes(cols: list, cap: int) -> int:
    from spark_rapids_tpu.columnar.encoding import EncodedColumn
    total = 0
    for c in cols:
        if isinstance(c, EncodedColumn):
            total += cap * 4 + cap // 8
        elif c.chars is not None:
            total += cap * (4 + c.chars.shape[1]) + cap // 8
        else:
            total += cap * c.data.dtype.itemsize + cap // 8
    return total


def _egress_cols(batches: List[ColumnarBatch]):
    """Per-batch column lists for the egress pack, with encoded
    ordinals unified onto one dictionary (codes stay codes on the
    wire — docs/compressed.md) when compressed egress is on.  An
    ordinal mixing encoded and dense batches (or egress off) densifies
    through the counted late decode when its planes are read."""
    from spark_rapids_tpu.columnar import encoding
    cols = [list(b.columns) for b in batches]
    if not encoding.egress_enabled() \
            or not any(encoding.has_encoded(b) for b in batches):
        return cols, {}
    return cols, encoding.unify_ordinals(cols)


def _col_flat(c, enc: bool):
    from spark_rapids_tpu.columnar.encoding import col_planes
    return col_planes(c, enc)[0]


def _col_sig(c, enc: bool):
    from spark_rapids_tpu.columnar.encoding import col_planes
    return col_planes(c, enc)[1]


def _count_wire(planes, plans, enc_dicts, out_cap: int) -> None:
    """The D2H raw-vs-wire mirror of the ingest trajectory counters
    (bench.py's per-suite `compressed` object): wire = the bytes the
    pull will actually stage, raw = what the same pack would stage
    fully dense — encoded columns decoded, integers un-narrowed,
    booleans and validity one byte per row.  The old accounting only
    credited dict columns against a ``raw = wire`` baseline, so any
    egress without an encoded column read raw == wire exactly (the
    BENCH_r06 signature) even while bitpacking and narrowing were
    compressing the wire."""
    wire = sum(getattr(a, "nbytes", 0)
               for a in jax.tree_util.tree_leaves(planes))
    raw = 0
    for ci, p in enumerate(plans):
        if p.enc:
            d = enc_dicts[ci]
            raw += out_cap * (4 + d.width)
        elif p.dtype == STRING:
            raw += out_cap * (4 + max(1, p.width))
        elif p.dtype == BOOLEAN:
            raw += out_cap
        else:
            raw += out_cap * _np_dtype(p.dtype).itemsize
        raw += out_cap  # the dense one-byte-per-row validity plane
    _bump_d2h("wire_bytes", wire)
    _bump_d2h("raw_bytes", raw)


class _PackPending:
    """Staged device-side pack (docs/d2h_egress.md): kernels dispatched
    asynchronously and host copies started; the blocking pull and host
    unpack are deferred to ``pack_finish`` — pipelined_d2h's
    dispatch/finish split."""

    __slots__ = ("planes", "total_dev", "n", "plans", "out_cap",
                 "arrow_schema", "dtypes", "ready")

    def __init__(self, planes=None, total_dev=None, n=None, plans=None,
                 out_cap=0, arrow_schema=None, dtypes=None, ready=None):
        self.planes = planes
        self.total_dev = total_dev
        self.n = n
        self.plans = plans
        self.out_cap = out_cap
        self.arrow_schema = arrow_schema
        self.dtypes = dtypes
        self.ready = ready

    def wire_bytes(self) -> int:
        """Host bytes the finish pull will stage (no sync: device
        arrays expose nbytes from their aval)."""
        if self.planes is None:
            return 0
        return sum(getattr(a, "nbytes", 0)
                   for a in jax.tree_util.tree_leaves(self.planes))


def pack_finish(pending: "_PackPending", metrics=None) -> pa.RecordBatch:
    """Blocking half of the pack: pull the staged planes (one link
    round trip — cheap when ``start_host_copies`` raced ahead) and
    unpack to a host RecordBatch."""
    if pending.ready is not None:
        return pending.ready
    if pending.total_dev is None:
        pulled_planes = device_pull(pending.planes, metrics=metrics)
        n = pending.n
    else:
        pulled_planes, n = device_pull(
            (pending.planes, pending.total_dev), metrics=metrics)
        n = int(n)
    arrays = []
    for ci, (dt, f) in enumerate(zip(pending.dtypes,
                                     pending.arrow_schema)):
        arr = _unpack_column(dt, pending.plans[ci], pulled_planes[ci],
                             n, pending.out_cap)
        arrays.append(arr.cast(f.type))
    return pa.RecordBatch.from_arrays(arrays,
                                      schema=pending.arrow_schema)


def pack_and_pull(batches: List[ColumnarBatch], schema: Schema,
                  stats_threshold: int = 1 << 20,
                  metrics=None) -> pa.RecordBatch:
    """Pack every device batch into one wire buffer and pull it in one
    link round trip (two for large results that warrant a stats pull).
    Returns a single host RecordBatch with exactly the live rows."""
    return pack_finish(pack_dispatch(batches, schema, stats_threshold,
                                     metrics=metrics), metrics=metrics)


def pack_dispatch(batches: List[ColumnarBatch], schema: Schema,
                  stats_threshold: int = 1 << 20,
                  metrics=None) -> "_PackPending":
    """Non-blocking half of the pack: decide the wire plan (the large-
    result path spends its tiny stats pull here), dispatch the pack
    kernel, and start the device->host copies.  Returns a
    ``_PackPending`` for ``pack_finish``."""
    arrow_schema = schema.to_arrow()
    if not batches:
        return _PackPending(ready=pa.RecordBatch.from_arrays(
            [pa.nulls(0, f.type) for f in arrow_schema],
            schema=arrow_schema))
    dtypes = [f.dtype for f in schema]
    dtypes_key = tuple(d.name for d in dtypes)
    all_cols, enc_dicts = _egress_cols(batches)
    sigs = tuple(
        tuple(_col_sig(c, ci in enc_dicts)
              for ci, c in enumerate(cols))
        for cols in all_cols)
    flats = tuple(tuple(_col_flat(c, ci in enc_dicts)
                        for ci, c in enumerate(cols))
                  for cols in all_cols)
    bound = sum(b.rows_bound for b in batches)
    bound_cap = transfer_bucket(bound)

    use_stats = _bound_bytes(all_cols[0], bound_cap) > stats_threshold
    if use_stats:
        # round trip 1: counts + per-column (min,max)/maxlen, all batches
        # in one device_get
        pend = []
        for b, sig, flat in zip(batches, sigs, flats):
            fn = _compile_stats(sig, dtypes_key, b.capacity, dtypes)
            pend.append(fn(flat, b.rows_traced))
        pulled = device_pull(pend, metrics=metrics)
        counts = [int(p[0]) for p in pulled]
        total = sum(counts)
        # the stats pull just materialized every count: cache them on the
        # batches so later host reads don't pay another round trip
        from spark_rapids_tpu.columnar.column import LazyRows
        for b, c in zip(batches, counts):
            if isinstance(b.rows_raw, LazyRows):
                b.rows_raw._val = c
        out_cap = transfer_bucket(max(1, total))
        # fold stats across batches
        plans: List[_ColPlan] = []
        i = 1
        lo_hi: List[Tuple[int, int]] = []
        maxlens: List[int] = []
        idx = [1] * len(batches)  # per-batch cursor into stats tuple
        for ci, dt in enumerate(dtypes):
            if ci in enc_dicts:
                # encoded: no stats entries (the kernel skipped them)
                lo_hi.append((0, 0))
                maxlens.append(0)
            elif dt == STRING:
                ml = 0
                for bi, p in enumerate(pulled):
                    ml = max(ml, int(p[idx[bi]]))
                    idx[bi] += 1
                maxlens.append(ml)
                lo_hi.append((0, 0))
            elif _int_like(dt):
                lo, hi = None, None
                for bi, p in enumerate(pulled):
                    blo, bhi = int(p[idx[bi]]), int(p[idx[bi] + 1])
                    idx[bi] += 2
                    if blo <= bhi:  # batch had valid values
                        lo = blo if lo is None else min(lo, blo)
                        hi = bhi if hi is None else max(hi, bhi)
                lo_hi.append((lo, hi) if lo is not None else (0, 0))
                maxlens.append(0)
            else:
                lo_hi.append((0, 0))
                maxlens.append(0)
        for ci, dt in enumerate(dtypes):
            if ci in enc_dicts:
                d = enc_dicts[ci]
                plans.append(_ColPlan(dt, 0,
                                      _narrow_store(max(0, d.size - 1)),
                                      0, enc=True, values=d.values))
            elif dt == STRING:
                width = transfer_bucket(max(1, maxlens[ci]))
                width = min(width,
                            max(c.string_width for c in
                                [cols[ci] for cols in all_cols]))
                st = _narrow_store(max(0, maxlens[ci]))
                plans.append(_ColPlan(dt, 0, st, width))
            elif dt == BOOLEAN:
                plans.append(_ColPlan(dt))
            elif _int_like(dt):
                lo, hi = lo_hi[ci]
                st = _narrow_store(hi - lo)
                base = lo if st is not None else 0
                plans.append(_ColPlan(dt, base, st))
            else:
                plans.append(_ColPlan(dt))
        plan_key = tuple(p.key() for p in plans)
        fn = _compile_pack(sigs, plan_key, out_cap, dtypes, plans,
                           with_counts=False)
        planes = fn(flats, tuple(counts))
        pending = _PackPending(planes=planes, n=total, plans=plans,
                               out_cap=out_cap,
                               arrow_schema=arrow_schema, dtypes=dtypes)
    else:
        # fast path: single round trip — counts ride with the data
        out_cap = bound_cap
        plans = []
        for ci, dt in enumerate(dtypes):
            if ci in enc_dicts:
                d = enc_dicts[ci]
                plans.append(_ColPlan(dt, 0,
                                      _narrow_store(max(0, d.size - 1)),
                                      0, enc=True, values=d.values))
            elif dt == STRING:
                width = max(cols[ci].string_width for cols in all_cols)
                plans.append(_ColPlan(dt, 0, None, width))
            else:
                plans.append(_ColPlan(dt))
        plan_key = tuple(p.key() for p in plans)
        fn = _compile_pack(sigs, plan_key, out_cap, dtypes, plans,
                           with_counts=True)
        planes, total_dev = fn(flats, tuple(b.rows_traced
                                            for b in batches))
        pending = _PackPending(planes=planes, total_dev=total_dev,
                               plans=plans, out_cap=out_cap,
                               arrow_schema=arrow_schema, dtypes=dtypes)
    _count_wire(pending.planes, plans, enc_dicts, out_cap)
    start_host_copies((pending.planes, pending.total_dev))
    return pending


# ---------------------------------------------------------------------------
# single-pull partition egress (docs/d2h_egress.md)
# ---------------------------------------------------------------------------

class _PartsPending:
    """Staged single-pull partition egress: gather+pack dispatched,
    copies started; blocking pull + host slicing deferred to
    ``pack_partitions_finish``."""

    __slots__ = ("pack", "counts", "num_parts")

    def __init__(self, pack: _PackPending, counts, num_parts: int):
        self.pack = pack
        self.counts = counts
        self.num_parts = num_parts

    def wire_bytes(self) -> int:
        return self.pack.wire_bytes()


def pack_partitions_dispatch(batch: ColumnarBatch, counts, perm,
                             num_parts: int,
                             schema: Optional[Schema] = None
                             ) -> "_PartsPending":
    """Non-blocking half of the single-pull partition egress: gather
    the partition-contiguous permutation on device (dead rows sort to
    the tail and mask invalid), dispatch the same plane-packing/
    validity-bitpack kernel ``pack_and_pull`` uses, and start the
    device->host copies.  Deliberately skips the large-result stats
    round trip (``pack_and_pull``'s narrowing pass): keeping the
    invariant at exactly one pull per input batch is the point of this
    path, and shuffle blocks are zstd-compressed right after, which
    recovers most of what narrowing would have saved on the wire."""
    schema = schema or batch.schema
    arrow_schema = schema.to_arrow()
    dtypes = [f.dtype for f in schema]
    # gather at the full permutation length: every live row has a
    # partition, so the live total equals the batch's row count and the
    # tail holds dead-row indices (>= num_rows) the gather invalidates —
    # no separate counts sync is needed to size the gather
    permuted = batch.gather(perm, batch.rows_raw)
    all_cols, enc_dicts = _egress_cols([permuted])
    cols0 = all_cols[0]
    sigs = (tuple(_col_sig(c, ci in enc_dicts)
                  for ci, c in enumerate(cols0)),)
    flats = (tuple(_col_flat(c, ci in enc_dicts)
                   for ci, c in enumerate(cols0)),)
    out_cap = transfer_bucket(max(1, permuted.rows_bound))
    plans: List[_ColPlan] = []
    for ci, dt in enumerate(dtypes):
        if ci in enc_dicts:
            d = enc_dicts[ci]
            plans.append(_ColPlan(dt, 0,
                                  _narrow_store(max(0, d.size - 1)),
                                  0, enc=True, values=d.values))
        elif dt == STRING:
            plans.append(_ColPlan(dt, 0, None,
                                  cols0[ci].string_width))
        else:
            plans.append(_ColPlan(dt))
    plan_key = tuple(p.key() for p in plans)
    fn = _compile_pack(sigs, plan_key, out_cap, dtypes, plans,
                       with_counts=True)
    planes, total_dev = fn(flats, (permuted.rows_traced,))
    _count_wire(planes, plans, enc_dicts, out_cap)
    pack = _PackPending(planes=planes, total_dev=total_dev, plans=plans,
                        out_cap=out_cap, arrow_schema=arrow_schema,
                        dtypes=dtypes)
    pending = _PartsPending(pack, counts, num_parts)
    start_host_copies((planes, total_dev, counts))
    return pending


def pack_partitions_finish(pending: "_PartsPending", metrics=None
                           ) -> List[Optional[pa.RecordBatch]]:
    """Blocking half: pull the packed planes, the live total, AND the
    per-partition counts in ONE ``device_get``, then slice
    per-partition record batches (zero-copy arrow slices) from the
    counts — None for empty partitions, matching ``partition_batch``'s
    contract."""
    pk = pending.pack
    pulled_planes, n, counts_h = device_pull(
        (pk.planes, pk.total_dev, pending.counts), metrics=metrics)
    n = int(n)
    counts_h = np.asarray(counts_h)
    arrays = []
    for ci, (dt, f) in enumerate(zip(pk.dtypes, pk.arrow_schema)):
        arr = _unpack_column(dt, pk.plans[ci], pulled_planes[ci], n,
                             pk.out_cap)
        arrays.append(arr.cast(f.type))
    rb = pa.RecordBatch.from_arrays(arrays, schema=pk.arrow_schema)
    out: List[Optional[pa.RecordBatch]] = []
    off = 0
    for p in range(pending.num_parts):
        c = int(counts_h[p])
        out.append(rb.slice(off, c) if c else None)
        off += c
    return out


def pack_partitions_and_pull(batch: ColumnarBatch, counts, perm,
                             num_parts: int,
                             schema: Optional[Schema] = None,
                             metrics=None
                             ) -> List[Optional[pa.RecordBatch]]:
    """One D2H pull for a whole partitioned batch — replaces one gather
    + one ``device_batch_to_host`` pull PER NON-EMPTY PARTITION: with
    8+ partitions at ~94ms of fixed link latency per pull, that is
    ~90% of the egress link time on every exchange batch."""
    return pack_partitions_finish(
        pack_partitions_dispatch(batch, counts, perm, num_parts, schema),
        metrics=metrics)
