"""Device runtime: chip discovery, HBM budget, task admission semaphore.

Reference: GpuDeviceManager.scala:31-242 (single-GPU-per-executor
acquisition, RMM pool init as a fraction of device memory, thread-pinning)
and GpuSemaphore.scala:27-161 (bounds concurrent tasks sharing one device).

TPU design: XLA owns the HBM arena, so instead of an RMM-style pooled
allocator we track a *budget* (allocFraction x HBM) that the spill layer
uses for admission decisions, and rely on the semaphore to bound concurrent
device users — the same two control points as the reference, minus the
custom allocator XLA makes unnecessary.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax

from spark_rapids_tpu.conf import MEM_DEBUG, TpuConf


class TpuSemaphore:
    """Counted multi-task chip admission (reference GpuSemaphore
    GpuSemaphore.scala:27; ``spark.rapids.tpu.concurrentTasks``, default
    2, legacy alias ``spark.rapids.sql.concurrentTpuTasks``).
    Re-entrant per thread, mirroring the per-task refcount.

    With 2+ permits a decode-bound scan task and a compute-bound task
    interleave on one chip — the admission half of the scan->H2D->compute
    overlap pipeline (docs/io_overlap.md).  ``wait_ns``/``wait_count``
    record contention so the bench can tell admission stalls from decode
    stalls.

    Capacity is a condition-guarded counter rather than a stdlib
    Semaphore so the chip-health layer can ``resize()`` it when chips
    quarantine or restore (docs/fault_tolerance.md, "Chip failure
    domain"): shrinking takes effect as holders release, growing wakes
    waiters immediately."""

    def __init__(self, permits: int):
        import time
        self.permits = max(1, int(permits))
        # the conf-derived capacity the health layer scales FROM when
        # the chip pool shrinks/grows (resize never loses the baseline)
        self.base_permits = self.permits
        self._cv = threading.Condition()
        self._in_use = 0
        self._held = threading.local()
        self._clock = time.perf_counter_ns
        # telemetry; admission correctness lives entirely under _cv.
        # acquire_count stays a GIL-racy advisory increment, but
        # wait_ns/wait_count are guarded: per-query end flushes
        # take-and-zero the accumulator, and an unlocked
        # read-modify-write racing that exchange could resurrect
        # already-flushed nanoseconds (double count) or drop a wait
        self.acquire_count = 0
        self.wait_count = 0
        self.wait_ns = 0
        self._stats_mu = threading.Lock()

    def _try_acquire(self) -> bool:
        with self._cv:
            if self._in_use < self.permits:
                self._in_use += 1
                return True
            return False

    def acquire(self) -> None:
        depth = getattr(self._held, "depth", 0)
        if depth == 0:
            self.acquire_count += 1
            if not self._try_acquire():
                t0 = self._clock()
                # bounded wait polling the active query's cancel token
                # (lifecycle.py): a cancelled/expired query parked on
                # admission raises typed instead of waiting out another
                # task's compute; no token -> behaves like a plain
                # blocking acquire, one poll interval at a time
                from spark_rapids_tpu import lifecycle
                while True:
                    with self._cv:
                        if self._in_use < self.permits:
                            self._in_use += 1
                            break
                        self._cv.wait(
                            timeout=lifecycle.poll_interval_s())
                        if self._in_use < self.permits:
                            self._in_use += 1
                            break
                    lifecycle.check_cancel()
                waited = self._clock() - t0
                with self._stats_mu:
                    self.wait_count += 1
                    self.wait_ns += waited
                # attribute the wait to the query doing the waiting
                # (this thread's context) — a concurrent query's end
                # flush cannot claim it
                lifecycle.note_sem_wait(waited)
                # admission-wait distribution (docs/observability.md):
                # contention shape, not just its total
                from spark_rapids_tpu.obs import registry as obs
                obs.record(obs.HIST_SEM_WAIT_US, waited // 1000)
        self._held.depth = depth + 1

    def drain_wait_ns(self) -> int:
        """Atomically take-and-zero the accumulated admission-wait ns
        (flushed at query end and at shutdown): a locked exchange, so a
        flush racing a concurrent acquire's increment can neither drop
        that wait nor count already-flushed nanoseconds twice."""
        with self._stats_mu:
            ns = self.wait_ns
            self.wait_ns = 0
            return ns

    def available(self) -> int:
        """Approximate free permits right now (advisory: another thread
        may take one between the read and any acquire).  The session
        server reads it for its stats snapshot and to derive its
        default worker-pool size — the fair scheduler sits in FRONT of
        this semaphore, dispatching roughly 2x permits so a decode- or
        pull-bound query never leaves the chip idle (docs/serving.md)."""
        with self._cv:
            return max(0, self.permits - self._in_use)

    def resize(self, permits: int) -> None:
        """Set admission capacity (floor 1).  The chip-health layer
        calls this when chips quarantine or restore so the counted
        concurrency tracks the surviving pool
        (docs/fault_tolerance.md, "Chip failure domain"): growth wakes
        parked waiters; shrink never revokes a held permit —
        over-capacity holders simply drain as they release."""
        with self._cv:
            self.permits = max(1, int(permits))
            self._cv.notify_all()

    def release(self) -> None:
        depth = getattr(self._held, "depth", 0)
        if depth <= 0:
            return
        self._held.depth = depth - 1
        if self._held.depth == 0:
            with self._cv:
                self._in_use -= 1
                self._cv.notify()

    @contextlib.contextmanager
    def held(self):
        self.acquire()
        try:
            yield
        finally:
            self.release()


class _ScanCache:
    """LRU of uploaded scan outputs (list[SpillableBatch] per key).

    Hot queries re-reading the same files skip the host decode + upload
    entirely; the handles stay registered in the spill catalog, so HBM
    pressure spills them tier-by-tier instead of breaking the budget.
    The TPU analog of the reference pipeline keeping decoded tables in
    GPU memory rather than re-decoding Parquet per query
    (GpuParquetScan.scala:316-458 decode feeds device memory directly)."""

    def __init__(self, max_entries: int = 8):
        self.max_entries = max_entries
        self._lock = threading.Lock()
        # key -> (list[SpillableBatch], schema, metrics_snapshot)
        self._entries: dict = {}
        self._order: list = []

    def get(self, key):
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None:
                self._order.remove(key)
                self._order.append(key)
            return ent

    def put(self, key, handles, schema, metrics=None) -> None:
        with self._lock:
            if key in self._entries:
                for h in self._entries[key][0]:
                    h.close()
                self._order.remove(key)
            self._entries[key] = (handles, schema, metrics or {})
            self._order.append(key)
            while len(self._order) > self.max_entries:
                old = self._order.pop(0)
                for h in self._entries.pop(old)[0]:
                    h.close()

    def clear(self) -> None:
        with self._lock:
            for ent in self._entries.values():
                for h in ent[0]:
                    h.close()
            self._entries.clear()
            self._order.clear()


class TpuRuntime:
    """Per-process device runtime (reference GpuDeviceManager +
    executor-side plugin init, Plugin.scala:220-242)."""

    _instance: Optional["TpuRuntime"] = None
    _lock = threading.Lock()

    def __init__(self, conf: TpuConf):
        self.conf = conf
        devices = jax.devices()
        if not devices:
            raise RuntimeError("no JAX devices available")
        # one worker per chip (reference: 1 executor per GPU enforced,
        # GpuDeviceManager.scala:98-112); multi-chip execution goes through
        # the parallel/ mesh layer, not multiple runtimes
        self.device = devices[0]
        self.all_devices = devices
        self.platform = self.device.platform
        from spark_rapids_tpu import _enable_compile_cache
        _enable_compile_cache(self.platform)
        # device float policy: DOUBLE-as-f32 on accelerator backends
        # unless overridden (spark.rapids.sql.device.doubleAsFloat)
        from spark_rapids_tpu.conf import DEVICE_DOUBLE_AS_FLOAT
        from spark_rapids_tpu.columnar.dtypes import set_double_as_float
        raw = conf.get(DEVICE_DOUBLE_AS_FLOAT)
        set_double_as_float(
            raw if raw is not None else self.platform != "cpu")
        self.semaphore = TpuSemaphore(conf.concurrent_tpu_tasks)
        self.hbm_budget_bytes = self._compute_budget()
        # spill catalog consuming the budget (reference: RMM event handler
        # + buffer catalog wiring in GpuDeviceManager.initializeMemory)
        from spark_rapids_tpu.memory.spill import BufferCatalog
        override = int(conf.get_raw(
            "spark.rapids.memory.tpu.budgetBytes", 0) or 0)
        host_limit = int(conf.get_raw(
            "spark.rapids.memory.host.spillStorageSize", 1 << 30) or 0)
        from spark_rapids_tpu.conf import (
            PINNED_POOL_SIZE, POOLED_ALLOCATOR,
        )
        self.catalog = BufferCatalog(
            override if override > 0 else self.hbm_budget_bytes,
            host_limit,
            debug=conf.get(MEM_DEBUG),
            pinned_pool_bytes=conf.get(PINNED_POOL_SIZE),
            pooling_enabled=conf.get(POOLED_ALLOCATOR))
        # device-resident scan cache: key -> list[SpillableBatch]
        # (spark.rapids.sql.scan.deviceCacheEnabled); entries live in the
        # spill catalog so memory pressure demotes them like any buffer
        self.scan_cache = _ScanCache(max_entries=8)
        # persistent compilation service (docs/compile_cache.md): the
        # capacity ladder and the kernel store configure from the SAME
        # conf the session carries — spawned shuffle/server workers
        # receive these keys with the shipped conf dict and the cache
        # dir through the env seam, so a worker's first batch reuses
        # the driver's kernels — and the AOT warm pool replays the
        # store's top-K recorded kernels so a restarted process reaches
        # hot-path latency before its first query.  One shared hook
        # (query scope, server start, and worker mains call the same);
        # compile.* unset = byte-identical to the pre-service engine
        from spark_rapids_tpu import compile as _compile
        _compile.configure_from_conf(conf, platform=self.platform)
        # cost-based placement (docs/placement.md): with
        # placement.mode=cost and any link constant left to measure,
        # probe the link once at startup — the one-shot probe bench.py
        # used to carry — so the first query's planning reads measured
        # constants instead of paying the probe itself
        from spark_rapids_tpu.plan import cost as _cost
        _cost.startup_probe(conf)

    def _compute_budget(self) -> int:
        frac = float(self.conf.get_raw(
            "spark.rapids.memory.tpu.allocFraction", 0.9))
        if self.platform == "cpu":
            # the host backend reports no device memory: budget as if it
            # were one v5e chip (16 GiB HBM) so tests exercise the same
            # admission arithmetic
            return int(16 * 1024 ** 3 * frac)
        # on an accelerator the budget is the device's own number; a
        # chip that cannot say how much memory it has is an error, not
        # a reason to assume
        stats = self.device.memory_stats() or {}
        total = stats.get("bytes_limit") or stats.get(
            "bytes_reservable_limit")
        if not total:
            raise RuntimeError(
                f"{self.device} reports no bytes_limit in memory_stats() "
                f"({sorted(stats)}); cannot size the HBM budget")
        return int(total * frac)

    @classmethod
    def get_or_create(cls, conf: TpuConf) -> "TpuRuntime":
        with cls._lock:
            if cls._instance is None:
                cls._instance = TpuRuntime(conf)
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        # deterministic stop: tear down every lifecycle-registered
        # resource (prefetch producers, compile warmers, transport
        # threads, worker process groups) BEFORE dropping the runtime,
        # so reset never leaves reclamation to GC and daemon flags
        from spark_rapids_tpu import lifecycle
        lifecycle.shutdown_all()
        with cls._lock:
            cls._instance = None

    def flush_semaphore_waits(self) -> int:
        """Flush admission-contention telemetry into the process-wide
        overlap counters and return the flushed milliseconds.  Called
        at QUERY end by the lifecycle layer (so bench sees admission
        waits without a session stop) and again at shutdown for
        whatever accrued in between.  Per-QUERY attribution happens at
        the acquire site itself (lifecycle.note_sem_wait), not here."""
        from spark_rapids_tpu.io import prefetch as _prefetch
        ms = self.semaphore.drain_wait_ns() // 1_000_000
        _prefetch._bump_global("sem_wait_ms", ms)
        return ms

    def acquire_device(self):
        """Admission-controlled device section (reference
        GpuSemaphore.acquireIfNecessary GpuSemaphore.scala:74)."""
        return self.semaphore.held()

    def shutdown(self) -> None:
        # deterministic teardown first: join every lifecycle-registered
        # thread / worker group so the leak audit below sees the state
        # AFTER supervised resources closed, not racing them
        from spark_rapids_tpu import lifecycle
        lifecycle.shutdown_all()
        # flush admission-contention telemetry into the process-wide
        # overlap counters before this runtime instance is dropped
        # (bench.py reads them after every per-suite session stops;
        # per-query flushes happen at lifecycle teardown — this covers
        # whatever accrued since the last query ended)
        self.flush_semaphore_waits()
        self.scan_cache.clear()
        leaked = self.catalog.audit_leaks()
        if leaked:
            import warnings
            warnings.warn(
                f"{leaked} spillable buffer(s) still registered at "
                "runtime shutdown (operator leak)", ResourceWarning)
        TpuRuntime.reset()
