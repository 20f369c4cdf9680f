"""Tiered spill framework: device -> host -> disk.

Reference: RapidsBufferCatalog.scala:40 (buffer registry + tier lookup),
RapidsBufferStore.scala:148-431 (device/host/disk stores with demotion),
DeviceMemoryEventHandler.scala:65-95 (allocation-failure -> synchronous
spill of lowest-priority buffers).

TPU design: XLA owns the real HBM arena, so there is no allocation hook to
intercept; instead operators register their *materialized intermediate
batches* (aggregate partials, sort inputs, window inputs) with the catalog
as spillable handles, and the catalog enforces the budget from
``TpuRuntime.hbm_budget_bytes`` by demoting least-recently-used handles:
device arrays -> pinned-host numpy (``jax.device_get``) -> an .npz file in
the spill directory.  ``get()`` promotes back on demand.  Demotion order
follows the reference's SpillPriorities convention
(SpillPriorities.scala:26-50): the priority CLASS decides first —
re-creatable buffers (device scan cache) before operator working
batches before broadcast builds — with least-recently-used as the
tie-break inside a class; handles being actively materialized are
pinned.
"""

from __future__ import annotations

import os
import tempfile
import threading
import weakref
from typing import Dict, Iterator, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_tpu import faults
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.errors import QueryBudgetExceededError

import logging
import sys
import warnings

log = logging.getLogger("spark_rapids_tpu.memory")

TIER_DEVICE = "device"
TIER_HOST = "host"
TIER_DISK = "disk"


# Spill priorities (reference SpillPriorities.scala:26-50): lower
# values demote FIRST.  Re-creatable data (cached scans) goes before
# working batches; broadcast/build tables every task needs go last.
PRIORITY_RECREATABLE = -100   # e.g. the device scan cache
PRIORITY_NORMAL = 0           # operator working batches
PRIORITY_RETAIN = 100         # broadcast builds, long-lived tables


class SpillableBatch:
    """A catalog-managed handle over one columnar batch (reference
    RapidsBuffer: id + tier + spill/materialize transitions).
    ``priority`` orders demotion across handles (SpillPriorities
    analog): lower spills first; LRU breaks ties within a class."""

    def __init__(self, batch: ColumnarBatch, catalog: "BufferCatalog",
                 priority: int = PRIORITY_NORMAL):
        from spark_rapids_tpu.columnar.encoding import (
            DeltaColumn, EncodedColumn, PackedBoolColumn, RleColumn,
        )
        self.priority = int(priority)
        self._catalog = catalog
        self.schema = batch.schema
        # int or LazyRows — kept device-resident, no sync here; the tiny
        # count scalar survives on device even if the data planes spill
        self.num_rows = batch.rows_raw
        # encoded columns spill their CODES plane, never the dense char
        # matrix (docs/compressed.md): the shared dictionary stays
        # device-resident in _dicts (small, shared across handles) and
        # the column re-wraps on materialization.  Plane-compressed
        # columns (rle/delta/packed bool) likewise spill their COMPRESSED
        # planes — materializing them here would both inflate every tier
        # and burn an uncounted decode before any stage can fuse it.
        self._meta = []
        self._device: Optional[List] = []
        self._dicts: List = []
        for c in batch.columns:
            if isinstance(c, EncodedColumn):
                self._meta.append((c.dtype, None))
                self._device.append((c.codes, c.validity, None))
                self._dicts.append(c.dict)
            elif isinstance(c, RleColumn):
                self._meta.append(
                    (c.dtype, ("rle", c.num_runs, c.capacity)))
                self._device.append((c.run_values, c.validity,
                                     c.run_ends))
                self._dicts.append(None)
            elif isinstance(c, DeltaColumn):
                self._meta.append((c.dtype, ("delta", c.capacity)))
                self._device.append((c.deltas, c.validity, c.base))
                self._dicts.append(None)
            elif isinstance(c, PackedBoolColumn):
                self._meta.append((c.dtype, ("packed", c.capacity)))
                self._device.append((c.packed, c.validity, None))
                self._dicts.append(None)
            else:
                self._meta.append(
                    (c.dtype, "chars" if c.chars is not None else None))
                self._device.append((c.data, c.validity, c.chars))
                self._dicts.append(None)
        # per-plane host-tier bitpack flags, filled by _to_host
        self._packed: Optional[List] = None
        self._host: Optional[List] = None
        self._disk_path: Optional[str] = None
        self.size = batch.size_bytes()
        self.tier = TIER_DEVICE
        self.pinned = False
        catalog._register(self)

    # -- demotion (called by the catalog under its lock) --------------------

    def _to_host(self) -> None:
        # single-writer invariant: tier transitions only under the catalog
        # lock (reference documents the same deliberate threading models,
        # RapidsShuffleClient.scala:61 "not thread safe")
        assert self._catalog._lock._is_owned(), \
            "catalog lock must be held for tier transitions"
        assert self.tier == TIER_DEVICE
        # fires BEFORE any state mutates, so an injected demotion failure
        # leaves the handle fully intact on its current tier
        faults.maybe_fail("spill.demote",
                          f"injected device->host demotion failure "
                          f"({self.size} bytes)")
        # ONE pull for every plane of every column (device_pull:
        # counted, fault-injectable via transfer.d2h — an InjectedFault
        # is an IOError, so _demote treats it as a bounded demotion
        # failure): per-plane np.asarray conversions each paid a full
        # link round trip, multiplying demotion latency by ~3x ncols.
        # Boolean/validity planes bitpack ON DEVICE first (the shared
        # transfer.bitpack_plane primitive the wire codec uses), so the
        # link and the host/disk tiers carry 8 rows/byte — the same
        # treatment the egress pack already applied, unified here.
        from spark_rapids_tpu.columnar.transfer import (
            bitpack_plane, device_pull,
        )
        packed_dev: List = []
        packed_meta: List = []
        for triple in self._device:
            out_triple = []
            out_flags = []
            for a in triple:
                if a is not None and a.dtype == jnp.bool_:
                    out_triple.append(bitpack_plane(a))
                    out_flags.append(int(a.shape[0]))  # original cap
                else:
                    out_triple.append(a)
                    out_flags.append(0)
            packed_dev.append(tuple(out_triple))
            packed_meta.append(tuple(out_flags))
        with self._catalog.staging.limit(self.size):
            host = device_pull(packed_dev)
            self._host = [tuple(None if a is None else np.asarray(a)
                                for a in triple)
                          for triple in host]
        self._packed = packed_meta
        self._device = None
        self.tier = TIER_HOST
        self._catalog._sync_info(self)

    def _to_disk(self) -> None:
        assert self._catalog._lock._is_owned(), \
            "catalog lock must be held for tier transitions"
        assert self.tier == TIER_HOST
        faults.maybe_fail("spill.demote",
                          f"injected host->disk demotion failure "
                          f"({self.size} bytes)")
        path = os.path.join(self._catalog.spill_dir,
                            f"spill-{id(self):x}.npz")
        arrays = {}
        for ci, triple in enumerate(self._host):
            for ai, a in enumerate(triple):
                if a is not None:
                    arrays[f"c{ci}_{ai}"] = a
        np.savez(path, **arrays)
        self._disk_path = path
        self._host = None
        self.tier = TIER_DISK
        self._catalog._sync_info(self)

    def _from_disk(self) -> None:
        assert self.tier == TIER_DISK
        with np.load(self._disk_path) as z:
            self._host = [
                tuple(z[f"c{ci}_{ai}"] if f"c{ci}_{ai}" in z.files else None
                      for ai in range(3))
                for ci in range(len(self._meta))]
        os.unlink(self._disk_path)
        self._disk_path = None
        self.tier = TIER_HOST
        self._catalog._sync_info(self)

    # -- materialization ----------------------------------------------------

    def get(self, device=None) -> ColumnarBatch:
        """Materialize on device, promoting through the tiers; makes room
        first so promotion itself can demote colder handles.  Under a
        per-query budget, a promotion that lands the owning query over
        ``spark.rapids.server.query.maxDeviceBytes`` re-enforces after
        the move: spillable working set demotes, and a pinned working
        set that cannot shrink cancels the query typed
        (docs/serving.md)."""
        cat = self._catalog
        with cat._lock:
            was_pinned = self.pinned
            self.pinned = True
        moves = []
        promoted = False
        try:
            if self.tier != TIER_DEVICE:
                # fires before any promotion state mutates: an injected
                # promotion failure (the disk-read-error analog) leaves
                # the handle recoverable on its current tier
                faults.maybe_fail(
                    "spill.promote",
                    f"injected {self.tier}->device promotion failure "
                    f"({self.size} bytes)")
                promoted = True
                cat.reserve(self.size)
            with cat._lock:
                if self.tier == TIER_DISK:
                    self._from_disk()
                    cat.disk_bytes = max(0, cat.disk_bytes - self.size)
                    cat.host_bytes += self.size
                    moves.append((True, TIER_DISK, TIER_HOST, self.size))
                if self.tier == TIER_HOST:
                    from spark_rapids_tpu.columnar.transfer import (
                        bitunpack_host,
                    )
                    with cat.staging.limit(self.size):
                        dev = []
                        for ci, triple in enumerate(self._host):
                            flags = self._packed[ci] if self._packed \
                                else (0, 0, 0)
                            planes = []
                            for a, cap in zip(triple, flags):
                                if a is None:
                                    planes.append(None)
                                elif cap:
                                    planes.append(jax.device_put(
                                        bitunpack_host(a, cap), device))
                                else:
                                    planes.append(jax.device_put(
                                        a, device))
                            dev.append(tuple(planes))
                        self._device = dev
                    self._host = None
                    self._packed = None
                    self.tier = TIER_DEVICE
                    cat._sync_info(self)
                    cat.host_bytes = max(0, cat.host_bytes - self.size)
                    cat.device_bytes += self.size
                    cat.unspill_count += 1
                    cat._log("unspill", self)
                    moves.append((True, TIER_HOST, TIER_DEVICE,
                                  self.size))
                cat._touch(self)
                from spark_rapids_tpu.columnar.encoding import (
                    DeltaColumn, EncodedColumn, PackedBoolColumn,
                    RleColumn,
                )
                cols = []
                for (dt, kind), (d, v, ch), dct in zip(
                        self._meta, self._device, self._dicts):
                    if dct is not None:
                        cols.append(EncodedColumn(d, v, self.num_rows,
                                                  dct))
                    elif kind is not None and kind[0] == "rle":
                        cols.append(RleColumn(dt, d, ch, kind[1], v,
                                              self.num_rows, kind[2]))
                    elif kind is not None and kind[0] == "delta":
                        cols.append(DeltaColumn(dt, d, ch, v,
                                                self.num_rows, kind[1]))
                    elif kind is not None and kind[0] == "packed":
                        cols.append(PackedBoolColumn(d, v, self.num_rows,
                                                     kind[1]))
                    else:
                        cols.append(DeviceColumn(dt, d, v,
                                                 self.num_rows,
                                                 chars=ch))
                out = ColumnarBatch(cols, self.num_rows, self.schema)
                out._size = self.size  # the same planes: no second walk
        finally:
            with cat._lock:
                self.pinned = was_pinned
            # journal the promote chain (disk->host, host->device)
            # outside the catalog lock; a move is only recorded after
            # its transition completed, so a promote that failed midway
            # still journals the tiers it actually crossed
            cat._emit_tier_moves(moves)
        if promoted:
            # the promotion may have carried the OWNING query past its
            # device budget: re-enforce (spill its working set, or —
            # when everything left is pinned, the materialize_all case
            # — cancel it typed).  After the finally: self is back at
            # its caller's pin state, and the returned arrays stay
            # valid even if enforcement demotes this handle again.
            cat._enforce_promote_budget(self)
        return out

    def host_nbytes(self) -> int:
        """Actual bytes resident on the host tier (bitpacked planes +
        codes, not the dense estimate ``size`` budgets by) — the number
        the spill tests assert shrinks under the shared pack
        primitives."""
        if self._host is None:
            return 0
        return sum(a.nbytes for triple in self._host
                   for a in triple if a is not None)

    def close(self) -> None:
        self._catalog._deregister(self)
        if self._disk_path and os.path.exists(self._disk_path):
            os.unlink(self._disk_path)
        self._device = self._host = None

    @property
    def suppress_leak_warning(self) -> bool:
        info = self._catalog._info.get(id(self))
        return bool(info and info.get("suppress"))

    @suppress_leak_warning.setter
    def suppress_leak_warning(self, v: bool) -> None:
        info = self._catalog._info.get(id(self))
        if info is not None:
            info["suppress"] = bool(v)


class HostStagingLimiter:
    """Bounded admission for host staging during tier transitions
    (reference PinnedMemoryPool / spark.rapids.memory.pinnedPool.size +
    memory.tpu.pooling.enabled): at most ``cap`` bytes of device<->host
    transfers stage concurrently, so a burst of parallel spills cannot
    transiently double the host footprint the way unbounded staging
    would.  cap==0 disables (no limiting)."""

    _ABORT_POLL_S = 0.05

    def __init__(self, cap_bytes: int = 0, name: str = ""):
        self.cap = max(0, int(cap_bytes))
        # waiter-class name ("spill"/"prefetch"/"egress"): keys this
        # limiter's admission-wait histogram (docs/observability.md)
        self.name = name
        self._inflight = 0
        self._cv = threading.Condition()
        self.wait_count = 0

    def acquire(self, nbytes: int, abort=None) -> int:
        """Block until ``nbytes`` (clamped to the cap so one transfer
        always fits) of staging budget is admitted; returns the granted
        byte count to pass to ``release``.  ``abort`` is an optional
        zero-arg predicate polled while waiting — when it turns true the
        wait gives up and -1 is returned with nothing held (the scan
        prefetch thread uses this so a closed consumer never leaves a
        producer parked on admission forever).  When no explicit
        predicate is given, the active query's cancel token is the
        abort (lifecycle.cancel_requested): a cancelled or past-deadline
        query never stays parked on staging admission.  cap==0 grants 0
        immediately (limiting disabled)."""
        if self.cap <= 0:
            return 0
        if abort is None:
            from spark_rapids_tpu.lifecycle import cancel_requested
            abort = cancel_requested
        import time as _time
        ask = min(int(nbytes), self.cap)
        t0 = None
        try:
            with self._cv:
                if self._inflight + ask > self.cap:
                    self.wait_count += 1
                    t0 = _time.perf_counter_ns()
                while self._inflight + ask > self.cap:
                    if abort():
                        return -1
                    self._cv.wait(timeout=self._ABORT_POLL_S)
                self._inflight += ask
            return ask
        finally:
            if t0 is not None and self.name:
                # admission-wait distribution per waiter class
                # (docs/observability.md): aborted waits record too —
                # time parked is time parked.  The canonical-name table
                # keeps this keyed to the HIST_STAGING_* constants.
                from spark_rapids_tpu.obs import registry as obs
                hist = obs.STAGING_WAIT_HISTS.get(self.name)
                if hist is not None:
                    obs.record(hist,
                               (_time.perf_counter_ns() - t0) // 1000)

    def release(self, granted: int) -> None:
        if granted <= 0:
            return
        with self._cv:
            self._inflight -= granted
            self._cv.notify_all()

    def limit(self, nbytes: int):
        import contextlib

        @contextlib.contextmanager
        def ctx():
            granted = self.acquire(nbytes)
            if granted < 0:
                # the wait aborted on the query's cancel token: surface
                # typed (QueryCancelledError / QueryTimeoutError) —
                # never proceed unadmitted, never park forever
                from spark_rapids_tpu.lifecycle import raise_if_cancelled
                raise_if_cancelled()
            try:
                yield
            finally:
                self.release(granted)
        return ctx()


class BufferCatalog:
    """Registry + budget enforcement (reference RapidsBufferCatalog +
    the store chain device->host->disk)."""

    def __init__(self, device_budget_bytes: int,
                 host_budget_bytes: int = 1 << 30,
                 spill_dir: Optional[str] = None,
                 debug: str = "NONE",
                 pinned_pool_bytes: int = 0,
                 pooling_enabled: bool = False):
        import atexit
        import shutil
        self.device_budget = int(device_budget_bytes)
        self.host_budget = int(host_budget_bytes)
        # host staging admission (reference PinnedMemoryPool,
        # GpuDeviceManager.scala:200-206): pinnedPool.size bounds how
        # many bytes of device<->host tier transfers may stage at once
        # when pooling is enabled; 0 disables
        self.staging = HostStagingLimiter(
            pinned_pool_bytes if pooling_enabled else 0, name="spill")
        # SEPARATE limiter (same cap) for scan-prefetch queue admission
        # (io/prefetch.py).  Prefetch grants are held across opaque
        # consumer compute and release only when the consumer pulls
        # again — sharing a budget with the spill tier-transition waits
        # above (abortable only by query cancel, not by consumer
        # progress) would let a consumer wedged in spill_all deadlock
        # against grants only its own next pull can release.  Two
        # limiters, two waiter classes, no shared resource
        # between them: prefetch blocks only decode, spill staging only
        # waits on short bounded copies that always complete.  Worst-case
        # host staging is bounded by 2x the pinned-pool size.
        self.prefetch_staging = HostStagingLimiter(
            pinned_pool_bytes if pooling_enabled else 0, name="prefetch")
        # THIRD limiter (same cap) for the egress download pipeline
        # (columnar/transfer.py:pipelined_d2h, docs/d2h_egress.md).
        # Egress admission is SCOPED: a grant covers one blocking pull
        # and releases before the result is yielded — never held across
        # opaque consumer work.  Still a separate instance from the
        # prefetch limiter (whose queue grants ARE held across consumer
        # compute) and the spill-staging one (whose waits end only on
        # bounded copy completion or query cancel): three waiter
        # classes, no shared resource between them, so no cross-class
        # deadlock is constructible.  The limiter provides
        # CROSS-pipeline backpressure on concurrent pulls; the
        # per-pipeline footprint is bounded structurally by pipelined_
        # d2h's buffer pair (at most two staged items live), whose
        # host copies start at dispatch — i.e. slightly ahead of the
        # scoped grant, a documented trade against the self-deadlock a
        # dispatch-held grant would invite.
        self.egress_staging = HostStagingLimiter(
            pinned_pool_bytes if pooling_enabled else 0, name="egress")
        # allocation-event logging (reference RMM debug logging,
        # spark.rapids.memory.gpu.debug RapidsConf.scala:227-233)
        self.debug = (debug or "NONE").upper()
        self.leak_count = 0
        self._owns_dir = spill_dir is None
        self.spill_dir = spill_dir or tempfile.mkdtemp(prefix="srt-spill-")
        if self._owns_dir:
            # remove the directory (and any orphaned .npz from a crash
            # between _to_disk and close) at interpreter exit
            atexit.register(shutil.rmtree, self.spill_dir,
                            ignore_errors=True)
        self._lock = threading.RLock()
        # WEAK references: the catalog must not keep a dropped handle
        # alive, or the leak detector below could never fire and leaked
        # payloads would be retained for the session lifetime.  The
        # ``_info`` sidecar carries what the death callback needs
        # (tier/size/disk path) since the object is gone by then.
        self._lru: Dict[int, "weakref.ref"] = {}  # insertion = LRU order
        self._info: Dict[int, dict] = {}
        self.device_bytes = 0
        self.host_bytes = 0
        self.disk_bytes = 0
        self.spill_to_host_count = 0
        self.spill_to_disk_count = 0
        self.unspill_count = 0
        self.demote_failure_count = 0
        # per-query budget enforcement (docs/serving.md): spills forced
        # by spark.rapids.server.query.maxDeviceBytes, and queries
        # cancelled typed because spilling could not satisfy the budget
        self.budget_spill_count = 0
        self.budget_exceeded_count = 0

    def _log(self, event: str, sb: "SpillableBatch") -> None:
        if self.debug == "NONE":
            return
        out = sys.stdout if self.debug == "STDOUT" else sys.stderr
        out.write(f"[tpu-mem] {event} id={id(sb):x} tier={sb.tier} "
                  f"size={sb.size} device={self.device_bytes} "
                  f"host={self.host_bytes} disk={self.disk_bytes}\n")
        out.flush()

    @staticmethod
    def _emit_tier_moves(moves) -> None:
        """Structured demote/promote events (docs/observability.md) —
        the journal is the durable record of memory-pressure behavior
        the STDOUT debug log above only shows interactively.  ``moves``
        is ``[(promote, tier_from, tier_to, bytes), ...]`` collected
        INSIDE the catalog lock and emitted here after release:
        journaling is file I/O, and a spill storm must not serialize
        every concurrent allocation on the catalog lock behind disk
        writes."""
        from spark_rapids_tpu.obs import journal
        if not moves or not journal.enabled():
            return
        for promote, tier_from, tier_to, nbytes in moves:
            journal.emit(journal.EVENT_SPILL_PROMOTE if promote
                         else journal.EVENT_SPILL_DEMOTE,
                         tier_from=tier_from, tier_to=tier_to,
                         bytes=nbytes)

    def audit_leaks(self) -> int:
        """Unclosed handle count (called at session shutdown; the leak
        audit half of the reference's refcount warnings)."""
        with self._lock:
            return len(self._lru)

    # -- registry -----------------------------------------------------------

    def _register(self, sb: SpillableBatch) -> None:
        key = id(sb)
        with self._lock:
            self._lru[key] = weakref.ref(
                sb, lambda _r, k=key: self._on_dead(k))
            self._info[key] = {"tier": sb.tier, "size": sb.size,
                               "suppress": False, "disk_path": None}
            self.device_bytes += sb.size
            self._log("register", sb)
        # adding may exceed the budget: demote colder handles
        self.reserve(0)
        # per-QUERY budget (docs/serving.md): attribute the handle to
        # the active supervised query and enforce its device-byte
        # budget — only when one is set (the server's tenant confs);
        # with no budget this is one current() read, byte-identical
        from spark_rapids_tpu import lifecycle
        qc = lifecycle.current()
        if qc is not None and qc.max_device_bytes > 0:
            with self._lock:
                info = self._info.get(key)
                if info is not None:
                    info["query"] = qc.query_id
            self._enforce_query_budget(qc, sb)

    def _release_bytes(self, tier: str, size: int) -> None:
        if tier == TIER_DEVICE:
            self.device_bytes = max(0, self.device_bytes - size)
        elif tier == TIER_HOST:
            self.host_bytes = max(0, self.host_bytes - size)
        else:
            self.disk_bytes = max(0, self.disk_bytes - size)

    def _on_dead(self, key: int) -> None:
        """Weakref death callback: the handle was garbage-collected while
        still registered — the leak path (cuDF refcount-warning analog,
        SURVEY §5.2; suppressible like noWarnLeakExpected,
        GpuBroadcastHashJoinExec.scala:~125)."""
        with self._lock:
            if key not in self._lru:
                return
            del self._lru[key]
            info = self._info.pop(key)
            tier, size = info["tier"], info["size"]
            self._release_bytes(tier, size)
            self.leak_count += 1
            suppress = info["suppress"]
            path = info["disk_path"]
        if path and os.path.exists(path):
            os.unlink(path)
        if not suppress:
            warnings.warn(
                f"SpillableBatch leaked without close() (tier={tier}, "
                f"{size} bytes) — operators must close or materialize "
                "their handles", ResourceWarning, stacklevel=2)

    def _deregister(self, sb: SpillableBatch) -> None:
        with self._lock:
            if id(sb) in self._lru:
                del self._lru[id(sb)]
                self._info.pop(id(sb), None)
                self._release_bytes(sb.tier, sb.size)

    def _sync_info(self, sb: "SpillableBatch") -> None:
        info = self._info.get(id(sb))
        if info is not None:
            info["tier"] = sb.tier
            info["disk_path"] = sb._disk_path

    def _touch(self, sb: SpillableBatch) -> None:
        if id(sb) in self._lru:
            self._lru[id(sb)] = self._lru.pop(id(sb))  # move to MRU end

    # -- budget enforcement -------------------------------------------------

    def _demote_to_host(self, sb: "SpillableBatch", moves,
                        budget: bool = False) -> bool:
        """One device->host demotion with the shared accounting (caller
        holds the lock and has already filtered tier/pin): used by the
        pressure sweep, ``spill_all``, AND the per-query budget sweep,
        so their bookkeeping can never drift apart."""
        if not self._demote(sb, sb._to_host):
            return False
        self.device_bytes = max(0, self.device_bytes - sb.size)
        self.host_bytes += sb.size
        self.spill_to_host_count += 1
        if budget:
            self.budget_spill_count += 1
        self._log("budget-spill->host" if budget else "spill->host", sb)
        moves.append((False, TIER_DEVICE, TIER_HOST, sb.size))
        return True

    def spill_all(self) -> int:
        """Demote every unpinned device-tier handle to host (the OOM
        pressure-relief sweep, reference DeviceMemoryEventHandler).  Does
        not touch the configured budget; returns bytes demoted."""
        freed = 0
        moves = []
        with self._lock:
            for ref_ in list(self._lru.values()):
                sb = ref_()
                if sb is None or sb.tier != TIER_DEVICE or sb.pinned:
                    continue
                if self._demote_to_host(sb, moves):
                    freed += sb.size
        self._emit_tier_moves(moves)
        return freed

    def _demote(self, sb: "SpillableBatch", transition) -> bool:
        """Run one tier transition, treating failure (disk full, I/O
        error, injected ``spill.demote`` fault) as bounded: the handle
        stays intact on its current tier and the sweep moves on to the
        next candidate — a single bad handle must not abort the operator
        that merely needed room (reference DeviceMemoryEventHandler
        returning false rather than throwing)."""
        try:
            transition()
            return True
        except (IOError, OSError) as e:
            self.demote_failure_count += 1
            log.warning("spill demotion of %d bytes (tier %s) failed, "
                        "skipping handle: %s", sb.size, sb.tier, e)
            return False

    def query_device_bytes(self, query_id: int) -> int:
        """Device-resident bytes attributed to one query's registered
        handles (per-query budget accounting, docs/serving.md)."""
        with self._lock:
            return sum(info["size"] for info in self._info.values()
                       if info.get("query") == query_id
                       and info["tier"] == TIER_DEVICE)

    def _enforce_promote_budget(self, sb: "SpillableBatch") -> None:
        """Promote-path budget re-check (SpillableBatch.get): only
        handles the active query itself registered count toward its
        budget — a shared scan-cache entry another query created is
        never charged to the reader."""
        from spark_rapids_tpu import lifecycle
        qc = lifecycle.current()
        if qc is None or qc.max_device_bytes <= 0:
            return
        info = self._info.get(id(sb))
        if info is None or info.get("query") != qc.query_id:
            return
        self._enforce_query_budget(qc, sb, close_on_fail=False)

    def _enforce_query_budget(self, qc, new_sb: "SpillableBatch",
                              close_on_fail: bool = True) -> None:
        """Keep ONE query's device-resident bytes within its budget
        (``spark.rapids.server.query.maxDeviceBytes``): first demote
        the query's OWN unpinned device handles to host — never a
        neighbor's, that is the whole point — and if spilling cannot
        satisfy the budget, cancel the query through its token so it
        unwinds typed (QueryBudgetExceededError) everywhere instead of
        OOMing the chip its neighbors share."""
        budget = qc.max_device_bytes
        used = self.query_device_bytes(qc.query_id)
        if used <= budget:
            return
        moves = []
        with self._lock:
            # the query's own handles in reserve()'s demotion order —
            # priority class first, LRU within a class — with the
            # just-registered/promoted arrival last, so the working set
            # ahead of it spills before the data the operator is about
            # to touch
            own = []
            for pos, ref_ in enumerate(self._lru.values()):
                sb = ref_()
                if sb is None or sb.tier != TIER_DEVICE or sb.pinned:
                    continue
                if self._info.get(id(sb), {}).get("query") \
                        != qc.query_id:
                    continue
                own.append((sb is new_sb, sb.priority, pos, sb))
            own.sort(key=lambda t: t[:3])
            for _is_new, _prio, _pos, sb in own:
                if used <= budget:
                    break
                if self._demote_to_host(sb, moves, budget=True):
                    used -= sb.size
        self._emit_tier_moves(moves)
        if moves:
            # budget spills may push the host tier over ITS budget:
            # the normal host->disk overflow sweep handles it
            self.reserve(0)
        if used > budget:
            self.budget_exceeded_count += 1
            if close_on_fail:
                # the raising constructor cannot hand its caller a
                # handle to close: deregister the arrival HERE or it
                # would only be reclaimed by the GC death callback (a
                # counted leak).  The promote path keeps the handle —
                # its owner closes it on the error's way out.
                new_sb.close()
            qc.token.cancel(
                f"query device-resident bytes ({used}) exceed "
                f"spark.rapids.server.query.maxDeviceBytes ({budget}) "
                "and its working set cannot spill further",
                QueryBudgetExceededError)
            qc.check()

    def reserve(self, nbytes: int) -> None:
        """Make room for ``nbytes`` of new device data by demoting LRU
        device-tier handles to host (and host overflow to disk).  Never
        raises: if everything spillable is pinned, callers proceed and XLA
        may still satisfy the allocation (reference
        DeviceMemoryEventHandler returns false -> OOM only then)."""
        # fast path: under budget on both tiers — never build the order
        with self._lock:
            if (self.device_bytes + nbytes <= self.device_budget
                    and self.host_bytes <= self.host_budget):
                return

        def demotion_order():
            # priority class first (lower spills first), LRU within a
            # class — the SpillPriorities ordering over the store
            # (reference SpillPriorities.scala:26-50)
            live = []
            for pos, ref_ in enumerate(self._lru.values()):
                sb = ref_()
                if sb is not None:
                    live.append((sb.priority, pos, sb))
            live.sort(key=lambda t: (t[0], t[1]))
            return [sb for _, _, sb in live]

        moves = []
        with self._lock:
            for sb in demotion_order():
                if self.device_bytes + nbytes <= self.device_budget:
                    break
                if sb.tier != TIER_DEVICE or sb.pinned:
                    continue
                self._demote_to_host(sb, moves)
            # host overflow -> disk
            for sb in demotion_order():
                if self.host_bytes <= self.host_budget:
                    break
                if sb.tier != TIER_HOST or sb.pinned:
                    continue
                if not self._demote(sb, sb._to_disk):
                    continue
                self.host_bytes = max(0, self.host_bytes - sb.size)
                self.disk_bytes += sb.size
                self.spill_to_disk_count += 1
                self._log("spill->disk", sb)
                moves.append((False, TIER_HOST, TIER_DISK, sb.size))
        self._emit_tier_moves(moves)


# ---------------------------------------------------------------------------
# operator helpers
# ---------------------------------------------------------------------------

def collect_spillable(batches: Iterator[ColumnarBatch],
                      ctx) -> List[SpillableBatch]:
    """Drain a child's batch stream into spillable handles, so an operator
    accumulating its whole input (sort, agg merge, window) stays within
    the device budget while collecting.  On any error the handles already
    registered are closed — the catalog is process-wide, so leaking them
    would inflate its accounting for the session's lifetime."""
    cat = ctx.runtime.catalog
    out: List[SpillableBatch] = []
    try:
        for b in batches:
            out.append(SpillableBatch(b, cat))
    except BaseException:
        close_all(out)
        raise
    return out


def close_all(handles: List[SpillableBatch]) -> None:
    for sb in handles:
        try:
            sb.close()
        except (IOError, OSError) as e:
            # a handle whose disk file vanished still deregisters; the
            # failure is logged, never silently swallowed
            log.warning("closing spillable handle failed: %s", e)


def materialize_all(handles: List[SpillableBatch],
                    ctx) -> List[ColumnarBatch]:
    """Bring every handle back on device (pinned against eviction BEFORE
    reserving, so making room cannot demote the very handles being
    materialized) and release the handles."""
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
