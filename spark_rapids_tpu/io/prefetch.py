"""Background scan prefetch: overlap host decode with device compute.

Reference: the plugin keeps the GPU busy while the CPU decodes by
admitting multiple tasks per device (GpuSemaphore.scala:27-161 +
``spark.rapids.sql.concurrentGpuTasks``) and by multi-threaded readers
that decode the next buffers while the current table computes
(GpuParquetScan.scala multi-threaded reader).  Theseus (PAPERS.md) makes
the same point from the other direction: end-to-end distributed query
time is dominated by data movement, so host I/O must overlap device
compute or the accelerator idles through every decode.

TPU shape: the hot loop used to be strictly serial — decode a batch on
the host (pyarrow), upload, compute, repeat — so the chip idled through
every decode.  ``PrefetchIterator`` moves the decode onto ONE background
thread feeding a BOUNDED queue:

  * one decode thread per scan, not a pool: pyarrow's readers are
    internally parallel already, and a single producer preserves the
    exact batch order, so prefetch-on and prefetch-off runs are
    byte-identical and deterministically ordered (the pipeline
    correctness suite asserts this);
  * the queue depth is ``spark.rapids.sql.io.prefetch.batches`` — never
    unbounded (tests/lint_robustness.py enforces a maxsize on every
    queue constructed under io/);
  * every queued host batch is admitted through the catalog's
    dedicated prefetch ``HostStagingLimiter`` first (same cap as the
    spill-staging one, deliberately a separate instance — see
    BufferCatalog), so prefetch cannot blow the host staging budget no
    matter how fast the decode runs ahead;
  * a decode error in the background thread is captured and re-raised —
    the SAME exception object — at the consumer's next ``__next__``, so
    failures keep their type and never turn into hangs (fault site
    ``io.prefetch.decode`` proves this under injection);
  * ``close()`` (or generator teardown) stops the producer, drains the
    queue, releases any admitted staging bytes, and joins the thread —
    the source generator is closed ON the producer thread, so
    thread-local state in the source (the device semaphore's re-entrant
    depth) unwinds in the thread that owns it.

``device_lookahead`` reuses the same machinery one level up: the
coalesce exec drives its child (typically a scan) from a background
thread with a depth-1 queue, so coalesce goals pull the next uploaded
batch while the current concat computes instead of stalling on the
child's decode+upload latency.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterator, Optional

from spark_rapids_tpu import faults, lifecycle
from spark_rapids_tpu.utils import tracing

FAULT_SITE_DECODE = "io.prefetch.decode"

# process-global overlap counters, surfaced by bench.py's summary line so
# the prefetch trajectory is visible across BENCH rounds
_GLOBAL_LOCK = threading.Lock()
_GLOBAL = {"batches": 0, "stall_ms": 0, "fill_ms": 0, "overlap_ms": 0,
           "sem_wait_ms": 0}


def _bump_global(key: str, v: int) -> None:
    if v:
        with _GLOBAL_LOCK:
            _GLOBAL[key] += int(v)


def global_stats() -> dict:
    """Snapshot of process-wide prefetch/overlap counters (bench.py)."""
    with _GLOBAL_LOCK:
        return dict(_GLOBAL)


def reset_global_stats() -> None:
    with _GLOBAL_LOCK:
        for k in _GLOBAL:
            _GLOBAL[k] = 0


class _Sentinel:
    __slots__ = ()


_DONE = _Sentinel()


class _Failure:
    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class PrefetchIterator:
    """Bounded single-producer background iterator.

    Wraps ``source`` so its items are produced on a dedicated thread up
    to ``depth`` items ahead of the consumer.  Order-preserving and
    exception-transparent (see module docstring).  ``nbytes(item)``,
    when given together with ``limiter``, sizes each item's host staging
    admission.  Grant ownership TRANSFERS to the consumer: an item's
    bytes stay admitted from producer enqueue until the consumer pulls
    the NEXT item — by which point it has finished uploading this one —
    so the grant covers the upload itself and the upload path must NOT
    re-admit the same bytes (a second ``staging.limit`` on top of held
    queue grants can exceed the cap with neither side able to release:
    see pipelined_scan, which only wraps uploads in ``staging.limit``
    on the serial non-prefetch path).  At most ``depth + 2`` item grants
    are ever held: ``depth`` queued, one in the consumer's hand, and one
    acquired by a producer parked on the full queue.
    """

    _JOIN_TIMEOUT = 10.0
    _POLL_S = 0.05

    def __init__(self, source: Iterator, depth: int = 2,
                 name: str = "prefetch",
                 limiter=None,
                 nbytes: Optional[Callable] = None,
                 metrics=None,
                 fault_site: Optional[str] = None,
                 span: str = tracing.SPAN_PREFETCH_WAIT,
                 bump_global: bool = True):
        self.depth = max(1, int(depth))
        self._source = source
        self._limiter = limiter
        self._nbytes = nbytes
        self._metrics = metrics
        self._fault_site = fault_site
        self._span = span
        # whether this iterator's counts feed the process-wide decode
        # stats bench.py reports; the coalesce device lookahead re-pulls
        # batches the scan already counted, so it only records per-op
        self._bump_global = bump_global
        self._prev_granted = 0  # grant of the item the consumer holds
        # bounded by construction: an unbounded queue here would let a
        # fast decode thread buffer the whole table on host
        self._q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._done = False
        self.stall_ns = 0
        # the FIRST item's wait is pipe fill, not a stall: nothing ran
        # on device yet, so there was no compute to overlap with — a
        # single-batch suite used to report its whole decode as
        # "stall_ms" with overlap_ms 0 (the BENCH_r07 stall_ms 320
        # headline), which reads as an overlap failure that never
        # happened
        self.fill_ns = 0
        self._filled = False
        self.batches = 0
        self._thread = threading.Thread(
            target=self._run, name=f"srt-{name}", daemon=True)
        # supervised: the active query's registry (or the global
        # fallback) owns this producer — teardown/stop closes it
        # deterministically instead of relying on the daemon flag
        self._reg = lifecycle.register_resource(
            self.close, kind="prefetch", name=f"srt-{name}")
        if self._reg.rejected:
            # a stop/teardown permanently closed the registry while
            # this iterator was constructing (close() already ran on
            # arrival): never start the producer, and surface a TYPED
            # abort to the consumer — an empty-success stream here
            # would let a cancelled query return wrong (empty) results
            from spark_rapids_tpu.errors import QueryCancelledError
            self._done = False  # close-on-arrival marked us done
            self._q.put((0, _Failure(QueryCancelledError(
                "scan prefetch construction raced query teardown"))))
            return
        self._thread.start()

    # -- producer -----------------------------------------------------------

    def _run(self) -> None:
        granted = 0
        try:
            while not self._stop.is_set():
                item = next(self._source)
                if self._fault_site is not None:
                    faults.maybe_fail(
                        self._fault_site,
                        f"injected background decode failure at "
                        f"{self._fault_site}")
                granted = 0
                if self._limiter is not None and self._nbytes is not None:
                    granted = self._limiter.acquire(
                        self._nbytes(item), abort=self._stop.is_set)
                    if granted < 0:  # aborted while waiting for admission
                        granted = 0
                        break
                if not self._put((granted, item)):
                    # consumer went away while the queue was full:
                    # nothing took ownership of the admitted bytes
                    if granted and self._limiter is not None:
                        self._limiter.release(granted)
                    granted = 0
                    break
                granted = 0
        except StopIteration:
            pass
        except BaseException as e:  # forwarded, not swallowed
            if granted and self._limiter is not None:
                self._limiter.release(granted)
            self._put((0, _Failure(e)))
        finally:
            # close the source on THIS thread: generators holding the
            # re-entrant device semaphore across a yield must unwind in
            # the thread whose thread-local depth tracks the permit
            close = getattr(self._source, "close", None)
            if close is not None:
                try:
                    close()
                except BaseException as e:
                    self._put((0, _Failure(e)))
            self._put((0, _DONE))

    def _put(self, wrapped) -> bool:
        """Bounded put that gives up when the consumer closed."""
        while True:
            if self._stop.is_set() and not isinstance(
                    wrapped[1], (_Sentinel, _Failure)):
                return False
            try:
                self._q.put(wrapped, timeout=self._POLL_S)
                return True
            except queue.Full:
                if self._stop.is_set():
                    return False

    # -- consumer -----------------------------------------------------------

    def __iter__(self) -> "PrefetchIterator":
        return self

    def _release_prev(self) -> None:
        if self._prev_granted and self._limiter is not None:
            self._limiter.release(self._prev_granted)
        self._prev_granted = 0

    def __next__(self):
        if self._done:
            raise StopIteration
        # release the PREVIOUS item's grant BEFORE blocking on the queue:
        # the consumer finished uploading it (that is why it is back for
        # more), and a producer parked on admission may need exactly
        # these bytes to make the next item this get() is waiting for
        self._release_prev()
        t0 = time.perf_counter_ns()
        with tracing.section(self._span, own="io_wait_us"):
            # bounded get polling the query's cancel token: a cancelled
            # or past-deadline query raises typed out of the wait
            # instead of parking on a queue a torn-down producer will
            # never fill (lint_robustness: every blocking queue get
            # under the package must carry a timeout)
            while True:
                try:
                    granted, item = self._q.get(
                        timeout=lifecycle.poll_interval_s())
                    break
                except queue.Empty:
                    lifecycle.check_cancel()
        waited = time.perf_counter_ns() - t0
        if self._filled:
            self.stall_ns += waited
        else:
            self.fill_ns += waited
            self._filled = True
        if isinstance(item, _Sentinel):
            self._done = True
            self._flush_metrics()
            raise StopIteration
        if isinstance(item, _Failure):
            self._done = True
            self._stop.set()
            self._flush_metrics()
            raise item.exc
        self._prev_granted = granted
        self.batches += 1
        return item

    def _flush_metrics(self) -> None:
        stall_ms = self.stall_ns // 1_000_000
        fill_ms = self.fill_ns // 1_000_000
        if self._metrics is not None:
            self._metrics["prefetchBatches"].add(self.batches)
            self._metrics["prefetchStallMs"].add(stall_ms)
            self._metrics["prefetchFillMs"].add(fill_ms)
        if self._bump_global:
            _bump_global("batches", self.batches)
            _bump_global("stall_ms", stall_ms)
            _bump_global("fill_ms", fill_ms)
        self.stall_ns = 0
        self.fill_ns = 0
        self.batches = 0

    def _drain(self) -> None:
        while True:
            try:
                granted, _item = self._q.get_nowait()
            except queue.Empty:
                return
            if granted and self._limiter is not None:
                self._limiter.release(granted)

    def close(self) -> None:
        """Stop the producer, drain admitted items, join the thread.
        Robust to running DURING ``__init__`` (a permanently-closed
        registry invokes the closer on arrival, before ``_reg`` is
        assigned and before the thread starts)."""
        reg = getattr(self, "_reg", None)
        if reg is not None:
            reg.release()  # idempotent; closed resources deregister
        self._stop.set()
        self._release_prev()
        # drain so a producer parked on a full queue can observe the stop
        # and so admitted staging bytes are returned
        self._drain()
        if self._thread.ident is not None:  # never-started: nothing to join
            self._thread.join(timeout=self._JOIN_TIMEOUT)
        # a put can land between the first drain and the producer
        # observing the stop flag; with the thread now joined this
        # second sweep returns any such straggler's admitted bytes
        self._drain()
        if not self._done:
            self._done = True
            self._flush_metrics()

    def __enter__(self) -> "PrefetchIterator":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


def maybe_prefetch(source: Iterator, ctx, metrics=None,
                   nbytes: Optional[Callable] = None,
                   name: str = "scan-decode"):
    """Wrap a host-decode iterator in a PrefetchIterator when
    ``spark.rapids.sql.io.prefetch.enabled`` is on; pass-through (the
    exact pre-prefetch serial behavior) when off."""
    if not ctx.conf.io_prefetch_enabled:
        return source
    # the catalog's DEDICATED prefetch limiter, not the spill-staging
    # one: queue grants outlive the admission call (held until the
    # consumer's next pull), and a consumer wedged in an abort-less
    # spill staging wait must never depend on grants that only its own
    # next pull can release (memory/spill.py:BufferCatalog)
    return PrefetchIterator(
        source, depth=ctx.conf.io_prefetch_batches, name=name,
        limiter=ctx.runtime.catalog.prefetch_staging, nbytes=nbytes,
        metrics=metrics, fault_site=FAULT_SITE_DECODE)


def device_lookahead(source: Iterator, ctx, metrics=None,
                     name: str = "coalesce-pull"):
    """Depth-1 background pull of an upstream DEVICE-batch iterator:
    the consumer (coalesce) works on batch k while the producer thread
    advances the child to batch k+1 (its decode + upload).  The child
    generator is driven entirely by the producer thread, so the scans'
    semaphore-held-across-yield admission stays thread-consistent.
    Disabled together with prefetch so the conf-off path is serial."""
    if not ctx.conf.io_prefetch_enabled:
        return source
    return PrefetchIterator(source, depth=1, name=name, metrics=metrics,
                            span=tracing.SPAN_COALESCE_PULL,
                            bump_global=False)
