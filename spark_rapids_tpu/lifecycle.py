"""Query lifecycle supervision: deadlines, cooperative cancellation,
resource registry, and the hang watchdog.

Reference: the plugin rides Spark's task-lifecycle hooks —
``TaskContext.addTaskCompletionListener`` closes every GPU resource a
task opened, and task kill/interruption propagates to
``GpuSemaphore``/shuffle waits — so one query's failure can never
strand another query's memory or threads.  This engine has no Spark
scheduler above it, so this module supplies the missing fault domain:

* ``QueryContext`` — created per execution entry point (``session.sql``
  action, write, device handoff) carrying a deadline
  (``spark.rapids.sql.queryTimeoutMs``, 0 = off), a cooperative
  ``CancelToken``, and an ordered **resource registry** every pipeline
  the query spawns registers with: scan-prefetch producer threads
  (io/prefetch.py), compile-warmer threads (exec/stage.py), host
  shuffle worker process groups (shuffle/stage.py), transport serve
  threads, and anything else holding a thread, a staging permit, or
  HBM on the query's behalf.

* **Cooperative cancellation** — ``check_cancel()`` runs at every
  operator pull boundary (``exec/base.py:_count_output``) and inside
  every bounded blocking wait (semaphore admission, staging-limiter
  admission, prefetch queue gets — the PR 2 ``acquire``/``release``
  split with abortable waits is exactly this seam), so a cancel or an
  expired deadline surfaces as a typed ``QueryCancelledError`` /
  ``QueryTimeoutError`` within one poll interval, never a hang.

* **Teardown** — on scope exit (success OR failure) registered
  resources close in registration order; closer errors are logged and
  never mask the query's own outcome.  ``shutdown_all()`` routes
  ``session.stop()`` / ``TpuRuntime.reset()`` through the same
  registry, so stop is deterministic instead of relying on GC and
  daemon flags.

* **Hang watchdog** — ``supervise(fn, site)`` bounds a blocking call
  that cooperative checks cannot reach (an XLA ``device_get``, a mesh
  collective sync) when ``spark.rapids.sql.watchdog.hangTimeoutMs`` >
  0: the call runs on a supervised thread and a trip raises a typed
  ``QueryHangError`` (at ``_guarded_collective`` the gate catches it
  and degrades the fragment to the host path).  The ``io.pipeline.hang``
  and ``shuffle.ici.hang`` fault sites simulate the wedge so the
  watchdog is testable without real link failures.

Everything is conf-gated off by default: with ``queryTimeoutMs=0``, no
cancel ever fires and no watchdog thread exists, so execution is
byte-identical to the unsupervised engine (asserted in
tests/test_lifecycle.py).
"""

from __future__ import annotations

import contextlib
import itertools
import logging
import threading
import time
from typing import Callable, Dict, Optional

from spark_rapids_tpu import faults
from spark_rapids_tpu.utils import tracing
from spark_rapids_tpu.errors import (
    EngineError, QueryCancelledError, QueryHangError, QueryTimeoutError,
)

__all__ = [
    "EngineError", "QueryCancelledError", "QueryTimeoutError",
    "QueryHangError", "CancelToken", "QueryContext", "current",
    "query_scope", "check_cancel", "cancel_requested", "poll_interval_s",
    "register_resource", "register_thread", "supervise", "shutdown_all",
    "cancel_thread_queries", "global_stats", "reset_global_stats",
    "WAIT_POLL_S",
]

log = logging.getLogger("spark_rapids_tpu.lifecycle")

# poll interval for bounded blocking waits (semaphore admission, queue
# gets, watchdog join slices): how long a cancel can go unobserved
WAIT_POLL_S = 0.05

FAULT_SITE_PIPELINE_HANG = "io.pipeline.hang"
FAULT_SITE_ICI_HANG = "shuffle.ici.hang"

# an injected hang with no watchdog AND no deadline must still end
# eventually (mirrors worker.hang's bounded 3600s park)
_PARK_CAP_S = 3600.0

# process-wide supervision counters, surfaced by bench.py's summary
# `lifecycle` object so BENCH rounds record that happy-path supervision
# overhead is ~zero
_STATS_LOCK = threading.Lock()
_STATS = {"queries": 0, "timeouts": 0, "cancels": 0,
          "watchdog_trips": 0, "teardown_ms": 0}


def _bump_global(key: str, v: int) -> None:
    if v:
        with _STATS_LOCK:
            _STATS[key] += int(v)


def global_stats() -> dict:
    with _STATS_LOCK:
        return dict(_STATS)


def reset_global_stats() -> None:
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0


class CancelToken:
    """Cooperative cancel flag + optional deadline.

    ``check()`` is the single choke point: raises the token's typed
    error once cancelled, and converts a passed deadline into a
    ``QueryTimeoutError`` exactly once (subsequent checks re-raise the
    same classification)."""

    def __init__(self, timeout_s: float = 0.0):
        self._event = threading.Event()
        self._lock = threading.Lock()
        self._reason = ""
        self._exc_type = QueryCancelledError
        self.timeout_s = max(0.0, float(timeout_s))
        self.deadline = (time.monotonic() + self.timeout_s
                         if self.timeout_s > 0 else None)

    def cancel(self, reason: str = "query cancelled",
               exc_type=QueryCancelledError) -> None:
        with self._lock:
            if not self._event.is_set():
                self._reason = reason
                self._exc_type = exc_type
            self._event.set()

    @property
    def cancelled(self) -> bool:
        return self._event.is_set()

    @property
    def timed_out(self) -> bool:
        return self._event.is_set() and issubclass(
            self._exc_type, QueryTimeoutError)

    def remaining_s(self) -> Optional[float]:
        if self.deadline is None:
            return None
        return self.deadline - time.monotonic()

    def expired(self) -> bool:
        rem = self.remaining_s()
        return rem is not None and rem <= 0

    def check(self) -> None:
        if not self._event.is_set() and self.expired():
            self.cancel(
                f"query exceeded spark.rapids.sql.queryTimeoutMs "
                f"({int(self.timeout_s * 1000)} ms)", QueryTimeoutError)
        if self._event.is_set():
            with self._lock:
                raise self._exc_type(self._reason)


class _Registration:
    """Handle for one registered resource; ``release()`` deregisters
    without closing (the resource closed itself on its normal path).
    ``rejected`` is True when the registry was already permanently
    closed: the closer ran on arrival, and a registrant still mid-
    construction must NOT bring the resource up (start its thread)
    afterwards."""

    __slots__ = ("_owner", "_key", "rejected")

    def __init__(self, owner, key: int, rejected: bool = False):
        self._owner = owner
        self._key = key
        self.rejected = rejected

    def release(self) -> None:
        owner, self._owner = self._owner, None
        if owner is not None:
            owner._remove(self._key)


class _Registry:
    """Ordered close-callable registry shared by QueryContext (scoped)
    and the module-global fallback (resources created outside any
    query scope — direct exec construction in tests, long-lived
    transport servers)."""

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._next = 0
        self._closed = False
        # insertion-ordered: teardown closes in registration order
        self._entries: "Dict[int, tuple]" = {}

    def add(self, close: Callable[[], None], kind: str, name: str,
            nbytes: Optional[Callable[[], int]] = None) -> _Registration:
        with self._lock:
            if not self._closed:
                key = self._next
                self._next += 1
                self._entries[key] = (kind, name, close, nbytes)
                return _Registration(self, key)
        # a permanently-closed registry (a stop/teardown raced this
        # registration in on another thread): close the resource NOW —
        # accepting it silently would leak it, nothing runs close_all
        # again.  Registrants mid-construction must check ``rejected``
        # and not bring the resource up afterwards.
        try:
            close()
        except Exception as e:
            log.warning("late registration of %s %r closed on arrival "
                        "(%s) and its closer failed: %s",
                        kind, name, self.name, e)
        return _Registration(None, -1, rejected=True)

    def _remove(self, key: int) -> None:
        with self._lock:
            self._entries.pop(key, None)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def close_all(self, permanent: bool = False) -> int:
        """Close every live entry in registration order; errors are
        logged, never raised (teardown must not mask the query's own
        outcome).  ``permanent`` marks the registry closed for good
        (a finished QueryContext): later registrations close on
        arrival instead of landing in a registry nothing will sweep
        again.  The module-global registry stays reusable — the next
        session's resources register into it after a stop.  Returns
        the number of entries closed."""
        with self._lock:
            entries = list(self._entries.items())
            self._entries.clear()
            if permanent:
                self._closed = True
        for _key, (kind, name, close, _nbytes) in entries:
            try:
                close()
            except Exception as e:
                log.warning("lifecycle teardown of %s %r (%s) failed: %s",
                            kind, name, self.name, e)
        return len(entries)

    def live_bytes(self) -> int:
        """Bytes currently held by registered resources that report a
        size (broadcast builds) — supervised memory, reclaimable
        deterministically, as opposed to leaked memory nothing will
        ever close (the distinction the test leak audit draws)."""
        with self._lock:
            entries = list(self._entries.values())
        total = 0
        for _kind, _name, _close, nbytes in entries:
            if nbytes is None:
                continue
            try:
                total += int(nbytes())
            except Exception:
                continue  # a racing close is not an accounting error
        return total


_QUERY_IDS = itertools.count(1)


class QueryContext:
    """Per-query fault domain: deadline + cancel token + resource
    registry.  Use through ``query_scope`` (the execution entry points
    do); direct construction is for tests."""

    def __init__(self, timeout_ms: int = 0, hang_timeout_ms: int = 0,
                 check_interval_ms: int = 50, max_device_bytes: int = 0):
        self.query_id = next(_QUERY_IDS)
        self.token = CancelToken(timeout_ms / 1000.0)
        self.hang_timeout_s = max(0.0, hang_timeout_ms / 1000.0)
        self.check_interval_s = max(0.005, check_interval_ms / 1000.0)
        # per-query device-resident byte budget, enforced by the spill
        # catalog at handle registration (memory/spill.py;
        # spark.rapids.server.query.maxDeviceBytes — the session
        # server's tenant confs set it).  0 = no budget: the catalog
        # never attributes or checks, byte-identical to today
        self.max_device_bytes = max(0, int(max_device_bytes))
        self._registry = _Registry("query")
        self.sem_wait_ms = 0
        self.teardown_ms = 0.0
        self.started = time.monotonic()
        self.wall_ms = 0.0
        # the in-flight error ``query_scope`` noted (journal fodder:
        # the query_error event carries type + typedness)
        self.error: Optional[BaseException] = None
        # the dispatch ledger's rows when this query began, and the
        # programs it launched since (compile/service.py); both stay
        # None / empty unless spark.rapids.sql.trace.enabled is on
        self.programs_mark: Optional[dict] = None
        self.programs: list = []
        self._finished = False
        self._finish_lock = threading.Lock()

    @classmethod
    def from_conf(cls, conf) -> "QueryContext":
        from spark_rapids_tpu.conf import (
            CANCEL_CHECK_INTERVAL_MS, QUERY_TIMEOUT_MS,
            SERVER_QUERY_MAX_DEVICE_BYTES, WATCHDOG_HANG_TIMEOUT_MS,
        )
        return cls(timeout_ms=conf.get(QUERY_TIMEOUT_MS),
                   hang_timeout_ms=conf.get(WATCHDOG_HANG_TIMEOUT_MS),
                   check_interval_ms=conf.get(CANCEL_CHECK_INTERVAL_MS),
                   max_device_bytes=conf.get(
                       SERVER_QUERY_MAX_DEVICE_BYTES))

    # -- registry -----------------------------------------------------------

    def register(self, close: Callable[[], None], kind: str = "resource",
                 name: str = "",
                 nbytes: Optional[Callable[[], int]] = None
                 ) -> _Registration:
        return self._registry.add(close, kind, name, nbytes)

    @property
    def live_resources(self) -> int:
        return len(self._registry)

    # -- cancellation -------------------------------------------------------

    def cancel(self, reason: str = "query cancelled") -> None:
        self.token.cancel(reason)

    def check(self) -> None:
        self.token.check()

    # -- teardown -----------------------------------------------------------

    def finish(self) -> None:
        """Tear down registered resources (registration order), flush
        per-query telemetry, record supervision stats.  Idempotent —
        atomically, so shutdown_all racing the owner thread's scope
        exit cannot double-run teardown or double-count stats."""
        with self._finish_lock:
            if self._finished:
                return
            self._finished = True
        self.wall_ms = (time.monotonic() - self.started) * 1e3
        t0 = time.perf_counter()
        self._registry.close_all(permanent=True)
        # flush admission-wait telemetry into the process-wide stats at
        # QUERY end (not only at runtime shutdown) so bench sees waits
        # without a session stop; this query's OWN waits were already
        # attributed at the acquire sites (note_sem_wait), so a
        # concurrent query finishing first cannot steal them
        try:
            from spark_rapids_tpu.runtime import TpuRuntime
            inst = TpuRuntime._instance
            if inst is not None:
                inst.flush_semaphore_waits()
        except Exception as e:
            log.debug("semaphore telemetry flush failed: %s", e)
        self.teardown_ms = (time.perf_counter() - t0) * 1e3
        _bump_global("queries", 1)
        _bump_global("teardown_ms", int(self.teardown_ms))
        if self.token.timed_out:
            _bump_global("timeouts", 1)
        elif self.token.cancelled:
            _bump_global("cancels", 1)
        self._observe_finish()

    def _observe_finish(self) -> None:
        """Record the query's wall time (obs histogram + profile note)
        and emit the typed finish events; observation never raises into
        teardown."""
        try:
            from spark_rapids_tpu.obs import journal, registry
            registry.record(registry.HIST_QUERY_WALL_US,
                            int(self.wall_ms * 1000))
            if not journal.enabled():
                return
            if self.token.timed_out:
                status = "timeout"
                journal.emit(journal.EVENT_QUERY_TIMEOUT,
                             query=self.query_id,
                             reason=self.token._reason)
            elif self.token.cancelled:
                status = "cancelled"
                journal.emit(journal.EVENT_QUERY_CANCEL,
                             query=self.query_id,
                             reason=self.token._reason)
            else:
                status = "error" if self.error is not None else "ok"
            if self.error is not None:
                journal.emit(journal.EVENT_QUERY_ERROR,
                             query=self.query_id,
                             error=type(self.error).__name__,
                             message=str(self.error),
                             typed=isinstance(self.error, EngineError))
            journal.emit(journal.EVENT_QUERY_FINISH,
                         query=self.query_id, status=status,
                         wall_ms=round(self.wall_ms, 3),
                         teardown_ms=round(self.teardown_ms, 3))
        except Exception as e:
            log.warning("query finish observation failed: %s", e)


# ---------------------------------------------------------------------------
# per-thread current-query plumbing
# ---------------------------------------------------------------------------
#
# The active context is tracked PER THREAD: two user threads running
# concurrent queries get independent fault domains (one query's cancel
# or teardown can never truncate or fail the other — the per-task
# mapping ROADMAP item 4's serving front end needs).  Engine-spawned
# worker threads that service a query (prefetch producers, watchdog
# runners) do NOT bind a context of their own: their blocking waits
# carry explicit abort predicates / stop events wired at spawn, and
# the resources they hold are reclaimed through the owning query's
# registry, so teardown reaches them without per-thread adoption.

_CONTEXTS_LOCK = threading.Lock()
_CONTEXTS: "Dict[int, QueryContext]" = {}  # thread ident -> active qc

# fallback registry for supervised resources created OUTSIDE any query
# scope; session.stop()/runtime reset close these through shutdown_all
_GLOBAL_REGISTRY = _Registry("global")


def current() -> Optional[QueryContext]:
    return _CONTEXTS.get(threading.get_ident())


def _set_current(qc: Optional[QueryContext]) -> Optional[QueryContext]:
    ident = threading.get_ident()
    with _CONTEXTS_LOCK:
        prev = _CONTEXTS.get(ident)
        if qc is None:
            _CONTEXTS.pop(ident, None)
        else:
            _CONTEXTS[ident] = qc
        return prev


def check_cancel() -> None:
    """The operator pull-boundary checkpoint (exec/base.py): raises the
    active query's typed error when cancelled or past deadline; no-op
    (one global read) when no query is supervised."""
    qc = current()
    if qc is not None:
        qc.check()


def poll_interval_s() -> float:
    """The active query's configured blocking-wait poll interval
    (``spark.rapids.sql.cancel.checkIntervalMs``), or the module
    default when no query is supervised.  Every bounded wait that
    polls the cancel token sizes its slices with this."""
    qc = current()
    return qc.check_interval_s if qc is not None else WAIT_POLL_S


def note_sem_wait(wait_ns: int) -> None:
    """Attribute an observed admission wait to the ACTIVE query (called
    by ``TpuSemaphore.acquire`` from the waiting thread itself, so
    under concurrent queries each context counts only its own waits —
    process-wide telemetry stays on the semaphore's accumulator)."""
    qc = current()
    if qc is not None:
        qc.sem_wait_ms += wait_ns // 1_000_000


def cancel_requested() -> bool:
    """Cheap predicate for abortable waits (HostStagingLimiter.acquire's
    ``abort=``): True once the active query is cancelled or expired."""
    qc = current()
    if qc is None:
        return False
    return qc.token.cancelled or qc.token.expired()


def raise_if_cancelled() -> None:
    """Raise the active token's typed error; used by waits that
    observed ``cancel_requested()`` and must surface it typed."""
    qc = current()
    if qc is not None:
        qc.check()
    raise QueryCancelledError("wait aborted by query cancellation")


@contextlib.contextmanager
def query_scope(conf=None, timeout_ms: Optional[int] = None):
    """Enter a query's supervision scope.  Nested scopes (a write
    action executing a sub-plan, a worker fragment) REUSE the enclosing
    scope — one query, one fault domain."""
    existing = current()
    if existing is not None:
        yield existing
        return
    # the scope's own work, entry and exit, is the action's bookkeeping
    # (``scope_us``), the trace's part of it ``trace_us``; neither
    # section is held across the yield
    with tracing.section(tracing.SPAN_QUERY_OPEN, own="scope_us"):
        qc = _open_scope(conf, timeout_ms)
        prev = _set_current(qc)
    try:
        yield qc
    except BaseException as e:
        qc.error = e
        raise
    finally:
        with tracing.section(tracing.SPAN_QUERY_CLOSE, own="scope_us"):
            _set_current(prev)
            if qc.programs_mark is not None:
                # a traced query: wait (off the device's path, on this
                # thread only) until the watcher has credited every
                # program it launched, so its profile and the `programs`
                # counters are complete when the scope closes
                from spark_rapids_tpu.compile import service as _service
                with tracing.section(tracing.SPAN_TRACE_DRAIN,
                                     own="trace_us"):
                    _service.drain_watcher()
                    qc.programs = _service.ledger_since(qc.programs_mark)
            qc.finish()


def _open_scope(conf, timeout_ms: Optional[int]) -> "QueryContext":
    """The outermost scope's entry: a QueryContext from ``conf`` and the
    process-global switches its keys configure."""
    if conf is not None:
        qc = QueryContext.from_conf(conf)
        # conf-driven fault injection reaches EVERY site from here, not
        # just paths that happen to build a shuffle manager: a conf
        # carrying spark.rapids.faults.* keys installs the injector at
        # query start (idempotent — same spec keeps counters).  A conf
        # with NO fault keys leaves the injector alone, so tests that
        # configure it directly keep their installation.
        settings = conf.to_dict()
        if any(k.startswith(faults.FAULTS_PREFIX) for k in settings):
            faults.configure_from_conf(settings)
        # chip-health scoring parameters configure the process-global
        # tracker the same way (docs/fault_tolerance.md, "Chip failure
        # domain"): only when the conf explicitly carries a health key,
        # and state (scores, quarantine timers) is always kept — a new
        # session must not grant a dead chip amnesty
        if any(k.startswith("spark.rapids.health.") for k in settings):
            from spark_rapids_tpu import health
            health.configure_from_conf(conf)
        # observability from the same conf (docs/observability.md):
        # the histogram switch and the JSONL journal configure at the
        # outermost scope of every query, worker fragments included
        # (their shipped conf carries the same keys) — but each setting
        # ONLY when ITS key is explicitly present: both are process-
        # global, and a session that does not mention the journal (or
        # the switch) must not close another session's open journal or
        # flip its recording state by re-applying defaults (the
        # per-key analog of the faults guard above)
        from spark_rapids_tpu.conf import (
            OBS_ENABLED, OBS_JOURNAL_DIR, OBS_JOURNAL_MAX_EVENTS,
        )
        if OBS_ENABLED.key in settings:
            from spark_rapids_tpu.obs import registry
            registry.set_enabled(conf.get(OBS_ENABLED))
        if OBS_JOURNAL_DIR.key in settings:
            from spark_rapids_tpu.obs import journal
            journal.configure_from_conf(conf)
        elif OBS_JOURNAL_MAX_EVENTS.key in settings:
            # cap-only conf: adjust the bound without closing/reopening
            # a journal some other session configured
            from spark_rapids_tpu.obs import journal
            journal.set_max_events(conf.get(OBS_JOURNAL_MAX_EVENTS))
        # persistent compilation service (docs/compile_cache.md): the
        # capacity ladder, the kernel store, and the warm pool are
        # process-global like the injector above — configured at the
        # outermost scope of every query whose conf explicitly carries
        # a compile key (the runtime singleton survives session.stop,
        # so runtime init alone would miss sessions reusing it); the
        # shared hook applies the same per-key guard, so a conf with
        # no compile keys leaves another session's store alone
        from spark_rapids_tpu import compile as _compile
        _compile.configure_from_conf(conf)
        if conf.trace_enabled:
            with tracing.section(tracing.SPAN_TRACE_DRAIN, own="trace_us"):
                qc.programs_mark = _compile.service.ledger_mark()
    else:
        qc = QueryContext(timeout_ms=timeout_ms or 0)
    from spark_rapids_tpu.obs import journal as _journal
    if _journal.enabled():
        _journal.emit(_journal.EVENT_QUERY_START, query=qc.query_id,
                      timeout_ms=int(qc.token.timeout_s * 1000),
                      hang_timeout_ms=int(qc.hang_timeout_s * 1000))
    return qc


def register_resource(close: Callable[[], None], kind: str = "resource",
                      name: str = "",
                      nbytes: Optional[Callable[[], int]] = None,
                      process_wide: bool = False) -> _Registration:
    """Register a close callable with the active query's registry (or
    the module-global fallback when no query is supervised, or when the
    resource serves every query: ``process_wide``).  Returns a
    handle whose ``release()`` deregisters after the resource closed
    itself on its normal path.  ``nbytes``, when given, reports the
    bytes the resource currently holds (``supervised_bytes``)."""
    qc = None if process_wide else current()
    if qc is not None:
        return qc.register(close, kind, name, nbytes)
    return _GLOBAL_REGISTRY.add(close, kind, name, nbytes)


def supervised_bytes() -> int:
    """Bytes held by lifecycle-registered resources (global registry +
    active query).  Supervised memory is reclaimable deterministically
    at teardown/stop — the leak audit distinguishes it from memory
    nothing will ever close."""
    total = _GLOBAL_REGISTRY.live_bytes()
    qc = current()
    if qc is not None:
        total += qc._registry.live_bytes()
    return total


def register_thread(thread: threading.Thread,
                    stop: Optional[Callable[[], None]] = None,
                    join_timeout: float = 10.0,
                    process_wide: bool = False) -> _Registration:
    """Register a (daemon) engine thread: teardown calls ``stop`` (if
    any) and joins with a bounded timeout.  Every ``threading.Thread``
    constructed under spark_rapids_tpu/ must pass through here or a
    QueryContext registration (tests/lint_robustness.py).
    ``process_wide`` registers a thread that outlives the query that
    happened to start it (the dispatch watcher) with ``shutdown_all``'s
    registry, not the query's."""
    def close():
        if stop is not None:
            stop()
        if thread.is_alive():
            thread.join(timeout=join_timeout)
            if thread.is_alive():
                log.warning("lifecycle teardown: thread %r still alive "
                            "after %.1fs join", thread.name, join_timeout)
    return register_resource(close, kind="thread", name=thread.name,
                             process_wide=process_wide)


def cancel_thread_queries(idents, reason: str) -> int:
    """Cancel the active QueryContext of each listed thread ident (the
    session server's close() cancels ITS worker threads' in-flight
    queries this way — a deadline-less query must not stall close by
    the full worker-join timeout, and queries on OTHER sessions'
    threads must not be touched).  Each context unwinds typed at its
    next cooperative checkpoint; its owning scope runs teardown.
    Returns the number of contexts cancelled."""
    idents = set(idents)
    with _CONTEXTS_LOCK:
        contexts = [qc for ident, qc in _CONTEXTS.items()
                    if ident in idents]
    for qc in contexts:
        qc.cancel(reason)
    return len(contexts)


def shutdown_all() -> int:
    """Deterministic stop: cancel and tear down EVERY live query
    context — not just the calling thread's; a stop issued from thread
    A must reclaim a query running on thread B — then close every
    resource registered outside a scope.  Routed from
    ``session.stop()`` / ``TpuRuntime.reset()`` so teardown never
    relies on GC or daemon flags.  Returns resources closed."""
    with _CONTEXTS_LOCK:
        contexts = list(_CONTEXTS.values())
    # cancel FIRST, and leave each map entry for its owning thread's
    # scope exit to pop: a query mid-drain on another thread must keep
    # seeing its own token (check_cancel reads current()), so it
    # unwinds typed instead of racing its torn-down resources blind
    for qc in contexts:
        qc.cancel("session stopped")
    n = 0
    for qc in contexts:
        qc.finish()
        n += 1
    n += _GLOBAL_REGISTRY.close_all()
    return n


# engine-spawned worker processes (shuffle/stage.py, shuffle/worker.py
# register each spawn): the exit reap below touches ONLY these — an
# embedding application's own multiprocessing children are never ours
# to terminate
import weakref as _weakref  # noqa: E402

_TRACKED_PROCS: "_weakref.WeakSet" = _weakref.WeakSet()


def track_process(proc) -> None:
    """Record an engine-spawned worker process so the interpreter-exit
    safety net can reap it if it outlives its owning stage (weakly
    held: normally the stage joins and drops it long before exit)."""
    _TRACKED_PROCS.add(proc)


def _join_watchdogs_at_exit(max_wait_s: float = 15.0) -> None:
    """Interpreter-exit safety net: a watchdog thread abandoned by a
    trip may still be inside an XLA call (the wedge it was bounding, or
    a slow compile the bound misjudged); letting CPython finalize while
    that C++ code runs segfaults.  Bounded wait for them to drain —
    registered via atexit on first use.  Also reaps any still-alive
    ENGINE-spawned worker processes (track_process; never the host
    application's own children): multiprocessing's own exit handler
    (registered at import, so it runs AFTER this one) joins live
    children WITHOUT a timeout, converting one wedged worker into an
    interpreter that never exits."""
    shutdown_all()
    try:
        for p in list(_TRACKED_PROCS):
            if not p.is_alive():
                continue
            p.terminate()
            p.join(timeout=5)
            if p.is_alive():
                p.kill()
                p.join(timeout=5)
    except Exception as e:
        log.warning("exit reap of worker processes failed: %s", e)
    deadline = time.monotonic() + max_wait_s
    for t in threading.enumerate():
        if not t.name.startswith("srt-watchdog"):
            continue
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            break
        t.join(timeout=remaining)


# ---------------------------------------------------------------------------
# hang watchdog
# ---------------------------------------------------------------------------

def _park(gave_up: threading.Event, qc: Optional[QueryContext]) -> None:
    """The simulated wedge an ``*.hang`` fault site injects: sleep in
    poll slices until the watchdog gives up on us, the query is
    cancelled/expired, or the bounded cap elapses (mirroring
    worker.hang's 3600s park)."""
    deadline = time.monotonic() + _PARK_CAP_S
    while time.monotonic() < deadline:
        if gave_up.is_set():
            return
        if qc is not None:
            qc.check()  # deadline/cancel interrupts the park, typed
        time.sleep(qc.check_interval_s if qc is not None else WAIT_POLL_S)


def supervise(fn: Callable, site: str):
    """Bound a blocking call with the hang watchdog.

    With no active query and no fault injection this is a plain call —
    the zero-overhead off path.  With a fired ``site`` trigger the call
    wedges (simulated).  With ``hangTimeoutMs`` > 0 the call runs on a
    supervised daemon thread; exceeding the bound counts a
    ``watchdog_trips`` and raises ``QueryHangError`` — at
    ``_guarded_collective`` that degrades the fragment to the host
    path, elsewhere it surfaces typed."""
    qc = current()
    inj = faults.injector()
    fires = inj.enabled and inj.should_fire(site)
    timeout_s = qc.hang_timeout_s if qc is not None else 0.0
    if not fires and timeout_s <= 0:
        # the hot-path exit: no injected wedge, no watchdog — a plain
        # call with zero allocation (every supervised query's
        # device_pull lands here with the watchdog off)
        return fn()
    gave_up = threading.Event()

    def work():
        if fires:
            _park(gave_up, qc)
            if gave_up.is_set():
                # the watchdog (or teardown) gave up on this call while
                # it was wedged: skip the real work, the result is dead
                return None
        return fn()

    if timeout_s <= 0:
        return work()
    box: dict = {}
    done = threading.Event()

    def runner():
        try:
            box["value"] = work()
        except BaseException as e:
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=runner, name=f"srt-watchdog-{site}",
                         daemon=True)
    reg = register_thread(t, stop=gave_up.set, join_timeout=1.0)
    if reg.rejected:
        # teardown permanently closed the registry between the
        # register and the start: never launch an unsupervised runner
        if qc is not None:
            qc.check()
        raise QueryCancelledError(
            f"supervised call at {site} aborted by teardown")
    t.start()
    deadline = time.monotonic() + timeout_s
    slice_s = qc.check_interval_s if qc is not None else WAIT_POLL_S
    try:
        while not done.wait(timeout=slice_s):
            if qc is not None and (qc.token.cancelled or qc.token.expired()):
                gave_up.set()
                qc.check()
            if time.monotonic() > deadline:
                gave_up.set()
                _bump_global("watchdog_trips", 1)
                from spark_rapids_tpu.obs import journal
                journal.emit(journal.EVENT_WATCHDOG_TRIP, site=site,
                             timeout_s=timeout_s)
                raise QueryHangError(site, timeout_s)
    finally:
        if done.is_set():
            reg.release()
    if "error" in box:
        raise box["error"]
    if fires and gave_up.is_set():
        # an EXTERNAL teardown (registry close from another thread)
        # unparked the injected wedge: the runner skipped the real work
        # and its None result is dead — surface typed, never hand it to
        # the caller
        if qc is not None:
            qc.check()
        raise QueryCancelledError(
            f"supervised call at {site} aborted by teardown")
    return box["value"]


# registered at import (every process that loads the engine, workers
# included).  atexit runs handlers LIFO, so for this bounded reap to
# run BEFORE multiprocessing's unbounded join-the-children handler,
# mp's handler must be registered FIRST — and `import multiprocessing`
# alone does NOT do that (only importing multiprocessing.util does,
# which normally happens lazily at the first Process spawn, i.e. AFTER
# this module loads).  Force it now: util's import registers
# _exit_function, then ours lands on top of the LIFO stack, so stray
# children are reaped with a bounded terminate/kill escalation before
# mp's unbounded join would park on a wedged worker forever.
import atexit as _atexit  # noqa: E402
import multiprocessing.util as _mp_util  # noqa: E402,F401

_atexit.register(_join_watchdogs_at_exit)
