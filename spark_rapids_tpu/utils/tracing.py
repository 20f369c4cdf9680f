"""Profiling ranges fused with metrics.

Reference: NvtxWithMetrics.scala:27 — an NVTX range that adds its elapsed ns
to a SQLMetric on close; ranges are pervasive (GpuSemaphore.scala:107,
aggregate.scala:346, GpuParquetScan.scala:317, Plugin.scala:120).  TPU
equivalent: ``jax.profiler.TraceAnnotation`` spans visible in Xprof, plus an
optional whole-query ``jax.profiler.trace`` capture to a log directory
(``spark.rapids.sql.trace.dir``).

The global enable switch is set from ``spark.rapids.sql.trace.enabled`` at
``ExecContext`` creation; when off, spans cost one flag check so the hot
loops stay clean (the reference's NVTX ranges are similarly near-free when
no profiler is attached).
"""

from __future__ import annotations

import contextlib
import threading
import time

try:
    import jax
    _HAVE_JAX = True
except Exception:  # pragma: no cover
    _HAVE_JAX = False

_enabled = False

# Overlap-pipeline span names (docs/io_overlap.md): the prefetch wait is
# the consumer blocked on the background decode queue; the H2D overlap
# span covers consumer compute running while the next upload is in
# flight.  Shared constants so Xprof captures from different operators
# aggregate under the same labels.
SPAN_PREFETCH_WAIT = "io.prefetch.wait"
SPAN_H2D_OVERLAP = "io.h2d.overlap"
SPAN_COALESCE_PULL = "io.coalesce.pull"
# the egress (device->host) mirror: the D2H wait is the consumer blocked
# on the background download queue; the overlap span covers host
# serialize/send/write running while the next pull is in flight
# (docs/d2h_egress.md)
SPAN_D2H_WAIT = "io.d2h.wait"
SPAN_D2H_OVERLAP = "io.d2h.overlap"
# the planner's whole-stage fusion rewrite (plan/fusion.py)
SPAN_PLAN_FUSION = "plan.fusion"
# adaptive replanning passes (docs/adaptive.md): one span per
# stats-driven replan of the not-yet-executed plan remainder
SPAN_PLAN_AQE = "plan.aqe"
# the two phases of a query (api.py): planning, then the root drain with
# the dispatch watcher's drain at its end; and the two blocking device
# reads (columnar/transfer.py): an egress pull, and a small synchronous
# read outside egress (``d2h.sync:<what>``: a row count, a debug column)
SPAN_QUERY_PLAN = "query.plan"
SPAN_QUERY_EXECUTE = "query.execute"
SPAN_D2H_PULL = "d2h.pull"
SPAN_D2H_SYNC = "d2h.sync"
# a served request (server/core.py), on the worker that picked its
# ticket up: ``server.execute:<tenant>`` from pick-up to typed outcome,
# a marker ``server.admit_wait`` at the pick-up (the wait itself ended
# there; its microseconds are the ``server.admit_wait_us`` counter), and
# the server's own work around the query: resolving the ticket to a
# DataFrame (parse or bind) and forming the result cache's key (plan and
# input-snapshot fingerprints, which stat and read every scanned file)
SPAN_SERVER_ADMIT_WAIT = "server.admit_wait"
SPAN_SERVER_EXECUTE = "server.execute"
SPAN_SERVER_RESOLVE = "server.resolve"
SPAN_SERVER_CACHE_KEY = "server.cache_key"

# the three phases of a mesh fragment (exec/meshexec.py,
# parallel/mesh.py ``phase``; docs/ici_shuffle.md): the drained child
# made one batch and split over the mesh, or scanned shard by shard
# onto it; the ``shard_map`` programs from launch to their sync; the
# result pulled or stacked back into one batch.  Their microseconds are
# the ``ici.ingest_us`` / ``collective_us`` / ``gather_us`` counters
SPAN_ICI_INGEST = "ici.ingest"
SPAN_ICI_COLLECTIVE = "ici.collective"
SPAN_ICI_GATHER = "ici.gather"

# the two sections of a one-chip hash join (exec/joins.py): the build
# side made one batch and probed for unique keys, and a stream batch
# joined against it (probe, expand, gather, or the one FK program).
# Their microseconds are the ``join.build_us`` / ``probe_us`` counters
SPAN_JOIN_BUILD = "join.build"
SPAN_JOIN_PROBE = "join.probe"
# a scan that missed the device scan cache (io/hostio.py): a host batch
# decoded from its file (on the prefetch thread where there is one), and
# a host batch uploaded.  Their microseconds are the ``scan.decode_us``
# / ``upload_us`` counters
SPAN_SCAN_DECODE = "scan.decode"
SPAN_SCAN_UPLOAD = "scan.upload"

# Always-on phase counters (the ``phases`` group of ``engine_stats()``,
# docs/observability.md): microseconds a query spent planning and
# executing, microseconds a thread sat in a blocking device read, and
# the number of synchronous reads outside ``device_pull``.  Bumped once
# per query or per pull, never per batch.
_PHASES_LOCK = threading.Lock()
_PHASES = {"plan_us": 0, "execute_us": 0, "pull_wait_us": 0,
           "blocking_reads": 0}

# Per-thread stacks kept under the switch only: the names of the open
# spans (``trace_range`` / ``MetricSet.timed``) and the plan nodes whose
# ``next()`` is running (exec/base.py ``_count_output``).  The dispatch
# ledger (compile/service.py) reads the top of each when a program is
# launched.
_TLS = threading.local()


def _stack(which: str) -> list:
    st = getattr(_TLS, which, None)
    if st is None:
        st = []
        setattr(_TLS, which, st)
    return st


def current_span() -> str:
    """Innermost open span of this thread ('' when none)."""
    st = getattr(_TLS, "spans", None)
    return st[-1] if st else ""


def current_node():
    """The plan node whose ``next()`` runs on this thread, or None."""
    st = getattr(_TLS, "nodes", None)
    return st[-1] if st else None


def running(node, it):
    """Iterate ``it`` with ``node`` on this thread's node stack while
    each ``next()`` runs, so a program launched inside it is charged to
    ``node``.  Callers wrap only when the switch is on."""
    it = iter(it)
    while True:
        nodes = _stack("nodes")  # of the thread that runs this next()
        nodes.append(node)
        try:
            item = next(it)
        except StopIteration:
            return
        finally:
            nodes.pop()
        yield item


def phase_add(counter: str, amount: int) -> None:
    with _PHASES_LOCK:
        _PHASES[counter] += amount


def phase_stats() -> dict:
    with _PHASES_LOCK:
        return dict(_PHASES)


def reset_phase_stats() -> None:
    with _PHASES_LOCK:
        for k in _PHASES:
            _PHASES[k] = 0


def set_enabled(on: bool) -> None:
    """Flip the global span switch (called from ExecContext with the
    session conf's ``trace.enabled`` value)."""
    global _enabled
    _enabled = bool(on)


def is_enabled() -> bool:
    return _enabled


class _Span:
    """One open span: the profiler annotation plus an entry on the
    thread's span stack."""

    __slots__ = ("name", "_ann")

    def __init__(self, name: str):
        self.name = name
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        _stack("spans").append(self.name)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        _stack("spans").pop()
        return False


def annotation(name: str):
    """A span for ``name`` if tracing is on, else None.  Callers hold it
    across a timed section (metrics._Timer)."""
    if _enabled and _HAVE_JAX:
        return _Span(name)
    return None


@contextlib.contextmanager
def trace_range(name: str, metric=None):
    """Named profiler range + optional metric accumulation (reference
    NvtxWithMetrics / MetricRange NvtxWithMetrics.scala:27,38)."""
    start = time.perf_counter_ns()
    ann = annotation(name)
    if ann is not None:
        ann.__enter__()
    try:
        yield
    finally:
        if ann is not None:
            ann.__exit__(None, None, None)
        if metric is not None:
            metric.add(time.perf_counter_ns() - start)


@contextlib.contextmanager
def phase(span: str, counter: str):
    """``trace_range(span)`` whose elapsed microseconds also land in the
    always-on phase counter ``counter``."""
    start = time.perf_counter_ns()
    try:
        with trace_range(span):
            yield
    finally:
        phase_add(counter, (time.perf_counter_ns() - start) // 1000)


# Traced scopes that are open now, and the switch as it stood before
# the first of them.  Two overlapping traced queries (two server workers
# under one traced session) each used to restore what they had found:
# the first to finish switched the other's spans and device clock off
# mid-query, and the second left the switch on behind it.
_SCOPES_LOCK = threading.Lock()
_open_traced = 0
_before_traced = False


@contextlib.contextmanager
def switch_scope(traced: bool):
    """Set the span switch to ``traced`` for the enclosed work and put
    it back on exit, success or failure.  Overlapping TRACED scopes
    count themselves in and out: the switch stays on until the last of
    them leaves, and then goes back to what it was before the first.
    An untraced scope restores what it found."""
    global _open_traced, _before_traced
    with _SCOPES_LOCK:
        prev = is_enabled()
        if traced:
            if _open_traced == 0:
                _before_traced = prev
            _open_traced += 1
        set_enabled(traced)
    try:
        yield
    finally:
        with _SCOPES_LOCK:
            if not traced:
                set_enabled(prev)
            else:
                _open_traced -= 1
                if _open_traced == 0:
                    set_enabled(_before_traced)


@contextlib.contextmanager
def query_trace(conf):
    """Whole-query profiler capture: when ``trace.enabled`` and a
    ``trace.dir`` are set, wraps execution in ``jax.profiler.trace`` so a
    collect() produces an Xprof trace (the Nsight-session analog).

    The span switch is scoped to the query (``switch_scope``): the
    previous enabled state is restored on exit, so a traced query
    inside an untraced session (or the reverse) cannot leak its switch
    into the next query (tests/test_tracing.py).  The switch itself
    remains process-global (like the reference's NVTX ranges):
    concurrent queries with DIFFERENT trace settings still
    last-writer-win while overlapped, and a traced query sees the
    programs of every other query in flight.  Per-request isolation
    needs a contextvar switch (ROADMAP M9)."""
    from spark_rapids_tpu import conf as C
    logdir = conf.get(C.TRACE_DIR)
    with switch_scope(conf.trace_enabled):
        if conf.trace_enabled and logdir and _HAVE_JAX:
            with jax.profiler.trace(logdir):
                yield
        else:
            yield
