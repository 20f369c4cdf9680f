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
from typing import Optional

try:
    import jax
    _HAVE_JAX = True
except Exception:  # pragma: no cover
    _HAVE_JAX = False

_enabled = False

# Overlap-pipeline span names (docs/io_overlap.md): the prefetch wait is
# the consumer blocked on the background decode queue, the coalesce pull
# the consumer blocked on the device lookahead.  Both are sections whose
# self time is the ``io_wait_us`` counter.  Shared constants so Xprof
# captures from different operators aggregate under the same labels.
SPAN_PREFETCH_WAIT = "io.prefetch.wait"
SPAN_COALESCE_PULL = "io.coalesce.pull"
# egress (docs/d2h_egress.md): a pulled result made host Arrow (the pull
# inside it is ``d2h.pull``), and an action's final table build.  Both
# are sections whose self time is the ``egress_us`` counter
SPAN_EGRESS_FINISH = "egress.finish"
SPAN_EGRESS_TABLE = "egress.table"
# a call that hands the device work: a program launch
# (compile/service.py) or a host->device copy dispatch
# (columnar/transfer.py); a section whose self time is ``dispatch_us``
SPAN_HOST_DISPATCH = "host.dispatch"
# the planner's whole-stage fusion rewrite (plan/fusion.py)
SPAN_PLAN_FUSION = "plan.fusion"
# adaptive replanning passes (docs/adaptive.md): one span per
# stats-driven replan of the not-yet-executed plan remainder
SPAN_PLAN_AQE = "plan.aqe"
# the two phases of a query (api.py): planning, then the root drain
# (sections whose totals are ``plan_us`` and ``execute_us``, and the
# drain's self time ``execute_self_us``); and the two blocking device
# reads (columnar/transfer.py): an egress pull, and a small synchronous
# read outside egress (``d2h.sync:<what>``: a row count, a debug column)
SPAN_QUERY_PLAN = "query.plan"
SPAN_QUERY_EXECUTE = "query.execute"
SPAN_D2H_PULL = "d2h.pull"
SPAN_D2H_SYNC = "d2h.sync"
# an action's own bookkeeping around plan and drain (lifecycle.py,
# api.py): the query scope's entry and its exit, and the trace's own
# work inside them (the dispatch ledger's mark, the watcher's drain and
# the ledger's read-back): sections whose self times are ``scope_us``
# and ``trace_us``
SPAN_QUERY_OPEN = "query.open"
SPAN_QUERY_CLOSE = "query.close"
SPAN_TRACE_DRAIN = "trace.drain"
# a served request (server/core.py), on the worker that picked its
# ticket up: ``server.execute:<tenant>`` from pick-up to typed outcome
# (a section whose self time is ``scope_us``; the admission wait ended
# at the pick-up and is the ``server.admit_wait_us`` counter), and the
# server's own work around the query: resolving the ticket to a
# DataFrame (parse or bind) and forming the result cache's key (plan and
# input-snapshot fingerprints, which stat and read every scanned file)
SPAN_SERVER_EXECUTE = "server.execute"
SPAN_SERVER_RESOLVE = "server.resolve"
SPAN_SERVER_CACHE_KEY = "server.cache_key"

# the three phases of a mesh fragment (exec/meshexec.py,
# parallel/mesh.py ``phase``; docs/ici_shuffle.md): the drained child
# made one batch and split over the mesh, or scanned shard by shard
# onto it; the ``shard_map`` programs from launch to their sync; the
# result pulled or stacked back into one batch.  Their microseconds are
# the ``ici.ingest_us`` / ``collective_us`` / ``gather_us`` counters.
# These, and the join and scan spans below, are totals measured beside
# the sections, not sections: the launches, pulls and waits inside them
# are the sections' own, and their Python is the operators'
# (``execute_self_us``)
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
# docs/observability.md), kept in nanoseconds and read in microseconds.
# Each is bumped by one section (``section``) as it closes, once per
# launch, wait or query, never per row.  ``plan_us``, ``execute_us`` and
# ``pull_wait_us`` are a section's whole time; the others its self time:
# the time on its thread inside it and inside no section nested in it,
# so the sections tile a query's host time and nothing counts twice.
# ``blocking_reads`` counts the synchronous reads outside ``device_pull``.
_PHASES_LOCK = threading.Lock()
_PHASES = dict.fromkeys((
    "plan_us", "execute_us", "pull_wait_us", "blocking_reads",
    "dispatch_us", "io_wait_us", "egress_us", "execute_self_us",
    "scope_us", "trace_us"), 0)


class _Thread(threading.local):
    """One thread's open sections (always on: ``[start_ns, nested_ns]``
    each, innermost last) and, under the switch only, the open spans
    (``trace_range`` / ``MetricSet.timed``) and the plan nodes whose
    ``next()`` is running (exec/base.py ``_count_output``).  The
    dispatch ledger (compile/service.py) reads the top of the last two
    when a program is launched.  ``tally`` is ``tally()``'s."""

    def __init__(self):
        self.sections: list = []
        self.spans: list = []
        self.nodes: list = []
        self.tally: Optional[dict] = None


_TLS = _Thread()


def current_span() -> str:
    """Innermost open span of this thread ('' when none)."""
    st = _TLS.spans
    return st[-1].name if st else ""


def current_node():
    """The plan node whose ``next()`` runs on this thread, or None."""
    st = _TLS.nodes
    return st[-1] if st else None


def running(node, it):
    """Iterate ``it`` with ``node`` on this thread's node stack while
    each ``next()`` runs, so a program launched inside it is charged to
    ``node``.  Callers wrap only when the switch is on."""
    it = iter(it)
    while True:
        nodes = _TLS.nodes  # of the thread that runs this next()
        nodes.append(node)
        try:
            item = next(it)
        except StopIteration:
            return
        finally:
            nodes.pop()
        yield item


def phase_add(counter: str, amount: int) -> None:
    """Add ``amount`` to a phase counter that counts (not a clock)."""
    with _PHASES_LOCK:
        _PHASES[counter] += amount


def phase_stats() -> dict:
    with _PHASES_LOCK:
        return {k: v // 1000 if k.endswith("_us") else v
                for k, v in _PHASES.items()}


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


def _leave(stack: list, entry) -> None:
    """Take ``entry`` off ``stack``: the top, unless a region was left
    out of order, and then its own entry and not another's."""
    if stack and stack[-1] is entry:
        stack.pop()
    else:
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is entry:
                del stack[i]
                break


class _Span:
    """One open span: the profiler annotation plus an entry on the
    thread's span stack."""

    __slots__ = ("name", "_ann")

    def __init__(self, name: str):
        self.name = name
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        _TLS.spans.append(self)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        _leave(_TLS.spans, self)
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


class section:
    """A timed region of a thread's host time, always on: its span under
    the switch (``annotation``, as ``trace_range`` opens it), and on
    close its whole time into the phase counter ``total`` and its self
    time (the time in no section nested in it) into ``own``, either or
    both.  ``ns`` is the whole time once closed.  A section opens and
    closes on one thread and is never held across a ``yield``
    (tests/lint_robustness.py): a generator resumed later, or on another
    thread, would charge its consumer's time to it."""

    __slots__ = ("span", "total", "own", "ns", "_frame", "_ann")

    def __init__(self, span: str, total: Optional[str] = None,
                 own: Optional[str] = None):
        self.span = span
        self.total = total
        self.own = own
        self.ns = 0

    def __enter__(self):
        self._ann = ann = annotation(self.span)
        if ann is not None:
            ann.__enter__()
        self._frame = frame = [time.perf_counter_ns(), 0]
        _TLS.sections.append(frame)
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        frame = self._frame
        th = _TLS
        st = th.sections
        _leave(st, frame)
        self.ns = ns = end - frame[0]
        own = ns - frame[1]
        if st:
            st[-1][1] += ns
        if self.total is not None or self.own is not None:
            with _PHASES_LOCK:
                if self.total is not None:
                    _PHASES[self.total] += ns
                if self.own is not None:
                    _PHASES[self.own] += own
        if th.tally is not None:
            th.tally[self.span] = th.tally.get(self.span, 0) + own
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


@contextlib.contextmanager
def tally():
    """Collect this thread's sections for the enclosed work: yields a
    dict that fills with ``{span: self nanoseconds}`` as each section
    closes, so the values add up to the time the sections covered (the
    per-query table of docs/observability.md, "Sections")."""
    was = _TLS.tally
    _TLS.tally = out = {}
    try:
        yield out
    finally:
        _TLS.tally = was


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
