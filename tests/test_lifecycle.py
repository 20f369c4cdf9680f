"""Query lifecycle supervision tests (spark_rapids_tpu/lifecycle.py):
deadlines, cooperative cancellation, the resource registry, the hang
watchdog, and the consolidated engine error hierarchy
(docs/fault_tolerance.md, "Query lifecycle")."""

import threading
import time

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as st
from spark_rapids_tpu import faults, lifecycle
from spark_rapids_tpu.errors import (
    EngineError, QueryCancelledError, QueryHangError, QueryTimeoutError,
)


def _table(n=300):
    rng = np.random.default_rng(7)
    return pa.table({
        "k": pa.array(rng.integers(0, 8, n), pa.int64()),
        "v": pa.array(rng.normal(size=n)),
    })


def _session(extra=None):
    conf = {"spark.rapids.sql.incompatibleOps.enabled": "true"}
    conf.update(extra or {})
    s = st.TpuSession(conf)
    s.create_dataframe(_table()).create_or_replace_temp_view("t")
    return s


QUERY = "SELECT k, SUM(v) AS sv, COUNT(*) AS c FROM t GROUP BY k ORDER BY k"


# -- error hierarchy --------------------------------------------------------

def test_error_hierarchy_consolidated():
    from spark_rapids_tpu.shuffle.manager import FetchFailedError
    from spark_rapids_tpu.shuffle.serializer import (
        BlockCorruptError, ChecksumUnavailableError, CodecUnavailableError,
        FrameUnavailableError,
    )
    # lifecycle hierarchy: a timeout IS a cancellation
    assert issubclass(QueryTimeoutError, QueryCancelledError)
    assert issubclass(QueryCancelledError, EngineError)
    assert issubclass(QueryHangError, EngineError)
    # shuffle plane joins the hierarchy WITHOUT losing its stdlib bases
    # (the retry machinery's isinstance checks are unchanged)
    assert issubclass(FetchFailedError, EngineError)
    assert issubclass(FetchFailedError, IOError)
    assert issubclass(BlockCorruptError, EngineError)
    assert issubclass(BlockCorruptError, IOError)
    assert issubclass(FrameUnavailableError, EngineError)
    assert issubclass(FrameUnavailableError, RuntimeError)
    assert issubclass(ChecksumUnavailableError, FrameUnavailableError)
    assert issubclass(CodecUnavailableError, FrameUnavailableError)
    assert issubclass(faults.InjectedFault, EngineError)
    assert issubclass(faults.InjectedFault, IOError)


# -- token / context units --------------------------------------------------

def test_cancel_token_deadline_and_classification():
    tok = lifecycle.CancelToken(timeout_s=0.05)
    tok.check()  # before the deadline: no-op
    time.sleep(0.08)
    assert tok.expired()
    with pytest.raises(QueryTimeoutError):
        tok.check()
    assert tok.timed_out
    # re-checks keep the classification
    with pytest.raises(QueryTimeoutError):
        tok.check()


def test_cancel_token_explicit_cancel():
    tok = lifecycle.CancelToken()
    tok.cancel("user abort")
    assert tok.cancelled and not tok.timed_out
    with pytest.raises(QueryCancelledError, match="user abort"):
        tok.check()


def test_registry_closes_in_registration_order_and_release():
    qc = lifecycle.QueryContext()
    order = []
    qc.register(lambda: order.append("a"), name="a")
    reg_b = qc.register(lambda: order.append("b"), name="b")
    qc.register(lambda: order.append("c"), name="c")
    reg_b.release()  # resource closed itself on its normal path
    assert qc.live_resources == 2
    qc.finish()
    assert order == ["a", "c"]
    # idempotent
    qc.finish()
    assert order == ["a", "c"]


def test_late_registration_into_finished_context_closes_on_arrival():
    # a stop can finish a context between another thread's cooperative
    # checkpoints; a resource that thread registers AFTER the registry
    # closed must be closed immediately, never silently accepted into a
    # registry nothing will sweep again
    qc = lifecycle.QueryContext()
    qc.finish()
    closed = []
    reg = qc.register(lambda: closed.append(True), name="late")
    assert closed == [True]
    reg.release()  # already-released handle: a no-op, never an error


def test_registry_teardown_survives_closer_errors():
    qc = lifecycle.QueryContext()
    closed = []
    qc.register(lambda: (_ for _ in ()).throw(RuntimeError("boom")),
                name="bad")
    qc.register(lambda: closed.append(True), name="good")
    qc.finish()  # must not raise, must reach the second closer
    assert closed == [True]


def test_check_interval_conf_reaches_blocking_waits():
    from spark_rapids_tpu.conf import TpuConf
    conf = TpuConf({"spark.rapids.sql.cancel.checkIntervalMs": "200"})
    with lifecycle.query_scope(conf) as qc:
        assert qc.check_interval_s == pytest.approx(0.2)
        # the helper every bounded wait sizes its poll slices with
        assert lifecycle.poll_interval_s() == pytest.approx(0.2)
    assert lifecycle.poll_interval_s() == lifecycle.WAIT_POLL_S


def test_query_scope_nesting_reuses_outer():
    with lifecycle.query_scope(timeout_ms=0) as outer:
        with lifecycle.query_scope(timeout_ms=5) as inner:
            assert inner is outer
        assert lifecycle.current() is outer
    assert lifecycle.current() is None


# -- supervision off == byte-identical --------------------------------------

def test_supervision_off_is_byte_identical():
    s = _session()
    base = s.sql(QUERY).to_arrow()
    s.stop()
    s = _session({"spark.rapids.sql.queryTimeoutMs": "600000",
                  "spark.rapids.sql.watchdog.hangTimeoutMs": "0"})
    supervised = s.sql(QUERY).to_arrow()
    s.stop()
    assert supervised.equals(base)


# -- deadlines --------------------------------------------------------------

def test_query_deadline_raises_typed_and_session_survives():
    s = _session({"spark.rapids.sql.queryTimeoutMs": "1"})
    with pytest.raises(QueryTimeoutError):
        s.sql(QUERY).to_arrow()
    # the session (and the next query) is unharmed: deadline off again
    s.set_conf("spark.rapids.sql.queryTimeoutMs", "0")
    assert s.sql(QUERY).to_arrow().num_rows == 8
    s.stop()


def test_deadline_counted_in_global_stats():
    lifecycle.reset_global_stats()
    s = _session({"spark.rapids.sql.queryTimeoutMs": "1"})
    with pytest.raises(QueryTimeoutError):
        s.sql(QUERY).to_arrow()
    s.stop()
    stats = lifecycle.global_stats()
    assert stats["timeouts"] == 1
    assert stats["queries"] >= 1


# -- cooperative cancellation ----------------------------------------------

def test_cancel_interrupts_pull_boundary():
    s = _session()
    with lifecycle.query_scope(timeout_ms=0) as qc:
        qc.cancel("test cancel")
        with pytest.raises(QueryCancelledError):
            s.sql(QUERY).to_arrow()
    s.stop()
    stats = lifecycle.global_stats()
    assert stats["cancels"] >= 1


def test_cancel_interrupts_semaphore_wait():
    from spark_rapids_tpu.runtime import TpuSemaphore
    sem = TpuSemaphore(1)
    entered = threading.Event()
    release = threading.Event()

    def holder():
        sem.acquire()
        entered.set()
        release.wait(timeout=10)
        sem.release()

    t = threading.Thread(target=holder, daemon=True)
    t.start()
    assert entered.wait(timeout=5)
    with lifecycle.query_scope(timeout_ms=0) as qc:
        qc.cancel("admission abort")
        with pytest.raises(QueryCancelledError):
            sem.acquire()
    release.set()
    t.join(timeout=5)
    # the permit was returned: a fresh acquire succeeds immediately
    sem.acquire()
    sem.release()


def test_cancel_interrupts_staging_wait():
    from spark_rapids_tpu.memory.spill import HostStagingLimiter
    lim = HostStagingLimiter(cap_bytes=100)
    granted = lim.acquire(100)
    assert granted == 100
    with lifecycle.query_scope(timeout_ms=0) as qc:
        qc.cancel("staging abort")
        with pytest.raises(QueryCancelledError):
            with lim.limit(50):
                pass
    lim.release(granted)
    assert lim._inflight == 0


# -- resource registry integration -----------------------------------------

def test_prefetch_thread_reclaimed_by_scope_teardown():
    from spark_rapids_tpu.io.prefetch import PrefetchIterator
    with lifecycle.query_scope(timeout_ms=0) as qc:
        it = PrefetchIterator(iter(range(100)), depth=1, name="leak-test")
        assert next(it) == 0
        assert qc.live_resources >= 1
    # scope exit closed the iterator: producer joined, no leak
    assert not it._thread.is_alive()


def test_session_stop_joins_outstanding_threads():
    # a prefetch iterator created OUTSIDE any query scope lands in the
    # global registry; session.stop() must reclaim it (satellite: stop
    # is deterministic, not GC-and-daemon-flags)
    from spark_rapids_tpu.io.prefetch import PrefetchIterator
    s = _session()
    assert s.sql(QUERY).to_arrow().num_rows == 8  # materialize runtime
    it = PrefetchIterator(iter(range(100)), depth=1, name="stop-test")
    assert next(it) == 0
    assert it._thread.is_alive()
    s.stop()
    assert not it._thread.is_alive()


def test_shutdown_all_reclaims_other_threads_contexts():
    # stop issued from thread A must cancel + tear down a query running
    # on thread B — shutdown_all drains EVERY live context, not just
    # the calling thread's
    started = threading.Event()
    unblock = threading.Event()
    seen = {}

    def worker():
        with lifecycle.query_scope(timeout_ms=0) as qc:
            closed = []
            qc.register(lambda: closed.append(True), name="r")
            seen["qc"], seen["closed"] = qc, closed
            started.set()
            unblock.wait(timeout=10)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    assert started.wait(timeout=5)
    try:
        lifecycle.shutdown_all()  # from the MAIN thread
        assert seen["closed"] == [True]
        assert seen["qc"].token.cancelled
    finally:
        unblock.set()
        t.join(timeout=5)


def test_warmer_thread_is_lifecycle_registered():
    # fused-stage queries over a file scan start a compile warmer; the
    # leak-audit fixture (conftest) asserts it never outlives the test,
    # and teardown leaves no registered stragglers
    import os
    import tempfile
    import pyarrow.parquet as pq
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "t.parquet")
        pq.write_table(_table(1000), path)
        s = st.TpuSession({"spark.rapids.sql.incompatibleOps.enabled":
                           "true"})
        df = s.read.parquet(path)
        df.create_or_replace_temp_view("pt")
        got = s.sql("SELECT k, v * 2 AS dv FROM pt WHERE v > 0").to_arrow()
        assert got.num_rows > 0
        s.stop()


# -- per-query semaphore telemetry flush (satellite) ------------------------

def test_semaphore_waits_flushed_at_query_end():
    from spark_rapids_tpu.io import prefetch as pf
    from spark_rapids_tpu.runtime import TpuRuntime
    s = _session()
    s.sql(QUERY).to_arrow()  # materialize the process singleton runtime
    rt = TpuRuntime._instance
    assert rt is not None
    pf.reset_global_stats()
    rt.semaphore.wait_ns = 7_000_000  # simulate 7ms of admission wait
    s.sql(QUERY).to_arrow()
    # flushed at QUERY end (not runtime shutdown): process-wide stats
    # already carry it and the runtime's accumulator was drained
    assert pf.global_stats()["sem_wait_ms"] >= 7
    assert rt.semaphore.wait_ns == 0
    s.stop()


def test_semaphore_wait_attributed_to_query_metrics():
    # waits are attributed at the ACQUIRE site to the waiting query's
    # own context (lifecycle.note_sem_wait) — not grabbed by whichever
    # query's end flush runs first — and surface as the semWaitMs root
    # metric of the query that actually waited
    from spark_rapids_tpu.runtime import TpuRuntime
    s = _session()
    s.sql(QUERY).to_arrow()  # materialize the process singleton runtime
    rt = TpuRuntime._instance
    release = threading.Event()
    holders = []
    entered = []
    for _ in range(rt.semaphore.permits):  # exhaust chip admission
        ev = threading.Event()

        def holder(ev=ev):
            rt.semaphore.acquire()
            ev.set()
            release.wait(timeout=10)
            rt.semaphore.release()

        t = threading.Thread(target=holder, daemon=True)
        t.start()
        holders.append(t)
        entered.append(ev)
    assert all(ev.wait(timeout=5) for ev in entered)
    timer = threading.Timer(0.3, release.set)
    timer.start()
    try:
        got = s.sql(QUERY).to_arrow()
    finally:
        release.set()
        timer.cancel()
        for t in holders:
            t.join(timeout=5)
    assert got.num_rows == 8
    assert "semWaitMs=" in s.last_query_metrics()
    s.stop()


# -- hang watchdog ----------------------------------------------------------

def test_watchdog_bounds_injected_pull_hang():
    lifecycle.reset_global_stats()
    s = _session({"spark.rapids.faults.io.pipeline.hang": "always",
                  "spark.rapids.sql.watchdog.hangTimeoutMs": "300"})
    t0 = time.monotonic()
    with pytest.raises(QueryHangError):
        s.sql("SELECT k, v FROM t WHERE v > 0").to_arrow()
    assert time.monotonic() - t0 < 30  # bounded, not a hang
    assert lifecycle.global_stats()["watchdog_trips"] >= 1
    s.stop()


def test_deadline_interrupts_injected_hang_without_watchdog():
    # watchdog off: the deadline alone must still bound the wedge
    s = _session({"spark.rapids.faults.io.pipeline.hang": "always",
                  "spark.rapids.sql.queryTimeoutMs": "700"})
    t0 = time.monotonic()
    with pytest.raises(QueryTimeoutError):
        s.sql("SELECT k, v FROM t WHERE v > 0").to_arrow()
    assert time.monotonic() - t0 < 30
    s.stop()


def test_supervise_passthrough_without_query_or_faults():
    assert lifecycle.current() is None
    assert lifecycle.supervise(lambda: 42,
                               lifecycle.FAULT_SITE_PIPELINE_HANG) == 42


def test_supervise_propagates_fn_errors_through_watchdog():
    class Boom(RuntimeError):
        pass

    with lifecycle.query_scope(timeout_ms=0) as qc:
        qc.hang_timeout_s = 5.0  # force the threaded path
        with pytest.raises(Boom):
            lifecycle.supervise(
                lambda: (_ for _ in ()).throw(Boom("x")),
                lifecycle.FAULT_SITE_PIPELINE_HANG)


@pytest.mark.multichip
def test_ici_hang_degrades_to_host_path():
    # a wedged mesh collective must degrade the fragment, not hang the
    # query: the injected park holds the collective sync past the
    # watchdog bound (each parked collective costs one bound's worth of
    # wall clock, so keep it modest); the fragment then re-runs on the
    # host path over the drained input and the result stays exact
    base = _session()
    expect = base.sql(QUERY).to_arrow()
    base.stop()
    s = _session({"spark.rapids.shuffle.mode": "ici",
                  "spark.rapids.faults.shuffle.ici.hang": "always",
                  "spark.rapids.sql.watchdog.hangTimeoutMs": "1200"})
    got = s.sql(QUERY).to_arrow()
    assert got.equals(expect)
    metrics = s.last_query_metrics()
    assert "iciFallbacks=" in metrics
    s.stop()


# -- bench integration ------------------------------------------------------

def test_global_stats_shape():
    stats = lifecycle.global_stats()
    assert set(stats) == {"queries", "timeouts", "cancels",
                          "watchdog_trips", "teardown_ms"}
