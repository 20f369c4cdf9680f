"""utils/tracing.py contract tests (docs/observability.md).

The span layer mirrors the reference's NVTX-with-metrics fusion
(NvtxWithMetrics.scala:27): spans cost one flag check when disabled,
metric accumulation works with tracing on OR off, and ``query_trace``
scopes the global switch to the query — the previous enabled state is
restored on exit, success or failure, so one traced query cannot leak
tracing into the next (previously only incidentally exercised through
test_aux.py)."""

import pytest

from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.utils import tracing
from spark_rapids_tpu.utils.metrics import MetricSet


@pytest.fixture(autouse=True)
def _restore_switch():
    prev = tracing.is_enabled()
    yield
    tracing.set_enabled(prev)


def test_annotation_off_is_none():
    tracing.set_enabled(False)
    assert tracing.annotation("x.section") is None


def test_annotation_on_is_usable_context():
    tracing.set_enabled(True)
    ann = tracing.annotation("x.section")
    assert ann is not None
    with ann:  # a real jax.profiler.TraceAnnotation must enter/exit
        pass


def test_trace_range_accumulates_metric_with_tracing_disabled():
    """Metric accumulation is independent of the span switch: a
    disabled profiler must not cost the operator its timings."""
    tracing.set_enabled(False)
    ms = MetricSet(owner="TestOp", adhoc=True)
    with tracing.trace_range("TestOp.section", ms["sectionTime"]):
        pass
    assert ms["sectionTime"].value > 0


def test_trace_range_accumulates_metric_with_tracing_enabled():
    tracing.set_enabled(True)
    ms = MetricSet(owner="TestOp", adhoc=True)
    with tracing.trace_range("TestOp.section", ms["sectionTime"]):
        pass
    assert ms["sectionTime"].value > 0


def test_trace_range_without_metric():
    for on in (False, True):
        tracing.set_enabled(on)
        with tracing.trace_range("TestOp.bare"):
            pass


def test_timed_sections_work_with_tracing_disabled():
    tracing.set_enabled(False)
    ms = MetricSet(owner="TestOp")
    with ms.timed("totalTime"):
        pass
    assert ms.snapshot()["totalTime"] > 0


def test_query_trace_sets_switch_from_conf():
    tracing.set_enabled(False)
    with tracing.query_trace(TpuConf(
            {"spark.rapids.sql.trace.enabled": True})):
        assert tracing.is_enabled()
    with tracing.query_trace(TpuConf(
            {"spark.rapids.sql.trace.enabled": False})):
        assert not tracing.is_enabled()


def test_query_trace_restores_prior_state_on_exit():
    """Both directions: an untraced query inside a traced session must
    restore True, a traced query inside an untraced session must
    restore False."""
    tracing.set_enabled(False)
    with tracing.query_trace(TpuConf(
            {"spark.rapids.sql.trace.enabled": True})):
        pass
    assert not tracing.is_enabled()

    tracing.set_enabled(True)
    with tracing.query_trace(TpuConf(
            {"spark.rapids.sql.trace.enabled": False})):
        assert not tracing.is_enabled()
    assert tracing.is_enabled()


def test_device_handoff_restores_span_switch():
    """to_device_batches (the to_jax path) constructs an ExecContext
    too — the switch must be query-scoped on the handoff path exactly
    like collect()."""
    import numpy as np
    import pyarrow as pa
    from tests.compare import tpu_session
    tracing.set_enabled(False)
    s = tpu_session({"spark.rapids.sql.trace.enabled": "true"})
    df = s.create_dataframe(pa.table({
        "a": pa.array(np.arange(16), pa.int64())}))
    batches = df.to_device_batches()
    assert batches
    assert not tracing.is_enabled()


def test_query_trace_restores_on_exception():
    tracing.set_enabled(False)
    with pytest.raises(RuntimeError):
        with tracing.query_trace(TpuConf(
                {"spark.rapids.sql.trace.enabled": True})):
            assert tracing.is_enabled()
            raise RuntimeError("query failed mid-trace")
    assert not tracing.is_enabled()


@pytest.mark.parametrize("first_out", ("a", "b"))
def test_overlapping_traced_scopes_keep_the_switch_on(first_out):
    """Two traced queries in flight at once (two server workers): the
    one that finishes first must not switch the other's spans off, and
    the last one out restores what stood before the first came in."""
    traced = TpuConf({"spark.rapids.sql.trace.enabled": True})
    tracing.set_enabled(False)
    a, b = tracing.query_trace(traced), tracing.query_trace(traced)
    a.__enter__()
    b.__enter__()
    first, last = (a, b) if first_out == "a" else (b, a)
    first.__exit__(None, None, None)
    assert tracing.is_enabled()
    last.__exit__(None, None, None)
    assert not tracing.is_enabled()


def test_switch_scope_restores_on_exception_and_nests_untraced():
    tracing.set_enabled(False)
    with tracing.switch_scope(True):
        with tracing.switch_scope(False):
            assert not tracing.is_enabled()
        assert tracing.is_enabled()
        with pytest.raises(RuntimeError):
            with tracing.switch_scope(True):
                raise RuntimeError("boom")
        assert tracing.is_enabled()
    assert not tracing.is_enabled()


# -- the span and node stacks the dispatch ledger reads ---------------------

def test_span_stack_names_the_innermost_open_span():
    tracing.set_enabled(True)
    assert tracing.current_span() == ""
    ms = MetricSet(owner="TestOp")
    with tracing.trace_range("outer.section"):
        assert tracing.current_span() == "outer.section"
        with ms.timed("totalTime"):
            assert tracing.current_span() == "TestOp.totalTime"
        assert tracing.current_span() == "outer.section"
    assert tracing.current_span() == ""


def test_span_stack_is_not_kept_with_the_switch_off():
    tracing.set_enabled(False)
    with tracing.trace_range("outer.section"):
        assert tracing.current_span() == ""


def test_span_stack_unwinds_on_exception():
    tracing.set_enabled(True)
    with pytest.raises(RuntimeError):
        with tracing.trace_range("failing.section"):
            raise RuntimeError("boom")
    assert tracing.current_span() == ""


def test_running_puts_the_node_on_the_stack_only_while_next_runs():
    seen = []

    def child():
        seen.append(tracing.current_node())
        yield 1
        seen.append(tracing.current_node())
        yield 2

    def parent():
        for item in tracing.running("child", child()):
            seen.append(tracing.current_node())
            yield item

    assert tracing.current_node() is None
    for _ in tracing.running("parent", parent()):
        seen.append(tracing.current_node())  # the consumer's own code
    # inside child's next(): child; back in parent's body: parent; in
    # the consumer: nobody
    assert seen == ["child", "parent", None, "child", "parent", None]
    assert tracing.current_node() is None


def test_timer_builds_its_span_name_once_and_only_under_the_switch():
    ms = MetricSet(owner="TestOp")
    tracing.set_enabled(False)
    with ms.timed("totalTime"):
        pass
    assert ms._span_names == {}
    tracing.set_enabled(True)
    with ms.timed("totalTime"):
        pass
    first = ms._span_names["totalTime"]
    assert first == "TestOp.totalTime"
    with ms.timed("totalTime"):
        pass
    assert ms._span_names["totalTime"] is first
    assert MetricSet().span_name("totalTime") == "totalTime"


def _spans_of(monkeypatch):
    seen = []
    enter = tracing._Span.__enter__

    def recording(self):
        seen.append(self.name)
        return enter(self)

    monkeypatch.setattr(tracing._Span, "__enter__", recording)
    return seen


def _small_query(s):
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu import functions as F
    df = s.create_dataframe(pa.table({
        "a": pa.array(np.arange(64), pa.int64()),
        "b": pa.array(np.arange(64) * 0.5)}))
    return df.filter(F.col("a") > 3).select(
        (F.col("b") * 2).alias("c"), F.col("a"))


@pytest.mark.parametrize("span", [
    tracing.SPAN_QUERY_PLAN, tracing.SPAN_PLAN_FUSION,
    tracing.SPAN_QUERY_EXECUTE, tracing.SPAN_D2H_PULL])
def test_traced_query_emits_the_phase_spans(monkeypatch, span):
    """Planning runs inside the query's switch scope, so the planner's
    own spans are a traced query's too."""
    from tests.compare import tpu_session
    seen = _spans_of(monkeypatch)
    tracing.set_enabled(False)
    _small_query(tpu_session(
        {"spark.rapids.sql.trace.enabled": "true"})).collect()
    assert span in seen
    assert seen.index(tracing.SPAN_QUERY_PLAN) \
        < seen.index(tracing.SPAN_QUERY_EXECUTE)
    assert not tracing.is_enabled()


def test_untraced_query_emits_no_span(monkeypatch):
    from tests.compare import tpu_session
    seen = _spans_of(monkeypatch)
    tracing.set_enabled(False)
    _small_query(tpu_session()).collect()
    assert seen == []


def test_blocking_read_is_spanned_and_counted(monkeypatch):
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.column import LazyRows
    seen = _spans_of(monkeypatch)
    tracing.set_enabled(True)
    before = tracing.phase_stats()
    assert LazyRows(jnp.int32(7), 16).get() == 7
    after = tracing.phase_stats()
    assert "d2h.sync:rows" in seen
    assert after["blocking_reads"] == before["blocking_reads"] + 1
    assert after["pull_wait_us"] >= before["pull_wait_us"]
