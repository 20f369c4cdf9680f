"""The dispatch ledger (compile/service.py; docs/observability.md,
"Programs"): every program has a family and a name, every launch is
counted with the trace switch on or off, and under the switch one watcher
thread turns launches into device time per program and per plan node."""

import logging
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.compile import service
from spark_rapids_tpu.utils import tracing
from tests.compare import sum_plan_metric, tpu_session

TRACED = {"spark.rapids.sql.trace.enabled": "true"}


@pytest.fixture(autouse=True)
def _restore_switch():
    prev = tracing.is_enabled()
    yield
    tracing.set_enabled(prev)


def _lineitem(s, n=3000, chunks=1):
    """``chunks`` > 1: as many record batches, so as many scan batches."""
    rng = np.random.default_rng(5)
    return s.create_dataframe(_chunked(chunks, pa.table({
        "flag": pa.array(rng.choice(["A", "N", "R"], n)),
        "status": pa.array(rng.choice(["F", "O"], n)),
        "qty": pa.array(rng.integers(1, 50, n).astype(np.float64)),
        "price": pa.array(rng.uniform(900, 100000, n)),
        "disc": pa.array(rng.integers(0, 11, n) / 100.0),
        "ship": pa.array(rng.integers(8000, 10600, n), pa.int32()),
    })))


def _chunked(chunks: int, t: pa.Table) -> pa.Table:
    size = t.num_rows // chunks
    return pa.concat_tables([t.slice(i * size, size)
                             for i in range(chunks)])


def _q1(s):
    return _lineitem(s).filter(F.col("ship") <= 10471).group_by(
        "flag", "status").agg(
        F.sum(F.col("qty")).alias("sum_qty"),
        F.sum(F.col("price") * (1 - F.col("disc"))).alias("sum_disc"),
        F.avg(F.col("disc")).alias("avg_disc"),
        F.count(F.col("qty")).alias("n")).order_by("flag", "status")


def _q6(s, chunks=1):
    return _lineitem(s, chunks=chunks).filter(
        (F.col("ship") >= 8766) & (F.col("ship") < 9131)
        & (F.col("disc") >= 0.05) & (F.col("qty") < 24)).agg(
        F.sum(F.col("price") * F.col("disc")).alias("revenue"))


def _growth(before, after, group="programs"):
    return {k: after[group][k] - before[group][k] for k in after[group]
            if after[group][k] != before[group][k]}


# -- names ------------------------------------------------------------------

def test_engine_jit_names_the_module_and_delegates_lower():
    prog = service.engine_jit(lambda x: x + 1, family="scan",
                              name="calibrate")
    assert isinstance(prog, service.Program)
    assert (prog.family, prog.name) == ("scan", "calibrate")
    text = prog.lower(jax.ShapeDtypeStruct((8,), jnp.int32)).as_text()
    assert "module @jit_scan_calibrate" in text
    assert "jit_run" not in text
    assert int(prog(jnp.zeros(8, jnp.int32))[0]) == 1


@pytest.mark.parametrize("family,name", [
    ("operators", "x"), ("stage", "Fused"), ("stage", "a.b"),
    ("stage", ""), ("sort", "full sort")])
def test_engine_jit_refuses_a_name_that_is_no_stats_key(family, name):
    with pytest.raises(ValueError):
        service.engine_jit(lambda x: x, family=family, name=name)


def test_engine_jit_refuses_a_role_it_does_not_know():
    with pytest.raises(ValueError):
        service.engine_jit(lambda x: x, family="aggregate",
                           name="merge", role="merge")
    prog = service.engine_jit(lambda x: x, family="aggregate",
                              name="masked_update", role=service.AGG_UPDATE)
    assert service.ledger_rows()["aggregate_masked_update"]["role"] == \
        "agg_update"
    assert (prog.family, prog.name) == ("aggregate", "masked_update")


def test_aot_compile_lowers_through_a_program():
    prog = service.engine_jit(lambda x: x * 2, family="stage",
                              name="project")
    compiled, ms, hit = service.aot_compile(
        prog, (jax.ShapeDtypeStruct((4,), jnp.float32),))
    assert compiled is not None and ms >= 0 and hit is False
    before = service.ledger_rows()["stage_project"]["dispatches"]
    out = prog.call_compiled(compiled, jnp.ones(4, jnp.float32))
    assert float(out[0]) == 2.0
    assert service.ledger_rows()["stage_project"]["dispatches"] \
        == before + 1


def test_stage_kernel_launches_through_the_ledger():
    from spark_rapids_tpu.exec.stage import StageKernel
    prog = service.engine_jit(lambda x: x - 1, family="stage",
                              name="filter")
    aval = jax.ShapeDtypeStruct((4,), jnp.int32)
    compiled, ms, _ = service.aot_compile(prog, (aval,))
    kern = StageKernel(compiled, prog, ms)
    before = service.ledger_rows()["stage_filter"]["dispatches"]
    assert int(kern(jnp.ones(4, jnp.int32))[0]) == 0
    # an aval the executable was not compiled for retraces through the
    # jitted function: still one launch, counted once
    assert int(kern(jnp.ones(8, jnp.int32))[0]) == 0
    assert service.ledger_rows()["stage_filter"]["dispatches"] \
        == before + 2


def test_no_program_of_q1_or_q6_is_called_run(caplog):
    """What XLA compiles while q1 and q6 run is named after the engine's
    program, never ``run``."""
    from spark_rapids_tpu.utils import kernel_cache
    with kernel_cache._REGISTRY_LOCK:
        caches = list(kernel_cache._REGISTRY)
    for c in caches:
        c.clear()
    jax.clear_caches()
    s = tpu_session()
    with caplog.at_level(logging.DEBUG,
                         logger="jax._src.interpreters.pxla"):
        _q1(s).collect()
        _q6(s).collect()
    compiled = [m.group(1) for r in caplog.records for m in
                [re.match(r"Compiling (?:jit\()?([\w<>]+)", r.getMessage())]
                if m]
    # the filter of both runs masked inside the aggregate's update
    # (docs/fusion.md, "The aggregate fold"): no stage program at all
    assert not [n for n in compiled if n.startswith("stage_")], compiled
    assert any(n.startswith("aggregate_masked_") for n in compiled), \
        compiled
    assert not [n for n in compiled if n in ("run", "body", "<lambda>")]


# -- counting ---------------------------------------------------------------

def test_snapshot_has_every_key_at_zero_or_more_from_the_start():
    snap = service.programs_snapshot()
    want = {"dispatches", "device_us", "starved_us", "untimed"}
    for fam in service.FAMILIES:
        want |= {f"{fam}_dispatches", f"{fam}_device_us"}
    assert set(snap) == want
    assert all(isinstance(v, int) and v >= 0 for v in snap.values())
    roles = service.roles_snapshot()
    assert set(roles) == {"agg_update_dispatches", "agg_update_device_us"}
    assert all(isinstance(v, int) and v >= 0 for v in roles.values())


def test_dispatch_counts_are_equal_with_the_switch_on_and_off():
    counts = []
    for conf in ({}, TRACED):
        s = tpu_session(conf)
        _q1(s).collect()  # warm: compiles launch nothing extra, but
        before = s.engine_stats()  # lazily built caches may
        _q1(s).collect()
        grown = _growth(before, s.engine_stats())
        counts.append({k: v for k, v in grown.items()
                       if k.endswith("dispatches")})
    off, on = counts
    assert off == on and off["dispatches"] >= 4
    assert off["dispatches"] == sum(
        v for k, v in off.items() if k != "dispatches")


def test_the_count_is_exact_under_threads():
    prog = service.engine_jit(lambda x: x, family="scan", name="decode")
    x = jnp.zeros(4, jnp.int32)
    prog(x)
    before = service.ledger_rows()["scan_decode"]["dispatches"]

    def work():
        for _ in range(500):
            prog(x)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    # reads in between must not eat launches either
    assert service.ledger_rows()["scan_decode"]["dispatches"] \
        == before + 4000


# -- the watcher's arithmetic -----------------------------------------------

def test_timeline_back_to_back():
    t = service.Timeline()
    assert t.account(100, 400) == (300, 0)
    # launched while the first still ran: it starts when the first ends
    assert t.account(150, 900) == (500, 0)
    assert t.account(160, 1000) == (100, 0)


def test_timeline_host_starved_gap():
    t = service.Timeline()
    assert t.account(0, 1000) == (1000, 0)
    # the device was ready at 1000 and the host launched at 1800
    assert t.account(1800, 2500) == (700, 800)
    assert t.account(2400, 2600) == (100, 0)


def test_timeline_mark_forgives_the_idle_time_before_a_query():
    t = service.Timeline()
    t.account(0, 1000)
    t.mark(50_000)  # a query begins long after
    assert t.account(50_300, 51_000) == (700, 300)
    t.mark(10)  # a mark in the past moves nothing
    assert t.prev_ready == 51_000


def test_timeline_out_of_order_append_keeps_time_whole():
    """Two threads launch at 10 and 11 and the later is appended first:
    the earlier one's output is then already there.  No time is counted
    twice, none is lost, none is negative."""
    t = service.Timeline()
    first = t.account(11, 500)   # launched second, appended first
    second = t.account(10, 501)  # its output was ready by then
    assert first == (489, 0) and second == (1, 0)
    assert first[0] + second[0] == 501 - 11 + 1 - 1
    # a ready time behind the stream's (a clock read out of order) is 0
    assert t.account(5, 300) == (0, 0)
    assert t.prev_ready == 501


def test_an_output_that_cannot_be_waited_on_is_untimed():
    tracing.set_enabled(True)
    prog = service.engine_jit(lambda x: None, family="egress",
                              name="stats")
    before = service.ledger_rows().get("egress_stats",
                                       {"untimed": 0, "dispatches": 0,
                                        "device_ns": 0})
    prog(jnp.zeros(4))
    assert service.drain_watcher()
    after = service.ledger_rows()["egress_stats"]
    assert after["untimed"] == before["untimed"] + 1
    assert after["dispatches"] == before["dispatches"] + 1
    assert after["device_ns"] == before["device_ns"]


def test_traced_launches_are_credited_in_order_by_one_watcher():
    tracing.set_enabled(True)
    prog = service.engine_jit(lambda x: jnp.sort(x) + 1, family="sort",
                              name="head")
    x = jnp.arange(50_000, dtype=jnp.float32)
    prog(x).block_until_ready()
    service.drain_watcher()
    before = service.ledger_rows()["sort_head"]
    with tracing.trace_range("test.span"):
        for _ in range(5):
            out = prog(x)
    assert service.drain_watcher()
    out.block_until_ready()
    after = service.ledger_rows()["sort_head"]
    assert after["dispatches"] - before["dispatches"] == 5
    assert after["device_ns"] > before["device_ns"]
    assert after["no_node_ns"] - before["no_node_ns"] \
        == after["device_ns"] - before["device_ns"]  # no plan node here
    assert set(after["starved_ns"]) - set(before["starved_ns"]) \
        <= {"test.span"}
    assert [t.name for t in threading.enumerate()
            ].count("srt-dispatch-watcher") == 1


# -- a query ----------------------------------------------------------------

def _node_sums(node, out):
    out["device_ns"] += node["metrics"].get("deviceTime", 0)
    out["dispatches"] += node["metrics"].get("deviceDispatches", 0)
    for c in node["children"]:
        _node_sums(c, out)
    return out


@pytest.mark.parametrize("query,update", [
    # the filter is folded into both updates (docs/fusion.md)
    (_q1, "aggregate_masked_update"),   # plain string keys: the sorted body
    (_q6, "aggregate_masked_pallas_update"),  # no keys: a known domain
], ids=["q1", "q6"])
def test_node_device_time_adds_up_to_the_programs_group(query, update):
    s = tpu_session(TRACED)
    query(s).collect()
    before = s.engine_stats()
    txt = query(s).explain(analyze=True)
    after = s.engine_stats()
    prof = s.last_query_profile().to_dict()
    rows = prof["programs"]
    assert rows and all(set(r) == {
        "program", "family", "dispatches", "device_ms", "no_node_ms",
        "untimed", "starved_ms"} for r in rows)
    nodes = _node_sums(prof["plan"], {"device_ns": 0, "dispatches": 0})
    grown = _growth(before, after)
    assert sum(r["dispatches"] for r in rows) == grown["dispatches"]
    assert nodes["dispatches"] == grown["dispatches"]  # one thread here
    no_node_ns = sum(r["no_node_ms"] for r in rows) * 1e6
    # the rows are rounded to a microsecond each, the group to one
    slack_us = len(rows) + 2
    assert abs((nodes["device_ns"] + no_node_ns) / 1e3
               - grown["device_us"]) <= slack_us
    assert abs(sum(r["device_ms"] for r in rows) * 1e3
               - grown["device_us"]) <= slack_us
    assert sum(r["untimed"] for r in rows) == 0
    assert "device=" in txt and "dispatches=" in txt
    assert "Programs:" in txt and f"{update}: dispatches=" in txt


def test_a_group_of_batches_is_one_dispatch_of_the_update():
    """Six input batches whose updates share a program ride one launch
    (exec/aggregate.py, ``GROUP_MEMBERS``): the update's row counts one
    dispatch a query where it counted six, the node's dispatches add up
    as before, and the node's batch counters still count batches."""
    s = tpu_session({**TRACED,
                     "spark.rapids.sql.reader.batchSizeRows": "512",
                     "spark.rapids.sql.batchSizeBytes": "8192"})
    _q6(s, chunks=6).collect()
    before = s.engine_stats()
    _q6(s, chunks=6).collect()
    after = s.engine_stats()
    prof = s.last_query_profile().to_dict()
    by_program = {r["program"]: r["dispatches"] for r in prof["programs"]}
    assert by_program["aggregate_masked_pallas_update"] == 1
    nodes = _node_sums(prof["plan"], {"device_ns": 0, "dispatches": 0})
    assert nodes["dispatches"] == sum(by_program.values()) \
        == _growth(before, after)["dispatches"]
    assert sum_plan_metric(s, "pallasAggBatches") == 6
    assert sum_plan_metric(s, "groupedUpdateBatches") == 6


def test_phases_count_plan_execute_and_blocking_reads():
    s = tpu_session()
    before = s.engine_stats()
    df = _lineitem(s).filter(F.col("qty") > 10)
    df.collect()
    batches = df.to_device_batches()
    assert batches[0].num_rows > 0  # a lazy row count: one blocking read
    grown = _growth(before, s.engine_stats(), "phases")
    assert grown["plan_us"] > 0 and grown["execute_us"] > 0
    assert grown["pull_wait_us"] > 0
    assert grown["blocking_reads"] >= 1


def test_session_stop_joins_the_watcher():
    s = tpu_session(TRACED)
    _q6(s).collect()
    assert "srt-dispatch-watcher" in [
        t.name for t in threading.enumerate()]
    s.stop()
    assert "srt-dispatch-watcher" not in [
        t.name for t in threading.enumerate() if t.is_alive()]
    # and the next traced query starts another
    s = tpu_session(TRACED)
    _q6(s).collect()
    assert s.last_query_profile().to_dict()["programs"]


def test_untraced_query_starts_no_watcher_and_writes_no_device_metrics():
    service.stop_watcher()
    s = tpu_session()
    _q1(s).collect()
    assert "srt-dispatch-watcher" not in [
        t.name for t in threading.enumerate()]
    d = s.last_query_profile().to_dict()
    assert "programs" not in d
    assert _node_sums(d["plan"], {"device_ns": 0, "dispatches": 0}) == \
        {"device_ns": 0, "dispatches": 0}
    assert "device=" not in s.last_query_profile().render()
    assert "deviceTime" not in s.last_query_metrics()
