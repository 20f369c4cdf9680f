"""The host-time sections of the ``phases`` group (utils/tracing.py
``section``; docs/observability.md, "Sections"): always-on self-time
counters that tile a query's host time, so that nothing counts twice and
every microsecond of the query's thread lands in one named section."""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.compile import service
from spark_rapids_tpu.utils import tracing
from tests.compare import tpu_session

TRACED = {"spark.rapids.sql.trace.enabled": "true"}
SMALL_BATCHES = {"spark.rapids.sql.reader.batchSizeRows": "512",
                 "spark.rapids.sql.batchSizeBytes": "16384"}
# sections of the action's own thread outside its root drain
OUTSIDE = ("query.plan", "query.open", "query.close", "trace.drain",
           "egress.table")


@pytest.fixture(autouse=True)
def _restore_switch():
    prev = tracing.is_enabled()
    yield
    tracing.set_enabled(prev)


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    root = tmp_path_factory.mktemp("sections")
    rng = np.random.default_rng(11)
    n = 4000
    pq.write_table(pa.table({
        "k": pa.array(rng.integers(0, 300, n)),
        "flag": pa.array(rng.choice(["A", "N", "R"], n)),
        "qty": pa.array(rng.integers(1, 50, n).astype(np.float64)),
        "price": pa.array(rng.uniform(900, 1e5, n)),
    }), str(root / "fact.parquet"), row_group_size=1000)
    pq.write_table(pa.table({
        "k": pa.array(np.arange(300)),
        "region": pa.array(rng.choice(["EU", "US", "ASIA"], 300)),
    }), str(root / "dim.parquet"))
    return {"fact": str(root / "fact.parquet"),
            "dim": str(root / "dim.parquet")}


def _aggregate(s, files):
    """scan -> filter -> aggregate -> egress"""
    return s.read.parquet(files["fact"]).filter(F.col("qty") < 30) \
        .group_by("flag").agg(F.sum(F.col("price")).alias("rev"),
                              F.count(F.col("qty")).alias("n")) \
        .order_by("flag")


def _join(s, files):
    fact = s.read.parquet(files["fact"])
    dim = s.read.parquet(files["dim"])
    return fact.join(dim, on="k").group_by("region").agg(
        F.sum(F.col("qty")).alias("q")).order_by("region")


def _rollup(s, files):
    """an Expand whose consumer launches between the batches it pulls,
    on the same thread (the session serial: no lookahead thread)"""
    return s.read.parquet(files["fact"]).rollup("flag").agg(
        F.sum(F.col("price")).alias("rev")).order_by("flag")


PLANS = {"aggregate": _aggregate, "join": _join, "rollup": _rollup}
CONFS = {"aggregate": {}, "join": {},
         "rollup": {"spark.rapids.sql.io.prefetch.enabled": "false"}}


def _measured(df, runs=1):
    """``to_arrow()``: its wall ns, the growth of the phase counters (us),
    and this thread's self ns by section; of ``runs`` runs the one the
    sections covered best (a preemption between two sections, on a
    loaded host, is time no section holds)."""
    best = None
    for _ in range(runs):
        before = tracing.phase_stats()
        with tracing.tally() as tally:
            t0 = time.perf_counter_ns()
            df.to_arrow()
            wall = time.perf_counter_ns() - t0
        after = tracing.phase_stats()
        got = (wall, {k: after[k] - before[k] for k in after}, tally)
        if best is None or wall - sum(tally.values()) \
                < best[0] - sum(best[2].values()):
            best = got
    return best


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("plan", sorted(PLANS))
def test_the_sections_tile_the_action(files, plan, traced):
    s = tpu_session({**SMALL_BATCHES, **CONFS[plan],
                     **(TRACED if traced else {})})
    PLANS[plan](s, files).to_arrow()  # compiles out of the way
    wall, grown, tally = _measured(PLANS[plan](s, files), runs=3)
    us = wall / 1000
    # plan_us + execute_us + scope_us + trace_us + the egress outside the
    # drain is the action's wall time ...
    tiled = grown["plan_us"] + grown["execute_us"] + grown["scope_us"] \
        + grown["trace_us"] + tally["egress.table"] / 1000
    assert tiled == pytest.approx(us, rel=0.02)
    # ... and so is what this thread's sections covered
    assert sum(tally.values()) / 1000 == pytest.approx(us, rel=0.02)
    # execute_us = execute_self_us + the sections nested in the drain
    nested = sum(ns for name, ns in tally.items()
                 if name not in OUTSIDE + ("query.execute",)) / 1000
    assert grown["execute_us"] == pytest.approx(
        grown["execute_self_us"] + nested, rel=0.02)
    assert grown["execute_self_us"] == pytest.approx(
        tally["query.execute"] / 1000, abs=2)
    # planning launched nothing of its own: its whole time is its self
    assert grown["plan_us"] == pytest.approx(tally["query.plan"] / 1000,
                                             abs=2)
    assert grown["dispatch_us"] > 0 and grown["pull_wait_us"] > 0
    assert "host.dispatch" in tally and "egress.finish" in tally
    if plan == "join":
        assert grown["io_wait_us"] > 0  # the background decode's queue


def test_trace_us_is_the_traces_own_and_outside_scope_us(files):
    s = tpu_session(SMALL_BATCHES)
    _aggregate(s, files).to_arrow()
    _, grown, tally = _measured(_aggregate(s, files))
    assert grown["trace_us"] == 0 and "trace.drain" not in tally
    s = tpu_session({**SMALL_BATCHES, **TRACED})
    _aggregate(s, files).to_arrow()
    _, grown, tally = _measured(_aggregate(s, files))
    assert grown["trace_us"] > 0
    assert grown["trace_us"] == pytest.approx(tally["trace.drain"] / 1000,
                                              abs=2)
    # scope_us is the self time of the scope's sections: trace.drain,
    # nested in them, is not in it
    scope = tally["query.open"] + tally["query.close"]
    assert grown["scope_us"] == pytest.approx(scope / 1000, abs=4)


def _sleep_ms(ms):
    t = time.perf_counter_ns() + ms * 1_000_000
    while time.perf_counter_ns() < t:
        pass


def test_nested_sections_count_once():
    """A dispatch inside egress and a pull inside it: each microsecond
    lands in the innermost section, the enclosing one keeps its self."""
    before = tracing.phase_stats()
    with tracing.tally() as tally:
        t0 = time.perf_counter_ns()
        with tracing.section(tracing.SPAN_EGRESS_FINISH, own="egress_us"):
            _sleep_ms(4)
            with tracing.section(tracing.SPAN_HOST_DISPATCH,
                                 own="dispatch_us"):
                _sleep_ms(3)
            with tracing.section(tracing.SPAN_D2H_PULL,
                                 total="pull_wait_us"):
                _sleep_ms(2)
        wall = time.perf_counter_ns() - t0
    after = tracing.phase_stats()
    egress, dispatch, pull = (tally[tracing.SPAN_EGRESS_FINISH],
                              tally[tracing.SPAN_HOST_DISPATCH],
                              tally[tracing.SPAN_D2H_PULL])
    assert dispatch >= 3_000_000 and pull >= 2_000_000
    # egress keeps its own 4 ms, not the 9 ms it encloses
    assert 4_000_000 <= egress <= wall - dispatch - pull
    assert egress + dispatch + pull == pytest.approx(wall, rel=0.01)
    # the shared counters hold at least this thread's part (another
    # thread of the process may add to them meanwhile)
    for key, ns in (("egress_us", egress), ("dispatch_us", dispatch),
                    ("pull_wait_us", pull)):
        assert after[key] - before[key] >= ns // 1000 - 1, key


def test_threads_lose_no_update():
    """Sections close on many threads at once: each thread's stack is its
    own, and the shared counter gains exactly the self time they add."""
    closed, lock = [], threading.Lock()
    switch = sys.getswitchinterval()

    def work():
        mine = []
        for _ in range(300):
            with tracing.section("t.outer", own="egress_us") as outer:
                with tracing.section("t.inner", own="dispatch_us") as inner:
                    pass
            mine.append((outer.ns - inner.ns, inner.ns))
        with lock:
            closed.extend(mine)

    sys.setswitchinterval(1e-6)
    try:
        with tracing._PHASES_LOCK:
            before = dict(tracing._PHASES)
        threads = [threading.Thread(target=work) for _ in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
        with tracing._PHASES_LOCK:
            after = dict(tracing._PHASES)
    finally:
        sys.setswitchinterval(switch)
    assert len(closed) == 16 * 300
    assert after["egress_us"] - before["egress_us"] \
        == sum(o for o, _ in closed)
    assert after["dispatch_us"] - before["dispatch_us"] \
        == sum(i for _, i in closed)


def test_a_pull_inside_join_probe_counts_once(files):
    """``join.probe`` is a total beside the sections: the pull and the
    launches inside it are theirs, and the drain's self excludes them."""
    from spark_rapids_tpu.exec import joins
    s = tpu_session(SMALL_BATCHES)
    _join(s, files).to_arrow()
    probe0 = joins.join_stats()["probe_us"]
    wall, grown, tally = _measured(_join(s, files), runs=3)
    probe = joins.join_stats()["probe_us"] - probe0
    assert probe > 0 and "d2h.pull" in tally
    inside = grown["execute_self_us"] + sum(
        ns for name, ns in tally.items()
        if name not in OUTSIDE + ("query.execute",)) / 1000
    assert inside == pytest.approx(grown["execute_us"], rel=0.02)


def test_an_upload_inside_ici_ingest_counts_once():
    from spark_rapids_tpu.columnar.transfer import place_on_device
    from spark_rapids_tpu.parallel import mesh
    before, ici0 = tracing.phase_stats(), mesh.gather_stats()["ingest_us"]
    with tracing.tally() as tally:
        with mesh.phase("ingest_us"):
            place_on_device(np.arange(1 << 16), jax.devices()[0])
            _sleep_ms(2)
    ingest = mesh.gather_stats()["ingest_us"] - ici0
    dispatch = tracing.phase_stats()["dispatch_us"] - before["dispatch_us"]
    assert 0 < dispatch < ingest
    assert set(tally) == {"host.dispatch"}  # the ingest is no section


def test_the_stack_is_balanced_after_an_exception():
    with pytest.raises(ValueError):
        with tracing.section("outer", own="egress_us"):
            with tracing.section("inner", own="dispatch_us"):
                raise ValueError("boom")
    assert len(tracing._TLS.sections) == 0


def test_a_failed_query_leaves_no_section_open(files):
    s = tpu_session({**SMALL_BATCHES, **TRACED,
                     "spark.rapids.faults.transfer.d2h": "always"})
    with pytest.raises(Exception):
        _aggregate(s, files).to_arrow()
    assert len(tracing._TLS.sections) == 0
    assert tracing.current_span() == ""


def test_a_span_leaves_its_own_entry():
    """Spans closed out of order (as a generator resumed elsewhere would)
    take their own entry off the stack, never another's."""
    tracing.set_enabled(True)
    a, b = tracing.annotation("a"), tracing.annotation("b")
    a.__enter__()
    b.__enter__()
    a.__exit__(None, None, None)
    assert tracing.current_span() == "b"
    b.__exit__(None, None, None)
    assert tracing.current_span() == ""
    outer, inner = tracing.section("x"), tracing.section("y")
    outer.__enter__()
    inner.__enter__()
    outer.__exit__(None, None, None)
    assert len(tracing._TLS.sections) == 1
    inner.__exit__(None, None, None)
    assert len(tracing._TLS.sections) == 0


def test_every_phases_key_is_there_at_zero_in_a_fresh_process():
    code = ("import json; from spark_rapids_tpu.obs import registry; "
            "print(json.dumps(registry.snapshot()['phases']))")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=300,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__)))).stdout
    phases = json.loads(out.strip().splitlines()[-1])
    assert phases == dict.fromkeys(
        ("plan_us", "execute_us", "pull_wait_us", "blocking_reads",
         "dispatch_us", "io_wait_us", "egress_us", "execute_self_us",
         "scope_us", "trace_us"), 0)


# -- spans at a launch, under the switch ------------------------------------

_OWN = ("tracing.py", "metrics.py", "contextlib.py")


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_no_span_is_held_across_a_yield(files, monkeypatch, plan):
    """At every launch each open span's ``with`` is still running on the
    launching thread's stack (not a suspended generator's), every
    thread's span and section stacks end empty, and no starved key names
    a span that held a consumer."""
    holders = {}
    enter = tracing._Span.__enter__

    def recording(self):
        f = sys._getframe(1)
        while f is not None and os.path.basename(
                f.f_code.co_filename) in _OWN:
            f = f.f_back
        holders[id(self)] = f
        return enter(self)

    launches = []
    submit = service._submit

    def checking(record):
        live, f = set(), sys._getframe(1)
        while f is not None:
            live.add(id(f))
            f = f.f_back
        for span in tracing._TLS.spans:
            launches.append((span.name, id(holders[id(span)]) in live))
        return submit(record)

    ends = {}
    leave = tracing._leave

    def leaving(stack, entry):
        leave(stack, entry)
        kind = "spans" if stack is tracing._TLS.spans else "sections"
        ends[(threading.get_ident(), kind)] = len(stack)

    monkeypatch.setattr(tracing._Span, "__enter__", recording)
    monkeypatch.setattr(service, "_submit", checking)
    monkeypatch.setattr(tracing, "_leave", leaving)
    s = tpu_session({**SMALL_BATCHES, **CONFS[plan], **TRACED})
    mark = service.ledger_mark()
    PLANS[plan](s, files).to_arrow()
    assert launches and all(held for _, held in launches), [
        name for name, held in launches if not held]
    assert not {name for name, _ in launches} & {tracing.SPAN_HOST_DISPATCH}
    assert {(t, k) for (t, k), n in ends.items() if n} == set()
    assert {"sections", "spans"} <= {k for _, k in ends}
    starved = {span for row in service.ledger_since(mark)
               for span in row["starved_ms"]}
    assert not {span for span in starved if "overlap" in span}
