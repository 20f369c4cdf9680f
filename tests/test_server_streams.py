"""TPC-H's throughput test at a test's size (ISSUE 27): two client threads
through ``SessionServer`` over the prepared Q6 and Q1 templates the
benchmark's cell ``tpch_sf1_served.throughput`` sends, with bindings from
the two grids of clause 2.4 (benchmark/queries/*.params.json), at 60 k
lineitem rows.  Every answer equals the plain reference's for ITS OWN
binding (benchmark/reference/tpch_streams.py, no engine import), no
binding after a template's first compiles anything, every binding after
the first reads the device scan cache, and the serving layer's always-on
counters and spans record each request.
"""

import importlib.util
import itertools
import os
import sys
import threading

import pytest

from spark_rapids_tpu.server import stats as server_stats
from spark_rapids_tpu.utils import tracing
from tests.compare import tpu_session

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
ROWS, SEED = 60_000, 2**31 + 27
CELL = {"kind": "served", "streams": 2, "cycle": ["q6", "q1"],
        "stream_offset": 0}
TEMPLATES = ("q1", "q6")


def _bench(*parts):
    path = os.path.join(BENCH, *parts)
    name = "streams_test_" + "_".join(parts).replace(".py", "")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def tpch(tmp_path_factory):
    """The benchmark's own generator, traffic, reference and comparison,
    and its tables at the rehearsal's size."""
    out = str(tmp_path_factory.mktemp("tpch_streams"))
    paths = _bench("datagen", "tpch.py").generate(out, ROWS, SEED)
    traffic = _bench("traffic.py").ServedTraffic(
        CELL, os.path.join(BENCH, "queries"), SEED)
    return {"paths": paths, "traffic": traffic,
            "reference": _bench("reference", "tpch_streams.py"),
            "compare": _bench("compare.py")}


def _serving(tpch, conf=None):
    sess = tpu_session(conf or {})
    sess.read.parquet(tpch["paths"]["lineitem"]) \
        .create_or_replace_temp_view("lineitem")
    server = sess.server()
    stmts = {n: server.prepare(tpch["traffic"].sql[n]) for n in TEMPLATES}
    return sess, server, stmts


def _same(tpch, name, params, got) -> bool:
    want = tpch["reference"].TEMPLATES[name](tpch["paths"], params)
    r = tpch["compare"].compare_tables(got, want)
    return r["exact_mismatches"] == 0 and r["gaps"] \
        and max(r["gaps"].values()) < 1e-9


def test_two_streams_every_answer_is_its_own_bindings(tpch):
    sess, server, stmts = _serving(tpch)
    traffic = tpch["traffic"]
    try:
        for name in TEMPLATES:
            warm = traffic.warm(name)
            got = server.submit(stmts[name], params=warm).result(600)
            assert _same(tpch, name, warm, got), name
        before = sess.engine_stats()
        answers = [[] for _ in range(traffic.streams)]

        def client(i):
            for name, params in itertools.islice(traffic.stream(i), 6):
                ticket = server.submit(stmts[name], params=params)
                answers[i].append((name, params, ticket.result(600),
                                   ticket.cache_hit))

        threads = [threading.Thread(target=client, args=(i,),
                                    name=f"test-stream-{i}")
                   for i in range(traffic.streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        after = sess.engine_stats()
    finally:
        sess.stop()
    sent = [(n, p) for rs in answers for n, p, _, _ in rs]
    assert len(sent) == 12 and len(set(sent)) == 12  # nothing repeats
    for rs in answers:
        assert [n for n, _, _, _ in rs] == ["q6", "q1"] * 3
        for name, params, got, cache_hit in rs:
            assert not cache_hit
            assert _same(tpch, name, params, got), (name, params)
    # a neighbour's answer would not do: bindings of one template differ
    q1 = [(p, t) for rs in answers for n, p, t, _ in rs if n == "q1"]
    assert not _same(tpch, "q1", q1[0][0], q1[1][1])

    def grown(path):
        a, b = before, after
        for key in path.split("."):
            a, b = a[key], b[key]
        return b - a

    # the warm binding of each template compiled and decoded; none after
    assert grown("kernel_cache.misses") == 0
    assert grown("fusion.cache_misses") == 0
    assert grown("scan.cache_lookups") == 12
    assert grown("scan.cache_hits") == 12
    assert grown("scan.decoded_bytes") == 0
    assert grown("server.completed") == 12
    assert grown("server.cache_hits") == 0
    assert grown("server.execute_us") > 0
    assert grown("server.admit_wait_us") >= 0
    assert after["server"]["admit_wait_us"] > 0  # one request queued > 0 us
    # a request is executing for at least as long as its query is
    assert grown("server.execute_us") >= grown("phases.execute_us")


@pytest.mark.parametrize("name", TEMPLATES)
def test_every_binding_runs_the_warm_bindings_programs(tpch, name):
    """One template alone, bindings one after another: the kernel caches
    and JAX's own compile events stay flat after the first binding (Q1's
    DELTA sits under ``date_sub`` and is hoisted out of the kernel key
    like any other prepared parameter)."""
    import jax.monitoring
    compiles = []

    def listener(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    sess, server, stmts = _serving(tpch)
    grid = tpch["traffic"].grid[name]
    try:
        server.submit(stmts[name], params=grid[-1]).result(600)
        warm = (sess.engine_stats()["kernel_cache"]["misses"],
                len(compiles))
        for params in grid[:4]:
            got = server.submit(stmts[name], params=params).result(600)
            assert _same(tpch, name, params, got), params
        assert (sess.engine_stats()["kernel_cache"]["misses"],
                len(compiles)) == warm
    finally:
        sess.stop()
        jax.monitoring.unregister_event_duration_listener(listener)


def test_a_traced_request_is_spanned_by_the_server(tpch, monkeypatch):
    seen = []
    enter = tracing._Span.__enter__

    def recording(self):
        seen.append((threading.current_thread().name, self.name))
        return enter(self)

    monkeypatch.setattr(tracing._Span, "__enter__", recording)
    was = tracing.is_enabled()
    sess, server, stmts = _serving(
        tpch, {"spark.rapids.sql.trace.enabled": "true"})
    try:
        params = tpch["traffic"].grid["q6"][0]
        server.submit(stmts["q6"], params=params,
                      tenant="dashboards").result(600)
    finally:
        sess.stop()
    assert tracing.is_enabled() == was  # the request's scope closed
    workers = {t for t, _ in seen if t.startswith("srt-server-worker-")}
    assert len(workers) == 1
    names = [n for t, n in seen if t in workers]
    assert names[:2] == ["server.execute:dashboards", "server.admit_wait"]
    for inner in ("server.resolve", "server.cache_key", "query.plan",
                  "query.execute"):
        assert inner in names, inner
    assert names.index("server.resolve") < names.index("query.plan")
    assert server_stats.global_stats()["execute_us"] > 0
