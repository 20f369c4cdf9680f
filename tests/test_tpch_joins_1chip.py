"""TPC-H Q3, Q5 and Q18 on one device: the benchmark configuration
``tpch_sf1_joins`` (benchmark/configs/tpch_sf1_joins.json) through
``TpuSession`` under the configuration file's own ``conf`` (empty: the
default engine), at 60 k lineitem rows, two passes of the cell's stream.

Every node of every plan is a ``Tpu*`` node and nothing falls back; the
answers equal the benchmark's plain reference (``reference/tpch_power.py``)
cell for cell on three seeds, and the bfloat16 control fails ``q5.revenue``.
A hot pass of ``[q3, q5, q18]`` asks the device scan cache for twelve scan
shapes thirteen times and is answered once from its eight entries (every
scan reads only the columns its query uses, so no scan of q5 is one of
q18's); a hot pass of the cell's own stream, ``[q3, q5]`` (Q18 was cut for
time), goes round nine shapes and is answered never: the arithmetic the
cell's ``why`` states, pinned here so that a change to the cache's key or
policy shows in a test before it shows on the chip.  A pass of ``[q3, q5]``
asks its readers for 26 of its tables' 80 columns (``scan.columns_read`` /
``columns_total``), hit or miss.  The
``join`` group of ``engine_stats()`` counts each join once under the route
that produced its rows; ``scan.decode_us``, ``upload_us`` and
``upload_bytes`` move on a miss and stand still on a hit; the four spans
(``join.build``, ``join.probe``, ``scan.decode``, ``scan.upload``) are
emitted under ``spark.rapids.sql.trace.enabled``.  With the broadcast
threshold scaled with the data, as at SF1 on the chip, Q3's and Q5's
lineitem joins build on the filtered orders, their smaller side with
unique keys, and still answer what the reference does.
"""

import contextlib
import importlib.util
import io
import json
import os
import sys

import pytest

from spark_rapids_tpu.exec.joins import JOIN_ROUTES
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.utils import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
ROWS = 60_000
SEEDS = (7, 11, 2**31 + 5)  # the first is traced, and answers a row of Q18
QUERIES = ("q3", "q5", "q18")
CELL_QUERIES = ("q3", "q5")  # what tpch_sf1_joins.power streams
PASSES = ("cold", "hot")
# joins in each query's text, and scans (Q18 names lineitem twice)
JOINS = {"q3": 2, "q5": 5, "q18": 3}
SCANS = {"q3": 3, "q5": 6, "q18": 4}
HOT_HITS = {"q3": 0, "q5": 0, "q18": 1}
# file columns each query's scans read, of their tables' (planner.py
# prune_scan_columns); Q18 reads lineitem twice
COLUMNS = {"q3": (2 + 4 + 4, 8 + 9 + 16),
           "q5": (2 + 3 + 4 + 2 + 3 + 2, 8 + 9 + 16 + 7 + 4 + 3),
           "q18": (2 + 4 + 2 + 2, 8 + 9 + 16 + 16)}
FALLBACKS = ("ici.fallbacks", "ooc.fallbacks", "compile.aotFailures",
             "fusion.warm_errors")
MISS_COSTS = ("decode_us", "upload_us", "upload_bytes")
SCALED_BROADCAST = 10 * 1024 * 1024 * ROWS // 6_000_000  # SF1's 10 MB
SPANS = (tracing.SPAN_JOIN_BUILD, tracing.SPAN_JOIN_PROBE,
         tracing.SPAN_SCAN_DECODE, tracing.SPAN_SCAN_UPLOAD)


def _load(*parts):
    path = os.path.join(BENCH, *parts)
    name = "joins1_" + "_".join(parts).replace(".py", "").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def _nodes(root):
    stack, out = [root], []
    while stack:
        node = stack.pop()
        out.append(node)
        stack.extend(node.children)
    return out


def _at(stats, path):
    for key in path.split("."):
        stats = stats[key]
    return stats


def _grown(after, before, group):
    return {k: after[group][k] - before[group][k] for k in after[group]}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "tpch_sf1_joins.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def runs(tmp_path_factory, config):
    """Two passes of the stream on every seed: each query's answer, the
    reference's and the control's, the executed plan's nodes, what
    ``explain()`` tags, the spans it opened, and what the ``join`` and
    ``scan`` groups and the fallback counters grew by."""
    builders = _load("queries", "tpch_power.py")
    reference = _load("reference", "tpch_power.py")
    datagen = _load("datagen", "tpch.py")
    out = {}
    seen = []
    enter = tracing._Span.__enter__

    def recording(self):
        seen.append(self.name)
        return enter(self)

    tracing._Span.__enter__ = recording
    try:
        for seed in SEEDS:
            paths = datagen.generate(
                str(tmp_path_factory.mktemp(f"tpch_joins_{seed}")), ROWS,
                seed)
            conf = dict(config["conf"])
            if seed == SEEDS[0]:
                conf["spark.rapids.sql.trace.enabled"] = "true"
            sess = TpuSession(conf)
            try:
                tables = {n: sess.read.parquet(p) for n, p in paths.items()}
                for when in PASSES:
                    for q in QUERIES:
                        before = sess.engine_stats()
                        del seen[:]
                        df = builders.build(q, tables)
                        got = df.to_arrow()
                        after = sess.engine_stats()
                        with contextlib.redirect_stdout(io.StringIO()):
                            explained = df.explain()
                        out[seed, when, q] = {
                            "got": got,
                            "want": reference.QUERIES[q](paths),
                            "control": reference.QUERIES[q](paths,
                                                            "bfloat16"),
                            "nodes": _nodes(sess.last_query_profile().root),
                            "tagged": [ln.strip()
                                       for ln in explained.splitlines()
                                       if ln.strip().startswith(("!",
                                                                 "Cpu"))],
                            "spans": list(seen),
                            "join": _grown(after, before, "join"),
                            "scan": _grown(after, before, "scan"),
                            "fallbacks": {c: _at(after, c) - _at(before, c)
                                          for c in FALLBACKS}}
                # the cell's stream, Q18 cut: the second pass is hot
                for when in ("cut_cold", "cut"):
                    for q in CELL_QUERIES:
                        before = sess.engine_stats()
                        builders.build(q, tables).to_arrow()
                        out[seed, when, q] = _grown(sess.engine_stats(),
                                                    before, "scan")
                # one small table scanned twice: whatever the first did,
                # the second is a hit
                tables["region"].to_arrow()
                before = sess.engine_stats()
                tables["region"].to_arrow()
                out[seed, "hit"] = _grown(sess.engine_stats(), before,
                                          "scan")
            finally:
                sess.stop()
            out[seed, "paths"] = paths
    finally:
        tracing._Span.__enter__ = enter
    return out


def _compared(config, got, want, query):
    compare = _load("compare.py")
    g = config["guarantees"]
    r = compare.compare_tables(got, want, floor=g["float_floor"])
    over = {c: gap for c, gap in r["gaps"].items()
            if gap > compare.gap_limit(g, f"{query}.{c}")}
    return r["exact_mismatches"], over


@pytest.mark.parametrize("when", PASSES)
@pytest.mark.parametrize("query", QUERIES)
@pytest.mark.parametrize("seed", SEEDS)
def test_answer_equals_the_reference(runs, config, seed, query, when):
    """Integers, dates, strings and the order of rows exactly; floats
    inside the configuration's limits; with misses and without."""
    run = runs[seed, when, query]
    if query != "q18":  # Q18's 300 leaves an order or none at this size
        assert run["want"].num_rows > (9 if query == "q3" else 0)
    assert _compared(config, run["got"], run["want"], query) == (0, {})


def test_q18_answers_a_row_on_the_first_seed(runs):
    assert runs[SEEDS[0], "hot", "q18"]["want"].num_rows >= 1


@pytest.mark.parametrize("query", QUERIES)
@pytest.mark.parametrize("seed", SEEDS)
def test_every_node_is_on_the_device_and_nothing_fell_back(runs, seed,
                                                           query):
    for when in PASSES:
        run = runs[seed, when, query]
        names = [n.name for n in run["nodes"]]
        assert not [n for n in names if n.startswith("Cpu")], names
        assert run["tagged"] == []
        assert not any(run["fallbacks"].values()), run["fallbacks"]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_bfloat16_control_fails_q5_revenue(runs, config, seed):
    run = runs[seed, "hot", "q5"]
    _, over = _compared(config, run["control"], run["want"], "q5")
    assert "revenue" in over, over
    limit = config["guarantees"]["float_rel_gap_limits"]["q5.revenue"]
    assert over["revenue"] > 10 * limit


@pytest.mark.parametrize("seed", SEEDS)
def test_a_hot_pass_reads_13_lookups_and_1_hit(runs, seed):
    """Twelve scan shapes a pass against ``runtime._ScanCache(max_entries=8)``
    under LRU: q3 none of 3, q5 none of 6, q18 its second read of lineitem,
    which asks for the first's two columns."""
    scans = {q: runs[seed, "hot", q]["scan"] for q in QUERIES}
    assert {q: s["cache_lookups"] for q, s in scans.items()} == SCANS
    assert {q: s["cache_hits"] for q, s in scans.items()} == HOT_HITS
    assert sum(s["cache_lookups"] for s in scans.values()) == 13
    assert sum(s["cache_hits"] for s in scans.values()) == 1
    assert all(s["decoded_bytes"] > 0 for s in scans.values())


@pytest.mark.parametrize("query,when", [
    (q, w) for w in PASSES for q in QUERIES] + [
    (q, w) for w in ("cut_cold", "cut") for q in CELL_QUERIES])
@pytest.mark.parametrize("seed", SEEDS)
def test_scans_read_only_the_columns_their_query_uses(runs, seed, query,
                                                      when):
    """The column counters move once a scan, on a miss (``cut``: none
    hits) as on a hit (Q18's second lineitem read in ``hot``)."""
    run = runs[seed, when, query]
    scan = run["scan"] if "scan" in run else run
    assert (scan["columns_read"], scan["columns_total"]) == COLUMNS[query]


def test_a_pass_of_the_cells_stream_reads_26_of_80_columns(runs):
    scans = [runs[SEEDS[0], "cut", q] for q in CELL_QUERIES]
    assert sum(s["columns_read"] for s in scans) == 26
    assert sum(s["columns_total"] for s in scans) == 80


@pytest.mark.parametrize("seed", SEEDS)
def test_a_hit_counts_its_columns(runs, seed):
    hit = runs[seed, "hit"]
    assert (hit["cache_hits"], hit["columns_read"],
            hit["columns_total"]) == (1, 3, 3)


@pytest.mark.parametrize("seed", SEEDS)
def test_a_hot_pass_of_the_cut_stream_reads_9_lookups_and_no_hit(runs, seed):
    """Nine scan shapes go round an LRU of eight: each is evicted before
    its turn comes again, so the cell's window decodes every table."""
    scans = {q: runs[seed, "cut", q] for q in CELL_QUERIES}
    assert {q: s["cache_lookups"] for q, s in scans.items()} == \
        {q: SCANS[q] for q in CELL_QUERIES}
    assert sum(s["cache_lookups"] for s in scans.values()) == 9
    assert sum(s["cache_hits"] for s in scans.values()) == 0
    assert all(s["decoded_bytes"] > 0 and s["decode_us"] > 0
               for s in scans.values())


@pytest.mark.parametrize("when", PASSES)
@pytest.mark.parametrize("query", QUERIES)
@pytest.mark.parametrize("seed", SEEDS)
def test_the_join_group_adds_up(runs, seed, query, when):
    run = runs[seed, when, query]
    join, names = run["join"], [n.name for n in run["nodes"]]
    planned = names.count("TpuHashJoinExec") \
        + names.count("TpuBroadcastHashJoinExec")
    assert planned == JOINS[query]
    assert join["joins"] == planned == sum(join[r] for r in JOIN_ROUTES)
    assert join["broadcast"] == names.count("TpuBroadcastHashJoinExec")
    assert join["out_slots"] >= join["out_rows"] > 0
    assert join["build_rows"] > 0 and join["stream_rows"] > 0
    assert join["probe_us"] > 0 and join["build_us"] >= 0
    # the sync-free routes are the ones that pulled no count of their own
    fast = sum(n.metrics.get("fkFastPathBatches", 0) > 0
               for n in run["nodes"])
    assert join["fk"] + join["fk_dense"] == fast


@pytest.fixture(scope="module")
def scaled(runs, config):
    """Q3 and Q5 on every seed with the broadcast threshold scaled with
    the data (SF1's 10 MB at 60 k rows), so that, as at SF1 on the chip,
    lineitem is too large to broadcast and each query's lineitem join
    is a ``TpuHashJoinExec`` over two sides the planner cannot size:
    each query's answer and what its ``join`` group grew by."""
    builders = _load("queries", "tpch_power.py")
    out = {}
    for seed in SEEDS:
        conf = dict(config["conf"])
        conf["spark.sql.autoBroadcastJoinThreshold"] = str(SCALED_BROADCAST)
        sess = TpuSession(conf)
        try:
            tables = {n: sess.read.parquet(p)
                      for n, p in runs[seed, "paths"].items()}
            for q in CELL_QUERIES:
                before = sess.engine_stats()
                got = builders.build(q, tables).to_arrow()
                out[seed, q] = {
                    "got": got,
                    "nodes": [n.name for n in
                              _nodes(sess.last_query_profile().root)],
                    "join": _grown(sess.engine_stats(), before, "join")}
        finally:
            sess.stop()
    return out


@pytest.mark.parametrize("query", CELL_QUERIES)
@pytest.mark.parametrize("seed", SEEDS)
def test_a_lineitem_join_builds_on_its_smaller_side(runs, scaled, config,
                                                    seed, query):
    """Each query's lineitem join builds on the filtered orders, whose
    keys are unique, and streams lineitem past it on a sync-free route
    (``exec/joins.py: _peek_smaller_left``): one ``build_left`` a query,
    every join on ``fk`` or ``fk_dense``, and the reference's answer."""
    run = scaled[seed, query]
    join = run["join"]
    assert run["nodes"].count("TpuHashJoinExec") == 1
    assert join["joins"] == JOINS[query]
    assert join["build_left"] == 1
    assert join["fk"] + join["fk_dense"] == join["joins"]
    assert join["out_slots"] >= join["out_rows"] > 0
    want = runs[seed, "hot", query]["want"]
    assert _compared(config, run["got"], want, query) == (0, {})


@pytest.mark.parametrize("counter", MISS_COSTS)
@pytest.mark.parametrize("seed", SEEDS)
def test_a_miss_moves_the_scan_clocks_and_a_hit_does_not(runs, seed,
                                                         counter):
    missed = runs[seed, "hot", "q3"]["scan"]  # three lookups, no hit
    assert missed["cache_hits"] == 0 and missed[counter] > 0
    hit = runs[seed, "hit"]
    assert hit["cache_lookups"] == hit["cache_hits"] == 1
    assert hit[counter] == 0 and hit["decoded_bytes"] == 0


@pytest.mark.parametrize("span", SPANS)
@pytest.mark.parametrize("query", QUERIES)
def test_the_sections_are_spans_under_the_trace_switch(runs, query, span):
    assert span in runs[SEEDS[0], "hot", query]["spans"]
    assert span not in runs[SEEDS[1], "hot", query]["spans"]


def test_the_configuration_sets_no_key(config):
    """The default engine: one chip, nothing set for the cell."""
    assert config["chips"] == 1 and config["conf"] == {}
    assert config["queries"] == list(CELL_QUERIES)
    with open(os.path.join(BENCH, "workloads",
                           "tpch_sf1_joins.power.json")) as fh:
        assert json.load(fh)["queries"] == list(CELL_QUERIES)


def test_the_join_group_loses_no_update_under_threads():
    """Joins end on many threads at once (a server's workers): every one
    is counted, under one route, with its rows."""
    import sys
    import threading

    import pyarrow as pa

    from spark_rapids_tpu.exec.joins import join_stats
    workers, rounds, rows = 16, 4, 64
    sess = TpuSession({})
    try:
        left = sess.create_dataframe(pa.table(
            {"k": list(range(rows)), "a": list(range(rows))}))
        right = sess.create_dataframe(pa.table(
            {"k": list(range(0, rows, 2)), "b": list(range(rows // 2))}))
        assert left.join(right, "k").to_arrow().num_rows == rows // 2
        before = join_stats()
        failed = []

        def work():
            try:
                for _ in range(rounds):
                    got = left.join(right, "k").to_arrow()
                    assert got.num_rows == rows // 2
            except BaseException as e:  # reported by the test's thread
                failed.append(e)

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=work) for _ in range(workers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
        finally:
            sys.setswitchinterval(interval)
        assert not failed and not any(t.is_alive() for t in threads)
        grown = {k: v - before[k] for k, v in join_stats().items()}
        assert grown["joins"] == workers * rounds
        assert sum(grown[r] for r in JOIN_ROUTES) == workers * rounds
        assert grown["stream_rows"] == workers * rounds * rows
        assert grown["out_slots"] >= grown["out_rows"] > 0
    finally:
        sess.stop()
