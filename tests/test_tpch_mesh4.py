"""TPC-H Q3 and Q18 on a four-wide mesh: the benchmark configuration
``tpch_sf1_mesh4`` (benchmark/configs/tpch_sf1_mesh4.json) through
``TpuSession`` under the configuration file's own ``conf``, at 60 k
lineitem rows on the eight forced host devices.

Every join and every grouped aggregate of both queries is a ``TpuMesh*Exec``
over four devices, nothing falls back, the answers equal the benchmark's
plain reference cell for cell, and the same queries under the default
configuration (width 1) give the same answers.  The three phases of a mesh
fragment are spans (``ici.ingest``, ``ici.collective``, ``ici.gather``) and
always-on counters (``ici.ingest_us``, ``collective_us``, ``gather_us``),
and the mesh programs have rows of their own in the dispatch ledger.
"""

import importlib.util
import json
import os
import sys

import pytest

from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.utils import tracing

multichip = pytest.mark.multichip

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
ROWS = 60_000
SEED = 7  # Q18 answers one row at this size; most seeds give one or none
# ``q18_250`` is Q18's builder and reference with QUANTITY lowered to 250,
# for this file only: at 60 k rows the clause's 300 leaves at most one
# order, and the outer GROUP BY, the ORDER BY and the string key want rows
QUERIES = ("q3", "q18", "q18_250")
WIDTHS = ("mesh4", "width1")
# joins and grouped aggregates in each query's text
FRAGMENTS = {"q3": (2, 1), "q18": (3, 2), "q18_250": (3, 2)}
PHASES = {"ingest_us": tracing.SPAN_ICI_INGEST,
          "collective_us": tracing.SPAN_ICI_COLLECTIVE,
          "gather_us": tracing.SPAN_ICI_GATHER}
PROGRAMS = ("exchange_mesh_aggregate", "exchange_mesh_join_count",
            "exchange_mesh_join")


def _load(*parts):
    path = os.path.join(BENCH, *parts)
    name = "mesh4_" + "_".join(parts).replace(".py", "").replace(".", "_")
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


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "tpch_sf1_mesh4.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def runs(tmp_path_factory, config):
    """Every query once under each width: its answer, the reference's, the
    executed plan's nodes, the spans it opened, and what the ``ici`` group of
    ``engine_stats()`` and the dispatch ledger grew by."""
    from spark_rapids_tpu.compile import service
    builders = _load("queries", "tpch_joins.py")
    reference = _load("reference", "tpch_joins.py")
    paths = _load("datagen", "tpch.py").generate(
        str(tmp_path_factory.mktemp("tpch_mesh4")), ROWS, SEED)
    out = {}
    seen = []
    enter = tracing._Span.__enter__

    def recording(self):
        seen.append(self.name)
        return enter(self)

    def quantity(q):
        return 250.0 if q == "q18_250" else 300.0

    tracing._Span.__enter__ = recording
    try:
        for width, conf in (("mesh4", config["conf"]), ("width1", {})):
            sess = TpuSession(dict(
                conf, **{"spark.rapids.sql.trace.enabled": "true"}))
            try:
                tables = {n: sess.read.parquet(p) for n, p in paths.items()}
                for q in QUERIES:
                    builders.Q18_QUANTITY = reference.Q18_QUANTITY = \
                        quantity(q)
                    before = sess.engine_stats()["ici"]
                    rows = {k: v["dispatches"]
                            for k, v in service.ledger_rows().items()}
                    del seen[:]
                    name = q.split("_")[0]
                    got = builders.build(name, tables).to_arrow()
                    after = sess.engine_stats()["ici"]
                    out[q, width] = {
                        "got": got,
                        "want": reference.QUERIES[name](paths),
                        "nodes": _nodes(sess.last_query_profile().root),
                        "spans": list(seen),
                        "ici": {k: after[k] - before[k] for k in after
                                if isinstance(after[k], int)},
                        "launches": {
                            k: v["dispatches"] - rows.get(k, 0)
                            for k, v in service.ledger_rows().items()}}
            finally:
                sess.stop()
    finally:
        tracing._Span.__enter__ = enter
        builders.Q18_QUANTITY = reference.Q18_QUANTITY = 300.0
    return out


def _compared(config, got, want, query):
    compare = _load("compare.py")
    g = config["guarantees"]
    r = compare.compare_tables(got, want, floor=g["float_floor"])
    name = query.split("_")[0]
    over = {c: gap for c, gap in r["gaps"].items()
            if gap > compare.gap_limit(g, f"{name}.{c}")}
    return r["exact_mismatches"], over


@multichip
@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("query", QUERIES)
def test_answer_equals_the_reference(runs, config, query, width):
    """Integers, dates, strings and the order of rows exactly; floats
    inside the configuration's limits."""
    run = runs[query, width]
    assert run["want"].num_rows > (0 if query != "q3" else 9)
    assert _compared(config, run["got"], run["want"], query) == (0, {})


@multichip
@pytest.mark.parametrize("query", QUERIES)
def test_width_1_equals_width_4(runs, config, query):
    assert _compared(config, runs[query, "mesh4"]["got"],
                     runs[query, "width1"]["got"], query) == (0, {})


@multichip
@pytest.mark.parametrize("query", QUERIES)
def test_every_join_and_grouped_aggregate_is_a_mesh_exec(runs, query):
    names = [n.name for n in runs[query, "mesh4"]["nodes"]]
    joins, aggregates = FRAGMENTS[query]
    assert names.count("TpuMeshHashJoinExec") == joins
    assert names.count("TpuMeshAggregateExec") == aggregates
    assert not [n for n in names if n.startswith("Cpu") or n in (
        "TpuHashJoinExec", "TpuBroadcastHashJoinExec",
        "TpuHashAggregateExec")]
    describes = [n.describe for n in runs[query, "mesh4"]["nodes"]
                 if n.name.startswith("TpuMesh")]
    assert all("mesh=4" in d for d in describes), describes


@multichip
@pytest.mark.parametrize("query", QUERIES)
def test_nothing_fell_back_and_the_exchanges_ran(runs, query):
    ici = runs[query, "mesh4"]["ici"]
    joins, aggregates = FRAGMENTS[query]
    assert ici["fallbacks"] == 0
    assert ici["exchanges"] == 2 * joins + aggregates
    assert ici["bytes"] > 0


@multichip
@pytest.mark.parametrize("counter", sorted(PHASES))
@pytest.mark.parametrize("query", QUERIES)
def test_each_phase_is_a_span_and_a_counter(runs, query, counter):
    run = runs[query, "mesh4"]
    assert PHASES[counter] in run["spans"]
    assert run["ici"][counter] > 0


@multichip
@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("query", QUERIES)
def test_mesh_programs_have_ledger_rows_of_their_own(runs, query, program):
    joins, aggregates = FRAGMENTS[query]
    want = aggregates if program.endswith("aggregate") else joins
    assert runs[query, "mesh4"]["launches"][program] == want


@multichip
@pytest.mark.parametrize("query", QUERIES)
def test_width_1_runs_no_mesh_fragment(runs, query):
    run = runs[query, "width1"]
    assert not [n.name for n in run["nodes"]
                if n.name.startswith("TpuMesh")]
    assert not [s for s in run["spans"] if s.startswith("ici.")]
    assert not any(run["ici"].values()), run["ici"]


@multichip
def test_a_mesh_aggregate_drains_only_the_columns_it_reads(runs):
    """Q18's subquery groups lineitem by its key; since PR 36 the planner
    narrows the scan itself to the two columns the aggregate reads, so the
    mesh fragment drains the scan as it is (``_over_read_columns`` has
    nothing left to cut)."""
    inner = [n for n in runs["q18", "mesh4"]["nodes"]
             if n.name == "TpuMeshAggregateExec"
             and "keys=[l_orderkey]" in n.describe]
    assert len(inner) == 1
    child = inner[0].children[0]
    assert child.name == "TpuParquetScanExec"
    assert "2/16 columns" in child.describe


def test_the_configuration_sets_no_key_of_its_own(config):
    """Mode and width, both keys every session has; four chips."""
    from spark_rapids_tpu.conf import TpuConf
    assert config["chips"] == 4
    assert config["conf"]["spark.rapids.shuffle.mode"] == "ici"
    assert int(config["conf"]["spark.rapids.shuffle.ici.devices"]) == 4
    assert set(config["conf"]) <= {
        "spark.rapids.shuffle.mode", "spark.rapids.shuffle.ici.devices",
        "spark.rapids.shuffle.ici.shardedScan.enabled"}
    conf = TpuConf(config["conf"])
    assert conf.shuffle_mode == "ici" and conf.ici_devices == 4
