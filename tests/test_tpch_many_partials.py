"""TPC-H Q1 and Q6 with as many partials an aggregate as at SF10: the
benchmark configuration ``tpch_sf10`` (benchmark/configs/tpch_sf10.json)
through ``TpuSession`` and the benchmark's own builders, at a few thousand
lineitem rows with the batch sizes lowered so that each aggregate updates
58 batches, SF10's count at 2^20 rows a batch: seven full groups of
``GROUP_MEMBERS`` and a remainder of two, so the merge folds 58 partials.

The answers equal the plain reference (``benchmark/reference/tpch.py``)
under the configuration's limits on three seeds; every update batch took
the dense route; ``roles.agg_update_dispatches`` grows by one launch a
group, ceil(58 / 8) = 8 an aggregate, while the merge, the evaluation and
the other aggregate programs move only the ``aggregate`` family; every
launch but the first goes out before the one before it is confirmed
(``overlappedUpdateLaunches`` 7); and under the trace switch
``roles.agg_update_device_us`` grows, off it not.
"""

import importlib.util
import json
import math
import os
import sys

import pytest

from spark_rapids_tpu.compile import service
from spark_rapids_tpu.exec.aggregate import GROUP_MEMBERS
from spark_rapids_tpu.session import TpuSession

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
ROWS = 6_000  # nominal lineitem rows: 5,997 made
BATCHES = 58  # an aggregate's update batches at SF10: ceil(59,999,997 / 2^20)
SEEDS = (3, 2**31 + 61, 97)  # the first is traced
QUERIES = ("q1", "q6")
UPDATES = ("aggregate_masked_pallas_update", "aggregate_masked_update")


def _load(*parts):
    path = os.path.join(BENCH, *parts)
    name = "partials_" + "_".join(parts).replace(".py", "").replace(".", "_")
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


def _counters(stats):
    """The ``programs`` and ``roles`` groups in one flat dict: their keys
    are apart."""
    return {**stats["programs"], **stats["roles"]}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "tpch_sf10.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def runs(tmp_path_factory, config):
    """A cold and a hot run of each query on every seed: the answer, the
    reference's, the batches its aggregate updated, and what the
    ``programs`` and ``roles`` groups and the ledger's aggregate rows grew
    by in the hot run."""
    builders = _load("queries", "tpch.py")
    reference = _load("reference", "tpch.py")
    datagen = _load("datagen", "tpch.py")
    lineitem = datagen.table_rows(ROWS)["lineitem"]
    per_batch = -(-lineitem // BATCHES)
    out = {}
    for seed in SEEDS:
        paths = datagen.generate(
            str(tmp_path_factory.mktemp(f"tpch_partials_{seed}")), ROWS,
            seed)
        conf = dict(config["conf"])
        conf.update({"spark.rapids.sql.reader.batchSizeRows": per_batch,
                     "spark.rapids.sql.batchSizeRows": per_batch})
        if seed == SEEDS[0]:
            conf["spark.rapids.sql.trace.enabled"] = "true"
        sess = TpuSession(conf)
        try:
            tables = {n: sess.read.parquet(p) for n, p in paths.items()}
            for q in QUERIES:
                builders.build(q, tables).to_arrow()  # cold: compiles
                before = _counters(sess.engine_stats())
                rows_before = service.ledger_rows()
                got = builders.build(q, tables).to_arrow()
                after = _counters(sess.engine_stats())
                rows_after = service.ledger_rows()
                nodes = _nodes(sess.last_query_profile().root)
                out[seed, q] = {
                    "got": got, "want": reference.QUERIES[q](paths),
                    "batches": sum(n.metrics.get("pallasAggBatches", 0)
                                   for n in nodes),
                    "overlapped": sum(
                        n.metrics.get("overlappedUpdateLaunches", 0)
                        for n in nodes),
                    "grown": {k: after[k] - before[k] for k in after},
                    "ledger": {
                        p: r["dispatches"]
                        - rows_before.get(p, {}).get("dispatches", 0)
                        for p, r in rows_after.items()
                        if r["family"] == "aggregate"}}
        finally:
            sess.stop()
    return out


@pytest.mark.parametrize("query", QUERIES)
@pytest.mark.parametrize("seed", SEEDS)
def test_answer_equals_the_reference(runs, config, seed, query):
    """Integers, strings and the order of rows exactly; every float column
    inside ``tpch_sf10``'s limit."""
    compare = _load("compare.py")
    g = config["guarantees"]
    run = runs[seed, query]
    assert run["want"].num_rows == (4 if query == "q1" else 1)
    r = compare.compare_tables(run["got"], run["want"],
                               floor=g["float_floor"])
    over = {c: gap for c, gap in r["gaps"].items()
            if gap > compare.gap_limit(g, f"{query}.{c}")}
    assert (r["exact_mismatches"], over) == (0, {})


@pytest.mark.parametrize("query", QUERIES)
@pytest.mark.parametrize("seed", SEEDS)
def test_an_aggregate_updates_sf10s_count_of_batches(runs, seed, query):
    """Every one of the 58 batches took the dense update, seven full groups
    of eight and one of two."""
    assert BATCHES // GROUP_MEMBERS == 7 and BATCHES % GROUP_MEMBERS == 2
    assert runs[seed, query]["batches"] == BATCHES


@pytest.mark.parametrize("query", QUERIES)
@pytest.mark.parametrize("seed", SEEDS)
def test_the_update_role_counts_one_launch_a_group_and_no_merge(runs, seed,
                                                                query):
    run = runs[seed, query]
    groups = math.ceil(BATCHES / GROUP_MEMBERS)
    assert run["grown"]["agg_update_dispatches"] == groups == 8
    ledger = run["ledger"]
    assert sum(ledger.get(p, 0) for p in UPDATES) == groups
    # the 58 partials are merged and evaluated: launches of the
    # ``aggregate`` family that the update's role does not count
    assert ledger["aggregate_merge"] >= 1
    assert ledger["aggregate_evaluate"] >= 1
    assert run["grown"]["aggregate_dispatches"] == sum(ledger.values()) \
        > groups


@pytest.mark.parametrize("query", QUERIES)
@pytest.mark.parametrize("seed", SEEDS)
def test_every_update_launch_but_the_first_overlaps_the_one_before(
        runs, seed, query):
    """The aggregate waits on a group only once the next has gone out:
    seven of its eight launches go out with the one before unconfirmed."""
    groups = math.ceil(BATCHES / GROUP_MEMBERS)
    assert runs[seed, query]["overlapped"] == groups - 1 == 7


@pytest.mark.parametrize("query", QUERIES)
def test_the_update_device_time_rides_the_trace_switch(runs, query):
    traced, untraced = runs[SEEDS[0], query], runs[SEEDS[1], query]
    assert 0 < traced["grown"]["agg_update_device_us"] \
        <= traced["grown"]["aggregate_device_us"]
    assert untraced["grown"]["agg_update_device_us"] == 0
    assert untraced["grown"]["agg_update_dispatches"] == \
        traced["grown"]["agg_update_dispatches"]
