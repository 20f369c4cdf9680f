"""``dense_agg_batches_per_aggregate``: update batches that took the
sort-free route (the node metric ``pallasAggBatches``) per
``TpuHashAggregate`` node of the plans the window executed.  The file
loads and names a reader that is there, its entry lists the one cell, and
the reader gives 6 on a recorded plan list of the cell's pass (q1, q6:
six update batches under each aggregate) and 0 where no node took the
route (a program without the route leaves the metric out of a node's
non-zero metrics)."""

import json
import os
from types import SimpleNamespace

from conftest import BENCH, REPO, load

NAME = "dense_agg_batches_per_aggregate"


def spec():
    with open(os.path.join(BENCH, "metrics", NAME + ".json")) as fh:
        return json.load(fh)


def test_metric_file_and_entry():
    s = spec()
    assert set(s) == {"reader", "args"}
    assert os.path.exists(os.path.join(BENCH, "readers",
                                       s["reader"] + ".py"))
    assert s["args"] == {"metric": "pallasAggBatches",
                         "nodes": "TpuHashAggregate"}
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        entry = next(m for m in json.load(fh)["per_layer"]
                     if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "operators",
        "moves": "query_s", "workloads": ["tpch_sf1.agg"]}


def node(describe, **metrics):
    return {"name": describe.split(" ")[0], "describe": describe,
            "metrics": metrics}


def plans(batches):
    """One pass of the cell as ``run.py: plan_nodes`` records it: q1's
    plan and q6's, zero-valued metrics left out."""
    agg = {"pallasAggBatches": batches} if batches else {}
    q1 = [node("DeviceToHost"), node("TpuSort [l_returnflag ASC]"),
          node("TpuHashAggregate [keys=[l_returnflag, l_linestatus], "
               "aggs=[sum_qty]]", numOutputRows=4, **agg),
          node("TpuFilter [(l_shipdate <= 10471)]"),
          node("TpuParquetScan lineitem", scanCacheHits=1)]
    q6 = [node("DeviceToHost"),
          node("TpuHashAggregate [keys=[], aggs=[revenue]]",
               numOutputRows=1, **agg),
          node("TpuFilter [...]"),
          node("TpuParquetScan lineitem", scanCacheHits=1)]
    return [q1, q6]


def test_reader_counts_batches_per_aggregate_node():
    s = spec()
    read = load(os.path.join(BENCH, "readers", s["reader"] + ".py"),
                "dense_agg_" + s["reader"]).read
    assert read(SimpleNamespace(plans=plans(6)), **s["args"]) == 6
    assert read(SimpleNamespace(plans=plans(0)), **s["args"]) == 0
    # a served cell exposes no plan: nothing to read, nothing raised
    assert read(SimpleNamespace(plans=[]), **s["args"]) is None
