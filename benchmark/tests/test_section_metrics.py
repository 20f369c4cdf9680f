"""The per-layer metrics that read the host-time sections of the
``phases`` group of ``engine_stats()`` (docs/observability.md,
"Sections"): their files load and name readers that exist, each reads a
hand-built run as its arithmetic says, a program without the counters
leaves them out, and a traced rehearsal on the CPU reports all five
(structure only: a CPU number is never a chip's)."""

import json
import os
from types import SimpleNamespace

import pytest
from conftest import BENCH, REPO, load

# metric -> (reader, the counters it reads)
NEW = {
    "dispatch_ms_per_query": ("engine_stat_growth", ["phases.dispatch_us"]),
    "io_wait_ms_per_query": ("engine_stat_growth", ["phases.io_wait_us"]),
    "egress_ms_per_query": ("engine_stat_growth", ["phases.egress_us"]),
    "bookkeeping_ms_per_query": ("engine_stat_growth",
                                 ["phases.scope_us"]),
    "execute_self_share": ("engine_stat_sum_growth",
                           ["phases.execute_self_us"]),
}
CELLS = ["tpch_sf1.agg", "tpch_sf1_served.throughput",
         "tpch_sf1_mesh4.shuffle", "tpch_sf1_joins.power"]


def spec_of(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as fh:
        return json.load(fh)


def read(name, run):
    spec = spec_of(name)
    module = load(os.path.join(BENCH, "readers", spec["reader"] + ".py"),
                  "section_metrics_" + spec["reader"])
    return module.read(run, **spec["args"])


@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_file_and_entry(name):
    spec = spec_of(name)
    reader, paths = NEW[name]
    assert spec["reader"] == reader
    assert spec["args"].get("paths", [spec["args"].get("path")]) == paths
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "query_s" and entry["workloads"] == CELLS
    moves = next(m for m in bench["end_to_end"] if m["name"] == "query_s")
    assert set(CELLS) <= set(moves["workloads"])


def run_with(before, after, done=4):
    return SimpleNamespace(
        stats_before={"phases": before}, stats_after={"phases": after},
        executions=[{"ok": True}] * done + [{"ok": False}])


BEFORE = {"dispatch_us": 1_000, "io_wait_us": 0, "egress_us": 500,
          "scope_us": 40, "execute_self_us": 2_000, "execute_us": 9_000}
AFTER = {"dispatch_us": 37_000, "io_wait_us": 12_000, "egress_us": 6_500,
         "scope_us": 8_040, "execute_self_us": 32_000, "execute_us": 129_000}


@pytest.mark.parametrize("name,value", [
    ("dispatch_ms_per_query", 9.0),      # 36 ms over 4 queries
    ("io_wait_ms_per_query", 3.0),
    ("egress_ms_per_query", 1.5),
    ("bookkeeping_ms_per_query", 2.0),
    ("execute_self_share", 25.0),        # 30 ms of the drain's 120
])
def test_each_reads_a_hand_built_run(name, value):
    assert read(name, run_with(BEFORE, AFTER)) == pytest.approx(value)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_counters_leaves_it_out(name):
    parent = {"plan_us": 5, "execute_us": 9_000, "pull_wait_us": 1,
              "blocking_reads": 0}
    assert read(name, run_with(parent, dict(parent, execute_us=19_000))) \
        is None
    # and a window that completed nothing (or drained for no time)
    assert read(name, run_with(BEFORE, BEFORE, done=0)) is None


@pytest.mark.parametrize("cell", ["tpch_sf1.agg",
                                  "tpch_sf1_served.throughput"])
def test_traced_rehearsal_reports_them(copy, capsys, cell):
    """(the mesh and join cells' rehearsals check every metric their
    cells list)"""
    result = copy.run(capsys, cell, trace=1)
    assert result["correct"] is True
    got = result["metrics"]
    assert set(NEW) <= set(got)
    for name in NEW:
        assert got[name]["value"] >= 0, name
    assert got["execute_self_share"]["value"] <= 100
    assert got["dispatch_ms_per_query"]["value"] > 0
