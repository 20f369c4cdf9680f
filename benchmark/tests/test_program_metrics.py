"""The per-layer metrics that read the engine's dispatch ledger
(``engine_stats()["programs"]`` and ``["phases"]``): their files load and
name readers that exist, a traced rehearsal on the CPU reports them
(structure only: a CPU number is never a device metric's), and the two
readers they brought leave a metric out, and do not raise, where a program
has no such counter or a run has no trace."""

import json
import os
from types import SimpleNamespace

import pytest
from conftest import BENCH, REPO, load

NEW = ("dispatches_per_query", "device_s.stage", "device_s.aggregate",
       "device_s.sort", "device_s.concat", "device_s.egress",
       "device_time_accounted_share", "plan_ms_per_query",
       "pull_wait_s_per_query")
CELL = "tpch_sf1.agg"


def reader(name):
    return load(os.path.join(BENCH, "readers", name + ".py"),
                "program_metrics_" + name)


@pytest.mark.parametrize("name", NEW)
def test_metric_file_and_entry(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as fh:
        spec = json.load(fh)
    assert set(spec) == {"reader", "args"}
    assert os.path.exists(
        os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    assert spec["args"]["path"].split(".")[0] in ("programs", "phases")
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    # held to the cells ``BENCHMARK.json`` lists, the batch cell among them,
    # each of which reports the end-to-end metric it moves
    moves = next(m for m in bench["end_to_end"] if m["name"] == "query_s")
    assert entry["moves"] == "query_s" and CELL in entry["workloads"]
    assert set(entry["workloads"]) <= set(moves["workloads"]) \
        <= {w["name"] for w in bench["workloads"]}
    assert len(set(entry["workloads"])) == len(entry["workloads"])
    assert entry["source"] == ("device_trace" if name ==
                               "device_time_accounted_share"
                               else "program_counter")


def test_traced_rehearsal_reports_them(copy, capsys):
    result = copy.run(capsys, CELL, trace=1)
    assert result["correct"] is True
    got = result["metrics"]
    # the CPU's trace has no device plane: the share against the
    # profiler's busy time has nothing to read and is left out
    assert set(NEW) - set(got) == {"device_time_accounted_share"}
    for name in set(NEW) & set(got):
        assert got[name]["value"] >= 0, name
    assert got["dispatches_per_query"]["value"] >= 3  # stage, update, pack
    assert got["pull_wait_s_per_query"]["value"] > 0


def run_with(before, after, trace=None, done=2):
    return SimpleNamespace(
        stats_before=before, stats_after=after, trace=trace,
        executions=[{"ok": True}] * done + [{"ok": False}])


def test_growth_reader():
    read = reader("engine_stat_growth").read
    run = run_with({"programs": {"dispatches": 10, "stage_device_us": 5}},
                   {"programs": {"dispatches": 250, "stage_device_us": 4e6 + 5}})
    assert read(run, "programs.dispatches", per="queries") == 120
    assert read(run, "programs.stage_device_us", per="queries",
                scale=1e-6) == pytest.approx(2.0)
    assert read(run, "programs.dispatches") == 240
    assert read(run, "programs.stage_device_us",
                per="programs.dispatches") == pytest.approx(4e6 / 240)
    # a program without the group, or without the counter: nothing to read
    parent = run_with({"d2h": {"pulls": 1}}, {"d2h": {"pulls": 9}})
    assert read(parent, "programs.dispatches", per="queries") is None
    assert read(run, "programs.join_device_us", per="queries") is None
    assert read(run_with({"programs": {"dispatches": 1}},
                         {"programs": {"dispatches": 2}}, done=0),
                "programs.dispatches", per="queries") is None


def test_share_of_the_profilers_busy_time():
    read = reader("engine_stat_over_trace_busy").read
    before = {"programs": {"device_us": 1_000_000}}
    after = {"programs": {"device_us": 35_300_000}}
    args = {"path": "programs.device_us", "scale": 1e-6}
    assert read(run_with(before, after), **args) is None  # no trace
    assert read(run_with(before, after, trace={"busy_s": 0.0}),
                **args) is None
    assert read(run_with(before, after, trace={"busy_s": 35.0}),
                **args) == pytest.approx(98.0)
    assert read(run_with({}, {}, trace={"busy_s": 35.0}), **args) is None
