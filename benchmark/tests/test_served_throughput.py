"""The cell ``tpch_sf1_served.throughput`` (TPC-H's throughput test, clause
5.3.4) as the command runs it, on the CPU at the rehearsal's size: the result
line carries every metric ``BENCHMARK.json`` names for the cell, Q1's grid is
clause 2.4.1.3's, the reference's Q1 at the validation DELTA is
``reference/tpch.py``'s ``q1``, and answers swapped between two bindings
come out not correct."""

import json
import os

import pytest

CELL = "tpch_sf1_served.throughput"
# what ISSUE 27's point 4 names for the cell, over readers that were there
TRACED = {
    "query_s.q1", "query_s.q6", "dispatches_per_query", "plan_ms_per_query",
    "pull_wait_s_per_query", "d2h_pulls_per_query", "device_s.stage",
    "device_s.aggregate", "device_s.sort", "device_s.concat",
    "device_s.egress", "compiles_in_window.served",
    "scan_cache_hit_share.served", "admit_wait_ms_per_query",
    "result_cache_hit_share.served"}
# readers of the device's trace find no device plane in a CPU trace
DEVICE_TRACE = {"device_time_accounted_share", "device_idle_share.served",
                "hbm_roofline_share.served"}


def test_the_cell_is_listed_with_the_issues_parameters(copy):
    with open(os.path.join(copy.root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("tpch_sf1_served", "throughput", 1)
    cell = copy.harness.load_json("workloads", f"{CELL}.json")
    assert {k: cell[k] for k in ("kind", "streams", "cycle", "stream_offset",
                                 "traced_cycles", "suite")} == {
        "kind": "served", "streams": 2, "cycle": ["q6", "q1"],
        "stream_offset": 0, "traced_cycles": 1, "suite": "tpch_streams"}
    config = copy.harness.load_json("configs", "tpch_sf1_served.json")
    batch = copy.harness.load_json("configs", "tpch_sf1.json")
    assert config["entry"] == "server" and config["conf"] == {}
    for key in ("suite", "scale_rows", "rows", "column_bytes", "chips"):
        assert config[key] == batch[key], key
    for key, limit in batch["guarantees"].items():
        if key.endswith(("_limit", "_limits", "_floor")):
            assert config["guarantees"][key] == limit, key
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == TRACED | DEVICE_TRACE
    assert [m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])] == ["query_s", "setup_s"]


@pytest.mark.parametrize("trace", (0, 1))
def test_result_line(copy, capsys, trace):
    result = copy.run(capsys, CELL, trace=trace)
    assert result["correct"] is True and result["failed"] == 0
    # two streams, whole cycles of [q6, q1]
    assert result["attempted"] >= 4 and result["attempted"] % 2 == 0
    compared = result["compared"]
    for name in ("exact_mismatches", "unanswered", "off_device_nodes"):
        assert compared[name]["value"] == 0, name
    assert compared["answers_compared"]["value"] == result["attempted"]
    assert "gap.q6.revenue" in compared and "gap.q1.sum_charge" in compared
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    if not trace:
        assert set(metrics) == {"query_s", "setup_s"}
        return
    assert set(metrics) == TRACED
    assert metrics["compiles_in_window.served"] == 0
    # one decode a template at warm-up, then every binding hits
    assert metrics["scan_cache_hit_share.served"] == 100
    # no binding repeats in a window: the result cache answers nothing
    assert metrics["result_cache_hit_share.served"] == 0
    assert metrics["admit_wait_ms_per_query"] >= 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_q1_grid_is_clause_2_4_1_3(copy):
    traffic = copy.harness.load_module("traffic.py")
    grid = traffic.binding_grid(
        os.path.join(copy.bench, "queries", "q1.params.json"), 2**31 + 5)
    assert len(grid) == 61
    assert all(len(b) == 1 and type(b[0]) is int for b in grid)
    assert sorted(b[0] for b in grid) == list(range(60, 121))
    cell = copy.harness.load_json("workloads", f"{CELL}.json")
    served = traffic.ServedTraffic(
        cell, os.path.join(copy.bench, "queries"), 2**31 + 5)
    firsts = [[next(s) for _ in range(8)]
              for s in (served.stream(0), served.stream(1))]
    for requests in firsts:  # both streams reach Q6 first (Appendix A)
        assert [n for n, _ in requests] == ["q6", "q1"] * 4
    sent = [r for requests in firsts for r in requests]
    assert len(set(sent)) == len(sent)  # nothing repeats
    assert served.warm("q1") not in [p for n, p in sent if n == "q1"]


def test_reference_q1_at_the_validation_delta(copy, capsys):
    config = copy.harness.load_json("configs", "tpch_sf1_served.json")
    datagen = copy.harness.load_module("datagen", "tpch.py")
    paths = copy.harness.ensure_data(datagen, "tpch", config["scale_rows"], 7)
    capsys.readouterr()
    streams = copy.harness.load_module("reference", "tpch_streams.py")
    batch = copy.harness.load_module("reference", "tpch.py")
    assert streams.TEMPLATES["q1"](paths, (90,)).equals(batch.q1(paths))
    low = streams.TEMPLATES["q1"](paths, (90,), "bfloat16")
    assert low.equals(batch.q1(paths, "bfloat16"))
    # another DELTA is another answer: fewer days kept, fewer rows counted
    counts = [sum(streams.TEMPLATES["q1"](paths, (d,))
                  .column("count_order").to_pylist()) for d in (60, 90, 120)]
    assert counts[0] > counts[1] > counts[2]


def test_answers_swapped_between_bindings_are_not_correct(copy, capsys,
                                                          monkeypatch):
    """One stream's Q1 answers come back one request late (each binding
    gets the answer of the binding before it): the shapes are right, the
    rows are another binding's, and ``correct`` is false."""
    import threading

    from spark_rapids_tpu.server.core import ServerQuery
    sound = ServerQuery.result
    held = {}

    def swapped(self, *a, **k):
        table = sound(self, *a, **k)
        if threading.current_thread().name != "bench-stream-1" \
                or table.num_columns == 1:
            return table
        held["last"], table = table, held.get("last", table)
        return table

    monkeypatch.setattr(ServerQuery, "result", swapped)
    result = copy.run(capsys, CELL, seconds=6.0)
    assert result["correct"] is False
    assert result["compared"]["exact_mismatches"]["value"] > 0
