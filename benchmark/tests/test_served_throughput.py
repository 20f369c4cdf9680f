"""The cell ``tpch_sf1_served.throughput`` (TPC-H's throughput test, clause
5.3.4) as the command runs it, on the CPU at the rehearsal's size: the result
line carries every metric ``BENCHMARK.json`` names for the cell, Q1's grid is
clause 2.4.1.3's, the reference's Q1 at the validation DELTA is
``reference/tpch.py``'s ``q1``, and answers swapped between two bindings
come out not correct."""

import datetime as dt
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


@pytest.mark.parametrize("delta", (60, 90, 120))
def test_q1_is_a_filter_first_q1_written_out_straight(copy, capsys, delta):
    """PR 30 took what no binding changes (group codes, products) out of the
    reference's Q1 and filters afterwards; the answer is, cell for cell, a
    Q1 that filters first and is written out here with nothing shared."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    config = copy.harness.load_json("configs", "tpch_sf1_served.json")
    datagen = copy.harness.load_module("datagen", "tpch.py")
    paths = copy.harness.ensure_data(datagen, "tpch", config["scale_rows"], 7)
    capsys.readouterr()
    t = pq.read_table(paths["lineitem"])
    last = dt.date(1998, 12, 1) - dt.timedelta(days=delta)
    keep = (t.column("l_shipdate").cast(pa.int32()).to_numpy()
            <= (last - dt.date(1970, 1, 1)).days)
    t = t.filter(pa.array(keep))
    pairs = list(zip(t.column("l_returnflag").to_pylist(),
                     t.column("l_linestatus").to_pylist()))
    groups = sorted(set(pairs))
    code = np.array([groups.index(p) for p in pairs])
    qty, price, disc, tax = (
        t.column(c).to_numpy().astype(np.float64) for c in (
            "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    disc_price = price * (1.0 - disc)

    def total(x):
        return np.bincount(code, weights=x, minlength=len(groups))

    count = np.bincount(code, minlength=len(groups))
    straight = pa.table({
        "l_returnflag": [g[0] for g in groups],
        "l_linestatus": [g[1] for g in groups],
        "sum_qty": total(qty), "sum_base_price": total(price),
        "sum_disc_price": total(disc_price),
        "sum_charge": total(disc_price * (1.0 + tax)),
        "avg_qty": total(qty) / count, "avg_price": total(price) / count,
        "avg_disc": total(disc) / count,
        "count_order": count.astype(np.int64)})
    streams = copy.harness.load_module("reference", "tpch_streams.py")
    assert streams.TEMPLATES["q1"](paths, (delta,)).equals(straight)


def test_a_column_is_read_once_and_answers_are_as_fresh_ones(copy, capsys,
                                                            monkeypatch):
    """The reference reads each parquet column once a process; a memoised
    answer is, cell for cell, the answer of a process that read anew."""
    import pyarrow.parquet as pq
    config = copy.harness.load_json("configs", "tpch_sf1_served.json")
    datagen = copy.harness.load_module("datagen", "tpch.py")
    paths = copy.harness.ensure_data(datagen, "tpch", config["scale_rows"], 7)
    capsys.readouterr()
    reads = []
    sound = pq.read_table
    monkeypatch.setattr(pq, "read_table", lambda path, columns: (
        reads.extend(columns), sound(path, columns=columns))[1])
    q6 = (dt.date(1993, 1, 1), dt.date(1994, 1, 1), 0.02, 0.04, 25.0)
    bindings = [("q1", (60,)), ("q6", q6), ("q1", (120,)), ("q1", (60,))]
    memoised = copy.harness.load_module("reference", "tpch_streams.py")
    answers = [memoised.TEMPLATES[n](paths, p) for n, p in bindings]
    assert sorted(reads) == sorted({
        "l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
        "l_extendedprice", "l_discount", "l_tax"})  # each once
    for (name, params), answer in zip(bindings, answers):
        fresh = copy.harness.load_module("reference", "tpch_streams.py")
        assert fresh.TEMPLATES[name](paths, params).equals(answer)
        low = fresh.TEMPLATES[name](paths, params, "bfloat16")
        assert low.equals(memoised.TEMPLATES[name](paths, params, "bfloat16"))
        assert not low.equals(answer)
    assert len(reads) > 7  # the fresh modules did read anew
    with pytest.raises(ValueError):  # what is shared is read-only
        memoised._tpch._columns(paths["lineitem"], ["l_tax"])["l_tax"][0] = 1


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
