"""The one-chip join cell end to end on the CPU backend at 60 k rows: the
result line is ``correct``, names every metric ``BENCHMARK.json`` lists for
the cell that needs no device trace, and holds ``gap.q5.revenue`` beside the
limit of the configuration's file; the stream's nine scan shapes thrash the
default scan cache's eight entries, so the window decodes every table; the
bfloat16 control comes out not correct, on Q18 too, which was cut from the
stream for time and keeps its reference and its limits; the roofline file
names only columns the configuration gives a width."""

import json
import os

import pytest
from conftest import BENCH, load
from test_rehearsal import expected_metrics

CELL = "tpch_sf1_joins.power"
CONFIG = "tpch_sf1_joins"


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


@pytest.mark.parametrize("trace", (0, 1))
def test_result_line(copy, capsys, trace):
    result = copy.run(capsys, CELL, trace=trace)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2 and result["attempted"] % 2 == 0
    assert set(result["metrics"]) == expected_metrics(copy, CELL, trace)
    compared = result["compared"]
    for name in ("exact_mismatches", "unanswered", "off_device_nodes"):
        assert compared[name] == {"value": 0, "limit": 0}, name
    limits = _json("configs", CONFIG + ".json")["guarantees"][
        "float_rel_gap_limits"]
    for gap in ("q3.revenue", "q5.revenue"):
        assert compared["gap." + gap]["limit"] == limits[gap], gap
        assert compared["gap." + gap]["value"] <= limits[gap], gap
    if not trace:
        return
    values = {k: v["value"] for k, v in result["metrics"].items()}
    # a hot pass: 9 scan shapes go round an LRU of 8 entries
    assert values["scan_cache_hit_share.joins"] == 0
    assert values["scan_decoded_bytes_per_query"] > 0
    assert values["scan_decode_ms_per_query"] > 0
    assert values["scan_upload_ms_per_query"] > 0
    assert values["join_probe_ms_per_query"] > 0
    assert values["join_slots_per_row"] >= 1
    assert 0 <= values["fk_join_share.joins"] <= 100
    for q in ("q3", "q5"):
        assert values["query_s." + q] > 0
    assert values["device_s.join"] > 0  # the watcher's clock, traced
    # CPU programs are seen by no device plane: the trace's readers have
    # nothing to read, and say so by leaving their metrics out
    assert "hbm_roofline_share.joins" not in values
    assert "device_idle_share.joins" not in values


def test_control_is_not_correct(copy, capsys):
    control = load(os.path.join(copy.bench, "tests", "control.py"),
                   "rehearsal_control_joins")
    control.harness = copy.harness
    for seed in (3, 2**31 + 5, 77):
        numbers = control.control_numbers(CELL, seed)
        assert numbers["control_correct"] is False, numbers
        assert "q5.revenue" in numbers["fails"], numbers
    capsys.readouterr()


def test_control_fails_q18_too(copy, capsys):
    """Q18 is cut from the cell's stream for time, and keeps its builder,
    its reference and its limits: where it answers a row, the control's
    ``o_totalprice`` (a DOUBLE rounded to bfloat16) is over its limit."""
    h = copy.harness
    config = h.load_json("configs", CONFIG + ".json")
    ref = h.load_module("reference", "tpch_power.py")
    compare = h.load_module("compare.py")
    limit = config["guarantees"]["float_rel_gap_limits"]["q18.o_totalprice"]
    answered = 0
    for seed in (3, 5, 7):
        paths = h.ensure_data(h.load_module("datagen", "tpch.py"), "tpch",
                              config["scale_rows"], seed)
        exact = ref.QUERIES["q18"](paths)
        if exact.num_rows:
            answered += 1
            r = compare.compare_tables(ref.QUERIES["q18"](paths, "bfloat16"),
                                       exact)
            assert r["exact_mismatches"] or \
                r["gaps"]["o_totalprice"] > limit, (seed, r)
    assert answered
    capsys.readouterr()


def test_reference_agrees_with_itself(copy, capsys):
    h = copy.harness
    config = h.load_json("configs", CONFIG + ".json")
    paths = h.ensure_data(h.load_module("datagen", "tpch.py"), "tpch",
                          config["scale_rows"], 3)
    ref = h.load_module("reference", "tpch_power.py")
    compare = h.load_module("compare.py")
    for q in ("q3", "q5", "q18"):
        r = compare.compare_tables(ref.QUERIES[q](paths),
                                   ref.QUERIES[q](paths))
        assert r["exact_mismatches"] == 0 and not any(r["gaps"].values())
    assert ref.QUERIES["q5"](paths).num_rows > 0
    capsys.readouterr()


def test_sum_growth_reader_leaves_out_what_a_program_lacks():
    """The parent of the PR that brought the ``join`` group has none: the
    reader returns nothing and does not raise."""
    from types import SimpleNamespace
    reader = load(os.path.join(BENCH, "readers", "engine_stat_sum_growth.py"),
                  "bench_sum_growth")
    args = _json("metrics", "fk_join_share.joins.json")["args"]
    run = SimpleNamespace(stats_before={"scan": {}}, stats_after={"scan": {}},
                          executions=[{"ok": True}])
    assert reader.read(run, **args) is None
    run.stats_before = {"join": {"fk": 1, "fk_dense": 2, "joins": 4}}
    run.stats_after = {"join": {"fk": 2, "fk_dense": 7, "joins": 14}}
    assert reader.read(run, **args) == pytest.approx(60.0)
    run.stats_after = run.stats_before  # no join ran: nothing to divide by
    assert reader.read(run, **args) is None


def test_roofline_names_only_columns_the_configuration_has():
    config = _json("configs", CONFIG + ".json")
    roofline = _json("rooflines", CONFIG + ".json")
    cell = _json("workloads", CELL + ".json")
    reader = load(os.path.join(BENCH, "readers", "trace_roofline_share.py"),
                  "bench_roofline_joins")
    assert set(cell["queries"]) <= set(roofline)
    for query in cell["queries"]:
        pairs = [tuple(p) for p in roofline[query]]
        assert len(set(pairs)) == len(pairs), query  # each pair once
        for table, column in pairs:
            assert table in config["rows"], (query, table)
            assert column in config["column_bytes"][table], (query, column)
        assert reader.query_bytes(config, roofline, query) == sum(
            config["rows"][t] * config["column_bytes"][t][c]
            for t, c in pairs)
    # Q5 reads six tables: 24 bytes a line, 20 an order, 16 a customer
    assert reader.query_bytes(config, roofline, "q5") == (
        5_999_995 * 24 + 1_500_000 * 20 + 150_000 * 16 + 10_000 * 16
        + 25 * 17 + 5 * 9)
