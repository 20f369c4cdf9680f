"""The SF10 aggregate cell end to end on the CPU backend at the rehearsal's
rows: the result line is ``correct``, names every metric ``BENCHMARK.json``
lists for the cell that a CPU run can read, and holds every ``gap.*`` beside
the configuration's limit, none looser than ``tpch_sf1``'s; a wrong answer planted in the
reference makes it not correct, and so does the plain reference computed in
bfloat16 (``control.py``); the configuration is SF10's by clause 4.2.5 and
keeps ``tpch_sf1``'s guarantees but three limits set tighter, between the
program's and the control's readings at SF10."""

import json
import os

import pytest
from conftest import BENCH, load
from test_rehearsal import expected_metrics

CELL = "tpch_sf10.agg"
CONFIG = "tpch_sf10"


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


@pytest.mark.parametrize("trace", (0, 1))
def test_result_line(copy, capsys, trace):
    result = copy.run(capsys, CELL, trace=trace, seed=2**31 + 61)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2 and result["attempted"] % 2 == 0
    assert set(result["metrics"]) == expected_metrics(copy, CELL, trace)
    compared = result["compared"]
    for name in ("exact_mismatches", "unanswered", "off_device_nodes"):
        assert compared[name] == {"value": 0, "limit": 0}, name
    ours = _json("configs", CONFIG + ".json")["guarantees"]
    sf1 = _json("configs", "tpch_sf1.json")["guarantees"]
    compare = load(os.path.join(BENCH, "compare.py"), "sf10_compare")
    gaps = [k for k in compared if k.startswith("gap.")]
    assert {"gap.q1.sum_qty", "gap.q1.sum_charge", "gap.q6.revenue"} \
        <= set(gaps)
    for gap in gaps:
        limit = compare.gap_limit(ours, gap[len("gap."):])
        assert compared[gap]["limit"] == limit, gap
        assert limit <= compare.gap_limit(sf1, gap[len("gap."):]), gap
        assert compared[gap]["value"] <= limit, gap
    if not trace:
        return
    values = {k: v["value"] for k, v in result["metrics"].items()}
    assert values["scan_cache_hit_share"] == 100
    assert values["compiles_in_window.batch"] == 0
    assert values["dense_agg_batches_per_aggregate"] >= 1
    # the folded filter runs masked on every update batch
    assert values["masked_filter_batches_per_aggregate"] == \
        values["dense_agg_batches_per_aggregate"]
    # the update programs, timed under the trace switch by the watcher
    assert 0 < values["device_s.agg_update"] <= values["device_s.aggregate"]
    assert "hbm_roofline_share.batch" not in values


def test_wrong_reference_is_not_correct(copy, capsys):
    with open(os.path.join(copy.bench, "reference", "tpch.py"), "a") as fh:
        fh.write(
            "\n\n_sound_q1_until = q1_until\n\n"
            "def q1_until(*args):\n"
            "    t = _sound_q1_until(*args)\n"
            "    col = t.column_names.index('sum_qty')\n"
            "    return t.set_column(col, 'sum_qty', pa.array(\n"
            "        [v * 1.00001 for v in t.column(col).to_pylist()]))\n")
    result = copy.run(capsys, CELL)
    assert result["correct"] is False
    gap = result["compared"]["gap.q1.sum_qty"]
    assert gap["value"] > gap["limit"]


# set between the program's widest reading on the chip and the bfloat16
# control's narrowest at SF10 (configs/tpch_sf10.json: float_rel_gap_why)
TIGHTER = {"q1.sum_base_price": 1e-6, "q1.avg_price": 1e-6,
           "q1.avg_disc": 1.5e-5}


def test_configuration_is_sf10_with_sf1s_guarantees():
    config = _json("configs", CONFIG + ".json")
    sf1 = _json("configs", "tpch_sf1.json")
    datagen = load(os.path.join(BENCH, "datagen", "tpch.py"), "sf10_datagen")
    assert config["scale_factor"] == 10
    assert config["scale_rows"] == 10 * sf1["scale_rows"]
    assert config["rows"] == datagen.table_rows(config["scale_rows"])
    assert config["rows"]["lineitem"] == 59_999_997
    assert {k: v * 10 for k, v in sf1["rows"].items()
            if k not in ("region", "nation", "lineitem")} == {
        k: v for k, v in config["rows"].items()
        if k not in ("region", "nation", "lineitem")}
    for key in ("column_bytes", "conf", "entry", "queries", "suite",
                "assumed"):
        assert config[key] == sf1[key], key
    ours, theirs = dict(config["guarantees"]), dict(sf1["guarantees"])
    ours.pop("float_rel_gap_why"), theirs.pop("float_rel_gap_why")
    limits = ours.pop("float_rel_gap_limits")
    assert limits == {**theirs.pop("float_rel_gap_limits"), **TIGHTER}
    assert ours == theirs
    assert set(config["reduced"]) == {"scale_factor", "queries"}
    bench = _json("..", "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    cell = _json("workloads", CELL + ".json")
    assert cell["queries"] == config["queries"] and cell["kind"] == "stream"


def test_control_is_not_correct(copy, capsys):
    """The plain reference in bfloat16, one step below the device's f32,
    fails the cell's own comparison on every seed."""
    control = load(os.path.join(copy.bench, "tests", "control.py"),
                   "sf10_control")
    control.harness = copy.harness
    for seed in (3, 2**31 + 5, 77):
        numbers = control.control_numbers(CELL, seed)
        assert numbers["control_correct"] is False, numbers
        assert numbers["fails"], numbers
    capsys.readouterr()


def test_roofline_is_sf1s_query_for_query():
    """Q1 reads 7 columns of lineitem and Q6 4 (clause 2.4) at any scale:
    the bytes follow the configuration's rows."""
    assert _json("rooflines", CONFIG + ".json") == \
        _json("rooflines", "tpch_sf1.json")
