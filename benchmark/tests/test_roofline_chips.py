"""``hbm_roofline_share.mesh4``: the bytes come from the configuration and
the clause's columns alone, and the share counts the chips: four chips busy
a quarter each read what one chip busy for all of it reads, and bytes read
once never read over 100 %."""

import json
import os
from types import SimpleNamespace

import pytest
from conftest import BENCH, load

MS = 1_000_000  # ns


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def reader():
    return load(os.path.join(BENCH, "readers",
                             "trace_roofline_share_chips.py"),
                "bench_roofline_chips")


@pytest.fixture(scope="module")
def reduce():
    return load(os.path.join(BENCH, "trace", "reduce.py"),
                "bench_reduce_chips")


# clause 2.4.3 and 2.4.18: every column the query's text names
CLAUSE = {
    "q3": {"customer": ("c_mktsegment", "c_custkey"),
           "orders": ("o_custkey", "o_orderkey", "o_orderdate",
                      "o_shippriority"),
           "lineitem": ("l_orderkey", "l_extendedprice", "l_discount",
                        "l_shipdate")},
    "q18": {"customer": ("c_name", "c_custkey"),
            "orders": ("o_orderkey", "o_custkey", "o_orderdate",
                       "o_totalprice"),
            "lineitem": ("l_orderkey", "l_quantity")}}
BYTES = {"q3": 150_000 * 9 + 1_500_000 * 28 + 5_999_995 * 20,
         "q18": 150_000 * 26 + 1_500_000 * 24 + 5_999_995 * 12}


@pytest.mark.parametrize("query", ("q3", "q18"))
def test_bytes_are_the_clauses_columns(reader, query):
    config = _json("configs", "tpch_sf1_mesh4.json")
    roofline = _json("rooflines", "tpch_sf1_mesh4.json")
    named = {}
    for table, column in roofline[query]:
        named.setdefault(table, []).append(column)
    assert {t: sorted(c) for t, c in named.items()} == \
        {t: sorted(c) for t, c in CLAUSE[query].items()}
    assert reader.query_bytes(config, roofline, query) == BYTES[query]
    # the widths agree with tpch_sf1's where both name a column
    assert config["column_bytes"]["lineitem"] == \
        _json("configs", "tpch_sf1.json")["column_bytes"]["lineitem"]


def _run(reader, reduce, device, chips=4):
    trace = reduce.Trace(device=device,
                         host=[(reduce.WINDOW_SPAN, 0, 1000 * MS)])
    config = dict(_json("configs", "tpch_sf1_mesh4.json"), chips=chips)
    return SimpleNamespace(
        trace=reduce.reduce(trace), config=config,
        roofline=_json("rooflines", "tpch_sf1_mesh4.json"),
        load_peaks=lambda: _json("trace", "peaks.json")["TPU v5 lite"],
        executions=[{"name": "q3", "ok": True}, {"name": "q18", "ok": True}])


def test_four_chips_a_quarter_each_read_as_one_chip_for_all(reader, reduce):
    least_s = (BYTES["q3"] + BYTES["q18"]) / 819e9
    busy = 400 * MS
    one = _run(reader, reduce, {"/device:TPU:0": [("op", 10 * MS, busy)]},
               chips=1)
    four = _run(reader, reduce, {
        f"/device:TPU:{d}": [("op", (10 + 100 * d) * MS, busy // 4)]
        for d in range(4)})
    assert one.trace["busy_s"] == pytest.approx(0.4)
    assert four.trace["busy_s"] == pytest.approx(0.1)  # the chips' mean
    assert reader.read(one) == pytest.approx(100 * least_s / 0.4)
    assert reader.read(four) == pytest.approx(reader.read(one))
    # one chip of four did all of it, three never ran: the same share
    lone = _run(reader, reduce, {
        "/device:TPU:0": [("op", 10 * MS, busy)],
        **{f"/device:TPU:{d}": [] for d in (1, 2, 3)}})
    assert reader.read(lone) == pytest.approx(reader.read(one))


def test_bytes_read_once_never_read_over_100(reader, reduce):
    """Each byte read once, by one chip or another, at the peak rate: the
    chips' busy times add up to bytes / peak at the least, however the
    bytes are divided."""
    least_ns = (BYTES["q3"] + BYTES["q18"]) / 819e9 * 1e9
    for shares in ((1, 0, 0, 0), (0.25, 0.25, 0.25, 0.25),
                   (0.7, 0.1, 0.1, 0.1)):
        run = _run(reader, reduce, {
            f"/device:TPU:{d}": ([("op", 10 * MS, int(least_ns * s) + 1)]
                                 if s else [])
            for d, s in enumerate(shares)})
        assert 99.9 < reader.read(run) <= 100.0, shares


def test_nothing_to_read(reader, reduce):
    run = _run(reader, reduce, {"/device:TPU:0": [("op", 0, MS)]})
    run.trace["busy_s"] = 0.0
    assert reader.read(run) is None
    run.trace = None
    assert reader.read(run) is None
    run = _run(reader, reduce, {"/device:TPU:0": [("op", 0, MS)]})
    run.executions = [{"name": "q1", "ok": True}]  # not in this roofline
    assert reader.read(run) is None
