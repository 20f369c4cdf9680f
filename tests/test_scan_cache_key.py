"""The device scan cache is keyed on what a scan READ, not on the values a
prepared statement was bound with (ISSUE 27; ROADMAP M3; docs/serving.md).

A pushed-down predicate does one thing to a parquet scan: it prunes row
groups by their footer statistics.  So the key carries the predicate with
its parameters masked and the outcome of that pruning, per file the row
groups kept: two bindings of a prepared statement that keep the same row
groups share one device-resident entry, while a binding that prunes
differently gets its own.  The always-on
counters ``engine_stats()["scan"]`` are how a served request, which leaves
no plan behind, can be read.  CSV and ORC go through the same helper and
key as they did.
"""

import datetime as dt

import numpy as np
import pyarrow as pa
import pyarrow.csv as pacsv
import pyarrow.orc as paorc
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.exprs import predicates as pr
from spark_rapids_tpu.exprs.base import (
    BoundReference, Literal, ParamLiteral,
)
from spark_rapids_tpu.columnar.dtypes import DATE, FLOAT64
from spark_rapids_tpu.io import parquet as scan_io
from tests.compare import cpu_session, tpu_session

Q6 = ("SELECT sum(l_extendedprice * l_discount) AS revenue FROM lineitem "
      "WHERE l_shipdate >= ? AND l_shipdate < ? "
      "AND l_discount BETWEEN ? AND ? AND l_quantity < ?")
ROWS, GROUP_ROWS = 12_000, 2_000


def _binding(year, discount=0.06, quantity=24.0):
    return (dt.date(year, 1, 1), dt.date(year + 1, 1, 1),
            round(discount - 0.01, 2), round(discount + 0.01, 2), quantity)


def _lineitem(seed=11) -> pa.Table:
    rng = np.random.default_rng(seed)
    days = rng.integers(0, 2526, ROWS) + (dt.date(1992, 1, 2)
                                          - dt.date(1970, 1, 1)).days
    return pa.table({
        "l_shipdate": pa.array(days.astype(np.int32), pa.int32())
        .cast(pa.date32()),
        "l_discount": np.round(rng.integers(0, 11, ROWS) * 0.01, 2),
        "l_quantity": rng.integers(1, 51, ROWS).astype(np.float64),
        "l_extendedprice": np.round(rng.uniform(900, 105000, ROWS), 2)})


@pytest.fixture()
def scan_counters():
    """The counters' growth since the fixture ran; the cache itself is
    process-wide, so every test scans files of its own."""
    scan_io.reset_scan_stats()
    return scan_io.scan_stats


def _answers(path, bindings, session):
    session.read.parquet(path).create_or_replace_temp_view("lineitem")
    stmt = session.prepare(Q6)
    return [stmt.execute(*b).column("revenue")[0].as_py() for b in bindings]


def _scan_metrics(session):
    stack = [session.last_query_profile().to_dict()["plan"]]
    while stack:
        node = stack.pop()
        if node["name"] == "TpuParquetScanExec":
            return node["metrics"]
        stack.extend(node["children"])
    raise AssertionError("no parquet scan in the plan")


def test_two_bindings_decode_once_and_hit_once(tmp_path, scan_counters):
    """Unsorted dates: every row group spans every year, nothing is
    pruned, and each new binding of the prepared Q6 reads the columns the
    first one uploaded."""
    path = str(tmp_path / "lineitem.parquet")
    pq.write_table(_lineitem(), path, row_group_size=GROUP_ROWS)
    bindings = [_binding(1994), _binding(1996, 0.03, 25.0)]
    s = tpu_session({})
    try:
        s.read.parquet(path).create_or_replace_temp_view("lineitem")
        stmt = s.prepare(Q6)
        first = stmt.execute(*bindings[0]).column("revenue")[0].as_py()
        after_first = scan_counters()
        assert after_first["cache_lookups"] == 1
        assert after_first["cache_hits"] == 0
        assert after_first["decoded_bytes"] > 0
        second = stmt.execute(*bindings[1]).column("revenue")[0].as_py()
        after_second = scan_counters()
        assert after_second["cache_lookups"] == 2
        assert after_second["cache_hits"] == 1
        assert after_second["decoded_bytes"] == after_first["decoded_bytes"]
        assert _scan_metrics(s)["scanCacheHits"] == 1
        assert s.engine_stats()["scan"] == after_second
    finally:
        s.stop()
    cpu = cpu_session({})
    try:
        want = _answers(path, bindings, cpu)
    finally:
        cpu.stop()
    assert first != second
    assert [first, second] == pytest.approx(want, rel=1e-9)


def test_binding_that_prunes_other_row_groups_gets_its_own_entry(
        tmp_path, scan_counters):
    """Written sorted by l_shipdate, a year keeps one or two row groups of
    six: 1994 and 1996 keep different ones (two entries), and another
    discount under 1994 keeps the same as 1994 did (a hit)."""
    path = str(tmp_path / "lineitem.parquet")
    pq.write_table(_lineitem().sort_by("l_shipdate"), path,
                   row_group_size=GROUP_ROWS)
    bindings = [_binding(1994), _binding(1996), _binding(1994, 0.03, 25.0)]
    s = tpu_session({})
    try:
        s.read.parquet(path).create_or_replace_temp_view("lineitem")
        stmt = s.prepare(Q6)
        got, kept = [], []
        for b in bindings:
            got.append(stmt.execute(*b).column("revenue")[0].as_py())
            m = _scan_metrics(s)
            assert m["numRowGroupsTotal"] == ROWS // GROUP_ROWS
            kept.append(m["numRowGroupsRead"])
        assert all(0 < k < ROWS // GROUP_ROWS for k in kept), kept
        counters = scan_counters()
        assert counters["cache_lookups"] == 3
        assert counters["cache_hits"] == 1  # the third binding only
    finally:
        s.stop()
    cpu = cpu_session({})
    try:
        want = _answers(path, bindings, cpu)
    finally:
        cpu.stop()
    assert got == pytest.approx(want, rel=1e-9)
    assert len(set(got)) == 3


def test_predicate_with_nothing_to_prune_by_shares_across_bindings(
        tmp_path, scan_counters):
    """``l_discount * 2 > ?`` is no `col <op> literal`: it prunes nothing
    under any binding, so all its bindings share one entry (TPC-H Q1's
    ``l_shipdate <= date_sub(DATE '1998-12-01', ?)`` is this case)."""
    path = str(tmp_path / "lineitem.parquet")
    pq.write_table(_lineitem(), path, row_group_size=GROUP_ROWS)
    s = tpu_session({})
    try:
        s.read.parquet(path).create_or_replace_temp_view("lineitem")
        stmt = s.prepare(
            "SELECT sum(l_discount) AS d FROM lineitem "
            "WHERE l_discount * 2 > ?")
        low = stmt.execute(0.04).column("d")[0].as_py()
        high = stmt.execute(0.16).column("d")[0].as_py()
        assert low > high > 0
        counters = scan_counters()
        assert counters["cache_lookups"] == 2
        assert counters["cache_hits"] == 1
    finally:
        s.stop()


def test_an_inline_literal_is_the_query_not_a_binding(tmp_path,
                                                      scan_counters):
    """Only prepared parameters are masked out of the key: two queries
    that differ in an inline constant keep an entry each, as they always
    did (and as the plan fingerprint treats them); the same text again
    hits."""
    path = str(tmp_path / "lineitem.parquet")
    pq.write_table(_lineitem(), path, row_group_size=GROUP_ROWS)
    s = tpu_session({})
    try:
        s.read.parquet(path).create_or_replace_temp_view("lineitem")

        def total(quantity):
            return s.sql("SELECT sum(l_discount) AS d FROM lineitem "
                         f"WHERE l_quantity < {quantity}") \
                .to_arrow().column("d")[0].as_py()

        a, b, again = total(24.0), total(25.0), total(24.0)
        assert b > a == again
        counters = scan_counters()
        assert counters["cache_lookups"] == 3
        assert counters["cache_hits"] == 1
    finally:
        s.stop()


def test_pruning_outcome_is_the_readers_own_decision(tmp_path):
    """The key's row groups are the ones ``ParquetPartitionReader`` reads,
    shard by shard, and a date statistic compares in a literal's days."""
    path = str(tmp_path / "lineitem.parquet")
    table = _lineitem().sort_by("l_shipdate")
    pq.write_table(table, path, row_group_size=GROUP_ROWS)
    days = (dt.date(1995, 1, 1) - dt.date(1970, 1, 1)).days
    pred = pr.GreaterThanOrEqual(
        BoundReference(0, DATE, True, "l_shipdate"), Literal(days, DATE))
    loose = pr.And(pred, pr.GreaterThan(
        BoundReference(1, FLOAT64, True, "l_discount"), Literal(-1.0)))
    ids = ((path, 0.0, 0),)
    scan_io._footer_stats.cache_clear()
    text, (kept,), shard = scan_io.pruning_outcome(pred, None)(ids)
    assert text == pred.key() and shard is None  # no parameter to mask
    assert 0 < len(kept) < ROWS // GROUP_ROWS
    assert kept == tuple(range(kept[0], ROWS // GROUP_ROWS))
    # a conjunct that rules nothing out changes the text, not the outcome
    assert scan_io.pruning_outcome(loose, None)(ids)[1] == (kept,)
    assert scan_io.pruning_outcome(None, None)(ids) == (None, None, None)
    # a parameter's value is masked out of the text; what it prunes stays
    bound = [pr.GreaterThanOrEqual(
        BoundReference(0, DATE, True, "l_shipdate"),
        ParamLiteral(0, days + shift, DATE)) for shift in (0, 1, 400)]
    keys = [scan_io.pruning_outcome(b, None)(ids) for b in bound]
    assert keys[0] == keys[1] != keys[2]
    assert keys[0][0] == keys[2][0] != text
    shards = []
    for r in range(2):
        reader = scan_io.ParquetPartitionReader(
            path, None, columns=["l_shipdate"], pred=pred,
            rg_shard=(r, 2))
        list(reader.read_host())
        outcome = scan_io.pruning_outcome(pred, (r, 2))(ids)
        assert outcome[2] == (r, 2)
        assert len(outcome[1][0]) == reader.read_row_groups
        shards.append(outcome[1][0])
    assert sorted(shards[0] + shards[1]) == list(kept)
    assert not set(shards[0]) & set(shards[1])


def _write(fmt, table, path):
    if fmt == "csv":
        pacsv.write_csv(table, path)
    else:
        paorc.write_table(table, path)


@pytest.mark.parametrize("fmt", ("csv", "orc"))
def test_csv_and_orc_key_as_before(tmp_path, scan_counters, fmt):
    """Both go through ``scan_cache_key`` with a plain value: the same
    query again hits; an ORC scan still keys on its predicate's text, so
    another literal is another entry (CSV pushes nothing down and hits)."""
    path = str(tmp_path / f"t.{fmt}")
    rng = np.random.default_rng(3)
    _write(fmt, pa.table({
        "k": pa.array(rng.integers(0, 9, 4000), pa.int64()),
        "v": rng.integers(0, 1000, 4000).astype(np.float64)}), path)
    s = tpu_session({})
    try:
        getattr(s.read, fmt)(path).create_or_replace_temp_view("t")

        def total(bound):
            return s.sql(f"SELECT sum(v) AS sv FROM t WHERE k < {bound}") \
                .to_arrow().column("sv")[0].as_py()

        a, again, b = total(5), total(5), total(7)
        assert a == again and b > a
        counters = scan_counters()
        assert counters["cache_lookups"] == 3
        assert counters["cache_hits"] == (2 if fmt == "csv" else 1)
    finally:
        s.stop()


@pytest.mark.parametrize("second,hits", [
    # the same two columns asked for in the other order: one key
    (("l_discount", "l_quantity"), 1),
    # another pair of the same table: another key
    (("l_quantity", "l_extendedprice"), 0),
])
def test_the_key_is_the_columns_read_not_their_order(tmp_path, scan_counters,
                                                     second, hits):
    """The planner narrows a scan to the columns its query reads, in file
    order (ISSUE 36), and the key names them: a narrowed scan is its own
    entry, never served another column set's planes."""
    path = str(tmp_path / "lineitem.parquet")
    pq.write_table(_lineitem(), path, row_group_size=GROUP_ROWS)
    s = tpu_session({})
    try:
        df = s.read.parquet(path)
        first = df.select("l_quantity", "l_discount").to_arrow()
        again = df.select(*second).to_arrow()
        counters = scan_counters()
        assert (counters["cache_lookups"], counters["cache_hits"]) == \
            (2, hits)
        assert (counters["columns_read"], counters["columns_total"]) == \
            (4, 8)
        assert again.column_names == list(second)
        for name in set(second) & set(first.column_names):
            assert again.column(name).equals(first.column(name))
    finally:
        s.stop()
