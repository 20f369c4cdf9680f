"""``datagen/tpch.py`` against the clauses of the TPC-H specification that
``configs/tpch_sf1.json`` names as its source: the tables and columns of
1.4, the cardinalities of 4.2.5, the column rules of 4.2.3.  At 60 k nominal
lineitem rows, on two seeds; the rules do not depend on the scale."""

import datetime as dt
import json
import os

import numpy as np
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest
from conftest import BENCH, REHEARSAL_ROWS, load

datagen = load(os.path.join(BENCH, "datagen", "tpch.py"), "spec_datagen")

# clause 1.4: the columns of each table, in order
COLUMNS = {
    "region": "r_regionkey r_name r_comment",
    "nation": "n_nationkey n_name n_regionkey n_comment",
    "customer": "c_custkey c_name c_address c_nationkey c_phone c_acctbal "
                "c_mktsegment c_comment",
    "supplier": "s_suppkey s_name s_address s_nationkey s_phone s_acctbal "
                "s_comment",
    "part": "p_partkey p_name p_mfgr p_brand p_type p_size p_container "
            "p_retailprice p_comment",
    "partsupp": "ps_partkey ps_suppkey ps_availqty ps_supplycost ps_comment",
    "orders": "o_orderkey o_custkey o_orderstatus o_totalprice o_orderdate "
              "o_orderpriority o_clerk o_shippriority o_comment",
    "lineitem": "l_orderkey l_partkey l_suppkey l_linenumber l_quantity "
                "l_extendedprice l_discount l_tax l_returnflag l_linestatus "
                "l_shipdate l_commitdate l_receiptdate l_shipinstruct "
                "l_shipmode l_comment",
}
# clause 4.2.5 at SF1; lineitem is "approximately" 6,000,000 there
SF1 = {"region": 5, "nation": 25, "customer": 150_000, "supplier": 10_000,
       "part": 200_000, "partsupp": 800_000, "orders": 1_500_000}


@pytest.fixture(scope="module", params=(11, 2**31 + 3))
def tables(request, tmp_path_factory):
    out = tmp_path_factory.mktemp("tpch")
    paths = datagen.generate(str(out), REHEARSAL_ROWS, request.param)
    return {n: pq.read_table(p) for n, p in paths.items()}


def col(table, name):
    c = table.column(name)
    if str(c.type) == "date32[day]":
        c = c.cast("int32")
    return c.to_numpy(zero_copy_only=False)


def days(d):
    return (d - dt.date(1970, 1, 1)).days


def test_cardinalities_at_sf1():
    rows = datagen.table_rows(6_000_000)
    assert {k: rows[k] for k in SF1} == SF1
    assert abs(rows["lineitem"] - 6_001_215) < 6_001_215 * 0.001
    with open(os.path.join(BENCH, "configs", "tpch_sf1.json")) as fh:
        config = json.load(fh)
    assert config["rows"] == datagen.table_rows(config["scale_rows"])
    assert list(config["column_bytes"]["lineitem"]) == \
        COLUMNS["lineitem"].split()


def test_columns_and_rows(tables):
    rows = datagen.table_rows(REHEARSAL_ROWS)
    for name, table in tables.items():
        assert table.column_names == COLUMNS[name].split(), name
        assert table.num_rows == rows[name], name
        assert not any(table.column(c).null_count
                       for c in table.column_names), name


def test_text_lengths(tables):
    for column, (lo, hi) in datagen.TEXT_LENGTHS.items():
        table = next(t for t in tables.values()
                     if column in t.column_names)
        n = pc.utf8_length(table.column(column)).to_numpy()
        assert lo <= n.min() and n.max() <= hi, column
    phone = tables["customer"].column("c_phone").to_pylist()
    nation = col(tables["customer"], "c_nationkey")
    assert all(len(p) == 15 and p.startswith(f"{k + 10}-")
               for p, k in zip(phone, nation))
    name = tables["part"].column("p_name").to_pylist()
    assert all(len(set(n.split())) == 5 for n in name)
    assert tables["customer"].column("c_name")[0].as_py() == \
        "Customer#000000001"


def test_orders(tables):
    o, li = tables["orders"], tables["lineitem"]
    key = col(o, "o_orderkey")
    index = np.arange(len(key))
    assert (key == index // 8 * 32 + index % 8 + 1).all()  # 8 of every 32
    cust = col(o, "o_custkey")
    assert (cust % 3 != 0).all() and cust.min() >= 1
    assert cust.max() <= tables["customer"].num_rows
    date = col(o, "o_orderdate")
    assert days(dt.date(1992, 1, 1)) <= date.min()
    assert date.max() <= days(dt.date(1998, 12, 31)) - 151
    # one to seven lines an order, numbered from one, in the order's key
    lkey, number = col(li, "l_orderkey"), col(li, "l_linenumber")
    keys, first, counts = np.unique(lkey, return_index=True,
                                    return_counts=True)
    assert (keys == key).all()
    assert counts.min() == 1 and counts.max() == 7
    assert abs(counts.mean() - 4) < 0.01
    assert (number == np.arange(len(lkey)) - np.repeat(first, counts)
            + 1).all()
    # status and total price from the lines
    is_open = col(li, "l_linestatus") == "O"
    n_open = np.add.reduceat(is_open.astype(int), first)
    status = np.where(n_open == 0, "F", np.where(n_open == counts, "O", "P"))
    assert (col(o, "o_orderstatus") == status).all()
    total = np.add.reduceat(
        col(li, "l_extendedprice") * (1 + col(li, "l_tax"))
        * (1 - col(li, "l_discount")), first)
    assert np.abs(col(o, "o_totalprice") - total).max() < 0.006


def test_lineitem(tables):
    li, part = tables["lineitem"], tables["part"]
    pkey = col(li, "l_partkey")
    assert pkey.min() >= 1 and pkey.max() <= part.num_rows
    k = col(part, "p_partkey")
    retail = (90000 + (k // 10) % 20001 + 100 * (k % 1000)) / 100
    assert (col(part, "p_retailprice") == retail).all()
    qty = col(li, "l_quantity")
    assert set(np.unique(qty)) == set(range(1, 51))
    assert np.abs(col(li, "l_extendedprice")
                  - qty * retail[pkey - 1]).max() < 1e-6
    assert set(np.unique(np.round(col(li, "l_discount") * 100))) == \
        set(range(11))
    assert set(np.unique(np.round(col(li, "l_tax") * 100))) == set(range(9))
    # the supplier is one of the part's four in partsupp
    ps = tables["partsupp"]
    s = tables["supplier"].num_rows
    i = np.tile(np.arange(4), part.num_rows)
    pp = col(ps, "ps_partkey")
    assert (pp == np.repeat(k, 4)).all()
    assert (col(ps, "ps_suppkey")
            == (pp + i * (s // 4 + (pp - 1) // s)) % s + 1).all()
    four = col(ps, "ps_suppkey").reshape(-1, 4)[pkey - 1]
    assert (four == col(li, "l_suppkey")[:, None]).any(axis=1).all()
    # dates, and the flags that follow from them
    odate = dict(zip(col(tables["orders"], "o_orderkey"),
                     col(tables["orders"], "o_orderdate")))
    order = np.array([odate[x] for x in col(li, "l_orderkey")])
    ship, commit, receipt = (col(li, c) for c in (
        "l_shipdate", "l_commitdate", "l_receiptdate"))
    assert ((ship - order >= 1) & (ship - order <= 121)).all()
    assert ((commit - order >= 30) & (commit - order <= 90)).all()
    assert ((receipt - ship >= 1) & (receipt - ship <= 30)).all()
    today = days(dt.date(1995, 6, 17))
    flag, status = col(li, "l_returnflag"), col(li, "l_linestatus")
    assert (status == np.where(ship > today, "O", "F")).all()
    assert (flag[receipt > today] == "N").all()
    assert set(flag[receipt <= today]) == {"R", "A"}
    # Q1 sees the spec's four groups
    assert sorted(set(zip(flag, status))) == [
        ("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")]
    assert set(li.column("l_shipinstruct").to_pylist()) == {
        "DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"}
    assert len(set(li.column("l_shipmode").to_pylist())) == 7


def test_small_tables(tables):
    nation = tables["nation"]
    assert nation.column("n_name").to_pylist()[:3] == [
        "ALGERIA", "ARGENTINA", "BRAZIL"]
    assert col(nation, "n_regionkey").tolist() == [
        0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2, 3, 4, 2,
        3, 3, 1]
    for t, c in (("customer", "c_acctbal"), ("supplier", "s_acctbal")):
        bal = col(tables[t], c)
        assert -999.99 <= bal.min() and bal.max() <= 9999.99
    cost = col(tables["partsupp"], "ps_supplycost")
    assert 1.0 <= cost.min() and cost.max() <= 1000.0
    avail = col(tables["partsupp"], "ps_availqty")
    assert 1 <= avail.min() and avail.max() <= 9999


def test_same_seed_same_data(tmp_path):
    a = datagen.generate(str(tmp_path / "a"), 6000, 2**31 + 9)
    b = datagen.generate(str(tmp_path / "b"), 6000, 2**31 + 9)
    c = datagen.generate(str(tmp_path / "c"), 6000, 2**31 + 10)
    assert pq.read_table(a["lineitem"]).equals(pq.read_table(b["lineitem"]))
    assert not pq.read_table(a["lineitem"]).equals(
        pq.read_table(c["lineitem"]))
