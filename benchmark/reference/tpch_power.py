"""The plain reference for TPC-H Q3, Q5 and Q18 (clauses 2.4.3, 2.4.5 and
2.4.18, with their validation parameters).

Q3 and Q18 are ``reference/tpch_joins.py``'s, loaded from that file and not
copied; Q5 is written here with that file's tools (its column reader, its
sort-and-search join, its two arithmetics): straight numpy over the parquet
files the benchmark's own generator wrote, float64 throughout, no engine
import, nothing the program has made.  Departures from clause 2.4.5's text,
all of form and none of meaning:

* a join is a sort of the unique side's keys and a binary search of the
  other side's (``_lookup``), not a hash table: the same pairs;
* the lines are taken through ORDERS to CUSTOMER and through SUPPLIER
  separately and ``c_nationkey = s_nationkey`` then compares the two, where
  the clause lists six tables and six equalities: the same rows;
* Q5 groups by the nation's key where the clause groups by ``n_name``:
  ``n_nationkey`` is NATION's primary key (clause 1.4.2) and no two nations
  share a name, so the groups are the same;
* the clause leaves the order of nations that tie on revenue open; here
  such rows follow in ascending ``n_name``.

``precision="bfloat16"`` is the control, as in ``tpch_joins.py``: every
DOUBLE column, literal and intermediate rounded to bfloat16, sums
accumulated in float32.
"""

from __future__ import annotations

import datetime as dt
import importlib.util
import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc


def _sibling(name: str):
    """A reference file beside this one as a module, by path."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + name.replace(".py", ""), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_joins = _sibling("tpch_joins.py")

Q5_REGION = "ASIA"
Q5_DATE = dt.date(1994, 1, 1)
Q5_DATE_END = dt.date(1995, 1, 1)


def q5(paths, precision: str = "float64") -> pa.Table:
    """Q5: the lines of one year's orders whose customer and supplier
    are of one nation, in one region; revenue by nation, highest first."""
    ar = _joins._Arith(precision)
    r = _joins._columns(paths["region"], ("r_regionkey", "r_name"))
    n = _joins._columns(paths["nation"], ("n_nationkey", "n_name",
                                          "n_regionkey"))
    c = _joins._columns(paths["customer"], ("c_custkey", "c_nationkey"))
    s = _joins._columns(paths["supplier"], ("s_suppkey", "s_nationkey"))
    o = _joins._columns(paths["orders"], ("o_orderkey", "o_custkey",
                                          "o_orderdate"))
    ln = _joins._columns(paths["lineitem"], ("l_orderkey", "l_suppkey",
                                             "l_extendedprice",
                                             "l_discount"))
    in_region = pc.equal(r["r_name"], Q5_REGION) \
        .to_numpy(zero_copy_only=False)
    # nation x region on n_regionkey = r_regionkey
    nations, _ = _joins._lookup(r["r_regionkey"][in_region],
                                n["n_regionkey"])
    # orders of the year x customer on o_custkey = c_custkey
    year = np.flatnonzero((o["o_orderdate"] >= _joins._days(Q5_DATE))
                          & (o["o_orderdate"] < _joins._days(Q5_DATE_END)))
    found, cust = _joins._lookup(c["c_custkey"], o["o_custkey"][year])
    orders, order_nation = year[found], c["c_nationkey"][cust]
    # ... x lineitem on l_orderkey = o_orderkey
    lines, of = _joins._lookup(o["o_orderkey"][orders], ln["l_orderkey"])
    # ... x supplier on l_suppkey = s_suppkey and c_nationkey = s_nationkey
    found, supp = _joins._lookup(s["s_suppkey"], ln["l_suppkey"][lines])
    local = s["s_nationkey"][supp] == order_nation[of[found]]
    lines, nation = lines[found][local], s["s_nationkey"][supp][local]
    # ... x nation on s_nationkey = n_nationkey, the region's nations only
    found, group = _joins._lookup(n["n_nationkey"][nations], nation)
    lines = lines[found]
    volume = ar.mul(ar.num(ln["l_extendedprice"][lines]),
                    ar.sub(ar.num(1.0), ar.num(ln["l_discount"][lines])))
    revenue = ar.group_sum(volume, group, len(nations))
    live = np.flatnonzero(np.bincount(group, minlength=len(nations)))
    names = n["n_name"].take(pa.array(nations[live], pa.int64()))
    revenue = revenue[live]
    text = names.to_pylist()
    top = sorted(range(len(live)), key=lambda i: (-revenue[i], text[i]))
    return pa.table({
        "n_name": names.take(pa.array(top, pa.int64())),
        "revenue": pa.array(revenue[top], pa.float64())})


QUERIES = {"q3": _joins.q3, "q5": q5, "q18": _joins.q18}
