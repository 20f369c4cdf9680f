"""TPC-H Q3, Q5 and Q18 by name, against the engine's DataFrame API: one
query stream of the power test (clause 5.3.3) cut to the three join
queries, each the DataFrame form of clause 2.4's text with its validation
parameters.

Q3 (2.4.3) and Q18 (2.4.18) are ``queries/tpch_joins.py``'s builders,
loaded from that file and not copied.  Q5 (2.4.5: REGION = ASIA, DATE =
1994-01-01) is written here from the clause, starting from
``spark_rapids_tpu/bench/tpch.py: q5``.  Its departures from the clause's
text, all of form:

* the DataFrame API joins on columns of one name, so a key is renamed to
  its partner's before a join (``c_custkey`` to ``o_custkey``,
  ``l_orderkey`` to ``o_orderkey``, ``l_suppkey`` to ``s_suppkey``,
  ``s_nationkey`` to ``n_nationkey``, ``r_regionkey`` to ``n_regionkey``);
* ``c_nationkey = s_nationkey`` is a filter over the joined rows, as the
  clause's WHERE has it, not a second key of the supplier join;
* ``l_extendedprice * (1 - l_discount)`` is computed in lineitem's
  projection, before the joins, as Q3's is;
* ``o_orderdate < date '1994-01-01' + interval '1' year`` is written with
  the date it comes to, 1995-01-01.

ORDERS and NATION are joined whole, every column of clause 1.4 with them:
the clause selects none away and the planner prunes none (PERF.md section
7), so what Q5 carries through its joins is the engine's to answer for.
``build(name, tables)`` is all that ``run.py`` calls.
"""

from __future__ import annotations

import datetime as dt
import importlib.util
import os

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.api import col, lit


def _sibling(name: str):
    """A builder file beside this one as a module, by path (``run.py``
    loads this file the same way: ``benchmark/`` is no package)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    spec = importlib.util.spec_from_file_location(
        "bench_queries_" + name.replace(".py", ""), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_joins = _sibling("tpch_joins.py")

TABLES = dict(_joins.TABLES, q5=("customer", "orders", "lineitem",
                                 "supplier", "nation", "region"))

Q5_REGION = "ASIA"
Q5_DATE = dt.date(1994, 1, 1)
Q5_DATE_END = dt.date(1995, 1, 1)


def q5(t):
    """TPC-H Q5 (2.4.5): local supplier volume; revenue by nation from
    the lines of one year's orders whose customer and supplier are of
    the same nation, in one region."""
    cust = t["customer"].select(col("c_custkey").alias("o_custkey"),
                                "c_nationkey")
    orders = t["orders"].filter((col("o_orderdate") >= lit(Q5_DATE))
                                & (col("o_orderdate") < lit(Q5_DATE_END)))
    lines = t["lineitem"].select(
        col("l_orderkey").alias("o_orderkey"),
        col("l_suppkey").alias("s_suppkey"),
        (col("l_extendedprice")
         * (lit(1.0) - col("l_discount"))).alias("volume"))
    supp = t["supplier"].select("s_suppkey",
                                col("s_nationkey").alias("n_nationkey"))
    region = (t["region"].filter(col("r_name") == lit(Q5_REGION))
              .select(col("r_regionkey").alias("n_regionkey")))
    return (cust.join(orders, "o_custkey")
            .join(lines, "o_orderkey")
            .join(supp, "s_suppkey")
            .filter(col("c_nationkey") == col("n_nationkey"))
            .join(t["nation"], "n_nationkey")
            .join(region, "n_regionkey")
            .group_by("n_name")
            .agg(F.sum(col("volume")).alias("revenue"))
            .order_by(col("revenue").desc()))


_BUILDERS = {"q3": _joins.q3, "q5": q5, "q18": _joins.q18}


def build(name: str, tables):
    """The DataFrame of query ``name`` over ``tables`` (name -> DataFrame)."""
    return _BUILDERS[name](tables)
