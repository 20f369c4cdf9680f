"""TPC-H Q3 and Q18 by name, against the engine's DataFrame API: the
DataFrame form of clause 2.4's text with its validation parameters (2.4.3:
SEGMENT = BUILDING, DATE = 1995-03-15; 2.4.18: QUANTITY = 300).

The DataFrame API joins on columns of one name, so a key is renamed to its
partner's before a join; nothing else departs from the clause.
``spark_rapids_tpu/bench/tpch.py`` has a ``q18`` of its own that is not the
clause's (quantity 212, no ``o_totalprice``, another ORDER BY): this one
follows 2.4.18.  ``build(name, tables)`` is all that ``run.py`` calls.
"""

from __future__ import annotations

import datetime as dt

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.api import col, lit

TABLES = {"q3": ("customer", "orders", "lineitem"),
          "q18": ("customer", "orders", "lineitem")}

Q3_SEGMENT = "BUILDING"
Q3_DATE = dt.date(1995, 3, 15)
Q18_QUANTITY = 300.0


def q3(t):
    """TPC-H Q3 (2.4.3): shipping priority; the ten unshipped orders of
    the highest revenue."""
    cust = (t["customer"].filter(col("c_mktsegment") == lit(Q3_SEGMENT))
            .select(col("c_custkey").alias("o_custkey")))
    orders = (t["orders"].filter(col("o_orderdate") < lit(Q3_DATE))
              .select(col("o_orderkey").alias("l_orderkey"), "o_custkey",
                      "o_orderdate", "o_shippriority"))
    lines = (t["lineitem"].filter(col("l_shipdate") > lit(Q3_DATE))
             .select("l_orderkey",
                     (col("l_extendedprice")
                      * (lit(1.0) - col("l_discount"))).alias("volume")))
    return (cust.join(orders, "o_custkey")
            .join(lines, "l_orderkey")
            .group_by("l_orderkey", "o_orderdate", "o_shippriority")
            .agg(F.sum(col("volume")).alias("revenue"))
            .select("l_orderkey", "revenue", "o_orderdate",
                    "o_shippriority")
            .order_by(col("revenue").desc(), "o_orderdate")
            .limit(10))


def q18(t):
    """TPC-H Q18 (2.4.18): large volume customers.  ``o_orderkey IN
    (subquery)`` is a left semi join against the subquery's keys."""
    large = (t["lineitem"].group_by("l_orderkey")
             .agg(F.sum(col("l_quantity")).alias("order_qty"))
             .filter(col("order_qty") > lit(Q18_QUANTITY))
             .select(col("l_orderkey").alias("o_orderkey")))
    orders = (t["orders"]
              .select("o_orderkey", col("o_custkey").alias("c_custkey"),
                      "o_orderdate", "o_totalprice")
              .join(large, "o_orderkey", "semi"))
    lines = t["lineitem"].select(col("l_orderkey").alias("o_orderkey"),
                                 "l_quantity")
    return (t["customer"].select("c_name", "c_custkey")
            .join(orders, "c_custkey")
            .join(lines, "o_orderkey")
            .group_by("c_name", "c_custkey", "o_orderkey", "o_orderdate",
                      "o_totalprice")
            .agg(F.sum(col("l_quantity")).alias("sum_qty"))
            .order_by(col("o_totalprice").desc(), "o_orderdate")
            .limit(100))


_BUILDERS = {"q3": q3, "q18": q18}


def build(name: str, tables):
    """The DataFrame of query ``name`` over ``tables`` (name -> DataFrame)."""
    return _BUILDERS[name](tables)
