"""TPC-H query builders by name, against the engine's DataFrame API.

Copies of ``spark_rapids_tpu/bench/tpch.py`` (q1, q6), with the validation
parameters of TPC-H clause 2.4.  A later cell adds its builders to a file
of its own; ``build(name, tables)`` is all that ``run.py`` calls.
"""

from __future__ import annotations

import datetime as dt

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.api import col, lit

TABLES = {"q1": ("lineitem",), "q6": ("lineitem",)}


def q1(t):
    """TPC-H Q1: pricing summary report."""
    disc_price = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    charge = disc_price * (lit(1.0) + col("l_tax"))
    return (t["lineitem"]
            .filter(col("l_shipdate") <= lit(dt.date(1998, 9, 2)))
            .group_by("l_returnflag", "l_linestatus")
            .agg(F.sum(col("l_quantity")).alias("sum_qty"),
                 F.sum(col("l_extendedprice")).alias("sum_base_price"),
                 F.sum(disc_price).alias("sum_disc_price"),
                 F.sum(charge).alias("sum_charge"),
                 F.avg(col("l_quantity")).alias("avg_qty"),
                 F.avg(col("l_extendedprice")).alias("avg_price"),
                 F.avg(col("l_discount")).alias("avg_disc"),
                 F.count(lit(1)).alias("count_order"))
            .order_by("l_returnflag", "l_linestatus"))


def q6(t):
    """TPC-H Q6: forecasting revenue change."""
    return (t["lineitem"].filter(
        (col("l_shipdate") >= lit(dt.date(1994, 1, 1)))
        & (col("l_shipdate") < lit(dt.date(1995, 1, 1)))
        & (col("l_discount") >= lit(0.05))
        & (col("l_discount") <= lit(0.07))
        & (col("l_quantity") < lit(24.0)))
        .agg(F.sum(col("l_extendedprice") * col("l_discount"))
             .alias("revenue")))


_BUILDERS = {"q1": q1, "q6": q6}


def build(name: str, tables):
    """The DataFrame of query ``name`` over ``tables`` (name -> DataFrame)."""
    return _BUILDERS[name](tables)
