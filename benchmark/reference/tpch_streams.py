"""The plain reference for the prepared templates of TPC-H's throughput
test (clause 5.3.4) as the cell ``tpch_sf1_served.throughput`` sends them:
Q6 with clause 2.4.6.3's parameters and Q1 with clause 2.4.1.3's DELTA.

Straight numpy over the parquet files the benchmark's own generator wrote:
float64 throughout, no engine import, nothing the program has made.  The
arithmetic, the column reader and Q6 are ``reference/tpch.py``'s, loaded
from the file beside this one; Q1 is written out here because that file's
``q1`` holds the validation DELTA (90) inside it.  Each function returns a
``pyarrow.Table`` in the query's own column and row order, and takes
``precision="bfloat16"`` for the control, as ``reference/tpch.py`` does.
"""

from __future__ import annotations

import datetime as dt
import importlib.util
import os

import numpy as np
import pyarrow as pa


def _beside(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    spec = importlib.util.spec_from_file_location("bench_reference_tpch",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_tpch = _beside("tpch.py")

_Q1_END = dt.date(1998, 12, 1)  # clause 2.4.1.2: date '1998-12-01'


def q1_template(paths, params, precision: str = "float64") -> pa.Table:
    """Q1 with ``l_shipdate <= date '1998-12-01' - interval 'DELTA' day``;
    ``params`` is ``(DELTA,)``, a whole number of days."""
    (delta,) = params
    ar = _tpch._Arith(precision)
    c = _tpch._columns(paths["lineitem"], (
        "l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
        "l_extendedprice", "l_discount", "l_tax"))
    flag = c["l_returnflag"].dictionary_encode()
    status = c["l_linestatus"].dictionary_encode()
    n_status = len(status.dictionary)
    keep = c["l_shipdate"] <= _tpch._days(
        _Q1_END - dt.timedelta(days=int(delta)))
    code = (flag.indices.to_numpy().astype(np.int64) * n_status
            + status.indices.to_numpy())[keep]
    qty, price, disc, tax = (ar.num(c[k][keep]) for k in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    one = ar.num(1.0)
    disc_price = ar.mul(price, ar.sub(one, disc))
    charge = ar.mul(disc_price, ar.add(one, tax))
    n_codes = len(flag.dictionary) * n_status
    count = np.bincount(code, minlength=n_codes)
    groups = sorted(
        (flag.dictionary[int(k) // n_status].as_py(),
         status.dictionary[int(k) % n_status].as_py(), int(k))
        for k in np.flatnonzero(count))
    live = np.array([k for _, _, k in groups], dtype=np.int64)

    def total(x):
        return ar.sum(x, code, n_codes)[live].astype(np.float64)

    cnt = count[live]
    sum_qty, sum_price, sum_disc = total(qty), total(price), total(disc)
    return pa.table({
        "l_returnflag": [g[0] for g in groups],
        "l_linestatus": [g[1] for g in groups],
        "sum_qty": sum_qty,
        "sum_base_price": sum_price,
        "sum_disc_price": total(disc_price),
        "sum_charge": total(charge),
        "avg_qty": sum_qty / cnt,
        "avg_price": sum_price / cnt,
        "avg_disc": sum_disc / cnt,
        "count_order": cnt.astype(np.int64)})


TEMPLATES = {"q6": _tpch.q6_template, "q1": q1_template}
