"""The plain reference for the prepared templates of TPC-H's throughput
test (clause 5.3.4) as the cell ``tpch_sf1_served.throughput`` sends them:
Q6 with clause 2.4.6.3's parameters and Q1 with clause 2.4.1.3's DELTA.

Straight numpy over the parquet files the benchmark's own generator wrote:
float64 throughout, no engine import, nothing the program has made.  Both
queries are ``reference/tpch.py``'s, loaded from the file beside this one,
with the binding in place of the validation parameters.  Each function
returns a ``pyarrow.Table`` in the query's own column and row order, and
takes ``precision="bfloat16"`` for the control, as ``reference/tpch.py``
does.
"""

from __future__ import annotations

import datetime as dt
import importlib.util
import os

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
    return _tpch.q1_until(paths, _Q1_END - dt.timedelta(days=int(delta)),
                          precision)


TEMPLATES = {"q6": _tpch.q6_template, "q1": q1_template}
