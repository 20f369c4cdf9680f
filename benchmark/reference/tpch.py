"""The plain reference for the TPC-H queries and templates the cells name.

Straight numpy over the parquet files the benchmark's own generator wrote:
float64 throughout, no engine import, nothing the program has made.  Each
function returns a ``pyarrow.Table`` in the query's own column and row
order.

``precision="bfloat16"`` is the control of "How correct is decided": the
same arithmetic with every DOUBLE column, literal and intermediate rounded
to bfloat16 (sums accumulated in float32, as a bf16 unit does) -- the step
below the f32 the configuration states for the device.  The benchmark's
own runs never ask for it; ``tests/control.py`` does.
"""

from __future__ import annotations

import datetime as dt
import functools
from typing import Dict, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

_EPOCH = dt.date(1970, 1, 1)


def _bf16(x):
    """Round float32 to the nearest bfloat16 (ties to even), kept as f32."""
    bits = np.asarray(x, dtype=np.float32).view(np.uint32)
    rounded = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16))
                                           & np.uint32(1)))
    return (rounded & np.uint32(0xFFFF0000)).view(np.float32)


class _Arith:
    """Rounding after every step, or none (float64)."""

    def __init__(self, precision: str):
        if precision not in ("float64", "bfloat16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.low = precision == "bfloat16"

    def num(self, x):
        return _bf16(x) if self.low else np.asarray(x, dtype=np.float64)

    def mul(self, a, b):
        return self.num(a * b)

    def add(self, a, b):
        return self.num(a + b)

    def sub(self, a, b):
        return self.num(a - b)

    def sum(self, x, groups=None, n_groups=0):
        """The sum of ``x``, or one sum per group code: accumulated in
        float64, or for the control in float32 (numpy's pairwise sum)."""
        acc = np.float32 if self.low else np.float64
        x = x.astype(acc)
        if groups is None:
            return x.sum(dtype=acc) if len(x) else None
        if not self.low:
            return np.bincount(groups, weights=x, minlength=n_groups)
        out = np.zeros(n_groups, dtype=acc)
        for k in np.flatnonzero(np.bincount(groups, minlength=n_groups)):
            out[k] = x[groups == k].sum(dtype=acc)
        return out


@functools.cache  # what no binding changes is worked out once a process
def _column(path: str, name: str):
    """One column of one parquet file as read, shared read-only."""
    c = pq.read_table(path, columns=[name]).column(name)
    if pa.types.is_string(c.type):
        return c.combine_chunks()
    if pa.types.is_date32(c.type):
        c = c.cast(pa.int32())
    values = c.to_numpy(zero_copy_only=False)
    values.flags.writeable = False
    return values


def _columns(path: str, names: Sequence[str]) -> Dict[str, np.ndarray]:
    """The named columns, each read once a process: 141 bindings of a
    served window cost one read and 141 passes over it."""
    return {n: _column(path, n) for n in names}


def _days(d: dt.date) -> int:
    return (d - _EPOCH).days


@functools.cache
def _q1_rows(path: str, precision: str):
    """Q1 before its filter, which is all that a binding changes: every
    row's group code, the groups' names by code, and the columns it sums.
    Each is worked out element by element, so a row reads the same whether
    the filter comes before or after."""
    ar = _Arith(precision)
    c = _columns(path, (
        "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
        "l_discount", "l_tax"))
    flag = c["l_returnflag"].dictionary_encode()
    status = c["l_linestatus"].dictionary_encode()
    n_status = len(status.dictionary)
    code = (flag.indices.to_numpy().astype(np.int64) * n_status
            + status.indices.to_numpy())
    names = [(f.as_py(), s.as_py())
             for f in flag.dictionary for s in status.dictionary]
    qty, price, disc, tax = (ar.num(c[k]) for k in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    one = ar.num(1.0)
    disc_price = ar.mul(price, ar.sub(one, disc))
    charge = ar.mul(disc_price, ar.add(one, tax))
    return code, names, {
        "sum_qty": qty, "sum_base_price": price,
        "sum_disc_price": disc_price, "sum_charge": charge, "avg_disc": disc}


def q1_until(paths, last_day: dt.date, precision: str = "float64") -> pa.Table:
    """Q1 over the rows shipped on or before ``last_day``."""
    ar = _Arith(precision)
    path = paths["lineitem"]
    code, names, columns = _q1_rows(path, precision)
    keep = _columns(path, ("l_shipdate",))["l_shipdate"] <= _days(last_day)
    code = code[keep]
    count = np.bincount(code, minlength=len(names))
    live = np.array(sorted(np.flatnonzero(count), key=lambda k: names[k]),
                    dtype=np.int64)
    sums = {k: ar.sum(v[keep], code, len(names))[live].astype(np.float64)
            for k, v in columns.items()}
    cnt = count[live]
    return pa.table({
        "l_returnflag": [names[k][0] for k in live],
        "l_linestatus": [names[k][1] for k in live],
        "sum_qty": sums["sum_qty"],
        "sum_base_price": sums["sum_base_price"],
        "sum_disc_price": sums["sum_disc_price"],
        "sum_charge": sums["sum_charge"],
        "avg_qty": sums["sum_qty"] / cnt,
        "avg_price": sums["sum_base_price"] / cnt,
        "avg_disc": sums["avg_disc"] / cnt,
        "count_order": cnt.astype(np.int64)})


def q1(paths, precision: str = "float64") -> pa.Table:
    """Q1 with the validation DELTA of 90 days before 1998-12-01."""
    return q1_until(paths, dt.date(1998, 9, 2), precision)


def _q6(paths, lo: dt.date, hi: dt.date, d_lo: float, d_hi: float,
        qty: float, precision: str) -> pa.Table:
    ar = _Arith(precision)
    c = _columns(paths["lineitem"], (
        "l_shipdate", "l_discount", "l_quantity", "l_extendedprice"))
    disc, quantity = ar.num(c["l_discount"]), ar.num(c["l_quantity"])
    keep = ((c["l_shipdate"] >= _days(lo)) & (c["l_shipdate"] < _days(hi))
            & (disc >= ar.num(d_lo)) & (disc <= ar.num(d_hi))
            & (quantity < ar.num(qty)))
    revenue = ar.sum(ar.mul(ar.num(c["l_extendedprice"])[keep], disc[keep]))
    return pa.table({"revenue": pa.array(
        [None if revenue is None else float(revenue)], pa.float64())})


def q6(paths, precision: str = "float64") -> pa.Table:
    return _q6(paths, dt.date(1994, 1, 1), dt.date(1995, 1, 1), 0.05, 0.07,
               24.0, precision)


def q6_template(paths, params, precision: str = "float64") -> pa.Table:
    return _q6(paths, *params, precision)


QUERIES = {"q1": q1, "q6": q6}
TEMPLATES = {"q6": q6_template}
