"""The plain reference for TPC-H Q3 and Q18 (clause 2.4.3 and 2.4.18, with
their validation parameters).

Straight numpy over the parquet files the benchmark's own generator wrote:
float64 throughout, no engine import, nothing the program has made.  Each
function returns a ``pyarrow.Table`` in the query's own column and row
order.  Departures from the clauses' text, all of form and none of meaning:

* a join is a sort of the unique side's keys and a binary search of the
  other side's (``_lookup``), not a hash table: the same pairs;
* Q3 groups by the joined order's position where the clause groups by
  ``l_orderkey, o_orderdate, o_shippriority``, and Q18 by the order where
  the clause groups by ``c_name, c_custkey, o_orderkey, o_orderdate,
  o_totalprice``: ``o_orderkey`` is the primary key of ORDERS (clause
  1.4.2) and every other grouping column depends on it, so the groups are
  the same;
* the clauses leave the order of rows that tie on every ORDER BY column
  open; here such rows follow in ascending order key.

``precision="bfloat16"`` is the control of "How correct is decided": the
same arithmetic with every DOUBLE column, literal and intermediate rounded
to bfloat16 and sums accumulated in float32, the step below the f32 the
configuration states for the device.  The benchmark's own runs never ask
for it; ``tests/control.py`` does.
"""

from __future__ import annotations

import datetime as dt
import functools
from typing import Dict, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

_EPOCH = dt.date(1970, 1, 1)

Q3_SEGMENT = "BUILDING"
Q3_DATE = dt.date(1995, 3, 15)
Q3_LIMIT = 10
Q18_QUANTITY = 300.0
Q18_LIMIT = 100


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

    def sub(self, a, b):
        return self.num(a - b)

    def group_sum(self, x, groups, n_groups: int):
        """One sum of ``x`` per group code, as float64: accumulated in
        float64, or for the control in float32 row after row."""
        if not self.low:
            return np.bincount(groups, weights=x, minlength=n_groups)
        out = np.zeros(n_groups, dtype=np.float32)
        if len(x):
            order = np.argsort(groups, kind="stable")
            codes = groups[order]
            first = np.flatnonzero(np.r_[True, codes[1:] != codes[:-1]])
            out[codes[first]] = np.add.reduceat(
                x[order].astype(np.float32), first, dtype=np.float32)
        return out.astype(np.float64)


@functools.cache
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
    return {n: _column(path, n) for n in names}


def _days(d: dt.date) -> int:
    return (d - _EPOCH).days


def _dates(days) -> pa.Array:
    return pa.array(np.asarray(days, dtype=np.int32), pa.int32()) \
        .cast(pa.date32())


def _lookup(unique_keys: np.ndarray, keys: np.ndarray):
    """The equi-join of ``keys`` against ``unique_keys`` (no key twice):
    ``(rows of keys that found a partner, the partner's position)``."""
    if not len(unique_keys) or not len(keys):
        none = np.zeros(0, dtype=np.int64)
        return none, none
    order = np.argsort(unique_keys, kind="stable")
    ordered = unique_keys[order]
    at = np.minimum(np.searchsorted(ordered, keys), len(ordered) - 1)
    found = np.flatnonzero(ordered[at] == keys)
    return found, order[at[found]]


def q3(paths, precision: str = "float64") -> pa.Table:
    """Q3: customers of one segment, their orders before a date, those
    orders' lines shipped after it; revenue by order, the ten highest."""
    ar = _Arith(precision)
    c = _columns(paths["customer"], ("c_custkey", "c_mktsegment"))
    o = _columns(paths["orders"], ("o_orderkey", "o_custkey", "o_orderdate",
                                   "o_shippriority"))
    ln = _columns(paths["lineitem"], ("l_orderkey", "l_shipdate",
                                      "l_extendedprice", "l_discount"))
    in_segment = pc.equal(c["c_mktsegment"], Q3_SEGMENT) \
        .to_numpy(zero_copy_only=False)
    # customer x orders on c_custkey = o_custkey
    early = np.flatnonzero(o["o_orderdate"] < _days(Q3_DATE))
    found, _ = _lookup(c["c_custkey"][in_segment], o["o_custkey"][early])
    orders = early[found]
    # ... x lineitem on l_orderkey = o_orderkey
    late = np.flatnonzero(ln["l_shipdate"] > _days(Q3_DATE))
    found, of = _lookup(o["o_orderkey"][orders], ln["l_orderkey"][late])
    lines = late[found]
    volume = ar.mul(ar.num(ln["l_extendedprice"][lines]),
                    ar.sub(ar.num(1.0), ar.num(ln["l_discount"][lines])))
    revenue = ar.group_sum(volume, of, len(orders))
    live = np.flatnonzero(np.bincount(of, minlength=len(orders)))
    key, date = o["o_orderkey"][orders][live], o["o_orderdate"][orders][live]
    revenue = revenue[live]
    top = np.lexsort((key, date, -revenue))[:Q3_LIMIT]
    return pa.table({
        "l_orderkey": pa.array(key[top], pa.int64()),
        "revenue": pa.array(revenue[top], pa.float64()),
        "o_orderdate": _dates(date[top]),
        "o_shippriority": pa.array(
            o["o_shippriority"][orders][live][top], pa.int64())})


def q18(paths, precision: str = "float64") -> pa.Table:
    """Q18: the orders whose lines' quantities sum over a threshold, with
    their customers; the hundred of the highest total price."""
    ar = _Arith(precision)
    c = _columns(paths["customer"], ("c_custkey", "c_name"))
    o = _columns(paths["orders"], ("o_orderkey", "o_custkey", "o_orderdate",
                                   "o_totalprice"))
    ln = _columns(paths["lineitem"], ("l_orderkey", "l_quantity"))
    quantity = ar.num(ln["l_quantity"])
    # the subquery: l_orderkey ... group by l_orderkey having sum > :1
    keys, code = np.unique(ln["l_orderkey"], return_inverse=True)
    large = keys[ar.group_sum(quantity, code, len(keys))
                 > ar.num(Q18_QUANTITY)]
    # o_orderkey in (subquery)
    orders, _ = _lookup(large, o["o_orderkey"])
    # customer x orders on c_custkey = o_custkey
    found, cust = _lookup(c["c_custkey"], o["o_custkey"][orders])
    orders = orders[found]
    # ... x lineitem on o_orderkey = l_orderkey, and the outer GROUP BY
    lines, of = _lookup(o["o_orderkey"][orders], ln["l_orderkey"])
    sum_qty = ar.group_sum(quantity[lines], of, len(orders))
    live = np.flatnonzero(np.bincount(of, minlength=len(orders)))
    orders, cust, sum_qty = orders[live], cust[live], sum_qty[live]
    price = ar.num(o["o_totalprice"][orders]).astype(np.float64)
    date, key = o["o_orderdate"][orders], o["o_orderkey"][orders]
    top = np.lexsort((key, date, -price))[:Q18_LIMIT]
    return pa.table({
        "c_name": c["c_name"].take(pa.array(cust[top], pa.int64())),
        "c_custkey": pa.array(c["c_custkey"][cust[top]], pa.int64()),
        "o_orderkey": pa.array(key[top], pa.int64()),
        "o_orderdate": _dates(date[top]),
        "o_totalprice": pa.array(price[top], pa.float64()),
        "sum_qty": pa.array(sum_qty[top], pa.float64())})


QUERIES = {"q3": q3, "q18": q18}
