"""The comparison that decides ``correct``: one answer against the plain
reference's, as numbers that each have a limit of their own.

``exact_mismatches`` counts everything that has to be equal and is not: the
column names and their order, the row count, nulls, and every integer,
date, boolean and string cell.  ``gaps`` gives, for every float column, the
widest ``|got - ref| / max(|ref|, floor)`` over its cells: one number per
column, because what rounding does to a column depends on how it is
computed, so each has readings and a limit of its own.  Rows are compared in
the order returned: every query here orders its answer or returns one row.
"""

from __future__ import annotations

import math
from typing import Iterable, Tuple

import pyarrow as pa


def _is_float(t: pa.DataType) -> bool:
    return pa.types.is_floating(t) or pa.types.is_decimal(t)


def compare_tables(got: pa.Table, ref: pa.Table,
                   floor: float = 1e-9) -> dict:
    """``{"exact_mismatches": n, "gaps": {column: g}}`` for one answer."""
    if got.column_names != ref.column_names:
        return {"exact_mismatches": max(1, ref.num_rows * ref.num_columns),
                "gaps": {}}
    if got.num_rows != ref.num_rows:
        return {"exact_mismatches": max(1, abs(got.num_rows - ref.num_rows))
                * max(1, ref.num_columns), "gaps": {}}
    mismatches, gaps = 0, {}
    for name in ref.column_names:
        g, r = got.column(name).to_pylist(), ref.column(name).to_pylist()
        as_float = _is_float(ref.schema.field(name).type)
        if as_float != _is_float(got.schema.field(name).type):
            mismatches += len(r)
            continue
        if as_float:
            gaps[name] = 0.0
        for a, b in zip(g, r):
            if a is None or b is None:
                mismatches += a is not b
            elif not as_float:
                mismatches += a != b
            elif not (math.isfinite(a) and math.isfinite(b)):
                mismatches += not (a == b or (math.isnan(a)
                                              and math.isnan(b)))
            else:
                gaps[name] = max(gaps[name],
                                 abs(a - b) / max(abs(b), floor))
    return {"exact_mismatches": mismatches, "gaps": gaps}


def worst(results: Iterable[Tuple[str, dict]]) -> dict:
    """Over many ``(query, comparison)``: the sum of the exact mismatches,
    and the widest gap of every ``<query>.<column>``."""
    total = {"exact_mismatches": 0, "gaps": {}}
    for query, r in results:
        total["exact_mismatches"] += r["exact_mismatches"]
        for column, gap in r["gaps"].items():
            key = f"{query}.{column}"
            total["gaps"][key] = max(total["gaps"].get(key, 0.0), gap)
    return total


def gap_limit(guarantees: dict, key: str):
    """The limit of ``<query>.<column>``: its own, or the default."""
    limits = guarantees["float_rel_gap_limits"]
    return limits.get(key, limits["default"])
