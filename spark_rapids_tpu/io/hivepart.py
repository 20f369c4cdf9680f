"""Hive-style partition discovery for file scans.

Reference: ColumnarPartitionReaderWithPartitionValues.scala:32 — the
reference appends the partition-value columns (parsed from the
``col=value/`` directory layout) to every batch a partitioned read
produces, and PartitioningAwareFileIndex prunes directories against
partition predicates before any file is opened.

Here: ``discover`` parses the directory segments between the scan root
and each file, infers partition column types (int64 -> float64 ->
string, Spark's inference order for the types this engine supports),
and the scan execs 1) prune files whose partition values cannot satisfy
pushed-down predicates and 2) append one constant column per partition
field to every batch of that file.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

from spark_rapids_tpu.columnar.dtypes import (
    Field, FLOAT64, INT64, Schema, STRING,
)

_HIVE_NULL = "__HIVE_DEFAULT_PARTITION__"


def _hive_unescape(s: str) -> str:
    out = []
    i = 0
    while i < len(s):
        ch = s[i]
        if ch == "%" and i + 2 < len(s) + 1 and i + 3 <= len(s):
            try:
                out.append(chr(int(s[i + 1:i + 3], 16)))
                i += 3
                continue
            except ValueError:
                pass
        out.append(ch)
        i += 1
    return "".join(out)


def _parse_segments(rel: str) -> List[Tuple[str, Optional[str]]]:
    """dir segments of a relative file path -> [(col, value|None)]."""
    out = []
    for seg in rel.split(os.sep)[:-1]:  # last segment is the file
        if "=" not in seg:
            return []
        name, _, raw = seg.partition("=")
        if not name:
            return []
        out.append((name, None if raw == _HIVE_NULL
                    else _hive_unescape(raw)))
    return out


def discover(roots: Sequence[str], files: Sequence[str]):
    """-> (partition Schema or None, per-file value tuples).

    Partitioning applies only when EVERY file carries the same ordered
    partition-column list; otherwise the layout is treated as plain
    files (matching Spark, which errors on conflicting layouts — being
    permissive here keeps ad-hoc globs working)."""
    norm_roots = sorted((os.path.abspath(r) for r in roots
                         if os.path.isdir(r)), key=len, reverse=True)
    per_file: List[List[Tuple[str, Optional[str]]]] = []
    for f in files:
        af = os.path.abspath(f)
        segs: List[Tuple[str, Optional[str]]] = []
        for r in norm_roots:
            if af.startswith(r + os.sep):
                segs = _parse_segments(os.path.relpath(af, r))
                break
        per_file.append(segs)
    if not per_file or not per_file[0]:
        return None, []
    cols = [c for c, _ in per_file[0]]
    for segs in per_file:
        if [c for c, _ in segs] != cols:
            return None, []

    # type inference per column: int64 -> float64 -> string
    values: Dict[str, List[Optional[str]]] = {
        c: [dict(segs)[c] for segs in per_file] for c in cols}
    fields = []
    typed: List[List] = []
    for c in cols:
        vs = values[c]
        for caster, dt in ((int, INT64), (float, FLOAT64)):
            try:
                tv = [None if v is None else caster(v) for v in vs]
                break
            except (TypeError, ValueError):
                continue
        else:
            tv, dt = list(vs), STRING
        fields.append(Field(c, dt, True))
        typed.append(tv)
    part_schema = Schema(fields)
    file_values = [tuple(typed[ci][fi] for ci in range(len(cols)))
                   for fi in range(len(files))]
    return part_schema, file_values


def narrow(part_schema: Optional[Schema], file_values, names):
    """``discover``'s result cut to the partition columns among ``names``:
    a scan the planner pruned appends only the columns its plan reads."""
    if part_schema is None:
        return None, []
    keep = [i for i, f in enumerate(part_schema) if f.name in names]
    if len(keep) == len(part_schema.fields):
        return part_schema, file_values
    if not keep:
        return None, []
    return (Schema([part_schema.fields[i] for i in keep]),
            [tuple(v[i] for i in keep) for v in file_values])


def prune_files(part_schema: Schema, file_values, files, pred):
    """Files whose partition values can satisfy the pushed-down simple
    predicates (the PartitioningAwareFileIndex pruning analog)."""
    if pred is None or part_schema is None:
        return files, file_values
    from spark_rapids_tpu.io.parquet import _collect_simple_predicates
    checks = _collect_simple_predicates(pred)
    if not checks:
        return files, file_values
    idx = {f.name: i for i, f in enumerate(part_schema)}
    keep_f, keep_v = [], []
    for f, vals in zip(files, file_values):
        ok = True
        for (name, op, value) in checks:
            i = idx.get(name)
            if i is None:
                continue
            v = vals[i]
            if v is None:
                ok = False
                break
            try:
                if op == "eq" and not v == value:
                    ok = False
                elif op == "lt" and not v < value:
                    ok = False
                elif op == "le" and not v <= value:
                    ok = False
                elif op == "gt" and not v > value:
                    ok = False
                elif op == "ge" and not v >= value:
                    ok = False
            except TypeError:
                continue
            if not ok:
                break
        if ok:
            keep_f.append(f)
            keep_v.append(vals)
    return keep_f, keep_v


def append_partition_columns(batch, part_schema: Schema, vals,
                             device=None):
    """Append one constant column per partition field to a device
    batch (the ColumnarPartitionReaderWithPartitionValues append)."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import DeviceColumn
    cols = list(batch.columns)
    cap = batch.capacity
    n = batch.rows_bound  # scalar columns only need the capacity bound
    for f, v in zip(part_schema, vals):
        cols.append(DeviceColumn.from_scalar(
            f.dtype, v, n, capacity=cap))
    full = Schema(list(batch.schema.fields) + list(part_schema.fields)) \
        if batch.schema is not None else None
    return ColumnarBatch(cols, batch.rows_raw, full)


def append_partition_arrow(rb, part_schema: Schema, vals):
    """Host-side analog for the CPU engine scans."""
    import pyarrow as pa
    from spark_rapids_tpu.columnar.dtypes import to_arrow_type
    arrays = [rb.column(i) for i in range(rb.num_columns)]
    names = list(rb.schema.names)
    for f, v in zip(part_schema, vals):
        at = to_arrow_type(f.dtype)
        arrays.append(pa.array([v] * rb.num_rows, type=at))
        names.append(f.name)
    return pa.RecordBatch.from_arrays(arrays, names=names)
