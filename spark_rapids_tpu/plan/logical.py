"""Logical plan nodes produced by the DataFrame API.

The Catalyst-analog input to the planner.  Expressions inside are
*unresolved* (attribute references by name); the planner binds them against
child output schemas during tagging (reference: Spark resolves before the
plugin sees the plan; here resolution and tagging happen together).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import pyarrow as pa

from spark_rapids_tpu.columnar.dtypes import Schema, Field
from spark_rapids_tpu.exprs.base import (
    Expression, Alias, bind_expression,
)


class LogicalPlan:
    children: List["LogicalPlan"] = []

    @property
    def node_name(self) -> str:
        return type(self).__name__

    def output_schema(self) -> Schema:
        """Resolved output schema (computed bottom-up)."""
        raise NotImplementedError(type(self).__name__)

    def __init_subclass__(cls, **kw):
        """Memoize ``output_schema`` per node: nodes are immutable once
        built (rewrite passes rebuild rather than mutate), and schema
        resolution recurses into children — without the cache a chain of
        Project/Window nodes recomputes child schemas once per expression,
        which is exponential in plan depth."""
        super().__init_subclass__(**kw)
        if "output_schema" in cls.__dict__:
            orig = cls.__dict__["output_schema"]

            def cached(self, _orig=orig) -> Schema:
                s = self.__dict__.get("_schema_cache")
                if s is None:
                    s = _orig(self)
                    self.__dict__["_schema_cache"] = s
                return s

            cls.output_schema = cached


class LocalRelation(LogicalPlan):
    def __init__(self, table: pa.Table):
        self.table = table
        self.children = []

    def output_schema(self) -> Schema:
        return Schema.from_arrow(self.table.schema)


class ParquetRelation(LogicalPlan):
    def __init__(self, paths, schema: Schema,
                 pushed: Optional[Expression] = None,
                 full_schema: Optional[Schema] = None):
        self.paths = paths
        self.schema = schema
        # Predicate pushed down from an enclosing Filter by the planner's
        # pushdown pass; used for footer min/max row-group pruning only
        # (conservative), so the Filter stays in the plan.
        self.pushed = pushed
        # the table's schema before the planner's column pruning narrowed
        # ``schema`` to the columns the plan reads (planner.py
        # prune_scan_columns)
        self.full_schema = full_schema or schema
        self.children = []

    def output_schema(self) -> Schema:
        return self.schema


class CsvRelation(LogicalPlan):
    def __init__(self, paths, schema: Schema, header: bool = True,
                 sep: str = ","):
        self.paths = paths
        self.schema = schema
        self.header = header
        self.sep = sep
        self.children = []

    def output_schema(self) -> Schema:
        return self.schema


class OrcRelation(LogicalPlan):
    def __init__(self, paths, schema: Schema, pushed=None):
        self.paths = paths
        self.schema = schema
        self.pushed = pushed  # predicate pushed down for stripe pruning
        self.children = []

    def output_schema(self) -> Schema:
        return self.schema


class Range(LogicalPlan):
    def __init__(self, start: int, end: int, step: int = 1):
        self.start, self.end, self.step = start, end, step
        self.children = []

    def output_schema(self) -> Schema:
        from spark_rapids_tpu.columnar.dtypes import INT64
        return Schema([Field("id", INT64, nullable=False)])


class Project(LogicalPlan):
    def __init__(self, exprs: Sequence[Expression], child: LogicalPlan):
        self.exprs = list(exprs)
        self.children = [child]

    def output_schema(self) -> Schema:
        bound = [bind_expression(e, self.children[0].output_schema())
                 for e in self.exprs]
        return Schema([Field(e.name, e.dtype, e.nullable) for e in bound])


class Filter(LogicalPlan):
    def __init__(self, pred: Expression, child: LogicalPlan):
        self.pred = pred
        self.children = [child]

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()


class Union(LogicalPlan):
    def __init__(self, children: Sequence[LogicalPlan]):
        self.children = list(children)

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()


class Limit(LogicalPlan):
    def __init__(self, n: int, child: LogicalPlan):
        self.n = n
        self.children = [child]

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()


class Sort(LogicalPlan):
    """orders: [(expr, ascending, nulls_first)]"""

    def __init__(self, orders: Sequence[Tuple[Expression, bool, bool]],
                 child: LogicalPlan):
        self.orders = list(orders)
        self.children = [child]

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()


class Aggregate(LogicalPlan):
    """groupings: grouping expressions; aggregates: Alias-wrapped
    AggregateExpression trees."""

    def __init__(self, groupings: Sequence[Expression],
                 aggregates: Sequence[Expression], child: LogicalPlan):
        self.groupings = list(groupings)
        self.aggregates = list(aggregates)
        self.children = [child]

    def output_schema(self) -> Schema:
        child_schema = self.children[0].output_schema()
        fields = []
        for e in self.groupings + self.aggregates:
            b = bind_expression(e, child_schema)
            fields.append(Field(b.name, b.dtype, b.nullable))
        return Schema(fields)


class Join(LogicalPlan):
    def __init__(self, left: LogicalPlan, right: LogicalPlan,
                 left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression],
                 join_type: str = "inner",
                 condition: Optional[Expression] = None):
        self.children = [left, right]
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.join_type = join_type
        self.condition = condition

    def output_schema(self) -> Schema:
        left, right = self.children
        lt = self.join_type
        if lt in ("semi", "anti"):
            return left.output_schema()
        lf = list(left.output_schema().fields)
        rf = list(right.output_schema().fields)
        if lt in ("left", "full"):
            pass
        if lt in ("right", "full"):
            lf = [Field(f.name, f.dtype, True) for f in lf]
        if lt in ("left", "full"):
            rf = [Field(f.name, f.dtype, True) for f in rf]
        return Schema(lf + rf)


class Expand(LogicalPlan):
    """Replicates every input row once per projection list — the grouping
    sets primitive behind rollup/cube (reference GpuExpandExec.scala:66;
    Spark's Expand operator)."""

    def __init__(self, projections: Sequence[Sequence[Expression]],
                 names: Sequence[str], child: LogicalPlan):
        self.projections = [list(p) for p in projections]
        self.names = list(names)
        self.children = [child]

    def output_schema(self) -> Schema:
        from spark_rapids_tpu.exec.expand import expand_schema
        child_schema = self.children[0].output_schema()
        bound_sets = [[bind_expression(e, child_schema) for e in p]
                      for p in self.projections]
        return expand_schema(bound_sets, self.names)


class Window(LogicalPlan):
    """Appends one computed column per window expression; all expressions
    in one node share a (partition, order) spec (the API groups them)."""

    def __init__(self, window_cols: Sequence[Tuple[str, Expression]],
                 child: LogicalPlan):
        self.window_cols = list(window_cols)
        self.children = [child]

    def output_schema(self) -> Schema:
        child_schema = self.children[0].output_schema()
        fields = list(child_schema.fields)
        for name, w in self.window_cols:
            b = bind_expression(w, child_schema)
            fields.append(Field(name, b.dtype, b.nullable))
        return Schema(fields)


class Generate(LogicalPlan):
    """explode/posexplode of a literal array appended to the child's
    output (reference GpuGenerateExec.scala:33-190).  ``names``: output
    column names ([pos_name,] col_name)."""

    def __init__(self, generator, names: Sequence[str],
                 child: LogicalPlan):
        self.generator = generator
        self.names = list(names)
        self.children = [child]

    def output_schema(self) -> Schema:
        from spark_rapids_tpu.exec.generate import generate_schema
        return generate_schema(self.generator,
                               self.children[0].output_schema(),
                               self.names)


class Repartition(LogicalPlan):
    """mode: hash | roundrobin | single | range.  Range partitioning
    carries sort ``orders`` [(expr, asc, nulls_first)] instead of keys
    (reference GpuRangePartitioning/GpuRangePartitioner)."""

    def __init__(self, num_partitions: int, keys: Sequence[Expression],
                 child: LogicalPlan, mode: str = "hash", orders=None):
        self.num_partitions = num_partitions
        self.keys = list(keys)
        self.orders = list(orders or [])
        self.mode = mode
        self.children = [child]

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()
