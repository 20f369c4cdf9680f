"""DataFrame API — the user surface.

Stands in for the Spark SQL DataFrame/Column API that drives the reference
plugin (queries in its tests/benchmarks are written against it; e.g.
TpchLikeSpark.scala:1150).  Builds logical plans that the planner
(plan/planner.py) tags and lowers to TPU/CPU physical operators.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence, Union as _Union

import pyarrow as pa

from spark_rapids_tpu.columnar.dtypes import (
    DataType, Schema, device_dtype,
)
from spark_rapids_tpu.exprs.base import (
    Alias, Expression, Literal, UnresolvedAttribute,
)
from spark_rapids_tpu.exprs import arithmetic as ar
from spark_rapids_tpu.exprs import predicates as pr
from spark_rapids_tpu.exprs import nullexprs as ne
from spark_rapids_tpu.exprs import conditional as cond
from spark_rapids_tpu.exprs.cast import Cast
from spark_rapids_tpu.plan import logical as lp
from spark_rapids_tpu.plan.planner import plan_query
from spark_rapids_tpu.exec.base import ExecContext


def _to_expr(v) -> Expression:
    if isinstance(v, Column):
        return v.expr
    if isinstance(v, Expression):
        return v
    return Literal(v)


class Column:
    """Expression wrapper with operator overloads (the pyspark Column
    analog)."""

    def __init__(self, expr: Expression):
        self.expr = expr

    # arithmetic
    def __add__(self, o):
        return Column(ar.Add(self.expr, _to_expr(o)))

    def __radd__(self, o):
        return Column(ar.Add(_to_expr(o), self.expr))

    def __sub__(self, o):
        return Column(ar.Subtract(self.expr, _to_expr(o)))

    def __rsub__(self, o):
        return Column(ar.Subtract(_to_expr(o), self.expr))

    def __mul__(self, o):
        return Column(ar.Multiply(self.expr, _to_expr(o)))

    def __rmul__(self, o):
        return Column(ar.Multiply(_to_expr(o), self.expr))

    def __truediv__(self, o):
        return Column(ar.Divide(self.expr, _to_expr(o)))

    def __rtruediv__(self, o):
        return Column(ar.Divide(_to_expr(o), self.expr))

    def __mod__(self, o):
        return Column(ar.Remainder(self.expr, _to_expr(o)))

    def __neg__(self):
        return Column(ar.UnaryMinus(self.expr))

    # comparisons
    def __eq__(self, o):  # type: ignore[override]
        return Column(pr.EqualTo(self.expr, _to_expr(o)))

    def __ne__(self, o):  # type: ignore[override]
        return Column(pr.NotEqual(self.expr, _to_expr(o)))

    def __lt__(self, o):
        return Column(pr.LessThan(self.expr, _to_expr(o)))

    def __le__(self, o):
        return Column(pr.LessThanOrEqual(self.expr, _to_expr(o)))

    def __gt__(self, o):
        return Column(pr.GreaterThan(self.expr, _to_expr(o)))

    def __ge__(self, o):
        return Column(pr.GreaterThanOrEqual(self.expr, _to_expr(o)))

    # boolean
    def __and__(self, o):
        return Column(pr.And(self.expr, _to_expr(o)))

    def __or__(self, o):
        return Column(pr.Or(self.expr, _to_expr(o)))

    def __invert__(self):
        return Column(pr.Not(self.expr))

    # named ops
    def alias(self, name: str) -> "Column":
        return Column(Alias(self.expr, name))

    def cast(self, dtype) -> "Column":
        if isinstance(dtype, str):
            from spark_rapids_tpu.columnar.dtypes import from_name
            dtype = from_name(dtype)
        return Column(Cast(self.expr, dtype))

    def is_null(self) -> "Column":
        return Column(pr.IsNull(self.expr))

    def is_not_null(self) -> "Column":
        return Column(pr.IsNotNull(self.expr))

    def isin(self, *values) -> "Column":
        vals = values[0] if len(values) == 1 and \
            isinstance(values[0], (list, tuple)) else values
        return Column(pr.In(self.expr, list(vals)))

    # string predicates (pyspark Column surface; patterns must be literals,
    # matching the reference's rule restriction GpuOverrides.scala:1294-1439)
    def startswith(self, other) -> "Column":
        from spark_rapids_tpu.exprs import strings as st
        return Column(st.StartsWith(self.expr, _to_expr(other)))

    def endswith(self, other) -> "Column":
        from spark_rapids_tpu.exprs import strings as st
        return Column(st.EndsWith(self.expr, _to_expr(other)))

    def contains(self, other) -> "Column":
        from spark_rapids_tpu.exprs import strings as st
        return Column(st.Contains(self.expr, _to_expr(other)))

    def like(self, pattern: str) -> "Column":
        from spark_rapids_tpu.exprs import strings as st
        return Column(st.Like(self.expr, _to_expr(pattern)))

    def substr(self, startPos, length=None) -> "Column":
        """pos/len may be ints (device path) or Columns (CPU fallback)."""
        from spark_rapids_tpu.exprs import strings as st
        ln = None if length is None else _to_expr(length)
        return Column(st.Substring(self.expr, _to_expr(startPos), ln))

    def eq_null_safe(self, o) -> "Column":
        return Column(pr.EqualNullSafe(self.expr, _to_expr(o)))

    # sort-direction markers consumed by order_by / Window.order_by
    def asc(self) -> "_SortCol":
        return _SortCol(self.expr, True)

    def desc(self) -> "_SortCol":
        return _SortCol(self.expr, False)

    def over(self, window: "WindowSpec") -> "Column":
        """Turn an aggregate/ranking function into a window expression
        (reference GpuWindowExpression GpuWindowExpression.scala:87)."""
        from spark_rapids_tpu.exprs.windows import WindowExpression
        func = self.expr
        if isinstance(func, Alias):
            func = func.children[0]
        return Column(WindowExpression(
            func, window._partition, window._orders, window._frame))

    def __repr__(self):
        return f"Column<{self.expr.name}>"


class _SortCol:
    """(expression, direction) marker produced by Column.asc()/desc()."""

    __slots__ = ("expr", "ascending")

    def __init__(self, expr: Expression, ascending: bool):
        self.expr = expr
        self.ascending = ascending


class WindowSpec:
    """Immutable window specification builder (the pyspark WindowSpec
    analog; reference GpuWindowSpecDefinition)."""

    def __init__(self, partition=None, orders=None, frame=None):
        self._partition = list(partition or [])
        self._orders = list(orders or [])
        self._frame = frame

    @staticmethod
    def _to_order(c):
        if isinstance(c, _SortCol):
            # Spark default null ordering: nulls first asc, nulls last desc
            return (c.expr, c.ascending, c.ascending)
        if isinstance(c, str):
            return (UnresolvedAttribute(c), True, True)
        return (_to_expr(c), True, True)

    def partition_by(self, *cols_) -> "WindowSpec":
        parts = [UnresolvedAttribute(c) if isinstance(c, str) else _to_expr(c)
                 for c in cols_]
        return WindowSpec(self._partition + parts, self._orders, self._frame)

    partitionBy = partition_by

    def order_by(self, *cols_) -> "WindowSpec":
        return WindowSpec(self._partition,
                          self._orders + [self._to_order(c) for c in cols_],
                          self._frame)

    orderBy = order_by

    def rows_between(self, start: int, end: int) -> "WindowSpec":
        from spark_rapids_tpu.exprs.windows import WindowFrame
        return WindowSpec(self._partition, self._orders,
                          WindowFrame("rows", start, end))

    rowsBetween = rows_between

    def range_between(self, start: int, end: int) -> "WindowSpec":
        from spark_rapids_tpu.exprs.windows import WindowFrame
        return WindowSpec(self._partition, self._orders,
                          WindowFrame("range", start, end))

    rangeBetween = range_between


class Window:
    """Static entry points mirroring pyspark.sql.Window."""

    unboundedPreceding = -(1 << 63)
    unboundedFollowing = (1 << 63) - 1
    currentRow = 0
    unbounded_preceding = unboundedPreceding
    unbounded_following = unboundedFollowing
    current_row = currentRow

    @staticmethod
    def partition_by(*cols_) -> WindowSpec:
        return WindowSpec().partition_by(*cols_)

    partitionBy = partition_by

    @staticmethod
    def order_by(*cols_) -> WindowSpec:
        return WindowSpec().order_by(*cols_)

    orderBy = order_by

    @staticmethod
    def rows_between(start: int, end: int) -> WindowSpec:
        return WindowSpec().rows_between(start, end)

    rowsBetween = rows_between

    @staticmethod
    def range_between(start: int, end: int) -> WindowSpec:
        return WindowSpec().range_between(start, end)

    rangeBetween = range_between


def _unique_name(base: str, names: set) -> str:
    """An internal column name not colliding with ``names`` (adds it)."""
    name, i = base, 0
    while name in names:
        i += 1
        name = f"{base.rstrip('_')}_{i}__" if base.endswith("__") \
            else f"{base}_{i}"
    names.add(name)
    return name


def _extract_generator(exprs: List[Expression], plan: lp.LogicalPlan):
    """Split a generator (explode/posexplode) out of a select list into an
    lp.Generate node, replacing it with references to the generated
    column(s) (the Spark ExtractGenerator analysis rule; the plugin sees
    the extracted GenerateExec, GpuGenerateExec.scala:33)."""
    from spark_rapids_tpu.exprs.generators import (
        find_generators, find_stray_array_literals,
    )
    for e in exprs:
        if find_stray_array_literals(e):
            raise ValueError(
                "F.array(...) literals are only usable inside "
                "explode()/posexplode()")
    gens = [g for e in exprs for g in find_generators(e)]
    if not gens:
        return exprs, plan
    if len(gens) > 1:
        raise ValueError("only one generator (explode/posexplode) is "
                         "allowed per select")
    gen = gens[0]
    col_name = "col"
    for e in exprs:
        base = e.children[0] if isinstance(e, Alias) else e
        if base is gen and isinstance(e, Alias):
            col_name = e.name
        elif base is not gen and find_generators(e):
            raise ValueError(
                "explode()/posexplode() must be a top-level select "
                "column (optionally aliased), not nested in an "
                "expression")
    # the Generate node appends columns under internal names unique
    # against the child schema, and the top Project aliases them back —
    # so a generated column may shadow/replace an existing column of the
    # same name (the with_column('v', explode(...)) case) without the
    # by-name reference binding to the old column
    existing = {f.name for f in plan.output_schema()}
    pos_internal = _unique_name("__gen_pos__", existing) \
        if gen.with_pos else None
    col_internal = _unique_name(f"__gen_{col_name}__", existing)
    new_exprs: List[Expression] = []
    for e in exprs:
        base = e.children[0] if isinstance(e, Alias) else e
        if base is gen:
            if gen.with_pos:
                new_exprs.append(
                    Alias(UnresolvedAttribute(pos_internal), "pos"))
            new_exprs.append(
                Alias(UnresolvedAttribute(col_internal), col_name))
        else:
            new_exprs.append(e)
    names = ([pos_internal, col_internal] if gen.with_pos
             else [col_internal])
    return new_exprs, lp.Generate(gen, names, plan)


def _extract_window_exprs(exprs: List[Expression], plan: lp.LogicalPlan):
    """Split WindowExpressions out of projection expressions into stacked
    lp.Window nodes (grouped by partition/order spec), replacing each with
    a reference to the generated column (reference: Spark's
    ExtractWindowExpressions analysis rule; the plugin sees the already
    extracted WindowExec, GpuWindowExec.scala:92)."""
    from spark_rapids_tpu.exprs.windows import (
        WindowExpression, WindowFunction,
    )
    # pass 1: find every distinct window expression and pick its column
    # name — the pyspark-style display name when it appears as a projected
    # column anywhere (an Alias renames it regardless), else a synthetic
    # reference name
    found: dict = {}          # wexpr key -> (wexpr, has_top_occurrence)

    def scan(e: Expression, top: bool) -> None:
        if isinstance(e, Alias):
            scan(e.children[0], top)
            return
        if isinstance(e, WindowExpression):
            wk = e.key()
            prev = found.get(wk)
            found[wk] = (e, top or (prev is not None and prev[1]))
            return
        if isinstance(e, WindowFunction):
            # not wrapped by a WindowExpression (scan does not descend
            # into those) -> the user forgot .over()
            raise ValueError(
                f"{e.name} is a window function and requires "
                ".over(Window.partition_by(...).order_by(...))")
        for c in e.children:
            scan(c, False)

    for e in exprs:
        scan(e, top=True)
    if not found:
        return exprs, plan

    assigned: dict = {}       # wexpr key -> attr name
    groups: dict = {}         # spec key -> [(name, wexpr)]
    for i, (wk, (w, has_top)) in enumerate(found.items()):
        name = w.name if has_top else f"__w{i}"
        assigned[wk] = name
        groups.setdefault(w.spec_key(), []).append((name, w))

    # pass 2: replace each window expression with a reference
    def walk(e: Expression) -> Expression:
        if isinstance(e, WindowExpression):
            return UnresolvedAttribute(assigned[e.key()])
        if not e.children:
            return e
        new = [walk(c) for c in e.children]
        if all(a is b for a, b in zip(new, e.children)):
            return e
        return e.with_children(new)

    new_exprs = [walk(e) for e in exprs]
    for group in groups.values():
        plan = lp.Window(group, plan)
    return new_exprs, plan


def col(name: str) -> Column:
    return Column(UnresolvedAttribute(name))


def lit(value, dtype: Optional[DataType] = None) -> Column:
    return Column(Literal(value, dtype))


def when(cond_col: Column, value) -> "CaseWhenBuilder":
    return CaseWhenBuilder([(cond_col.expr, _to_expr(value))])


class CaseWhenBuilder(Column):
    def __init__(self, branches):
        self._branches = branches
        super().__init__(cond.CaseWhen(branches))

    def when(self, cond_col: Column, value) -> "CaseWhenBuilder":
        return CaseWhenBuilder(
            self._branches + [(cond_col.expr, _to_expr(value))])

    def otherwise(self, value) -> Column:
        return Column(cond.CaseWhen(self._branches, _to_expr(value)))


def coalesce(*cols) -> Column:
    return Column(ne.Coalesce(*[_to_expr(c) for c in cols]))


class DataFrame:
    """Lazy logical-plan builder; actions plan + execute."""

    def __init__(self, session, plan: lp.LogicalPlan):
        self.session = session
        self.plan = plan

    # -- transformations ----------------------------------------------------

    def select(self, *cols_) -> "DataFrame":
        exprs = []
        for c in cols_:
            if isinstance(c, str):
                exprs.append(UnresolvedAttribute(c))
            else:
                exprs.append(_to_expr(c))
        exprs, plan = _extract_generator(exprs, self.plan)
        exprs, plan = _extract_window_exprs(exprs, plan)
        return DataFrame(self.session, lp.Project(exprs, plan))

    def filter(self, cond_col) -> "DataFrame":
        e = cond_col.expr if isinstance(cond_col, Column) else cond_col
        from spark_rapids_tpu.exprs.generators import find_generators
        from spark_rapids_tpu.exprs.nondeterministic import (
            contains_nondeterministic,
        )
        if find_generators(e):
            raise ValueError(
                "explode()/posexplode() is not allowed in filter() — "
                "generators are only valid in select()/with_column()")
        (e,), plan = _extract_window_exprs([e], self.plan)
        if contains_nondeterministic(e):
            # materialize the predicate through a Project so rand() etc.
            # see the per-batch partition id (only Project threads it);
            # the sampling idiom filter(rand() < p) stays independent
            # across batches on both engines
            tmp = _unique_name(
                "__pred__", {f.name for f in plan.output_schema()})
            plan = lp.Project(
                [UnresolvedAttribute(f.name)
                 for f in plan.output_schema()] + [Alias(e, tmp)], plan)
            e = UnresolvedAttribute(tmp)
        filtered = lp.Filter(e, plan)
        if plan is not self.plan:
            # helper columns were materialized for the predicate; project
            # back to the original schema
            filtered = lp.Project(
                [UnresolvedAttribute(f.name)
                 for f in self.plan.output_schema()], filtered)
        return DataFrame(self.session, filtered)

    where = filter

    def with_column(self, name: str, c: Column) -> "DataFrame":
        schema = self.plan.output_schema()
        exprs: List[Expression] = []
        replaced = False
        for f in schema:
            if f.name == name:
                exprs.append(Alias(_to_expr(c), name))
                replaced = True
            else:
                exprs.append(UnresolvedAttribute(f.name))
        if not replaced:
            exprs.append(Alias(_to_expr(c), name))
        exprs, plan = _extract_generator(exprs, self.plan)
        exprs, plan = _extract_window_exprs(exprs, plan)
        return DataFrame(self.session, lp.Project(exprs, plan))

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(self.session, lp.Union([self.plan, other.plan]))

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(self.session, lp.Limit(n, self.plan))

    def order_by(self, *cols_, ascending=True) -> "DataFrame":
        orders = []
        ascs = ascending if isinstance(ascending, (list, tuple)) \
            else [ascending] * len(cols_)
        for c, asc in zip(cols_, ascs):
            if isinstance(c, _SortCol):
                # col("x").desc()/.asc() markers override the kwarg
                asc = c.ascending
                e = c.expr
            elif isinstance(c, str):
                e = UnresolvedAttribute(c)
            else:
                e = _to_expr(c)
            # Spark default null ordering: nulls first when asc, last if desc
            orders.append((e, bool(asc), bool(asc)))
        keys, plan = _extract_window_exprs([e for e, _, _ in orders],
                                           self.plan)
        orders = [(k, asc, nf) for k, (_, asc, nf) in zip(keys, orders)]
        sorted_plan = lp.Sort(orders, plan)
        if plan is not self.plan:
            # window sort keys were materialized; drop them after sorting
            sorted_plan = lp.Project(
                [UnresolvedAttribute(f.name)
                 for f in self.plan.output_schema()], sorted_plan)
        return DataFrame(self.session, sorted_plan)

    sort = order_by

    def group_by(self, *cols_) -> "GroupedData":
        exprs = [UnresolvedAttribute(c) if isinstance(c, str) else _to_expr(c)
                 for c in cols_]
        return GroupedData(self, exprs)

    def rollup(self, *cols_) -> "GroupedData":
        """Hierarchical grouping sets: rollup(a, b) aggregates by (a, b),
        (a), and () (reference GpuExpandExec grouping-set lowering)."""
        return GroupedData(self, self._key_names(cols_), mode="rollup")

    def cube(self, *cols_) -> "GroupedData":
        """All-subset grouping sets over the key columns."""
        return GroupedData(self, self._key_names(cols_), mode="cube")

    def _key_names(self, cols_) -> List[Expression]:
        exprs = []
        for c in cols_:
            e = UnresolvedAttribute(c) if isinstance(c, str) else _to_expr(c)
            if not isinstance(e, UnresolvedAttribute):
                raise ValueError(
                    "rollup/cube keys must be plain column references")
            exprs.append(e)
        return exprs

    def agg(self, *agg_cols) -> "DataFrame":
        return GroupedData(self, []).agg(*agg_cols)

    def join(self, other: "DataFrame", on, how: str = "inner") -> "DataFrame":
        if isinstance(on, str):
            on = [on]
        left_keys = [UnresolvedAttribute(k) if isinstance(k, str)
                     else _to_expr(k) for k in on]
        right_keys = [UnresolvedAttribute(k) if isinstance(k, str)
                      else _to_expr(k) for k in on]
        how = {"left_outer": "left", "right_outer": "right",
               "outer": "full", "leftsemi": "semi", "left_semi": "semi",
               "leftanti": "anti", "left_anti": "anti"}.get(how, how)
        plan = lp.Join(self.plan, other.plan, left_keys, right_keys, how)
        if isinstance(on[0], str) and how in ("inner", "left", "right",
                                              "full"):
            # drop the duplicate right key columns like pyspark's
            # join-on-names
            lschema = self.plan.output_schema()
            rschema = other.plan.output_schema()
            # disambiguate: select by position via bound refs.  Spark's
            # USING-join key column comes from the left side for inner/left,
            # the right side for right joins, and coalesce(left, right) for
            # full outer (both sides can be null-extended).
            from spark_rapids_tpu.exprs.base import BoundReference
            from spark_rapids_tpu.exprs.nullexprs import Coalesce
            nleft = len(lschema.fields)
            rpos = {f.name: i for i, f in enumerate(rschema.fields)}
            fields = lschema.fields + rschema.fields
            exprs = []
            for i, f in enumerate(fields):
                if i >= nleft and f.name in on:
                    continue
                if i < nleft and f.name in on:
                    rf = rschema.fields[rpos[f.name]]
                    rref = BoundReference(nleft + rpos[f.name], rf.dtype,
                                          True, rf.name)
                    lref = BoundReference(i, f.dtype, True, f.name)
                    if how == "right":
                        exprs.append(Alias(rref, f.name))
                        continue
                    if how == "full":
                        exprs.append(Alias(Coalesce(lref, rref), f.name))
                        continue
                exprs.append(Alias(BoundReference(
                    i, f.dtype, True, f.name), f.name))
            plan = lp.Project(exprs, plan)
        return DataFrame(self.session, plan)

    def repartition(self, num_partitions: int, *cols_) -> "DataFrame":
        keys = [UnresolvedAttribute(c) if isinstance(c, str) else _to_expr(c)
                for c in cols_]
        return DataFrame(self.session, lp.Repartition(
            num_partitions, keys, self.plan))

    def repartition_by_range(self, num_partitions: int,
                             *cols_) -> "DataFrame":
        """Range-partition by the given sort columns (``col('x').desc()``
        markers honored; Spark default null ordering).  Reference
        GpuRangePartitioning.scala / GpuRangePartitioner.scala."""
        orders = []
        for c in cols_:
            if isinstance(c, _SortCol):
                orders.append((c.expr, c.ascending, c.ascending))
            elif isinstance(c, str):
                orders.append((UnresolvedAttribute(c), True, True))
            else:
                orders.append((_to_expr(c), True, True))
        if not orders:
            raise ValueError("repartition_by_range needs at least one "
                             "sort column")
        return DataFrame(self.session, lp.Repartition(
            num_partitions, [], self.plan, mode="range", orders=orders))

    repartitionByRange = repartition_by_range

    def create_or_replace_temp_view(self, name: str) -> None:
        """Register this DataFrame under ``name`` for session.sql()
        (the Spark createOrReplaceTempView analog)."""
        self.session.register_view(name, self)

    createOrReplaceTempView = create_or_replace_temp_view

    def distinct(self) -> "DataFrame":
        schema = self.plan.output_schema()
        groupings = [UnresolvedAttribute(f.name) for f in schema]
        return DataFrame(self.session,
                         lp.Aggregate(groupings, [], self.plan))

    # -- actions ------------------------------------------------------------

    def _execute(self) -> pa.Table:
        from spark_rapids_tpu import lifecycle
        from spark_rapids_tpu.utils import tracing
        # the query's fault domain (lifecycle.py): deadline + cancel
        # token + resource registry; teardown runs on scope exit
        # whether the drain below succeeds, times out, or fails
        with lifecycle.query_scope(self.session.conf) as qc:
            # query_trace OUTSIDE the ExecContext construction: both set
            # the process-global span switch from the conf, but only
            # query_trace snapshots and restores the prior state — the
            # switch must be query-scoped on this path
            # (tests/test_tracing.py).  Planning runs inside it, so the
            # planner's spans (plan.fusion, plan.aqe) are a traced
            # query's too
            with tracing.query_trace(self.session.conf):
                with tracing.phase(tracing.SPAN_QUERY_PLAN, "plan_us"):
                    result = plan_query(self.plan, self.session.conf)
                ctx = ExecContext(self.session.conf)
                batches = []
                with tracing.phase(tracing.SPAN_QUERY_EXECUTE,
                                   "execute_us"):
                    for rb in result.physical.execute_host(ctx):
                        # root-drain checkpoint: covers plans (or
                        # subtrees) on the CPU fallback engine, whose
                        # operators have no device pull boundary of
                        # their own
                        lifecycle.check_cancel()
                        batches.append(rb)
        if qc.sem_wait_ms:
            # per-query admission-wait telemetry, visible through
            # session.last_query_metrics() beside the operator metrics
            result.physical.metrics["semWaitMs"].add(qc.sem_wait_ms)
        # pair the retained plan with ITS query's identity — the
        # profile header (docs/observability.md) reads these, never a
        # process-global "last finished" note a later write or a
        # concurrent session could overwrite
        result.query_id = qc.query_id
        result.wall_ms = qc.wall_ms
        result.programs = qc.programs
        self.session._last_plan_result = result
        if self.session.conf.placement_mode != "tpu":
            # calibration feed (plan/cost.py, docs/placement.md): the
            # executed tree's per-operator rows/wall update the
            # throughput EWMAs, and the projected-vs-actual accounting
            # gets this query's wall.  Never on the default mode —
            # the metric-snapshot walk can sync pending device counts
            # (a counted device_pull), which mode=tpu must not pay.
            from spark_rapids_tpu.plan import cost as _cost
            from spark_rapids_tpu.plan import placement as _placement
            _cost.observe_plan(result.physical)
            _placement.note_query(result.placement, qc.wall_ms,
                                  query_id=qc.query_id)
        arrow_schema = result.physical.output_schema.to_arrow()
        if not batches:
            return pa.Table.from_batches([], schema=arrow_schema)
        return pa.Table.from_batches(batches).cast(arrow_schema)

    # -- ML handoff (reference InternalColumnarRddConverter.scala:470-579:
    # export the internal columnar stream without a row conversion) --------

    def to_device_batches(self) -> List["object"]:
        """Execute and hand back the INTERNAL device batches without any
        device->host conversion — the zero-copy path into JAX ML code
        (train directly on the query output, still in HBM)."""
        from spark_rapids_tpu.exec.basic import DeviceToHostExec
        from spark_rapids_tpu.exec.base import TpuExec
        from spark_rapids_tpu.utils import tracing
        with tracing.phase(tracing.SPAN_QUERY_PLAN, "plan_us"):
            result = plan_query(self.plan, self.session.conf)
        root = result.physical
        if isinstance(root, DeviceToHostExec):
            root = root.children[0]
        if not isinstance(root, TpuExec):
            raise RuntimeError(
                "plan did not stay on the device engine; device handoff "
                "needs a fully TPU plan (see explain())")
        from spark_rapids_tpu import lifecycle
        with lifecycle.query_scope(self.session.conf) as qc:
            # query_trace scopes the span switch here exactly as in
            # _execute: the handoff path must not leak it either
            with tracing.query_trace(self.session.conf):
                ctx = ExecContext(self.session.conf)
                with tracing.phase(tracing.SPAN_QUERY_EXECUTE,
                                   "execute_us"):
                    batches = list(root.execute_columnar(ctx))
        # retain + stamp only after the drain succeeded (the _execute
        # invariant): a failed handoff must not replace a prior query's
        # valid profile with an unexecuted, unstamped tree
        result.query_id = qc.query_id
        result.wall_ms = qc.wall_ms
        result.programs = qc.programs
        self.session._last_plan_result = result
        return batches

    def to_jax(self):
        """-> (columns, masks, num_rows): dict of device value arrays and
        validity masks per column, sliced to the row count.  Strings stay
        in the (lengths, chars) device representation."""
        import jax.numpy as jnp
        from spark_rapids_tpu.exec.coalesce import concat_batches
        batches = self.to_device_batches()
        schema = self.plan.output_schema()
        if not batches:
            cols = {}
            for f in schema:
                if f.dtype.name == "string":
                    cols[f.name] = (jnp.zeros(0, jnp.int32),
                                    jnp.zeros((0, 1), jnp.uint8))
                else:
                    cols[f.name] = jnp.zeros(0, device_dtype(f.dtype))
            return cols, {f.name: jnp.zeros(0, bool) for f in schema}, 0
        batch = concat_batches(batches)
        n = batch.num_rows
        cols, masks = {}, {}
        for f, c in zip(schema, batch.columns):
            cols[f.name] = c.data[:n] if c.chars is None else \
                (c.data[:n], c.chars[:n])
            masks[f.name] = c.validity[:n]
        return cols, masks, n

    def to_numpy(self):
        """-> dict of numpy arrays (nulls as numpy masked arrays)."""
        import numpy as np
        t = self.to_arrow()
        out = {}
        for name in t.column_names:
            col = t.column(name)
            vals = col.to_numpy(zero_copy_only=False)
            if col.null_count:
                out[name] = np.ma.masked_array(
                    vals, mask=~np.asarray(col.is_valid()))
            else:
                out[name] = vals
        return out

    def to_torch(self):
        """-> dict of CPU torch tensors for numeric columns (the reference
        exports to ML via the columnar RDD; torch is the common sink)."""
        import numpy as np
        import pyarrow.compute as pc
        import torch
        t = self.to_arrow()
        out = {}
        for name, f in zip(t.column_names, self.plan.output_schema()):
            col = t.column(name)
            if f.dtype.name in ("date", "timestamp"):
                # torch rejects datetime64; export the physical epoch ints
                # (days / UTC micros), matching the device representation
                if f.dtype.name == "date":
                    col = col.cast(pa.int32()).cast(pa.int64())
                else:
                    col = col.cast(pa.int64())
            elif not (f.dtype.is_numeric or f.dtype.name == "boolean"):
                continue
            if col.null_count:
                # torch has no null mask: export zero-filled values plus
                # an explicit <name>__mask tensor (True = valid) so nulls
                # stay distinguishable and dtypes stay schema-faithful
                out[name + "__mask"] = torch.from_numpy(
                    np.asarray(col.combine_chunks().is_valid()).copy())
                fill = False if col.type == pa.bool_() else 0
                col = pc.fill_null(col, fill)
            vals = col.to_numpy(zero_copy_only=False)
            out[name] = torch.from_numpy(vals.copy())
        return out

    def to_arrow(self) -> pa.Table:
        return self._execute()

    def collect(self) -> List[dict]:
        return self.to_arrow().to_pylist()

    def count(self) -> int:
        return self.to_arrow().num_rows

    def head(self, n: Optional[int] = None):
        """PySpark contract: head() -> single row dict (or None);
        head(n) -> list of n row dicts (head(1) included)."""
        if n is None:
            rows = self.limit(1).collect()
            return rows[0] if rows else None
        return self.limit(n).collect()

    def take(self, n: int) -> List[dict]:
        return self.limit(n).collect()

    def first(self):
        return self.head(1)

    def explain(self, analyze: bool = False) -> str:
        """The plan as text.  ``analyze=False`` (default) plans without
        executing — byte-identical to the pre-obs output.
        ``analyze=True`` EXECUTES the query and renders the executed
        plan tree (AQE's evolved children and ICI-lowered fragments as
        they ran) annotated per operator with rows / batches / wall and
        self time and every non-zero metric — the Spark UI SQL-tab view
        (docs/observability.md, "Query profiles")."""
        import sys
        if analyze:
            self._execute()
            txt = self.session.last_query_profile().render()
            sys.stdout.write(txt + "\n")
            return txt
        result = plan_query(
            self.plan,
            self.session.conf.set("spark.rapids.sql.explain", "NONE"))
        txt = result.explain + "\n\nPhysical plan:\n" + \
            result.physical.tree_string()
        sys.stdout.write(txt + "\n")
        return txt

    @property
    def schema(self) -> Schema:
        return self.plan.output_schema()

    @property
    def columns(self) -> List[str]:
        return self.plan.output_schema().names

    @property
    def write(self) -> "DataFrameWriter":
        return DataFrameWriter(self)


GROUPING_ID_COL = "__grouping_id__"


class GroupedData:
    def __init__(self, df: DataFrame, groupings: List[Expression],
                 mode: Optional[str] = None):
        self.df = df
        self.groupings = groupings
        self.mode = mode  # None | "rollup" | "cube"

    def agg(self, *agg_cols) -> DataFrame:
        aggs = [_to_expr(c) for c in agg_cols]
        if self.mode is None:
            return DataFrame(self.df.session,
                             lp.Aggregate(self.groupings, aggs,
                                          self.df.plan))
        return self._grouping_sets_agg(aggs)

    def _grouping_sets_agg(self, aggs: List[Expression]) -> DataFrame:
        """rollup/cube -> Expand (rows replicated per set with masked keys
        + grouping id) -> Aggregate by keys+gid -> Project (reference
        GpuExpandExec.scala:66; Spark's ResolveGroupingAnalytics)."""
        child_schema = self.df.plan.output_schema()
        from spark_rapids_tpu.exprs.base import bind_expression
        key_names = [k.col_name for k in self.groupings]
        key_dtypes = [bind_expression(k, child_schema).dtype
                      for k in self.groupings]
        nk = len(key_names)
        if self.mode == "rollup":
            # full set first, then drop keys from the right:
            # rollup(a, b) -> masked {} (gid 0), {b} (gid 1), {a,b} (gid 3)
            masked_sets = [set(range(nk - i, nk)) for i in range(nk + 1)]
        else:  # cube: every subset of masked keys
            masked_sets = [set(i for i in range(nk) if gid & (1 << (
                nk - 1 - i))) for gid in range(1 << nk)]
        # Every original child column passes through unchanged — aggregate
        # arguments must see real values, not masked keys (Spark's
        # ResolveGroupingAnalytics masks only the grouping COPIES) — plus
        # one masked copy per key and the grouping id.
        gk_names = [f"__gk_{kn}__" for kn in key_names]
        names = [f.name for f in child_schema] + gk_names + \
            [GROUPING_ID_COL]
        projections = []
        for masked in masked_sets:
            gid = sum(1 << (nk - 1 - i) for i in masked)
            proj: List[Expression] = [
                UnresolvedAttribute(f.name) for f in child_schema]
            for i, (kn, gkn, kd) in enumerate(zip(key_names, gk_names,
                                                  key_dtypes)):
                src = Literal(None, kd) if i in masked \
                    else UnresolvedAttribute(kn)
                proj.append(Alias(src, gkn))
            proj.append(Alias(Literal(gid), GROUPING_ID_COL))
            projections.append(proj)
        expand = lp.Expand(projections, names, self.df.plan)
        groupings = [UnresolvedAttribute(n) for n in gk_names] + \
            [UnresolvedAttribute(GROUPING_ID_COL)]
        # split out grouping_id() passthroughs from real aggregates
        out_cols: List[Tuple[str, Optional[str]]] = []
        real_aggs: List[Expression] = []
        for a in aggs:
            target = a.children[0] if isinstance(a, Alias) else a
            if isinstance(target, UnresolvedAttribute) and \
                    target.col_name == GROUPING_ID_COL:
                out_cols.append((a.name if isinstance(a, Alias)
                                 else "grouping_id()", GROUPING_ID_COL))
            else:
                real_aggs.append(a)
                out_cols.append((None, None))
        agg_plan = lp.Aggregate(groupings, real_aggs, expand)
        agg_schema = agg_plan.output_schema()
        agg_out_names = [f.name for f in agg_schema][nk + 1:]
        final: List[Expression] = [
            Alias(UnresolvedAttribute(gkn), kn)
            for gkn, kn in zip(gk_names, key_names)]
        it = iter(agg_out_names)
        for disp, src in out_cols:
            if src is not None:
                final.append(Alias(UnresolvedAttribute(src), disp))
            else:
                final.append(UnresolvedAttribute(next(it)))
        return DataFrame(self.df.session, lp.Project(final, agg_plan))

    def count(self) -> DataFrame:
        from spark_rapids_tpu.exprs.aggregates import Count
        from spark_rapids_tpu.exprs.base import Literal as L
        return self.agg(Column(Alias(Count(L(1)), "count")))


class DataFrameReader:
    """reference: the DataSource scan rules (GpuOverrides.scala:1455-1510)."""

    def __init__(self, session):
        self.session = session
        self._schema: Optional[Schema] = None

    def schema(self, schema: Schema) -> "DataFrameReader":
        self._schema = schema
        return self

    def parquet(self, *paths) -> DataFrame:
        from spark_rapids_tpu.io.parquet import read_schema
        schema = self._schema or read_schema(list(paths))
        return DataFrame(self.session,
                         lp.ParquetRelation(list(paths), schema))

    def csv(self, *paths, header: bool = True, sep: str = ",") -> DataFrame:
        from spark_rapids_tpu.io.csv import read_csv_relation
        return DataFrame(self.session,
                         read_csv_relation(list(paths), self._schema,
                                           header=header, sep=sep))

    def orc(self, *paths) -> DataFrame:
        from spark_rapids_tpu.io.orc import read_orc_relation
        return DataFrame(self.session,
                         read_orc_relation(list(paths), self._schema))


class DataFrameWriter:
    def __init__(self, df: DataFrame):
        self.df = df
        self._mode = "error"
        self._partition_cols: List[str] = []

    def mode(self, m: str) -> "DataFrameWriter":
        self._mode = m
        return self

    def partition_by(self, *cols_) -> "DataFrameWriter":
        """Hive-style dynamic partitioning: one col=value/ directory per
        distinct partition value (reference GpuDynamicPartitionDataWriter
        in GpuFileFormatDataWriter.scala)."""
        self._partition_cols = list(cols_)
        return self

    partitionBy = partition_by

    def parquet(self, path: str) -> None:
        from spark_rapids_tpu.io.writers import write_parquet
        write_parquet(self.df, path, self._mode,
                      partition_cols=self._partition_cols)

    def orc(self, path: str) -> None:
        from spark_rapids_tpu.io.writers import write_orc
        write_orc(self.df, path, self._mode,
                  partition_cols=self._partition_cols)

    def csv(self, path: str) -> None:
        from spark_rapids_tpu.io.writers import write_csv
        write_csv(self.df, path, self._mode)


def create_dataframe(session, data, schema=None) -> DataFrame:
    """Rows/arrow/pandas -> DataFrame over a LocalRelation."""
    if isinstance(data, pa.Table):
        table = data
    elif isinstance(data, pa.RecordBatch):
        table = pa.Table.from_batches([data])
    elif isinstance(data, dict):
        table = pa.table(data)
    elif isinstance(data, list) and data and isinstance(data[0], dict):
        table = pa.Table.from_pylist(data)
    elif isinstance(data, list) and schema is not None:
        names = schema.names if isinstance(schema, Schema) else list(schema)
        cols = list(zip(*data)) if data else [[] for _ in names]
        table = pa.table({n: list(c) for n, c in zip(names, cols)})
    else:
        raise TypeError(f"cannot build DataFrame from {type(data)}")
    if isinstance(schema, Schema):
        table = table.cast(schema.to_arrow())
    return DataFrame(session, lp.LocalRelation(table))


def range_df(session, start: int, end: Optional[int] = None,
             step: int = 1) -> DataFrame:
    if end is None:
        start, end = 0, start
    return DataFrame(session, lp.Range(start, end, step))
