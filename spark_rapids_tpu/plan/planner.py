"""The plan rewriter: meta wrapping, tagging, conversion, transitions.

Reference call stack (SURVEY §3.2): GpuOverrides.apply (GpuOverrides.scala:
1708-1765) wraps the plan in RapidsMeta nodes, tags bottom-up
(RapidsMeta.scala:173-196), prints explain, converts per node
(convertIfNeeded :522-537); then GpuTransitionOverrides inserts
host<->device transitions and coalesce nodes (:36-146).

Here the meta tree tags each logical node with ``will_not_work_on_tpu``
reasons (type gate, per-operator conf keys
``spark.rapids.sql.{exec,expression}.<Name>``, unsupported expressions) and
converts to TpuExec or CpuExec; an engine-boundary pass then inserts
HostToDeviceExec / DeviceToHostExec.
"""

from __future__ import annotations

import copy

from typing import List, Optional, Sequence

from spark_rapids_tpu.columnar.dtypes import Schema, is_supported_type
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.exprs.base import (
    Expression, Alias, BoundReference, Literal, bind_expression,
)
from spark_rapids_tpu.plan import logical as lp
from spark_rapids_tpu.exec.base import CpuExec, PhysicalPlan, TpuExec
from spark_rapids_tpu.exec import basic as tb
from spark_rapids_tpu.exec.basic import HostToDeviceExec, DeviceToHostExec
from spark_rapids_tpu.cpu import engine as cb


# ---------------------------------------------------------------------------
# Expression registry (reference: ~100 expression rules
# GpuOverrides.scala:453-1445, each with an auto-generated conf key)
# ---------------------------------------------------------------------------

_EXPR_RULES: dict = {}


class ExprRule:
    def __init__(self, name: str, incompat: Optional[str] = None,
                 disabled_by_default: bool = False):
        self.name = name
        self.incompat = incompat
        self.disabled_by_default = disabled_by_default

    @property
    def conf_key(self) -> str:
        return f"spark.rapids.sql.expression.{self.name}"


def register_expr(cls_name: str, incompat: Optional[str] = None,
                  disabled_by_default: bool = False):
    _EXPR_RULES[cls_name] = ExprRule(cls_name, incompat, disabled_by_default)


for _n in [
    # ParamLiteral: a prepared-statement binding behaves exactly like
    # the Literal it subclasses on both engines (docs/serving.md)
    "BoundReference", "Literal", "ParamLiteral", "Alias",
    "Add", "Subtract", "Multiply", "Divide", "IntegralDivide", "Remainder",
    "Pmod", "UnaryMinus", "Abs",
    "EqualTo", "NotEqual", "LessThan", "LessThanOrEqual", "GreaterThan",
    "GreaterThanOrEqual", "EqualNullSafe", "And", "Or", "Not", "IsNull",
    "IsNotNull", "IsNaN", "In",
    "Coalesce", "NaNvl", "AtLeastNNonNulls", "NullOf", "If", "CaseWhen", "Cast",
    "Sqrt", "Cbrt", "Exp", "Expm1", "Log", "Log2", "Log10", "Log1p",
    "Sin", "Cos", "Tan", "Asin", "Acos", "Atan", "Sinh", "Cosh", "Tanh",
    "Rint", "ToDegrees", "ToRadians", "Signum", "Floor", "Ceil", "Pow",
    "Atan2",
    "BitwiseAnd", "BitwiseOr", "BitwiseXor", "BitwiseNot", "ShiftLeft",
    "ShiftRight", "ShiftRightUnsigned",
    "Year", "Month", "DayOfMonth", "DayOfWeek", "WeekDay", "DayOfYear",
    "Quarter", "LastDay", "Hour", "Minute", "Second", "DateAdd", "DateSub",
    "DateDiff", "UnixTimestampFromDateTime", "TimeSub", "TimeAdd",
]:
    register_expr(_n)

# Upper/Lower are ASCII-only on device, so they carry an incompat note and
# need incompatibleOps.enabled (reference marks them incompat for locale
# casing too, GpuOverrides.scala:1294-1439)
register_expr("Rand",
              incompat="threefry RNG sequence differs from Spark XORShift")
register_expr("MonotonicallyIncreasingID")
register_expr("SparkPartitionID")
register_expr("Upper", incompat="ASCII-only case conversion")
register_expr("Lower", incompat="ASCII-only case conversion")
register_expr("InitCap", incompat="ASCII-only case conversion")
for _n in ["StringLength", "Substring", "Concat",
           "StartsWith", "EndsWith", "Contains", "Like",
           "StringTrim", "StringTrimLeft", "StringTrimRight",
           "StringLocate", "StringReplace", "SubstringIndex",
           "ConcatWs", "RegExpReplace", "RLike", "SplitPart",
           "PallasContains",
           "Count", "Sum", "Min", "Max", "Average", "First", "Last",
           "WindowExpression", "RowNumber", "Rank", "DenseRank",
           "Lag", "Lead"]:
    register_expr(_n)


class ExecRule:
    def __init__(self, name: str):
        self.name = name

    @property
    def conf_key(self) -> str:
        return f"spark.rapids.sql.exec.{self.name}"


_EXEC_RULES = {n: ExecRule(n) for n in [
    "Project", "Filter", "Union", "Limit", "LocalRelation",
    "ParquetRelation", "CsvRelation", "OrcRelation", "Range", "Sort",
    "Aggregate", "Join", "Repartition", "Window", "Expand", "Generate",
]}


# ---------------------------------------------------------------------------
# Meta tree (reference RapidsMeta.scala:63-277)
# ---------------------------------------------------------------------------

class PlanMeta:
    """Tagging/conversion wrapper over one logical node (reference
    SparkPlanMeta RapidsMeta.scala:395)."""

    def __init__(self, node: lp.LogicalPlan, conf: TpuConf):
        self.node = node
        self.conf = conf
        self.children = [PlanMeta(c, conf) for c in node.children]
        self.reasons: List[str] = []
        self.bound_exprs: dict = {}
        # cost-based placement (plan/placement.py, docs/placement.md):
        # a CAPABLE node the cost model routed to the CPU engine — a
        # separate flag from `reasons` because explain and the
        # test-mode on-TPU assert must keep seeing it as supported
        self.cost_demoted = False
        self.demote_reason: Optional[str] = None

    def will_not_work_on_tpu(self, reason: str) -> None:
        """reference RapidsMeta.willNotWorkOnGpu RapidsMeta.scala:173."""
        if reason not in self.reasons:
            self.reasons.append(reason)

    @property
    def can_run_on_tpu(self) -> bool:
        return not self.reasons

    # -- tagging ------------------------------------------------------------

    def tag(self) -> None:
        for c in self.children:
            c.tag()
        rule = _EXEC_RULES.get(self._rule_name())
        if rule is None:
            self.will_not_work_on_tpu(
                f"no TPU rule for operator {self.node.node_name}")
            return
        if not self.conf.is_operator_enabled(rule.conf_key, False, False):
            self.will_not_work_on_tpu(
                f"operator disabled by {rule.conf_key}")
        self._tag_types()
        self._tag_expressions()
        self._tag_specific()

    def _rule_name(self) -> str:
        return self.node.node_name

    def _tag_types(self) -> None:
        """Type gate (reference isSupportedType GpuOverrides.scala:375)."""
        try:
            schema = self.node.output_schema()
        except Exception as e:
            self.will_not_work_on_tpu(f"cannot resolve schema: {e}")
            return
        for f in schema:
            if not is_supported_type(f.dtype):
                self.will_not_work_on_tpu(
                    f"unsupported type {f.dtype} for column {f.name}")

    def _expressions(self) -> List[Tuple[Expression, Optional[Schema]]]:
        """(expression, binding schema) pairs; None = first child's schema.
        Join keys bind per side and conditions against the joint output."""
        n = self.node
        if isinstance(n, lp.Project):
            return [(e, None) for e in n.exprs]
        if isinstance(n, lp.Filter):
            return [(n.pred, None)]
        if isinstance(n, lp.Sort):
            return [(e, None) for e, _, _ in n.orders]
        if isinstance(n, lp.Aggregate):
            return [(e, None)
                    for e in list(n.groupings) + list(n.aggregates)]
        if isinstance(n, lp.Join):
            rs = n.children[1].output_schema()
            out = [(e, None) for e in n.left_keys]
            out += [(e, rs) for e in n.right_keys]
            if n.condition is not None:
                out.append((n.condition, n.output_schema()))
            return out
        if isinstance(n, lp.Repartition):
            return [(e, None) for e in n.keys] + \
                [(e, None) for e, _, _ in n.orders]
        if isinstance(n, lp.Window):
            return [(w, None) for _, w in n.window_cols]
        if isinstance(n, lp.Expand):
            return [(e, None) for p in n.projections for e in p]
        return []

    def _tag_expressions(self) -> None:
        if not self.children:
            return
        child_schema = self.children[0].node.output_schema()
        for i, (e, schema) in enumerate(self._expressions()):
            try:
                bound = bind_expression(e, schema if schema is not None
                                        else child_schema)
            except Exception as ex:
                self.will_not_work_on_tpu(f"cannot bind {e!r}: {ex}")
                continue
            self.bound_exprs[i] = bound
            self._tag_expr_tree(bound)

    def _tag_expr_tree(self, e: Expression) -> None:
        rule = _EXPR_RULES.get(type(e).__name__)
        reason = getattr(e, "unsupported_on_tpu", None)
        if reason is not None:
            # expression self-reported a device limitation (e.g. string ops
            # with non-literal patterns) -> clean CPU fallback
            self.will_not_work_on_tpu(f"{type(e).__name__}: {reason}")
        if rule is None:
            self.will_not_work_on_tpu(
                f"expression {type(e).__name__} is not supported on TPU")
        elif getattr(e, "ignore_nulls", True) is False:
            # First/Last(ignore_nulls=False): both engines' segment kernels
            # pick the first/last VALID row, so honoring nulls is
            # unimplemented — reject rather than silently diverge from Spark
            self.will_not_work_on_tpu(
                f"{type(e).__name__}(ignore_nulls=False) is not supported")
        else:
            if not self.conf.is_operator_enabled(
                    rule.conf_key, rule.incompat is not None,
                    rule.disabled_by_default):
                self.will_not_work_on_tpu(
                    f"expression disabled by {rule.conf_key}")
        for c in e.children:
            self._tag_expr_tree(c)

    def _tag_specific(self) -> None:
        n = self.node
        if isinstance(n, lp.ParquetRelation):
            if not self.conf.get_bool(
                    "spark.rapids.sql.format.parquet.enabled", True):
                self.will_not_work_on_tpu(
                    "parquet disabled by spark.rapids.sql.format.parquet.enabled")
        if isinstance(n, lp.CsvRelation):
            if not self.conf.get_bool(
                    "spark.rapids.sql.format.csv.enabled", True):
                self.will_not_work_on_tpu(
                    "csv disabled by spark.rapids.sql.format.csv.enabled")
        if isinstance(n, lp.OrcRelation):
            if not self.conf.get_bool(
                    "spark.rapids.sql.format.orc.enabled", True):
                self.will_not_work_on_tpu(
                    "orc disabled by spark.rapids.sql.format.orc.enabled")
        if isinstance(n, lp.Join):
            if n.join_type not in ("inner", "left", "right", "full",
                                   "semi", "anti", "cross"):
                self.will_not_work_on_tpu(
                    f"join type {n.join_type} not supported")
            # post-filter conditions are only sound for inner/cross: outer
            # joins must null-extend rows whose matches all fail the
            # condition (reference restricts likewise, GpuHashJoin.scala:26)
            elif n.condition is not None and n.join_type not in (
                    "inner", "cross"):
                self.will_not_work_on_tpu(
                    f"join condition on {n.join_type} join is not "
                    "supported (post-filter is unsound for outer joins)")

    # -- explain ------------------------------------------------------------

    def explain_lines(self, indent: int = 0, mode: str = "ALL") -> List[str]:
        """reference RapidsMeta print RapidsMeta.scala:207-277."""
        pad = "  " * indent
        if not self.can_run_on_tpu:
            mark = "!"
            why = " <-- cannot run on TPU because " + "; ".join(self.reasons)
        elif self.cost_demoted:
            # cost placement (docs/placement.md): supported, but the
            # measured cost model routed it to the CPU engine — only
            # ever set when spark.rapids.sql.placement.mode != tpu, so
            # default explain output is byte-identical
            mark = "!"
            why = " <-- placed on CPU: " + (self.demote_reason or "")
        else:
            mark = "*"
            why = ""
        line = f"{pad}{mark} {self.node.node_name}{why}"
        lines = []
        if mode == "ALL" or not self.can_run_on_tpu or self.cost_demoted:
            lines.append(line)
        for c in self.children:
            lines.extend(c.explain_lines(indent + 1, mode))
        return lines

    # -- conversion (reference convertIfNeeded RapidsMeta.scala:522) --------

    @property
    def target_engine(self) -> str:
        """``'tpu'`` | ``'cpu'`` — the ONE engine decision conversion
        reads.  Tag reasons (unsupported ops) and cost-placement
        demotions (plan/placement.py) land in the same gate, so a
        cost-demoted fragment containing an unsupported op lowers
        exactly once through ``_to_cpu`` — never twice, never through
        diverging paths (docs/placement.md)."""
        if not self.can_run_on_tpu or self.cost_demoted:
            return "cpu"
        return "tpu"

    def convert(self) -> PhysicalPlan:
        phys_children = [c.convert() for c in self.children]
        if self.target_engine == "tpu":
            return self._to_tpu(phys_children)
        return self._to_cpu(phys_children)

    def _bound(self, exprs: Sequence[Expression]) -> List[Expression]:
        schema = self.children[0].node.output_schema()
        return [bind_expression(e, schema) for e in exprs]

    def _bind_pushed(self, rel: lp.ParquetRelation) -> Optional[Expression]:
        """Bind a pushed-down predicate against the scan schema; pushdown is
        best-effort, so an unbindable predicate just disables pruning."""
        if rel.pushed is None:
            return None
        try:
            return bind_expression(rel.pushed, rel.schema)
        except Exception:
            return None

    def _to_tpu(self, children: List[PhysicalPlan]) -> PhysicalPlan:
        n = self.node
        children = [to_device(c) for c in children]
        if isinstance(n, lp.LocalRelation):
            return tb.TpuLocalScanExec(n.table)
        if isinstance(n, lp.ParquetRelation):
            from spark_rapids_tpu.io.parquet import TpuParquetScanExec
            return TpuParquetScanExec(
                n.paths, n.schema, pred=self._bind_pushed(n),
                full_schema=n.full_schema)
        if isinstance(n, lp.CsvRelation):
            from spark_rapids_tpu.io.csv import TpuCsvScanExec
            return TpuCsvScanExec(n.paths, n.schema, n.header, n.sep)
        if isinstance(n, lp.OrcRelation):
            from spark_rapids_tpu.io.orc import TpuOrcScanExec
            return TpuOrcScanExec(n.paths, n.schema,
                                  pred=self._bind_pushed(n))
        if isinstance(n, lp.Range):
            return tb.TpuRangeExec(n.start, n.end, n.step)
        if isinstance(n, lp.Project):
            return tb.TpuProjectExec(self._bound(n.exprs), children[0])
        if isinstance(n, lp.Filter):
            return tb.TpuFilterExec(self._bound([n.pred])[0], children[0])
        if isinstance(n, lp.Union):
            return tb.TpuUnionExec(children)
        if isinstance(n, lp.Limit):
            from spark_rapids_tpu.exec.sort import TpuSortExec, TpuTopNExec
            c = children[0]
            if isinstance(c, TpuSortExec) and c.global_sort:
                # limit-over-sort fuses to streaming top-N (the
                # TakeOrderedAndProject shape) — never materializes more
                # than limit + one batch
                return TpuTopNExec(c.orders, n.n, c.children[0])
            return tb.TpuLocalLimitExec(n.n, children[0])
        if isinstance(n, lp.Sort):
            from spark_rapids_tpu.exec.sort import TpuSortExec
            schema = self.children[0].node.output_schema()
            orders = [(bind_expression(e, schema), asc, nf)
                      for e, asc, nf in n.orders]
            return TpuSortExec(orders, children[0])
        if isinstance(n, lp.Aggregate):
            from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
            schema = self.children[0].node.output_schema()
            return TpuHashAggregateExec(
                [bind_expression(e, schema) for e in n.groupings],
                [bind_expression(e, schema) for e in n.aggregates],
                children[0])
        if isinstance(n, lp.Join):
            ls = self.children[0].node.output_schema()
            rs = self.children[1].node.output_schema()
            cond = None
            if n.condition is not None:
                cond = bind_expression(n.condition, n.output_schema())
            return self._plan_join(
                n, children,
                [bind_expression(e, ls) for e in n.left_keys],
                [bind_expression(e, rs) for e in n.right_keys], cond)
        if isinstance(n, lp.Repartition):
            from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
            schema = self.children[0].node.output_schema()
            keys = [bind_expression(e, schema) for e in n.keys]
            orders = [(bind_expression(e, schema), asc, nf)
                      for e, asc, nf in n.orders]
            return TpuShuffleExchangeExec(
                n.num_partitions, keys, n.mode, children[0], orders=orders)
        if isinstance(n, lp.Window):
            from spark_rapids_tpu.exec.window import TpuWindowExec
            schema = self.children[0].node.output_schema()
            bound = [(name, bind_expression(w, schema))
                     for name, w in n.window_cols]
            return TpuWindowExec(bound, children[0])
        if isinstance(n, lp.Expand):
            from spark_rapids_tpu.exec.expand import TpuExpandExec
            schema = self.children[0].node.output_schema()
            bound = [[bind_expression(e, schema) for e in p]
                     for p in n.projections]
            return TpuExpandExec(bound, n.names, children[0])
        if isinstance(n, lp.Generate):
            from spark_rapids_tpu.exec.generate import TpuGenerateExec
            return TpuGenerateExec(n.generator, n.names, children[0])
        raise NotImplementedError(f"convert {n.node_name} to TPU")

    def _plan_join(self, n: "lp.Join", children: List[PhysicalPlan],
                   lkeys, rkeys, cond) -> PhysicalPlan:
        """Join strategy selection (reference GpuOverrides join rules +
        Spark's JoinSelection): broadcast the build side when its
        estimated size is under spark.rapids.sql.autoBroadcastJoinThreshold
        — preferring the right side, swapping behind a column-reordering
        projection when only the left qualifies — else shuffled hash
        join.

        With spark.rapids.sql.adaptive.enabled, equi-joins skip the
        static choice entirely: both sides shuffle through AQE-inserted
        exchanges (the EnsureRequirements placement Spark's AQE replans
        over) and the broadcast decision is made at runtime from the
        build side's MEASURED map-output bytes (plan/adaptive.py),
        replacing the planner-time size guess.  What the static rule
        WOULD have chosen is recorded on the join so a runtime
        contradiction counts as a broadcast demotion."""
        from spark_rapids_tpu.exec.joins import TpuHashJoinExec
        from spark_rapids_tpu.exec.broadcast import (
            TpuBroadcastExchangeExec, TpuBroadcastHashJoinExec,
        )
        thresh = self.conf.broadcast_threshold
        jt = n.join_type
        # AQE join exchanges and host-shuffle worker lowering are
        # alternative distribution strategies: an in-process AQE
        # exchange under a join would make the fragment unsplittable
        # and silently strip the multi-process map parallelism host
        # shuffle exists for, so with workers configured the join
        # follows the static path and the host exchange adapts
        # internally (stats-driven reduce grouping, docs/adaptive.md)
        if self.conf.adaptive_enabled and lkeys and rkeys and \
                self.conf.host_shuffle_workers <= 1:
            from spark_rapids_tpu.exec.exchange import (
                TpuShuffleExchangeExec,
            )
            nparts = self.conf.aqe_initial_partitions
            if nparts > 1:
                static_side = None
                if thresh >= 0:
                    # replicate the static rule exactly (incl. the
                    # both-qualify smaller-side tie-break) so demotion
                    # accounting compares runtime stats against what
                    # the static planner would truly have done
                    r_est = estimate_logical_size(n.children[1])
                    l_est = estimate_logical_size(n.children[0])
                    r_ok = r_est is not None and r_est <= thresh
                    l_ok = l_est is not None and l_est <= thresh and \
                        jt in ("inner", "cross", "left", "right",
                               "full")
                    if r_ok and l_ok:
                        static_side = "left" if l_est < r_est \
                            else "right"
                    elif r_ok:
                        static_side = "right"
                    elif l_ok:
                        static_side = "left"
                lex = TpuShuffleExchangeExec(nparts, lkeys, "hash",
                                             children[0])
                rex = TpuShuffleExchangeExec(nparts, rkeys, "hash",
                                             children[1])
                lex.aqe_inserted = True
                rex.aqe_inserted = True
                join = TpuHashJoinExec(lex, rex, lkeys, rkeys, jt,
                                       cond)
                join.aqe_static_side = static_side
                return join
        if thresh >= 0:
            r_est = estimate_logical_size(n.children[1])
            l_est = estimate_logical_size(n.children[0])
            r_ok = r_est is not None and r_est <= thresh
            # semi/anti must stream the left side, so only build-right works
            l_ok = l_est is not None and l_est <= thresh and jt in (
                "inner", "cross", "left", "right", "full")
            if r_ok and l_ok:
                # both qualify: broadcast the smaller (Spark JoinSelection)
                if l_est < r_est:
                    r_ok = False
                else:
                    l_ok = False
            if r_ok:
                return TpuBroadcastHashJoinExec(
                    children[0], TpuBroadcastExchangeExec(children[1]),
                    lkeys, rkeys, jt, cond)
            if l_ok:
                return swapped_broadcast_join(
                    children[1], TpuBroadcastExchangeExec(children[0]),
                    lkeys, rkeys, jt, cond,
                    len(n.children[0].output_schema().fields),
                    len(n.children[1].output_schema().fields),
                    n.output_schema().fields)
        return TpuHashJoinExec(children[0], children[1], lkeys, rkeys,
                               jt, cond)

    def _to_cpu(self, children: List[PhysicalPlan]) -> PhysicalPlan:
        n = self.node
        children = [to_host(c) for c in children]
        if isinstance(n, lp.LocalRelation):
            return cb.CpuLocalScanExec(n.table)
        if isinstance(n, lp.ParquetRelation):
            from spark_rapids_tpu.io.parquet import CpuParquetScanExec
            return CpuParquetScanExec(
                n.paths, n.schema, pred=self._bind_pushed(n),
                full_schema=n.full_schema)
        if isinstance(n, lp.CsvRelation):
            from spark_rapids_tpu.io.csv import CpuCsvScanExec
            return CpuCsvScanExec(n.paths, n.schema, n.header, n.sep)
        if isinstance(n, lp.OrcRelation):
            from spark_rapids_tpu.io.orc import CpuOrcScanExec
            return CpuOrcScanExec(n.paths, n.schema)
        if isinstance(n, lp.Project):
            return cb.CpuProjectExec(self._bound(n.exprs), children[0])
        if isinstance(n, lp.Filter):
            return cb.CpuFilterExec(self._bound([n.pred])[0], children[0])
        if isinstance(n, lp.Union):
            return cb.CpuUnionExec(children)
        if isinstance(n, lp.Limit):
            return cb.CpuLocalLimitExec(n.n, children[0])
        if isinstance(n, lp.Sort):
            from spark_rapids_tpu.cpu.relational import CpuSortExec
            schema = self.children[0].node.output_schema()
            orders = [(bind_expression(e, schema), asc, nf)
                      for e, asc, nf in n.orders]
            return CpuSortExec(orders, children[0])
        if isinstance(n, lp.Aggregate):
            from spark_rapids_tpu.cpu.relational import CpuHashAggregateExec
            schema = self.children[0].node.output_schema()
            return CpuHashAggregateExec(
                [bind_expression(e, schema) for e in n.groupings],
                [bind_expression(e, schema) for e in n.aggregates],
                children[0])
        if isinstance(n, lp.Join):
            from spark_rapids_tpu.cpu.relational import CpuHashJoinExec
            ls = self.children[0].node.output_schema()
            rs = self.children[1].node.output_schema()
            cond = None
            if n.condition is not None:
                cond = bind_expression(n.condition, n.output_schema())
            return CpuHashJoinExec(
                children[0], children[1],
                [bind_expression(e, ls) for e in n.left_keys],
                [bind_expression(e, rs) for e in n.right_keys],
                n.join_type, cond)
        if isinstance(n, lp.Range):
            return cb.CpuRangeExec(n.start, n.end, n.step)
        if isinstance(n, lp.Repartition):
            return cb.CpuRepartitionExec(n.num_partitions, children[0])
        if isinstance(n, lp.Window):
            from spark_rapids_tpu.cpu.relational import CpuWindowExec
            schema = self.children[0].node.output_schema()
            bound = [(name, bind_expression(w, schema))
                     for name, w in n.window_cols]
            return CpuWindowExec(bound, children[0])
        if isinstance(n, lp.Expand):
            from spark_rapids_tpu.exec.expand import CpuExpandExec
            schema = self.children[0].node.output_schema()
            bound = [[bind_expression(e, schema) for e in p]
                     for p in n.projections]
            return CpuExpandExec(bound, n.names, children[0])
        if isinstance(n, lp.Generate):
            from spark_rapids_tpu.exec.generate import CpuGenerateExec
            return CpuGenerateExec(n.generator, n.names, children[0])
        raise NotImplementedError(f"convert {n.node_name} to CPU")


# ---------------------------------------------------------------------------
# Transitions (reference GpuTransitionOverrides.scala:36-146)
# ---------------------------------------------------------------------------

def to_device(p: PhysicalPlan) -> TpuExec:
    if isinstance(p, TpuExec):
        return p
    if isinstance(p, DeviceToHostExec):
        # collapse DeviceToHost . HostToDevice pairs
        return p.children[0]
    return HostToDeviceExec(p)


def to_host(p: PhysicalPlan) -> CpuExec:
    if isinstance(p, CpuExec):
        return p
    if isinstance(p, HostToDeviceExec):
        return p.children[0]
    return DeviceToHostExec(p)


# ---------------------------------------------------------------------------
# Entry point (reference GpuOverrides.apply GpuOverrides.scala:1708)
# ---------------------------------------------------------------------------

class PlanResult:
    def __init__(self, physical: PhysicalPlan, meta: PlanMeta,
                 explain: str):
        self.physical = physical
        self.meta = meta
        self.explain = explain
        # stamped by the execution entry points (api.py) from the
        # supervising QueryContext after it finishes, so the retained
        # plan and its id/wall time can never be mis-paired — another
        # query finishing later (a write, a concurrent session) must
        # not relabel this one's profile (docs/observability.md)
        self.query_id = None
        self.wall_ms = None
        # per-fragment placement decisions (plan/placement.py): empty
        # unless spark.rapids.sql.placement.mode != tpu; rendered by
        # explain(analyze=True) and stamped by plan_query
        self.placement: List[dict] = []


class NotOnTpuError(RuntimeError):
    """Raised in test mode when part of the plan fell back (reference
    assertIsOnTheGpu GpuTransitionOverrides.scala:211-254)."""


def estimate_logical_size(node: lp.LogicalPlan) -> Optional[int]:
    """Best-effort build-side size estimate in bytes for join strategy
    selection (the Spark statistics analog the reference relies on:
    sizeInBytes driving autoBroadcastJoinThreshold).  Conservative: only
    shapes whose size is knowable without running return a number;
    Filter/Limit/Project pass through as upper bounds."""
    import os
    if isinstance(node, lp.LocalRelation):
        return node.table.nbytes
    if isinstance(node, (lp.ParquetRelation, lp.OrcRelation,
                         lp.CsvRelation)):
        if isinstance(node, lp.ParquetRelation):
            from spark_rapids_tpu.io.parquet import expand_paths
        elif isinstance(node, lp.OrcRelation):
            from spark_rapids_tpu.io.orc import \
                expand_orc_paths as expand_paths
        else:
            from spark_rapids_tpu.io.csv import \
                expand_csv_paths as expand_paths
        try:
            files = expand_paths(node.paths)
            if not files:
                # unknown size must NOT read as "zero bytes": a 0 estimate
                # would elect an arbitrarily large table for broadcast
                return None
            return sum(os.path.getsize(f) for f in files)
        except OSError:
            return None
    if isinstance(node, lp.Range):
        return 8 * max(0, (node.end - node.start) // (node.step or 1))
    if isinstance(node, (lp.Filter, lp.Limit, lp.Project)):
        return estimate_logical_size(node.children[0])
    return None


def swapped_broadcast_join(stream: PhysicalPlan,
                           build_exchange: PhysicalPlan,
                           lkeys, rkeys, jt: str,
                           cond: Optional[Expression],
                           nl: int, nr: int, out_fields):
    """The build-LEFT broadcast shape, shared by the static rule
    (``_plan_join``'s l_ok branch) and AQE's runtime promotion
    (plan/adaptive.py) so the two can never diverge: mirror the join
    type, build on the broadcast left side (``build_exchange``), remap
    the condition onto the swapped [right, left] layout, and restore
    the original column order behind a reordering projection.
    ``nl``/``nr``: field counts of the original left/right inputs;
    ``out_fields``: the unswapped join's output fields."""
    from spark_rapids_tpu.exec.broadcast import TpuBroadcastHashJoinExec
    mirror = {"inner": "inner", "cross": "cross",
              "left": "right", "right": "left",
              "full": "full"}[jt]
    swapped = TpuBroadcastHashJoinExec(
        stream, build_exchange, rkeys, lkeys, mirror,
        _remap_ordinals(cond, nl, nr))
    reorder = []
    for i, f in enumerate(out_fields):
        src = nr + i if i < nl else i - nl
        reorder.append(BoundReference(src, f.dtype, f.nullable, f.name))
    return tb.TpuProjectExec(reorder, swapped)


def _remap_ordinals(cond: Optional[Expression], nl: int,
                    nr: int) -> Optional[Expression]:
    """Rebase a join condition bound against [left, right] output onto the
    side-swapped [right, left] layout."""
    if cond is None:
        return None

    def walk(e: Expression) -> Expression:
        if isinstance(e, BoundReference):
            o = e.ordinal
            o = o + nr if o < nl else o - nl
            return BoundReference(o, e.dtype, e.nullable, e.col_name)
        if not e.children:
            return e
        return e.with_children([walk(c) for c in e.children])

    return walk(cond)


def push_join_conditions(node: lp.LogicalPlan) -> lp.LogicalPlan:
    """Predicate pushdown through INNER joins (the Catalyst
    PushPredicateThroughJoin rule the reference inherits from Spark):
    conjuncts of a Filter directly above an inner Join move (a) to the
    side they reference alone — pruning rows before the join — or
    (b) into the join CONDITION when they reference both sides, where
    the band-aware probe (exec/joins.py _BandSpec) can narrow candidate
    ranges instead of materializing every equi pair (TPCx-BB q3/q8's
    date-window shape).  Conjuncts naming ambiguous columns stay put."""
    from spark_rapids_tpu.exprs import predicates as _pr
    from spark_rapids_tpu.exprs.base import UnresolvedAttribute

    new_children = [push_join_conditions(c) for c in node.children]
    if any(a is not b for a, b in zip(new_children, node.children)):
        node = copy.copy(node)
        node.children = new_children
        node.__dict__.pop("_schema_cache", None)

    if not (isinstance(node, lp.Filter)
            and isinstance(node.children[0], lp.Join)
            and node.children[0].join_type == "inner"):
        return node
    join = node.children[0]

    def conjuncts(e):
        if isinstance(e, _pr.And):
            return conjuncts(e.children[0]) + conjuncts(e.children[1])
        return [e]

    def attr_names(e):
        out = set()

        def walk(x):
            if isinstance(x, UnresolvedAttribute):
                out.add(x.col_name)
            for c in x.children:
                walk(c)
        walk(e)
        return out

    def and_all(terms):
        acc = terms[0]
        for t in terms[1:]:
            acc = _pr.And(acc, t)
        return acc

    lnames = set(join.children[0].output_schema().names)
    rnames = set(join.children[1].output_schema().names)
    ambiguous = lnames & rnames
    left_p, right_p, cond_p, keep = [], [], [], []
    for c in conjuncts(node.pred):
        refs = attr_names(c)
        if not refs or refs & ambiguous:
            keep.append(c)
        elif refs <= lnames:
            left_p.append(c)
        elif refs <= rnames:
            right_p.append(c)
        elif refs <= (lnames | rnames):
            cond_p.append(c)
        else:
            keep.append(c)
    if not (left_p or right_p or cond_p):
        return node
    new_left = join.children[0]
    if left_p:
        new_left = push_join_conditions(
            lp.Filter(and_all(left_p), new_left))
    new_right = join.children[1]
    if right_p:
        new_right = push_join_conditions(
            lp.Filter(and_all(right_p), new_right))
    cond = join.condition
    for t in cond_p:
        cond = t if cond is None else _pr.And(cond, t)
    new_join = lp.Join(new_left, new_right, join.left_keys,
                       join.right_keys, join.join_type, condition=cond)
    if keep:
        return lp.Filter(and_all(keep), new_join)
    return new_join


def push_scan_filters(node: lp.LogicalPlan) -> lp.LogicalPlan:
    """Fold a Filter's predicate into the parquet scan directly below it so
    the reader can prune row groups by footer min/max stats (reference
    GpuParquetScan.scala:316-458).  Pruning is conservative, so the Filter
    node stays in the plan; nodes are rebuilt, never mutated (logical plans
    are shared between DataFrames)."""
    new_children = [push_scan_filters(c) for c in node.children]
    if isinstance(node, lp.Filter):
        child = new_children[0]
        for rel_cls in (lp.ParquetRelation, lp.OrcRelation):
            if isinstance(child, rel_cls):
                return lp.Filter(node.pred, _with_pushed(child, node.pred))
            # stacked filters: the bottom-up pass already pushed the
            # inner predicate, so AND this one into the same scan
            if isinstance(child, lp.Filter) and \
                    isinstance(child.children[0], rel_cls):
                return lp.Filter(node.pred, lp.Filter(
                    child.pred, _with_pushed(child.children[0], node.pred)))
    if any(a is not b for a, b in zip(new_children, node.children)):
        node = copy.copy(node)
        node.children = new_children
        node.__dict__.pop("_schema_cache", None)
    return node


def _with_pushed(rel, pred: Expression):
    """A copy of the scan relation ``rel`` with ``pred`` ANDed into its
    pushed predicate; every other attribute (the pruned schema too) as
    it was."""
    from spark_rapids_tpu.exprs import predicates as _pr
    rel = copy.copy(rel)
    rel.pushed = pred if rel.pushed is None else _pr.And(rel.pushed, pred)
    return rel


def prune_scan_columns(root: lp.LogicalPlan) -> lp.LogicalPlan:
    """Column pruning (Catalyst's ColumnPruning, which Spark runs ahead of
    every file scan; the reference's GpuParquetScan reads its clipped read
    schema): every ``ParquetRelation`` narrowed to the columns the plan
    above it reads, in file order, so its scan decodes, uploads and
    carries no other.  Walks top-down with the output ordinals each node
    must hand on: a Project keeps only the expressions its parent reads
    and reads what they reference; Filter, Sort and a Join add what their
    predicate, order and keys read (a Join sends each ordinal to its
    side); Limit passes its parent's set on; an Aggregate reads its
    groupings and aggregate inputs; every other node reads all of its
    children's output.  A relation keeps its pushed predicate's columns
    and, where nothing is read (``count(*)``), its narrowest file column.
    Ordinal references above a narrowed node are rebound.  Nodes are
    rebuilt, never mutated (logical plans are shared between
    DataFrames); CSV, ORC and local relations are left whole."""
    return _prune(root, None)[0]


def _refs(exprs, schema: Schema) -> set:
    """The ordinals of ``schema`` that ``exprs`` read: a name's first
    field, which is the one it binds to, and a bound reference's own."""
    from spark_rapids_tpu.exprs.base import UnresolvedAttribute
    first = {}
    for i, n in enumerate(schema.names):
        first.setdefault(n, i)
    out = set()

    def walk(e):
        if isinstance(e, UnresolvedAttribute):
            if e.col_name in first:
                out.add(first[e.col_name])
        elif isinstance(e, BoundReference):
            out.add(e.ordinal)
        for c in e.children:
            walk(c)
    for e in exprs:
        walk(e)
    return out


def _rebind(e: Expression, remap: Optional[dict]) -> Expression:
    """``e`` with every bound reference moved by ``remap`` (old ordinal
    -> new); ``remap`` None leaves it as it is."""
    if remap is None:
        return e
    if isinstance(e, BoundReference):
        return BoundReference(remap[e.ordinal], e.dtype, e.nullable,
                              e.col_name)
    kids = [_rebind(c, remap) for c in e.children]
    if all(a is b for a, b in zip(kids, e.children)):
        return e
    return e.with_children(kids)


def _narrowest(fields) -> int:
    """Index of the narrowest of ``fields`` (fixed widths first, then by
    bytes; the first of equals)."""
    return min(range(len(fields)), key=lambda i: (
        not fields[i].dtype.fixed_width, fields[i].dtype.byte_width))


def _prune(node: lp.LogicalPlan, need: Optional[set]):
    """``node`` rebuilt to hand on at least its output ordinals ``need``
    (None: all of them), and where it hands on fewer the map from its old
    output ordinals to the new (None: its output is unchanged)."""
    if isinstance(node, lp.ParquetRelation):
        return _prune_relation(node, need)
    if isinstance(node, lp.Project):
        return _prune_project(node, need)
    if isinstance(node, (lp.Filter, lp.Sort, lp.Limit)):
        return _prune_pass_through(node, need)
    if isinstance(node, lp.Aggregate):
        child = node.children[0]
        new_child, cmap = _prune(child, _refs(
            node.groupings + node.aggregates, child.output_schema()))
        if new_child is child:
            return node, None
        return lp.Aggregate([_rebind(e, cmap) for e in node.groupings],
                            [_rebind(e, cmap) for e in node.aggregates],
                            new_child), None
    if isinstance(node, lp.Join):
        return _prune_join(node, need)
    # Union, Expand, Window, Generate, Repartition and every leaf but a
    # parquet relation: all of each child's output
    new_children = [_prune(c, None)[0] for c in node.children]
    if all(a is b for a, b in zip(new_children, node.children)):
        return node, None
    node = copy.copy(node)
    node.children = new_children
    node.__dict__.pop("_schema_cache", None)
    return node, None


def _prune_project(node: lp.Project, need: Optional[set]):
    keep = list(range(len(node.exprs))) if need is None else sorted(need)
    if not keep:
        keep = [_narrowest(node.output_schema().fields)]
    exprs = [node.exprs[i] for i in keep]
    child = node.children[0]
    new_child, cmap = _prune(child, _refs(exprs, child.output_schema()))
    whole = len(keep) == len(node.exprs)
    if new_child is child and whole:
        return node, None
    return (lp.Project([_rebind(e, cmap) for e in exprs], new_child),
            None if whole else {o: i for i, o in enumerate(keep)})


def _prune_pass_through(node: lp.LogicalPlan, need: Optional[set]):
    """Filter, Sort and Limit hand on their child's output: the child
    hands on what the parent reads and what the predicate or order does."""
    child = node.children[0]
    if isinstance(node, lp.Filter):
        exprs = [node.pred]
    elif isinstance(node, lp.Sort):
        exprs = [e for e, _, _ in node.orders]
    else:
        exprs = []
    new_child, cmap = _prune(child, None if need is None else
                             need | _refs(exprs, child.output_schema()))
    if new_child is child:
        return node, None
    if isinstance(node, lp.Filter):
        return lp.Filter(_rebind(node.pred, cmap), new_child), cmap
    if isinstance(node, lp.Sort):
        return lp.Sort([(_rebind(e, cmap), asc, nf)
                        for e, asc, nf in node.orders], new_child), cmap
    return lp.Limit(node.n, new_child), cmap


def _prune_join(node: lp.Join, need: Optional[set]):
    """Each wanted ordinal of the join's output goes to its side, with
    the side's keys; a semi or anti join hands on its left side alone, so
    its right side keeps only its keys whatever the parent reads."""
    left, right = node.children
    ls, rs, out = (left.output_schema(), right.output_schema(),
                   node.output_schema())
    nl = len(ls.fields)
    left_only = node.join_type in ("semi", "anti")
    want = set(range(len(out.fields))) if need is None else set(need)
    if node.condition is not None:
        want |= _refs([node.condition], out)
    lneed = None if need is None else \
        {i for i in want if i < nl} | _refs(node.left_keys, ls)
    rneed = None if need is None and not left_only else \
        {i - nl for i in want if i >= nl} | _refs(node.right_keys, rs)
    new_left, lmap = _prune(left, lneed)
    new_right, rmap = _prune(right, rneed)
    if new_left is left and new_right is right:
        return node, None
    remap = lmap
    if not left_only and (lmap or rmap):
        lmap = lmap or {i: i for i in range(nl)}
        rmap = rmap or {i: i for i in range(len(rs.fields))}
        new_nl = len(new_left.output_schema().fields)
        remap = dict(lmap)
        remap.update((nl + o, new_nl + n) for o, n in rmap.items())
    return lp.Join(new_left, new_right,
                   [_rebind(e, lmap) for e in node.left_keys],
                   [_rebind(e, rmap) for e in node.right_keys],
                   node.join_type,
                   condition=None if node.condition is None else
                   _rebind(node.condition, remap)), remap


def _prune_relation(rel: lp.ParquetRelation, need: Optional[set]):
    if need is None:
        return rel, None
    fields = rel.schema.fields
    keep = set(need)
    if rel.pushed is not None:
        keep |= _refs([rel.pushed], rel.schema)
    file_idx = _file_column_indices(rel)
    if not keep & set(file_idx):
        # a scan reads rows through a file column: the narrowest
        keep.add(file_idx[_narrowest([fields[i] for i in file_idx])])
    if len(keep) == len(fields):
        return rel, None
    keep = sorted(keep)
    narrowed = lp.ParquetRelation(
        rel.paths, Schema([fields[i] for i in keep]), pushed=rel.pushed,
        full_schema=rel.full_schema)
    return narrowed, {o: i for i, o in enumerate(keep)}


def _file_column_indices(rel: lp.ParquetRelation) -> List[int]:
    """Ordinals of ``rel``'s schema that its files hold: all but the
    hive partition columns, which exist only under a directory root."""
    import os
    roots = rel.paths if isinstance(rel.paths, (list, tuple)) \
        else [rel.paths]
    part = set()
    if any(os.path.isdir(r) for r in roots):
        from spark_rapids_tpu.io import hivepart
        from spark_rapids_tpu.io.parquet import expand_paths
        part_schema, _ = hivepart.discover(roots, expand_paths(rel.paths))
        part = set(part_schema.names) if part_schema else set()
    return [i for i, f in enumerate(rel.schema.fields)
            if f.name not in part] or list(range(len(rel.schema.fields)))


def insert_coalesce(plan: PhysicalPlan, conf: TpuConf) -> PhysicalPlan:
    """Insert TpuCoalesceBatchesExec where an exec's declared child goal is
    not already met by the child's output batching (reference
    GpuTransitionOverrides.insertCoalesce GpuTransitionOverrides.scala:36
    + the CoalesceGoal lattice GpuCoalesceBatches.scala:90)."""
    from spark_rapids_tpu.exec.coalesce import TpuCoalesceBatchesExec
    new_children = [insert_coalesce(c, conf) for c in plan.children]
    if isinstance(plan, TpuExec):
        goals = plan.child_coalesce_goals(conf)
        for i, (c, goal) in enumerate(zip(new_children, goals)):
            if goal is None or not isinstance(c, TpuExec):
                continue
            have = c.output_batching
            if have is not None and goal.satisfied_by(have):
                continue
            new_children[i] = TpuCoalesceBatchesExec(goal, c)
    plan.children = new_children
    return plan


def plan_query(root: lp.LogicalPlan, conf: TpuConf) -> PlanResult:
    if conf.get_bool(
            "spark.rapids.sql.optimizer.pushJoinConditions.enabled", True):
        root = push_join_conditions(root)
    if conf.get_bool(
            "spark.rapids.sql.format.parquet.filterPushdown.enabled", True):
        root = push_scan_filters(root)
    root = prune_scan_columns(root)
    meta = PlanMeta(root, conf)
    # analysis-time placement check — runs on BOTH engine paths (neither
    # threads a partition id outside Project)
    _check_nondeterministic_placement(meta)
    if conf.sql_enabled:
        meta.tag()
    else:
        _disable_all(meta)
    # cost-based hybrid placement (plan/placement.py,
    # docs/placement.md): with placement.mode=cost each maximal
    # TPU-assignable fragment is scored — projected transfer + compile
    # + kernel cost against the calibrated CPU throughputs — and
    # losing fragments demote through the same _to_cpu seam as
    # unsupported-op fallback; mode=cpu demotes everything (the A/B
    # baseline).  Default tpu never enters the module: plans, results,
    # and metrics stay byte-identical.
    placement_decisions: List[dict] = []
    if conf.sql_enabled and conf.placement_mode != "tpu":
        from spark_rapids_tpu.plan.placement import place_fragments
        placement_decisions = place_fragments(meta, conf)
    explain_mode = conf.explain.upper()
    lines = meta.explain_lines(mode="ALL")
    explain = "\n".join(lines)
    if explain_mode in ("ALL", "NOT_ON_TPU", "NOT_ON_GPU"):
        shown = meta.explain_lines(
            mode="ALL" if explain_mode == "ALL" else "NOT_ON_TPU")
        if shown:
            # the conf-requested explain surface: a deliberate stdout
            # write, not a stray debug print (the lint bans those)
            import sys
            sys.stdout.write("\n".join(shown) + "\n")
    if conf.test_enabled:
        _assert_on_tpu(meta, conf.test_allowed_non_tpu)
    physical = meta.convert()
    if conf.mesh_devices > 1:
        from spark_rapids_tpu.exec.meshexec import mesh_lower
        physical = mesh_lower(physical, conf)
    else:
        # spark.rapids.shuffle.mode=ici (docs/ici_shuffle.md): the
        # shuffle manager owns the host/ICI decision (workers, device
        # pool, explicit-mesh precedence); when it elects ici, exchange
        # fragments lower onto the full mesh with the single-chip exec
        # carried as the per-fragment host-path fallback
        from spark_rapids_tpu.shuffle.manager import select_shuffle_mode
        if select_shuffle_mode(conf) == "ici":
            from spark_rapids_tpu.exec.meshexec import ici_lower
            physical = ici_lower(physical, conf)
    if conf.host_shuffle_workers > 1:
        physical = host_shuffle_lower(physical, conf)
    # whole-stage fusion AFTER the lowering passes (so chains inside
    # lowered fragments fuse too and splittability decisions are
    # unaffected), BEFORE coalesce insertion (a stage declares the same
    # batching contract as the ops it replaced)
    from spark_rapids_tpu.plan.fusion import fuse_physical
    physical = fuse_physical(physical, conf)
    physical = insert_coalesce(to_host(physical), conf)
    # sharded scan ingest (docs/sharded_scan.md): AFTER fusion +
    # coalesce so the chain each guarded mesh fragment's spec captures
    # is the tree that will execute; gated on
    # spark.rapids.shuffle.ici.shardedScan.enabled (off touches no
    # node — plans stay byte-identical)
    if conf.ici_sharded_scan:
        from spark_rapids_tpu.parallel.shardscan import mark_sharded_scans
        physical = mark_sharded_scans(physical, conf)
    # adaptive wrapper LAST: it owns the fully-lowered plan (fusion
    # folded, coalesce inserted) and replans it between stage
    # materializations (docs/adaptive.md); off never constructs the
    # wrapper, so static plans are untouched byte-for-byte
    if conf.adaptive_enabled:
        from spark_rapids_tpu.plan.adaptive import insert_adaptive
        physical = insert_adaptive(physical, conf)
    result = PlanResult(physical, meta, explain)
    result.placement = placement_decisions
    return result


def host_shuffle_lower(plan, conf):
    """Insert TpuHostShuffleExchangeExec below aggregates and joins
    when spark.rapids.shuffle.workers.count > 1, spreading map-side
    work across OS processes (reference GpuShuffleExchangeExec
    insertion by GpuOverrides; exchange-consistency per
    RapidsMeta.scala:413-478: a join shuffles BOTH sides with the
    same partition count and matching key positions, or NEITHER
    side)."""
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.joins import TpuHashJoinExec
    from spark_rapids_tpu.shuffle.stage import (
        TpuHostShuffleExchangeExec, splittable,
    )
    n = conf.host_shuffle_workers
    # spark.rapids.shuffle.defaultNumPartitions (0 = keep the derived
    # workers*2 default inside the exchange)
    nparts = conf.shuffle_default_partitions or None

    def rewrite(node):
        node.children = [rewrite(c) for c in node.children]
        if isinstance(node, TpuHostShuffleExchangeExec):
            return node  # already lowered
        if isinstance(node, TpuHashAggregateExec) and node.groupings \
                and splittable(node.children[0]):
            node.children = [TpuHostShuffleExchangeExec(
                node.groupings, node.children[0], n,
                num_partitions=nparts)]
            return node
        if isinstance(node, TpuHashJoinExec) and node.left_keys and \
                node.right_keys:
            left, right = node.children
            if splittable(left) and splittable(right):
                node.children = [
                    TpuHostShuffleExchangeExec(node.left_keys, left,
                                               n, num_partitions=nparts),
                    TpuHostShuffleExchangeExec(node.right_keys,
                                               right, n,
                                               num_partitions=nparts),
                ]
            return node
        return node

    return rewrite(plan)


def _check_nondeterministic_placement(meta: PlanMeta) -> None:
    """Spark's analyzer restricts nondeterministic expressions to
    Project/Filter; the API rewrites filter predicates through a Project,
    so anywhere else is an error regardless of which engine runs."""
    from spark_rapids_tpu.exprs.nondeterministic import (
        contains_nondeterministic,
    )
    if not isinstance(meta.node, lp.Project):
        for e, _ in meta._expressions():
            if contains_nondeterministic(e):
                raise ValueError(
                    "nondeterministic expressions (rand, "
                    "monotonically_increasing_id, spark_partition_id) "
                    "are only allowed in select()/with_column()/"
                    f"filter(), not in {meta.node.node_name}")
    for c in meta.children:
        _check_nondeterministic_placement(c)


def _disable_all(meta: PlanMeta) -> None:
    meta.will_not_work_on_tpu("spark.rapids.sql.enabled is false")
    for c in meta.children:
        _disable_all(c)


def _assert_on_tpu(meta: PlanMeta, allowed: List[str]) -> None:
    name = meta.node.node_name
    if not meta.can_run_on_tpu and name not in allowed:
        raise NotOnTpuError(
            f"{name} did not convert to TPU: {'; '.join(meta.reasons)} "
            "(spark.rapids.sql.test.enabled is set)")
    for c in meta.children:
        _assert_on_tpu(c, allowed)
