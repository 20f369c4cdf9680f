"""The planner fusion pass: collapse project/filter chains into stages.

Runs over the PHYSICAL plan (after conversion and the mesh / host-shuffle
lowering passes, before coalesce insertion) — the same rewrite layer the
reference uses for its plan surgery (GpuOverrides /
GpuTransitionOverrides) and the analog of Spark's WholeStageCodegenExec
insertion: walk the tree bottom-up, fold every maximal chain of
consecutive ``TpuProjectExec`` / ``TpuFilterExec`` nodes into one
``TpuStageExec`` (exec/stage.py) whose whole step list compiles to a
single XLA program, then unwrap the chains of length one so isolated
operators keep their per-op execution (and metrics) untouched.

Chain membership is deliberately narrow: project and filter are the
per-batch, capacity-preserving, 1-batch-in-1-batch-out operators, so
fusing them changes neither batching nor row order nor any downstream
contract.  The hash exchange additionally recognizes a fused-stage
child at execute time and folds the stage's steps plus its own
partition-key projection into one kernel (exec/exchange.py).

The aggregate takes its chain at PLAN time (``_fold_aggregates``): a
chain whose only consumer is a ``TpuHashAggregateExec`` update moves
into that node, which runs the steps MASKED in its update program — a
filter in front of a reduction needs no compaction, only a liveness
mask (exec/aggregate.py, docs/fusion.md).  The coalesce the aggregate
asks for then lands above the scan, with the same goal.

Gated by ``spark.rapids.sql.fusion.enabled``; with it off the plan is
returned untouched and execution is byte-for-byte today's per-op path.
"""

from __future__ import annotations

from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.exec import basic as tb
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.exec.base import PhysicalPlan
from spark_rapids_tpu.exec.stage import TpuStageExec
from spark_rapids_tpu.utils import tracing


def fuse_physical(plan: PhysicalPlan, conf: TpuConf) -> PhysicalPlan:
    """Apply whole-stage fusion to ``plan`` (no-op when disabled)."""
    if not conf.fusion_enabled:
        return plan
    max_ops = conf.fusion_max_ops
    with tracing.trace_range(tracing.SPAN_PLAN_FUSION):
        return _unwrap_singletons(
            _fold_aggregates(_collapse(plan, max_ops)))


def _step_of(node: PhysicalPlan):
    if isinstance(node, tb.TpuProjectExec):
        return ("project", tuple(node.exprs))
    if isinstance(node, tb.TpuFilterExec):
        return ("filter", (node.pred,))
    return None


def _collapse(node: PhysicalPlan, max_ops: int) -> PhysicalPlan:
    node.children = [_collapse(c, max_ops) for c in node.children]
    step = _step_of(node)
    if step is None:
        return node
    child = node.children[0]
    if isinstance(child, TpuStageExec) and len(child.steps) < max_ops:
        # the child chain already collapsed; append this op's step
        return TpuStageExec(child.steps + [step], child.children[0])
    return TpuStageExec([step], child)


def _fold_aggregates(node: PhysicalPlan) -> PhysicalPlan:
    """Move every chain that feeds an aggregate update into the
    aggregate.  Decided on what the plan shows: the chain is a collapsed
    stage (chains of one are still stages here), the consumer is the
    single-chip aggregate (the mesh lowering ran earlier and took its
    own), and no step is nondeterministic or reads row position — masked
    rows keep their input position, and the exchange refuses
    split-on-retry on the same test."""
    node.children = [_fold_aggregates(c) for c in node.children]
    if type(node) is TpuHashAggregateExec:
        child = node.children[0]
        if isinstance(child, TpuStageExec) and not child.nondeterministic:
            node.fold_steps(child.steps, child.children[0])
    return node


def _unwrap_singletons(node: PhysicalPlan) -> PhysicalPlan:
    node.children = [_unwrap_singletons(c) for c in node.children]
    if isinstance(node, TpuStageExec) and len(node.steps) == 1:
        kind, exprs = node.steps[0]
        if kind == "project":
            return tb.TpuProjectExec(list(exprs), node.children[0])
        return tb.TpuFilterExec(exprs[0], node.children[0])
    return node
