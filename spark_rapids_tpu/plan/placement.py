"""Cost-based hybrid fragment placement (docs/placement.md).

The decision half of ROADMAP item 5: route each maximal engine-
assignable fragment to the engine that wins it, so the TPU stops
losing the small/string-heavy suites it pays ~94 ms of link latency to
accelerate.  The reference plugin's entire planner layer
(``GpuOverrides``/``RapidsMeta``, PAPER.md section 1 layer 2) is this
same cost-gated decision about what belongs on the accelerator, with
clean per-operator CPU fallback; the measured inputs live in
plan/cost.py.

Two passes, one scoring formula, one fault site (``plan.place``):

* **Static pass** (``place_fragments``) — runs inside ``plan_query``
  between tagging and conversion, on the META tree: every maximal
  connected subtree of can-run-on-TPU nodes is a fragment, scored with
  the estimated input bytes (``estimate_logical_size``); losing
  fragments are marked ``cost_demoted`` so ``PlanMeta.convert`` lowers
  them through the SAME ``_to_cpu`` path as unsupported-op fallback —
  one conversion per node, transitions inserted exactly as today
  (the double-lowering seam this module was required to close).
* **AQE re-score** (``aqe_rescore``) — called from the replan pass
  after each stage materializes: the remaining fragment above the
  stage is re-scored with the MEASURED stage bytes, and when the
  static estimate was wrong (the measured bytes place it on the CPU
  engine) the remainder is demoted physically — supported device
  operators convert to their CPU analogs over a ``DeviceToHostExec``
  of the materialized stage, behind a ``HostToDeviceExec`` preserving
  the adaptive wrapper's device-batch contract.  Anything the
  physical converter cannot move (joins, pending exchanges, windows)
  skips the demotion: same fall-back-to-static contract as the other
  replan rules.

Gated by ``spark.rapids.sql.placement.mode`` (default ``tpu`` = this
module never runs; ``cpu`` = every fragment demotes, the A/B
baseline).  An injected ``plan.place`` fault — or any error in either
pass — degrades to the static all-TPU plan, counted, query correct.
"""

from __future__ import annotations

import logging
import threading
from typing import List, Optional, Tuple

from spark_rapids_tpu.plan import cost

log = logging.getLogger("spark_rapids_tpu.plan.placement")

FAULT_SITE_PLACE = "plan.place"

# ---------------------------------------------------------------------------
# Process-wide placement statistics (the `placement` group of the obs
# registry snapshot and bench.py's summary object)
# ---------------------------------------------------------------------------

_STATS_LOCK = threading.Lock()
_STATS = {
    "fragments_scored": 0,
    "fragments_tpu": 0,
    "fragments_cpu": 0,
    "aqe_demotions": 0,
    "place_faults": 0,
    "queries_observed": 0,
    "projected_ms": 0.0,
    "actual_ms": 0.0,
}


def _bump(key: str, v) -> None:
    with _STATS_LOCK:
        _STATS[key] += v


def global_stats() -> dict:
    with _STATS_LOCK:
        out = dict(_STATS)
    out["projected_ms"] = round(out["projected_ms"], 1)
    out["actual_ms"] = round(out["actual_ms"], 1)
    from spark_rapids_tpu.obs import registry
    err = registry.histogram(
        registry.HIST_PLACEMENT_COST_ERROR_PCT).snapshot()
    out["cost_error_p50_pct"] = err["p50"]
    out["cost_error_p99_pct"] = err["p99"]
    return out


def reset_stats() -> None:
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0.0 if k.endswith("_ms") else 0


def note_query(decisions: List[dict], wall_ms: Optional[float],
               query_id: Optional[int] = None) -> None:
    """Post-execution accounting for one query (api._execute): the sum
    of the chosen-engine projections against the measured wall — the
    cost-error number bench.py reports per suite — and the
    ``fragment_placed`` journal line per static decision.  Journaling
    the static decisions HERE rather than at plan time gives them the
    owning query id and runs after query_scope configured the journal
    from the conf."""
    if not decisions:
        return
    for d in decisions:
        _journal_decision(d, query_id=query_id)
    if not wall_ms:
        return
    projected = sum(d["cpu_ms"] if d["engine"] == "cpu" else d["tpu_ms"]
                    for d in decisions)
    with _STATS_LOCK:
        _STATS["queries_observed"] += 1
        _STATS["projected_ms"] += projected
        _STATS["actual_ms"] += wall_ms
    # per-query drift of the cost model, as a percentage of the
    # measured wall: the quantile surfaced in the `placement` obs
    # group (global_stats) so projection bugs are visible per query,
    # not only as a cumulative ratio
    from spark_rapids_tpu.obs import registry
    registry.record(registry.HIST_PLACEMENT_COST_ERROR_PCT,
                    abs(projected - wall_ms) / wall_ms * 100.0)


def _journal_decision(decision: dict,
                      query_id: Optional[int] = None) -> None:
    from spark_rapids_tpu.obs import journal
    if journal.enabled():
        journal.emit(journal.EVENT_FRAGMENT_PLACED, query=query_id, **{
            k: decision.get(k) for k in (
                "phase", "fragment", "ops", "classes", "engine",
                "tpu_ms", "cpu_ms", "deciding", "rows", "bytes_in",
                "bytes_out")})


# ---------------------------------------------------------------------------
# Static pass: maximal fragments on the meta tree
# ---------------------------------------------------------------------------

def _collect_fragments(meta) -> List[List]:
    """Maximal connected subtrees of can-run-on-TPU meta nodes, root
    first per fragment — exactly the regions ``convert`` would lower to
    the device engine, so one fragment = one placement decision."""
    frags: List[List] = []

    def start(m):
        if m.can_run_on_tpu:
            frag: List = []
            frags.append(frag)
            gather(m, frag)
        else:
            for c in m.children:
                start(c)

    def gather(m, frag):
        frag.append(m)
        for c in m.children:
            if c.can_run_on_tpu:
                gather(c, frag)
            else:
                start(c)

    start(meta)
    return frags


def _fragment_input(frag: List) -> Tuple[Optional[int], int]:
    """(estimated input bytes, estimated input rows) across the
    fragment's leaf inputs — source relations inside the fragment plus
    the outputs of CPU child subtrees feeding it.  ``(None, 0)`` when
    any input is unknowable: an unknown size must keep the fragment on
    the device (never demote blind)."""
    from spark_rapids_tpu.plan.planner import estimate_logical_size
    frag_set = set(id(m) for m in frag)
    bytes_in = 0
    rows = 0.0
    for m in frag:
        inputs = [m.node] if not m.children else \
            [c.node for c in m.children if id(c) not in frag_set]
        for n in inputs:
            est = estimate_logical_size(n)
            if est is None:
                return None, 0
            bytes_in += est
            try:
                width = cost.schema_row_width(n.output_schema())
            except Exception:
                width = 16
            rows += est / width
    return bytes_in, int(rows)


def _logical_class(node) -> str:
    """Operator class of one logical node, string-aware: a Project or
    Filter whose expression tree carries a string kernel scores under
    ``project_str``/``filter_str`` — the classes the calibration feed
    measures, so a measured TPU overtake on string work flips exactly
    these fragments (ISSUE 17 prong c)."""
    cls = cost.LOGICAL_CLASS.get(node.node_name, "project")
    exprs = getattr(node, "exprs", None)
    if exprs is None:
        pred = getattr(node, "pred", None)
        exprs = [pred] if pred is not None else []
    return cost.step_class(cls, exprs)


def _score_fragment(frag: List, conf, consts, calib) -> dict:
    from spark_rapids_tpu.plan import logical as lp
    root = frag[0]
    decision = {"phase": "static", "fragment": root.node.node_name,
                "ops": len(frag)}
    bytes_in, rows = _fragment_input(frag)
    if bytes_in is None:
        decision.update({"engine": "tpu", "deciding": "unknown_size",
                         "tpu_ms": 0.0, "cpu_ms": 0.0, "rows": 0,
                         "bytes_in": 0, "bytes_out": 0})
        return decision
    from spark_rapids_tpu.plan.planner import estimate_logical_size
    bytes_out = estimate_logical_size(root.node)
    if bytes_out is None:
        has_agg = any(isinstance(m.node, lp.Aggregate) for m in frag)
        # aggregates collapse output; everything else passes through as
        # an upper bound (docs/placement.md, size heuristics)
        bytes_out = int(bytes_in * 0.05) if has_agg else bytes_in
    classes = [_logical_class(m.node) for m in frag]
    decision["classes"] = classes
    decision.update(cost.score_ops(
        classes, rows, bytes_in, bytes_out, conf, consts, calib,
        compile_ms=cost.expected_compile_ms(),
        ooc_budget=conf.ici_max_stage_bytes
        if conf.ooc_enabled else 0))
    return decision


def _demote(frag: List, reason: str) -> None:
    for m in frag:
        m.cost_demoted = True
        m.demote_reason = reason


def _clear_demotions(meta) -> None:
    meta.cost_demoted = False
    meta.demote_reason = None
    for c in meta.children:
        _clear_demotions(c)


def place_fragments(meta, conf) -> List[dict]:
    """The static placement pass (mode != ``tpu``): score every maximal
    device-assignable fragment and mark losing ones ``cost_demoted`` so
    conversion lowers them through the shared ``_to_cpu`` seam.
    Returns the per-fragment decision records (stamped onto the
    PlanResult, journaled, and rendered by ``explain(analyze=True)``).
    Degrade contract: an injected ``plan.place`` fault or ANY failure
    clears every partial demotion and returns no decisions — the
    static all-TPU plan runs unchanged (``place_faults`` counted)."""
    from spark_rapids_tpu import faults
    # the pass runs at PLAN time, before query_scope's conf-driven
    # injector install — mirror its contract (install only when the
    # conf explicitly carries fault keys; never clear a
    # manually-configured injector otherwise) so a conf-injected
    # plan.place fault fires on the FIRST query too
    if any(k.startswith(faults.FAULTS_PREFIX)
           for k in conf.to_dict()):
        faults.configure_from_conf(conf)
    mode = conf.placement_mode
    decisions: List[dict] = []
    try:
        faults.maybe_fail(FAULT_SITE_PLACE,
                          "injected placement-pass failure")
        frags = _collect_fragments(meta)
        if mode == "cpu":
            for frag in frags:
                _demote(frag, "placement.mode=cpu")
                decisions.append({
                    "phase": "static",
                    "fragment": frag[0].node.node_name,
                    "ops": len(frag), "engine": "cpu",
                    "tpu_ms": 0.0, "cpu_ms": 0.0, "deciding": "mode",
                    "rows": 0, "bytes_in": 0, "bytes_out": 0})
        else:
            # aggregate-aware: a session whose fragments ingest
            # through the sharded scan path moves bytes over N
            # concurrent per-chip streams (docs/sharded_scan.md) —
            # score with the aggregate link rates, not one chip's
            consts = cost.effective_link_constants(conf)
            calib = cost.calibration()
            for frag in frags:
                d = _score_fragment(frag, conf, consts, calib)
                if d["engine"] == "cpu":
                    _demote(frag, f"cost model: tpu {d['tpu_ms']}ms vs "
                                  f"cpu {d['cpu_ms']}ms "
                                  f"({d['deciding']})")
                decisions.append(d)
    except Exception as e:
        _clear_demotions(meta)
        _bump("place_faults", 1)
        log.warning("placement pass failed (%s: %s); running the "
                    "static all-TPU plan", type(e).__name__, e)
        return []
    with _STATS_LOCK:
        _STATS["fragments_scored"] += len(decisions)
        _STATS["fragments_cpu"] += sum(
            1 for d in decisions if d["engine"] == "cpu")
        _STATS["fragments_tpu"] += sum(
            1 for d in decisions if d["engine"] == "tpu")
    return decisions


# ---------------------------------------------------------------------------
# AQE runtime re-score: demote a remainder the static estimate got wrong
# ---------------------------------------------------------------------------

class _Unconvertible(Exception):
    """The remainder contains an operator the physical CPU converter
    cannot move (a join, a pending exchange, a window): skip the
    demotion, keep the static plan."""


def _convertible_types():
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.basic import TpuFilterExec, TpuLocalLimitExec, \
        TpuProjectExec
    from spark_rapids_tpu.exec.coalesce import TpuCoalesceBatchesExec
    from spark_rapids_tpu.exec.sort import TpuSortExec
    from spark_rapids_tpu.exec.stage import TpuStageExec
    return (TpuProjectExec, TpuFilterExec, TpuStageExec,
            TpuCoalesceBatchesExec, TpuSortExec, TpuHashAggregateExec,
            TpuLocalLimitExec)


def _remainder_classes(node, stage) -> List[str]:
    """Operator-class list of the unary chain from the adaptive
    wrapper's child down to ``stage``; raises ``_Unconvertible`` on
    anything ``_demote_physical`` cannot carry to the CPU engine —
    which also guarantees no unmaterialized exchange survives inside a
    demoted remainder."""
    from spark_rapids_tpu.exec.coalesce import TpuCoalesceBatchesExec
    from spark_rapids_tpu.exec.stage import TpuStageExec
    out: List[str] = []
    while node is not stage:
        if not isinstance(node, _convertible_types()) or not node.children:
            raise _Unconvertible(node.node_name)
        if isinstance(node, TpuStageExec):
            out.extend(cost.step_class(kind, exprs)
                       for kind, exprs in node.steps)
        elif not isinstance(node, TpuCoalesceBatchesExec):
            cls = cost.op_class(node.node_name)
            exprs = getattr(node, "exprs", None)
            if exprs is None:
                pred = getattr(node, "pred", None)
                exprs = [pred] if pred is not None else []
            out.append(cost.step_class(cls, exprs))
            # the chain an aggregate folded in (plan/fusion.py) is still
            # work the remainder does: score it with its own classes
            out.extend(cost.step_class(kind, exprs) for kind, exprs
                       in reversed(getattr(node, "pre_steps", ())))
        node = node.children[0]
    return out


def _demote_physical(node, stage):
    """Convert the remainder chain above the materialized ``stage`` to
    the CPU engine: each supported device operator becomes its CPU
    analog over the SAME bound expressions (both engines bind through
    ``bind_expression``, so the trees are engine-neutral), fused stages
    (and the steps an aggregate folded in) expand back to project/filter
    chains, coalesce nodes drop (host
    batching needs no capacity contract), and the stage itself crosses
    through a ``DeviceToHostExec`` — its buffered device batches are
    pulled once, like any egress."""
    from spark_rapids_tpu.cpu import engine as cb
    from spark_rapids_tpu.cpu.relational import (
        CpuHashAggregateExec, CpuSortExec,
    )
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.basic import (
        DeviceToHostExec, TpuFilterExec, TpuLocalLimitExec,
        TpuProjectExec,
    )
    from spark_rapids_tpu.exec.coalesce import TpuCoalesceBatchesExec
    from spark_rapids_tpu.exec.sort import TpuSortExec
    from spark_rapids_tpu.exec.stage import TpuStageExec
    if node is stage:
        return DeviceToHostExec(stage)
    child = _demote_physical(node.children[0], stage)

    def expand(steps, cur):
        for kind, exprs in steps:
            cur = cb.CpuProjectExec(list(exprs), cur) if kind == "project" \
                else cb.CpuFilterExec(exprs[0], cur)
        return cur

    if isinstance(node, TpuCoalesceBatchesExec):
        return child
    if isinstance(node, TpuStageExec):
        return expand(node.steps, child)
    if isinstance(node, TpuProjectExec):
        return cb.CpuProjectExec(node.exprs, child)
    if isinstance(node, TpuFilterExec):
        return cb.CpuFilterExec(node.pred, child)
    if isinstance(node, TpuSortExec):
        return CpuSortExec(node.orders, child)
    if isinstance(node, TpuHashAggregateExec):
        # the filter / project chain the aggregate folded in
        # (plan/fusion.py) expands back below its CPU analog
        return CpuHashAggregateExec(node.groupings, node.aggregates,
                                    expand(node.pre_steps, child))
    if isinstance(node, TpuLocalLimitExec):
        return cb.CpuLocalLimitExec(node.limit, child)
    raise _Unconvertible(node.node_name)


def aqe_rescore(root, stage, conf, metrics) -> Optional[dict]:
    """Runtime placement demotion (docs/placement.md, "AQE demotion"):
    re-score the remainder above the just-materialized ``stage`` with
    its MEASURED bytes — the same scoring formula as the static pass,
    answering "would the static decision have differed had it known
    the real bytes" — and demote it to the CPU engine when the answer
    is yes.  Returns the decision record on a demotion, None when the
    device keeps the remainder or the demotion is inapplicable.  Same
    degrade contract as every replan rule: an injected ``plan.place``
    fault or any failure leaves the static plan running."""
    if conf.placement_mode != "cost" or not conf.placement_aqe_enabled:
        return None
    from spark_rapids_tpu import faults
    from spark_rapids_tpu.exec.basic import HostToDeviceExec
    try:
        faults.maybe_fail(FAULT_SITE_PLACE,
                          "injected placement re-score failure")
        remainder = root.children[0]
        classes = _remainder_classes(remainder, stage)
        if not classes:
            # nothing but the stage (and batching nodes) above: a
            # demotion would insert a pure D2H+H2D round trip with
            # zero operator work moved — never a win
            return None
        measured = stage.stats.total_bytes
        rows = sum(stage.stats.partition_rows)
        has_agg = "hashaggregate" in classes
        bytes_out = int(measured * 0.05) if has_agg else measured
        d = cost.score_ops(classes, rows, measured, bytes_out, conf,
                           cost.effective_link_constants(conf),
                           cost.calibration(),
                           compile_ms=cost.expected_compile_ms(),
                           ooc_budget=conf.ici_max_stage_bytes
                           if conf.ooc_enabled else 0)
        d.update({"phase": "aqe", "fragment": remainder.node_name,
                  "ops": len(classes)})
        if d["engine"] != "cpu":
            return None
        root.children[0] = HostToDeviceExec(
            _demote_physical(remainder, stage))
        from spark_rapids_tpu.utils.metrics import (
            METRIC_PLACEMENT_DEMOTIONS,
        )
        metrics[METRIC_PLACEMENT_DEMOTIONS].add(1)
        _bump("aqe_demotions", 1)
        _journal_decision(d)
        return d
    except _Unconvertible as e:
        log.debug("placement re-score skipped (remainder not "
                  "convertible at %s)", e)
        return None
    except Exception as e:
        _bump("place_faults", 1)
        log.warning("placement re-score failed (%s: %s); keeping the "
                    "static plan", type(e).__name__, e)
        return None
