"""Query profiles: the executed plan tree with its measured metrics.

Reference: the Spark UI SQL tab the plugin populates — the physical
plan tree annotated per operator with the ``GpuMetricNames`` metrics
(GpuExec.scala:25-67) — which is how "where did this query's 94 ms go"
is answered without re-running under a profiler.

``QueryProfile.from_plan`` walks the EXECUTED physical tree (the live
objects, so AQE's evolved children and ICI-lowered fragments appear as
they actually ran) and snapshots every operator's metrics once.  The
snapshot forces any pending device-resident counts through ONE batched
``transfer.device_pull`` per metric — counted in ``d2hPulls`` and
covered by the ``transfer.d2h`` fault site like every other egress.

Three renderings share the walk:

* ``render()`` — the ``df.explain(analyze=True)`` text tree: one line
  per operator with rows / batches / wall time / self time (own wall
  minus children's, clamped at zero) and every other non-zero metric;
  under ``spark.rapids.sql.trace.enabled`` also ``device=`` /
  ``dispatches=`` (the programs the node launched and the device time
  the dispatch ledger measured for them) and a ``Programs:`` table;
* ``to_dict()`` — the same tree as plain dicts for programmatic
  consumers (``session.last_query_profile().to_dict()``);
* ``legacy_lines()`` — byte-identical to the pre-obs flat
  ``session.last_query_metrics()`` string, which is now implemented on
  top of this walk instead of its own.
"""

from __future__ import annotations

from typing import Dict, List, Optional

_NO_NODE = "(no node)"


class OperatorProfile:
    """One node of the executed plan: identity + metric snapshot."""

    __slots__ = ("name", "describe", "metrics", "children")

    def __init__(self, name: str, describe: str,
                 metrics: Dict[str, int],
                 children: List["OperatorProfile"]):
        self.name = name
        self.describe = describe
        self.metrics = metrics
        self.children = children

    @property
    def rows(self) -> int:
        return self.metrics.get("numOutputRows", 0)

    @property
    def batches(self) -> int:
        return self.metrics.get("numOutputBatches", 0)

    @property
    def time_ms(self) -> float:
        return self.metrics.get("totalTime", 0) / 1e6

    @property
    def self_time_ms(self) -> float:
        child_ns = sum(c.metrics.get("totalTime", 0)
                       for c in self.children)
        return max(0.0, (self.metrics.get("totalTime", 0)
                         - child_ns) / 1e6)


class QueryProfile:
    """The executed plan tree + per-operator metric snapshots of one
    query (docs/observability.md, "Query profiles")."""

    def __init__(self, root: OperatorProfile,
                 query_id: Optional[int] = None,
                 wall_ms: Optional[float] = None,
                 placement: Optional[List[dict]] = None,
                 programs: Optional[List[dict]] = None):
        self.root = root
        self.query_id = query_id
        self.wall_ms = wall_ms
        # per-fragment cost-placement decisions (plan/placement.py):
        # empty unless spark.rapids.sql.placement.mode != tpu, so the
        # default analyze rendering is unchanged (docs/placement.md)
        self.placement = list(placement or [])
        # the programs the query launched (compile/service.py
        # ``ledger_since``): empty unless the query ran under
        # spark.rapids.sql.trace.enabled, so the default renderings are
        # unchanged
        self.programs = list(programs or [])

    # -- construction -------------------------------------------------------

    @classmethod
    def from_plan(cls, physical, query_id: Optional[int] = None,
                  wall_ms: Optional[float] = None,
                  placement: Optional[List[dict]] = None,
                  programs: Optional[List[dict]] = None
                  ) -> "QueryProfile":
        def walk(node) -> OperatorProfile:
            children = [walk(c) for c in node.children]
            return OperatorProfile(node.node_name, node.describe(),
                                   node.metrics.snapshot(), children)
        return cls(walk(physical), query_id=query_id, wall_ms=wall_ms,
                   placement=placement, programs=programs)

    # -- renderings ---------------------------------------------------------

    _CORE = ("numOutputRows", "numOutputBatches", "totalTime",
             "deviceTime", "deviceDispatches")

    @staticmethod
    def _fmt(name: str, v) -> str:
        """One metric as ``name=value`` — the single source of truth
        for the ``*time``-suffix ns→ms convention, shared by the
        analyze tree and the byte-identity legacy rendering so the two
        can never drift."""
        if name.lower().endswith("time"):
            return f"{name}={v / 1e6:.1f}ms"
        return f"{name}={v}"

    def render(self) -> str:
        """The ``explain(analyze=True)`` text tree."""
        head = "== Executed plan"
        if self.query_id is not None:
            head += f" (query {self.query_id}"
            if self.wall_ms is not None:
                head += f", {self.wall_ms:.1f} ms"
            head += ")"
        head += " =="
        lines = [head]

        def walk(node: OperatorProfile, depth: int) -> None:
            parts = [f"rows={node.rows}", f"batches={node.batches}"]
            if node.metrics.get("totalTime", 0):
                parts.append(f"time={node.time_ms:.1f}ms")
                parts.append(f"self={node.self_time_ms:.1f}ms")
            if node.metrics.get("deviceDispatches", 0):
                # time= is host wall time around calls that return
                # before the device has run; device= is what the
                # programs this node launched took on the device
                parts.append(
                    f"device={node.metrics.get('deviceTime', 0) / 1e6:.1f}ms")
                parts.append(
                    f"dispatches={node.metrics['deviceDispatches']}")
            for name, v in sorted(node.metrics.items()):
                if name in self._CORE or not v:
                    continue
                parts.append(self._fmt(name, v))
            lines.append("  " * depth + node.describe + ": "
                         + " ".join(parts))
            for c in node.children:
                walk(c, depth + 1)

        walk(self.root, 0)
        if self.programs:
            lines.append("Programs:")
        for p in self.programs:
            starved = sum(p["starved_ms"].values())
            lines.append(
                f"  {p['program']}: dispatches={p['dispatches']} "
                f"device={p['device_ms']:.1f}ms"
                + (f" {_NO_NODE}={p['no_node_ms']:.1f}ms"
                   if p["no_node_ms"] else "")
                + (f" starved={starved:.1f}ms" if starved else "")
                + (f" untimed={p['untimed']}" if p["untimed"] else ""))
        for d in self.placement:
            lines.append(
                f"Placement: {d.get('fragment')} -> {d.get('engine')} "
                f"[{d.get('phase')}] tpu={d.get('tpu_ms')}ms "
                f"cpu={d.get('cpu_ms')}ms deciding={d.get('deciding')}")
        return "\n".join(lines)

    def to_dict(self) -> dict:
        def walk(node: OperatorProfile) -> dict:
            return {"name": node.name, "describe": node.describe,
                    "rows": node.rows, "batches": node.batches,
                    "time_ms": round(node.time_ms, 3),
                    "self_time_ms": round(node.self_time_ms, 3),
                    "metrics": {n: v for n, v in node.metrics.items()
                                if v},
                    "children": [walk(c) for c in node.children]}
        out = {"query_id": self.query_id, "wall_ms": self.wall_ms,
               "plan": walk(self.root)}
        if self.placement:
            # only under a non-default placement mode: the default
            # profile dict schema stays byte-identical
            out["placement"] = self.placement
        if self.programs:
            # only for a query run under the trace switch
            out["programs"] = self.programs
        return out

    def legacy_lines(self) -> List[str]:
        """The pre-obs ``last_query_metrics()`` rendering, byte for
        byte: one line per operator, non-zero metrics sorted by name,
        ``*time``-suffixed names printed as ms."""
        lines: List[str] = []

        def walk(node: OperatorProfile, depth: int) -> None:
            parts = [self._fmt(name, v)
                     for name, v in sorted(node.metrics.items()) if v]
            lines.append("  " * depth + node.describe
                         + (": " + ", ".join(parts) if parts else ""))
            for c in node.children:
                walk(c, depth + 1)

        walk(self.root, 0)
        return lines
