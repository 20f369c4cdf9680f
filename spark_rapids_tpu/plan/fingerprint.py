"""Plan + input-snapshot fingerprinting (docs/serving.md).

The session server's result cache and prepared statements both need a
stable identity for "the same query over the same data":

* ``plan_fingerprint`` — a structural digest of a logical plan in which
  prepared-statement parameters (``ParamLiteral``) contribute only
  their slot and dtype, never their value: two bindings of one template
  share a fingerprint (their values ride separately in the cache key),
  while two queries differing in an ordinary inline literal do NOT —
  an inline constant is part of the query's identity.  This is the
  plan-level mirror of kernel-level literal hoisting (exprs/base.py),
  which keys hoisted values out of the compiled-kernel cache the same
  way.

* ``snapshot_fingerprint`` — a digest of the *current content
  identity* of every leaf input: per scanned file (path, mtime_ns,
  size), so a rewritten/overwritten input changes the key and a stale
  cached result can never be served; in-memory relations key on object
  identity and are pinned by the cache entry so a recycled ``id()``
  can never alias a dead table.  Plans over inputs whose snapshot
  cannot be established (missing files, unknown leaf types) return
  ``None`` — the cache skips them.

* ``bind_params`` — rebuild a prepared template's logical plan with new
  parameter values (fresh tree per execution: templates are shared by
  concurrent clients and must never be mutated in place).
"""

from __future__ import annotations

import copy
import hashlib
from typing import Callable, List, Optional, Sequence, Tuple

from spark_rapids_tpu.columnar.dtypes import Schema
from spark_rapids_tpu.exprs.base import Expression, ParamLiteral
from spark_rapids_tpu.plan import logical as lp

# node attributes that are not part of a plan's structural identity
_SKIP_ATTRS = frozenset({"children", "_schema_cache"})


# ---------------------------------------------------------------------------
# generic expression mapping over logical-plan nodes
# ---------------------------------------------------------------------------

def _map_value(value, fn: Callable[[Expression], Expression]):
    """Map ``fn`` over every Expression inside one node attribute —
    covers the shapes the lp nodes use: bare expressions, lists of
    expressions, (expr, asc, nulls_first) order triples, (name, expr)
    window pairs, and nested projection lists."""
    if isinstance(value, Expression):
        return fn(value)
    if isinstance(value, list):
        return [_map_value(v, fn) for v in value]
    if isinstance(value, tuple):
        return tuple(_map_value(v, fn) for v in value)
    return value


def map_plan_exprs(plan: lp.LogicalPlan,
                   fn: Callable[[Expression], Expression]
                   ) -> lp.LogicalPlan:
    """Rebuild a logical plan with ``fn`` applied to every expression
    tree it carries.  Nodes are shallow-copied (schema caches dropped)
    and children rebuilt recursively — the input plan is never mutated,
    so a prepared template shared by concurrent clients stays intact."""
    node = copy.copy(plan)
    node.__dict__.pop("_schema_cache", None)
    for name, value in list(vars(node).items()):
        if name in _SKIP_ATTRS:
            continue
        node.__dict__[name] = _map_value(value, fn)
    node.children = [map_plan_exprs(c, fn) for c in plan.children]
    return node


# ---------------------------------------------------------------------------
# parameter re-binding (prepared statements)
# ---------------------------------------------------------------------------

def _rewrite_params(e: Expression, values: Sequence) -> Expression:
    if isinstance(e, ParamLiteral):
        return ParamLiteral(e.slot, values[e.slot], e._dtype)
    if not e.children:
        return e
    new = [_rewrite_params(c, values) for c in e.children]
    if all(a is b for a, b in zip(new, e.children)):
        return e
    return e.with_children(new)


def bind_params(plan: lp.LogicalPlan, values: Sequence) -> lp.LogicalPlan:
    """A fresh copy of a prepared template with each ``ParamLiteral``
    slot carrying ``values[slot]``.  Callers guarantee the values'
    inferred dtypes match the template's (the per-type-signature plan
    cache in server/prepared.py keys on exactly that), so schemas and
    kernel signatures are unchanged — only the hoisted constants move."""
    return map_plan_exprs(
        plan, lambda e: _rewrite_params(e, values))


# ---------------------------------------------------------------------------
# plan fingerprint
# ---------------------------------------------------------------------------

class _MaskedParam(Expression):
    """Fingerprint stand-in for a ParamLiteral: slot + dtype, no value."""

    def __init__(self, slot: int, dtype):
        self.slot = slot
        self._dtype = dtype
        self.children = ()

    @property
    def dtype(self):
        return self._dtype

    def key(self) -> str:
        return f"param[{self.slot}:{self._dtype.name}]"


def mask_params(e: Expression) -> Expression:
    """``e`` with every ParamLiteral replaced by its slot and dtype: what
    all bindings of a prepared statement have in common (the plan
    fingerprint here, and the scan cache's key in io/parquet.py)."""
    if isinstance(e, ParamLiteral):
        return _MaskedParam(e.slot, e._dtype)
    if not e.children:
        return e
    new = [mask_params(c) for c in e.children]
    if all(a is b for a, b in zip(new, e.children)):
        return e
    return e.with_children(new)


def _value_fp(v) -> str:
    if isinstance(v, Expression):
        return mask_params(v).key()
    if isinstance(v, (list, tuple)):
        return "[" + ",".join(_value_fp(x) for x in v) + "]"
    if isinstance(v, Schema):
        return "schema(" + ",".join(
            f"{f.name}:{f.dtype.name}:{int(f.nullable)}"
            for f in v.fields) + ")"
    # LocalRelation's pa.Table: structural shape only — content
    # identity belongs to the snapshot fingerprint
    if hasattr(v, "num_rows") and hasattr(v, "schema"):
        return f"table({v.num_rows}x{getattr(v, 'num_columns', '?')})"
    return repr(v)


def _node_fp(node: lp.LogicalPlan) -> str:
    own = ";".join(
        f"{k}={_value_fp(v)}"
        for k, v in sorted(vars(node).items())
        if k not in _SKIP_ATTRS)
    kids = ",".join(_node_fp(c) for c in node.children)
    return f"{node.node_name}({own})[{kids}]"


def plan_fingerprint(plan: lp.LogicalPlan) -> str:
    """Structural digest of a logical plan with parameter values masked
    (inline literal values stay in — they ARE the query)."""
    return hashlib.sha256(_node_fp(plan).encode()).hexdigest()


def bound_param_values(plan: lp.LogicalPlan) -> tuple:
    """The ``(slot, value)`` pairs of every ParamLiteral bound into a
    plan, slot-ordered.  The result-cache key carries these alongside
    the masked plan fingerprint, so a DataFrame built from
    ``stmt.bind(x)`` and submitted directly can never collide with a
    different binding of the same template."""
    found = {}

    def scan(e: Expression) -> None:
        if isinstance(e, ParamLiteral):
            found[e.slot] = e.value
        for c in e.children:
            scan(c)

    def walk_value(v) -> None:
        if isinstance(v, Expression):
            scan(v)
        elif isinstance(v, (list, tuple)):
            for x in v:
                walk_value(x)

    def walk(node: lp.LogicalPlan) -> None:
        for k, v in vars(node).items():
            if k not in _SKIP_ATTRS:
                walk_value(v)
        for c in node.children:
            walk(c)

    walk(plan)
    return tuple(sorted(found.items()))


# conf keys that can never change a query's ROWS: server-layer sizing,
# supervision deadlines (a per-tenant timeout overlay must not split
# the cache across tenants), and observation switches
_RESULT_NEUTRAL_PREFIXES = (
    "spark.rapids.server.",
    "spark.rapids.sql.obs.",
    "spark.rapids.sql.trace.",
    # the compilation service changes WHERE kernels come from (store vs
    # fresh compile) and what capacities pad to, never a query's rows
    "spark.rapids.sql.compile.",
    # fleet keys size the router/replica topology, never a query's
    # rows — and they must not split the fleet-wide disk result tier
    # across replicas whose conf differs only in fleet keys
    "spark.rapids.fleet.",
    # stream keys pace WHEN standing queries refresh and whether cache
    # entries maintain vs invalidate — the maintained result is
    # asserted byte-identical to a recompute, so the keys must not
    # split the cache between streaming and non-streaming submitters
    "spark.rapids.stream.",
)
_RESULT_NEUTRAL_KEYS = frozenset({
    "spark.rapids.sql.queryTimeoutMs",
    "spark.rapids.sql.cancel.checkIntervalMs",
    "spark.rapids.sql.watchdog.hangTimeoutMs",
})


def conf_fingerprint(conf) -> str:
    """Digest of the conf settings that could change a query's result.
    Result-neutral keys (server sizing, deadlines, observation) are
    excluded; everything else (engine toggles, float policy, fault
    schedules) conservatively keys the cache."""
    items = sorted(
        (k, str(v)) for k, v in conf.to_dict().items()
        if k not in _RESULT_NEUTRAL_KEYS
        and not k.startswith(_RESULT_NEUTRAL_PREFIXES))
    return hashlib.sha256(repr(items).encode()).hexdigest()


# ---------------------------------------------------------------------------
# input snapshot fingerprint
# ---------------------------------------------------------------------------

def _file_tokens(paths, expand, tail=None
                 ) -> Optional[List[Tuple[str, str]]]:
    """One ``(path, "path:mtime_ns:size[:tail]")`` pair per expanded
    file — the token carries the full spelling (digested as-is), the
    explicit path component lets the result-cache maintenance diff
    split per file without parsing (paths may contain ``:``).  The
    optional ``tail`` callable appends a cheap content marker (parquet:
    the 8 footer-tail bytes) so an append or rewrite landing within
    filesystem mtime granularity at an unchanged byte size still
    changes the token — a same-stat rewrite can never serve a stale
    cache entry."""
    import os
    try:
        files = expand(paths)
    except OSError:
        return None
    if not files:
        return None
    out = []
    for f in files:
        try:
            st = os.stat(f)
            mark = f":{tail(f)}" if tail is not None else ""
        except OSError:
            return None
        out.append((f, f"{f}:{st.st_mtime_ns}:{st.st_size}{mark}"))
    return out


def leaf_file_tokens(node: lp.LogicalPlan
                     ) -> Optional[List[Tuple[str, str]]]:
    """The ``(path, token)`` snapshot pairs of one FILE-BACKED leaf
    relation (None for any other node, or when the leaf cannot be
    snapshotted).  The single token grammar shared by
    ``snapshot_fingerprint``, the result-cache maintenance diff, and
    the stream tailing sources — one spelling, so the three can never
    disagree about what counts as \"the same file\"."""
    if isinstance(node, lp.ParquetRelation):
        from spark_rapids_tpu.io.parquet import expand_paths, tail_marker
        return _file_tokens(node.paths, expand_paths, tail=tail_marker)
    if isinstance(node, lp.OrcRelation):
        from spark_rapids_tpu.io.orc import expand_orc_paths
        return _file_tokens(node.paths, expand_orc_paths)
    if isinstance(node, lp.CsvRelation):
        from spark_rapids_tpu.io.csv import expand_csv_paths
        return _file_tokens(node.paths, expand_csv_paths)
    return None


def snapshot_detail(plan: lp.LogicalPlan
                    ) -> Tuple[Optional[str], tuple, tuple]:
    """``(digest, pins, leaf_tokens)`` — ``snapshot_fingerprint`` plus
    the per-file-leaf ``(path, token)`` pair lists in walk order,
    ``((leaf, ((path, token), ...)), ...)``, which the result-cache
    maintenance path diffs to decide append-only vs invalidate."""
    parts: List[str] = []
    pins: List[object] = []
    leaves: List[tuple] = []

    def walk(node: lp.LogicalPlan) -> bool:
        pairs = leaf_file_tokens(node)
        if pairs is not None:
            leaves.append((node, tuple(pairs)))
            toks = [tok for _, tok in pairs]
        elif isinstance(node, (lp.ParquetRelation, lp.OrcRelation,
                               lp.CsvRelation)):
            return False  # file leaf that failed to snapshot
        elif isinstance(node, lp.LocalRelation):
            t = node.table
            pins.append(t)
            toks = [f"local:{id(t)}:{t.num_rows}:{t.nbytes}"]
        elif isinstance(node, lp.Range):
            toks = []
        elif node.children:
            toks = []
        else:
            return False  # unknown leaf: not snapshottable
        parts.extend(toks)
        return all(walk(c) for c in node.children)

    if not walk(plan):
        return None, (), ()
    digest = hashlib.sha256("\n".join(parts).encode()).hexdigest()
    return digest, tuple(pins), tuple(leaves)


def snapshot_fingerprint(plan: lp.LogicalPlan
                         ) -> Tuple[Optional[str], tuple]:
    """``(digest, pins)`` for the current content of every leaf input,
    or ``(None, ())`` when any leaf cannot be snapshotted (the result
    cache then skips the query).  ``pins`` are objects the cache entry
    must hold alive — in-memory tables keyed by ``id()`` stay valid
    exactly as long as the entry pins them."""
    digest, pins, _leaves = snapshot_detail(plan)
    return digest, pins
