"""Static robustness lint over the failure-critical packages.

The shuffle and memory planes are the two places where "it mostly
works" is indistinguishable from "it deadlocks under the first real
fault", so two anti-patterns are banned outright and enforced by the
test suite itself:

1. **Silent exception swallows** (``except Exception:`` / bare
   ``except:`` whose body is only ``pass``): a swallowed transport or
   spill error is precisely the failure the fault-injection sites exist
   to surface.  Errors must be logged, re-raised, or mapped to a typed
   error (``BlockCorruptError``, ``FetchFailedError``).

2. **Unbounded ``recv`` loops**: any file doing socket ``recv`` must
   also configure socket timeouts (``settimeout`` on the Python path;
   ``SO_RCVTIMEO`` keeps the native path honest) — otherwise one dead
   peer parks a reducer thread forever, the exact hang this PR's
   timeout confs eliminate.

3. **Unbounded prefetch queues** (io/ only): every ``queue.Queue``
   constructed under the scan/prefetch layer must carry a positive
   ``maxsize`` — an unbounded queue lets a fast background decode
   thread buffer a whole table on host, defeating the staging-limiter
   admission the prefetch design depends on (io/prefetch.py).

4. **Raw ``jax.device_get`` calls** (exec/, shuffle/, io/, parallel/):
   every device->host pull in the egress-facing packages must route through
   ``columnar/transfer.py``'s helpers (``device_pull`` /
   ``pack_and_pull`` / ``pack_partitions_and_pull`` /
   ``device_batch_to_host``) so staging admission, the ``d2hPulls``/
   ``d2hBytes`` metrics, and the ``transfer.d2h`` fault site can never
   be bypassed by a new call site (docs/d2h_egress.md).

5. **Unbounded module-level kernel caches** (repo-wide over
   ``spark_rapids_tpu/``): a module-level ``*CACHE*`` name assigned a
   raw ``{}`` / ``dict()`` / ``OrderedDict()`` is a compiled-kernel
   leak waiting to happen — expression cache keys can embed literal
   values, so distinct-constant query streams grow such dicts forever
   (the ``_FILTER_CACHE`` bug class).  Caches must be
   ``utils/kernel_cache.KernelCache`` instances (LRU-bounded by
   construction, hit/miss/evict counted) or another structure that is
   bounded by construction.  Under ``parallel/`` the same holds for an
   OBJECT's memo (``self._step_cache = {}``): a mesh program kept on
   its ``Distributed*`` object dies with the plan that built the
   object, and the next plan traces, lowers and loads it again (13 s a
   hot Q3 on four chips; docs/ici_shuffle.md, "Where a mesh program
   lives").  Programs go through ``parallel/mesh.py: mesh_program``.

6. **A timed region held across a ``yield``** (repo-wide): a
   ``with`` over a section, a span or a metric timer
   (``tracing.section`` / ``trace_range`` / ``annotation``,
   ``MetricSet.timed``, ``mesh.phase``) whose body yields.  The
   generator's consumer then runs inside the region, on whatever
   thread resumes it, and the thread's section and span stacks charge
   that work to the wrong name (docs/observability.md, "Sections").
   Time each ``next()`` instead (``MetricSet.timed_iter``).  A
   ``@contextmanager`` function is exempt: its ``yield`` is the
   caller's ``with`` body.

Run as part of the normal suite (pytest.ini collects ``lint_*.py``).
"""

from __future__ import annotations

import ast
import functools
import os
from typing import List

import pytest


@functools.lru_cache(maxsize=None)
def _parsed(path: str) -> ast.AST:
    """Parse each linted source once per session: nine parametrized
    rules over ~100 files would otherwise re-read and re-parse every
    file per rule, a measurable chunk of tier-1 wall clock."""
    with open(path, encoding="utf-8") as f:
        return ast.parse(f.read(), filename=path)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHECKED_DIRS = (
    os.path.join(_REPO, "spark_rapids_tpu", "shuffle"),
    os.path.join(_REPO, "spark_rapids_tpu", "memory"),
    # the background-prefetch scan layer: a swallowed decode error in a
    # producer thread is a silent wrong-answer/hang factory
    os.path.join(_REPO, "spark_rapids_tpu", "io"),
    # the planner + adaptive replanning layer: a swallowed replan error
    # must reach the logged fallback-to-static path, never vanish
    os.path.join(_REPO, "spark_rapids_tpu", "plan"),
    # the session server: a swallowed admission/dispatch error is a
    # ticket whose caller waits forever — every failure must surface
    # typed on the ticket (docs/serving.md)
    os.path.join(_REPO, "spark_rapids_tpu", "server"),
    # the serving fleet: router/replica process supervision — a
    # swallowed pump or heartbeat error is a replica the watchdog can
    # never declare and a ticket that never resolves
    os.path.join(_REPO, "spark_rapids_tpu", "fleet"),
    # continuous queries: a swallowed poll or refresh error is a
    # standing query silently serving stale rows forever — every
    # failure must be counted and flagged for the repair tick
    # (docs/streaming.md)
    os.path.join(_REPO, "spark_rapids_tpu", "stream"),
)
_IO_DIR = os.path.join(_REPO, "spark_rapids_tpu", "io")
_SERVER_DIR = os.path.join(_REPO, "spark_rapids_tpu", "server")
_FLEET_DIR = os.path.join(_REPO, "spark_rapids_tpu", "fleet")
_STREAM_DIR = os.path.join(_REPO, "spark_rapids_tpu", "stream")


def _python_sources() -> List[str]:
    out = []
    for d in _CHECKED_DIRS:
        for root, _dirs, files in os.walk(d):
            out.extend(os.path.join(root, f) for f in files
                       if f.endswith(".py"))
    assert out, f"robustness lint found no sources under {_CHECKED_DIRS}"
    return sorted(out)


def _is_silent_swallow(handler: ast.ExceptHandler) -> bool:
    """except Exception/BaseException/bare whose body does nothing."""
    if handler.type is not None:
        if not (isinstance(handler.type, ast.Name)
                and handler.type.id in ("Exception", "BaseException")):
            return False
    body = [n for n in handler.body
            if not (isinstance(n, ast.Expr)
                    and isinstance(n.value, ast.Constant)
                    and isinstance(n.value.value, str))]  # docstrings
    return all(isinstance(n, ast.Pass) for n in body)


@pytest.mark.parametrize("path", _python_sources(),
                         ids=lambda p: os.path.relpath(p, _REPO))
def test_no_silent_exception_swallows(path):
    tree = _parsed(path)
    offenders = [
        f"{os.path.relpath(path, _REPO)}:{node.lineno}"
        for node in ast.walk(tree)
        if isinstance(node, ast.ExceptHandler) and _is_silent_swallow(node)
    ]
    assert not offenders, (
        "silent `except Exception: pass` swallows in failure-critical "
        f"code (log, re-raise, or map to a typed error): {offenders}")


@pytest.mark.parametrize("path", _python_sources(),
                         ids=lambda p: os.path.relpath(p, _REPO))
def test_recv_loops_are_bounded(path):
    with open(path, encoding="utf-8") as f:
        src = f.read()
    if ".recv(" not in src:
        return
    assert "settimeout" in src, (
        f"{os.path.relpath(path, _REPO)} reads from sockets but never "
        "configures a timeout — a dead peer would hang the receive "
        "loop forever (use spark.rapids.shuffle.timeout.*)")


def _io_sources() -> List[str]:
    # filtered from the shared walker so the two lint passes can never
    # silently diverge in coverage; server/ carries the same bounded-
    # queue contract as the prefetch layer (an unbounded admission
    # queue is exactly the backlog the typed shedding exists to ban)
    out = [p for p in _python_sources()
           if p.startswith(_IO_DIR + os.sep)
           or p.startswith(_SERVER_DIR + os.sep)
           or p.startswith(_FLEET_DIR + os.sep)
           or p.startswith(_STREAM_DIR + os.sep)]
    assert out, f"robustness lint found no sources under {_IO_DIR}"
    return out


def _is_queue_ctor(node: ast.Call) -> bool:
    """queue.Queue(...) / Queue(...) / LifoQueue / PriorityQueue."""
    names = ("Queue", "LifoQueue", "PriorityQueue", "SimpleQueue")
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr in names
    if isinstance(f, ast.Name):
        return f.id in names
    return False


def _queue_is_bounded(node: ast.Call) -> bool:
    """True when the constructor passes a positive maxsize (positional
    or keyword).  A non-literal expression is accepted — boundedness
    then rests on the expression, which review can see — but a missing,
    zero, None, or NEGATIVE literal maxsize is an unbounded queue
    (queue.Queue treats maxsize <= 0 as infinite)."""
    args = list(node.args)
    for kw in node.keywords:
        if kw.arg == "maxsize":
            args.append(kw.value)
    if not args:
        return False
    v = args[0]
    if isinstance(v, ast.UnaryOp) and isinstance(v.op, ast.USub) \
            and isinstance(v.operand, ast.Constant):
        return False  # negative literal = infinite queue
    if isinstance(v, ast.Constant):
        return isinstance(v.value, int) and v.value > 0
    return True


@pytest.mark.parametrize("path", _io_sources(),
                         ids=lambda p: os.path.relpath(p, _REPO))
def test_io_prefetch_queues_are_bounded(path):
    tree = _parsed(path)
    offenders = [
        f"{os.path.relpath(path, _REPO)}:{node.lineno}"
        for node in ast.walk(tree)
        if isinstance(node, ast.Call) and _is_queue_ctor(node)
        and not _queue_is_bounded(node)
    ]
    assert not offenders, (
        "unbounded queue construction in the scan/prefetch layer — "
        "every prefetch queue must carry a positive maxsize so decode "
        f"cannot outrun the host budget: {offenders}")


_EGRESS_DIRS = (
    os.path.join(_REPO, "spark_rapids_tpu", "exec"),
    os.path.join(_REPO, "spark_rapids_tpu", "shuffle"),
    os.path.join(_REPO, "spark_rapids_tpu", "io"),
    os.path.join(_REPO, "spark_rapids_tpu", "parallel"),
    # AQE statistics pulls must route through transfer.device_pull like
    # every other egress: a raw device_get in a replanning rule would
    # bypass admission, d2h metrics, and the transfer.d2h fault site
    os.path.join(_REPO, "spark_rapids_tpu", "plan"),
    # Metric.value's pending device-scalar resolution is an egress too
    # (docs/observability.md): a metric sync pays a real link round
    # trip, so utils/ carries the same ban
    os.path.join(_REPO, "spark_rapids_tpu", "utils"),
    # standing-query refreshes surface results like any other query:
    # a raw device_get in the stream layer would bypass egress
    # admission, the d2h metrics, and the transfer.d2h fault site
    os.path.join(_REPO, "spark_rapids_tpu", "stream"),
)


def _egress_sources() -> List[str]:
    out = []
    for d in _EGRESS_DIRS:
        for root, _dirs, files in os.walk(d):
            if "__pycache__" in root:
                continue
            out.extend(os.path.join(root, f) for f in files
                       if f.endswith(".py"))
    assert out, f"egress lint found no sources under {_EGRESS_DIRS}"
    return sorted(out)


def _is_device_get_call(node: ast.Call) -> bool:
    """jax.device_get(...) / device_get(...) (a from-import alias)."""
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr == "device_get"
    if isinstance(f, ast.Name):
        return f.id == "device_get"
    return False


@pytest.mark.parametrize("path", _egress_sources(),
                         ids=lambda p: os.path.relpath(p, _REPO))
def test_no_raw_device_get_in_egress_packages(path):
    """Every device->host pull under exec/, shuffle/, io/, and
    parallel/ must go
    through columnar/transfer.py's helpers — a raw jax.device_get
    bypasses egress admission, the d2hPulls/d2hBytes metrics, and the
    transfer.d2h fault site (docs/d2h_egress.md)."""
    tree = _parsed(path)
    offenders = [
        f"{os.path.relpath(path, _REPO)}:{node.lineno}"
        for node in ast.walk(tree)
        if isinstance(node, ast.Call) and _is_device_get_call(node)
    ]
    assert not offenders, (
        "raw jax.device_get in an egress-facing package — route the "
        "pull through columnar/transfer.py (device_pull / pack_and_pull "
        "/ device_batch_to_host) so admission, metrics, and fault "
        f"injection cover it: {offenders}")


_PACKAGE_DIR = os.path.join(_REPO, "spark_rapids_tpu")


def _package_sources() -> List[str]:
    out = []
    for root, _dirs, files in os.walk(_PACKAGE_DIR):
        if "__pycache__" in root:
            continue
        out.extend(os.path.join(root, f) for f in files
                   if f.endswith(".py"))
    assert out, f"cache lint found no sources under {_PACKAGE_DIR}"
    return sorted(out)


def _is_unbounded_cache_ctor(node: ast.expr) -> bool:
    """A raw dict-ish constructor: ``{}``, ``dict()``, ``OrderedDict()``,
    ``defaultdict(...)``.  ``KernelCache(...)`` (bounded by
    construction) and non-mapping values pass."""
    if isinstance(node, (ast.Dict, ast.DictComp)):
        return True
    if isinstance(node, ast.Call):
        f = node.func
        name = f.attr if isinstance(f, ast.Attribute) else \
            f.id if isinstance(f, ast.Name) else ""
        return name in ("dict", "OrderedDict", "defaultdict")
    return False


@pytest.mark.parametrize("path", _package_sources(),
                         ids=lambda p: os.path.relpath(p, _REPO))
def test_module_level_caches_are_bounded(path):
    """Every module-level ``*CACHE*`` assignment in the package must be
    size-bounded: raw dict constructors leak compiled kernels across
    distinct-constant queries (route them through
    utils/kernel_cache.KernelCache, which bounds and counts)."""
    tree = _parsed(path)
    offenders = []
    for node in tree.body:  # module level only: locals are short-lived
        if isinstance(node, ast.AnnAssign):
            targets = [node.target]
            value = node.value
        elif isinstance(node, ast.Assign):
            targets = node.targets
            value = node.value
        else:
            continue
        if value is None or not _is_unbounded_cache_ctor(value):
            continue
        for t in targets:
            if isinstance(t, ast.Name) and "CACHE" in t.id.upper():
                offenders.append(
                    f"{os.path.relpath(path, _REPO)}:{node.lineno} "
                    f"({t.id})")
    assert not offenders, (
        "unbounded module-level cache dict(s) — compiled-kernel leak "
        "(use utils/kernel_cache.KernelCache, LRU-bounded + counted): "
        f"{offenders}")


_PARALLEL_DIR = os.path.join(_PACKAGE_DIR, "parallel")
# what an attribute that memoises programs is called
_MEMO_WORDS = ("cache", "memo", "step", "program", "compiled", "jit")


def _parallel_sources() -> List[str]:
    out = [p for p in _package_sources()
           if p.startswith(_PARALLEL_DIR + os.sep)]
    assert out, f"memo lint found no sources under {_PARALLEL_DIR}"
    return out


def _object_memo_offenders(tree: ast.AST, rel: str) -> List[str]:
    """``self.<memo-like name> = {}`` (or ``dict()``, ``OrderedDict()``,
    ``defaultdict(...)``), annotated or not, anywhere in the file."""
    offenders = []
    for node in ast.walk(tree):
        if isinstance(node, ast.AnnAssign):
            targets, value = [node.target], node.value
        elif isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        else:
            continue
        if value is None or not _is_unbounded_cache_ctor(value):
            continue
        for t in targets:
            if isinstance(t, ast.Attribute) and any(
                    w in t.attr.lower() for w in _MEMO_WORDS):
                offenders.append(f"{rel}:{node.lineno} ({t.attr})")
    return offenders


@pytest.mark.parametrize("path", _parallel_sources(),
                         ids=lambda p: os.path.relpath(p, _REPO))
def test_no_per_object_program_memos_in_parallel(path):
    """A ``Distributed*`` object keeps no dict of jitted programs: the
    object dies with its plan, and a memo that dies with a plan is a
    retrace, a lowering and an executable reload in every hot query
    (ROADMAP M5).  ``mesh.mesh_program`` holds them for the process; an
    object that cannot be keyed by value (a ``prelude``) keeps a
    ``KernelCache(..., register=False)`` of its own."""
    offenders = _object_memo_offenders(
        _parsed(path), os.path.relpath(path, _REPO))
    assert not offenders, (
        "per-object program memo(s) under parallel/ — the programs die "
        "with the plan (use parallel/mesh.py: mesh_program): "
        f"{offenders}")


def test_the_memo_lint_catches_what_it_is_for():
    """The four dicts this rule was written against, as they stood."""
    was = ast.parse(
        "class D:\n"
        "    def __init__(self):\n"
        "        self._step_cache: dict = {}\n"
        "        self._count_cache: dict = {}\n"
        "        self._join_cache = dict()\n"
        "        self._programs = collections.OrderedDict()\n"
        "        self.rows = {}\n"
        "        self._steps = KernelCache('x', 4, register=False)\n")
    assert _object_memo_offenders(was, "was.py") == [
        "was.py:3 (_step_cache)", "was.py:4 (_count_cache)",
        "was.py:5 (_join_cache)", "was.py:6 (_programs)"]


# ---------------------------------------------------------------------------
# ICI collective hygiene (docs/ici_shuffle.md): the device-resident
# shuffle path exists to keep exchange bytes OFF the host link and to
# guarantee every collective lowering can degrade to the host path.
# Three statically-checkable invariants protect that:
#
# 6. **No raw ``jax.device_put`` in ICI exchange code** (parallel/ +
#    exec/meshexec.py): an explicit device_put — or a per-device host
#    loop of them — is a host-staged scatter, exactly the link crossing
#    the collective path deletes.  Uploads belong to
#    ``columnar/transfer.py``'s admission-counted helpers; sharded
#    inputs reach devices through the jitted ``shard_map`` program's
#    own argument transfer.
#
# 7. **``jax.lax.all_to_all`` only inside parallel/**: the SPMD
#    pipelines are the one layer allowed to touch the collective
#    primitive, because only they are invoked through the guarded
#    exec wrappers that carry the host-path degrade.
#
# 8. **Every ICI lowering site carries a fallback branch**: each mesh
#    exec's ``execute_columnar`` in exec/meshexec.py must route its
#    pipeline invocation through ``_guarded_collective`` — no bare
#    collective without the fault site + qualification + host-path
#    degrade.
# ---------------------------------------------------------------------------

_ICI_DIRS = (
    os.path.join(_REPO, "spark_rapids_tpu", "parallel"),
)
_MESHEXEC = os.path.join(_REPO, "spark_rapids_tpu", "exec", "meshexec.py")


def _ici_sources() -> List[str]:
    out = [_MESHEXEC]
    for d in _ICI_DIRS:
        for root, _dirs, files in os.walk(d):
            if "__pycache__" in root:
                continue
            out.extend(os.path.join(root, f) for f in files
                       if f.endswith(".py"))
    assert len(out) > 1, f"ici lint found no sources under {_ICI_DIRS}"
    return sorted(out)


def _is_call_named(node: ast.Call, name: str) -> bool:
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr == name
    if isinstance(f, ast.Name):
        return f.id == name
    return False


@pytest.mark.parametrize("path", _ici_sources(),
                         ids=lambda p: os.path.relpath(p, _REPO))
def test_no_raw_device_put_in_ici_code(path):
    tree = _parsed(path)
    offenders = [
        f"{os.path.relpath(path, _REPO)}:{node.lineno}"
        for node in ast.walk(tree)
        if isinstance(node, ast.Call)
        and _is_call_named(node, "device_put")
    ]
    assert not offenders, (
        "raw jax.device_put in ICI exchange code — a host-staged "
        "scatter is the link crossing the collective path exists to "
        "delete; route uploads through columnar/transfer.py: "
        f"{offenders}")


def test_all_to_all_confined_to_parallel():
    """The collective primitive may only appear under parallel/ — the
    pipelines the guarded exec wrappers invoke."""
    offenders = []
    for path in _package_sources():
        rel = os.path.relpath(path, _REPO)
        if rel.startswith(os.path.join("spark_rapids_tpu", "parallel")):
            continue
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=path)
        offenders.extend(
            f"{rel}:{node.lineno}" for node in ast.walk(tree)
            if isinstance(node, ast.Call)
            and _is_call_named(node, "all_to_all"))
    assert not offenders, (
        "jax.lax.all_to_all outside parallel/ — collectives must live "
        "in the SPMD pipelines so every invocation flows through the "
        f"guarded exec wrappers (host-path degrade): {offenders}")


def test_every_mesh_exec_routes_through_guarded_collective():
    """Every mesh exec class in exec/meshexec.py (the ICI lowering
    sites) must call ``_guarded_collective`` from its
    ``execute_columnar`` — the one gate carrying the
    ``shuffle.ici.collective`` fault site, the over-HBM qualification,
    and the host-path fallback branch."""
    with open(_MESHEXEC, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=_MESHEXEC)
    offenders = []
    checked = 0
    for cls in tree.body:
        if not isinstance(cls, ast.ClassDef) or \
                not cls.name.startswith("TpuMesh"):
            continue
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)) or \
                    fn.name != "execute_columnar":
                continue
            checked += 1
            # the shared single-child body (_single_child_collective)
            # is sanctioned routing: it is checked below to itself
            # call the gate
            calls = [n for n in ast.walk(fn)
                     if isinstance(n, ast.Call)
                     and (_is_call_named(n, "_guarded_collective")
                          or _is_call_named(
                              n, "_single_child_collective"))]
            if not calls:
                offenders.append(f"{cls.name}.execute_columnar")
    helper = [n for n in tree.body
              if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
              and n.name == "_single_child_collective"]
    if helper:
        gate_calls = [n for n in ast.walk(helper[0])
                      if isinstance(n, ast.Call)
                      and _is_call_named(n, "_guarded_collective")]
        assert gate_calls, (
            "_single_child_collective no longer routes through "
            "_guarded_collective — the shared body must carry the gate")
    assert checked >= 3, (
        "expected the three mesh exec classes in exec/meshexec.py; "
        f"found {checked} execute_columnar bodies — update this lint "
        "if the lowering layer moved")
    assert not offenders, (
        "mesh exec runs its collective outside _guarded_collective — "
        "every ICI lowering site must carry the fault site + "
        f"qualification + host-path fallback: {offenders}")


# ---------------------------------------------------------------------------
# Sharded scan ingest hygiene (docs/sharded_scan.md): the host-split
# shard_table and the full-drain ingest are the SANCTIONED FALLBACK of
# ICI-lowered fragments, not their data path.  Two rules keep the
# device-resident ingest honest:
#
# 12. **``shard_table`` is confined to its definition (mesh.py) and the
#     dist pipelines' drained-input drivers** (``run_sharded`` /
#     ``run_mixed``): a host re-split creeping into exec/ or into the
#     sharded ingest (shardscan.py) would silently reintroduce the
#     drain->pull->re-upload round trip the sharded path deletes.
#
# 13. **The mesh-run path never drains**: ``_run_mesh`` /
#     ``_ensure_dist`` bodies in exec/meshexec.py must not call
#     ``_drain_single_batch`` / ``_collect_handles`` — draining is the
#     execute_columnar-level ingest decision and the fallback path,
#     never something the collective path does behind the gate's back.
# ---------------------------------------------------------------------------

_SHARD_TABLE_SANCTIONED_FUNCS = ("run_sharded", "run_mixed")


def test_shard_table_confined_to_sanctioned_fallback():
    offenders = []
    mesh_py = os.path.join(_PACKAGE_DIR, "parallel", "mesh.py")
    for path in _package_sources():
        rel = os.path.relpath(path, _REPO)
        if os.path.abspath(path) == os.path.abspath(mesh_py):
            continue  # the definition site
        tree = _parsed(path)
        parents = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                parents[child] = node
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and _is_call_named(node, "shard_table")):
                continue
            cur = parents.get(node)
            names = []
            while cur is not None:
                if isinstance(cur, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    names.append(cur.name)
                cur = parents.get(cur)
            if not any(n in _SHARD_TABLE_SANCTIONED_FUNCS
                       for n in names):
                offenders.append(f"{rel}:{node.lineno}")
    assert not offenders, (
        "shard_table outside the sanctioned drained-fallback drivers "
        "(parallel/*.run_sharded / run_mixed) — the host re-split is "
        "the fallback of ICI fragments, never their ingest "
        f"(docs/sharded_scan.md): {offenders}")


def test_mesh_run_path_never_drains():
    with open(_MESHEXEC, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=_MESHEXEC)
    offenders = []
    banned = ("_drain_single_batch", "_collect_handles")
    for cls in tree.body:
        if not isinstance(cls, ast.ClassDef) or \
                not cls.name.startswith("TpuMesh"):
            continue
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)) or \
                    fn.name not in ("_run_mesh", "_ensure_dist"):
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) and any(
                        _is_call_named(node, b) for b in banned):
                    offenders.append(
                        f"{cls.name}.{fn.name}:{node.lineno}")
    assert not offenders, (
        "the mesh-run path drained its input behind the gate — "
        "full-drain ingest belongs to the execute_columnar-level "
        "ingest decision and the sanctioned fallback only "
        f"(docs/sharded_scan.md): {offenders}")


# ---------------------------------------------------------------------------
# Query-lifecycle hygiene (docs/fault_tolerance.md "Query lifecycle"):
# the supervision layer only reclaims what it can see, so three
# statically-checkable invariants keep every blocking edge visible:
#
# 9.  **Every ``threading.Thread`` is daemonized AND its file registers
#     with the lifecycle registry**: an unregistered thread is an
#     orphan session.stop() cannot join (it survives on its daemon
#     flag, the nondeterministic teardown this layer exists to
#     replace), and a non-daemon thread can wedge interpreter exit.
#
# 10. **Every blocking queue receive carries a timeout**: a zero-arg
#     (or timeout-less blocking) ``.get()`` on a queue-shaped receiver
#     parks its thread beyond the reach of cooperative cancellation —
#     one dead sender hangs the query forever.  Bounded gets poll and
#     re-check the cancel token (lifecycle.check_cancel).
#
# 11. **Every thread/process ``.join()`` carries a timeout**: a
#     zero-arg join on a wedged thread converts one hang into two.
# ---------------------------------------------------------------------------

_LIFECYCLE_REG_NAMES = ("register_thread", "register_resource")


def _is_thread_ctor(node: ast.Call) -> bool:
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr == "Thread"
    if isinstance(f, ast.Name):
        return f.id == "Thread"
    return False


def _is_register_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    name = f.attr if isinstance(f, ast.Attribute) else \
        f.id if isinstance(f, ast.Name) else ""
    return name in _LIFECYCLE_REG_NAMES


def test_threads_are_daemonized_and_lifecycle_registered():
    # one aggregated pass over the package (NOT per-file parametrized:
    # three rules x ~100 files of pytest item overhead is real tier-1
    # wall clock); offenders are listed per file:line in the assert
    offenders = []
    for path in _package_sources():
        tree = _parsed(path)
        ctors = [n for n in ast.walk(tree)
                 if isinstance(n, ast.Call) and _is_thread_ctor(n)]
        if not ctors:
            continue
        parents = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                parents[child] = node
        for node in ctors:
            daemon = next((kw.value for kw in node.keywords
                           if kw.arg == "daemon"), None)
            if not (isinstance(daemon, ast.Constant)
                    and daemon.value is True):
                offenders.append(
                    f"{os.path.relpath(path, _REPO)}:{node.lineno} "
                    "(daemon=True missing)")
            # registration must live in the ctor's OWN scope — the
            # nearest enclosing class if any (a server's __init__ may
            # register the stop() that reaps threads its accept loop
            # spawns), else the enclosing function, else the module —
            # so one registered thread elsewhere in the file cannot
            # vacuously cover an unregistered one
            scope = None
            func_scope = None
            cur = parents.get(node)
            while cur is not None:
                if isinstance(cur, ast.ClassDef):
                    scope = cur
                    break
                if func_scope is None and isinstance(
                        cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    func_scope = cur
                cur = parents.get(cur)
            scope = scope if scope is not None else \
                func_scope if func_scope is not None else tree
            if not any(_is_register_call(n) for n in ast.walk(scope)):
                offenders.append(
                    f"{os.path.relpath(path, _REPO)}:{node.lineno} "
                    "(no lifecycle registration in the constructing "
                    "scope)")
    assert not offenders, (
        "unsupervised thread construction — every engine thread must "
        "be a daemon AND lifecycle-registered so session.stop()/query "
        f"teardown can join it deterministically: {offenders}")


_QUEUE_NAME = ("q", "queue")


def _queueish_receiver(func: ast.expr) -> bool:
    """Receiver names that denote a queue by this repo's conventions:
    ``q``, ``*_q``, or anything containing ``queue``."""
    if isinstance(func, ast.Attribute):
        base = func.value
        name = base.attr if isinstance(base, ast.Attribute) else \
            base.id if isinstance(base, ast.Name) else ""
    else:
        return False
    low = name.lower().lstrip("_")
    return low == "q" or low.endswith("_q") or "queue" in low


def _call_has_timeout(node: ast.Call) -> bool:
    if any(kw.arg == "timeout" for kw in node.keywords):
        return True
    if len(node.args) >= 2:  # get(block, timeout) positional form
        return True
    # non-blocking receives cannot park: q.get(False) / q.get(block=False)
    if node.args and isinstance(node.args[0], ast.Constant) \
            and node.args[0].value is False:
        return True
    return any(kw.arg == "block" and isinstance(kw.value, ast.Constant)
               and kw.value.value is False for kw in node.keywords)


def test_blocking_queue_gets_are_bounded():
    offenders = []
    for path in _package_sources():
        for node in ast.walk(_parsed(path)):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "get"
                    and _queueish_receiver(node.func)):
                continue
            if not _call_has_timeout(node):
                offenders.append(
                    f"{os.path.relpath(path, _REPO)}:{node.lineno}")
    assert not offenders, (
        "blocking queue .get() without a timeout — a dead sender parks "
        "the receiver beyond cooperative cancellation; poll with a "
        f"timeout and re-check the cancel token: {offenders}")


def test_joins_are_bounded():
    offenders = [
        f"{os.path.relpath(path, _REPO)}:{node.lineno}"
        for path in _package_sources()
        for node in ast.walk(_parsed(path))
        if isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr == "join"
        and not node.args and not node.keywords
    ]
    assert not offenders, (
        "unbounded .join() — joining a wedged thread/process without a "
        f"timeout converts one hang into two: {offenders}")


# ---------------------------------------------------------------------------
# Observability hygiene (docs/observability.md):
#
# 12. **No bare ``print(`` in the engine** (spark_rapids_tpu/ outside
#     bench/): engine output goes through logging, the event journal,
#     or the metrics exporter — a stray debug print is invisible to
#     post-mortems and pollutes stdout consumers (bench's one-line JSON
#     contract).  Deliberate user-facing surfaces (explain, the API
#     validation report) write ``sys.stdout.write`` explicitly.
#
# 13. **Every METRIC_* / SPAN_* constant is documented**: each name in
#     utils/metrics.py and utils/tracing.py must appear in docs/ — an
#     undocumented metric is a number nobody can interpret, and the
#     known-names registry (utils/metrics.KNOWN_METRICS) makes every
#     name in the table mintable, so the table IS the public surface.
# ---------------------------------------------------------------------------

_BENCH_DIR = os.path.join(_PACKAGE_DIR, "bench")


def test_no_bare_print_in_engine():
    offenders = []
    for path in _package_sources():
        if path.startswith(_BENCH_DIR + os.sep):
            continue
        for node in ast.walk(_parsed(path)):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id == "print":
                offenders.append(
                    f"{os.path.relpath(path, _REPO)}:{node.lineno}")
    assert not offenders, (
        "bare print() in engine code — route output through logging, "
        "the obs journal, or the exporter (deliberate user-facing "
        f"surfaces use sys.stdout.write): {offenders}")


def _named_str_constants(path: str, prefix: str) -> dict:
    """{constant_name: string_value} for module-level ``PREFIX_*``
    assignments of string literals."""
    out = {}
    for node in _parsed(path).body:
        if not isinstance(node, ast.Assign):
            continue
        if not (isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)):
            continue
        for t in node.targets:
            if isinstance(t, ast.Name) and t.id.startswith(prefix):
                out[t.id] = node.value.value
    return out


@pytest.mark.parametrize("src,prefix", [
    (os.path.join("utils", "metrics.py"), "METRIC_"),
    (os.path.join("utils", "tracing.py"), "SPAN_"),
])
def test_metric_and_span_constants_are_documented(src, prefix):
    path = os.path.join(_PACKAGE_DIR, src)
    consts = _named_str_constants(path, prefix)
    assert consts, f"no {prefix}* constants found in {src}"
    docs_dir = os.path.join(_REPO, "docs")
    corpus = ""
    for fn in sorted(os.listdir(docs_dir)):
        if fn.endswith(".md"):
            with open(os.path.join(docs_dir, fn), encoding="utf-8") as f:
                corpus += f.read()
    missing = sorted(f"{name} ({value!r})"
                     for name, value in consts.items()
                     if f"`{value}`" not in corpus)
    assert not missing, (
        f"{prefix}* constants in {src} missing from docs/*.md — every "
        "metric/span name must be documented (docs/observability.md "
        f"carries the tables): {missing}")


# ---------------------------------------------------------------------------
# Fault-site coverage (ISSUE 11 satellite): KNOWN_SITES grew piecemeal
# across PRs 8/9 and sites drifted out of the docs table — every
# registered site must appear in at least one test (something exercises
# or asserts on it) and as a backticked row in docs/fault_tolerance.md
# (operators can read what firing it means).
# ---------------------------------------------------------------------------

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def _known_sites():
    from spark_rapids_tpu.faults import KNOWN_SITES
    return KNOWN_SITES


def _tests_corpus() -> str:
    out = []
    for fn in sorted(os.listdir(_TESTS_DIR)):
        if fn.endswith(".py") and fn != os.path.basename(__file__):
            with open(os.path.join(_TESTS_DIR, fn),
                      encoding="utf-8") as f:
                out.append(f.read())
    return "\n".join(out)


def test_every_fault_site_appears_in_tests():
    corpus = _tests_corpus()
    missing = [s for s in _known_sites() if s not in corpus]
    assert not missing, (
        "fault sites registered in faults.KNOWN_SITES but exercised by "
        "no test — an untested site is a recovery path nobody has ever "
        f"run: {missing}")


def test_every_fault_site_is_documented():
    with open(os.path.join(_REPO, "docs", "fault_tolerance.md"),
              encoding="utf-8") as f:
        doc = f.read()
    missing = [s for s in _known_sites() if f"`{s}`" not in doc]
    assert not missing, (
        "fault sites registered in faults.KNOWN_SITES but missing from "
        "the docs/fault_tolerance.md site table — operators cannot "
        f"know what firing them means: {missing}")


# ---------------------------------------------------------------------------
# Compressed-domain hygiene (docs/compressed.md): every dictionary
# materialization must route through columnar/encoding.py's ONE counted
# ``decode_late`` primitive — a direct pyarrow ``dictionary_encode``/
# ``dictionary_decode`` (or a hand-rolled take-by-codes against the
# dictionary planes) elsewhere bypasses the `lateDecodes` trajectory
# number, the `io.encode` fault site, and the rank-code invariant the
# code-domain kernels rely on.
# ---------------------------------------------------------------------------

_ENCODING_PY = os.path.join("spark_rapids_tpu", "columnar",
                            "encoding.py")
_DICT_MATERIALIZE_PATTERNS = (
    ".dictionary_encode(", ".dictionary_decode(",
    ".dict.chars[", ".dict.lengths[",
)


@pytest.mark.parametrize("path", _package_sources(),
                         ids=lambda p: os.path.relpath(p, _REPO))
def test_dictionary_materialization_confined_to_encoding(path):
    rel = os.path.relpath(path, _REPO)
    if rel == _ENCODING_PY:
        return
    with open(path, encoding="utf-8") as f:
        src = f.read()
    offenders = [pat for pat in _DICT_MATERIALIZE_PATTERNS
                 if pat in src]
    assert not offenders, (
        f"{rel} materializes dictionary values directly ({offenders}) — "
        "route every decode through columnar/encoding.py's decode_late "
        "(counted as `lateDecodes`) or a DictGather rewrite, so the "
        "compressed-domain trajectory numbers stay honest")


# ---------------------------------------------------------------------------
# Compilation-service hygiene (docs/compile_cache.md): every XLA
# lower/compile must route through compile/ — the one seam carrying
# the persistent-store counters, the cold-vs-store-hit compile-time
# split, and the `compile.store` fault site.  Same pattern as the
# device_get and kernel-cache-dict bans:
#
# 14. **No raw ``jax.jit`` outside compile/** (use
#     ``compile.service.engine_jit``), and no ``from jax import jit``
#     alias smuggling one in; and every ``engine_jit`` call names its
#     program: a literal ``family`` of ``compile.service.FAMILIES`` and a
#     ``name`` (docs/observability.md, "Programs").
#
# 15. **No AOT ``.lower(...).compile(...)`` chains outside compile/**
#     (use ``compile.service.aot_compile``, which measures, classifies
#     cold-vs-store-hit, and records the warm-pool payload).
# ---------------------------------------------------------------------------

_COMPILE_DIR = os.path.join(_PACKAGE_DIR, "compile")


def _compile_banned_sources() -> List[str]:
    return [p for p in _package_sources()
            if not p.startswith(_COMPILE_DIR + os.sep)]


def _is_raw_jax_jit(node: ast.AST) -> bool:
    """``jax.jit`` / ``_jax.jit`` attribute access."""
    return (isinstance(node, ast.Attribute) and node.attr == "jit"
            and isinstance(node.value, ast.Name)
            and node.value.id in ("jax", "_jax"))


def _is_aot_chain(node: ast.AST) -> bool:
    """``<expr>.lower(...).compile(...)`` — the AOT compile chain.
    Plain ``str.lower()`` / ``re.compile()`` calls never match: the
    pattern requires a ``compile`` call whose receiver is itself a
    ``lower(...)`` call."""
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "compile"
            and isinstance(node.func.value, ast.Call)
            and isinstance(node.func.value.func, ast.Attribute)
            and node.func.value.func.attr == "lower")


def test_xla_compiles_confined_to_compile_service():
    offenders = []
    for path in _compile_banned_sources():
        rel = os.path.relpath(path, _REPO)
        for node in ast.walk(_parsed(path)):
            if _is_raw_jax_jit(node) or _is_aot_chain(node):
                offenders.append(f"{rel}:{node.lineno}")
            if isinstance(node, ast.ImportFrom) and node.module == "jax" \
                    and any(a.name == "jit" for a in node.names):
                offenders.append(f"{rel}:{node.lineno} (from jax "
                                 "import jit)")
    assert not offenders, (
        "raw jax.jit / .lower().compile() outside compile/ — every "
        "XLA compile must route through the compilation service "
        "(compile.service.engine_jit / aot_compile) so the persistent "
        "store, the compile-time split, and the compile.store fault "
        f"site cover it (docs/compile_cache.md): {offenders}")


def _engine_jit_calls():
    """(rel_path, call) for every ``engine_jit(...)`` call in the package
    outside compile/."""
    for path in _compile_banned_sources():
        rel = os.path.relpath(path, _REPO)
        for node in ast.walk(_parsed(path)):
            if isinstance(node, ast.Call) and _is_call_named(
                    node, "engine_jit"):
                yield rel, node


def test_every_engine_jit_names_its_family_and_program():
    """Rule 14, second half (docs/observability.md, "Programs"): every
    program is built with a ``family`` from the fixed tuple and a
    ``[a-z0-9_]+`` ``name``, so no XLA module is ``jit_run`` and every
    launch lands in a row of the dispatch ledger.  A name computed at
    the call site (the aggregate's phase, the stage's kind) is checked
    by ``engine_jit`` itself when the program is built."""
    import re
    from spark_rapids_tpu.compile.service import FAMILIES
    calls = list(_engine_jit_calls())
    assert len(calls) >= 30, "the engine_jit call sites were not found"
    offenders = []
    for rel, node in calls:
        kw = {k.arg: k.value for k in node.keywords}
        family, name = kw.get("family"), kw.get("name")
        where = f"{rel}:{node.lineno}"
        if not (isinstance(family, ast.Constant)
                and family.value in FAMILIES):
            offenders.append(f"{where} (family must be a literal of "
                             f"{FAMILIES})")
        if name is None:
            offenders.append(f"{where} (no name=)")
        elif isinstance(name, ast.Constant) and not (
                isinstance(name.value, str)
                and re.fullmatch(r"[a-z0-9_]+", name.value)):
            offenders.append(f"{where} (name {name.value!r} is not "
                             "[a-z0-9_]+)")
    assert not offenders, (
        "engine_jit without a stable program name — every program "
        "needs family= and name= (compile/service.py) so profiles and "
        f"engine_stats()['programs'] can say who owns the device: "
        f"{offenders}")


def test_native_transport_has_receive_timeouts():
    """The C++ data plane must carry the same bound: SO_RCVTIMEO on
    client sockets (srt_connect_t)."""
    cc = os.path.join(_REPO, "native", "transport.cc")
    with open(cc, encoding="utf-8") as f:
        src = f.read()
    assert "SO_RCVTIMEO" in src and "srt_connect_t" in src, (
        "native/transport.cc lost its socket receive timeouts "
        "(srt_connect_t / SO_RCVTIMEO)")


# ---------------------------------------------------------------------------
# Expression-kernel hygiene (docs/compressed.md): exprs/ bodies are
# pure device traces over the flat planes the stage hands them.  An
# ad-hoc materialization inside an expression — a host pull, a
# ``.decoded()`` call, or a direct plane-decode kernel — bypasses the
# counted ``decode_late`` / ``decode_plane_late`` seams (the
# `lateDecodes`/`fusedDecodes` trajectory numbers) AND breaks stage
# fusion (the decode must trace INSIDE the consuming kernel via
# stage_view's PlaneDecode, never dispatch on its own).
# ---------------------------------------------------------------------------

_EXPRS_DIR = os.path.join(_PACKAGE_DIR, "exprs")
_EXPR_MATERIALIZE_PATTERNS = (
    # host pulls: an expression must never leave the device
    "jax.device_get(", ".addressable_data(",
    ".to_numpy(", ".to_pylist(",
    # direct plane materialization: the counted seams own these
    ".decoded()", "decode_late(", "decode_plane_late(",
    "_rle_dense(", "_delta_dense(", "_packed_dense(",
)


def _exprs_sources() -> List[str]:
    return [p for p in _package_sources()
            if p.startswith(_EXPRS_DIR + os.sep)]


@pytest.mark.parametrize("path", _exprs_sources(),
                         ids=lambda p: os.path.relpath(p, _REPO))
def test_no_adhoc_materialization_in_exprs(path):
    rel = os.path.relpath(path, _REPO)
    with open(path, encoding="utf-8") as f:
        src = f.read()
    offenders = [pat for pat in _EXPR_MATERIALIZE_PATTERNS
                 if pat in src]
    assert not offenders, (
        f"{rel} materializes planes ad hoc ({offenders}) — expression "
        "kernels stay on device over the flat planes they are handed; "
        "dictionary/compressed planes decode only through the counted "
        "seams (columnar/encoding.py decode_late / decode_plane_late) "
        "or fuse via stage_view so the lateDecodes/fusedDecodes "
        "trajectory stays honest (docs/compressed.md)")


# ---------------------------------------------------------------------------
# Out-of-core hygiene (docs/out_of_core.md): exec/ooc.py exists to keep
# over-budget operators on device WITHOUT ever holding the whole input
# — every byte moves through the counted spill/promote seams one
# partition at a time.  A whole-input materialization call inside it
# (the drained-ingest helpers or materialize_all over the full handle
# list) would silently reintroduce the giant concat the module replaces
# while the OOC metrics keep claiming out-of-core execution.  And every
# ``spark.rapids.sql.ooc.*`` conf key must appear backticked in
# docs/out_of_core.md — an undocumented knob on the spill path is one
# nobody can safely turn.
# ---------------------------------------------------------------------------

_OOC_PY = os.path.join(_REPO, "spark_rapids_tpu", "exec", "ooc.py")
_OOC_BANNED_CALLS = ("_collect_handles", "_drain_single_batch",
                     "_concat_from_handles", "materialize_all")


def test_ooc_never_materializes_whole_input():
    tree = _parsed(_OOC_PY)
    offenders = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and any(
                _is_call_named(node, b) for b in _OOC_BANNED_CALLS):
            offenders.append(f"exec/ooc.py:{node.lineno}")
    assert not offenders, (
        "exec/ooc.py materializes its whole input (banned calls: "
        f"{_OOC_BANNED_CALLS}) — out-of-core operators move one "
        "partition at a time through SpillableBatch registration and "
        "the module's own grouped-promote seam; a full drain here is "
        "the giant-concat path this module exists to replace "
        f"(docs/out_of_core.md): {offenders}")


def test_every_stream_conf_key_is_documented():
    from spark_rapids_tpu.conf import conf_entries
    with open(os.path.join(_REPO, "docs", "configs.md"),
              encoding="utf-8") as f:
        doc = f.read()
    keys = [e.key for e in conf_entries()
            if e.key.startswith("spark.rapids.stream.")]
    assert keys, "no spark.rapids.stream.* keys registered"
    missing = [k for k in keys if f"`{k}`" not in doc]
    assert not missing, (
        "spark.rapids.stream.* conf keys missing from docs/configs.md "
        "— regenerate it (python -m spark_rapids_tpu.conf > "
        f"docs/configs.md): {missing}")


def test_every_ooc_conf_key_is_documented():
    from spark_rapids_tpu.conf import conf_entries
    with open(os.path.join(_REPO, "docs", "out_of_core.md"),
              encoding="utf-8") as f:
        doc = f.read()
    keys = [e.key for e in conf_entries()
            if e.key.startswith("spark.rapids.sql.ooc.")]
    assert keys, "no spark.rapids.sql.ooc.* keys registered"
    missing = [k for k in keys if f"`{k}`" not in doc]
    assert not missing, (
        "spark.rapids.sql.ooc.* conf keys missing from "
        "docs/out_of_core.md — an undocumented out-of-core knob is "
        f"one nobody can safely turn: {missing}")


_TIMED_REGIONS = ("section", "trace_range", "annotation", "timed", "phase")


def _callee_name(node: ast.expr):
    if isinstance(node, ast.Call):
        f = node.func
        return f.attr if isinstance(f, ast.Attribute) \
            else getattr(f, "id", None)
    return None


def _yields_in(body) -> List[int]:
    """Line numbers of the yields in ``body``, nested functions aside."""
    out, todo = [], list(body)
    while todo:
        n = todo.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda, ast.ClassDef)):
            continue
        if isinstance(n, (ast.Yield, ast.YieldFrom)):
            out.append(n.lineno)
        todo.extend(ast.iter_child_nodes(n))
    return out


def _is_contextmanager(fn: ast.AST) -> bool:
    return any((getattr(d, "attr", None) or getattr(d, "id", None))
               == "contextmanager" for d in fn.decorator_list)


def _regions_held_across_yield(tree: ast.AST, rel: str) -> List[str]:
    exempt = set()
    for fn in ast.walk(tree):
        if isinstance(fn, ast.FunctionDef) and _is_contextmanager(fn):
            exempt.update(id(n) for n in fn.body)
    offenders = []

    def visit(node, in_cm):
        for child in ast.iter_child_nodes(node):
            cm = in_cm or id(child) in exempt
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                cm = False
            if isinstance(child, ast.With) and not cm and any(
                    _callee_name(i.context_expr) in _TIMED_REGIONS
                    for i in child.items):
                offenders.extend(f"{rel}:{ln}"
                                 for ln in _yields_in(child.body))
            visit(child, cm)
    visit(tree, False)
    return offenders


def test_no_timed_region_is_held_across_a_yield():
    offenders = []
    for path in _package_sources():
        offenders += _regions_held_across_yield(
            _parsed(path), os.path.relpath(path, _REPO))
    assert not offenders, (
        "a section, span or metric timer held open across a yield: the "
        "consumer's work lands in it, on whichever thread resumes the "
        "generator — time each next() instead "
        f"(MetricSet.timed_iter): {offenders}")


def test_the_yield_lint_catches_what_it_is_for():
    src = """
import contextlib

def held(it, ms):
    for b in it:
        with ms.timed("totalTime"):
            yield b

def spanned(it):
    with tracing.section("x", own="egress_us"):
        yield from it

def fine(it, ms):
    for b in it:
        with ms.timed("totalTime"):
            out = b
        yield out

def nested_def_is_its_own(ms):
    with ms.timed("totalTime"):
        def gen():
            yield 1
    return gen

@contextlib.contextmanager
def phase(name):
    with tracing.trace_range(name):
        yield
"""
    found = _regions_held_across_yield(ast.parse(src), "t.py")
    assert found == ["t.py:7", "t.py:11"], found
