"""The compilation entry points (docs/compile_cache.md).

``engine_jit`` and ``aot_compile`` are the ONLY places in the engine
allowed to touch ``jax.jit`` / ``.lower(...).compile(...)`` —
``tests/lint_robustness.py`` bans the raw forms everywhere outside
``compile/`` the same way it bans raw ``jax.device_get`` in egress
code.  Funneling every compile through one seam is what makes the
compile path a subsystem instead of scattered memo dicts: the store
counters (``compileStoreHits``/``Misses``), the cold-vs-store-hit
split of measured compile time, and the ``compile.store`` fault site
cover every kernel by construction, and a future backend or cache
policy changes ONE module.

The same seam carries the **dispatch ledger** (docs/observability.md,
"Programs"): every program has a family and a name, so its XLA module is
``jit_<family>_<name>``; every launch is counted, always; and under
``spark.rapids.sql.trace.enabled`` one completion-watcher thread turns
launches into device seconds per program, per plan node, and into the
time the device sat waiting for the host, by the span that was open.
"""

from __future__ import annotations

import functools
import itertools
import queue
import re
import threading
import time
from typing import Callable, Dict, Optional, Tuple

import jax

from spark_rapids_tpu.utils import tracing
from spark_rapids_tpu.utils.metrics import (
    METRIC_DEVICE_DISPATCHES, METRIC_DEVICE_TIME,
)

_LOCK = threading.Lock()
_STATS = {"aot_compiles": 0, "aot_failures": 0,
          "cold_ms": 0.0, "store_hit_ms": 0.0, "trace_ms": 0.0}


def _bump(key: str, v) -> None:
    if v:
        with _LOCK:
            _STATS[key] += v


# One family per layer of PERF.md section 3 that launches programs.
FAMILIES = ("scan", "stage", "aggregate", "sort", "join", "window",
            "concat", "exchange", "egress")
# The one role a metric reads apart from the families and from whatever
# body does the work: every aggregate update program (the dense and the
# sorted body alike), never a merge.  Its rows count under their family too,
# and its sums sit in ``engine_stats()["roles"]``, outside ``programs``.
AGG_UPDATE = "agg_update"
_NAME_RE = re.compile(r"[a-z0-9_]+")
NO_SPAN = "(no span)"


class _Row:
    """One ledger row, shared by every ``Program`` called
    ``<family>_<name>`` (one per compiled signature).  ``bump`` is a
    single C call, atomic under the GIL, so the untraced launch path
    takes no lock; a read spends a tick of its own and subtracts the
    reads made so far.  Everything else is written by the watcher under
    ``_LEDGER_LOCK``."""

    __slots__ = ("family", "name", "role", "_ticks", "bump", "_reads",
                 "device_ns", "no_node_ns", "untimed", "starved_ns")

    def __init__(self, family: str, name: str, role: Optional[str]):
        self.family = family
        self.name = name
        self.role = role
        self._ticks = itertools.count()
        self.bump = self._ticks.__next__
        self._reads = 0
        self.device_ns = 0
        self.no_node_ns = 0
        self.untimed = 0
        self.starved_ns: Dict[str, int] = {}

    def dispatches(self) -> int:
        """Call under ``_LEDGER_LOCK``."""
        n = next(self._ticks) - self._reads
        self._reads += 1
        return n


_LEDGER_LOCK = threading.Lock()
_ROWS: Dict[Tuple[str, str], _Row] = {}


def _row(family: str, name: str, role: Optional[str] = None) -> _Row:
    if family not in FAMILIES:
        raise ValueError(f"engine_jit: family {family!r} is not one of "
                         f"{FAMILIES}")
    if not _NAME_RE.fullmatch(name):
        raise ValueError(f"engine_jit: name {name!r} must match "
                         "[a-z0-9_]+ (it is an engine_stats() key)")
    if role not in (None, AGG_UPDATE):
        raise ValueError(f"engine_jit: role {role!r} is not {AGG_UPDATE!r}")
    with _LEDGER_LOCK:
        row = _ROWS.get((family, name))
        if row is None:
            row = _ROWS[(family, name)] = _Row(family, name, role)
        return row


class Timeline:
    """The arithmetic of one in-order device stream.  A launch made at
    ``t_dispatch`` whose output was ready at ``t_ready`` ran from the
    later of ``t_dispatch`` and the previous ``t_ready``; when the
    previous one was ready first, the gap is time the device waited for
    the host.  Times are ``perf_counter_ns``."""

    __slots__ = ("prev_ready",)

    def __init__(self):
        self.prev_ready: Optional[int] = None

    def mark(self, t: int) -> None:
        """Nothing before ``t`` counts as waiting (a query begins)."""
        if self.prev_ready is None or t > self.prev_ready:
            self.prev_ready = t

    def account(self, t_dispatch: int, t_ready: int) -> Tuple[int, int]:
        """-> (device_ns, starved_ns) of one launch."""
        prev = self.prev_ready
        if prev is None or t_dispatch >= prev:
            starved = 0 if prev is None else t_dispatch - prev
            start = t_dispatch
        else:
            starved, start = 0, prev
        self.prev_ready = max(t_ready, start)
        return max(0, t_ready - start), starved


_DRAIN = object()
_MARK = object()
_WATCHER_LOCK = threading.Lock()
_WATCHER: Optional["_Watcher"] = None


class _Watcher:
    """The completion watcher: waits on each traced launch's first
    output in launch order and credits the ledger.  One per process,
    started by the first traced launch, joined by
    ``lifecycle.shutdown_all`` (``session.stop()``); the next traced
    launch starts another."""

    def __init__(self):
        from spark_rapids_tpu import lifecycle
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._stop = threading.Event()
        self._timeline = Timeline()
        self.thread = threading.Thread(
            target=self._run, name="srt-dispatch-watcher", daemon=True)
        self._reg = lifecycle.register_thread(
            self.thread, stop=self._stop.set, process_wide=True)
        self.thread.start()

    def put(self, record: tuple) -> None:
        self._q.put(record)

    def stop(self, join_timeout: float = 10.0) -> None:
        """Credit what is queued, then exit and be joined."""
        self._stop.set()
        self.thread.join(timeout=join_timeout)
        self._reg.release()

    def _run(self) -> None:
        global _WATCHER
        while True:
            try:
                rec = self._q.get(timeout=0.2)
            except queue.Empty:
                if self._stop.is_set():
                    with _WATCHER_LOCK:  # no put can slip in behind us
                        if self._q.empty():
                            if _WATCHER is self:
                                _WATCHER = None
                            return
                continue
            if rec[0] is _DRAIN:
                rec[1].set()
            elif rec[0] is _MARK:
                self._timeline.mark(rec[1])
            else:
                self._complete(*rec)

    def _complete(self, row: _Row, node_time, span: str,
                  t_dispatch: int, leaf) -> None:
        try:
            leaf.block_until_ready()
        except Exception:  # a failed or deleted output: never guessed
            with _LEDGER_LOCK:
                row.untimed += 1
            return
        device_ns, starved_ns = self._timeline.account(
            t_dispatch, time.perf_counter_ns())
        with _LEDGER_LOCK:
            row.device_ns += device_ns
            if node_time is None:
                row.no_node_ns += device_ns
            if starved_ns:
                row.starved_ns[span] = \
                    row.starved_ns.get(span, 0) + starved_ns
        if node_time is not None:
            node_time.add(device_ns)


def _submit(record: tuple) -> None:
    global _WATCHER
    with _WATCHER_LOCK:
        if _WATCHER is None:
            _WATCHER = _Watcher()
        _WATCHER.put(record)


def drain_watcher(timeout_s: float = 600.0) -> bool:
    """Wait until the watcher has credited every launch made so far;
    True when it has (or there is no watcher).  ``lifecycle.query_scope``
    calls it on exit of a traced query, so the query's profile is
    complete."""
    with _WATCHER_LOCK:
        w = _WATCHER
        if w is None:
            return True
        done = threading.Event()
        w.put((_DRAIN, done))
    deadline = time.monotonic() + timeout_s
    while not done.wait(0.05):
        if not w.thread.is_alive() or time.monotonic() > deadline:
            return done.is_set()
    return True


def stop_watcher() -> None:
    """Stop and join the watcher, if one runs (the next traced launch
    starts another): what ``session.stop()`` does through
    ``lifecycle.shutdown_all``, for a caller that keeps its session."""
    with _WATCHER_LOCK:
        w = _WATCHER
    if w is not None:
        w.stop()


def _first_waitable(out):
    for leaf in jax.tree_util.tree_leaves(out):
        if hasattr(leaf, "block_until_ready") \
                and not isinstance(leaf, jax.core.Tracer):
            return leaf
    return None


def _traced_launch(row: _Row, target, args, kwargs):
    """A launch under the trace switch: count it, charge it to the plan
    node whose ``next()`` is running and to the span the launching code
    has open (read before the launch's own ``host.dispatch``), and hand
    its first output to the watcher.  Never blocks on the device."""
    span = tracing.current_span() or NO_SPAN
    with tracing.section(tracing.SPAN_HOST_DISPATCH, own="dispatch_us"):
        out = target(*args, **kwargs)
    t_dispatch = time.perf_counter_ns()
    row.bump()
    node = tracing.current_node()
    node_time = None
    if node is not None:
        node.metrics[METRIC_DEVICE_DISPATCHES].add(1)
        node_time = node.metrics[METRIC_DEVICE_TIME]
    leaf = _first_waitable(out)
    if leaf is None:
        with _LEDGER_LOCK:
            row.untimed += 1
    else:
        _submit((row, node_time, span, t_dispatch, leaf))
    return out


class Program:
    """What ``engine_jit`` returns: the jitted function plus its ledger
    row.  Calling it launches the program; ``lower`` and every other
    attribute are the jitted function's own (``aot_compile`` lowers
    through it)."""

    __slots__ = ("family", "name", "_fn", "_row")

    def __init__(self, fn, row: _Row):
        self.family = row.family
        self.name = row.name
        self._fn = fn
        self._row = row

    def _launch(self, target, args, kwargs):
        if tracing.is_enabled():
            return _traced_launch(self._row, target, args, kwargs)
        with tracing.section(tracing.SPAN_HOST_DISPATCH, own="dispatch_us"):
            out = target(*args, **kwargs)
        self._row.bump()
        return out

    def __call__(self, *args, **kwargs):
        return self._launch(self._fn, args, kwargs)

    def call_compiled(self, compiled, *args):
        """Launch this program's AOT-compiled executable
        (exec/stage.py ``StageKernel``) through the same ledger row."""
        return self._launch(compiled, args, {})

    def __getattr__(self, attr):
        if attr in Program.__slots__:  # unset (mid-copy): do not recurse
            raise AttributeError(attr)
        return getattr(self._fn, attr)

    def __repr__(self):
        return f"Program({self.family}_{self.name})"


def engine_jit(fn, *, family: str, name: str, role: Optional[str] = None,
               **kwargs) -> Program:
    """The one sanctioned ``jax.jit`` wrapper.  ``family`` is one of
    ``FAMILIES`` and ``name`` says which program of the family; the XLA
    module is ``jit_<family>_<name>`` and the body runs under
    ``jax.named_scope("<family>.<name>")``, so a profile names the
    engine's programs, not ``jit_run``.  ``role=AGG_UPDATE`` adds the
    program's launches and device time to ``roles_snapshot()`` as well.
    A jitted fn compiles lazily on first call per signature (the JAX
    persistent cache, when the store enabled it, covers those compiles at
    the XLA layer); call sites that want measured compile time and store
    counters AOT-compile through ``aot_compile`` instead."""
    row = _row(family, name, role)
    scope = f"{family}.{name}"

    @functools.wraps(fn)
    def body(*args, **kw):
        with jax.named_scope(scope):
            return fn(*args, **kw)

    body.__name__ = body.__qualname__ = f"{family}_{name}"
    return Program(jax.jit(body, **kwargs), row)


def ledger_rows() -> Dict[str, dict]:
    """``{<family>_<name>: counters}``, every program ever named."""
    with _LEDGER_LOCK:
        return {f"{r.family}_{r.name}": {
            "family": r.family, "role": r.role,
            "dispatches": r.dispatches(),
            "device_ns": r.device_ns, "no_node_ns": r.no_node_ns,
            "untimed": r.untimed, "starved_ns": dict(r.starved_ns)}
            for r in _ROWS.values()}


def ledger_mark() -> Dict[str, dict]:
    """A query begins under the switch: the rows to subtract at its end,
    and a mark in the watcher's stream so the idle time before the query
    is not read as the device waiting for it."""
    with _WATCHER_LOCK:
        if _WATCHER is not None:
            _WATCHER.put((_MARK, time.perf_counter_ns()))
    return ledger_rows()


def ledger_since(mark: Dict[str, dict]) -> list:
    """The programs launched since ``mark`` (``ledger_mark``), one row
    each, most device time first: what a ``QueryProfile`` shows."""
    out = []
    for program, now in ledger_rows().items():
        was = mark.get(program, {})
        n = now["dispatches"] - was.get("dispatches", 0)
        if not n:
            continue
        starved = {span: (ns - was.get("starved_ns", {}).get(span, 0))
                   for span, ns in now["starved_ns"].items()}
        out.append({
            "program": program, "family": now["family"], "dispatches": n,
            "device_ms": round(
                (now["device_ns"] - was.get("device_ns", 0)) / 1e6, 3),
            "no_node_ms": round(
                (now["no_node_ns"] - was.get("no_node_ns", 0)) / 1e6, 3),
            "untimed": now["untimed"] - was.get("untimed", 0),
            "starved_ms": {span: round(ns / 1e6, 3)
                           for span, ns in sorted(starved.items()) if ns}})
    out.sort(key=lambda r: (-r["device_ms"], r["program"]))
    return out


def programs_snapshot() -> dict:
    """The ``programs`` group of ``engine_stats()``: flat, numeric, every
    key present from the first snapshot on."""
    out = {"dispatches": 0, "device_us": 0, "starved_us": 0, "untimed": 0}
    fam_n = dict.fromkeys(FAMILIES, 0)
    fam_ns = dict.fromkeys(FAMILIES, 0)
    device_ns = starved_ns = 0
    for r in ledger_rows().values():
        fam_n[r["family"]] += r["dispatches"]
        fam_ns[r["family"]] += r["device_ns"]
        device_ns += r["device_ns"]
        starved_ns += sum(r["starved_ns"].values())
        out["untimed"] += r["untimed"]
    out["dispatches"] = sum(fam_n.values())
    out["device_us"] = device_ns // 1000
    out["starved_us"] = starved_ns // 1000
    for fam in FAMILIES:
        out[f"{fam}_dispatches"] = fam_n[fam]
        out[f"{fam}_device_us"] = fam_ns[fam] // 1000
    return out


def roles_snapshot() -> dict:
    """The ``roles`` group of ``engine_stats()``: the launches (always) and
    device microseconds (under the trace switch) of every program built
    with ``role=AGG_UPDATE``, whatever its family or name."""
    n = ns = 0
    for r in ledger_rows().values():
        if r["role"] == AGG_UPDATE:
            n += r["dispatches"]
            ns += r["device_ns"]
    return {f"{AGG_UPDATE}_dispatches": n,
            f"{AGG_UPDATE}_device_us": ns // 1000}


def pallas_interpret() -> bool:
    """The one rule for the hand-written Pallas kernels
    (exec/pallas_agg.py, exprs/pallas_strings.py): interpret iff the
    backend is not ``tpu``.  On the chip a kernel always goes through
    Mosaic, and what Mosaic refuses is an error."""
    return jax.default_backend() != "tpu"


def store_active() -> bool:
    from spark_rapids_tpu.compile import store
    return store.current() is not None


def aot_compile(fn, avals, store_key=None,
                payload_fn: Optional[Callable[[], bytes]] = None,
                record: bool = True
                ) -> Tuple[Optional[object], float, bool]:
    """AOT-compile a jitted ``fn`` at abstract ``avals`` through the
    service: ``(compiled_or_None, compile_ms, store_hit)``.

    With the persistent store installed and a ``store_key`` given, the
    key is looked up in the on-disk fingerprint index BEFORE compiling
    — so the measured milliseconds land in ``store_hit_ms`` when XLA
    is about to deserialize a stored executable and in ``cold_ms``
    when this is a genuinely fresh compile.  Only the ``.compile()``
    phase is attributed to that split: tracing/lowering runs the same
    Python either way and lands in ``trace_ms`` — folding it into the
    hit bucket is how BENCH_r06's ``xlaCompileStoreHitMs`` came to
    exceed ``xlaCompileColdMs`` — and recorded into it only
    AFTER the compile succeeded (a failing kernel must never be
    indexed as seen).  ``payload_fn`` supplies the pickled (steps,
    signature, capacity) triple the AOT warm pool replays; it runs
    only when the payload file is missing.  ``record=False`` classifies
    without recording — the warm pool's own replays use it so they
    cannot inflate their keys' top-K popularity on every restart.  A
    failed AOT compile returns ``None`` — jit-on-first-call remains
    correct — and any store failure (injected or real) degrades to a
    counted fresh compile."""
    hit = False
    digest = st = None
    if store_key is not None:
        from spark_rapids_tpu.compile import store as store_mod
        st = store_mod.current()
        if st is not None:
            digest, hit = st.lookup(store_key)
    t0 = time.perf_counter()
    compile_ms = 0.0
    try:
        lowered = fn.lower(*avals)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        compile_ms = (time.perf_counter() - t1) * 1e3
        _bump("trace_ms", (t1 - t0) * 1e3)
    except Exception:
        # AOT is an optimization; jit-on-first-call remains correct
        compiled = None
        _bump("aot_failures", 1)
    ms = (time.perf_counter() - t0) * 1e3
    _bump("aot_compiles", 1)
    # the deserialize seam is the .compile() call alone: a store hit
    # skips XLA compilation there, not the Python tracing before it
    _bump("store_hit_ms" if hit else "cold_ms", compile_ms)
    if record and compiled is not None and digest is not None:
        st.record_execution(digest, payload_fn)
    return compiled, ms, hit


def service_stats() -> dict:
    with _LOCK:
        out = dict(_STATS)
    out["cold_ms"] = round(out["cold_ms"], 1)
    out["store_hit_ms"] = round(out["store_hit_ms"], 1)
    out["trace_ms"] = round(out["trace_ms"], 1)
    return out


def reset_stats() -> None:
    with _LOCK:
        for k in _STATS:
            _STATS[k] = 0.0 if k.endswith("_ms") else 0


def snapshot() -> dict:
    """The ``compile`` group of the unified engine-stats snapshot
    (obs/registry.py; docs/observability.md carries the row table):
    store counters, the cold-vs-store-hit compile-time split, warm-pool
    counters, and the bucket-ladder bounds."""
    from spark_rapids_tpu.compile import buckets, store, warm
    st = store.stats()
    svc = service_stats()
    wm = warm.stats()
    lad = buckets.stats()
    return {
        "storeEnabled": st["enabled"],
        "compileStoreHits": st["hits"],
        "compileStoreMisses": st["misses"],
        "compileStoreBytes": st["bytes"],
        "compileStoreEntries": st["entries"],
        "compileStoreCorrupt": st["corrupt"],
        "compileStoreFaults": st["faults"],
        "compileStoreIoErrors": st["io_errors"],
        "xlaCompileColdMs": svc["cold_ms"],
        "xlaCompileStoreHitMs": svc["store_hit_ms"],
        "xlaCompileTraceMs": svc["trace_ms"],
        "aotCompiles": svc["aot_compiles"],
        "aotFailures": svc["aot_failures"],
        "warmPoolCompiles": wm["compiles"],
        "warmPoolErrors": wm["errors"],
        "bucketMinRows": lad["minRows"],
        "bucketMaxRows": lad["maxRows"],
    }
