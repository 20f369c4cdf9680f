"""Measured cost model for hybrid fragment placement (docs/placement.md).

The numbers half of the placement pass (plan/placement.py).  Three
inputs, each measured rather than guessed — the reference plugin's
planner layer makes the same *decision* (what belongs on the
accelerator) but with hard-coded operator costs; here BENCH_r05's
lesson is that the LINK constants dominate, so they are probed:

* **Link constants** — H2D/D2H bandwidth and the fixed per-pull latency.
  ``probe_link()`` is the one-shot measurement bench.py used to carry
  (promoted here so bench and planner read ONE set of constants instead
  of two drifting copies); the ``spark.rapids.sql.placement.{h2dMBps,
  d2hMBps,pullLatencyMs}`` conf keys override the probe, which is what
  pins decisions in tests and on known attachments.
* **Per-operator-class throughput** — a ``CalibrationStore`` of EWMA
  rows/sec per (engine, operator class), learned from executed-query
  profiles (the same per-operator rows/wall snapshot the obs
  ``QueryProfile`` walk reads) and persisted beside the persistent
  compile store when one is installed (``calibration.json`` in the
  store directory — the compile/store.py pattern: shared across
  processes and restarts, every failure degrades to the in-memory
  priors).  The ``spark.rapids.sql.placement.{cpu,tpu}RowsPerSec``
  priors seed uncalibrated classes.
* **Expected compile cost** — read from the compile store's hit/miss
  counters: zero on an expected store hit (and zero without a store,
  where the in-process kernel caches make re-compiles rare), else the
  store's average measured cold-compile milliseconds scaled by its
  miss ratio.

``score_ops`` combines them:

    tpu_ms = bytes_in / h2d_bw + pulls x pull_latency
             + bytes_out / d2h_bw + sum(rows / tpu_rate(op)) + compile
    cpu_ms = sum(rows / cpu_rate(op))

and the fragment goes to whichever engine projects cheaper.  All
approximations are documented in docs/placement.md; the contract that
matters is conf-gated determinism — with every constant pinned the
decision is a pure function of the plan and the estimates.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Dict, List, Optional

log = logging.getLogger("spark_rapids_tpu.plan.cost")

# ---------------------------------------------------------------------------
# Link constants: one-shot probe + conf overrides
# ---------------------------------------------------------------------------

_PROBE_LOCK = threading.Lock()
_PROBE: Optional[dict] = None
_PROBE_BYTES = 1 << 22


def probe_link() -> dict:
    """Measure H2D/D2H bandwidth and the fixed per-pull latency once
    per process, so per-suite numbers (bench.py) and placement
    decisions (plan/placement.py) are read against the physics of the
    attachment (docs/performance.md carries what the directly attached
    v5e measured).  Routed
    through the engine's sanctioned seams: ``engine_jit`` for the tiny
    kernels and ``transfer.device_pull`` for the pulls, so even the
    probe's link crossings are admission-counted like every other
    egress."""
    global _PROBE
    with _PROBE_LOCK:
        if _PROBE is not None:
            return dict(_PROBE)
        import jax
        import jax.numpy as jnp
        import numpy as np

        from spark_rapids_tpu.columnar.transfer import device_pull
        from spark_rapids_tpu.compile.service import engine_jit
        out = {}
        jnp.zeros(8).block_until_ready()
        h = np.random.default_rng(0).integers(
            0, 255, _PROBE_BYTES).astype(np.uint8)
        jax.device_put(h[:16]).block_until_ready()  # warm the path
        t0 = time.perf_counter()
        d = jax.device_put(h)
        d.block_until_ready()
        out["h2d_mbps"] = round(
            _PROBE_BYTES / (time.perf_counter() - t0) / 1e6, 1)
        g = engine_jit(lambda x: x + 1, family="scan", name="calibrate")
        y = g(d)
        t0 = time.perf_counter()
        device_pull(y)
        out["d2h_mbps"] = round(
            _PROBE_BYTES / (time.perf_counter() - t0) / 1e6, 1)
        z = g(jnp.zeros(8, jnp.uint8))
        t0 = time.perf_counter()
        device_pull(z)
        out["d2h_latency_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 1)
        _PROBE = out
        return dict(out)


_AGG_PROBE_LOCK = threading.Lock()
_AGG_PROBE: Dict[int, dict] = {}
_AGG_PROBE_BYTES = 1 << 21  # per chip


def probe_link_aggregate(n_devices: Optional[int] = None) -> dict:
    """Measure the AGGREGATE H2D/D2H bandwidth across every visible
    chip's independent link stream, once per process — the number the
    sharded scan ingest (docs/sharded_scan.md) actually moves data at:
    ``probe_link()`` times ONE device's stream, but N chips upload and
    pull concurrently, so pricing a mesh fragment at single-link
    bandwidth undercounts the mesh by up to Nx.  Uploads dispatch
    per-chip (``jax.device_put`` is asynchronous — the same overlapped
    dispatch the ingest uses) and the pulls fan out through
    ``transfer.parallel_device_pull`` (counted, fault-covered).
    Returns ``{devices, agg_h2d_mbps, agg_d2h_mbps}``; memoized PER
    measured width, so a width-capped session
    (``spark.rapids.shuffle.ici.devices``) and a full-mesh bench in
    one process each read their own number."""
    with _AGG_PROBE_LOCK:
        import jax
        import numpy as np

        from spark_rapids_tpu.columnar.transfer import (
            parallel_device_pull,
        )
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:max(1, int(n_devices))]
        n = len(devices)
        if n in _AGG_PROBE:
            return dict(_AGG_PROBE[n])
        h = np.random.default_rng(0).integers(
            0, 255, _AGG_PROBE_BYTES).astype(np.uint8)
        for d in devices:  # warm each chip's path
            jax.device_put(h[:16], d).block_until_ready()
        t0 = time.perf_counter()
        placed = [jax.device_put(h, d) for d in devices]
        for a in placed:
            a.block_until_ready()
        h2d_s = max(1e-9, time.perf_counter() - t0)
        t0 = time.perf_counter()
        parallel_device_pull(placed)
        d2h_s = max(1e-9, time.perf_counter() - t0)
        out = {
            "devices": n,
            "agg_h2d_mbps": round(n * _AGG_PROBE_BYTES / h2d_s / 1e6, 1),
            "agg_d2h_mbps": round(n * _AGG_PROBE_BYTES / d2h_s / 1e6, 1),
        }
        _AGG_PROBE[n] = out
        return dict(out)


def aggregate_link_constants(conf, n_devices: Optional[int] = None
                             ) -> dict:
    """Multi-chip link constants: the
    ``spark.rapids.sql.placement.aggregate{H2d,D2h}MBps`` conf keys
    when set (the deterministic path tests pin), the one-shot
    multi-chip probe filling whatever was left to measure."""
    from spark_rapids_tpu.conf import (
        PLACEMENT_AGG_D2H_MBPS, PLACEMENT_AGG_H2D_MBPS,
    )
    h2d = float(conf.get(PLACEMENT_AGG_H2D_MBPS))
    d2h = float(conf.get(PLACEMENT_AGG_D2H_MBPS))
    probed = False
    if h2d <= 0 or d2h <= 0:
        probe = probe_link_aggregate(n_devices)
        probed = True
        if h2d <= 0:
            h2d = probe["agg_h2d_mbps"]
        if d2h <= 0:
            d2h = probe["agg_d2h_mbps"]
    return {"agg_h2d_mbps": h2d, "agg_d2h_mbps": d2h,
            "probed": probed}


def mesh_ingest_qualified(conf) -> bool:
    """True when this session's exchange fragments would ingest through
    the sharded scan path (docs/sharded_scan.md): ICI mode selected AND
    sharded scan enabled.  The placement pass prices fragment transfers
    at the AGGREGATE link rates then — the mesh's N concurrent streams,
    not one chip's."""
    if not conf.ici_sharded_scan:
        return False
    from spark_rapids_tpu.shuffle.manager import select_shuffle_mode
    return select_shuffle_mode(conf) == "ici"


def effective_link_constants(conf) -> dict:
    """The constants ``place_fragments``/``aqe_rescore`` score with:
    the single-link probe/conf values, widened to the aggregate
    multi-chip rates when the session's fragments ingest sharded —
    cost mode must not price a mesh fragment at single-link
    bandwidth."""
    consts = link_constants(conf)
    if mesh_ingest_qualified(conf):
        # probe at the width the session's fragments actually ingest
        # over (shuffle.ici.devices cap + healthy pool), never the full
        # host: an 8-chip aggregate rate on a width-2 session would be
        # up to 4x optimistic on every transfer term
        from spark_rapids_tpu.shuffle.manager import ici_mesh_width
        agg = aggregate_link_constants(conf, ici_mesh_width(conf))
        consts = dict(consts)
        consts["h2d_mbps"] = max(consts["h2d_mbps"],
                                 agg["agg_h2d_mbps"])
        consts["d2h_mbps"] = max(consts["d2h_mbps"],
                                 agg["agg_d2h_mbps"])
        consts["aggregate"] = True
    return consts


def link_constants(conf) -> dict:
    """The link constants the cost model charges transfers with:
    ``spark.rapids.sql.placement.{h2dMBps,d2hMBps,pullLatencyMs}`` when
    set (the deterministic path tests pin), the one-shot probe filling
    whatever was left to measure."""
    from spark_rapids_tpu.conf import (
        PLACEMENT_D2H_MBPS, PLACEMENT_H2D_MBPS, PLACEMENT_PULL_LATENCY_MS,
    )
    h2d = float(conf.get(PLACEMENT_H2D_MBPS))
    d2h = float(conf.get(PLACEMENT_D2H_MBPS))
    lat = float(conf.get(PLACEMENT_PULL_LATENCY_MS))
    probed = False
    if h2d <= 0 or d2h <= 0 or lat < 0:
        probe = probe_link()
        probed = True
        if h2d <= 0:
            h2d = probe["h2d_mbps"]
        if d2h <= 0:
            d2h = probe["d2h_mbps"]
        if lat < 0:
            lat = probe["d2h_latency_ms"]
    return {"h2d_mbps": h2d, "d2h_mbps": d2h, "pull_latency_ms": lat,
            "probed": probed}


def startup_probe(conf) -> None:
    """One-shot startup probe (runtime init): with ``placement.mode=
    cost`` and any link constant left to measure, pay the probe now so
    the first query's planning does not.  Never raises — the probe is
    an optimization over lazy probing at first scoring."""
    from spark_rapids_tpu.conf import PLACEMENT_MODE
    try:
        if str(conf.get(PLACEMENT_MODE)).strip().lower() != "cost":
            return
        link_constants(conf)
    except Exception as e:
        log.warning("placement link probe failed (constants will "
                    "default or re-probe lazily): %s", e)


# ---------------------------------------------------------------------------
# Calibration: EWMA rows/sec per (engine, operator class)
# ---------------------------------------------------------------------------

_CAL_ALPHA = 0.3
_CAL_FILE = "calibration.json"

# process-global calibration-mode switch (set from
# spark.rapids.sql.placement.mode at every ExecContext construction,
# like the tracing span switch): the CPU engine's per-operator counting
# hooks (exec/base.py CpuExec._count_output) record only while it is
# not 'tpu', so the default mode stays byte-identical in metrics
_MODE = "tpu"


def set_mode(mode: str) -> None:
    """Process-global, set at every execution entry point like the
    tracing/hoisting/encoding switches — concurrent sessions with
    DIFFERENT placement modes in one process are unsupported (the same
    limitation every process-global switch in this engine carries);
    the session server's tenants share one session conf, so serving is
    single-mode by construction."""
    global _MODE
    _MODE = mode


def calibration_active() -> bool:
    return _MODE != "tpu"


class CalibrationStore:
    """Measured throughput per (engine, operator class): EWMA rows/sec
    observed from executed-query profiles, persisted beside the
    persistent compile store when one is installed (module
    docstring)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._rates: Dict[str, float] = {}   # "engine:class" -> rows/s
        self._counts: Dict[str, int] = {}
        self._loaded_dir: Optional[str] = None
        self._dirty = False

    def observe(self, engine: str, op_class: str, rows: int,
                seconds: float) -> None:
        if rows <= 0 or seconds <= 1e-7:
            return
        key = f"{engine}:{op_class}"
        rate = rows / seconds
        with self._lock:
            prev = self._rates.get(key)
            self._rates[key] = rate if prev is None else \
                _CAL_ALPHA * rate + (1 - _CAL_ALPHA) * prev
            self._counts[key] = self._counts.get(key, 0) + 1
            self._dirty = True

    def rate(self, engine: str, op_class: str, default: float) -> float:
        with self._lock:
            return self._rates.get(f"{engine}:{op_class}", default)

    def snapshot(self) -> Dict[str, dict]:
        with self._lock:
            return {k: {"rows_per_sec": round(r, 1),
                        "observations": self._counts.get(k, 0)}
                    for k, r in sorted(self._rates.items())}

    # -- persistence (compile/store.py failure matrix: every store
    # failure degrades to the in-memory priors, never a query) --------------

    def load(self, root: str) -> None:
        path = os.path.join(root, _CAL_FILE)
        try:
            with open(path, encoding="utf-8") as fh:
                raw = json.load(fh)
            with self._lock:
                for key, ent in raw.items():
                    if key not in self._rates:
                        self._rates[key] = float(ent["rate"])
                        self._counts[key] = int(ent.get("n", 1))
                self._loaded_dir = root
        except FileNotFoundError:
            with self._lock:
                self._loaded_dir = root
        except (OSError, ValueError, KeyError, TypeError) as e:
            log.warning("cannot read calibration store %s (priors "
                        "stand): %s", path, e)
            with self._lock:
                self._loaded_dir = root

    def save(self, root: str) -> None:
        path = os.path.join(root, _CAL_FILE)
        tmp = path + f".tmp{os.getpid()}"
        with self._lock:
            if not self._dirty:
                return
            payload = {k: {"rate": round(r, 3),
                           "n": self._counts.get(k, 1)}
                       for k, r in self._rates.items()}
            self._dirty = False
        try:
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(payload, fh, separators=(",", ":"))
            os.replace(tmp, path)  # atomic vs concurrent readers
        except OSError as e:
            log.warning("calibration save failed (learning stays "
                        "in-process): %s", e)


_CAL = CalibrationStore()


def calibration() -> CalibrationStore:
    """The process-wide calibration store, lazily loaded from the
    persistent compile store's directory when one is installed (the
    stores share a lifecycle: a process that reuses kernels across
    restarts reuses throughputs too)."""
    from spark_rapids_tpu.compile import store as compile_store
    st = compile_store.current()
    if st is not None and _CAL._loaded_dir != st.root:
        _CAL.load(st.root)
    return _CAL


def reset() -> None:
    """Test teardown: drop learned rates, the probe memos, and the mode
    switch so one test's calibration can never steer another's
    placement decisions."""
    global _CAL, _PROBE, _MODE
    _CAL = CalibrationStore()
    with _PROBE_LOCK:
        _PROBE = None
    with _AGG_PROBE_LOCK:
        _AGG_PROBE.clear()
    _MODE = "tpu"


# ---------------------------------------------------------------------------
# Operator classes and size arithmetic
# ---------------------------------------------------------------------------

def op_class(name: str) -> str:
    """Engine-neutral operator-class key: ``TpuProjectExec`` and
    ``CpuProjectExec`` both calibrate (and score) as ``project``."""
    for pre in ("Tpu", "Cpu"):
        if name.startswith(pre):
            name = name[len(pre):]
            break
    if name.endswith("Exec"):
        name = name[:-4]
    return name.lower()


# logical node -> the operator class its physical lowering calibrates
# under (plan/logical.py node_name -> op_class of the exec both
# planner._to_tpu and ._to_cpu produce for it)
LOGICAL_CLASS = {
    "Project": "project", "Filter": "filter", "Union": "union",
    "Limit": "locallimit", "LocalRelation": "localscan",
    "ParquetRelation": "parquetscan", "CsvRelation": "csvscan",
    "OrcRelation": "orcscan", "Range": "range", "Sort": "sort",
    "Aggregate": "hashaggregate", "Join": "hashjoin",
    "Repartition": "shuffleexchange", "Window": "window",
    "Expand": "expand", "Generate": "generate",
}

# expression modules whose kernels calibrate under the string classes:
# a char-matrix kernel's rows/sec profile is nothing like an arithmetic
# projection's, so project/filter fragments dominated by them score
# (and are measured) under `project_str` / `filter_str` — the classes
# whose measured TPU overtake flips string fragments back to the
# device (docs/placement.md, ISSUE 17 prong c)
_STRING_EXPR_MODULES = ("exprs.strings", "exprs.pallas_strings")


def _has_string_kernel(exprs) -> bool:
    stack = list(exprs or ())
    while stack:
        e = stack.pop()
        mod = type(e).__module__ or ""
        if mod.endswith(_STRING_EXPR_MODULES):
            return True
        stack.extend(getattr(e, "children", ()) or ())
    return False


def step_class(kind: str, exprs) -> str:
    """Operator class of one fused-stage step (or one project/filter
    node given its expressions): ``project``/``filter`` become
    ``project_str``/``filter_str`` when the expression tree contains a
    string kernel.  Used symmetrically by the scorer
    (placement._score_fragment / _remainder_classes) and the
    calibration feed (_observe_node) so the class a fragment is scored
    under is the class its execution calibrates."""
    if kind in ("project", "filter") and _has_string_kernel(exprs):
        return kind + "_str"
    return kind


def schema_row_width(schema) -> int:
    """Estimated bytes per row in the device layout — the rows <->
    bytes bridge for size estimates that arrive in bytes (file sizes).
    Delegates to the engine's ONE size estimator
    (``columnar/batch.py:estimate_batch_size_bytes``) so the cost model
    and batch planning can never carry drifting row-size constants."""
    from spark_rapids_tpu.columnar.batch import estimate_batch_size_bytes
    return max(1, estimate_batch_size_bytes(schema, 1))


def expected_compile_ms() -> float:
    """Expected XLA compile cost of a fresh fragment, read from the
    persistent compile store's hit/miss counters: zero on an expected
    store hit and zero without a store (the in-process kernel caches
    make re-compiles rare), else the average measured cold-compile
    milliseconds scaled by the store's miss ratio.

    The miss ratio counts the IN-PROCESS kernel-cache hits in its
    denominator: the store only ever sees the lookups those caches
    miss, so a warm process with a cold store used to project the full
    cold-compile cost onto every fragment even though almost every
    kernel re-use never reaches the store at all (a BENCH_r07
    ``cost_error_p99_pct`` driver — projected compile legs on plans
    that would compile nothing)."""
    from spark_rapids_tpu.compile import service, store
    from spark_rapids_tpu.utils import kernel_cache
    st = store.current()
    if st is None:
        return 0.0
    s = st.stats()
    kc_hits = sum(v["hits"] for v in kernel_cache.all_stats().values())
    total = s["hits"] + s["misses"] + kc_hits
    if total == 0 or s["misses"] == 0:
        return 0.0
    svc = service.service_stats()
    avg_cold = svc["cold_ms"] / max(1, s["misses"])
    return avg_cold * (s["misses"] / total)


# ---------------------------------------------------------------------------
# Fragment scoring
# ---------------------------------------------------------------------------

_PACK_GROUP_BYTES = 256 << 20  # DeviceToHostExec's pull-group bound


def score_ops(op_classes: List[str], rows: int, bytes_in: int,
              bytes_out: int, conf, consts: dict,
              calib: CalibrationStore,
              compile_ms: float = 0.0,
              ooc_budget: int = 0) -> dict:
    """Score one fragment both ways and pick the engine.  The SAME
    formula serves the static pass (estimated sizes) and the AQE
    runtime re-score (measured stage bytes): the runtime question is
    'would the static decision have differed had it known the real
    bytes', so the terms are identical by design (docs/placement.md).

    Returns the decision record journaled as ``fragment_placed``:
    chosen engine, both projected costs, and the deciding term (the
    largest TPU-side term when the CPU engine wins, ``cpu_compute``
    when the device keeps the fragment)."""
    from spark_rapids_tpu.conf import (
        PLACEMENT_CPU_ROWS_PER_SEC, PLACEMENT_TPU_ROWS_PER_SEC,
    )
    cpu_prior = float(conf.get(PLACEMENT_CPU_ROWS_PER_SEC))
    tpu_prior = float(conf.get(PLACEMENT_TPU_ROWS_PER_SEC))

    def bw_ms(nbytes: int, mbps: float) -> float:
        # MB/s -> ms: bytes / (mbps * 1e6) seconds
        return nbytes / (mbps * 1000.0) if mbps > 0 else 0.0

    pulls = 1 + int(bytes_out // _PACK_GROUP_BYTES)
    terms = {
        "h2d": bw_ms(bytes_in, consts["h2d_mbps"]),
        # latency charged ONCE: the pull groups are pipelined
        # (pipelined_d2h overlaps dispatch with the previous group's
        # copy), so only the first pull's round trip is exposed —
        # multiplying by the group count stacked hundreds of phantom
        # milliseconds onto large-output plans (BENCH_r07
        # cost_error_p99_pct 24576); ``pulls`` stays in the decision
        # record for the bandwidth-vs-latency post-mortem read
        "pull_latency": consts["pull_latency_ms"],
        "d2h": bw_ms(bytes_out, consts["d2h_mbps"]),
        "tpu_kernel": sum(
            rows / max(1.0, calib.rate("tpu", c, tpu_prior))
            for c in op_classes) * 1e3,
        "compile": compile_ms,
    }
    if ooc_budget > 0 and bytes_in > ooc_budget:
        # out-of-core legs (docs/out_of_core.md): an over-budget input
        # grace-partitions through the spill tier — every input byte
        # crosses the link down once (partition spill) and back up once
        # (partition promote); keys absent when OOC is off so the
        # decision record's shape stays byte-identical
        terms["ooc_spill"] = bw_ms(bytes_in, consts["d2h_mbps"])
        terms["ooc_promote"] = bw_ms(bytes_in, consts["h2d_mbps"])
    tpu_ms = sum(terms.values())
    cpu_ms = sum(rows / max(1.0, calib.rate("cpu", c, cpu_prior))
                 for c in op_classes) * 1e3
    if cpu_ms < tpu_ms:
        engine = "cpu"
        deciding = max(terms, key=terms.get)
    else:
        engine = "tpu"
        deciding = "cpu_compute"
    return {"engine": engine,
            "tpu_ms": round(tpu_ms, 3), "cpu_ms": round(cpu_ms, 3),
            "deciding": deciding, "rows": int(rows),
            "bytes_in": int(bytes_in), "bytes_out": int(bytes_out),
            "pulls": pulls,
            "terms": {k: round(v, 3) for k, v in terms.items()}}


# ---------------------------------------------------------------------------
# Calibration feed: executed-plan observation
# ---------------------------------------------------------------------------

def observe_plan(physical) -> None:
    """Walk an EXECUTED physical tree feeding per-operator throughput
    into the calibration store (the obs QueryProfile walk's snapshot,
    read for rows/wall instead of rendering).  Approximations, by
    design: device operators time their own compute (totalTime is
    self time), host operators time the whole pull (self time =
    total minus direct children), and rates key on INPUT rows (the sum
    of the children's output rows; a leaf's own output) — the same
    rows ``score_ops`` charges.  Keying on output rows inflated
    low-selectivity projections by the inverse selectivity (the
    BENCH_r06 projected ≈ 7.8× actual drift).  Called only with
    placement calibration active; never raises."""
    cal = calibration()
    try:
        _observe_node(physical, cal)
    except Exception as e:
        log.warning("placement calibration observe failed (rates "
                    "unchanged): %s", e)
        return
    from spark_rapids_tpu.compile import store as compile_store
    st = compile_store.current()
    if st is not None:
        cal.save(st.root)


def _observe_node(node, cal: CalibrationStore) -> None:
    snaps = []
    for c in node.children:
        snaps.append(_observe_node(c, cal))
    snap = node.metrics.snapshot()
    total_ns = snap.get("totalTime", 0)
    rows = snap.get("numOutputRows", 0)
    # the rows the operator PROCESSED: its children's combined output
    # (a leaf processes what it produces) — the same rows score_ops
    # charges, so projected and measured rates share a denominator
    in_rows = sum(s.get("numOutputRows", 0) for s in snaps) or rows
    if total_ns and in_rows:
        if node.is_device:
            self_ns = total_ns
        else:
            self_ns = max(0, total_ns - sum(s.get("totalTime", 0)
                                            for s in snaps))
        engine = "tpu" if node.is_device else "cpu"
        steps = getattr(node, "steps", None)
        if engine == "tpu" and steps:
            # a fused TpuStageExec ran its whole step list in one
            # dispatch; record each member op's class (the classes the
            # scorer reads) with an even share of the stage time, so
            # fused project/filter calibration is not dead under
            # fusion's default-on collapse
            share = (self_ns / len(steps)) / 1e9
            for kind, exprs in steps:
                cal.observe(engine, step_class(kind, exprs), in_rows,
                            share)
        else:
            cls = op_class(node.node_name)
            exprs = getattr(node, "exprs", None)
            if exprs is None:
                exprs = getattr(node, "projections", None) or \
                    [getattr(node, "condition", None)]
            cls = step_class(cls, [e for e in exprs if e is not None])
            # an aggregate that folded its filter / project chain
            # (plan/fusion.py) ran the steps in its update program:
            # they share its time evenly, as a stage's members do
            folded = getattr(node, "pre_steps", ())
            share = (self_ns / (1 + len(folded))) / 1e9
            cal.observe(engine, cls, in_rows, share)
            for kind, step_exprs in folded:
                cal.observe(engine, step_class(kind, step_exprs),
                            in_rows, share)
    return snap
