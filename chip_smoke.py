#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the engine still starts and
runs on the chip.

Drives the default path once through the entry points a user calls
(``TpuSession.builder().get_or_create()``, the DataFrame builders,
``session.sql()``, ``session.server()``) over TPC-H at the generator's
SF1 ratios, compares every answer with the CPU engine
(``spark.rapids.sql.enabled=false``) on the same files, and fails on any
fallback: a node tagged off-device, an ICI/out-of-core degrade, a failed
AOT compile, a result not resident on the TPU, a Pallas route that did
not lower to a Mosaic custom call.  One process, one chip; ``--chips 4``
runs ONLY the mesh phase (width 1 vs width 4 vs the CPU engine).

    python chip_smoke.py [--seed N] [--chips 1|4]

It refuses to run without a TPU and at any other scale — to rehearse on
the CPU, import its phase functions from a scratch script with a small
``lineitem_rows``.  Every earlier stdout line is one JSON object; the
LAST line is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
DATA_ROOT = os.path.join(ROOT, ".smoke_data")  # ignored, reused if present
LINEITEM_ROWS = 6_000_000  # TPC-H SF1 at gen_tpch's ratios
HOT_RUNS = 2
TABLES = ("region", "nation", "customer", "supplier", "part", "partsupp",
          "orders", "lineitem")

SQL_TEXT = (
    "SELECT year(o_orderdate) AS o_year, count(*) AS n, "
    "sum(l_extendedprice * (1 - l_discount)) AS revenue "
    "FROM lineitem JOIN orders ON l_orderkey = o_orderkey "
    "GROUP BY year(o_orderdate) ORDER BY revenue DESC LIMIT 5")
# functions.contains routes a literal of PALLAS_PATTERN_MIN (24) bytes or
# more to PallasContains.  No TPC-H column here holds unique strings that
# long (the comment columns are six-value dictionaries, which evaluate
# per code), so the needle spans a literal prefix and c_name
NAME_PREFIX = "customer of record: "
NEEDLE = NAME_PREFIX + "Customer#00000012"  # custkeys 120..129

# two prepared templates, one over orders (1.5 M rows) and one over
# customer (150 k).  The phase is about concurrency and re-binding and
# the queries above carry lineitem's scale: two templates over lineitem
# took 219 s of the 1200 s limit and two over orders 86-92 s, against
# 15 s for two over customer (chip runs, PR 21; PERF.md section 6)
SERVER_TEMPLATES = {
    "by_priority": (
        "SELECT o_orderpriority, count(*) AS n, max(o_orderkey) AS last_key "
        "FROM orders WHERE o_custkey < ? GROUP BY o_orderpriority "
        "ORDER BY o_orderpriority",
        [(30000,), (60000,), (90000,), (120000,)]),
    "by_segment": (
        "SELECT c_mktsegment, count(*) AS n, sum(c_nationkey) AS nk "
        "FROM customer WHERE c_acctbal > ? GROUP BY c_mktsegment "
        "ORDER BY c_mktsegment",
        [(0.0,), (2500.0,), (5000.0,), (7500.0,)]),
}

FLOAT_TOLERANCE = {
    "rtol": 5e-3, "atol": 1e-5,
    "why": "the device stores and sums DOUBLE as f32 "
           "(spark.rapids.sql.device.doubleAsFloat defaults on for "
           "accelerators); bench.compare_tables holds integers, dates "
           "and strings exact"}


class SmokeFailure(AssertionError):
    """A phase found the chip path wrong; ends the run non-zero."""


def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileMeter:
    """What JAX itself reports about compiles (``jax.monitoring``):
    persistent-cache hits and misses, and the seconds spent tracing,
    lowering, in the backend compiler and reading the cache."""

    _DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_compile_s",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
    }
    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        import jax.monitoring
        self._lock = threading.Lock()
        self._totals = dict.fromkeys(
            list(self._DURATIONS.values()) + list(self._EVENTS.values()), 0)
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, name: str, **_) -> None:
        key = self._EVENTS.get(name)
        if key:
            with self._lock:
                self._totals[key] += 1

    def _on_duration(self, name: str, secs: float, **_) -> None:
        key = self._DURATIONS.get(name)
        if key:
            with self._lock:
                self._totals[key] += secs

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: round(now[k] - before[k], 2) for k in now}

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._totals)


@contextlib.contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    yield
    emit({"phase": name, "seconds": round(time.perf_counter() - t0, 3)})


# ---------------------------------------------------------------------------
# phase 1: device gate and environment
# ---------------------------------------------------------------------------

def device_gate(chips: int) -> dict:
    """Exit before anything else unless JAX's default backend is a TPU
    with at least ``chips`` devices."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: needs a TPU, JAX found {d.platform!r} "
            f"({d.device_kind}); refusing to run\n")
        raise SystemExit(2)
    if len(devices) < chips:
        sys.stderr.write(
            f"chip_smoke: --chips {chips} needs {chips} devices, JAX "
            f"found {len(devices)}\n")
        raise SystemExit(2)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def report_environment(sess) -> None:
    import jax
    import jaxlib

    from spark_rapids_tpu.plan import cost
    rt = sess.runtime  # initialises the device runtime (budget, cache)
    stats = rt.device.memory_stats()
    check(stats and stats.get("bytes_limit"),
          f"memory_stats() has no bytes_limit: {stats!r}")
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "unknown"  # distribution metadata only; the run goes on
    cache_dir = jax.config.jax_compilation_cache_dir
    emit({"phase": "device", "jax": jax.__version__,
          "jaxlib": jaxlib.__version__, "libtpu": libtpu,
          "runtime": rt.device.client.platform_version.splitlines()[0],
          "device_kind": rt.device.device_kind,
          "devices": len(jax.devices()),
          "bytes_limit": int(stats["bytes_limit"]),
          "hbm_budget_bytes": int(rt.hbm_budget_bytes),
          "cache_dir": cache_dir,
          "cache_dir_from_env":
              bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
          "cache_entries": _cache_entries(cache_dir),
          "link": cost.probe_link()})


def _cache_entries(cache_dir) -> int:
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return len(os.listdir(cache_dir))


# ---------------------------------------------------------------------------
# phase 2: data
# ---------------------------------------------------------------------------

def ensure_data(seed: int, lineitem_rows: int = LINEITEM_ROWS,
                root: str = DATA_ROOT) -> dict:
    """TPC-H at the generator's SF1 ratios, made from ``seed`` under a
    fixed ignored directory of the checkout and reused when complete."""
    import pyarrow.parquet as pq

    from spark_rapids_tpu.bench import tpch
    out = os.path.join(root, f"tpch-{lineitem_rows}-seed{seed}")
    done = os.path.join(out, "_COMPLETE")
    paths = {n: os.path.join(out, f"{n}.parquet") for n in TABLES}
    reused = os.path.exists(done)
    if not reused:
        paths = tpch.gen_tpch(out, lineitem_rows=lineitem_rows, seed=seed)
        with open(done, "w") as fh:
            fh.write("ok\n")
    rows = {n: pq.ParquetFile(p).metadata.num_rows
            for n, p in paths.items()}
    emit({"phase": "data", "reduced": "SF1",
          "why_reduced": "ROADMAP R1 names SF10; a smoke must stay short",
          "dir": out, "reused": reused, "seed": seed, "rows": rows,
          "bytes": sum(os.path.getsize(p) for p in paths.values())})
    return {"paths": paths, "rows": rows}


# ---------------------------------------------------------------------------
# phase 3: queries through the normal entry points
# ---------------------------------------------------------------------------

def smoke_queries(sess, tables) -> dict:
    """name -> (builder, scanned tables).  Five DataFrame builders, one
    SQL text through the sql.py front end (its single low-cardinality
    integer group key is the Pallas dense-slot aggregate's shape), one
    long-needle contains (the PallasContains route)."""
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.api import col, lit
    from spark_rapids_tpu.bench import tpch
    for name, df in tables.items():
        sess.register_view(name, df)
    return {
        "q1": (lambda: tpch.q1(tables), ("lineitem",)),
        "q6": (lambda: tpch.q6(tables), ("lineitem",)),
        "q3": (lambda: tpch.q3(tables),
               ("customer", "orders", "lineitem")),
        "q5": (lambda: tpch.q5(tables),
               ("customer", "orders", "lineitem", "supplier", "nation",
                "region")),
        "q18": (lambda: tpch.q18(tables),
                ("lineitem", "orders", "customer")),
        "sql_year_revenue": (lambda: sess.sql(SQL_TEXT),
                             ("lineitem", "orders")),
        "contains_name": (
            lambda: tables["customer"]
            .filter(F.contains(F.concat(lit(NAME_PREFIX), col("c_name")),
                               lit(NEEDLE)))
            .agg(F.count(lit(1)).alias("n")),
            ("customer",)),
    }


def _pallas_agg_batches(sess) -> int:
    """``pallasAggBatches`` summed over the last executed plan."""
    total = 0
    stack = [sess._last_plan_result.physical]
    while stack:
        node = stack.pop()
        total += node.metrics["pallasAggBatches"].value
        stack.extend(node.children)
    return total


def run_queries(sess, data: dict, ir_dir: str, meter: CompileMeter,
                hot_runs: int = HOT_RUNS, names=None) -> dict:
    """One cold and ``hot_runs`` hot ``to_arrow()`` per query (all of
    them, or the rehearsal's ``names``); returns name -> {"table",
    "custom_call", "pallas_agg_batches": one count per run}.  Every run
    reports its own seconds, compiles and ``pallasAggBatches``: a hot
    run that still compiles, or that leaves the Pallas kernel, shows on
    its line."""
    from bench import compare_tables
    from spark_rapids_tpu.bench import tpch
    from spark_rapids_tpu.exec.stage import stage_kernel_cache
    tables = tpch.load_tables(sess, data["paths"])
    out = {}
    for name, (build, scanned) in smoke_queries(sess, tables).items():
        if names is not None and name not in names:
            continue
        before = sess.engine_stats()
        stage_before = stage_kernel_cache().stats()
        ir_before = set(os.listdir(ir_dir))
        table, runs = None, []
        for _ in range(1 + hot_runs):
            compiles_before = meter.snapshot()
            kernels_before = sess.engine_stats()["kernel_cache"]["misses"]
            t0 = time.perf_counter()
            again = build().to_arrow()
            seconds = time.perf_counter() - t0
            runs.append({
                "s": round(seconds, 4),
                "compile": meter.since(compiles_before),
                "kernels_compiled":
                    sess.engine_stats()["kernel_cache"]["misses"]
                    - kernels_before,
                "pallas_agg_batches": _pallas_agg_batches(sess)})
            if table is None:
                table = again
            check(compare_tables(again, table),
                  f"{name}: a hot run's answer differs from the cold one")
        after = sess.engine_stats()
        custom = _custom_call_modules(ir_dir, ir_before)
        out[name] = {"table": table, "custom_call": custom,
                     "pallas_agg_batches":
                         [r["pallas_agg_batches"] for r in runs]}
        emit({"phase": "query", "name": name,
              "rows_in": sum(data["rows"][t] for t in scanned),
              "rows_out": table.num_rows,
              "cold_s": runs[0]["s"], "hot_s": [r["s"] for r in runs[1:]],
              "runs": runs,
              "stage_kernels_compiled":
                  stage_kernel_cache().stats()["misses"]
                  - stage_before["misses"],
              "d2h_pulls_per_run":
                  (after["d2h"]["pulls"] - before["d2h"]["pulls"])
                  / len(runs),
              "d2h_bytes_per_run":
                  (after["d2h"]["bytes"] - before["d2h"]["bytes"])
                  / len(runs),
              "tpu_custom_call_modules": custom,
              "last_hot_run_operators": _operator_times(sess)})
    return out


def _operator_times(sess) -> list:
    """[operator, ms, {timer metric: ms}] down the last executed plan, for
    every operator that timed anything.  Dispatch is asynchronous, so a
    time lands on the operator that first waited for the device."""
    out = []
    stack = [sess.last_query_profile().to_dict()["plan"]]
    while stack:
        node = stack.pop()
        timers = {k: round(v / 1e6, 1) if k.endswith("Time") else v
                  for k, v in node["metrics"].items()
                  if k.endswith(("Time", "Ms")) and v}
        if node["time_ms"] or timers:
            out.append([node["describe"][:48], round(node["time_ms"], 1),
                        timers])
        stack.extend(reversed(node["children"]))
    return out


def _custom_call_modules(ir_dir: str, before: set) -> list:
    """Names of the programs lowered since ``before`` whose IR holds a
    Mosaic kernel (``tpu_custom_call``): JAX dumps every program it
    lowers into ``ir_dir`` (``jax_dump_ir_to``)."""
    found = []
    for f in sorted(set(os.listdir(ir_dir)) - before):
        with open(os.path.join(ir_dir, f), errors="replace") as fh:
            if "tpu_custom_call" in fh.read():
                found.append(f)
    return found


# ---------------------------------------------------------------------------
# phase 4: the CPU engine on the same files
# ---------------------------------------------------------------------------

def compare_with_cpu(data: dict, device: dict) -> None:
    from bench import compare_tables
    from spark_rapids_tpu.bench import tpch
    from spark_rapids_tpu.session import TpuSession
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    try:
        tables = tpch.load_tables(cpu, data["paths"])
        emit({"phase": "compare", "engine": "spark.rapids.sql.enabled="
              "false on the same files", "float_tolerance": FLOAT_TOLERANCE})
        for name, (build, _) in smoke_queries(cpu, tables).items():
            t0 = time.perf_counter()
            ref = build().to_arrow()
            ok = compare_tables(device[name]["table"], ref)
            emit({"phase": "compare", "name": name, "match": ok,
                  "rows": ref.num_rows,
                  "cpu_engine_s": round(time.perf_counter() - t0, 3)})
            check(ok, f"{name}: device answer differs from the CPU "
                      f"engine's\n device: "
                      f"{device[name]['table'].slice(0, 5).to_pylist()}\n"
                      f" cpu: {ref.slice(0, 5).to_pylist()}")
    finally:
        cpu.stop()


# ---------------------------------------------------------------------------
# phase 5: nothing hid the device
# ---------------------------------------------------------------------------

def assert_no_fallback(sess, data: dict, device: dict) -> None:
    import jax

    from spark_rapids_tpu.bench import tpch
    tables = tpch.load_tables(sess, data["paths"])
    queries = smoke_queries(sess, tables)
    for name, (build, _) in queries.items():
        with contextlib.redirect_stdout(io.StringIO()):
            text = build().explain()
        off = [ln.strip() for ln in text.splitlines()
               if ln.strip().startswith("!")
               or ln.strip().startswith("Cpu")]
        check(not off, f"{name}: explain() tags nodes off-device: {off}")
    es = sess.engine_stats()
    counters = {"iciFallbacks": es["ici"]["fallbacks"],
                "oocFallbacks": es["ooc"]["fallbacks"],
                "aot_failures": es["compile"]["aotFailures"],
                "warm_errors": es["fusion"]["warm_errors"]}
    check(not any(counters.values()), f"fallback counters: {counters}")

    cols, masks, n = queries["q3"][0]().to_jax()
    leaves = jax.tree_util.tree_leaves((cols, masks))
    where = sorted({d.platform for a in leaves for d in a.devices()})
    check(where == ["tpu"] and n == device["q3"]["table"].num_rows,
          f"to_jax(): {n} rows on {where}")

    agg, contains = device["sql_year_revenue"], device["contains_name"]
    check(sum(agg["pallas_agg_batches"]) > 0 and agg["custom_call"],
          "sql_year_revenue: the Pallas dense-slot aggregate did not run "
          f"as a Mosaic kernel: batches per run {agg['pallas_agg_batches']}"
          f", custom-call programs {agg['custom_call']}")
    check(contains["custom_call"],
          "contains_name: PallasContains lowered no tpu_custom_call")
    emit({"phase": "no_fallback", **counters, "to_jax_devices": where,
          "pallas_agg": {"batches_per_run": agg["pallas_agg_batches"],
                         "programs": agg["custom_call"],
                         "query": "sql_year_revenue (one integer key: "
                                  "the route that probes its range; q1 "
                                  "and q6 take the kernel too, their "
                                  "domain known without a pull)",
                         "why_not_every_batch":
                             "join outputs are fresh buffers: after two "
                             "range-probe pulls the miss gate sends the "
                             "spec's later batches and runs to the sorted "
                             "kernel (ROADMAP D12)"},
          "pallas_contains": {"programs": contains["custom_call"]},
          "spill": {k: es["catalog"][k] for k in
                    ("spill_to_host", "spill_to_disk", "budget_spills")}})


# ---------------------------------------------------------------------------
# phase 6: the session server
# ---------------------------------------------------------------------------

def serve(sess) -> None:
    """Six requests from three threads over two prepared templates with
    re-bound parameters: answers equal the serial ones, and re-binding
    compiles no new stage kernel."""
    from spark_rapids_tpu.exec.stage import stage_kernel_cache
    server = sess.server()
    stmts = {name: server.prepare(sql)
             for name, (sql, _) in SERVER_TEMPLATES.items()}
    for name, (_, bindings) in SERVER_TEMPLATES.items():
        server.submit(stmts[name], params=bindings[0]).result(600)
    misses = stage_kernel_cache().stats()["misses"]

    results, errors = {}, []

    def client(i: int) -> None:
        try:
            for name, (_, bindings) in SERVER_TEMPLATES.items():
                t0 = time.perf_counter()
                table = server.submit(
                    stmts[name], params=bindings[i]).result(600)
                results[(name, i)] = (table, time.perf_counter() - t0)
        except BaseException as e:  # reported below, from the main thread
            errors.append(repr(e))

    threads = [threading.Thread(target=client, args=(i,)) for i in (1, 2, 3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(900)
    check(not errors and not any(t.is_alive() for t in threads),
          f"server clients failed or hung: {errors}")
    new_misses = stage_kernel_cache().stats()["misses"] - misses
    check(len(results) == 6, f"expected 6 answers, got {len(results)}")
    check(new_misses == 0,
          f"re-binding compiled {new_misses} new stage kernels")
    for (name, i), (table, _) in sorted(results.items()):
        serial = stmts[name].execute(*SERVER_TEMPLATES[name][1][i])
        check(table.equals(serial) and table.num_rows > 0,
              f"server answer for {name}{SERVER_TEMPLATES[name][1][i]} "
              "differs from the serial one")
    st = sess.engine_stats()["server"]
    emit({"phase": "server", "requests": len(results), "threads": 3,
          "templates": len(stmts), "new_stage_kernel_misses": new_misses,
          "latency_s": {f"{n}[{i}]": round(s, 4)
                        for (n, i), (_, s) in sorted(results.items())},
          "completed": st["completed"], "failed": st["failed"],
          "result_cache_hits": st["cache_hits"]})
    check(st["failed"] == 0, f"server failed {st['failed']} queries")


# ---------------------------------------------------------------------------
# --chips 4: the mesh phase
# ---------------------------------------------------------------------------

def mesh_queries(tables) -> dict:
    from spark_rapids_tpu.api import col
    from spark_rapids_tpu.bench import tpch
    return {
        "q1": lambda: tpch.q1(tables),
        "q3": lambda: tpch.q3(tables),
        "order_by": lambda: tables["orders"]
        .select("o_orderkey", "o_custkey", "o_orderdate")
        .order_by(col("o_orderdate"), col("o_orderkey")),
    }


MESH_CONFS = {
    "width1": {},
    "ici": {"spark.rapids.shuffle.mode": "ici"},
    "mesh_devices": {"spark.rapids.sql.mesh.devices": "4"},
}


def _run_mesh_mode(mode: str, data: dict, chips: int,
                   meter: CompileMeter) -> dict:
    """One cold and one hot ``to_arrow()`` per mesh query under
    ``MESH_CONFS[mode]``; returns name -> table."""
    from spark_rapids_tpu.bench import tpch
    from spark_rapids_tpu.exec import meshexec
    from spark_rapids_tpu.session import TpuSession
    answers = {}
    sess = TpuSession(dict(MESH_CONFS[mode]))
    try:
        tables = tpch.load_tables(sess, data["paths"])
        for name, build in mesh_queries(tables).items():
            before = meshexec.ici_stats()
            t0 = time.perf_counter()
            answers[name] = build().to_arrow()
            cold_s = time.perf_counter() - t0
            compiles_before = meter.snapshot()
            t0 = time.perf_counter()
            build().to_arrow()
            hot_s = time.perf_counter() - t0
            after = meshexec.ici_stats()
            exchanges = after["exchanges"] - before["exchanges"]
            fallbacks = after["fallbacks"] - before["fallbacks"]
            emit({"phase": "mesh", "mode": mode, "name": name,
                  "width": 1 if mode == "width1" else chips,
                  "rows_out": answers[name].num_rows,
                  "cold_s": round(cold_s, 3), "hot_s": round(hot_s, 3),
                  "hot_compile": meter.since(compiles_before),
                  "ici_exchanges": exchanges,
                  "ici_bytes": after["bytes"] - before["bytes"],
                  "iciFallbacks": fallbacks,
                  "gather_pulls": after["gather_pulls"]
                  - before["gather_pulls"],
                  "operators": _operator_times(sess)})
            check(fallbacks == 0, f"{mode}/{name}: {fallbacks} ICI fallbacks")
            if mode != "width1":
                check(exchanges > 0,
                      f"{mode}/{name}: no collective exchange ran")
    finally:
        sess.stop()
    return answers


def run_mesh(data: dict, meter: CompileMeter, chips: int = 4) -> None:
    """q1, q3 and a global ORDER BY at width 1, under the guarded ICI
    lowering (width = every visible chip) and under the static
    ``mesh.devices`` lowering, in one process driving every chip; each
    against the CPU engine."""
    import jax

    from bench import compare_tables
    from spark_rapids_tpu.bench import tpch
    from spark_rapids_tpu.session import TpuSession
    answers = {mode: _run_mesh_mode(mode, data, chips, meter)
               for mode in ("width1", "ici")}

    # code that has only seen one real chip may put everything on
    # jax.devices()[0]: every chip must have held a share
    peaks = {str(d): (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]}
    emit({"phase": "mesh_memory", "peak_bytes_in_use": peaks})
    check(len(peaks) == chips and min(peaks.values()) > (64 << 20),
          f"a chip stayed (nearly) empty: {peaks}")

    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    try:
        tables = tpch.load_tables(cpu, data["paths"])
        refs = {name: build().to_arrow()
                for name, build in mesh_queries(tables).items()}
    finally:
        cpu.stop()
    # the static lowering last: it builds the same SPMD programs again
    for mode in ("width1", "ici", "mesh_devices"):
        if mode not in answers:
            answers[mode] = _run_mesh_mode(mode, data, chips, meter)
        for name, table in answers[mode].items():
            ok = compare_tables(table, refs[name])
            emit({"phase": "mesh_compare", "mode": mode, "name": name,
                  "match_cpu_engine": ok})
            check(ok, f"{mode}/{name} differs from the CPU engine")


def assert_sharded_planes(data: dict, chips: int = 4) -> None:
    """One exchange program through the ``parallel`` entry point: its
    row-sharded planes sit on ``chips`` distinct devices."""
    import pyarrow.parquet as pq

    from spark_rapids_tpu.columnar.batch import host_batch_to_device
    from spark_rapids_tpu.columnar.dtypes import INT64, Schema
    from spark_rapids_tpu.exprs.aggregates import Count
    from spark_rapids_tpu.exprs.base import Alias, BoundReference
    from spark_rapids_tpu.parallel import DistributedAggregate, data_mesh
    table = pq.read_table(data["paths"]["orders"],
                          columns=["o_custkey", "o_orderkey"])
    batch = host_batch_to_device(
        table.combine_chunks().to_batches()[0],
        Schema.from_arrow(table.schema))
    dist = DistributedAggregate(
        [BoundReference(0, INT64, True, "o_custkey")],
        [Alias(Count(BoundReference(1, INT64, True, "o_orderkey")), "n")],
        mesh=data_mesh(chips))
    n_groups, out_cols = dist.run_sharded(batch)
    homes = {sh.device for planes in out_cols for a in planes
             if a is not None for sh in a.addressable_shards}
    emit({"phase": "mesh_shards", "rows_in": table.num_rows,
          "groups_per_device": [int(n) for n in n_groups],
          "devices": sorted(map(str, homes))})
    check(len(homes) == chips,
          f"the exchange's planes sit on {len(homes)} devices: {homes}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=19)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    device = device_gate(args.chips)
    t_start = time.perf_counter()

    import jax

    from spark_rapids_tpu.session import TpuSession
    with tempfile.TemporaryDirectory(prefix="smoke-ir-") as ir_dir:
        # every program JAX lowers is dumped here; the no-fallback phase
        # reads which of them hold a Mosaic kernel
        jax.config.update("jax_dump_ir_to", ir_dir)
        meter = CompileMeter()
        sess = TpuSession.builder().get_or_create()
        try:
            with phase("device"):
                report_environment(sess)
            with phase("data"):
                data = ensure_data(args.seed)
            if args.chips == 4:
                with phase("mesh"):
                    assert_sharded_planes(data)
                    run_mesh(data, meter)
            else:
                with phase("queries"):
                    results = run_queries(sess, data, ir_dir, meter)
                with phase("compare"):
                    compare_with_cpu(data, results)
                with phase("no_fallback"):
                    assert_no_fallback(sess, data, results)
                with phase("server"):
                    serve(sess)
        finally:
            sess.stop()
        emit({"phase": "total",
              "seconds": round(time.perf_counter() - t_start, 3),
              "compiles": meter.snapshot(),
              "cache_entries": _cache_entries(
                  jax.config.jax_compilation_cache_dir)})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
