#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell (``workloads/<cell>.json``) and its configuration
(``configs/<config>.json``), makes the data from ``--seed``, brings the
engine up under the configuration's ``conf`` through the normal entry point,
warms every program the cell's traffic uses (all of that is ``setup_s``),
drives the window in whole rounds, frees the engine, and only then runs the
plain reference and compares every answer the window returned.  It knows two
kinds of traffic (``stream``, ``served``) and nothing about any suite, query or
metric: those are files found by the names in ``BENCHMARK.json``
(``README.md``).  One process, one chip per device the configuration asks
for; without a TPU it exits 2 and prints no result.  Every line on standard
output is one JSON object; the last is the result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import io  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DATA_ROOT = os.path.join(HERE, ".data")    # ignored; one seed at a time
TRACE_ROOT = os.path.join(HERE, ".trace")  # ignored; emptied after reading
FALLBACK_COUNTERS = ("ici.fallbacks", "ooc.fallbacks", "compile.aotFailures",
                     "fusion.warm_errors")
ANSWER_WAIT_S = 60  # how long past the close a served answer is waited for
STALL_FACTOR = 10   # a round over this many times the median is a stall
LONGEST = 5         # how many stalls and collections the window line lists
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


def load_module(*parts: str):
    """A file under ``benchmark/`` as a module, by path: no package, so a
    later PR's file needs no entry anywhere."""
    path = os.path.join(HERE, *parts)
    name = "bench_" + "_".join(parts).replace(".py", "").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def device_gate(chips: int) -> dict:
    """Exit 2, with no result, unless JAX's default backend is a TPU with
    at least ``chips`` devices."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu" or len(devices) < chips:
        sys.stderr.write(
            f"benchmark: needs {chips} TPU device(s), JAX found "
            f"{len(devices)} x {d.platform!r} ({d.device_kind}); "
            "refusing to run\n")
        raise SystemExit(2)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(device: dict) -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:device["count"]]]
    if any(p is None for p in peaks):
        if device["platform"] == "tpu":
            raise RuntimeError("memory_stats() reports no peak_bytes_in_use")
        return 0  # a backend without the counter: only a rehearsal gets here
    return int(max(peaks))


class CompileClock:
    """When JAX compiled a program or fetched one from its persistent cache
    (``jax.monitoring``): one timestamp per event, and the running totals."""

    def __init__(self):
        import jax.monitoring
        self._lock = threading.Lock()
        self.times = []
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name: str, secs: float, **_) -> None:
        if name == _COMPILE_EVENT:
            with self._lock:
                self.times.append(time.perf_counter())

    def _event(self, name: str, **_) -> None:
        key = {"/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "misses"}.get(name)
        if key:
            with self._lock:
                self.cache[key] += 1


def ensure_data(datagen, suite: str, rows: int, seed: int) -> dict:
    """The suite's tables from ``seed`` under ``.data/``, reused when
    complete; any other seed's directory goes first, so a dozen seeds never
    pile up in the checkout."""
    out = os.path.join(DATA_ROOT, f"{suite}-{rows}-seed{seed}")
    os.makedirs(DATA_ROOT, exist_ok=True)
    for other in os.listdir(DATA_ROOT):
        if os.path.join(DATA_ROOT, other) != out:
            shutil.rmtree(os.path.join(DATA_ROOT, other), ignore_errors=True)
    done = os.path.join(out, "_COMPLETE")
    reused = os.path.exists(done)
    if reused:
        paths = {n: os.path.join(out, f"{n}.parquet")
                 for n in datagen.TABLES}
    else:
        shutil.rmtree(out, ignore_errors=True)
        paths = datagen.generate(out, rows, seed)
        with open(done, "w") as fh:
            fh.write("ok\n")
    emit({"phase": "data", "dir": os.path.relpath(out, ROOT),
          "reused": reused,
          "bytes": sum(os.path.getsize(p) for p in paths.values())})
    return paths


class GcClock:
    """The collector's pauses while it is entered (``gc.callbacks``: two
    clock reads a collection): ``(start, generation, seconds)`` of each.
    The collector itself is left as a deployment runs it."""

    def __init__(self):
        self.pauses = []
        self._began = None

    def __call__(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._began = now
        elif self._began is not None:
            self.pauses.append((self._began, info["generation"],
                                now - self._began))
            self._began = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)

    def summary(self, since: float) -> dict:
        """Collections by generation, their total seconds, and the longest
        as ``[seconds into the window, generation, seconds]``."""
        by_generation = [0, 0, 0]
        for _, generation, _ in self.pauses:
            by_generation[generation] += 1
        worst = sorted(self.pauses, key=lambda p: -p[2])[:LONGEST]
        return {"collections": by_generation,
                "seconds": sum(p[2] for p in self.pauses),
                "longest": [[round(t - since, 6), g, round(s, 6)]
                            for t, g, s in worst]}


def executed_nodes(sess):
    """The nodes of the last executed plan (``OperatorProfile``: ``name``,
    ``describe``, ``metrics``, ``children``), parents first."""
    stack = [sess.last_query_profile().root]
    while stack:
        node = stack.pop()
        yield node
        stack.extend(node.children)


def off_device_nodes(df) -> list:
    """The lines of ``explain()`` that tag a node off the device."""
    with contextlib.redirect_stdout(io.StringIO()):
        text = df.explain()
    return [ln.strip() for ln in text.splitlines()
            if ln.strip().startswith(("!", "Cpu"))]


def cache_is_empty(path: str) -> bool:
    return not os.path.isdir(path) or not os.listdir(path)


# ---------------------------------------------------------------------------
# the two kinds of traffic
# ---------------------------------------------------------------------------

def whole_rounds(one_round, start: float, seconds: float,
                 traced_rounds=None) -> None:
    """Call ``one_round`` back to back: another starts only if the time
    since ``start`` plus the last round's duration fits in ``seconds``;
    always at least one.  So a run's work is a whole number of rounds, the
    same from run to run, and nothing is cut off at the close.  A traced
    run makes ``traced_rounds`` instead."""
    rounds = 0
    while True:
        t0 = time.perf_counter()
        one_round()
        rounds += 1
        now = time.perf_counter()
        if traced_rounds is not None:
            if rounds >= traced_rounds:
                return
        elif now - start + (now - t0) > seconds:
            return


def rounds_of(executions, per_round: int) -> list:
    """The executions as the rounds ``whole_rounds`` made of them: each
    stream's, in order, cut into rounds of ``per_round``.  ``[{stream,
    start, seconds, queries, ok}]`` in the order they started; a round's
    ``seconds`` are the sum of its executions' own ``end - start``, so
    nothing the harness does between two executions is in them."""
    by_stream = {}
    for e in sorted(executions, key=lambda e: e["start"]):
        by_stream.setdefault(e.get("stream", 0), []).append(e)
    out = []
    for stream, mine in by_stream.items():
        for i in range(0, len(mine) - per_round + 1, per_round):
            part = mine[i:i + per_round]
            out.append({"stream": stream, "start": part[0]["start"],
                        "seconds": sum(e["end"] - e["start"] for e in part),
                        "queries": per_round,
                        "ok": all(e["ok"] for e in part)})
    return sorted(out, key=lambda r: r["start"])


def rounds_summary(rounds, streams: int, since: float) -> dict:
    """What a run says of its rounds: how many, the median, the highest
    percentile with ten rounds beyond it, the longest, and the rounds over
    ``STALL_FACTOR`` times the median (``[stream, seconds into the window,
    seconds]`` of the longest few).  ``median_s_per_query`` is the median
    round over its queries and the ``streams`` that run at once: what
    ``query_s`` would read if every round took as long as the middle one,
    a diagnostic that no stall moves and no bound holds."""
    took = sorted(r["seconds"] for r in rounds if r["ok"])
    out = {"count": len(rounds), "failed": len(rounds) - len(took)}
    if not took:
        return out
    median = statistics.median(took)
    out["median_s_per_query"] = median / (rounds[0]["queries"] * streams)
    slow = sorted((r for r in rounds
                   if r["ok"] and r["seconds"] > STALL_FACTOR * median),
                  key=lambda r: -r["seconds"])
    out.update(median_s=median, longest_s=took[-1])
    if len(took) > 10:
        out["tail"] = {"percentile": 100.0 * (len(took) - 10) / len(took),
                       "seconds": took[-11]}
    out["stalls"] = {"count": len(slow),
                     "seconds": sum(r["seconds"] for r in slow),
                     "longest": [[r["stream"], round(r["start"] - since, 6),
                                  round(r["seconds"], 6)]
                                 for r in slow[:LONGEST]]}
    return out


class Stream:
    """Kind ``stream``: one client replays the cell's ordered list of
    queries in whole passes."""

    streams = 1

    def __init__(self, cell, config, sess, tables, seed):
        self.cell, self.sess, self.tables = cell, sess, tables
        self.builders = load_module("queries", query_suite(cell, config)
                                    + ".py")
        self.names = list(cell["queries"])
        self.per_round = len(self.names)
        self.keep_plans = False  # a traced window's per-layer metrics do
        self.plans = []      # then: the executed plan of every timed query
        self.cpu_nodes = []  # always: every executed plan's ``Cpu*`` nodes
        self.explained = {}  # one DataFrame of each distinct query

    def _execute(self, name: str) -> dict:
        from jax.profiler import TraceAnnotation
        start = time.perf_counter()
        with TraceAnnotation(f"bench.build:{name}"):
            df = self.builders.build(name, self.tables)
        with TraceAnnotation(f"bench.to_arrow:{name}"):
            table = df.to_arrow()
        end = time.perf_counter()
        # what follows lies between two executions, inside the window and
        # so inside ``query_s``: it keeps only what is read after the close.
        # ``explain()`` plans anew from the DataFrame's logical plan and the
        # session's conf, and a builder makes the same logical plan of the
        # same name and tables every time: one DataFrame a query will do
        nodes = list(executed_nodes(self.sess))
        self.cpu_nodes += [n.describe for n in nodes
                           if n.name.startswith("Cpu")]
        if self.keep_plans:
            self.plans.append([{"name": n.name, "describe": n.describe,
                                "metrics": n.metrics} for n in nodes])
        self.explained.setdefault(name, df)
        return {"name": name, "params": None, "start": start, "end": end,
                "ok": True, "table": table}

    def warm(self) -> None:
        for name in self.names:
            self._execute(name)
        self.cpu_nodes.clear()
        self.explained.clear()

    def window(self, seconds: float, traced: bool) -> list:
        """Whole passes of the list (``whole_rounds``); a traced run makes
        ``traced_passes`` and keeps their plans."""
        self.keep_plans = traced
        done = []
        whole_rounds(
            lambda: done.extend(self._execute(n) for n in self.names),
            time.perf_counter(), seconds,
            int(self.cell.get("traced_passes", 1)) if traced else None)
        return done

    def off_device(self) -> list:
        """Nodes off the device among the plans the window executed: every
        executed plan's own ``Cpu*`` nodes, and what ``explain()`` tags on
        each distinct query that ran."""
        return self.cpu_nodes + [ln for df in self.explained.values()
                                 for ln in off_device_nodes(df)]

    def reference(self, ref, paths, execution):
        return ref.QUERIES[execution["name"]](paths)


class Served:
    """Kind ``served``: closed-loop client streams over prepared templates
    through ``session.server()``; each sends its next request when its last
    returns."""

    def __init__(self, cell, config, sess, tables, seed):
        traffic = load_module("traffic.py")
        self.cell = cell
        self.traffic = traffic.ServedTraffic(
            cell, os.path.join(HERE, "queries"), seed)
        for name, df in tables.items():
            sess.register_view(name, df)
        self.server = sess.server()
        self.stmts = {n: self.server.prepare(self.traffic.sql[n])
                      for n in self.traffic.templates}
        self.streams = self.traffic.streams
        self.per_round = len(self.traffic.cycle)
        self.plans = []  # a served request's plan is not exposed
        self.sent = set()  # every (template, binding) the window sent

    def _request(self, name: str, params: tuple, wait_s: float) -> dict:
        from jax.profiler import TraceAnnotation
        self.sent.add((name, params))
        start = time.perf_counter()
        out = {"name": name, "params": params, "start": start, "ok": False,
               "table": None}
        try:
            with TraceAnnotation(f"bench.submit:{name}"):
                ticket = self.server.submit(self.stmts[name], params=params)
            with TraceAnnotation(f"bench.result:{name}"):
                out["table"] = ticket.result(wait_s)
            out["ok"] = True
        except Exception as e:  # a refused, failed or unanswered request
            out["error"] = repr(e)
        out["end"] = time.perf_counter()
        return out

    def warm(self) -> None:
        for name in self.traffic.templates:
            r = self._request(name, self.traffic.warm(name), 1200)
            if not r["ok"]:
                raise RuntimeError(f"warm-up of {name} failed: {r['error']}")
        self.sent.clear()

    def window(self, seconds: float, traced: bool) -> list:
        """Every stream sends whole cycles of the cell's mix
        (``whole_rounds``); a traced run makes ``traced_cycles`` each."""
        start = time.perf_counter()
        traced_cycles = int(self.cell.get("traced_cycles", 1)) \
            if traced else None
        results = [[] for _ in range(self.streams)]

        def client(i: int) -> None:
            requests = self.traffic.stream(i)
            whole_rounds(
                lambda: results[i].extend(
                    self._request(name, params, seconds + ANSWER_WAIT_S)
                    for name, params in itertools.islice(requests,
                                                         self.per_round)),
                start, seconds, traced_cycles)

        threads = [threading.Thread(target=client, args=(i,),
                                    name=f"bench-stream-{i}")
                   for i in range(self.streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        done = [dict(r, stream=i) for i, rs in enumerate(results)
                for r in rs]
        return sorted(done, key=lambda r: r["start"])

    def off_device(self) -> list:
        """What ``explain()`` tags off the device on the bound statement of
        every distinct binding the window sent (the executed plan of a
        served request is not exposed)."""
        return [ln for name, params in sorted(self.sent)
                for ln in off_device_nodes(self.stmts[name].bind(*params))]

    def reference(self, ref, paths, execution):
        return ref.TEMPLATES[execution["name"]](paths, execution["params"])


KINDS = {"stream": Stream, "served": Served}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def cell_metrics(bench: dict, cell_name: str, traced: bool) -> list:
    """Names of the metrics this run reports: the cell's end-to-end ones
    without a trace, its per-layer ones with."""
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if not traced:
        return [m["name"] for m in e2e]
    moved = {m["name"] for m in e2e}
    return [m["name"] for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and m["moves"] in moved]


def read_metrics(names, units, run) -> dict:
    out = {}
    for name in names:
        spec = load_json("metrics", f"{name}.json")
        reader = load_module("readers", spec["reader"] + ".py")
        value = reader.read(run, **spec.get("args", {}))
        if value is not None:  # nothing to read: the metric is left out
            out[name] = {"value": value, "unit": units[name]}
    return out


def query_suite(cell: dict, config: dict) -> str:
    """The suite whose builders and reference the cell's queries come from:
    the cell's own, where a later PR brought queries as files of their own,
    or else the configuration's (which always names the data)."""
    return cell.get("suite", config["suite"])


def judge(cell: dict, config: dict, traffic, executions, paths,
          fallbacks: dict) -> dict:
    """Every answer the window returned against the plain reference's, and
    the no-fallback counts: ``{name: [number, limit]}``."""
    compare = load_module("compare.py")
    ref = load_module("reference", query_suite(cell, config) + ".py")
    g = config["guarantees"]
    memo, results = {}, []
    for e in executions:
        if not e["ok"]:
            continue
        key = (e["name"], e["params"])
        if key not in memo:
            memo[key] = traffic.reference(ref, paths, e)
        results.append((e["name"], compare.compare_tables(
            e["table"], memo[key], floor=g["float_floor"])))
    total = compare.worst(results)
    gaps = {f"gap.{k}": [v, compare.gap_limit(g, k)]
            for k, v in sorted(total["gaps"].items())}
    return {
        "exact_mismatches": [total["exact_mismatches"],
                             g["exact_mismatches_limit"]],
        **gaps,
        "unanswered": [sum(not e["ok"] for e in executions),
                       g["unanswered_limit"]],
        "off_device_nodes": [len(fallbacks["off_device_nodes"])
                             + fallbacks["counters"],
                             g["off_device_nodes_limit"]],
        "answers_compared": [len(results), None],
    }


def at(stats: dict, path: str):
    for key in path.split("."):
        stats = stats[key]
    return stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    traced = bool(args.trace)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next((w for w in bench["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        sys.stderr.write(f"benchmark: no workload {args.workload!r} in "
                         "BENCHMARK.json\n")
        return 2
    cell = load_json("workloads", f"{args.workload}.json")
    config = load_json("configs", f"{entry['config']}.json")
    device = device_gate(int(entry["chips"]))
    emit({"phase": "start", "workload": args.workload, "seed": args.seed,
          "seconds": args.seconds, "trace": args.trace})

    # XLA's persistent cache at a fixed path inside the checkout: the engine
    # takes JAX_COMPILATION_CACHE_DIR where it is set and sets no other
    cache_dir = os.path.join(ROOT, ".jax_cache", device["platform"])
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    first_run = cache_is_empty(cache_dir)  # this run compiles everything
    sys.path.insert(0, ROOT)
    import jax

    from spark_rapids_tpu.session import TpuSession
    clock = CompileClock()
    datagen = load_module("datagen", config["suite"] + ".py")
    paths = ensure_data(datagen, config["suite"], config["scale_rows"],
                        args.seed)
    builder = TpuSession.builder()
    for key, value in config["conf"].items():
        builder = builder.config(key, value)
    if traced:  # the program's own spans land in the profile
        builder = builder.config("spark.rapids.sql.trace.enabled", "true")
    sess = builder.get_or_create()
    trace_dir = os.path.join(TRACE_ROOT, args.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        tables = {n: sess.read.parquet(p) for n, p in paths.items()}
        traffic = KINDS[cell["kind"]](cell, config, sess, tables, args.seed)
        t_warm = time.perf_counter()
        traffic.warm()
        emit({"phase": "warm", "seconds": time.perf_counter() - t_warm,
              "programs": len(clock.times), "cache": dict(clock.cache),
              "cache_dir": jax.config.jax_compilation_cache_dir})

        stats_before = sess.engine_stats()
        collector = GcClock()
        window_start = time.perf_counter()
        setup_s = window_start - T_START
        if traced:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            try:
                with jax.profiler.TraceAnnotation("bench.window"), collector:
                    executions = traffic.window(args.seconds, True)
            finally:
                jax.profiler.stop_trace()
        else:
            with collector:
                executions = traffic.window(args.seconds, False)
        window_end = time.perf_counter()
        stats_after = sess.engine_stats()
        device["memory_peak_bytes"] = memory_peak_bytes(device)
        fallbacks = {
            "off_device_nodes": traffic.off_device(),
            "counters": sum(at(stats_after, c) for c in FALLBACK_COUNTERS)}
        plans = traffic.plans
    finally:
        sess.stop()  # the engine's state is freed before the reference runs

    reduced = None
    if traced:
        reducer = load_module("trace", "reduce.py")
        t0 = time.perf_counter()
        reduced = reducer.reduce(reducer.load_xplane(
            reducer.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        emit({"phase": "trace", "reduce_seconds": time.perf_counter() - t0,
              "device_events": reduced["events"]})
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

    rounds = rounds_of(executions, traffic.per_round)
    last_end = max([e["end"] for e in executions] + [window_start])
    run = SimpleNamespace(
        executions=executions, streams=traffic.streams,
        window_start=window_start, window_end=last_end, setup_s=setup_s,
        compile_times=list(clock.times),
        stats_before=stats_before, stats_after=stats_after, plans=plans,
        trace=reduced, config=config, cell=cell)
    if traced:
        # read only by a reader that has a trace to divide by: an unknown
        # device kind is an error there, never a default
        run.load_peaks = lambda: reducer.load_peaks(
            os.path.join(HERE, "trace", "peaks.json"), device["kind"])
        roofline = ("rooflines", f"{entry['config']}.json")
        run.roofline = load_json(*roofline) \
            if os.path.exists(os.path.join(HERE, *roofline)) else {}
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = read_metrics(cell_metrics(bench, args.workload, traced),
                           units, run)

    emit({"phase": "window", "seconds": window_end - window_start,
          "compiles_in_window": sum(window_start <= t <= window_end
                                    for t in clock.times),
          # all the work over all the time, as ``query_s`` reads it
          # (``readers/seconds_per_query.py``), beside the rounds
          "mean_s_per_query": (last_end - window_start)
          / max(1, sum(e["ok"] for e in executions)),
          "rounds": rounds_summary(rounds, traffic.streams, window_start),
          "gc": collector.summary(window_start),
          "executions": [[e.get("stream", 0), e["name"],
                          round(e["start"] - window_start, 6),
                          round(e["end"] - e["start"], 6)]
                         for e in executions],
          "errors": [e["error"] for e in executions if not e["ok"]][:5],
          "off_device_nodes": fallbacks["off_device_nodes"][:5]})
    t0 = time.perf_counter()
    compared = judge(cell, config, traffic, executions, paths, fallbacks)
    emit({"phase": "reference", "seconds": time.perf_counter() - t0})
    correct = all(limit is None or number <= limit
                  for number, limit in compared.values())
    result = {"correct": correct, "attempted": len(executions),
              "failed": sum(not e["ok"] for e in executions),
              "metrics": metrics, "device": device,
              # the run that found no compiled program in the checkout: its
              # setup_s holds every compile, and is reported apart
              "first_run_in_checkout": first_run}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    for k, (v, lim) in compared.items():
        sys.stderr.write(f"compared {k}: {v!r} (limit {lim!r})\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
