"""Process-wide metrics registry + exporter (docs/observability.md).

Before this module, engine-wide statistics lived in five scattered
module globals (prefetch overlap counters, d2h egress counters, fusion
stats, AQE stats, ICI stats, lifecycle supervision stats) that bench.py
aggregated bespoke.  The registry is the ONE read surface over all of
them plus the log2 latency histograms (``utils/metrics.Histogram``):

* ``snapshot()`` — the full engine-stats dict (``session.engine_stats()``
  returns it; bench.py's summary objects are thin reads of it);
* ``prometheus_text()`` — the same snapshot rendered in Prometheus
  exposition format (``python -m spark_rapids_tpu.obs``);
* ``histogram(name)`` / ``record(name, value)`` — shared fixed-bucket
  histograms recording D2H/H2D latency+bytes, semaphore and staging
  admission waits, XLA compile time, and per-query wall time.  Units
  ride in the name (``*.us`` microseconds, ``*.bytes``).

Recording is gated by ``spark.rapids.sql.obs.enabled`` (a process-wide
flag set at query-scope entry, like the tracing span switch): off makes
``record`` a single flag check.
"""

from __future__ import annotations

import threading
from typing import Dict

from spark_rapids_tpu.utils.metrics import Histogram

# -- histogram names (units in the name; docs/observability.md table) -------

HIST_D2H_PULL_US = "transfer.device_pull.us"
HIST_D2H_PULL_BYTES = "transfer.device_pull.bytes"
HIST_H2D_UPLOAD_US = "transfer.pipelined_h2d.us"
HIST_H2D_UPLOAD_BYTES = "transfer.pipelined_h2d.bytes"
HIST_SEM_WAIT_US = "tpu.semaphore.wait.us"
HIST_STAGING_SPILL_WAIT_US = "staging.spill.wait.us"
HIST_STAGING_PREFETCH_WAIT_US = "staging.prefetch.wait.us"
HIST_STAGING_EGRESS_WAIT_US = "staging.egress.wait.us"
HIST_XLA_COMPILE_US = "xla.compile.us"
HIST_QUERY_WALL_US = "query.wall.us"
# serverAdmitWaitUs: submit -> dispatch latency through the session
# server's weighted-fair admission queue (docs/serving.md) — the
# serving-tier queueing delay bench_serve.py regresses against
HIST_SERVER_ADMIT_WAIT_US = "server.admit.wait.us"
# per-query |projected - actual| / actual of the placement cost model,
# in percent (docs/placement.md "Cost error") — the drift signal the
# BENCH_r06 7.8× projection bug was invisible without; quantiles are
# surfaced inside the `placement` snapshot group
HIST_PLACEMENT_COST_ERROR_PCT = "placement.cost_error.pct"
# standing-query freshness lag: micro-batch detection -> refresh
# completion (docs/streaming.md) — the p99 bench_serve.py's streaming
# mode reports
HIST_STREAM_FRESHNESS_US = "stream.freshness.us"

# canonical staging-wait histogram per waiter class: the ONE table
# tying the HIST_STAGING_* constants to the BufferCatalog limiter
# names (memory/spill.py records through this), so the two spellings
# can never drift into separate histogram keys
STAGING_WAIT_HISTS = {
    "spill": HIST_STAGING_SPILL_WAIT_US,
    "prefetch": HIST_STAGING_PREFETCH_WAIT_US,
    "egress": HIST_STAGING_EGRESS_WAIT_US,
}

_ENABLED = True

_HIST_LOCK = threading.Lock()
_HISTOGRAMS: Dict[str, Histogram] = {}


def set_enabled(on: bool) -> None:
    """Flip the process-wide recording switch (set from
    ``spark.rapids.sql.obs.enabled`` at query-scope entry)."""
    global _ENABLED
    _ENABLED = bool(on)


def enabled() -> bool:
    return _ENABLED


def histogram(name: str) -> Histogram:
    """The process-wide histogram for ``name`` (created on first use)."""
    h = _HISTOGRAMS.get(name)
    if h is not None:
        return h
    with _HIST_LOCK:
        h = _HISTOGRAMS.get(name)
        if h is None:
            h = Histogram(name)
            _HISTOGRAMS[name] = h
        return h


def record(name: str, value) -> None:
    """Record one observation; a no-op (one flag read) when obs is off."""
    if _ENABLED:
        histogram(name).record(value)


def histogram_snapshots() -> Dict[str, dict]:
    with _HIST_LOCK:
        hists = dict(_HISTOGRAMS)
    return {name: h.snapshot() for name, h in sorted(hists.items())}


def reset_histograms() -> None:
    with _HIST_LOCK:
        hists = list(_HISTOGRAMS.values())
    for h in hists:
        h.reset()


# -- the unified snapshot ---------------------------------------------------

def _catalog_stats() -> dict:
    from spark_rapids_tpu.runtime import TpuRuntime
    rt = TpuRuntime._instance
    if rt is None:
        return {"device_bytes": 0, "host_bytes": 0, "disk_bytes": 0,
                "spill_to_host": 0, "spill_to_disk": 0, "unspill": 0,
                "demote_failures": 0, "budget_spills": 0,
                "budget_exceeded": 0}
    cat = rt.catalog
    return {"device_bytes": cat.device_bytes,
            "host_bytes": cat.host_bytes,
            "disk_bytes": cat.disk_bytes,
            "spill_to_host": cat.spill_to_host_count,
            "spill_to_disk": cat.spill_to_disk_count,
            "unspill": cat.unspill_count,
            "demote_failures": cat.demote_failure_count,
            "budget_spills": cat.budget_spill_count,
            "budget_exceeded": cat.budget_exceeded_count}


def _kernel_cache_stats() -> dict:
    from spark_rapids_tpu.utils import kernel_cache
    per = kernel_cache.all_stats()
    agg = {"caches": len(per), "entries": 0, "hits": 0, "misses": 0,
           "evictions": 0}
    for st in per.values():
        agg["entries"] += st["size"]
        agg["hits"] += st["hits"]
        agg["misses"] += st["misses"]
        agg["evictions"] += st["evictions"]
    return agg


def _compressed_stats_snapshot() -> dict:
    from spark_rapids_tpu.columnar import encoding
    raw = encoding.compressed_stats()
    out = {"encodedColumns": raw.pop("encoded_columns"),
           "lateDecodes": raw.pop("late_decodes"),
           "compressedBytesSaved": raw.pop("bytes_saved")}
    out.update(raw)
    return out


def _ooc_stats_snapshot() -> dict:
    from spark_rapids_tpu.exec import ooc
    return ooc.ooc_stats()


def _stream_stats_snapshot() -> dict:
    from spark_rapids_tpu.stream import stats as stream_stats
    return stream_stats.global_stats()


def snapshot() -> dict:
    """The full engine-stats dict: every previously-scattered global
    stats object under one key each, plus spill-catalog gauges, the
    kernel-cache aggregate, journal counters, and the histogram
    snapshots.  ``session.engine_stats()`` and bench.py read this."""
    from spark_rapids_tpu import health, lifecycle
    from spark_rapids_tpu.columnar import encoding, transfer
    from spark_rapids_tpu.compile import service as compile_service
    from spark_rapids_tpu.exec import aqe, joins, meshexec, stage
    from spark_rapids_tpu.io import parquet as scan_io
    from spark_rapids_tpu.io import prefetch
    from spark_rapids_tpu.fleet import stats as fleet_stats
    from spark_rapids_tpu.obs import journal
    from spark_rapids_tpu.plan import placement
    from spark_rapids_tpu.server import stats as server_stats
    from spark_rapids_tpu.utils import tracing
    return {
        "prefetch": prefetch.global_stats(),
        "d2h": transfer.d2h_stats(),
        # the device scan cache, counted where it is looked up
        # (io/parquet.py cached_device_scan): lookups, hits, the device
        # bytes the misses decoded and uploaded, and their host cost
        "scan": scan_io.scan_stats(),
        # the one-chip hash join, counted once a join where it ends
        # (exec/joins.py TpuHashJoinExec._run): joins by the route that
        # produced their rows, broadcast build sides, rows and handed-on
        # capacity as the host knows them, build and probe microseconds
        "join": joins.join_stats(),
        # compressed-domain execution trajectory (docs/compressed.md):
        # `encodedColumns` (columns ingested as codes), `lateDecodes`
        # (separate decode dispatches — the escape hatch), and
        # `compressedBytesSaved` (raw-minus-wire, both link directions)
        # are the snapshot spellings of these counters
        "compressed": _compressed_stats_snapshot(),
        "fusion": stage.global_stats(),
        # the persistent compilation service (docs/compile_cache.md):
        # store hit/miss/bytes counters, the cold-vs-store-hit split of
        # measured compile time, warm-pool counters, ladder bounds
        "compile": compile_service.snapshot(),
        # the dispatch ledger (docs/observability.md, "Programs"):
        # launches per program family, always counted; device and
        # host-starved microseconds under spark.rapids.sql.trace.enabled
        "programs": compile_service.programs_snapshot(),
        # the same ledger's rows by role, apart from the families: every
        # aggregate update program, whatever its body, and no merge
        "roles": compile_service.roles_snapshot(),
        # where a query's wall time went on the host: planning,
        # executing, and blocked in a device read
        "phases": tracing.phase_stats(),
        "aqe": aqe.global_stats(),
        # cost-based hybrid placement (docs/placement.md): fragments
        # per engine, AQE runtime demotions, degraded passes, and the
        # projected-vs-actual cost accounting bench.py derives its
        # per-suite cost error from
        "placement": placement.global_stats(),
        "ici": meshexec.ici_stats(),
        # out-of-core device execution (docs/out_of_core.md): grace
        # partitions/runs written, bytes through the partition-spill
        # seam, re-salted recursions, counted host fallbacks, promote
        # dispatch overlap, and device merge steps
        "ooc": _ooc_stats_snapshot(),
        "lifecycle": lifecycle.global_stats(),
        "health": health.global_stats(),
        "kernel_cache": _kernel_cache_stats(),
        "catalog": _catalog_stats(),
        "server": server_stats.global_stats(),
        # the serving fleet's router-side counters (docs/serving.md,
        # "Serving fleet"): routing/overflow, failovers, quarantines,
        # probes, replica deaths and restarts.  Replica-process serving
        # counters live in each replica's own snapshot
        # (FleetRouter.replica_stats)
        "fleet": fleet_stats.global_stats(),
        # continuous queries (docs/streaming.md): tailing-source
        # ticks/batches, standing-query refresh outcomes (incremental
        # vs counted recompute vs error), and maintained-cache-entry
        # counters.  All zeros with spark.rapids.stream.* unset — the
        # conf-off engine never writes this group
        "stream": _stream_stats_snapshot(),
        "journal": journal.stats(),
        "histograms": histogram_snapshots(),
    }


# -- Prometheus exposition --------------------------------------------------

_PREFIX = "spark_rapids_tpu"


def _sanitize(name: str) -> str:
    return "".join(c if c.isalnum() or c == "_" else "_" for c in name)


def prometheus_text() -> str:
    """Render ``snapshot()`` in Prometheus text exposition format:
    scalar stats as gauges ``spark_rapids_tpu_<group>_<key>``,
    histograms as summaries with ``quantile`` labels plus ``_count`` /
    ``_sum`` series (``python -m spark_rapids_tpu.obs``)."""
    snap = snapshot()
    lines = []
    for group, stats in snap.items():
        if group == "histograms":
            continue
        for key, value in sorted(stats.items()):
            if isinstance(value, bool):
                value = int(value)
            if not isinstance(value, (int, float)):
                continue  # non-numeric detail (paths) stays JSON-only
            metric = f"{_PREFIX}_{_sanitize(group)}_{_sanitize(key)}"
            lines.append(f"# TYPE {metric} gauge")
            lines.append(f"{metric} {value}")
    for name, snp in snap["histograms"].items():
        metric = f"{_PREFIX}_{_sanitize(name)}"
        lines.append(f"# TYPE {metric} summary")
        for q in ("p50", "p90", "p99"):
            quant = int(q[1:]) / 100
            lines.append(f'{metric}{{quantile="{quant}"}} {snp[q]}')
        lines.append(f"{metric}_count {snp['count']}")
        lines.append(f"{metric}_sum {snp['sum']}")
    return "\n".join(lines) + "\n"
