"""Engine observability (docs/observability.md): query profiles,
log2 latency histograms, the structured JSONL event journal, the
unified metrics exporter, and the known-metric-names registry.

Reference model: the Spark UI SQL tab the plugin populates (per-operator
GpuMetricNames, GpuExec.scala:25-67) plus the plugin's NVTX/metric
fusion — here surfaced as ``df.explain(analyze=True)``,
``session.engine_stats()``, and the conf-gated journal.  The off==today
guarantee (all ``spark.rapids.sql.obs.*`` keys unset → byte-identical
output) is asserted directly."""

import json
import os

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.obs import journal, registry
from spark_rapids_tpu.utils.metrics import Histogram, MetricSet
from tests.compare import tpu_session


def _df(s, n=1000):
    rng = np.random.default_rng(11)
    return s.create_dataframe(pa.table({
        "k": pa.array(rng.integers(0, 10, n), pa.int64()),
        "v": pa.array(rng.normal(size=n)),
    }))


def _journal_lines(tmp_path):
    out = []
    for fn in os.listdir(tmp_path):
        if fn.startswith("events-") and fn.endswith(".jsonl"):
            with open(os.path.join(tmp_path, fn), encoding="utf-8") as f:
                out.extend(json.loads(line) for line in f)
    return out


# ---------------------------------------------------------------------------
# Histogram
# ---------------------------------------------------------------------------

def test_histogram_empty_snapshot_is_zero():
    h = Histogram("t.us")
    snap = h.snapshot()
    assert snap == {"count": 0, "sum": 0, "mean": 0,
                    "p50": 0, "p90": 0, "p99": 0}


def test_histogram_percentiles_are_bucket_midpoints():
    h = Histogram("t.us")
    for v in [100] * 98 + [100_000] * 2:
        h.record(v)
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["sum"] == 98 * 100 + 2 * 100_000
    # 100 has bit_length 7 -> bucket [64, 128), midpoint 96
    assert snap["p50"] == 96
    assert snap["p90"] == 96
    # p99 lands in 100000's bucket [65536, 131072), midpoint 98304
    assert snap["p99"] == 98304
    assert snap["mean"] == snap["sum"] // 100


def test_histogram_negative_and_zero_values_bucket_to_zero():
    h = Histogram("t.us")
    h.record(-5)
    h.record(0)
    snap = h.snapshot()
    assert snap["count"] == 2 and snap["sum"] == 0 and snap["p99"] == 0


def test_histogram_reset():
    h = Histogram("t.us")
    h.record(42)
    h.reset()
    assert h.snapshot()["count"] == 0


def test_histogram_huge_values_clamp_to_last_bucket():
    h = Histogram("t.us")
    h.record(1 << 200)  # beyond 64 buckets: clamped, never an IndexError
    assert h.snapshot()["count"] == 1


# ---------------------------------------------------------------------------
# registry: recording switch + exporter
# ---------------------------------------------------------------------------

def test_registry_record_is_gated_by_enabled_switch():
    name = "test.gated.us"
    before = registry.histogram(name).snapshot()["count"]
    registry.set_enabled(False)
    registry.record(name, 10)
    assert registry.histogram(name).snapshot()["count"] == before
    registry.set_enabled(True)
    registry.record(name, 10)
    assert registry.histogram(name).snapshot()["count"] == before + 1


def test_registry_histogram_identity():
    assert registry.histogram("test.same.us") is \
        registry.histogram("test.same.us")


def test_snapshot_unifies_every_stats_group():
    snap = registry.snapshot()
    assert set(snap) >= {"prefetch", "d2h", "fusion", "aqe", "ici",
                         "lifecycle", "kernel_cache", "catalog",
                         "journal", "histograms"}
    assert "pulls" in snap["d2h"]
    assert "queries" in snap["lifecycle"] or snap["lifecycle"]


def test_snapshot_has_the_programs_and_phases_groups_flat_and_numeric():
    from spark_rapids_tpu.compile import service
    snap = registry.snapshot()
    assert set(snap["phases"]) == {"plan_us", "execute_us",
                                   "pull_wait_us", "blocking_reads"}
    assert {"dispatches", "device_us", "starved_us", "untimed"} \
        <= set(snap["programs"])
    for fam in service.FAMILIES:
        assert f"{fam}_dispatches" in snap["programs"]
        assert f"{fam}_device_us" in snap["programs"]
    for group in ("programs", "phases"):
        assert all(type(v) is int for v in snap[group].values())


def test_engine_stats_is_the_registry_snapshot():
    s = tpu_session()
    stats = s.engine_stats()
    assert set(stats) == set(registry.snapshot())


def test_prometheus_text_renders_gauges_and_summaries():
    registry.record("test.prom.us", 1000)
    txt = registry.prometheus_text()
    assert "# TYPE spark_rapids_tpu_d2h_pulls gauge" in txt
    assert 'spark_rapids_tpu_test_prom_us{quantile="0.5"}' in txt
    assert "spark_rapids_tpu_test_prom_us_count" in txt
    # the dispatch ledger's two groups export unchanged: flat gauges
    assert "# TYPE spark_rapids_tpu_programs_dispatches gauge" in txt
    assert "spark_rapids_tpu_programs_aggregate_device_us " in txt
    assert "spark_rapids_tpu_programs_starved_us " in txt
    assert "# TYPE spark_rapids_tpu_phases_plan_us gauge" in txt
    assert "spark_rapids_tpu_phases_blocking_reads " in txt
    # every non-comment line is "name{labels}? value"
    for line in txt.strip().splitlines():
        if line.startswith("#"):
            continue
        name, value = line.rsplit(" ", 1)
        assert name and float(value) >= 0


@pytest.mark.slow  # spawns a fresh interpreter (cold jax import)
def test_obs_main_module_dumps_exposition(tmp_path):
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.obs"],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env)
    assert out.returncode == 0
    assert "spark_rapids_tpu_d2h_pulls" in out.stdout


# ---------------------------------------------------------------------------
# journal
# ---------------------------------------------------------------------------

def test_journal_disabled_by_default_and_emit_is_noop():
    assert not journal.enabled()
    journal.emit(journal.EVENT_QUERY_START, query=1)  # must not raise


def test_journal_emit_and_parse(tmp_path):
    journal.configure(str(tmp_path))
    journal.emit(journal.EVENT_SPILL_DEMOTE, query=7,
                 tier_from="device", tier_to="host", bytes=128)
    events = _journal_lines(tmp_path)
    assert len(events) == 1
    ev = events[0]
    assert ev["event"] == "spill_demote"
    assert ev["query"] == 7 and ev["bytes"] == 128
    assert ev["ts"] > 0 and ev["mono"] > 0


def test_journal_is_bounded_by_max_events(tmp_path):
    journal.configure(str(tmp_path), max_events=3)
    for i in range(5):
        journal.emit(journal.EVENT_FAULT_FIRE, query=None, site="s",
                     call=i)
    assert len(_journal_lines(tmp_path)) == 3
    st = journal.stats()
    assert st["written"] == 3 and st["dropped"] == 2


def test_journal_bad_dir_never_raises():
    journal.configure("/proc/definitely/not/writable")
    assert not journal.enabled()
    journal.emit(journal.EVENT_QUERY_START)  # still a no-op


def test_journal_new_dir_resets_counters(tmp_path):
    journal.configure(str(tmp_path / "a"), max_events=1)
    journal.emit(journal.EVENT_QUERY_START)
    journal.emit(journal.EVENT_QUERY_START)
    assert journal.stats()["dropped"] == 1
    journal.configure(str(tmp_path / "b"), max_events=1)
    assert journal.stats()["written"] == 0
    journal.emit(journal.EVENT_QUERY_START)
    assert journal.stats()["written"] == 1


def test_query_scope_journals_lifecycle_events(tmp_path):
    s = tpu_session({"spark.rapids.sql.obs.journalDir": str(tmp_path)})
    _df(s).filter(F.col("v") > 0).collect()
    events = _journal_lines(tmp_path)
    kinds = [e["event"] for e in events]
    assert "query_start" in kinds and "query_finish" in kinds
    start = next(e for e in events if e["event"] == "query_start")
    finish = next(e for e in events if e["event"] == "query_finish")
    assert start["query"] == finish["query"] and start["query"] > 0
    assert finish["status"] == "ok" and finish["wall_ms"] > 0


def test_journal_reopens_after_write_failure(tmp_path):
    """A write error disables the journal, but a later configure with
    the SAME dir must reopen it — the idempotence early-return must not
    pin the journal dead for the process."""
    journal.configure(str(tmp_path))

    class _Boom:
        def write(self, s):
            raise OSError(28, "No space left on device")

        def flush(self):
            pass

        def close(self):
            pass

    journal._FH = _Boom()
    journal.emit(journal.EVENT_QUERY_START, query=1)  # disables, no raise
    assert not journal.enabled()
    journal.configure(str(tmp_path))
    assert journal.enabled()
    journal.emit(journal.EVENT_QUERY_START, query=2)
    assert journal.stats()["written"] == 1


def test_query_scope_without_journal_key_keeps_journal_open(tmp_path):
    """The obs keys are process-global: a session whose conf does not
    mention the journal must not close another session's open journal
    (the per-key guard in lifecycle.query_scope)."""
    journal.configure(str(tmp_path))
    s = tpu_session({"spark.rapids.sql.obs.enabled": "false"})
    _df(s, 100).collect()
    assert journal.enabled()


def test_cap_only_conf_adjusts_bound_without_closing_journal(tmp_path):
    """A conf carrying only journal.maxEvents tightens the cap on the
    already-open journal — it must not close/reopen it (the dir is
    another session's)."""
    journal.configure(str(tmp_path))
    journal.emit(journal.EVENT_QUERY_START)
    s = tpu_session({"spark.rapids.sql.obs.journal.maxEvents": "2"})
    _df(s, 100).collect()
    assert journal.enabled()
    for _ in range(4):
        journal.emit(journal.EVENT_QUERY_START)
    st = journal.stats()
    assert st["written"] == 2 and st["dropped"] >= 3


def test_dir_only_conf_keeps_existing_cap(tmp_path):
    """The symmetric case: a conf carrying only journalDir (same dir)
    must not reset a tighter maxEvents another session configured back
    to the default."""
    journal.configure(str(tmp_path), max_events=5)
    s = tpu_session({"spark.rapids.sql.obs.journalDir": str(tmp_path)})
    _df(s, 100).collect()  # start+finish events fit under the cap
    for _ in range(10):
        journal.emit(journal.EVENT_QUERY_START)
    st = journal.stats()
    assert st["written"] == 5 and st["dropped"] > 0


def test_query_scope_without_obs_keys_leaves_switch_alone(tmp_path):
    registry.set_enabled(False)
    s = tpu_session()  # no obs keys at all
    _df(s, 100).collect()
    assert not registry.enabled()


def test_chaos_run_journals_fault_and_typed_error(tmp_path):
    """The acceptance shape: an injected-fault run with journalDir set
    produces a parseable JSONL journal carrying BOTH the fault_fire and
    the typed query_error/query_finish events, correlated by query id
    (docs/observability.md, "Event journal")."""
    s = tpu_session({
        "spark.rapids.sql.obs.journalDir": str(tmp_path),
        "spark.rapids.faults.transfer.d2h": "always",
    })
    from spark_rapids_tpu.faults import InjectedFault
    with pytest.raises(InjectedFault):
        _df(s).filter(F.col("v") > 0).collect()
    events = _journal_lines(tmp_path)
    fires = [e for e in events if e["event"] == "fault_fire"]
    errors = [e for e in events if e["event"] == "query_error"]
    finishes = [e for e in events if e["event"] == "query_finish"]
    assert fires and fires[0]["site"] == "transfer.d2h"
    assert errors and errors[0]["error"] == "InjectedFault"
    assert errors[0]["typed"] is True
    assert finishes and finishes[0]["status"] == "error"
    assert errors[0]["query"] == finishes[0]["query"]


def test_adaptive_run_journals_stage_and_replan_events(tmp_path):
    """An AQE run journals each materialized stage and each replanning
    decision with its before/after partition specs."""
    rng = np.random.default_rng(3)
    s = tpu_session({
        "spark.rapids.sql.obs.journalDir": str(tmp_path),
        "spark.rapids.sql.adaptive.enabled": "true",
        "spark.sql.autoBroadcastJoinThreshold": -1,
    })
    left = s.create_dataframe(pa.table({
        "k": pa.array(rng.integers(0, 50, 500), pa.int64()),
        "v": pa.array(rng.normal(size=500))}))
    right = s.create_dataframe(pa.table({
        "k": pa.array(np.arange(50, dtype=np.int64)),
        "w": pa.array(rng.normal(size=50))}))
    left.join(right, on="k").to_arrow()
    events = _journal_lines(tmp_path)
    kinds = {e["event"] for e in events}
    assert "stage_materialize" in kinds
    assert "aqe_replan" in kinds
    replan = next(e for e in events if e["event"] == "aqe_replan")
    assert "before_partition_bytes" in replan


# ---------------------------------------------------------------------------
# query profiles
# ---------------------------------------------------------------------------

def test_explain_analyze_renders_executed_plan_with_metrics():
    s = tpu_session()
    txt = _df(s).filter(F.col("v") > 0).select(
        (F.col("v") * 2).alias("d")).explain(analyze=True)
    assert txt.startswith("== Executed plan")
    assert "rows=" in txt and "batches=" in txt
    # non-zero row counts on the executed tree
    assert any(part.startswith("rows=") and part != "rows=0"
               for line in txt.splitlines()
               for part in line.split())


def test_explain_analyze_tpch_q3_with_aqe(tmp_path):
    """The acceptance query: explain(analyze=True) on a TPC-H q3 run
    with AQE on renders the EXECUTED (evolved) plan tree — adaptive
    wrapper and materialized stages as they ran — with non-zero
    per-operator rows and time."""
    from spark_rapids_tpu.bench.tpch import (
        TPCH_QUERIES, gen_tpch, load_tables,
    )
    paths = gen_tpch(str(tmp_path), lineitem_rows=2_000)
    s = tpu_session({"spark.rapids.sql.adaptive.enabled": "true"})
    txt = TPCH_QUERIES["q3"](load_tables(s, paths)).explain(analyze=True)
    assert txt.startswith("== Executed plan (query ")
    assert "TpuAdaptiveSparkPlan" in txt
    rows = [int(p.split("=", 1)[1]) for line in txt.splitlines()
            for p in line.split() if p.startswith("rows=")]
    assert rows and max(rows) > 0
    assert "time=" in txt and "self=" in txt


def test_explain_without_analyze_does_not_execute():
    s = tpu_session()
    txt = _df(s).explain()
    assert "Physical plan:" in txt
    assert s._last_plan_result is None  # nothing ran


def test_last_query_profile_tree_and_dict():
    s = tpu_session()
    assert s.last_query_profile() is None
    _df(s).filter(F.col("v") > 0).collect()
    p = s.last_query_profile()
    assert p is not None
    assert p.query_id and p.wall_ms > 0
    d = p.to_dict()
    assert d["query_id"] == p.query_id

    def rows(node):
        return node["rows"] + sum(rows(c) for c in node["children"])

    assert rows(d["plan"]) > 0
    # self time never exceeds wall time and never goes negative
    def walk(node):
        assert node.self_time_ms >= 0
        assert node.self_time_ms <= node.time_ms + 1e-9
        for c in node.children:
            walk(c)
    walk(p.root)


def test_last_query_metrics_is_byte_identical_to_pre_obs_walk():
    """The legacy flat string is now a thin rendering of the profile
    walk — byte-identical to the pre-obs implementation, which this
    test reimplements against the live plan."""
    s = tpu_session()
    _df(s).filter(F.col("v") > 0).group_by("k").agg(
        F.count(F.col("v")).alias("c")).collect()

    r = s._last_plan_result
    lines = []

    def walk(node, depth):  # the seed implementation, verbatim
        parts = []
        for name, m in sorted(node.metrics.items()):
            if not m.value:
                continue
            if name.lower().endswith("time"):
                parts.append(f"{name}={m.value / 1e6:.1f}ms")
            else:
                parts.append(f"{name}={m.value}")
        lines.append("  " * depth + node.describe()
                     + (": " + ", ".join(parts) if parts else ""))
        for c in node.children:
            walk(c, depth + 1)

    walk(r.physical, 0)
    assert s.last_query_metrics() == "\n".join(lines)


def test_default_profile_dict_schema_is_unchanged_with_the_switch_off():
    """The dispatch ledger adds `programs`, `deviceTime` and
    `deviceDispatches` only to a query run under the trace switch."""
    s = tpu_session()
    _df(s).filter(F.col("v") > 0).group_by("k").agg(
        F.count(F.col("v")).alias("c")).collect()
    d = s.last_query_profile().to_dict()
    assert set(d) == {"query_id", "wall_ms", "plan"}

    def walk(node):
        assert set(node) == {"name", "describe", "rows", "batches",
                             "time_ms", "self_time_ms", "metrics",
                             "children"}
        assert not {"deviceTime", "deviceDispatches"} & set(node["metrics"])
        for c in node["children"]:
            walk(c)
    walk(d["plan"])
    txt = s.last_query_profile().render()
    assert "device=" not in txt and "Programs:" not in txt


def test_traced_profile_adds_device_time_beside_host_time():
    s = tpu_session({"spark.rapids.sql.trace.enabled": "true"})
    txt = _df(s).filter(F.col("v") > 0).group_by("k").agg(
        F.count(F.col("v")).alias("c")).explain(analyze=True)
    d = s.last_query_profile().to_dict()
    assert set(d) == {"query_id", "wall_ms", "plan", "programs"}
    assert any(" device=" in ln and " dispatches=" in ln
               for ln in txt.splitlines())
    assert "deviceTime=" not in txt  # printed once, as device=
    # the legacy flat string prints the two metrics like any other
    assert "deviceDispatches=" in s.last_query_metrics()


def test_query_wall_histogram_records():
    before = registry.histogram(
        registry.HIST_QUERY_WALL_US).snapshot()["count"]
    s = tpu_session()
    _df(s, 100).collect()
    after = registry.histogram(
        registry.HIST_QUERY_WALL_US).snapshot()["count"]
    assert after > before


def test_obs_enabled_false_stops_histogram_recording():
    s = tpu_session({"spark.rapids.sql.obs.enabled": "false"})
    before = registry.histogram(
        registry.HIST_QUERY_WALL_US).snapshot()["count"]
    _df(s, 100).collect()
    after = registry.histogram(
        registry.HIST_QUERY_WALL_US).snapshot()["count"]
    assert after == before


def test_staging_limiter_waits_record_canonical_histograms():
    """The limiter records through registry.STAGING_WAIT_HISTS, the one
    table tying waiter-class names to the HIST_STAGING_* constants —
    an aborted wait records too (time parked is time parked)."""
    from spark_rapids_tpu.memory.spill import HostStagingLimiter
    assert set(registry.STAGING_WAIT_HISTS) == \
        {"spill", "prefetch", "egress"}
    lim = HostStagingLimiter(10, name="spill")
    granted = lim.acquire(10)
    hist = registry.histogram(registry.HIST_STAGING_SPILL_WAIT_US)
    before = hist.snapshot()["count"]
    assert lim.acquire(5, abort=lambda: True) == -1
    assert hist.snapshot()["count"] == before + 1
    lim.release(granted)


# ---------------------------------------------------------------------------
# known-metric-names registry
# ---------------------------------------------------------------------------

def test_metricset_rejects_unknown_name_at_construction():
    with pytest.raises(KeyError, match="unknown metric name"):
        MetricSet("numOutputRowz")


def test_metricset_rejects_unknown_name_at_getitem():
    ms = MetricSet()
    with pytest.raises(KeyError, match="unknown metric name"):
        ms["totalTimee"]


def test_metricset_adhoc_escape_hatches():
    ms = MetricSet("synthetic", adhoc=True)
    ms["another"].add(1)
    assert ms.snapshot()["another"] == 1

    from spark_rapids_tpu.utils.metrics import register_adhoc_metric
    register_adhoc_metric("blessed")
    ms2 = MetricSet()
    ms2["blessed"].add(2)
    assert ms2["blessed"].value == 2


# ---------------------------------------------------------------------------
# metric syncs route through the egress primitive
# ---------------------------------------------------------------------------

def test_metric_pending_sync_counts_as_device_pull():
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import transfer
    from spark_rapids_tpu.utils.metrics import Metric
    m = Metric("numOutputRows")
    m.add(jnp.asarray(41))
    before = transfer.d2h_stats()["pulls"]
    assert m.value == 41
    assert transfer.d2h_stats()["pulls"] == before + 1


def test_metric_pending_sync_is_fault_covered():
    """The transfer.d2h fault site covers metric syncs like every other
    pull — a raw jax.device_get would have dodged it."""
    import jax.numpy as jnp
    from spark_rapids_tpu import faults
    from spark_rapids_tpu.utils.metrics import Metric
    m = Metric("numOutputRows")
    m.add(jnp.asarray(1))
    faults.configure({"transfer.d2h": "always"})
    try:
        with pytest.raises(faults.InjectedFault):
            m.value
    finally:
        faults.reset()
