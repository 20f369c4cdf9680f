"""Cost-based hybrid placement tests (docs/placement.md).

Covers: placement.mode unset/tpu byte-identity (plans, results,
metrics), mode=cpu equality with the CPU engine, mode=cost
result-identity across fuzz + TPC-H q1/q3/q6 + TPCx-BB q3 in both
link regimes, the tiny-string-scan-goes-to-CPU / large-numeric-stays-
on-TPU acceptance shapes (with the zero-device-pull assertion), the
mixed-fragment single-lowering regression (a cost-demoted fragment
around an unsupported op lowers once, no transitions), the AQE
runtime demotion with a deliberately wrong static estimate, the
``plan.place`` fault degrade-to-static contract, link-constant conf
overrides, and calibration/scoring units.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.api import col
from spark_rapids_tpu import functions as F
from spark_rapids_tpu.session import TpuSession
from tests.compare import assert_tables_equal, cpu_session, tpu_session
from tests.fuzzer import gen_table

# link regimes, pinned so no probe runs and decisions are pure
# functions of the plan: REMOTE models the measured BENCH_r05
# attachment (94ms pulls, 45/4 MB/s — small fragments lose), LOCAL a
# fast local link (fragments stay on the device)
REMOTE_LINK = {
    "spark.rapids.sql.placement.pullLatencyMs": "94",
    "spark.rapids.sql.placement.h2dMBps": "45",
    "spark.rapids.sql.placement.d2hMBps": "4",
}
LOCAL_LINK = {
    "spark.rapids.sql.placement.pullLatencyMs": "0.5",
    "spark.rapids.sql.placement.h2dMBps": "100000",
    "spark.rapids.sql.placement.d2hMBps": "100000",
}


def cost_conf(link=REMOTE_LINK, **extra):
    conf = {"spark.rapids.sql.placement.mode": "cost"}
    conf.update(link)
    conf.update(extra)
    return conf


def _write_parquet(tmp_path, name, table):
    path = str(tmp_path / name)
    pq.write_table(table, path)
    return path


def _tiny_string_table(n=1000):
    rng = np.random.default_rng(5)
    return pa.table({
        "k": pa.array(rng.integers(0, 50, n), pa.int64()),
        "s": pa.array([f"name_{i % 13}" for i in range(n)]),
        "v": pa.array(rng.normal(size=n)),
    })


def _large_numeric_table(n=200_000):
    rng = np.random.default_rng(6)
    return pa.table({
        "k": pa.array(rng.integers(0, 1000, n), pa.int64()),
        "v": pa.array(rng.normal(size=n)),
    })


# ---------------------------------------------------------------------------
# Byte-identity of the default mode
# ---------------------------------------------------------------------------

def test_mode_unset_and_tpu_byte_identical(tmp_path):
    """placement.mode unset and =tpu must be byte-identical to each
    other in plans, results, and metric structure — the conf-off
    contract every feature in this engine carries.  (Metric VALUES
    carry wall clocks and cross-run cache effects, so the structural
    comparison is per-operator metric names + row/batch counts.)"""
    table = _tiny_string_table()

    def run(extra, path):
        s = tpu_session(extra)
        try:
            df = (s.read.parquet(path)
                  .filter(col("k") < 25)
                  .select((col("v") * 2.0).alias("a"), col("s")))
            explain = df.explain()
            out = df.to_arrow()
            prof = s.last_query_profile()
            shape = []

            def walk(node, depth):
                shape.append((depth, node.describe, node.rows,
                              node.batches,
                              sorted(k for k, v in node.metrics.items()
                                     if v and not k.lower()
                                     .endswith(("time", "ms", "hits")))))
                for c in node.children:
                    walk(c, depth + 1)
            walk(prof.root, 0)
            return explain, out, shape, prof.placement
        finally:
            s.stop()

    # one identical file per mode: the device scan cache keys on the
    # path, and a cross-run cache hit would change the scan's metric
    # shape for reasons unrelated to placement
    ex0, out0, shape0, place0 = run(
        {}, _write_parquet(tmp_path, "t0.parquet", table))
    ex1, out1, shape1, place1 = run(
        {"spark.rapids.sql.placement.mode": "tpu"},
        _write_parquet(tmp_path, "t1.parquet", table))
    assert ex0 == ex1
    assert out0.equals(out1)
    assert shape0 == shape1
    assert place0 == [] and place1 == []


def test_mode_unset_records_no_placement():
    s = tpu_session()
    try:
        s.create_dataframe(_tiny_string_table(64)).select(
            col("k")).to_arrow()
        assert s._last_plan_result.placement == []
        from spark_rapids_tpu.plan import placement
        st = placement.global_stats()
        assert st["fragments_scored"] == 0
        assert st["queries_observed"] == 0
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# mode=cpu: the A/B baseline
# ---------------------------------------------------------------------------

def test_mode_cpu_equals_cpu_engine(tmp_path):
    path = _write_parquet(tmp_path, "t.parquet", _tiny_string_table())

    def build(s):
        return (s.read.parquet(path)
                .filter(col("k") < 25)
                .group_by(col("s"))
                .agg(F.sum(col("v")).alias("sv"),
                     F.count(col("k")).alias("c"))
                .order_by(col("s")))

    s_place = tpu_session({"spark.rapids.sql.placement.mode": "cpu"})
    s_cpu = cpu_session()
    try:
        from spark_rapids_tpu.plan.planner import plan_query
        t_place = build(s_place).to_arrow()
        t_cpu = build(s_cpu).to_arrow()
        assert t_place.equals(t_cpu)
        # the physical plans must be the SAME CPU-engine plan, not
        # merely equivalent: one conversion path serves both
        p_place = plan_query(build(s_place).plan, s_place.conf)
        p_cpu = plan_query(build(s_cpu).plan, s_cpu.conf)
        assert p_place.physical.tree_string() == \
            p_cpu.physical.tree_string()
        assert "Tpu" not in p_place.physical.tree_string()
    finally:
        s_place.stop()
        s_cpu.stop()


# ---------------------------------------------------------------------------
# mode=cost acceptance shapes
# ---------------------------------------------------------------------------

def test_cost_tiny_string_scan_places_on_cpu_zero_pulls(tmp_path):
    """The headline failure mode BENCH_r05 measured: paying ~94 ms of
    link latency to accelerate a query the CPU engine finishes in
    microseconds.  Under the remote-link constants the 1k-row
    string-heavy scan fragment must run fully on the CPU engine — zero
    TPU fragments, zero device pulls — and still match the CPU
    oracle."""
    from spark_rapids_tpu.columnar import transfer
    from spark_rapids_tpu.plan import placement
    path = _write_parquet(tmp_path, "tiny.parquet", _tiny_string_table())

    def build(s):
        return (s.read.parquet(path)
                .filter(col("k") < 25)
                .select(col("s"), (col("v") + 1.0).alias("a")))

    s = tpu_session(cost_conf())
    try:
        pulls_before = transfer.d2h_stats()["pulls"]
        out = build(s).to_arrow()
        decisions = s._last_plan_result.placement
        assert decisions, "cost mode must record fragment decisions"
        assert all(d["engine"] == "cpu" for d in decisions)
        assert all(d["deciding"] in
                   ("pull_latency", "h2d", "d2h") for d in decisions)
        st = placement.global_stats()
        assert st["fragments_cpu"] >= 1
        assert st["fragments_tpu"] == 0
        assert transfer.d2h_stats()["pulls"] == pulls_before, \
            "an all-CPU placement must touch the device link zero times"
        assert "Tpu" not in s._last_plan_result.physical.tree_string()
    finally:
        s.stop()
    ref = cpu_session()
    try:
        assert_tables_equal(out, build(ref).to_arrow())
    finally:
        ref.stop()


def test_cost_large_numeric_stays_on_tpu(tmp_path):
    """The other half of the decision matrix: a large numeric
    aggregate under a fast link (and a CPU engine the calibration
    priors say is slower) keeps its device placement."""
    from spark_rapids_tpu.plan import placement
    path = _write_parquet(tmp_path, "big.parquet",
                          _large_numeric_table())

    def build(s):
        return (s.read.parquet(path)
                .group_by(col("k"))
                .agg(F.sum(col("v")).alias("sv")))

    s = tpu_session(cost_conf(LOCAL_LINK))
    try:
        out = build(s).to_arrow()
        decisions = s._last_plan_result.placement
        assert decisions
        assert all(d["engine"] == "tpu" for d in decisions)
        assert all(d["deciding"] == "cpu_compute" for d in decisions)
        st = placement.global_stats()
        assert st["fragments_tpu"] >= 1
        assert st["fragments_cpu"] == 0
        assert "TpuHashAggregate" in \
            s._last_plan_result.physical.tree_string()
    finally:
        s.stop()
    ref = cpu_session()
    try:
        assert_tables_equal(out, build(ref).to_arrow(),
                            approx_float=True)
    finally:
        ref.stop()


# ---------------------------------------------------------------------------
# mode=cost result identity: on == off in both link regimes
# ---------------------------------------------------------------------------

FUZZ_SPEC = [("k", pa.int64()), ("i", pa.int32()), ("v", pa.float64()),
             ("s", pa.string())]


@pytest.mark.parametrize("link", [REMOTE_LINK, LOCAL_LINK],
                         ids=["remote", "local"])
@pytest.mark.parametrize("seed", [11, 12])
def test_cost_on_off_identical_fuzz(link, seed):
    t = gen_table(seed, FUZZ_SPEC, 3000)

    def build(s):
        df = s.create_dataframe(t)
        return (df.filter(col("k").is_not_null() & (col("i") > 0))
                .select(col("k"), col("s"),
                        (col("v") * 3.0 + 1.0).alias("a"))
                .group_by(col("s"))
                .agg(F.count(col("k")).alias("c"),
                     F.sum(col("a")).alias("sa"))
                .order_by(col("s")))

    s_on = tpu_session(cost_conf(link))
    s_off = tpu_session()
    try:
        assert_tables_equal(build(s_on).to_arrow(),
                            build(s_off).to_arrow(),
                            ignore_order=False, approx_float=True)
    finally:
        s_on.stop()
        s_off.stop()


@pytest.mark.parametrize("qname", ["q1", "q3", "q6"])
def test_cost_tpch_matches_cpu(tmp_path_factory, qname):
    from spark_rapids_tpu.bench.tpch import TPCH_QUERIES, gen_tpch, \
        load_tables
    paths = gen_tpch(str(tmp_path_factory.mktemp("place_tpch")),
                     lineitem_rows=10_000)

    def build(s):
        return TPCH_QUERIES[qname](load_tables(s, paths))

    s_cost = tpu_session(cost_conf())
    ref = cpu_session()
    try:
        assert_tables_equal(build(s_cost).to_arrow(),
                            build(ref).to_arrow(),
                            ignore_order=False, approx_float=True)
        assert s_cost._last_plan_result.placement
    finally:
        s_cost.stop()
        ref.stop()


def test_cost_tpcxbb_q3_matches_cpu(tmp_path_factory):
    from spark_rapids_tpu.bench.tpcxbb import (
        TPCXBB_QUERIES, gen_tpcxbb, register_views,
    )
    paths = gen_tpcxbb(str(tmp_path_factory.mktemp("place_xbb")),
                       sales_rows=10_000)
    results = {}
    for label, conf in (("cost", cost_conf(
            **{"spark.rapids.sql.test.enabled": "false"})),
            ("cpu", {"spark.rapids.sql.enabled": "false",
                     "spark.rapids.sql.test.enabled": "false"})):
        s = tpu_session(dict(conf))
        try:
            register_views(s, paths)
            results[label] = s.sql(TPCXBB_QUERIES["q3"]).to_arrow()
        finally:
            s.stop()
    assert_tables_equal(results["cost"], results["cpu"],
                        ignore_order=False, approx_float=True)


# ---------------------------------------------------------------------------
# Mixed fragments: one conversion path, no double lowering
# ---------------------------------------------------------------------------

def _mixed_session(extra):
    # Filter disabled per-operator -> it falls back (unsupported-op
    # path), splitting the plan into two device fragments around a CPU
    # island; test mode off because fallback is the point
    conf = {"spark.rapids.sql.enabled": True,
            "spark.rapids.sql.exec.Filter": "false"}
    conf.update(extra)
    return TpuSession(conf)


def _mixed_query(s, t):
    return (s.create_dataframe(t)
            .select(col("k"), (col("v") * 2.0).alias("a"), col("s"))
            .filter(col("k") < 25)
            .select((col("a") + 1.0).alias("b"), col("s")))


def test_mixed_fragment_demotes_once_no_transitions():
    """Regression for the double-lowering seam: a cost-demoted plan
    whose middle operator ALREADY fell back (unsupported-op path) must
    lower every node exactly once through the shared conversion gate —
    all-CPU plan, zero transition execs, correct rows."""
    t = _tiny_string_table(500)
    s = _mixed_session(cost_conf())
    try:
        out = _mixed_query(s, t).to_arrow()
        tree = s._last_plan_result.physical.tree_string()
        assert "HostToDevice" not in tree
        assert "DeviceToHost" not in tree
        assert "Tpu" not in tree
        # one physical node per logical node: nothing lowered twice
        assert tree.count("CpuProject") == 2
        assert tree.count("CpuFilter") == 1
        assert tree.count("CpuLocalScan") == 1
    finally:
        s.stop()
    ref = cpu_session()
    try:
        assert_tables_equal(out, _mixed_query(ref, t).to_arrow())
    finally:
        ref.stop()


def test_mixed_fragment_keeps_transitions_when_tpu_wins():
    """Same mixed plan under the fast-link regime: the two device
    fragments stay on the device and the CPU island keeps exactly the
    transitions the static planner would insert."""
    t = _tiny_string_table(500)
    s = _mixed_session(cost_conf(
        LOCAL_LINK,
        **{"spark.rapids.sql.placement.cpuRowsPerSec": "1000"}))
    try:
        out = _mixed_query(s, t).to_arrow()
        tree = s._last_plan_result.physical.tree_string()
        assert "HostToDevice" in tree
        assert "DeviceToHost" in tree
        assert "CpuFilter" in tree
        assert "TpuProject" in tree or "TpuStage" in tree
    finally:
        s.stop()
    ref = cpu_session()
    try:
        assert_tables_equal(out, _mixed_query(ref, t).to_arrow(),
                            approx_float=True)
    finally:
        ref.stop()


# ---------------------------------------------------------------------------
# AQE runtime demotion: a deliberately wrong static estimate
# ---------------------------------------------------------------------------

def _aqe_conf(link=REMOTE_LINK, **extra):
    conf = cost_conf(link)
    conf["spark.rapids.sql.adaptive.enabled"] = "true"
    # a deliberately pessimistic CPU prior: the static pass (which
    # sees FILE bytes, pre-filter) keeps the fragment on the device...
    conf["spark.rapids.sql.placement.cpuRowsPerSec"] = "1000"
    # ...and a fast upload so only the fixed pull latency is at stake
    conf["spark.rapids.sql.placement.h2dMBps"] = "100000"
    conf["spark.rapids.sql.placement.d2hMBps"] = "100000"
    conf.update(extra)
    return conf


def _aqe_query(s, path, selective: bool):
    df = s.read.parquet(path)
    if selective:
        df = df.filter(col("k") < 1)
    return (df.repartition(4, "k")
            .select((col("v") * 2.0).alias("a"), col("k")))


@pytest.fixture
def aqe_parquet(tmp_path):
    rng = np.random.default_rng(7)
    t = pa.table({"k": pa.array(rng.integers(0, 100, 4000), pa.int64()),
                  "v": pa.array(rng.normal(size=4000))})
    return _write_parquet(tmp_path, "aqe.parquet", t)


def test_aqe_demotes_remainder_on_wrong_static_estimate(aqe_parquet):
    """Static pass sees 4000 file rows -> keeps the fragment on the
    device; the selective filter leaves ~40 rows at the stage, the
    re-score with MEASURED bytes says the remainder loses to its pull
    latency -> the project above the stage demotes to the CPU engine
    mid-query, result identical."""
    from spark_rapids_tpu.plan import placement
    from spark_rapids_tpu.plan.adaptive import find_adaptive
    s = tpu_session(_aqe_conf())
    try:
        out = _aqe_query(s, aqe_parquet, selective=True).to_arrow()
        pr = s._last_plan_result
        assert [d["engine"] for d in pr.placement] == ["tpu"]
        ad = find_adaptive(pr.physical)
        assert ad is not None
        assert any(r.get("decision") == "placement_demoted"
                   for r in ad.reports)
        assert placement.global_stats()["aqe_demotions"] == 1
        assert "CpuProject" in pr.physical.tree_string()
    finally:
        s.stop()
    ref = cpu_session()
    try:
        assert_tables_equal(
            out, _aqe_query(ref, aqe_parquet, selective=True).to_arrow())
    finally:
        ref.stop()


def test_aqe_demotion_carries_an_aggregates_folded_filter(aqe_parquet):
    """An aggregate that folded its filter (plan/fusion.py) is one node
    of the remainder: the re-score counts the filter's class with the
    aggregate's, and the demotion re-expands it into a CPU filter under
    the CPU aggregate — the predicate is not lost with the device node."""
    from spark_rapids_tpu.api import lit
    from spark_rapids_tpu.plan import placement
    from spark_rapids_tpu.plan.adaptive import find_adaptive
    s = tpu_session(_aqe_conf())
    try:
        df = (s.read.parquet(aqe_parquet).filter(col("k") < 2)
              .repartition(4, "k").filter(col("v") > -0.5)
              .group_by("k").agg(F.sum(col("v")).alias("sv"),
                                 F.count(lit(1)).alias("n")))
        assert df.to_arrow().num_rows == 2
        root = find_adaptive(s._last_plan_result.physical)
        agg = root.children[0]
        assert agg.describe().startswith("TpuHashAggregate") \
            and "masked=[Filter[(v > -0.5)]]" in agg.describe()
        stage = agg.children[0].children[0]
        assert stage.node_name == "TpuQueryStageExec"
        classes = placement._remainder_classes(agg, stage)
        assert classes[0].startswith("hashaggregate") \
            and [c for c in classes if c.startswith("filter")], classes
        tree = placement._demote_physical(agg, stage).tree_string()
        lines = [ln.strip().split(" ")[0] for ln in tree.splitlines()]
        assert lines[:3] == ["CpuHashAggregate", "CpuFilter",
                             "DeviceToHost"], tree
    finally:
        s.stop()


def test_aqe_keeps_remainder_when_measured_bytes_large(aqe_parquet):
    """No filter -> the measured stage bytes match the static estimate
    and the remainder stays on the device (no demotion)."""
    from spark_rapids_tpu.plan import placement
    s = tpu_session(_aqe_conf())
    try:
        out = _aqe_query(s, aqe_parquet, selective=False).to_arrow()
        assert placement.global_stats()["aqe_demotions"] == 0
        assert "CpuProject" not in \
            s._last_plan_result.physical.tree_string()
    finally:
        s.stop()
    ref = cpu_session()
    try:
        assert_tables_equal(
            out, _aqe_query(ref, aqe_parquet, selective=False).to_arrow())
    finally:
        ref.stop()


def test_aqe_demotion_respects_gate(aqe_parquet):
    """placement.aqe.enabled=false: the measured bytes still say
    demote, but the gate holds the static plan."""
    from spark_rapids_tpu.plan import placement
    s = tpu_session(_aqe_conf(
        **{"spark.rapids.sql.placement.aqe.enabled": "false"}))
    try:
        _aqe_query(s, aqe_parquet, selective=True).to_arrow()
        assert placement.global_stats()["aqe_demotions"] == 0
        assert "CpuProject" not in \
            s._last_plan_result.physical.tree_string()
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# plan.place fault: degrade to the static all-TPU plan
# ---------------------------------------------------------------------------

@pytest.mark.faults
def test_plan_place_fault_degrades_to_static(placement_fault_conf):
    """The constants demand demote-everything, but every pass hits the
    injected ``plan.place`` fault: the static all-TPU plan runs,
    results stay correct, the degrade is counted."""
    from spark_rapids_tpu.plan import placement
    t = _tiny_string_table(500)

    def build(s):
        return (s.create_dataframe(t)
                .filter(col("k") < 25)
                .select(col("s"), (col("v") * 2.0).alias("a")))

    s = tpu_session(placement_fault_conf)
    try:
        out = build(s).to_arrow()
        pr = s._last_plan_result
        assert pr.placement == []
        assert "Tpu" in pr.physical.tree_string()
        assert placement.global_stats()["place_faults"] >= 1
    finally:
        s.stop()
    ref = cpu_session()
    try:
        assert_tables_equal(out, build(ref).to_arrow())
    finally:
        ref.stop()


@pytest.mark.faults
def test_plan_place_fault_skips_aqe_demotion(aqe_parquet, fault_seed):
    """count:2 on plan.place: the static pass (consult 1) runs and
    keeps the fragment on the device, the AQE re-score (consult 2)
    hits the fault and must leave the static plan running — correct
    rows, no demotion, degrade counted."""
    from spark_rapids_tpu.plan import placement
    conf = _aqe_conf()
    conf["spark.rapids.faults.seed"] = str(fault_seed)
    conf["spark.rapids.faults.plan.place"] = "count:2"
    s = tpu_session(conf)
    try:
        out = _aqe_query(s, aqe_parquet, selective=True).to_arrow()
        st = placement.global_stats()
        assert st["aqe_demotions"] == 0
        assert st["place_faults"] >= 1
        assert "CpuProject" not in \
            s._last_plan_result.physical.tree_string()
    finally:
        s.stop()
    ref = cpu_session()
    try:
        assert_tables_equal(
            out, _aqe_query(ref, aqe_parquet, selective=True).to_arrow())
    finally:
        ref.stop()


# ---------------------------------------------------------------------------
# Observability: decisions journaled, rendered, and snapshotted
# ---------------------------------------------------------------------------

def test_fragment_placed_journal_and_analyze(tmp_path):
    import json
    jdir = tmp_path / "journal"
    conf = cost_conf(**{"spark.rapids.sql.obs.journalDir": str(jdir)})
    s = tpu_session(conf)
    try:
        df = s.create_dataframe(_tiny_string_table(200)).select(
            (col("v") + 1.0).alias("a"))
        txt = df.explain(analyze=True)
        assert "Placement:" in txt
        assert "-> cpu" in txt
        events = []
        for p in jdir.glob("events-*.jsonl"):
            with open(p, encoding="utf-8") as fh:
                events += [json.loads(line) for line in fh]
        placed = [e for e in events if e["event"] == "fragment_placed"]
        assert placed and placed[0]["engine"] == "cpu"
        assert placed[0]["phase"] == "static"
        assert "tpu_ms" in placed[0] and "deciding" in placed[0]
    finally:
        s.stop()


def test_placement_group_in_engine_stats():
    s = tpu_session(cost_conf())
    try:
        s.create_dataframe(_tiny_string_table(100)).select(
            col("k")).to_arrow()
        snap = s.engine_stats()["placement"]
        assert snap["fragments_cpu"] >= 1
        assert snap["queries_observed"] >= 1
        assert snap["actual_ms"] > 0
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# Units: link constants, calibration, scoring
# ---------------------------------------------------------------------------

def test_link_constants_read_from_conf_without_probe():
    from spark_rapids_tpu.conf import TpuConf
    from spark_rapids_tpu.plan import cost
    conf = TpuConf({"spark.rapids.sql.placement.h2dMBps": "45",
                    "spark.rapids.sql.placement.d2hMBps": "3.9",
                    "spark.rapids.sql.placement.pullLatencyMs": "94"})
    consts = cost.link_constants(conf)
    assert consts == {"h2d_mbps": 45.0, "d2h_mbps": 3.9,
                      "pull_latency_ms": 94.0, "probed": False}
    assert cost._PROBE is None, "pinned constants must not probe"


def test_calibration_ewma_and_persistence(tmp_path):
    from spark_rapids_tpu.plan.cost import CalibrationStore
    cal = CalibrationStore()
    cal.observe("cpu", "project", rows=1000, seconds=0.001)  # 1M r/s
    assert cal.rate("cpu", "project", 0.0) == pytest.approx(1e6)
    cal.observe("cpu", "project", rows=3000, seconds=0.001)  # 3M r/s
    # EWMA alpha=0.3: 0.3*3e6 + 0.7*1e6
    assert cal.rate("cpu", "project", 0.0) == pytest.approx(1.6e6)
    assert cal.rate("tpu", "project", 42.0) == 42.0  # prior stands
    cal.save(str(tmp_path))
    fresh = CalibrationStore()
    fresh.load(str(tmp_path))
    assert fresh.rate("cpu", "project", 0.0) == pytest.approx(1.6e6)
    # corrupt file degrades to priors, never raises
    (tmp_path / "calibration.json").write_text("{not json")
    broken = CalibrationStore()
    broken.load(str(tmp_path))
    assert broken.rate("cpu", "project", 7.0) == 7.0


def test_score_ops_deciding_terms():
    from spark_rapids_tpu.conf import TpuConf
    from spark_rapids_tpu.plan.cost import CalibrationStore, score_ops
    conf = TpuConf({})
    cal = CalibrationStore()
    remote = {"h2d_mbps": 45.0, "d2h_mbps": 4.0,
              "pull_latency_ms": 94.0}
    d = score_ops(["project", "filter"], rows=1000, bytes_in=40_000,
                  bytes_out=40_000, conf=conf, consts=remote,
                  calib=cal)
    assert d["engine"] == "cpu"
    assert d["deciding"] == "pull_latency"
    local = {"h2d_mbps": 1e5, "d2h_mbps": 1e5, "pull_latency_ms": 0.0}
    d2 = score_ops(["project", "filter"], rows=50_000_000,
                   bytes_in=1 << 30, bytes_out=1 << 30, conf=conf,
                   consts=local, calib=cal)
    assert d2["engine"] == "tpu"
    assert d2["deciding"] == "cpu_compute"
    # calibrated rates move the decision: a measured slow device flips
    # the big fragment to the CPU engine
    cal.observe("tpu", "project", rows=1000, seconds=10.0)
    cal.observe("tpu", "filter", rows=1000, seconds=10.0)
    d3 = score_ops(["project", "filter"], rows=50_000_000,
                   bytes_in=1 << 30, bytes_out=1 << 30, conf=conf,
                   consts=local, calib=cal)
    assert d3["engine"] == "cpu"
    assert d3["deciding"] == "tpu_kernel"


def test_cpu_calibration_hooks_record_only_in_cost_mode():
    """The CPU engine's operators count rows/wall ONLY while placement
    calibration is active: the default mode's per-operator metrics
    stay byte-identical (empty for CPU ops), cost mode learns
    measured CPU throughputs."""
    from spark_rapids_tpu.plan import cost
    t = _tiny_string_table(2000)

    def build(s):
        return s.create_dataframe(t).filter(col("k") < 25).select(
            col("s"))

    s_plain = cpu_session()
    try:
        build(s_plain).to_arrow()
        assert "totalTime" not in s_plain.last_query_metrics()
    finally:
        s_plain.stop()
    assert cost.calibration().rate("cpu", "filter", 0.0) == 0.0

    s_cal = cpu_session({"spark.rapids.sql.placement.mode": "cpu"})
    try:
        build(s_cal).to_arrow()
    finally:
        s_cal.stop()
    assert cost.calibration().rate("cpu", "filter", 0.0) > 0.0


# ---------------------------------------------------------------------------
# String operator classes close the loop (docs/placement.md): measured
# device overtake flips string fragments back to the TPU engine
# ---------------------------------------------------------------------------

def test_string_fragment_calibration_flip(tmp_path):
    """A string-heavy projection starts on the CPU engine under a
    deliberately slow device prior; once the calibration store has
    measured the device overtaking the CPU for the string classes,
    mode=cost flips the same fragment back to the TPU — asserted
    through the ``fragment_placed`` journal, not the plan text."""
    import json
    from spark_rapids_tpu.plan import cost
    jdir = tmp_path / "journal"
    conf = cost_conf(link=LOCAL_LINK, **{
        "spark.rapids.sql.obs.journalDir": str(jdir),
        "spark.rapids.sql.placement.tpuRowsPerSec": "10",
    })
    t = _tiny_string_table(2000)
    s = tpu_session(conf)
    try:
        def run():
            return s.create_dataframe(t).select(
                F.substring(col("s"), 1, 4).alias("u")).to_arrow()

        run()
        # the CPU execution calibrated the STRING class, not plain
        # `project` — the class whose device overtake flips the
        # fragment back
        assert cost.calibration().rate("cpu", "project_str", 0.0) > 0.0
        # feed the measured device overtake for every class in the
        # fragment (what observe_plan records after a device run)
        for cls in ("project_str", "project", "localscan"):
            for _ in range(4):
                cost.calibration().observe("tpu", cls,
                                           rows=2_000_000,
                                           seconds=0.001)
        run()
    finally:
        s.stop()
    events = []
    for p in jdir.glob("events-*.jsonl"):
        with open(p, encoding="utf-8") as fh:
            events += [json.loads(line) for line in fh]
    placed = [e for e in events if e["event"] == "fragment_placed"
              and "project_str" in (e.get("classes") or [])]
    assert placed, \
        "string fragments must journal under their string class"
    engines = [e["engine"] for e in placed]
    assert engines[0] == "cpu", (
        "with a slow device prior the string fragment must start on "
        f"the CPU engine, journaled {engines}")
    assert engines[-1] == "tpu", (
        "after the measured device rate overtakes the CPU the same "
        f"string fragment must flip back to the TPU, journaled "
        f"{engines}")


def test_cost_error_quantile_recorded_per_query():
    """Every executed cost-mode query records |projected-actual|/actual
    into the ``placement.cost_error.pct`` histogram, surfaced as
    p50/p99 inside the placement stats group (satellite: the 7.8x
    projection drift must be visible per query, not only as a
    cumulative ratio)."""
    from spark_rapids_tpu.obs import registry
    before = registry.histogram(
        registry.HIST_PLACEMENT_COST_ERROR_PCT).snapshot()["count"]
    s = tpu_session(cost_conf())
    try:
        s.create_dataframe(_tiny_string_table(500)).select(
            col("k")).to_arrow()
        snap = s.engine_stats()["placement"]
    finally:
        s.stop()
    after = registry.histogram(
        registry.HIST_PLACEMENT_COST_ERROR_PCT).snapshot()["count"]
    assert after > before, \
        "each cost-mode query must record one cost_error sample"
    assert "cost_error_p50_pct" in snap
    assert "cost_error_p99_pct" in snap
    assert snap["cost_error_p99_pct"] >= snap["cost_error_p50_pct"] >= 0


def test_pull_latency_charged_once_regardless_of_pull_groups():
    """BENCH_r07 cost_error_p99_pct 24576: the pull groups are
    pipelined, so only the FIRST pull's round trip is exposed —
    multiplying the fixed latency by the group count stacked phantom
    milliseconds onto every large-output plan.  ``pulls`` stays in the
    decision record for the post-mortem read."""
    from spark_rapids_tpu.conf import TpuConf
    from spark_rapids_tpu.plan.cost import CalibrationStore, score_ops
    consts = {"h2d_mbps": 1e5, "d2h_mbps": 1e5,
              "pull_latency_ms": 94.0}
    small = score_ops(["project"], rows=10, bytes_in=100,
                      bytes_out=100, conf=TpuConf({}), consts=consts,
                      calib=CalibrationStore())
    # 3 GiB of output = multiple 256 MiB pull groups
    big = score_ops(["project"], rows=10, bytes_in=100,
                    bytes_out=3 << 30, conf=TpuConf({}), consts=consts,
                    calib=CalibrationStore())
    assert big["pulls"] > 1 > 0
    assert big["terms"]["pull_latency"] == \
        small["terms"]["pull_latency"] == 94.0, \
        "latency must not scale with the pull-group count"


def test_expected_compile_ms_counts_kernel_cache_hits():
    """BENCH_r07 cost_error_p50_pct 96: the persistent store only sees
    the lookups the in-process kernel caches miss, so a warm process
    with a cold store used to project the full cold-compile cost onto
    fragments that would compile nothing.  The miss ratio's denominator
    must include the kernel-cache hits."""
    from spark_rapids_tpu.compile import service, store
    from spark_rapids_tpu.plan import cost
    from spark_rapids_tpu.utils import kernel_cache

    class _StubStore:
        def stats(self):
            return {"hits": 0, "misses": 4}

    orig_current = store.current
    orig_svc = service.service_stats
    store.current = lambda: _StubStore()
    service.service_stats = lambda: {"cold_ms": 400.0}
    kc = kernel_cache.KernelCache("test.placement.compile", 4)
    try:
        base_hits = sum(v["hits"]
                        for v in kernel_cache.all_stats().values())
        projected_cold = cost.expected_compile_ms()
        # avg_cold=100ms scaled by 4 misses over (4 + existing hits)
        want = 100.0 * (4 / (4 + base_hits))
        assert projected_cold == pytest.approx(want)
        # 96 in-process kernel-cache hits later, the projection shrinks
        # toward zero instead of staying pinned at the store's ratio
        kc["k"] = object()
        for _ in range(96):
            kc.get("k")
        warmer = cost.expected_compile_ms()
        assert warmer == pytest.approx(100.0 * (4 / (100 + base_hits)))
        assert warmer < projected_cold
    finally:
        store.current = orig_current
        service.service_stats = orig_svc


def test_score_ops_ooc_terms_only_when_over_budget():
    """docs/out_of_core.md cost terms: an over-budget fragment pays the
    partition-spill round trip (each input byte down once, back up
    once); a fitting fragment scores byte-identically with OOC on or
    off — the terms dict gains no keys."""
    from spark_rapids_tpu.conf import TpuConf
    from spark_rapids_tpu.plan.cost import CalibrationStore, score_ops
    consts = {"h2d_mbps": 50.0, "d2h_mbps": 5.0,
              "pull_latency_ms": 0.0}
    kw = dict(rows=1000, bytes_out=1000, conf=TpuConf({}),
              consts=consts, calib=CalibrationStore())
    off = score_ops(["project"], bytes_in=1 << 20, ooc_budget=0, **kw)
    fits = score_ops(["project"], bytes_in=1 << 20,
                     ooc_budget=1 << 30, **kw)
    assert "ooc_spill" not in off["terms"]
    assert off["terms"] == fits["terms"], \
        "a fitting fragment must score identically with OOC enabled"
    over = score_ops(["project"], bytes_in=1 << 20,
                     ooc_budget=1 << 10, **kw)
    assert over["terms"]["ooc_spill"] == \
        pytest.approx((1 << 20) / (5.0 * 1000.0), abs=1e-3)
    assert over["terms"]["ooc_promote"] == \
        pytest.approx((1 << 20) / (50.0 * 1000.0), abs=1e-3)
    assert over["tpu_ms"] > fits["tpu_ms"]
