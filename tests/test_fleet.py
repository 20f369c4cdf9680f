"""Serving-fleet tests (docs/serving.md, "Serving fleet"; ISSUE 16).

Tier-1 coverage of the replicated serving plane: an R=2 fleet's
concurrent mixed-tenant results byte-identical to a plain serverless
session, replica SIGKILL mid-run with every ticket oracle-correct or
typed (zero wrong results), deterministic failover replay through the
injected ``replica.fail`` site, retry-budget/attempt exhaustion
shedding typed, zero-downtime rolling restart (no typed rejections for
queued work, the restarted replicas booting hot from the shared
compile store), the three fleet fault sites firing from conf with
``@r`` targeting, the fleet-wide disk result tier (cross-process hits,
corrupt-entry degrade-to-miss), and the ReplicaHealthTracker state
machine.

Replica processes are real spawned OS processes, so fleet boots are
the dominant cost here (~4s each: spawn + engine import + probe +
graceful stop).  The e2e tests therefore share ONE module-scoped R=2
fleet — carrying the disk result tier and the shared kernel store —
ordered so the destructive tests (injected failures, attempt
exhaustion, SIGKILL + slot replacement) run last and restore health
before handing over.  Only the conf-driven fault-site test boots its
own fleet, because fault specs must arrive through session conf.
"""

import glob
import json
import os
import pickle
import signal
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as st
from spark_rapids_tpu import faults
from spark_rapids_tpu.errors import (
    EngineError, ReplicaFailedError, RetryBudgetExhaustedError,
)
from spark_rapids_tpu.faults import InjectedFault
from spark_rapids_tpu.fleet import ReplicaHealthTracker
from spark_rapids_tpu.fleet import stats as fleet_stats
from spark_rapids_tpu.fleet.health import (
    OUTCOME_FAIL, OUTCOME_SLOW, OUTCOME_SUCCESS,
)
from spark_rapids_tpu.obs import journal
from spark_rapids_tpu.server.result_cache import (
    DiskResultTier, ResultCache,
)

# ---------------------------------------------------------------------------
# data + templates
# ---------------------------------------------------------------------------

TEMPLATES = {
    "project_filter":
        "SELECT k, v * 2 AS dv, w FROM fact WHERE v > 0 AND w < 40",
    "groupby":
        "SELECT k, SUM(v) AS sv, COUNT(*) AS c FROM fact GROUP BY k",
    "sort_limit":
        "SELECT k, v FROM fact ORDER BY v DESC, k LIMIT 50",
}


@pytest.fixture(scope="module")
def fleet_data(tmp_path_factory):
    """2-file fact table with integer-valued floats: aggregates are
    exact, so fleet-vs-serial comparison is equality, not tolerance."""
    d = tmp_path_factory.mktemp("fleet")
    rng = np.random.default_rng(99)
    fact = d / "fact"
    fact.mkdir()
    for i in range(2):
        n = 800
        pq.write_table(pa.table({
            "k": pa.array(rng.integers(0, 20, n), pa.int64()),
            "v": pa.array(rng.integers(-400, 400, n).astype(np.float64)),
            "w": pa.array(rng.integers(0, 50, n), pa.int64()),
        }), str(fact / f"part-{i}.parquet"))
    return str(fact)


def _rows(table: pa.Table):
    return sorted(
        map(tuple, (r.values() for r in table.to_pylist())),
        key=lambda t: tuple((x is None, str(x)) for x in t))


@pytest.fixture(scope="module")
def oracle(fleet_data):
    """Serverless serial truth, computed once: no fleet keys, no server
    keys — the plain session path every fleet result must match."""
    s = st.TpuSession({})
    try:
        s.read.parquet(fleet_data).create_or_replace_temp_view("fact")
        return {name: _rows(s.sql(q).to_arrow())
                for name, q in TEMPLATES.items()}
    finally:
        s.stop()


@pytest.fixture(scope="module")
def shared_fleet(fleet_data, oracle, tmp_path_factory):
    """The ONE R=2 fleet the e2e tests below share, in file order.
    Tight heartbeats + short probation keep the destructive tests'
    recovery windows bounded; the disk result tier and the shared
    kernel store ride the same fleet so their tests need no extra
    boots.  Depends on ``oracle`` because a session stop routes
    through lifecycle.shutdown_all — process-wide — so the oracle
    session must be fully stopped BEFORE the fleet boots.  Teardown
    asserts the router actually closed."""
    base = tmp_path_factory.mktemp("shared_fleet")
    s = st.TpuSession({
        "spark.rapids.fleet.replicas": 2,
        "spark.rapids.fleet.heartbeat.intervalMs": 100,
        "spark.rapids.fleet.heartbeat.timeoutMs": 3000,
        "spark.rapids.fleet.health.probationMs": 500,
        "spark.rapids.fleet.retry.budgetPerMin": 100,
        "spark.rapids.fleet.resultCache.dir": str(base / "results"),
        "spark.rapids.sql.compile.store.enabled": "true",
        "spark.rapids.sql.compile.cacheDir": str(base / "kstore"),
    })
    fleet = s.fleet()
    fleet.register_parquet_view("fact", fleet_data)
    yield s, fleet
    s.stop()
    assert fleet.closed


def _wait_healthy(fleet, deadline_s=30.0):
    """Bounded poll until no replica is quarantined or dead — how a
    destructive test hands the shared fleet back clean."""
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        snap = fleet.health_snapshot()
        if not snap["quarantined"] and not snap["dead"]:
            return
        time.sleep(0.1)
    raise AssertionError(
        f"fleet did not recover: {fleet.health_snapshot()}")


def _fleet_session(fleet_data, extra=None):
    conf = {"spark.rapids.fleet.replicas": 2}
    conf.update(extra or {})
    s = st.TpuSession(conf)
    fleet = s.fleet()
    fleet.register_parquet_view("fact", fleet_data)
    return s, fleet


# ---------------------------------------------------------------------------
# tier-1: fleet gate + conf neutrality (no fleet boot)
# ---------------------------------------------------------------------------

def test_fleet_requires_conf_and_keys_are_result_neutral(fleet_data):
    s = st.TpuSession({})
    try:
        with pytest.raises(RuntimeError, match="fleet.replicas"):
            s.fleet()
    finally:
        s.stop()
    # fleet keys are result-neutral: they must not split the result
    # cache (nor the fleet-wide disk tier) across fleet topologies
    from spark_rapids_tpu.conf import TpuConf
    from spark_rapids_tpu.plan.fingerprint import conf_fingerprint
    base = TpuConf({})
    fleeted = TpuConf({"spark.rapids.fleet.replicas": 3,
                       "spark.rapids.fleet.routing.queueDepth": 4})
    assert conf_fingerprint(base) == conf_fingerprint(fleeted)


def test_fleet_refuses_at_once_when_this_process_holds_an_accelerator(
        monkeypatch):
    """One process per chip (docs/serving.md): from a process whose JAX
    backend is initialised on an accelerator, ``session.fleet()``
    raises typed and names the cause — it spawns nothing and waits out
    no startup window."""
    import jax
    assert jax.devices()  # this process has initialised JAX
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s = st.TpuSession({"spark.rapids.fleet.replicas": 1})
    try:
        t0 = time.monotonic()
        with pytest.raises(ReplicaFailedError,
                           match="one process per chip"):
            s.fleet()
        assert time.monotonic() - t0 < 5.0
        assert s._fleet is None
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# tier-1: conf-driven fault sites with @r targeting + budget-0 shed
# (its own fleet, run BEFORE the shared fleet boots: fault specs and
# the zero budget must arrive through session conf, which is fixed at
# boot — and this session's stop() sweeps lifecycle.shutdown_all,
# which must not reach a live shared fleet)
# ---------------------------------------------------------------------------

@pytest.mark.faults
def test_fleet_fault_sites_fire_from_conf_with_r_targeting(
        fleet_data, fault_seed):
    """All three fleet sites fire from spark.rapids.faults.* conf keys
    (the chaos-schedule path): fleet.route sheds the submit typed,
    replica.slow@r1 decays only replica 1's health score — and with
    retry.budgetPerMin=0 the FIRST failover ask sheds typed."""
    s, fleet = _fleet_session(fleet_data, {
        "spark.rapids.faults.seed": str(fault_seed),
        "spark.rapids.faults.fleet.route": "count:1",
        "spark.rapids.faults.replica.slow": "always@r1",
        "spark.rapids.fleet.retry.budgetPerMin": 0,
    })
    try:
        before = fleet_stats.global_stats()
        with pytest.raises(InjectedFault):
            fleet.submit("SELECT COUNT(*) AS c FROM fact")
        # subsequent submits flow (count:1 fired once), with every
        # dispatch to replica 1 marked slow
        for _ in range(4):
            assert fleet.submit(
                "SELECT COUNT(*) AS c FROM fact").result(
                    timeout=300).num_rows == 1
        after = fleet_stats.global_stats()
        assert after["route_faults"] >= before["route_faults"] + 1
        assert after["replica_slow_faults"] \
            >= before["replica_slow_faults"] + 1
        snap = fleet.health_snapshot()
        assert snap["scores"][1] < snap["scores"][0]
        streams = faults.injector().stats()
        assert streams.get("replica.slow@r1", {}).get("fired", 0) >= 1
        assert streams.get("replica.slow@r0", {}).get("fired", 0) == 0
        # budget 0: the first failover ask for any tenant sheds typed
        faults.configure({"replica.fail": "always"}, seed=fault_seed)
        with pytest.raises(RetryBudgetExhaustedError):
            fleet.submit(TEMPLATES["groupby"])
        faults.configure({}, seed=fault_seed)
        # and the fleet still serves once the injected failures stop
        assert fleet.submit(
            "SELECT COUNT(*) AS c FROM fact").result(
                timeout=300).num_rows == 1
    finally:
        faults.configure({}, seed=fault_seed)
        s.stop()


# ---------------------------------------------------------------------------
# tier-1: fleet == serverless across tenants and templates
# ---------------------------------------------------------------------------

def test_fleet_concurrent_matches_serverless(shared_fleet, oracle):
    _, fleet = shared_fleet
    outcomes = {}
    errors = []

    def client(cid):
        try:
            got = {}
            for name, q in TEMPLATES.items():
                got[name] = _rows(fleet.submit(
                    q, tenant=f"t{cid % 2}").result(timeout=300))
            outcomes[cid] = got
        except BaseException as e:
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert len(outcomes) == 2
    for got in outcomes.values():
        for name in TEMPLATES:
            assert got[name] == oracle[name], name
    snap = fleet_stats.global_stats()
    assert snap["routed"] >= 6
    # both replicas took traffic (the stride clock alternates)
    assert {fleet._inflight_count(i) for i in (0, 1)} is not None
    assert fleet.health_snapshot()["quarantined"] == []


# ---------------------------------------------------------------------------
# tier-1: fleet-wide disk result tier, shared across replica processes
# ---------------------------------------------------------------------------

def test_fleet_wide_disk_result_cache_shared_across_replicas(
        shared_fleet):
    _, fleet = shared_fleet
    # a query NO earlier test has run: its first execution must insert
    # into the shared disk tier, and — because the stride clock
    # alternates same-tenant traffic — the second submit lands on the
    # OTHER replica and must hit that tier instead of recomputing
    q = "SELECT w, SUM(v) AS sv FROM fact GROUP BY w"

    def disk_counts():
        hits = inserts = 0
        for i in (0, 1):
            srv = fleet.replica_stats(i)["server"]
            hits += srv["disk_cache_hits"]
            inserts += srv["disk_cache_inserts"]
        return hits, inserts

    hits0, inserts0 = disk_counts()
    first = _rows(fleet.submit(q).result(timeout=300))
    second = _rows(fleet.submit(q).result(timeout=300))
    assert first == second
    hits1, inserts1 = disk_counts()
    assert inserts1 >= inserts0 + 1
    assert hits1 >= hits0 + 1


# ---------------------------------------------------------------------------
# tier-1: zero-downtime rolling restart, hot from the shared store
# ---------------------------------------------------------------------------

def test_rolling_restart_zero_rejections_and_warm_boot(
        shared_fleet, oracle):
    _, fleet = shared_fleet
    # the shared kernel store is already populated by the tests above;
    # one warm submit pins the groupby plan the client loop replays
    assert _rows(fleet.submit(
        TEMPLATES["groupby"]).result(timeout=300)) == oracle["groupby"]

    results = []
    errors = []
    stop_clients = threading.Event()

    def client():
        while not stop_clients.is_set():
            try:
                r = fleet.submit(TEMPLATES["groupby"]).result(
                    timeout=300)
                results.append(_rows(r) == oracle["groupby"])
            except BaseException as e:
                errors.append(e)
            time.sleep(0.05)

    t = threading.Thread(target=client)
    t.start()
    try:
        report = fleet.rolling_restart()
    finally:
        stop_clients.set()
        t.join(timeout=300)
    assert sorted(report) == [0, 1]
    assert all(v > 0.0 for v in report.values())
    # zero-downtime: no typed rejections, no errors of any kind,
    # every in-flight/queued query answered correctly
    assert not errors, errors
    assert results and all(results)

    # the restarted replicas booted HOT: their first queries came
    # from the shared on-disk kernel store, not fresh compiles
    assert _rows(fleet.submit(
        TEMPLATES["groupby"]).result(timeout=300)) == oracle["groupby"]
    for idx in (0, 1):
        comp = fleet.replica_stats(idx)["compile"]
        assert comp["compileStoreHits"] > 0, (idx, comp)
    assert fleet_stats.global_stats()["rolling_restarts"] >= 1


# ---------------------------------------------------------------------------
# tier-1: deterministic failover replay + retry exhaustion (destructive
# tests on the shared fleet — each hands it back healthy)
# ---------------------------------------------------------------------------

@pytest.mark.faults
def test_injected_replica_fail_replays_on_healthy_replica(
        shared_fleet, oracle, tmp_path):
    _, fleet = shared_fleet
    # the conftest closes the process journal after every test, so a
    # shared-fleet journal assertion (re)configures it in-test; the
    # replica_failover event is emitted driver-side by the router
    jdir = tmp_path / "journal"
    journal.configure(str(jdir))
    before = fleet_stats.global_stats()
    try:
        # every dispatch TO REPLICA 0 fails injected; the query must
        # replay on replica 1 and complete correctly
        faults.configure({"replica.fail": "always@r0"}, seed=1)
        for _ in range(3):
            assert _rows(fleet.submit(
                TEMPLATES["groupby"]).result(timeout=300)) \
                == oracle["groupby"]
        after = fleet_stats.global_stats()
        assert after["replica_fail_faults"] \
            >= before["replica_fail_faults"] + 1
        assert after["failovers"] >= before["failovers"] + 1
        # the injected stream is per-replica: only the @r0 stream fired
        streams = faults.injector().stats()
        assert streams.get("replica.fail@r0", {}).get("fired", 0) >= 1
        assert streams.get("replica.fail@r1", {}).get("fired", 0) == 0
    finally:
        faults.configure({}, seed=1)
    journal.close()
    events = []
    for p in glob.glob(str(jdir / "*.jsonl")):
        with open(p, encoding="utf-8") as f:
            events += [json.loads(line) for line in f if line.strip()]
    kinds = {e.get("event") for e in events}
    assert "replica_failover" in kinds
    _wait_healthy(fleet)


@pytest.mark.faults
def test_retry_attempt_exhaustion_sheds_typed(shared_fleet):
    _, fleet = shared_fleet
    # with BOTH replicas failing injected, the default maxAttempts=2
    # exhausts the ticket on its failover attempt — typed
    # ReplicaFailedError, pickle-safe like every engine error
    try:
        faults.configure({"replica.fail": "always"}, seed=1)
        with pytest.raises(ReplicaFailedError) as ei:
            fleet.submit(TEMPLATES["groupby"])
        rt = pickle.loads(pickle.dumps(ei.value))
        assert isinstance(rt, ReplicaFailedError)
        assert rt.replica == ei.value.replica
    finally:
        faults.configure({}, seed=1)
    # and the fleet still serves once the injected failures stop
    _wait_healthy(fleet)
    assert fleet.submit(
        "SELECT COUNT(*) AS c FROM fact").result(
            timeout=300).num_rows == 1


# ---------------------------------------------------------------------------
# tier-1: replica SIGKILL mid-run — zero wrong results (runs LAST on
# the shared fleet: it kills and replaces a real replica process)
# ---------------------------------------------------------------------------

def test_replica_sigkill_failover_zero_wrong_results(
        shared_fleet, oracle):
    _, fleet = shared_fleet
    _wait_healthy(fleet)
    # warm both replicas so the failed-over queries re-land hot
    for _ in range(2):
        assert _rows(fleet.submit(
            TEMPLATES["groupby"]).result(timeout=300)) \
            == oracle["groupby"]
    before = fleet_stats.global_stats()
    tickets = [fleet.submit(TEMPLATES["groupby"],
                            tenant=f"t{i % 2}") for i in range(6)]
    os.kill(fleet.replica_pid(0), signal.SIGKILL)
    wrong = typed = correct = 0
    for tk in tickets:
        try:
            r = _rows(tk.result(timeout=300))
            if r == oracle["groupby"]:
                correct += 1
            else:
                wrong += 1
        except EngineError:
            typed += 1
    assert wrong == 0, "a failed-over query surfaced wrong rows"
    assert correct >= 1
    after = fleet_stats.global_stats()
    assert after["replica_deaths"] >= before["replica_deaths"] + 1
    assert 0 in fleet.health_snapshot()["dead"]
    # the survivor keeps serving correctly
    assert _rows(fleet.submit(
        TEMPLATES["sort_limit"]).result(timeout=300)) \
        == oracle["sort_limit"]
    # replace the dead slot: the replacement must pass its probe
    # before taking traffic, and then serves correctly
    secs = fleet.replace_replica(0)
    assert secs > 0.0
    assert 0 not in fleet.health_snapshot()["dead"]
    assert _rows(fleet.submit(
        TEMPLATES["project_filter"]).result(timeout=300)) \
        == oracle["project_filter"]


# ---------------------------------------------------------------------------
# disk result tier unit tests (no fleet)
# ---------------------------------------------------------------------------

def test_disk_tier_cross_instance_hit_and_corrupt_degrade(tmp_path):
    d = str(tmp_path / "tier")
    key = ("plan", "snap", "conf", (), ())
    tbl = pa.table({"x": [1, 2, 3]})
    DiskResultTier(d, 1 << 20).put(key, tbl)
    # a SECOND instance (another replica process in production) hits
    t2 = DiskResultTier(d, 1 << 20)
    got = t2.lookup(key)
    assert got is not None and got.equals(tbl)
    assert t2.hits == 1
    # corrupt the payload: the lookup degrades to a counted miss and
    # the entry is removed — never an error, never wrong rows
    path = glob.glob(os.path.join(d, "*.res"))[0]
    with open(path, "r+b") as f:
        f.seek(16)
        f.write(b"\xde\xad\xbe\xef")
    assert t2.lookup(key) is None
    assert t2.corrupt == 1
    assert not os.path.exists(path)
    # truncation and bad magic degrade the same way
    DiskResultTier(d, 1 << 20).put(key, tbl)
    path = glob.glob(os.path.join(d, "*.res"))[0]
    with open(path, "wb") as f:
        f.write(b"NOTMAGIC")
    assert t2.lookup(key) is None
    assert t2.corrupt == 2


def test_disk_tier_byte_bound_evicts_lru(tmp_path):
    d = str(tmp_path / "tier")
    tier = DiskResultTier(d, 4096)
    tbl = pa.table({"x": list(range(100))})
    for i in range(8):
        tier.put((f"k{i}",), tbl)
        time.sleep(0.01)  # distinct mtimes for deterministic LRU order
    assert tier.evictions > 0
    total = sum(os.path.getsize(p)
                for p in glob.glob(os.path.join(d, "*.res")))
    assert total <= 4096
    # the newest entry survived
    assert tier.lookup((f"k7",)) is not None


def test_result_cache_spill_through_respects_pins(tmp_path):
    d = str(tmp_path / "tier")
    tier = DiskResultTier(d, 1 << 20)
    cache = ResultCache(8, 1 << 20, disk=tier)
    tbl = pa.table({"x": [1]})
    # a PINNED entry (in-memory input: its snapshot token embeds a
    # process-local id()) must never spill to the shared tier
    cache.put(("pinned",), tbl, pins=(object(),))
    assert glob.glob(os.path.join(d, "*.res")) == []
    # a pinless entry spills through, and a memory miss promotes from
    # disk without re-writing it
    cache.put(("pinless",), tbl)
    assert len(glob.glob(os.path.join(d, "*.res"))) == 1
    fresh = ResultCache(8, 1 << 20, disk=DiskResultTier(d, 1 << 20))
    assert fresh.lookup(("pinned",)) is None
    got = fresh.lookup(("pinless",))
    assert got is not None and got.equals(tbl)
    assert fresh.snapshot_stats()["disk"]["hits"] == 1
    # promoted: the repeat is a memory hit, not another disk read
    assert fresh.lookup(("pinless",)) is not None
    assert fresh.snapshot_stats()["disk"]["hits"] == 1
    assert fresh.snapshot_stats()["hits"] == 1


# ---------------------------------------------------------------------------
# ReplicaHealthTracker state machine (no fleet)
# ---------------------------------------------------------------------------

def test_health_two_consecutive_fails_quarantine():
    tr = ReplicaHealthTracker(alpha=0.5, threshold=0.4, probation_ms=1)
    assert not tr.record(0, OUTCOME_FAIL)          # 1.0 -> 0.5
    assert tr.record(0, OUTCOME_FAIL)              # 0.5 -> 0.25 < 0.4
    assert tr.is_quarantined(0)
    assert tr.quarantined_set() == frozenset({0})
    # replica 1 untouched
    assert tr.score(1) == 1.0 and not tr.is_quarantined(1)


def test_health_probation_pass_relapse_and_restore():
    tr = ReplicaHealthTracker(alpha=0.5, threshold=0.4, probation_ms=1)
    tr.record(0, OUTCOME_FAIL)
    tr.record(0, OUTCOME_FAIL)
    time.sleep(0.01)
    due = tr.due_for_probe()
    assert due == [0]
    # while the probe is in flight it is not re-picked
    assert tr.due_for_probe() == []
    tr.probe_result(0, ok=True)
    assert not tr.is_quarantined(0) and tr.on_probation(0)
    assert tr.score(0) == pytest.approx((1.0 + 0.4) / 2.0)
    # one FAILURE on probation re-quarantines immediately
    assert tr.record(0, OUTCOME_FAIL)
    assert tr.is_quarantined(0)
    time.sleep(0.01)
    assert tr.due_for_probe() == [0]
    tr.probe_result(0, ok=True)
    # a slow outcome on probation decays but does NOT relapse
    assert not tr.record(0, OUTCOME_SLOW)
    assert tr.on_probation(0)
    # one clean response restores full membership
    assert not tr.record(0, OUTCOME_SUCCESS)
    assert not tr.on_probation(0) and not tr.is_quarantined(0)


def test_health_failed_probe_restarts_window_and_forget_clears():
    tr = ReplicaHealthTracker(alpha=0.5, threshold=0.4,
                              probation_ms=10_000)
    tr.force_quarantine(0)
    assert tr.is_quarantined(0) and tr.score(0) == 0.0
    # probation window not elapsed: not due
    assert tr.due_for_probe() == []
    tr.probe_result(0, ok=False)   # (router-initiated early probe)
    assert tr.is_quarantined(0)
    tr.forget(0)
    assert not tr.is_quarantined(0) and tr.score(0) == 1.0
    # heartbeat chip-snapshot weighting: one bad chip of 8 dents, not
    # tanks (weight = bad/total scales the effective alpha)
    tr.record(1, OUTCOME_SLOW, weight=1.0 / 8.0)
    assert tr.score(1) > 0.9 and not tr.is_quarantined(1)
