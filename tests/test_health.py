"""Chip failure domain (docs/fault_tolerance.md, "Chip failure
domain"): per-chip EWMA health scoring, quarantine and probation
re-admission, degraded-mesh re-lowering on the power-of-two ladder, and
the session server's bounded query replay + graceful drain.

The acceptance contract (ISSUE 11): with ``spark.rapids.health.enabled``
off, plans and results are byte-identical to the health-less engine;
with it on, a persistent injected ``chip.fail`` on one chip quarantines
it within the threshold's failure count, the mesh re-forms at width 4,
subsequent ICI fragments run collectives on the degraded mesh with zero
exchange pulls, and a mid-flight server query replays once and returns
oracle-correct rows.
"""

import time

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as st
from spark_rapids_tpu import faults, health
from spark_rapids_tpu import functions as F
from spark_rapids_tpu.api import col
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.errors import (
    AdmissionRejectedError, ChipFailedError, RetryBudgetExhaustedError,
)
from spark_rapids_tpu.exec import meshexec
from spark_rapids_tpu.plan.planner import plan_query
from spark_rapids_tpu.shuffle.manager import (
    ici_mesh_width, select_shuffle_mode,
)
from tests.compare import tpu_session

multichip = pytest.mark.multichip

ICI = {"spark.rapids.shuffle.mode": "ici"}

# fast-quarantine tuning for the e2e tests: one chip-attributed
# failure drops the score to 0.5 < 0.6 — quarantine on the first fire
HCONF = dict(ICI)
HCONF.update({
    "spark.rapids.health.enabled": "true",
    "spark.rapids.health.scoreAlpha": "0.5",
    "spark.rapids.health.quarantineThreshold": "0.6",
    "spark.rapids.health.probationMs": "600000",
})


def _table(rng, n=3000):
    return pa.table({
        "k": pa.array(rng.integers(0, 23, n), pa.int64()),
        "v": pa.array(rng.integers(-500, 500, n).astype(np.float64)),
    })


def _agg(session, t):
    return (session.create_dataframe(t)
            .group_by(col("k"))
            .agg(F.sum(col("v")).alias("s"),
                 F.count(col("v")).alias("c")))


def _rows(table):
    return sorted(table.to_pylist(), key=lambda r: r["k"])


# ---------------------------------------------------------------------------
# units: trigger grammar, scoring, ladder, probation
# ---------------------------------------------------------------------------

def test_chip_trigger_targeting():
    inj = faults.FaultInjector({"chip.fail": "always@c3"})
    assert not inj.should_fire("chip.fail", chip=2)
    assert inj.should_fire("chip.fail", chip=3)
    # a spec without @c matches every chip (the shared site counter
    # still advances once per consult)
    inj2 = faults.FaultInjector({"chip.slow": "count:2"})
    assert not inj2.should_fire("chip.slow", chip=0)
    assert inj2.should_fire("chip.slow", chip=5)
    # a chip-TARGETED count spec evaluates against that chip's OWN
    # consult stream, not the interleaved site-wide counter: the gate
    # consults chips 0..7 in mesh order, so "count:1@c6" must fire on
    # chip 6's first consult (site-wide it would be call 7 and the
    # trigger could never fire)
    inj4 = faults.FaultInjector({"chip.fail": "count:1@c6"})
    for c in range(6):
        assert not inj4.should_fire("chip.fail", chip=c)
    assert inj4.should_fire("chip.fail", chip=6)
    assert not inj4.should_fire("chip.fail", chip=6)  # count spent
    # worker targeting is unchanged; unknown targets stay errors
    inj3 = faults.FaultInjector({"worker.kill": "count:1@w1"}, worker=1)
    assert inj3.should_fire("worker.kill")
    with pytest.raises(ValueError):
        faults.FaultInjector({"chip.fail": "always@x3"})


def test_ewma_score_quarantines_within_threshold_failures():
    tr = health.ChipHealthTracker(alpha=0.35, threshold=0.3,
                                  probation_ms=600000)
    fails = 0
    while not tr.is_quarantined(5):
        tr.record(5, health.OUTCOME_FAIL)
        fails += 1
        assert fails < 10, "quarantine never triggered"
    # 0.65, 0.4225, 0.2746: three attributed failures cross 0.3
    assert fails == 3
    # successes on other chips leave them alone; a success stream
    # recovers a degraded (but unquarantined) score
    tr.record(1, health.OUTCOME_FAIL)
    for _ in range(8):
        tr.record(1, health.OUTCOME_SUCCESS)
    assert not tr.is_quarantined(1)
    assert tr.score(1) > 0.9


def test_mesh_wide_blame_is_spread():
    tr = health.ChipHealthTracker(alpha=0.35, threshold=0.3,
                                  probation_ms=600000)
    # one stage-level incident across an 8-wide mesh must not
    # quarantine anything; a chip-attributed failure weighs 8x more
    for chip in range(8):
        tr.record(chip, health.OUTCOME_FAIL, weight=1.0 / 8)
    assert tr.quarantined_set() == frozenset()
    assert tr.score(0) > 0.9


def test_pow2_ladder_and_effective_width():
    assert [health.pow2_floor(n) for n in (8, 7, 5, 4, 3, 2, 1, 0)] \
        == [8, 4, 4, 4, 2, 2, 1, 0]
    tr = health.ChipHealthTracker(alpha=0.5, threshold=0.6,
                                  probation_ms=600000)
    assert tr.effective_width(8, total=8) == 8
    widths = []
    for chip in range(7):
        tr.record(chip, health.OUTCOME_FAIL)
        widths.append(tr.effective_width(8, total=8))
    # 7,6,5 healthy -> 4; 4 -> 4; 3 -> 2; 2 -> 2; 1 -> 1
    assert widths == [4, 4, 4, 4, 2, 2, 1]


def test_slow_marks_converge_to_quarantine():
    tr = health.ChipHealthTracker(alpha=0.35, threshold=0.3,
                                  probation_ms=600000)
    marks = 0
    while not tr.is_quarantined(2):
        tr.record(2, health.OUTCOME_SLOW)
        marks += 1
        assert marks < 40, "persistent slowness must quarantine"
    assert marks > 3, "slow must take longer than hard failure"


@multichip
def test_probation_readmission_probe_and_relapse():
    # alpha/threshold chosen so ONE hard failure quarantines
    # (0.35 < 0.4) while one slow mark on the 0.7 re-entry score stays
    # above the threshold (0.4075) — the relapse rule, not EWMA decay,
    # is what the probation assertions exercise
    tr = health.ChipHealthTracker(alpha=0.65, threshold=0.4,
                                  probation_ms=30)
    tr.record(2, health.OUTCOME_FAIL)
    assert tr.is_quarantined(2)
    assert 2 not in tr.healthy_indices(8)
    time.sleep(0.06)
    # probation window elapsed: the healthy-set read probes chip 2 (no
    # fault configured -> the device answers) and re-admits it
    healthy = tr.healthy_indices(8)
    assert 2 in healthy and tr.on_probation(2)
    # a slow mark during probation is non-fatal (score decays only);
    # one FAILED collective re-quarantines immediately
    tr.record(2, health.OUTCOME_SLOW)
    assert not tr.is_quarantined(2) and tr.on_probation(2)
    tr.record(2, health.OUTCOME_FAIL)
    assert tr.is_quarantined(2)
    # a clean collective after the next probe restores full membership
    time.sleep(0.06)
    assert 2 in tr.healthy_indices(8)
    tr.record(2, health.OUTCOME_SUCCESS)
    assert not tr.on_probation(2) and not tr.is_quarantined(2)


@multichip
def test_probe_failure_keeps_chip_quarantined(fault_seed):
    faults.configure({"chip.fail": "always@c2"}, seed=fault_seed)
    tr = health.ChipHealthTracker(alpha=0.5, threshold=0.6,
                                  probation_ms=30)
    tr.record(2, health.OUTCOME_FAIL)
    time.sleep(0.06)
    # the probe consults chip.fail first: a persistently failing chip
    # fails its re-entry probe and the window restarts
    assert 2 not in tr.healthy_indices(8)
    assert tr.is_quarantined(2)


def test_width_selection_honors_quarantine():
    conf = TpuConf(HCONF)
    health.tracker().configure(0.5, 0.6, 600000)
    assert ici_mesh_width(conf, n_devices=None) in (4, 8)  # pool-shaped
    health.tracker().record(7, health.OUTCOME_FAIL)
    assert ici_mesh_width(conf) == 4
    for chip in range(1, 7):
        health.tracker().record(chip, health.OUTCOME_FAIL)
    # one healthy chip: no interconnect — the session keeps host mode
    assert ici_mesh_width(conf) == 1
    assert select_shuffle_mode(conf) == "host"
    # health off: the quarantine state is invisible
    assert select_shuffle_mode(TpuConf(ICI), n_devices=8) == "ici"


def test_semaphore_resize_scales_with_pool():
    from spark_rapids_tpu.runtime import TpuSemaphore
    sem = TpuSemaphore(2)
    sem.acquire()
    assert sem.available() == 1
    sem.resize(4)
    assert sem.available() == 3 and sem.base_permits == 2
    sem.resize(1)
    # the held permit outlives the shrink; capacity floors at 1
    assert sem.available() == 0
    sem.release()
    assert sem.available() == 1


# ---------------------------------------------------------------------------
# off-path byte-identity (acceptance: health off == PR 9)
# ---------------------------------------------------------------------------

@multichip
def test_health_off_is_byte_identical(rng):
    t = _table(rng)

    def run(extra):
        conf = dict(ICI)
        conf.update(extra)
        s = tpu_session(conf)
        q = _agg(s, t).order_by(col("k"))
        plan_str = plan_query(q.plan, s.conf).physical.tree_string()
        rows = q.to_arrow().to_pylist()
        # counts, not clocks: the three phases' microseconds
        # (ingest_us, collective_us, gather_us) differ run to run; and
        # not warmth: the second run finds the mesh programs the first
        # compiled (program_hits), whichever of the two it is
        ici = {k: v for k, v in meshexec.ici_stats().items()
               if not k.endswith("_us") and k != "program_hits"}
        s.stop()
        return plan_str, rows, ici

    meshexec.reset_ici_stats()
    base_plan, base_rows, base_ici = run({})
    meshexec.reset_ici_stats()
    off_plan, off_rows, off_ici = run(
        {"spark.rapids.health.enabled": "false"})
    assert off_plan == base_plan
    assert off_rows == base_rows
    assert off_ici == base_ici
    # no health code ran: every counter untouched
    assert all(v == 0 for v in health.global_stats().values()), \
        health.global_stats()


# ---------------------------------------------------------------------------
# e2e: quarantine -> degraded mesh -> zero-pull collectives (acceptance)
# ---------------------------------------------------------------------------

@multichip
@pytest.mark.faults
def test_chip_fail_quarantines_and_mesh_reforms(rng, fault_conf):
    t = _table(rng)
    conf = dict(fault_conf)
    conf.update(HCONF)
    conf["spark.rapids.faults.chip.fail"] = "always@c7"

    s_host = tpu_session()
    want = _rows(_agg(s_host, t).to_arrow())
    s_host.stop()

    s = tpu_session(conf)
    # the chip-attributed failure kills the query TYPED (no silent
    # host-path-forever degrade) and quarantines within the threshold
    with pytest.raises(ChipFailedError):
        _agg(s, t).to_arrow()
    stats = health.global_stats()
    assert stats["quarantines"] == 1 and stats["chip_failures"] == 1
    assert health.tracker().is_quarantined(7)
    assert stats["degrades"] == 1  # mesh_degrade published: 8 -> 4
    assert health.effective_width(8) == 4

    # subsequent fragments run collectives on the re-formed width-4
    # mesh: oracle-correct, ZERO exchange pulls, zero fallbacks — and
    # chip 7 is out of the consult set, so the persistent fault is mute
    meshexec.reset_ici_stats()
    got = _rows(_agg(s, t).to_arrow())
    assert got == want
    ici = meshexec.ici_stats()
    assert ici["exchanges"] > 0, ici
    assert ici["exchange_pulls"] == 0, ici
    assert ici["fallbacks"] == 0, ici
    # the admission pool shrank with the chips (2 permits * 7/8 -> 1);
    # the query path's runtime is the get_or_create singleton
    from spark_rapids_tpu.runtime import TpuRuntime
    sem = TpuRuntime._instance.semaphore
    assert sem.permits == max(1, sem.base_permits * 7 // 8)
    s.stop()


@multichip
def test_width_degrade_mid_query_falls_back_to_host(rng):
    """A plan lowered at width 8 whose pool degrades below 2 healthy
    chips BEFORE execution keeps the host path per fragment, tagged
    with the ``width`` fallback reason."""
    from spark_rapids_tpu.exec.base import ExecContext
    t = _table(rng)
    s = tpu_session(HCONF)
    s_host = tpu_session()
    want = _rows(_agg(s_host, t).to_arrow())
    s_host.stop()
    q = _agg(s, t)
    result = plan_query(q.plan, s.conf)
    assert "TpuMeshAggregate" in result.physical.tree_string()
    health.tracker().configure(0.5, 0.6, 600000)
    for chip in range(1, 8):
        health.tracker().record(chip, health.OUTCOME_FAIL)
    meshexec.reset_ici_stats()
    batches = list(result.physical.execute_host(ExecContext(s.conf)))
    got = _rows(pa.Table.from_batches(
        batches, schema=result.physical.output_schema.to_arrow()))
    assert got == want
    ici = meshexec.ici_stats()
    assert ici["fallbacks_width"] >= 1 and ici["exchanges"] == 0, ici
    s.stop()


@multichip
def test_same_width_membership_change_rebuilds_mesh(rng):
    """A second quarantine at the SAME power-of-two width changes the
    healthy set's membership: the cached distributed pipeline must
    rebuild over the new chip set, never keep running collectives on
    the newly-dead chip (the cache key is the chip tuple, not the
    width)."""
    from spark_rapids_tpu.exec.base import ExecContext
    t = _table(rng)
    s = tpu_session(HCONF)
    s_host = tpu_session()
    want = _rows(_agg(s_host, t).to_arrow())
    s_host.stop()
    health.tracker().configure(0.5, 0.6, 600000)
    health.tracker().record(1, health.OUTCOME_FAIL)  # healthy 7 -> w4
    q = _agg(s, t)
    result = plan_query(q.plan, s.conf)
    ctx = ExecContext(s.conf)

    def run():
        batches = list(result.physical.execute_host(ctx))
        return _rows(pa.Table.from_batches(
            batches, schema=result.physical.output_schema.to_arrow()))

    assert run() == want
    # membership changes, width stays 4: chips (0,2,3,4) -> (0,3,4,5)
    health.tracker().record(2, health.OUTCOME_FAIL)
    assert health.effective_width(8) == 4
    meshexec.reset_ici_stats()
    assert run() == want
    ici = meshexec.ici_stats()
    assert ici["exchanges"] > 0 and ici["fallbacks"] == 0, ici
    s.stop()


@multichip
@pytest.mark.faults
def test_fallback_reason_counters(rng, fault_conf):
    t = _table(rng)
    # over-budget: the per-stage HBM guard
    conf = dict(ICI)
    conf["spark.rapids.shuffle.ici.maxStageBytes"] = "1"
    s = tpu_session(conf)
    meshexec.reset_ici_stats()
    _agg(s, t).to_arrow()
    ici = meshexec.ici_stats()
    assert ici["fallbacks_over_budget"] >= 1, ici
    assert ici["fallbacks"] == ici["fallbacks_over_budget"]
    s.stop()
    # injected collective fault
    conf2 = dict(fault_conf)
    conf2.update(ICI)
    conf2["spark.rapids.faults.shuffle.ici.collective"] = "count:1"
    s2 = tpu_session(conf2)
    meshexec.reset_ici_stats()
    _agg(s2, t).to_arrow()
    ici2 = meshexec.ici_stats()
    assert ici2["fallbacks_injected"] == 1, ici2
    s2.stop()


@multichip
@pytest.mark.faults
def test_chip_slow_marks_feed_score_without_failing(rng, fault_conf):
    t = _table(rng)
    conf = dict(fault_conf)
    conf.update(HCONF)
    conf["spark.rapids.faults.chip.slow"] = "count:1,2@c1"
    s = tpu_session(conf)
    s_host = tpu_session()
    want = _rows(_agg(s_host, t).to_arrow())
    s_host.stop()
    got = _rows(_agg(s, t).to_arrow())
    assert got == want  # the collective still completed
    stats = health.global_stats()
    assert stats["slow_marks"] >= 1
    assert health.tracker().score(1) < 1.0
    assert not health.tracker().is_quarantined(1)
    s.stop()


# ---------------------------------------------------------------------------
# the serving path: bounded replay + graceful drain
# ---------------------------------------------------------------------------

@multichip
@pytest.mark.faults
def test_server_replays_chip_failed_query_once(rng, fault_conf):
    t = _table(rng)
    conf = dict(fault_conf)
    conf.update(HCONF)
    conf["spark.rapids.faults.chip.fail"] = "always@c7"
    s_host = tpu_session()
    want = _rows(_agg(s_host, t).to_arrow())
    s_host.stop()

    s = tpu_session(conf)
    server = s.server(max_concurrency=2)
    # attempt 1 dies ChipFailedError and quarantines chip 7; the
    # replay runs on the re-formed width-4 mesh and succeeds — the
    # ticket sees only oracle-correct rows
    table = server.submit(_agg(s, t)).result(timeout=300)
    assert _rows(table) == want
    stats = health.global_stats()
    assert stats["replays"] == 1 and stats["quarantines"] == 1, stats
    s.stop()


@multichip
@pytest.mark.faults
def test_server_replay_budget_sheds_typed(rng, fault_conf):
    t = _table(rng)
    conf = dict(fault_conf)
    conf.update(HCONF)
    conf["spark.rapids.faults.chip.fail"] = "always@c7"
    conf["spark.rapids.server.retry.budgetPerMin"] = "0"
    s = tpu_session(conf)
    server = s.server(max_concurrency=2)
    ticket = server.submit(_agg(s, t))
    with pytest.raises(RetryBudgetExhaustedError) as ei:
        ticket.result(timeout=300)
    # the shed is an AdmissionRejectedError (retry-with-backoff
    # contract) chained on the original chip failure
    assert isinstance(ei.value, AdmissionRejectedError)
    assert isinstance(ei.value.__cause__, ChipFailedError)
    assert health.global_stats()["replays_shed"] == 1
    s.stop()


def test_server_drain_rejects_queued_and_stops_admission(rng):
    t = _table(rng)
    s = tpu_session()
    # no workers: the submitted ticket stays queued, so drain's
    # typed-reject path is observable deterministically
    server = s.server(max_concurrency=0)
    ticket = server.submit(_agg(s, t))
    ms = server.drain(timeout=1.0)
    assert ms >= 0.0 and server.closed
    with pytest.raises(AdmissionRejectedError):
        ticket.result(timeout=1.0)
    with pytest.raises(AdmissionRejectedError):
        server.submit(_agg(s, t))
    stats = health.global_stats()
    assert stats["drains"] == 1
    # a second drain on a closed server is a no-op
    assert server.drain(timeout=0.1) == 0.0
    assert health.global_stats()["drains"] == 1
    s.stop()


def test_server_drain_finishes_inflight(rng):
    t = _table(rng)
    s = tpu_session()
    server = s.server(max_concurrency=2)
    ticket = server.submit(_agg(s, t))
    rows = _rows(ticket.result(timeout=120))
    server.drain(timeout=30.0)
    # the completed ticket keeps its rows; the server is closed
    assert _rows(ticket.result(timeout=0.1)) == rows
    assert server.closed
    s.stop()
