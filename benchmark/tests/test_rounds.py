"""``query_s`` on made-up runs: window start to last completion over the
queries completed (``readers/seconds_per_query.py``), all the work over all
the time, so a stall and the harness's own time show in it; and what the
``window`` line says beside it of a run's rounds (``run.py: rounds_of``,
``rounds_summary``, whose ``median_s_per_query`` is the diagnostic that no
stall moves) and of the collector."""

import gc
import os
from types import SimpleNamespace

import pytest
from conftest import BENCH, load

harness = load(os.path.join(BENCH, "run.py"), "rounds_run")
query_s = load(os.path.join(BENCH, "readers", "seconds_per_query.py"),
               "mean_reader").read


def median_of_rounds(run):
    return harness.rounds_summary(run.rounds, run.streams,
                                  run.window_start).get("median_s_per_query")


STREAM = {"kind": "stream", "names": ["q1", "q6"], "streams": 1}
SERVED = {"kind": "served", "names": ["q6", "q1"], "streams": 2}


def made_up(kind, rounds, took=(0.03, 0.06), gap=0.0, slow=None, fail=None):
    """``rounds`` rounds a stream, back to back from 100.0 s with ``gap``
    seconds of harness between two executions; round ``slow`` of stream 0
    has ``slow[1]`` seconds more in its last execution, and that execution
    of round ``fail`` failed."""
    executions = []
    for stream in range(kind["streams"]):
        now = 100.0
        for r in range(rounds):
            for i, name in enumerate(kind["names"]):
                took_now = took[i]
                last = i == len(kind["names"]) - 1
                if slow and stream == 0 and r == slow[0] and last:
                    took_now += slow[1]
                executions.append({
                    "name": name, "stream": stream, "start": now,
                    "end": now + took_now,
                    "ok": not (fail == r and stream == 0 and last)})
                now += took_now + gap
    executions.sort(key=lambda e: e["start"])
    return SimpleNamespace(
        executions=executions, streams=kind["streams"], window_start=100.0,
        rounds=harness.rounds_of(executions, len(kind["names"])))


@pytest.mark.parametrize("kind", (STREAM, SERVED), ids=("stream", "served"))
def test_equal_rounds_read_alike(kind):
    run = made_up(kind, 50)
    assert len(run.rounds) == 50 * kind["streams"]
    # stream: a pass of 0.09 s completes two; served: a cycle of 0.09 s on
    # each of two streams completes four
    assert query_s(run) == pytest.approx(0.09 / (2 * kind["streams"]))
    assert median_of_rounds(run) == pytest.approx(query_s(run))


@pytest.mark.parametrize("kind", (STREAM, SERVED), ids=("stream", "served"))
def test_a_stall_shows_in_query_s_and_not_in_the_median_of_rounds(kind):
    """The stall is the engine's time, which its users feel: the end-to-end
    number holds it whole; the diagnostic beside it says what the window
    would have read without."""
    calm, stalled = made_up(kind, 500), made_up(kind, 500, slow=(250, 3.0))
    assert query_s(stalled) == pytest.approx(
        query_s(calm) + 3.0 / (500 * 2 * kind["streams"]))
    assert query_s(stalled) > 1.05 * query_s(calm)
    assert median_of_rounds(stalled) == median_of_rounds(calm)


def test_harness_time_between_executions_is_in_query_s_and_in_no_round():
    calm, padded = made_up(STREAM, 100), made_up(STREAM, 100, gap=0.002)
    assert query_s(padded) > 1.04 * query_s(calm)
    assert median_of_rounds(padded) == pytest.approx(median_of_rounds(calm))


def test_a_failed_request_is_not_completed_and_its_round_is_left_out():
    # every other round is slow, so the median of 6 differs from that of 5
    run = made_up(STREAM, 6)
    for r in run.rounds[::2]:
        r["seconds"] += 1.0
    whole = median_of_rounds(run)
    failed = made_up(STREAM, 6, fail=0)
    assert [r["ok"] for r in failed.rounds] == [False] + [True] * 5
    for r in failed.rounds[::2]:
        r["seconds"] += 1.0
    assert whole == pytest.approx((0.09 + 1.09) / 2 / 2)
    assert median_of_rounds(failed) == pytest.approx(0.09 / 2)
    # the same window over eleven completed queries, not twelve
    assert query_s(failed) == pytest.approx(6 * 0.09 / 11)


def test_streams_are_grouped_apart():
    """Two streams whose requests interleave in time: a round is one
    stream's cycle, never one request of each."""
    run = made_up(SERVED, 3)
    assert [r["stream"] for r in run.rounds] == [0, 1] * 3
    names = {(e["stream"], e["start"]): e["name"] for e in run.executions}
    for r in run.rounds:
        assert names[r["stream"], r["start"]] == "q6"
        assert r["seconds"] == pytest.approx(0.09) and r["queries"] == 2
    # a stream one cycle ahead of the other changes nothing
    ahead = [e for e in run.executions
             if not (e["stream"] == 1 and e["start"] > 100.1)]
    rounds = harness.rounds_of(ahead, 2)
    assert sorted(r["stream"] for r in rounds) == [0, 0, 0, 1]


def test_nothing_completed_nothing_to_read():
    cut = made_up(STREAM, 1).executions[:1]  # half a pass
    assert harness.rounds_of(cut, 2) == []
    only_failed = made_up(STREAM, 1, fail=0)
    assert median_of_rounds(only_failed) is None
    only_failed.executions = [e for e in only_failed.executions
                              if not e["ok"]]
    assert query_s(only_failed) is None


def test_rounds_summary():
    run = made_up(STREAM, 500, slow=(250, 3.0))
    out = harness.rounds_summary(run.rounds, 1, run.window_start)
    assert out["count"] == 500 and out["failed"] == 0
    assert out["median_s"] == pytest.approx(0.09)
    assert out["median_s_per_query"] == pytest.approx(0.045)
    assert out["longest_s"] == pytest.approx(3.09)
    # 490 of 500 rounds lie at or under it: ten beyond
    assert out["tail"]["percentile"] == pytest.approx(98.0)
    assert out["tail"]["seconds"] == pytest.approx(0.09)
    assert out["stalls"]["count"] == 1
    assert out["stalls"]["seconds"] == pytest.approx(3.09)
    (stream, at, seconds), = out["stalls"]["longest"]
    assert (stream, seconds) == (0, pytest.approx(3.09))
    assert at == pytest.approx(250 * 0.09)
    few = harness.rounds_summary(made_up(STREAM, 10).rounds, 1, 100.0)
    assert "tail" not in few and few["stalls"]["count"] == 0
    assert harness.rounds_summary([], 1, 100.0) == {"count": 0, "failed": 0}


def test_gc_clock_counts_collections_while_entered():
    clock = harness.GcClock()
    gc.collect()  # before: not counted
    import time
    since = time.perf_counter()
    with clock:
        gc.collect(0)
        gc.collect(2)
        gc.collect(2)
    gc.collect()  # after: not counted
    out = clock.summary(since)
    assert out["collections"] == [1, 0, 2]
    assert out["seconds"] == pytest.approx(sum(p[2] for p in clock.pauses))
    assert len(out["longest"]) == 3
    assert out["longest"][0][2] == max(s for _, _, s in out["longest"])
    assert all(at >= 0 and g in (0, 2) for at, g, _ in out["longest"])
    assert clock not in gc.callbacks
