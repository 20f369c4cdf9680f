"""``trace/reduce.py`` on a trace built by hand: overlapping device
operations count once, the idle share and the attribution of each gap to a
host span come out as worked out by hand, and an unknown device kind in
``peaks.json`` raises."""

import os
from types import SimpleNamespace

import pytest
from conftest import BENCH, load

reduce = load(os.path.join(BENCH, "trace", "reduce.py"), "bench_reduce")
MS = 1_000_000


def hand_trace():
    # device: busy 0-1.5 ms (two ops overlapping by half), 3-4 ms, 9-10 ms
    device = [("sort", 0, 1 * MS), ("scatter", MS // 2, 1 * MS),
              ("sort", 3 * MS, 1 * MS), ("reduce", 9 * MS, 1 * MS)]
    host = [(reduce.WINDOW_SPAN, 0, 10 * MS),
            ("bench.to_arrow:q1", 0, 10 * MS),   # covers every gap
            ("io.d2h.wait", 4 * MS, 5 * MS),     # covers the 4-9 ms gap
            ("plan", 1 * MS + MS // 2, MS)]      # inside the 1.5-3 ms gap
    return reduce.Trace(device={"/device:TPU:0": device}, host=host)


def test_busy_idle_and_gaps_by_hand():
    out = reduce.reduce(hand_trace())
    assert out["window_s"] == pytest.approx(0.010)
    assert out["busy_s"] == pytest.approx(0.0035)  # 1.5 + 1 + 1, not 4
    assert out["events"] == 4
    assert dict(map(tuple, out["device_ops"])) == pytest.approx(
        {"sort": 0.002, "scatter": 0.001, "reduce": 0.001})
    # the 5 ms gap lies wholly inside io.d2h.wait, the shortest span that
    # covers it; the 1.5 ms gap only inside the outer span
    assert dict(map(tuple, out["idle_gaps"])) == pytest.approx(
        {"io.d2h.wait": 0.005, "bench.to_arrow:q1": 0.0015})
    idle = load(os.path.join(BENCH, "readers", "trace_idle_share.py"),
                "bench_idle")
    assert idle.read(SimpleNamespace(trace=out)) == pytest.approx(65.0)
    assert idle.read(SimpleNamespace(trace=None)) is None


def test_window_clips_and_two_chips_average():
    t = hand_trace()
    t.device["/device:TPU:1"] = [("sort", 0, 10 * MS)]
    out = reduce.reduce(t, window=(0, 5 * MS))
    # chip 0: 1.5 + 1 ms inside [0, 5); chip 1: 5 ms
    assert out["busy_s"] == pytest.approx((0.0025 + 0.005) / 2)
    assert out["window_s"] == pytest.approx(0.005)


def test_no_window_span_takes_the_extent():
    t = hand_trace()
    t.host = [e for e in t.host if e[0] != reduce.WINDOW_SPAN]
    assert reduce.window_of(t) == (0, 10 * MS)


def test_unknown_device_kind_raises():
    peaks = os.path.join(BENCH, "trace", "peaks.json")
    assert reduce.load_peaks(peaks, "TPU v5 lite")["hbm_bytes_per_s"] \
        == 819e9
    with pytest.raises(KeyError, match="TPU v9 ultra"):
        reduce.load_peaks(peaks, "TPU v9 ultra")
