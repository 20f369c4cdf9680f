"""The bytes a query must read come from ``rooflines/<config>.json`` and the
configuration's rows and widths alone: no import of the engine, so they do
not change when its plan does."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest
from conftest import BENCH, load


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


def test_q6_bytes_are_rows_times_widths():
    reader = load(os.path.join(BENCH, "readers", "trace_roofline_share.py"),
                  "bench_roofline")
    config, roofline = _json("configs", "tpch_sf1.json"), \
        _json("rooflines", "tpch_sf1.json")
    widths = config["column_bytes"]["lineitem"]
    columns = ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice")
    assert sorted(c for _, c in roofline["q6"]) == sorted(columns)
    rows = config["rows"]["lineitem"]
    assert reader.query_bytes(config, roofline, "q6") == \
        rows * sum(widths[c] for c in columns) == 5_999_995 * 16
    assert reader.query_bytes(config, roofline, "q1") == rows * 22

    peaks = _json("trace", "peaks.json")["TPU v5 lite"]
    run = SimpleNamespace(
        trace={"busy_s": 2.0, "window_s": 3.0, "events": 1}, config=config,
        roofline=roofline, load_peaks=lambda: peaks,
        executions=[{"name": "q6", "ok": True}, {"name": "q1", "ok": True}])
    assert reader.read(run) == pytest.approx(
        100 * rows * (16 + 22) / 819e9 / 2.0)
    run.trace["busy_s"] = 0.0  # a device that ran nothing: nothing to read
    assert reader.read(run) is None


def test_no_engine_import():
    code = ("import sys, importlib.util as u\n"
            f"s = u.spec_from_file_location('r', {os.path.join(BENCH, 'readers', 'trace_roofline_share.py')!r})\n"
            "m = u.module_from_spec(s); s.loader.exec_module(m)\n"
            f"s = u.spec_from_file_location('t', {os.path.join(BENCH, 'trace', 'reduce.py')!r})\n"
            "m = u.module_from_spec(s); sys.modules['t'] = m\n"
            "s.loader.exec_module(m)\n"
            "assert not [k for k in sys.modules if k.startswith(('spark_rapids_tpu', 'jax'))]\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)
