"""Pallas low-cardinality aggregate kernel tests (exec/pallas_agg.py).

Runs in interpret mode on the CPU backend; asserts the sort-free path is
actually taken (pallasAggBatches metric) and that its results are
identical to both the sorted-segment kernel and the CPU oracle."""

import datetime as dt

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as st
from spark_rapids_tpu import functions as F
from spark_rapids_tpu.api import col
from tests.compare import assert_tpu_and_cpu_equal, tpu_session


def _agg_exec(session):
    pr = session._last_plan_result

    def find(n):
        if type(n).__name__ == "TpuHashAggregateExec":
            return n
        for c in n.children:
            r = find(c)
            if r is not None:
                return r
    return find(pr.physical)


def _run(session, t, conf_pallas="true"):
    session.set_conf("spark.rapids.sql.tpu.pallas.agg.enabled",
                     conf_pallas)
    df = session.create_dataframe(t).group_by("k").agg(
        F.count(col("v")).alias("c"), F.sum(col("v")).alias("s"),
        F.min(col("v")).alias("mn"), F.max(col("v")).alias("mx"),
        F.avg(col("v")).alias("a"))
    out = df.to_arrow()
    used = _agg_exec(session).metrics["pallasAggBatches"].value
    return sorted(out.to_pylist(), key=lambda r: (r["k"] is None,
                                                  r["k"])), used


def _table(n=5000, lo=-20, hi=20, null_keys=0.1, seed=0):
    rng = np.random.default_rng(seed)
    keys = [None if rng.random() < null_keys
            else int(x) for x in rng.integers(lo, hi, n)]
    vals = [None if rng.random() < 0.07 else float(x)
            for x in rng.normal(size=n)]
    return pa.table({"k": pa.array(keys, pa.int64()),
                     "v": pa.array(vals, pa.float64())})


def test_pallas_agg_matches_sorted_kernel():
    t = _table()
    s = tpu_session()
    fast, used_fast = _run(s, t, "true")
    assert used_fast > 0, "pallas path was not taken"
    slow, used_slow = _run(s, t, "false")
    assert used_slow == 0
    # identical group sets/counts/extrema; float sums differ only in
    # accumulation order (the variableFloatAgg caveat the reference
    # documents, RapidsConf.scala ENABLE_FLOAT_AGG)
    assert len(fast) == len(slow)
    for a, b in zip(fast, slow):
        assert a["k"] == b["k"] and a["c"] == b["c"]
        assert a["mn"] == b["mn"] and a["mx"] == b["mx"]
        assert a["s"] == pytest.approx(b["s"], rel=1e-12)
        assert a["a"] == pytest.approx(b["a"], rel=1e-12)


def test_pallas_agg_compare_cpu():
    t = _table(seed=3)
    s = tpu_session()
    s.set_conf("spark.rapids.sql.tpu.pallas.agg.enabled", "true")
    assert_tpu_and_cpu_equal(
        lambda s2: s2.create_dataframe(t).group_by("k").agg(
            F.count(col("v")).alias("c"), F.sum(col("v")).alias("s"),
            F.avg(col("v")).alias("a")),
        approx_float=True)


def test_pallas_agg_nan_min_max_semantics():
    """Spark NaN ordering through the pallas planes: max -> NaN when any
    NaN; min ignores NaN unless the group is all-NaN."""
    t = pa.table({
        "k": pa.array([0, 0, 1, 1, 2], pa.int64()),
        "v": pa.array([1.0, float("nan"), float("nan"), float("nan"),
                       5.0]),
    })
    s = tpu_session()
    out, used = _run(s, t)
    assert used > 0
    by_k = {r["k"]: r for r in out}
    assert by_k[0]["mn"] == 1.0 and np.isnan(by_k[0]["mx"])
    assert np.isnan(by_k[1]["mn"]) and np.isnan(by_k[1]["mx"])
    assert by_k[2]["mn"] == 5.0 and by_k[2]["mx"] == 5.0


def test_pallas_agg_int_sums_exact():
    """int64 sums must wrap exactly like the sorted kernel (no float
    accumulation)."""
    big = (1 << 62)
    t = pa.table({"k": pa.array([0, 0, 1], pa.int64()),
                  "v": pa.array([big, big, 7], pa.int64())})
    s = tpu_session()
    s.set_conf("spark.rapids.sql.tpu.pallas.agg.enabled", "true")
    df = s.create_dataframe(t).group_by("k").agg(
        F.sum(col("v")).alias("s"))
    out = {r["k"]: r["s"] for r in df.to_arrow().to_pylist()}
    assert _agg_exec(s).metrics["pallasAggBatches"].value > 0
    assert out[0] == -(1 << 63)  # 2^62 + 2^62 wraps to INT64_MIN
    assert out[1] == 7


def test_pallas_agg_int_sum_limbs_do_not_overflow_the_lane_fold():
    """A 16-bit limb summed over more than 2^15 rows of one group passes
    2^31: the 128 int32 lane accumulators must fold in int64."""
    n = 70_000
    big = (1 << 40) - 1  # limbs 0xFFFF, 0xFFFF, 0x00FF
    t = pa.table({"k": pa.array([3] * n, pa.int64()),
                  "v": pa.array([big] * n, pa.int64())})
    s = tpu_session()
    s.set_conf("spark.rapids.sql.tpu.pallas.agg.enabled", "true")
    df = s.create_dataframe(t).group_by("k").agg(
        F.sum(col("v")).alias("s"), F.count(col("v")).alias("c"))
    out = df.to_arrow().to_pylist()
    assert _agg_exec(s).metrics["pallasAggBatches"].value > 0
    assert out == [{"k": 3, "s": n * big, "c": n}]


def test_pallas_agg_wide_domain_falls_back():
    rng = np.random.default_rng(1)
    t = pa.table({
        "k": pa.array(rng.integers(0, 10**9, 3000), pa.int64()),
        "v": pa.array(rng.normal(size=3000)),
    })
    s = tpu_session()
    _, used = _run(s, t)
    assert used == 0  # domain too wide -> sorted kernel


def test_pallas_agg_date_key():
    base = dt.date(2020, 1, 1)
    t = pa.table({
        "k": pa.array([base + dt.timedelta(days=i % 7)
                       for i in range(500)]),
        "v": pa.array(np.arange(500, dtype=np.float64)),
    })
    s = tpu_session()
    s.set_conf("spark.rapids.sql.tpu.pallas.agg.enabled", "true")
    df = s.create_dataframe(t).group_by("k").agg(
        F.count(col("v")).alias("c"))
    out = df.to_arrow()
    assert _agg_exec(s).metrics["pallasAggBatches"].value > 0
    assert out.num_rows == 7
    assert sum(out.column("c").to_pylist()) == 500
    assert_tpu_and_cpu_equal(
        lambda s2: s2.create_dataframe(t).group_by("k").agg(
            F.count(col("v")).alias("c")))


def test_pallas_agg_multi_batch_merge(tmp_path):
    """Pallas updates per row-group batch, sorted merge combines."""
    import pyarrow.parquet as pq
    rng = np.random.default_rng(5)
    n = 40_000
    t = pa.table({"k": pa.array(rng.integers(-5, 6, n), pa.int64()),
                  "v": pa.array(rng.normal(size=n))})
    p = str(tmp_path / "m.parquet")
    pq.write_table(t, p, row_group_size=8_000)
    s = tpu_session({"spark.rapids.sql.reader.batchSizeRows": "8192",
                     # keep coalesce from merging the scan batches so the
                     # agg runs several pallas updates + one sorted merge
                     "spark.rapids.sql.batchSizeBytes": "131072"})
    s.set_conf("spark.rapids.sql.tpu.pallas.agg.enabled", "true")
    df = s.read.parquet(p).group_by("k").agg(
        F.sum(col("v")).alias("s"), F.count(col("v")).alias("c"))
    out = df.to_arrow()
    assert _agg_exec(s).metrics["pallasAggBatches"].value > 1
    assert out.num_rows == 11
    assert sum(out.column("c").to_pylist()) == n


def test_pallas_agg_narrow_int_all_null_group_merge(tmp_path):
    """An all-null narrow-int group's min/max sentinel must survive the
    cast back and lose the cross-batch merge (int32 extremes would wrap
    to -1/0 in int8)."""
    import pyarrow.parquet as pq
    t = pa.table({
        "k": pa.array([1, 1, 1, 1], pa.int64()),
        "v": pa.array([None, None, 5, -7], pa.int8()),
    })
    p = str(tmp_path / "n.parquet")
    pq.write_table(t, p, row_group_size=2)  # batch1 all-null, batch2 real
    s = tpu_session({"spark.rapids.sql.reader.batchSizeRows": "2",
                     "spark.rapids.sql.batchSizeBytes": "64"})
    s.set_conf("spark.rapids.sql.tpu.pallas.agg.enabled", "true")
    out = s.read.parquet(p).group_by("k").agg(
        F.min(col("v")).alias("mn"), F.max(col("v")).alias("mx")
    ).to_arrow()
    assert _agg_exec(s).metrics["pallasAggBatches"].value >= 1
    assert out.to_pylist() == [{"k": 1, "mn": -7, "mx": 5}]
