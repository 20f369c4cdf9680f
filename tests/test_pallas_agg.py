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
from spark_rapids_tpu.api import col, lit
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


# ---------------------------------------------------------------------------
# the static route: every key a dictionary code (or no key at all), so
# the host knows the domain without a pull (ISSUE 26)
# ---------------------------------------------------------------------------

_SMALL_BATCHES = {"spark.rapids.sql.reader.batchSizeRows": "8192",
                  # keep coalesce from merging the scan batches, so the
                  # aggregate runs one dense update per row group
                  "spark.rapids.sql.batchSizeBytes": "131072"}


def _AGGS():
    return (F.count(col("v")).alias("c"), F.sum(col("v")).alias("s"),
            F.sum(col("i")).alias("si"), F.min(col("i")).alias("mn"),
            F.max(col("v")).alias("mx"), F.avg(col("v")).alias("a"),
            F.count(lit(1)).alias("n"))


def _dict_table(n, cards, null_keys=0.0, seed=0, prefix="k"):
    """``len(cards)`` string keys of the given cardinalities, a float
    and an int32 value with nulls, and a filter column ``f`` in 0..99."""
    rng = np.random.default_rng(seed)
    cols = {}
    for ki, card in enumerate(cards):
        vals = [None if rng.random() < null_keys
                else f"{prefix}{ki}_{int(x):03d}"
                for x in rng.integers(0, card, n)]
        cols[f"k{ki}"] = pa.array(vals, pa.string())
    cols["v"] = pa.array([None if rng.random() < 0.05 else float(x)
                          for x in rng.normal(size=n)], pa.float64())
    cols["i"] = pa.array(rng.integers(-1000, 1000, n), pa.int32())
    cols["f"] = pa.array(rng.integers(0, 100, n), pa.int32())
    return pa.table(cols)


def _write(tmp_path, name, table, row_group_size=8_000):
    import pyarrow.parquet as pq
    p = str(tmp_path / name)
    pq.write_table(table, p, row_group_size=row_group_size)
    return p


def _dense_and_sorted(build):
    """``build(session) -> DataFrame`` run with the dense route on and
    off: (rows on, rows off, batches on, batches off), rows in the
    order the plan returned them."""
    out = []
    for on in ("true", "false"):
        s = tpu_session(_SMALL_BATCHES)
        s.set_conf("spark.rapids.sql.tpu.pallas.agg.enabled", on)
        rows = build(s).to_arrow().to_pylist()
        out.append((rows, _agg_exec(s).metrics["pallasAggBatches"].value))
    return out[0][0], out[1][0], out[0][1], out[1][1]


def _assert_same_groups(fast, slow):
    """Integers, counts, nulls and GROUP ORDER exact; float sums within
    f32 reassociation."""
    assert len(fast) == len(slow)
    for a, b in zip(fast, slow):
        assert a.keys() == b.keys()
        for name in a:
            if isinstance(b[name], float):
                assert a[name] == pytest.approx(b[name], rel=1e-5,
                                                abs=1e-5), name
            else:
                assert a[name] == b[name], name



def _case_two_keys(tmp_path):
    p = _write(tmp_path, "t.parquet", _dict_table(40_000, (3, 2)))
    return (lambda s: s.read.parquet(p).group_by("k0", "k1")
            .agg(*_AGGS())), 5, 6


def _case_three_keys(tmp_path):
    p = _write(tmp_path, "t.parquet", _dict_table(40_000, (3, 2, 5),
                                                  seed=1))
    return (lambda s: s.read.parquet(p).group_by("k0", "k1", "k2")
            .agg(*_AGGS())), 5, 30


def _case_null_keys(tmp_path):
    p = _write(tmp_path, "t.parquet",
               _dict_table(40_000, (3, 2), null_keys=0.2, seed=2))
    return (lambda s: s.read.parquet(p).group_by("k0", "k1")
            .agg(*_AGGS())), 5, 12


def _case_value_absent_from_batch(tmp_path):
    """``k0_002`` is in every row group's dictionary and no row that
    passes the filter carries it: its slots stay empty."""
    t = _dict_table(40_000, (3, 2), seed=3)
    f = np.where(np.asarray(t["k0"].to_pylist()) == "k0_002", 99,
                 np.asarray(t["f"]) % 90).astype(np.int32)
    t = t.set_column(t.schema.get_field_index("f"), "f", pa.array(f))
    p = _write(tmp_path, "t.parquet", t)
    return (lambda s: s.read.parquet(p).filter(col("f") < lit(95))
            .group_by("k0", "k1").agg(*_AGGS())), 5, 4


def _NO_ROW():
    """A predicate no row passes and no row group's statistics prune."""
    return col("f") + col("i") > lit(100_000)


def _case_all_filtered(tmp_path):
    p = _write(tmp_path, "t.parquet", _dict_table(40_000, (3, 2), seed=4))
    return (lambda s: s.read.parquet(p).filter(_NO_ROW())
            .group_by("k0", "k1").agg(*_AGGS())), 5, 0


def _case_keyless(tmp_path):
    p = _write(tmp_path, "t.parquet", _dict_table(40_000, (3,), seed=5))
    return (lambda s: s.read.parquet(p).filter(col("f") < lit(50))
            .agg(*_AGGS())), 5, 1


def _case_keyless_all_filtered(tmp_path):
    """No live row in any batch: one row of initial values."""
    p = _write(tmp_path, "t.parquet", _dict_table(40_000, (3,), seed=6))
    return (lambda s: s.read.parquet(p).filter(_NO_ROW())
            .agg(*_AGGS())), 5, 1


def _case_different_dictionaries(tmp_path):
    """Two files whose key columns hold different value sets: every
    partial is on its own dictionary and the concat re-keys them."""
    d = tmp_path / "parts"
    d.mkdir()
    _write(d, "a.parquet", _dict_table(16_000, (3, 2), seed=7))
    _write(d, "b.parquet", _dict_table(16_000, (4, 3), seed=8,
                                       prefix="z"))
    return (lambda s: s.read.parquet(str(d)).group_by("k0", "k1")
            .agg(*_AGGS())), 4, 18


@pytest.mark.parametrize("case", [
    _case_two_keys, _case_three_keys, _case_null_keys,
    _case_value_absent_from_batch, _case_all_filtered, _case_keyless,
    _case_keyless_all_filtered, _case_different_dictionaries,
], ids=lambda c: c.__name__[len("_case_"):])
def test_dense_route_matches_sorted_body(tmp_path, case):
    build, batches, groups = case(tmp_path)
    fast, slow, used_fast, used_slow = _dense_and_sorted(build)
    assert used_fast == batches, "the dense route was not taken"
    assert used_slow == 0
    assert len(slow) == groups
    _assert_same_groups(fast, slow)


def test_dense_route_domain_over_max_k_falls_back(tmp_path):
    """41 x 41 slots > MAX_K: the sorted body runs, the same programs
    at the same capacities as with the route switched off."""
    from spark_rapids_tpu.exec import aggregate, pallas_agg
    p = _write(tmp_path, "t.parquet", _dict_table(40_000, (40, 40),
                                                  seed=9))
    assert 41 * 41 > pallas_agg.MAX_K

    def build(s):
        return s.read.parquet(p).group_by("k0", "k1").agg(*_AGGS())

    keys = []
    for on in ("true", "false"):
        aggregate._AGG_CACHE.clear()
        s = tpu_session(_SMALL_BATCHES)
        s.set_conf("spark.rapids.sql.tpu.pallas.agg.enabled", on)
        rows = build(s).to_arrow().to_pylist()
        assert _agg_exec(s).metrics["pallasAggBatches"].value == 0
        keys.append((sorted(map(repr, aggregate._AGG_CACHE._entries)),
                     rows))
    assert keys[0] == keys[1]
    assert len(keys[0][1]) == 1600


def test_dense_route_keyless_empty_batch_emits_initial_values(tmp_path):
    """Zero digits over a batch of zero rows: exactly one group of
    initial values, as the sorted body's empty-input rule gives."""
    from spark_rapids_tpu.exec.aggregate import _empty_input_batch
    p = _write(tmp_path, "t.parquet", _dict_table(100, (3,), seed=10))
    s = tpu_session()
    s.read.parquet(p).agg(*_AGGS()).to_arrow()
    node = _agg_exec(s)
    empty = _empty_input_batch(node.children[0].output_schema)
    before = node.metrics["pallasAggBatches"].value
    dense = node._run_update(empty, s.conf)
    assert node.metrics["pallasAggBatches"].value == before + 1
    plain = node._run_update(empty)
    assert dense.rows_bound == 1 and dense.capacity == 8
    assert dense.num_rows == plain.num_rows == 1
    for a, b in zip(dense.columns, plain.columns):
        (da, va), (db, vb) = a.to_numpy(), b.to_numpy()
        assert da.dtype == db.dtype
        np.testing.assert_array_equal(va[:1], vb[:1])
        np.testing.assert_array_equal(da[:1], db[:1])


def test_dense_partials_keep_downstream_at_the_domains_capacity(tmp_path):
    """A q1-shaped plan (filter, two dictionary keys, ORDER BY) over
    five batches: every partial is 16 slots under a bound of 12, so the
    concat, the merge, the sort and the egress pack are all keyed at
    128 or under — never at a capacity taken from the input."""
    from spark_rapids_tpu.columnar import transfer
    from spark_rapids_tpu.exec import aggregate, coalesce, sort
    p = _write(tmp_path, "t.parquet", _dict_table(40_000, (3, 2),
                                                  seed=11))
    caches = (coalesce._CONCAT_CACHE, sort._SORT_CACHE,
              transfer._PACK_CACHE, aggregate._AGG_CACHE)
    for c in caches:
        c.clear()
    s = tpu_session(_SMALL_BATCHES)
    out = (s.read.parquet(p).filter(col("f") < lit(90))
           .group_by("k0", "k1").agg(*_AGGS())
           .order_by("k0", "k1").to_arrow())
    assert out.num_rows == 6
    assert _agg_exec(s).metrics["pallasAggBatches"].value == 5
    concat_caps = [k[1] for k in coalesce._CONCAT_CACHE._entries]
    merge_caps = [k[3] for k in aggregate._AGG_CACHE._entries
                  if k[1] == "merge"]
    sort_caps = [k[2] for k in sort._SORT_CACHE._entries]
    pack_caps = [col_sig[1] for k in transfer._PACK_CACHE._entries
                 for batch_sig in k[0] for col_sig in batch_sig]
    assert concat_caps and merge_caps and sort_caps and pack_caps
    assert max(concat_caps + merge_caps + sort_caps + pack_caps) <= 128
    # no update ran the sorted body at all: every update program was
    # built over radices
    updates = [k for k in aggregate._AGG_CACHE._entries
               if k[0] == "folded"]
    assert updates and all(k[-1] is not None for k in updates)
