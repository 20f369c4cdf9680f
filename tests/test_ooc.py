"""Out-of-core device execution (docs/out_of_core.md): with
``spark.rapids.sql.ooc.enabled`` on an ICI session, join / aggregate /
sort fragments whose drained working set exceeds
``spark.rapids.shuffle.ici.maxStageBytes`` execute as grace-style
partitioned operators — phase-1 hash partition into spill-resident
partitions (encoded planes spill as-is), phase-2 streams bounded
partition pairs through HBM — instead of degrading the whole fragment
to the host path over one giant concatenated batch.

Reference: the plugin's sized hash join partitions an oversized build
side, its sort spills sorted runs and merges them back, and aggregates
re-partition on RetryOOM (GpuShuffledSizedHashJoinExec.scala,
GpuSortExec.scala, GpuHashAggregateExec's repartition path).
"""


import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.api import col
from spark_rapids_tpu.exec import meshexec, ooc
from tests.compare import (
    assert_tables_equal, assert_tpu_and_cpu_equal, sum_plan_metric,
    tpu_session,
)
from tests.fuzzer import gen_table

multichip = pytest.mark.multichip
slow = pytest.mark.slow

ICI = {"spark.rapids.shuffle.mode": "ici"}


def _ooc_conf(budget=16384, **extra):
    """ICI session with a stage budget tiny enough that a few-thousand
    row input must go out of core, and OOC on.  16 KiB keeps any single
    grouping key's rows under the budget (a partition holding ONE key
    can never split by key hash — by design it would be a counted
    fallback, which these tests pin to zero)."""
    conf = dict(ICI)
    conf["spark.rapids.shuffle.ici.maxStageBytes"] = str(budget)
    conf["spark.rapids.sql.ooc.enabled"] = "true"
    conf.update(extra)
    return conf


def _table(rng, n=4000):
    return pa.table({
        "k": pa.array(rng.integers(0, 37, n), pa.int64()),
        "v": pa.array(rng.normal(size=n)),
        "w": pa.array(rng.integers(-5, 5, n), pa.int64()),
    })


def _no_fallbacks(s):
    """The acceptance gate: the over-budget stage stayed on-device —
    no blanket over-budget degrade (iciFallbacks counts it per plan),
    no per-partition host fallback.  The process-global
    ``fallbacks_over_budget`` counter is asserted by delta in
    test_ooc_beats_forced_host_fallback_wallclock (other tests in the
    same process legitimately bump it)."""
    assert sum_plan_metric(s, "iciFallbacks") == 0
    assert sum_plan_metric(s, "oocFallbacks") == 0
    assert ooc.ooc_stats()["fallbacks"] == 0


# -- the tentpole: over-budget stages stay on-device ------------------------

@multichip
def test_ooc_agg_sort_over_budget_stays_on_device(rng):
    """agg-under-exchange + global sort, input ~10x the stage budget:
    both fragments grace-partition instead of degrading, results match
    the CPU and the host-mode TPU path row for row."""
    t = _table(rng)

    def build(s):
        df = s.create_dataframe(t)
        return (df.group_by(col("k"))
                  .agg(F.count(col("v")).alias("c"),
                       F.sum(col("v")).alias("s"),
                       F.min(col("w")).alias("mn"),
                       F.max(col("v")).alias("mx"))
                  .order_by(col("k")))

    def check(s):
        assert sum_plan_metric(s, "oocPartitions") > 0, \
            "the over-budget stages must grace-partition"
        _no_fallbacks(s)

    ooc_t = assert_tpu_and_cpu_equal(build, conf=_ooc_conf(),
                                     ignore_order=False,
                                     approx_float=True,
                                     tpu_check=check)
    host_t = build(tpu_session()).to_arrow()
    assert_tables_equal(ooc_t, host_t, ignore_order=False,
                        approx_float=True)


@multichip
@pytest.mark.parametrize("how", ["inner", "left", "right", "full",
                                 "semi", "anti"])
def test_ooc_join_types_match_cpu(rng, how):
    """Co-partitioning correctness: both sides split with the same
    K/salt, so every equi-join type is correct per partition pair —
    including the null-producing outer types and the existence types."""
    t1 = _table(rng, 1500)
    t2 = pa.table({
        "k": pa.array(rng.integers(0, 37, 1000), pa.int64()),
        "u": pa.array(rng.normal(size=1000)),
    })
    conf = _ooc_conf()
    conf["spark.sql.autoBroadcastJoinThreshold"] = "-1"

    def build(s):
        a = s.create_dataframe(t1)
        b = s.create_dataframe(t2)
        return a.join(b, on="k", how=how)

    def check(s):
        assert sum_plan_metric(s, "oocPartitions") > 0
        _no_fallbacks(s)

    assert_tpu_and_cpu_equal(build, conf=conf, approx_float=True,
                             tpu_check=check)


@multichip
@slow
def test_ooc_sort_multipass_merge(rng):
    """More runs than ooc.sort.mergeWidth=2 forces the multi-pass
    merge: folds re-spill as longer runs (counted as recursions) until
    one final streaming pass remains."""
    n = 20_000
    t = pa.table({
        "k": pa.array(rng.integers(0, 1000, n), pa.int64()),
        "v": pa.array(rng.normal(size=n)),
    })

    def build(s):
        return s.create_dataframe(t).order_by(col("k"), col("v"))

    def check(s):
        snap = ooc.ooc_stats()
        assert snap["partitions"] > 2, "run generation must spill runs"
        assert snap["merge_steps"] > 0
        assert snap["recursions"] > 0, \
            "width 2 over many runs must merge in multiple passes"
        _no_fallbacks(s)

    assert_tpu_and_cpu_equal(
        build,
        conf=_ooc_conf(budget=4096,
                       **{"spark.rapids.sql.ooc.sort.mergeWidth": "2"}),
        ignore_order=False, approx_float=True, tpu_check=check)


@multichip
def test_ooc_sort_strings_widen_across_runs(rng):
    """Runs generated from different chunks bucket different char
    widths; the merge widens every block to the per-column max before
    concatenating windows."""
    n = 6000
    words = [f"{'x' * int(i % 17)}{i % 251:03d}" for i in range(n)]
    rng.shuffle(words)
    t = pa.table({
        "s": pa.array(words),
        "v": pa.array(rng.normal(size=n)),
    })

    def build(s):
        return s.create_dataframe(t).order_by(col("s"), col("v"))

    def check(s):
        assert ooc.ooc_stats()["merge_steps"] > 0
        _no_fallbacks(s)

    assert_tpu_and_cpu_equal(build, conf=_ooc_conf(budget=8192),
                             ignore_order=False, approx_float=True,
                             tpu_check=check)


# -- off is byte-identical --------------------------------------------------

@multichip
def test_ooc_off_keeps_old_fallback_and_stays_inert(rng):
    """Default off: the over-budget stage degrades to the host path
    exactly as before (iciFallbacks counted), with ZERO out-of-core
    side effects — no metrics, no snapshot counters, no journal events
    — and the plan renders identically whether the key is absent or
    explicitly false."""
    t = _table(rng)
    tiny = dict(ICI)
    tiny["spark.rapids.shuffle.ici.maxStageBytes"] = "16384"

    # agg only (no order_by): the aggregate fragment's gate estimate
    # comes from the host-known 4000-row scan batch, so the off-path
    # decision is deterministic (a downstream sort's estimate rides a
    # LazyRows count whose sync is timing-dependent, pre-OOC behavior)
    def build(s):
        df = s.create_dataframe(t)
        return (df.group_by(col("k"))
                  .agg(F.sum(col("v")).alias("s")))

    def check(s):
        assert sum_plan_metric(s, "iciFallbacks") >= 1, \
            "off must keep the pre-OOC blanket over-budget degrade"
        assert sum_plan_metric(s, "oocPartitions") == 0
        assert sum_plan_metric(s, "oocSpillBytes") == 0
        snap = ooc.ooc_stats()
        assert all(v == 0 for v in snap.values()), snap

    ooc.reset_ooc_stats()
    absent_t = assert_tpu_and_cpu_equal(build, conf=tiny,
                                        approx_float=True,
                                        tpu_check=check)

    explicit = dict(tiny)
    explicit["spark.rapids.sql.ooc.enabled"] = "false"
    s_abs, s_exp = tpu_session(tiny), tpu_session(explicit)
    df_abs, df_exp = build(s_abs), build(s_exp)
    assert df_abs.explain() == df_exp.explain(), \
        "ooc.enabled=false must not perturb the plan"
    # both runs see identical process-global AQE exchange stats (the
    # measured-bytes estimates feed the over-budget gate): reset them
    # before each so the two sessions make the same cold decisions
    from spark_rapids_tpu.exec import aqe
    aqe.reset_stats()
    t_abs = df_abs.to_arrow()
    aqe.reset_stats()
    t_exp = df_exp.to_arrow()
    assert t_abs.equals(t_exp), "absent vs false: results byte-differ"
    assert_tables_equal(t_abs, absent_t, approx_float=True)
    # identical metric STRUCTURE: same operator metric names, and the
    # ooc counters never minted on either plan
    def metric_names(s):
        names = set()

        def walk(node):
            names.update(n for n, _ in node.metrics.items())
            for c in node.children:
                walk(c)
        walk(s._last_plan_result.physical)
        return names
    assert metric_names(s_abs) == metric_names(s_exp)
    assert ooc.ooc_stats()["partitions"] == 0


# -- OOC answers what the forced host fallback answers ----------------------

@multichip
def test_ooc_matches_forced_host_fallback_without_degrading(rng):
    """An over-budget sort + aggregate workload streams grace partitions
    through HBM (``oocPartitions``), never consults the over-budget
    degrade, never falls back, and answers what the forced host
    fallback over one giant concatenated batch answers.  Each path runs
    twice, so the second run meets warm kernels and warm statistics.
    Which of the two is faster is the chip's to say (ROADMAP R2), not
    two CPU clocks over an emulated mesh."""
    n = 60_000
    t = pa.table({
        "k": pa.array(rng.integers(0, 5000, n), pa.int64()),
        "v": pa.array(rng.normal(size=n)),
    })

    def build(s):
        df = s.create_dataframe(t)
        return (df.group_by(col("k"))
                  .agg(F.sum(col("v")).alias("s"),
                       F.count(col("v")).alias("c"))
                  .order_by(col("k"), col("s")))

    def run(conf):
        s = tpu_session(conf)
        build(s).to_arrow()          # the warm run
        return build(s).to_arrow(), s

    tiny = dict(ICI)
    tiny["spark.rapids.shuffle.ici.maxStageBytes"] = "65536"
    ooc.reset_ooc_stats()
    over_budget_before = meshexec.ici_stats()["fallbacks_over_budget"]
    ooc_out, s = run(_ooc_conf(budget=65536))
    assert sum_plan_metric(s, "oocPartitions") > 0
    assert meshexec.ici_stats()["fallbacks_over_budget"] \
        == over_budget_before, \
        "the OOC runs must never consult the over-budget degrade"
    assert ooc.ooc_stats()["fallbacks"] == 0
    off_out, _ = run(tiny)
    assert_tables_equal(ooc_out, off_out, ignore_order=False,
                        approx_float=True)


# -- fallback matrix --------------------------------------------------------

@multichip
@pytest.mark.faults
def test_ooc_partition_fault_recovers_losslessly(rng, fault_conf):
    """An injected ``ooc.partition`` fault abandons the grace pass
    mid-flight: already-spilled partitions, the in-flight batch, and
    every unread handle re-concatenate on the host path (oocFallbacks
    counted) — the query stays correct with nothing lost."""
    from spark_rapids_tpu import faults
    t = _table(rng)
    conf = dict(fault_conf)
    conf.update(_ooc_conf())
    conf["spark.rapids.faults.ooc.partition"] = "always"
    faults.configure_from_conf(conf)

    def build(s):
        df = s.create_dataframe(t)
        return (df.group_by(col("k"))
                  .agg(F.sum(col("v")).alias("s"),
                       F.count(col("w")).alias("c"))
                  .order_by(col("k")))

    def check(s):
        assert sum_plan_metric(s, "oocFallbacks") >= 1
        assert ooc.ooc_stats()["fallbacks"] >= 1

    assert_tpu_and_cpu_equal(build, conf=conf, ignore_order=False,
                             approx_float=True, tpu_check=check)


@multichip
def test_ooc_single_key_partition_counts_fallback(rng):
    """The recursion bound: a partition owning ONE grouping key's rows
    can never split by key hash under any salt — at maxRecursionDepth
    it degrades to the host path for that partition only, counted, and
    the query stays correct."""
    n = 4000
    t = pa.table({
        "k": pa.array(np.zeros(n), pa.int64()),  # one key owns it all
        "v": pa.array(rng.normal(size=n)),
    })

    def build(s):
        df = s.create_dataframe(t)
        return (df.group_by(col("k"))
                  .agg(F.sum(col("v")).alias("s"),
                       F.count(col("v")).alias("c")))

    def check(s):
        snap = ooc.ooc_stats()
        assert snap["recursions"] >= 1, \
            "the over-budget partition must re-salt before giving up"
        assert snap["fallbacks"] >= 1
        assert sum_plan_metric(s, "oocFallbacks") >= 1

    assert_tpu_and_cpu_equal(build, conf=_ooc_conf(budget=4096),
                             approx_float=True, tpu_check=check)


# -- fuzz + representative suites -------------------------------------------

@multichip
@pytest.mark.parametrize("seed", [7, 21, 42])
def test_ooc_fuzz_matches_cpu(seed):
    t = gen_table(seed, [("k", pa.int64()), ("v", pa.float64()),
                         ("w", pa.int32())], 2500)

    def build(s):
        df = s.create_dataframe(t)
        return (df.group_by(col("k"))
                  .agg(F.count(col("v")).alias("c"),
                       F.sum(col("w")).alias("sw"))
                  .order_by(col("k")))

    assert_tpu_and_cpu_equal(build, conf=_ooc_conf(),
                             ignore_order=False, approx_float=True)


@pytest.fixture(scope="module")
def tpch_paths(tmp_path_factory):
    from spark_rapids_tpu.bench.tpch import gen_tpch
    d = tmp_path_factory.mktemp("tpch_ooc")
    return gen_tpch(str(d), lineitem_rows=8_000)


@multichip
@slow
def test_ooc_tpch_q3_matches_cpu(tpch_paths):
    from spark_rapids_tpu.bench.tpch import TPCH_QUERIES, load_tables

    def build(s):
        return TPCH_QUERIES["q3"](load_tables(s, tpch_paths))

    def check(s):
        assert sum_plan_metric(s, "oocPartitions") > 0, \
            "q3's join/agg stages must exceed the tiny budget"

    assert_tpu_and_cpu_equal(build, conf=_ooc_conf(budget=32768),
                             approx_float=True, tpu_check=check)


@multichip
@slow
def test_ooc_tpcxbb_q3_matches_cpu(tmp_path_factory):
    from spark_rapids_tpu.bench.tpcxbb import (
        TPCXBB_QUERIES, gen_tpcxbb, register_views,
    )
    from tests.compare import cpu_session
    xbb = gen_tpcxbb(str(tmp_path_factory.mktemp("xbb_ooc")),
                     sales_rows=20_000)
    conf = _ooc_conf(budget=32768)
    conf["spark.rapids.sql.test.enabled"] = "false"
    s = tpu_session(conf)
    register_views(s, xbb)
    got = s.sql(TPCXBB_QUERIES["q3"]).to_arrow()
    cpu = cpu_session()
    register_views(cpu, xbb)
    want = cpu.sql(TPCXBB_QUERIES["q3"]).to_arrow()
    assert_tables_equal(got, want, approx_float=True)


# -- satellite: encoded planes survive the partition-spill seam -------------

def _dense_ref(col):
    vals, valid = col.to_numpy()
    return np.asarray(vals), np.asarray(valid)


def test_encoded_planes_spill_roundtrip_all_tiers():
    """The phase-1 contract: RLE / delta / packed-bool / dict-encoded
    planes spill AS-IS through all three tiers and come back
    byte-identical to their dense materialization — with another
    handle mid-promote on the same catalog, since phase 2 promotes
    partition i+1 while partition i's planes are still in flight."""
    import jax
    from spark_rapids_tpu.columnar import encoding
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.dtypes import (
        BOOLEAN, INT32, INT64, STRING, Field, Schema,
    )
    from spark_rapids_tpu.compile.buckets import bucket_capacity
    from spark_rapids_tpu.memory.spill import BufferCatalog, SpillableBatch

    n = 1024
    cap = bucket_capacity(n)
    rng = np.random.default_rng(11)
    valid = np.ones(cap, np.bool_)

    # RLE: long runs
    rv = np.zeros(8, np.int64)
    rv[:4] = [5, -3, 5, 9]
    re_ = np.full(8, cap, np.int32)
    re_[:4] = [300, 600, 900, n]
    rle = encoding.RleColumn(INT64, jax.device_put(rv),
                             jax.device_put(re_), 4,
                             jax.device_put(valid), n, cap)
    # delta: small diffs off an int base
    deltas = np.zeros(cap, np.int8)
    deltas[1:n] = rng.integers(-3, 4, n - 1, dtype=np.int8)
    delta = encoding.DeltaColumn(
        INT32, jax.device_put(deltas),
        jax.device_put(np.asarray([1000], np.int32)),
        jax.device_put(valid), n, cap)
    # packed bool: one bit per row
    bits = np.zeros(cap, np.uint8)
    bits[:n] = rng.integers(0, 2, n, dtype=np.uint8)
    packed = encoding.PackedBoolColumn(
        jax.device_put(np.packbits(bits, bitorder="little")),
        jax.device_put(valid), n, cap)
    # dictionary-encoded strings
    enc = encoding.IngestEncoder(max_dict_fraction=1.0)
    dict_col = enc.upload_column(
        pa.array([f"s{int(i)}" for i in rng.integers(0, 7, n)]),
        STRING, cap)
    assert dict_col is not None

    cols = [rle, delta, packed, dict_col]
    refs = [_dense_ref(c) for c in cols]
    schema = Schema([Field("r", INT64), Field("d", INT32),
                     Field("b", BOOLEAN), Field("s", STRING)])
    batch = ColumnarBatch(cols, n, schema)

    cat = BufferCatalog(device_budget_bytes=1 << 40)
    sb = SpillableBatch(batch, cat)
    other = SpillableBatch(batch, cat)  # the concurrent partition
    try:
        for tier in ("host", "disk"):
            with cat._lock:
                sb._to_host()
                if tier == "disk":
                    sb._to_disk()
                other._to_host()
            # the other partition promotes first and stays device-
            # resident while sb comes back from the deeper tier
            mid = other.get()
            assert other.tier == "device" and mid is not None
            before = encoding.compressed_stats()["late_decodes"]
            out = sb.get()
            assert sb.tier == "device"
            assert encoding.compressed_stats()["late_decodes"] == before, \
                "a tier round trip must never decode a plane"
            for i, (got, (want_vals, want_valid), kind) in enumerate(zip(
                    out.columns, refs,
                    ("rle", "delta", "packed", "dict"))):
                assert type(got) is type(batch.columns[i]), kind
                vals, vld = got.to_numpy()
                np.testing.assert_array_equal(
                    np.asarray(vals), want_vals,
                    err_msg=f"{kind} values after {tier} round trip")
                np.testing.assert_array_equal(
                    np.asarray(vld), want_valid,
                    err_msg=f"{kind} validity after {tier} round trip")
    finally:
        sb.close()
        other.close()
