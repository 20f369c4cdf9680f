"""Compressed-domain execution (docs/compressed.md): encoded-plane
ingest, code-domain kernels, and encoded egress/spill.

Coverage contract (ISSUE 12):
  * compressed on == off BYTE-IDENTICAL (values AND order) across
    parquet/ORC/CSV scans and hash/range exchanges;
  * fuzzed dictionary shapes (high/low cardinality, long-run RLE)
    against the CPU oracle;
  * shared-vs-disjoint-dictionary equi-joins against the CPU oracle;
  * a dict-key group-by completes with ``lateDecodes`` == 0;
  * TPC-H q1/q3 and TPCx-BB q3 run with ``encodedColumns > 0`` while
    still matching the CPU engine;
  * an injected ``io.encode`` fault degrades the column to the plain
    plane path, counted, with the query still correct;
  * the dictionary-heavy scan's wire ratio ``h2d_wire/h2d_raw <= 0.5``.
"""

import os

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar import encoding
from tests.compare import (
    assert_tables_equal, assert_tpu_and_cpu_equal, cpu_session,
    tpu_session,
)
from tests.fuzzer import gen_dict_table

CONF_ON = {"spark.rapids.sql.compressed.enabled": "true"}
CONF_OFF = {"spark.rapids.sql.compressed.enabled": "false"}


@pytest.fixture(scope="module")
def dict_paths(tmp_path_factory):
    """Dictionary-heavy fixture written in every scan format."""
    import pyarrow.csv as pacsv
    import pyarrow.orc as paorc
    import pyarrow.parquet as pq
    d = tmp_path_factory.mktemp("compressed")
    tbl = gen_dict_table(11, 4000, cardinality=12, null_prob=0.08)
    paths = {}
    p = str(d / "t.parquet")
    pq.write_table(tbl, p, row_group_size=1024)
    paths["parquet"] = p
    p = str(d / "t.orc")
    paorc.write_table(tbl, p)
    paths["orc"] = p
    p = str(d / "t.csv")
    # CSV cannot carry nulls distinguishably for strings; write a
    # null-free variant for the csv leg
    tbl_nn = gen_dict_table(12, 4000, cardinality=12, null_prob=0.0)
    pacsv.write_csv(tbl_nn, p)
    paths["csv"] = p
    return paths


def _read(s, fmt, path):
    return getattr(s.read, fmt)(path)


# ---------------------------------------------------------------------------
# on == off byte identity (values AND row order)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["parquet", "orc", "csv"])
def test_scan_on_off_byte_identical(dict_paths, fmt):
    q = lambda s: _read(s, fmt, dict_paths[fmt])  # noqa: E731
    on = q(tpu_session(CONF_ON)).to_arrow()
    off = q(tpu_session(CONF_OFF)).to_arrow()
    assert on.equals(off), f"{fmt} scan differs between compressed " \
        "on and off"


@pytest.mark.parametrize("mode", ["hash", "range"])
def test_exchange_on_off_byte_identical(dict_paths, mode):
    def q(s):
        df = _read(s, "parquet", dict_paths["parquet"])
        if mode == "hash":
            return df.repartition(4, "k")
        return df.order_by("k", "v")

    on = q(tpu_session(CONF_ON)).to_arrow()
    off = q(tpu_session(CONF_OFF)).to_arrow()
    assert on.equals(off), f"{mode} exchange differs between " \
        "compressed on and off"


def test_scan_values_match_cpu(dict_paths):
    assert_tpu_and_cpu_equal(
        lambda s: _read(s, "parquet", dict_paths["parquet"]),
        conf=CONF_ON)


# ---------------------------------------------------------------------------
# fuzzed dictionary shapes vs the CPU oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("card,run_length", [
    (4, 1),       # low cardinality: dictionary-heavy
    (400, 1),     # high cardinality near the maxDictFraction edge
    (6, 64),      # long-run RLE shape
])
def test_fuzz_dict_shapes_vs_cpu(tmp_path, card, run_length):
    import pyarrow.parquet as pq
    tbl = gen_dict_table(card * 7 + run_length, 3000,
                         cardinality=card, run_length=run_length)
    p = str(tmp_path / "fz.parquet")
    pq.write_table(tbl, p, row_group_size=777)

    def q(s):
        s.register_view("fz", s.read.parquet(p))
        return s.sql(
            "SELECT k, COUNT(*) AS c, SUM(v) AS sv, MIN(g) AS mg "
            "FROM fz WHERE k <> 'val_0001_' AND v > -500 "
            "GROUP BY k")

    assert_tpu_and_cpu_equal(q, conf=CONF_ON)


# ---------------------------------------------------------------------------
# code-domain joins: shared and disjoint dictionaries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shared", [True, False])
def test_join_shared_vs_disjoint_dictionary(tmp_path, shared):
    import pyarrow.parquet as pq
    rng = np.random.default_rng(7)
    n = 2500
    left_vals = [f"key{i}" for i in range(12)]
    # shared: both sides draw from one value set (same dictionary after
    # rank normalization); disjoint: the build side carries extra values
    # absent from the stream and misses some stream values
    right_vals = left_vals if shared else \
        [f"key{i}" for i in range(6, 24)]
    lt = pa.table({
        "k": pa.array([left_vals[i] for i in
                       rng.integers(0, len(left_vals), n)]),
        "v": pa.array(rng.integers(0, 1000, n), pa.int64()),
    })
    rt = pa.table({
        "k2": pa.array(right_vals),
        "w": pa.array(np.arange(len(right_vals)), pa.int64()),
    })
    # duplicate some build keys so the general (non-FK) path also runs
    rt = pa.concat_tables([rt, rt.slice(0, 3)])
    lp, rp = str(tmp_path / "l.parquet"), str(tmp_path / "r.parquet")
    pq.write_table(lt, lp)
    pq.write_table(rt, rp)

    def q(s):
        s.register_view("l", s.read.parquet(lp))
        s.register_view("r", s.read.parquet(rp))
        return s.sql("SELECT l.k, l.v, r.w FROM l JOIN r "
                     "ON l.k = r.k2")

    assert_tpu_and_cpu_equal(q, conf=CONF_ON)


def test_join_duplicate_key_ordinal_falls_back(tmp_path):
    """Two key pairs sharing one stream column (l.k = r.a AND
    l.k = r.b) must drop to the dense path instead of double-rekeying
    the shared ordinal (regression: AttributeError in for_stream)."""
    import pyarrow.parquet as pq
    rng = np.random.default_rng(9)
    lt = pa.table({
        "k": pa.array([f"key{i}" for i in rng.integers(0, 6, 400)]),
        "v": pa.array(rng.integers(0, 50, 400), pa.int64()),
    })
    rt = pa.table({
        "a": pa.array([f"key{i}" for i in range(6)]),
        "b": pa.array([f"key{i}" for i in range(6)]),
        "w": pa.array(np.arange(6), pa.int64()),
    })
    lp, rp = str(tmp_path / "l.parquet"), str(tmp_path / "r.parquet")
    pq.write_table(lt, lp)
    pq.write_table(rt, rp)

    def q(s):
        s.register_view("l", s.read.parquet(lp))
        s.register_view("r", s.read.parquet(rp))
        return s.sql("SELECT l.k, l.v, r.w FROM l JOIN r "
                     "ON l.k = r.a AND l.k = r.b")

    assert_tpu_and_cpu_equal(q, conf=CONF_ON)


def test_dict_predicate_literals_share_kernels(dict_paths):
    """Two queries differing only in a dictionary-column predicate's
    literal share one compiled stage kernel: the constant lives in the
    aux gather TABLE (a runtime argument), so the DictGather cache key
    is literal-free — the compressed analog of literal hoisting."""
    from spark_rapids_tpu.exec.stage import stage_kernel_cache
    s = tpu_session(CONF_ON)
    s.register_view("t", s.read.parquet(dict_paths["parquet"]))
    s.sql("SELECT v FROM t WHERE k = 'val_0001_'").to_arrow()  # warm
    misses0 = stage_kernel_cache().stats()["misses"]
    s.sql("SELECT v FROM t WHERE k = 'val_0002_x'").to_arrow()
    s.sql("SELECT v FROM t WHERE k = 'val_0003_xx'").to_arrow()
    assert stage_kernel_cache().stats()["misses"] == misses0, (
        "rotating the predicate literal on a dictionary column must "
        "not compile new stage kernels")


def test_join_left_outer_encoded_vs_cpu(tmp_path):
    """Unmatched stream rows must keep their ORIGINAL string values
    (the re-keyed comparison column never leaks into side outputs)."""
    import pyarrow.parquet as pq
    rng = np.random.default_rng(3)
    lt = pa.table({
        "k": pa.array([f"key{i}" for i in rng.integers(0, 10, 800)]),
        "v": pa.array(rng.integers(0, 100, 800), pa.int64()),
    })
    rt = pa.table({
        "k2": pa.array([f"key{i}" for i in range(0, 20, 2)] * 3),
        "w": pa.array(np.arange(30), pa.int64()),
    })
    lp, rp = str(tmp_path / "l.parquet"), str(tmp_path / "r.parquet")
    pq.write_table(lt, lp)
    pq.write_table(rt, rp)

    def q(s):
        s.register_view("l", s.read.parquet(lp))
        s.register_view("r", s.read.parquet(rp))
        return s.sql("SELECT l.k, l.v, r.w FROM l LEFT JOIN r "
                     "ON l.k = r.k2")

    assert_tpu_and_cpu_equal(q, conf=CONF_ON)


# ---------------------------------------------------------------------------
# lateDecodes stays zero for a dict-key group-by
# ---------------------------------------------------------------------------

def test_dict_key_group_by_zero_late_decodes(dict_paths):
    # fresh ingest: the device scan cache would otherwise serve batches
    # another test already uploaded, zeroing the deltas asserted below
    s = tpu_session({**CONF_ON,
                     "spark.rapids.sql.scan.deviceCacheEnabled":
                     "false"})
    s.register_view("t", s.read.parquet(dict_paths["parquet"]))
    before = encoding.compressed_stats()
    out = s.sql("SELECT k, COUNT(*) AS c, SUM(v) AS sv FROM t "
                "GROUP BY k").to_arrow()
    after = encoding.compressed_stats()
    assert out.num_rows > 0
    assert after["encoded_columns"] > before["encoded_columns"], \
        "the scan must ingest the dict key as an encoded column"
    assert after["late_decodes"] == before["late_decodes"], (
        "a dict-key group-by must stay in the code domain end to end "
        "(group by codes, codes on the egress wire) — no decode_late "
        "dispatch anywhere")
    from tests.compare import sum_plan_metric
    assert sum_plan_metric(s, "encodedColumns") > 0, \
        "the scan operator must count its encoded columns"


def test_engine_stats_carries_compressed_counters():
    s = tpu_session(CONF_ON)
    snap = s.engine_stats()
    assert "compressed" in snap
    for key in ("encodedColumns", "lateDecodes",
                "compressedBytesSaved"):
        assert key in snap["compressed"], key


# ---------------------------------------------------------------------------
# wire-ratio acceptance: codes, not values, cross the link
# ---------------------------------------------------------------------------

def test_dict_heavy_scan_wire_ratio(dict_paths):
    s = tpu_session({**CONF_ON,
                     "spark.rapids.sql.scan.deviceCacheEnabled":
                     "false"})
    before = encoding.compressed_stats()
    s.read.parquet(dict_paths["parquet"]).to_arrow()
    after = encoding.compressed_stats()
    raw = after["h2d_raw_bytes"] - before["h2d_raw_bytes"]
    wire = after["h2d_wire_bytes"] - before["h2d_wire_bytes"]
    assert raw > 0, "dictionary-heavy scan must exercise encoded ingest"
    assert wire / raw <= 0.5, (
        f"encoded wire ratio {wire}/{raw} = {wire / raw:.2f} must stay "
        "<= 0.5 on a dictionary-heavy scan (the whole point of codes "
        "on the link)")


# ---------------------------------------------------------------------------
# io.encode fault: degrade to plain planes, counted, correct
# ---------------------------------------------------------------------------

@pytest.mark.faults
def test_io_encode_fault_degrades_to_plain(dict_paths,
                                           encode_fault_conf):
    conf = dict(encode_fault_conf)
    conf.update(CONF_ON)
    conf["spark.rapids.sql.scan.deviceCacheEnabled"] = "false"
    before = encoding.compressed_stats()
    s = tpu_session(conf)
    faulted = s.read.parquet(dict_paths["parquet"]).to_arrow()
    after = encoding.compressed_stats()
    assert after["encode_faults"] > before["encode_faults"], \
        "the injected io.encode fault must be counted"
    assert after["plain_columns"] >= before["plain_columns"]
    clean = tpu_session(
        {**CONF_ON, "spark.rapids.sql.scan.deviceCacheEnabled":
         "false"}).read.parquet(dict_paths["parquet"]).to_arrow()
    assert faulted.equals(clean), (
        "a column degraded to the plain plane path must still produce "
        "byte-identical results")


# ---------------------------------------------------------------------------
# TPC-H q1/q3 + TPCx-BB q3 run encoded AND match the CPU engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tpch_paths(tmp_path_factory):
    from spark_rapids_tpu.bench.tpch import gen_tpch
    d = tmp_path_factory.mktemp("tpch_comp")
    return gen_tpch(str(d), lineitem_rows=4_000)


@pytest.mark.parametrize("qname", ["q1", "q3"])
def test_tpch_encoded_matches_cpu(tpch_paths, qname):
    from spark_rapids_tpu.bench.tpch import TPCH_QUERIES, load_tables
    before = encoding.compressed_stats()
    assert_tpu_and_cpu_equal(
        lambda s: TPCH_QUERIES[qname](load_tables(s, tpch_paths)),
        conf=CONF_ON, approx_float=True)
    after = encoding.compressed_stats()
    assert after["encoded_columns"] > before["encoded_columns"], (
        f"TPC-H {qname} touches dictionary-shaped string columns "
        "(l_returnflag/l_linestatus/c_mktsegment) — the scan must "
        "ingest them encoded")


def test_tpcxbb_q3_encoded_matches_cpu(tmp_path_factory):
    from spark_rapids_tpu.bench.tpcxbb import (
        TPCXBB_QUERIES, gen_tpcxbb, register_views,
    )
    d = tmp_path_factory.mktemp("tpcxbb_comp")
    paths = gen_tpcxbb(str(d), sales_rows=6_000)
    before = encoding.compressed_stats()

    def q(s):
        register_views(s, paths)
        return s.sql(TPCXBB_QUERIES["q3"])

    assert_tpu_and_cpu_equal(q, conf=CONF_ON, approx_float=True)
    after = encoding.compressed_stats()
    assert after["encoded_columns"] > before["encoded_columns"]


# ---------------------------------------------------------------------------
# unit coverage of the encoding primitives
# ---------------------------------------------------------------------------

def test_rank_code_invariant():
    """Codes are ranks over the sorted dictionary: code order == value
    order, the invariant the group-by/min-max code paths rely on."""
    import jax
    arr = pa.array(["pear", "apple", "pear", None, "fig", "apple"])
    enc = encoding.IngestEncoder(max_dict_fraction=1.0)
    from spark_rapids_tpu.columnar.dtypes import STRING
    col = enc.upload_column(arr, STRING, 8)
    assert col is not None
    assert list(col.dict.values) == ["apple", "fig", "pear"]
    codes = np.asarray(jax.device_get(col.codes))[:6]
    valid = np.asarray(jax.device_get(col.validity))[:6]
    assert list(codes[valid]) == [2, 0, 2, 1, 0]
    dense = col.decoded()
    vals, dv = dense.to_numpy()
    assert list(vals[:3]) == ["pear", "apple", "pear"]
    assert not dv[3]


def test_unify_and_rekey_for_join():
    enc = encoding.IngestEncoder(max_dict_fraction=1.0)
    from spark_rapids_tpu.columnar.dtypes import STRING
    a = enc.upload_column(pa.array(["a", "b", "a", "c"]), STRING, 4)
    b = enc.upload_column(pa.array(["b", "d", "d", "b"]), STRING, 4)
    unified, union = encoding.unify_columns([a, b])
    assert list(union.values) == ["a", "b", "c", "d"]
    import jax
    ca = np.asarray(jax.device_get(unified[0].codes))[:4]
    cb = np.asarray(jax.device_get(unified[1].codes))[:4]
    assert list(ca) == [0, 1, 0, 2]
    assert list(cb) == [1, 3, 3, 1]
    # rekey b into a's (smaller) dictionary: 'd' must map PAST a's size
    rk = encoding.rekey_for_join(b, a.dict)
    rb = np.asarray(jax.device_get(rk.data))[:4]
    assert rb[0] == 1 and rb[3] == 1          # 'b' -> a-code 1
    assert rb[1] >= a.dict.size and rb[2] >= a.dict.size


# ---------------------------------------------------------------------------
# non-dictionary compute planes: RLE / delta-narrow / bit-packed bool
# ---------------------------------------------------------------------------

_PLANE_SWITCH = {
    "rle": ("spark.rapids.sql.compressed.rle.enabled", "rle_columns"),
    "delta": ("spark.rapids.sql.compressed.delta.enabled",
              "delta_columns"),
    "packed_bool": ("spark.rapids.sql.compressed.packedBool.enabled",
                    "packed_bool_columns"),
}


def _plane_table(n=4000):
    """One column per plane encoding, each shaped so only its own
    encoder wins: ``r`` runs of far-apart values (deltas overflow
    int16, so RLE wins), ``q`` a null-free small-step cumsum (delta
    wins), ``b`` booleans (bit-packed), ``v`` a float payload that
    always rides plain."""
    rng = np.random.default_rng(31)
    run_vals = rng.integers(0, 2 ** 30, n // 40 + 1) * 4
    runs = np.repeat(run_vals, 40)[:n].astype(np.int64)
    rmask = rng.random(n) < 0.05
    seq = np.cumsum(rng.integers(0, 3, n)).astype(np.int64)
    flags = rng.random(n) < 0.5
    bmask = rng.random(n) < 0.05
    return pa.table({
        "r": pa.array([None if m else int(x)
                       for x, m in zip(runs, rmask)], pa.int64()),
        "q": pa.array(seq, pa.int64()),
        "b": pa.array([None if m else bool(x)
                       for x, m in zip(flags, bmask)], pa.bool_()),
        "v": pa.array(rng.normal(size=n), pa.float64()),
    })


@pytest.fixture(scope="module")
def plane_path(tmp_path_factory):
    import pyarrow.parquet as pq
    d = tmp_path_factory.mktemp("planes")
    p = str(d / "planes.parquet")
    pq.write_table(_plane_table(), p, row_group_size=1024)
    return p


_NO_CACHE = {"spark.rapids.sql.scan.deviceCacheEnabled": "false"}


def test_plane_encodings_selected_and_counted(plane_path):
    before = encoding.compressed_stats()
    out = tpu_session({**CONF_ON, **_NO_CACHE}).read \
        .parquet(plane_path).to_arrow()
    after = encoding.compressed_stats()
    assert out.num_rows == 4000
    for key in ("rle_columns", "delta_columns", "packed_bool_columns"):
        assert after[key] > before[key], (
            f"{key} must be selected for its tailor-made column "
            "(per-column encoder selection, docs/compressed.md)")
    raw = after["h2d_raw_bytes"] - before["h2d_raw_bytes"]
    wire = after["h2d_wire_bytes"] - before["h2d_wire_bytes"]
    assert 0 < wire < raw, "plane encodings must win wire bytes"


@pytest.mark.parametrize("enc", sorted(_PLANE_SWITCH))
def test_plane_encoding_on_off_byte_identical(plane_path, enc):
    """Each per-encoding switch alone flips its plane to plain with
    byte-identical output — the ``plain`` degrade every encoding owes
    (values AND row order)."""
    key, counter = _PLANE_SWITCH[enc]
    on = tpu_session({**CONF_ON, **_NO_CACHE}).read \
        .parquet(plane_path).to_arrow()
    before = encoding.compressed_stats()
    off = tpu_session({**CONF_ON, **_NO_CACHE, key: "false"}).read \
        .parquet(plane_path).to_arrow()
    after = encoding.compressed_stats()
    assert after[counter] == before[counter], (
        f"{key}=false must keep {counter} flat")
    assert on.equals(off), (
        f"disabling {enc} must be byte-identical to the encoded run")


def test_plane_scan_all_off_matches_cpu(plane_path):
    q = lambda s: s.read.parquet(plane_path)  # noqa: E731
    on = q(tpu_session({**CONF_ON, **_NO_CACHE})).to_arrow()
    off = q(tpu_session({**CONF_OFF, **_NO_CACHE})).to_arrow()
    cpu = q(cpu_session()).to_arrow()
    assert on.equals(off)
    assert_tables_equal(on, cpu)


def test_plane_group_by_fused_decode_matches_cpu(plane_path):
    """Aggregating over plane-compressed columns decodes INSIDE the
    compiled update (fusedDecodes), never via the late-decode path."""
    from spark_rapids_tpu.api import col
    from spark_rapids_tpu import functions as F

    def q(s):
        return s.read.parquet(plane_path).group_by("r").agg(
            F.sum(col("q")).alias("sq"),
            F.count(col("b")).alias("nb")).sort("r")

    before = encoding.compressed_stats()
    out = q(tpu_session({**CONF_ON, **_NO_CACHE})).to_arrow()
    after = encoding.compressed_stats()
    assert after["fused_decodes"] > before["fused_decodes"]
    assert after["late_decodes"] == before["late_decodes"], (
        "plane columns must decode inside the compiled stage/update, "
        "not via decode_plane_late")
    cpu = q(cpu_session()).to_arrow()
    assert_tables_equal(out, cpu, approx_float=True)


def test_plane_columns_never_reach_the_aggregate_merge(plane_path,
                                                       monkeypatch):
    """Only the update reads scan batches: its partials come out of a
    kernel as dense or dictionary columns and ``concat_batches`` keeps
    them so, which is why the merge program takes no plane decoder."""
    from spark_rapids_tpu.api import col
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    merged = []
    real = TpuHashAggregateExec._run_merge

    def spy(self, batch):
        merged.append([type(c).__name__ for c in batch.columns
                       if encoding.is_plane_compressed(c)])
        return real(self, batch)

    monkeypatch.setattr(TpuHashAggregateExec, "_run_merge", spy)

    def q(s):
        return s.read.parquet(plane_path).group_by("b").agg(
            F.sum(col("q")).alias("sq"), F.max(col("r")).alias("mr"),
            F.count(col("b")).alias("nb")).sort("b")

    before = encoding.compressed_stats()
    out = q(tpu_session({
        **CONF_ON, **_NO_CACHE,
        "spark.rapids.sql.reader.batchSizeRows": "1024",
        "spark.rapids.sql.batchSizeBytes": "8192"})).to_arrow()
    after = encoding.compressed_stats()
    assert after["fused_decodes"] > before["fused_decodes"]
    assert after["late_decodes"] == before["late_decodes"]
    assert merged == [[]], "one merge, over no plane-compressed column"
    assert_tables_equal(out, q(cpu_session()).to_arrow())


@pytest.mark.faults
def test_plane_encode_fault_degrades_to_plain(plane_path,
                                              encode_fault_conf):
    """io.encode fault on a plane-encoded (int/bool) scan: degrade to
    dense planes, counted, query still correct."""
    conf = dict(encode_fault_conf)
    conf.update(CONF_ON)
    conf.update(_NO_CACHE)
    before = encoding.compressed_stats()
    faulted = tpu_session(conf).read.parquet(plane_path).to_arrow()
    after = encoding.compressed_stats()
    assert after["encode_faults"] > before["encode_faults"], \
        "the injected io.encode fault must be counted"
    assert after["plain_columns"] > before["plain_columns"]
    clean = tpu_session({**CONF_ON, **_NO_CACHE}).read \
        .parquet(plane_path).to_arrow()
    assert faulted.equals(clean), (
        "a plane column degraded by an encode fault must still "
        "produce byte-identical results")


# ---------------------------------------------------------------------------
# composed (code1, code2) gathers: two encoded columns, one table
# ---------------------------------------------------------------------------

def test_composed_gather_two_dict_columns_matches_cpu(dict_paths):
    """concat(k, g) references exactly two encoded columns: the
    rewrite composes one (code1, code2) gather table instead of
    decoding either side (composedGathers counter)."""
    from spark_rapids_tpu.api import col
    from spark_rapids_tpu import functions as F

    def q(s):
        return s.read.parquet(dict_paths["parquet"]).select(
            F.concat(col("k"), col("g")).alias("kg"))

    before = encoding.compressed_stats()
    out = q(tpu_session({**CONF_ON, **_NO_CACHE})).to_arrow()
    after = encoding.compressed_stats()
    assert after["composed_gathers"] > before["composed_gathers"], (
        "a two-encoded-column subtree must rewrite to DictGather2")
    cpu = q(cpu_session()).to_arrow()
    assert_tables_equal(out, cpu)


def test_composed_gather_respects_cell_budget(dict_paths):
    """With maxComposedCells below (d1+1)*(d2+1) the pair rewrite must
    decline — and the result stays identical."""
    from spark_rapids_tpu.api import col
    from spark_rapids_tpu import functions as F

    def q(s):
        return s.read.parquet(dict_paths["parquet"]).select(
            F.concat(col("k"), col("g")).alias("kg"))

    base = q(tpu_session({**CONF_ON, **_NO_CACHE})).to_arrow()
    before = encoding.compressed_stats()
    capped = q(tpu_session({
        **CONF_ON, **_NO_CACHE,
        "spark.rapids.sql.compressed.maxComposedCells": "4",
    })).to_arrow()
    after = encoding.compressed_stats()
    assert after["composed_gathers"] == before["composed_gathers"]
    assert base.equals(capped)
