"""Persistent compilation service tests (docs/compile_cache.md).

Covers: conf-off default (no store, byte-identical results), store
record-then-hit across a simulated process restart, cross-process
reuse through spawned host-shuffle workers (no fresh index entries on
a warm second run), a ``SessionServer`` restart against a warm store
reporting zero fresh compiles, the ``compile.store`` fault site and
store-corruption degrade paths, the startup AOT warm pool (prewarmed
kernels + ``compile_warm`` journal events + lifecycle teardown), the
conf-bounded capacity ladder, and the coalesce/ladder regression: two
runs differing only in row count share stage kernels.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.api import col
from spark_rapids_tpu.compile import buckets, service, store, warm
from spark_rapids_tpu.exec.stage import stage_kernel_cache
from tests.compare import assert_tables_equal, tpu_session


@pytest.fixture(autouse=True)
def _fresh_compile_state():
    """Each test starts from a fresh process's compile state: the
    shared in-process stage-kernel memo survives across tests, and a
    kernel another test already memoized would silently skip the AOT
    (and therefore the store transaction) this module asserts on."""
    _simulate_restart()
    yield


def _store_conf(d, extra=None):
    conf = {"spark.rapids.sql.compile.store.enabled": "true",
            "spark.rapids.sql.compile.cacheDir": str(d)}
    conf.update(extra or {})
    return conf


def _write(path, n, seed=7):
    rng = np.random.default_rng(seed)
    pq.write_table(pa.table({
        "k": pa.array(rng.integers(0, 100, n), pa.int64()),
        "v": pa.array(rng.normal(size=n)),
    }), str(path))
    return str(path)


@pytest.fixture
def corpus(tmp_path):
    return _write(tmp_path / "t.parquet", 4000)


def _query(s, path):
    return (s.read.parquet(path)
            .select((col("v") * 2.0).alias("a"),
                    (col("v") + 1.0).alias("b"), col("k"))
            .filter(col("k") < 50))


def _run_once(conf, path):
    s = tpu_session(conf)
    try:
        return _query(s, path).to_arrow()
    finally:
        s.stop()


def _simulate_restart():
    """A fresh process's compile state: empty in-process kernel memo,
    no installed store object, zeroed service/warm counters.  The
    on-disk store (index + XLA cache) survives — that is the point."""
    stage_kernel_cache().clear()
    stage_kernel_cache().reset_counters()
    warm.reset()
    store.reset()
    service.reset_stats()


def _index_keys(store_dir) -> set:
    path = os.path.join(str(store_dir), "index.jsonl")
    if not os.path.exists(path):
        return set()
    keys = set()
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            if not line.strip():
                continue
            try:
                keys.add(json.loads(line)["key"])
            except (ValueError, KeyError):
                continue  # torn/poisoned lines are the store's problem
    return keys


# ---------------------------------------------------------------------------
# conf-off default
# ---------------------------------------------------------------------------

def test_store_off_by_default(corpus):
    out = _run_once({}, corpus)
    assert store.current() is None
    snap = service.snapshot()
    assert snap["storeEnabled"] == 0
    assert snap["compileStoreHits"] == 0
    assert snap["compileStoreMisses"] == 0
    assert snap["warmPoolCompiles"] == 0
    # default ladder bounds are the historical ones
    assert snap["bucketMinRows"] == 8 and snap["bucketMaxRows"] == 0
    assert out.num_rows > 0


def test_store_on_results_identical(corpus, tmp_path):
    off = _run_once({}, corpus)
    _simulate_restart()
    on = _run_once(_store_conf(tmp_path / "store"), corpus)
    assert_tables_equal(on, off)


# ---------------------------------------------------------------------------
# record-then-hit across restarts
# ---------------------------------------------------------------------------

def test_store_records_then_hits_after_restart(corpus, tmp_path):
    conf = _store_conf(tmp_path / "store",
                       {"spark.rapids.sql.compile.warm.enabled":
                        "false"})
    first = _run_once(conf, corpus)
    st = store.current()
    assert st is not None
    s1 = st.stats()
    assert s1["misses"] >= 1 and s1["hits"] == 0
    assert s1["entries"] == s1["misses"]
    svc1 = service.service_stats()
    assert svc1["cold_ms"] > 0 and svc1["store_hit_ms"] == 0

    _simulate_restart()
    second = _run_once(conf, corpus)
    s2 = store.stats()
    # a restarted process compiles ZERO fresh kernels for already-seen
    # fingerprints: every AOT compile classifies as a store hit
    assert s2["misses"] == 0, s2
    assert s2["hits"] >= 1
    svc2 = service.service_stats()
    assert svc2["store_hit_ms"] > 0 and svc2["cold_ms"] == 0
    assert_tables_equal(second, first)


# ---------------------------------------------------------------------------
# cross-process reuse: spawned host-shuffle map workers
# ---------------------------------------------------------------------------

@pytest.fixture
def multi_file_fact(tmp_path):
    d = tmp_path / "fact"
    d.mkdir()
    rng = np.random.default_rng(3)
    for i in range(3):
        n = 900
        pq.write_table(pa.table({
            "k": pa.array(rng.integers(0, 40, n), pa.int64()),
            "v": pa.array(rng.normal(size=n)),
        }), str(d / f"part-{i}.parquet"))
    return str(d)


def test_spawned_worker_reuses_warm_store(multi_file_fact, tmp_path):
    """Map workers ship the compile conf + the env-seam cache dir: a
    second (restart-simulated) run of the same exchange query — driver
    AND freshly spawned worker processes — must add ZERO new entries
    to the shared on-disk index, i.e. nobody compiled a fresh kernel
    for an already-seen fingerprint."""
    store_dir = tmp_path / "store"
    conf = _store_conf(store_dir, {
        "spark.rapids.shuffle.workers.count": "2",
        "spark.rapids.sql.compile.warm.enabled": "false",
    })

    def build(s):
        return (s.read.parquet(multi_file_fact)
                .filter(col("k") < 30)
                .select((col("v") * 4.0).alias("v4"), col("k"))
                .group_by(col("k"))
                .agg(F.sum(col("v4")).alias("sv"))
                .order_by(col("k")))

    s = tpu_session(conf)
    try:
        first = s and build(s).to_arrow()
    finally:
        s.stop()
    keys_after_first = _index_keys(store_dir)
    assert keys_after_first, "first run recorded nothing"

    _simulate_restart()
    s = tpu_session(conf)
    try:
        second = build(s).to_arrow()
    finally:
        s.stop()
    assert store.stats()["misses"] == 0, store.stats()
    keys_after_second = _index_keys(store_dir)
    assert keys_after_second == keys_after_first, (
        "a warm second run (driver or spawned worker) recorded fresh "
        f"compiles: {sorted(keys_after_second - keys_after_first)}")
    assert_tables_equal(second, first)


# ---------------------------------------------------------------------------
# SessionServer restart against a warm store
# ---------------------------------------------------------------------------

def test_session_server_restart_zero_fresh_compiles(corpus, tmp_path):
    conf = _store_conf(tmp_path / "store")
    sql = ("select v * 2.0 as a, k from t where k < 50")

    s = tpu_session(conf)
    try:
        s.read.parquet(corpus).create_or_replace_temp_view("t")
        s.server().sql(sql, result_timeout=120.0)
    finally:
        s.stop()
    assert store.stats()["misses"] >= 1

    _simulate_restart()
    s = tpu_session(conf)
    try:
        s.read.parquet(corpus).create_or_replace_temp_view("t")
        # server start triggers the warm pool against the warm store
        srv = s.server()
        warm.wait_idle()
        out = srv.sql(sql, result_timeout=120.0)
        assert out.num_rows > 0
    finally:
        s.stop()
    st = store.stats()
    assert st["misses"] == 0, st
    assert st["hits"] >= 1
    assert warm.stats()["compiles"] >= 1


# ---------------------------------------------------------------------------
# fault site + corruption degrade paths
# ---------------------------------------------------------------------------

@pytest.mark.faults
def test_compile_store_fault_degrades_to_fresh_compile(
        corpus, tmp_path, fault_conf):
    conf = _store_conf(tmp_path / "store", fault_conf)
    conf["spark.rapids.faults.compile.store"] = "always"
    off = _run_once({}, corpus)
    _simulate_restart()
    out = _run_once(conf, corpus)
    st = store.stats()
    # every lookup degraded to a counted fresh compile; the query is
    # correct and nothing was claimed as a hit
    assert st["faults"] >= 1, st
    assert st["hits"] == 0 and st["misses"] == 0
    assert_tables_equal(out, off)


def test_poisoned_payload_degrades_counted(corpus, tmp_path):
    store_dir = tmp_path / "store"
    conf = _store_conf(store_dir)
    first = _run_once(conf, corpus)
    payload_dir = os.path.join(str(store_dir), "payload")
    blobs = sorted(os.listdir(payload_dir))
    assert blobs, "no warm payloads recorded"
    for name in blobs:
        with open(os.path.join(payload_dir, name), "wb") as fh:
            fh.write(b"\x00poisoned\xff")

    _simulate_restart()
    # restart: the warm pool replays the poisoned entries and must
    # degrade each to a counted skip; queries stay correct
    from spark_rapids_tpu.conf import TpuConf
    conf_obj = TpuConf(conf)
    store.configure_from_conf(conf_obj)
    warm.start_if_configured(conf_obj)
    assert warm.wait_idle()
    assert warm.stats()["errors"] >= 1
    assert warm.stats()["compiles"] == 0
    assert store.current().stats()["corrupt"] >= 1
    out = _run_once(conf, corpus)
    assert_tables_equal(out, first)


def test_corrupt_index_lines_are_skipped(corpus, tmp_path):
    store_dir = tmp_path / "store"
    conf = _store_conf(store_dir,
                       {"spark.rapids.sql.compile.warm.enabled":
                        "false"})
    first = _run_once(conf, corpus)
    keys = _index_keys(store_dir)
    with open(os.path.join(str(store_dir), "index.jsonl"), "a",
              encoding="utf-8") as fh:
        fh.write("{torn json line\n")
        fh.write('{"nokey": 1}\n')
    _simulate_restart()
    second = _run_once(conf, corpus)
    st = store.stats()
    assert st["corrupt"] >= 2
    # the intact entries still hit; nothing recompiled fresh
    assert st["misses"] == 0 and st["hits"] >= 1
    assert _index_keys(store_dir) == keys
    assert_tables_equal(second, first)


# ---------------------------------------------------------------------------
# warm pool
# ---------------------------------------------------------------------------

def test_warm_pool_prewarms_and_journals(corpus, tmp_path):
    from spark_rapids_tpu.obs import journal
    store_dir = tmp_path / "store"
    _run_once(_store_conf(store_dir), corpus)
    recorded = store.stats()["entries"]
    assert recorded >= 1

    _simulate_restart()
    jdir = str(tmp_path / "journal")
    journal.configure(jdir)
    from spark_rapids_tpu.conf import TpuConf
    conf_obj = TpuConf(_store_conf(store_dir))
    store.configure_from_conf(conf_obj)
    warm.start_if_configured(conf_obj)
    try:
        assert warm.wait_idle()
        stats = warm.stats()
        assert stats["compiles"] >= 1 and stats["errors"] == 0
        # the prewarmed kernels are in the shared stage cache: the
        # first query compiles nothing fresh (store misses stay 0)
        misses_before = stage_kernel_cache().stats()["misses"]
        assert misses_before == stats["compiles"], (
            "warm pool should be the only stage-cache writer so far")
        out = _run_once(_store_conf(store_dir), corpus)
        assert out.num_rows > 0
        assert store.stats()["misses"] == 0
    finally:
        journal.close()
    events = []
    for fn in os.listdir(jdir):
        with open(os.path.join(jdir, fn), encoding="utf-8") as fh:
            events.extend(json.loads(line) for line in fh)
    warms = [e for e in events if e["event"] == "compile_warm"]
    assert len(warms) == stats["compiles"]
    assert all("key" in e and "ms" in e for e in warms)


def test_warm_pool_thread_is_lifecycle_supervised(corpus, tmp_path):
    import threading
    store_dir = tmp_path / "store"
    _run_once(_store_conf(store_dir), corpus)
    _simulate_restart()
    s = tpu_session(_store_conf(store_dir))
    try:
        s.runtime
        warm.wait_idle()
    finally:
        s.stop()
    # stop joined the srt-compile-* worker (the conftest leak audit
    # enforces the same for every srt- thread)
    assert not any(t.name.startswith("srt-compile")
                   for t in threading.enumerate() if t.is_alive())


# ---------------------------------------------------------------------------
# the capacity ladder
# ---------------------------------------------------------------------------

def test_bucket_ladder_bounds():
    buckets.configure(min_rows=4096, max_rows=1 << 20)
    try:
        assert buckets.bucket_capacity(10) == 4096
        assert buckets.bucket_capacity(4097) == 8192
        # a batch larger than the max still gets a capacity holding it
        assert buckets.bucket_capacity((1 << 20) + 1) == 1 << 21
        assert buckets.snap_rows(3_000_000) == 1 << 20
        assert buckets.snap_rows(100) == 4096  # never below the floor
    finally:
        buckets.reset()
    assert buckets.bucket_capacity(10) == 16
    assert buckets.snap_rows(1 << 20) == 1 << 20  # identity at pow2


def test_bucket_min_rows_conf_collapses_small_shapes(corpus, tmp_path):
    small = _write(tmp_path / "small.parquet", 600, seed=5)
    off = _run_once({}, small)
    _simulate_restart()
    on = _run_once(
        {"spark.rapids.sql.compile.buckets.minRows": "4096"}, small)
    # results identical; the batch padded to the raised floor
    assert_tables_equal(on, off)
    assert buckets.stats()["minRows"] == 4096


def test_row_count_variants_share_stage_kernels(tmp_path):
    """The coalesce/ladder regression (docs/compile_cache.md): two
    runs of one query differing ONLY in input row count must share
    stage kernels — both row counts land on the same ladder rung, so
    the second run adds zero stage-cache misses."""
    a = _write(tmp_path / "a.parquet", 3000, seed=1)
    b = _write(tmp_path / "b.parquet", 3500, seed=2)
    s = tpu_session({})
    try:
        _query(s, a).to_arrow()
        misses_after_a = stage_kernel_cache().stats()["misses"]
        _query(s, b).to_arrow()
        misses_after_b = stage_kernel_cache().stats()["misses"]
    finally:
        s.stop()
    assert misses_after_b == misses_after_a, (
        "a row-count-only change compiled fresh stage kernels "
        f"({misses_after_a} -> {misses_after_b}) — capacities left "
        "the shared bucket ladder")


def test_store_hit_timing_pins_deserialize_seam(monkeypatch):
    """The hit/cold compile-time split is attributed at the
    ``.compile()`` deserialize seam ALONE: tracing/lowering runs the
    same Python on a hit and a miss and lands in ``trace_ms`` —
    folding it into the hit bucket is how BENCH_r06's
    ``xlaCompileStoreHitMs`` came to exceed ``xlaCompileColdMs``."""
    import time as _time
    service.reset_stats()

    class _FakeStore:
        def lookup(self, key):
            return ("digest", True)

        def record_execution(self, digest, payload_fn):
            pass

    monkeypatch.setattr(store, "current", lambda: _FakeStore())

    class _Lowered:
        def compile(self):
            _time.sleep(0.05)   # the deserialize seam
            return object()

    class _Fn:
        def lower(self, *avals):
            _time.sleep(0.2)    # tracing/lowering, hit or miss alike
            return _Lowered()

    compiled, ms, hit = service.aot_compile(_Fn(), (None,),
                                            store_key="k")
    assert hit and compiled is not None
    st = service.service_stats()
    assert st["trace_ms"] >= 150, \
        "lowering time must land in trace_ms"
    assert 30 <= st["store_hit_ms"] < 150, (
        "a store hit's measured time is the .compile() phase alone — "
        f"got store_hit_ms={st['store_hit_ms']} (the 200ms trace must "
        "not be attributed to the hit bucket)")
    assert st["cold_ms"] == 0
    assert "xlaCompileTraceMs" in service.snapshot()


_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


@pytest.mark.parametrize("how", ["default_tpu", "enable", "install",
                                 "unset_default_tpu"])
def test_xla_cache_dir_is_placed_from_outside(how, monkeypatch,
                                              tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` places XLA's cache: with it set
    (the conftest exports it for the whole test process) neither the
    accelerator default, an explicit enable, nor a store install moves
    ``jax.config.jax_compilation_cache_dir`` or reassigns the variable;
    unset, the tpu default is the fixed ``<checkout>/.jax_cache/tpu``."""
    import jax
    other = str(tmp_path / "elsewhere")
    if how == "unset_default_tpu":
        monkeypatch.delenv(_CACHE_ENV)
        store.enable_default_cache("tpu")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(repo, ".jax_cache", "tpu")
        assert _CACHE_ENV not in os.environ  # the implicit path: no export
        return
    placed = os.environ[_CACHE_ENV]
    assert jax.config.jax_compilation_cache_dir == placed
    if how == "default_tpu":
        store.enable_default_cache("tpu")
    elif how == "enable":
        assert store.enable_persistent_cache(other)
    else:
        st = store.install(other)
        # the index and payloads still live under the store's own dir
        assert st is not None and st.root == other
        assert os.path.isdir(os.path.join(other, "payload"))
    assert jax.config.jax_compilation_cache_dir == placed
    assert os.environ[_CACHE_ENV] == placed
    assert not os.path.exists(os.path.join(other, "xla"))
