"""Test configuration.

Correctness tests run on a virtual 8-device CPU platform so float64 /
int64 Spark semantics hold exactly (TPU v5e demotes f64 to f32 — an
incompat documented in the package docs) and so multi-device code can run
without TPU hardware: ``JAX_PLATFORMS=cpu`` with eight forced host
devices.  Real-chip coverage is ``python chip_smoke.py`` at the repo
root, run through the chip tool; ``tests/test_tpu_compile.py`` compiles
the main-path kernels for a described v5e without one attached.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_ENABLE_X64"] = "true"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
# The suite compiles thousands of XLA:CPU kernels; cache the compiled
# executables across runs (repo-local, untracked — see .gitignore) so a
# repeat run spends its budget on tests, not recompiles (full suite:
# 825s cold -> 551s warm; tests/test_window.py alone: 229s -> 96s).
# ONE implementation: the engine's compilation service owns the
# persistent-cache setup (compile/store.py — runtime init applies it
# from the spark.rapids.sql.compile.* conf keys; docs/compile_cache.md)
# and this conftest is a thin consumer of the same function, including
# the env export that lets spawned shuffle-worker processes inherit
# the cache.  JAX_COMPILATION_CACHE_DIR, when set, places it
# (store.xla_cache_dir); otherwise the dir is keyed by the package's
# host fingerprint — XLA:CPU artifacts embed machine features, so a
# checkout moving to a different machine gets a fresh cache, never
# foreign CPU artifacts.
import spark_rapids_tpu as _srt  # noqa: E402
from spark_rapids_tpu.compile import store as _compile_store  # noqa: E402

_compile_store.enable_persistent_cache(
    os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache",
        "cpu-" + _srt._host_fingerprint()),
    min_compile_secs=0.0)
# the virtual CPU platform must present the full 8-device mesh (the
# XLA_FLAGS above guarantee it); on a real accelerator backend the
# device count is whatever the hardware has — `multichip`-marked tests
# auto-skip below 2 devices instead of erroring (pytest.ini)
if jax.default_backend() == "cpu":
    assert len(jax.devices()) == 8, (
        "tests require the 8-device virtual CPU platform; got "
        f"{jax.devices()}")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


# -- compiled-code pressure relief (per test FILE) --------------------------
#
# One tier-1 process compiles thousands of XLA:CPU executables; past
# roughly a thousand tests the accumulated JIT code reproducibly
# crashes XLA (a hard SIGSEGV inside backend_compile / cache
# deserialization around the TPC-H suite, present on unmodified HEAD
# and insensitive to cold vs warm persistent cache).  At each module
# boundary, once the engine's kernel caches hold more than a bounded
# number of live executables, drop them and jax's own jit caches: the
# persistent compile cache turns the re-compiles this causes into
# deserializations, so the cost is small and the long-process failure
# mode disappears.

# 100, not the 700 it was: tests/test_window.py leaves 177 entries and a
# worker that went on to tests/test_ooc.py with them still loaded
# aborted inside the persistent cache's deserialization, on the parent
# of PR 35 too; which worker runs which file after which is xdist's to
# choose, and a new test file was enough to deal that pair to one
# worker in four whole runs of five (PERF.md section 6, PR 35)
_KERNEL_PRESSURE_ENTRIES = 100
_last_test_module = [None]


def pytest_runtest_setup(item):
    mod = getattr(item, "module", None)
    name = getattr(mod, "__name__", None)
    if name is None or _last_test_module[0] == name:
        return
    _last_test_module[0] = name
    from spark_rapids_tpu.utils import kernel_cache
    with kernel_cache._REGISTRY_LOCK:
        caches = list(kernel_cache._REGISTRY)
    total = sum(len(c) for c in caches)
    if total <= _KERNEL_PRESSURE_ENTRIES:
        return
    for c in caches:
        c.clear()  # counters survive; only the executables drop
    jax.clear_caches()


def pytest_collection_modifyitems(config, items):
    """Auto-skip ``multichip``-marked tests when fewer than 2 devices
    are visible: the ICI collective suites need a real (or virtual)
    mesh, and a 1-device environment must skip them cleanly instead of
    erroring inside ``shard_map``.  On the tier-1 virtual 8-device CPU
    platform (and on the real 8-chip pod) they run."""
    if len(jax.devices()) >= 2:
        return
    skip = pytest.mark.skip(
        reason=f"multichip: needs >= 2 JAX devices, have "
               f"{len(jax.devices())}")
    for item in items:
        if "multichip" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def rng():
    return np.random.default_rng(42)


# -- fault-injection plumbing (the `faults` marker's fixtures) --------------
#
# Fault tests configure the process-global injector through
# spark.rapids.faults.* conf keys (never monkeypatching); the autouse
# reset below guarantees no injector state leaks between tests, so a
# fault test crashing mid-run cannot poison an unrelated test that
# happens to build a shuffle manager next.

FAULTS_SEED = 1234


@pytest.fixture(autouse=True)
def _reset_fault_injector():
    # the chip-health tracker is process-global like the injector
    # (quarantine must survive across queries) — tests reset both so a
    # quarantine from one test can never shrink another test's mesh
    from spark_rapids_tpu import faults, health
    faults.reset()
    health.reset()
    yield
    faults.reset()
    health.reset()


@pytest.fixture(autouse=True)
def _reset_compile_service():
    # the persistent kernel store, the AOT warm pool, and the capacity
    # ladder are process-global (docs/compile_cache.md); a test that
    # enables them (compile.* conf keys) must not leave a store pointed
    # at its deleted tmp dir for the rest of the suite.  XLA's own cache
    # stays where this conftest exported it (store.xla_cache_dir), so
    # only the min-compile-time and — for the one test that clears the
    # variable — the directory need restoring.  Warm threads carry the
    # srt-compile-* prefix and are covered by the srt- leak audit below
    # like every other engine thread.
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    from spark_rapids_tpu.compile import buckets, store, warm
    warm.reset()
    store.reset()
    buckets.reset()
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      prev_min)


# -- observability hygiene (docs/observability.md) --------------------------
#
# The journal and the histogram switch are process-global and conf-
# driven at query scope; a test that configures them directly (or runs
# a query with obs keys set) must not leak an open journal handle or a
# flipped recording switch into the next test.


@pytest.fixture(autouse=True)
def _reset_obs():
    from spark_rapids_tpu.obs import journal, registry
    yield
    journal.close()
    registry.set_enabled(True)


@pytest.fixture(autouse=True)
def _stop_dispatch_watcher(_lifecycle_leak_audit):
    # the dispatch ledger's completion watcher (compile/service.py) is
    # one thread per process, started by the first traced program launch
    # and joined by session.stop(); a test that traces a query and keeps
    # its session must not leave it for the leak audit (set up first,
    # so it looks after this has run)
    yield
    from spark_rapids_tpu.compile import service
    service.stop_watcher()


@pytest.fixture(autouse=True)
def _reset_ooc():
    # the out-of-core counters are process-global (docs/out_of_core.md):
    # partitions one test spilled must not inflate another's assertions
    from spark_rapids_tpu.exec import ooc
    ooc.reset_ooc_stats()
    yield
    ooc.reset_ooc_stats()


@pytest.fixture(autouse=True)
def _reset_stream_stats():
    # the continuous-query counters are process-global
    # (docs/streaming.md): ticks/refreshes/maintains one test drove
    # must not inflate another's assertions (the stats module never
    # imports the poller machinery, so this keeps conf-off inertness)
    from spark_rapids_tpu.stream import stats as stream_stats
    stream_stats.reset()
    yield
    stream_stats.reset()


@pytest.fixture(autouse=True)
def _reset_placement():
    # the placement decision counters, the throughput calibration
    # store, the link-probe memo, and the calibration-mode switch are
    # process-global (docs/placement.md): rates one test learned (or
    # a mode one test flipped) must never steer another test's
    # placement decisions or metric recording
    from spark_rapids_tpu.plan import cost, placement
    cost.reset()
    placement.reset_stats()
    yield
    cost.reset()
    placement.reset_stats()


# -- lifecycle leak audit (package-wide, autouse) ---------------------------
#
# Every test must return the engine to its pre-test resource state:
# zero leaked engine threads (all carry the `srt-` prefix — the
# session server's `srt-server-*` worker pool included, so N
# concurrent/cancelled/timed-out server queries must return worker
# threads to baseline like any other engine thread), zero stranded
# staging permits on any of the catalog's three limiters, and
# no growth in live catalog bytes (device+host+disk, net of the
# device scan cache, whose entries legitimately persist across queries
# of a live session).  A short grace poll absorbs bounded teardown
# (warmer joins, watchdog drains) without hiding real leaks.

_LEAK_GRACE_S = 5.0


def _engine_threads():
    import threading
    return {t.ident: t.name for t in threading.enumerate()
            if t.is_alive() and (t.name or "").startswith("srt-")}


def _catalog_state():
    """(runtime, catalog, live_bytes) or Nones.  Live bytes are net of
    the device scan cache AND of lifecycle-supervised resources
    (broadcast builds held by a still-open session): both are
    reclaimable deterministically, so only UNsupervised growth is a
    leak."""
    from spark_rapids_tpu import lifecycle
    from spark_rapids_tpu.runtime import TpuRuntime
    rt = TpuRuntime._instance
    if rt is None:
        return None, None, 0
    cat = rt.catalog
    cached = sum(h.size for ent in rt.scan_cache._entries.values()
                 for h in ent[0])
    live = (cat.device_bytes + cat.host_bytes + cat.disk_bytes
            - cached - lifecycle.supervised_bytes())
    return rt, cat, live


@pytest.fixture(autouse=True)
def _lifecycle_leak_audit(request):
    import time
    before_threads = set(_engine_threads())
    rt0, cat0, bytes0 = _catalog_state()
    yield

    def leaked_threads():
        return sorted(name for ident, name in _engine_threads().items()
                      if ident not in before_threads)

    # each check gets its OWN grace window: a slow (but legitimate)
    # thread teardown must not eat the tolerance of the permit/bytes
    # checks that follow it
    deadline = time.monotonic() + _LEAK_GRACE_S
    leaked = leaked_threads()
    while leaked and time.monotonic() < deadline:
        time.sleep(0.05)
        leaked = leaked_threads()
    assert not leaked, (
        f"engine thread(s) leaked by {request.node.nodeid}: {leaked} — "
        "register them with the lifecycle registry and close on every "
        "path (docs/fault_tolerance.md, Query lifecycle)")

    rt1, cat1, bytes1 = _catalog_state()
    if cat1 is not None:
        for limiter_name in ("staging", "prefetch_staging",
                             "egress_staging"):
            lim = getattr(cat1, limiter_name)
            deadline = time.monotonic() + _LEAK_GRACE_S
            while lim._inflight and time.monotonic() < deadline:
                time.sleep(0.05)
            assert lim._inflight == 0, (
                f"{lim._inflight} bytes of {limiter_name} admission "
                f"stranded by {request.node.nodeid} — a wait path "
                "failed to release its grant")
    if cat1 is not None and cat1 is cat0:
        deadline = time.monotonic() + _LEAK_GRACE_S
        while bytes1 > bytes0 and time.monotonic() < deadline:
            time.sleep(0.05)
            _, _, bytes1 = _catalog_state()
        assert bytes1 <= bytes0, (
            f"live catalog bytes grew {bytes0} -> {bytes1} across "
            f"{request.node.nodeid} — spillable handles leaked without "
            "close()")


@pytest.fixture
def fault_seed():
    """The deterministic seed every `faults`-marked test threads into
    spark.rapids.faults.seed (and any local RNG), so probabilistic
    triggers replay the exact same fire pattern on every run."""
    return FAULTS_SEED


@pytest.fixture
def fault_conf(fault_seed):
    """Base conf dict for fault tests: seed pinned, tight timeouts and
    backoff so injected failures resolve in test time, not wall time."""
    return {
        "spark.rapids.faults.seed": str(fault_seed),
        "spark.rapids.shuffle.timeout.connect": "2.0",
        "spark.rapids.shuffle.timeout.read": "5.0",
        "spark.rapids.shuffle.retry.backoff.base": "0.01",
        "spark.rapids.shuffle.retry.backoff.cap": "0.05",
        "spark.rapids.shuffle.worker.heartbeat.interval": "0.1",
        "spark.rapids.shuffle.worker.heartbeat.timeout": "3.0",
    }


@pytest.fixture
def aqe_fault_conf(fault_conf):
    """fault_conf + adaptive execution on + an always-firing trigger on
    the ``aqe.replan`` site (plan/adaptive.py): every replanning pass
    aborts and must degrade to the static plan — query results stay
    correct and ``aqeReplans`` stays 0 (tests/test_adaptive.py)."""
    conf = dict(fault_conf)
    conf["spark.rapids.sql.adaptive.enabled"] = "true"
    conf["spark.rapids.faults.aqe.replan"] = "always"
    return conf


@pytest.fixture
def placement_fault_conf(fault_conf):
    """fault_conf + cost-mode placement with an always-firing trigger
    on the ``plan.place`` site (plan/placement.py): every placement
    pass — the static fragment scoring AND the AQE runtime re-score —
    degrades to the static all-TPU plan (``place_faults`` counted,
    query correct), matching the aqe.replan degrade contract
    (tests/test_placement.py).  Link constants are pinned to a
    demote-everything regime so the test proves the fault, not the
    model, kept the plan on the device; pinned constants also keep the
    link probe out of the loop."""
    conf = dict(fault_conf)
    conf["spark.rapids.sql.placement.mode"] = "cost"
    conf["spark.rapids.sql.placement.pullLatencyMs"] = "1000"
    conf["spark.rapids.sql.placement.h2dMBps"] = "1"
    conf["spark.rapids.sql.placement.d2hMBps"] = "1"
    conf["spark.rapids.faults.plan.place"] = "always"
    return conf


@pytest.fixture
def server_fault_conf(fault_conf):
    """fault_conf + triggers on the session-server sites
    (docs/serving.md): the FIRST submit sheds typed at ``server.admit``
    (fired BEFORE enqueue, so the admission queue can never be wedged
    by an injected failure — later submits must flow), and every
    result-cache lookup degrades to a counted miss
    (``server.cache.lookup``) — queries stay correct with a broken
    cache.  Chaos-style schedules draw these sites the same way
    (tests/test_server.py)."""
    conf = dict(fault_conf)
    conf["spark.rapids.faults.server.admit"] = "count:1"
    conf["spark.rapids.faults.server.cache.lookup"] = "always"
    return conf


@pytest.fixture
def encode_fault_conf(fault_conf):
    """fault_conf + a first-column trigger on the ingest-encode fault
    site (``io.encode``, columnar/encoding.py IngestEncoder): the
    injected failure degrades that scan column to the plain dense-plane
    upload, counted, with the query still correct
    (tests/test_compressed.py)."""
    conf = dict(fault_conf)
    conf["spark.rapids.faults.io.encode"] = "count:1"
    return conf


@pytest.fixture
def egress_fault_conf(fault_conf):
    """fault_conf + a first-pull trigger on the egress fault site
    (``transfer.d2h``, columnar/transfer.py:device_pull): the D2H
    egress pipeline shares the PR 1 injector grammar
    (count/first/prob@seed), so egress faults replay deterministically
    like every other site (tests/test_d2h_egress.py)."""
    conf = dict(fault_conf)
    conf["spark.rapids.faults.transfer.d2h"] = "count:1"
    return conf


@pytest.fixture
def ingest_fault_conf(fault_conf):
    """fault_conf + ICI mode + sharded scan ingest on + an always
    trigger on the ingest fault site (``shuffle.ici.ingest``,
    parallel/shardscan.py): every sharded ingest aborts and the
    fragment must degrade to the host path over a freshly drained
    input — query correct, ``iciFallbacks`` counted with reason
    ``ingest`` (tests/test_sharded_scan.py)."""
    conf = dict(fault_conf)
    conf["spark.rapids.shuffle.mode"] = "ici"
    conf["spark.rapids.shuffle.ici.shardedScan.enabled"] = "true"
    conf["spark.rapids.faults.shuffle.ici.ingest"] = "always"
    return conf


@pytest.fixture
def stream_fault_conf(fault_conf):
    """fault_conf + streaming on + a first-poll trigger on the tailing
    sources' poll site (``stream.poll``, stream/source.py): the first
    tick is skipped — counted ``tick_faults``, the committed snapshot
    NOT advanced — and the standing query converges to the correct
    result on the next tick, because a skipped poll loses nothing
    (tests/test_stream.py)."""
    conf = dict(fault_conf)
    conf["spark.rapids.server.enabled"] = "true"
    conf["spark.rapids.stream.enabled"] = "true"
    conf["spark.rapids.stream.pollIntervalMs"] = "60000"
    conf["spark.rapids.faults.stream.poll"] = "count:1"
    return conf
