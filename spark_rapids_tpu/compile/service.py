"""The compilation entry points (docs/compile_cache.md).

``engine_jit`` and ``aot_compile`` are the ONLY places in the engine
allowed to touch ``jax.jit`` / ``.lower(...).compile(...)`` —
``tests/lint_robustness.py`` bans the raw forms everywhere outside
``compile/`` the same way it bans raw ``jax.device_get`` in egress
code.  Funneling every compile through one seam is what makes the
compile path a subsystem instead of scattered memo dicts: the store
counters (``compileStoreHits``/``Misses``), the cold-vs-store-hit
split of measured compile time, and the ``compile.store`` fault site
cover every kernel by construction, and a future backend or cache
policy changes ONE module.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional, Tuple

import jax

_LOCK = threading.Lock()
_STATS = {"aot_compiles": 0, "aot_failures": 0,
          "cold_ms": 0.0, "store_hit_ms": 0.0, "trace_ms": 0.0}


def _bump(key: str, v) -> None:
    if v:
        with _LOCK:
            _STATS[key] += v


def engine_jit(fn, **kwargs):
    """The one sanctioned ``jax.jit`` wrapper.  Deliberately thin: a
    jitted fn compiles lazily on first call per signature (the JAX
    persistent cache, when the store enabled it, covers those compiles
    at the XLA layer); call sites that want measured compile time and
    store counters AOT-compile through ``aot_compile`` instead."""
    return jax.jit(fn, **kwargs)


def pallas_interpret() -> bool:
    """The one rule for the hand-written Pallas kernels
    (exec/pallas_agg.py, exprs/pallas_strings.py): interpret iff the
    backend is not ``tpu``.  On the chip a kernel always goes through
    Mosaic, and what Mosaic refuses is an error."""
    return jax.default_backend() != "tpu"


def store_active() -> bool:
    from spark_rapids_tpu.compile import store
    return store.current() is not None


def aot_compile(fn, avals, store_key=None,
                payload_fn: Optional[Callable[[], bytes]] = None,
                record: bool = True
                ) -> Tuple[Optional[object], float, bool]:
    """AOT-compile a jitted ``fn`` at abstract ``avals`` through the
    service: ``(compiled_or_None, compile_ms, store_hit)``.

    With the persistent store installed and a ``store_key`` given, the
    key is looked up in the on-disk fingerprint index BEFORE compiling
    — so the measured milliseconds land in ``store_hit_ms`` when XLA
    is about to deserialize a stored executable and in ``cold_ms``
    when this is a genuinely fresh compile.  Only the ``.compile()``
    phase is attributed to that split: tracing/lowering runs the same
    Python either way and lands in ``trace_ms`` — folding it into the
    hit bucket is how BENCH_r06's ``xlaCompileStoreHitMs`` came to
    exceed ``xlaCompileColdMs`` — and recorded into it only
    AFTER the compile succeeded (a failing kernel must never be
    indexed as seen).  ``payload_fn`` supplies the pickled (steps,
    signature, capacity) triple the AOT warm pool replays; it runs
    only when the payload file is missing.  ``record=False`` classifies
    without recording — the warm pool's own replays use it so they
    cannot inflate their keys' top-K popularity on every restart.  A
    failed AOT compile returns ``None`` — jit-on-first-call remains
    correct — and any store failure (injected or real) degrades to a
    counted fresh compile."""
    hit = False
    digest = st = None
    if store_key is not None:
        from spark_rapids_tpu.compile import store as store_mod
        st = store_mod.current()
        if st is not None:
            digest, hit = st.lookup(store_key)
    t0 = time.perf_counter()
    compile_ms = 0.0
    try:
        lowered = fn.lower(*avals)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        compile_ms = (time.perf_counter() - t1) * 1e3
        _bump("trace_ms", (t1 - t0) * 1e3)
    except Exception:
        # AOT is an optimization; jit-on-first-call remains correct
        compiled = None
        _bump("aot_failures", 1)
    ms = (time.perf_counter() - t0) * 1e3
    _bump("aot_compiles", 1)
    # the deserialize seam is the .compile() call alone: a store hit
    # skips XLA compilation there, not the Python tracing before it
    _bump("store_hit_ms" if hit else "cold_ms", compile_ms)
    if record and compiled is not None and digest is not None:
        st.record_execution(digest, payload_fn)
    return compiled, ms, hit


def service_stats() -> dict:
    with _LOCK:
        out = dict(_STATS)
    out["cold_ms"] = round(out["cold_ms"], 1)
    out["store_hit_ms"] = round(out["store_hit_ms"], 1)
    out["trace_ms"] = round(out["trace_ms"], 1)
    return out


def reset_stats() -> None:
    with _LOCK:
        for k in _STATS:
            _STATS[k] = 0.0 if k.endswith("_ms") else 0


def snapshot() -> dict:
    """The ``compile`` group of the unified engine-stats snapshot
    (obs/registry.py; docs/observability.md carries the row table):
    store counters, the cold-vs-store-hit compile-time split, warm-pool
    counters, and the bucket-ladder bounds."""
    from spark_rapids_tpu.compile import buckets, store, warm
    st = store.stats()
    svc = service_stats()
    wm = warm.stats()
    lad = buckets.stats()
    return {
        "storeEnabled": st["enabled"],
        "compileStoreHits": st["hits"],
        "compileStoreMisses": st["misses"],
        "compileStoreBytes": st["bytes"],
        "compileStoreEntries": st["entries"],
        "compileStoreCorrupt": st["corrupt"],
        "compileStoreFaults": st["faults"],
        "compileStoreIoErrors": st["io_errors"],
        "xlaCompileColdMs": svc["cold_ms"],
        "xlaCompileStoreHitMs": svc["store_hit_ms"],
        "xlaCompileTraceMs": svc["trace_ms"],
        "aotCompiles": svc["aot_compiles"],
        "aotFailures": svc["aot_failures"],
        "warmPoolCompiles": wm["compiles"],
        "warmPoolErrors": wm["errors"],
        "bucketMinRows": lad["minRows"],
        "bucketMaxRows": lad["maxRows"],
    }
