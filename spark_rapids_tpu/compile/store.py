"""Persistent kernel store: the JAX compilation cache + an on-disk
fingerprint index shared across processes and restarts
(docs/compile_cache.md).

Two layers, one directory (``spark.rapids.sql.compile.cacheDir``):

* ``<dir>/xla/``          — the JAX persistent compilation cache.  XLA
  writes serialized executables here keyed by its own HLO fingerprint;
  a later compile of the same program (this process, a spawned worker,
  or a restarted server) deserializes instead of recompiling.  The
  directory is exported through the env seam
  (``JAX_COMPILATION_CACHE_DIR``) so spawned shuffle/server worker
  processes inherit it with the rest of the shipped conf.  Where that
  variable is ALREADY set, XLA's cache stays where it points and only
  the index and payloads live under ``<dir>`` (``xla_cache_dir`` is the
  one function that decides).
* ``<dir>/index.jsonl`` + ``<dir>/payload/`` — the engine's OWN
  fingerprint index: one append-only JSONL line per executed
  (stage fingerprint, batch signature, capacity) triple, digested
  together with the engine/jax versions and the host fingerprint into
  the store key.  The index is what makes reuse *observable*
  (``compileStoreHits`` / ``Misses`` counters — a restarted process
  asserts zero fresh compiles through them) and what the AOT warm pool
  replays at startup: each first-sighting records a pickled payload of
  the triple, so a fresh process can re-drive the stage compiler into
  the warm XLA cache before the first query arrives.

Failure matrix: every store operation degrades to a counted fresh
compile — an unreadable index line, a poisoned payload, a full disk,
or an injected ``compile.store`` fault never fails the query, only the
reuse.  Conf-gated off by default: with ``compile.store.enabled``
unset no store exists and compilation behaves exactly as before.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

log = logging.getLogger("spark_rapids_tpu.compile.store")

FAULT_SITE_STORE = "compile.store"

_INDEX_NAME = "index.jsonl"
_PAYLOAD_DIR = "payload"
_XLA_DIR = "xla"


# ---------------------------------------------------------------------------
# JAX persistent-cache enablement (the ONE implementation; conftest and
# runtime init are both thin consumers)
# ---------------------------------------------------------------------------

_CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def xla_cache_dir(proposed: str) -> str:
    """Where XLA's persistent cache lives — the one function that
    decides.  ``JAX_COMPILATION_CACHE_DIR`` places it from outside: when
    set, every caller (the accelerator default, the conf-gated store,
    the test conftest) gets that directory back and never another; only
    with it unset does the caller's ``proposed`` directory stand."""
    return os.environ.get(_CACHE_DIR_ENV) or proposed


def enable_persistent_cache(cache_dir: str,
                            min_compile_secs: float = 0.0,
                            export_env: bool = True) -> bool:
    """Point the JAX persistent compilation cache at
    ``xla_cache_dir(cache_dir)`` and, with ``export_env``, export it
    through the env seam so spawned worker processes (mp "spawn" in
    shuffle/stage.py and shuffle/worker.py import jax fresh) inherit
    the same cache.  Never raises — the cache is an optimization and
    must not block startup.  Returns success."""
    import jax
    cache_dir = xla_cache_dir(cache_dir)
    try:
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_secs))
        if export_env:
            # never reassigned once set: whoever placed it first —
            # the user, or an earlier export of this process — stands
            os.environ.setdefault(_CACHE_DIR_ENV, cache_dir)
            os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = \
                str(min_compile_secs)
        return True
    except Exception as e:
        log.warning("cannot enable the persistent compile cache at "
                    "%r: %s", cache_dir, e)
        return False


def enable_default_cache(platform: str) -> None:
    """The accelerator-platform default: TPU cold compiles run seconds
    to minutes, so accelerator backends always get the persistent
    cache, at ``JAX_COMPILATION_CACHE_DIR`` or else a FIXED path (the
    path is part of what a later run must find again).  CPU runs never
    touch it by default — XLA:CPU AOT deserialization is unreliable
    across machine-feature mismatches — unless the store conf opts in
    explicitly (the test suite does, same-host by fingerprint)."""
    if platform == "cpu":
        return
    # no env export on this implicit path: only an explicit opt-in —
    # the conf-gated store or the test conftest — exports the variable
    enable_persistent_cache(_default_jax_cache_dir(platform),
                            min_compile_secs=1.0, export_env=False)


def _repo_root() -> Optional[str]:
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if os.access(repo, os.W_OK) and not repo.endswith("site-packages"):
        return repo
    return None


def _default_jax_cache_dir(platform: str) -> str:
    """``<checkout>/.jax_cache/<platform>`` (installed package: the
    user cache dir).  No host fingerprint: accelerator executables are
    keyed by XLA on the device, not on the host's CPU features."""
    repo = _repo_root()
    if repo is not None:
        return os.path.join(repo, ".jax_cache", platform)
    return os.path.join(os.path.expanduser("~"), ".cache", "srt-jax",
                        platform)


def default_store_dir(platform: Optional[str] = None) -> str:
    """Per-user default for ``spark.rapids.sql.compile.cacheDir``:
    keyed by backend platform and host fingerprint, because XLA:CPU
    artifacts embed machine features that are not in the cache key."""
    from spark_rapids_tpu import _host_fingerprint
    if platform is None:
        import jax
        platform = jax.default_backend()
    return os.path.join(os.path.expanduser("~"), ".cache",
                        "srt-compile", f"{platform}-{_host_fingerprint()}")


# ---------------------------------------------------------------------------
# the fingerprint index
# ---------------------------------------------------------------------------

class KernelStore:
    """On-disk fingerprint index over the XLA cache (one per process,
    installed by runtime init; see module docstring)."""

    def __init__(self, root: str, platform: str = ""):
        self.root = root
        self.platform = platform
        self.index_path = os.path.join(root, _INDEX_NAME)
        self.payload_dir = os.path.join(root, _PAYLOAD_DIR)
        os.makedirs(self.payload_dir, exist_ok=True)
        self._lock = threading.Lock()
        # digest -> [execution count, last ts] from the index (all
        # processes, all restarts that shared this dir)
        self._seen: Dict[str, List[float]] = {}
        self.hits = 0
        self.misses = 0
        self.faults = 0
        self.corrupt = 0
        self.io_errors = 0
        self.bytes_written = 0
        self._tag = self._version_tag(platform)
        self._load_index()

    @staticmethod
    def _version_tag(platform: str) -> str:
        import jax

        from spark_rapids_tpu import _host_fingerprint
        from spark_rapids_tpu.version import __version__
        return f"{__version__}|{jax.__version__}|{platform}|" \
               f"{_host_fingerprint()}"

    # past this many raw lines the index is rewritten as one
    # count-aggregated line per digest at load time, so a long-lived
    # shared store (one appended line per successful compile per
    # process run) cannot grow into an unbounded parse at every
    # process start
    COMPACT_LINES = 50_000

    def _load_index(self) -> None:
        lines = 0
        try:
            with open(self.index_path, encoding="utf-8") as fh:
                for line in fh:
                    line = line.strip()
                    if not line:
                        continue
                    lines += 1
                    try:
                        rec = json.loads(line)
                        key = rec["key"]
                    except (ValueError, KeyError, TypeError):
                        # a torn/poisoned index line costs one reuse
                        # opportunity, never a query
                        self.corrupt += 1
                        continue
                    ent = self._seen.setdefault(key, [0, 0.0])
                    # "n" is a compacted line's aggregated count
                    ent[0] += int(rec.get("n", 1))
                    ent[1] = max(ent[1], float(rec.get("ts", 0.0)))
        except FileNotFoundError:
            pass
        except OSError as e:
            log.warning("cannot read compile-store index %s: %s",
                        self.index_path, e)
            self.io_errors += 1
        if lines > self.COMPACT_LINES:
            self._compact_index()

    def _compact_index(self) -> None:
        """Rewrite the index as one ``{"key","ts","n"}`` line per
        digest.  Lines a concurrent process appends between our read
        and the atomic replace lose their popularity increment (never
        their digest — that process holds it in memory and its next
        execution re-appends); the count is an advisory warm-pool
        signal, so bounded loss is the right trade for a bounded
        file."""
        tmp = self.index_path + f".tmp{os.getpid()}"
        try:
            with open(tmp, "w", encoding="utf-8") as fh:
                for key, (count, ts) in self._seen.items():
                    fh.write(json.dumps(
                        {"key": key, "ts": ts, "n": int(count)},
                        separators=(",", ":")) + "\n")
            os.replace(tmp, self.index_path)
        except OSError as e:
            log.warning("compile-store index compaction failed "
                        "(index keeps growing, queries unaffected): "
                        "%s", e)
            self.io_errors += 1

    def digest(self, material) -> str:
        """Store key: sha256 over the cache-key material (stage
        fingerprint + batch signature + capacity) plus the engine/jax
        versions, backend platform, and host fingerprint — a version
        bump or a machine move can never claim a stale hit."""
        return hashlib.sha256(
            (self._tag + "\n" + repr(material)).encode()).hexdigest()

    def payload_path(self, digest: str) -> str:
        return os.path.join(self.payload_dir, digest + ".pkl")

    def lookup(self, material) -> Tuple[Optional[str], bool]:
        """Classify one compile BEFORE it runs: was this key seen by
        any process/restart sharing the store (counted hit/miss — the
        split the measured compile time lands in).  Degrades to
        ``(None, False)`` — a counted fresh compile — on an injected
        ``compile.store`` fault."""
        from spark_rapids_tpu import faults
        try:
            faults.maybe_fail(FAULT_SITE_STORE,
                              "injected compile-store failure")
        except faults.InjectedFault:
            with self._lock:
                self.faults += 1
            return None, False
        digest = self.digest(material)
        with self._lock:
            hit = digest in self._seen
            if hit:
                self.hits += 1
            else:
                self.misses += 1
        return digest, hit

    def record_execution(self, digest: str,
                         payload_fn: Optional[Callable[[], bytes]]
                         = None) -> None:
        """Append one SUCCESSFUL compile to the index (the warm pool's
        popularity signal), writing the pickled triple payload whenever
        its file is missing — not only on a first sighting, so a key
        whose first recording lost its payload to a transient write
        error is not excluded from the warm pool forever.  Called only
        after the compile succeeded: a failing kernel must never be
        indexed as seen (a restart would misclassify its fresh compile
        as a store hit and the warm pool would replay it forever)."""
        ts = round(time.time(), 3)
        with self._lock:
            payload = None
            if payload_fn is not None and \
                    not os.path.exists(self.payload_path(digest)):
                try:
                    payload = payload_fn()
                except Exception as e:
                    log.debug("compile-store payload build failed "
                              "(warm pool will skip this key): %s", e)
            try:
                if payload is not None:
                    path = self.payload_path(digest)
                    tmp = path + f".tmp{os.getpid()}"
                    with open(tmp, "wb") as fh:
                        fh.write(payload)
                    os.replace(tmp, path)  # atomic vs readers
                    self.bytes_written += len(payload)
                line = json.dumps({"key": digest, "ts": ts},
                                  separators=(",", ":")) + "\n"
                with open(self.index_path, "a", encoding="utf-8") as fh:
                    fh.write(line)  # O_APPEND: atomic for short lines
                self.bytes_written += len(line)
            except OSError as e:
                log.warning("compile-store write failed (reuse "
                            "degrades, query unaffected): %s", e)
                self.io_errors += 1
            ent = self._seen.setdefault(digest, [0, 0.0])
            ent[0] += 1
            ent[1] = max(ent[1], ts)

    def note_corrupt(self) -> None:
        with self._lock:
            self.corrupt += 1

    def top_entries(self, k: int) -> List[Tuple[str, int, str]]:
        """The warm pool's worklist: up to ``k`` (digest, execution
        count, payload path) triples, most-executed first (ties broken
        most-recent first), restricted to digests whose payload file
        exists — a key recorded without a payload cannot be replayed."""
        with self._lock:
            ranked = sorted(self._seen.items(),
                            key=lambda kv: (-kv[1][0], -kv[1][1]))
        out = []
        for digest, (count, _ts) in ranked:
            path = self.payload_path(digest)
            if os.path.exists(path):
                out.append((digest, int(count), path))
                if len(out) >= k:
                    break
        return out

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"entries": len(self._seen), "hits": self.hits,
                    "misses": self.misses, "faults": self.faults,
                    "corrupt": self.corrupt,
                    "io_errors": self.io_errors,
                    "bytes": self.bytes_written}


# ---------------------------------------------------------------------------
# process-global installation
# ---------------------------------------------------------------------------

_STORE_LOCK = threading.Lock()
_STORE: Optional[KernelStore] = None


def current() -> Optional[KernelStore]:
    return _STORE


def install(cache_dir: str, platform: str = "",
            min_compile_secs: float = 0.0) -> Optional[KernelStore]:
    """Install the store at ``cache_dir`` (idempotent on the same dir —
    counters survive) and point the JAX persistent cache at its
    ``xla/`` subdirectory, unless ``JAX_COMPILATION_CACHE_DIR`` already
    placed it (``xla_cache_dir``).  Returns None when the directory is
    unusable (the store is an optimization)."""
    global _STORE
    if not platform:
        # resolve the backend uniformly no matter which hook installed
        # the store (runtime init, query scope, server start, worker
        # main): a caller-dependent platform string would fork the
        # digest namespace and the same kernel would never hit across
        # the two install paths
        import jax
        platform = jax.default_backend()
    with _STORE_LOCK:
        if _STORE is not None and _STORE.root == cache_dir:
            return _STORE
        enable_persistent_cache(os.path.join(cache_dir, _XLA_DIR),
                                min_compile_secs=min_compile_secs)
        try:
            _STORE = KernelStore(cache_dir, platform)
        except OSError as e:
            log.warning("cannot install the compile store at %r: %s",
                        cache_dir, e)
            _STORE = None
        return _STORE


def disable() -> None:
    global _STORE
    with _STORE_LOCK:
        _STORE = None


def reset() -> None:
    """Test teardown: drop the installed store."""
    disable()


def configure_from_conf(conf, platform: Optional[str] = None
                        ) -> Optional[KernelStore]:
    """Install (or drop) the store from the ``spark.rapids.sql.
    compile.*`` conf keys — only when ``compile.store.enabled`` is
    explicitly present: the store is process-global, and a session that
    does not mention it must not drop (or re-point) another session's
    store.  Called by runtime init and by spawned worker mains with
    the shipped conf (shuffle/stage.py, shuffle/worker.py)."""
    from spark_rapids_tpu.conf import (
        COMPILE_CACHE_DIR, COMPILE_STORE_ENABLED,
    )
    settings = conf.to_dict()
    if COMPILE_STORE_ENABLED.key not in settings:
        return _STORE
    if not conf.get(COMPILE_STORE_ENABLED):
        disable()
        return None
    cache_dir = conf.get(COMPILE_CACHE_DIR) or default_store_dir(platform)
    return install(cache_dir, platform=platform or "")


def stats() -> Dict[str, int]:
    st = _STORE
    if st is None:
        return {"enabled": 0, "entries": 0, "hits": 0, "misses": 0,
                "faults": 0, "corrupt": 0, "io_errors": 0, "bytes": 0}
    out = {"enabled": 1}
    out.update(st.stats())
    return out
