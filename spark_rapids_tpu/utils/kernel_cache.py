"""Shared LRU cache for compiled device kernels.

Every jit call site in the engine memoizes its compiled function on a
(logical key, batch signature, capacity) tuple.  Those memos used to be
ad-hoc module dicts — several of them unbounded, so queries differing
only in embedded constants leaked compiled executables forever (the
``_FILTER_CACHE`` class of bug).  This module is the one sanctioned
shape for such caches: LRU-bounded by construction, thread-safe, and
instrumented with hit/miss/evict counters that the bench harness and
the fusion tests read (``tests/lint_robustness.py`` bans raw
module-level cache dicts repo-wide).

The interface is dict-like on purpose — ``get`` + item assignment —
so converting a module cache is a one-line change at its declaration;
``get_or_build`` is the preferred form for new call sites.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional

_REGISTRY: List["KernelCache"] = []
_REGISTRY_LOCK = threading.Lock()


class KernelCache:
    """Named, LRU-bounded, counter-instrumented kernel memo.

    ``register=False`` keeps the cache out of the process-wide registry
    (``all_stats`` / ``find``): for a memo that belongs to one object and
    dies with it, which the registry would otherwise pin forever."""

    def __init__(self, name: str, max_entries: int = 256,
                 register: bool = True):
        if max_entries <= 0:
            raise ValueError(f"KernelCache {name!r} needs a positive bound")
        self.name = name
        self.max_entries = int(max_entries)
        self._entries: "OrderedDict" = OrderedDict()
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        if register:
            with _REGISTRY_LOCK:
                _REGISTRY.append(self)

    def get(self, key, default=None):
        with self._lock:
            try:
                value = self._entries[key]
            except KeyError:
                self.misses += 1
                return default
            self._entries.move_to_end(key)
            self.hits += 1
            return value

    def peek(self, key, default=None):
        """Counter-neutral lookup (for double-checked re-reads that
        already counted their miss on the first ``get``)."""
        with self._lock:
            value = self._entries.get(key, default)
            if value is not default:
                self._entries.move_to_end(key)
            return value

    def __setitem__(self, key, value) -> None:
        with self._lock:
            self._entries[key] = value
            self._entries.move_to_end(key)
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
                self.evictions += 1

    def get_or_build(self, key, build: Callable[[], Any]):
        """Cached value for ``key``, building (and inserting) on miss.
        The build runs outside the lock — XLA compiles can take seconds
        and must not serialize unrelated lookups; a racing duplicate
        build is benign (last writer wins, both values equivalent)."""
        hit = self.get(key)
        if hit is not None:
            return hit
        value = build()
        self[key] = value
        return value

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._entries

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"size": len(self._entries), "hits": self.hits,
                    "misses": self.misses, "evictions": self.evictions}

    def reset_counters(self) -> None:
        with self._lock:
            self.hits = self.misses = self.evictions = 0


def all_stats() -> Dict[str, Dict[str, int]]:
    """name -> counters for every cache in the process (bench summary)."""
    with _REGISTRY_LOCK:
        caches = list(_REGISTRY)
    return {c.name: c.stats() for c in caches}


def find(name: str) -> Optional[KernelCache]:
    with _REGISTRY_LOCK:
        for c in _REGISTRY:
            if c.name == name:
                return c
    return None
