"""Process-wide session-server counters (docs/serving.md).

The one aggregation point the obs registry snapshot reads
(``obs/registry.py`` -> ``snapshot()["server"]``) and bench.py's
``server`` summary object is a thin view of.  Deliberately standalone —
no imports from the rest of the server package — so the registry can
pull it without dragging the worker-pool machinery into every
``engine_stats()`` call.
"""

from __future__ import annotations

import threading
from typing import Dict

_LOCK = threading.Lock()

_COUNTERS = {
    "servers": 0,          # SessionServer instances started
    "submitted": 0,        # submit() calls that passed the fault gate
    "admitted": 0,         # accepted into the bounded fair queue
    "rejected": 0,         # shed typed (AdmissionRejectedError)
    "completed": 0,        # finished with a result (cache hits included)
    "failed": 0,           # surfaced an error to the ticket
    "cache_hits": 0,
    "cache_misses": 0,
    "cache_evictions": 0,
    "cache_inserts": 0,
    "cache_faults": 0,     # injected server.cache.lookup degrades
    "disk_cache_hits": 0,      # fleet-wide disk result tier (result_cache.py)
    "disk_cache_misses": 0,
    "disk_cache_inserts": 0,
    "disk_cache_evictions": 0,
    "disk_cache_corrupt": 0,   # corrupt/unreadable entries degraded to miss
    "prepared": 0,         # PreparedStatement handles created
    "prepared_execs": 0,   # bindings executed through handles
    # microseconds, summed over requests: submit -> a worker picked the
    # ticket up (what the server.admit.wait.us histogram observes), and
    # pick-up -> typed outcome on the ticket (replays included)
    "admit_wait_us": 0,
    "execute_us": 0,
}
# replay/drain counters live in the HEALTH stats object alone
# (health.py: replays / replays_shed / drains / drain_ms) — one store,
# one reset path (docs/serving.md, "Bounded query replay")

_GAUGES = {
    "cache_bytes": 0,      # current result-cache footprint
    "cache_entries": 0,
}


def bump(key: str, v: int = 1) -> None:
    if v:
        with _LOCK:
            _COUNTERS[key] += int(v)


def set_gauge(key: str, v: int) -> None:
    with _LOCK:
        _GAUGES[key] = int(v)


def global_stats() -> Dict[str, int]:
    with _LOCK:
        out = dict(_COUNTERS)
        out.update(_GAUGES)
        return out


def reset() -> None:
    with _LOCK:
        for k in _COUNTERS:
            _COUNTERS[k] = 0
        for k in _GAUGES:
            _GAUGES[k] = 0
