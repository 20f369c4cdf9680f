"""Consolidated engine error hierarchy.

Every typed failure the engine can surface to a caller derives from
``EngineError``, so a serving layer (ROADMAP item 4) can catch ONE base
class and know the query failed in a *supervised* way — resources
reclaimed, teardown run — as opposed to an arbitrary exception escaping
a worker thread.  The shuffle plane's typed errors
(``FetchFailedError``, ``BlockCorruptError``, ...) multiple-inherit
from their original stdlib bases (``IOError``/``RuntimeError``) so the
retry/recompute machinery's ``isinstance`` checks are unchanged.

Reference: the plugin maps every recoverable failure to a typed
exception Spark's scheduler understands (FetchFailedException ->
map-stage recompute, SplitAndRetryOOM -> retry iterator); this module
is the analog hierarchy for the lifecycle layer
(docs/fault_tolerance.md, "Query lifecycle").
"""

from __future__ import annotations


class EngineError(Exception):
    """Base of every typed engine error (lifecycle, shuffle, injection).

    A query raising an ``EngineError`` subclass failed in a supervised
    way: the lifecycle registry has torn down its threads, staging
    permits, and device buffers."""


class QueryCancelledError(EngineError):
    """The query's cancel token was triggered (user cancel, session
    stop, or a deadline — see ``QueryTimeoutError``); cooperative
    checkpoints observed it and unwound."""


class QueryTimeoutError(QueryCancelledError):
    """The query exceeded ``spark.rapids.sql.queryTimeoutMs``.
    Subclasses ``QueryCancelledError`` because a deadline IS a
    cancellation — callers handling cancellation handle timeouts for
    free; callers that care can still distinguish."""


class AdmissionRejectedError(EngineError):
    """The session server's bounded admission queue shed this query
    (overload: ``spark.rapids.server.admission.queueDepth`` reached, or
    the server is stopping).  The query was never admitted — no plan was
    built, no resources were held — so the caller can retry with
    backoff or route to another replica (the typed overload-shedding
    contract of docs/serving.md)."""


class QueryBudgetExceededError(EngineError):
    """The query's device-resident bytes exceeded
    ``spark.rapids.server.query.maxDeviceBytes`` and spilling its own
    working set could not bring it back under budget.  Raised through
    the query's cancel token, so every thread of the query unwinds
    typed and teardown reclaims its buffers — the neighbors sharing the
    chip never see the pressure (docs/serving.md, "Memory budgets")."""


class ChipFailedError(EngineError):
    """A chip-attributed failure at an ICI collective gate
    (``exec/meshexec.py:_guarded_collective`` with
    ``spark.rapids.health.enabled``): the chip's EWMA health score was
    fed the failure and may have crossed the quarantine threshold
    (docs/fault_tolerance.md, "Chip failure domain").  The query dies
    mid-flight TYPED — the serving path replays it once against the
    re-formed mesh (``spark.rapids.server.retry.*``) instead of
    degrading every fragment to the host path forever."""

    def __init__(self, chip: int, message: str = ""):
        super().__init__(
            message or f"chip {chip} failed an ICI collective "
                       "(chip-attributed; fed to the health score)")
        self.chip = int(chip)

    def __reduce__(self):
        # BaseException's default pickle re-calls the class with
        # self.args (the formatted message alone), which cannot satisfy
        # this multi-argument signature
        return (ChipFailedError, (self.chip, str(self)))


class ReplicaFailedError(EngineError):
    """A session-server replica process died or was quarantined while
    this query was in flight on it (the replica failure domain,
    docs/serving.md "Serving fleet").  The fleet router replays the
    query once on a healthy replica when no results were surfaced and
    the per-tenant retry budget allows; otherwise this error surfaces —
    the caller retries with backoff exactly like an admission shed."""

    def __init__(self, replica: int, message: str = ""):
        super().__init__(
            message or f"replica {replica} failed while the query was "
                       "in flight (replica-attributed; fed to the "
                       "fleet health score)")
        self.replica = int(replica)

    def __reduce__(self):
        # BaseException's default pickle re-calls the class with
        # self.args (the formatted message alone), which cannot satisfy
        # this multi-argument signature
        return (ReplicaFailedError, (self.replica, str(self)))


class RetryBudgetExhaustedError(AdmissionRejectedError):
    """The session server's per-tenant replay budget
    (``spark.rapids.server.retry.budgetPerMin``) was exhausted: a
    chip-attributed failure that would have replayed is shed typed
    instead.  Subclasses ``AdmissionRejectedError`` because the shed
    contract is the same — the caller retries with backoff or routes to
    another replica (docs/serving.md, "Bounded query replay")."""


class QueryHangError(EngineError):
    """The hang watchdog (``spark.rapids.sql.watchdog.hangTimeoutMs``)
    bounded a blocking device pull / collective sync that did not
    complete in time.  NOT a cancellation: at an ICI collective the
    guarded gate catches this and degrades the fragment to the host
    path instead of failing the query (docs/fault_tolerance.md)."""

    def __init__(self, site: str, timeout_s: float, message: str = ""):
        super().__init__(
            message or f"watchdog: blocking call at {site} exceeded "
                       f"{timeout_s:.1f}s hang timeout")
        self.site = site
        self.timeout_s = timeout_s

    def __reduce__(self):
        # BaseException's default pickle re-calls the class with
        # self.args (the formatted message alone), which cannot satisfy
        # this multi-argument signature
        return (QueryHangError, (self.site, self.timeout_s, str(self)))
