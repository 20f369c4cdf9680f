"""The multi-tenant session server (docs/serving.md).

``SessionServer`` is the serving front end ROADMAP item 4 calls for: N
concurrent queries submitted through a bounded weighted-fair admission
queue (admission.py) ahead of the chip semaphore, executed by a worker
pool under per-tenant deadlines and per-query device-memory budgets,
with prepared statements (prepared.py) and a plan-fingerprint result
cache (result_cache.py).  Every component composes existing machinery:

* admitted queries execute through the SAME ``DataFrame._execute``
  path single-query sessions use — ``lifecycle.query_scope`` gives each
  its own fault domain, ``TpuSemaphore`` bounds device concurrency,
  and the spill catalog enforces the budget — so server-on and
  server-off results are byte-identical by construction;
* per-tenant conf (deadline, budget) rides a ``_TenantSession`` facade:
  the base session's views, runtime, catalog, and scan cache are
  shared, only ``conf`` is overlaid per query;
* failures surface TYPED at the ticket (``AdmissionRejectedError``,
  ``QueryTimeoutError``, ``QueryBudgetExceededError``, ...) — a caller
  of ``ticket.result()`` always gets rows or one ``EngineError``
  subclass, never a hang (workers poll, teardown drains the queue).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, Optional, Tuple

from spark_rapids_tpu import faults, health, lifecycle
from spark_rapids_tpu.conf import (
    FLEET_RESULT_CACHE_DIR, FLEET_RESULT_CACHE_MAX_BYTES,
    QUERY_TIMEOUT_MS, SERVER_DEFAULT_WEIGHT, SERVER_MAX_CONCURRENCY,
    SERVER_QUERY_MAX_DEVICE_BYTES, SERVER_QUEUE_DEPTH,
    SERVER_RESULT_CACHE, SERVER_RESULT_CACHE_BYTES,
    SERVER_RESULT_CACHE_ENTRIES, SERVER_RETRY_BUDGET_PER_MIN,
    SERVER_RETRY_MAX_ATTEMPTS, SERVER_TENANT_PREFIX,
    SERVER_TENANT_TIMEOUT_MS, STREAM_CACHE_MAINTAIN, STREAM_ENABLED,
)
from spark_rapids_tpu.errors import (
    AdmissionRejectedError, ChipFailedError, RetryBudgetExhaustedError,
)
from spark_rapids_tpu.obs import journal
from spark_rapids_tpu.obs import registry as obs
from spark_rapids_tpu.server import stats
from spark_rapids_tpu.server.admission import FairAdmissionQueue
from spark_rapids_tpu.server.prepared import PreparedStatement
from spark_rapids_tpu.server.result_cache import (
    DiskResultTier, ResultCache,
)
from spark_rapids_tpu.utils import tracing

FAULT_SITE_ADMIT = "server.admit"

# worker poll slice: how long a stop can go unobserved by an idle worker
_POLL_S = 0.1


class ServerQuery:
    """Ticket for one submitted query: ``result()`` blocks until the
    worker completes it (rows) or fails it (one typed error)."""

    __slots__ = ("tenant", "kind", "payload", "params", "timeout_ms",
                 "use_cache", "submitted_at", "started_at",
                 "finished_at", "cache_hit", "_done", "_result",
                 "_error")

    def __init__(self, tenant: str, kind: str, payload, params: tuple,
                 timeout_ms: Optional[int], use_cache: bool = True):
        self.tenant = tenant
        self.kind = kind            # "sql" | "df" | "prepared"
        self.payload = payload
        self.params = params
        self.timeout_ms = timeout_ms
        self.use_cache = use_cache  # standing-query refreshes bypass
        self.submitted_at = time.monotonic()
        self.started_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.cache_hit = False
        self._done = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._done.is_set()

    @property
    def latency_ms(self) -> Optional[float]:
        if self.finished_at is None:
            return None
        return (self.finished_at - self.submitted_at) * 1e3

    def result(self, timeout: Optional[float] = None):
        if not self._done.wait(timeout=timeout):
            raise TimeoutError(
                f"query not finished within {timeout}s (still "
                f"{'running' if self.started_at else 'queued'})")
        if self._error is not None:
            raise self._error
        return self._result

    def _complete(self, table) -> None:
        self.finished_at = time.monotonic()
        self._result = table
        self._done.set()

    def _fail(self, exc: BaseException) -> None:
        self.finished_at = time.monotonic()
        self._error = exc
        self._done.set()


class _TenantSession:
    """Per-query session view: the base session's views, runtime, and
    caches with a tenant conf overlaid — two tenants' deadlines or
    budgets can differ without either mutating the shared session."""

    def __init__(self, base, conf):
        self._base = base
        self.conf = conf
        self._last_plan_result = None

    def __getattr__(self, name):
        return getattr(self._base, name)


class SessionServer:
    """N-concurrent-query serving front end over one ``TpuSession``."""

    def __init__(self, session, max_concurrency: Optional[int] = None):
        conf = session.conf
        self.session = session
        # conf-driven fault injection must reach the PRE-query server
        # sites (server.admit fires before any query scope exists, so
        # query_scope's injector installation would come too late);
        # same guard as lifecycle.query_scope — a conf with no fault
        # keys leaves a directly-configured injector alone
        if any(k.startswith(faults.FAULTS_PREFIX)
               for k in conf.to_dict()):
            faults.configure_from_conf(conf)
        # chip-health scoring parameters, same per-key guard
        # (docs/fault_tolerance.md, "Chip failure domain")
        if any(k.startswith(health.HEALTH_PREFIX)
               for k in conf.to_dict()):
            health.configure_from_conf(conf)
        # persistent compilation service at SERVER start
        # (docs/compile_cache.md): the shared hook installs the store
        # from this conf (same per-key guard as the blocks above) and
        # kicks the AOT warm pool, so a restarted serving replica
        # replays the store's top-K recorded kernels BEFORE the first
        # tenant query lands — idempotent with the runtime-init and
        # query-scope hooks
        from spark_rapids_tpu import compile as compile_pkg
        compile_pkg.configure_from_conf(conf)
        # bounded query replay (docs/serving.md): total attempts per
        # chip-failed query + the per-tenant replay token window
        self._retry_max = conf.get(SERVER_RETRY_MAX_ATTEMPTS)
        self._retry_budget = conf.get(SERVER_RETRY_BUDGET_PER_MIN)
        self._replay_lock = threading.Lock()
        self._replay_times: Dict[str, deque] = {}
        self._draining = threading.Event()
        # close()/drain() claim the terminal transition under this lock
        # (the QueryContext.finish pattern): concurrent callers — a
        # rolling restart's drain racing session.stop(), say — must
        # resolve to exactly ONE drain sweep and ONE close sweep
        self._close_lock = threading.Lock()
        self._queue = FairAdmissionQueue(
            conf.get(SERVER_QUEUE_DEPTH),
            conf.get(SERVER_DEFAULT_WEIGHT),
            self._tenant_weights(conf))
        self._cache: Optional[ResultCache] = None
        if conf.get(SERVER_RESULT_CACHE):
            disk = None
            disk_dir = conf.get(FLEET_RESULT_CACHE_DIR)
            if disk_dir:
                # the fleet-wide disk tier (docs/serving.md, "Serving
                # fleet"): shared across replica processes beside the
                # compile store
                disk = DiskResultTier(
                    disk_dir, conf.get(FLEET_RESULT_CACHE_MAX_BYTES))
            self._cache = ResultCache(
                conf.get(SERVER_RESULT_CACHE_ENTRIES),
                conf.get(SERVER_RESULT_CACHE_BYTES), disk=disk)
        if max_concurrency is None:
            n = conf.get(SERVER_MAX_CONCURRENCY)
            if n <= 0:
                # 2x the chip permits: enough in-flight queries that a
                # decode- or pull-bound one never idles the device, few
                # enough that host memory stays bounded (the scheduler
                # in front of the semaphore, not a replacement for it)
                n = 2 * session.runtime.semaphore.permits
        else:
            n = int(max_concurrency)   # 0 = no workers (test hook:
            #                            tests drain the queue manually)
        self._closed = threading.Event()
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        self._threads = []
        self._streaming = None
        # the server itself is a lifecycle-supervised resource:
        # session.stop() / shutdown_all reaches close() even when the
        # caller forgets, so worker threads are joined deterministically
        reg = lifecycle.register_resource(self.close, kind="server",
                                          name="session-server")
        self._reg = reg
        if reg.rejected:
            # teardown raced construction: never bring workers up
            self._closed.set()
            return
        for i in range(max(0, n)):
            t = threading.Thread(target=self._worker,
                                 name=f"srt-server-worker-{i}",
                                 daemon=True)
            self._threads.append(t)
            t.start()
        if conf.get(STREAM_ENABLED):
            # the continuous-query layer (docs/streaming.md): tailing
            # sources + standing queries + the poller thread, brought
            # up WITH the workers it refreshes through and torn down
            # by close() before them
            from spark_rapids_tpu.stream.standing import (
                StandingQueryRegistry,
            )
            self._streaming = StandingQueryRegistry(self)
        stats.bump("servers")

    @staticmethod
    def _tenant_weights(conf) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for key, value in conf.to_dict().items():
            if key.startswith(SERVER_TENANT_PREFIX) \
                    and key.endswith(".weight"):
                tenant = key[len(SERVER_TENANT_PREFIX):-len(".weight")]
                out[tenant] = int(value)
        return out

    @property
    def closed(self) -> bool:
        return self._closed.is_set()

    @property
    def streaming(self):
        """The standing-query registry (docs/streaming.md).  Exists
        only when the server was built with
        ``spark.rapids.stream.enabled`` — everything continuous hangs
        off this accessor, so an unset conf leaves the serving path
        byte-identical to a build without the stream package."""
        if self._streaming is None:
            raise RuntimeError(
                "streaming is disabled: set spark.rapids.stream.enabled "
                "before constructing the SessionServer")
        return self._streaming

    # -- submission ---------------------------------------------------------

    def submit(self, query, tenant: str = "default",
               timeout_ms: Optional[int] = None,
               params: Optional[tuple] = None,
               use_cache: bool = True) -> ServerQuery:
        """Admit a query (SQL text, DataFrame, or PreparedStatement +
        ``params``) into the fair queue; returns its ticket.  Raises
        ``AdmissionRejectedError`` when shed (queue full / server
        stopping or draining) and ``InjectedFault`` when the
        ``server.admit`` fault site fires — both BEFORE anything is
        enqueued, so an admission failure can never wedge the queue.
        ``use_cache=False`` bypasses the result cache for this ticket
        (standing-query refreshes: delta plans are one-shot by
        construction and must neither read nor populate it)."""
        if self._closed.is_set():
            raise AdmissionRejectedError(
                "session server is stopped; query not admitted")
        if self._draining.is_set():
            raise AdmissionRejectedError(
                "session server is draining; query not admitted "
                "(resubmit to another replica)")
        faults.maybe_fail(FAULT_SITE_ADMIT,
                          f"injected admission failure (tenant "
                          f"{tenant!r})")
        stats.bump("submitted")
        if isinstance(query, str):
            kind = "sql"
        elif isinstance(query, PreparedStatement):
            kind = "prepared"
        else:
            kind = "df"
        ticket = ServerQuery(tenant, kind, query,
                             tuple(params or ()), timeout_ms,
                             use_cache=use_cache)
        try:
            self._queue.offer(tenant, ticket)
        except AdmissionRejectedError:
            stats.bump("rejected")
            journal.emit(journal.EVENT_QUERY_REJECTED, tenant=tenant,
                         waiting=self._queue.size(),
                         depth=self._queue.depth)
            raise
        stats.bump("admitted")
        journal.emit(journal.EVENT_QUERY_ADMITTED, tenant=tenant,
                     kind=kind, waiting=self._queue.size())
        return ticket

    def sql(self, sql: str, tenant: str = "default",
            timeout_ms: Optional[int] = None,
            result_timeout: Optional[float] = None):
        """Blocking convenience: submit + ``result()``."""
        return self.submit(sql, tenant=tenant,
                           timeout_ms=timeout_ms).result(result_timeout)

    def prepare(self, sql: str) -> PreparedStatement:
        """A prepared-statement handle executable through ``submit``
        (or directly, outside the server)."""
        return PreparedStatement(self.session, sql)

    # -- the worker pool ----------------------------------------------------

    def _worker(self) -> None:
        def claim():
            # runs UNDER the queue lock at the pop (take's on_dispatch
            # contract): the ticket is counted in-flight atomically
            # with leaving the backlog, so a drain() can never observe
            # it in neither place and close onto a running query
            with self._inflight_lock:
                self._inflight += 1

        while True:
            got = self._queue.take(timeout=_POLL_S, on_dispatch=claim)
            if got is None:
                if self._closed.is_set() or self._queue.closed:
                    return
                continue
            _tenant, ticket = got
            try:
                self._execute(ticket)
            finally:
                with self._inflight_lock:
                    self._inflight -= 1

    def _execute(self, ticket: ServerQuery) -> None:
        """Run one admitted query to a typed outcome on its ticket; a
        worker thread must survive ANY per-query failure.  A
        chip-attributed ``ChipFailedError`` (the chip failure domain,
        docs/fault_tolerance.md) replays the query against the
        re-formed mesh through the per-tenant retry budget — bounded by
        ``spark.rapids.server.retry.maxAttempts`` and only when the
        failed attempt surfaced no results."""
        ticket.started_at = time.monotonic()
        waited_us = int((ticket.started_at - ticket.submitted_at) * 1e6)
        obs.record(obs.HIST_SERVER_ADMIT_WAIT_US, waited_us)
        stats.bump("admit_wait_us", waited_us)
        attempts = 0
        try:
            # the request is a traced scope of its own: its bookkeeping
            # before and after the query (tenant conf, bind, result-cache
            # key) is under the switch too, not only DataFrame._execute
            with tracing.switch_scope(self.session.conf.trace_enabled), \
                    tracing.trace_range(
                        f"{tracing.SPAN_SERVER_EXECUTE}:{ticket.tenant}"):
                # the wait is over by the time a worker can say so: a
                # marker at the pick-up; its length is in admit_wait_us
                with tracing.trace_range(tracing.SPAN_SERVER_ADMIT_WAIT):
                    pass
                while True:
                    attempts += 1
                    view = _TenantSession(
                        self.session, self._tenant_conf(ticket.tenant,
                                                        ticket.timeout_ms))
                    try:
                        self._run_attempt(ticket, view)
                        return
                    except ChipFailedError as e:
                        self._check_replay(ticket, view, attempts, e)
                        health.note_replay()
                        journal.emit(journal.EVENT_QUERY_REPLAY,
                                     tenant=ticket.tenant, chip=e.chip,
                                     attempt=attempts)
        except BaseException as e:
            stats.bump("failed")
            ticket._fail(e)
        finally:
            stats.bump("execute_us", int(
                (time.monotonic() - ticket.started_at) * 1e6))

    def _run_attempt(self, ticket: ServerQuery,
                     view: "_TenantSession") -> None:
        with tracing.trace_range(tracing.SPAN_SERVER_RESOLVE):
            df = self._resolve(ticket, view)
        key = pins = None
        leaves = None
        maintain = False
        if self._cache is not None and ticket.use_cache:
            maintain = view.conf.get(STREAM_CACHE_MAINTAIN)
            with tracing.trace_range(tracing.SPAN_SERVER_CACHE_KEY):
                key, pins, leaves = self._cache_key(
                    df, ticket.params, view.conf, with_leaves=maintain)
            if key is not None:
                hit = self._cache.lookup(key)
                if hit is not None:
                    journal.emit(journal.EVENT_CACHE_HIT,
                                 tenant=ticket.tenant)
                    ticket.cache_hit = True
                    stats.bump("completed")
                    ticket._complete(hit)
                    return
                journal.emit(journal.EVENT_CACHE_MISS,
                             tenant=ticket.tenant)
                if maintain:
                    table = self._try_maintain(df, key, pins, leaves,
                                               view, ticket.tenant)
                    if table is not None:
                        stats.bump("completed")
                        ticket._complete(table)
                        return
        table = df.to_arrow()
        if key is not None:
            self._cache.put(key, table, pins, leaves=leaves)
        stats.bump("completed")
        ticket._complete(table)

    def _check_replay(self, ticket: ServerQuery, view: "_TenantSession",
                      attempts: int, exc: ChipFailedError) -> None:
        """Gate one replay of a chip-failed query; raises (the original
        error, or the typed budget shed) when the replay is not
        allowed.  Replay is only meaningful under the chip failure
        domain — health off re-raises immediately."""
        if not health.conf_enabled(self.session.conf):
            raise exc
        if attempts >= self._retry_max:
            raise exc
        # the PlanResult seam: df._execute retains a PlanResult on its
        # session view only AFTER the full drain succeeded, so a set
        # _last_plan_result means results were surfaced — a replay
        # could then double-produce; a None means the attempt died
        # clean and a fresh attempt is safe
        if getattr(view, "_last_plan_result", None) is not None:
            raise exc
        now = time.monotonic()
        with self._replay_lock:
            window = self._replay_times.setdefault(
                ticket.tenant, deque())
            while window and now - window[0] > 60.0:
                window.popleft()
            if len(window) >= self._retry_budget:
                health.note_replay_shed()
                raise RetryBudgetExhaustedError(
                    f"tenant {ticket.tenant!r} exhausted its replay "
                    f"budget ({self._retry_budget}/min, "
                    "spark.rapids.server.retry.budgetPerMin); "
                    "chip-failed query shed") from exc
            window.append(now)

    def _resolve(self, ticket: ServerQuery, view: _TenantSession):
        from spark_rapids_tpu.api import DataFrame
        if ticket.kind == "sql":
            from spark_rapids_tpu.sql import parse_sql
            # SQL text may carry `?` markers with the values in
            # ticket.params (the one-shot parameterized form); a
            # marker/value count mismatch surfaces as a typed SqlError
            return parse_sql(ticket.payload, view,
                             params=list(ticket.params)
                             if ticket.params else None)
        if ticket.kind == "prepared":
            return ticket.payload.bind(*ticket.params, session=view)
        # a DataFrame built against the base session: re-home it on the
        # tenant view so the tenant's deadline/budget conf governs
        return DataFrame(view, ticket.payload.plan)

    def _tenant_conf(self, tenant: str, timeout_ms: Optional[int]):
        """The base conf with the tenant's deadline default (and budget
        override, when present) applied — flowing into the query's
        ``QueryContext`` through the normal ``from_conf`` path."""
        base = self.session.conf
        raw = base.to_dict()
        overlay: Dict[str, object] = {}
        if timeout_ms is None:
            per = raw.get(f"{SERVER_TENANT_PREFIX}{tenant}.timeoutMs")
            if per is not None:
                timeout_ms = int(per)
            else:
                default = base.get(SERVER_TENANT_TIMEOUT_MS)
                if default > 0:
                    timeout_ms = default
        if timeout_ms is not None:
            overlay[QUERY_TIMEOUT_MS.key] = int(timeout_ms)
        budget = raw.get(f"{SERVER_TENANT_PREFIX}{tenant}"
                         ".maxDeviceBytes")
        if budget is not None:
            overlay[SERVER_QUERY_MAX_DEVICE_BYTES.key] = int(budget)
        return base.with_settings(overlay) if overlay else base

    def _cache_key(self, df, params: tuple, conf,
                   with_leaves: bool = False
                   ) -> Tuple[Optional[tuple], tuple, Optional[tuple]]:
        from spark_rapids_tpu.plan.fingerprint import (
            bound_param_values, conf_fingerprint, plan_fingerprint,
            snapshot_detail,
        )
        snap, pins, leaves = snapshot_detail(df.plan)
        if snap is None:
            return None, (), None
        try:
            # the masked plan fingerprint needs the values back in the
            # key: read them from the PLAN itself (bound_param_values),
            # so a DataFrame built from stmt.bind(x) and submitted as a
            # df (empty ticket.params) can never collide with another
            # binding of the same template
            key = (plan_fingerprint(df.plan), snap,
                   conf_fingerprint(conf), params,
                   bound_param_values(df.plan))
            hash(key)
        except TypeError:
            return None, (), None  # unhashable binding: skip the cache
        # leaf tokens ride on the cache entry ONLY under cache
        # maintenance (docs/streaming.md) — they hold live plan nodes,
        # and a non-streaming server must not grow its entries
        return key, pins, (leaves if with_leaves else None)

    # -- maintained cache entries (docs/streaming.md) -----------------------

    def _try_maintain(self, df, key, pins, leaves, view,
                      tenant: str):
        """Maintain a stale cache entry in place instead of recomputing:
        when the previous entry for the same plan/conf/bindings differs
        from the live snapshot by APPENDED FILES ONLY on one
        incrementalizable leaf, fold just those files in and re-key the
        entry under the new snapshot.  Any other drift — a changed,
        shrunk, or vanished committed file, appends on several leaves,
        a non-incrementalizable plan — falls back to the normal
        recompute path (counted ``cache_maintain_fallbacks``), which
        repopulates the cache with a fresh maintainable entry."""
        from spark_rapids_tpu.stream import stats as stream_stats
        cand = self._cache.maintain_candidate(key)
        if cand is None:
            return None
        old_key, old_table, old_leaves = cand
        if leaves is None or len(old_leaves) != len(leaves):
            stream_stats.bump("cache_maintain_fallbacks")
            return None
        # identical plan fingerprints walk identical leaf orders, so
        # the two snapshots zip positionally
        changed = []
        for (new_leaf, new_pairs), (_old, old_pairs) in zip(leaves,
                                                            old_leaves):
            old_map = dict(old_pairs)
            new_map = dict(new_pairs)
            if any(new_map.get(p) != tok for p, tok in old_map.items()):
                # a committed file changed or vanished: not append-only
                stream_stats.bump("cache_maintain_fallbacks")
                return None
            appended = [p for p, _ in new_pairs if p not in old_map]
            if appended:
                changed.append((new_leaf, appended))
        if len(changed) != 1:
            # nothing appended (the snapshot moved elsewhere — a pinned
            # relation, say) or appends across several leaves at once
            stream_stats.bump("cache_maintain_fallbacks")
            return None
        leaf, appended = changed[0]
        table = self._maintain_delta(df, leaf, appended, old_table,
                                     view)
        if table is None:
            stream_stats.bump("cache_maintain_fallbacks")
            return None
        self._cache.replace(old_key, key, table, pins, leaves=leaves)
        stream_stats.bump("cache_maintains")
        journal.emit(journal.EVENT_CACHE_MAINTAIN, tenant=tenant,
                     files=len(appended))
        return table

    def _maintain_delta(self, df, leaf, appended, old_table, view):
        """The refreshed result from the cached one plus the appended
        files, or None when this plan cannot be maintained WITHOUT
        stored auxiliary state: append-mode plans (old ++ delta) and
        mergeable aggregations whose result still carries the full
        state — the chain above the Aggregate is pure attribute
        renames (the SQL planner's output projection), a bijection
        back onto every group and aggregate column, and no Average
        (its (sum, count) state is wider than its result column).
        A HAVING-style Filter above the agg drops groups from the
        result and is rejected here (standing queries keep full state
        and DO maintain it).  Each step executes through the normal
        engine under the tenant view."""
        import pyarrow as pa
        from spark_rapids_tpu.api import DataFrame
        from spark_rapids_tpu.plan import incremental as inc
        from spark_rapids_tpu.stream.source import new_files_leaf

        rewrite, _reason = inc.analyze(df.plan, stream_leaf=leaf)
        if rewrite is None:
            return None
        delta_leaf = new_files_leaf(leaf, appended)

        def run(plan):
            return DataFrame(view, plan).to_arrow()

        if rewrite.kind == "append":
            delta = run(rewrite.delta_plan(delta_leaf))
            return pa.concat_tables(
                [old_table, delta.cast(old_table.schema)])
        state = self._state_from_result(rewrite, old_table)
        if state is None:
            return None
        delta_state = run(rewrite.delta_state_plan(delta_leaf))
        merged = run(rewrite.merge_plan([state, delta_state]))
        return run(rewrite.finalize_plan(merged)).cast(old_table.schema)

    @staticmethod
    def _state_from_result(rewrite, old_table):
        """The partial-state table rebuilt from a cached agg RESULT, or
        None when the result does not determine the state: an Average
        in the aggregate list, or an upper chain that is not a pure
        attribute-rename bijection of the Aggregate's output."""
        from spark_rapids_tpu.exprs.base import (
            Alias, UnresolvedAttribute,
        )
        from spark_rapids_tpu.plan import logical as lp
        if len(rewrite._state_aggs) != len(rewrite._agg.aggregates):
            return None  # an Average widened the state
        agg_out = (list(rewrite._group_names)
                   + [a.out_name for a in rewrite._agg.aggregates])
        # thread (visible name -> originating agg-output column)
        # through the upper chain, bottom-up
        cols = [(n, n) for n in agg_out]
        for node in reversed(rewrite._upper):
            if not isinstance(node, lp.Project):
                return None  # a Filter drops groups: state is gone
            byname = dict(cols)
            new = []
            for e in node.exprs:
                if isinstance(e, Alias) \
                        and isinstance(e.child, UnresolvedAttribute):
                    src, out = byname.get(e.child.name), e.out_name
                elif isinstance(e, UnresolvedAttribute):
                    src, out = byname.get(e.name), e.name
                else:
                    return None  # a computed column: not invertible
                if src is None:
                    return None
                new.append((out, src))
            cols = new
        srcs = [s for _, s in cols]
        if sorted(srcs) != sorted(agg_out):
            return None  # dropped or duplicated a column: no bijection
        import pyarrow as pa
        src_idx = {s: i for i, (_, s) in enumerate(cols)}
        state_names = (list(rewrite._group_names)
                       + [a.name for a in rewrite._state_aggs])
        return pa.table(
            {sn: old_table.column(src_idx[src])
             for sn, src in zip(state_names, agg_out)})

    # -- introspection / teardown -------------------------------------------

    def stats(self) -> dict:
        out = {"workers": len(self._threads),
               "inflight": self._inflight,
               "closed": self._closed.is_set(),
               "draining": self._draining.is_set(),
               "queue": self._queue.stats(),
               "semaphore_available":
                   self.session.runtime.semaphore.available()}
        if self._cache is not None:
            out["cache"] = self._cache.snapshot_stats()
        if self._streaming is not None:
            out["stream"] = self._streaming.stats()
        return out

    def drain(self, timeout: float = 60.0) -> float:
        """Graceful drain (docs/serving.md): stop admitting (further
        submits shed typed), typed-reject the still-QUEUED tickets,
        wait — bounded by ``timeout`` — for in-flight queries to
        finish, then close.  A rolling restart under chip trouble is an
        operation, not an outage: in-flight work completes, nothing is
        cancelled unless the bound expires (close() then escalates to
        cancellation).  Returns the drain duration in ms (also
        accumulated in the ``health`` stats object as ``drain_ms``)."""
        # atomic claim (the QueryContext.finish pattern): exactly one
        # caller runs the drain sweep.  A plain is_set() check races —
        # two concurrent drain() calls would both pass it and
        # double-count drain_ms / double-emit the journal events; a
        # drain racing close() would sweep a queue close() already
        # drained
        with self._close_lock:
            if self._closed.is_set() or self._draining.is_set():
                return 0.0
            self._draining.set()
        t0 = time.perf_counter()
        journal.emit(journal.EVENT_SERVER_DRAIN, phase="start",
                     inflight=self._inflight,
                     queued=self._queue.size())
        for _tenant, ticket in self._queue.close_and_drain():
            stats.bump("failed")
            ticket._fail(AdmissionRejectedError(
                "session server draining; queued query rejected "
                "(resubmit to another replica)"))
        deadline = time.monotonic() + max(0.0, float(timeout))
        while time.monotonic() < deadline:
            with self._inflight_lock:
                if self._inflight == 0:
                    break
            time.sleep(0.02)
        self.close()
        ms = (time.perf_counter() - t0) * 1e3
        health.note_drain(ms)
        journal.emit(journal.EVENT_SERVER_DRAIN, phase="done",
                     ms=round(ms, 3))
        return ms

    def close(self) -> None:
        """Stop accepting, fail still-queued tickets typed, join the
        workers (bounded — an in-flight query's own deadline bounds the
        worker), drop the cache.  Idempotent — the terminal transition
        is claimed atomically, so concurrent close() calls (a drain
        racing session.stop() racing the lifecycle sweep) resolve to
        one teardown; also reached from ``session.stop()`` via the
        lifecycle registry."""
        with self._close_lock:
            if self._closed.is_set():
                return
            self._closed.set()
        streaming = getattr(self, "_streaming", None)
        if streaming is not None:
            # stop the poller FIRST: it submits refreshes through the
            # queue this teardown is about to fail
            streaming.close()
        for _tenant, ticket in self._queue.close_and_drain():
            stats.bump("failed")
            ticket._fail(AdmissionRejectedError(
                "session server stopped before the query was "
                "dispatched"))
        # cancel the WORKER THREADS' in-flight queries (and only
        # those — other sessions' queries are not ours to kill): a
        # deadline-less running query otherwise stalls close for the
        # whole join timeout; cancelled ones unwind typed within a
        # poll interval and their tickets fail with the cancel error
        lifecycle.cancel_thread_queries(
            (t.ident for t in self._threads if t.ident is not None),
            "session server stopped")
        for t in self._threads:
            t.join(timeout=10.0)
        if self._cache is not None:
            self._cache.clear()
        reg = getattr(self, "_reg", None)
        if reg is not None:
            # a closed-on-arrival registration invokes close() from
            # inside register_resource, before _reg is assigned
            reg.release()
