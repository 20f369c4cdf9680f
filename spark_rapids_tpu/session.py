"""TpuSession — the user entry point (stands in for SparkSession + the plugin
bootstrap; reference Plugin.scala:145-242). Fleshed out with the DataFrame
API in spark_rapids_tpu.api."""

from __future__ import annotations

from typing import Any, Dict, Optional

from spark_rapids_tpu.conf import TpuConf


class TpuSession:
    """Session holding conf + runtime singletons (device manager, semaphore,
    shuffle env). Reference: RapidsDriverPlugin/RapidsExecutorPlugin init
    Plugin.scala:209-242."""

    _active: Optional["TpuSession"] = None

    def __init__(self, conf: Optional[Dict[str, Any]] = None):
        self.conf = TpuConf(conf)
        self._runtime = None
        self._last_plan_result = None
        self._views: Dict[str, Any] = {}  # temp view registry
        self._server = None  # lazy SessionServer (docs/serving.md)
        self._fleet = None  # lazy FleetRouter (docs/serving.md)
        TpuSession._active = self

    # -- SQL catalog (reference: the plugin is driven by spark.sql(...),
    # TpcxbbLikeSpark.scala) -------------------------------------------------

    def register_view(self, name: str, df) -> None:
        self._views[name.lower()] = df

    def drop_view(self, name: str) -> None:
        self._views.pop(name.lower(), None)

    def table(self, name: str):
        df = self._views.get(name.lower())
        if df is None:
            raise ValueError(
                f"table or view not found: {name} (register with "
                "df.create_or_replace_temp_view)")
        return df

    def sql(self, query: str):
        """Run a SQL SELECT (the spark.sql analog; see sql.py for the
        supported dialect)."""
        from spark_rapids_tpu.sql import parse_sql
        return parse_sql(query, self)

    def prepare(self, query: str):
        """Prepare a parameterized SELECT (``?`` markers): the template
        parses once per binding type signature and every binding shares
        one compiled kernel through the hoisted-literal slots
        (docs/serving.md).  ``.execute(*values)`` / ``.bind(*values)``
        re-execute it; submit the handle to ``session.server()`` for
        concurrent serving with result caching."""
        from spark_rapids_tpu.server.prepared import PreparedStatement
        return PreparedStatement(self, query)

    def server(self, max_concurrency: Optional[int] = None):
        """The session's multi-tenant ``SessionServer`` (started on
        first call; docs/serving.md): fair bounded admission, per-tenant
        deadlines, per-query memory budgets, prepared statements, and
        the plan-fingerprint result cache.  ``session.stop()`` closes
        it with the rest of the session's supervised resources."""
        from spark_rapids_tpu.conf import SERVER_ENABLED
        if not self.conf.get_bool(SERVER_ENABLED.key, default=True):
            # the key gates the serving plane: explicitly false means
            # an operator turned it off — refuse loudly rather than
            # start a worker pool they disabled.  Unset = calling
            # server() IS the opt-in.
            raise RuntimeError(
                f"{SERVER_ENABLED.key} is false; the session server "
                "is disabled for this session")
        if self._server is None or self._server.closed:
            from spark_rapids_tpu.server import SessionServer
            self._server = SessionServer(
                self, max_concurrency=max_concurrency)
        return self._server

    def fleet(self):
        """The session's ``FleetRouter`` front door over
        ``spark.rapids.fleet.replicas`` spawned SessionServer replica
        processes (started on first call; docs/serving.md, "Serving
        fleet"): tenant-aware routing with cross-replica overflow,
        replica-level quarantine/probation, single-replay failover under
        the per-tenant retry budget, and zero-downtime
        ``rolling_restart()``.  Requires ``spark.rapids.fleet.replicas``
        >= 1 — with the fleet keys unset the session behaves exactly as
        before (use ``session.server()`` for the in-process server).
        ``session.stop()`` closes the fleet with the rest of the
        session's supervised resources."""
        from spark_rapids_tpu.conf import FLEET_REPLICAS
        if self.conf.get(FLEET_REPLICAS) < 1:
            # unset/0 means no fleet: refuse loudly rather than spawn
            # a replica pool nobody configured
            raise RuntimeError(
                f"{FLEET_REPLICAS.key} is unset (or < 1); set it to "
                "the desired replica count before calling fleet()")
        if self._fleet is None or self._fleet.closed:
            from spark_rapids_tpu.fleet import FleetRouter
            self._fleet = FleetRouter(self)
        return self._fleet

    @classmethod
    def builder(cls) -> "_Builder":
        return _Builder()

    @classmethod
    def active(cls) -> "TpuSession":
        if cls._active is None:
            cls._active = TpuSession()
        return cls._active

    def set_conf(self, key: str, value) -> None:
        self.conf = self.conf.set(key, value)
        self._runtime = None  # force re-init with new conf

    def last_query_metrics(self) -> str:
        """Per-operator SQL metrics of the most recent executed query
        (reference: the Spark UI SQL metrics the plugin populates,
        GpuExec.scala:25-67).  One line per physical operator with its
        non-zero metrics; times reported in ms.  A thin legacy rendering
        of the ``last_query_profile()`` walk — byte-identical to the
        pre-obs flat string."""
        p = self.last_query_profile()
        if p is None:
            return "<no query executed>"
        return "\n".join(p.legacy_lines())

    def last_query_profile(self):
        """``QueryProfile`` of the most recent executed query: the
        executed plan tree (AQE's evolved children and ICI-lowered
        fragments as they actually ran) with per-operator metric
        snapshots — ``render()`` for the explain(analyze=True) text
        tree, ``to_dict()`` for programmatic consumers
        (docs/observability.md).  None before the first execution."""
        r = self._last_plan_result
        if r is None:
            return None
        from spark_rapids_tpu.obs.profile import QueryProfile
        return QueryProfile.from_plan(r.physical,
                                      query_id=r.query_id,
                                      wall_ms=r.wall_ms,
                                      placement=getattr(
                                          r, "placement", None),
                                      programs=getattr(
                                          r, "programs", None))

    def engine_stats(self) -> dict:
        """The process-wide engine-stats snapshot (docs/observability.md):
        every previously-scattered global stats object (prefetch, d2h,
        fusion, aqe, ici, lifecycle, kernel caches, spill catalog,
        journal counters) plus the latency/size histogram snapshots.
        ``python -m spark_rapids_tpu.obs`` renders the same snapshot in
        Prometheus exposition format."""
        from spark_rapids_tpu.obs import registry
        return registry.snapshot()

    @property
    def runtime(self):
        if self._runtime is None:
            from spark_rapids_tpu.runtime import TpuRuntime
            self._runtime = TpuRuntime(self.conf)
        return self._runtime

    @property
    def read(self):
        from spark_rapids_tpu.api import DataFrameReader
        return DataFrameReader(self)

    def create_dataframe(self, data, schema=None):
        from spark_rapids_tpu.api import create_dataframe
        return create_dataframe(self, data, schema)

    def range(self, start: int, end: Optional[int] = None, step: int = 1):
        from spark_rapids_tpu.api import range_df
        return range_df(self, start, end, step)

    def stop(self) -> None:
        if self._fleet is not None:
            # the fleet first: its replicas are whole child processes
            # holding their own sessions — close them before tearing
            # down this process's own serving plane
            self._fleet.close()
            self._fleet = None
        if self._server is not None:
            # explicit close first (idempotent): the server is also
            # lifecycle-registered, so shutdown_all would reach it, but
            # closing here fails still-queued tickets typed BEFORE the
            # registry sweep races their workers
            self._server.close()
            self._server = None
        if self._runtime is not None:
            # runtime.shutdown() routes through lifecycle.shutdown_all:
            # outstanding prefetch/warmer/shuffle-worker resources are
            # joined deterministically, never left to GC + daemon flags
            self._runtime.shutdown()
            self._runtime = None
        else:
            # no runtime ever materialized (or it was already dropped):
            # supervised resources registered outside a runtime still
            # tear down
            from spark_rapids_tpu import lifecycle
            lifecycle.shutdown_all()
        if TpuSession._active is self:
            TpuSession._active = None


class _Builder:
    def __init__(self):
        self._conf: Dict[str, Any] = {}

    def config(self, key: str, value) -> "_Builder":
        self._conf[key] = value
        return self

    def get_or_create(self) -> TpuSession:
        if TpuSession._active is not None:
            for k, v in self._conf.items():
                TpuSession._active.set_conf(k, v)
            return TpuSession._active
        return TpuSession(self._conf)
