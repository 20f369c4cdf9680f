"""Per-operator SQL metrics.

Reference: GpuMetricNames and the metric wiring in GpuExec.scala:25-67 —
standard per-exec metrics (output rows/batches, total time, peak device
memory) plus operator-specific extras (aggregate.scala:835-845 computeAggTime/
concatTime; GpuShuffledHashJoinExec.scala:68-73 build/join times).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List

from spark_rapids_tpu.utils import tracing


METRIC_NUM_OUTPUT_ROWS = "numOutputRows"
METRIC_NUM_OUTPUT_BATCHES = "numOutputBatches"
METRIC_NUM_INPUT_ROWS = "numInputRows"
METRIC_NUM_INPUT_BATCHES = "numInputBatches"
METRIC_TOTAL_TIME = "totalTime"
METRIC_PEAK_DEVICE_MEMORY = "peakDeviceMemory"
# overlap-pipeline metrics (docs/io_overlap.md) — unlike the ns-valued
# time metrics above, the *Ms pair accumulates MILLISECONDS (the names
# carry the unit; producers aggregate ns internally and flush once)
METRIC_PREFETCH_BATCHES = "prefetchBatches"
METRIC_PREFETCH_STALL_MS = "prefetchStallMs"
# first-item pipe-fill wait, split out of stall: before the first batch
# lands there is no device compute to overlap with, so that wait is the
# pipeline priming cost, not an overlap failure
METRIC_PREFETCH_FILL_MS = "prefetchFillMs"
METRIC_H2D_OVERLAP_MS = "h2dOverlapMs"
# egress-pipeline metrics (docs/d2h_egress.md): device->host pulls
# issued (the fixed-latency unit on a remote-attached link), bytes
# pulled, and consumer time overlapped with an in-flight download (the
# *Ms suffix carries the unit, matching the prefetch pair above)
METRIC_D2H_PULLS = "d2hPulls"
METRIC_D2H_BYTES = "d2hBytes"
METRIC_D2H_OVERLAP_MS = "d2hOverlapMs"
# whole-stage fusion metrics (docs/fusion.md): ops folded into this
# stage, jitted dispatches issued (1 per batch when nothing split), and
# XLA compile milliseconds paid by this operator's kernels (the *Ms
# suffix again carries the unit)
METRIC_FUSED_OPS = "fusedOps"
METRIC_STAGE_DISPATCHES = "stageDispatches"
METRIC_XLA_COMPILE_MS = "xlaCompileMs"
# adaptive-query-execution metrics (docs/adaptive.md): replanning passes
# that changed the running plan, reduce partitions removed by runtime
# coalescing, extra sub-partitions created by skew splitting, the
# runtime broadcast decisions replacing the planner's static guess, and
# the total measured map-output bytes per exchange
METRIC_AQE_REPLANS = "aqeReplans"
METRIC_COALESCED_PARTITIONS = "coalescedPartitions"
METRIC_SKEW_SPLITS = "skewSplits"
METRIC_BROADCAST_PROMOTIONS = "broadcastPromotions"
METRIC_BROADCAST_DEMOTIONS = "broadcastDemotions"
METRIC_SHUFFLE_PARTITION_BYTES = "shufflePartitionBytes"
# cost-based placement (docs/placement.md): remainders the AQE
# runtime re-score demoted to the CPU engine after measured stage
# bytes contradicted the static size estimate
METRIC_PLACEMENT_DEMOTIONS = "placementDemotions"
# device-resident ICI shuffle metrics (docs/ici_shuffle.md): exchange
# fragments executed as on-device collectives, the estimated bytes they
# moved over the interconnect (per-destination counts x row width —
# host arithmetic on already-synced counts, never an extra link round
# trip), and fragments that degraded to the host path (injected
# collective fault, over-HBM stage, runtime RESOURCE_EXHAUSTED)
METRIC_ICI_EXCHANGES = "iciExchanges"
METRIC_ICI_BYTES = "iciBytes"
METRIC_ICI_FALLBACKS = "iciFallbacks"
# sharded scan ingest (docs/sharded_scan.md): fragments whose input
# arrived device-resident through per-chip scan pipelines, and the
# shard pipelines those fragments ran
METRIC_ICI_SHARDED_SCANS = "iciShardedScans"
METRIC_ICI_SHARDED_SHARDS = "iciShardedShards"
# operator-specific metrics (docs/observability.md carries the full
# table).  These were string literals scattered across exec/, io/, and
# shuffle/ — named here so the known-names registry below can reject a
# typo'd metric name instead of silently minting a metric nobody reads
METRIC_COMPUTE_AGG_TIME = "computeAggTime"
METRIC_CONCAT_TIME = "concatTime"
METRIC_BUILD_TIME = "buildTime"
METRIC_JOIN_TIME = "joinTime"
METRIC_BROADCAST_TIME = "broadcastTime"
METRIC_SAMPLE_TIME = "sampleTime"
METRIC_UPLOAD_TIME = "uploadTime"
# the other half of a scan that missed the device scan cache
# (io/hostio.py pipelined_scan): nanoseconds decoding files to host
# batches, wherever that ran, and the host bytes handed to the upload
METRIC_DECODE_TIME = "decodeTime"
METRIC_UPLOAD_BYTES = "uploadBytes"
METRIC_SEM_WAIT_MS = "semWaitMs"
METRIC_DATA_SIZE = "dataSize"
METRIC_PALLAS_AGG_BATCHES = "pallasAggBatches"
METRIC_MASKED_FILTER_BATCHES = "maskedFilterBatches"
METRIC_GROUPED_UPDATE_BATCHES = "groupedUpdateBatches"
METRIC_FK_FAST_PATH_BATCHES = "fkFastPathBatches"
METRIC_BAND_JOIN_PROBES = "bandJoinProbes"
METRIC_SCAN_CACHE_HITS = "scanCacheHits"
METRIC_NUM_FILES_READ = "numFilesRead"
METRIC_NUM_FILES_TOTAL = "numFilesTotal"
METRIC_NUM_ROW_GROUPS_READ = "numRowGroupsRead"
METRIC_NUM_ROW_GROUPS_TOTAL = "numRowGroupsTotal"
METRIC_NUM_STRIPES_READ = "numStripesRead"
METRIC_NUM_STRIPES_TOTAL = "numStripesTotal"
METRIC_ENCODED_COLUMNS = "encodedColumns"
METRIC_LATE_DECODES = "lateDecodes"
METRIC_COMPRESSED_BYTES_SAVED = "compressedBytesSaved"
METRIC_SHUFFLE_ROWS_WRITTEN = "shuffleRowsWritten"
METRIC_SHUFFLE_MAP_RECOMPUTES = "shuffleMapRecomputes"
METRIC_SHUFFLE_PARTITIONS_RECOMPUTED = "shufflePartitionsRecomputed"
# out-of-core device execution (docs/out_of_core.md): spill-resident
# partitions written by the grace-partition phase, bytes routed through
# the partition spill seam, recursive re-partition rounds on
# still-over-budget partitions, and operators that degraded to the
# single-chip host path (recursion exhausted or injected ooc.partition
# fault)
METRIC_OOC_PARTITIONS = "oocPartitions"
METRIC_OOC_SPILL_BYTES = "oocSpillBytes"
METRIC_OOC_RECURSIONS = "oocRecursions"
METRIC_OOC_FALLBACKS = "oocFallbacks"
# the dispatch ledger (compile/service.py, docs/observability.md
# "Programs"): programs this node launched and the device nanoseconds
# the completion watcher measured for them.  Written only under
# spark.rapids.sql.trace.enabled, so untraced profiles are unchanged
METRIC_DEVICE_TIME = "deviceTime"
METRIC_DEVICE_DISPATCHES = "deviceDispatches"


def _collect_known_metrics() -> frozenset:
    return frozenset(v for k, v in globals().items()
                     if k.startswith("METRIC_") and isinstance(v, str))


# Every metric name an operator may mint.  ``MetricSet`` asserts
# membership so a typo'd name fails loudly at the call site instead of
# silently vanishing into a metric nobody reads (the docs lint in
# tests/lint_robustness.py keeps this table in sync with
# docs/observability.md).  Tests exercising synthetic names opt out with
# ``MetricSet(adhoc=True)`` or ``register_adhoc_metric``.
KNOWN_METRICS = _collect_known_metrics()

_ADHOC_LOCK = threading.Lock()
_ADHOC_METRICS = set()


def register_adhoc_metric(name: str) -> None:
    """Escape hatch for names outside the METRIC_* registry (tests,
    experiments): permits ``name`` process-wide."""
    with _ADHOC_LOCK:
        _ADHOC_METRICS.add(name)


class Metric:
    """Additive metric (ns for times, counts otherwise).

    ``add`` accepts device-resident counts (LazyRows / 0-d device arrays)
    without forcing a host sync — pending device scalars are resolved in
    one batched pull the first time ``value`` is read (each sync over a
    remote-attached chip costs a link round trip, so per-batch metric
    reads must not block the hot path)."""

    __slots__ = ("name", "_value", "_pending", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._value = 0
        self._pending = []
        self._lock = threading.Lock()

    def __getstate__(self):
        """Plans ship to shuffle worker processes by pickle: drop the
        lock and any device-resident pending counts (a device array is
        meaningless in another process)."""
        return {"name": self.name, "_value": self._value}

    def __setstate__(self, state):
        self.name = state["name"]
        self._value = state["_value"]
        self._pending = []
        self._lock = threading.Lock()

    def add(self, v) -> None:
        from spark_rapids_tpu.columnar.column import LazyRows
        with self._lock:
            if isinstance(v, LazyRows):
                if v.known:
                    self._value += v.get()
                else:
                    self._pending.append(v)
            elif isinstance(v, (int, float)):
                self._value += int(v)
            else:  # 0-d device array
                self._pending.append(v)

    def set_max(self, v: int) -> None:
        with self._lock:
            self._value = max(self._value, int(v))

    @property
    def value(self) -> int:
        with self._lock:
            if self._pending:
                from spark_rapids_tpu.columnar.column import LazyRows
                from spark_rapids_tpu.columnar.transfer import device_pull
                raw = [p.dev if isinstance(p, LazyRows) else p
                       for p in self._pending]
                # one batched pull for every pending device count,
                # through THE egress primitive (docs/d2h_egress.md): a
                # metric sync pays a real link round trip, so it counts
                # in the process-wide d2hPulls and is covered by the
                # transfer.d2h fault site like every other pull
                vals = device_pull(raw)
                for p, v in zip(self._pending, vals):
                    if isinstance(p, LazyRows):
                        p._val = int(v)
                self._value += sum(int(v) for v in vals)
                self._pending = []
            return self._value


class MetricSet:
    """Metrics owned by one physical operator instance.

    ``__getitem__`` mints metrics on demand but only for KNOWN names
    (the METRIC_* registry above): a typo'd metric name used to mint a
    fresh zero-valued metric that silently diverged from the one the
    operator actually accumulated.  ``adhoc=True`` (tests) or
    ``register_adhoc_metric`` opt specific names out."""

    def __init__(self, *names: str, owner: str = "", adhoc: bool = False):
        base = (METRIC_NUM_OUTPUT_ROWS, METRIC_NUM_OUTPUT_BATCHES, METRIC_TOTAL_TIME)
        self._adhoc = adhoc
        for n in names:
            self._check(n)
        self._metrics: Dict[str, Metric] = {n: Metric(n) for n in (*base, *names)}
        self.owner = owner
        self._span_names: Dict[str, str] = {}

    def _check(self, name: str) -> None:
        if self._adhoc or name in KNOWN_METRICS:
            return
        with _ADHOC_LOCK:
            if name in _ADHOC_METRICS:
                return
        raise KeyError(
            f"unknown metric name {name!r}: add a METRIC_* constant in "
            "utils/metrics.py (and document it in docs/observability.md)"
            " — minting unregistered names silently hides typos; tests "
            "may use MetricSet(adhoc=True) or register_adhoc_metric()")

    def __getitem__(self, name: str) -> Metric:
        if name not in self._metrics:
            self._check(name)
            self._metrics[name] = Metric(name)
        return self._metrics[name]

    def timed(self, name: str):
        return _Timer(self[name], self)

    def span_name(self, metric: str) -> str:
        """``<owner>.<metric>``, built once per metric (and only when a
        traced timer first asks)."""
        name = self._span_names.get(metric)
        if name is None:
            name = f"{self.owner}.{metric}" if self.owner else metric
            self._span_names[metric] = name
        return name

    def items(self):
        return self._metrics.items()

    def snapshot(self) -> Dict[str, int]:
        return {n: m.value for n, m in self._metrics.items()}


class _Timer:
    __slots__ = ("_metric", "_start", "_ann", "_set")

    def __init__(self, metric: Metric, owner: MetricSet):
        self._metric = metric
        self._set = owner
        self._start = 0
        self._ann = None

    def __enter__(self):
        self._start = time.perf_counter_ns()
        # named profiler range so timed operator sections show in Xprof
        # (reference NvtxWithMetrics.scala:27 fusing NVTX + SQLMetric);
        # gated on the session trace switch so untraced runs pay one check
        if tracing.is_enabled():
            self._ann = tracing.annotation(
                self._set.span_name(self._metric.name))
            if self._ann is not None:
                self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        self._metric.add(time.perf_counter_ns() - self._start)
        return False


class Histogram:
    """Fixed-bucket log2 latency/size histogram (docs/observability.md).

    64 buckets, bucket ``b`` holding values whose ``bit_length()`` is
    ``b`` (i.e. [2^(b-1), 2^b)); bucket 0 holds zero.  Recording is one
    ``bit_length`` plus three increments under a short lock — cheap
    enough for the D2H pull and admission-wait paths it instruments —
    and ``snapshot()`` derives p50/p90/p99 from the bucket counts
    (resolution is the factor-of-two bucket width; estimates use the
    bucket midpoint).  Units ride in the histogram NAME (``*.us`` /
    ``*.bytes``), mirroring the ``*Ms`` metric-name convention."""

    NBUCKETS = 64
    QUANTILES = (0.5, 0.9, 0.99)

    __slots__ = ("name", "_counts", "_count", "_sum", "_lock")

    def __init__(self, name: str = ""):
        self.name = name
        self._counts: List[int] = [0] * self.NBUCKETS
        self._count = 0
        self._sum = 0
        self._lock = threading.Lock()

    def record(self, value) -> None:
        v = int(value)
        if v < 0:
            v = 0
        b = min(v.bit_length(), self.NBUCKETS - 1)
        with self._lock:
            self._counts[b] += 1
            self._count += 1
            self._sum += v

    @staticmethod
    def _bucket_mid(b: int) -> int:
        if b <= 0:
            return 0
        lo = 1 << (b - 1)
        return lo + (lo >> 1)  # midpoint of [2^(b-1), 2^b)

    def snapshot(self) -> Dict[str, int]:
        """{"count", "sum", "mean", "p50", "p90", "p99"} — percentile
        estimates are log2-bucket midpoints (zero when empty)."""
        with self._lock:
            counts = list(self._counts)
            n = self._count
            total = self._sum
        out = {"count": n, "sum": total,
               "mean": (total // n) if n else 0}
        targets = {f"p{int(q * 100)}": q * n for q in self.QUANTILES}
        cum = 0
        mids = {k: 0 for k in targets}
        found = {k: False for k in targets}
        for b, c in enumerate(counts):
            if not c:
                continue
            cum += c
            for key, tgt in targets.items():
                if not found[key] and cum >= tgt:
                    found[key] = True
                    mids[key] = self._bucket_mid(b)
        out.update(mids)
        return out

    def reset(self) -> None:
        with self._lock:
            self._counts = [0] * self.NBUCKETS
            self._count = 0
            self._sum = 0
