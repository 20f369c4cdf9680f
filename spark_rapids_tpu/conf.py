"""Typed, self-documenting configuration registry.

Re-designs the reference's config system (sql-plugin RapidsConf.scala:96-206
``ConfEntry``/``TypedConfBuilder`` and :699-832 ``RapidsConf``): every entry
self-registers with a key, doc string, default and optional validator, and the
registry can render user documentation (reference: RapidsConf.help
RapidsConf.scala:600-688 -> docs/configs.md).

Per-operator enable keys (``spark.rapids.sql.{expression,exec,input,
partitioning,output}.<Class>``, reference GpuOverrides.scala:118-123) are
created dynamically by the planner rule registry; ``TpuConf.is_operator_enabled``
mirrors RapidsConf.scala:828-831.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional


class ConfEntry:
    """One registered configuration key (reference: ConfEntry RapidsConf.scala:96)."""

    def __init__(self, key: str, default: Any, doc: str, conf_type: type,
                 validator: Optional[Callable[[Any], Optional[str]]] = None,
                 internal: bool = False):
        self.key = key
        self.default = default
        self.doc = doc
        self.conf_type = conf_type
        self.validator = validator
        self.internal = internal

    def convert(self, raw: Any) -> Any:
        if raw is None:
            return None
        if self.conf_type is bool:
            if isinstance(raw, bool):
                return raw
            return str(raw).strip().lower() in ("true", "1", "yes")
        if self.conf_type in (int, float, str):
            return self.conf_type(raw)
        return raw

    def validate(self, value: Any) -> None:
        if self.validator is not None:
            err = self.validator(value)
            if err:
                raise ValueError(f"{self.key}: {err} (got {value!r})")


_REGISTRY: Dict[str, ConfEntry] = {}
_REGISTRY_LOCK = threading.Lock()


def register(key: str, default: Any, doc: str, conf_type: type = str,
             validator: Optional[Callable[[Any], Optional[str]]] = None,
             internal: bool = False) -> ConfEntry:
    """Register a conf entry; idempotent per key (reference ConfBuilder
    RapidsConf.scala:175-206 appends to the registered-entries table)."""
    with _REGISTRY_LOCK:
        if key in _REGISTRY:
            return _REGISTRY[key]
        entry = ConfEntry(key, default, doc, conf_type, validator, internal)
        _REGISTRY[key] = entry
        return entry


def conf_entries() -> List[ConfEntry]:
    return sorted(_REGISTRY.values(), key=lambda e: e.key)


def _positive(v) -> Optional[str]:
    return None if v > 0 else "must be positive"


def _non_negative(v) -> Optional[str]:
    return None if v >= 0 else "must be >= 0"


def _fraction(v) -> Optional[str]:
    return None if 0.0 < v <= 1.0 else "must be in (0, 1]"


def _fraction_inclusive(v) -> Optional[str]:
    return None if 0.0 <= v <= 1.0 else "must be in [0, 1]"


def _one_of(*options):
    # case-insensitive for string enums (Spark conf convention)
    folded = tuple(o.upper() if isinstance(o, str) else o for o in options)

    def check(v):
        vv = v.upper() if isinstance(v, str) else v
        return None if vv in folded else f"must be one of {options}"
    return check


# ---------------------------------------------------------------------------
# Core entries. Keys keep the reference's spark.rapids.* naming with the sql/
# memory/shuffle sub-namespaces so reference users find what they expect
# (reference: RapidsConf.scala:208-697), with "tpu" replacing "gpu".
# ---------------------------------------------------------------------------

SQL_ENABLED = register(
    "spark.rapids.sql.enabled", True,
    "Master enable for TPU SQL acceleration. When false every operator stays "
    "on the CPU engine (reference RapidsConf.scala ENABLE_SQL).", bool)

TEST_ENABLED = register(
    "spark.rapids.sql.test.enabled", False,
    "Test mode: fail if a query does not fully execute on the TPU, modulo the "
    "allowed-non-tpu list (reference RapidsConf.scala:456-469, enforced in "
    "GpuTransitionOverrides.scala:211-254).", bool)

TEST_ALLOWED_NON_TPU = register(
    "spark.rapids.sql.test.allowedNonTpu", "",
    "Comma-separated class names allowed to stay on CPU in test mode "
    "(reference TEST_ALLOWED_NONGPU RapidsConf.scala:462).", str)

INCOMPATIBLE_OPS = register(
    "spark.rapids.sql.incompatibleOps.enabled", False,
    "Enable operators that produce results different from Spark in corner "
    "cases (reference RapidsConf.scala:333-337).", bool)

IMPROVED_FLOAT_OPS = register(
    "spark.rapids.sql.improvedFloatOps.enabled", False,
    "Use faster float transcendentals that may differ from Java semantics in "
    "the last ulp (reference RapidsConf.scala improvedFloatOps).", bool)

HAS_NANS = register(
    "spark.rapids.sql.hasNans", True,
    "Assume floating point data may contain NaNs; disables some groupby "
    "paths when true (reference RapidsConf.scala HAS_NANS; aggregate.scala:159-165).",
    bool)

VARIABLE_FLOAT_AGG = register(
    "spark.rapids.sql.variableFloatAgg.enabled", False,
    "Allow float aggregations whose result can vary with evaluation order "
    "(reference RapidsConf.scala ENABLE_FLOAT_AGG).", bool)

DEVICE_DOUBLE_AS_FLOAT = register(
    "spark.rapids.sql.device.doubleAsFloat", None,
    "Store and compute DOUBLE columns as float32 on the device, widening "
    "back to float64 at the host boundary.  TPUs have no f64 hardware — "
    "XLA emulates it in software (~3.5x slower scatter/segment ops, 2x "
    "HBM and link bytes) — so the default is true on accelerator "
    "backends and false on CPU (where the compare oracle runs bit-exact "
    "f64).  Results can differ from CPU Spark in the ~1e-7 relative "
    "range, the same class of documented difference the reference admits "
    "behind spark.rapids.sql.variableFloatAgg.enabled.", bool)

CAST_FLOAT_TO_STRING = register(
    "spark.rapids.sql.castFloatToString.enabled", False,
    "Enable float->string cast (formatting differs slightly from Java; "
    "reference GpuCast.scala CastExprMeta gates).", bool)

CAST_STRING_TO_FLOAT = register(
    "spark.rapids.sql.castStringToFloat.enabled", False,
    "Enable string->float cast (reference RapidsConf ENABLE_CAST_STRING_TO_FLOAT).", bool)

CAST_STRING_TO_TIMESTAMP = register(
    "spark.rapids.sql.castStringToTimestamp.enabled", False,
    "Enable string->timestamp cast (reference RapidsConf).", bool)

CAST_STRING_TO_INTEGER = register(
    "spark.rapids.sql.castStringToInteger.enabled", False,
    "Enable string->integral cast (overflow corner cases; reference RapidsConf).", bool)

EXPLAIN = register(
    "spark.rapids.sql.explain", "NONE",
    "Print plan tagging: NONE, ALL, or NOT_ON_TPU with per-node reasons "
    "(reference RapidsConf.scala:584-589; RapidsMeta.scala:207-277).",
    str, _one_of("NONE", "ALL", "NOT_ON_TPU"))

BATCH_SIZE_BYTES = register(
    "spark.rapids.sql.batchSizeBytes", 2147483647,
    "Target size in bytes for coalesced TPU batches (reference "
    "RapidsConf.scala:289-296 GPU_BATCH_SIZE_BYTES).", int, _positive)

BATCH_SIZE_ROWS = register(
    "spark.rapids.sql.batchSizeRows", 1 << 20,
    "Target row count for coalesced TPU batches; also the bucket cap used to "
    "pad batches to a small set of static shapes so XLA compiles once per "
    "bucket (TPU-specific; reference caps rows at 2^31 in "
    "GpuCoalesceBatches.scala:263-311).", int, _positive)

MAX_READER_BATCH_SIZE_ROWS = register(
    "spark.rapids.sql.reader.batchSizeRows", 1 << 20,
    "Soft limit on rows per batch produced by file readers (reference "
    "RapidsConf.scala:297-302). Larger batches amortize per-dispatch "
    "latency; the spill catalog absorbs the memory cost.", int, _positive)

MAX_READER_BATCH_SIZE_BYTES = register(
    "spark.rapids.sql.reader.batchSizeBytes", 512 * 1024 * 1024,
    "Soft limit on bytes per batch produced by file readers (reference "
    "RapidsConf.scala:303-308).", int, _positive)

PALLAS_AGG = register(
    "spark.rapids.sql.tpu.pallas.agg.enabled", True,
    "Use the Pallas one-hot-reduction kernel (sort-free update phase, "
    "partials as long as the key domain) for aggregations whose key "
    "domain the host knows and fits 1024 dense slots: every group key "
    "a dictionary code (the slot is the mixed radix of the codes, "
    "nothing pulled), no group key at all, or one integer key whose "
    "range a memoized probe pulls.  Which specs it takes is a static "
    "rule (exec/pallas_agg.py:supports — 32-bit planes only on the "
    "chip); every other aggregation runs the sorted-segment kernel.",
    bool)

RANGE_SAMPLE_SIZE = register(
    "spark.rapids.sql.rangePartitioning.sampleSize", 10_000,
    "Maximum rows sampled to compute range-partition bounds (reference "
    "reservoir sampling, GpuRangePartitioner.scala:42).", int, _positive)

MAX_STRING_WIDTH = register(
    "spark.rapids.sql.maxDeviceStringWidth", 512,
    "Maximum string width (bytes) representable in the device padded-bytes "
    "string layout; longer strings fall back to CPU (TPU-specific analog of "
    "cuDF's 2GB string column limit, GpuCoalesceBatches.scala:263-311).",
    int, _positive)

CONCURRENT_TPU_TASKS = register(
    "spark.rapids.sql.concurrentTpuTasks", 0,
    "Legacy alias for spark.rapids.tpu.concurrentTasks: when set to a "
    "positive value it overrides that key (reference "
    "RapidsConf.scala:276-282 CONCURRENT_GPU_TASKS). 0 defers.",
    int, _non_negative)

TPU_CONCURRENT_TASKS = register(
    "spark.rapids.tpu.concurrentTasks", 2,
    "Number of concurrent tasks the chip semaphore admits (reference "
    "GpuSemaphore.scala:27 + concurrentGpuTasks). 2 lets a decode-bound "
    "scan task and a compute-bound task interleave on one chip — the "
    "admission half of the scan->H2D->compute overlap pipeline "
    "(docs/io_overlap.md); raise it only if host memory allows the "
    "extra in-flight batches.", int, _positive)

IO_PREFETCH_ENABLED = register(
    "spark.rapids.sql.io.prefetch.enabled", True,
    "Decode the next file-scan batches on a background host thread while "
    "the device computes on the current batch, and double-buffer the "
    "host->device uploads so the upload of batch k+1 is dispatched "
    "before batch k's consumer synchronizes (docs/io_overlap.md). "
    "Prefetch-on and prefetch-off runs produce byte-identical, "
    "identically-ordered results; false restores the strictly serial "
    "decode->upload->compute loop.", bool)

IO_PREFETCH_BATCHES = register(
    "spark.rapids.sql.io.prefetch.batches", 2,
    "Bounded depth of the background decode queue: how many decoded host "
    "batches a scan may hold ahead of the consumer.  Each queued batch "
    "is admitted through the host staging limiter "
    "(spark.rapids.memory.pinnedPool.size) before it may occupy queue "
    "space, bounding dispatch-time staging at depth+2 batches (queued + "
    "consumer-held + one acquired by a producer parked on the full "
    "queue); like the serial path's release-at-dispatch accounting, an "
    "in-flight async copy can briefly exceed the cap by about one "
    "batch.", int, _positive)

IO_EGRESS_ENABLED = register(
    "spark.rapids.sql.io.egress.enabled", True,
    "Device->host egress pipeline (docs/d2h_egress.md), the downstream "
    "mirror of the scan prefetch pipeline.  Two effects: (1) partition "
    "exchanges writing to the host shuffle pack the WHOLE partition-"
    "contiguous batch on device and cross the link in ONE pull per "
    "input batch regardless of partition count (per-partition counts "
    "ride in the same pull; the host slices per-partition record "
    "batches from them), and (2) downloads are double-buffered: batch "
    "k+1's pack kernel and device->host copy are dispatched "
    "(asynchronously — no background thread) before batch k's blocking "
    "pull, so k+1's link transfer overlaps host serialization/"
    "compression/sends (shuffle) or encoding (writers) of batch k; "
    "each blocking pull is admitted through a dedicated egress "
    "host-staging limiter (spark.rapids.memory.pinnedPool.size cap) "
    "for the pull's duration only.  Egress-on and egress-off runs "
    "produce byte-identical results; false restores the strictly "
    "serial pull-per-partition path.", bool)

QUERY_TIMEOUT_MS = register(
    "spark.rapids.sql.queryTimeoutMs", 0,
    "Per-query deadline in milliseconds, enforced cooperatively by the "
    "lifecycle layer (spark_rapids_tpu/lifecycle.py): operator pull "
    "boundaries and every bounded blocking wait (chip-semaphore "
    "admission, staging-limiter admission, prefetch queue gets) check "
    "the query's cancel token and surface a typed QueryTimeoutError "
    "once the deadline passes, after which registered resources "
    "(prefetch threads, compile warmers, shuffle worker processes, "
    "staging permits) tear down in registration order.  0 disables "
    "supervision entirely — execution is byte-identical to the "
    "unsupervised engine.", int, _non_negative)

CANCEL_CHECK_INTERVAL_MS = register(
    "spark.rapids.sql.cancel.checkIntervalMs", 50,
    "Poll interval for the lifecycle layer's bounded blocking waits: "
    "the longest a cancel or an expired deadline can go unobserved by "
    "a wait that cannot be woken directly (semaphore admission, "
    "prefetch queue gets, watchdog join slices).", int, _positive)

WATCHDOG_HANG_TIMEOUT_MS = register(
    "spark.rapids.sql.watchdog.hangTimeoutMs", 0,
    "Hang watchdog bound in milliseconds on blocking calls cooperative "
    "cancellation cannot reach: a device->host pull "
    "(columnar/transfer.py:device_pull, fault site io.pipeline.hang) "
    "or an ICI collective sync (exec/meshexec.py:_guarded_collective, "
    "fault site shuffle.ici.hang).  When > 0 the call runs on a "
    "supervised thread; exceeding the bound raises a typed "
    "QueryHangError — at the guarded collective gate the fragment "
    "degrades to the host path (iciFallbacks) instead of hanging the "
    "query.  0 disables (blocking calls run inline, byte-identical).",
    int, _non_negative)

FUSION_ENABLED = register(
    "spark.rapids.sql.fusion.enabled", True,
    "Whole-stage kernel fusion: collapse maximal chains of per-batch, "
    "capacity-preserving operators (project, filter, and the hash "
    "exchange's partition-key projection) into one jitted stage kernel, "
    "so a project->filter->project chain costs ONE dispatch round trip "
    "per batch and zero intermediate full-capacity materializations "
    "(docs/fusion.md; the TPU analog of Spark whole-stage codegen). "
    "false restores the per-operator execution path byte-for-byte.",
    bool)

FUSION_MAX_OPS = register(
    "spark.rapids.sql.fusion.maxOps", 16,
    "Upper bound on operators folded into one fused stage; longer "
    "chains split into multiple stages so a pathological plan cannot "
    "produce an unboundedly large XLA program.", int, _positive)

FUSION_LITERAL_HOISTING = register(
    "spark.rapids.sql.fusion.literalHoisting.enabled", True,
    "Pass non-null, non-string literal constants into kernels as traced "
    "scalar arguments instead of baked XLA constants, keyed OUT of the "
    "kernel cache key — two queries differing only in their constants "
    "then share one compiled kernel (docs/fusion.md).  Only active "
    "while spark.rapids.sql.fusion.enabled is true.", bool)

FUSION_WARMER_ENABLED = register(
    "spark.rapids.sql.fusion.warmer.enabled", True,
    "Start compiling a fused stage's kernel on a background thread at "
    "execution setup when the scan signature is predictable from the "
    "file schema and reader batching, overlapping XLA compile with the "
    "scan/prefetch pipeline's first decodes (docs/fusion.md).", bool)

# -- persistent compilation service (docs/compile_cache.md) -----------------
#
# All off by default: with spark.rapids.sql.compile.* unset no store
# exists, the capacity ladder keeps today's bounds, and plans, results,
# and metrics are byte-identical to the pre-service engine (asserted in
# tests/test_compile.py).

COMPILE_PREFIX = "spark.rapids.sql.compile."

COMPILE_STORE_ENABLED = register(
    "spark.rapids.sql.compile.store.enabled", False,
    "Persistent kernel store (docs/compile_cache.md): enable the JAX "
    "persistent compilation cache inside the engine and layer the "
    "on-disk fingerprint index over it, so stage kernels compiled by "
    "any process sharing spark.rapids.sql.compile.cacheDir (spawned "
    "shuffle/server workers inherit it through the env seam) "
    "deserialize instead of recompiling across restarts — the r05 "
    "cold_ms of 8-33s per suite is the number this attacks.  Reuse is "
    "observable through the compileStoreHits/Misses counters and the "
    "cold-vs-store-hit split of measured compile time; every store "
    "failure (corrupt index line, poisoned payload, full disk, "
    "injected compile.store fault) degrades to a counted fresh "
    "compile.  false/unset = today's behavior exactly.", bool)

COMPILE_CACHE_DIR = register(
    "spark.rapids.sql.compile.cacheDir", "",
    "Directory of the persistent kernel store (XLA cache under xla/, "
    "fingerprint index + warm-pool payloads beside it), shared across "
    "processes and restarts.  Empty (the default) derives a per-user "
    "dir keyed by backend platform and host fingerprint "
    "(~/.cache/srt-compile/<platform>-<fp>) — XLA:CPU artifacts embed "
    "machine features, so a checkout moving between hosts must never "
    "share them.  Only consulted when compile.store.enabled.", str)

COMPILE_BUCKET_MIN_ROWS = register(
    "spark.rapids.sql.compile.buckets.minRows", 8,
    "Smallest rung of the shared power-of-two capacity ladder "
    "(compile/buckets.py) every kernel-cache capacity routes through; "
    "rounded up to a power of two.  The default 8 (the f32 sublane "
    "count) is today's floor; raising it collapses small batches onto "
    "one capacity so a fused-stage fingerprint compiles O(log n) "
    "kernels instead of one per observed batch shape.", int, _positive)

COMPILE_BUCKET_MAX_ROWS = register(
    "spark.rapids.sql.compile.buckets.maxRows", 0,
    "Largest ladder rung coalesce row targets snap down to (rounded "
    "up to a power of two; 0 = unbounded, the default).  A single "
    "batch larger than the bound still gets a capacity that holds it "
    "— correctness always wins over the bound.", int, _non_negative)

COMPILE_WARM_ENABLED = register(
    "spark.rapids.sql.compile.warm.enabled", True,
    "AOT warm pool (docs/compile_cache.md): with the store enabled, "
    "session/server start replays the store's top-K recorded (stage "
    "fingerprint, batch signature, bucket) triples through the stage "
    "compiler on a bounded lifecycle-registered srt-compile-* thread, "
    "so a restarted process reaches hot-path latency before the first "
    "query (journal event compile_warm per kernel; warmPoolCompiles "
    "counter).  Inert unless compile.store.enabled.", bool)

COMPILE_WARM_TOP_K = register(
    "spark.rapids.sql.compile.warm.topK", 16,
    "How many of the store's most-executed recorded kernel triples "
    "the startup warm pool replays.", int, _positive)

ADAPTIVE_ENABLED = register(
    "spark.rapids.sql.adaptive.enabled", False,
    "Adaptive query execution (docs/adaptive.md): every in-process "
    "shuffle exchange becomes a stage boundary whose runtime map-output "
    "statistics (per-partition byte/row counts) replan the not-yet-"
    "executed remainder of the plan — partition coalescing, skew-split "
    "joins, and broadcast promotion/demotion replacing the planner's "
    "static autoBroadcastJoinThreshold guess.  The reference plugin "
    "inherits this from Spark 3.0, where it also defaults off; false "
    "reproduces today's static plans byte-for-byte.", bool)

ADAPTIVE_COALESCE_ENABLED = register(
    "spark.rapids.sql.adaptive.coalescePartitions.enabled", True,
    "With adaptive.enabled: merge adjacent undersized reduce partitions "
    "toward advisoryPartitionSizeInBytes so reduce-side dispatch count "
    "tracks observed data, not the static partition count (the Spark "
    "CoalesceShufflePartitions rule).  Only AQE-inserted exchanges "
    "coalesce; explicit repartition(n) counts are a user contract.",
    bool)

ADAPTIVE_ADVISORY_SIZE = register(
    "spark.rapids.sql.adaptive.advisoryPartitionSizeInBytes",
    64 * 1024 * 1024,
    "Target byte size per reduce partition for AQE partition coalescing "
    "and the split target for skewed partitions (the Spark "
    "spark.sql.adaptive.advisoryPartitionSizeInBytes analog).",
    int, _positive)

ADAPTIVE_MIN_PARTITIONS = register(
    "spark.rapids.sql.adaptive.coalescePartitions.minPartitionNum", 1,
    "Lower bound on the reduce-partition count AQE coalescing may merge "
    "down to.", int, _positive)

ADAPTIVE_SKEW_ENABLED = register(
    "spark.rapids.sql.adaptive.skewJoin.enabled", True,
    "With adaptive.enabled: a reduce partition on the stream side of a "
    "join whose measured bytes exceed max(skewedPartitionFactor x "
    "median, skewedPartitionThresholdInBytes) is split into sub-"
    "partitions; the build side streams against each sub-partition "
    "unchanged (the in-process realization of Spark's "
    "OptimizeSkewedJoin build-side replication).", bool)

ADAPTIVE_SKEW_FACTOR = register(
    "spark.rapids.sql.adaptive.skewJoin.skewedPartitionFactor", 5,
    "A partition is skew-split when its bytes exceed this multiple of "
    "the median non-empty partition size (and the absolute threshold "
    "below).", int, _positive)

ADAPTIVE_SKEW_THRESHOLD = register(
    "spark.rapids.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes",
    256 * 1024 * 1024,
    "Absolute floor for skew detection: partitions below this size are "
    "never skew-split regardless of the factor test (the Spark "
    "skewedPartitionThresholdInBytes analog).", int, _positive)

# -- cost-based hybrid placement (docs/placement.md) ------------------------
#
# Default tpu = the placement module never runs: plans, results, and
# metrics are byte-identical to the pre-placement engine (asserted in
# tests/test_placement.py).

PLACEMENT_MODE = register(
    "spark.rapids.sql.placement.mode", "tpu",
    "Fragment placement policy (docs/placement.md).  'tpu' (default): "
    "every fragment the planner can lower to the device engine runs "
    "there — byte-identical to the pre-placement engine.  'cost': each "
    "maximal device-assignable fragment is scored with the measured "
    "cost model — projected TPU cost (H2D bytes over the measured link "
    "bandwidth + fixed pull latency x projected pulls + kernel time "
    "from calibrated per-operator-class throughputs + expected compile "
    "cost, zero on a compile-store hit) against the projected CPU cost "
    "from the calibrated CPU throughputs — and fragments the CPU "
    "engine wins re-lower through the same conversion path as "
    "unsupported-op fallback, with engine-boundary transitions "
    "inserted exactly as today.  'cpu': every fragment runs on the "
    "in-process CPU engine (the A/B baseline).  An injected plan.place "
    "fault degrades to the static all-TPU plan, counted, query "
    "correct.", str, _one_of("tpu", "cost", "cpu"))

PLACEMENT_H2D_MBPS = register(
    "spark.rapids.sql.placement.h2dMBps", 0.0,
    "Host->device link bandwidth (MB/s) the placement cost model "
    "charges fragment ingress with.  0 (default) = measure: the engine "
    "probes the link once per process (plan/cost.py:probe_link — the "
    "probe bench.py used to carry, promoted into the engine so bench "
    "and planner read ONE set of constants).  Set explicitly to pin "
    "placement decisions for tests or known attachments.",
    float, _non_negative)

PLACEMENT_D2H_MBPS = register(
    "spark.rapids.sql.placement.d2hMBps", 0.0,
    "Device->host link bandwidth (MB/s) the placement cost model "
    "charges fragment egress with.  0 (default) = measure via the "
    "one-shot link probe; set explicitly to pin decisions.",
    float, _non_negative)

PLACEMENT_AGG_H2D_MBPS = register(
    "spark.rapids.sql.placement.aggregateH2dMBps", 0.0,
    "AGGREGATE host->device bandwidth (MB/s) across every visible "
    "chip's independent H2D stream — what a sharded scan ingest "
    "(docs/sharded_scan.md) actually moves per second, vs the "
    "single-link h2dMBps.  0 (default) = measure via the multi-chip "
    "probe (plan/cost.py:probe_link_aggregate) when a mesh session "
    "qualifies; set explicitly to pin placement decisions.",
    float, _non_negative)

PLACEMENT_AGG_D2H_MBPS = register(
    "spark.rapids.sql.placement.aggregateD2hMBps", 0.0,
    "AGGREGATE device->host bandwidth (MB/s) across every visible "
    "chip's independent D2H pull — what the per-chip parallel "
    "gather pulls (docs/sharded_scan.md) achieve, vs the "
    "single-link d2hMBps.  0 (default) = measure via the multi-chip "
    "probe; set explicitly to pin placement decisions.",
    float, _non_negative)

PLACEMENT_PULL_LATENCY_MS = register(
    "spark.rapids.sql.placement.pullLatencyMs", -1.0,
    "Fixed latency (ms) per device->host pull the placement cost "
    "model charges — the ~94 ms that makes accelerating a 50 ms query "
    "a planning bug (docs/placement.md).  Negative (default) = "
    "measure via the one-shot link probe; 0 is a legitimate pinned "
    "value (a locally-attached chip).", float)

PLACEMENT_AQE_ENABLED = register(
    "spark.rapids.sql.placement.aqe.enabled", True,
    "With placement.mode=cost and adaptive execution on: after each "
    "query stage materializes, re-score the remaining fragment with "
    "its MEASURED bytes (the shufflePartitionBytes stats) and demote "
    "it to the CPU engine when the static size estimate was wrong.  "
    "Same conf-gated fall-back-to-static contract as the replan "
    "rules: an error or an injected plan.place fault leaves the "
    "static plan running; placementDemotions counts the rewrites.",
    bool)

PLACEMENT_CPU_ROWS_PER_SEC = register(
    "spark.rapids.sql.placement.cpuRowsPerSec", 5_000_000,
    "Prior CPU-engine throughput (rows/sec per operator) the placement "
    "cost model starts from; executed-query profiles blend measured "
    "per-operator-class rates over it (EWMA, persisted beside the "
    "compile store when one is installed — docs/placement.md, "
    "calibration lifecycle).", int, _positive)

PLACEMENT_TPU_ROWS_PER_SEC = register(
    "spark.rapids.sql.placement.tpuRowsPerSec", 50_000_000,
    "Prior device-engine kernel throughput (rows/sec per operator) the "
    "placement cost model starts from; calibrated like cpuRowsPerSec.",
    int, _positive)

SHUFFLE_MODE = register(
    "spark.rapids.shuffle.mode", "host",
    "Shuffle data plane for exchange fragments (docs/ici_shuffle.md). "
    "'host': partition blocks move through host memory — in-process "
    "device gathers for single-process runs, the socket transport for "
    "spark.rapids.shuffle.workers.count > 1 (two crossings of the "
    "host<->device link per exchange).  'ici': when more than one chip "
    "is visible and the stage qualifies, the planner lowers "
    "agg-under-exchange, sort-under-exchange, and shuffled-join "
    "fragments to on-device collectives — the partition kernel "
    "scatters rows into fixed-capacity per-destination buckets moved "
    "with jax.lax.all_to_all inside ONE shard_map program (partition "
    "-> collective -> downstream consumer fused, zero device pulls per "
    "exchange; the reference's device-resident UCX shuffle, PAPER.md "
    "section 7).  Unqualified fragments, multi-process runs, and "
    "single-chip sessions keep the host path automatically; an ICI "
    "failure degrades to the host path per stage (iciFallbacks).",
    str, _one_of("host", "ici"))

SHUFFLE_ICI_DEVICES = register(
    "spark.rapids.shuffle.ici.devices", 0,
    "Width of the device mesh ICI-mode exchanges collectivize over; "
    "0 = every visible chip.  Ignored unless "
    "spark.rapids.shuffle.mode=ici.", int, _non_negative)

SHUFFLE_ICI_MAX_STAGE_BYTES = register(
    "spark.rapids.shuffle.ici.maxStageBytes", 1 << 30,
    "Estimated input bytes above which an exchange fragment stays on "
    "the host path instead of lowering its run onto the mesh (the "
    "over-HBM guard: shard_map exchange buffers replicate each "
    "device's bucket capacity mesh-wide, so a stage several times "
    "larger than HBM must keep the spill-tier host path).  Checked "
    "per stage at execution against the drained input's byte "
    "estimate; exceeding it counts an iciFallback.", int, _positive)

SHUFFLE_ICI_SHARDED_SCAN = register(
    "spark.rapids.shuffle.ici.shardedScan.enabled", False,
    "Sharded scan ingest for ICI-mode exchange fragments "
    "(docs/sharded_scan.md): when a guarded mesh fragment's input "
    "subtree bottoms out in a file scan (optionally under "
    "project/filter/fused-stage/coalesce ops), the planner partitions "
    "the input files (parquet: row groups too) across the healthy "
    "mesh and each shard runs its own bounded prefetch/decode "
    "pipeline feeding a dedicated per-chip H2D upload stream, with "
    "the per-shard operator chain executing on that shard's chip and "
    "the results landing directly as the shard_map exchange "
    "program's device-resident input — no full host drain, no "
    "host-side re-split.  Result collection mirrors it with one "
    "concurrent device_pull per chip.  An ingest failure (fault site "
    "shuffle.ici.ingest) degrades the fragment to the host path "
    "(iciFallbacks).  Default false = the drained-input ingest, "
    "byte-identical plans/results/metrics.", bool)

OOC_ENABLED = register(
    "spark.rapids.sql.ooc.enabled", False,
    "Out-of-core device execution (docs/out_of_core.md): hash join, "
    "hash aggregate, and global sort fragments whose working set "
    "exceeds spark.rapids.shuffle.ici.maxStageBytes execute as "
    "grace-style partitioned operators instead of degrading the whole "
    "fragment to the host path — phase 1 hash-partitions the input "
    "into spill-resident partitions in the encoded domain (dict "
    "codes / RLE / delta planes spill as-is through the three-tier "
    "SpillableBatch path), phase 2 streams partition pairs through "
    "HBM under the existing BufferCatalog budgets with partition i+1 "
    "promoting while partition i computes; sort runs on-device run "
    "generation plus a device K-way merge over promoted run "
    "prefixes.  Default false = byte-identical plans, results, and "
    "metric structure.", bool)

OOC_PARTITIONS = register(
    "spark.rapids.sql.ooc.partitions", 0,
    "Partition count K for the out-of-core grace-partition phase.  "
    "0 = pick K from the measured byte stats: ceil(2 x input bytes / "
    "spark.rapids.shuffle.ici.maxStageBytes), doubled when the AQE "
    "exchange statistics show heavy partition skew (max over median "
    "partition bytes > 4), clamped to [2, 64].", int, _non_negative)

OOC_MAX_RECURSION_DEPTH = register(
    "spark.rapids.sql.ooc.maxRecursionDepth", 2,
    "How many times an out-of-core partition (or partition pair) that "
    "still exceeds the stage budget may recursively re-partition with "
    "a re-salted hash before the operator degrades that partition's "
    "work to the single-chip host path (oocFallbacks counted, query "
    "correct).  Bounds the pathological all-keys-equal input, which "
    "no amount of re-salting can split.", int, _non_negative)

OOC_SORT_MERGE_WIDTH = register(
    "spark.rapids.sql.ooc.sort.mergeWidth", 8,
    "Maximum sorted runs merged per device K-way merge pass of the "
    "out-of-core sort.  More runs than this merge in multiple passes "
    "(each pass merges mergeWidth runs into one new spilled run); the "
    "final pass streams merged output batches directly.  Bounds the "
    "merge window footprint at mergeWidth x the run block size.",
    int, _positive)

SHUFFLE_DEFAULT_NUM_PARTITIONS = register(
    "spark.rapids.shuffle.defaultNumPartitions", 0,
    "Default reduce-partition count for shuffle exchanges that do not "
    "carry an explicit count: the host shuffle's map-output partitioning "
    "(previously hard-coded to workers x 2) and AQE-inserted join "
    "exchanges.  0 preserves the derived defaults (workers x 2 for the "
    "host shuffle; spark.sql.shuffle.partitions for AQE exchanges).",
    int, _non_negative)

MEM_FRACTION = register(
    "spark.rapids.memory.tpu.allocFraction", 0.9,
    "Fraction of chip HBM the arena may use (reference "
    "GpuDeviceManager.scala:152-198 RMM pool fraction).", float, _fraction)

HOST_SPILL_STORAGE_SIZE = register(
    "spark.rapids.memory.host.spillStorageSize", 1024 * 1024 * 1024,
    "Bytes of host memory for the spill store before data goes to disk "
    "(reference RapidsHostMemoryStore.scala:33-67).", int, _positive)

PINNED_POOL_SIZE = register(
    "spark.rapids.memory.pinnedPool.size", 0,
    "Bytes of pre-touched host staging memory (reference PinnedMemoryPool, "
    "GpuDeviceManager.scala:200-206). 0 disables.", int, _non_negative)

MEM_DEBUG = register(
    "spark.rapids.memory.tpu.debug", "NONE",
    "Log device allocations: NONE, STDOUT, STDERR (reference "
    "RapidsConf.scala:227-233).", str, _one_of("NONE", "STDOUT", "STDERR"))

SHUFFLE_TRANSPORT_CLASS = register(
    "spark.rapids.shuffle.transport.class",
    "spark_rapids_tpu.shuffle.transport.LocalShuffleTransport",
    "Fully qualified class of the shuffle transport backend (reference "
    "RapidsConf.scala:505-509 SHUFFLE_TRANSPORT_CLASS_NAME).", str)

SHUFFLE_MAX_METADATA_SIZE = register(
    "spark.rapids.shuffle.maxMetadataSize", 50 * 1024,
    "Pooled metadata message size for the shuffle control plane (reference "
    "RapidsConf SHUFFLE_MAX_METADATA_SIZE).", int, _positive)

SHUFFLE_MAX_INFLIGHT_BYTES = register(
    "spark.rapids.shuffle.maxBytesInFlight", 1024 * 1024 * 1024,
    "Inflight-bytes throttle for shuffle fetches (reference "
    "RapidsShuffleTransport.scala:418-430 queuePending).", int, _positive)

SHUFFLE_BOUNCE_BUFFER_SIZE = register(
    "spark.rapids.shuffle.bounceBuffers.size", 4 * 1024 * 1024,
    "Size of each staging bounce buffer (reference RapidsConf.scala:529-548).",
    int, _positive)

SHUFFLE_BOUNCE_BUFFER_COUNT = register(
    "spark.rapids.shuffle.bounceBuffers.count", 8,
    "Number of staging bounce buffers per direction (reference "
    "RapidsConf.scala:529-548).", int, _positive)

SHUFFLE_COMPRESSION_CODEC = register(
    "spark.rapids.shuffle.compression.codec", "zstd",
    "Codec for serialized shuffle batches: none, lz4, or zstd "
    "(reference ShuffleCommon.fbs CodecType — only UNCOMPRESSED "
    "implemented there).  Frames are self-describing (SRTZ magic), so "
    "mixed-codec fleets interoperate; codecs whose library is absent "
    "(lz4 in this image) degrade to the best available one.",
    str, _one_of("none", "lz4", "zstd"))

SHUFFLE_CONNECT_TIMEOUT = register(
    "spark.rapids.shuffle.timeout.connect", 5.0,
    "Seconds a shuffle client waits for a TCP connect to a peer block "
    "server before failing the attempt (retried with backoff).  Without "
    "it a dead peer hangs fetches forever (reference: UCX connection "
    "management timeouts, UCX.scala).", float, _positive)

SHUFFLE_READ_TIMEOUT = register(
    "spark.rapids.shuffle.timeout.read", 30.0,
    "Seconds a shuffle client (and a server mid-frame) waits for the "
    "next bytes of a response before treating the peer as dead.  Bounds "
    "every receive loop in the transport.", float, _positive)

SHUFFLE_FETCH_RETRIES = register(
    "spark.rapids.shuffle.fetch.retries", 3,
    "Transient-failure retries per peer operation before the fetch "
    "surfaces as a FetchFailedError and the stage reroutes to map "
    "recompute (reference RapidsShuffleIterator.scala:170-240).",
    int, _non_negative)

SHUFFLE_RETRY_BACKOFF_BASE = register(
    "spark.rapids.shuffle.retry.backoff.base", 0.05,
    "Base delay in seconds for exponential backoff between peer retry "
    "attempts (attempt k sleeps ~base * 2^k, capped and jittered).",
    float, _positive)

SHUFFLE_RETRY_BACKOFF_CAP = register(
    "spark.rapids.shuffle.retry.backoff.cap", 2.0,
    "Upper bound in seconds on any single retry backoff delay.",
    float, _positive)

SHUFFLE_RETRY_BACKOFF_JITTER = register(
    "spark.rapids.shuffle.retry.backoff.jitter", 0.2,
    "Jitter fraction for retry backoff: each delay is scaled by a "
    "uniform factor in [1 - jitter, 1], decorrelating peers that fail "
    "simultaneously so a recovering server is not hammered in lockstep.",
    float, _fraction_inclusive)

SHUFFLE_CHECKSUM = register(
    "spark.rapids.shuffle.checksum", "crc32c",
    "Checksum algorithm stamped on serialized shuffle blocks and "
    "verified at deserialize: crc32c (Castagnoli, via google-crc32c), "
    "crc32 (zlib), or off.  A mismatch raises BlockCorruptError and the "
    "manager refetches the block instead of returning wrong rows.  "
    "Frames are self-describing, so mixed settings interoperate.",
    str, _one_of("crc32c", "crc32", "off"))

SHUFFLE_CORRUPT_REFETCHES = register(
    "spark.rapids.shuffle.corrupt.refetches", 2,
    "How many times a reduce fetch whose payload failed checksum or "
    "decode is refetched before surfacing FetchFailedError.  Counted "
    "separately from transient-connection retries in manager stats.",
    int, _non_negative)

SHUFFLE_PEER_MAX_FAILURES = register(
    "spark.rapids.shuffle.peer.maxFailures", 3,
    "Consecutive exhausted-retry failures against one peer before it is "
    "blacklisted: further fetches to it fail fast with FetchFailedError "
    "so the stage reroutes to the map-recompute path instead of burning "
    "full retry cycles per partition.", int, _positive)

SHUFFLE_RECOMPUTE_ENABLED = register(
    "spark.rapids.shuffle.recompute.enabled", True,
    "When a reduce fetch fails permanently (dead/blacklisted peer, "
    "unrecoverable corruption), re-run the owning map work from its "
    "source input instead of aborting the query (the FetchFailed -> "
    "map-stage-recompute contract Spark guarantees; reference "
    "RapidsShuffleIterator surfacing FetchFailedException).", bool)

SHUFFLE_STAGE_TIMEOUT = register(
    "spark.rapids.shuffle.stage.timeout", 3600.0,
    "Seconds the host shuffle driver waits for the map stage before "
    "failing the exchange.", float, _positive)

WORKER_HEARTBEAT_INTERVAL = register(
    "spark.rapids.shuffle.worker.heartbeat.interval", 0.5,
    "Seconds between heartbeats a shuffle worker process sends the "
    "driver.", float, _positive)

WORKER_HEARTBEAT_TIMEOUT = register(
    "spark.rapids.shuffle.worker.heartbeat.timeout", 20.0,
    "Seconds without a heartbeat (with the process still alive) before "
    "the driver declares a worker hung, terminates it, and reassigns "
    "its stripe to survivors.", float, _positive)

FAULTS_SEED = register(
    "spark.rapids.faults.seed", 0,
    "Seed for probabilistic fault-injection triggers "
    "(spark.rapids.faults.<site> = prob:p).  Site trigger specs are "
    "documented in docs/fault_tolerance.md; count-based triggers do not "
    "use the seed.", int)

HOST_SHUFFLE_WORKERS = register(
    "spark.rapids.shuffle.workers.count", 0,
    "Number of OS worker processes the host shuffle spreads map-side "
    "work (scan, below-exchange expressions, hash partitioning) across; "
    "0/1 = in-process execution.  Map fragments exchange partition "
    "blocks through the TpuShuffleManager transport; the reduce side "
    "runs where the chip lives (reference "
    "RapidsShuffleInternalManager.scala:90-138).", int)

MULTITHREADED_SHUFFLE_THREADS = register(
    "spark.rapids.shuffle.multiThreaded.threads", 4,
    "Executor threads used by the shuffle transport for copy/serialize work "
    "(reference UCXShuffleTransport exec/copy executors).", int, _positive)

MESH_DEVICES = register(
    "spark.rapids.sql.mesh.devices", 0,
    "Width of the 1-D device mesh query operators lower onto: N > 1 "
    "rewrites grouped aggregates, global sorts, and equi-joins to SPMD "
    "shard_map pipelines that exchange rows over ICI with all_to_all "
    "(parallel/distagg.py, distjoin.py, distsort.py). 0/1 = single "
    "device. The analog of the reference distributing these operators "
    "across executors via GpuShuffleExchangeExec "
    "(GpuShuffleExchangeExec.scala:60-244).", int, _non_negative)

COMPRESSED_ENABLED = register(
    "spark.rapids.sql.compressed.enabled", True,
    "Master switch for compressed-domain execution (docs/compressed.md): "
    "dictionary-encoded string planes cross the host->device link as "
    "codes (parquet's own dictionary pages via read_dictionary; a "
    "host-side dictionary build for ORC/CSV/local data), fused stage "
    "kernels rewrite predicates/projections over encoded columns to "
    "per-code gathers against dictionary-evaluated tables, group-by "
    "keys group by code (rank codes keep output order identical), "
    "equi-join keys compare as codes (re-keying one side across "
    "disjoint dictionaries), and egress/spill carry codes instead of "
    "dense char matrices.  false = no column is ever encoded; plans, "
    "kernels, metrics, and results are byte-identical to the dense "
    "engine.", bool)

COMPRESSED_INGEST = register(
    "spark.rapids.sql.compressed.ingest", True,
    "With compressed.enabled: upload dictionary-encoded string planes "
    "(codes + a small dictionary) instead of dense char matrices at "
    "every scan and host->device transition.  An injected io.encode "
    "fault (docs/fault_tolerance.md) degrades the column to the plain "
    "plane path, counted, query correct.  false = every column rides "
    "the plain plane path (and no compressed-domain kernel ever "
    "engages, since only ingest creates encoded columns).", bool)

COMPRESSED_EGRESS = register(
    "spark.rapids.sql.compressed.egress", True,
    "With compressed.enabled: device->host egress (result pulls, "
    "single-pull partition exchanges, spill demotion) keeps encoded "
    "columns in the code domain — the ~94 ms pull carries int codes "
    "plus nothing (the dictionary values are already host-resident "
    "from ingest), and the host unpack rebuilds exact string values "
    "from the host dictionary.  false = encoded columns decode on "
    "device before crossing (byte-identical results, dense wire).",
    bool)

COMPRESSED_MAX_DICT_FRACTION = register(
    "spark.rapids.sql.compressed.maxDictFraction", 0.5,
    "Encode a string column only when its distinct-value count is at "
    "most this fraction of the batch's rows: past it the dictionary "
    "planes stop paying for the codes indirection and the column rides "
    "the plain path (the `plain` passthrough encoding).", float,
    _fraction)

COMPRESSED_MAX_COMPOSED_CELLS = register(
    "spark.rapids.sql.compressed.maxComposedCells", 65536,
    "Upper bound on the composed-table size for MULTI-column "
    "dictionary rewrites: a deterministic subtree over two encoded "
    "columns evaluates once per (code1, code2) pair — "
    "(size1+1)*(size2+1) cells including the null slots — and becomes "
    "one combined-code gather in the fused stage.  Pairs past this "
    "bound keep the per-column rewrite (each column still gathers "
    "independently); 0 disables composed rewrites entirely.", int,
    _non_negative)

COMPRESSED_RLE = register(
    "spark.rapids.sql.compressed.rle.enabled", True,
    "With compressed.ingest: upload run-length-encoded integer planes "
    "(run values + cumulative run ends) when the run structure wins "
    "the wire — sorted/clustered scan columns cross the link as a few "
    "runs instead of a dense vector, and fused stage kernels decode "
    "in-kernel (a searchsorted gather, counted fusedDecodes).  An "
    "injected io.encode fault degrades the column to the plain plane "
    "path, counted, query correct.  false = integer columns never "
    "ride RLE (plain planes, byte-identical results).", bool)

COMPRESSED_DELTA = register(
    "spark.rapids.sql.compressed.delta.enabled", True,
    "With compressed.ingest: upload delta-narrowed integer planes "
    "(base + int8/int16 row deltas) when every consecutive delta fits "
    "the narrow store — monotonic ids and near-sorted keys cross the "
    "link at 1-2 bytes/row, and fused stage kernels decode in-kernel "
    "(a cumsum, counted fusedDecodes).  Columns with nulls or wide "
    "deltas ride plain.  false = never delta-encode (byte-identical "
    "results).", bool)

COMPRESSED_PACKED_BOOL = register(
    "spark.rapids.sql.compressed.packedBool.enabled", True,
    "With compressed.ingest: upload boolean columns bit-packed (8 "
    "rows/byte) and unpack in-kernel inside the consuming fused stage "
    "(counted fusedDecodes) — the compute-plane counterpart of the "
    "egress validity bitpack.  false = booleans ride dense uint8 "
    "planes (byte-identical results).", bool)

TRANSFER_PACK_ENABLED = register(
    "spark.rapids.sql.transfer.pack.enabled", True,
    "Pack result batches on device (concat + row-bucket trim + validity "
    "bitpack + lossless integer delta-narrowing) and pull them in one "
    "link round trip — the TPU-side analog of the reference compressing "
    "tables before they cross PCIe (TableCompressionCodec.scala); "
    "essential on remote-attached chips where each device->host pull "
    "pays ~100ms of link latency.", bool)

TRANSFER_STATS_THRESHOLD = register(
    "spark.rapids.sql.transfer.statsThresholdBytes", 1 << 20,
    "Result sizes above this spend one extra tiny pull on device-side "
    "(count,min,max,maxlen) stats to shrink the big data pull via "
    "integer narrowing and string-width trimming; below it a single "
    "round trip pulls counts together with the data.", int, _positive)

SCAN_DEVICE_CACHE = register(
    "spark.rapids.sql.scan.deviceCacheEnabled", True,
    "Keep decoded+uploaded scan tables on device across queries, keyed "
    "by (paths, mtimes, schema, batching), managed by the spill catalog "
    "so memory pressure demotes them tier-by-tier. The TPU analog of the "
    "reference keeping hot tables in GPU memory across the query "
    "pipeline instead of re-reading Parquet per query.", bool)

EXPORT_COLUMNAR_RDD = register(
    "spark.rapids.sql.exportColumnarRdd", False,
    "Tag the final plan so the internal columnar stream can be exported "
    "zero-copy for ML handoff (reference RapidsConf; "
    "InternalColumnarRddConverter.scala:470-579).", bool)

HOST_SPILL_STORAGE_SIZE = register(
    "spark.rapids.memory.host.spillStorageSize", 1 << 30,
    "Bytes of host memory holding spilled device buffers before they "
    "demote to disk (reference RapidsConf spillStorageSize / "
    "RapidsBufferStore.scala host tier).", int)

TPU_BUDGET_OVERRIDE = register(
    "spark.rapids.memory.tpu.budgetBytes", 0,
    "Explicit device-memory budget for the spill catalog in bytes; 0 "
    "derives it from device HBM x spark.rapids.memory.tpu.allocFraction "
    "(test hook mirroring the reference's pool-size overrides).", int)

STABLE_SORT = register(
    "spark.rapids.sql.stableSort.enabled", True,
    "Use stable device sort (Spark sort is not required to be stable but the "
    "compare harness prefers determinism).", bool)

PARQUET_DEBUG_DUMP_PREFIX = register(
    "spark.rapids.sql.parquet.debug.dumpPrefix", "",
    "If set, readers dump each reassembled split to <prefix>-<n>.parquet "
    "(reference RapidsConf.scala:471-481).", str)

ENABLE_PARQUET = register(
    "spark.rapids.sql.format.parquet.enabled", True,
    "Enable TPU parquet read/write (reference RapidsConf format enables).", bool)
PARQUET_FILTER_PUSHDOWN = register(
    "spark.rapids.sql.format.parquet.filterPushdown.enabled", True,
    "Push Filter predicates above a parquet scan into the scan so row "
    "groups are pruned by footer min/max statistics (reference "
    "GpuParquetScan.scala:316-458).", bool)
ENABLE_ORC = register(
    "spark.rapids.sql.format.orc.enabled", True,
    "Enable TPU ORC read/write.", bool)
ENABLE_CSV = register(
    "spark.rapids.sql.format.csv.enabled", True,
    "Enable TPU CSV read.", bool)

SHUFFLE_PARTITIONS = register(
    "spark.sql.shuffle.partitions", 8,
    "Number of partitions for shuffle exchanges (Spark core conf honored by "
    "the planner).", int, _positive)

BROADCAST_THRESHOLD = register(
    "spark.sql.autoBroadcastJoinThreshold", 10 * 1024 * 1024,
    "Max estimated byte size of a join side to broadcast it (Spark core conf "
    "honored by join planning). -1 disables broadcast.", int)

METRICS_ENABLED = register(
    "spark.rapids.sql.metrics.enabled", True,
    "Collect per-operator SQL metrics (reference GpuExec.scala:25-67).", bool)

# the obs keys configure PROCESS-GLOBAL state (the histogram switch,
# the journal); query_scope applies each setting only when ITS key is
# explicitly present in a conf, so a session that doesn't mention a
# setting can never clobber another session's observability mid-flight
# (the per-key analog of faults.FAULTS_PREFIX)
OBS_PREFIX = "spark.rapids.sql.obs."

OBS_ENABLED = register(
    "spark.rapids.sql.obs.enabled", True,
    "Engine observability recording (docs/observability.md): the log2 "
    "latency/size histograms behind session.engine_stats() and the "
    "python -m spark_rapids_tpu.obs exporter (D2H/H2D transfer latency "
    "and bytes, chip-semaphore and staging-limiter admission waits, "
    "XLA compile time, per-query wall time).  Recording costs one "
    "bit_length and three increments at sites that already pay a link "
    "round trip or a lock; false reduces every record to a single flag "
    "check.  Plan output and per-operator SQL metrics are identical "
    "either way.", bool)

OBS_JOURNAL_DIR = register(
    "spark.rapids.sql.obs.journalDir", "",
    "When set, the engine appends a structured JSONL event journal to "
    "<dir>/events-<pid>.jsonl: typed query lifecycle events (start/"
    "finish/cancel/timeout/error), AQE replan decisions with before/"
    "after partition specs, ICI host-path fallbacks with reasons, "
    "fault-injection fires, spill demotions/promotions, and watchdog "
    "trips — one line per event with wall + monotonic timestamps and "
    "the owning query id (docs/observability.md carries the event "
    "schema table).  Empty (the default) disables the journal "
    "entirely.", str)

OBS_JOURNAL_MAX_EVENTS = register(
    "spark.rapids.sql.obs.journal.maxEvents", 100_000,
    "Per-process cap on journal events written under "
    "spark.rapids.sql.obs.journalDir; past it further events are "
    "counted as dropped (visible in engine_stats) instead of written, "
    "so an event storm (a chaos soak, a fault loop) cannot fill the "
    "disk.", int, _positive)

TRACE_ENABLED = register(
    "spark.rapids.sql.trace.enabled", False,
    "Wrap operator hot loops in jax.profiler ranges (reference NVTX ranges, "
    "NvtxWithMetrics.scala:27).", bool)

TRACE_DIR = register(
    "spark.rapids.sql.trace.dir", "",
    "When set (and trace.enabled), each collect() runs under "
    "jax.profiler.trace writing an Xprof capture to this directory "
    "(the Nsight-session analog of the reference's NVTX ranges).", str)

POOLED_ALLOCATOR = register(
    "spark.rapids.memory.tpu.pooling.enabled", True,
    "Use the native arena suballocator for host staging buffers (reference "
    "RMM pooling GpuDeviceManager.scala:152-198).", bool)

# -- multi-tenant session server (docs/serving.md) --------------------------
#
# None of these keys is consulted on the single-query session.sql()
# path: with them unset (and no SessionServer constructed) execution is
# byte-identical to the serverless engine.  Per-tenant overrides ride
# as raw keys (`spark.rapids.server.tenant.<name>.weight` /
# `.timeoutMs` / `.maxDeviceBytes`), documented in docs/serving.md.

SERVER_ENABLED = register(
    "spark.rapids.server.enabled", False,
    "Multi-tenant session server switch (docs/serving.md): "
    "session.server() starts a worker pool accepting N concurrent "
    "queries through a weighted-fair bounded admission queue in front "
    "of the chip semaphore, with per-tenant deadline defaults, "
    "per-query device-memory budgets, prepared statements, and the "
    "plan-fingerprint result cache.  Calling session.server() is "
    "itself the opt-in; EXPLICITLY setting this key false makes "
    "session.server() refuse (an operator kill switch).  Unset, no "
    "serving code runs unless server() is called.", bool)

SERVER_MAX_CONCURRENCY = register(
    "spark.rapids.server.maxConcurrency", 0,
    "Worker threads executing admitted queries concurrently (each "
    "still passes the chip semaphore for device sections).  0 derives "
    "2 x spark.rapids.tpu.concurrentTasks — enough in-flight queries "
    "to keep the chip busy while others decode or pull results.",
    int, _non_negative)

SERVER_QUEUE_DEPTH = register(
    "spark.rapids.server.admission.queueDepth", 64,
    "Bound on queries waiting in the fair admission queue (in-flight "
    "queries do not count).  A submit past the bound is shed with a "
    "typed AdmissionRejectedError instead of growing an unbounded "
    "backlog — the overload contract a serving tier needs "
    "(docs/serving.md).", int, _positive)

SERVER_DEFAULT_WEIGHT = register(
    "spark.rapids.server.admission.defaultWeight", 1,
    "Fair-share weight of a tenant with no explicit "
    "spark.rapids.server.tenant.<name>.weight: the scheduler dequeues "
    "proportionally to weight (stride scheduling), so one heavy tenant "
    "cannot starve interactive tenants no matter how deep its backlog.",
    int, _positive)

SERVER_TENANT_TIMEOUT_MS = register(
    "spark.rapids.server.tenant.defaultTimeoutMs", 0,
    "Per-tenant query deadline default in milliseconds, flowing into "
    "each admitted query's QueryContext exactly like "
    "spark.rapids.sql.queryTimeoutMs (which it overrides when > 0 and "
    "no per-tenant spark.rapids.server.tenant.<name>.timeoutMs is "
    "set).  0 defers to the session-wide key.", int, _non_negative)

SERVER_QUERY_MAX_DEVICE_BYTES = register(
    "spark.rapids.server.query.maxDeviceBytes", 0,
    "Device-resident byte budget per query, enforced through the "
    "spill catalog: a query whose registered device-tier bytes exceed "
    "the budget first spills ITS OWN working set to host, and if that "
    "cannot satisfy the budget the query is cancelled with a typed "
    "QueryBudgetExceededError — it can never OOM its neighbors "
    "(docs/serving.md).  0 disables per-query budgets.",
    int, _non_negative)

SERVER_RESULT_CACHE = register(
    "spark.rapids.server.resultCache.enabled", True,
    "Result cache for server-submitted queries, keyed on (plan "
    "fingerprint over hoisted literals, input snapshot fingerprint "
    "(file path+mtime+size), prepared-statement bindings).  A scanned "
    "file changing its mtime or size changes the key, so stale entries "
    "can never hit; LRU-bounded with hit/miss/evict counters "
    "(docs/serving.md).  Only consulted on the SessionServer path.",
    bool)

SERVER_RESULT_CACHE_ENTRIES = register(
    "spark.rapids.server.resultCache.maxEntries", 64,
    "Entry bound of the server result cache.", int, _positive)

SERVER_RESULT_CACHE_BYTES = register(
    "spark.rapids.server.resultCache.maxBytes", 256 * 1024 * 1024,
    "Byte bound of the server result cache (Arrow result sizes); "
    "least-recently-used entries evict past either bound.",
    int, _positive)

SERVER_RETRY_MAX_ATTEMPTS = register(
    "spark.rapids.server.retry.maxAttempts", 2,
    "Total execution attempts per server-submitted query when a "
    "chip-attributed ChipFailedError kills it mid-flight (the chip "
    "failure domain, docs/fault_tolerance.md): 2 = the query replays "
    "once against the re-formed mesh, 1 = no replay.  Replay engages "
    "only with spark.rapids.health.enabled, only when the failed "
    "attempt surfaced no results (checked through the PlanResult "
    "seam), and only inside the per-tenant replay budget.",
    int, _positive)

SERVER_RETRY_BUDGET_PER_MIN = register(
    "spark.rapids.server.retry.budgetPerMin", 10,
    "Per-tenant budget of chip-failure replays per rolling minute; a "
    "replay past the budget is shed typed with "
    "RetryBudgetExhaustedError (an AdmissionRejectedError — the same "
    "retry-with-backoff contract as overload shedding, "
    "docs/serving.md) so a persistently failing mesh cannot double "
    "every tenant's load.", int, _non_negative)

# per-tenant override keys are raw (tenant names are user data, not
# registry entries): spark.rapids.server.tenant.<name>.weight /
# .timeoutMs / .maxDeviceBytes — read via TpuConf.get_raw by the
# session server (docs/serving.md)
SERVER_TENANT_PREFIX = "spark.rapids.server.tenant."

# -- chip failure domain (docs/fault_tolerance.md, "Chip failure domain") ---
#
# All off by default: with spark.rapids.health.enabled unset/false no
# health code runs on any query path — plans, metrics, and results are
# byte-identical to the health-less engine (asserted in
# tests/test_health.py).

HEALTH_PREFIX = "spark.rapids.health."

HEALTH_ENABLED = register(
    "spark.rapids.health.enabled", False,
    "Chip failure domain (docs/fault_tolerance.md): every guarded ICI "
    "collective outcome feeds a per-chip EWMA health score; a chip "
    "crossing the quarantine threshold is removed from the mesh device "
    "set and the admission pool (TpuSemaphore capacity scales with the "
    "surviving chips), future exchange fragments re-lower onto the "
    "surviving power-of-two mesh width (8->4->2->1), and a quarantined "
    "chip re-enters on probation after spark.rapids.health.probationMs "
    "with a probe on re-entry.  Chip-attributed failures (the "
    "chip.fail fault site) fail the query typed (ChipFailedError) for "
    "the server's bounded replay instead of silently degrading every "
    "fragment to the host path.  false = no health code runs; "
    "byte-identical plans and results.", bool)

HEALTH_SCORE_ALPHA = register(
    "spark.rapids.health.scoreAlpha", 0.35,
    "EWMA weight of the newest per-chip collective outcome: score' = "
    "alpha*outcome + (1-alpha)*score, outcome 1.0 for a clean "
    "collective, 0.25 for a chip.slow mark, 0.0 for a chip-attributed "
    "failure (mesh-wide failures spread blame: alpha/width).  Larger "
    "alpha reacts faster; smaller alpha needs a longer failure streak "
    "before quarantine.", float, _fraction)

HEALTH_QUARANTINE_THRESHOLD = register(
    "spark.rapids.health.quarantineThreshold", 0.4,
    "Health score below which a chip is quarantined: removed from the "
    "mesh device set (future fragments re-lower onto the surviving "
    "power-of-two width) and the admission pool until probation "
    "re-admission.  With the default scoreAlpha 0.35 a chip starting "
    "healthy quarantines after 3 consecutive attributed failures.",
    float, _fraction)

HEALTH_PROBATION_MS = register(
    "spark.rapids.health.probationMs", 30000,
    "Quarantine duration before a chip becomes eligible for probation "
    "re-admission: at the next query's mesh formation the chip is "
    "probed (a tiny device program; an injected chip.fail fails the "
    "probe) — a passing probe re-admits it ON PROBATION (one failed "
    "collective re-quarantines immediately with a fresh window; one "
    "clean collective restores full membership), a failing probe "
    "restarts the window.", int, _positive)


# -- serving fleet (docs/serving.md, "Serving fleet") -----------------------
#
# All off by default: with spark.rapids.fleet.* unset no fleet code
# runs — session.fleet() refuses, no replica processes spawn, and the
# single-process serving plane is byte-identical to the fleet-less
# engine (asserted in tests/test_fleet.py).

FLEET_PREFIX = "spark.rapids.fleet."

FLEET_REPLICAS = register(
    "spark.rapids.fleet.replicas", 0,
    "Number of SessionServer replica processes the fleet router "
    "(session.fleet(), docs/serving.md \"Serving fleet\") spawns, each "
    "its own OS process and failure domain: a replica dying takes only "
    "its in-flight queries, which fail over typed to the survivors.  "
    "0 (the default) = no fleet: session.fleet() refuses and no fleet "
    "code runs.", int, _non_negative)

FLEET_QUEUE_DEPTH = register(
    "spark.rapids.fleet.routing.queueDepth", 16,
    "Per-replica bound on router-dispatched in-flight queries.  The "
    "stride router overflows a full replica's traffic onto the other "
    "healthy replicas first; only when EVERY healthy replica is at its "
    "bound is the query shed typed (AdmissionRejectedError) — "
    "cross-replica overflow before any shed.", int, _positive)

FLEET_HEARTBEAT_INTERVAL_MS = register(
    "spark.rapids.fleet.heartbeat.intervalMs", 200,
    "How often each replica's srt-fleet-beat thread ships a heartbeat "
    "(carrying its own chip-failure-domain health snapshot) to the "
    "router.", int, _positive)

FLEET_HEARTBEAT_TIMEOUT_MS = register(
    "spark.rapids.fleet.heartbeat.timeoutMs", 10000,
    "Heartbeat silence after which the router treats a live-looking "
    "replica process as dead (terminate-before-declare, the shuffle "
    "worker watchdog contract): its in-flight queries fail over and it "
    "stops taking traffic.  A reaped exit code declares death "
    "immediately, without waiting out this window.", int, _positive)

FLEET_HEALTH_SCORE_ALPHA = register(
    "spark.rapids.fleet.health.scoreAlpha", 0.5,
    "EWMA weight of the newest per-replica outcome in the fleet health "
    "rollup: score' = alpha*outcome + (1-alpha)*score, outcome 1.0 for "
    "a clean response or heartbeat, 0.25 for a slow mark (replica.slow "
    "or a heartbeat reporting quarantined chips), 0.0 for a "
    "replica-attributed failure.", float, _fraction)

FLEET_HEALTH_QUARANTINE_THRESHOLD = register(
    "spark.rapids.fleet.health.quarantineThreshold", 0.4,
    "Fleet health score below which a replica is quarantined exactly "
    "like a chip (docs/fault_tolerance.md): routed around, probed "
    "after probationMs, re-admitted on probation.", float, _fraction)

FLEET_HEALTH_PROBATION_MS = register(
    "spark.rapids.fleet.health.probationMs", 2000,
    "Quarantine duration before a quarantined replica becomes eligible "
    "for probation re-admission: the router sends it a probe query — a "
    "passing probe re-admits it ON PROBATION (one failure "
    "re-quarantines immediately; one clean response restores full "
    "membership), a failing probe restarts the window.",
    int, _positive)

FLEET_RETRY_MAX_ATTEMPTS = register(
    "spark.rapids.fleet.retry.maxAttempts", 2,
    "Total dispatch attempts per fleet-routed query when the replica "
    "holding it dies or is quarantined mid-flight: 2 = the query "
    "replays once on a healthy replica, 1 = no failover.  Failover "
    "engages only when the dead attempt surfaced no results and only "
    "inside the per-tenant replay budget; otherwise the query fails "
    "typed (ReplicaFailedError).", int, _positive)

FLEET_RETRY_BUDGET_PER_MIN = register(
    "spark.rapids.fleet.retry.budgetPerMin", 10,
    "Per-tenant budget of replica-failover replays per rolling minute "
    "(the PR 10 chip-replay budget promoted to the replica domain); a "
    "failover past the budget is shed typed with "
    "RetryBudgetExhaustedError so a crash-looping replica cannot "
    "double every tenant's load.", int, _non_negative)

FLEET_STARTUP_TIMEOUT_MS = register(
    "spark.rapids.fleet.startupTimeoutMs", 180000,
    "Bound on one replica process reaching ready (spawn + engine "
    "import + SessionServer up + probe query passed).  A replica "
    "missing the bound is terminated and fleet construction or "
    "rolling_restart fails typed.", int, _positive)

FLEET_RESULT_CACHE_DIR = register(
    "spark.rapids.fleet.resultCache.dir", "",
    "Directory of the fleet-wide on-disk result-cache tier every "
    "replica's ResultCache spills through (docs/serving.md \"Serving "
    "fleet\").  Entries are keyed on plan+snapshot+conf fingerprints, "
    "so they are valid fleet-wide by construction; only file-backed "
    "snapshots spill (in-memory relations key on object identity, "
    "which does not survive a process boundary).  Every disk failure "
    "(corrupt payload, bad checksum, full disk) degrades to a counted "
    "miss — the compile store's corrupt-entry matrix.  Empty (the "
    "default) = no disk tier.", str)

FLEET_RESULT_CACHE_MAX_BYTES = register(
    "spark.rapids.fleet.resultCache.maxBytes", 256 * 1024 * 1024,
    "Byte bound on the fleet-wide disk result tier; oldest entries are "
    "evicted first when an insert would exceed it.", int, _positive)

STREAM_ENABLED = register(
    "spark.rapids.stream.enabled", False,
    "Continuous-query subsystem switch (docs/streaming.md): the "
    "session server gains tailing sources (a poller diffing registered "
    "parquet/ORC/CSV directories into append micro-batches), standing "
    "queries with a register/retire lifecycle refreshed incrementally "
    "through the partial-aggregate merge path, and append-only "
    "maintenance of result-cache entries.  Default false = no poller "
    "thread, no standing-query registry, plans/results/metric "
    "structure byte-identical to the non-streaming engine.", bool)

STREAM_POLL_INTERVAL_MS = register(
    "spark.rapids.stream.pollIntervalMs", 1000,
    "Milliseconds between tailing-source polls.  Each tick stats the "
    "registered directories, diffs the file set against the committed "
    "snapshot (new files + grown files, the snapshot-fingerprint "
    "token grammar incl. the parquet tail marker), and refreshes the "
    "standing queries bound to sources that produced a micro-batch.",
    int, _positive)

STREAM_MAX_FILES_PER_TICK = register(
    "spark.rapids.stream.maxFilesPerTick", 64,
    "Bound on NEW files one micro-batch may carry; a backlog larger "
    "than the bound drains across consecutive ticks (oldest first) so "
    "one bulk load cannot turn a refresh into an unbounded scan.  "
    "Grown files are always fully drained (their delta is the grown "
    "tail, already bounded by what arrived).", int, _positive)

STREAM_INCREMENTAL = register(
    "spark.rapids.stream.incremental.enabled", True,
    "Incremental refresh of standing queries (docs/streaming.md): "
    "plans the rewriter can incrementalize (Count/Sum/Min/Max/Average "
    "group-bys and append-mode project/filter/stream-table-join "
    "chains over one tailed leaf) fold each micro-batch through the "
    "partial-aggregate merge path instead of recomputing; evolving "
    "string dictionaries unify through the sorted-union translate.  "
    "False forces every refresh to a full recompute (counted), "
    "results identical.", bool)

STREAM_CACHE_MAINTAIN = register(
    "spark.rapids.stream.cache.maintain", False,
    "Maintain server result-cache entries whose snapshot diff is "
    "append-only NEW FILES on exactly one scanned leaf: the delta is "
    "computed incrementally and merged into the cached result instead "
    "of invalidating it (docs/streaming.md, \"Maintenance vs "
    "invalidate\").  Any other change — rewritten, shrunk, or grown "
    "files, multiple changed leaves, a non-incrementalizable plan — "
    "falls back to the normal miss+recompute, counted.  Requires "
    "spark.rapids.stream.enabled.", bool)

STREAM_REFRESH_TIMEOUT_MS = register(
    "spark.rapids.stream.refreshTimeoutMs", 60000,
    "Bound on one standing-query refresh (the ticket wait, on top of "
    "the per-tenant query deadline that supervises each refresh's "
    "QueryContext).  A refresh missing the bound is counted a refresh "
    "error and the query falls back to a full recompute on the next "
    "tick — freshness degrades, correctness does not.", int, _positive)


class TpuConf:
    """Immutable snapshot of settings with typed accessors (reference
    RapidsConf RapidsConf.scala:699-832)."""

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self._settings: Dict[str, Any] = dict(settings or {})

    def get(self, entry: ConfEntry) -> Any:
        raw = self._settings.get(entry.key, entry.default)
        value = entry.convert(raw)
        entry.validate(value)
        return value

    def get_raw(self, key: str, default: Any = None) -> Any:
        return self._settings.get(key, default)

    def set(self, key: str, value: Any) -> "TpuConf":
        new = dict(self._settings)
        new[key] = value
        return TpuConf(new)

    def with_settings(self, settings: Dict[str, Any]) -> "TpuConf":
        new = dict(self._settings)
        new.update(settings)
        return TpuConf(new)

    def to_dict(self) -> Dict[str, Any]:
        return dict(self._settings)

    # -- typed accessors (the handful used on hot paths) --------------------
    @property
    def sql_enabled(self) -> bool: return self.get(SQL_ENABLED)
    @property
    def test_enabled(self) -> bool: return self.get(TEST_ENABLED)
    @property
    def test_allowed_non_tpu(self) -> List[str]:
        raw = self.get(TEST_ALLOWED_NON_TPU)
        return [s.strip() for s in raw.split(",") if s.strip()]
    @property
    def incompatible_ops_enabled(self) -> bool: return self.get(INCOMPATIBLE_OPS)
    @property
    def explain(self) -> str: return self.get(EXPLAIN)
    @property
    def batch_size_rows(self) -> int: return self.get(BATCH_SIZE_ROWS)
    @property
    def batch_size_bytes(self) -> int: return self.get(BATCH_SIZE_BYTES)
    @property
    def reader_batch_size_rows(self) -> int: return self.get(MAX_READER_BATCH_SIZE_ROWS)
    @property
    def reader_batch_size_bytes(self) -> int: return self.get(MAX_READER_BATCH_SIZE_BYTES)
    @property
    def max_string_width(self) -> int: return self.get(MAX_STRING_WIDTH)
    @property
    def range_sample_size(self) -> int: return self.get(RANGE_SAMPLE_SIZE)
    @property
    def concurrent_tpu_tasks(self) -> int:
        # legacy key wins when explicitly positive; otherwise the counted
        # spark.rapids.tpu.concurrentTasks admission (default 2)
        legacy = self.get(CONCURRENT_TPU_TASKS)
        return legacy if legacy > 0 else self.get(TPU_CONCURRENT_TASKS)
    @property
    def fusion_enabled(self) -> bool:
        return self.get(FUSION_ENABLED)
    @property
    def fusion_max_ops(self) -> int:
        return self.get(FUSION_MAX_OPS)
    @property
    def fusion_literal_hoisting(self) -> bool:
        return self.get(FUSION_LITERAL_HOISTING)
    @property
    def fusion_warmer_enabled(self) -> bool:
        return self.get(FUSION_WARMER_ENABLED)
    @property
    def compile_store_enabled(self) -> bool:
        return self.get(COMPILE_STORE_ENABLED)
    @property
    def compile_cache_dir(self) -> str:
        return self.get(COMPILE_CACHE_DIR)
    @property
    def compile_warm_enabled(self) -> bool:
        return self.get(COMPILE_WARM_ENABLED)
    @property
    def io_prefetch_enabled(self) -> bool:
        return self.get(IO_PREFETCH_ENABLED)
    @property
    def io_prefetch_batches(self) -> int:
        return self.get(IO_PREFETCH_BATCHES)
    @property
    def io_egress_enabled(self) -> bool:
        return self.get(IO_EGRESS_ENABLED)
    @property
    def query_timeout_ms(self) -> int:
        return self.get(QUERY_TIMEOUT_MS)
    @property
    def cancel_check_interval_ms(self) -> int:
        return self.get(CANCEL_CHECK_INTERVAL_MS)
    @property
    def watchdog_hang_timeout_ms(self) -> int:
        return self.get(WATCHDOG_HANG_TIMEOUT_MS)
    @property
    def adaptive_enabled(self) -> bool:
        return self.get(ADAPTIVE_ENABLED)
    @property
    def adaptive_coalesce_enabled(self) -> bool:
        return self.get(ADAPTIVE_COALESCE_ENABLED)
    @property
    def adaptive_advisory_bytes(self) -> int:
        return self.get(ADAPTIVE_ADVISORY_SIZE)
    @property
    def adaptive_min_partitions(self) -> int:
        return self.get(ADAPTIVE_MIN_PARTITIONS)
    @property
    def adaptive_skew_enabled(self) -> bool:
        return self.get(ADAPTIVE_SKEW_ENABLED)
    @property
    def adaptive_skew_factor(self) -> int:
        return self.get(ADAPTIVE_SKEW_FACTOR)
    @property
    def adaptive_skew_threshold(self) -> int:
        return self.get(ADAPTIVE_SKEW_THRESHOLD)
    @property
    def placement_mode(self) -> str:
        return str(self.get(PLACEMENT_MODE)).strip().lower()
    @property
    def placement_aqe_enabled(self) -> bool:
        return self.get(PLACEMENT_AQE_ENABLED)
    @property
    def shuffle_default_partitions(self) -> int:
        return self.get(SHUFFLE_DEFAULT_NUM_PARTITIONS)
    @property
    def shuffle_mode(self) -> str:
        return str(self.get(SHUFFLE_MODE)).strip().lower()
    @property
    def ici_devices(self) -> int:
        return self.get(SHUFFLE_ICI_DEVICES)
    @property
    def ici_max_stage_bytes(self) -> int:
        return self.get(SHUFFLE_ICI_MAX_STAGE_BYTES)
    @property
    def ici_sharded_scan(self) -> bool:
        return self.get(SHUFFLE_ICI_SHARDED_SCAN)
    @property
    def ooc_enabled(self) -> bool:
        return self.get(OOC_ENABLED)
    @property
    def ooc_partitions(self) -> int:
        return self.get(OOC_PARTITIONS)
    @property
    def ooc_max_recursion_depth(self) -> int:
        return self.get(OOC_MAX_RECURSION_DEPTH)
    @property
    def ooc_sort_merge_width(self) -> int:
        return self.get(OOC_SORT_MERGE_WIDTH)
    @property
    def aqe_initial_partitions(self) -> int:
        """Initial reduce-partition count for AQE-inserted exchanges:
        spark.rapids.shuffle.defaultNumPartitions when set, else
        spark.sql.shuffle.partitions."""
        n = self.get(SHUFFLE_DEFAULT_NUM_PARTITIONS)
        return n if n > 0 else self.get(SHUFFLE_PARTITIONS)
    @property
    def shuffle_partitions(self) -> int: return self.get(SHUFFLE_PARTITIONS)
    @property
    def broadcast_threshold(self) -> int: return self.get(BROADCAST_THRESHOLD)
    @property
    def has_nans(self) -> bool: return self.get(HAS_NANS)
    @property
    def metrics_enabled(self) -> bool: return self.get(METRICS_ENABLED)
    @property
    def compressed_enabled(self) -> bool:
        return self.get(COMPRESSED_ENABLED)
    @property
    def compressed_ingest(self) -> bool:
        return self.get(COMPRESSED_INGEST)
    @property
    def compressed_egress(self) -> bool:
        return self.get(COMPRESSED_EGRESS)
    @property
    def compressed_max_dict_fraction(self) -> float:
        return self.get(COMPRESSED_MAX_DICT_FRACTION)
    @property
    def compressed_max_composed_cells(self) -> int:
        return self.get(COMPRESSED_MAX_COMPOSED_CELLS)
    @property
    def compressed_rle(self) -> bool:
        return self.get(COMPRESSED_RLE)
    @property
    def compressed_delta(self) -> bool:
        return self.get(COMPRESSED_DELTA)
    @property
    def compressed_packed_bool(self) -> bool:
        return self.get(COMPRESSED_PACKED_BOOL)
    @property
    def transfer_pack_enabled(self) -> bool:
        return self.get(TRANSFER_PACK_ENABLED)
    @property
    def transfer_stats_threshold(self) -> int:
        return self.get(TRANSFER_STATS_THRESHOLD)
    @property
    def scan_device_cache_enabled(self) -> bool:
        return self.get(SCAN_DEVICE_CACHE)
    @property
    def mesh_devices(self) -> int:
        return self.get(MESH_DEVICES)
    @property
    def host_shuffle_workers(self) -> int:
        return self.get(HOST_SHUFFLE_WORKERS)
    @property
    def trace_enabled(self) -> bool: return self.get(TRACE_ENABLED)

    def get_bool(self, key: str, default: bool = True) -> bool:
        """Read a raw key as a boolean, parsing string values ("false",
        "0", "no") the way Spark conf strings arrive."""
        raw = self._settings.get(key)
        if raw is None:
            return default
        if isinstance(raw, bool):
            return raw
        return str(raw).strip().lower() in ("true", "1", "yes")

    # -- per-operator enable keys ------------------------------------------
    def is_operator_enabled(self, conf_key: str, incompat: bool,
                            is_disabled_by_default: bool) -> bool:
        """Reference: RapidsConf.isOperatorEnabled RapidsConf.scala:828-831."""
        raw = self._settings.get(conf_key)
        if raw is not None:
            return str(raw).strip().lower() in ("true", "1", "yes")
        if incompat:
            return self.incompatible_ops_enabled
        return not is_disabled_by_default


def generate_docs() -> str:
    """Render the registry as markdown (reference RapidsConf.help
    RapidsConf.scala:600-688 which generates docs/configs.md)."""
    lines = [
        "# spark_rapids_tpu configuration",
        "",
        "Generated from the conf registry (`python -m spark_rapids_tpu.conf`).",
        "",
        "Failure-handling knobs (`spark.rapids.shuffle.timeout.*`, retry "
        "backoff, checksums, peer blacklisting, recompute) and the "
        "`spark.rapids.faults.*` injection keys are catalogued with their "
        "recovery semantics in [fault_tolerance.md](fault_tolerance.md).",
        "",
        "| Key | Default | Description |",
        "|---|---|---|",
    ]
    for e in conf_entries():
        if e.internal:
            continue
        doc = " ".join(str(e.doc).split())
        lines.append(f"| `{e.key}` | `{e.default}` | {doc} |")
    return "\n".join(lines) + "\n"


if __name__ == "__main__":  # pragma: no cover
    import sys
    # write, don't print: the doc-sync test compares the file
    # byte-for-byte and print's extra newline would always fail it
    sys.stdout.write(generate_docs())
