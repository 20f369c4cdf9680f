"""spark_rapids_tpu — a TPU-native Spark-SQL-style columnar acceleration framework.

A brand-new framework with the capabilities of the RAPIDS Accelerator for Apache
Spark (reference: viirya/spark-rapids), re-designed TPU-first on JAX/XLA/Pallas:

- A Catalyst-style planner rewrites supported physical operators into ``Tpu*Exec``
  nodes (reference: sql-plugin GpuOverrides.scala / RapidsMeta.scala).
- Columnar batches live in TPU HBM as XLA device buffers with Arrow-compatible
  layout (reference: GpuColumnVector.java wrapping cuDF device columns).
- Joins, aggregates, sorts, filters, projections execute as jitted XLA/Pallas
  kernels (reference: libcudf kernels driven through ai.rapids.cudf JNI).
- A tiered device->host->disk spill framework replaces the RMM pool + event
  handler (reference: RapidsBufferStore.scala / DeviceMemoryEventHandler.scala).
- An accelerated shuffle moves partitioned columnar batches over ICI/DCN via
  jax.lax collectives, with an Arrow-IPC host fallback (reference:
  shuffle-plugin UCX transport + GpuColumnarBatchSerializer.scala).
"""

import jax as _jax

# Spark LongType/DoubleType semantics require 64-bit lanes; without this JAX
# silently downcasts int64->int32 and float64->float32 (wrong results, not
# slow results). TPU executes f64 via emulation — hot kernels downcast
# internally where Spark semantics allow.
_jax.config.update("jax_enable_x64", True)

# Host fingerprint (cpu flags + python/jax versions): XLA:CPU AOT artifacts
# embed machine features that are not in the cache key, and loading one
# compiled on a different machine SIGILLs or segfaults.  It keys the CPU
# test cache (tests/conftest.py) and the kernel store's index
# (compile/store.py); the accelerator cache does not need it.
def _host_fingerprint() -> str:
    import hashlib
    import platform
    parts = [platform.machine(), platform.python_version(),
             _jax.__version__]
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith("flags"):
                    parts.append(line.strip())
                    break
    except OSError:
        pass
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


def _enable_compile_cache(platform: str) -> None:
    """Turn on the persistent XLA compile cache for accelerator
    platforms (called by TpuRuntime once the backend is known).

    Not at import time: XLA:CPU AOT deserialization is unreliable
    (machine-feature mismatches surface as SIGILL/segfaults or hangs in
    cache reads even same-host), so CPU runs never touch it by default.
    The one implementation lives in the compilation service
    (compile/store.py — the tests' conftest and the conf-gated kernel
    store are thin consumers of the same functions):
    ``JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
    ``<checkout>/.jax_cache/<platform>``."""
    from spark_rapids_tpu.compile.store import enable_default_cache
    enable_default_cache(platform)

from spark_rapids_tpu.version import __version__

from spark_rapids_tpu.conf import TpuConf, conf_entries
from spark_rapids_tpu.errors import (
    AdmissionRejectedError, ChipFailedError, EngineError,
    QueryBudgetExceededError, QueryCancelledError, QueryHangError,
    QueryTimeoutError, RetryBudgetExhaustedError,
)
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.api import Window, WindowSpec

__all__ = ["__version__", "TpuConf", "conf_entries", "TpuSession",
           "Window", "WindowSpec", "EngineError", "QueryCancelledError",
           "QueryTimeoutError", "QueryHangError",
           "AdmissionRejectedError", "QueryBudgetExceededError",
           "ChipFailedError", "RetryBudgetExhaustedError"]
