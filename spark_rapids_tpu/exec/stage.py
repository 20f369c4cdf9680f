"""Whole-stage fused execution: one jitted kernel per pipeline segment.

The TPU analog of Spark's whole-stage codegen, applied where the
reference applies its plan rewrites (GpuOverrides /
GpuTransitionOverrides): the planner's fusion pass (plan/fusion.py)
collapses maximal chains of per-batch, capacity-preserving operators —
project, filter, and the exchange's partition-key projection — into a
single ``TpuStageExec`` whose whole step list traces into ONE XLA
program per (stage fingerprint, batch signature, capacity).  A
project -> filter -> project chain is then one dispatch round trip per
batch (instead of three, ~100ms each on a remote-attached chip) and
zero intermediate full-capacity materializations: the keep-mask, the
compaction gather, and the downstream projections never leave the
kernel.

Compile cost is attacked on two fronts:

* **literal hoisting** (exprs/base.py): constants enter the kernel as
  traced scalar arguments keyed OUT of the cache key, so two queries
  differing only in their literals share one compiled executable;
* a **background compile warmer**: when the stage sits over a file
  scan whose batch signature is predictable from the scan schema and
  reader batching, the stage kernel starts compiling on a thread at
  ``execute_columnar`` setup, overlapping XLA compile with the
  scan/prefetch pipeline's first decodes the same way uploads already
  overlap decode (docs/io_overlap.md).

Kernels are AOT-compiled through the compilation service
(``compile/service.py`` — the one module allowed to touch
``jit(...).lower(...).compile()``) and memoized in the shared
``utils/kernel_cache.py`` cache, so compile time is measured exactly
(the ``xlaCompileMs`` metric, split cold-vs-store-hit by the service)
and the per-op call sites in exec/basic.py route through the very same
compiler (a lone project or filter is just a single-step stage).  With
the persistent kernel store enabled, every compile consults and
records the on-disk fingerprint index (docs/compile_cache.md): a
restarted process (or a spawned worker) deserializes already-seen
stage kernels instead of recompiling, and the recorded (fingerprint,
signature, capacity) triples feed the startup AOT warm pool.  See
docs/fusion.md.
"""

from __future__ import annotations

import threading
import time
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import (
    DeviceColumn, LazyRows, bucket_capacity,
)
from spark_rapids_tpu.columnar.dtypes import (
    Field, Schema, STRING, device_dtype, from_name,
)
from spark_rapids_tpu.exec.base import ExecContext, TpuExec
from spark_rapids_tpu.exprs.base import (
    ColVal, EvalContext, Expression, _batch_signature, _flatten_batch,
    hoist_literals, hoisted_args,
)
from spark_rapids_tpu.utils.kernel_cache import KernelCache
from spark_rapids_tpu.utils.metrics import (
    METRIC_FUSED_OPS, METRIC_STAGE_DISPATCHES, METRIC_TOTAL_TIME,
    METRIC_XLA_COMPILE_MS,
)
from spark_rapids_tpu.utils.pscan import masked_positions

# A step is ("project", (expr, ...)) or ("filter", (pred,)).
Step = Tuple[str, Tuple[Expression, ...]]

_STAGE_KERNELS = KernelCache("stage", 512)

# process-wide fusion counters, surfaced by bench.py's summary line so
# the compile-cost trajectory is visible across BENCH rounds
_GLOBAL_LOCK = threading.Lock()
_GLOBAL = {"stages": 0, "fused_ops": 0, "compile_ms": 0.0,
           "dispatches": 0, "warm_compiles": 0, "warm_errors": 0}


def _bump_global(key: str, v) -> None:
    if v:
        with _GLOBAL_LOCK:
            _GLOBAL[key] += v


def global_stats() -> dict:
    """Snapshot of process-wide fusion counters plus the stage kernel
    cache's hit/miss/evict counters (bench.py summary line)."""
    with _GLOBAL_LOCK:
        out = dict(_GLOBAL)
    out["compile_ms"] = round(out["compile_ms"], 1)
    out.update({"cache_" + k: v for k, v in _STAGE_KERNELS.stats().items()})
    return out


def reset_global_stats() -> None:
    with _GLOBAL_LOCK:
        for k in _GLOBAL:
            _GLOBAL[k] = 0.0 if k == "compile_ms" else 0
    _STAGE_KERNELS.reset_counters()


def stage_kernel_cache() -> KernelCache:
    return _STAGE_KERNELS


# ---------------------------------------------------------------------------
# The shared stage compiler
# ---------------------------------------------------------------------------

def hoist_steps(steps: Sequence[Step]):
    """Hoist literals across a whole step list with one shared slot
    space.  Returns ``(hoisted_steps, values)``."""
    flat: List[Expression] = []
    shape: List[Tuple[str, int]] = []
    for kind, exprs in steps:
        shape.append((kind, len(exprs)))
        flat.extend(exprs)
    hoisted, values = hoist_literals(flat)
    out: List[Step] = []
    i = 0
    for kind, n in shape:
        out.append((kind, tuple(hoisted[i:i + n])))
        i += n
    return tuple(out), values


def stage_fingerprint(steps: Sequence[Step]) -> tuple:
    """Stable identity of a (hoisted) step list for kernel memoization."""
    return tuple((kind,) + tuple(e.key() for e in exprs)
                 for kind, exprs in steps)


def describe_steps(steps: Sequence[Step]) -> str:
    """The step list as ``explain()`` shows it."""
    parts = []
    for kind, exprs in steps:
        if kind == "project":
            parts.append(
                "Project[" + ", ".join(e.name for e in exprs) + "]")
        else:
            parts.append(f"Filter[{exprs[0].name}]")
    return " -> ".join(parts)


def emit_steps(steps: Sequence[Step], cols: List[ColVal], num_rows,
               capacity: int, partition_id, hoisted, aux=(),
               compact: bool = True):
    """Trace the whole step chain over ``cols`` inside a jitted kernel.
    Projections evaluate and validity-mask exactly like the per-op
    projection kernel; filters compute the keep-mask, its population
    count, and the padded compaction gather of every current column
    (the fused static-shape filter of exec/basic.py), after which the
    traced row count becomes the filter's count.  Returns
    ``(cols, num_rows)``.

    ``compact=False`` is the MASKED mode, for a consumer that reduces
    (the aggregate update, exec/aggregate.py): a filter only narrows
    the liveness mask (``live = live & keep``) and every plane stays
    where it is, a projection masks validity with ``live``, and the
    return is ``(cols, live)`` — no position vector, no gather.  Row
    position and the traced row count keep the INPUT's meaning, so a
    chain that reads either (a nondeterministic step) must compact.

    Float rounding note (docs/fusion.md): XLA contracts mul+add chains
    (fma) inside one program, so a fused chain's float outputs can
    differ from the per-op path in the LAST ULP when a multiply is not
    exact — the same contraction the per-op kernels already apply
    within a single projection expression (``v*2.5 + 1.0`` in one
    select contracts today).  HLO-level fences (optimization_barrier,
    reduce_precision) do not stop it: LLVM applies fast-math
    contraction inside fused loops regardless.  Non-float bytes and
    row order are identical by construction; row membership too,
    unless a float predicate boundary falls inside that last ulp."""
    if not compact:
        live = jnp.arange(capacity) < num_rows
        for kind, exprs in steps:
            ctx = EvalContext(cols, num_rows, capacity, partition_id,
                              hoisted=hoisted, aux=aux)
            if kind == "project":
                outs = [e.emit(ctx) for e in exprs]
                cols = [ColVal(o.data, o.validity & live, o.chars)
                        for o in outs]
            else:  # filter: the keep-mask IS the result
                p = exprs[0].emit(ctx)
                live = p.data & p.validity & live
        return cols, live
    n = num_rows
    for kind, exprs in steps:
        ctx = EvalContext(cols, n, capacity, partition_id,
                          hoisted=hoisted, aux=aux)
        live = jnp.arange(capacity) < n
        if kind == "project":
            outs = [e.emit(ctx) for e in exprs]
            cols = [ColVal(o.data, o.validity & live, o.chars)
                    for o in outs]
        else:  # filter
            p = exprs[0].emit(ctx)
            keep = p.data & p.validity & live
            count = jnp.sum(keep.astype(jnp.int32))
            idx = masked_positions(keep, capacity, capacity)
            ok = jnp.arange(capacity) < count
            new = []
            for cv in cols:
                data = jnp.take(cv.data, idx, axis=0, mode="clip")
                valid = jnp.where(
                    ok, jnp.take(cv.validity, idx, mode="clip"), False)
                chars = None if cv.chars is None else \
                    jnp.take(cv.chars, idx, axis=0, mode="clip")
                new.append(ColVal(data, valid, chars))
            cols = new
            n = count
    return cols, n


def _build_stage_fn(steps: Sequence[Step], capacity: int):
    def run(flat_cols, aux, num_rows, partition_id, hoisted):
        cols = [ColVal(*t) for t in flat_cols]
        cols, n = emit_steps(steps, cols, num_rows, capacity,
                             partition_id, hoisted, aux=aux)
        return n, tuple((c.data, c.validity, c.chars) for c in cols)
    return run


def norm_rows(batch: ColumnarBatch):
    """The traced row-count argument, normalized to a strong int32 so
    every dispatch (and the warmer's abstract signature) shares ONE
    aval regardless of whether the count is host-resident or a device
    scalar from an upstream filter.  A host count stays a host scalar:
    the launch carries it over, where ``jnp.asarray`` would be an eager
    device op of its own per batch."""
    rows = batch.rows_traced
    if isinstance(rows, (int, np.integer)):
        return np.int32(rows)
    return jnp.asarray(rows, jnp.int32)


def _sig_avals(sig: tuple):
    flat = []
    for dtype_name, cap, width in sig:
        # compressed compute-plane markers (columnar/encoding.py
        # stage_view): the flat triple carries the encoding's own
        # planes, decoded in-kernel by a prepended PlaneDecode step
        if dtype_name.startswith("@rle:"):
            dt = from_name(dtype_name[5:])
            flat.append((jax.ShapeDtypeStruct((cap,), device_dtype(dt)),
                         jax.ShapeDtypeStruct((width,), np.bool_),
                         jax.ShapeDtypeStruct((cap,), np.int32)))
            continue
        if dtype_name.startswith("@delta:"):
            _, base_name, store = dtype_name.split(":")
            dt = from_name(base_name)
            flat.append((jax.ShapeDtypeStruct((cap,), np.dtype(store)),
                         jax.ShapeDtypeStruct((cap,), np.bool_),
                         jax.ShapeDtypeStruct((1,), device_dtype(dt))))
            continue
        if dtype_name == "@packed":
            flat.append((jax.ShapeDtypeStruct((cap,), np.uint8),
                         jax.ShapeDtypeStruct((width,), np.bool_),
                         None))
            continue
        dt = from_name(dtype_name)
        valid = jax.ShapeDtypeStruct((cap,), np.bool_)
        if dt == STRING:
            flat.append((jax.ShapeDtypeStruct((cap,), np.int32), valid,
                         jax.ShapeDtypeStruct((cap, width), np.uint8)))
        else:
            flat.append((jax.ShapeDtypeStruct((cap,), device_dtype(dt)),
                         valid, None))
    return tuple(flat)


def aval_inputs(input_sig: tuple, capacity: int, values,
                aux_sig: tuple = ()):
    """ShapeDtypeStructs mirroring a concrete dispatch's arguments, for
    AOT compilation from a signature alone (the warmer path).
    ``aux_sig`` describes the compressed code view's dictionary gather
    tables (empty on the dense path)."""
    n = jax.ShapeDtypeStruct((), np.int32)
    pid = jax.ShapeDtypeStruct((), np.int64)
    hoisted = tuple(jax.ShapeDtypeStruct((), device_dtype(dt))
                    for _, dt in values)
    return (_sig_avals(input_sig), _sig_avals(aux_sig), n, pid, hoisted)


class StageKernel:
    """A compiled stage executable.  Prefers the AOT-compiled form (its
    compile time is measured, and the warmer produces it from abstract
    shapes); an aval-deviating call falls back to the retraceable jit
    fn for THAT call only — the AOT executable stays live for the
    common shape it was compiled for."""

    __slots__ = ("_compiled", "_fn", "compile_ms")

    def __init__(self, compiled, fn, compile_ms: float):
        self._compiled = compiled
        self._fn = fn
        self.compile_ms = compile_ms

    def __call__(self, *args):
        if self._compiled is not None:
            try:
                # through the Program, so the launch is in the ledger
                return self._fn.call_compiled(self._compiled, *args)
            except TypeError:
                # aval mismatch (not a launch failure): retrace via jit
                pass
            except ValueError as e:
                # the AOT executable is pinned to the device it was
                # lowered for; inputs COMMITTED to another chip (a
                # sharded scan ingest's per-shard chain,
                # docs/sharded_scan.md) retrace via jit, which compiles
                # and caches one variant per placement — anything else
                # is a real launch failure and must surface
                if "sharding" not in str(e):
                    raise
        return self._fn(*args)


# in-flight stage compiles, so the warmer and the first dispatch never
# compile the same program twice: the second caller WAITS on the first
# build (the whole point of warming is that the dispatch path joins an
# already-running compile instead of starting its own)
_INFLIGHT: dict = {}
_INFLIGHT_LOCK = threading.Lock()


def get_stage_kernel(steps: Sequence[Step], input_sig: tuple,
                     capacity: int, metrics=None, aux_sig: tuple = ()):
    """The shared stage compiler: cached compiled kernel + the hoisted
    literal values the caller must pass (``hoisted_args(values)``).
    Compile time lands in ``xlaCompileMs`` on ``metrics`` and in the
    process-wide fusion stats.  ``aux_sig`` carries the compressed code
    view's dictionary-table signatures (empty on the dense path, so
    dense cache keys are untouched by the compressed feature)."""
    h_steps, values = hoist_steps(steps)
    kern = compile_hoisted_stage(h_steps, values, input_sig, capacity,
                                 metrics=metrics, aux_sig=aux_sig)
    return kern, values


def compile_hoisted_stage(h_steps: Sequence[Step], values,
                          input_sig: tuple, capacity: int,
                          metrics=None, aux_sig: tuple = (),
                          record_execution: bool = True):
    """The post-hoist half of the stage compiler.  Split out so the
    AOT warm pool (compile/warm.py) can replay a recorded kernel from
    its pickled HOISTED form: literal hoisting is gated on a
    process-global conf flag set at ExecContext construction, so
    re-hoisting raw steps outside a query would produce a different
    fingerprint than the live dispatch and warm the wrong key.
    ``record_execution=False`` is the warm pool's replay mode: the
    compile still classifies against the store (hit), but does not
    append an execution record that would inflate its own key's
    popularity on every restart."""
    key = (stage_fingerprint(h_steps), input_sig, aux_sig, capacity)
    kern = _STAGE_KERNELS.get(key)
    if kern is not None:
        return kern
    with _INFLIGHT_LOCK:
        kern = _STAGE_KERNELS.peek(key)
        if kern is not None:
            return kern
        done = _INFLIGHT.get(key)
        owner = done is None
        if owner:
            done = threading.Event()
            _INFLIGHT[key] = done
    if not owner:
        done.wait()
        kern = _STAGE_KERNELS.peek(key)
        if kern is not None:
            return kern
        # the owning build failed; fall through and build ourselves
    try:
        from spark_rapids_tpu.compile import service as compile_service
        kinds = {kind for kind, _ in h_steps}
        fn = compile_service.engine_jit(
            _build_stage_fn(h_steps, capacity), family="stage",
            name=kinds.pop() if len(kinds) == 1 else "fused")

        def payload():
            # the warm pool's replay unit (compile/warm.py): the
            # HOISTED steps plus the literal slot values (dtypes shape
            # the kernel's traced-scalar avals), so a fresh process
            # replays through compile_hoisted_stage to the identical
            # cache key and store digest no matter how ITS hoisting
            # flag is set at warm time
            import pickle
            return pickle.dumps(
                ([(k, tuple(es)) for k, es in h_steps], tuple(values),
                 input_sig, aux_sig, capacity))

        compiled, ms, _store_hit = compile_service.aot_compile(
            fn, aval_inputs(input_sig, capacity, values, aux_sig),
            store_key=key, payload_fn=payload,
            record=record_execution)
        kern = StageKernel(compiled, fn, ms)
        _STAGE_KERNELS[key] = kern
        _bump_global("compile_ms", ms)
        # compile-time distribution (docs/observability.md): the
        # cold-start shape ROADMAP item 3 regresses against
        from spark_rapids_tpu.obs import registry as obs
        obs.record(obs.HIST_XLA_COMPILE_US, int(ms * 1000))
        if metrics is not None:
            metrics[METRIC_XLA_COMPILE_MS].add(int(round(ms)))
    finally:
        if owner:
            with _INFLIGHT_LOCK:
                _INFLIGHT.pop(key, None)
            done.set()
    return kern


# -- per-op routing (exec/basic.py): a lone op is a single-step stage ------

def run_project(exprs: Sequence[Expression], batch: ColumnarBatch,
                partition_id: int = 0, metrics=None) -> List[DeviceColumn]:
    """Projection through the shared stage compiler (one dispatch).
    Encoded columns run in the code domain (columnar/encoding.py
    stage_view): the view is the identity when none are present."""
    from spark_rapids_tpu.columnar import encoding
    exprs = tuple(exprs)
    view = encoding.stage_view((("project", exprs),), batch)
    kern, values = get_stage_kernel(view.steps, view.sig,
                                    batch.capacity, metrics=metrics,
                                    aux_sig=view.aux_sig)
    _n, outs = kern(view.flat, view.aux, norm_rows(batch),
                    jnp.int64(partition_id), hoisted_args(values))
    cols = []
    for i, (e, (d, v, ch)) in enumerate(zip(exprs, outs)):
        wrapped = view.wrap_column(i, d, v, batch.rows_raw)
        cols.append(wrapped if wrapped is not None else
                    DeviceColumn(e.dtype, d, v, batch.rows_raw,
                                 chars=ch))
    return cols


def run_filter(pred: Expression, batch: ColumnarBatch,
               metrics=None) -> ColumnarBatch:
    """Fused static-shape filter through the shared stage compiler: the
    output keeps the input capacity and its row count stays
    device-resident (LazyRows) — no host sync here.  Over encoded
    columns the predicate rewrites to code-set membership and the
    outputs stay encoded (codes compact like any other plane)."""
    from spark_rapids_tpu.columnar import encoding
    view = encoding.stage_view((("filter", (pred,)),), batch)
    kern, values = get_stage_kernel(view.steps, view.sig,
                                    batch.capacity, metrics=metrics,
                                    aux_sig=view.aux_sig)
    n_dev, outs = kern(view.flat, view.aux, norm_rows(batch),
                       jnp.int64(0), hoisted_args(values))
    rows = LazyRows(n_dev, batch.rows_bound)
    cols = []
    for i, (c, (d, v, ch)) in enumerate(zip(batch.columns, outs)):
        wrapped = view.wrap_column(i, d, v, rows)
        cols.append(wrapped if wrapped is not None else
                    DeviceColumn(c.dtype, d, v, rows, chars=ch))
    return ColumnarBatch(cols, rows, batch.schema)


# ---------------------------------------------------------------------------
# The fused stage operator
# ---------------------------------------------------------------------------

_SCAN_EXEC_NAMES = ("TpuParquetScanExec", "TpuOrcScanExec",
                    "TpuCsvScanExec")


class TpuStageExec(TpuExec):
    """A fused chain of project/filter steps executing as ONE jitted
    dispatch per input batch (see module docstring and docs/fusion.md).
    Built exclusively by the planner fusion pass; batches flow through
    with their input capacity preserved, so the stage composes with the
    coalesce/exchange machinery exactly like the ops it replaced."""

    def __init__(self, steps: Sequence[Step], child):
        super().__init__()
        self.steps: List[Step] = [(k, tuple(es)) for k, es in steps]
        self.children = [child]
        schema = child.output_schema
        for kind, exprs in self.steps:
            if kind == "project":
                schema = Schema([Field(e.name, e.dtype, e.nullable)
                                 for e in exprs])
        self._schema = schema
        self._has_filter = any(k == "filter" for k, _ in self.steps)
        from spark_rapids_tpu.exprs.nondeterministic import (
            contains_nondeterministic,
        )
        self.nondeterministic = any(
            contains_nondeterministic(e)
            for _, exprs in self.steps for e in exprs)
        # the most recent warmer thread, exposed so tests can assert
        # teardown (joined on stage iterator close, incl. limit early-exit)
        self._last_warmer: Optional[threading.Thread] = None

    def __getstate__(self):
        """Plans ship to shuffle worker processes by pickle: a live (or
        finished) warmer Thread is process-local state, never part of
        the plan."""
        state = dict(self.__dict__)
        state["_last_warmer"] = None
        return state

    @property
    def output_schema(self) -> Schema:
        return self._schema

    @property
    def has_filter(self) -> bool:
        return self._has_filter

    def describe(self) -> str:
        return "TpuStage [" + describe_steps(self.steps) + "]"

    # -- warmer -------------------------------------------------------------

    def _predict_signature(self, ctx: ExecContext):
        """(input_sig, capacity) the child scan will most likely produce,
        or None when unpredictable.  Only file scans have a signature
        knowable before the first decode (schema + reader batching);
        STRING columns make the padded char width data-dependent, so
        stages over string scans are never warmed."""
        node = self.children[0]
        while type(node).__name__ == "TpuCoalesceBatchesExec" \
                and node.children:
            node = node.children[0]
        if type(node).__name__ not in _SCAN_EXEC_NAMES:
            return None
        schema = node.output_schema
        if any(f.dtype == STRING for f in schema):
            return None
        cap = bucket_capacity(min(ctx.conf.reader_batch_size_rows,
                                  ctx.conf.batch_size_rows))
        return tuple((f.dtype.name, cap, 0) for f in schema), cap

    def _start_warmer(self, ctx: ExecContext):
        if not ctx.conf.fusion_warmer_enabled:
            return None
        pred = self._predict_signature(ctx)
        if pred is None:
            return None
        sig, cap = pred
        stop = threading.Event()

        def work():
            if stop.is_set():
                return
            try:
                get_stage_kernel(self.steps, sig, cap,
                                 metrics=self.metrics)
                _bump_global("warm_compiles", 1)
            except Exception:
                # warm compile is best-effort: the dispatch path compiles
                # for real if the prediction missed or the build failed
                _bump_global("warm_errors", 1)

        t = threading.Thread(target=work, name="srt-stage-warmer",
                             daemon=True)
        from spark_rapids_tpu import lifecycle
        # supervised: query teardown (or session stop) stops + joins a
        # still-running warmer instead of leaving it to the daemon flag.
        # Short join bound: a warmer deep in an XLA compile cannot be
        # interrupted and finishes on its own into the shared cache —
        # teardown must not serialize behind it
        reg = lifecycle.register_thread(t, stop=stop.set,
                                        join_timeout=2.0)
        self._last_warmer = t
        if reg.rejected:
            # query teardown raced warmer startup: skip the warm — the
            # dispatch path compiles for real if the prediction missed
            return None
        t.start()
        return (t, stop, reg)

    # -- execution ----------------------------------------------------------

    def _dispatch(self, ctx: ExecContext, batch: ColumnarBatch,
                  partition_id: int) -> List[ColumnarBatch]:
        from spark_rapids_tpu.utils.retry import (
            split_batch_half, with_retry,
        )

        def call(b):
            # kernel resolved per (sub)batch: an OOM split-retry half is
            # re-bucketed to a SMALLER capacity, so it needs its own
            # compiled kernel, not the original batch's.  The code view
            # (columnar/encoding.py) is likewise per (sub)batch: its
            # dictionary tables are capacity-independent aux inputs.
            from spark_rapids_tpu.columnar import encoding
            view = encoding.stage_view(self.steps, b)
            kern, values = get_stage_kernel(
                view.steps, view.sig, b.capacity,
                metrics=self.metrics, aux_sig=view.aux_sig)
            # the fused kernel's launch IS a launch site, fired once
            # per attempt (with_retry's own fire is suppressed below so
            # one attempt never consumes two triggers): injected OOMs
            # exercise spill-retry-split THROUGH the stage, and an
            # exhausted injection surfaces typed at the consumer
            from spark_rapids_tpu import faults
            faults.maybe_fail_oom("kernel.launch")
            n_dev, outs = kern(view.flat, view.aux, norm_rows(b),
                               jnp.int64(partition_id),
                               hoisted_args(values))
            rows = LazyRows(n_dev, b.rows_bound) if self._has_filter \
                else b.rows_raw
            cols = []
            for i, (f, (d, v, ch)) in enumerate(zip(self._schema,
                                                    outs)):
                wrapped = view.wrap_column(i, d, v, rows)
                cols.append(wrapped if wrapped is not None else
                            DeviceColumn(f.dtype, d, v, rows,
                                         chars=ch))
            return ColumnarBatch(cols, rows, self._schema)

        # row-splitting commutes with per-row project/filter steps, but
        # nondeterministic expressions key off row position — those
        # stages spill-retry without splitting so results stay identical
        split = None if self.nondeterministic else split_batch_half
        results = with_retry(call, batch, ctx, split=split,
                             fire_launch_site=False)
        self.metrics[METRIC_STAGE_DISPATCHES].add(len(results))
        _bump_global("dispatches", len(results))
        return results

    def execute_columnar(self, ctx: ExecContext
                         ) -> Iterator[ColumnarBatch]:
        def gen():
            self.metrics[METRIC_FUSED_OPS].add(len(self.steps))
            _bump_global("stages", 1)
            _bump_global("fused_ops", len(self.steps))
            warm = self._start_warmer(ctx)
            try:
                for pid, batch in enumerate(
                        self.children[0].execute_columnar(ctx)):
                    with self.metrics.timed(METRIC_TOTAL_TIME):
                        outs = self._dispatch(ctx, batch, pid)
                    yield from outs
            finally:
                if warm is not None:
                    t, stop, reg = warm
                    stop.set()
                    # bounded join: an early-exiting consumer (limit)
                    # must not stall behind a multi-second XLA compile.
                    # The daemon thread finishes on its own and its
                    # result still lands in the shared cache, where a
                    # later query of the same shape collects it.
                    t.join(timeout=5)
                    if not t.is_alive():
                        reg.release()
        return self._count_output(gen())
