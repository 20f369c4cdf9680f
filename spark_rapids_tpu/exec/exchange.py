"""Shuffle exchange: partition batches by key hash / round-robin / single.

Reference: GpuShuffleExchangeExec.scala:60-244 (partition each batch, hand
(partitionId, slice) pairs to the shuffle), GpuHashPartitioning.scala
(cuDF ``Table.hashPartition`` producing a partition-contiguous table +
offsets), GpuRoundRobinPartitioning.scala, GpuSinglePartitioning.scala,
partition slicing Plugin.scala:42-131.

TPU design: one jitted kernel computes a per-row partition id (splitmix64
key hash pmod n, or round-robin), a stable argsort by partition id (the
partition-contiguous permutation — the ``hashPartition`` analog; XLA sorts
are MXU-friendly fixed-shape), and per-partition counts.  The host reads
the counts (one sync), then per-partition compaction gathers produce the
output batches at bucket capacities.  The same kernel is the local half of
the multi-chip exchange: on a mesh the permuted batch is exchanged with
``jax.lax.all_to_all`` over ICI (see spark_rapids_tpu/parallel/).
"""

from __future__ import annotations

from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp

from spark_rapids_tpu.compile.service import engine_jit
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import (
    DeviceColumn, LazyRows, bucket_capacity,
)
from spark_rapids_tpu.columnar.dtypes import Schema
from spark_rapids_tpu.exec.base import ExecContext, TpuExec
from spark_rapids_tpu.exec.coalesce import concat_batches
from spark_rapids_tpu.exec.stage import (
    TpuStageExec, emit_steps, hoist_steps, norm_rows, stage_fingerprint,
)
from spark_rapids_tpu.exprs.base import (
    ColVal, EvalContext, Expression, _batch_signature, _flatten_batch,
    hoisted_args,
)
from spark_rapids_tpu.utils.metrics import (
    METRIC_FUSED_OPS, METRIC_STAGE_DISPATCHES, METRIC_TOTAL_TIME,
)

from spark_rapids_tpu.utils.kernel_cache import KernelCache

_PARTITION_CACHE = KernelCache("exchange.partition", 128)


def record_partition_sizes(metrics, sizes) -> None:
    """The ONE sink for per-partition exchange byte statistics, shared
    by the host exchange (``_record_partition_stats``) and the ICI
    collective path (exec/meshexec.py:_record_ici_exchange): adds the
    total to ``shufflePartitionBytes`` and records the size shape in
    the process-wide AQE stats object (docs/adaptive.md) — one sink so
    the two data planes can never silently diverge in what the
    adaptive rules see."""
    from spark_rapids_tpu.exec.aqe import record_exchange_stats
    from spark_rapids_tpu.utils.metrics import (
        METRIC_SHUFFLE_PARTITION_BYTES,
    )
    metrics[METRIC_SHUFFLE_PARTITION_BYTES].add(sum(sizes))
    record_exchange_stats(sizes)


def _pid_to_counts_perm(pid: jnp.ndarray, live: jnp.ndarray,
                        num_parts: int):
    """Shared kernel tail: per-row partition id -> (per-partition counts,
    partition-contiguous stable permutation); dead rows sort to the end."""
    pid = jnp.where(live, pid, num_parts)
    from spark_rapids_tpu.exec.sortkeys import bitonic_lex_sort
    perm = bitonic_lex_sort([pid])[-1]
    counts = jnp.sum(
        pid[None, :] == jnp.arange(num_parts, dtype=jnp.int32)[:, None],
        axis=1)
    return counts, perm


def _slice_partitions(batch: ColumnarBatch, counts, perm,
                      num_parts: int) -> List[Optional[ColumnarBatch]]:
    """Shared host tail: gather each partition's rows out of the
    partition-contiguous permutation (None for empty partitions).

    A partition whose slice window ``[off, off + cap)`` overruns the
    permutation (its bucket capacity rounds past the tail) reads from a
    ONCE-padded copy of the permutation extended with the dead-row
    sentinel ``batch.capacity`` (the gather invalidates out-of-range
    indices) — the pad is sized for the widest possible overrun
    (the largest partition's bucket) and built at most once per batch,
    where the old fallback materialized a fresh concatenated index
    array per overrunning partition on the hot path."""
    import numpy as np
    counts = np.asarray(counts)
    out: List[Optional[ColumnarBatch]] = []
    padded = None
    off = 0
    for p in range(num_parts):
        n = int(counts[p])
        if n == 0:
            out.append(None)
        else:
            cap = bucket_capacity(n)
            src = perm
            if off + cap > perm.shape[0]:
                if padded is None:
                    # the overrun is bounded by one partition's bucket,
                    # itself bounded by the largest count's bucket
                    pad = bucket_capacity(int(counts.max()))
                    padded = jnp.concatenate(
                        [perm, jnp.full(pad, batch.capacity, perm.dtype)])
                src = padded
            idx = jax.lax.dynamic_slice_in_dim(src, off, cap)
            out.append(batch.gather(idx, n))
        off += n
    return out


def _compile_partitioner(mode: str, keys_key: str, keys: List[Expression],
                         input_sig, capacity: int, num_parts: int,
                         aux_sig: tuple = (), salt: int = 0):
    key = (mode, keys_key, input_sig, aux_sig, capacity, num_parts, salt)
    fn = _PARTITION_CACHE.get(key)
    if fn is not None:
        return fn

    def run(flat_cols, aux, num_rows, rr_start):
        cols = [ColVal(*t) for t in flat_cols]
        ctx = EvalContext(cols, num_rows, capacity, aux=aux)
        live = jnp.arange(capacity) < num_rows
        if mode == "hash":
            from spark_rapids_tpu.exec.joins import _hash_keys, _splitmix64
            h, _valid, _ = _hash_keys(keys, ctx)
            if salt:
                # re-salted remix (docs/out_of_core.md): a recursive
                # re-partition must land rows in DIFFERENT buckets than
                # the parent round, or an over-budget partition would
                # re-partition into itself forever; the salt is a
                # compile-time constant, part of the kernel-cache key
                h = _splitmix64(h.astype(jnp.uint64)
                                ^ jnp.uint64(salt)).astype(jnp.int64)
            # Spark uses pmod(hash, n); null keys hash deterministically.
            pid = (h.astype(jnp.uint64) % jnp.uint64(num_parts)).astype(
                jnp.int32)
        else:  # roundrobin
            pid = ((jnp.arange(capacity, dtype=jnp.int64) + rr_start)
                   % num_parts).astype(jnp.int32)
        return _pid_to_counts_perm(pid, live, num_parts)

    fn = engine_jit(run, family="exchange", name="partition")
    _PARTITION_CACHE[key] = fn
    return fn


def _partition_view(batch: ColumnarBatch, keys, mode: str):
    """The compressed code view of a partition dispatch: encoded
    columns flatten as codes and hash keys over them become per-code
    hash gathers built with the dense hash kernel — partition
    assignment is byte-identical to the dense path
    (columnar/encoding.py).  Identity when nothing is encoded."""
    from spark_rapids_tpu.columnar import encoding
    return encoding.stage_view(
        (), batch, keys=tuple(keys) if mode == "hash" and keys else ())


def partition_batch(batch: ColumnarBatch, num_parts: int,
                    keys: Optional[List[Expression]] = None,
                    mode: str = "hash", rr_start: int = 0,
                    salt: int = 0) -> List[Optional[ColumnarBatch]]:
    """Split one batch into ``num_parts`` batches (None for empty parts).

    The ``hashPartition`` analog: one kernel produces the
    partition-contiguous permutation + counts, then one gather per
    non-empty partition.  ``salt`` != 0 remixes the key hash (the
    out-of-core recursive re-partition, docs/out_of_core.md); 0 keeps
    the exchange-compatible Spark pmod assignment byte-identical.
    """
    if mode == "hash" and keys:
        view = _partition_view(batch, keys, mode)
        v_keys = list(view.keys or keys)
        keys_key = "|".join(k.key() for k in v_keys)
    else:
        mode, keys_key = "roundrobin", ""
        view = _partition_view(batch, None, mode)
        v_keys = []
    fn = _compile_partitioner(mode, keys_key, v_keys,
                              view.sig, batch.capacity,
                              num_parts, aux_sig=view.aux_sig,
                              salt=salt)
    counts, perm = fn(view.flat, view.aux, jnp.int32(batch.num_rows),
                      jnp.int64(rr_start))
    return _slice_partitions(batch, counts, perm, num_parts)


def partition_batch_to_host_dispatch(batch: ColumnarBatch,
                                     num_parts: int,
                                     keys: Optional[List[Expression]]
                                     = None,
                                     mode: str = "hash",
                                     rr_start: int = 0):
    """Non-blocking half of the single-pull partition EGRESS
    (docs/d2h_egress.md): same partition kernel as ``partition_batch``,
    plus the whole-batch gather and pack dispatched asynchronously with
    the device->host copies started — ``pipelined_d2h``'s dispatch
    phase.  ``transfer.pack_partitions_finish`` then pulls planes +
    per-partition counts in ONE ``device_get`` and slices per-partition
    ``pa.RecordBatch``es (None for empty partitions) — the host-side
    contract the shuffle map writers consume."""
    if mode == "hash" and keys:
        view = _partition_view(batch, keys, mode)
        v_keys = list(view.keys or keys)
        keys_key = "|".join(k.key() for k in v_keys)
    else:
        mode, keys_key = "roundrobin", ""
        view = _partition_view(batch, None, mode)
        v_keys = []
    fn = _compile_partitioner(mode, keys_key, v_keys,
                              view.sig, batch.capacity,
                              num_parts, aux_sig=view.aux_sig)
    # norm_rows, NOT batch.num_rows: a device-resident count (LazyRows
    # from an upstream filter) must stay on device — syncing it here
    # would pay a hidden second link round trip per batch, silently
    # breaking the one-pull invariant this path exists for
    counts, perm = fn(view.flat, view.aux, norm_rows(batch),
                      jnp.int64(rr_start))
    from spark_rapids_tpu.columnar.transfer import (
        pack_partitions_dispatch,
    )
    return pack_partitions_dispatch(batch, counts, perm, num_parts)


def partition_batch_to_host(batch: ColumnarBatch, num_parts: int,
                            keys: Optional[List[Expression]] = None,
                            mode: str = "hash", rr_start: int = 0,
                            metrics=None):
    """One-shot single-pull partition egress: dispatch + finish — one
    gather, one pack, ONE link round trip for every partition of the
    batch, regardless of partition count."""
    from spark_rapids_tpu.columnar.transfer import pack_partitions_finish
    return pack_partitions_finish(
        partition_batch_to_host_dispatch(batch, num_parts, keys, mode,
                                         rr_start), metrics=metrics)


def _compile_fused_hash(steps, keys, keys_key: str, input_sig,
                        capacity: int, num_parts: int, values=(),
                        metrics=None, aux_sig: tuple = ()):
    """Stage steps + partition-key projection + hash assignment + the
    partition-contiguous permutation, ALL in one jitted kernel (the
    whole-stage-fusion extension of the hashPartition analog: the
    project/filter chain below the exchange never materializes — its
    output columns leave the kernel together with counts and the
    permutation).  ``steps``/``keys`` must already be hoisted with a
    shared slot space (hoist_steps over steps + keys)."""
    key = ("fusedhash", stage_fingerprint(steps), keys_key, input_sig,
           aux_sig, capacity, num_parts)
    fn = _PARTITION_CACHE.get(key)
    if fn is not None:
        return fn

    def run(flat_cols, aux, num_rows, partition_id, hoisted):
        cols = [ColVal(*t) for t in flat_cols]
        cols, n = emit_steps(steps, cols, num_rows, capacity,
                             partition_id, hoisted, aux=aux)
        ctx = EvalContext(cols, n, capacity, partition_id,
                          hoisted=hoisted, aux=aux)
        live = jnp.arange(capacity) < n
        from spark_rapids_tpu.exec.joins import _hash_keys
        h, _valid, _ = _hash_keys(keys, ctx)
        pid = (h.astype(jnp.uint64) % jnp.uint64(num_parts)).astype(
            jnp.int32)
        counts, perm = _pid_to_counts_perm(pid, live, num_parts)
        return counts, perm, n, tuple(
            (c.data, c.validity, c.chars) for c in cols)

    # AOT-compile through the compilation service so this kernel's
    # compile time lands in compile_ms/xlaCompileMs like every other
    # fused-stage compile (bench.py's cold split reads those) and the
    # persistent store counts/classifies it (docs/compile_cache.md;
    # no warm payload — the warm pool replays plain stage triples,
    # this fused-hash shape recompiles with its exchange)
    from spark_rapids_tpu.compile import service as compile_service
    from spark_rapids_tpu.exec import stage as _stage
    from spark_rapids_tpu.utils.metrics import METRIC_XLA_COMPILE_MS
    fn = engine_jit(run, family="exchange", name="fused_hash")
    compiled, ms, _store_hit = compile_service.aot_compile(
        fn, _stage.aval_inputs(input_sig, capacity, values, aux_sig),
        store_key=key)
    kern = _stage.StageKernel(compiled, fn, ms)
    _stage._bump_global("compile_ms", ms)
    if metrics is not None:
        metrics[METRIC_XLA_COMPILE_MS].add(int(round(ms)))
    _PARTITION_CACHE[key] = kern
    return kern


def partition_batch_fused(batch: ColumnarBatch, stage: TpuStageExec,
                          keys: List[Expression], num_parts: int,
                          partition_id: int, metrics=None
                          ) -> List[Optional[ColumnarBatch]]:
    """Hash-partition ``batch`` through ``stage``'s fused steps: one
    kernel yields the stage output columns, per-partition counts, and
    the partition-contiguous permutation; the host then gathers each
    non-empty partition exactly like the unfused path.  Encoded
    columns run the whole pipeline in the code domain — stage steps
    rewrite to per-code gathers and the key hash gathers per-code
    hashes (columnar/encoding.py stage_view)."""
    from spark_rapids_tpu.columnar import encoding
    view = encoding.stage_view(stage.steps, batch, keys=tuple(keys))
    v_keys = tuple(view.keys or keys)
    hoisted, values = hoist_steps(
        list(view.steps) + [("project", v_keys)])
    h_steps, h_keys = hoisted[:-1], hoisted[-1][1]
    keys_key = "|".join(k.key() for k in h_keys)
    fn = _compile_fused_hash(h_steps, h_keys, keys_key,
                             view.sig, batch.capacity,
                             num_parts, values=values, metrics=metrics,
                             aux_sig=view.aux_sig)
    counts, perm, n_dev, outs = fn(
        view.flat, view.aux, norm_rows(batch),
        jnp.int64(partition_id), hoisted_args(values))
    rows = LazyRows(n_dev, batch.rows_bound) if stage.has_filter \
        else batch.rows_raw
    schema = stage.output_schema
    cols = []
    for i, (f, (d, v, ch)) in enumerate(zip(schema, outs)):
        wrapped = view.wrap_column(i, d, v, rows)
        cols.append(wrapped if wrapped is not None else
                    DeviceColumn(f.dtype, d, v, rows, chars=ch))
    out_batch = ColumnarBatch(cols, rows, schema)
    return _slice_partitions(out_batch, counts, perm, num_parts)


def _compile_keys_kernel(orders_key: tuple, orders, input_sig,
                         capacity: int, pad_width: int):
    """Jitted kernel: batch -> tuple of per-row sort-key arrays for the
    range partitioner.  String char matrices are padded to ``pad_width``
    so every batch yields the same key count regardless of its own
    width."""
    key = ("rangekeys", orders_key, input_sig, capacity, pad_width)
    fn = _PARTITION_CACHE.get(key)
    if fn is not None:
        return fn
    from spark_rapids_tpu.columnar.dtypes import STRING
    from spark_rapids_tpu.exec.sortkeys import colval_sort_keys

    def run(flat_cols, num_rows):
        cols = [ColVal(*t) for t in flat_cols]
        ctx = EvalContext(cols, num_rows, capacity)
        keys = []
        for expr, asc, nf in orders:
            cv = expr.emit(ctx)
            if expr.dtype == STRING and cv.chars is not None and \
                    cv.chars.shape[1] < pad_width:
                cv = ColVal(cv.data, cv.validity, jnp.pad(
                    cv.chars,
                    ((0, 0), (0, pad_width - cv.chars.shape[1]))))
            keys.extend(colval_sort_keys(cv, expr.dtype, asc, nf))
        return tuple(keys)

    fn = engine_jit(run, family="exchange", name="keys")
    _PARTITION_CACHE[key] = fn
    return fn


def _observed_key_width(orders, batches, conf_max: int) -> int:
    """Width (multiple of 4, capped at the conf max) the string sort-key
    char matrices must be padded to so every batch emits the same key
    count: the max EMITTED chars width across batches, found with
    ``jax.eval_shape`` (shape-only, no device work) — typically far
    narrower than maxDeviceStringWidth for short strings."""
    from spark_rapids_tpu.columnar.dtypes import STRING
    if not any(e.dtype == STRING for e, _, _ in orders):
        return 4
    widest = 1
    seen = set()
    for b in batches:
        sig = _batch_signature(b)
        if sig in seen:
            continue
        seen.add(sig)

        def probe(flat_cols, num_rows):
            cols = [ColVal(*t) for t in flat_cols]
            ctx = EvalContext(cols, num_rows, b.capacity)
            outs = []
            for e, _, _ in orders:
                cv = e.emit(ctx)
                if cv.chars is not None:
                    outs.append(cv.chars)
            return tuple(outs)

        shapes = jax.eval_shape(probe, _flatten_batch(b), jnp.int32(0))
        for s in shapes:
            widest = max(widest, s.shape[1])
    return min(-(-widest // 4) * 4, -(-conf_max // 4) * 4)


def _compile_range_assign(nkeys: int, capacity: int, num_parts: int):
    """Jitted kernel: (keys, bounds) -> counts + partition-contiguous
    permutation.  pid(row) = #bounds with key_tuple(row) > bound_tuple
    (Spark RangePartitioner.getPartition: first bound >= key)."""
    key = ("rangeassign", nkeys, capacity, num_parts)
    fn = _PARTITION_CACHE.get(key)
    if fn is not None:
        return fn

    def run(keys, bounds, num_rows):
        live = jnp.arange(capacity) < num_rows
        nb = num_parts - 1
        eq = jnp.ones((capacity, nb), bool)
        gt = jnp.zeros((capacity, nb), bool)
        for k, b in zip(keys, bounds):
            kc = k[:, None]
            br = b[None, :]
            gt = gt | (eq & (kc > br))
            eq = eq & (kc == br)
        pid = jnp.sum(gt, axis=1).astype(jnp.int32)
        return _pid_to_counts_perm(pid, live, num_parts)

    fn = engine_jit(run, family="exchange", name="range_assign")
    _PARTITION_CACHE[key] = fn
    return fn


def compute_range_bounds(key_rows: "list", num_parts: int,
                         sample_max: int = 10_000):
    """Host-side bound computation from sampled key tuples (reference
    GpuRangePartitioner.sketch/createRangeBounds GpuRangePartitioner.scala:
    42,95 — reservoir sample then weighted quantile bounds).

    ``key_rows``: list of per-batch tuples of host key arrays (one array
    per sort key, aligned by row).  Returns a tuple of ``num_parts - 1``-
    long numpy arrays, one per key, or None when there is no data."""
    import numpy as np
    if not key_rows:
        return None
    nkeys = len(key_rows[0])
    cols = [np.concatenate([np.asarray(kr[i]) for kr in key_rows])
            for i in range(nkeys)]
    n = cols[0].shape[0]
    if n == 0:
        return None
    if n > sample_max:
        # deterministic uniform subsample (the reservoir analog; seeded
        # like the reference's XORShift sampler, SamplingUtils.scala:29)
        idx = np.random.default_rng(42).choice(n, sample_max, replace=False)
        cols = [c[idx] for c in cols]
        n = sample_max
    # lexicographic sort (np.lexsort keys are least-significant first)
    order = np.lexsort(tuple(reversed(cols)))
    bounds = []
    pos = [min(n - 1, (i + 1) * n // num_parts)
           for i in range(num_parts - 1)]
    for c in cols:
        s = c[order]
        bounds.append(s[pos])
    return tuple(bounds)


def partition_batch_by_range(batch: ColumnarBatch, num_parts: int,
                             keys, bounds) -> List[Optional[ColumnarBatch]]:
    """Split one batch along precomputed range bounds using the batch's
    already-computed device key arrays (device kernel + per-partition
    gathers, same shape as the hash path)."""
    fn = _compile_range_assign(len(keys), batch.capacity, num_parts)
    jb = tuple(jnp.asarray(b) for b in bounds)
    counts, perm = fn(keys, jb, jnp.int32(batch.num_rows))
    return _slice_partitions(batch, counts, perm, num_parts)


def partition_batch_by_range_to_host(batch: ColumnarBatch, num_parts: int,
                                     keys, bounds, metrics=None):
    """Range-mode single-pull egress: the range assignment kernel's
    counts + permutation feed the same one-pull pack as the hash and
    round-robin modes (``pack_partitions_and_pull``), so a host-side
    range egress consumer pays one link round trip per batch too."""
    fn = _compile_range_assign(len(keys), batch.capacity, num_parts)
    jb = tuple(jnp.asarray(b) for b in bounds)
    # norm_rows: no hidden count sync (see partition_batch_to_host)
    counts, perm = fn(keys, jb, norm_rows(batch))
    from spark_rapids_tpu.columnar.transfer import pack_partitions_and_pull
    return pack_partitions_and_pull(batch, counts, perm, num_parts,
                                    metrics=metrics)


class TpuShuffleExchangeExec(TpuExec):
    """Single-process exchange: re-buckets rows into ``num_partitions``
    output batches (reference GpuShuffleExchangeExec.scala:60-244).  On a
    device mesh the distributed driver (parallel/) replaces this with an
    ``all_to_all`` collective over the same partition kernel."""

    def __init__(self, num_partitions: int, keys: List[Expression],
                 mode: str, child, orders=None):
        super().__init__()
        self.num_partitions = max(1, int(num_partitions))
        self.keys = list(keys)
        self.orders = list(orders or [])  # [(expr, asc, nulls_first)]
        if mode == "range" and self.orders:
            self.mode = "range"
        else:
            self.mode = mode if (keys or mode == "single") else "roundrobin"
        self.children = [child]
        # True for exchanges the planner inserted under a join for AQE
        # (docs/adaptive.md): only those may coalesce/skew-split — an
        # explicit repartition(n) count is a user contract
        self.aqe_inserted = False
        # per-partition byte estimates from the last map pass (host
        # ints; the runtime statistics AQE replans on)
        self.last_partition_bytes: Optional[List[int]] = None

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def describe(self) -> str:
        k = ", ".join(e.name for e in self.keys)
        if self.mode == "range":
            k = ", ".join(e.name + ("" if asc else " DESC")
                          for e, asc, _ in self.orders)
        return (f"TpuShuffleExchange [n={self.num_partitions}, "
                f"mode={self.mode}{', keys=' + k if k else ''}]")

    def _execute_range(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        """Range partitioning: two passes over the (materialized) input —
        sample sort keys to bound tuples, then slice every batch along
        them (reference GpuRangePartitioner.scala:42,95 sketch + slice)."""
        from spark_rapids_tpu.memory.spill import (
            close_all, collect_spillable,
        )
        # the two-pass exchange holds the whole input: keep it behind
        # spill handles so it participates in the device budget; per-batch
        # sort keys are recomputed in pass 2 (cached kernel) instead of
        # being pinned in HBM across both passes
        handles = collect_spillable(
            self.children[0].execute_columnar(ctx), ctx)
        if not handles:
            return
        try:
            import numpy as np
            orders_key = tuple((e.key(), asc, nf)
                               for e, asc, nf in self.orders)
            # pad must be observed over EVERY batch (string widths vary
            # per file): a narrower first batch would emit fewer packed
            # key arrays than a wider later one and misalign the
            # bounds/key zip.  Observed one handle at a time (shape-only
            # probe, no device sync) so the whole input is never
            # resident at once.
            pad = 4
            for h in handles:
                pad = max(pad, _observed_key_width(
                    self.orders, [h.get(device=ctx.runtime.device)],
                    ctx.conf.max_string_width))
            sample_max = ctx.conf.range_sample_size
            total_rows = sum(
                h.num_rows if isinstance(h.num_rows, int)
                else h.num_rows.get() for h in handles)

            def keys_of(b):
                fn = _compile_keys_kernel(orders_key, self.orders,
                                          _batch_signature(b),
                                          b.capacity, pad)
                return fn(_flatten_batch(b), b.rows_traced)

            key_rows = []
            with self.metrics.timed("sampleTime"):
                for h in handles:
                    b = h.get(device=ctx.runtime.device)
                    keys = keys_of(b)
                    # only a bounded, evenly-spaced sample crosses to
                    # host; per-batch share proportional to its row count
                    # so the pooled sample approximates a uniform row
                    # sample (the reference's weighted reservoir sketch,
                    # GpuRangePartitioner.scala:42)
                    take = min(b.num_rows, max(
                        1, sample_max * b.num_rows // max(1, total_rows)))
                    if take == 0 or b.num_rows == 0:
                        continue
                    idx = np.unique(np.linspace(
                        0, b.num_rows - 1, take).astype(np.int64))
                    jidx = jnp.asarray(idx)
                    # ONE pull for every key's sample (device_pull:
                    # counted, fault-injectable) — per-key np.asarray
                    # conversions each paid a link round trip
                    from spark_rapids_tpu.columnar.transfer import (
                        device_pull,
                    )
                    key_rows.append(tuple(
                        np.asarray(a) for a in device_pull(
                            tuple(jnp.take(k, jidx) for k in keys),
                            metrics=self.metrics)))
                bounds = compute_range_bounds(
                    key_rows, self.num_partitions, sample_max=sample_max)
            if bounds is None:
                for h in handles:
                    yield h.get(device=ctx.runtime.device)
                return
            from spark_rapids_tpu.utils.retry import (
                split_batch_half, with_retry,
            )

            def range_partition(bb):
                # keys recomputed per (sub)batch so row-split halves
                # carry their own key arrays; range assignment is
                # per-row, so halves partition identically (same
                # argument that makes hash mode row-splittable)
                return partition_batch_by_range(
                    bb, self.num_partitions, keys_of(bb), bounds)

            parts: List[List[ColumnarBatch]] = [
                [] for _ in range(self.num_partitions)]
            for h in handles:
                b = h.get(device=ctx.runtime.device)
                with self.metrics.timed(METRIC_TOTAL_TIME):
                    for pieces in with_retry(range_partition, b, ctx,
                                             split=split_batch_half):
                        for p, piece in enumerate(pieces):
                            if piece is not None:
                                parts[p].append(piece)
            for bucket in parts:
                if not bucket:
                    continue
                yield bucket[0] if len(bucket) == 1 else \
                    concat_batches(bucket, self.output_schema)
        finally:
            close_all(handles)

    def _fused_stage_child(self, ctx: ExecContext):
        """The TpuStageExec child to fold into the partition kernel, or
        None.  Only the hash mode folds: round-robin assignment depends
        on the batch-global POST-FILTER row offset (host-unknowable
        without a sync per batch) and range mode runs its own two-pass
        driver."""
        if not ctx.conf.fusion_enabled:
            return None
        if self.mode != "hash" or self.num_partitions <= 1:
            return None
        child = self.children[0]
        return child if isinstance(child, TpuStageExec) else None

    def _partition_buckets(self, ctx: ExecContext
                           ) -> List[List[ColumnarBatch]]:
        """The map side of the exchange: run the child and bucket every
        batch's rows by partition id.  Shared by the streaming
        ``execute_columnar`` path and by AQE's ``TpuQueryStageExec``
        (docs/adaptive.md), which buffers the buckets as a materialized
        stage and replans on their measured sizes."""
        from spark_rapids_tpu.utils.retry import (
            split_batch_half, with_retry,
        )
        fused = self._fused_stage_child(ctx)
        if fused is not None:
            self.metrics[METRIC_FUSED_OPS].add(len(fused.steps) + 1)
            from spark_rapids_tpu.exec import stage as _stage
            _stage._bump_global("stages", 1)
            _stage._bump_global("fused_ops", len(fused.steps) + 1)
            source = fused.children[0]
        else:
            source = self.children[0]
        parts: List[List[ColumnarBatch]] = [
            [] for _ in range(self.num_partitions)]
        rr = 0
        for pid_ord, batch in enumerate(
                source.execute_columnar(ctx)):
            with self.metrics.timed(METRIC_TOTAL_TIME):
                if self.num_partitions == 1 or self.mode == "single":
                    parts[0].append(batch)
                    continue
                if fused is not None:
                    # stage steps + key hash + permutation in ONE
                    # dispatch; splitting is per-row sound unless a
                    # step is nondeterministic (row-position seeded)
                    split = None if fused.nondeterministic \
                        else split_batch_half
                    pieces_iter = with_retry(
                        lambda b: partition_batch_fused(
                            b, fused, self.keys,
                            self.num_partitions, pid_ord,
                            metrics=self.metrics),
                        batch, ctx, split=split)
                    n_disp = 0
                    for pieces in pieces_iter:
                        n_disp += 1
                        for p, piece in enumerate(pieces):
                            if piece is not None:
                                parts[p].append(piece)
                    self.metrics[METRIC_STAGE_DISPATCHES].add(n_disp)
                    _stage._bump_global("dispatches", n_disp)
                    continue
                rr0 = rr
                rr += batch.num_rows
                # hash assignment is per-row -> row-split halves
                # partition identically; round-robin depends on the
                # batch-global row offset, so it only spill-retries
                for pieces in with_retry(
                        lambda b: partition_batch(
                            b, self.num_partitions, self.keys,
                            self.mode, rr_start=rr0),
                        batch, ctx,
                        split=(split_batch_half
                               if self.mode == "hash" else None)):
                    for p, piece in enumerate(pieces):
                        if piece is not None:
                            parts[p].append(piece)
        self._record_partition_stats(parts)
        return parts

    def _record_partition_stats(self, parts) -> None:
        """Per-partition byte estimates from host-known row counts (the
        counts already crossed in the partition kernel's sync, so this
        is pure host arithmetic — no extra link round trip).  Feeds the
        ``shufflePartitionBytes`` metric, the process-wide AQE stats
        object bench.py surfaces, and AQE replanning."""
        from spark_rapids_tpu.exec.aqe import est_batch_bytes
        sizes = [sum(est_batch_bytes(b) for b in bucket)
                 for bucket in parts]
        self.last_partition_bytes = sizes
        record_partition_sizes(self.metrics, sizes)

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        if self.mode == "range" and self.num_partitions > 1:
            return self._count_output(self._execute_range(ctx))

        def gen():
            for bucket in self._partition_buckets(ctx):
                if not bucket:
                    continue
                yield bucket[0] if len(bucket) == 1 else \
                    concat_batches(bucket, self.output_schema)
        return self._count_output(gen())
