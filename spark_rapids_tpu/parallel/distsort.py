"""Distributed sort: range exchange + local sort in ONE SPMD program.

Reference pipeline: global sort distributes by range partitioning
(GpuRangePartitioner.scala sampled bounds + GpuShuffleExchangeExec), then
each task sorts its range locally (GpuSortExec) — bounds sampling on the
driver, shuffle over UCX, per-task cuDF sort.

TPU-native design: the host samples sort-key bounds once (the same
order-preserving int-key machinery the single-chip exchange uses), then a
single ``shard_map`` program per mesh does
  1. per-device sort-key computation (colval_sort_keys),
  2. per-device range partition: destination = #bounds < key tuple,
  3. ``jax.lax.all_to_all`` over ICI,
  4. per-device local sort of the received rows (variadic ``lax.sort``).
Concatenating the device shards in mesh order IS the global sort — no
merge pass, no host round trip between exchange and sort.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from spark_rapids_tpu.compile.service import engine_jit
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import bucket_capacity
from spark_rapids_tpu.columnar.dtypes import STRING, Schema
from spark_rapids_tpu.exec.exchange import (
    compute_range_bounds, _observed_key_width,
)
from spark_rapids_tpu.exec.sortkeys import colval_sort_keys, sort_permutation
from spark_rapids_tpu.exprs.base import (
    ColVal, EvalContext, Expression, _batch_signature, _flatten_batch,
)
from spark_rapids_tpu.parallel.distagg import _bucket_scatter
from spark_rapids_tpu.parallel.mesh import (
    DATA_AXIS, data_mesh, mesh_key, mesh_program, phase, planes_signature,
    shard_table,
)


def _emit_keys(orders, flat_cols, num_rows, cap: int, pad: int):
    cols = [ColVal(*t) for t in flat_cols]
    ctx = EvalContext(cols, num_rows, cap)
    keys = []
    for e, asc, nf in orders:
        cv = e.emit(ctx)
        if e.dtype == STRING and cv.chars is not None and \
                cv.chars.shape[1] < pad:
            cv = ColVal(cv.data, cv.validity, jnp.pad(
                cv.chars, ((0, 0), (0, pad - cv.chars.shape[1]))))
        keys.extend(colval_sort_keys(cv, e.dtype, asc, nf))
    return keys


def _range_pids(keys, bounds, live, n_dev: int) -> jnp.ndarray:
    """Destination device = #bounds lexicographically < key tuple (the
    same compare the single-chip range exchange uses); dead rows -> n_dev
    (dropped by the scatter)."""
    cap = live.shape[0]
    nb = n_dev - 1
    eq = jnp.ones((cap, nb), bool)
    gt = jnp.zeros((cap, nb), bool)
    for k, b in zip(keys, bounds):
        kc = k[:, None]
        br = b[None, :]
        gt = gt | (eq & (kc > br))
        eq = eq & (kc == br)
    pid = jnp.sum(gt, axis=1).astype(jnp.int32)
    return jnp.where(live, pid, n_dev)


class DistributedSort:
    """Compile + run a global sort sharded over a 1-D data mesh.  The
    jitted step lives in the process-wide memo of mesh programs
    (``mesh.mesh_program``), not in the object."""

    def __init__(self, orders: Sequence[Tuple[Expression, bool, bool]],
                 schema: Schema, mesh=None, n_devices: int = None,
                 pad_width: int = 512):
        self.mesh = mesh if mesh is not None else data_mesh(n_devices)
        self.n_dev = self.mesh.devices.size
        self.orders = list(orders)
        self.schema = schema
        # configured MAXIMUM string-key pad; each run derives its actual
        # pad from this (never from a previous run's observation, which
        # would ratchet the width down across runs)
        self.pad_max = pad_width

    def _build_step(self, cap: int, pad: int):
        n_dev = self.n_dev
        orders = self.orders
        recv_cap = bucket_capacity(n_dev * cap)

        def device_step(flat_cols, num_rows, bounds):
            flat_cols = [tuple(None if a is None else a[0] for a in t)
                         for t in flat_cols]
            num_rows = num_rows[0]
            live = jnp.arange(cap) < num_rows

            # 1-2. keys + range destination
            keys = _emit_keys(orders, flat_cols, num_rows, cap, pad)
            pid = _range_pids(keys, bounds, live, n_dev)

            flat_arrays: List[jnp.ndarray] = []
            layout = []
            for (data, valid, chars) in flat_cols:
                flat_arrays.append(data)
                flat_arrays.append(valid)
                layout.append(chars is not None)
                if chars is not None:
                    flat_arrays.append(chars)
            bufs, live_buf = _bucket_scatter(flat_arrays, pid, n_dev, cap)

            # 3. exchange over ICI
            recv = [jax.lax.all_to_all(b, DATA_AXIS, split_axis=0,
                                       concat_axis=0, tiled=True)
                    for b in bufs]
            recv_live = jax.lax.all_to_all(
                live_buf, DATA_AXIS, split_axis=0, concat_axis=0,
                tiled=True)
            mask = jnp.zeros(recv_cap, jnp.bool_)
            mask = mask.at[:n_dev * cap].set(recv_live.reshape(-1))

            def pad_full(a):
                flat = a.reshape((n_dev * cap,) + a.shape[2:])
                out = jnp.zeros((recv_cap,) + flat.shape[1:], flat.dtype)
                return out.at[:n_dev * cap].set(flat)

            merged = []
            i = 0
            for has_chars in layout:
                data = pad_full(recv[i]); i += 1
                valid = pad_full(recv[i]) & mask; i += 1
                chars = pad_full(recv[i]) if has_chars else None
                if has_chars:
                    i += 1
                merged.append((data, valid, chars))
            n_local = jnp.sum(mask.astype(jnp.int32))

            # 4. local sort of the received range
            keys2 = _emit_keys(orders, merged, jnp.int32(recv_cap),
                               recv_cap, pad)
            # dead rows must sort last regardless of key content
            perm = sort_permutation(keys2, recv_cap, live_first=mask)
            outs = []
            for (data, valid, chars) in merged:
                d = jnp.take(data, perm, axis=0)
                v = jnp.take(valid, perm, axis=0)
                c = None if chars is None else \
                    jnp.take(chars, perm, axis=0)
                outs.append((d[None], v[None],
                             None if c is None else c[None]))
            return n_local[None], tuple(outs)

        return shard_map(
            device_step, mesh=self.mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS), P()),
            out_specs=(P(DATA_AXIS), P(DATA_AXIS)))

    def _step(self, cap: int, pad: int, planes_sig: tuple):
        # keyed on (capacity, pad): a cached step compiled for one pad
        # must never serve bounds computed at another
        key = ("sort", mesh_key(self.mesh),
               tuple((e.key(), asc, nf) for e, asc, nf in self.orders),
               cap, pad, planes_sig)
        return mesh_program(
            key, lambda: engine_jit(self._build_step(cap, pad),
                                    family="exchange", name="mesh_sort"))

    # -- host driver --------------------------------------------------------

    def _bounds(self, batch: ColumnarBatch, sample_max: int = 10_000):
        """Host-side sampled bound tuples over the whole input (the
        GpuRangePartitioner sketch)."""
        from spark_rapids_tpu.exec.exchange import _compile_keys_kernel
        orders_key = tuple((e.key(), a, nf) for e, a, nf in self.orders)
        pad = _observed_key_width(self.orders, [batch], self.pad_max)
        fn = _compile_keys_kernel(orders_key, self.orders,
                                  _batch_signature(batch),
                                  batch.capacity, pad)
        keys = fn(_flatten_batch(batch), jnp.int32(batch.num_rows))
        n = batch.num_rows
        take = min(n, sample_max)
        idx = np.unique(np.linspace(0, max(n - 1, 0), max(take, 1))
                        .astype(np.int64))
        jidx = jnp.asarray(idx)
        # ONE pull for every key's sample (device_pull: counted,
        # fault-injectable) — per-key conversions each pay a round trip
        from spark_rapids_tpu.columnar.transfer import device_pull
        key_rows = [tuple(np.asarray(a) for a in device_pull(
            tuple(jnp.take(k, jidx) for k in keys)))]
        return compute_range_bounds(key_rows, self.n_dev,
                                    sample_max=sample_max), pad

    def run_sharded(self, batch: ColumnarBatch):
        """The exchange half: sample bounds, shard, and run the SPMD
        range-exchange + local-sort step.  Returns host-synced
        per-device received-row counts plus the still-device-resident
        stacked output planes (``None`` planes signal a degenerate
        input — empty or unboundable — whose rows pass through
        unsorted-by-exchange; ``run`` handles both).  The bounds sample
        is the pipeline's one pre-gather ``device_pull``; the exchange
        itself issues none."""
        if batch.num_rows == 0:
            return None, None
        bounds, pad = self._bounds(batch)
        if bounds is None:
            return None, None
        stacked, counts, cap = shard_table(batch, self.n_dev)
        return self.run_stacked(stacked,
                                jnp.asarray(counts, jnp.int32), cap,
                                bounds, pad)

    def run_stacked(self, stacked, counts, cap: int, bounds, pad: int):
        """Run the range-exchange + local-sort step over already-
        stacked planes (host-split or the sharded scan ingest's
        device-resident global arrays) with pre-computed ``bounds`` —
        ``_bounds`` for a drained batch, ``sample_bounds_sharded`` for
        per-shard device-resident views."""
        with phase("collective_us"):
            jb = tuple(jnp.asarray(b) for b in bounds)
            step = self._step(cap, pad, planes_signature(stacked))
            n_local, out_cols = step(tuple(stacked), counts, jb)
            return np.asarray(n_local), out_cols

    def sample_bounds_sharded(self, views: List[ColumnarBatch],
                              sample_max: int = 10_000):
        """Per-shard bound sampling for device-resident shard views
        (docs/sharded_scan.md): one tiny pull syncs the per-shard live
        counts (cached onto the views), the sample budget is split
        PROPORTIONALLY to each shard's live rows — pooled samples feed
        the unweighted ``compute_range_bounds``, so equal per-shard
        counts would let a 1k-row shard's keys outvote a 500k-row
        shard's ~400:1 and funnel the big shard into one partition —
        then each shard's keys compute ON ITS OWN CHIP and the strided
        sample rows pull for ALL shards in one second ``device_pull``.
        Two small pulls instead of the drained path's full-table drain;
        returns ``(bounds, pad)``; bounds None = degenerate (empty)
        input."""
        from spark_rapids_tpu.exec.exchange import _compile_keys_kernel
        from spark_rapids_tpu.columnar.transfer import device_pull
        from spark_rapids_tpu.columnar.column import LazyRows
        orders_key = tuple((e.key(), a, nf) for e, a, nf in self.orders)
        pad = _observed_key_width(self.orders, views, self.pad_max)
        # pull 1: the per-shard live counts (n_dev scalars), cached on
        # the views so later host reads are free
        counts = device_pull(tuple(b.rows_traced for b in views))
        ns = [int(c) for c in counts]
        for b, n in zip(views, ns):
            rr = b.rows_raw
            if isinstance(rr, LazyRows):
                rr._val = n
        total = sum(ns)
        if total == 0:
            return None, pad
        staged = []
        for b, n in zip(views, ns):
            if n == 0:
                continue
            fn = _compile_keys_kernel(orders_key, self.orders,
                                      _batch_signature(b),
                                      b.capacity, pad)
            keys = fn(_flatten_batch(b), b.rows_traced)
            take = max(1, min(n, (sample_max * n) // total))
            idx = np.unique(np.linspace(0, n - 1, take)
                            .astype(np.int64))
            jidx = jnp.asarray(idx)
            staged.append(tuple(jnp.take(k, jidx) for k in keys))
        # pull 2: every shard's samples in one round trip
        pulled = device_pull(staged)
        key_rows = [tuple(np.asarray(k) for k in sampled)
                    for sampled in pulled]
        return (compute_range_bounds(key_rows, self.n_dev,
                                     sample_max=sample_max), pad)

    def gather(self, n_local: np.ndarray, out_cols,
               parallel_pull: bool = False) -> ColumnarBatch:
        """The collection half: concatenating the device shards in mesh
        order IS the global sort, collected by ``mesh.gather_stacked``
        — one pull for all stacked planes, or one concurrent pull per
        chip with ``parallel_pull`` (docs/sharded_scan.md)."""
        from spark_rapids_tpu.parallel.mesh import gather_stacked
        return gather_stacked(
            list(out_cols), n_local, [f.dtype for f in self.schema],
            self.schema, parallel_pull=parallel_pull)

    def run(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Shard, exchange, sort; concatenate shards in mesh order."""
        n_local, out_cols = self.run_sharded(batch)
        if n_local is None:
            return batch
        return self.gather(n_local, out_cols)
