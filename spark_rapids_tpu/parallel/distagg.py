"""Distributed hash aggregation: one jitted SPMD step per mesh.

Reference pipeline (SURVEY §3.4): partial aggregate -> hash-partition ->
shuffle exchange (UCX peer-to-peer) -> final merge aggregate, orchestrated
by the host across executors (GpuShuffleExchangeExec.scala:60-244,
aggregate.scala:259-460).

TPU-native design: the whole pipeline is ONE ``shard_map`` program —
  1. per-device partial aggregate (the update-phase segmented-sort kernel
     from exec/aggregate.py, traced inline),
  2. per-device hash partition of the partial groups by key hash pmod
     n_dev, scattered into fixed-size per-destination buckets,
  3. ``jax.lax.all_to_all`` moves bucket p to device p over ICI,
  4. per-device merge aggregate over the received partials (non-contiguous
     liveness carried as a mask through the exchange).
XLA compiles partition+collective+merge into a single program; there is no
host round-trip between shuffle and merge, which a NCCL/UCX port could not
achieve.
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
from spark_rapids_tpu.columnar.dtypes import Field, Schema
from spark_rapids_tpu.exec.aggregate import (
    _AggSpec, make_agg_body, unwrap_aggregate,
)
from spark_rapids_tpu.exprs.base import ColVal, Expression
from spark_rapids_tpu.parallel.mesh import (
    DATA_AXIS, data_mesh, mesh_key, mesh_program, phase, planes_signature,
    shard_table,
)
from spark_rapids_tpu.utils.kernel_cache import KernelCache


def _hash_pids(key_cvs: Sequence[ColVal], key_dtypes, n_dev: int,
               live: jnp.ndarray) -> jnp.ndarray:
    """Destination device per row = splitmix64(keys) pmod n_dev; dead rows
    get pid n_dev (out of range -> dropped by the scatter)."""
    from spark_rapids_tpu.exec.joins import _splitmix64, _hash_colval
    acc = jnp.zeros(live.shape[0], jnp.uint64)
    for cv, dt in zip(key_cvs, key_dtypes):
        acc = _splitmix64(acc ^ _hash_colval(cv, dt).astype(jnp.uint64))
    pid = (acc % jnp.uint64(n_dev)).astype(jnp.int32)
    return jnp.where(live, pid, n_dev)


def _bucket_scatter(arrs: List[jnp.ndarray], pid: jnp.ndarray,
                    n_dev: int, bucket: int):
    """Scatter rows into (n_dev, bucket) send buffers by destination.

    Rows are ordered by pid (stable argsort), the slot within a bucket is
    the rank among same-destination rows; out-of-range pids (dead rows)
    are dropped by XLA scatter semantics.  Also returns a liveness buffer
    so the receiver can distinguish real rows from padding.
    """
    cap = pid.shape[0]
    from spark_rapids_tpu.exec.sortkeys import bitonic_lex_sort
    perm = bitonic_lex_sort([pid])[-1]
    pid_s = jnp.take(pid, perm)
    counts = jnp.sum(
        pid_s[None, :] == jnp.arange(n_dev, dtype=jnp.int32)[:, None],
        axis=1)
    offsets = jnp.concatenate(
        [jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
    slot = jnp.arange(cap) - jnp.take(
        offsets, jnp.clip(pid_s, 0, n_dev - 1))
    slot = jnp.clip(slot, 0, bucket - 1)
    outs = []
    for a in arrs:
        a_s = jnp.take(a, perm, axis=0)
        buf = jnp.zeros((n_dev, bucket) + a.shape[1:], a.dtype)
        outs.append(buf.at[pid_s, slot].set(a_s, mode="drop"))
    live_buf = jnp.zeros((n_dev, bucket), jnp.bool_)
    live_buf = live_buf.at[pid_s, slot].set(True, mode="drop")
    return outs, live_buf


class DistributedAggregate:
    """Compile + run a groupby aggregation sharded over a 1-D data mesh.

    ``prelude`` (optional) is a traced hook run per device BEFORE the
    partial aggregate: ``prelude(flat_cols, num_rows, extra, cap) ->
    (new_flat_cols, live_mask)``.  ``extra`` is a tuple of REPLICATED
    arrays (same full value on every device, in_spec ``P()``) — the
    mesh-sharded broadcast join rides this hook, with the broadcast build
    table as the replicated extra.

    The jitted step lives in the process-wide memo of mesh programs
    (``mesh.mesh_program``) under the mesh's chips, ``_AggSpec.key()``,
    the shard capacity and the input planes' signature, so an object
    built by a later plan runs the program an earlier plan compiled.  A
    ``prelude`` is a closure and has no key by value: an object that has
    one stays out of that memo and keeps its steps in one of its own,
    which lives as long as the object does."""

    def __init__(self, groupings: Sequence[Expression],
                 aggregates: Sequence[Expression], mesh=None,
                 n_devices: int = None, prelude=None):
        self.mesh = mesh if mesh is not None else data_mesh(n_devices)
        self.n_dev = self.mesh.devices.size
        self.groupings = list(groupings)
        self.agg_pairs = [unwrap_aggregate(e) for e in aggregates]
        self.spec = _AggSpec(self.groupings, self.agg_pairs)
        self.prelude = prelude
        fields = [Field(g.name, g.dtype, g.nullable) for g in self.groupings]
        fields += [Field(n, f.dtype, f.nullable) for n, f in self.agg_pairs]
        self.output_schema = Schema(fields)
        self._prelude_steps = None if prelude is None else KernelCache(
            "mesh_aggregate_prelude", 4, register=False)

    # -- compiled step ------------------------------------------------------

    def _build_step(self, cap: int):
        """One SPMD step: (stacked flat cols, per-shard counts) ->
        (per-device group counts, stacked key/buffer ColVals)."""
        n_dev = self.n_dev
        spec = self.spec
        merge_cap = bucket_capacity(n_dev * cap)
        update = make_agg_body(spec, "update", cap)
        merge = make_agg_body(spec, "merge", merge_cap)
        key_dtypes = [g.dtype for g in spec.groupings]

        prelude = self.prelude

        def device_step(flat_cols, num_rows, extra):
            # squeeze the leading device axis shard_map leaves on blocks
            flat_cols = [tuple(None if a is None else a[0] for a in t)
                         for t in flat_cols]
            num_rows = num_rows[0]

            live_mask = None
            if prelude is not None:
                flat_cols, live_mask = prelude(flat_cols, num_rows,
                                               extra, cap)

            # 1. local partial aggregate
            n_g, key_outs, buf_outs = update(flat_cols, num_rows,
                                             live_mask=live_mask)
            part_live = jnp.arange(cap) < n_g

            # 2. hash-partition the partial groups
            pid = _hash_pids(key_outs, key_dtypes, n_dev, part_live)
            flat_arrays: List[jnp.ndarray] = []
            layout = []  # (has_chars,) per colval, keys then buffers
            for cv in list(key_outs) + list(buf_outs):
                flat_arrays.append(cv.data)
                flat_arrays.append(
                    cv.validity if cv.validity is not None
                    else jnp.zeros(cap, jnp.bool_))
                layout.append(cv.chars is not None)
                if cv.chars is not None:
                    flat_arrays.append(cv.chars)
            bufs, live_buf = _bucket_scatter(flat_arrays, pid, n_dev, cap)

            # 3. exchange: bucket p of every device lands on device p
            recv = [jax.lax.all_to_all(b, DATA_AXIS, split_axis=0,
                                       concat_axis=0, tiled=True)
                    for b in bufs]
            recv_live = jax.lax.all_to_all(
                live_buf, DATA_AXIS, split_axis=0, concat_axis=0,
                tiled=True)
            mask = jnp.zeros(merge_cap, jnp.bool_)
            mask = mask.at[:n_dev * cap].set(recv_live.reshape(-1))

            def pad(a):
                flat = a.reshape((n_dev * cap,) + a.shape[2:])
                out = jnp.zeros((merge_cap,) + flat.shape[1:], flat.dtype)
                return out.at[:n_dev * cap].set(flat)

            # 4. merge aggregate over received partials
            merged_cols = []
            i = 0
            for has_chars in layout:
                data = pad(recv[i]); i += 1
                valid = pad(recv[i]) & mask; i += 1
                chars = None
                if has_chars:
                    chars = pad(recv[i]); i += 1
                merged_cols.append((data, valid, chars))
            n_out, keys2, bufs2 = merge(
                merged_cols, jnp.int32(merge_cap), live_mask=mask)

            # 5. evaluate: buffers -> final output columns (the
            # evaluateExpression phase, AggregateFunctions.scala:277-530)
            group_live = jnp.arange(merge_cap) < n_out
            finals = []
            i = 0
            bufs2 = list(bufs2)
            for _, f in spec.aggs:
                nbuf = len(f.buffer_dtypes())
                ev = f.evaluate(bufs2[i:i + nbuf])
                i += nbuf
                finals.append(ColVal(ev.data, ev.validity & group_live,
                                     ev.chars))

            # re-add the leading device axis for shard_map stacking
            def lead(x):
                return x[None] if x is not None else None
            out_cols = tuple(
                (lead(cv.data), lead(cv.validity), lead(cv.chars))
                for cv in list(keys2) + finals)
            return n_out[None], out_cols

        return shard_map(
            device_step, mesh=self.mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS), P()),
            out_specs=(P(DATA_AXIS), P(DATA_AXIS)))

    def _step(self, cap: int, planes_sig: tuple):
        key = (cap, planes_sig)
        if self.prelude is None:
            key = ("aggregate", mesh_key(self.mesh), self.spec.key()) + key
        return mesh_program(
            key, lambda: engine_jit(self._build_step(cap),
                                    family="exchange",
                                    name="mesh_aggregate"),
            self._prelude_steps)

    # -- host driver --------------------------------------------------------

    def run_sharded(self, batch: ColumnarBatch, extra: tuple = ()):
        """The exchange half: shard ``batch`` over the mesh and run the
        SPMD step (partial aggregate -> all_to_all -> merge, one XLA
        program).  Returns host-synced per-device group counts plus the
        still-DEVICE-RESIDENT stacked output planes — the counts sync is
        the pipeline's one host round trip before the output gather, so
        callers (exec/meshexec.py) can assert the exchange itself issued
        zero ``device_pull``s and attribute the single gather pull to
        result collection."""
        stacked, counts, cap = shard_table(batch, self.n_dev)
        return self.run_stacked(
            stacked, jnp.asarray(counts, jnp.int32), cap, extra)

    def run_stacked(self, stacked, counts, cap: int, extra: tuple = ()):
        """Run the SPMD step over ALREADY-STACKED input planes: either
        ``shard_table``'s host-split arrays (``run_sharded``) or the
        sharded scan ingest's device-resident global arrays
        (parallel/shardscan.py, docs/sharded_scan.md) — the latter land
        here with every shard committed to its own chip, so the
        exchange program consumes them without any host re-split."""
        with phase("collective_us"):
            step = self._step(cap, planes_signature(stacked))
            n_groups, out_cols = step(tuple(stacked), counts, extra)
            return np.asarray(n_groups), out_cols

    def gather(self, n_groups: np.ndarray, out_cols,
               parallel_pull: bool = False) -> ColumnarBatch:
        """The collection half: device d's first n_groups[d] rows are
        its result groups, collected by ``mesh.gather_stacked`` — one
        ``device_get`` for every stacked plane, or one concurrent pull
        per chip with ``parallel_pull`` (docs/sharded_scan.md)."""
        from spark_rapids_tpu.parallel.mesh import gather_stacked
        return gather_stacked(
            list(out_cols), n_groups,
            [f.dtype for f in self.output_schema],
            self.output_schema, parallel_pull=parallel_pull)

    def run(self, batch: ColumnarBatch,
            extra: tuple = ()) -> ColumnarBatch:
        """Shard ``batch`` over the mesh, run the SPMD step, and gather the
        per-device result groups into one host-side batch.  ``extra`` is
        replicated to every device (broadcast build tables etc.)."""
        n_groups, out_cols = self.run_sharded(batch, extra)
        return self.gather(n_groups, out_cols)
