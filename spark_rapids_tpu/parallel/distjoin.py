"""Mesh-sharded broadcast join fused into the distributed aggregate.

Reference pipeline: GpuBroadcastHashJoinExec.scala:83 feeding
GpuHashAggregateExec — build side broadcast to every executor, stream side
partitioned, then a shuffle for the aggregation.

TPU-native design (the scaling-book "replicated small operand" layout):
the build table is REPLICATED to every device (``shard_map`` in_spec
``P()``), the stream side is sharded over the data axis, and the join is
a pure gather — probe each stream row's key hash against the replicated
sorted build hashes with ``searchsorted``, verify equality over a static
candidate window, gather the matched build row.  No collective moves any
join data at all; only the post-aggregation exchange (all_to_all of
partial groups, distagg.py) touches the interconnect.  The whole
join+groupby compiles to ONE SPMD program.

The build side must be a dimension table with UNIQUE join keys (checked at
construction) — exactly the shape the planner broadcasts."""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from spark_rapids_tpu.compile.service import engine_jit
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import bucket_capacity
from spark_rapids_tpu.columnar.dtypes import Field, Schema
from spark_rapids_tpu.exec.joins import (
    _compile_build, _hash_keys, _keys_equal,
)
from spark_rapids_tpu.exprs.base import (
    ColVal, EvalContext, Expression, _batch_signature, _flatten_batch,
)
from spark_rapids_tpu.parallel.distagg import (
    DistributedAggregate, _bucket_scatter,
)
from spark_rapids_tpu.parallel.mesh import (
    DATA_AXIS, data_mesh, gather_stacked, mesh_key, mesh_program, phase,
    planes_signature, shard_table,
)

# hash-collision probe window: candidates examined per stream row; with
# unique build keys only hash collisions ever add candidates
_PROBE_WINDOW = 4


class DistributedBroadcastJoinAggregate(DistributedAggregate):
    """INNER join (sharded stream x replicated unique-key build) fused
    with a groupby aggregation over the joined schema.

    ``groupings``/``aggregates`` bind against the JOINED column space:
    stream columns first, then build columns."""

    def __init__(self, build_batch: ColumnarBatch,
                 stream_keys: Sequence[Expression],
                 build_keys: Sequence[Expression],
                 groupings: Sequence[Expression],
                 aggregates: Sequence[Expression],
                 mesh=None, n_devices: int = None):
        self.build_batch = build_batch
        self.stream_keys = list(stream_keys)
        self.build_keys = list(build_keys)
        b_cap = build_batch.capacity
        b_rows = build_batch.num_rows

        # unique-key check (host-side, once); string keys compare by the
        # (length, chars) pair, not the lengths-only data plane
        b_ctx = EvalContext([ColVal(c.data, c.validity, c.chars)
                             for c in build_batch.columns],
                            jnp.int32(b_rows), b_cap)
        _, _, bk_cvs = _hash_keys(self.build_keys, b_ctx)
        if b_rows:
            key_cols = []
            for cv in bk_cvs:
                key_cols.append(
                    np.asarray(cv.data)[:b_rows].reshape(b_rows, -1))
                if cv.chars is not None:
                    key_cols.append(np.asarray(cv.chars)[:b_rows]
                                    .astype(np.int64))
            stacked = np.concatenate(key_cols, axis=1)
            if len(np.unique(stacked, axis=0)) != b_rows:
                raise ValueError(
                    "distributed broadcast join requires unique build-side "
                    "keys (dimension-table shape)")

        # sorted hash index for the probe (same build kernel the
        # single-chip join uses); the pre-evaluated build KEY columns ride
        # along in `extra` so the SPMD program never re-hashes them
        keys_key = (tuple(e.key() for e in self.build_keys), "dist")
        b_flat = _flatten_batch(build_batch)
        build_fn = _compile_build(keys_key, self.build_keys,
                                  _batch_signature(build_batch), b_cap)
        sorted_h, perm_b, _run_len, _max_run, _klo, _khi = build_fn(
            b_flat, jnp.int32(b_rows))
        bk_layout = [(cv.chars is not None) for cv in bk_cvs]
        bk_flat = tuple(
            a for cv in bk_cvs
            for a in (cv.data, cv.validity, cv.chars) if a is not None)
        extra = tuple(a for t in b_flat for a in t if a is not None) + \
            bk_flat + (sorted_h, perm_b)
        self._extra = extra
        self._b_layout = [(c.chars is not None) for c in
                          build_batch.columns]
        self._b_cap = b_cap

        stream_keys_ = self.stream_keys
        b_layout = self._b_layout

        def prelude(flat_cols, num_rows, ext, cap):
            # unpack replicated build arrays
            it = iter(ext)
            b_cols = []
            for has_chars in b_layout:
                data = next(it)
                valid = next(it)
                chars = next(it) if has_chars else None
                b_cols.append(ColVal(data, valid, chars))
            bk_cvs2 = []
            for has_chars in bk_layout:
                data = next(it)
                valid = next(it)
                chars = next(it) if has_chars else None
                bk_cvs2.append(ColVal(data, valid, chars))
            s_h, p_b = ext[-2], ext[-1]

            s_cvs = [ColVal(*t) for t in flat_cols]
            ctx = EvalContext(s_cvs, num_rows, cap)
            h, kvalid, sk_cvs = _hash_keys(stream_keys_, ctx)
            live = jnp.arange(cap) < num_rows

            lo = jnp.searchsorted(s_h, h, side="left").astype(jnp.int32)
            hi = jnp.searchsorted(s_h, h, side="right").astype(jnp.int32)
            matched = jnp.zeros(cap, jnp.bool_)
            bi = jnp.zeros(cap, jnp.int32)
            for k in range(_PROBE_WINDOW):
                cand = jnp.clip(lo + k, 0, b_cap - 1)
                in_range = (lo + k) < hi
                brow = jnp.take(p_b, cand)
                eq = in_range
                for e, scv, bcv in zip(stream_keys_, sk_cvs, bk_cvs2):
                    bg = ColVal(
                        jnp.take(bcv.data, brow, axis=0),
                        jnp.take(bcv.validity, brow, axis=0),
                        None if bcv.chars is None else
                        jnp.take(bcv.chars, brow, axis=0))
                    eq = eq & bg.validity & _keys_equal(scv, bg, e.dtype)
                first = eq & ~matched
                bi = jnp.where(first, brow, bi)
                matched = matched | eq
            joined_live = live & kvalid & matched

            out = list(flat_cols)
            for cv in b_cols:
                data = jnp.take(cv.data, bi, axis=0)
                valid = jnp.take(cv.validity, bi, axis=0) & joined_live
                chars = None if cv.chars is None else \
                    jnp.take(cv.chars, bi, axis=0)
                out.append((data, valid, chars))
            return out, joined_live

        super().__init__(groupings, aggregates, mesh=mesh,
                         n_devices=n_devices, prelude=prelude)

    def run(self, stream_batch: ColumnarBatch) -> ColumnarBatch:
        return super().run(stream_batch, extra=self._extra)


# ---------------------------------------------------------------------------
# Repartition (shuffled) hash join over the mesh
# ---------------------------------------------------------------------------

# The traced pieces are functions of their arguments alone, so a program in
# the process-wide memo holds no ``DistributedHashJoin`` and reads nothing
# its key does not name.


def _exchange_side(n_dev: int, flat_cols, num_rows, key_exprs, cap):
    """Per-device: hash-partition the local shard by join-key hash
    and all_to_all it; returns (merged col planes, live mask, key
    hash, keys-valid) at n_dev*cap rows."""
    cols = [ColVal(*t) for t in flat_cols]
    ctx = EvalContext(cols, num_rows, cap)
    h, kvalid, _ = _hash_keys(key_exprs, ctx)
    live = jnp.arange(cap) < num_rows
    pid = (h.astype(jnp.uint64) % jnp.uint64(n_dev)).astype(jnp.int32)
    pid = jnp.where(live, pid, n_dev)
    arrs: List[jnp.ndarray] = [h, kvalid]
    layout = []
    for cv in cols:
        arrs.append(cv.data)
        arrs.append(cv.validity)
        layout.append(cv.chars is not None)
        if cv.chars is not None:
            arrs.append(cv.chars)
    bufs, live_buf = _bucket_scatter(arrs, pid, n_dev, cap)
    recv = [jax.lax.all_to_all(b, DATA_AXIS, split_axis=0,
                               concat_axis=0, tiled=True)
            for b in bufs]
    recv_live = jax.lax.all_to_all(live_buf, DATA_AXIS, split_axis=0,
                                   concat_axis=0, tiled=True)
    flat = [r.reshape((n_dev * cap,) + r.shape[2:]) for r in recv]
    mask = recv_live.reshape(-1)
    h_m = flat[0]
    kv_m = flat[1] & mask
    out_cols = []
    i = 2
    for has_chars in layout:
        data = flat[i]; i += 1
        valid = flat[i] & mask; i += 1
        chars = None
        if has_chars:
            chars = flat[i]; i += 1
        out_cols.append((data, valid, chars))
    return out_cols, mask, h_m, kv_m


def _local_probe(h_l, kv_l, mask_l, h_r, kv_r, mask_r):
    """Build over received right hashes, count candidates per left
    row; returns (counts int64, lo, sorted_h, perm, run_len)."""
    from spark_rapids_tpu.exec.sortkeys import bitonic_lex_sort
    from spark_rapids_tpu.exec.joins import _left_search, _run_lengths
    hb = jnp.where(mask_r & kv_r, h_r, jnp.iinfo(jnp.int64).max)
    # pad to a power of two for the bitonic network: recv size is
    # n_dev * cap and the mesh width need not be a power of two
    pad_n = bucket_capacity(hb.shape[0])
    if pad_n != hb.shape[0]:
        hb = jnp.concatenate(
            [hb, jnp.full(pad_n - hb.shape[0],
                          jnp.iinfo(jnp.int64).max, hb.dtype)])
    sorted_h, perm = bitonic_lex_sort([hb])
    run_len = _run_lengths(sorted_h)
    lo = _left_search(sorted_h, h_l)
    n = sorted_h.shape[0]
    loc = jnp.clip(lo, 0, n - 1)
    present = (lo < n) & (jnp.take(sorted_h, loc) == h_l)
    runs = jnp.where(present, jnp.take(run_len, loc), 0)
    usable = mask_l & kv_l
    counts = jnp.where(usable, runs, 0).astype(jnp.int64)
    return counts, lo, sorted_h, perm


class DistributedHashJoin:
    """Both sides hash-partitioned over the mesh with ``all_to_all``,
    then each device joins its key range locally — the fact-fact join
    shape (reference GpuShuffledHashJoinExec.scala:58-137 over
    GpuShuffleExchangeExec; TPCx-BB q16/q24).

    Static-shape two-pass design: pass 1 (one SPMD program) exchanges
    both sides and COUNTS the verified candidate pairs per device — the
    only host sync of the join; pass 2 re-runs the exchange (pure ICI,
    recomputed inside the same XLA program rather than staged through
    HBM) and expands/gathers at the bucketed max per-device count.
    Because a key's rows all land on one device, outer/semi/anti
    semantics are locally complete: unmatched rows are emitted by the
    device that owns the key.

    Both jitted programs live in the process-wide memo of mesh programs
    (``mesh.mesh_program``) under the mesh's chips, both sides' key
    expressions, the join type, the capacities and both sides' plane
    signatures: the object holds the schemas and their names, the memo
    the programs, and a join built by a later plan runs what an earlier
    plan compiled.
    """

    def __init__(self, left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression],
                 left_schema: Schema, right_schema: Schema,
                 join_type: str = "inner", mesh=None,
                 n_devices: int = None):
        if join_type not in ("inner", "left", "right", "full", "semi",
                             "anti"):
            raise ValueError(f"unsupported join type {join_type}")
        self.mesh = mesh if mesh is not None else data_mesh(n_devices)
        self.n_dev = self.mesh.devices.size
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.left_schema = left_schema
        self.right_schema = right_schema
        self.join_type = join_type
        lf = list(left_schema.fields)
        rf = list(right_schema.fields)
        if join_type in ("right", "full"):
            lf = [Field(f.name, f.dtype, True) for f in lf]
        if join_type in ("left", "full"):
            rf = [Field(f.name, f.dtype, True) for f in rf]
        if join_type in ("semi", "anti"):
            self.output_schema = left_schema
        else:
            self.output_schema = Schema(lf + rf)

    def _program(self, name: str, build, caps: tuple, planes_sig: tuple,
                 *also):
        """The program ``name`` at ``caps`` from the memo of mesh
        programs; ``also`` is what its trace reads besides the keys, the
        capacities and the planes (the join program: the join type; the
        count program counts alike for every type)."""
        key = (name, mesh_key(self.mesh),
               tuple(e.key() for e in self.left_keys),
               tuple(e.key() for e in self.right_keys),
               caps, planes_sig) + also
        return mesh_program(key, lambda: engine_jit(
            build(*caps), family="exchange", name=name))

    def _build_count_step(self, lcap: int, rcap: int):
        n_dev = self.n_dev
        lkeys, rkeys = self.left_keys, self.right_keys

        def device_step(l_flat, l_rows, r_flat, r_rows):
            l_flat = [tuple(None if a is None else a[0] for a in t)
                      for t in l_flat]
            r_flat = [tuple(None if a is None else a[0] for a in t)
                      for t in r_flat]
            _, mask_l, h_l, kv_l = _exchange_side(
                n_dev, l_flat, l_rows[0], lkeys, lcap)
            _, mask_r, h_r, kv_r = _exchange_side(
                n_dev, r_flat, r_rows[0], rkeys, rcap)
            counts, _, _, _ = _local_probe(
                h_l, kv_l, mask_l, h_r, kv_r, mask_r)
            return jnp.sum(counts)[None]

        return shard_map(
            device_step, mesh=self.mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                      P(DATA_AXIS)),
            out_specs=P(DATA_AXIS))

    def _build_join_step(self, lcap: int, rcap: int, out_cap: int):
        from spark_rapids_tpu.utils.pscan import (
            masked_positions, prefix_sum,
        )
        lkeys, rkeys = self.left_keys, self.right_keys
        jt = self.join_type
        n_dev = self.n_dev
        recv_l = n_dev * lcap
        recv_r = n_dev * rcap

        def device_step(l_flat, l_rows, r_flat, r_rows):
            l_flat = [tuple(None if a is None else a[0] for a in t)
                      for t in l_flat]
            r_flat = [tuple(None if a is None else a[0] for a in t)
                      for t in r_flat]
            l_cols, mask_l, h_l, kv_l = _exchange_side(
                n_dev, l_flat, l_rows[0], lkeys, lcap)
            r_cols, mask_r, h_r, kv_r = _exchange_side(
                n_dev, r_flat, r_rows[0], rkeys, rcap)
            counts, lo, sorted_h, perm = _local_probe(
                h_l, kv_l, mask_l, h_r, kv_r, mask_r)

            inclusive = prefix_sum(counts)
            exclusive = inclusive - counts
            total = inclusive[-1]

            # candidate -> left row (delta-scatter construction, same as
            # the single-chip expand)
            counts32 = counts.astype(jnp.int32)
            nonempty = counts32 > 0
            comp = masked_positions(nonempty, recv_l, recv_l)
            comp_prev = jnp.concatenate(
                [jnp.zeros(1, comp.dtype), comp[:-1]])
            delta_vals = jnp.where(comp < recv_l, comp - comp_prev, 0)
            starts = jnp.take(exclusive,
                              jnp.clip(comp, 0, recv_l - 1))
            pos_t = jnp.where(comp < recv_l, starts,
                              out_cap).astype(jnp.int32)
            delta = jnp.zeros(out_cap, jnp.int32).at[pos_t].add(
                delta_vals, mode="drop")
            i = jnp.clip(prefix_sum(delta), 0, recv_l - 1)
            kk = jnp.arange(out_cap, dtype=jnp.int64)
            j_off = kk - jnp.take(exclusive, i)
            j = jnp.take(lo, i).astype(jnp.int64) + j_off
            j = jnp.clip(j, 0, recv_r - 1).astype(jnp.int32)
            brow = jnp.take(perm, j)
            keep = kk < total

            # verify true key equality on the exchanged columns
            lc = [ColVal(*t) for t in l_cols]
            rc = [ColVal(*t) for t in r_cols]
            lctx = EvalContext(lc, jnp.int32(recv_l), recv_l)
            rctx = EvalContext(rc, jnp.int32(recv_r), recv_r)
            for le, re_ in zip(lkeys, rkeys):
                lcv = le.emit(lctx)
                rcv = re_.emit(rctx)
                lg = ColVal(jnp.take(lcv.data, i, axis=0),
                            jnp.take(lcv.validity, i, axis=0),
                            None if lcv.chars is None else
                            jnp.take(lcv.chars, i, axis=0))
                rg = ColVal(jnp.take(rcv.data, brow, axis=0),
                            jnp.take(rcv.validity, brow, axis=0),
                            None if rcv.chars is None else
                            jnp.take(rcv.chars, brow, axis=0))
                keep = keep & lg.validity & rg.validity & \
                    _keys_equal(lg, rg, le.dtype)
            kept = jnp.sum(keep.astype(jnp.int32))
            m_left = jax.ops.segment_sum(keep.astype(jnp.int32), i,
                                         num_segments=recv_l)
            m_right = jax.ops.segment_sum(keep.astype(jnp.int32), brow,
                                          num_segments=recv_r)

            def compact_pairs():
                idx = masked_positions(keep, out_cap, out_cap - 1)
                si = jnp.take(i, idx)
                bi = jnp.take(brow, idx)
                pos_live = jnp.arange(out_cap) < kept
                outs = []
                for (d, v, ch) in l_cols:
                    outs.append((jnp.take(d, si, axis=0),
                                 jnp.take(v, si, axis=0) & pos_live,
                                 None if ch is None else
                                 jnp.take(ch, si, axis=0)))
                for (d, v, ch) in r_cols:
                    outs.append((jnp.take(d, bi, axis=0),
                                 jnp.take(v, bi, axis=0) & pos_live,
                                 None if ch is None else
                                 jnp.take(ch, bi, axis=0)))
                return outs

            def select_left(sel_mask, n_sel):
                idx = masked_positions(sel_mask, recv_l, recv_l - 1)
                pos_live = jnp.arange(recv_l) < n_sel
                outs = []
                for (d, v, ch) in l_cols:
                    outs.append((jnp.take(d, idx, axis=0),
                                 jnp.take(v, idx, axis=0) & pos_live,
                                 None if ch is None else
                                 jnp.take(ch, idx, axis=0)))
                return outs

            def lead(block):
                return tuple((d[None], v[None],
                              None if ch is None else ch[None])
                             for (d, v, ch) in block)

            if jt in ("semi", "anti"):
                want = (m_left > 0) if jt == "semi" else (m_left == 0)
                sel = mask_l & want
                n_sel = jnp.sum(sel.astype(jnp.int32))
                ns1 = jnp.stack([n_sel])
                return (ns1[None], (lead(select_left(sel, n_sel)),))

            outs = compact_pairs()
            blocks = [(kept, outs)]
            if jt in ("left", "full"):
                un = mask_l & (m_left == 0)
                n_un = jnp.sum(un.astype(jnp.int32))
                lun = select_left(un, n_un)
                # right side all-null
                for (d, v, ch) in r_cols:
                    lun.append((
                        jnp.zeros((recv_l,) + d.shape[1:], d.dtype),
                        jnp.zeros(recv_l, jnp.bool_),
                        None if ch is None else
                        jnp.zeros((recv_l,) + ch.shape[1:], ch.dtype)))
                blocks.append((n_un, lun))
            if jt in ("right", "full"):
                unb = mask_r & (m_right == 0)
                n_unb = jnp.sum(unb.astype(jnp.int32))
                idx = masked_positions(unb, recv_r, recv_r - 1)
                pos_live = jnp.arange(recv_r) < n_unb
                run_block = []
                for (d, v, ch) in l_cols:
                    run_block.append((
                        jnp.zeros((recv_r,) + d.shape[1:], d.dtype),
                        jnp.zeros(recv_r, jnp.bool_),
                        None if ch is None else
                        jnp.zeros((recv_r,) + ch.shape[1:], ch.dtype)))
                for (d, v, ch) in r_cols:
                    run_block.append((jnp.take(d, idx, axis=0),
                                      jnp.take(v, idx, axis=0) & pos_live,
                                      None if ch is None else
                                      jnp.take(ch, idx, axis=0)))
                blocks.append((n_unb, run_block))
            ns = jnp.stack([b[0].astype(jnp.int32) for b in blocks])
            return (ns[None], tuple(lead(b[1]) for b in blocks))

        return shard_map(
            device_step, mesh=self.mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                      P(DATA_AXIS)),
            out_specs=(P(DATA_AXIS), P(DATA_AXIS)))

    # -- host driver --------------------------------------------------------

    def run_sharded(self, left: ColumnarBatch, right: ColumnarBatch):
        """The exchange half: shard both sides, count verified pairs
        (pass 1, the join's one host sync), and run the exchange+join
        step (pass 2).  Returns host-synced per-device block counts and
        the still-device-resident stacked output blocks — both
        ``all_to_all`` exchanges run with zero ``device_pull``s; only
        ``gather`` crosses the link."""
        sl, cl, lcap = shard_table(left, self.n_dev)
        sr, cr, rcap = shard_table(right, self.n_dev)
        return self.run_stacked(sl, jnp.asarray(cl, jnp.int32), lcap,
                                sr, jnp.asarray(cr, jnp.int32), rcap)

    def run_mixed(self, left, right):
        """Mixed-ingest driver: each side is either a ColumnarBatch
        (host-split here via ``shard_table`` — the sanctioned drained
        fallback split) or an already-stacked ``(planes, counts, cap)``
        triple from the sharded scan ingest."""

        def side(x):
            if isinstance(x, tuple):
                return x
            s, c, cap = shard_table(x, self.n_dev)
            return s, jnp.asarray(c, jnp.int32), cap

        sl, jl, lcap = side(left)
        sr, jr, rcap = side(right)
        return self.run_stacked(sl, jl, lcap, sr, jr, rcap)

    def run_stacked(self, sl, jl, lcap: int, sr, jr, rcap: int):
        """Count + join over already-stacked per-side planes: either
        side may arrive host-split (``shard_table``) or device-resident
        from the sharded scan ingest (parallel/shardscan.py), including
        mixed — each side's arrays just feed the same SPMD programs."""
        with phase("collective_us"):
            sig = (planes_signature(sl), planes_signature(sr))
            count = self._program("mesh_join_count",
                                  self._build_count_step, (lcap, rcap), sig)
            totals = np.asarray(count(tuple(sl), jl, tuple(sr), jr))
            out_cap = bucket_capacity(max(1, int(totals.max())))
            join = self._program("mesh_join", self._build_join_step,
                                 (lcap, rcap, out_cap), sig,
                                 self.join_type)
            ns, blocks = join(tuple(sl), jl, tuple(sr), jr)
            return np.asarray(ns), blocks  # ns: (n_dev, n_blocks)

    def gather(self, ns: np.ndarray, blocks,
               parallel_pull: bool = False) -> ColumnarBatch:
        """The collection half: pull every output block's stacked planes
        (one ``device_pull`` per block via ``gather_stacked``, or one
        concurrent pull per chip per block with ``parallel_pull``) and
        concatenate in block order."""
        from spark_rapids_tpu.exec.coalesce import concat_batches
        jt = self.join_type
        l_dtypes = [f.dtype for f in self.left_schema]
        r_dtypes = [f.dtype for f in self.right_schema]
        if jt in ("semi", "anti"):
            return gather_stacked(list(blocks[0]), ns[:, 0],
                                  l_dtypes, self.output_schema,
                                  parallel_pull=parallel_pull)
        out_dtypes = l_dtypes + r_dtypes
        parts = []
        for bi, block in enumerate(blocks):
            counts = ns[:, bi]
            if counts.sum() == 0 and bi > 0:
                continue
            parts.append(gather_stacked(
                list(block), counts, out_dtypes, self.output_schema,
                parallel_pull=parallel_pull))
        out = parts[0] if len(parts) == 1 else concat_batches(parts)
        out.schema = self.output_schema
        return out

    def run(self, left: ColumnarBatch,
            right: ColumnarBatch) -> ColumnarBatch:
        ns, blocks = self.run_sharded(left, right)
        return self.gather(ns, blocks)
