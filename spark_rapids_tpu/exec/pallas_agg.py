"""Pallas TPU kernel: low-cardinality hash aggregate update phase.

Reference scope: the per-batch ``update`` aggregation the sorted-segment
kernel in exec/aggregate.py implements (cuDF ``Table.groupBy().aggregate``
analog, aggregate.scala:731).  For the common BI shape — group keys whose
joint value domain is small and known on the host — sorting every batch
by its keys is wasted work.  The domain is known two ways: every key is
a dictionary-code view (``encoding.stage_view`` left it as codes: radix
``dict.size + 1`` per key, nothing pulled; no keys at all is the
one-slot case), or one bare integer key whose range a memoized probe
pulled (date/flag/status keys, a year bucket).  Either way each key is
one DIGIT (key - base + 1, digit 0 reserved for null), the slot is the
mixed radix of the digits (first key most significant), and this kernel
streams lane-dense row blocks through a VMEM one-hot accumulation:

    rows live as (capacity/128, 128): one sublane row = 128 input rows
    per sublane row s:  hit = (iota_slots(K, 128) == gid[s])     # VMEM
                        acc[k, lane] (op)= where(hit, plane[s], neutral)

TPU grid steps run sequentially, so the (K, 128) accumulators stay
resident in the output blocks across steps (the standard Pallas
accumulation pattern); the final 128-lane fold is one XLA reduction
outside the kernel.  The (capacity, K) one-hot never exists in HBM, and
no sort runs at all.  Slot order (per digit: null, base, base+1, ...)
equals the sorted kernel's group order (nulls-first ascending, first key
most significant); counts/min/max/integer sums
are bit-identical to the sort path, float sums accumulate in block order
(the variableFloatAgg caveat, same as the reference's GPU float aggs).

Mosaic has no 64-bit types, so on the chip every plane is int32 or
float32 and every index the kernel touches is typed int32 explicitly
(the package runs with ``jax_enable_x64``).  ``supports()`` decides that
statically from the spec and the device float policy; a spec it admits
compiles, and a compile error propagates.  Off-TPU the same kernel runs
in interpret mode (compile/service.py:pallas_interpret).

This module builds bodies and the range probe; the one jitting caller of
``make_update_body`` is ``exec/aggregate.py:_compile_folded_update``.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.compile.service import engine_jit
from spark_rapids_tpu.columnar.column import bucket_capacity
from spark_rapids_tpu.columnar.dtypes import BOOLEAN, STRING, TIMESTAMP
from spark_rapids_tpu.exprs.base import ColVal, EvalContext
from spark_rapids_tpu.exprs import aggregates as agf

MAX_K = 1024          # largest dense key domain the kernel handles
_LANES = 128          # rows per sublane row of the lane-dense planes
_SUBLANES = 64        # sublane rows per grid step (8192 input rows)
# accumulator slots one pallas_call may keep resident: each plane holds a
# (K, 128) 32-bit block twice (Pallas double-buffers outputs), so
# 10 * 1024 slots is 10 MiB of the 16 MiB scoped VMEM; wider specs run
# as several calls over the same gid plane
_MAX_RESIDENT_SLOTS = 10 * 1024

from spark_rapids_tpu.utils.kernel_cache import KernelCache

_RANGE_CACHE = KernelCache("pallas.range", 128)


def enabled(conf) -> bool:
    from spark_rapids_tpu.conf import PALLAS_AGG
    return bool(conf.get(PALLAS_AGG))


def max_capacity(spec) -> int:
    """Largest batch capacity the dense-slot kernel stays EXACT at for
    this spec.  Int64 sums decompose into four 16-bit limbs summed in
    int32: one (slot, lane) accumulator sees capacity/128 rows, so its
    limb sum stays under 2^16 * 2^14 = 2^30 up to 2^21 rows; count-only
    / float-sum / min-max specs have no limb bound and run to 2^24 (the
    band-join + COUNT shape, TPCx-BB q3/q8, aggregates 8M joined pairs
    in one dense kernel instead of a 2^23-capacity bitonic sort)."""
    from spark_rapids_tpu.exprs import aggregates as _agf
    for _, f in spec.aggs:
        if isinstance(f, (_agf.Sum, _agf.Average)):
            proj = f.input_projection()[0]
            if not proj.dtype.is_floating:
                return 1 << 21
    return 1 << 24


def supports(spec) -> bool:
    """Every group key integer-like (a digit; whether each has a
    host-known radix is the caller's to establish); Count/Sum/Min/Max/
    Average over non-string inputs (their buffers all reduce with
    add/min/max).  Static in the spec and the device float policy: every
    plane a spec admitted here emits is one the kernel compiles for."""
    for g in spec.groupings:
        if g.dtype == STRING or g.dtype.is_floating:
            return False
    from spark_rapids_tpu.columnar.dtypes import INT64, device_dtype
    from spark_rapids_tpu.compile import service
    mosaic = not service.pallas_interpret()
    for _, f in spec.aggs:
        if not isinstance(f, (agf.Count, agf.Sum, agf.Min, agf.Max,
                              agf.Average)):
            return False
        proj = f.input_projection()[0]
        if proj.dtype == STRING or proj.dtype == BOOLEAN:
            return False
        # Mosaic has no 64-bit types: int64 SUMS decompose into exact
        # 16-bit limb sums (below), but 64-bit MIN/MAX would need a
        # two-pass lexicographic reduce -> those stay on the sorted path
        if isinstance(f, (agf.Min, agf.Max)) and \
                proj.dtype in (INT64, TIMESTAMP):
            return False
        # a DOUBLE the device keeps as f64 (doubleAsFloat off) has no
        # Mosaic plane; the interpreted kernel (tests, CPU) carries it
        if mosaic and proj.dtype.is_floating and \
                device_dtype(proj.dtype) == np.float64:
            return False
    return True


def _neutral(op: str, dtype) -> np.ndarray:
    """The value an unoccupied slot holds under ``op`` — a numpy scalar
    of the plane's own dtype, so tracing it inside the kernel never
    widens to the x64 defaults."""
    dtype = np.dtype(dtype)
    if op == "add":
        return np.zeros((), dtype)
    if np.issubdtype(dtype, np.floating):
        return np.asarray(np.inf if op == "min" else -np.inf, dtype)
    info = np.iinfo(dtype)
    return np.asarray(info.max if op == "min" else info.min, dtype)


_COMBINE = {"add": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def _fold_lanes(acc: jnp.ndarray, op: str) -> jnp.ndarray:
    """(K, 128) lane accumulators -> (K,).  Integer sums widen first:
    one accumulator stays inside int32 (max_capacity), but 128 of them
    together need not — a 16-bit limb over 2^21 rows reaches 2^37."""
    if op == "min":
        return jnp.min(acc, axis=1)
    if op == "max":
        return jnp.max(acc, axis=1)
    if jnp.issubdtype(acc.dtype, jnp.integer):
        acc = acc.astype(jnp.int64)
    return jnp.sum(acc, axis=1)


def _pallas_reduce(gid: jnp.ndarray, planes: Tuple[jnp.ndarray, ...],
                   ops: Tuple[str, ...], K: int, capacity: int):
    """(capacity,) planes -> per-slot (K,) reductions.  Wide specs split
    into several calls so the resident accumulators fit VMEM."""
    from spark_rapids_tpu.compile import service
    interpret = service.pallas_interpret()
    if not interpret:
        bad = [str(p.dtype) for p in planes
               if p.dtype not in (jnp.int32, jnp.float32)]
        if bad:
            # supports() admits only specs whose planes Mosaic has; a
            # plane that got here anyway is an engine bug, not a reason
            # to run something else
            raise TypeError(
                f"pallas_agg: {bad} planes have no Mosaic lowering")
    # lane-dense layout: 128 input rows per sublane row, at least one
    # (8, 128) tile; padded rows carry gid -1 and hit no slot
    rows2 = max(capacity // _LANES, 8)
    pad = rows2 * _LANES - capacity

    def dense(a, fill):
        if pad:
            a = jnp.pad(a, (0, pad), constant_values=fill)
        return a.reshape(rows2, _LANES)

    gid2 = dense(gid, -1)
    per_call = max(1, _MAX_RESIDENT_SLOTS // K)
    outs: list = []
    for at in range(0, len(planes), per_call):
        outs.extend(_pallas_reduce_call(
            gid2, tuple(dense(p, 0) for p in planes[at:at + per_call]),
            ops[at:at + per_call], K, interpret))
    return outs


def _pallas_reduce_call(gid2, planes2, ops, K: int, interpret: bool):
    from jax.experimental import pallas as pl

    rows2 = gid2.shape[0]
    sub = min(_SUBLANES, rows2)
    n = len(planes2)
    zero = np.int32(0)

    def kernel(gid_ref, *refs):
        crefs, orefs = refs[:n], refs[n:]

        @pl.when(pl.program_id(0) == 0)
        def _init():
            for o, op in zip(orefs, ops):
                o[...] = jnp.full((K, _LANES), _neutral(op, o.dtype),
                                  o.dtype)

        slot = jax.lax.broadcasted_iota(jnp.int32, (K, _LANES), 0)

        def row(s, carry):
            # one sublane row = 128 input rows; the (1, 128) gid row
            # broadcasts down the K slots
            hit = slot == gid_ref[pl.ds(s, 1), :]
            for c, o, op in zip(crefs, orefs, ops):
                v = jnp.where(hit, c[pl.ds(s, 1), :],
                              _neutral(op, c.dtype))
                o[...] = _COMBINE[op](o[...], v)
            return carry

        jax.lax.fori_loop(zero, np.int32(sub), row, zero)

    accs = pl.pallas_call(
        kernel,
        grid=(rows2 // sub,),
        in_specs=[pl.BlockSpec((sub, _LANES), lambda i: (i, zero))]
        * (1 + n),
        out_specs=[pl.BlockSpec((K, _LANES), lambda i: (zero, zero))] * n,
        out_shape=[jax.ShapeDtypeStruct((K, _LANES), p.dtype)
                   for p in planes2],
        interpret=interpret,
    )(gid2, *planes2)
    return [_fold_lanes(a, op) for a, op in zip(accs, ops)]


def key_range(view, batch) -> Optional[Tuple[int, int]]:
    """(min, max) of the valid values of the update's first key over
    every row of ``batch``, or None when no valid key exists; one cached
    jitted kernel + one host sync, memoized on buffer identity so a
    re-run over the device scan cache never pulls.  ``view`` is the
    update's own code view (``encoding.stage_view`` of the folded chain
    and the last projection): the probe runs its projections (a
    plane-decode prefix, then the key's expression alone) and none of
    its filters, so it reads the planes the update reads."""
    from spark_rapids_tpu.exec.stage import (
        emit_steps, norm_rows, stage_fingerprint,
    )
    projects = [s for s in view.steps if s[0] == "project"]
    steps = tuple(projects[:-1]) + (("project", projects[-1][1][:1]),)
    cap = batch.capacity
    sig = (stage_fingerprint(steps), view.sig, view.aux_sig, cap)
    fn = _RANGE_CACHE.get(sig)
    if fn is None:
        def run(flat_cols, aux, num_rows):
            cols = [ColVal(*t) for t in flat_cols]
            (cv,), _live = emit_steps(steps, cols, num_rows, cap,
                                      jnp.int64(0), (), aux=aux,
                                      compact=False)
            m = cv.validity  # the projection masked it with liveness
            v = cv.data.astype(jnp.int64)
            lo = jnp.min(jnp.where(m, v, jnp.iinfo(jnp.int64).max))
            hi = jnp.max(jnp.where(m, v, jnp.iinfo(jnp.int64).min))
            return lo, hi, jnp.any(m)

        fn = engine_jit(run, family="aggregate", name="pallas_key_range")
        _RANGE_CACHE[sig] = fn
    from spark_rapids_tpu.utils.memo import memoized_pull
    rows = batch.rows_traced
    arrays = [a for t in view.flat + view.aux for a in t if a is not None]
    logical = ("pallas_key_range", sig)
    if isinstance(rows, int):
        logical = logical + (rows,)
    else:
        arrays.append(rows)

    def compute():
        # one combined pull for all three scalars: each separate host
        # read of a device scalar is a round trip of its own
        from spark_rapids_tpu.columnar.transfer import device_pull
        lo, hi, any_valid = device_pull(
            fn(view.flat, view.aux, norm_rows(batch)))
        if not bool(any_valid):
            return None
        return int(lo), int(hi)

    return memoized_pull(logical, arrays, compute)


def fits(lo: int, hi: int) -> bool:
    return hi - lo + 2 <= MAX_K  # +1 null slot


def _round_k(span: int) -> int:
    k = 128
    while k < span:
        k *= 2
    return k


def range_radix(lo: int, hi: int) -> int:
    """The radix of a bare integer key whose range was probed: its span
    plus the null digit, rounded up the kernel's K ladder so batches
    with nearby ranges share one compiled kernel."""
    return _round_k(hi - lo + 2)


def make_update_body(spec, capacity: int, radices: Sequence[int]):
    """The traceable update body ``(flat_cols, num_rows, bases,
    live_mask=None) -> (n_groups, keys, buffers)``, matching
    make_agg_body's update contract (group order identical).

    ``radices`` holds one host-known radix per grouping: key ``i`` is the
    digit ``key - bases[i] + 1`` (0 = null) and the slot is the mixed
    radix of the digits, first key most significant — this function is
    the single owner of that layout.  ``bases`` (an int64 vector, one per
    digit) stays a traced argument so batches with different key ranges
    share a kernel per radix bucket.  No groupings is zero digits: every
    live row hits slot 0 and there is always exactly one group (the
    empty-input rule, aggregate.scala:406-419).  The outputs are
    ``bucket_capacity(prod(radices))`` slots long — the partial has the
    shape of its domain, not of its input.  ``live_mask`` (optional)
    overrides the contiguous row-liveness ``arange < num_rows``: a
    filter folded in front of the update hands its keep-mask here, rows
    in place (exec/aggregate.py, docs/fusion.md)."""
    radices = tuple(int(r) for r in radices)
    domain = math.prod(radices)
    K = _round_k(domain)
    out_cap = min(K, bucket_capacity(domain))
    groupings = list(spec.groupings)
    # stride of digit i = product of the radices after it
    strides = [math.prod(radices[i + 1:]) for i in range(len(radices))]

    def run(flat_cols, num_rows, bases, live_mask=None):
        cols = [ColVal(*t) for t in flat_cols]
        ctx = EvalContext(cols, num_rows, capacity)
        live = live_mask if live_mask is not None \
            else jnp.arange(capacity) < num_rows
        key_cvs = [g.emit(ctx) for g in groupings]
        gid = jnp.zeros((capacity,), jnp.int64)
        for i, kcv in enumerate(key_cvs):
            digit = jnp.where(
                kcv.validity & live,
                kcv.data.astype(jnp.int64) - bases[i] + 1,
                jnp.zeros((), jnp.int64))
            gid = gid + jnp.clip(digit, 0, radices[i] - 1) * strides[i]
        gid = gid.astype(jnp.int32)

        planes: List[jnp.ndarray] = []
        ops: List[str] = []
        # slot occupancy: any LIVE row (null keys land in slot 0)
        planes.append(live.astype(jnp.int32))
        ops.append("add")
        # Mosaic has no 64-bit types, so every plane is a 32-bit int or
        # float: counts reduce in int32 (capacity < 2^31) and cast
        # back; int64 sums split into four unsigned 16-bit limb planes
        # summed in int32 (max_capacity keeps every accumulator under
        # 2^31), recombined as sum(limb_k << 16k) in wrapping int64 —
        # EXACT including Java wraparound; narrow int min/max reduce in
        # int32 and cast back
        post: List[tuple] = []  # (kind, indices...) per output buffer
        for _, f in spec.aggs:
            cv = f.input_projection()[0].emit(ctx)
            m = cv.validity & live
            for op in f.update_ops():
                if op == "count":
                    planes.append(m.astype(jnp.int32))
                    ops.append("add")
                    post.append(("cast", len(planes) - 1, jnp.int64))
                elif op == "sum":
                    if jnp.issubdtype(cv.data.dtype, jnp.floating):
                        planes.append(jnp.where(
                            m, cv.data, jnp.zeros((), cv.data.dtype)))
                        ops.append("add")
                        post.append(("plain", len(planes) - 1))
                    else:
                        v = cv.data.astype(jnp.int64)
                        z = jnp.zeros((), jnp.int32)
                        for limb in range(4):
                            bits = ((v >> (16 * limb)) & 0xFFFF).astype(
                                jnp.int32)
                            planes.append(jnp.where(m, bits, z))
                            ops.append("add")
                        post.append(("sum64", len(planes) - 4))
                elif jnp.issubdtype(cv.data.dtype, jnp.floating):
                    # Spark NaN ordering (same as _segment_reduce):
                    # min ignores NaN unless all-NaN; max: any NaN -> NaN
                    nan = jnp.isnan(cv.data)
                    planes.append(jnp.where(m & ~nan, cv.data,
                                            _neutral(op, cv.data.dtype)))
                    ops.append(op)
                    i_val = len(planes) - 1
                    planes.append((m & nan).astype(jnp.int32))
                    ops.append("max")
                    planes.append((m & ~nan).astype(jnp.int32))
                    ops.append("max")
                    post.append(("nan" + op, i_val, len(planes) - 2,
                                 len(planes) - 1))
                else:
                    # int8/16/32/date: widen to int32 for the reduction.
                    # The neutral is the NARROW dtype's extreme (widened)
                    # so an empty group's sentinel survives the cast back
                    # and still loses every cross-batch merge — int32
                    # extremes would wrap to -1/0 in the narrow dtype
                    v32 = cv.data.astype(jnp.int32)
                    neutral32 = _neutral(op, cv.data.dtype).astype(
                        jnp.int32)
                    planes.append(jnp.where(m, v32, neutral32))
                    ops.append(op)
                    post.append(("cast", len(planes) - 1,
                                 cv.data.dtype))

        reds = _pallas_reduce(gid, tuple(planes), tuple(ops), K,
                              capacity)

        seen = reds[0] > 0
        n_groups = jnp.sum(seen.astype(jnp.int32)) if radices \
            else jnp.int32(1)
        # compact occupied slots to the front; slot order already equals
        # the sorted kernel's nulls-first-ascending group order.  Slots
        # past the domain cannot be hit, so the compacted front
        # ``out_cap`` slots hold every group
        perm = jnp.argsort(~seen, stable=True)[:out_cap]
        group_valid = jnp.arange(out_cap, dtype=jnp.int32) < n_groups

        slot = perm.astype(jnp.int64)
        key_outs = []
        for i, kcv in enumerate(key_cvs):
            digit = (slot // strides[i]) % radices[i]
            kd = (bases[i] - 1 + digit).astype(kcv.data.dtype)
            key_outs.append(ColVal(kd, group_valid & (digit != 0), None))

        buf_outs = []
        for item in post:
            if item[0] == "plain":
                buf_outs.append(ColVal(
                    jnp.take(reds[item[1]], perm), group_valid, None))
            elif item[0] == "cast":
                buf_outs.append(ColVal(
                    jnp.take(reds[item[1]], perm).astype(item[2]),
                    group_valid, None))
            elif item[0] == "sum64":
                total = jnp.zeros((K,), jnp.int64)
                for limb in range(4):
                    total = total + (
                        reds[item[1] + limb].astype(jnp.int64)
                        << (16 * limb))
                buf_outs.append(ColVal(jnp.take(total, perm),
                                       group_valid, None))
            else:
                base = jnp.take(reds[item[1]], perm)
                has_nan = jnp.take(reds[item[2]], perm) > 0
                has_non = jnp.take(reds[item[3]], perm) > 0
                nan_v = jnp.asarray(jnp.nan, base.dtype)
                if item[0] == "nanmin":
                    out = jnp.where(has_nan & ~has_non, nan_v, base)
                else:
                    out = jnp.where(has_nan, nan_v, base)
                buf_outs.append(ColVal(out, group_valid, None))
        return n_groups, tuple(key_outs), tuple(buf_outs)

    return run
