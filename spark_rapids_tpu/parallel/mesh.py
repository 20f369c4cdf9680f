"""Mesh construction + host-side row sharding helpers.

The data axis ("data") is the partition-parallel axis — the analog of
Spark's task partitions (SURVEY §2.8: data parallelism is the reference's
only compute parallelism; here one logical operator can span chips).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import List, Optional, Tuple

import numpy as np

import jax
from jax.sharding import Mesh

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn, bucket_capacity
from spark_rapids_tpu.columnar.dtypes import Schema
from spark_rapids_tpu.utils import tracing
from spark_rapids_tpu.utils.kernel_cache import KernelCache

DATA_AXIS = "data"

# process-wide counters of the mesh path (merged into
# exec/meshexec.py:ici_stats() so bench.py and the sharded-scan tests
# read one snapshot): parallel per-chip result pulls issued and the
# link wall time the fan-out reclaimed (docs/sharded_scan.md), and the
# microseconds a mesh fragment spent in each of its three phases
# (``phase``; docs/ici_shuffle.md, "Phases"), and the mesh programs
# asked for and found compiled (``mesh_program``)
_GATHER_LOCK = threading.Lock()
_GATHER = {"gather_pulls": 0, "gather_overlap_ms": 0,
           "ingest_us": 0, "collective_us": 0, "gather_us": 0,
           "program_lookups": 0, "program_hits": 0}
_PHASE_SPANS = {"ingest_us": tracing.SPAN_ICI_INGEST,
                "collective_us": tracing.SPAN_ICI_COLLECTIVE,
                "gather_us": tracing.SPAN_ICI_GATHER}


def gather_stats() -> dict:
    with _GATHER_LOCK:
        return dict(_GATHER)


def reset_gather_stats() -> None:
    with _GATHER_LOCK:
        for k in _GATHER:
            _GATHER[k] = 0


def _bump_gather(pulls: int, overlap_ms: int) -> None:
    with _GATHER_LOCK:
        _GATHER["gather_pulls"] += int(pulls)
        _GATHER["gather_overlap_ms"] += int(overlap_ms)


@contextlib.contextmanager
def phase(counter: str):
    """One phase of a mesh fragment: its span (``ici.ingest``,
    ``ici.collective`` or ``ici.gather``, under the trace switch) and
    its always-on microseconds in ``ici_stats()`` (``ingest_us``: the
    drained child made one batch and split over the mesh, or scanned
    shard by shard onto it; ``collective_us``: the ``shard_map``
    programs from launch to their sync; ``gather_us``: the result
    pulled back and made one batch).  Bumped once a fragment and phase,
    never per row.  Also a decorator: ``@phase("gather_us")``."""
    start = time.perf_counter_ns()
    try:
        with tracing.trace_range(_PHASE_SPANS[counter]):
            yield
    finally:
        with _GATHER_LOCK:
            _GATHER[counter] += (time.perf_counter_ns() - start) // 1000


# The jitted ``shard_map`` programs of distagg.py, distjoin.py and
# distsort.py, for the whole process: a plan built anew finds the program
# an earlier plan compiled (docs/ici_shuffle.md, "Where a mesh program
# lives").  The bound is entries, and an entry pins one loaded executable
# on every chip of its mesh.  At SF1 on a v5e:2x2 the three kinds are 85,
# 53 and 76 MB of code a chip (PERF.md section 6, PR 33): 71 MB the mean,
# 87 MB the largest seen.  24 entries are 1.7 GB a chip at that mean and
# 2.1 GB if every one were the largest, of 16 GB; Q3 asks for 5 programs
# and Q18 for 8, so both queries of the mesh configuration stay resident
# with room for as many again.
_MESH_PROGRAMS = KernelCache("mesh_programs", 24)


def mesh_key(mesh: Mesh) -> tuple:
    """A mesh as a program's cache key reads it: its chips in order, not
    its width.  A mesh that lost a chip and re-formed at the same width
    (exec/meshexec.py: ``_mesh_key_and_builder``) is another mesh, and no
    program compiled for the old one may run over the new."""
    return (mesh.axis_names,
            tuple((d.platform, d.id) for d in mesh.devices.flat))


def planes_signature(stacked) -> tuple:
    """What a trace reads of stacked input planes ``[(data, validity,
    chars | None), ...]`` besides the shard capacity: each column's dtype,
    the shape under its rows, and its string width."""
    return tuple(
        (data.dtype.name, tuple(data.shape[2:]),
         None if chars is None else int(chars.shape[2]))
        for data, _valid, chars in stacked)


def mesh_program(key: tuple, build, programs: KernelCache = None):
    """The jitted program under ``key``, built by ``build()`` where no
    plan of this process has asked for it yet, and the two counters that
    say so: ``ici.program_lookups`` and ``ici.program_hits``, once a
    program asked for.  ``key`` holds everything the traced body reads:
    ``mesh_key``, the expressions by ``key()``, the join type, the
    capacities and ``planes_signature`` of the inputs.  ``programs`` is
    the memo looked in: the process-wide one unless the caller keeps its
    own (a ``DistributedAggregate`` with a ``prelude``)."""
    missed = []

    def build_and_note():
        missed.append(True)
        return build()

    if programs is None:
        programs = _MESH_PROGRAMS
    fn = programs.get_or_build(key, build_and_note)
    with _GATHER_LOCK:
        _GATHER["program_lookups"] += 1
        _GATHER["program_hits"] += not missed
    return fn


def data_mesh(n_devices: Optional[int] = None,
              devices: Optional[list] = None) -> Mesh:
    """1-D mesh over the data axis (devices default to all available)."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if len(devices) < n_devices:
                raise ValueError(
                    f"need {n_devices} devices, have {len(devices)}")
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (DATA_AXIS,))


def _per_device_trees(out_cols, n_dev: int):
    """Split stacked global planes into per-device pull trees — one
    tree per mesh device, mirroring ``out_cols``'s tuple structure —
    when every plane is row-sharded across exactly ``n_dev`` devices
    (the shard_map output shape).  None when any plane is not (a
    single-device stacked array, the dryrun shape, keeps the one-pull
    path)."""
    per = [[] for _ in range(n_dev)]
    for tup in out_cols:
        slots = []
        for a in tup:
            if a is None:
                slots.append(None)
                continue
            shards = getattr(a, "addressable_shards", None)
            if shards is None or len(shards) != n_dev:
                return None
            by_row = {}
            for sh in shards:
                idx = sh.index[0] if sh.index else slice(0, 1)
                start = 0 if idx.start is None else int(idx.start)
                by_row[start] = sh.data
            if sorted(by_row) != list(range(n_dev)):
                return None
            slots.append(by_row)
        for d in range(n_dev):
            per[d].append(tuple(
                None if s is None else s[d] for s in slots))
    return per


@phase("gather_us")
def gather_stacked(out_cols, counts: np.ndarray, dtypes,
                   schema: Optional[Schema] = None,
                   parallel_pull: bool = False) -> ColumnarBatch:
    """Collect per-device stacked result planes into ONE host-side
    ColumnarBatch: device d contributes its first counts[d] rows.

    ``out_cols``: [(data (n_dev, cap, ...), valid, chars|None), ...]
    device arrays.  One ``device_pull`` moves every plane (per-slice
    pulls pay a full link round trip each on remote-attached chips);
    with ``parallel_pull`` and row-sharded planes, ONE pull PER CHIP
    issued concurrently (``transfer.parallel_device_pull``), so the
    fixed per-pull link latency overlaps across devices instead of one
    serial pull carrying every chip's bytes — the egress mirror of the
    sharded scan ingest (docs/sharded_scan.md; overlap recorded in
    ``gather_stats()`` / ``meshexec.ici_stats()``).

    Each output plane is allocated ONCE at ``bucket_capacity(total)``
    and the per-device live slices are copied in place; only the dead
    tail past ``total`` is zeroed (validity is all-False by
    construction, and downstream gathers of dead rows must read
    deterministic bytes).  The old path zero-filled every full-capacity
    plane before overwriting the live prefix — pure memory-bandwidth
    churn on the result-collection hot path."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.transfer import (
        device_pull, parallel_device_pull,
    )
    counts = np.asarray(counts)
    n_dev = len(counts)
    total = int(counts.sum())
    host_per_dev = None
    if parallel_pull and n_dev > 1:
        trees = _per_device_trees(out_cols, n_dev)
        if trees is not None:
            host_per_dev, overlap_ms = parallel_device_pull(trees)
            _bump_gather(n_dev, overlap_ms)
    if host_per_dev is None:
        host_cols = device_pull([
            (d, v, c) if c is not None else (d, v)
            for (d, v, c) in out_cols])

        def planes(ci, d):
            tup = host_cols[ci]
            return (np.asarray(tup[0])[d], np.asarray(tup[1])[d],
                    np.asarray(tup[2])[d] if len(tup) > 2 else None)

        def plane_info(ci):
            tup = host_cols[ci]
            data = np.asarray(tup[0])
            chars = np.asarray(tup[2]) if len(tup) > 2 else None
            return data.shape[2:], data.dtype, chars
    else:
        def planes(ci, d):
            data, valid, chars = host_per_dev[d][ci]
            return (np.asarray(data)[0], np.asarray(valid)[0],
                    None if chars is None else np.asarray(chars)[0])

        def plane_info(ci):
            data, _valid, chars = host_per_dev[0][ci]
            data = np.asarray(data)
            return (data.shape[2:], data.dtype,
                    None if chars is None else np.asarray(chars))
    out_cap = bucket_capacity(max(total, 1))
    cols = []
    for ci, dt in enumerate(dtypes):
        shape_tail, np_dtype, chars0 = plane_info(ci)
        pdata = np.empty((out_cap,) + shape_tail, np_dtype)
        pvalid = np.zeros(out_cap, bool)
        pchars = None if chars0 is None else \
            np.empty((out_cap, chars0.shape[2]), chars0.dtype)
        off = 0
        for d in range(n_dev):
            m = int(counts[d])
            if m:
                data, valid, chars = planes(ci, d)
                pdata[off:off + m] = data[:m]
                pvalid[off:off + m] = valid[:m]
                if pchars is not None:
                    pchars[off:off + m] = chars[:m]
                off += m
        pdata[total:] = 0
        if pchars is not None:
            pchars[total:] = 0
        cols.append(DeviceColumn(
            dt, jnp.asarray(pdata), jnp.asarray(pvalid), total,
            chars=None if pchars is None else jnp.asarray(pchars)))
    return ColumnarBatch(cols, total, schema)


@phase("ingest_us")
def shard_table(batch: ColumnarBatch, n_dev: int
                ) -> Tuple[list, np.ndarray, int]:
    """Split one host-visible batch into ``n_dev`` equal-capacity row
    shards, stacked on a new leading device axis.

    Returns (stacked flat cols [(data, validity, chars), ...] with leading
    axis n_dev, per-shard row counts (n_dev,), shard capacity).  The
    split is part of the fragment's ingest (``phase``).
    """
    n = batch.num_rows
    per = -(-max(n, 1) // n_dev)
    cap = bucket_capacity(per)
    counts = np.zeros(n_dev, np.int64)
    stacked = []
    for c in batch.columns:
        data = np.zeros((n_dev, cap) + np.asarray(c.data).shape[1:],
                        np.asarray(c.data).dtype)
        valid = np.zeros((n_dev, cap), bool)
        chars = None
        if c.chars is not None:
            ch = np.asarray(c.chars)
            chars = np.zeros((n_dev, cap, ch.shape[1]), ch.dtype)
        hd = np.asarray(c.data)[:n]
        hv = np.asarray(c.validity)[:n]
        hc = np.asarray(c.chars)[:n] if c.chars is not None else None
        for d in range(n_dev):
            lo, hi = d * per, min((d + 1) * per, n)
            m = max(0, hi - lo)
            counts[d] = m
            if m:
                data[d, :m] = hd[lo:hi]
                valid[d, :m] = hv[lo:hi]
                if chars is not None:
                    chars[d, :m] = hc[lo:hi]
        stacked.append((data, valid, chars))
    return stacked, counts, cap
