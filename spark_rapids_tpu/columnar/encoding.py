"""Encoded device columns: execute on compressed data (docs/compressed.md).

BENCH_r05 measured the host<->device link at ~45 MB/s H2D and ~3.9 MB/s
D2H — every raw byte crossing it is the tax.  "GPU Acceleration of SQL
Analytics on Compressed Data" (PAPERS.md) shows compressed-domain
execution beats decompress-then-scan even with more complex kernels; at
this link bandwidth the argument is ~10x stronger.  This module is the
one home for every dictionary-domain concern:

* **EncodedColumn** — a STRING ``DeviceColumn`` whose device planes are
  an int32 ``codes`` vector plus a small shared dictionary
  (``DictPlanes``: padded char matrix + lengths, a few hundred rows)
  instead of the dense ``(capacity, width)`` char matrix.  The 45 MB/s
  link carries codes, not values.  The dictionary is NORMALIZED at
  construction: values unique and sorted by UTF-8 bytes, codes are
  ranks — so code order == value order, grouped/sorted output over
  codes is byte-identical to the dense path, and min/max reduce over
  codes directly.  A ``plain`` column (already-dense data the encoder
  declined) is just a ``DeviceColumn`` — the passthrough encoding.

* **decode_late** — the ONE dictionary-materialization primitive
  (tests/lint_robustness.py bans take-by-codes gathers elsewhere).
  Any legacy consumer reading ``.data``/``.chars`` off an EncodedColumn
  decodes lazily through it, counted (``lateDecodes``), so correctness
  never depends on an operator being encoding-aware.  Operators that
  ARE aware fold the decode into their own kernel (``DictGather`` below
  — counted separately as ``fusedDecodes``, zero extra dispatches) or
  never decode at all (group-by/join over codes, egress codes-on-wire).

* **code-view rewrites** — ``stage_view`` rewrites a fused stage's
  step list so encoded columns flatten as codes: any deterministic
  expression subtree referencing exactly ONE encoded column evaluates
  once over the dictionary (plus a null slot, so null semantics are the
  expression's own) and becomes a per-row gather by code
  (``DictGather``); bare references pass codes through untouched.
  Predicates therefore become code-set membership, hash-partition keys
  become per-code hash gathers, and a project/filter chain over a
  dictionary column never touches a char matrix at batch width.

* **ingest** — ``IngestEncoder`` turns arrow string arrays (parquet's
  own dictionary pages via ``read_dictionary``, or a host-side
  ``dictionary_encode`` for ORC/CSV/local data) into EncodedColumns,
  with the ``io.encode`` fault site: an injected encode failure
  degrades that column to the plain plane path, counted, query
  correct.

Everything gates on ``spark.rapids.sql.compressed.{enabled,ingest,
egress}``; with the master key false no EncodedColumn is ever built and
every code path below is the identity — plans, kernels, metrics, and
results byte-identical to the dense engine.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

import jax
import jax.numpy as jnp

from spark_rapids_tpu.compile.service import engine_jit
from spark_rapids_tpu import faults
from spark_rapids_tpu.columnar.column import (
    DeviceColumn, LazyRows, bucket_capacity,
)
from spark_rapids_tpu.columnar.dtypes import (
    BOOLEAN, DataType, INT32, INT64, STRING, device_dtype,
)
from spark_rapids_tpu.utils.kernel_cache import KernelCache

FAULT_SITE_ENCODE = "io.encode"

# ---------------------------------------------------------------------------
# process-global switches (set from ExecContext like tracing/hoisting)
# and counters (bench.py's per-suite `compressed` object reads these)
# ---------------------------------------------------------------------------

_ENABLED = False
_INGEST = False
_EGRESS = False
_MAX_DICT_FRACTION = 0.5
_MAX_COMPOSED_CELLS = 65536
_RLE = False
_DELTA = False
_PACKED = False

_STATS_LOCK = threading.Lock()
_STATS = {
    # H2D: what the dense upload would have cost vs what actually
    # crossed (codes + dictionary planes)
    "h2d_raw_bytes": 0, "h2d_wire_bytes": 0,
    "encoded_columns": 0, "plain_columns": 0, "encode_faults": 0,
    # per-encoding selection record: which compute-plane encoding each
    # ingested column won (strings count under encoded_columns)
    "rle_columns": 0, "delta_columns": 0, "packed_bool_columns": 0,
    # decode accounting: late = a separate decode dispatch (the
    # counted escape hatch); fused = decode folded into a consuming
    # stage kernel (zero extra dispatches); code_stages = fused-stage
    # dispatches that ran with at least one column in the code domain
    "late_decodes": 0, "fused_decodes": 0, "code_stages": 0,
    # multi-column rewrites: subtrees over TWO encoded columns kept in
    # the code domain via a composed (code1, code2) gather table
    "composed_gathers": 0,
}


def set_conf(conf) -> None:
    """Install the session's compressed-execution switches (process
    global, set at every execution entry point like the tracing span
    switch — see ExecContext)."""
    global _ENABLED, _INGEST, _EGRESS, _MAX_DICT_FRACTION
    global _MAX_COMPOSED_CELLS, _RLE, _DELTA, _PACKED
    _ENABLED = conf.compressed_enabled
    _INGEST = _ENABLED and conf.compressed_ingest
    _EGRESS = _ENABLED and conf.compressed_egress
    _MAX_DICT_FRACTION = conf.compressed_max_dict_fraction
    _MAX_COMPOSED_CELLS = conf.compressed_max_composed_cells
    _RLE = _INGEST and conf.compressed_rle
    _DELTA = _INGEST and conf.compressed_delta
    _PACKED = _INGEST and conf.compressed_packed_bool


def enabled() -> bool:
    return _ENABLED


def ingest_enabled() -> bool:
    return _INGEST


def egress_enabled() -> bool:
    return _EGRESS


def _bump(key: str, v: int = 1) -> None:
    if v:
        with _STATS_LOCK:
            _STATS[key] += int(v)


def compressed_stats() -> dict:
    """Snapshot of process-wide compressed-execution counters, joined
    with the D2H raw/wire mirror kept by columnar/transfer.py (bench.py
    and the obs registry snapshot read this)."""
    from spark_rapids_tpu.columnar import transfer
    with _STATS_LOCK:
        out = dict(_STATS)
    d2h = transfer.d2h_stats()
    out["d2h_raw_bytes"] = d2h.get("raw_bytes", 0)
    out["d2h_wire_bytes"] = d2h.get("wire_bytes", 0)
    out["bytes_saved"] = max(
        0, out["h2d_raw_bytes"] - out["h2d_wire_bytes"]) + max(
        0, out["d2h_raw_bytes"] - out["d2h_wire_bytes"])
    return out


def reset_stats() -> None:
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0


# ---------------------------------------------------------------------------
# DictPlanes: the shared device dictionary
# ---------------------------------------------------------------------------

class DictPlanes:
    """One string dictionary, device-resident, shared by every batch
    that references it.

    Invariants: ``values`` (host numpy object array of str) is unique
    and sorted by UTF-8 bytes, so codes are ranks; the device planes
    carry ``size + 1`` logical rows — index ``size`` is the NULL SLOT
    (zero chars, zero length, validity False) dictionary-domain
    expression evaluation maps null rows onto, so any expression's null
    semantics are its own, not special-cased here.

    ``aux(key, build)`` memoizes dictionary-domain derived planes (a
    predicate's membership mask, a hash gather table, a projected
    column) per dictionary, so a rewritten subtree evaluates over
    ``size + 1`` rows ONCE and every batch after that is a pure
    gather."""

    __slots__ = ("values", "size", "capacity", "width", "lengths",
                 "chars", "validity", "fingerprint", "_aux", "_aux_lock")

    _AUX_BOUND = 64

    def __init__(self, values: np.ndarray, device=None):
        self.values = values
        d = int(values.shape[0])
        self.size = d
        cap = bucket_capacity(max(1, d + 1))
        self.capacity = cap
        encoded = [v.encode("utf-8") for v in values]
        lens = np.zeros(cap, np.int32)
        lens[:d] = [len(b) for b in encoded]
        width = bucket_capacity(max(1, int(lens.max()) if d else 1))
        chars = np.zeros((cap, width), np.uint8)
        for i, b in enumerate(encoded):
            chars[i, :len(b)] = np.frombuffer(b, np.uint8)
        self.width = width
        valid = np.zeros(cap, np.bool_)
        valid[:d] = True
        put = (lambda a: jax.device_put(a, device)) if device is not None \
            else jax.device_put
        self.lengths = put(lens)
        self.chars = put(chars)
        self.validity = put(valid)
        # stable identity for kernel/unification decisions: equal value
        # sets share a fingerprint even across separately-built planes
        self.fingerprint = hash((d,) + tuple(encoded[:32]) +
                                (encoded[-1] if d else b"",))
        self._aux: "Dict[object, tuple]" = {}
        self._aux_lock = threading.Lock()

    def wire_bytes(self) -> int:
        return int(self.lengths.nbytes + self.chars.nbytes +
                   self.validity.nbytes)

    def aux(self, key, build):
        """Memoized dictionary-domain plane tuple for ``key`` (bounded:
        a dictionary outliving many distinct queries drops its oldest
        derived planes rather than accumulating them forever)."""
        with self._aux_lock:
            hit = self._aux.get(key)
        if hit is not None:
            return hit
        planes = build()
        with self._aux_lock:
            if len(self._aux) >= self._AUX_BOUND:
                self._aux.pop(next(iter(self._aux)))
            self._aux[key] = planes
        return planes

    def dense_column(self) -> DeviceColumn:
        """The dictionary itself as a dense STRING column of
        ``size + 1`` rows (the null slot last) — the evaluation domain
        for rewritten subtrees."""
        return DeviceColumn(STRING, self.lengths, self.validity,
                            self.size + 1, chars=self.chars)

    def same_values(self, other: "DictPlanes") -> bool:
        if self is other:
            return True
        return (self.size == other.size
                and self.fingerprint == other.fingerprint
                and bool(np.array_equal(self.values, other.values)))


# ---------------------------------------------------------------------------
# EncodedColumn
# ---------------------------------------------------------------------------

_DECODE_CACHE = KernelCache("encoding.decode", 128)


def _compile_decode(cap: int, dcap: int, width: int):
    key = (cap, dcap, width)

    def build():
        def run(codes, valid, d_lens, d_chars):
            idx = jnp.clip(codes, 0, dcap - 1)
            lens = jnp.where(valid, jnp.take(d_lens, idx), 0)
            chars = jnp.where(valid[:, None],
                              jnp.take(d_chars, idx, axis=0), 0)
            return lens.astype(jnp.int32), chars
        return engine_jit(run, family="scan", name="decode")
    return _DECODE_CACHE.get_or_build(key, build)


class EncodedColumn(DeviceColumn):
    """A STRING column stored as dictionary codes + a shared dictionary.

    Looks exactly like a ``DeviceColumn`` to every consumer: ``.data``
    (lengths) and ``.chars`` decode lazily through ``decode_late`` on
    first touch — correctness never requires encoding awareness.
    Encoding-aware paths read ``.codes``/``.dict`` instead and never
    materialize the dense planes."""

    __slots__ = ("codes", "dict", "_dense")

    def __init__(self, codes, validity, num_rows, dict_planes: DictPlanes):
        # deliberately NOT calling DeviceColumn.__init__: `data`/`chars`
        # are shadowed by the lazy-decode properties below
        self.dtype = STRING
        self.codes = codes
        self.validity = validity
        self._rows = num_rows if isinstance(num_rows, LazyRows) \
            else int(num_rows)
        self.dict = dict_planes
        self._dense = None

    # -- lazy dense view (the counted escape hatch) -------------------------

    def decoded(self) -> DeviceColumn:
        if self._dense is None:
            self._dense = decode_late(self)
        return self._dense

    @property
    def data(self):
        return self.decoded().data

    @property
    def chars(self):
        return self.decoded().chars

    @property
    def capacity(self) -> int:
        return int(self.codes.shape[0])

    @property
    def string_width(self) -> int:
        return self.dict.width

    def size_bytes(self) -> int:
        # the encoded device footprint; the shared dictionary is
        # charged to each referencing column (conservative)
        return int(self.codes.nbytes + self.validity.nbytes +
                   self.dict.wire_bytes())

    # -- transforms stay in the code domain ---------------------------------

    def with_rows(self, num_rows) -> "EncodedColumn":
        return EncodedColumn(self.codes, self.validity, num_rows,
                             self.dict)

    def gather(self, indices, num_rows) -> "EncodedColumn":
        codes = jnp.take(self.codes, indices, axis=0, mode="clip")
        valid = jnp.take(self.validity, indices, axis=0, mode="clip")
        in_range = (indices >= 0) & (indices < self.num_rows)
        pos = jnp.arange(indices.shape[0])
        nlim = num_rows.dev if isinstance(num_rows, LazyRows) \
            else int(num_rows)
        valid = jnp.where(in_range & (pos < nlim), valid, False)
        return EncodedColumn(codes, valid, num_rows, self.dict)

    def slice_rows(self, start: int, length: int) -> "EncodedColumn":
        cap = bucket_capacity(length)
        idx = jnp.arange(cap) + start
        return self.gather(idx, length)

    def to_numpy(self):
        """Host values without touching device char matrices: pull
        codes + validity, then index the HOST dictionary."""
        from spark_rapids_tpu.columnar.transfer import device_pull
        n = self.num_rows
        codes_h, valid_h = device_pull((self.codes, self.validity))
        codes_h = np.asarray(codes_h)[:n]
        valid_h = np.asarray(valid_h)[:n]
        out = np.empty(n, dtype=object)
        vals = self.dict.values
        for i in range(n):
            out[i] = vals[codes_h[i]] if valid_h[i] else ""
        return out, valid_h

    def __repr__(self):
        return (f"EncodedColumn(dict={self.dict.size}, "
                f"rows={self.num_rows}, cap={self.capacity})")


def decode_late(col: EncodedColumn) -> DeviceColumn:
    """THE dictionary-materialization primitive: gather dense string
    planes from the dictionary by code, as ONE jitted kernel.  Invalid
    rows decode to zeros (matching the dense ingest path, so sort
    tie-breaks over null rows cannot diverge).  Counted — the
    ``lateDecodes`` trajectory number is the measure of how much of a
    plan still runs in the value domain."""
    fn = _compile_decode(col.capacity, col.dict.capacity, col.dict.width)
    lens, chars = fn(col.codes, col.validity, col.dict.lengths,
                     col.dict.chars)
    _bump("late_decodes")
    return DeviceColumn(STRING, lens, col.validity, col.rows_raw,
                        chars=chars)


def is_encoded(col) -> bool:
    return isinstance(col, EncodedColumn)


def has_encoded(batch) -> bool:
    return any(isinstance(c, EncodedColumn) for c in batch.columns)


# ---------------------------------------------------------------------------
# non-dictionary compute planes: RLE / delta-narrow / bit-packed bool
# ---------------------------------------------------------------------------
#
# The egress pack already ships validity bitpacks and delta-narrowed
# integers as WIRE formats (columnar/transfer.py); these classes make
# the same encodings COMPUTE planes on ingest: the link carries the
# compressed representation, and the decode runs inside the consuming
# fused stage kernel (``PlaneDecode`` below, counted fusedDecodes) or —
# for encoding-unaware consumers — lazily through the counted
# ``decode_plane_late``, exactly the EncodedColumn contract.

_PLANE_DECODE_CACHE = KernelCache("encoding.plane_decode", 128)


def _rle_dense(run_values, run_ends, validity, cap: int, rcap: int):
    """In-kernel RLE decode: run index per row by searchsorted over the
    cumulative run ends (padding runs carry value 0 and end ``cap``, so
    rows past the data decode to 0 — the dense pad).  Nulls were filled
    with 0 before run construction, so the decoded data plane is
    byte-identical to the dense upload."""
    pos = jnp.arange(cap, dtype=jnp.int32)
    idx = jnp.searchsorted(run_ends, pos, side="right")
    return jnp.take(run_values, jnp.clip(idx, 0, rcap - 1))


def _delta_dense(deltas, base, validity, out_dtype):
    """In-kernel delta decode: base + running sum of the narrowed
    per-row deltas.  Delta encoding is only selected for null-free
    columns, so ``validity`` is exactly the rows<n mask — masking with
    it reproduces the dense path's zero padding."""
    # pscan.prefix_sum, not jnp.cumsum: XLA:TPU's scan expansion of a
    # full-capacity cumsum compiles for most of a minute per shape
    # (53 s at 2^20 int64 for v5e).  The deltas are int8/int16, so
    # their f64 partial sums are exact integers at any capacity.
    from spark_rapids_tpu.utils.pscan import prefix_sum
    run = prefix_sum(deltas.astype(jnp.float64)).astype(out_dtype)
    return jnp.where(validity, base[0] + run, 0).astype(out_dtype)


def _packed_dense(packed, cap: int):
    """In-kernel bool unpack: 8 rows/byte, LSB first.  Pad bits are 0,
    matching the dense path's False padding."""
    pos = jnp.arange(cap, dtype=jnp.int32)
    byte = jnp.take(packed, pos // 8, mode="clip")
    return ((byte >> (pos % 8).astype(jnp.uint8)) & 1).astype(jnp.bool_)


class RleColumn(DeviceColumn):
    """An integer column stored as run values + cumulative run ends.
    Looks like a ``DeviceColumn``: ``.data`` decodes lazily through the
    counted ``decode_plane_late``; the fused stage path decodes
    in-kernel instead (``stage_view`` -> ``PlaneDecode``)."""

    __slots__ = ("run_values", "run_ends", "num_runs", "_cap", "_dense")

    def __init__(self, dtype, run_values, run_ends, num_runs: int,
                 validity, num_rows, capacity: int):
        self.dtype = dtype
        self.run_values = run_values    # (rcap,) device, pad 0
        self.run_ends = run_ends        # (rcap,) int32 cumulative, pad cap
        self.num_runs = int(num_runs)
        self.validity = validity
        self._rows = num_rows if isinstance(num_rows, LazyRows) \
            else int(num_rows)
        self._cap = int(capacity)
        self._dense = None

    def decoded(self) -> DeviceColumn:
        if self._dense is None:
            self._dense = decode_plane_late(self)
        return self._dense

    @property
    def data(self):
        return self.decoded().data

    @property
    def chars(self):
        return None

    @property
    def capacity(self) -> int:
        return self._cap

    def size_bytes(self) -> int:
        return int(self.run_values.nbytes + self.run_ends.nbytes +
                   self.validity.nbytes)

    def with_rows(self, num_rows) -> "RleColumn":
        return RleColumn(self.dtype, self.run_values, self.run_ends,
                         self.num_runs, self.validity, num_rows,
                         self._cap)

    def gather(self, indices, num_rows):
        return self.decoded().gather(indices, num_rows)

    def slice_rows(self, start: int, length: int):
        return self.decoded().slice_rows(start, length)

    def _dense_planes(self):
        rcap = int(self.run_values.shape[0])
        cap = self._cap

        def build():
            def run(rv, re_, valid):
                return _rle_dense(rv, re_, valid, cap, rcap)
            return engine_jit(run, family="scan", name="rle_dense")
        fn = _PLANE_DECODE_CACHE.get_or_build(
            ("rle", cap, rcap, self.dtype.name), build)
        return fn(self.run_values, self.run_ends, self.validity)

    def __repr__(self):
        return (f"RleColumn({self.dtype.name}, runs={self.num_runs}, "
                f"rows={self.num_rows}, cap={self._cap})")


class DeltaColumn(DeviceColumn):
    """A null-free integer column stored as a base value plus narrowed
    (int8/int16) consecutive deltas; decode is one in-kernel cumsum."""

    __slots__ = ("deltas", "base", "_cap", "_dense")

    def __init__(self, dtype, deltas, base, validity, num_rows,
                 capacity: int):
        self.dtype = dtype
        self.deltas = deltas        # (cap,) int8/int16, pad 0
        self.base = base            # (1,) device, the first value
        self.validity = validity
        self._rows = num_rows if isinstance(num_rows, LazyRows) \
            else int(num_rows)
        self._cap = int(capacity)
        self._dense = None

    def decoded(self) -> DeviceColumn:
        if self._dense is None:
            self._dense = decode_plane_late(self)
        return self._dense

    @property
    def data(self):
        return self.decoded().data

    @property
    def chars(self):
        return None

    @property
    def capacity(self) -> int:
        return self._cap

    def size_bytes(self) -> int:
        return int(self.deltas.nbytes + self.base.nbytes +
                   self.validity.nbytes)

    def with_rows(self, num_rows) -> "DeltaColumn":
        return DeltaColumn(self.dtype, self.deltas, self.base,
                           self.validity, num_rows, self._cap)

    def gather(self, indices, num_rows):
        return self.decoded().gather(indices, num_rows)

    def slice_rows(self, start: int, length: int):
        return self.decoded().slice_rows(start, length)

    def _dense_planes(self):
        out_dt = device_dtype(self.dtype)
        store = str(self.deltas.dtype)

        def build():
            def run(deltas, base, valid):
                return _delta_dense(deltas, base, valid, out_dt)
            return engine_jit(run, family="scan", name="delta_dense")
        fn = _PLANE_DECODE_CACHE.get_or_build(
            ("delta", self._cap, store, self.dtype.name), build)
        return fn(self.deltas, self.base, self.validity)

    def __repr__(self):
        return (f"DeltaColumn({self.dtype.name}, "
                f"store={self.deltas.dtype}, rows={self.num_rows}, "
                f"cap={self._cap})")


class PackedBoolColumn(DeviceColumn):
    """A boolean column stored bit-packed, 8 rows per byte (LSB
    first) — the compute-plane counterpart of the egress validity
    bitpack."""

    __slots__ = ("packed", "_cap", "_dense")

    def __init__(self, packed, validity, num_rows, capacity: int):
        self.dtype = BOOLEAN
        self.packed = packed        # (cap//8,) uint8
        self.validity = validity
        self._rows = num_rows if isinstance(num_rows, LazyRows) \
            else int(num_rows)
        self._cap = int(capacity)
        self._dense = None

    def decoded(self) -> DeviceColumn:
        if self._dense is None:
            self._dense = decode_plane_late(self)
        return self._dense

    @property
    def data(self):
        return self.decoded().data

    @property
    def chars(self):
        return None

    @property
    def capacity(self) -> int:
        return self._cap

    def size_bytes(self) -> int:
        return int(self.packed.nbytes + self.validity.nbytes)

    def with_rows(self, num_rows) -> "PackedBoolColumn":
        return PackedBoolColumn(self.packed, self.validity, num_rows,
                                self._cap)

    def gather(self, indices, num_rows):
        return self.decoded().gather(indices, num_rows)

    def slice_rows(self, start: int, length: int):
        return self.decoded().slice_rows(start, length)

    def _dense_planes(self):
        cap = self._cap

        def build():
            def run(packed, valid):
                return _packed_dense(packed, cap)
            return engine_jit(run, family="scan", name="packed_bool_dense")
        fn = _PLANE_DECODE_CACHE.get_or_build(("packed", cap), build)
        return fn(self.packed, self.validity)

    def __repr__(self):
        return (f"PackedBoolColumn(rows={self.num_rows}, "
                f"cap={self._cap})")


_PLANE_TYPES = (RleColumn, DeltaColumn, PackedBoolColumn)


def is_plane_compressed(col) -> bool:
    return isinstance(col, _PLANE_TYPES)


def decode_plane_late(col) -> DeviceColumn:
    """The counted materialization primitive for the non-dictionary
    compute planes — the exact ``decode_late`` contract: one jitted
    decode dispatch, dense planes byte-identical to the plain upload,
    ``lateDecodes`` counted.  Encoding-aware stages never come here;
    they fuse the decode via ``PlaneDecode``."""
    data = col._dense_planes()
    _bump("late_decodes")
    return DeviceColumn(col.dtype, data, col.validity, col.rows_raw)


# ---------------------------------------------------------------------------
# ingest: arrow -> EncodedColumn
# ---------------------------------------------------------------------------

# dictionary reuse across batches of one file/scan: keyed by the arrow
# dictionary buffer identity (address, length) — parquet's
# read_dictionary path hands every batch of a row group the same
# buffer, so the device planes upload once
_DICT_MEMO = KernelCache("encoding.dicts", 64)


def _dict_planes_for(values_arr: pa.Array, device
                     ) -> Tuple[DictPlanes, bool]:
    """DictPlanes for an arrow dictionary value array, memoized on the
    arrow buffer identity, values sorted + deduped (codes are ranks).
    Returns ``(planes, uploaded_now)`` — False on a memo hit, so the
    wire accounting charges the dictionary upload ONCE per scan, not
    once per batch sharing it."""
    bufs = values_arr.buffers()
    data_buf = bufs[-1]
    memo_key = None
    if data_buf is not None:
        # (address, size, length) identifies the arrow value buffer; the
        # memo entry keeps the array alive, so the address cannot be
        # reused by a different dictionary while the entry exists
        memo_key = (data_buf.address, data_buf.size, len(values_arr),
                    id(device) if device is not None else 0)
        hit = _DICT_MEMO.get(memo_key)
        if hit is not None:
            return hit[0], False
    vals = np.asarray(values_arr.to_pylist(), dtype=object)
    planes = DictPlanes(np.asarray(sorted(set(vals)), dtype=object),
                        device=device)
    if memo_key is not None:
        # keep the arrow array alive with the planes so the buffer
        # address cannot be reused by a different dictionary
        _DICT_MEMO[memo_key] = (planes, values_arr)
    return planes, True


def _rank_codes(values_arr: pa.Array, indices: np.ndarray,
                planes: DictPlanes) -> np.ndarray:
    """Remap arrow dictionary indices to the sorted-rank code space."""
    vals = np.asarray(values_arr.to_pylist(), dtype=object)
    trans = np.searchsorted(planes.values, vals).astype(np.int32)
    return trans[indices]


class IngestEncoder:
    """Per-scan encoder: decides per column whether the wire carries
    codes or dense planes, builds the EncodedColumn, and keeps the
    raw-vs-wire byte trajectory (docs/compressed.md)."""

    def __init__(self, device=None, metrics=None,
                 max_dict_fraction: Optional[float] = None):
        self.device = device
        self.metrics = metrics
        self.max_dict_fraction = (_MAX_DICT_FRACTION
                                  if max_dict_fraction is None
                                  else max_dict_fraction)

    def upload_column(self, arr, dtype: DataType, cap: int,
                      max_string_width: Optional[int] = None
                      ) -> Optional[DeviceColumn]:
        """EncodedColumn for a string arrow array when encoding wins,
        else None (caller takes the plain plane path).  An injected
        ``io.encode`` fault degrades to None — the column rides plain,
        counted, the query stays correct."""
        # note: gating on the session conf happens at construction
        # (io/hostio.py builds an encoder only when compressed ingest
        # is on); an encoder in hand is the authority — the
        # per-encoding switches (rle/delta/packedBool) refine it
        if dtype != STRING:
            if dtype == BOOLEAN or dtype in (INT32, INT64):
                return self._upload_plane(arr, dtype, cap)
            return None
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        n = len(arr)
        if n == 0:
            return None
        try:
            faults.maybe_fail(FAULT_SITE_ENCODE,
                              "injected ingest-encode failure")
            if pa.types.is_dictionary(arr.type):
                dict_arr = arr
            else:
                # the ONE sanctioned host-side dictionary build
                # (lint_robustness bans dictionary_encode elsewhere)
                dict_arr = arr.dictionary_encode()
            if dict_arr.dictionary.null_count:
                # null dictionary VALUES (vs null indices) would need a
                # second null channel; the plain path handles them
                self._count_plain(arr, cap, n)
                return None
            d = len(dict_arr.dictionary)
            if d > max(1, int(n * self.max_dict_fraction)):
                self._count_plain(arr, cap, n)
                return None
            planes, dict_uploaded = _dict_planes_for(
                dict_arr.dictionary, self.device)
            if max_string_width is not None \
                    and planes.width > max_string_width:
                self._count_plain(arr, cap, n)
                return None
            indices = dict_arr.indices
            valid = np.ones(n, np.bool_) if indices.null_count == 0 \
                else np.asarray(indices.is_valid())
            idx_np = np.asarray(indices.fill_null(0)).astype(np.int64)
            codes_np = _rank_codes(dict_arr.dictionary, idx_np, planes)
            codes_np = np.where(valid, codes_np, 0).astype(np.int32)
        except (IOError, OSError, pa.ArrowInvalid) as e:
            _bump("encode_faults")
            # a fault-degraded column rides dense planes: count them
            # into BOTH raw and wire so the reported ratio stays honest
            # exactly in the degraded case it exists to expose
            self._count_plain(arr, cap, n)
            import logging
            logging.getLogger("spark_rapids_tpu.io").warning(
                "ingest encode degraded to plain planes: %s", e)
            return None
        put = (lambda a: jax.device_put(a, self.device)) \
            if self.device is not None else jax.device_put
        codes_pad = np.zeros(cap, np.int32)
        codes_pad[:n] = codes_np
        valid_pad = np.zeros(cap, np.bool_)
        valid_pad[:n] = valid
        col = EncodedColumn(put(codes_pad), put(valid_pad), n, planes)
        # trajectory accounting: the dense upload would have cost
        # lengths(int32) + validity + a (cap, W) char matrix at the
        # batch's own observed width
        dense_w = self._dense_width(arr, n)
        raw = cap * (4 + 1) + cap * dense_w
        # the dictionary planes upload once per scan (memoized on the
        # arrow buffer): later batches sharing them carry codes only
        wire = cap * (4 + 1) + \
            (planes.wire_bytes() if dict_uploaded else 0)
        _bump("h2d_raw_bytes", raw)
        _bump("h2d_wire_bytes", wire)
        _bump("encoded_columns")
        if self.metrics is not None:
            from spark_rapids_tpu.utils.metrics import (
                METRIC_ENCODED_COLUMNS,
            )
            self.metrics[METRIC_ENCODED_COLUMNS].add(1)
        return col

    @staticmethod
    def _dense_width(arr, n: int) -> int:
        try:
            import pyarrow.compute as pc
            if pa.types.is_dictionary(arr.type):
                lens = pc.binary_length(arr.dictionary)
                codes_ok = arr.indices.fill_null(0)
                lens = lens.take(codes_ok)
            else:
                lens = pc.binary_length(arr)
            mx = pc.max(lens).as_py() or 1
        except (pa.ArrowInvalid, pa.ArrowNotImplementedError):
            mx = 8
        return bucket_capacity(max(1, int(mx)))

    def _count_plain(self, arr, cap: int, n: int) -> None:
        """A declined string column rides the plain planes: its dense
        bytes count EQUALLY into raw and wire, so the reported ratio is
        over ALL string planes the scan uploaded, not just the columns
        the encoder happened to win on."""
        dense = cap * (4 + 1) + cap * self._dense_width(arr, n)
        _bump("h2d_raw_bytes", dense)
        _bump("h2d_wire_bytes", dense)
        _bump("plain_columns")

    def _upload_plane(self, arr, dtype: DataType, cap: int
                      ) -> Optional[DeviceColumn]:
        """Non-dictionary compute planes: a bit-packed plane for
        BOOLEAN, and for integers whichever of RLE / delta-narrow wins
        the most wire bytes (per-column selection, recorded in the
        stats).  Declines — switches off, no byte win, nulls under
        delta — return None and the column rides the plain path,
        byte-identical.  An injected ``io.encode`` fault degrades the
        same way, counted."""
        if dtype == BOOLEAN:
            if not _PACKED:
                return None
        elif not (_RLE or _DELTA):
            return None
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        n = len(arr)
        if n == 0:
            return None
        dev_dt = device_dtype(dtype)
        itemsize = np.dtype(dev_dt).itemsize
        raw = cap * (itemsize + 1)
        try:
            faults.maybe_fail(FAULT_SITE_ENCODE,
                              "injected ingest-encode failure")
            valid = np.ones(n, np.bool_) if arr.null_count == 0 \
                else np.asarray(arr.is_valid())
            import pyarrow.compute as pc
            filled = pc.fill_null(
                arr, False if dtype == BOOLEAN else 0) \
                if arr.null_count else arr
            vals = filled.to_numpy(zero_copy_only=False).astype(dev_dt)
        except (IOError, OSError, pa.ArrowInvalid) as e:
            _bump("encode_faults")
            _bump("h2d_raw_bytes", raw)
            _bump("h2d_wire_bytes", raw)
            _bump("plain_columns")
            import logging
            logging.getLogger("spark_rapids_tpu.io").warning(
                "ingest encode degraded to plain planes: %s", e)
            return None
        valid_pad = np.zeros(cap, np.bool_)
        valid_pad[:n] = valid
        put = (lambda a: jax.device_put(a, self.device)) \
            if self.device is not None else jax.device_put

        if dtype == BOOLEAN:
            bits = np.zeros(cap, np.uint8)
            bits[:n] = vals.astype(np.uint8)
            packed = np.packbits(bits, bitorder="little")
            wire = packed.nbytes + cap
            col = PackedBoolColumn(put(packed), put(valid_pad), n, cap)
            return self._plane_won(col, "packed_bool_columns", raw,
                                   wire)

        # integer: pick the cheaper of the eligible encodings
        best = None  # (wire, kind, payload)
        if _RLE:
            change = np.nonzero(np.diff(vals))[0]
            runs = int(change.shape[0]) + 1
            rcap = bucket_capacity(max(1, runs + 1))
            wire_rle = rcap * (itemsize + 4) + cap
            if wire_rle < raw:
                best = (wire_rle, "rle", (change, runs, rcap))
        if _DELTA and arr.null_count == 0 and n >= 1:
            diffs = np.diff(vals.astype(np.int64))
            store = None
            if diffs.size == 0 or \
                    (diffs.min() >= -128 and diffs.max() <= 127):
                store = np.int8
            elif diffs.min() >= -32768 and diffs.max() <= 32767:
                store = np.int16
            if store is not None \
                    and np.dtype(store).itemsize < itemsize:
                wire_delta = cap * np.dtype(store).itemsize + \
                    itemsize + cap
                if wire_delta < raw and \
                        (best is None or wire_delta < best[0]):
                    best = (wire_delta, "delta", (diffs, store))
        if best is None:
            return None
        wire, kind, payload = best
        if kind == "rle":
            change, runs, rcap = payload
            starts = np.insert(change + 1, 0, 0)
            rv = np.zeros(rcap, dev_dt)
            rv[:runs] = vals[starts]
            re_ = np.full(rcap, cap, np.int32)
            re_[:runs] = np.append(change + 1, n).astype(np.int32)
            col = RleColumn(dtype, put(rv), put(re_), runs,
                            put(valid_pad), n, cap)
            return self._plane_won(col, "rle_columns", raw, wire)
        diffs, store = payload
        deltas = np.zeros(cap, store)
        deltas[1:n] = diffs.astype(store)
        base = np.asarray([vals[0]], dev_dt)
        col = DeltaColumn(dtype, put(deltas), put(base),
                          put(valid_pad), n, cap)
        return self._plane_won(col, "delta_columns", raw, wire)

    def _plane_won(self, col, stat_key: str, raw: int,
                   wire: int) -> DeviceColumn:
        _bump("h2d_raw_bytes", raw)
        _bump("h2d_wire_bytes", wire)
        _bump(stat_key)
        if self.metrics is not None:
            from spark_rapids_tpu.utils.metrics import (
                METRIC_ENCODED_COLUMNS,
            )
            self.metrics[METRIC_ENCODED_COLUMNS].add(1)
        return col


# ---------------------------------------------------------------------------
# dictionary-domain expression evaluation (the aux planes)
# ---------------------------------------------------------------------------

def _eval_over_dict(planes: DictPlanes, subtree, ordinal: int):
    """Evaluate ``subtree`` (which references the encoded column at
    ``ordinal``) over the dictionary's ``size + 1`` rows (null slot
    last) ONCE, memoized per dictionary.  Returns the derived ColVal
    planes ``(data, validity, chars|None)`` — the gather table a
    ``DictGather`` indexes by code."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.exprs.base import evaluate_projection

    key = ("expr", subtree.key(), ordinal)

    def build():
        rebound = _rebind_to(subtree, ordinal, 0)
        dict_batch = ColumnarBatch([planes.dense_column()],
                                   planes.size + 1, None)
        out = evaluate_projection([rebound], dict_batch)[0]
        return (out.data, out.validity, out.chars)

    return planes.aux(key, build)


def hash_planes(planes: DictPlanes):
    """Per-code partition/join hash of the dictionary values, computed
    with the SAME `_hash_colval` the dense path applies — so a
    hash-partition over codes assigns every row the identical partition
    the dense path would (on==off byte-identical exchanges).  The null
    slot carries the hash of a null string row (zeroed planes), exactly
    what the dense kernel computes for null rows; its validity stays
    False so the gathered validity equals the column's own (the dense
    `_hash_keys` valid-mask contract)."""
    key = ("hash",)

    def build():
        from spark_rapids_tpu.exec.joins import _hash_colval
        from spark_rapids_tpu.exprs.base import ColVal

        def run(lens, valid, chars):
            h = _hash_colval(ColVal(lens, valid, chars), STRING)
            return h, valid

        fn = engine_jit(run, family="scan", name="hash_planes")
        h, v = fn(planes.lengths, planes.validity, planes.chars)
        return (h, v, None)

    return planes.aux(key, build)


def _rebind_to(expr, from_ordinal: int, to_ordinal: int):
    """Rewrite BoundReference(from) -> BoundReference(to)."""
    from spark_rapids_tpu.exprs.base import BoundReference
    if isinstance(expr, BoundReference):
        if expr.ordinal == from_ordinal:
            return BoundReference(to_ordinal, expr.dtype, expr.nullable,
                                  expr.col_name)
        return expr
    if not expr.children:
        return expr
    return expr.with_children(
        [_rebind_to(c, from_ordinal, to_ordinal) for c in expr.children])


def _rebind_many(expr, mapping: Dict[int, int]):
    """Simultaneous BoundReference ordinal remap (collision-safe, unlike
    chained ``_rebind_to`` calls)."""
    from spark_rapids_tpu.exprs.base import BoundReference
    if isinstance(expr, BoundReference):
        to = mapping.get(expr.ordinal)
        if to is not None:
            return BoundReference(to, expr.dtype, expr.nullable,
                                  expr.col_name)
        return expr
    if not expr.children:
        return expr
    return expr.with_children(
        [_rebind_many(c, mapping) for c in expr.children])


def _eval_over_dict_pair(d1: DictPlanes, d2: DictPlanes, subtree,
                         ord1: int, ord2: int):
    """The MULTI-column rewrite's table build: evaluate ``subtree``
    (referencing encoded columns at ``ord1``/``ord2``) over the full
    (size1+1) x (size2+1) cross product of the two dictionaries' rows
    (null slots included) ONCE, memoized on the primary dictionary.
    The composed table is indexed by ``code1 * (size2+1) + code2`` —
    the combined code a ``DictGather2`` computes per row."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.exprs.base import evaluate_projection

    rebound = _rebind_many(subtree, {ord1: 0, ord2: 1})
    key = ("expr2", rebound.key(), d2.fingerprint)

    def build():
        n2 = d2.size + 1
        cells = (d1.size + 1) * n2
        cap = bucket_capacity(cells)
        i1 = np.minimum(np.arange(cap) // n2, d1.size)
        i2 = np.minimum(np.arange(cap) % n2, d2.size)

        def col_of(d, idx):
            return DeviceColumn(
                STRING, jnp.take(d.lengths, idx),
                jnp.take(d.validity, idx), cells,
                chars=jnp.take(d.chars, idx, axis=0))

        pair_batch = ColumnarBatch([col_of(d1, i1), col_of(d2, i2)],
                                   cells, None)
        out = evaluate_projection([rebound], pair_batch)[0]
        return (out.data, out.validity, out.chars)

    return d1.aux(key, build)


# ---------------------------------------------------------------------------
# code-domain expressions
# ---------------------------------------------------------------------------

from spark_rapids_tpu.exprs.base import ColVal, Expression  # noqa: E402


class DictGather(Expression):
    """``f(col)`` rewritten as a gather: the aux input column at
    ``aux_ordinal`` holds ``f`` evaluated over the dictionary (null
    slot last); emit maps each row's code — null rows map to the null
    slot — through it.  This IS the fused late decode: when ``f`` is
    the identity, the gather materializes dense planes inside the
    consuming kernel, never as a separate dispatch."""

    def __init__(self, aux_ordinal: int, col_ordinal: int,
                 dict_size: int, dtype: DataType, nullable: bool,
                 subtree_key: str, out_name: str,
                 precomputed_hash: bool = False):
        self.aux_ordinal = int(aux_ordinal)
        self.col_ordinal = int(col_ordinal)
        self.dict_size = int(dict_size)
        self._dtype = dtype
        self._nullable = nullable
        self.subtree_key = subtree_key
        self.out_name = out_name
        self.is_precomputed_hash = precomputed_hash
        self.children = ()

    @property
    def dtype(self) -> DataType:
        return self._dtype

    @property
    def nullable(self) -> bool:
        return self._nullable

    @property
    def name(self) -> str:
        return self.out_name

    def key(self) -> str:
        # deliberately literal-free (the subtree's constants live in
        # the aux TABLE, a runtime kernel argument): two queries
        # differing only in a dictionary-column predicate's literal
        # share one compiled kernel, exactly like hoisted literals —
        # the gather's traced structure depends only on the ordinals,
        # the null-slot index, the output dtype, and the hash-combine
        # mode
        h = ":h" if self.is_precomputed_hash else ""
        return (f"dictgather[{self.aux_ordinal},{self.col_ordinal},"
                f"{self.dict_size}:{self._dtype.name}{h}]")

    def emit(self, ctx) -> ColVal:
        col = ctx.cols[self.col_ordinal]
        aux = ctx.aux[self.aux_ordinal]
        dcap = aux.data.shape[0]
        codes = jnp.where(col.validity, col.data,
                          jnp.int32(self.dict_size))
        idx = jnp.clip(codes, 0, dcap - 1)
        data = jnp.take(aux.data, idx, axis=0)
        valid = jnp.take(aux.validity, idx, axis=0)
        chars = None if aux.chars is None else \
            jnp.take(aux.chars, idx, axis=0)
        return ColVal(data, valid, chars)


class DictGather2(Expression):
    """``f(col1, col2)`` rewritten as ONE gather over a composed table:
    the aux input holds ``f`` evaluated over the (size1+1) x (size2+1)
    dictionary cross product, and emit combines each row's two codes —
    null rows map to the respective null slot — into
    ``code1 * (size2 + 1) + code2`` before the gather.  A two-encoded-
    column predicate or projection therefore stays in the code domain
    end to end (docs/compressed.md, multi-column rewrites)."""

    def __init__(self, aux_ordinal: int, ord1: int, ord2: int,
                 size1: int, size2: int, dtype: DataType,
                 nullable: bool, subtree_key: str, out_name: str):
        self.aux_ordinal = int(aux_ordinal)
        self.ord1 = int(ord1)
        self.ord2 = int(ord2)
        self.size1 = int(size1)
        self.size2 = int(size2)
        self._dtype = dtype
        self._nullable = nullable
        self.subtree_key = subtree_key
        self.out_name = out_name
        self.children = ()

    @property
    def dtype(self) -> DataType:
        return self._dtype

    @property
    def nullable(self) -> bool:
        return self._nullable

    @property
    def name(self) -> str:
        return self.out_name

    def key(self) -> str:
        # literal-free like DictGather: constants live in the aux table
        return (f"dictgather2[{self.aux_ordinal},{self.ord1},"
                f"{self.ord2},{self.size1}x{self.size2}:"
                f"{self._dtype.name}]")

    def emit(self, ctx) -> ColVal:
        c1 = ctx.cols[self.ord1]
        c2 = ctx.cols[self.ord2]
        aux = ctx.aux[self.aux_ordinal]
        dcap = aux.data.shape[0]
        n2 = self.size2 + 1
        code1 = jnp.where(c1.validity, c1.data, jnp.int32(self.size1))
        code2 = jnp.where(c2.validity, c2.data, jnp.int32(self.size2))
        idx = jnp.clip(code1 * n2 + code2, 0, dcap - 1)
        data = jnp.take(aux.data, idx, axis=0)
        valid = jnp.take(aux.validity, idx, axis=0)
        chars = None if aux.chars is None else \
            jnp.take(aux.chars, idx, axis=0)
        return ColVal(data, valid, chars)


class PlaneDecode(Expression):
    """In-kernel decode of an RLE / delta / bit-packed compute plane:
    ``stage_view`` prepends a projection evaluating one of these per
    compressed column, so the decode fuses into the stage's own kernel
    (counted fusedDecodes) instead of dispatching separately.  The
    flattened planes ride the ColVal slots as (see ``col_planes``):
    rle = (run_values, validity, run_ends), delta = (deltas, validity,
    base), packed = (packed_bits, validity, None)."""

    def __init__(self, ordinal: int, mode: str, dtype: DataType,
                 nullable: bool, out_name: str):
        self.ordinal = int(ordinal)
        self.mode = mode
        self._dtype = dtype
        self._nullable = nullable
        self.out_name = out_name
        self.children = ()

    @property
    def dtype(self) -> DataType:
        return self._dtype

    @property
    def nullable(self) -> bool:
        return self._nullable

    @property
    def name(self) -> str:
        return self.out_name

    def key(self) -> str:
        return (f"planedecode[{self.mode},{self.ordinal},"
                f"{self._dtype.name}]")

    def emit(self, ctx) -> ColVal:
        cv = ctx.cols[self.ordinal]
        cap = ctx.capacity
        if self.mode == "rle":
            rcap = int(cv.data.shape[0])
            data = _rle_dense(cv.data, cv.chars, cv.validity, cap, rcap)
        elif self.mode == "delta":
            data = _delta_dense(cv.data, cv.chars, cv.validity,
                                device_dtype(self._dtype))
        else:  # packed
            data = _packed_dense(cv.data, cap)
        return ColVal(data, cv.validity, None)


class CodeRef(Expression):
    """A bare reference to an encoded column inside a code-view kernel:
    passes the codes plane through untouched (dtype reports STRING —
    the logical type — while the planes are int32 codes; the view's
    wrap info re-wraps the output as an EncodedColumn)."""

    def __init__(self, ordinal: int, nullable: bool, out_name: str):
        self.ordinal = int(ordinal)
        self._nullable = nullable
        self.out_name = out_name
        self.children = ()

    @property
    def dtype(self) -> DataType:
        return STRING

    @property
    def nullable(self) -> bool:
        return self._nullable

    @property
    def name(self) -> str:
        return self.out_name

    def key(self) -> str:
        return f"coderef[{self.ordinal}]"

    def emit(self, ctx) -> ColVal:
        return ctx.cols[self.ordinal]


# ---------------------------------------------------------------------------
# the stage code view
# ---------------------------------------------------------------------------

class StageView:
    """The code-domain view of one fused stage dispatch: rewritten
    steps, the per-column flat inputs + signature (codes for encoded
    columns), the aux gather tables riding as a SEPARATE kernel
    argument space (``EvalContext.aux`` — filters compact columns, and
    dictionary-capacity tables must never be swept into that gather),
    and the wrap map re-wrapping code outputs as EncodedColumns."""

    __slots__ = ("steps", "flat", "sig", "aux", "aux_sig", "wrap",
                 "keys", "identity")

    def __init__(self, steps, flat, sig, aux, aux_sig, wrap, keys,
                 identity: bool):
        self.steps = steps
        self.flat = flat
        self.sig = sig
        self.aux = aux            # tuple of (data, validity, chars)
        self.aux_sig = aux_sig
        self.wrap = wrap          # {output ordinal -> DictPlanes}
        self.keys = keys          # rewritten partition keys (or None)
        self.identity = identity

    def wrap_column(self, i: int, data, valid, rows):
        d = self.wrap.get(i)
        if d is not None:
            return EncodedColumn(data, valid, rows, d)
        return None


def _refs(expr) -> set:
    from spark_rapids_tpu.exprs.base import BoundReference
    out = set()

    def walk(e):
        if isinstance(e, BoundReference):
            out.add(e.ordinal)
        for c in e.children:
            walk(c)
    walk(expr)
    return out


def _deterministic(expr) -> bool:
    from spark_rapids_tpu.exprs.nondeterministic import (
        contains_nondeterministic,
    )
    return not contains_nondeterministic(expr)


def stage_planes(batch) -> Tuple[tuple, tuple]:
    """``(flat, sig)`` of ``batch`` as a code-view kernel takes it: an
    encoded column rides as its codes, a plane-compressed one as its
    own planes (``PlaneDecode`` has the layout), anything else dense —
    ``_flatten_batch`` / ``_batch_signature`` when nothing is encoded.
    ``StageView.flat`` is this; a consumer that parks a batch between
    viewing and launching it (the aggregate's group, exec/aggregate.py)
    drops the view's planes and reads them here again."""
    flat: List[tuple] = []
    sig: List[tuple] = []
    for c in batch.columns:
        if isinstance(c, EncodedColumn):
            flat.append((c.codes, c.validity, None))
            sig.append((INT32.name, c.capacity, 0))
        elif isinstance(c, RleColumn):
            flat.append((c.run_values, c.validity, c.run_ends))
            sig.append((f"@rle:{c.dtype.name}",
                        int(c.run_values.shape[0]), c.capacity))
        elif isinstance(c, DeltaColumn):
            flat.append((c.deltas, c.validity, c.base))
            sig.append((f"@delta:{c.dtype.name}:{c.deltas.dtype}",
                        c.capacity, 0))
        elif isinstance(c, PackedBoolColumn):
            flat.append((c.packed, c.validity, None))
            sig.append(("@packed", int(c.packed.shape[0]), c.capacity))
        else:
            flat.append((c.data, c.validity, c.chars))
            width = c.string_width if c.chars is not None else 0
            sig.append((c.dtype.name, c.capacity, width))
    return tuple(flat), tuple(sig)


def stage_view(steps, batch, keys: Sequence[Expression] = (),
               dense_tail: int = 0) -> "StageView":
    """Build the code-domain view of ``steps`` (and optional trailing
    partition-key expressions) over ``batch``.

    Per encoded input column the rewrite walks every expression:

    * a subtree whose references are exactly that column and which is
      deterministic becomes a ``DictGather`` over planes evaluated once
      on the dictionary (+ null slot) — predicates become code-set
      membership, scalar functions become per-code tables, and a bare
      reference used by a value-domain parent becomes a FUSED identity
      decode inside the same kernel;
    * a bare reference that IS a step output stays codes (``CodeRef``)
      and the output re-wraps as an EncodedColumn sharing the
      dictionary;
    * key expressions that are bare references to an encoded column
      hash by per-code gather tables built with the dense path's own
      hash kernel (byte-identical partition assignment).

    ``dense_tail`` marks the last N outputs of the FINAL project step
    as inputs of a value-domain consumer (the folded aggregate's input
    projections, exec/aggregate.py): a bare encoded reference there
    decodes in-kernel instead of leaving as codes.

    With no encoded columns (or compressed off) the view is the
    identity: flatten/signature/steps exactly as the dense engine
    builds them, so kernel cache keys cannot drift."""
    from spark_rapids_tpu.exprs.base import Alias, BoundReference

    enc: Dict[int, EncodedColumn] = {
        i: c for i, c in enumerate(batch.columns)
        if isinstance(c, EncodedColumn)}
    comp: Dict[int, DeviceColumn] = {
        i: c for i, c in enumerate(batch.columns)
        if isinstance(c, _PLANE_TYPES)}
    flat, sig = stage_planes(batch)
    if not enc and not comp:
        return StageView(tuple(steps), flat, sig, (), (), {},
                         tuple(keys) if keys else None, True)

    if comp:
        # fuse every compressed plane's decode into THIS kernel: a
        # prepended projection decodes the RLE/delta/packed columns
        # (PlaneDecode) and passes everything else through untouched —
        # bare encoded refs stay codes via the normal rewrite below
        from spark_rapids_tpu.exprs.base import BoundReference as _BR
        first = []
        for i, c in enumerate(batch.columns):
            if i in comp:
                mode = ("rle" if isinstance(c, RleColumn) else
                        "delta" if isinstance(c, DeltaColumn) else
                        "packed")
                first.append(PlaneDecode(i, mode, c.dtype, True,
                                         f"c{i}"))
                _bump("fused_decodes")
            else:
                first.append(_BR(i, c.dtype, True, f"c{i}"))
        steps = (("project", tuple(first)),) + tuple(steps)

    aux_flat: List[tuple] = []
    aux_sig: List[tuple] = []
    aux_cache: Dict[tuple, int] = {}

    def aux_ordinal(planes_triple, cap: int, dtype_name: str,
                    width: int, memo_key) -> int:
        hit = aux_cache.get(memo_key)
        if hit is not None:
            return hit
        ordn = len(aux_flat)
        aux_flat.append(planes_triple)
        aux_sig.append((dtype_name, cap, width))
        aux_cache[memo_key] = ordn
        return ordn

    # ordinal -> DictPlanes for the CURRENT step's input space
    live_dicts: Dict[int, DictPlanes] = {
        i: c.dict for i, c in enc.items()}

    def rewrite(expr, is_output: bool):
        """Rewrite one expression against live_dicts.  Returns the new
        expression plus (for outputs) the DictPlanes when the output
        stays in the code domain."""
        refs = _refs(expr)
        enc_refs = refs & set(live_dicts)
        if not enc_refs:
            return expr, None
        target = expr.children[0] if isinstance(expr, Alias) else expr
        # bare passthrough output: stay codes
        if is_output and isinstance(target, BoundReference) \
                and target.ordinal in live_dicts:
            d = live_dicts[target.ordinal]
            return (CodeRef(target.ordinal, target.nullable, expr.name),
                    d)
        # maximal single-encoded-column deterministic subtree -> gather
        if len(enc_refs) == 1 and refs == enc_refs \
                and _deterministic(expr) and not isinstance(expr, Alias):
            (ordn,) = enc_refs
            d = live_dicts[ordn]
            planes = _eval_over_dict(d, expr, ordn)
            dtype_name = (STRING.name if planes[2] is not None
                          else _plane_dtype_name(expr.dtype))
            width = int(planes[2].shape[1]) if planes[2] is not None \
                else 0
            a = aux_ordinal(planes, int(planes[0].shape[0]), dtype_name,
                            width, ("expr", expr.key(), ordn))
            _bump("fused_decodes",
                  1 if isinstance(expr, BoundReference) else 0)
            return (DictGather(a, ordn, d.size, expr.dtype,
                               expr.nullable, expr.key(), expr.name),
                    None)
        # multi-column: a deterministic subtree over exactly TWO
        # encoded columns stays in the code domain via a composed
        # (code1, code2) gather table, bounded by maxComposedCells
        if len(enc_refs) == 2 and refs == enc_refs \
                and _deterministic(expr) and not isinstance(expr, Alias):
            o1, o2 = sorted(enc_refs)
            d1, d2 = live_dicts[o1], live_dicts[o2]
            cells = (d1.size + 1) * (d2.size + 1)
            if 0 < cells <= _MAX_COMPOSED_CELLS:
                planes = _eval_over_dict_pair(d1, d2, expr, o1, o2)
                dtype_name = (STRING.name if planes[2] is not None
                              else _plane_dtype_name(expr.dtype))
                width = int(planes[2].shape[1]) \
                    if planes[2] is not None else 0
                a = aux_ordinal(planes, int(planes[0].shape[0]),
                                dtype_name, width,
                                ("expr2", expr.key(), o1, o2))
                _bump("composed_gathers")
                return (DictGather2(a, o1, o2, d1.size, d2.size,
                                    expr.dtype, expr.nullable,
                                    expr.key(), expr.name), None)
        if not expr.children:
            return expr, None
        new_children = []
        for c in expr.children:
            nc, _ = rewrite(c, False)
            new_children.append(nc)
        if all(a is b for a, b in zip(new_children, expr.children)):
            return expr, None
        return expr.with_children(new_children), None

    out_steps: List[tuple] = []
    wrap: Dict[int, DictPlanes] = {}
    for si, (kind, exprs) in enumerate(steps):
        if kind == "project":
            new_exprs = []
            next_dicts: Dict[int, DictPlanes] = {}
            codes_until = len(exprs) - dense_tail \
                if si == len(steps) - 1 else len(exprs)
            for oi, e in enumerate(exprs):
                ne, d = rewrite(e, oi < codes_until)
                new_exprs.append(ne)
                if d is not None:
                    next_dicts[oi] = d
            out_steps.append(("project", tuple(new_exprs)))
            live_dicts = next_dicts
        else:  # filter: columns pass through, ordinals unchanged
            ne, _ = rewrite(exprs[0], False)
            out_steps.append(("filter", (ne,)))
    wrap = dict(live_dicts)

    new_keys: Optional[List[Expression]] = None
    if keys:
        new_keys = []
        for k in keys:
            target = k.children[0] if isinstance(k, Alias) else k
            if isinstance(target, BoundReference) \
                    and target.ordinal in live_dicts:
                d = live_dicts[target.ordinal]
                planes = hash_planes(d)
                a = aux_ordinal(planes, int(planes[0].shape[0]),
                                "long", 0, ("hash", target.ordinal,
                                            d.fingerprint))
                new_keys.append(DictGather(
                    a, target.ordinal, d.size, STRING, target.nullable,
                    f"hash({target.key()})", k.name,
                    precomputed_hash=True))
            else:
                nk, _ = rewrite(k, False)
                new_keys.append(nk)

    _bump("code_stages")
    return StageView(tuple(out_steps), flat, sig,
                     tuple(aux_flat), tuple(aux_sig), wrap,
                     tuple(new_keys) if new_keys is not None else
                     (tuple(keys) if keys else None), False)


def _plane_dtype_name(dt: DataType) -> str:
    # aux plane signature entry: the DEVICE representation's logical
    # name (aval construction in stage.aval_inputs goes through
    # from_name + device_dtype)
    return dt.name


# ---------------------------------------------------------------------------
# unification (merge/concat across dictionaries)
# ---------------------------------------------------------------------------

_TRANS_CACHE = KernelCache("encoding.translate", 128)


def _compile_translate(cap: int, tcap: int):
    key = (cap, tcap)

    def build():
        def run(codes, valid, trans):
            idx = jnp.clip(codes, 0, tcap - 1)
            out = jnp.where(valid, jnp.take(trans, idx), 0)
            return out.astype(jnp.int32)
        return engine_jit(run, family="scan", name="translate")
    return _TRANS_CACHE.get_or_build(key, build)


def _codes_device(col: EncodedColumn):
    """The device the column's codes are committed to — translate
    tables and union planes must land there, not on the default
    device (a remote-attached chip is rarely jax.devices()[0])."""
    try:
        devs = col.codes.devices()
        return next(iter(devs)) if len(devs) == 1 else None
    except (AttributeError, TypeError):
        return None


def unify_columns(cols: Sequence[EncodedColumn]
                  ) -> Tuple[List[EncodedColumn], DictPlanes]:
    """Re-key every column onto one shared dictionary (the sorted union
    of their value sets).  Columns already on the union dict pass
    through; others translate codes with one tiny device gather.  The
    union dictionary is sorted, so the rank invariant holds."""
    first = cols[0].dict
    if all(c.dict.same_values(first) for c in cols):
        return list(cols), first
    union_vals = sorted(set().union(*[set(c.dict.values)
                                      for c in cols]))
    device = _codes_device(cols[0])
    union = DictPlanes(np.asarray(union_vals, dtype=object),
                       device=device)
    out = []
    for c in cols:
        if c.dict.same_values(union):
            out.append(EncodedColumn(c.codes, c.validity, c.rows_raw,
                                     union))
            continue
        trans_np = np.searchsorted(
            union.values, c.dict.values).astype(np.int32)
        tcap = bucket_capacity(max(1, trans_np.shape[0]))
        trans_pad = np.zeros(tcap, np.int32)
        trans_pad[:trans_np.shape[0]] = trans_np
        fn = _compile_translate(c.capacity, tcap)
        codes2 = fn(c.codes, c.validity,
                    jax.device_put(trans_pad, _codes_device(c)))
        out.append(EncodedColumn(codes2, c.validity, c.rows_raw, union))
    return out, union


def unify_ordinals(col_lists: List[list]) -> Dict[int, DictPlanes]:
    """The shared per-ordinal unify sweep (concat + egress pack both
    route here so the convention cannot drift): for every column index
    where EVERY batch's column is encoded, re-key all of them onto one
    union dictionary IN PLACE in ``col_lists`` and record the ordinal's
    dictionary in the returned map."""
    enc_dicts: Dict[int, DictPlanes] = {}
    for ci in range(len(col_lists[0])):
        cl = [cols[ci] for cols in col_lists]
        if all(isinstance(c, EncodedColumn) for c in cl):
            unified, d = unify_columns(cl)
            for bi, u in enumerate(unified):
                col_lists[bi][ci] = u
            enc_dicts[ci] = d
    return enc_dicts


def rekey_for_join(col: EncodedColumn, build_dict: DictPlanes
                   ) -> DeviceColumn:
    """Re-key one side's codes into the OTHER side's code space for a
    code-domain equi-join across disjoint dictionaries: values present
    in ``build_dict`` map to its codes; values absent map to distinct
    codes past its size (they can never equal a build code — a correct
    non-match — while still hashing spread out).  Returns a plain INT32
    key column (comparison view only; the payload column stays
    encoded)."""
    if col.dict.same_values(build_dict):
        return DeviceColumn(INT32, col.codes, col.validity,
                            col.rows_raw)
    pos = np.searchsorted(build_dict.values, col.dict.values)
    pos = np.clip(pos, 0, max(0, build_dict.size - 1))
    present = np.zeros(col.dict.size, np.bool_)
    if build_dict.size:
        present = build_dict.values[pos] == col.dict.values
    trans_np = np.where(
        present, pos,
        build_dict.size + np.arange(col.dict.size)).astype(np.int32)
    tcap = bucket_capacity(max(1, trans_np.shape[0]))
    trans_pad = np.zeros(tcap, np.int32)
    trans_pad[:trans_np.shape[0]] = trans_np
    fn = _compile_translate(col.capacity, tcap)
    codes2 = fn(col.codes, col.validity,
                jax.device_put(trans_pad, _codes_device(col)))
    return DeviceColumn(INT32, codes2, col.validity, col.rows_raw)


# ---------------------------------------------------------------------------
# codes-preserving flatten for plane-gathering kernels
# ---------------------------------------------------------------------------

def col_planes(c, as_codes: bool) -> Tuple[tuple, tuple]:
    """THE per-column flatten convention for plane-gathering kernels:
    ``(flat_triple, sig_entry)``.  ``as_codes=True`` flattens an
    encoded column as ``(codes, validity, None)`` under a ``@codes``
    signature marker; False (a mixed ordinal the caller chose to
    densify) reads ``.data``/``.chars`` — the counted late decode.
    Every codes-aware dispatch site (joins, concat, egress pack, batch
    gather) routes through here so the convention cannot drift."""
    if as_codes and isinstance(c, EncodedColumn):
        return (c.codes, c.validity, None), ("@codes", c.capacity, 0)
    return ((c.data, c.validity, c.chars),
            (c.dtype.name, c.capacity,
             c.string_width if c.chars is not None else 0))


def flat_and_sig(batch) -> Tuple[tuple, tuple]:
    """Codes-preserving flatten + signature for kernels that only
    GATHER column planes (join gathers, side selects): an encoded
    column contributes ``(codes, validity, None)`` with a ``@codes``
    signature marker, so payload columns ride the code domain through
    any row-gather kernel.  Identical to ``_flatten_batch`` /
    ``_batch_signature`` when nothing is encoded."""
    pairs = [col_planes(c, True) for c in batch.columns]
    return (tuple(f for f, _ in pairs), tuple(s for _, s in pairs))


def wrap_gathered(src_columns, outs, rows, schema, extra_wrap=None):
    """Rebuild a batch from gather-kernel outputs, re-wrapping columns
    whose SOURCE was encoded (same dictionary — a row gather never
    changes the code space).  ``extra_wrap`` overrides the dictionary
    per source position (the join's re-keyed stream column decodes
    through the BUILD dictionary)."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    cols = []
    for i, (c, (d, v, ch)) in enumerate(zip(src_columns, outs)):
        override = extra_wrap.get(i) if extra_wrap else None
        if override is not None:
            cols.append(EncodedColumn(d, v, rows, override))
        elif isinstance(c, EncodedColumn):
            cols.append(EncodedColumn(d, v, rows, c.dict))
        else:
            cols.append(DeviceColumn(c.dtype, d, v, rows, chars=ch))
    return ColumnarBatch(cols, rows, schema)


# ---------------------------------------------------------------------------
# the join code view (exec/joins.py)
# ---------------------------------------------------------------------------

def _bare_ref(expr):
    from spark_rapids_tpu.exprs.base import Alias, BoundReference
    t = expr.children[0] if isinstance(expr, Alias) else expr
    return t if isinstance(t, BoundReference) else None


class _StreamJoinView:
    """One stream batch's resolved join view: the (possibly re-keyed)
    batches, key expressions, and output wrap maps."""

    __slots__ = ("s_batch", "b_batch", "lkeys", "rkeys", "keys_tag",
                 "s_wrap", "b_wrap")

    def __init__(self, s_batch, b_batch, lkeys, rkeys, keys_tag,
                 s_wrap, b_wrap):
        self.s_batch = s_batch
        self.b_batch = b_batch
        self.lkeys = lkeys
        self.rkeys = rkeys
        self.keys_tag = keys_tag    # "code" | "dense": keys-key suffix
        self.s_wrap = s_wrap        # {ordinal -> DictPlanes override}
        self.b_wrap = b_wrap


def _substitute(batch, ordinals):
    """Batch with the encoded columns at ``ordinals`` replaced by their
    dense decode (counted late decodes — the join fallback path)."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    if not ordinals:
        return batch
    cols = list(batch.columns)
    changed = False
    for i in ordinals:
        if isinstance(cols[i], EncodedColumn):
            cols[i] = cols[i].decoded()
            changed = True
    if not changed:
        return batch
    return ColumnarBatch(cols, batch.rows_raw, batch.schema)


class JoinCodeView:
    """Equi-join keys compared as CODES (docs/compressed.md): a key
    pair whose two sides are bare references to encoded columns joins
    in the code domain — the build side keeps its rank codes, and each
    stream batch re-keys its codes into the build code space
    (``rekey_for_join``: shared dictionaries translate 1:1, disjoint
    values map past the build dictionary and can never falsely match).
    The rewritten keys are plain INT32 references, so the whole join
    machinery — hash, equality verify, even the dense direct-address
    LUT fast path — runs on small ints.

    Non-pair key references to encoded columns (and columns a join
    condition reads inside the band probe) densify through the counted
    late decode; a stream batch whose pair column arrives dense drops
    that batch to the dense-keys variant against a lazily-built dense
    build view."""

    def __init__(self, b_batch, left_keys, right_keys, n_left_cols: int,
                 condition=None):
        from spark_rapids_tpu.exprs.base import BoundReference
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.pairs: Dict[int, Tuple[int, int, DictPlanes]] = {}
        b_key_refs = set()
        for e in right_keys:
            b_key_refs |= _refs(e)
        s_key_refs = set()
        for e in left_keys:
            s_key_refs |= _refs(e)
        cond_s: set = set()
        cond_b: set = set()
        if condition is not None:
            for r in _refs(condition):
                if r < n_left_cols:
                    cond_s.add(r)
                else:
                    cond_b.add(r - n_left_cols)
        if _ENABLED:
            # a pair may only claim an ordinal NO OTHER key expression
            # references: the claimed column's planes become rekeyed
            # INT32 codes, which a second reference (another pair over
            # the same ordinal, or a value-domain key expr) would read
            # as string planes — so shared-ordinal candidates all drop
            # to the dense path instead
            for ki, (lk, rk) in enumerate(zip(left_keys, right_keys)):
                lt, rt = _bare_ref(lk), _bare_ref(rk)
                if lt is None or rt is None:
                    continue
                other_l = set()
                other_r = set()
                for kj, (lk2, rk2) in enumerate(zip(left_keys,
                                                    right_keys)):
                    if kj != ki:
                        other_l |= _refs(lk2)
                        other_r |= _refs(rk2)
                c = b_batch.columns[rt.ordinal] \
                    if rt.ordinal < len(b_batch.columns) else None
                if isinstance(c, EncodedColumn) \
                        and rt.ordinal not in cond_b \
                        and lt.ordinal not in cond_s \
                        and lt.ordinal not in other_l \
                        and rt.ordinal not in other_r:
                    self.pairs[ki] = (lt.ordinal, rt.ordinal, c.dict)
        pair_b = {b for _, b, _ in self.pairs.values()}
        self.pair_s = {ki: s for ki, (s, _, _) in self.pairs.items()}
        # build variants: code keeps pair codes; dense decodes them too
        decode_b = {i for i, c in enumerate(b_batch.columns)
                    if isinstance(c, EncodedColumn)
                    and (i in b_key_refs or i in cond_b)
                    and i not in pair_b}
        self._b_code = _substitute(b_batch, decode_b)
        self._b_dense = None
        self._b_orig = b_batch
        self._decode_b_all = decode_b | pair_b
        self._s_key_refs = s_key_refs | cond_s
        # code-variant right keys: pair keys become INT32 references
        self.rkeys_code = [
            BoundReference(self.pairs[ki][1], INT32,
                           rk.nullable, rk.name)
            if ki in self.pairs else rk
            for ki, rk in enumerate(right_keys)]
        self.b_wrap = {i: c.dict
                       for i, c in enumerate(self._b_code.columns)
                       if isinstance(c, EncodedColumn)}

    @property
    def build_batch(self):
        """The code-variant build batch (pair columns still encoded)."""
        return self._b_code

    def _dense_build(self):
        if self._b_dense is None:
            self._b_dense = _substitute(self._b_orig,
                                        self._decode_b_all)
        return self._b_dense

    def for_stream(self, sb) -> "_StreamJoinView":
        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        from spark_rapids_tpu.exprs.base import BoundReference
        code_ok = bool(self.pairs) and all(
            isinstance(sb.columns[s_ord], EncodedColumn)
            for ki, (s_ord, _, _) in self.pairs.items())
        if code_ok:
            cols = list(sb.columns)
            s_wrap = {}
            lkeys = list(self.left_keys)
            for ki, (s_ord, _b_ord, bdict) in self.pairs.items():
                col = cols[s_ord]
                cols[s_ord] = rekey_for_join(col, bdict)
                s_wrap[s_ord] = bdict
                lk = self.left_keys[ki]
                lkeys[ki] = BoundReference(s_ord, INT32, lk.nullable,
                                           lk.name)
            sb2 = ColumnarBatch(cols, sb.rows_raw, sb.schema)
            # remaining key/condition-referenced encoded columns densify
            rest = {i for i in self._s_key_refs
                    if i not in self.pair_s.values()
                    and isinstance(sb2.columns[i], EncodedColumn)}
            sb2 = _substitute(sb2, rest)
            for i, c in enumerate(sb2.columns):
                if isinstance(c, EncodedColumn) and i not in s_wrap:
                    s_wrap[i] = c.dict
            return _StreamJoinView(sb2, self._b_code, lkeys,
                                   self.rkeys_code, "code", s_wrap,
                                   self.b_wrap)
        # dense fallback: original keys over densified key columns
        dense_refs = {i for i in (self._s_key_refs |
                                  set(self.pair_s.values()))
                      if i < len(sb.columns)
                      and isinstance(sb.columns[i], EncodedColumn)}
        sb2 = _substitute(sb, dense_refs)
        b2 = self._dense_build() if self.pairs else self._b_code
        s_wrap = {i: c.dict for i, c in enumerate(sb2.columns)
                  if isinstance(c, EncodedColumn)}
        b_wrap = {i: c.dict for i, c in enumerate(b2.columns)
                  if isinstance(c, EncodedColumn)}
        return _StreamJoinView(sb2, b2, self.left_keys,
                               self.right_keys, "dense", s_wrap, b_wrap)


def key_columns_code_view(batch, nk: int):
    """The aggregate MERGE/EVALUATE phases' code view: the first ``nk``
    columns of a partial/merged batch are the group keys — substitute
    codes columns for the encoded ones (dtype INT32 stand-ins for the
    spec), returning ``(batch2, dtype_overrides, wrap)`` or ``None``.
    ``wrap`` maps key position -> DictPlanes for re-wrapping."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch

    if not _ENABLED:
        return None
    wrap = {ki: batch.columns[ki].dict for ki in range(nk)
            if isinstance(batch.columns[ki], EncodedColumn)}
    if not wrap:
        return None
    cols2 = []
    for i, c in enumerate(batch.columns):
        if i in wrap:
            cols2.append(DeviceColumn(INT32, c.codes, c.validity,
                                      c.rows_raw))
        else:
            cols2.append(c)
    batch2 = ColumnarBatch(cols2, batch.rows_raw, batch.schema)
    overrides = {ki: INT32 for ki in wrap}
    return batch2, overrides, wrap
