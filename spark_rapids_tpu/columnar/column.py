"""Device-resident columns.

Reference: GpuColumnVector.java:41 — a Spark ``ColumnVector`` facade over a
cuDF device column; all row accessors throw (GpuColumnVector.java:388
``BAD_ACCESS``) because data must stay columnar on-device.

TPU design: a column is a set of XLA device buffers —
  * fixed-width types: ``data`` (capacity,) + ``validity`` (capacity,) bool
  * strings: ``chars`` (capacity, width) uint8 + ``lengths`` (capacity,)
    int32 + ``validity``
Rows beyond ``num_rows`` are padding: arrays are padded to power-of-two
bucket capacities so every kernel sees a small set of static shapes and XLA
compiles once per bucket (the TPU analog of cuDF's size-classed device
allocations). Logical row count travels host-side; kernels that care receive
it as a traced scalar so the compiled code is shared across row counts.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.dtypes import (
    DataType, STRING, BOOLEAN, device_dtype,
)

from spark_rapids_tpu.compile import buckets as _buckets


class LazyRows:
    """A row count that lives on device until the host truly needs it.

    Every host materialization of a device scalar blocks until the
    device reaches it and pays a D2H round trip, so eagerly calling
    ``int(count)`` after each kernel — the natural cuDF-style pattern
    (the reference reads ``Table.rowCount`` host-side for free over
    PCIe) — serializes the host against the chip.  Instead counts stay as
    0-d device arrays; ``bound`` is a host-known upper bound (typically
    the producing kernel's capacity) that static-shape decisions use, and
    ``get()`` syncs once and caches.
    """

    __slots__ = ("dev", "bound", "_val")

    def __init__(self, dev, bound: int):
        self.dev = dev
        self.bound = int(bound)
        self._val: Optional[int] = None

    @property
    def known(self) -> bool:
        return self._val is not None

    def get(self) -> int:
        if self._val is None:
            from spark_rapids_tpu.columnar.transfer import blocking_read
            self._val = int(blocking_read(self.dev, "rows"))
        return self._val

    def __repr__(self):
        return (f"LazyRows({self._val if self._val is not None else '?'}, "
                f"bound={self.bound})")


def rows_get(n) -> int:
    """Host value of an int-or-LazyRows (syncs if lazy)."""
    return n.get() if isinstance(n, LazyRows) else int(n)


def rows_known(n) -> bool:
    return n.known if isinstance(n, LazyRows) else True


def rows_bound(n) -> int:
    """Host-known upper bound without syncing."""
    return n.bound if isinstance(n, LazyRows) else int(n)


def rows_traced(n):
    """Traceable scalar (device array if lazy, python int otherwise) —
    safe to pass straight into a jitted kernel without a host sync."""
    if isinstance(n, LazyRows):
        return n._val if n._val is not None else n.dev
    return int(n)


def bucket_capacity(n: int) -> int:
    """Next rung of the shared power-of-two capacity ladder >= n
    (default floor 8, the f32 sublane count).  Every capacity in the
    engine routes through the ONE conf-bounded ladder in
    compile/buckets.py so a kernel fingerprint compiles O(log n)
    variants instead of one per observed batch shape
    (docs/compile_cache.md)."""
    return _buckets.bucket_capacity(n)


def _pad_to(arr: np.ndarray, capacity: int, fill=0) -> np.ndarray:
    n = arr.shape[0]
    if n == capacity:
        return arr
    pad_shape = (capacity - n,) + arr.shape[1:]
    return np.concatenate([arr, np.full(pad_shape, fill, dtype=arr.dtype)])


class DeviceColumn:
    """One device column (reference GpuColumnVector.java:41)."""

    __slots__ = ("dtype", "data", "validity", "chars", "_rows")

    def __init__(self, dtype: DataType, data, validity, num_rows,
                 chars=None):
        self.dtype = dtype
        self.data = data            # jnp array (capacity,) — lengths for STRING
        self.validity = validity    # jnp bool (capacity,); False = null/padding
        self.chars = chars          # jnp uint8 (capacity, width) for STRING
        # int or LazyRows; host access via .num_rows syncs lazily
        self._rows = num_rows if isinstance(num_rows, LazyRows) \
            else int(num_rows)

    @property
    def num_rows(self) -> int:
        return rows_get(self._rows)

    @property
    def rows_raw(self):
        return self._rows

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    @property
    def string_width(self) -> int:
        return int(self.chars.shape[1]) if self.chars is not None else 0

    def null_count(self) -> int:
        """Host sync; used by metadata paths only."""
        n = self.num_rows
        return int(n - jnp.sum(self.validity[:n]))

    def size_bytes(self) -> int:
        total = self.data.size * self.data.dtype.itemsize + self.validity.size
        if self.chars is not None:
            total += self.chars.size
        return int(total)

    # -- construction -------------------------------------------------------

    @staticmethod
    def from_numpy(dtype: DataType, values: np.ndarray,
                   validity: Optional[np.ndarray] = None,
                   capacity: Optional[int] = None,
                   string_width: Optional[int] = None,
                   lengths: Optional[np.ndarray] = None,
                   device=None) -> "DeviceColumn":
        n = values.shape[0]
        cap = capacity or bucket_capacity(n)
        if validity is None:
            validity = np.ones(n, dtype=np.bool_)
        valid = _pad_to(validity.astype(np.bool_), cap, False)
        put = (lambda a: jax.device_put(a, device)) if device is not None \
            else jax.device_put
        if dtype == STRING:
            # values is an object/str ndarray OR an (n, W) uint8 matrix with
            # true byte lengths passed via `lengths` (strings may contain NUL
            # bytes, so counting nonzero bytes would be wrong).
            if values.dtype == np.uint8 and values.ndim == 2:
                chars_np = values
                if lengths is None:
                    lengths = np.count_nonzero(chars_np != 0, axis=1) \
                        .astype(np.int32)
                lengths = lengths.astype(np.int32)
            else:
                encoded = [s.encode("utf-8") if isinstance(s, str) else
                           (s if s is not None else b"") for s in values]
                lengths = np.array([len(b) for b in encoded], dtype=np.int32)
                width = string_width or max(1, int(lengths.max()) if n else 1)
                width = bucket_capacity(width)
                chars_np = np.zeros((n, width), dtype=np.uint8)
                for i, b in enumerate(encoded):
                    chars_np[i, :len(b)] = np.frombuffer(b, dtype=np.uint8)
            if string_width and chars_np.shape[1] < string_width:
                chars_np = np.pad(chars_np,
                                  ((0, 0), (0, string_width - chars_np.shape[1])))
            chars_p = _pad_to(chars_np, cap)
            lengths_p = _pad_to(lengths, cap)
            return DeviceColumn(STRING, put(lengths_p.astype(np.int32)),
                                put(valid), n, chars=put(chars_p))
        np_dtype = np.dtype(device_dtype(dtype))
        data = _pad_to(np.ascontiguousarray(values, dtype=np_dtype), cap)
        return DeviceColumn(dtype, put(data), put(valid), n)

    @staticmethod
    def full_null(dtype: DataType, num_rows: int, capacity: Optional[int] = None,
                  string_width: int = 8) -> "DeviceColumn":
        cap = capacity or bucket_capacity(num_rows)
        valid = jnp.zeros(cap, dtype=jnp.bool_)
        if dtype == STRING:
            return DeviceColumn(
                STRING, jnp.zeros(cap, dtype=jnp.int32), valid, num_rows,
                chars=jnp.zeros((cap, string_width), dtype=jnp.uint8))
        data = jnp.zeros(cap, dtype=device_dtype(dtype))
        return DeviceColumn(dtype, data, valid, num_rows)

    @staticmethod
    def from_scalar(dtype: DataType, value, num_rows: int,
                    capacity: Optional[int] = None) -> "DeviceColumn":
        """Broadcast a scalar to a column (reference GpuScalar / GpuLiteral
        literals.scala:33,120)."""
        cap = capacity or bucket_capacity(num_rows)
        if value is None:
            return DeviceColumn.full_null(dtype, num_rows, cap)
        if dtype == STRING:
            return DeviceColumn.from_numpy(
                STRING, np.array([value] * num_rows, dtype=object),
                capacity=cap)
        data = jnp.full(cap, value, dtype=device_dtype(dtype))
        valid = jnp.ones(cap, dtype=jnp.bool_)
        return DeviceColumn(dtype, data, valid, num_rows)

    # -- transforms ---------------------------------------------------------

    def with_rows(self, num_rows: int) -> "DeviceColumn":
        return DeviceColumn(self.dtype, self.data, self.validity, num_rows,
                            chars=self.chars)

    def gather(self, indices, num_rows: int) -> "DeviceColumn":
        """Row gather. Out-of-range indices produce rows with validity=False
        (jnp.take clips the *data* to the last row, but validity is masked
        against the true source row count so clipped rows never read valid —
        even when num_rows == capacity and no padding row exists)."""
        data = jnp.take(self.data, indices, axis=0, mode="clip")
        valid = jnp.take(self.validity, indices, axis=0, mode="clip")
        in_range = (indices >= 0) & (indices < self.num_rows)
        # also mask out rows beyond the logical output count
        pos = jnp.arange(indices.shape[0])
        valid = jnp.where(in_range & (pos < num_rows), valid, False)
        chars = None
        if self.chars is not None:
            chars = jnp.take(self.chars, indices, axis=0, mode="clip")
        return DeviceColumn(self.dtype, data, valid, num_rows, chars=chars)

    def slice_rows(self, start: int, length: int) -> "DeviceColumn":
        """Host-driven contiguous slice (used by limit and partition split)."""
        cap = bucket_capacity(length)
        idx = jnp.arange(cap) + start
        col = self.gather(idx, length)
        return col

    # -- host conversion ----------------------------------------------------

    def to_numpy(self):
        """Returns (values, validity) trimmed to num_rows. STRING returns an
        object ndarray of python strings."""
        from spark_rapids_tpu.columnar.transfer import blocking_read
        n = self.num_rows
        planes = blocking_read((self.validity, self.data, self.chars),
                               "column")
        valid = np.asarray(planes[0])[:n]
        if self.dtype == STRING:
            chars = np.asarray(planes[2])[:n]
            lengths = np.asarray(planes[1])[:n]
            out = np.empty(n, dtype=object)
            for i in range(n):
                out[i] = bytes(chars[i, :lengths[i]]).decode("utf-8",
                                                             errors="replace")
            return out, valid
        return np.asarray(planes[1])[:n], valid

    def __repr__(self):
        return (f"DeviceColumn({self.dtype}, rows={self.num_rows}, "
                f"cap={self.capacity})")
