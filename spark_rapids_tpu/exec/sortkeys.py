"""Sortable-integer key construction for lexicographic ``lax.sort``.

The TPU sort/groupby strategy: every column maps to one or more int64/int32
arrays whose ascending order equals the column's SQL order, then one
variadic ``jax.lax.sort`` call (num_keys=K) sorts rows by all keys with an
iota payload carrying the permutation.  This replaces cuDF's
``Table.orderBy`` / ``Table.groupBy`` (reference GpuSortExec.scala:52-101,
aggregate.scala:731).

Transforms:
  * floats -> order-preserving int bitcast (sign-magnitude to two's
    complement), with NaN canonicalized so all NaNs compare equal and
    greatest (Spark ordering), and -0.0 == 0.0 (NormalizeFloatingNumbers
    analog for grouping);
  * strings -> big-endian 4-byte packs of the padded char matrix plus the
    length as tiebreak (correct byte order even with embedded NULs);
  * descending -> bitwise NOT of the key; null ordering -> a leading 0/1
    validity key.
"""

from __future__ import annotations

from typing import List

import jax.numpy as jnp

from spark_rapids_tpu.columnar.dtypes import (
    DataType, BOOLEAN, STRING, FLOAT32, FLOAT64,
)
from spark_rapids_tpu.exprs.base import ColVal


def float_order_keys(x: jnp.ndarray):
    """IEEE float column -> (nan_rank int32, canonical float) key pair
    whose lexicographic ascending order is the Spark order: NaN greatest
    (and all NaNs equal, so grouping boundaries see one NaN group) and
    -0.0 == +0.0.

    The float itself is the second sort key — XLA compares floats natively
    and the NaN rank removes the only non-total-order case.  This
    deliberately avoids the classic bitcast-to-int trick: the TPU x64
    rewriter cannot lower 64-bit ``bitcast_convert``, so float64 keys must
    never round-trip through int64 bit patterns."""
    isnan = jnp.isnan(x)
    canon = jnp.where(isnan, jnp.zeros_like(x), x)   # NaNs group equal
    canon = jnp.where(canon == 0, jnp.zeros_like(canon), canon)  # -0 -> +0
    return isnan.astype(jnp.int32), canon


import jax  # noqa: E402  (lax used above)


def colval_sort_keys(cv: ColVal, dtype: DataType, ascending: bool = True,
                     nulls_first: bool = True) -> List[jnp.ndarray]:
    """ColVal -> list of int arrays, most-significant first."""
    keys: List[jnp.ndarray] = []
    if nulls_first:
        nk = jnp.where(cv.validity, 1, 0).astype(jnp.int32)
    else:
        nk = jnp.where(cv.validity, 0, 1).astype(jnp.int32)
    keys.append(nk)
    if dtype == STRING:
        chars = cv.chars
        w = chars.shape[1]
        pad = (-w) % 4
        if pad:
            chars = jnp.pad(chars, ((0, 0), (0, pad)))
            w += pad
        blocks = chars.reshape(chars.shape[0], w // 4, 4).astype(jnp.int64)
        packed = (blocks[:, :, 0] * (1 << 24) + blocks[:, :, 1] * (1 << 16)
                  + blocks[:, :, 2] * (1 << 8) + blocks[:, :, 3])
        data_keys = [packed[:, i] for i in range(w // 4)]
        data_keys.append(cv.data.astype(jnp.int64))  # length tiebreak
    elif dtype == BOOLEAN:
        data_keys = [cv.data.astype(jnp.int32)]
    elif dtype in (FLOAT32, FLOAT64):
        data_keys = list(float_order_keys(cv.data))
    else:
        data_keys = [cv.data]
    if not ascending:
        data_keys = [~k if jnp.issubdtype(k.dtype, jnp.integer) else -k
                     for k in data_keys]
    # null rows carry arbitrary data; zero them so equal-null groups dedupe
    data_keys = [jnp.where(cv.validity, k, jnp.zeros_like(k))
                 for k in data_keys]
    keys.extend(data_keys)
    return keys


def _bitonic_passes(n: int):
    """Static (k, j) schedule of the bitonic network for n (power of 2)."""
    import numpy as np
    ks, js = [], []
    k = 2
    while k <= n:
        j = k >> 1
        while j >= 1:
            ks.append(k)
            js.append(j)
            j >>= 1
        k <<= 1
    return np.asarray(ks, np.int64), np.asarray(js, np.int64)


def bitonic_lex_sort(keys: List[jnp.ndarray],
                     payloads: List[jnp.ndarray] = ()):
    """Stable variadic lexicographic sort as a bitonic network inside ONE
    ``lax.fori_loop`` — the TPU-shaped replacement for ``jax.lax.sort``.

    Why not ``lax.sort``: XLA's sort expander compiles its variadic
    comparator catastrophically slowly on TPU at these operand counts
    (measured 47s at 2^16 and 72-700s at 2^20 per shape, vs ~5s here),
    and every (capacity, dtypes) bucket pays it again.  The bitonic
    network needs no comparator codegen: each of the log^2(n) passes is
    a pair of ``jnp.roll``s (partner i^j is i-j or i+j by the j-bit, so
    no gather) plus elementwise selects, and the ``fori_loop`` compiles
    the body once.  Runtime is ~log^2(n) HBM sweeps (~40ms for 1M rows
    x 3 operands) — bandwidth-bound, which is what the TPU is built for.

    Stability: bitonic networks are unstable, so an int32 iota is always
    appended as the final key; equal-key rows therefore keep input order
    (matching ``lax.sort(is_stable=True)``).

    Returns the list of sorted key arrays + payload arrays + the iota
    (the permutation) as the last element.
    """
    n = int(keys[0].shape[0])
    assert n & (n - 1) == 0, f"bitonic sort needs power-of-2 size, got {n}"
    ksched, jsched = _bitonic_passes(n)
    ksd, jsd = jnp.asarray(ksched), jnp.asarray(jsched)
    i = jnp.arange(n, dtype=jnp.int64)
    iota = jnp.arange(n, dtype=jnp.int32)
    # strip weak types: the fori carry requires exact aval equality and
    # jnp.where() inside the body produces strongly-typed outputs
    canon = [jnp.asarray(a).astype(jnp.asarray(a).dtype)
             for a in tuple(keys) + (iota,) + tuple(payloads)]
    # under shard_map the operands may carry varying manual axes (vma)
    # while the fresh iota is replicated; pvary everything to the union
    # so the fori carry avals match
    vma = set()
    for a in canon:
        vma |= set(jax.typeof(a).vma)
    if vma:
        canon = [a if set(jax.typeof(a).vma) == vma
                 else jax.lax.pcast(a, tuple(vma), to="varying")
                 for a in canon]
    arrs = tuple(canon)
    nk = len(keys) + 1  # iota is the stability tiebreak key

    def body(p, arrs):
        k = ksd[p]
        j = jsd[p]
        upper = (i & j) != 0            # partner is i-j for these lanes
        take_min = ((i & k) == 0) == (~upper)
        b = tuple(jnp.where(upper, jnp.roll(a, j), jnp.roll(a, -j))
                  for a in arrs)
        b_lt = jnp.zeros(n, bool)
        b_eq = jnp.ones(n, bool)
        for t in range(nk):
            b_lt = b_lt | (b_eq & (b[t] < arrs[t]))
            b_eq = b_eq & (b[t] == arrs[t])
        use_b = jnp.where(take_min, b_lt, ~(b_lt | b_eq))
        return tuple(jnp.where(use_b, bb, aa) for aa, bb in zip(arrs, b))

    out = jax.lax.fori_loop(0, len(ksched), body, arrs)
    # reorder: keys..., payloads..., iota last
    keys_out = list(out[:len(keys)])
    iota_out = out[len(keys)]
    pay_out = list(out[len(keys) + 1:])
    return keys_out + pay_out + [iota_out]


def sort_permutation(all_keys: List[jnp.ndarray], capacity: int,
                     live_first: jnp.ndarray = None) -> jnp.ndarray:
    """Variadic stable sort -> permutation.  ``live_first`` (bool,
    True = live row) forces padding rows to the end."""
    operands = []
    if live_first is not None:
        operands.append(jnp.where(live_first, 0, 1).astype(jnp.int32))
    operands.extend(all_keys)
    return bitonic_lex_sort(operands)[-1]
